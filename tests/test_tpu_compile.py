"""Compile the main-path Pallas kernels for a described TPU v5e (no chip).

Interpret mode accepts layouts the TPU compiler refuses (blocks whose last
two dimensions are not ``(8, 128)``-aligned, in-kernel reshapes Mosaic
cannot lower, 3-D gathers), so these tests lower the kernels with
``interpret=False`` for one chip of a ``v5e:2x2`` topology at porcine1's
grid (paper Table 2, tile 5) and let the real compiler judge.  The topology
is described inside a module fixture only: describing it loads the TPU
library, which one process at a time may hold.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import RegistrationOptions, ffd
from repro.core.similarity import fused_spec
from repro.engine.autotune import resolve_options
from repro.engine.batch import ffd_level_loss
from repro.kernels import ops

PORCINE1 = (303, 167, 212)
TILE = (5, 5, 5)
GRID = ffd.grid_shape_for_volume(PORCINE1, TILE) + (3,)
DENSE = tuple((n - 3) * d for n, d in zip(GRID, TILE)) + (3,)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Dispatchers resolve ``interpret`` from the (CPU) backend; steer them
    to the compiled kernels the TPU would run."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _kernel_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_names(txt):
    """Names of the Pallas kernels (``tpu_custom_call`` instructions)."""
    return set(re.findall(r"%(\w+?)(?:\.\d+)? = .*custom-call\(.*"
                          r"tpu_custom_call", txt))


@pytest.mark.parametrize("mode", ops.PALLAS_MODES)
def test_forward_kernel_compiles_at_porcine1(one_chip, compiled_kernels,
                                             mode):
    txt = _kernel_text(lambda p: ops.bsi_pallas(p, TILE, mode=mode),
                       _spec(GRID, one_chip))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("form", ["separable", "matmul"])
def test_adjoint_kernel_compiles_at_porcine1(one_chip, compiled_kernels,
                                             form):
    txt = _kernel_text(lambda g: ops.bsi_adjoint_pallas(g, TILE, form=form),
                       _spec(DENSE, one_chip))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("mode,grad_impl", [("separable", "pallas"),
                                            ("matmul", "matmul")])
def test_level_step_compiles_with_kernels(one_chip, compiled_kernels, mode,
                                          grad_impl):
    """The whole level-step gradient (BSI -> warp -> SSD, analytic adjoint)
    compiles at porcine1 with its three kernels inside: the BSI forward, its
    adjoint and the warp."""

    def step(p, f, m):
        loss = ffd_level_loss(f, m, tile=TILE, bending_weight=5e-3,
                              mode=mode, impl="pallas", grad_impl=grad_impl)
        return jax.value_and_grad(loss)(p)

    txt = _kernel_text(step, _spec(GRID, one_chip),
                       _spec(PORCINE1, one_chip), _spec(PORCINE1, one_chip))
    form = {"pallas": "separable", "matmul": "matmul"}[grad_impl]
    assert _kernel_names(txt) == {f"bsi_{mode}", f"bsi_adjoint_{form}",
                                  "warp_trilinear"}


@pytest.mark.parametrize("partials", [False, True])
@pytest.mark.parametrize("vol", [PORCINE1, (294, 130, 208)],
                         ids=["porcine1", "phantom2"])
def test_warp_kernel_compiles_at_the_cells(one_chip, compiled_kernels, vol,
                                           partials):
    """The warp kernel, its moving volume resident in VMEM, compiles at both
    cells' volumes, values alone and with the partials, and leaves no
    gather behind."""
    assert ops.warp_supported(vol, jnp.float32)
    txt = _kernel_text(
        lambda m, d: ops.warp_trilinear(m, d, partials=partials),
        _spec(vol, one_chip), _spec(vol + (3,), one_chip))
    assert re.search(r"%warp_trilinear(?:\.\d+)? = .*custom-call", txt)
    assert " gather(" not in txt


@pytest.mark.parametrize("sim", ["ssd", "ncc", "lncc", "nmi"])
def test_fused_supported_is_truthful_on_tpu(one_chip, compiled_kernels,
                                            sim):
    """Where kernels compile, ``fused_supported`` refuses the fused kernel,
    ``fused="on"`` raises that reason, and the compiler indeed refuses the
    kernel at a size the interpreter's VMEM budget admits."""
    vol = (40, 40, 40)
    spec = tuple(fused_spec(sim))
    assert ops.fused_supported(vol, spec) == (False, ops.FUSED_NO_TPU)
    with pytest.raises(ValueError, match="3-D gather"):
        resolve_options(RegistrationOptions(
            mode="separable", impl="pallas", grad_impl="pallas",
            similarity=sim, fused="on"), vol)
    grid = ffd.grid_shape_for_volume(vol, TILE) + (3,)
    with pytest.raises(Exception, match="gather"):
        _kernel_text(
            lambda p, m, f: ops.fused_similarity_loss(p, m, f, TILE,
                                                      sim_spec=spec),
            _spec(grid, one_chip), _spec(vol, one_chip), _spec(vol, one_chip))


# --- stage scopes: what the benchmark's trace reader buckets by --------------

SMALL = (40, 36, 32)
SMALL_GRID = ffd.grid_shape_for_volume(SMALL, TILE) + (3,)
_SCOPE = re.compile(r"(?:[\w-]+\()*([\w-]+)\)*")


def _scopes(op_name):
    """Scope names in an ``op_name``'s components, with the
    transformations around them (``transpose(jvp(bsi))``) taken off."""
    return {m.group(1) for part in op_name.split("/")
            for m in [_SCOPE.fullmatch(part)] if m}


def _ops(txt):
    """``(instruction, op_name, body of the computation it calls)`` for each
    instruction of the compiled text outside fused computations."""
    bodies, current = {}, None
    for line in txt.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.-]+) \(.*\{$", line)
        if head:
            current = head.group(1)
            bodies[current] = []
        elif current is not None:
            bodies[current].append(line)
    out = []
    for comp, lines in bodies.items():
        if comp.startswith("fused_computation"):
            continue
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.-]+) = ", line)
            if not m:
                continue
            called = re.search(r"calls=%([\w.-]+)", line)
            op = re.search(r'op_name="([^"]*)"', line)
            body = "\n".join(bodies.get(called.group(1), [])) if called else ""
            out.append((line, op.group(1) if op else "", body))
    return out


def _assert_stages(txt, nmi_exp):
    """Every ``bsi_*`` kernel lies in ``bsi`` and the ``warp_trilinear``
    kernel in ``warp``; no gather fusion is left in ``warp`` (the kernel
    took the warp); NMI's exponentials lie in ``similarity``."""
    ops = _ops(txt)
    kernels = [(re.match(r"\s*(?:ROOT )?%(\w+)", l).group(1), op)
               for l, op, _ in ops if "tpu_custom_call" in l]
    names = {name for name, _ in kernels}
    assert "warp_trilinear" in names, names
    assert any(name.startswith("bsi_") for name in names), names
    for name, op in kernels:
        stage = "warp" if name == "warp_trilinear" else "bsi"
        assert name.startswith("bsi_") or stage == "warp", name
        assert stage in _scopes(op), (name, op)
    warp_gathers = [(l, op) for l, op, body in ops
                    if " fusion(" in l and " gather(" in body
                    and "warp" in _scopes(op)]
    assert not warp_gathers, warp_gathers
    if nmi_exp:
        exps = [(l, op) for l, op, body in ops
                if " fusion(" in l and " exponential(" in body]
        assert exps
        assert all("similarity" in _scopes(op) for _, op in exps), exps


def _cell_options(sim, grad_impl):
    from repro.core.similarity import nmi

    return resolve_options(RegistrationOptions(
        tile=TILE, levels=1, iters=2, mode="separable", impl="pallas",
        grad_impl=grad_impl, similarity=nmi(64) if sim == "nmi" else sim,
        fused="off"), SMALL)


@pytest.mark.parametrize("grad_impl", ["pallas", "jnp"])
@pytest.mark.parametrize("sim", ["ssd", "nmi"])
def test_level_step_ops_carry_their_stage(one_chip, compiled_kernels, sim,
                                          grad_impl):
    """The level step of the benchmark cells' autotuned forms
    (``separable/pallas/pallas`` and ``separable/pallas/jnp``), compiled
    for the chip: the BSI kernels lie in the ``bsi`` scope, the warp kernel
    in ``warp`` with no gather left there, and NMI's exponentials in
    ``similarity``."""
    from repro.core.registration import _ffd_level_runner

    opts = _cell_options(sim, grad_impl)
    txt = (_ffd_level_runner(SMALL, opts)
           .lower(_spec(SMALL_GRID, one_chip), _spec(SMALL, one_chip),
                  _spec(SMALL, one_chip)).compile().as_text())
    _assert_stages(txt, nmi_exp=sim == "nmi")


@pytest.mark.parametrize("sim", ["ssd", "nmi"])
def test_final_warp_is_one_program_with_stages(one_chip, compiled_kernels,
                                               sim):
    """``ffd_register``'s final displacement and warp compile as one
    program whose warp is the ``warp_trilinear`` kernel (no gathers) and
    whose BSI kernel lies in ``bsi``."""
    from repro.engine.batch import compile_finish

    opts = _cell_options(sim, "pallas")
    txt = (compile_finish(SMALL, opts)
           .lower(_spec(SMALL_GRID, one_chip), _spec(SMALL, one_chip))
           .compile().as_text())
    _assert_stages(txt, nmi_exp=False)


def test_kernel_names_differ_by_form(one_chip, compiled_kernels):
    """Each Pallas kernel compiles to a custom call named for its form; only
    the adjoints' names start with ``bsi_adjoint``."""
    dense = tuple((n - 3) * d for n, d in zip(SMALL_GRID, TILE)) + (3,)
    names = {}
    for mode in ops.PALLAS_MODES:
        txt = _kernel_text(lambda p: ops.bsi_pallas(p, TILE, mode=mode),
                           _spec(SMALL_GRID, one_chip))
        names[mode] = re.findall(r"%(\w+)\.\d+ = \S+ custom-call\(.*"
                                 r"tpu_custom_call", txt)
    for form in ("separable", "matmul"):
        txt = _kernel_text(
            lambda g: ops.bsi_adjoint_pallas(g, TILE, form=form),
            _spec(dense, one_chip))
        names["adjoint_" + form] = re.findall(
            r"%(\w+)\.\d+ = \S+ custom-call\(.*tpu_custom_call", txt)
    assert names == {"separable": ["bsi_separable"], "matmul": ["bsi_matmul"],
                     "adjoint_separable": ["bsi_adjoint_separable"],
                     "adjoint_matmul": ["bsi_adjoint_matmul"]}


# --- the scheduler's lane programs over a four-chip mesh ----------------------


@pytest.mark.parametrize("program", ["chunk", "splice"])
def test_scheduler_lane_programs_compile_over_a_mesh(topo, compiled_kernels,
                                                     program):
    """``RegistrationScheduler(mesh=)``'s chunk and splice programs, with the
    lane arrays sharded over the four chips of a ``v5e:2x2``, compile with
    their Pallas kernels inside (each chip makes its own calls: a
    GSPMD-partitioned program cannot hold one)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.engine.batch import compile_level_chunk, compile_level_splice
    from repro.engine.serve import lane_arrays
    from repro.engine.shard import lane_sharding, make_registration_mesh

    mesh = make_registration_mesh(devices=topo.devices)
    lanes, rep = lane_sharding(mesh), NamedSharding(mesh, PartitionSpec())
    opts = _cell_options("ssd", "pallas")
    stacked = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=lanes),
        jax.eval_shape(lambda: lane_arrays(8, SMALL, opts)))
    if program == "chunk":
        fn, args = compile_level_chunk(SMALL, opts, 2, mesh), stacked
    else:
        fn = compile_level_splice(SMALL, opts, mesh)
        args = stacked + (jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
                          _spec(SMALL_GRID, rep), _spec(SMALL, rep),
                          _spec(SMALL, rep))
    txt = fn.lower(*args).compile().as_text()
    names = _kernel_names(txt)
    assert "warp_trilinear" in names, names
    assert any(n.startswith("bsi_adjoint_") for n in names), names
