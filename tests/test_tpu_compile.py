"""Compile the main-path Pallas kernels for a described TPU v5e (no chip).

Interpret mode accepts layouts the TPU compiler refuses (blocks whose last
two dimensions are not ``(8, 128)``-aligned, in-kernel reshapes Mosaic
cannot lower, 3-D gathers), so these tests lower the kernels with
``interpret=False`` for one chip of a ``v5e:2x2`` topology at porcine1's
grid (paper Table 2, tile 5) and let the real compiler judge.  The topology
is described inside a module fixture only: describing it loads the TPU
library, which one process at a time may hold.
"""
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
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Dispatchers resolve ``interpret`` from the (CPU) backend; steer them
    to the compiled kernels the TPU would run."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _kernel_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


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
    compiles at porcine1 with both kernels inside."""

    def step(p, f, m):
        loss = ffd_level_loss(f, m, tile=TILE, bending_weight=5e-3,
                              mode=mode, impl="pallas", grad_impl=grad_impl)
        return jax.value_and_grad(loss)(p)

    txt = _kernel_text(step, _spec(GRID, one_chip),
                       _spec(PORCINE1, one_chip), _spec(PORCINE1, one_chip))
    assert txt.count("tpu_custom_call") >= 2


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
