"""The gather-based BSI adjoint: custom VJP vs autodiff, kernels, engine.

The contract (ISSUE 4): every ``grad_impl`` computes the gradient of the
same linear map, so the analytic adjoint must match ``jax.grad`` of the
``bsi_gather`` reference to 1e-5 across modes/tiles/channels, the Pallas
adjoint must match the jnp separable-transpose, and registration driven
through any ``grad_impl`` must land on the same result to 1e-4.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.interpolate import (GRAD_IMPLS, bsi_adjoint,
                                    bsi_adjoint_separable, bsi_gather,
                                    interpolate)
from repro.data.volumes import make_pair
from repro.kernels import ops

SHAPE_SWEEP = [
    # (grid points per axis, tile, channels)
    ((7, 6, 5), (5, 4, 3), 3),
    ((9, 9, 9), (5, 5, 5), 3),     # paper's default tile
    ((4, 4, 4), (3, 3, 3), 1),     # single tile per axis, smallest tile
    ((11, 4, 6), (7, 7, 7), 2),    # paper's largest tile, non-cubic grid
    ((5, 13, 9), (4, 6, 5), 3),    # mixed tile
]


def _cotangent(grid, tile, c, seed=0):
    rng = np.random.default_rng(seed)
    dense = tuple((g - 3) * t for g, t in zip(grid, tile))
    return jnp.asarray(rng.standard_normal(dense + (c,)), jnp.float32)


def _grad_of_gather_ref(phi, tile, g):
    return jax.grad(lambda p: jnp.vdot(bsi_gather(p, tile), g))(phi)


@pytest.mark.parametrize("grid,tile,c", SHAPE_SWEEP)
def test_adjoint_matches_grad_of_gather_reference(grid, tile, c):
    rng = np.random.default_rng(hash((grid, tile)) % 2**31)
    phi = jnp.asarray(rng.standard_normal(grid + (c,)), jnp.float32)
    g = _cotangent(grid, tile, c)
    ref = _grad_of_gather_ref(phi, tile, g)
    for impl in ("jnp", "pallas"):
        out = bsi_adjoint(g, tile, impl=impl)
        assert out.shape == ref.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


@pytest.mark.parametrize("mode", ["gather", "tt", "ttli", "separable"])
@pytest.mark.parametrize("grad_impl", ["jnp", "pallas"])
def test_custom_vjp_matches_autodiff_across_modes(mode, grad_impl):
    grid, tile, c = (8, 7, 6), (4, 3, 5), 3
    rng = np.random.default_rng(5)
    phi = jnp.asarray(rng.standard_normal(grid + (c,)), jnp.float32)
    g = _cotangent(grid, tile, c, seed=5)
    ref = _grad_of_gather_ref(phi, tile, g)
    got = jax.grad(
        lambda p: jnp.vdot(interpolate(p, tile, mode=mode,
                                       grad_impl=grad_impl), g))(phi)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_pallas_forward_differentiable_with_custom_adjoint():
    """The Pallas forward kernels have no VJP under plain autodiff; the
    custom adjoint is what makes them usable inside the optimisation loop."""
    grid, tile = (7, 7, 7), (4, 4, 4)
    rng = np.random.default_rng(2)
    phi = jnp.asarray(rng.standard_normal(grid + (3,)), jnp.float32)
    g = _cotangent(grid, tile, 3, seed=2)
    ref = _grad_of_gather_ref(phi, tile, g)
    got = jax.grad(
        lambda p: jnp.vdot(interpolate(p, tile, mode="separable",
                                       impl="pallas", grad_impl="jnp"), g))(phi)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    with pytest.raises(Exception):
        jax.grad(lambda p: interpolate(p, tile, mode="separable",
                                       impl="pallas", grad_impl="xla").sum())(phi)


def test_adjoint_pallas_block_shapes_and_chunking(monkeypatch):
    """Every x block size (the grid's chunking of the cotangent: cells
    share three control planes, accumulated in the resident output block)
    gives the same answer, in both kernel forms — including a VMEM budget
    so small that the picker falls back to one tile per cell."""
    g = _cotangent((9, 9, 15), (4, 4, 3), 3, seed=7)
    ref = bsi_adjoint_separable(g, (4, 4, 3))
    for form in ("separable", "matmul"):
        for bt in (1, 2, 4):
            out = ops.bsi_adjoint_pallas(g, (4, 4, 3), block_tiles=bt,
                                         form=form)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5)
    assert ops.pick_block_ctrl((6, 6, 12), (4, 4, 3)) == 6
    one = ops._block_bytes(1, (6, 6, 12), (4, 4, 3), 4, adjoint=True)
    assert ops.pick_block_ctrl((6, 6, 12), (4, 4, 3), budget=one) == 1


def test_adjoint_accumulates_fp32_for_bf16_cotangents():
    g = _cotangent((8, 8, 8), (4, 4, 4), 3)
    for impl in ("jnp", "pallas"):
        out = bsi_adjoint(g.astype(jnp.bfloat16), (4, 4, 4), impl=impl)
        assert out.dtype == jnp.float32
        ref = bsi_adjoint(g, (4, 4, 4), impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-2)


def test_interpolate_rejects_unknown_grad_impl():
    phi = jnp.zeros((5, 5, 5, 3), jnp.float32)
    with pytest.raises(ValueError):
        interpolate(phi, (3, 3, 3), grad_impl="nosuch")
    assert set(GRAD_IMPLS) == {"xla", "jnp", "pallas", "matmul"}


def test_custom_vjp_linear_no_residuals():
    """BSI is linear: the VJP is independent of the primal point (the fwd
    rule saves no residuals), so pulling back the same cotangent at two
    different grids gives bit-identical gradients."""
    from repro.core.interpolate import _custom_vjp_interp

    f = _custom_vjp_interp((4, 4, 4), "separable", "jnp", "jnp", None,
                           "float32")
    rng = np.random.default_rng(0)
    p1 = jnp.asarray(rng.standard_normal((7, 7, 7, 3)), jnp.float32)
    p2 = jnp.asarray(rng.standard_normal((7, 7, 7, 3)), jnp.float32)
    g = _cotangent((7, 7, 7), (4, 4, 4), 3)
    _, vjp1 = jax.vjp(f, p1)
    _, vjp2 = jax.vjp(f, p2)
    np.testing.assert_array_equal(np.asarray(vjp1(g)[0]),
                                  np.asarray(vjp2(g)[0]))


def test_bf16_warp_coordinates_stay_fp32_beyond_256_voxels():
    """bf16 cannot represent integers above 256: a bf16 identity grid would
    shift sampling by whole voxels on paper-scale volumes.  warp_volume must
    keep coordinates fp32 and cast only the sampled intensities."""
    from repro.core import ffd

    # alternating 0/1 intensities are bf16-exact, so any error is a
    # *coordinate* error: a one-voxel shift flips the parity to 1.0
    x = jnp.arange(320, dtype=jnp.float32)
    vol = jnp.broadcast_to((x % 2)[:, None, None], (320, 2, 2))
    disp = jnp.zeros(vol.shape + (3,), jnp.float32).at[..., 0].set(1.0)
    warped = ffd.warp_volume(vol, disp, compute_dtype="bfloat16")
    err = jnp.abs(warped[:-1].astype(jnp.float32) - vol[1:])
    # the old bug (bf16 identity grid): indices in [256, 320) quantise to
    # even, the integer shift lands on the wrong voxel, err.max() == 1.0
    assert float(err.max()) < 1e-2, float(err.max())


def test_bf16_compute_registration_converges_close_to_fp32():
    """Mixed-precision first step (ROADMAP): bf16 BSI + warp inside the
    loop, fp32 params/adjoint accumulation, on the bench small preset."""
    fixed, moving, _ = make_pair(shape=(24, 20, 18), tile=(6, 6, 6),
                                 magnitude=1.5, seed=3)
    from repro.core.registration import ffd_register

    kw = dict(tile=(6, 6, 6), levels=2, iters=8, mode="separable",
              impl="jnp", grad_impl="jnp")
    r32 = ffd_register(fixed, moving, **kw)
    r16 = ffd_register(fixed, moving, compute_dtype="bfloat16", **kw)
    assert r16.warped.dtype == r32.warped.dtype
    # both descend to comparable objectives ...
    assert r16.losses[-1] < 1.1 * r32.losses[-1] + 1e-4
    # ... and land on nearby warps (bf16 has ~3 decimal digits)
    mae = float(jnp.abs(r16.warped - r32.warped).mean())
    assert mae < 5e-3, mae


def test_register_batch_grad_impl_variants_agree():
    """Regression: the batched engine lands on the same registration for
    every adjoint implementation (1e-4, the engine's parity contract)."""
    from repro.engine import register_batch

    pairs = [make_pair(shape=(20, 18, 16), tile=(5, 5, 5), magnitude=1.2,
                       seed=s) for s in (0, 1)]
    F = jnp.stack([p[0] for p in pairs])
    M = jnp.stack([p[1] for p in pairs])
    kw = dict(tile=(5, 5, 5), levels=2, iters=5, mode="separable",
              impl="jnp")
    base = register_batch(F, M, grad_impl="xla", **kw)
    for gi in ("jnp", "pallas"):
        res = register_batch(F, M, grad_impl=gi, **kw)
        np.testing.assert_allclose(np.asarray(res.warped),
                                   np.asarray(base.warped), atol=1e-4)
        np.testing.assert_allclose(np.asarray(res.params),
                                   np.asarray(base.params), atol=1e-4)
        np.testing.assert_allclose(np.asarray(res.losses),
                                   np.asarray(base.losses),
                                   rtol=1e-4, atol=1e-6)


def test_sharded_register_batch_with_custom_adjoint_matches_unsharded():
    """Acceptance: sharded results unchanged (1e-4) under the custom VJP."""
    from repro.engine import make_registration_mesh, register_batch

    pairs = [make_pair(shape=(18, 16, 14), tile=(5, 5, 5), magnitude=1.2,
                       seed=s) for s in range(3)]
    F = jnp.stack([p[0] for p in pairs])
    M = jnp.stack([p[1] for p in pairs])
    kw = dict(tile=(5, 5, 5), levels=1, iters=4, mode="separable",
              impl="jnp", grad_impl="jnp")
    base = register_batch(F, M, **kw)
    res = register_batch(F, M, mesh=make_registration_mesh(), **kw)
    np.testing.assert_allclose(np.asarray(res.warped),
                               np.asarray(base.warped), atol=1e-4)
    np.testing.assert_allclose(np.asarray(res.params),
                               np.asarray(base.params), atol=1e-4)


def test_autotune_enumerates_adjoint_axis(tmp_path):
    """resolve_bsi with grad_impl='auto' tunes the (fwd x adjoint) matrix
    and returns a concrete triple the runner caches key on."""
    from repro.engine import resolve_bsi

    mode, impl, gi = resolve_bsi(
        "separable", "jnp", (8, 8, 8), (3, 3, 3), grad_impl="auto",
        reps=1, cache_path=str(tmp_path / "c.json"))
    assert (mode, impl) == ("separable", "jnp")
    assert gi in GRAD_IMPLS
    # fully explicit triples never touch the tuner
    assert resolve_bsi("tt", "jnp", (8, 8, 8), (3, 3, 3),
                       grad_impl="jnp") == ("tt", "jnp", "jnp")
    # legacy pair behaviour is preserved for forward-only callers
    assert resolve_bsi("tt", "jnp", (8, 8, 8), (3, 3, 3)) == ("tt", "jnp")


def test_autotune_compute_dtype_keys_and_excludes_xla(tmp_path):
    """Under a reduced compute dtype, 'auto' never picks plain autodiff
    (its backward would accumulate in that dtype, not fp32), and the cache
    entry is per-dtype so fp32/bf16 callers never share a winner."""
    import json

    from repro.engine import resolve_bsi

    cache = str(tmp_path / "c.json")
    # a single-candidate pool short-circuits the tuner, so leave mode open
    # to force a measured choice (small grid keeps the sweep cheap)
    _, _, gi = resolve_bsi("auto", "jnp", (7, 7, 7), (2, 2, 2),
                           grad_impl="auto", reps=1, cache_path=cache,
                           compute_dtype="bfloat16")
    assert gi != "xla"
    resolve_bsi("auto", "jnp", (7, 7, 7), (2, 2, 2),
                grad_impl="auto", reps=1, cache_path=cache)
    keys = list(json.load(open(cache))["entries"])  # v2 schema wrapper
    assert any("|cd=bfloat16|" in k for k in keys)
    assert any("|cd=" not in k for k in keys)
    assert len(keys) == 2  # distinct entries, no sharing


def test_autotune_selects_custom_adjoint_for_scatter_heavy_forward(tmp_path):
    """Acceptance: for the gather forward (whose XLA transpose is the
    per-voxel scatter-add) the tuner measures the custom VJP as fastest and
    selects it — the margin is ~65x on the CI preset, far beyond timing
    noise."""
    from repro.engine.autotune import autotune_bsi

    choice = autotune_bsi(
        (8, 8, 8), (4, 4, 4), 3, reps=1, measure_grad=True,
        candidates=(("gather", "jnp"),), grad_impls=("xla", "jnp"),
        cache_path=str(tmp_path / "c.json"))
    assert choice.grad_impl == "jnp"


def test_autotune_pallas_forward_survives_with_custom_adjoint(tmp_path):
    """Under measure_grad, (pallas fwd, xla adjoint) is undifferentiable and
    drops out — but (pallas fwd, jnp adjoint) is a live candidate now."""
    from repro.engine.autotune import autotune_bsi

    from repro.engine.autotune import NO_AUTODIFF

    choice = autotune_bsi(
        (7, 7, 7), (2, 2, 2), 2, reps=1, measure_grad=True,
        candidates=(("separable", "pallas", "xla"),
                    ("separable", "pallas", "jnp")),
        cache_path=str(tmp_path / "c.json"))
    assert (choice.mode, choice.impl, choice.grad_impl) == \
        ("separable", "pallas", "jnp")
    assert choice.skipped == (("separable/pallas/xla", NO_AUTODIFF),)


def test_pick_block_ctrl_clamps_to_grid():
    assert ops.pick_block_ctrl((2, 2, 1), (5, 5, 5)) == 2
    big = ops.pick_block_ctrl((64, 64, 64), (7, 7, 7), budget=8 * 2**20)
    assert ops._block_bytes(big, (64, 64, 64), (7, 7, 7), 4,
                            adjoint=True) <= 8 * 2**20 or big == 1
