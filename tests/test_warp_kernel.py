"""The trilinear warp kernel (``kernels/warp.py``) against the XLA form.

The kernel runs under the Pallas interpreter here.  ``warp_volume`` takes it
only where kernels compile; these tests steer it there by patching
``ops.warp_supported``, and compare values, both cotangents, forward mode
and ``vmap`` with ``ffd.trilinear_sample``'s gathers.  The shape is odd on
every axis and its z axis spans two 128-lane registers; the random field
(3 voxels std) sends samples past every border, and the zero field puts the
border samples exactly on the clamp's bounds, where ``jnp.clip``'s
derivative is 1/2.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ffd
from repro.kernels import ops

SHAPE = (19, 13, 150)


def _volume(seed=0, shape=SHAPE):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _field(kind, seed=1, shape=SHAPE):
    if kind == "zero":
        return jnp.zeros(shape + (3,), jnp.float32)
    return 3.0 * jax.random.normal(jax.random.PRNGKey(seed), shape + (3,),
                                   jnp.float32)


@pytest.fixture
def kernel_path(monkeypatch):
    """``warp_volume`` takes the kernel (interpreted here)."""
    monkeypatch.setattr(ops, "warp_supported", lambda shape, dtype: True)


def _xla(moving, disp):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "warp_supported", lambda shape, dtype: False)
        return ffd.warp_volume(moving, disp)


@pytest.mark.parametrize("field", ["random", "zero"])
def test_kernel_values_match_trilinear_sample(field):
    vol, disp = _volume(), _field(field)
    ident = jnp.stack(jnp.meshgrid(*[jnp.arange(n, dtype=jnp.float32)
                                     for n in SHAPE], indexing="ij"), -1)
    want = ffd.trilinear_sample(vol, ident + disp)
    got = ops.warp_trilinear(vol, disp)
    got_p, _ = ops.warp_trilinear(vol, disp, partials=True)
    assert got.shape == SHAPE
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    np.testing.assert_array_equal(got_p, got)
    if field == "random":  # the field reaches past every border
        coords = ident + disp
        for ax, n in enumerate(SHAPE):
            assert jnp.any(coords[..., ax] < 0)
            assert jnp.any(coords[..., ax] > n - 1)


def test_nan_displacement_gives_nan_samples():
    """A NaN displacement gives a NaN sample there and nowhere else, as in
    the XLA form (the kernel's loop bounds come from clamped indices)."""
    vol, disp = _volume(), _field("random")
    disp = disp.at[3, 4, 5, 0].set(jnp.nan).at[7, 0, 140, 2].set(jnp.nan)
    got = ops.warp_trilinear(vol, disp)
    want = _xla(vol, disp)
    np.testing.assert_array_equal(jnp.isnan(got), jnp.isnan(want))
    assert int(jnp.sum(jnp.isnan(got))) == 2
    np.testing.assert_allclose(jnp.nan_to_num(got), jnp.nan_to_num(want),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("field", ["random", "zero"])
def test_displacement_gradient_matches_autodiff(kernel_path, field):
    vol, disp = _volume(), _field(field)
    g = _volume(seed=2)

    def loss(warp, d):
        return jnp.sum(g * warp(vol, d))

    got = jax.grad(lambda d: loss(ffd.warp_volume, d))(disp)
    want = jax.grad(lambda d: loss(_xla, d))(disp)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    if field == "zero":
        # x = 0 lies on the clamp's lower bound: half the one-sided slope
        half = 0.5 * (vol[1] - vol[0]) * g[0]
        np.testing.assert_allclose(want[0, ..., 0], half, rtol=0, atol=1e-5)
        np.testing.assert_allclose(got[0, ..., 0], half, rtol=0, atol=1e-5)


def test_moving_cotangent_is_the_xla_forms(kernel_path):
    vol, disp = _volume(), _field("random")
    g = _volume(seed=2)
    got = jax.grad(lambda m: jnp.sum(g * ffd.warp_volume(m, disp)))(vol)
    want = jax.grad(lambda m: jnp.sum(g * _xla(m, disp)))(vol)
    np.testing.assert_array_equal(got, want)


def test_forward_mode_matches_autodiff(kernel_path):
    """Gauss-Newton linearises the warp (``jax.linearize``)."""
    vol, disp = _volume(), _field("random")
    tangent = _field("random", seed=3)
    _, got = jax.jvp(lambda d: ffd.warp_volume(vol, d), (disp,), (tangent,))
    _, want = jax.jvp(lambda d: _xla(vol, d), (disp,), (tangent,))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def test_vmap_over_pairs_matches_single_calls(kernel_path):
    vols = jnp.stack([_volume(0), _volume(4)])
    disps = jnp.stack([_field("random", 1), _field("random", 5)])
    g = _volume(seed=2)

    def value_and_grad(m, d):
        return jax.value_and_grad(
            lambda d: jnp.sum(g * ffd.warp_volume(m, d)))(d)

    batched = jax.vmap(ops.warp_trilinear)(vols, disps)
    _, bgrad = jax.vmap(value_and_grad)(vols, disps)
    for b in range(2):
        np.testing.assert_array_equal(batched[b],
                                      ops.warp_trilinear(vols[b], disps[b]))
        np.testing.assert_array_equal(bgrad[b],
                                      value_and_grad(vols[b], disps[b])[1])


def test_warp_volume_takes_the_kernel_only_where_supported(monkeypatch):
    """Where kernels compile, float32 volumes within the VMEM budget take the
    kernel (both cells' volumes); bfloat16 and phantom1 keep the gathers.
    On this backend nothing does."""
    assert not ops.warp_supported((303, 167, 212), jnp.float32)
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    for shape in [(303, 167, 212), (294, 130, 208), SHAPE]:
        assert ops.warp_supported(shape, jnp.float32), shape
    assert not ops.warp_supported((303, 167, 212), jnp.bfloat16)
    assert not ops.warp_supported((512, 228, 385), jnp.float32)
    assert ops.warp_vmem_bytes((512, 228, 385)) > 2**26


def test_tile_visits_of_the_zero_field():
    """An identity warp reads its own plane and the next (one on the last
    plane), its own rows and the next, and one z-register."""
    shape = (5, 16, 128)
    visits = ops.warp_tile_visits(jnp.zeros(shape + (3,), jnp.float32))
    assert float(visits["planes"]) == pytest.approx(9 / 5)
    assert float(visits["rows"]) == 2.0
    assert float(visits["zregs"]) == 1.0
    assert float(visits["iterations"]) == pytest.approx(2 * 9 / 5)
    wavy = ops.warp_tile_visits(_field("random", shape=shape))
    assert float(wavy["iterations"]) > 2 * 9 / 5


@pytest.mark.parametrize("scale", [3.0, 0.5])
def test_tile_visits_count_the_kernels_iterations(monkeypatch, scale):
    """``warp_tile_visits`` counts the inner iterations (one row window of
    one source plane for one z-register) that the interpreted kernel runs on
    a random field: each executed iteration reports itself."""
    shape = (7, 21, 133)
    jax.clear_caches()  # trace the kernel anew, with the counting loop
    hits = []
    fori_loop = jax.lax.fori_loop

    def counted(lo, hi, body, init, **kw):
        if body.__name__ == "row":  # _gather_corners' innermost loop
            inner = body

            def body(i, carry):
                jax.debug.callback(lambda: hits.append(1))
                return inner(i, carry)

        return fori_loop(lo, hi, body, init, **kw)

    monkeypatch.setattr(jax.lax, "fori_loop", counted)
    disp = scale * _field("random", seed=6, shape=shape)
    jax.block_until_ready(ops.warp_trilinear(_volume(shape=shape), disp))
    jax.effects_barrier()
    registers = shape[0] * 3 * 2  # x-planes x 8-row tiles x 128-lane tiles
    want = ops.warp_tile_visits(disp)["iterations"] * registers
    assert len(hits) == round(want) > 4 * registers
