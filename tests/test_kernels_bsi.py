"""Pallas BSI kernels vs the pure-jnp oracle: shape/dtype sweeps (interpret)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import bsi_ref

KERNEL_MODES = ops.PALLAS_MODES

SHAPE_SWEEP = [
    # (grid points per axis, tile)
    ((7, 6, 5), (5, 4, 3)),
    ((9, 9, 9), (5, 5, 5)),      # paper's default tile
    ((4, 4, 4), (3, 3, 3)),      # single tile per axis, smallest tile
    ((11, 4, 6), (7, 7, 7)),     # paper's largest tile, non-cubic grid
    ((12, 12, 5), (6, 6, 6)),
    ((5, 13, 9), (4, 6, 5)),     # mixed tile
    ((14, 5, 30), (5, 5, 5)),    # 11 x-tiles: padded up to whole x blocks
    ((5, 30, 33), (2, 5, 5)),    # dense y, z past one (8, 128) plane tile
]


@pytest.mark.parametrize("mode", KERNEL_MODES)
@pytest.mark.parametrize("grid,tile", SHAPE_SWEEP)
def test_kernel_matches_oracle(mode, grid, tile):
    rng = np.random.default_rng(hash((grid, tile)) % 2**31)
    phi = jnp.asarray(rng.standard_normal(grid + (3,)), jnp.float32)
    ref = bsi_ref(phi, tile)
    out = ops.bsi_pallas(phi, tile, mode=mode)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


@pytest.mark.parametrize("mode", KERNEL_MODES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtypes(mode, dtype):
    rng = np.random.default_rng(3)
    phi = jnp.asarray(rng.standard_normal((7, 7, 7, 3)), dtype)
    ref = bsi_ref(phi.astype(jnp.float32), (5, 5, 5))
    out = ops.bsi_pallas(phi, (5, 5, 5), mode=mode)
    atol = 3e-6 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=atol
    )


@pytest.mark.parametrize("mode", KERNEL_MODES)
def test_kernel_channels(mode):
    # deformation fields are C=3, but the kernels are generic (paper §8: BSI
    # as generic interpolation, e.g. image zoom with C=1).
    for c in (1, 2, 4):
        rng = np.random.default_rng(c)
        phi = jnp.asarray(rng.standard_normal((6, 6, 6, c)), jnp.float32)
        ref = bsi_ref(phi, (4, 4, 4))
        out = ops.bsi_pallas(phi, (4, 4, 4), mode=mode)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


@pytest.mark.parametrize("mode", KERNEL_MODES)
@pytest.mark.parametrize("block_tiles", [1, 2, 4])
def test_kernel_block_shapes(block_tiles, mode):
    rng = np.random.default_rng(7)
    phi = jnp.asarray(rng.standard_normal((8, 8, 8, 3)), jnp.float32)
    ref = bsi_ref(phi, (5, 5, 5))
    out = ops.bsi_pallas(phi, (5, 5, 5), mode=mode, block_tiles=block_tiles)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


@pytest.mark.parametrize("mode", sorted(ops.NO_KERNEL))
def test_modes_without_kernel_refused_with_reason(mode):
    """Forms with no Pallas kernel are refused up front, naming why — never
    left to fail inside the TPU compiler at run time."""
    phi = jnp.zeros((5, 5, 5, 3), jnp.float32)
    with pytest.raises(ValueError, match=ops.NO_KERNEL[mode][:20]):
        ops.bsi_pallas(phi, (3, 3, 3), mode=mode)


def test_default_interpret_resolves_from_backend(monkeypatch):
    """interpret defaults per-backend: compiled on TPU, interpreter elsewhere
    — callers never thread the flag."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.default_interpret() is False
    for backend in ("cpu", "gpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops.default_interpret() is True


def test_bsi_pallas_runs_without_interpret_flag():
    # on the CPU test backend the default must resolve to interpret mode
    rng = np.random.default_rng(0)
    phi = jnp.asarray(rng.standard_normal((6, 6, 6, 3)), jnp.float32)
    out = ops.bsi_pallas(phi, (4, 4, 4), mode="separable")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(bsi_ref(phi, (4, 4, 4))), atol=3e-6)


def test_pick_block_tiles_respects_budget():
    """The x block shrinks until the cell's VMEM model fits the budget; at
    phantom1's grid (103 x 46 x 77 tiles) the default budget takes 8 tiles."""
    tiles, tile = (103, 46, 77), (5, 5, 5)
    assert ops.pick_block_tiles(tiles, tile) == 8
    small = 20 * 2**20
    bt = ops.pick_block_tiles(tiles, tile, budget=small)
    assert 1 <= bt < 8
    assert ops._block_bytes(bt, tiles, tile, 4, adjoint=False) <= small
    assert ops._block_bytes(bt + 1, tiles, tile, 4, adjoint=False) > small


def test_pick_block_tiles_clamps_to_tiny_grids():
    """num_tiles is honoured: a grid with fewer x tiles than the default
    block never pads up to a larger block."""
    assert ops.pick_block_tiles((2, 1, 3), (5, 5, 5)) == 2
    assert ops.pick_block_ctrl((1, 1, 64), (7, 7, 7)) == 1
    # and the padded kernel path agrees with the oracle on such grids
    rng = np.random.default_rng(11)
    phi = jnp.asarray(rng.standard_normal((5, 4, 6, 3)), jnp.float32)
    out = ops.bsi_pallas(phi, (4, 4, 4), mode="separable")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(bsi_ref(phi, (4, 4, 4))), atol=3e-6)


def test_op_count_model():
    """Paper App. B: 255 ops/voxel (TT) vs 126 (TTLI) vs separable.

    Counted per scalar output on the weighted-sum DAG:
      TT:   64 summands * (3 mults + 1 add) - 1 = 255
      TTLI: 63 lerps * 2 ops = 126
      separable: per-axis sweeps, 4 MACs per intermediate element.
    """
    tt = 64 * (3 + 1) - 1
    ttli = (8 * 7 + 7) * 2
    assert tt == 255 and ttli == 126
    # separable MACs per tile of d^3 voxels: each sweep output costs 4 MACs;
    # x sweep has d*4*4 outputs, y sweep d*d*4, z sweep d^3.
    d = 5
    sep = 4 * (d * 4 * 4) + 4 * (d * d * 4) + 4 * d**3
    naive = 64 * d**3
    assert sep == 1220 and naive == 8000
    assert naive / sep > 6.5  # ~6.6x MAC reduction for d=5
    # per-voxel form quoted in DESIGN.md: 4 + 16/d + 64/d^2 MACs/voxel
    per_voxel_sep = 4 + 16 / d + 64 / d**2
    assert abs(per_voxel_sep - sep / d**3) < 1e-9
    assert 64 / per_voxel_sep > 6.5  # -> 16x asymptotically in d
