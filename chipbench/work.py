"""The work BSI must do in a registration, counted from shapes.

Whatever implements it, the forward expansion reads the control grid and
writes the dense float32 field (3 channels, 12 B per voxel); the adjoint
reads the field's gradient and writes the grid's.  The FLOP count is that of
the cheapest exact evaluation, three separable passes of 4 multiply-adds per
output point.  Both are lower bounds, so a measured kernel time can never
beat the roofline time computed from them.
"""
from __future__ import annotations

import dataclasses

ITEMSIZE = 4  # float32


@dataclasses.dataclass(frozen=True)
class Work:
    bytes: float
    flops: float

    def __add__(self, other):
        return Work(self.bytes + other.bytes, self.flops + other.flops)

    def __mul__(self, k):
        return Work(self.bytes * k, self.flops * k)


def grid_shape(vol_shape, tile):
    return tuple(-(-int(s) // int(h)) + 3 for s, h in zip(vol_shape, tile))


def level_shapes(vol_shape, levels):
    """Pyramid volume shapes, coarse to fine (crop to even, halve)."""
    shapes = [tuple(int(s) for s in vol_shape)]
    for _ in range(int(levels) - 1):
        shapes.append(tuple((s - s % 2) // 2 for s in shapes[-1]))
    return shapes[::-1]


def bsi_pass(vol_shape, tile, channels=3):
    """One forward expansion, or one adjoint: the same bytes and FLOPs."""
    x, y, z = vol_shape
    nx, ny, nz = grid_shape(vol_shape, tile)
    voxels, points = x * y * z, nx * ny * nz
    flops = 2 * 4 * channels * (nx * ny * z + nx * y * z + x * y * z)
    return Work(ITEMSIZE * channels * (voxels + points), flops)


def registration_bsi(vol_shape, tile, levels, iters):
    """BSI work of one registration, ``(forward, adjoint)``: at each level
    ``iters + 1`` loss and gradient evaluations (the first seeds the
    optimiser), each one forward and one adjoint, and the final warp's
    forward at full size."""
    per_level = Work(0.0, 0.0)
    for shape in level_shapes(vol_shape, levels):
        per_level = per_level + bsi_pass(shape, tile) * (int(iters) + 1)
    return per_level + bsi_pass(vol_shape, tile), per_level


def roofline_seconds(work, peaks):
    """The least time the chip can take: the larger of the two bounds."""
    return max(work.bytes / peaks["hbm_bytes_per_s"],
               work.flops / peaks["flops_per_s"])
