"""Shared plumbing for the BSI Pallas kernels: the TPU plane layout.

Mosaic tiles the last two dimensions of every VMEM array into ``(8, 128)``
vector-register tiles, so a channel-last ``(X, Y, Z, 3)`` field would put the
three displacement channels in the 128 lanes (and a Pallas block of it is
refused outright unless its z extent is a multiple of 8).  The kernels
therefore work channel-first, on ``(C, X, Y, Z)`` stacks of x-planes: each
plane is a 2-D ``(Y, Z)`` slab with y on sublanes and z on lanes, padded to
``(8, 128)`` multiples.  ``to_planes`` / ``from_planes`` do the transpose and
padding in XLA at the kernel boundary.

On the voxel-aligned grid the B-spline expansion along one axis is a banded
matrix, ``A[t*d + a, t + l] = W[a, l]`` (``band_matrix``), so a control plane
expands to a dense plane by two MXU matmuls ``Ay @ P @ Az^T`` — the per-axis
LUT sweeps of the separable form with the tile interleave folded into the
matrix, which keeps every in-kernel op a 2-D matmul or an elementwise FMA.
Along x, planes sit on the untiled leading axis and combine with the four
LUT taps on the VPU, the paper's per-tile reuse of the control window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from repro.core.bspline import _weight_lut_np

__all__ = ["SUBLANE", "LANE", "VMEM_LIMIT_BYTES", "VMEM_BUDGET_BYTES",
           "round_up", "plane_bytes", "band_matrix", "x_taps", "mxu_dot",
           "compiler_params"]

SUBLANE, LANE = 8, 128
# Scoped-VMEM limit handed to Mosaic for every BSI kernel (a v5e core has
# 128 MiB of VMEM; the compiler's default scoped limit is 16 MiB), and the
# share of it the block pickers may plan for: the rest is Mosaic's own
# internal scratch (matmul staging, spills).
VMEM_LIMIT_BYTES = 96 * 2**20
VMEM_BUDGET_BYTES = 64 * 2**20


def round_up(n, m):
    return -(-int(n) // m) * m


def plane_bytes(rows, cols, itemsize=4):
    """VMEM bytes of one ``(rows, cols)`` slab after ``(8, 128)`` tiling."""
    return round_up(rows, SUBLANE) * round_up(cols, LANE) * itemsize


@functools.lru_cache(maxsize=None)
def _band_np(tiles, d, rows, cols, dtype_name):
    w = _weight_lut_np(d, "float64")
    m = np.zeros((rows, cols), np.float64)
    t = np.arange(tiles)
    for a in range(d):
        for l in range(4):
            m[t * d + a, t + l] = w[a, l]
    return m.astype(dtype_name)


def band_matrix(tiles, d, rows, cols, dtype_name="float32"):
    """``(rows, cols)`` banded expansion matrix of one axis (zero-padded).

    Row ``t*d + a`` holds the four LUT weights ``W[a, :]`` at columns
    ``t .. t+3``: multiplying a control axis of length ``tiles + 3`` by it
    gives the ``tiles * d`` voxel axis.  Rows and columns beyond those are
    zero, so padded control points never reach a voxel and padded voxels
    never reach a control point.  Returned as NumPy: a compile-time constant.
    """
    return _band_np(int(tiles), int(d), int(rows), int(cols), dtype_name)


def x_taps(d):
    """The ``(d, 4)`` x-axis LUT as Python floats (inlined into kernels)."""
    return tuple(tuple(float(v) for v in row) for row in _weight_lut_np(d, "float64"))


def mxu_dot(a, b):
    """2-D matmul with fp32 accumulation; fp32 operands at full precision
    (Mosaic refuses ``HIGHEST`` on bf16 operands, which need no passes)."""
    hi = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jnp.dot(a, b, precision=hi, preferred_element_type=jnp.float32)


def compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)
