"""Separable BSI Pallas kernel: control planes first, then the x taps.

The aligned-grid weighted sum is a Tucker contraction,

    out[x,y,z] = sum_{l,m,n} Wx[x%dx,l] Wy[y%dy,m] Wz[z%dz,n]
                             * phi[x//dx+l, y//dy+m, z//dz+n],

so it runs as three per-axis sweeps instead of 64 MACs per voxel.  In the
plane layout of ``kernels.common`` the y and z sweeps of one control plane
are the two MXU matmuls ``Q = Ay @ P @ Az^T`` (banded matrices), and the x
sweep combines four consecutive ``Q`` planes with the LUT taps on the VPU.
Each grid cell owns ``bt`` x-tiles: it expands its ``bt + 3`` control planes
(the paper's halo window, Eq. A.4, now along x) into a VMEM scratch, then
writes its ``bt * dx`` dense planes exactly once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

__all__ = ["bsi_separable_pallas"]

def _kernel(ay_ref, azt_ref, phi_ref, out_ref, q_ref, *, dx, bt, taps):
    t0 = pl.program_id(1) * bt
    ay = ay_ref[...]
    azt = azt_ref[...]

    def expand(j, carry):
        h = common.mxu_dot(phi_ref[0, t0 + j], azt)
        q_ref[j] = common.mxu_dot(ay, h.astype(ay.dtype))
        return carry

    jax.lax.fori_loop(0, bt + 3, expand, 0)

    def tile(j, carry):
        q = [q_ref[j + l] for l in range(4)]
        for a, w in enumerate(taps):
            row = w[0] * q[0] + w[1] * q[1] + w[2] * q[2] + w[3] * q[3]
            out_ref[0, j * dx + a] = row.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bt, tile, 0)


@functools.partial(jax.jit, static_argnames=("dx", "bt", "interpret"))
def bsi_separable_pallas(phi, ay, azt, *, dx, bt, interpret):
    """Planes-layout forward: ``phi (C, nb*bt+3, Ny, Nz)`` -> ``(C, nb*bt*dx, Y, Z)``.

    ``ay`` is the ``(Y, Ny)`` and ``azt`` the ``(Nz, Z)`` banded matrix
    (``common.band_matrix``); ``Ny, Nz, Y, Z`` are already padded to
    ``(8, 128)`` multiples by ``kernels.ops``.
    """
    c, nxp, ny, nz = phi.shape
    y, z = ay.shape[0], azt.shape[1]
    nb = (nxp - 3) // bt
    assert nb * bt + 3 == nxp, (phi.shape, bt)
    return pl.pallas_call(
        functools.partial(_kernel, dx=dx, bt=bt, taps=common.x_taps(dx)),
        grid=(c, nb),
        in_specs=[
            pl.BlockSpec((y, ny), lambda ch, i: (0, 0)),
            pl.BlockSpec((nz, z), lambda ch, i: (0, 0)),
            pl.BlockSpec((1, nxp, ny, nz), lambda ch, i: (ch, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt * dx, y, z), lambda ch, i: (ch, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, nb * bt * dx, y, z), phi.dtype),
        scratch_shapes=[pltpu.VMEM((bt + 3, y, z), jnp.float32)],
        compiler_params=common.compiler_params("parallel", "parallel"),
        interpret=interpret,
        name="bsi_separable",
    )(ay, azt, phi)
