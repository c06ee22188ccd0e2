"""Matrix-form BSI Pallas kernel: every dense plane as ``Ay @ R @ Az^T``.

Wu & Zou ("Matrix representation and GPU-optimized parallel B-spline
computing") write a uniform B-spline surface as a product of basis
matrices, ``S = U M P M^T V^T``.  On the voxel-aligned grid the basis
matrices of the y and z axes are the banded expansion matrices of
``kernels.common``, so each dense x-plane is

    out[x] = Ay @ R_x @ Az^T,   R_x = sum_l Wx[x % dx, l] * phi[x // dx + l]

— the x sweep runs first, on the small control planes (VPU), and the whole
dense plane is then two MXU matmuls.  Against the separable kernel this
trades VPU work on dense planes for MXU work: ``dx`` plane products per
tile instead of about one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common

__all__ = ["bsi_matmul_pallas"]

def _kernel(ay_ref, azt_ref, phi_ref, out_ref, *, dx, bt, taps):
    t0 = pl.program_id(1) * bt
    ay = ay_ref[...]
    azt = azt_ref[...]

    def tile(j, carry):
        p = [phi_ref[0, t0 + j + l].astype(jnp.float32) for l in range(4)]
        for a, w in enumerate(taps):
            r = w[0] * p[0] + w[1] * p[1] + w[2] * p[2] + w[3] * p[3]
            h = common.mxu_dot(r.astype(azt.dtype), azt)
            row = common.mxu_dot(ay, h.astype(ay.dtype))
            out_ref[0, j * dx + a] = row.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bt, tile, 0)


@functools.partial(jax.jit, static_argnames=("dx", "bt", "interpret"))
def bsi_matmul_pallas(phi, ay, azt, *, dx, bt, interpret):
    """Same contract as ``bsi_separable.bsi_separable_pallas``."""
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
        compiler_params=common.compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(ay, azt, phi)
