"""BSI adjoint Pallas kernels: dense cotangent -> control-grid cotangent.

BSI is linear, so its gradient is the transpose of the forward map.  Both
kernels run in the plane layout of ``kernels.common`` and are the exact
transposes of the two forward kernels, so every in-kernel op stays a 2-D
matmul or an elementwise FMA:

``separable``  (``grad_impl="pallas"``) transposes ``bsi_separable``: the x
               taps fold each tile's ``dx`` cotangent planes onto a VMEM
               window of ``bt + 3`` dense planes (VPU), then each window
               plane projects to control space as ``Ay^T @ G @ Az`` (MXU).
``matmul``     (``grad_impl="matmul"``) transposes ``bsi_matmul``: every
               cotangent plane projects first (``Ay^T @ g_x @ Az``), and the
               x taps then act on the small control planes.

Each grid cell reads its ``bt * dx`` cotangent planes once.  The control
grid of one channel stays resident in VMEM as the output block across the
sequential x axis of the grid and accumulates in fp32 (bf16 cotangents
included): neighbouring cells share three control planes, the transpose of
the forward's halo overlap, and accumulating is what makes that sharing a
reduction instead of a scatter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

__all__ = ["bsi_adjoint_pallas_planes"]

def _project(ayt, az, plane):
    return common.mxu_dot(ayt, common.mxu_dot(plane, az))


def _kernel_separable(ayt_ref, az_ref, g_ref, out_ref, q_ref, *, dx, bt, taps):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    q_ref[...] = jnp.zeros_like(q_ref)

    def tile(j, carry):
        rows = [g_ref[0, j * dx + a].astype(jnp.float32) for a in range(dx)]
        for l in range(4):
            q_ref[j + l] += sum(w[l] * r for w, r in zip(taps, rows))
        return carry

    jax.lax.fori_loop(0, bt, tile, 0)
    ayt = ayt_ref[...]
    az = az_ref[...]
    t0 = i * bt

    def project(j, carry):
        out_ref[0, t0 + j] += _project(ayt, az, q_ref[j])
        return carry

    jax.lax.fori_loop(0, bt + 3, project, 0)


def _kernel_matmul(ayt_ref, az_ref, g_ref, out_ref, *, dx, bt, taps):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    ayt = ayt_ref[...]
    az = az_ref[...]
    t0 = i * bt

    def tile(j, carry):
        d = [_project(ayt, az, g_ref[0, j * dx + a].astype(jnp.float32))
             for a in range(dx)]
        for l in range(4):
            out_ref[0, t0 + j + l] += sum(w[l] * r for w, r in zip(taps, d))
        return carry

    jax.lax.fori_loop(0, bt, tile, 0)


@functools.partial(jax.jit, static_argnames=("dx", "bt", "form", "interpret"))
def bsi_adjoint_pallas_planes(g, ayt, az, *, dx, bt, form, interpret):
    """Planes-layout adjoint: ``g (C, nb*bt*dx, Y, Z)`` -> ``(C, nb*bt+3, Ny, Nz)``.

    ``ayt`` is the ``(Ny, Y)`` transposed y band matrix and ``az`` the
    ``(Z, Nz)`` z band matrix, both float32; the output is float32.
    """
    c, xp, y, z = g.shape
    ny, nz = ayt.shape[0], az.shape[1]
    nb = xp // (bt * dx)
    assert nb * bt * dx == xp, (g.shape, dx, bt)
    nxp = nb * bt + 3
    kernel = _kernel_separable if form == "separable" else _kernel_matmul
    scratch = ([pltpu.VMEM((bt + 3, y, z), jnp.float32)]
               if form == "separable" else [])
    return pl.pallas_call(
        functools.partial(kernel, dx=dx, bt=bt, taps=common.x_taps(dx)),
        grid=(c, nb),
        in_specs=[
            pl.BlockSpec((ny, y), lambda ch, i: (0, 0)),
            pl.BlockSpec((z, nz), lambda ch, i: (0, 0)),
            pl.BlockSpec((1, bt * dx, y, z), lambda ch, i: (ch, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nxp, ny, nz), lambda ch, i: (ch, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, nxp, ny, nz), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=common.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name=f"bsi_adjoint_{form}",
    )(ayt, az, g)
