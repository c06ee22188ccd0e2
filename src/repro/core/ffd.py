"""Free-Form Deformation: control grid -> dense deformation field -> warp.

The FFD transform (Rueckert et al. 1999, as used by NiftyReg and the paper)
manipulates a coarse uniform grid of 3-vector control points; BSI expands it
to a dense per-voxel displacement field; the moving volume is resampled at the
displaced coordinates (trilinear image resampling, NiftyReg's default).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.interpolate import interpolate

__all__ = [
    "grid_shape_for_volume",
    "dense_field",
    "fused_warp_loss",
    "trilinear_sample",
    "warp_volume",
    "bending_energy",
    "downsample2",
    "upsample_grid",
]


def grid_shape_for_volume(vol_shape, tile) -> tuple:
    """Stored control-grid dims covering ``vol_shape`` at spacing ``tile``."""
    return tuple(-(-int(s) // int(d)) + 3 for s, d in zip(vol_shape, tile))


def downsample2(vol):
    """2x average-pool downsampling (pyramid level)."""
    X, Y, Z = (s - s % 2 for s in vol.shape)
    v = vol[:X, :Y, :Z].reshape(X // 2, 2, Y // 2, 2, Z // 2, 2)
    return v.mean(axis=(1, 3, 5))


def upsample_grid(phi, new_shape):
    """Upsample a control grid to a finer level's grid shape (trilinear).

    One batched ``trilinear_sample`` (``vmap`` over the displacement channel)
    so pyramid-level promotion compiles to a single gather instead of a
    per-channel Python loop.
    """
    old = phi.shape[:3]
    coords = jnp.stack(
        jnp.meshgrid(
            *[jnp.linspace(0.0, o - 1.0, n) for o, n in zip(old, new_shape)],
            indexing="ij",
        ),
        axis=-1,
    )
    comps = jax.vmap(trilinear_sample, in_axes=(3, None), out_axes=3)(
        phi, coords)
    return comps * 2.0  # displacements double at 2x res


def dense_field(phi, tile, vol_shape, *, mode="separable", impl="jnp",
                grad_impl="xla", compute_dtype=None):
    """Expand control grid to a dense displacement field cropped to volume.

    ``grad_impl`` selects how the expansion differentiates (``xla`` = plain
    autodiff; ``jnp`` / ``pallas`` = the analytic gather-only adjoint via
    ``jax.custom_vjp`` — see ``repro.core.interpolate``).  ``compute_dtype``
    (e.g. ``bfloat16``) runs the interpolation in reduced precision while
    params and the analytic adjoints' accumulation stay fp32; an *explicit*
    ``grad_impl="xla"`` is the one combination whose backward follows the
    compute dtype instead (plain autodiff of the reduced-precision forward
    — the engine's ``"auto"`` therefore never picks it under a reduced
    ``compute_dtype``).
    """
    full = interpolate(phi, tile, mode=mode, impl=impl, grad_impl=grad_impl,
                       dtype=compute_dtype)
    return full[: vol_shape[0], : vol_shape[1], : vol_shape[2]]


def fused_warp_loss(phi, moving, fixed, tile, *, similarity="ssd",
                    mode="separable", impl="jnp", grad_impl="xla",
                    compute_dtype=None):
    """``sim(warp(moving, bsi(phi)), fixed)`` without a dense field in HBM.

    The differentiable face of the fused level step: the forward runs the
    single-pass Pallas kernel (``kernels.ops.fused_similarity_loss`` — BSI
    displacement + trilinear warp + similarity partial sums per VMEM block),
    and a ``jax.custom_vjp`` backward recomputes the unfused composition
    ``dense_field -> warp_volume -> sim`` under ``jax.vjp`` so the gradient
    flows through PR 4's analytic gather-only adjoint (``grad_impl``) —
    gradients are therefore *identical* to the unfused path, not merely
    close.  ``similarity`` must have a fused accumulator
    (``core.similarity.fused_spec``); custom callables raise.

    ``impl`` / ``grad_impl`` configure only the backward's recompute;
    ``mode`` also selects the fused forward's displacement stage —
    ``mode="matmul"`` runs the megakernel's BSI contraction in the MXU
    matrix form (``kernels.bsi_fused._disp_block(form="matmul")``), every
    other mode runs the separable sweeps (the kernel's two contraction
    forms; both produce the same displacement).  ``compute_dtype``
    quantises the displacement and the sampled intensities exactly as the
    unfused pair of knobs does, with fp32 partial-sum accumulation.
    """
    from repro.core.similarity import fused_spec

    spec = fused_spec(similarity)
    if spec is None:
        raise ValueError(
            f"similarity {similarity!r} has no fused kernel — custom "
            "callables must run unfused (fused='off')")
    cd = None if compute_dtype is None else jnp.dtype(compute_dtype).name
    f = _fused_objective(tuple(int(t) for t in tile), tuple(spec),
                         str(mode), str(impl), str(grad_impl), cd)
    return f(phi, moving, fixed)


@functools.lru_cache(maxsize=None)
def _fused_objective(tile, spec, mode, impl, grad_impl, cdtype):
    from repro.core.similarity import _loss_from_spec
    from repro.kernels import ops

    sim = _loss_from_spec(spec)

    def unfused(p, mov, fix):
        disp = dense_field(p, tile, mov.shape, mode=mode, impl=impl,
                           grad_impl=grad_impl, compute_dtype=cdtype)
        warped = warp_volume(mov, disp, compute_dtype=cdtype)
        return sim(warped.astype(jnp.float32), fix.astype(jnp.float32))

    disp_form = "matmul" if mode == "matmul" else "separable"

    @jax.custom_vjp
    def fused(p, mov, fix):
        return ops.fused_similarity_loss(p, mov, fix, tile, sim_spec=spec,
                                         compute_dtype=cdtype,
                                         disp_form=disp_form)

    def fwd(p, mov, fix):
        return fused(p, mov, fix), (p, mov, fix)

    def bwd(res, g):
        # recompute-based backward: unused cotangents (mov/fix are data,
        # not optimisation variables) are dead code XLA prunes
        _, vjp = jax.vjp(unfused, *res)
        return vjp(g)

    fused.defvjp(fwd, bwd)
    return fused


def trilinear_sample(vol, coords):
    """Sample ``vol`` (X, Y, Z) at continuous voxel coords ``(..., 3)``.

    Border policy: clamp (NiftyReg uses nearest/zero padding; clamp keeps the
    objective smooth for autodiff).
    """
    vol = jnp.asarray(vol)
    shape = jnp.asarray(vol.shape, coords.dtype)
    c = jnp.clip(coords, 0.0, shape - 1.0)
    f = jnp.floor(c)
    t = c - f
    i0 = f.astype(jnp.int32)
    i1 = jnp.minimum(i0 + 1, jnp.asarray(vol.shape, jnp.int32) - 1)

    def at(ix, iy, iz):
        return vol[ix, iy, iz]

    x0, y0, z0 = i0[..., 0], i0[..., 1], i0[..., 2]
    x1, y1, z1 = i1[..., 0], i1[..., 1], i1[..., 2]
    tx, ty, tz = t[..., 0], t[..., 1], t[..., 2]
    c00 = at(x0, y0, z0) * (1 - tx) + at(x1, y0, z0) * tx
    c01 = at(x0, y0, z1) * (1 - tx) + at(x1, y0, z1) * tx
    c10 = at(x0, y1, z0) * (1 - tx) + at(x1, y1, z0) * tx
    c11 = at(x0, y1, z1) * (1 - tx) + at(x1, y1, z1) * tx
    c0 = c00 * (1 - ty) + c10 * ty
    c1 = c01 * (1 - ty) + c11 * ty
    return c0 * (1 - tz) + c1 * tz


def warp_volume(moving, disp, compute_dtype=None):
    """Resample ``moving`` at identity + displacement (both in voxel units).

    ``compute_dtype`` (e.g. ``bfloat16``) casts the sampled *intensities*
    (the memory-bound gather) — the mixed-precision partner of
    ``dense_field``'s knob; the caller decides where to cast back up
    (``engine.batch.ffd_level_loss`` scores the objective in the fixed
    volume's dtype).  Sampling *coordinates* always stay fp32: bf16 cannot
    represent integers above 256, so a bf16 identity grid would shift
    sampling positions by whole voxels on paper-scale (>256-voxel) volumes.
    """
    coord_dtype = jnp.promote_types(disp.dtype, jnp.float32)
    if compute_dtype is not None:
        moving = jnp.asarray(moving, compute_dtype)
    disp = jnp.asarray(disp, coord_dtype)
    X, Y, Z = moving.shape
    xs = jnp.arange(X, dtype=coord_dtype)
    ys = jnp.arange(Y, dtype=coord_dtype)
    zs = jnp.arange(Z, dtype=coord_dtype)
    ident = jnp.stack(jnp.meshgrid(xs, ys, zs, indexing="ij"), axis=-1)
    return trilinear_sample(moving, ident + disp)


def bending_energy(phi):
    """Thin-plate bending energy of the control grid (NiftyReg regulariser).

    Second-order finite differences on the control lattice — a standard,
    cheap surrogate for the analytic B-spline bending energy.
    """
    e = 0.0
    for ax in range(3):
        d2 = jnp.diff(phi, n=2, axis=ax)
        e = e + jnp.mean(d2**2)
    # mixed second derivatives
    for a in range(3):
        for b in range(a + 1, 3):
            d = jnp.diff(jnp.diff(phi, axis=a), axis=b)
            e = e + 2.0 * jnp.mean(d**2)
    return e
