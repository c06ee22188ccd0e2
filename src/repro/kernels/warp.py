"""Trilinear warp Pallas kernel: the moving volume held in VMEM, sampled by
in-register lane gathers.

XLA lowers ``core.ffd.trilinear_sample``'s eight corner reads to eight
scalar gathers over every voxel, one element per index.  Mosaic lowers a
gather only inside one vector register (``take_along_axis`` over the 128
lanes of an ``(8, 128)`` tile), so this kernel keeps the whole moving volume
in VMEM, read once per call and single-buffered, and brings each output
register the source registers its samples touch:

* the grid runs over output x-planes; one output register covers 8 y-rows
  and 128 z-lanes of its plane, and its sample coordinates come from the
  displacement block and the register's own indices;
* the samples' clamped corner indices bound the 128-lane z-registers the
  output register needs and, for each, the source x-planes and y-row
  offsets of the samples that need it.  The bounds are read in the kernel,
  so the warp is exact for any displacement and its cost follows the
  field's local spread (:func:`corner_indices`, :func:`zreg_range`,
  :func:`zreg_box`; :func:`visit_counts` counts them);
* for each z-register, source plane and row offset ``r``, an 8-row window
  whose sublane ``s`` holds stored row ``r + s`` (two aligned loads and a
  sublane rotate) is lane-gathered at ``z0`` and ``z1``; where a sample's
  corner indices match, the gathered value fills that corner's register;
* the eight corners are lerped in ``trilinear_sample``'s order and,
  optionally, differentiated: the three partials dI/d(x, y, z) at each
  sample, with ``jnp.clip``'s derivative (1/2 on a bound, 0 beyond it).

Layout (``kernels.ops.warp_trilinear`` builds it): the displacement
channel-first, ``(3, X, Yo, Zo)`` with ``Yo, Zo`` padded to ``(8, 128)``
multiples; the moving volume stored as ``(X, Ys, Zo)`` with ``ROW_PAD`` zero
rows in front of row 0 and at least ``ROW_PAD`` after it, so that every
window a sample can ask for lies inside the array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

__all__ = ["ROW_PAD", "stored_rows", "corner_indices", "zreg_range",
           "zreg_box", "visit_counts", "warp_trilinear_pallas"]

# Zero rows stored in front of y = 0: a window starts at stored row
# ``y + ROW_PAD - s`` for output sublane ``s``, never below row 1.
ROW_PAD = common.SUBLANE
_LOG2_LANE = common.LANE.bit_length() - 1


def stored_rows(y):
    """Rows of the stored moving volume: the front pad, then room for the
    two aligned 8-row loads of the last window (start ``y - 1 + ROW_PAD``)."""
    last = (int(y) - 1 + ROW_PAD) // common.SUBLANE * common.SUBLANE
    return last + 2 * common.SUBLANE


def _axis(c, n):
    """``trilinear_sample`` along one axis: clamp, floor, lerp weight and the
    two corner indices (``i1`` clamped like ``i0 + 1``).  ``i0`` is clamped
    too: not every backend defines the integer a NaN coordinate converts
    to, and the kernel's loop bounds are read from these indices (the
    sample is NaN either way)."""
    c = jnp.minimum(jnp.float32(n - 1), jnp.maximum(jnp.float32(0), c))
    f = jnp.floor(c)
    i0 = jnp.minimum(jnp.maximum(f.astype(jnp.int32), 0), n - 1)
    return c - f, i0, jnp.minimum(i0 + 1, n - 1)


def corner_indices(cx, cy, cz, sub, shape):
    """Lerp weights and the corner indices the kernel loops over.

    ``cx, cy, cz`` are unclamped sample coordinates and ``sub`` each
    sample's output sublane (its y modulo 8).  Returns the weights
    ``(tx, ty, tz)``, the x-plane indices ``(x0, x1)``, the window starts
    ``(r0, r1)`` (stored row of the y corner minus the sublane), the
    z-registers ``(v0, v1)`` and the lanes in them ``(l0, l1)``.  Shared by
    the kernel and :func:`repro.kernels.ops.warp_tile_visits`.
    """
    X, Y, Z = shape
    tx, x0, x1 = _axis(cx, X)
    ty, y0, y1 = _axis(cy, Y)
    tz, z0, z1 = _axis(cz, Z)
    lane = common.LANE - 1
    return ((tx, ty, tz), (x0, x1), (y0 + ROW_PAD - sub, y1 + ROW_PAD - sub),
            (z0 >> _LOG2_LANE, z1 >> _LOG2_LANE), (z0 & lane, z1 & lane))


def _clip_slope(c, n):
    """d/dc of ``jnp.clip(c, 0, n - 1)`` as JAX differentiates it: the
    ``maximum`` then ``minimum`` it is built from each give 1/2 at a tie."""
    hi = jnp.float32(n - 1)
    m = jnp.maximum(jnp.float32(0), c)
    lo_s = jnp.where(c > 0, 1.0, jnp.where(c == 0, 0.5, 0.0))
    hi_s = jnp.where(m < hi, 1.0, jnp.where(m == hi, 0.5, 0.0))
    return lo_s * hi_s


def _masked_range(a, b, mask, axis=None):
    """``[min a, max b]`` over the samples in ``mask`` (empty when none)."""
    big = jnp.int32(2**30)
    return (jnp.min(jnp.where(mask, a, big), axis=axis),
            jnp.max(jnp.where(mask, b, -big), axis=axis) + 1)


def zreg_range(v, axis=None):
    """``[lo, hi)`` of the z-registers an output register's samples touch
    (per register, over ``axis`` of the corner indices)."""
    return jnp.min(v[0], axis=axis), jnp.max(v[1], axis=axis) + 1


def zreg_box(x, r, v, u, axis=None):
    """The source planes and row windows visited for z-register ``u``:
    ``([lo, hi) of planes, [lo, hi) of windows)`` over the samples that
    need ``u`` (empty where none does)."""
    need = (v[0] == u) | (v[1] == u)
    return (_masked_range(x[0], x[1], need, axis),
            _masked_range(r[0], r[1], need, axis))


def visit_counts(x, r, v, axis):
    """The loop bounds of :func:`_gather_corners` for output registers laid
    out along ``axis`` of the corner indices: per register, the z-registers
    visited, and for each z-register index the planes and row windows
    visited (0 where the register does not visit it)."""
    lo, hi = zreg_range(v, axis)
    boxes = [[jnp.maximum(e - b, 0) for b, e in zreg_box(x, r, v, u, axis)]
             for u in range(int(jnp.max(hi)))]
    return hi - lo, boxes


def _gather_corners(vol_ref, x, r, v, lz):
    """The eight corner values ``c[a][b][c]`` (x corner ``a``, y ``b``, z
    ``c``) of one output register's samples.

    Loops over the z-registers the samples touch and, for each, over the
    planes and row windows of just the samples that need that z-register
    (most need only their own, so the registers beside it cost a narrow
    box).  A corner takes the gathered value where its key, (plane, window,
    z-register) as one integer, matches the iteration's.
    """
    sl, ln = common.SUBLANE, common.LANE
    nw, nv = vol_ref.shape[1], vol_ref.shape[2] // ln
    keys = [(x[a] * nw + r[b]) * nv + v[c]
            for a in range(2) for b in range(2) for c in range(2)]
    s = jax.lax.broadcasted_iota(jnp.int32, lz[0].shape, 0)

    def zreg(u, acc):
        planes, windows = zreg_box(x, r, v, u)
        lanes = pl.ds(pl.multiple_of(u * ln, ln), ln)

        def plane(p, acc):
            def row(w, acc):
                # rows w .. w+7: the two aligned loads around them, rotated
                # and merged by sublane
                a0 = pl.multiple_of(w // sl * sl, sl)
                o = w - a0
                lo = vol_ref[p, pl.ds(a0, sl), lanes]
                hi = vol_ref[p, pl.ds(pl.multiple_of(a0 + sl, sl), sl), lanes]
                shift = (sl - o) % sl
                src = jnp.where(s < sl - o, pltpu.roll(lo, shift, 0),
                                pltpu.roll(hi, shift, 0))
                g = [jnp.take_along_axis(src, l, axis=1,
                                         mode="promise_in_bounds")
                     for l in lz]
                key = (p * nw + w) * nv + u
                return tuple(jnp.where(keys[k] == key, g[k % 2], acc[k])
                             for k in range(8))

            return jax.lax.fori_loop(*windows, row, acc)

        return jax.lax.fori_loop(*planes, plane, acc)

    zero = jnp.zeros(lz[0].shape, jnp.float32)
    c = jax.lax.fori_loop(*zreg_range(v), zreg, (zero,) * 8)
    return [[c[0:2], c[2:4]], [c[4:6], c[6:8]]]


def _kernel(disp_ref, vol_ref, out_ref, *grad_refs, shape):
    sl, ln = common.SUBLANE, common.LANE
    i = pl.program_id(0)
    yo, zo = out_ref.shape[1:]
    sub = jax.lax.broadcasted_iota(jnp.int32, (sl, ln), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (sl, ln), 1)

    def register(j, carry):
        y0 = pl.multiple_of(j * sl, sl)
        for k in range(zo // ln):
            ix = (pl.ds(y0, sl), pl.ds(k * ln, ln))
            cx = i.astype(jnp.float32) + disp_ref[0, 0, ix[0], ix[1]]
            cy = (y0 + sub).astype(jnp.float32) + disp_ref[1, 0, ix[0], ix[1]]
            cz = (k * ln + lane).astype(jnp.float32) + disp_ref[2, 0, ix[0],
                                                              ix[1]]
            (tx, ty, tz), x, r, v, lz = corner_indices(cx, cy, cz, sub, shape)
            c = _gather_corners(vol_ref, x, r, v, lz)
            # trilinear_sample's lerps, in its order
            c00 = c[0][0][0] * (1 - tx) + c[1][0][0] * tx
            c01 = c[0][0][1] * (1 - tx) + c[1][0][1] * tx
            c10 = c[0][1][0] * (1 - tx) + c[1][1][0] * tx
            c11 = c[0][1][1] * (1 - tx) + c[1][1][1] * tx
            c0 = c00 * (1 - ty) + c10 * ty
            c1 = c01 * (1 - ty) + c11 * ty
            out_ref[0, ix[0], ix[1]] = c0 * (1 - tz) + c1 * tz
            if grad_refs:
                (g_ref,) = grad_refs

                def dx(z):
                    return ((c[1][0][z] - c[0][0][z]) * (1 - ty)
                            + (c[1][1][z] - c[0][1][z]) * ty)

                gx = dx(0) * (1 - tz) + dx(1) * tz
                gy = (c10 - c00) * (1 - tz) + (c11 - c01) * tz
                gz = c1 - c0
                for ch, (gc, cc, n) in enumerate(zip((gx, gy, gz),
                                                     (cx, cy, cz), shape)):
                    g_ref[ch, 0, ix[0], ix[1]] = gc * _clip_slope(cc, n)
        return carry

    jax.lax.fori_loop(0, yo // sl, register, 0)


@functools.partial(jax.jit, static_argnames=("shape", "partials",
                                             "interpret"))
def warp_trilinear_pallas(disp, vol, *, shape, partials, interpret):
    """Planes-layout warp: ``disp (3, X, Yo, Zo)``, ``vol (X, Ys, Zo)``
    (float32, laid out as the module docstring says) -> the warped
    ``(X, Yo, Zo)`` volume, and with ``partials`` also its ``(3, X, Yo, Zo)``
    partials with respect to the sample coordinates.  ``shape`` is the
    moving volume's unpadded ``(X, Y, Z)``.
    """
    _, X, yo, zo = disp.shape
    plane = pl.BlockSpec((1, yo, zo), lambda i: (i, 0, 0))
    chans = pl.BlockSpec((3, 1, yo, zo), lambda i: (0, i, 0, 0))
    out_specs = [plane, chans] if partials else plane
    out_shape = jax.ShapeDtypeStruct((X, yo, zo), jnp.float32)
    if partials:
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((3, X, yo, zo), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_kernel, shape=shape),
        grid=(X,),
        in_specs=[chans,
                  pl.BlockSpec(vol.shape, lambda i: (0, 0, 0),
                               pipeline_mode=pl.Buffered(1))],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=common.compiler_params("parallel"),
        interpret=interpret,
        name="warp_trilinear",
    )(disp, vol)
