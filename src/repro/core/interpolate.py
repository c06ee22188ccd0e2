"""B-spline interpolation — public API and the jnp-level algorithm forms.

The algorithmic forms of paper Eq. (1), mirroring the paper's comparison
matrix (§5), plus a mode dispatcher.  Every form exists here as a pure-jnp
implementation — the *CPU analogs* (the paper's Fig. 7 VT/VV role) and the
reference semantics.  ``separable`` and ``matmul`` also exist in
``repro.kernels`` as Pallas TPU kernels with explicit VMEM tiling
(``kernels.ops.NO_KERNEL`` says why the other forms have none).

Forms
-----
``gather``      thread-per-voxel analog (NiftyReg-TV baseline): every voxel
                gathers its 64 control points and weight-sums them.  Maximal
                redundant data movement — the paper's comparison baseline.
``tt``          thread-per-tile: tile-shared slices of the control grid are
                broadcast over the tile's voxels; 64 FMA accumulation steps.
``ttli``        tt + the trilinear/lerp reformulation (126 ops/voxel vs 255).
``separable``   beyond-paper tensor-contraction form: the per-tile sum is a
                Tucker contraction -> three small matmuls (MXU-friendly),
                ~(4/d + 4/d^2 + 4/d^3) MACs/voxel instead of 64.
``matmul``      Wu & Zou's matrix form: the per-axis ``(d, 4)`` LUTs are
                Kronecker-multiplied once per (tile, dtype) into a
                ``(d^3, 64)`` basis matrix and every tile is one dense
                ``(d^3, 64) @ (64, C)`` product — a single MXU/TensorCore-
                shaped contraction with fp32 accumulation over bf16-friendly
                operands, instead of gathers and elementwise FMAs.

Gradient path
-------------
Every form computes the same *linear* function of the control grid, so they
share one analytic adjoint: the Tucker contraction run in reverse
(``bsi_adjoint_separable``, plus a Pallas kernel in
``repro.kernels.bsi_adjoint``).  ``interpolate(..., grad_impl=)`` selects it:

``xla``     plain autodiff of the chosen forward (the historical behaviour;
            transposes the gather form into a per-voxel scatter-add — the
            maximal-data-movement pattern the paper's §3 design avoids).
``jnp``     ``jax.custom_vjp`` whose backward is the separable-transpose:
            each control point's cotangent is a weighted reduction over its
            own (4·tile)^3 support window — gather-only, three small matmuls.
``pallas``  a VMEM-tiled TPU kernel (``repro.kernels.bsi_adjoint``), the
            exact transpose of the separable forward kernel: x taps fold
            the cotangent planes, then banded MXU matmuls project each
            plane to the control grid.
``matmul``  the transpose of the matmul forward kernel (same module): each
            cotangent plane projects first, the x taps act on the small
            control planes.

Because BSI is linear, the custom VJP stores **no residuals** — the backward
needs only the cotangent, unlike XLA's transpose which re-materialises
whatever intermediates the forward fused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.bspline import basis_matrix, lerp_luts, weight_lut

__all__ = ["bsi_gather", "bsi_tt", "bsi_ttli", "bsi_separable", "bsi_matmul",
           "bsi_adjoint_separable", "bsi_adjoint_matmul", "bsi_adjoint",
           "interpolate", "MODES", "MODE_NAMES", "GRAD_IMPLS"]


def _dims(phi, tile):
    dx, dy, dz = (int(t) for t in tile)
    tx, ty, tz = (int(n) - 3 for n in phi.shape[:3])
    if min(tx, ty, tz) < 1:
        raise ValueError(f"control grid {phi.shape} too small for any tile")
    return (dx, dy, dz), (tx, ty, tz), phi.shape[3]


def bsi_gather(phi, tile, dtype=None):
    """Thread-per-voxel analog: per-voxel 64-point gather + weighted sum."""
    dtype = dtype or phi.dtype
    phi = jnp.asarray(phi, dtype)
    (dx, dy, dz), (tx, ty, tz), _ = _dims(phi, tile)
    wx, wy, wz = (weight_lut(d, dtype) for d in (dx, dy, dz))

    x = jnp.arange(tx * dx)
    y = jnp.arange(ty * dy)
    z = jnp.arange(tz * dz)
    bx, ax = x // dx, x % dx
    by, ay = y // dy, y % dy
    bz, az = z // dz, z % dz

    out = jnp.zeros((tx * dx, ty * dy, tz * dz, phi.shape[3]), dtype)
    for l in range(4):
        for m in range(4):
            for n in range(4):
                g = phi[bx[:, None, None] + l, by[None, :, None] + m, bz[None, None, :] + n]
                w = (
                    wx[ax, l][:, None, None]
                    * wy[ay, m][None, :, None]
                    * wz[az, n][None, None, :]
                )
                out = out + g * w[..., None]
    return out


def bsi_tt(phi, tile, dtype=None):
    """Thread-per-tile form: tile-shared control-point slices, 64 FMA steps."""
    dtype = dtype or phi.dtype
    phi = jnp.asarray(phi, dtype)
    (dx, dy, dz), (tx, ty, tz), c = _dims(phi, tile)
    wx, wy, wz = (weight_lut(d, dtype) for d in (dx, dy, dz))

    out = jnp.zeros((tx, dx, ty, dy, tz, dz, c), dtype)
    for l in range(4):
        for m in range(4):
            for n in range(4):
                sl = phi[l : l + tx, m : m + ty, n : n + tz]  # shared by the whole tile
                w = (
                    wx[:, l][:, None, None] * wy[:, m][None, :, None] * wz[:, n][None, None, :]
                ).reshape(1, dx, 1, dy, 1, dz, 1)
                out = out + sl[:, None, :, None, :, None, :] * w
    return out.reshape(tx * dx, ty * dy, tz * dz, c)


def _lerp(a, b, t):
    return a + t * (b - a)


def bsi_ttli(phi, tile, dtype=None):
    """TT + trilinear/lerp reformulation (paper §3.3, App. B).

    Axis-staged pairwise lerps: 3 lerps collapse the 4 x-neighbours, then y,
    then z — 63 lerps (126 FMA-class ops) per voxel, the same DAG as the
    paper's 8 sub-cubes + 1 final cube regrouping.
    """
    dtype = dtype or phi.dtype
    phi = jnp.asarray(phi, dtype)
    (dx, dy, dz), (tx, ty, tz), c = _dims(phi, tile)
    t0x, t1x, sx = lerp_luts(dx, dtype)
    t0y, t1y, sy = lerp_luts(dy, dtype)
    t0z, t1z, sz = lerp_luts(dz, dtype)

    # x stage: (tx+3, Y, Z, C) -> (tx, dx, Y, Z, C)
    f = [phi[l : l + tx] for l in range(4)]
    r = lambda t: t[None, :, None, None, None]  # broadcast LUT over (tile, a, ...)
    h01 = _lerp(f[0][:, None], f[1][:, None], r(t0x))
    h23 = _lerp(f[2][:, None], f[3][:, None], r(t1x))
    hx = _lerp(h01, h23, r(sx))
    hx = hx.reshape(tx * dx, ty + 3, tz + 3, c)

    # y stage: (X, ty+3, Z, C) -> (X, ty, dy, Z, C)
    f = [hx[:, m : m + ty] for m in range(4)]
    r = lambda t: t[None, None, :, None, None]
    h01 = _lerp(f[0][:, :, None], f[1][:, :, None], r(t0y))
    h23 = _lerp(f[2][:, :, None], f[3][:, :, None], r(t1y))
    hy = _lerp(h01, h23, r(sy))
    hy = hy.reshape(tx * dx, ty * dy, tz + 3, c)

    # z stage
    f = [hy[:, :, n : n + tz] for n in range(4)]
    r = lambda t: t[None, None, None, :, None]
    h01 = _lerp(f[0][:, :, :, None], f[1][:, :, :, None], r(t0z))
    h23 = _lerp(f[2][:, :, :, None], f[3][:, :, :, None], r(t1z))
    hz = _lerp(h01, h23, r(sz))
    return hz.reshape(tx * dx, ty * dy, tz * dz, c)


def bsi_separable(phi, tile, dtype=None):
    """Beyond-paper separable form: three per-axis tensor contractions."""
    dtype = dtype or phi.dtype
    phi = jnp.asarray(phi, dtype)
    (dx, dy, dz), (tx, ty, tz), c = _dims(phi, tile)
    wx, wy, wz = (weight_lut(d, dtype) for d in (dx, dy, dz))

    # x sweep: out[t, a, ...] = sum_l Wx[a, l] * phi[t + l, ...]
    px = jnp.stack([phi[l : l + tx] for l in range(4)])  # (4, tx, Y, Z, C)
    hx = jnp.einsum("al,ltyzc->tayzc", wx, px).reshape(tx * dx, ty + 3, tz + 3, c)
    py = jnp.stack([hx[:, m : m + ty] for m in range(4)])  # (4, X, ty, Z, C)
    hy = jnp.einsum("bm,mxtzc->xtbzc", wy, py).reshape(tx * dx, ty * dy, tz + 3, c)
    pz = jnp.stack([hy[:, :, n : n + tz] for n in range(4)])  # (4, X, Y, tz, C)
    hz = jnp.einsum("cn,nxytk->xytck", wz, pz)
    return hz.reshape(tx * dx, ty * dy, tz * dz, c)


def bsi_matmul(phi, tile, dtype=None):
    """Matrix form (Wu & Zou): one ``(d^3, 64) @ (64, C)`` matmul per tile.

    The 64 shifted views of the control grid become the per-tile column
    matrix; the precomputed Kronecker basis (:func:`~repro.core.bspline.
    basis_matrix`) contracts them in a single MXU-shaped ``dot_general``
    with fp32 accumulation (``preferred_element_type``) — bf16 operands
    stay bf16 in memory, products accumulate in fp32.
    """
    dtype = dtype or phi.dtype
    phi = jnp.asarray(phi, dtype)
    (dx, dy, dz), (tx, ty, tz), c = _dims(phi, tile)
    b = basis_matrix((dx, dy, dz), dtype)  # (d^3, 64)

    win = jnp.stack([
        phi[l : l + tx, m : m + ty, n : n + tz]
        for l in range(4) for m in range(4) for n in range(4)
    ], axis=3)  # (tx, ty, tz, 64, C)
    h = jax.lax.dot_general(b, win, (((1,), (3,)), ((), ())),
                            preferred_element_type=jnp.float32)
    h = h.astype(dtype).reshape(dx, dy, dz, tx, ty, tz, c)
    h = h.transpose(3, 0, 4, 1, 5, 2, 6)
    return h.reshape(tx * dx, ty * dy, tz * dz, c)


MODES = {
    "gather": bsi_gather,
    "tt": bsi_tt,
    "ttli": bsi_ttli,
    "separable": bsi_separable,
    "matmul": bsi_matmul,
}

# The canonical mode-name set.  Every other layer that validates or
# enumerates modes (options validation, the autotuner's candidate list,
# benchmarks) derives from this tuple — do not restate the names elsewhere.
MODE_NAMES = tuple(sorted(MODES))

# Adjoint implementations for the custom-VJP gradient path: "xla" is plain
# autodiff of the forward (no custom VJP), the others are analytic adjoints —
# the separable transpose as jnp ("jnp") / as the Pallas kernel ("pallas"),
# and the transposed-matmul Pallas kernel ("matmul").
GRAD_IMPLS = ("xla", "jnp", "pallas", "matmul")


def bsi_adjoint_separable(g, tile, dtype=None):
    """Transpose of Eq. (1): dense-field cotangent -> control-grid cotangent.

    The Tucker contraction of :func:`bsi_separable` run in reverse: each axis
    sweep contracts the per-tile voxel axis against the ``(d, 4)`` weight LUT
    (one small MXU-friendly matmul) and overlap-adds the four shifted bands —
    every control point's gradient is a weighted *reduction* over its own
    ``(4*d)^3`` support window, never a scatter.  Sweeps run in reverse axis
    order (z, y, x) so intermediates shrink as early as possible.

    Args:
      g: ``(Tx*dx, Ty*dy, Tz*dz, C)`` cotangent of the dense field.
      tile: ``(dx, dy, dz)`` control-point spacing in voxels.
      dtype: accumulation/output dtype; defaults to float32 (promoted with
        ``g.dtype``) so bf16-compute forwards still accumulate in fp32.

    Returns:
      ``(Tx+3, Ty+3, Tz+3, C)`` control-grid cotangent.
    """
    dtype = dtype or jnp.promote_types(g.dtype, jnp.float32)
    dx, dy, dz = (int(t) for t in tile)
    X, Y, Z, c = g.shape
    if X % dx or Y % dy or Z % dz:
        raise ValueError(f"cotangent shape {g.shape} not a multiple of {tile}")
    tx, ty, tz = X // dx, Y // dy, Z // dz
    g = jnp.asarray(g, dtype)
    wx, wy, wz = (weight_lut(d, dtype) for d in (dx, dy, dz))

    # z sweep: (X, Y, tz*dz, C) -> (X, Y, tz+3, C).  c[t, n] = sum_a W[a, n]
    # * g[t*dz + a]; band n of the result lands at control index t + n.
    u = g.reshape(X, Y, tz, dz, c)
    cz = jnp.einsum("an,xytac->nxytc", wz, u)
    hz = sum(jnp.pad(cz[n], ((0, 0), (0, 0), (n, 3 - n), (0, 0)))
             for n in range(4))
    # y sweep
    u = hz.reshape(X, ty, dy, tz + 3, c)
    cy = jnp.einsum("am,xtazc->mxtzc", wy, u)
    hy = sum(jnp.pad(cy[m], ((0, 0), (m, 3 - m), (0, 0), (0, 0)))
             for m in range(4))
    # x sweep
    u = hy.reshape(tx, dx, ty + 3, tz + 3, c)
    cx = jnp.einsum("al,tayzc->ltyzc", wx, u)
    return sum(jnp.pad(cx[l], ((l, 3 - l), (0, 0), (0, 0), (0, 0)))
               for l in range(4))


def bsi_adjoint_matmul(g, tile, dtype=None):
    """Transposed matrix form of :func:`bsi_matmul` (jnp reference).

    ``c4[t, k] = sum_v B[v, k] * g[t, v]`` — one ``(64, d^3) @ (d^3, T*C)``
    contraction per call — followed by the 64-band shifted overlap-add that
    scatters tile ``t``'s offset-``(l, m, n)`` band onto control point
    ``t + (l, m, n)``.  Same signature and semantics as
    :func:`bsi_adjoint_separable`; a Pallas kernel of the same contraction
    lives in ``repro.kernels.bsi_adjoint`` (``grad_impl="matmul"``).
    """
    dtype = dtype or jnp.promote_types(g.dtype, jnp.float32)
    dx, dy, dz = (int(t) for t in tile)
    X, Y, Z, c = g.shape
    if X % dx or Y % dy or Z % dz:
        raise ValueError(f"cotangent shape {g.shape} not a multiple of {tile}")
    tx, ty, tz = X // dx, Y // dy, Z // dz
    g = jnp.asarray(g, dtype)
    b = basis_matrix((dx, dy, dz), dtype)  # (d^3, 64)

    u = g.reshape(tx, dx, ty, dy, tz, dz, c).transpose(0, 2, 4, 1, 3, 5, 6)
    u = u.reshape(tx, ty, tz, dx * dy * dz, c)
    c4 = jax.lax.dot_general(b, u, (((0,), (3,)), ((), ())),
                             preferred_element_type=jnp.float32)
    c4 = c4.astype(dtype).reshape(4, 4, 4, tx, ty, tz, c)
    return sum(
        jnp.pad(c4[l, m, n], ((l, 3 - l), (m, 3 - m), (n, 3 - n), (0, 0)))
        for l in range(4) for m in range(4) for n in range(4))


@functools.partial(jax.jit, static_argnames=("tile", "impl", "dtype_name"))
def _adjoint_jit(g, tile, impl, dtype_name):
    dtype = jnp.dtype(dtype_name) if dtype_name else None
    if impl == "jnp":
        return bsi_adjoint_separable(g, tile, dtype)
    if impl in ("pallas", "matmul"):
        from repro.kernels import ops  # local import: kernels import this module

        form = "separable" if impl == "pallas" else "matmul"
        return ops.bsi_adjoint_pallas(g, tile, dtype=dtype, form=form)
    raise ValueError(f"unknown adjoint impl {impl!r}")


def bsi_adjoint(g, tile, *, impl="jnp", dtype=None):
    """Dispatch the analytic BSI adjoint (see :func:`bsi_adjoint_separable`).

    ``impl``: ``jnp`` (reference separable-transpose), ``pallas`` (the
    VMEM-tiled separable-transpose kernel in ``repro.kernels.bsi_adjoint``)
    or ``matmul`` (the transposed-matmul kernel in the same module).
    """
    name = jnp.dtype(dtype).name if dtype is not None else None
    return _adjoint_jit(g, tuple(int(t) for t in tile), impl, name)


@functools.partial(jax.jit, static_argnames=("tile", "mode", "impl", "dtype_name"))
def _interpolate_jit(phi, tile, mode, impl, dtype_name):
    dtype = jnp.dtype(dtype_name) if dtype_name else None
    if impl == "jnp":
        return MODES[mode](phi, tile, dtype)
    if impl == "pallas":
        from repro.kernels import ops  # local import: kernels import this module

        return ops.bsi_pallas(phi, tile, mode=mode, dtype=dtype)
    raise ValueError(f"unknown impl {impl!r}")


@functools.lru_cache(maxsize=None)
def _custom_vjp_interp(tile, mode, impl, grad_impl, dtype_name, in_dtype_name):
    """Build (and cache) the custom-VJP interpolation for one configuration.

    BSI is linear in ``phi``, so the VJP needs no residuals: the backward is
    the analytic adjoint applied to the cotangent alone, accumulated in fp32
    and cast back to the primal dtype (fp32 params keep fp32 gradients even
    when the forward computes in bf16).
    """

    @jax.custom_vjp
    def f(phi):
        return _interpolate_jit(phi, tile, mode, impl, dtype_name)

    def fwd(phi):
        return f(phi), None

    def bwd(_, g):
        dphi = _adjoint_jit(g, tile, grad_impl, None)
        return (dphi.astype(in_dtype_name),)

    f.defvjp(fwd, bwd)
    return f


def interpolate(phi, tile, *, mode="separable", impl="jnp", dtype=None,
                grad_impl="xla"):
    """Interpolate a control grid to a dense field.

    Args:
      phi: ``(Tx+3, Ty+3, Tz+3, C)`` control grid (aligned, +1 offset).
      tile: ``(dx, dy, dz)`` control-point spacing in voxels.
      mode: one of ``MODE_NAMES`` (``gather | matmul | separable | tt |
        ttli``).
      impl: ``jnp`` (XLA-fused reference forms) or ``pallas`` (TPU kernels
        for ``separable`` / ``matmul``; the Pallas interpreter off-TPU).
      dtype: optional compute dtype (e.g. ``bfloat16``); the output takes
        this dtype, gradients stay in ``phi.dtype``.
      grad_impl: how this call differentiates (module docstring, "Gradient
        path"): ``xla`` = plain autodiff of the forward, ``jnp`` / ``pallas``
        = ``jax.custom_vjp`` with the analytic gather-only adjoint.  With a
        non-``xla`` choice the Pallas forward kernels become differentiable.
    Returns:
      ``(Tx*dx, Ty*dy, Tz*dz, C)`` dense field.
    """
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; choose from {MODE_NAMES}")
    if grad_impl not in GRAD_IMPLS:
        raise ValueError(
            f"unknown grad_impl {grad_impl!r}; choose from {GRAD_IMPLS}")
    name = jnp.dtype(dtype).name if dtype is not None else None
    tile = tuple(int(t) for t in tile)
    if grad_impl == "xla":
        return _interpolate_jit(phi, tile, mode, impl, name)
    f = _custom_vjp_interp(tile, mode, impl, grad_impl, name,
                           jnp.dtype(phi.dtype).name)
    return f(phi)
