"""Fused level-step Pallas kernel: BSI + warp + similarity in one VMEM pass.

Runs under the Pallas interpreter only: its warp is a 3-D gather from the
VMEM moving volume, which Mosaic does not lower on a TPU, so
``kernels.ops.fused_supported`` refuses it there.

The paper's thesis is that B-spline interpolation is memory-bound — wins come
from "minimizing the data that needs to be moved between memory and
processing cores".  The unfused level step moves a lot: it writes the dense
``(X, Y, Z, 3)`` displacement field to HBM, reads it back to warp, writes the
``(X, Y, Z)`` warped volume, and reads *that* back for the similarity
reduction.  This kernel does all three stages per tile-block while the data
is still in VMEM:

* the control grid is pinned in VMEM (one HBM load total, as in the forward
  kernels) and each Pallas grid cell evaluates its block's displacement with
  three per-axis LUT sweeps (``core.interpolate.bsi_separable``'s form);
* the moving and fixed volumes are pinned in VMEM too, so the warp is a
  VMEM gather at ``identity + displacement`` (clamped trilinear — exactly
  ``core.ffd.warp_volume``'s sampling);
* the similarity is accumulated as *partial sums* into one tiny output block
  shared by every grid cell (TPU grids execute sequentially, so first-cell
  init + accumulate is the standard Pallas reduction pattern): SSD / NCC
  moments, LNCC windowed moments via in-register box sums, and NMI as a
  fused Parzen joint-histogram — per block only a ``(block_voxels, bins)``
  temporary ever exists, never the ``(X*Y*Z, bins)`` HBM intermediate.

The dense field and the warped volume therefore never exist in HBM.  The
host-side combination of the partial sums into the scalar loss lives in
``kernels.ops.fused_similarity_loss``; the differentiable wrapper (custom
VJP via the analytic gather adjoint) is ``core.ffd.fused_warp_loss``.

Reductions run in two passes when the similarity needs global statistics of
the warped volume (NCC: its mean; NMI: its min/max for intensity
normalisation): pass one is the ``("stats",)`` variant below, pass two
consumes the resulting scalars.  Statistics of the *fixed* volume need no
kernel — fixed is a real HBM input, plain ``jnp`` reductions are already
single-pass.

Edge voxels: the dispatcher zero-pads the control grid and both volumes up
to whole blocks; out-of-volume voxels are masked out of every partial sum
(and LNCC masks to its VALID-window output positions), so padding never
changes the result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


__all__ = ["bsi_fused_pallas", "fused_out_shape", "SCALAR_LANES"]

# Width of the (1, SCALAR_LANES) rows used for scalar partial sums and for
# the host->kernel statistics operand (mean / min-max of the warped volume).
SCALAR_LANES = 8


def _whole(shape):
    """BlockSpec handing every grid cell the whole array."""
    nd = len(shape)
    return pl.BlockSpec(shape, lambda i, j, k: (0,) * nd)


def kron_basis(wx, wy, wz):
    """The ``(dx*dy*dz, 64)`` Kronecker basis of the three per-axis LUTs
    (in-kernel twin of ``repro.core.bspline.basis_matrix``)."""
    dx, dy, dz = wx.shape[0], wy.shape[0], wz.shape[0]
    b = (wx.reshape(dx, 1, 1, 4, 1, 1)
         * wy.reshape(1, dy, 1, 1, 4, 1)
         * wz.reshape(1, 1, dz, 1, 1, 4))
    return b.reshape(dx * dy * dz, 64)


def contract_window(win, b, tile, block_tiles):
    """Evaluate a ``(bx+3, by+3, bz+3, C)`` halo window as one contraction
    against the basis; returns the fp32 ``(bx*dx, by*dy, bz*dz, C)`` block."""
    dx, dy, dz = tile
    bx, by, bz = block_tiles
    c = win.shape[-1]
    cols = jnp.stack([
        win[l : l + bx, m : m + by, n : n + bz].reshape(-1)
        for l in range(4) for m in range(4) for n in range(4)
    ])  # (64, bx*by*bz*C)
    h = jax.lax.dot_general(
        b, cols, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (dx*dy*dz, bx*by*bz*C)
    h = h.reshape(dx, dy, dz, bx, by, bz, c)
    h = h.transpose(3, 0, 4, 1, 5, 2, 6)
    return h.reshape(bx * dx, by * dy, bz * dz, c)


def fused_out_shape(sim):
    """Partial-sum output shape for similarity spec ``sim`` (see ops)."""
    if sim[0] == "nmi":
        bins = int(sim[1])
        return (bins, bins)
    return (1, SCALAR_LANES)


def _disp_block(phi_ref, wx, wy, wz, *, tile, block_tiles, extra,
                form="separable"):
    """This cell's displacement block via the selected BSI contraction.

    ``form="separable"`` runs three per-axis LUT sweeps;
    ``form="matmul"`` runs one contraction against
    the Kronecker basis (built in-kernel from the same three LUT refs — tiny
    at ``64 * d^3`` elements).  Either way the block is *extended* by
    ``extra`` tiles per axis (LNCC's window halo; zero elsewhere).  Returns
    float32 ``((bx+ex)*dx, (by+ey)*dy, (bz+ez)*dz, C)``.
    """
    dx, dy, dz = tile
    bx0, by0, bz0 = block_tiles
    bx, by, bz = (b + e for b, e in zip(block_tiles, extra))
    c = phi_ref.shape[-1]
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)
    win = phi_ref[pl.ds(i * bx0, bx + 3), pl.ds(j * by0, by + 3),
                  pl.ds(k * bz0, bz + 3), :]
    if form == "matmul":
        return contract_window(win, kron_basis(wx, wy, wz), tile,
                               (bx, by, bz))
    px = jnp.stack([win[l: l + bx] for l in range(4)])
    h = jax.lax.dot_general(
        wx, px.reshape(4, -1), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(dx, bx, by + 3, bz + 3, c)
    h = jnp.moveaxis(h, 0, 1).reshape(bx * dx, by + 3, bz + 3, c)
    py = jnp.stack([h[:, m: m + by] for m in range(4)])
    h = jax.lax.dot_general(
        wy, py.reshape(4, -1), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(dy, bx * dx, by, bz + 3, c)
    h = jnp.moveaxis(h, 0, 2).reshape(bx * dx, by * dy, bz + 3, c)
    pz = jnp.stack([h[:, :, n: n + bz] for n in range(4)])
    h = jax.lax.dot_general(
        wz, pz.reshape(4, -1), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(dz, bx * dx, by * dy, bz, c)
    return jnp.moveaxis(h, 0, 3).reshape(bx * dx, by * dy, bz * dz, c)


def _warp_block(mov_ref, disp, *, base, vol_shape):
    """Trilinear-sample the VMEM moving volume at identity + displacement.

    Mirrors ``core.ffd.trilinear_sample``/``warp_volume``: fp32 coordinates,
    clamp-to-border, intensities in the moving volume's (compute) dtype with
    the lerp promoting to fp32.  Returns float32 ``(BX, BY, BZ)``.
    """
    X, Y, Z = vol_shape
    shape3 = disp.shape[:3]
    gx = jax.lax.broadcasted_iota(jnp.float32, shape3, 0) + base[0]
    gy = jax.lax.broadcasted_iota(jnp.float32, shape3, 1) + base[1]
    gz = jax.lax.broadcasted_iota(jnp.float32, shape3, 2) + base[2]
    cx = jnp.clip(gx + disp[..., 0], 0.0, X - 1.0)
    cy = jnp.clip(gy + disp[..., 1], 0.0, Y - 1.0)
    cz = jnp.clip(gz + disp[..., 2], 0.0, Z - 1.0)
    fx, fy, fz = jnp.floor(cx), jnp.floor(cy), jnp.floor(cz)
    tx, ty, tz = cx - fx, cy - fy, cz - fz
    x0 = fx.astype(jnp.int32)
    y0 = fy.astype(jnp.int32)
    z0 = fz.astype(jnp.int32)
    x1 = jnp.minimum(x0 + 1, X - 1)
    y1 = jnp.minimum(y0 + 1, Y - 1)
    z1 = jnp.minimum(z0 + 1, Z - 1)
    mov = mov_ref[...]
    c00 = mov[x0, y0, z0] * (1 - tx) + mov[x1, y0, z0] * tx
    c01 = mov[x0, y0, z1] * (1 - tx) + mov[x1, y0, z1] * tx
    c10 = mov[x0, y1, z0] * (1 - tx) + mov[x1, y1, z0] * tx
    c11 = mov[x0, y1, z1] * (1 - tx) + mov[x1, y1, z1] * tx
    c0 = c00 * (1 - ty) + c10 * ty
    c1 = c01 * (1 - ty) + c11 * ty
    return (c0 * (1 - tz) + c1 * tz).astype(jnp.float32)


def _box_sum(x, size):
    """VALID box *sum* over all three axes (LNCC's windowed moments)."""
    for ax in range(3):
        n = x.shape[ax] - size + 1
        acc = jax.lax.slice_in_dim(x, 0, n, axis=ax)
        for a in range(1, size):
            acc = acc + jax.lax.slice_in_dim(x, a, a + n, axis=ax)
        x = acc
    return x


def _scalar_row(*vals):
    """Pack partial-sum scalars into one (1, SCALAR_LANES) row."""
    row = list(vals) + [jnp.float32(0.0)] * (SCALAR_LANES - len(vals))
    return jnp.stack(row).reshape(1, SCALAR_LANES)


def _fused_kernel(wx_ref, wy_ref, wz_ref, sc_ref, phi_ref, mov_ref, fix_ref,
                  out_ref, *, tile, block_tiles, extra, vol_shape, sim,
                  disp_form="separable"):
    X, Y, Z = vol_shape
    dx, dy, dz = tile
    first = ((pl.program_id(0) == 0) & (pl.program_id(1) == 0)
             & (pl.program_id(2) == 0))
    base = (pl.program_id(0) * (block_tiles[0] * dx),
            pl.program_id(1) * (block_tiles[1] * dy),
            pl.program_id(2) * (block_tiles[2] * dz))

    h = _disp_block(phi_ref, wx_ref[...], wy_ref[...], wz_ref[...],
                    tile=tile, block_tiles=block_tiles, extra=extra,
                    form=disp_form)
    # quantise to the compute dtype (what the unfused path stores to HBM),
    # then sample with fp32 coordinates exactly as warp_volume does
    disp = h.astype(phi_ref.dtype).astype(jnp.float32)
    w = _warp_block(mov_ref, disp, base=base, vol_shape=vol_shape)

    shape3 = w.shape
    ix = jax.lax.broadcasted_iota(jnp.int32, shape3, 0) + base[0]
    iy = jax.lax.broadcasted_iota(jnp.int32, shape3, 1) + base[1]
    iz = jax.lax.broadcasted_iota(jnp.int32, shape3, 2) + base[2]
    valid = (ix < X) & (iy < Y) & (iz < Z)
    fb = fix_ref[pl.ds(base[0], shape3[0]), pl.ds(base[1], shape3[1]),
                 pl.ds(base[2], shape3[2])].astype(jnp.float32)

    kind = sim[0]
    if kind == "stats":
        part = _scalar_row(
            jnp.sum(jnp.where(valid, w, 0.0)),
            jnp.min(jnp.where(valid, w, jnp.inf)),
            jnp.max(jnp.where(valid, w, -jnp.inf)),
            jnp.sum(valid.astype(jnp.float32)),
        )

        @pl.when(first)
        def _():
            out_ref[...] = _scalar_row(
                jnp.float32(0.0), jnp.inf, -jnp.inf, jnp.float32(0.0))

        cur = out_ref[...]
        out_ref[...] = jnp.concatenate(
            [cur[:, 0:1] + part[:, 0:1],
             jnp.minimum(cur[:, 1:2], part[:, 1:2]),
             jnp.maximum(cur[:, 2:3], part[:, 2:3]),
             cur[:, 3:] + part[:, 3:]], axis=1)
        return

    if kind == "ssd":
        d2 = jnp.where(valid, (w - fb) ** 2, 0.0)
        part = _scalar_row(jnp.sum(d2), jnp.sum(valid.astype(jnp.float32)))
    elif kind == "ncc":
        mu_w = sc_ref[0, 0]
        mu_f = sc_ref[0, 1]
        a = jnp.where(valid, w - mu_w, 0.0)
        b = jnp.where(valid, fb - mu_f, 0.0)
        part = _scalar_row(jnp.sum(a * b), jnp.sum(a * a), jnp.sum(b * b))
    elif kind == "lncc":
        _, size, eps = sim
        inv = 1.0 / float(size) ** 3
        mu_w = _box_sum(w, size) * inv
        mu_f = _box_sum(fb, size) * inv
        var_w = _box_sum(w * w, size) * inv - mu_w**2
        var_f = _box_sum(fb * fb, size) * inv - mu_f**2
        cross = _box_sum(w * fb, size) * inv - mu_w * mu_f
        cc = cross**2 / (var_w * var_f + eps)
        # own positions [0, block) of this cell that are VALID-window
        # positions of the true volume; the halo recompute region and the
        # zero-padding contribute nothing
        rshape = cc.shape
        px = jax.lax.broadcasted_iota(jnp.int32, rshape, 0)
        py = jax.lax.broadcasted_iota(jnp.int32, rshape, 1)
        pz = jax.lax.broadcasted_iota(jnp.int32, rshape, 2)
        own = ((px < block_tiles[0] * dx) & (py < block_tiles[1] * dy)
               & (pz < block_tiles[2] * dz))
        own &= ((px + base[0] < X - size + 1) & (py + base[1] < Y - size + 1)
                & (pz + base[2] < Z - size + 1))
        cc = jnp.where(own, cc, 0.0)
        part = _scalar_row(jnp.sum(cc), jnp.sum(own.astype(jnp.float32)))
    elif kind == "nmi":
        _, bins, sigma_ratio, eps = sim
        lo_w, hi_w = sc_ref[0, 0], sc_ref[0, 1]
        lo_f, hi_f = sc_ref[0, 2], sc_ref[0, 3]
        an = ((w - lo_w) / jnp.maximum(hi_w - lo_w, 1e-8)).reshape(-1)
        bn = ((fb - lo_f) / jnp.maximum(hi_f - lo_f, 1e-8)).reshape(-1)
        centres = jnp.linspace(0.0, 1.0, bins, dtype=jnp.float32)
        sigma = sigma_ratio / (bins - 1)
        wa = jnp.exp(-0.5 * ((an[:, None] - centres[None, :]) / sigma) ** 2)
        wb = jnp.exp(-0.5 * ((bn[:, None] - centres[None, :]) / sigma) ** 2)
        wa = wa / (jnp.sum(wa, axis=1, keepdims=True) + eps)
        wb = wb / (jnp.sum(wb, axis=1, keepdims=True) + eps)
        wa = wa * valid.reshape(-1)[:, None]  # padding voxels: zero rows
        part = jax.lax.dot_general(  # (V, bins) x (V, bins) -> (bins, bins)
            wa, wb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:  # pragma: no cover - dispatcher validates
        raise ValueError(f"no fused accumulator for similarity {kind!r}")

    @pl.when(first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += part


@functools.partial(jax.jit, static_argnames=(
    "tile", "block_tiles", "extra", "vol_shape", "sim", "interpret",
    "disp_form"))
def bsi_fused_pallas(phi, mov, fix, wx, wy, wz, scalars, *, tile, block_tiles,
                     extra, vol_shape, sim, interpret,
                     disp_form="separable"):
    """Run the fused level-step kernel; returns the partial-sum block.

    ``phi``/``mov``/``fix`` arrive pre-padded to whole (extended) blocks from
    ``kernels.ops``; ``scalars`` is the ``(1, SCALAR_LANES)`` statistics row
    (zeros when ``sim`` needs none); ``sim`` is a similarity spec tuple
    (``("stats",) | ("ssd",) | ("ncc",) | ("lncc", size, eps) |
    ("nmi", bins, sigma_ratio, eps)``); ``disp_form`` picks the BSI
    contraction of the displacement stage (see :func:`_disp_block`).
    """
    bx, by, bz = block_tiles
    ex, ey, ez = extra
    dx, dy, dz = tile
    grid = ((phi.shape[0] - 3 - ex) // bx, (phi.shape[1] - 3 - ey) // by,
            (phi.shape[2] - 3 - ez) // bz)
    assert mov.shape == tuple(
        g * b * d + e * d
        for g, b, e, d in zip(grid, block_tiles, extra, tile)), (
            mov.shape, grid, block_tiles, extra, tile)
    out_shape = fused_out_shape(sim)
    return pl.pallas_call(
        functools.partial(_fused_kernel, tile=tile, block_tiles=block_tiles,
                          extra=extra, vol_shape=vol_shape, sim=sim,
                          disp_form=disp_form),
        grid=grid,
        in_specs=[
            _whole(wx.shape),
            _whole(wy.shape),
            _whole(wz.shape),
            _whole(scalars.shape),
            _whole(phi.shape),
            _whole(mov.shape),
            _whole(fix.shape),
        ],
        out_specs=pl.BlockSpec(out_shape, lambda i, j, k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
    )(wx, wy, wz, scalars, phi, mov, fix)
