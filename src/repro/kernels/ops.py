"""Jit'd dispatch wrappers for the BSI Pallas kernels.

Handles the plumbing the kernels don't: the channel-first, ``(8, 128)``-padded
plane layout of ``kernels.common`` (one XLA transpose+pad in, one slice+
transpose out), the banded y/z expansion matrices, padding the x tile count
up to whole blocks (padded control planes never reach the cropped output),
and the x block size, picked so a grid cell's VMEM blocks fit
``common.VMEM_BUDGET_BYTES``.

``interpret`` is never a caller's choice: kernels compile on a TPU backend
and run under the Pallas interpreter everywhere else (:func:`default_interpret`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.bspline import weight_lut
from repro.kernels import common
from repro.kernels.bsi_adjoint import bsi_adjoint_pallas_planes
from repro.kernels.bsi_fused import SCALAR_LANES, bsi_fused_pallas
from repro.kernels.bsi_matmul import bsi_matmul_pallas
from repro.kernels.bsi_separable import bsi_separable_pallas
from repro.kernels.warp import (ROW_PAD, corner_indices, stored_rows,
                                visit_counts, warp_trilinear_pallas)

__all__ = ["PALLAS_MODES", "NO_KERNEL", "FUSED_SIM_KINDS", "FUSED_NO_TPU",
           "bsi_pallas", "bsi_adjoint_pallas", "fused_similarity_loss",
           "fused_supported", "default_interpret", "pick_block_tiles",
           "pick_block_ctrl", "warp_supported", "warp_vmem_bytes",
           "warp_trilinear", "warp_tile_visits"]

# Modes with a Pallas kernel.  The engine autotuner enumerates its Pallas
# candidates from this; every other mode runs as its jnp form only.
PALLAS_MODES = ("separable", "matmul")
# Why the other modes have no kernel.
NO_KERNEL = {
    "gather": "the per-voxel gather is the baseline the kernels beat",
    "tt": ("the 64-term weighted sum interleaves tile and voxel offsets on "
           "every axis, a lane shuffle Mosaic does not lower on a TPU; the "
           "separable and matmul kernels fold that interleave into banded "
           "MXU matmuls instead"),
    "ttli": ("the lerp form interleaves tile and voxel offsets on every "
             "axis, a lane shuffle Mosaic does not lower on a TPU; the "
             "separable and matmul kernels fold that interleave into banded "
             "MXU matmuls instead"),
}

# Budget for the fused kernel's VMEM-pinned volumes (see fused_supported).
_FUSED_VMEM_BUDGET_BYTES = 12 * 2**20
_DEFAULT_BLOCK_TILES = (4, 4, 4)  # cubes maximise halo overlap (paper §3.4)
_MAX_X_BLOCK = 8  # x tiles per grid cell of the BSI kernels, at most


def default_interpret() -> bool:
    """Whether the kernels run under the Pallas interpreter here.

    Pallas TPU kernels compile only on a TPU backend; everywhere else (CPU
    CI, GPU hosts) they run under the interpreter.  Every dispatcher resolves
    ``interpret`` from this, so no kernel runs interpreted on a TPU.
    """
    return jax.default_backend() != "tpu"


def _planes(num_tiles, tile):
    """Padded plane extents ``(Y, Z, Ny, Nz)``: dense and control."""
    (_, ty, tz), (_, dy, dz) = num_tiles, tile
    return (common.round_up(ty * dy, common.SUBLANE),
            common.round_up(tz * dz, common.LANE),
            common.round_up(ty + 3, common.SUBLANE),
            common.round_up(tz + 3, common.LANE))


def _block_bytes(bt, num_tiles, tile, itemsize, *, adjoint):
    """VMEM a grid cell of the forward (or adjoint) kernels plans for.

    Double-buffered dense block and resident control block, the two band
    matrices, the ``bt + 3``-plane scratch window and a few plane-sized
    temporaries; every slab counted after ``(8, 128)`` tiling.
    """
    y, z, ny, nz = _planes(num_tiles, tile)
    dx = tile[0]
    nxp = -(-num_tiles[0] // bt) * bt + 3
    dense = common.plane_bytes(y, z)
    ctrl = common.plane_bytes(ny, nz)
    mats = 2 * (common.plane_bytes(y, ny) + common.plane_bytes(nz, z))
    io = (2 * bt * dx * common.plane_bytes(y, z, itemsize)
          + 2 * nxp * (ctrl if adjoint
                       else common.plane_bytes(ny, nz, itemsize)))
    return io + mats + (bt + 3) * dense + 6 * dense + 8 * ctrl


def pick_block_tiles(num_tiles, tile, itemsize=4,
                     budget=common.VMEM_BUDGET_BYTES, *, adjoint=False):
    """x tiles per grid cell: the largest (up to 8) whose VMEM fits ``budget``.

    Never below 1: a grid whose resident planes alone overflow VMEM is left
    to the compiler, which refuses it with an out-of-memory error.
    """
    for bt in range(min(_MAX_X_BLOCK, int(num_tiles[0])), 0, -1):
        if _block_bytes(bt, num_tiles, tile, itemsize,
                        adjoint=adjoint) <= budget:
            return bt
    return 1


def pick_block_ctrl(num_tiles, tile, itemsize=4,
                    budget=common.VMEM_BUDGET_BYTES):
    """x tiles per grid cell of the adjoint kernels (see pick_block_tiles)."""
    return pick_block_tiles(num_tiles, tile, itemsize, budget, adjoint=True)


def bsi_pallas(phi, tile, *, mode="separable", dtype=None, block_tiles=None):
    """Run one of the BSI Pallas kernels on a stored control grid.

    Args match ``repro.core.interpolate.interpolate``; ``mode`` selects the
    kernel (``separable`` | ``matmul``; :data:`NO_KERNEL` says why the other
    modes have none).  ``block_tiles`` overrides the x tiles per grid cell.
    """
    return _bsi_pallas_jit(phi, tuple(int(t) for t in tile), mode=mode,
                           dtype=dtype, block_tiles=block_tiles,
                           interpret=default_interpret())


@functools.partial(
    jax.jit, static_argnames=("tile", "mode", "dtype", "block_tiles", "interpret")
)
def _bsi_pallas_jit(phi, tile, *, mode, dtype, block_tiles, interpret):
    if mode not in PALLAS_MODES:
        raise ValueError(f"no Pallas kernel for mode {mode!r}: "
                         f"{NO_KERNEL.get(mode, 'unknown mode')}")
    if dtype is not None:
        phi = phi.astype(dtype)
    num_tiles = tuple(int(n) - 3 for n in phi.shape[:3])
    (tx, ty, tz), (dx, dy, dz) = num_tiles, tile
    y, z, ny, nz = _planes(num_tiles, tile)
    bt = block_tiles or pick_block_tiles(num_tiles, tile, phi.dtype.itemsize)
    bt = min(int(bt), tx)
    nb = -(-tx // bt)
    p = jnp.pad(jnp.transpose(phi, (3, 0, 1, 2)),
                ((0, 0), (0, nb * bt - tx), (0, ny - ty - 3), (0, nz - tz - 3)))
    name = phi.dtype.name
    ay = jnp.asarray(common.band_matrix(ty, dy, y, ny, name))
    azt = jnp.asarray(common.band_matrix(tz, dz, z, nz, name).T)
    kern = bsi_separable_pallas if mode == "separable" else bsi_matmul_pallas
    out = kern(p, ay, azt, dx=dx, bt=bt, interpret=interpret)
    return jnp.transpose(out[:, : tx * dx, : ty * dy, : tz * dz], (1, 2, 3, 0))


def bsi_adjoint_pallas(g, tile, *, dtype=None, block_tiles=None,
                       form="separable"):
    """Run the Pallas BSI adjoint: dense cotangent -> control-grid cotangent.

    The transpose of :func:`bsi_pallas` (same answer for every forward mode —
    BSI is linear, all modes compute the same function).  ``g`` is the
    ``(Tx*dx, Ty*dy, Tz*dz, C)`` cotangent of the dense field; returns the
    ``(Tx+3, Ty+3, Tz+3, C)`` control-grid cotangent in ``dtype`` (default
    float32 — fp32 accumulation even for bf16 cotangents).  ``form`` picks
    the kernel: ``separable`` (``grad_impl="pallas"``) or ``matmul``
    (``grad_impl="matmul"``), see ``kernels.bsi_adjoint``.
    """
    return _bsi_adjoint_jit(g, tuple(int(t) for t in tile), dtype=dtype,
                            block_tiles=block_tiles, form=form,
                            interpret=default_interpret())


@functools.partial(
    jax.jit, static_argnames=("tile", "dtype", "block_tiles", "form",
                              "interpret"))
def _bsi_adjoint_jit(g, tile, *, dtype, block_tiles, form, interpret):
    if form not in ("separable", "matmul"):
        raise ValueError(f"unknown adjoint form {form!r}")
    out_dtype = jnp.dtype(dtype) if dtype is not None else jnp.float32
    dx, dy, dz = tile
    X, Y, Z, _ = g.shape
    if X % dx or Y % dy or Z % dz:
        raise ValueError(f"cotangent shape {g.shape} not a multiple of {tile}")
    num_tiles = (X // dx, Y // dy, Z // dz)
    tx, ty, tz = num_tiles
    y, z, ny, nz = _planes(num_tiles, tile)
    bt = block_tiles or pick_block_ctrl(num_tiles, tile, g.dtype.itemsize)
    bt = min(int(bt), tx)
    nb = -(-tx // bt)
    gp = jnp.pad(jnp.transpose(g, (3, 0, 1, 2)),
                 ((0, 0), (0, (nb * bt - tx) * dx), (0, y - Y), (0, z - Z)))
    ayt = jnp.asarray(common.band_matrix(ty, dy, y, ny).T)
    az = jnp.asarray(common.band_matrix(tz, dz, z, nz))
    out = bsi_adjoint_pallas_planes(gp, ayt, az, dx=dx, bt=bt, form=form,
                                    interpret=interpret)
    out = out[:, : tx + 3, : ty + 3, : tz + 3]
    return jnp.transpose(out, (1, 2, 3, 0)).astype(out_dtype)


def _shrink_to_budget(limits, bytes_fn, budget):
    """Clamp the default block to ``limits``, then halve the largest axis
    until ``bytes_fn(block)`` fits half the budget (or every axis is 1)."""
    b = [min(d, max(1, int(n))) for d, n in zip(_DEFAULT_BLOCK_TILES, limits)]
    while bytes_fn(b) >= budget // 2 and max(b) > 1:
        b[b.index(max(b))] = max(1, max(b) // 2)
    return tuple(b)


# --- fused level step (BSI + warp + similarity, kernels.bsi_fused) ---------

# Similarity kinds with a fused partial-sum accumulator.  The spec tuples
# come from ``repro.core.similarity.fused_spec`` (first element = kind).
FUSED_SIM_KINDS = ("ssd", "ncc", "lncc", "nmi")
# Why the fused kernel cannot run where kernels compile (see fused_supported).
FUSED_NO_TPU = ("the fused kernel's warp is a 3-D gather from the VMEM "
                "moving volume, which Mosaic does not lower on a TPU (only "
                "2-D gathers)")


def fused_supported(vol_shape, sim_spec, itemsize=4,
                    budget=_FUSED_VMEM_BUDGET_BYTES):
    """Whether the fused kernel can run this level: ``(ok, reason)``.

    The fused kernel pins the moving *and* fixed volumes in VMEM (the warp
    is a VMEM gather), so it is bounded by volume size, not grid size —
    beyond the budget the unfused tiled kernels remain the path.  The
    similarity must also have a fused accumulator (a registered kind with
    known parameters; custom callables don't).  Where kernels compile (a TPU
    backend) it cannot run at any size: :data:`FUSED_NO_TPU`.
    """
    if not default_interpret():
        return False, FUSED_NO_TPU
    if sim_spec is None or sim_spec[0] not in FUSED_SIM_KINDS:
        return False, "similarity has no fused accumulator"
    vox = 1
    for s in vol_shape:
        vox *= int(s)
    if 3 * vox * itemsize > budget:
        return False, (f"volume {tuple(int(s) for s in vol_shape)} exceeds "
                       "the fused kernel's VMEM volume budget")
    return True, ""


def pick_block_tiles_fused(num_tiles, tile, extra, sim_spec, itemsize,
                           budget=_FUSED_VMEM_BUDGET_BYTES):
    """Tile-block for the fused kernel: cube-ish, VMEM-bounded.

    Per-voxel temporaries dominate: the displacement block plus the eight
    gather/lerp operands (~24 lanes), and for NMI the two ``(voxels, bins)``
    Parzen weight blocks — the only place the histogram width ever
    materialises.
    """
    lanes = 24
    if sim_spec[0] == "nmi":
        lanes += 2 * int(sim_spec[1])

    def block_bytes(bt):
        vox = 1
        win = 1
        for b, e, d in zip(bt, extra, tile):
            vox *= (b + e) * d
            win *= b + e + 3
        return (vox * lanes + 24 * win) * itemsize

    return _shrink_to_budget(num_tiles, block_bytes, budget)


def fused_similarity_loss(phi, moving, fixed, tile, *, sim_spec,
                          compute_dtype=None, block_tiles=None,
                          disp_form="separable"):
    """Similarity loss of the warped moving volume — fused, no dense field.

    Computes ``sim(warp(moving, bsi(phi)), fixed)`` where ``sim`` is the
    registry loss named by ``sim_spec`` (see
    ``repro.core.similarity.fused_spec``) without ever materialising the
    ``(X, Y, Z, 3)`` displacement field or the warped volume in HBM: the
    Pallas kernel (``kernels.bsi_fused``) accumulates partial sums per
    VMEM tile-block and only the tiny reduction block reaches the host,
    where this dispatcher finishes the registry-exact scalar formula.
    Two-pass for NCC (mean of the warped volume) and NMI (its min/max).
    ``disp_form`` picks the displacement stage's BSI contraction
    (``separable`` sweeps or the ``matmul`` MXU form — see
    ``kernels.bsi_fused._disp_block``).

    Forward only — the differentiable wrapper is
    ``repro.core.ffd.fused_warp_loss``.
    """
    cd = None if compute_dtype is None else jnp.dtype(compute_dtype).name
    return _fused_loss_jit(phi, moving, fixed, tuple(int(t) for t in tile),
                           sim_spec=tuple(sim_spec), compute_dtype=cd,
                           block_tiles=block_tiles,
                           interpret=default_interpret(),
                           disp_form=disp_form)


@functools.partial(jax.jit, static_argnames=(
    "tile", "sim_spec", "compute_dtype", "block_tiles", "interpret",
    "disp_form"))
def _fused_loss_jit(phi, moving, fixed, tile, *, sim_spec, compute_dtype,
                    block_tiles, interpret, disp_form="separable"):
    kind = sim_spec[0]
    if kind not in FUSED_SIM_KINDS:
        raise ValueError(f"no fused kernel for similarity spec {sim_spec!r}")
    if fixed.shape != moving.shape:
        raise ValueError(f"shape mismatch: {fixed.shape} vs {moving.shape}")
    vol_shape = tuple(int(s) for s in moving.shape)
    X, Y, Z = vol_shape
    num_tiles = tuple(int(n) - 3 for n in phi.shape[:3])
    for n, d, s in zip(num_tiles, tile, vol_shape):
        if n * d < s:
            raise ValueError(f"control grid {phi.shape} does not cover "
                             f"volume {vol_shape} at tile spacing {tile}")
    if kind == "lncc":
        # clamp like similarity.uniform_filter, then size the halo in tiles
        size = max(1, min(int(sim_spec[1]), X, Y, Z))
        sim_spec = ("lncc", size, float(sim_spec[2]))
        extra = tuple(-(-(size - 1) // d) for d in tile)
    else:
        extra = (0, 0, 0)
    if compute_dtype is not None:
        phi = phi.astype(compute_dtype)
        moving = moving.astype(compute_dtype)
    fixed32 = fixed.astype(jnp.float32)
    if block_tiles is None:
        block_tiles = pick_block_tiles_fused(num_tiles, tile, extra, sim_spec,
                                             phi.dtype.itemsize)
    block_tiles = tuple(min(b, t) for b, t in zip(block_tiles, num_tiles))
    grid = tuple(-(-t // b) for t, b in zip(num_tiles, block_tiles))
    # pad the control grid to whole blocks + the LNCC halo, and both volumes
    # to the matching voxel extent (padding is masked out of every sum)
    ctrl = tuple(g * b + e + 3 for g, b, e in zip(grid, block_tiles, extra))
    pads = [(0, c - p) for c, p in zip(ctrl, phi.shape[:3])] + [(0, 0)]
    if any(p[1] for p in pads):
        phi = jnp.pad(phi, pads)
    vshape_p = tuple((g * b + e) * d
                     for g, b, e, d in zip(grid, block_tiles, extra, tile))
    vpads = [(0, vp - s) for vp, s in zip(vshape_p, vol_shape)]
    mov_p = jnp.pad(moving, vpads) if any(p[1] for p in vpads) else moving
    fix_p = jnp.pad(fixed32, vpads) if any(p[1] for p in vpads) else fixed32
    luts = tuple(weight_lut(d, phi.dtype) for d in tile)
    n = X * Y * Z
    zeros = jnp.zeros((1, SCALAR_LANES), jnp.float32)

    def run(sim, scalars):
        return bsi_fused_pallas(phi, mov_p, fix_p, *luts, scalars, tile=tile,
                                block_tiles=block_tiles, extra=extra,
                                vol_shape=vol_shape, sim=sim,
                                interpret=interpret, disp_form=disp_form)

    if kind == "ssd":
        acc = run(sim_spec, zeros)
        return acc[0, 0] / n
    if kind == "ncc":
        st = run(("stats",), zeros)
        scal = zeros.at[0, 0].set(st[0, 0] / n).at[0, 1].set(jnp.mean(fixed32))
        acc = run(sim_spec, scal)
        denom = jnp.maximum(jnp.sqrt(acc[0, 1] * acc[0, 2]), 1e-8)
        return 1.0 - acc[0, 0] / denom
    if kind == "lncc":
        _, size, _ = sim_spec
        acc = run(sim_spec, zeros)
        npos = (X - size + 1) * (Y - size + 1) * (Z - size + 1)
        return 1.0 - acc[0, 0] / npos
    # nmi: joint Parzen histogram -> entropies, exactly similarity.nmi
    _, bins, _, eps = sim_spec
    st = run(("stats",), zeros)
    scal = (zeros.at[0, 0].set(st[0, 1]).at[0, 1].set(st[0, 2])
            .at[0, 2].set(jnp.min(fixed32)).at[0, 3].set(jnp.max(fixed32)))
    pab = run(sim_spec, scal) / n
    pa = jnp.sum(pab, axis=1)
    pb = jnp.sum(pab, axis=0)
    ha = -jnp.sum(pa * jnp.log(pa + eps))
    hb = -jnp.sum(pb * jnp.log(pb + eps))
    hab = -jnp.sum(pab * jnp.log(pab + eps))
    return 2.0 - (ha + hb) / (hab + eps)


# --- trilinear warp (kernels.warp) -------------------------------------------


def _warp_planes(vol_shape):
    """``(Yo, Zo, Ys)``: output plane extents and stored moving-volume rows."""
    _, y, z = (int(s) for s in vol_shape)
    return (common.round_up(y, common.SUBLANE), common.round_up(z, common.LANE),
            stored_rows(y))


def warp_vmem_bytes(vol_shape):
    """VMEM the warp kernel plans for: the single-buffered moving volume and
    double-buffered displacement, output and partials planes."""
    x = int(vol_shape[0])
    yo, zo, ys = _warp_planes(vol_shape)
    return x * common.plane_bytes(ys, zo) + 2 * 7 * common.plane_bytes(yo, zo)


def warp_supported(vol_shape, dtype):
    """Whether ``core.ffd.warp_volume`` can take the warp kernel: where
    kernels compile, for a float32 moving volume whose VMEM plan fits
    ``common.VMEM_BUDGET_BYTES``.  Elsewhere the XLA gather form runs."""
    return (not default_interpret() and jnp.dtype(dtype) == jnp.float32
            and warp_vmem_bytes(vol_shape) <= common.VMEM_BUDGET_BYTES)


def warp_trilinear(moving, disp, *, partials=False):
    """``trilinear_sample(moving, identity + disp)`` by the warp kernel.

    ``moving`` is ``(X, Y, Z)`` and ``disp`` ``(X, Y, Z, 3)``, both float32.
    With ``partials`` also returns the ``(X, Y, Z, 3)`` partials of the
    warped volume with respect to ``disp`` (autodiff of the clamped XLA
    form's values, computed from the same eight corners).
    """
    return _warp_jit(moving, disp, partials=partials,
                     interpret=default_interpret())


def _disp_planes(disp):
    """The displacement channel-first, padded to ``(8, 128)`` planes."""
    _, y, z, _ = disp.shape
    yo, zo, _ = _warp_planes(disp.shape[:3])
    return jnp.pad(jnp.moveaxis(disp, -1, 0),
                   ((0, 0), (0, 0), (0, yo - y), (0, zo - z)))


@functools.partial(jax.jit, static_argnames=("partials", "interpret"))
def _warp_jit(moving, disp, *, partials, interpret):
    shape = tuple(int(s) for s in moving.shape)
    if disp.shape != shape + (3,):
        raise ValueError(f"displacement {disp.shape} does not match volume "
                         f"{shape}")
    _, y, z = shape
    _, zo, ys = _warp_planes(shape)
    vol = jnp.pad(moving, ((0, 0), (ROW_PAD, ys - y - ROW_PAD), (0, zo - z)))
    out = warp_trilinear_pallas(_disp_planes(disp), vol, shape=shape,
                                partials=partials, interpret=interpret)
    if not partials:
        return out[:, :y, :z]
    warped, grads = out
    return warped[:, :y, :z], jnp.moveaxis(grads[:, :, :y, :z], 0, -1)


def warp_tile_visits(disp):
    """What the warp kernel visits per output register for the
    ``(X, Y, Z, 3)`` field ``disp``, by the kernel's own bounds: the mean
    z-registers, the mean source planes and row windows per z-register
    visited, and the mean inner iterations (plane-window pairs summed over
    z-registers).  The counter of how much of the field's local spread the
    kernel pays for."""
    d = _disp_planes(disp)
    _, x, yo, zo = d.shape
    ix = jnp.meshgrid(jnp.arange(x), jnp.arange(yo), jnp.arange(zo),
                      indexing="ij")
    cx, cy, cz = (i.astype(jnp.float32) + d[k] for k, i in enumerate(ix))
    _, xs, rs, vs, _ = corner_indices(cx, cy, cz, ix[1] % common.SUBLANE,
                                      tuple(int(s) for s in disp.shape[:3]))
    tiles = (x, yo // common.SUBLANE, common.SUBLANE, zo // common.LANE,
             common.LANE)
    zregs, boxes = visit_counts(*[[a.reshape(tiles) for a in pair]
                                  for pair in (xs, rs, vs)], axis=(2, 4))
    visited = sum(jnp.sum(p > 0) for p, _ in boxes)
    return {"zregs": float(jnp.mean(zregs)),
            "planes": float(sum(jnp.sum(p) for p, _ in boxes) / visited),
            "rows": float(sum(jnp.sum(w) for _, w in boxes) / visited),
            "iterations": float(jnp.mean(sum(p * w for p, w in boxes)))}
