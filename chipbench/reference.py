"""Plain float32 reference of an FFD registration, independent of the program.

Each piece is written from its definition, in ``jax.numpy``, with no kernel,
cache or batching, and imports nothing of ``repro``:

* cubic B-spline interpolation (BSI) of a voxel-aligned control grid: voxel
  ``x = t*h + a`` reads stored points ``t .. t+3`` with the basis
  ``B_l(a/h)``; evaluated as three per-axis basis-matrix contractions;
* trilinear resampling with coordinates clamped to the volume;
* SSD, and NMI from a Gaussian Parzen joint histogram (min-max normalised
  intensities, ``bins`` centres, width ``sigma_ratio`` bins) summed over
  voxel chunks so that it fits the device;
* the thin-plate bending energy of the spline, integrated exactly by 4-point
  Gauss-Legendre quadrature on every tile (mean density over the spline
  domain in voxels);
* Adam (update first, then loss and gradient at the new grid), ``jax.grad``,
  2x average-pool pyramid, trilinear grid upsampling (displacements double).

Every matrix product runs at HIGHEST precision: float32 throughout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NMI_CHUNK = 1 << 20  # voxels per Parzen-window chunk


def grid_shape(vol_shape, tile):
    """Stored control points per axis: whole tiles covering the volume + 3."""
    return tuple(-(-int(s) // int(h)) + 3 for s, h in zip(vol_shape, tile))


def _basis(u, d):
    """``B_0..B_3`` (``d``-th derivative) at ``u`` in [0, 1), float64."""
    u = np.asarray(u, np.float64)
    if d == 0:
        return np.stack([(1 - u) ** 3 / 6, (3 * u**3 - 6 * u**2 + 4) / 6,
                         (-3 * u**3 + 3 * u**2 + 3 * u + 1) / 6, u**3 / 6], -1)
    if d == 1:
        return np.stack([-((1 - u) ** 2) / 2, 1.5 * u**2 - 2 * u,
                         -1.5 * u**2 + u + 0.5, u**2 / 2], -1)
    if d == 2:
        return np.stack([1 - u, 3 * u - 2, 1 - 3 * u, u], -1)
    raise ValueError(f"derivative order {d}")


@functools.lru_cache(maxsize=None)
def basis_matrix(n_vox, h, n_ctrl):
    """``(n_vox, n_ctrl)`` float64: row ``x`` holds its 4 spline weights."""
    x = np.arange(n_vox)
    t, a = x // h, x % h
    m = np.zeros((n_vox, n_ctrl))
    w = _basis(a / h, 0)
    for l in range(4):
        m[x, t + l] = w[:, l]
    return m


HIGHEST = lax.Precision.HIGHEST


def _contract(phi, mx, my, mz):
    """``phi`` contracted with one matrix per axis (float32, HIGHEST)."""
    m = [jnp.asarray(a, jnp.float32) for a in (mx, my, mz)]
    out = jnp.einsum("xi,ijkc->xjkc", m[0], phi, precision=HIGHEST)
    out = jnp.einsum("yj,xjkc->xykc", m[1], out, precision=HIGHEST)
    return jnp.einsum("zk,xykc->xyzc", m[2], out, precision=HIGHEST)


def bsi(phi, tile, vol_shape):
    """Dense ``(X, Y, Z, C)`` field of the control grid ``phi``."""
    mats = [basis_matrix(int(s), int(h), int(n))
            for s, h, n in zip(vol_shape, tile, phi.shape[:3])]
    return _contract(jnp.asarray(phi, jnp.float32), *mats)


def trilinear(vol, coords):
    """``vol`` sampled at voxel ``coords`` ``(..., 3)``, clamped to the volume."""
    shape = vol.shape
    hi = jnp.asarray(shape, jnp.float32) - 1.0
    c = jnp.clip(coords.astype(jnp.float32), 0.0, hi)
    base = jnp.floor(c)
    frac = c - base
    i0 = base.astype(jnp.int32)
    i1 = jnp.minimum(i0 + 1, jnp.asarray(shape, jnp.int32) - 1)
    flat = vol.reshape(-1)
    out = 0.0
    for cx in (0, 1):
        for cy in (0, 1):
            for cz in (0, 1):
                ix = (i0, i1)[cx][..., 0]
                iy = (i0, i1)[cy][..., 1]
                iz = (i0, i1)[cz][..., 2]
                w = ((frac[..., 0] if cx else 1 - frac[..., 0])
                     * (frac[..., 1] if cy else 1 - frac[..., 1])
                     * (frac[..., 2] if cz else 1 - frac[..., 2]))
                idx = (ix * shape[1] + iy) * shape[2] + iz
                out = out + jnp.take(flat, idx) * w
    return out


def warp(moving, disp):
    """``moving`` resampled at identity + ``disp`` (voxel units)."""
    axes = [jnp.arange(s, dtype=jnp.float32) for s in moving.shape]
    ident = jnp.stack(jnp.meshgrid(*axes, indexing="ij"), axis=-1)
    return trilinear(moving, ident + disp.astype(jnp.float32))


def ssd(warped, fixed):
    return jnp.mean((warped - fixed) ** 2)


def _norm01(x):
    lo, hi = jnp.min(x), jnp.max(x)
    return (x - lo) / jnp.maximum(hi - lo, 1e-8)


def nmi(warped, fixed, *, bins, sigma_ratio=0.5, eps=1e-8, chunk=NMI_CHUNK):
    """``2 - (H(a) + H(b)) / H(a, b)`` from a Parzen joint histogram."""
    a = _norm01(warped).reshape(-1)
    b = _norm01(fixed).reshape(-1)
    n = a.shape[0]
    chunk = min(chunk, n)
    pad = (-n) % chunk
    live = (jnp.arange(n + pad) < n).astype(jnp.float32).reshape(-1, chunk)
    a = jnp.pad(a, (0, pad)).reshape(-1, chunk)
    b = jnp.pad(b, (0, pad)).reshape(-1, chunk)
    centres = jnp.linspace(0.0, 1.0, bins, dtype=jnp.float32)
    sigma = sigma_ratio / (bins - 1)

    def parzen(v):
        w = jnp.exp(-0.5 * ((v[:, None] - centres[None, :]) / sigma) ** 2)
        return w / (jnp.sum(w, axis=1, keepdims=True) + eps)

    @jax.checkpoint
    def part(ac, bc, mc):
        return jnp.matmul((parzen(ac) * mc[:, None]).T, parzen(bc),
                          precision=HIGHEST)

    def body(acc, xs):
        return acc + part(*xs), None

    pab, _ = lax.scan(body, jnp.zeros((bins, bins), jnp.float32), (a, b, live))
    pab = pab / n
    pa, pb = jnp.sum(pab, axis=1), jnp.sum(pab, axis=0)
    ha = -jnp.sum(pa * jnp.log(pa + eps))
    hb = -jnp.sum(pb * jnp.log(pb + eps))
    hab = -jnp.sum(pab * jnp.log(pab + eps))
    return 2.0 - (ha + hb) / (hab + eps)


def similarity_fn(spec):
    """``(warped, fixed) -> loss`` for a configuration's similarity spec."""
    name = spec["name"]
    if name == "ssd":
        return ssd
    if name == "nmi":
        return functools.partial(nmi, bins=int(spec.get("bins", 32)),
                                 sigma_ratio=float(spec.get("sigma_ratio", .5)),
                                 eps=float(spec.get("eps", 1e-8)))
    raise ValueError(f"the reference has no similarity {name!r}")


# --- bending energy by quadrature -------------------------------------------

_GL_NODES, _GL_WEIGHTS = np.polynomial.legendre.leggauss(4)
# (d_x, d_y, d_z, multiplicity) of u_xx, u_yy, u_zz, u_xy, u_xz, u_yz
_TERMS = ((2, 0, 0, 1.0), (0, 2, 0, 1.0), (0, 0, 2, 1.0),
          (1, 1, 0, 2.0), (1, 0, 1, 2.0), (0, 1, 1, 2.0))


@functools.lru_cache(maxsize=None)
def quadrature_matrix(n_ctrl, h, d):
    """``(4*T, n_ctrl)``: ``d``-th x-derivative of each basis at the Gauss
    points of every tile, and the points' weights in voxel units."""
    tiles = n_ctrl - 3
    t = (_GL_NODES + 1.0) / 2.0
    vals = _basis(t, d) / float(h) ** d  # (4 points, 4 controls)
    m = np.zeros((4 * tiles, n_ctrl))
    for c in range(tiles):
        m[4 * c:4 * c + 4, c:c + 4] = vals
    w = np.tile(_GL_WEIGHTS / 2.0 * h, tiles)
    return m, w


def bending_energy(phi, tile):
    """Mean thin-plate bending-energy density of the spline (voxel units)."""
    dims = phi.shape[:3]
    domain = float(np.prod([(n - 3) * h for n, h in zip(dims, tile)]))
    phi = jnp.asarray(phi, jnp.float32)
    wts = [quadrature_matrix(int(n), int(h), 0)[1] for n, h in zip(dims, tile)]
    w3 = jnp.asarray(np.einsum("i,j,k->ijk", *wts), jnp.float32)
    total = 0.0
    for dx, dy, dz, mult in _TERMS:
        mats = [quadrature_matrix(int(n), int(h), d)[0]
                for n, h, d in zip(dims, tile, (dx, dy, dz))]
        deriv = _contract(phi, *mats)
        total = total + mult * jnp.sum(w3[..., None] * deriv**2)
    return total / domain


# --- pyramid and registration -----------------------------------------------


def downsample2(vol):
    """2x average pool after cropping each axis to an even length."""
    x, y, z = (s - s % 2 for s in vol.shape)
    v = vol[:x, :y, :z].reshape(x // 2, 2, y // 2, 2, z // 2, 2)
    return v.mean(axis=(1, 3, 5))


@functools.lru_cache(maxsize=None)
def _linear_matrix(n_old, n_new):
    """``(n_new, n_old)`` linear interpolation at ``linspace(0, n_old-1)``."""
    pos = np.linspace(0.0, n_old - 1.0, n_new)
    i0 = np.clip(np.floor(pos).astype(int), 0, n_old - 1)
    i1 = np.minimum(i0 + 1, n_old - 1)
    t = pos - i0
    m = np.zeros((n_new, n_old))
    np.add.at(m, (np.arange(n_new), i0), 1.0 - t)
    np.add.at(m, (np.arange(n_new), i1), t)
    return m


def upsample_grid(phi, new_shape):
    """Trilinear resample of a control grid to ``new_shape``, times 2."""
    mats = [_linear_matrix(int(o), int(n))
            for o, n in zip(phi.shape[:3], new_shape)]
    return 2.0 * _contract(phi, *mats)


def level_objective(fixed, moving, cfg):
    """``phi -> similarity(warp(moving, bsi(phi)), fixed) + bending``."""
    tile = tuple(cfg["tile"])
    sim = similarity_fn(cfg["similarity"])
    weight = float(cfg["regularizer"]["weight"])

    def objective(phi):
        disp = bsi(phi, tile, fixed.shape)
        return sim(warp(moving, disp), fixed) + weight * bending_energy(phi, tile)

    return objective


def adam_level(objective, phi, *, iters, lr, b1=0.9, b2=0.999, eps=1e-8):
    """``iters`` Adam steps; returns the grid and the loss after each step."""
    vg = jax.value_and_grad(objective)
    _, g0 = vg(phi)

    def step(carry, i):
        p, m, v, g = carry
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        p = p - lr * (m / (1 - b1**i)) / (jnp.sqrt(v / (1 - b2**i)) + eps)
        loss, g = vg(p)
        return (p, m, v, g), loss

    zeros = jnp.zeros_like(phi)
    steps = jnp.arange(1, iters + 1, dtype=jnp.float32)
    (phi, _, _, _), trace = lax.scan(step, (phi, zeros, zeros, g0), steps)
    return phi, trace


@functools.lru_cache(maxsize=None)
def _level_runner(cfg_key):
    cfg = _cfg_from_key(cfg_key)

    def run(phi, fixed, moving):
        obj = level_objective(fixed, moving, cfg)
        return adam_level(obj, phi, iters=int(cfg["iters"]),
                          lr=float(cfg["lr"]))

    return jax.jit(run)


def _cfg_key(cfg):
    """The registration fields of a configuration as a hashable key."""
    return (("tile", tuple(cfg["tile"])), ("levels", int(cfg["levels"])),
            ("iters", int(cfg["iters"])), ("lr", float(cfg["lr"])),
            ("similarity", tuple(sorted(cfg["similarity"].items()))),
            ("regularizer", tuple(sorted(cfg["regularizer"].items()))))


def _cfg_from_key(cfg_key):
    cfg = dict(cfg_key)
    cfg["similarity"] = dict(cfg["similarity"])
    cfg["regularizer"] = dict(cfg["regularizer"])
    return cfg


@functools.lru_cache(maxsize=None)
def _final_warp(tile):
    return jax.jit(lambda phi, moving: warp(moving,
                                            bsi(phi, tile, moving.shape)))


def final_warp(phi, moving, tile):
    """The registered volume: ``moving`` warped by the full-size field."""
    return _final_warp(tuple(tile))(phi, moving)


@functools.lru_cache(maxsize=None)
def _objective_at(cfg_key):
    cfg = _cfg_from_key(cfg_key)
    return jax.jit(lambda phi, fixed, moving:
                   level_objective(fixed, moving, cfg)(phi))


def objective_at(phi, fixed, moving, cfg):
    """The finest level's objective at the control grid ``phi``."""
    return _objective_at(_cfg_key(cfg))(phi, fixed, moving)


def register(fixed, moving, cfg):
    """The whole registration: ``{"phi", "losses", "warped"}``.

    ``losses[l]`` is the objective after the last step of level ``l``
    (coarse to fine); ``phi`` the finest level's grid.
    """
    tile = tuple(cfg["tile"])
    fixed = jnp.asarray(fixed, jnp.float32)
    moving = jnp.asarray(moving, jnp.float32)
    pyramid = [(fixed, moving)]
    for _ in range(int(cfg["levels"]) - 1):
        pyramid.append(tuple(downsample2(v) for v in pyramid[-1]))
    run = _level_runner(_cfg_key(cfg))
    phi, losses = None, []
    for f, m in pyramid[::-1]:
        gshape = grid_shape(f.shape, tile)
        phi = (jnp.zeros(gshape + (3,), jnp.float32) if phi is None
               else upsample_grid(phi, gshape))
        phi, trace = run(phi, f, m)
        losses.append(trace[-1])
    warped = final_warp(phi, moving, tile)
    return {"phi": phi, "losses": jnp.stack(losses), "warped": warped}
