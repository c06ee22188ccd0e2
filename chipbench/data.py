"""Seeded registration pairs, made on the device.

The recipe of the program's synthetic data (liver-phantom volume: a lobed
ellipsoid of parenchyma, bright tumour spheres and vessel tubes, acquisition
noise; the moving volume is the phantom warped by a random smooth control
grid), copied so that the benchmark's inputs do not change when the program
does.  The few random parameters and the noise are drawn on the host in the
recipe's order, so a seed gives the recipe's volume; the volume arithmetic
runs on the device in one jitted call, and the deformation uses this
benchmark's own BSI and trilinear warp (``chipbench.reference``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference


def pair_seed(seed, index):
    """A 32-bit seed for pair ``index`` of a run seeded with ``seed``."""
    ss = np.random.SeedSequence([int(seed) % 2**64, int(index)])
    return int(ss.generate_state(1)[0] >> 1)


def phantom_params(shape, seed, n_tumors=5, n_vessels=3):
    """The recipe's random draws for one phantom, in the recipe's order."""
    rng = np.random.default_rng(seed)
    tumors = []
    for _ in range(n_tumors):
        c = rng.uniform(-0.45, 0.45, 3)
        tumors.append(np.append(c, rng.uniform(0.06, 0.14)))
    vessels = []
    for _ in range(n_vessels):
        p = rng.uniform(-0.35, 0.35, 3)
        d = rng.standard_normal(3)
        vessels.append(np.concatenate([p, d / np.linalg.norm(d)]))
    noise = rng.normal(0.0, 0.01, tuple(shape)).astype(np.float32)
    return (np.asarray(tumors, np.float32).reshape(n_tumors, 4),
            np.asarray(vessels, np.float32).reshape(n_vessels, 6), noise)


@jax.jit
def _phantom(tumors, vessels, noise):
    x, y, z = (jnp.linspace(-1.0, 1.0, n, dtype=jnp.float32)
               for n in noise.shape)
    xs, ys, zs = jnp.meshgrid(x, y, z, indexing="ij")
    r2 = (xs / 0.8) ** 2 + (ys / 0.7) ** 2 + (zs / 0.75) ** 2
    lobes = 0.12 * jnp.sin(3 * xs + 1.0) * jnp.cos(2 * ys)
    vol = 0.55 * (1.0 / (1.0 + jnp.exp(40 * (r2 - 0.8 + lobes))))
    for c in tumors:
        d2 = (xs - c[0]) ** 2 + (ys - c[1]) ** 2 + (zs - c[2]) ** 2
        vol = vol + 0.35 * jnp.exp(-d2 / (2 * c[3] ** 2))
    for v in vessels:
        rel = (xs - v[0], ys - v[1], zs - v[2])
        t = rel[0] * v[3] + rel[1] * v[4] + rel[2] * v[5]
        dist2 = sum((r - t * d) ** 2 for r, d in zip(rel, v[3:]))
        vol = vol + (0.25 * jnp.exp(-dist2 / (2 * 0.03**2))
                     * (jnp.abs(t) < 0.6))
    return jnp.clip(vol + noise, 0.0, 1.0)


def make_phantom(shape, seed):
    """The phantom volume of ``seed`` at ``shape`` (float32, on the device)."""
    return _phantom(*(jnp.asarray(a) for a in phantom_params(shape, seed)))


def deformation(shape, seed, tile, magnitude):
    """The recipe's random control grid for the pair of ``seed``."""
    rng = np.random.default_rng(seed + 1)
    gshape = reference.grid_shape(shape, tile)
    return jnp.asarray(rng.normal(0.0, magnitude, gshape + (3,)), jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile",))
def _deform(fixed, phi, tile):
    return reference.warp(fixed, reference.bsi(phi, tile, fixed.shape))


def monotone_remap(v):
    """Monotone-decreasing intensity remap of [0, 1]: a second modality.

    Intensities are clipped to [0, 1] first: interpolation can overshoot 1
    by a rounding step, and a negative base has no real power."""
    return (1.0 - jnp.clip(v, 0.0, 1.0)) ** 1.5


REMAPS = {"none": lambda v: v, "monotone": monotone_remap}


def make_pair(shape, seed, *, tile=(6, 6, 6), magnitude=2.5, remap="none"):
    """``(fixed, moving)``: the phantom, and it warped by a random grid.

    ``remap`` names an intensity map applied to the moving volume after the
    warp (``"monotone"`` stands for a second modality).
    """
    fixed = make_phantom(shape, seed)
    phi = deformation(shape, seed, tuple(tile), magnitude)
    moving = _deform(fixed, phi, tuple(int(t) for t in tile))
    return fixed, REMAPS[remap](moving)
