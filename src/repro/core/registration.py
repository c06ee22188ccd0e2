"""Non-rigid (FFD) and affine registration — the paper's application layer.

A JAX re-build of the NiftyReg workflow the paper integrates into (§6):
multi-resolution pyramid, a pluggable similarity term (SSD by default; NCC,
local NCC and differentiable NMI for multi-modal pairs — see
``repro.core.similarity``), bending-energy regularisation, gradient-based
optimisation of the control grid.  The expensive inner step —
expanding the control grid to the dense deformation field — is exactly the
paper's BSI and is dispatched through ``repro.core.interpolate`` so any of
the algorithm forms / kernels can be plugged in (``mode=``, ``impl=``;
both default to ``"auto"``, the ``repro.engine.autotune`` winner).

The inner optimisation is device-resident: each pyramid level runs as ONE
``jax.lax.scan``-compiled program (``repro.engine.loop``) under the
pluggable ``optimizer=`` registry (``repro.engine.optimizer`` — Adam by
default, L-BFGS / Gauss-Newton for second-order convergence), with runners
cached per configuration so repeated calls pay zero re-jits, and the
params buffer donated on accelerator backends.  For many pairs at
once, use ``repro.engine.register_batch`` — the same pipeline under ``vmap``.

Hand-derived gradients (NiftyReg's approach) are replaced by autodiff; the
BSI forward is still the dominant cost, so the paper's speedup story carries.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import ffd
from repro.core.ffd import downsample2  # re-exported (seed API)
from repro.core.options import (UNSET, RegistrationOptions,
                                merge_legacy_options)
from repro.engine.autotune import resolve_options
from repro.engine.batch import compile_finish, ffd_level_objective
from repro.engine.loop import make_adam_runner

__all__ = ["RegistrationResult", "affine_register", "ffd_register", "downsample2"]

# affine_register's historical keyword defaults (the FFD defaults live on
# RegistrationOptions itself)
AFFINE_DEFAULTS = RegistrationOptions(iters=60, lr=0.02)


@dataclasses.dataclass
class RegistrationResult:
    warped: Any              # registered moving volume
    params: Any              # affine matrix or control grid pytree per level
    losses: list             # loss trace
    seconds: float           # wall time
    steps: Any = None        # optimiser steps per level when stop= was set


def _affine_ident_centre(vol_shape):
    centre = (jnp.asarray(vol_shape, jnp.float32) - 1.0) / 2.0
    X, Y, Z = vol_shape
    ident = jnp.stack(
        jnp.meshgrid(
            jnp.arange(X, dtype=jnp.float32),
            jnp.arange(Y, dtype=jnp.float32),
            jnp.arange(Z, dtype=jnp.float32),
            indexing="ij",
        ),
        axis=-1,
    )
    return ident, centre


def _affine_warp(theta, moving, vol_shape):
    ident, centre = _affine_ident_centre(vol_shape)
    A = theta[:, :3] + jnp.eye(3)
    coords = (ident - centre) @ A.T + centre + theta[:, 3]
    return ffd.trilinear_sample(moving, coords)


@functools.lru_cache(maxsize=32)
def _affine_runner(vol_shape, options):
    """One compiled affine loop per (shape, options) — ``options`` is a
    canonical ``RegistrationOptions.for_affine()`` instance (which keeps the
    ``optimizer`` axis), the sole cache key beyond the volume shape."""
    from repro.core.similarity import resolve_similarity
    from repro.engine.optimizer import make_objective

    sim_key, sim = resolve_similarity(options.similarity)

    def loss_builder(f, mov):
        def loss_fn(theta):
            return sim(_affine_warp(theta, mov, vol_shape), f)

        if sim_key != "ssd":
            return loss_fn

        # ssd exposes its least-squares residual (mean(r**2), no
        # regulariser on the affine model) so optimizer="gauss_newton"
        # can linearise the warp directly
        def residual_fn(theta):
            return (_affine_warp(theta, mov, vol_shape) - f).ravel()

        return make_objective(loss_fn, residual_fn=residual_fn)

    return make_adam_runner(loss_builder, options=options)


def affine_register(fixed, moving, *, options=None, iters=UNSET, lr=UNSET,
                    similarity=UNSET, stop=UNSET, optimizer=UNSET):
    """Optimise a 3x4 affine (around the volume centre) on ``similarity``.

    The whole optimisation is one scan-compiled program; the runner is
    cached by (shape, options), so repeat calls skip compilation.  Configure
    via ``options=RegistrationOptions(...)`` — only its ``iters`` / ``lr`` /
    ``similarity`` / ``stop`` fields apply to the affine model (legacy
    defaults: ``iters=60, lr=0.02``); the legacy keywords still work through
    the deprecation shim and produce bit-identical results.  ``similarity``
    is a registered name (``"ssd" | "ncc" | "lncc" | "nmi"``) or a loss
    callable (lower = better).  ``stop`` (a ``ConvergenceConfig``) runs the
    loop as an early-stopped ``lax.while_loop`` instead — the result's
    ``steps`` records the optimiser steps actually taken (``stop.max_iters``
    defaults to ``iters``).  ``optimizer`` (``"adam" | "lbfgs" |
    "gauss_newton"`` or an ``engine.optimizer`` spec) picks the loop —
    ``"gauss_newton"`` needs ``similarity="ssd"`` and linearises the affine
    warp directly.
    """
    fixed = jnp.asarray(fixed, jnp.float32)
    moving = jnp.asarray(moving, jnp.float32)
    opts = merge_legacy_options(
        "affine_register", options,
        dict(iters=iters, lr=lr, similarity=similarity, stop=stop,
             optimizer=optimizer),
        defaults=AFFINE_DEFAULTS).for_affine()
    stop = opts.stop  # resolved by for_affine()'s normalized()
    t0 = time.perf_counter()
    runner = _affine_runner(fixed.shape, opts)
    theta0 = jnp.zeros((3, 4), jnp.float32)
    out = runner(theta0, fixed, moving)
    theta, trace = out[:2]
    steps = [int(out[2])] if stop is not None else None
    # same sampling points as the seed's Python loop: every 10th + last
    # (the early-stopped trace is padded with its final loss past the stop)
    span = opts.iters if stop is None else stop.max_iters
    marks = sorted(set(range(10, span + 1, 10)) | {span})
    losses = [float(trace[i - 1]) for i in marks]
    warped = _affine_warp(theta, moving, fixed.shape)
    jax.block_until_ready(warped)
    return RegistrationResult(warped, theta, losses,
                              time.perf_counter() - t0, steps=steps)


@functools.lru_cache(maxsize=64)  # bounded: ~levels x configs in flight
def _ffd_level_runner(vol_shape, options):
    """One compiled level loop per (shape, options) — the resolved
    ``RegistrationOptions`` instance is the sole cache key beyond shape."""
    del vol_shape  # cache key only; shapes re-trace via jit

    def loss_builder(f, mov):
        return ffd_level_objective(f, mov, tile=options.tile,
                                   bending_weight=options.bending_weight,
                                   mode=options.mode, impl=options.impl,
                                   grad_impl=options.grad_impl,
                                   compute_dtype=options.compute_dtype,
                                   similarity=options.similarity,
                                   transform=options.transform,
                                   regularizer=options.regularizer,
                                   fused=options.fused)

    return make_adam_runner(loss_builder, options=options)


def ffd_register(
    fixed,
    moving,
    *,
    options=None,
    tile=UNSET,
    levels=UNSET,
    iters=UNSET,
    lr=UNSET,
    bending_weight=UNSET,
    mode=UNSET,
    impl=UNSET,
    grad_impl=UNSET,
    compute_dtype=UNSET,
    similarity=UNSET,
    transform=UNSET,
    regularizer=UNSET,
    stop=UNSET,
    optimizer=UNSET,
):
    """Multi-resolution FFD registration (NiftyReg workflow, paper §6).

    Pyramid: coarse-to-fine on 2x-downsampled volumes; the control grid is
    upsampled (re-expanded through BSI itself) between levels.  Each level's
    optimiser loop is a single ``lax.scan`` program — one compile per pyramid
    level, cached across calls, keyed by the resolved
    ``RegistrationOptions``.  Configure via ``options=`` (a
    ``repro.core.RegistrationOptions``); the legacy keyword arguments still
    work through a deprecation shim and produce bit-identical results.
    ``mode``/``impl``/``grad_impl`` default to ``"auto"``: the autotuned
    fastest BSI forward x adjoint pair for the finest-level grid under the
    chosen ``similarity``'s forward+backward workload (``grad_impl`` selects
    between XLA autodiff and the analytic gather-only custom VJP — see
    ``repro.core.interpolate``).  ``compute_dtype`` (e.g. ``"bfloat16"``)
    runs BSI + warp in reduced precision with fp32 params and adjoint
    accumulation.  ``similarity`` is a registered name (``"ssd" | "ncc" |
    "lncc" | "nmi"`` — NMI being the multi-modal NiftyReg path) or a
    ``(warped, fixed) -> scalar`` loss callable (lower = better; see
    ``repro.core.similarity``).  ``transform`` (``"displacement" |
    "velocity"`` or a ``repro.core.transform`` spec) picks the deformation
    model — ``"velocity"`` integrates a stationary velocity field by scaling
    and squaring, giving invertible, fold-free (diffeomorphic) warps;
    ``regularizer`` (``"none" | "bending"`` or a ``repro.core.regularizer``
    spec) picks the smoothness term — ``"bending"`` is the analytic
    B-spline bending energy with closed-form gradient, replacing the legacy
    ``bending_weight`` finite-difference proxy.  ``stop`` (a
    ``ConvergenceConfig``, see
    ``repro.engine.convergence``) replaces each level's fixed-``iters`` scan
    with an early-stopped ``lax.while_loop`` (``stop.max_iters`` defaults to
    ``iters``); the result's ``steps`` then lists the optimiser steps each
    level actually ran.  ``optimizer`` (``"adam" | "lbfgs" | "gauss_newton"``
    or an ``engine.optimizer`` spec, see the README's Optimisers table)
    picks each level's optimisation loop — the default ``"adam"`` is
    bit-identical to the historical engine; the second-order entries
    typically converge hard pairs in a fraction of the steps
    (``"gauss_newton"`` requires ``similarity="ssd"``).
    """
    fixed = jnp.asarray(fixed, jnp.float32)
    moving = jnp.asarray(moving, jnp.float32)
    opts = merge_legacy_options(
        "ffd_register", options,
        dict(tile=tile, levels=levels, iters=iters, lr=lr,
             bending_weight=bending_weight, mode=mode, impl=impl,
             grad_impl=grad_impl, compute_dtype=compute_dtype,
             similarity=similarity, transform=transform,
             regularizer=regularizer, stop=stop, optimizer=optimizer))
    with TraceAnnotation("repro.resolve"):
        opts = resolve_options(opts, fixed.shape)  # autotune + canonicalise
    tile, stop = opts.tile, opts.stop

    with TraceAnnotation("repro.pyramid"):
        pyramid = [(fixed, moving)]
        for _ in range(opts.levels - 1):
            f, m = pyramid[-1]
            pyramid.append((downsample2(f), downsample2(m)))
        pyramid = pyramid[::-1]  # coarse -> fine

    phi = None
    losses = []
    steps = [] if stop is not None else None
    t0 = time.perf_counter()

    for level, (f, m) in enumerate(pyramid):
        with TraceAnnotation("repro.level", level=level):
            gshape = ffd.grid_shape_for_volume(f.shape, tile)
            if phi is None:
                phi = jnp.zeros(gshape + (3,), jnp.float32)
            else:
                phi = ffd.upsample_grid(phi, gshape)
            out = _ffd_level_runner(f.shape, opts)(phi, f, m)
        phi, trace = out[:2]
        with TraceAnnotation("repro.sync"):
            if stop is not None:
                steps.append(int(out[2]))
            phi.block_until_ready()
            losses.append(float(trace[-1]))

    with TraceAnnotation("repro.final"):
        warped = compile_finish(fixed.shape, opts)(phi, moving)
    return RegistrationResult(warped, phi, losses, time.perf_counter() - t0,
                              steps=steps)
