"""Batched, fully-jitted FFD registration — the "serve heavy traffic" primitive.

``ffd_pipeline`` is the whole multi-level FFD optimisation (pyramid,
scan-based Adam per level, grid upsampling between levels, final warp) as a
pure traced function of ``(fixed, moving)``.  That purity is the point: it
``vmap``s over a leading batch axis, so ``register_batch`` registers N volume
pairs in ONE jitted program — no Python-loop dispatch anywhere, and XLA is
free to batch every BSI expansion, gradient, and Adam update across pairs.

Compiled programs are cached per configuration (shapes x
``RegistrationOptions``), so a serving loop pays one compile per volume
geometry and then runs back-to-back batches at device speed.  For the
continuous-batching scheduler (``engine.serve``) this module also provides
the *resumable* form: ``compile_level_chunk`` runs a fixed-width lane array
through ``chunk`` masked Adam steps of one pyramid level and hands the whole
optimiser state back to the host, so converged lanes can be spliced out and
queued pairs spliced in between chunks.
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
from repro.core.options import UNSET, merge_legacy_options
from repro.core.regularizer import regularizer_term
from repro.core.similarity import resolve_similarity
from repro.core.transform import (VelocityTransform, dense_displacement,
                                  resolve_transform)
from repro.engine.convergence import (level_live, optimize_plateau_step,
                                      optimize_until)
from repro.engine.loop import optimize_scan
from repro.engine.optimizer import init_state, make_objective

__all__ = ["BatchRegistrationResult", "ffd_level_loss", "ffd_level_objective",
           "ffd_pipeline", "register_batch", "level_vol_shapes",
           "compile_level_chunk", "compile_level_init",
           "compile_level_splice", "compile_finish"]


@dataclasses.dataclass
class BatchRegistrationResult:
    warped: Any     # (B, X, Y, Z) registered moving volumes
    params: Any     # (B, *grid_shape, 3) finest-level control grids
    losses: Any     # (B, levels) final loss per pyramid level
    seconds: float  # wall time for the whole batch (see ``compiled``)
    # True when this call (re)compiled the batch program: ``seconds`` then
    # includes the one-time trace+compile and is NOT a steady-state batch
    # time — time a second call (or check this flag) before comparing.
    compiled: bool = False
    # (B, levels) int32 Adam steps actually run per pair per level when the
    # call used early stopping (``stop=``); None under fixed-``iters``.
    steps: Any = None


def ffd_level_loss(f, mov, *, tile, bending_weight, mode, impl,
                   grad_impl="xla", compute_dtype=None, similarity="ssd",
                   transform="displacement", regularizer="none",
                   fused="off"):
    """Similarity + regularisation objective for one pyramid level.

    ``similarity`` is a registered name or a ``(warped, fixed) -> scalar``
    loss callable (lower = better; see ``repro.core.similarity``).  Shared
    verbatim by the per-pair path (``core.registration.ffd_register``) and
    the batched path so the two produce matching optimisations.
    ``grad_impl`` picks the BSI adjoint (``xla`` autodiff vs the analytic
    gather-only custom VJP — see ``repro.core.interpolate``);
    ``compute_dtype`` runs the BSI expansion + warp in reduced precision
    (params, adjoint accumulation and the objective stay fp32).

    ``transform`` (name or spec, see ``repro.core.transform``) picks how
    the control grid becomes a displacement: classic FFD (default) or a
    stationary velocity field integrated by scaling and squaring.
    ``regularizer`` (see ``repro.core.regularizer``) picks the smoothness
    term: ``"none"`` keeps the historical ``bending_weight``
    finite-difference proxy; ``"bending"`` replaces it with the analytic
    B-spline bending energy at the spec's own weight.

    ``fused="on"`` (or ``True``) swaps the similarity term for the fused
    Pallas level step (``core.ffd.fused_warp_loss``): BSI displacement +
    warp + similarity partial sums in one VMEM pass, no ``(X, Y, Z, 3)``
    field or warped volume in HBM, with the gradient recomputed through the
    unfused composition (so it is identical).  Requires a similarity with a
    fused accumulator and the ``displacement`` transform (the megakernel
    cannot interleave velocity compositions); the regularisation term stays
    outside (it reads only the control grid).
    """
    vol_shape = f.shape
    _, sim = resolve_similarity(similarity)
    tspec = resolve_transform(transform)
    gshape = ffd.grid_shape_for_volume(vol_shape, tile)
    reg = regularizer_term(regularizer, grid_shape=gshape, tile=tile,
                           bending_weight=bending_weight)

    if fused in ("on", True):
        if isinstance(tspec, VelocityTransform):
            raise ValueError(
                "fused='on' cannot run the velocity transform: the fused "
                "level step has no scaling-and-squaring composition; use "
                "fused='off' (or 'auto') with transform='velocity'")

        def loss_fn(p):
            simloss = ffd.fused_warp_loss(
                p, mov, f, tile, similarity=similarity, mode=mode, impl=impl,
                grad_impl=grad_impl, compute_dtype=compute_dtype)
            return simloss + reg(p)

        return loss_fn

    def loss_fn(p):
        disp = dense_displacement(tspec, p, tile, vol_shape, mode=mode,
                                  impl=impl, grad_impl=grad_impl,
                                  compute_dtype=compute_dtype)
        warped = ffd.warp_volume(mov, disp, compute_dtype=compute_dtype)
        # score the objective in fp32 regardless of input dtype: casting to
        # f.dtype would silently score a bf16 fixed volume (similarity AND
        # its trade-off against the fp32 regulariser) in bf16
        warped = warped.astype(jnp.float32)
        fixed32 = f.astype(jnp.float32)
        with jax.named_scope("similarity"):
            simloss = sim(warped, fixed32)
        return simloss + reg(p)

    return loss_fn


def ffd_level_objective(f, mov, *, tile, bending_weight, mode, impl,
                        grad_impl="xla", compute_dtype=None, similarity="ssd",
                        transform="displacement", regularizer="none",
                        fused="off"):
    """The :func:`ffd_level_loss` objective as an ``engine.optimizer.Objective``.

    The scalar loss (and its ``value_and_grad``) is :func:`ffd_level_loss`
    verbatim — the first-order path through this wrapper is bit-identical
    to calling the loss directly.  When the similarity is the canonical
    ``"ssd"`` (``mean((warped - fixed)**2)``) and the level is unfused, the
    objective additionally exposes the least-squares *residual* form
    ``r(p) = (warped - fixed).ravel()`` plus the standalone regulariser
    term — what ``optimizer="gauss_newton"`` linearises for its matrix-free
    ``J^T J`` products (on the XLA-differentiable BSI graph: forward-mode
    ``jax.linearize`` cannot enter the analytic custom-VJP adjoint, which
    stays on the gradient path only).  Any other similarity (including callables and the fused
    megakernel, whose partial-sum accumulator never materialises the
    residual volume) yields a scalar-only objective, which the Gauss-Newton
    step rejects with a clear error.
    """
    loss_fn = ffd_level_loss(
        f, mov, tile=tile, bending_weight=bending_weight, mode=mode,
        impl=impl, grad_impl=grad_impl, compute_dtype=compute_dtype,
        similarity=similarity, transform=transform, regularizer=regularizer,
        fused=fused)
    key, _ = resolve_similarity(similarity)
    if key != "ssd" or fused in ("on", True):
        return make_objective(loss_fn)

    vol_shape = f.shape
    tspec = resolve_transform(transform)
    gshape = ffd.grid_shape_for_volume(vol_shape, tile)
    reg = regularizer_term(regularizer, grid_shape=gshape, tile=tile,
                           bending_weight=bending_weight)
    fixed32 = f.astype(jnp.float32)

    def residual_fn(p):
        # grad_impl is pinned to "xla" here: Gauss-Newton linearises the
        # residual with jax.linearize (forward mode), and the analytic
        # adjoint is a custom_vjp with no JVP rule.  The forward values are
        # identical either way — grad_impl only swaps the backward graph —
        # so the gradient path (obj.vg, above) keeps the configured adjoint.
        disp = dense_displacement(tspec, p, tile, vol_shape, mode=mode,
                                  impl=impl, grad_impl="xla",
                                  compute_dtype=compute_dtype)
        warped = ffd.warp_volume(mov, disp, compute_dtype=compute_dtype)
        return (warped.astype(jnp.float32) - fixed32).ravel()

    return make_objective(loss_fn, residual_fn=residual_fn, reg_fn=reg)


def ffd_pipeline(fixed, moving, *, tile, levels, iters, lr, bending_weight,
                 mode, impl, grad_impl="xla", compute_dtype=None,
                 similarity="ssd", transform="displacement",
                 regularizer="none", stop=None, fused="off",
                 optimizer="adam"):
    """Pure multi-level FFD registration of ONE ``(fixed, moving)`` pair.

    Traceable end-to-end (no timing, no host sync): the levels unroll into
    the trace and each level's inner loop is a ``lax.scan``
    (``engine.loop.optimize_scan``) — or, with a resolved
    ``ConvergenceConfig`` as ``stop``, the early-stopped ``lax.while_loop``
    (``engine.convergence.optimize_until``), under which ``vmap``ped lanes
    freeze as they converge and the level exits when the last lane is done.
    ``optimizer`` is a registered name or spec (``engine.optimizer``;
    default ``"adam"``, bit-identical to the pre-registry pipeline) — the
    optimiser state restarts fresh at each level (the grid changes shape
    between levels, so curvature history cannot carry across).  Returns
    ``(warped, phi, level_losses)``; with ``stop`` set, ``(warped, phi,
    level_losses, level_steps)`` where ``level_steps[l]`` is the optimiser
    steps level ``l`` actually ran.
    """
    pyramid = [(fixed, moving)]
    for _ in range(levels - 1):
        f, m = pyramid[-1]
        pyramid.append((ffd.downsample2(f), ffd.downsample2(m)))
    pyramid = pyramid[::-1]  # coarse -> fine

    phi = None
    finals = []
    steps = []
    for f, m in pyramid:
        gshape = ffd.grid_shape_for_volume(f.shape, tile)
        phi = (jnp.zeros(gshape + (3,), jnp.float32) if phi is None
               else ffd.upsample_grid(phi, gshape))
        obj = ffd_level_objective(f, m, tile=tile,
                                  bending_weight=bending_weight,
                                  mode=mode, impl=impl, grad_impl=grad_impl,
                                  compute_dtype=compute_dtype,
                                  similarity=similarity, transform=transform,
                                  regularizer=regularizer, fused=fused)
        if stop is None:
            phi, trace = optimize_scan(obj, phi, optimizer=optimizer,
                                       iters=iters, lr=lr)
        else:
            phi, trace, taken = optimize_until(obj, phi, optimizer=optimizer,
                                               stop=stop, lr=lr)
            steps.append(taken)
        finals.append(trace[-1])

    disp = dense_displacement(transform, phi, tile, fixed.shape, mode=mode,
                              impl=impl, grad_impl=grad_impl)
    warped = ffd.warp_volume(moving, disp)
    if stop is None:
        return warped, phi, jnp.stack(finals)
    return warped, phi, jnp.stack(finals), jnp.stack(steps)


@functools.lru_cache(maxsize=32)
def _compiled_batch(vol_shape, options, mesh=None):
    """One compiled program per (shape, options, mesh).

    ``options`` is a *resolved* ``RegistrationOptions`` (concrete
    mode/impl/grad_impl, canonical similarity key, resolved ``stop``) — the
    sole configuration cache key.  ``mesh`` is part of the key too
    (``jax.sharding.Mesh`` hashes by devices + axis names), so single-device
    and pod-sharded callers never collide, and two meshes over the same
    devices share a compile.  The early-stopped while-loop program and the
    fixed-length scan program differ through ``options.stop``."""
    del vol_shape  # cache key only; jax re-traces on new shapes anyway
    o = options

    def single(f, m):
        return ffd_pipeline(f, m, tile=o.tile, levels=o.levels,
                            iters=o.iters, lr=o.lr,
                            bending_weight=o.bending_weight,
                            mode=o.mode, impl=o.impl, grad_impl=o.grad_impl,
                            compute_dtype=o.compute_dtype,
                            similarity=o.similarity, transform=o.transform,
                            regularizer=o.regularizer, stop=o.stop,
                            fused=o.fused, optimizer=o.optimizer)

    if mesh is None:
        return jax.jit(jax.vmap(single))
    from repro.engine.shard import compile_sharded_batch

    return compile_sharded_batch(jax.vmap(single), mesh)


def register_batch(fixed, moving, *, options=None, tile=UNSET, levels=UNSET,
                   iters=UNSET, lr=UNSET, bending_weight=UNSET, mode=UNSET,
                   impl=UNSET, grad_impl=UNSET, compute_dtype=UNSET,
                   similarity=UNSET, transform=UNSET, regularizer=UNSET,
                   mesh=None, stop=UNSET, optimizer=UNSET):
    """Register a batch of volume pairs in a single jitted program.

    Args:
      fixed, moving: ``(B, X, Y, Z)`` stacks of volume pairs (B >= 1).
      options: a ``repro.core.RegistrationOptions`` — the preferred way to
        configure the run; the remaining keyword arguments are the legacy
        per-field spelling (as ``core.registration.ffd_register``), kept
        working through a deprecation shim and bit-identical to the
        equivalent ``options=``.  ``mode``/``impl``/``grad_impl`` default to
        ``"auto"`` — the ``engine.autotune`` winner for this ``(grid_shape,
        tile)`` under the chosen ``similarity``'s joint forward+backward
        workload (the adjoint axis picks between XLA autodiff and the
        analytic gather-only custom VJP).  ``compute_dtype`` (e.g.
        ``"bfloat16"``) runs BSI + warp in reduced precision with fp32
        params/adjoint accumulation.  ``similarity`` is a registered name
        (``"ssd" | "ncc" | "lncc" | "nmi"``) or a loss callable.
        ``transform`` (``"displacement" | "velocity"`` or a
        ``repro.core.transform`` spec) picks the deformation model —
        ``"velocity"`` yields diffeomorphic, fold-free warps; ``regularizer``
        (``"none" | "bending"`` or a ``repro.core.regularizer`` spec) picks
        the smoothness term.  ``optimizer`` (``"adam" | "lbfgs" |
        "gauss_newton"`` or an ``engine.optimizer`` spec) picks the per-level
        optimisation loop — the default ``"adam"`` is bit-identical to the
        pre-registry engine; ``"gauss_newton"`` requires
        ``similarity="ssd"``.
      mesh: optional ``jax.sharding.Mesh`` (see
        ``engine.shard.make_registration_mesh``) — the batch axis shards
        over the mesh's data axes (``REGISTRATION_RULES``) and every device
        runs the one-device program on its rows (``shard_map``).
        Non-divisible batches are padded (repeating the last pair) and
        stripped on return, so results are identical to the unsharded path
        for any B.  Deliberately *not* an options field:
        it names physical devices, so it would poison option-keyed caches.
      stop: optional ``ConvergenceConfig`` — run each pyramid level as an
        early-stopped ``lax.while_loop`` instead of a fixed-``iters`` scan
        (``stop.max_iters`` defaults to ``iters``).  Converged pairs (and
        ``pad_batch`` filler lanes) freeze — their updates are masked and
        their best-visited params are returned — and the level exits as
        soon as the *last* lane converges, so a batch of easy pairs
        finishes in a fraction of the budget.  Note the SPMD cost model:
        until that exit, frozen lanes still execute the (masked) BSI work,
        so a mixed batch's wall-clock is set by its slowest pair — the
        ``steps`` array the result gains counts optimiser steps per pair
        (quality/accounting), not wall-clock saved.  ``stop=None``
        (default) is the fixed-iteration pipeline, bit-identical to not
        passing ``stop``.

    Returns a :class:`BatchRegistrationResult`; ``warped[b]`` matches what
    per-pair ``ffd_register`` produces for pair ``b``.
    """
    fixed = jnp.asarray(fixed, jnp.float32)
    moving = jnp.asarray(moving, jnp.float32)
    if fixed.ndim != 4:
        raise ValueError(
            f"register_batch expects (B, X, Y, Z) stacks, got {fixed.shape}; "
            "use ffd_register for a single pair")
    if fixed.shape[0] == 0:
        raise ValueError(
            "register_batch got an empty batch (B=0); supply at least one "
            "(fixed, moving) pair")
    if fixed.shape != moving.shape:
        raise ValueError(f"shape mismatch: {fixed.shape} vs {moving.shape}")
    opts = merge_legacy_options(
        "register_batch", options,
        dict(tile=tile, levels=levels, iters=iters, lr=lr,
             bending_weight=bending_weight, mode=mode, impl=impl,
             grad_impl=grad_impl, compute_dtype=compute_dtype,
             similarity=similarity, transform=transform,
             regularizer=regularizer, stop=stop, optimizer=optimizer))

    from repro.engine.autotune import resolve_options

    # NOTE: the autotune workload pins stop=None — the winner is measured on
    # the fixed-iteration forward+backward BSI step, which is exactly the
    # per-step work an early-stopped loop runs (stopping changes how many
    # steps execute, never which kernel each step should use).
    opts = resolve_options(opts, fixed.shape[1:])

    t0 = time.perf_counter()
    b = fixed.shape[0]
    if mesh is not None:
        from repro.engine.shard import batch_multiple, pad_batch

        fixed, b = pad_batch(fixed, batch_multiple(mesh))
        moving, _ = pad_batch(moving, batch_multiple(mesh))
    misses = _compiled_batch.cache_info().misses
    fn = _compiled_batch(fixed.shape[1:], opts, mesh)
    compiled = _compiled_batch.cache_info().misses > misses
    stop = opts.stop
    with TraceAnnotation("repro.level"):
        out = fn(fixed, moving)
    warped, phi, losses = out[:3]
    steps = out[3] if stop is not None else None
    with TraceAnnotation("repro.sync"):
        jax.block_until_ready(warped)
    seconds = time.perf_counter() - t0
    if mesh is not None:  # strip the pad rows (see engine.shard.pad_batch)
        warped, phi, losses = warped[:b], phi[:b], losses[:b]
        steps = steps[:b] if steps is not None else None
    return BatchRegistrationResult(warped, phi, losses, seconds,
                                   compiled=compiled, steps=steps)


# ---------------------------------------------------------------------------
# Resumable chunked execution — the continuous-batching substrate.
#
# ``register_batch`` runs each pyramid level to completion inside one
# program, so a new pair can only join at batch boundaries.  The serving
# scheduler (``engine.serve``) instead drives each level in fixed-size
# *chunks* of masked optimiser steps over a fixed-width lane array: after
# every chunk the full optimiser state returns to the host, converged lanes
# are harvested and queued pairs spliced into the freed slots.  The per-step
# arithmetic is ``engine.convergence.optimize_plateau_step`` — the exact body
# of ``optimize_until`` — so a lane's trajectory is step-for-step identical
# to the uninterrupted while-loop no matter how chunks and lane recycling
# slice it.  The optimiser state nests under the lane dict's ``"opt"`` key
# (``engine.optimizer.init_state``), so splicing and masking are plain
# ``jax.tree.map`` over the lane pytree for every registered optimiser.
# ---------------------------------------------------------------------------


def level_vol_shapes(vol_shape, levels):
    """Per-level volume shapes, coarse -> fine (``downsample2`` geometry)."""
    shapes = [tuple(int(s) for s in vol_shape)]
    for _ in range(int(levels) - 1):
        shapes.append(tuple((s - s % 2) // 2 for s in shapes[-1]))
    return shapes[::-1]


def _lane_obj(f, m, options):
    o = options
    return ffd_level_objective(
        f, m, tile=o.tile, bending_weight=o.bending_weight, mode=o.mode,
        impl=o.impl, grad_impl=o.grad_impl, compute_dtype=o.compute_dtype,
        similarity=o.similarity, transform=o.transform,
        regularizer=o.regularizer, fused=o.fused)


@functools.lru_cache(maxsize=128)
def compile_level_init(lvl_shape, options):
    """Jitted per-pair lane-state initialiser for one pyramid level.

    ``(phi0, fixed, moving) -> state`` with ``fixed``/``moving`` already at
    this level's resolution (``lvl_shape``) and ``phi0`` the level's starting
    grid (zeros at the coarsest level, the upsampled previous-level grid
    after a migration).  The returned state leaves are unbatched — the
    scheduler splices them into lane ``i`` of its stacked arrays with
    ``jax.tree.map(lambda a, s: a.at[i].set(s), state, lane)``.  Matches
    ``optimize_until``'s init exactly: the gradient at ``phi0`` seeds step 1
    and the initial loss seeds the best-so-far (so a pair the optimiser can
    only make worse retires with its starting params).  The fresh optimiser
    state for ``options.optimizer`` nests under the ``"opt"`` key.
    """
    del lvl_shape  # cache key only; jit re-traces on new shapes anyway
    return jax.jit(functools.partial(_lane_init, options=options))


def _lane_init(phi, f, m, *, options):
    loss0, g0 = _lane_obj(f, m, options).vg(phi)
    i0 = jnp.zeros((), jnp.int32)
    loss0 = loss0.astype(jnp.float32)
    return dict(phi=phi, opt=init_state(options.optimizer, phi), g=g0,
                k=i0, since=i0, best=loss0, best_p=phi, loss=loss0,
                active=jnp.ones((), jnp.bool_))


@functools.lru_cache(maxsize=128)
def compile_level_splice(lvl_shape, options, mesh=None):
    """Jitted lane admission: init one pair AND scatter it into lane ``i``.

    ``(state, F, M, i, phi0, f, m) -> (state, F, M)`` — the fused form of
    ``compile_level_init`` + a per-leaf ``.at[i].set``: one program dispatch
    admits a pair, where leaf-by-leaf host splicing would pay ~a dozen
    dispatches (profiled at ~10ms/admission on CPU, a third of the serving
    wall-clock at small volume sizes).  The stacked operands are donated on
    accelerator backends — the scheduler threads them through every call.
    With ``mesh`` the stacked operands are lane-sharded and the program runs
    per device (``engine.shard.compile_lane_program``): every device inits
    the pair, and the one holding lane ``i`` keeps it.
    """
    del lvl_shape  # cache key only

    def splice(state, F, M, i, phi, f, m):
        lane = _lane_init(phi, f, m, options=options)
        if mesh is not None:
            from repro.engine.shard import local_lane

            i = local_lane(i, F.shape[0], mesh)

        def put(a, s):
            return a.at[i].set(s, mode="drop")

        return jax.tree.map(put, state, lane), put(F, f), put(M, m)

    donate = (0, 1, 2) if jax.default_backend() != "cpu" else ()
    if mesh is None:
        return jax.jit(splice, donate_argnums=donate)
    from repro.engine.shard import compile_lane_program

    return compile_lane_program(splice, mesh, (True,) * 3 + (False,) * 4,
                                donate)


@functools.lru_cache(maxsize=128)
def compile_level_chunk(lvl_shape, options, chunk, mesh=None):
    """Jitted ``(state, fixed, moving) -> state``: one chunk of a level.

    Runs ``chunk`` masked optimiser steps (``options.optimizer``) over a
    ``(W, ...)`` lane array at this level's resolution.  Each step
    re-evaluates every lane's liveness — ``active`` (the slot holds a real
    pair) AND ``level_live`` (budget left, patience window open, exactly
    ``optimize_until``'s ``cond``) — and freezes dead lanes by selecting
    their old state, the same per-lane masking the ``while_loop`` batching
    rule applies.  A lane retired mid-chunk therefore holds exactly its
    solo-run result when the state returns to the host, and a freshly
    spliced lane starts its trajectory wherever the chunk boundary fell.
    Rejected second-order steps leave a lane's iterate numerically in place
    (``engine.optimizer.opt_step``), indistinguishable from the masking —
    either way the lane's next live step resumes its exact trajectory.  The
    state argument is donated on accelerator backends (the scheduler
    threads it through every call).

    With ``options.stop`` unset the masking reduces to the fixed-``iters``
    budget and ``tol=-inf`` makes every accepted step "improve", so
    ``best_p`` tracks the current params and the result matches
    ``optimize_scan``.  With ``mesh`` the lanes are sharded and each device
    steps its own (``engine.shard.compile_lane_program``).
    """
    del lvl_shape  # cache key only
    o = options
    stop = o.stop
    tol = jnp.float32(stop.tol) if stop is not None else -jnp.inf

    def lane(state, f, m):
        obj = _lane_obj(f, m, o)

        def one(s, _):
            live = jnp.logical_and(
                s["active"],
                level_live(s["k"], s["since"], stop=stop, iters=o.iters))
            k, p, opt, g, loss, since, best, best_p = optimize_plateau_step(
                obj, o.optimizer, s["k"], s["phi"], s["opt"], s["g"],
                s["loss"], s["since"], s["best"], s["best_p"],
                tol=tol, lr=o.lr)
            new = dict(phi=p, opt=opt, g=g, k=k, since=since, best=best,
                       best_p=best_p, loss=loss, active=s["active"])
            return jax.tree.map(
                lambda n, old: jnp.where(live, n, old), new, s), None

        s, _ = jax.lax.scan(one, state, None, length=int(chunk))
        return s

    donate = (0,) if jax.default_backend() != "cpu" else ()
    if mesh is None:
        return jax.jit(jax.vmap(lane), donate_argnums=donate)
    from repro.engine.shard import compile_lane_program

    return compile_lane_program(jax.vmap(lane), mesh, (True,) * 3, donate)


@functools.lru_cache(maxsize=64)
def compile_finish(vol_shape, options):
    """Jitted ``(phi, moving) -> warped``: finest grid -> registered volume.

    The same final expansion+warp as ``ffd_pipeline`` (full-resolution BSI of
    the finest-level control grid, then one trilinear warp of the original
    moving volume).
    """
    o = options

    def fin(phi, moving):
        disp = dense_displacement(o.transform, phi, o.tile, vol_shape,
                                  mode=o.mode, impl=o.impl,
                                  grad_impl=o.grad_impl)
        return ffd.warp_volume(moving, disp)

    return jax.jit(fin)
