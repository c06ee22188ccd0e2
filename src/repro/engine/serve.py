"""Continuous-batching registration serving — an async queue over lane arrays.

``register_batch`` is the throughput primitive for *synchronous* workloads:
N pairs arrive together, one program registers them together, everyone waits
for the slowest pair.  A registration service sees neither of those things —
requests arrive singly (Poisson-ish), with mixed difficulty, and each caller
cares about its own latency.  This module transplants the continuous
batching idea from LLM serving (retire a finished sequence's slot and splice
the next prompt in, instead of waiting for the whole batch) onto the
registration loop, where the per-lane convergence mask of the early-stopped
optimiser loop (``engine.convergence``) is the retire signal:

* Requests are **bucketed by volume shape**: one set of compiled programs
  per bucket (reusing the module-level runner caches in ``engine.batch``),
  so a mixed-geometry stream pays one compile per distinct shape, ever.
* Inside a bucket, each pyramid level is a **stage**: a fixed-width lane
  array of optimiser state driven in ``chunk``-step slices by
  ``engine.batch.compile_level_chunk``.  Stage arrays — rather than a
  per-lane level switch — are the LLM prefill/decode disaggregation move:
  under ``vmap`` a ``lax.switch`` would execute *every* level's branch for
  *every* lane, so one coarse lane would pay fine-level cost; separate
  per-level programs keep each lane paying exactly its level's price.
* After every chunk the state returns to the host; lanes whose convergence
  mask retired mid-chunk are harvested (their state froze at their own
  stopping point, so the result is step-for-step identical to a solo run)
  and queued pairs are **spliced into the freed lanes** — lane recycling.
  Harvested lanes migrate coarse -> fine (grid upsampling, exactly
  ``ffd_register``'s pyramid promotion) and finish with the full-resolution
  warp.

The scheduler is deliberately synchronous and single-threaded — ``step()``
runs one scheduling round, and the caller (the asyncio facade
:class:`AsyncRegistrationService`, the Poisson load generator in
``benchmarks/serving_bench.py``, or a test with a fake clock) owns the
drive loop.  Admission control (``max_queue`` -> :class:`QueueFull`) and
deadlines (``timeout`` -> :class:`RegistrationTimeout`) fail fast and
clean instead of hanging.

The lane programs inherit the full ``RegistrationOptions`` surface through
``engine.batch``'s option-keyed compiles — including the ``transform=``
(diffeomorphic velocity fields), ``regularizer=`` (analytic bending
energy) and ``optimizer=`` (second-order L-BFGS / Gauss-Newton) axes, which
change only the per-lane loss/step/finish programs, not the scheduling
mechanics: the optimiser state nests under the lane dict's ``"opt"`` key and
splices/freezes like any other leaf.
"""
from __future__ import annotations

import asyncio
import collections
import dataclasses
import functools
import itertools
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import ffd
from repro.core.options import RegistrationOptions
from repro.engine.batch import (compile_finish, compile_level_chunk,
                                compile_level_splice, level_vol_shapes)
from repro.engine.optimizer import init_state

__all__ = ["QueueFull", "RegistrationTimeout", "ServeResult", "ServeStats",
           "RequestHandle", "RegistrationScheduler",
           "AsyncRegistrationService"]


class QueueFull(RuntimeError):
    """Admission refused: the scheduler's queue is at ``max_queue``.

    Backpressure is the caller's signal to shed load or retry later —
    queueing unboundedly would just convert overload into timeouts.
    """


class RegistrationTimeout(TimeoutError):
    """The request's deadline passed before a lane could take it."""


@dataclasses.dataclass
class ServeResult:
    """One completed registration, as the scheduler hands it back."""

    warped: Any            # (X, Y, Z) registered moving volume
    params: Any            # finest-level control grid (gx, gy, gz, 3)
    losses: list           # final loss per pyramid level (coarse -> fine)
    steps: list            # optimiser steps actually run per level
    seconds: float         # submit -> complete latency (scheduler clock)
    recycled: bool = False # True if any lane was spliced mid-flight


@dataclasses.dataclass
class ServeStats:
    submitted: int = 0
    completed: int = 0
    timed_out: int = 0
    rejected: int = 0      # QueueFull admissions
    recycled: int = 0      # requests that entered a mid-flight stage
    buckets: int = 0       # distinct volume shapes seen
    compiles: int = 0      # distinct compiled stage programs acquired
    chunks: int = 0        # chunk programs dispatched


@dataclasses.dataclass
class RequestHandle:
    """The caller's view of a submitted request.

    Poll ``done`` while driving ``scheduler.step()`` (or let
    :class:`AsyncRegistrationService` do both); then ``result()`` returns
    the :class:`ServeResult` or raises the request's failure
    (:class:`RegistrationTimeout`).
    """

    id: int
    submitted_at: float
    done: bool = False
    _result: Any = None
    _error: Any = None

    def result(self) -> ServeResult:
        if not self.done:
            raise RuntimeError(
                f"request {self.id} is still in flight; drive "
                "scheduler.step() (or use AsyncRegistrationService)")
        if self._error is not None:
            raise self._error
        return self._result


@dataclasses.dataclass
class _Request:
    handle: RequestHandle
    moving: Any                  # full-resolution, for the final warp
    pyramid: Any                 # ((f, m) per level, coarse -> fine)
    deadline: Any                # absolute clock time or None
    phi: Any = None              # carried control grid between levels
    losses: list = dataclasses.field(default_factory=list)
    steps: list = dataclasses.field(default_factory=list)
    recycled: bool = False


class _Stage:
    """One pyramid level's lane array inside a bucket."""

    def __init__(self, level):
        self.level = level
        self.queue = collections.deque()   # _Request waiting to enter
        self.state = None                  # stacked lane state (or None)
        self.fixed = None                  # (W, *lvl_shape)
        self.moving = None
        self.lanes = None                  # list[_Request | None]

    def any_active(self):
        return self.lanes is not None and any(
            r is not None for r in self.lanes)


class _Bucket:
    """All scheduling state for one volume shape."""

    def __init__(self, vol_shape, options):
        self.vol_shape = vol_shape
        self.options = options             # resolved for this shape
        self.lvl_shapes = level_vol_shapes(vol_shape, options.levels)
        self.stages = [_Stage(i) for i in range(options.levels)]


@functools.lru_cache(maxsize=64)
def _pyramid_fn(vol_shape, levels):
    """Jitted ``(f, m) -> ((f_l, m_l), ...)`` pyramid, coarse -> fine."""
    del vol_shape  # cache key only

    def build(f, m):
        levels_fm = [(f, m)]
        for _ in range(levels - 1):
            f, m = levels_fm[-1]
            levels_fm.append((ffd.downsample2(f), ffd.downsample2(m)))
        return tuple(levels_fm[::-1])

    return jax.jit(build)


@functools.lru_cache(maxsize=64)
def _upsample_fn(gshape):
    return jax.jit(lambda p: ffd.upsample_grid(p, gshape))


def _host_live(k, since, stop, iters):
    if stop is None:
        return k < iters
    return (k < stop.max_iters) and (since < stop.patience)


class RegistrationScheduler:
    """Continuous-batching scheduler for registration requests.

    Args:
      options: the ``RegistrationOptions`` every request runs under (the
        service analogue of a model checkpoint: one configuration per
        scheduler; buckets only vary by volume shape).
      lanes: lane-array width per stage — the in-flight pair capacity of
        each pyramid level.  With ``mesh=``, must be a multiple of
        ``engine.shard.batch_multiple(mesh)``.
      chunk: optimiser steps per scheduling slice.  Smaller -> finer recycling
        granularity (lower queue latency) but more host round-trips;
        ``chunk`` never affects results, only when the host looks.
      max_queue: admission bound on waiting requests (across buckets);
        ``submit`` raises :class:`QueueFull` beyond it.
      timeout: default per-request seconds from submit until the request
        must have *completed*; expired requests fail with
        :class:`RegistrationTimeout` at the next round boundary (a round's
        device work is never interrupted mid-chunk).
      mesh: optional ``jax.sharding.Mesh`` — lane arrays shard batch-over-
        data (``engine.shard.lane_sharding``), one chunk program driving
        all devices.
      clock: injectable monotonic-seconds source (tests use a fake clock to
        exercise deadlines deterministically).
    """

    def __init__(self, options=None, *, lanes=8, chunk=4, max_queue=64,
                 timeout=None, mesh=None, clock=time.monotonic):
        if options is None:
            options = RegistrationOptions()
        if not isinstance(options, RegistrationOptions):
            raise TypeError(
                f"options must be a RegistrationOptions, got "
                f"{type(options).__name__}")
        if lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {lanes}")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        if mesh is not None:
            from repro.engine.shard import batch_multiple

            mult = batch_multiple(mesh)
            if lanes % mult:
                raise ValueError(
                    f"lanes={lanes} must be a multiple of the mesh's batch "
                    f"multiple ({mult}) for an even lane split")
        self.options = options
        self.lanes = int(lanes)
        self.chunk = int(chunk)
        self.max_queue = int(max_queue)
        self.timeout = timeout
        self.mesh = mesh
        self.clock = clock
        self.stats = ServeStats()
        self._buckets: dict = {}
        self._ids = itertools.count()
        self._queued = 0              # waiting (not yet in a lane)
        self._inflight = 0            # in a lane somewhere
        self._programs: set = set()   # distinct stage-program keys acquired

    # -- submission ---------------------------------------------------------

    def submit(self, fixed, moving, *, timeout=None) -> RequestHandle:
        """Queue one ``(fixed, moving)`` pair; returns immediately.

        Raises :class:`QueueFull` when ``max_queue`` requests are already
        waiting.  The pair's pyramid is built (on device) at submission so
        admission into a freed lane is a pure splice.
        """
        fixed = jnp.asarray(fixed, jnp.float32)
        moving = jnp.asarray(moving, jnp.float32)
        if fixed.ndim != 3 or fixed.shape != moving.shape:
            raise ValueError(
                "submit expects one (X, Y, Z) pair of equal shapes, got "
                f"{fixed.shape} vs {moving.shape}")
        if self._queued >= self.max_queue:
            self.stats.rejected += 1
            raise QueueFull(
                f"{self._queued} requests waiting (max_queue="
                f"{self.max_queue}); retry later or raise max_queue")
        bucket = self._bucket_for(fixed.shape)
        now = self.clock()
        timeout = self.timeout if timeout is None else timeout
        handle = RequestHandle(id=next(self._ids), submitted_at=now)
        req = _Request(
            handle=handle, moving=moving,
            pyramid=_pyramid_fn(fixed.shape, bucket.options.levels)(
                fixed, moving),
            deadline=None if timeout is None else now + float(timeout))
        bucket.stages[0].queue.append(req)
        self._queued += 1
        self.stats.submitted += 1
        return handle

    def _bucket_for(self, vol_shape) -> _Bucket:
        bucket = self._buckets.get(vol_shape)
        if bucket is None:
            from repro.engine.autotune import resolve_options

            bucket = _Bucket(vol_shape, resolve_options(self.options,
                                                        vol_shape))
            self._buckets[vol_shape] = bucket
            self.stats.buckets += 1
        return bucket

    # -- the scheduling round ----------------------------------------------

    def step(self) -> int:
        """One scheduling round over every bucket; returns completions.

        Per stage, coarse -> fine: expire dead queue entries, splice queued
        pairs into free lanes, run one ``chunk`` of masked optimiser
        steps, then harvest lanes whose convergence mask retired — migrating them to
        the next stage's queue (so a pair can traverse one stage per round)
        or finishing with the full-resolution warp.
        """
        done = 0
        for bucket in self._buckets.values():
            ran = []
            # dispatch every stage's chunk before the first (blocking)
            # harvest: the chunks execute asynchronously, so the coarse and
            # fine programs overlap instead of serialising on each sync
            for stage in bucket.stages:
                self._expire(stage)
                self._fill(bucket, stage)
                if not stage.any_active():
                    continue
                key = (bucket.lvl_shapes[stage.level], bucket.options,
                       self.chunk)
                if key not in self._programs:
                    self._programs.add(key)
                    self.stats.compiles += 1
                fn = compile_level_chunk(*key)
                stage.state = fn(stage.state, stage.fixed, stage.moving)
                self.stats.chunks += 1
                ran.append(stage)
            for stage in ran:
                done += self._harvest(bucket, stage)
        return done

    def run_until_idle(self, max_rounds=100_000) -> int:
        """Drive ``step()`` until no request is waiting or in flight."""
        done = 0
        for _ in range(max_rounds):
            if not self.pending:
                return done
            done += self.step()
        raise RuntimeError(
            f"still {self._queued} queued / {self._inflight} in flight "
            f"after {max_rounds} rounds — is the clock advancing?")

    @property
    def pending(self) -> int:
        """Requests not yet completed (waiting + in a lane)."""
        return self._queued + self._inflight

    # -- internals ----------------------------------------------------------

    def _expire(self, stage):
        now = self.clock()
        keep = collections.deque()
        for req in stage.queue:
            if req.deadline is not None and now >= req.deadline:
                if stage.level == 0:  # migration queues hold in-flight work
                    self._queued -= 1
                else:
                    self._inflight -= 1
                self.stats.timed_out += 1
                req.handle._error = RegistrationTimeout(
                    f"request {req.handle.id} expired after "
                    f"{now - req.handle.submitted_at:.3f}s waiting for a "
                    "lane")
                req.handle.done = True
            else:
                keep.append(req)
        stage.queue = keep

    def _alloc(self, bucket, stage, lvl_shape):
        """Allocate the stage's stacked lane arrays (all lanes inactive)."""
        W = self.lanes
        gshape = ffd.grid_shape_for_volume(lvl_shape, bucket.options.tile)
        grid = gshape + (3,)
        # a fresh buffer per leaf: the splice and chunk programs donate the
        # state, and one buffer behind two leaves cannot be donated twice
        zg = functools.partial(jnp.zeros, (W,) + grid, jnp.float32)
        zi = functools.partial(jnp.zeros, (W,), jnp.int32)
        zf = functools.partial(jnp.zeros, (W,), jnp.float32)
        # the optimiser state's lane template comes from the registry, so a
        # new optimiser's lanes allocate (and shard) without touching the
        # scheduler: every leaf is stacked to a leading (W, ...) lane axis
        opt = jax.tree.map(
            lambda a: jnp.zeros((W,) + a.shape, a.dtype),
            init_state(bucket.options.optimizer, jnp.zeros(grid,
                                                           jnp.float32)))
        state = dict(phi=zg(), opt=opt, g=zg(), best_p=zg(), k=zi(),
                     since=zi(), best=zf(), loss=zf(),
                     active=jnp.zeros((W,), jnp.bool_))
        stage.fixed = jnp.zeros((W,) + lvl_shape, jnp.float32)
        stage.moving = jnp.zeros((W,) + lvl_shape, jnp.float32)
        stage.lanes = [None] * W
        if self.mesh is not None:
            from repro.engine.shard import lane_sharding

            sh = lane_sharding(self.mesh)
            state = jax.device_put(state, sh)
            stage.fixed = jax.device_put(stage.fixed, sh)
            stage.moving = jax.device_put(stage.moving, sh)
        stage.state = state

    def _fill(self, bucket, stage):
        if not stage.queue:
            return
        lvl_shape = bucket.lvl_shapes[stage.level]
        splice = compile_level_splice(lvl_shape, bucket.options)
        mid_flight = stage.any_active()
        if stage.lanes is None:
            self._alloc(bucket, stage, lvl_shape)
        for i, slot in enumerate(stage.lanes):
            if slot is not None:
                continue
            if not stage.queue:
                break
            req = stage.queue.popleft()
            f, m = req.pyramid[stage.level]
            if req.phi is None:  # coarsest level starts from the zero grid
                gshape = ffd.grid_shape_for_volume(lvl_shape,
                                                   bucket.options.tile)
                req.phi = jnp.zeros(gshape + (3,), jnp.float32)
            stage.state, stage.fixed, stage.moving = splice(
                stage.state, stage.fixed, stage.moving, i, req.phi, f, m)
            stage.lanes[i] = req
            if stage.level == 0:
                self._queued -= 1
                self._inflight += 1
            if mid_flight and not req.recycled:
                req.recycled = True
                self.stats.recycled += 1

    def _harvest(self, bucket, stage) -> int:
        opts = bucket.options
        host = jax.device_get({k: stage.state[k]
                               for k in ("k", "since", "active", "best")})
        done = 0
        retired = []
        for i, req in enumerate(stage.lanes):
            if req is None or not bool(host["active"][i]):
                continue
            if _host_live(int(host["k"][i]), int(host["since"][i]),
                          opts.stop, opts.iters):
                continue
            # retired: its carry froze at the stopping point, so best_p is
            # exactly the solo optimize_until result
            req.phi = stage.state["best_p"][i]
            req.losses.append(float(host["best"][i]))
            req.steps.append(int(host["k"][i]))
            stage.lanes[i] = None
            retired.append(i)
            if stage.level + 1 < opts.levels:
                next_g = ffd.grid_shape_for_volume(
                    bucket.lvl_shapes[stage.level + 1], opts.tile)
                req.phi = _upsample_fn(next_g)(req.phi)
                bucket.stages[stage.level + 1].queue.append(req)
            else:
                self._finish(bucket, req)
                done += 1
        if retired:  # one fused clear instead of a dispatch per lane
            stage.state["active"] = stage.state["active"].at[
                jnp.asarray(retired)].set(False)
        return done

    def _finish(self, bucket, req):
        warped = compile_finish(bucket.vol_shape, bucket.options)(
            req.phi, req.moving)
        handle = req.handle
        handle._result = ServeResult(
            warped=warped, params=req.phi, losses=req.losses,
            steps=req.steps,
            seconds=self.clock() - handle.submitted_at,
            recycled=req.recycled)
        handle.done = True
        self._inflight -= 1
        self.stats.completed += 1


class AsyncRegistrationService:
    """Asyncio facade: ``await service.register(fixed, moving)``.

    A thin drive loop over :class:`RegistrationScheduler` — concurrent
    ``register`` calls share the scheduler through a lock, each pumping
    ``step()`` (in the default executor, so the event loop stays live
    while the device works) until its own request completes.  Admission
    and deadline failures surface as the scheduler's exceptions.
    """

    def __init__(self, scheduler=None, **scheduler_kwargs):
        self.scheduler = (RegistrationScheduler(**scheduler_kwargs)
                          if scheduler is None else scheduler)
        self._lock = asyncio.Lock()

    async def register(self, fixed, moving, *, timeout=None) -> ServeResult:
        handle = self.scheduler.submit(fixed, moving, timeout=timeout)
        loop = asyncio.get_running_loop()
        while not handle.done:
            async with self._lock:
                if not handle.done:
                    await loop.run_in_executor(None, self.scheduler.step)
            await asyncio.sleep(0)  # let other registrations interleave
        return handle.result()
