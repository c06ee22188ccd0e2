"""Autotuner: pick the fastest BSI (mode, impl) for a (grid_shape, tile).

The paper's comparison matrix (§5) has no single winner: which algorithm
form is fastest depends on tile size, grid size and the backend (the
separable tensor-contraction form wins where matmul units dominate; the
lerp form wins where FMA-bound).  Instead of hardcoding ``mode=`` / ``impl=``
defaults in every caller, the engine benchmarks the available forms for the
configuration actually being registered and caches the winner:

* in-process memory cache, keyed by ``backend|grid|tile|channels``;
* an optional JSON disk cache (``$REPRO_AUTOTUNE_CACHE`` or
  ``~/.cache/repro/bsi_autotune.json``) so repeated process launches —
  benchmark runs, serving replicas — skip the measurement entirely.

The disk file is versioned (``SCHEMA_VERSION``): entries live under a
``{"__schema__": N, "entries": {...}}`` wrapper, and a file from another
schema — e.g. a pre-fused-axis cache — reads as a clean miss (re-benchmark
and rewrite), never a ``KeyError`` or a silently mis-dispatched choice.

Callers go through :func:`resolve_bsi`, which passes explicit choices
through untouched and only tunes the ``"auto"`` axes;
:func:`resolve_options` additionally races the fused level step
(``core.ffd.fused_warp_loss``) against the unfused winner when
``options.fused == "auto"`` (:func:`autotune_fused`).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.interpolate import GRAD_IMPLS, MODES, interpolate
from repro.core.similarity import resolve_similarity, similarity_token
from repro.core.transform import (VelocityTransform, resolve_transform,
                                  scaling_and_squaring, transform_token)
from repro.kernels.ops import PALLAS_MODES

__all__ = ["BsiChoice", "SCHEMA_VERSION", "autotune_bsi", "autotune_fused",
           "resolve_bsi", "resolve_options", "default_candidates",
           "default_grad_impls", "default_cache_path"]

JNP_CANDIDATES = tuple((m, "jnp") for m in sorted(MODES))
PALLAS_CANDIDATES = tuple((m, "pallas") for m in PALLAS_MODES)

# Disk-cache schema.  v2 added the fused level-step axis (BsiChoice.fused +
# the "|fused|" race entries) and moved entries under the versioned wrapper;
# v1 files (flat {key: choice} dicts) predate it and read as a clean miss.
# v3 added the matmul mode + the "matmul" adjoint to the candidate space:
# pre-matmul (v2) files pinned winners measured without the MXU form in the
# race, so they re-benchmark as a clean miss rather than silently excluding
# the new candidates.
SCHEMA_VERSION = 3


@dataclasses.dataclass(frozen=True)
class BsiChoice:
    mode: str
    impl: str
    us_per_call: float
    # adjoint implementation ("xla" = plain autodiff — the pre-custom-VJP
    # behaviour, and what legacy cache entries decode to)
    grad_impl: str = "xla"
    # fused level step ("on" = core.ffd.fused_warp_loss won the race for
    # this configuration; entries written by autotune_fused only)
    fused: str = "off"
    # candidates left out of the race, as ("mode/impl/grad_impl", reason)
    skipped: tuple = ()


# Share of the device's memory one candidate's compiled level step may take.
# The registration keeps its volumes, pyramid and optimiser state beside the
# step, and a program that only just fits alone fails to load: at phantom1 a
# 14.6 GiB step compiled for a 15.75 GiB v5e and then found 14.4 GiB free.
STEP_MEMORY_SHARE = 0.75
NO_AUTODIFF = ("a Pallas forward has no autodiff rule; it runs only under "
               "an analytic adjoint (grad_impl jnp, pallas or matmul)")


_MEM_CACHE: dict = {}


def default_cache_path() -> str:
    return os.environ.get("REPRO_AUTOTUNE_CACHE") or os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "bsi_autotune.json")


def default_candidates():
    """Forms worth benchmarking on the current backend.

    On CPU the Pallas kernels only run under ``interpret=True`` — a
    correctness path, orders of magnitude slower than the jnp forms — so
    they are excluded unless ``REPRO_AUTOTUNE_PALLAS=1`` forces them in.
    """
    cands = list(JNP_CANDIDATES)
    if jax.default_backend() != "cpu" or os.environ.get("REPRO_AUTOTUNE_PALLAS"):
        cands += list(PALLAS_CANDIDATES)
    return tuple(cands)


def default_grad_impls():
    """Adjoint implementations worth benchmarking on the current backend.

    ``xla`` (plain autodiff) and ``jnp`` (the analytic separable-transpose
    custom VJP) everywhere; the Pallas adjoint kernels — ``pallas`` (the
    separable sweeps) and ``matmul`` (the transposed MXU contraction) —
    join off-CPU (or with ``REPRO_AUTOTUNE_PALLAS=1``), same reasoning as
    :func:`default_candidates`.
    """
    impls = ["xla", "jnp"]
    if jax.default_backend() != "cpu" or os.environ.get("REPRO_AUTOTUNE_PALLAS"):
        impls += ["pallas", "matmul"]
    return tuple(impls)


def _key(grid_shape, tile, channels) -> str:
    g = "x".join(map(str, grid_shape))
    t = "x".join(map(str, tile))
    return f"{jax.default_backend()}|g{g}|t{t}|c{channels}"


def _load_disk(path) -> dict:
    """Best-effort read: a corrupt/stale/wrong-shape cache is a miss.

    A half-written or hand-edited ``bsi_autotune.json`` must trigger a clean
    re-benchmark (which then rewrites the file), never an unhandled
    ``JSONDecodeError`` — and so must a file written by another
    ``SCHEMA_VERSION`` (e.g. a pre-fused flat ``{key: choice}`` cache),
    whose entries would otherwise decode with the new axes silently filled
    by defaults measured under a different dispatch.
    """
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError):
        return {}
    if not isinstance(data, dict) or data.get("__schema__") != SCHEMA_VERSION:
        return {}
    entries = data.get("entries")
    return entries if isinstance(entries, dict) else {}


def _parse_choice(hit):
    """A malformed cache entry (missing/mistyped fields) is a miss."""
    try:
        choice = BsiChoice(str(hit["mode"]), str(hit["impl"]),
                           float(hit["us_per_call"]),
                           str(hit.get("grad_impl", "xla")),
                           str(hit.get("fused", "off")),
                           tuple((str(c), str(r))
                                 for c, r in hit.get("skipped", ())))
    except (KeyError, TypeError, ValueError, AttributeError):
        return None
    return choice if choice.fused in ("on", "off") else None


def _store_disk(path, key, choice) -> None:
    entries = _load_disk(path)
    entries[key] = dataclasses.asdict(choice)
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump({"__schema__": SCHEMA_VERSION, "entries": entries},
                      fh, indent=1, sort_keys=True)
        os.replace(tmp, path)  # atomic: concurrent tuners never corrupt it
    except OSError:
        pass  # cache is best-effort; tuning still returned in-process


def _is_out_of_memory(err) -> bool:
    """Whether ``err`` is XLA's out-of-memory error (device HBM or a
    kernel's VMEM), the one failure a candidate may be skipped for."""
    return (isinstance(err, jax.errors.JaxRuntimeError)
            and str(err).startswith("RESOURCE_EXHAUSTED"))


def _device_bytes_limit(dev):
    stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
    return (stats or {}).get("bytes_limit")


def _step_footprint(compiled) -> int:
    """Device bytes a compiled program holds while it runs."""
    ma = compiled.memory_analysis()
    return int(ma.temp_size_in_bytes + ma.argument_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes
               + ma.generated_code_size_in_bytes)


def _time_candidates(cands, args, dev, reps):
    """Compile, admit and time each ``(name, jitted_fn)``.

    Returns ``(timed, skipped)``: ``[(us, name)]`` and ``[(name, reason)]``.
    Only an out-of-memory error (at compile or at load) skips a candidate,
    as does a compiled step that would leave too little of the device for
    the rest of the registration (:data:`STEP_MEMORY_SHARE`); every other
    exception propagates.
    """
    limit = _device_bytes_limit(dev)
    timed, skipped = [], []
    for name, fn in cands:
        try:
            compiled = fn.lower(*args).compile()
            need = _step_footprint(compiled)
            if limit and need > STEP_MEMORY_SHARE * limit:
                skipped.append((name, (
                    f"needs {need / 2**30:.2f} GiB of the device's "
                    f"{limit / 2**30:.2f} GiB; a level step may take "
                    f"{STEP_MEMORY_SHARE:.0%}")))
                continue
            jax.block_until_ready(compiled(*args))  # load + warm up
        except jax.errors.JaxRuntimeError as e:
            if not _is_out_of_memory(e):
                raise
            skipped.append((name, str(e).splitlines()[0][:300]))
            continue
        times = []
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*args))
            times.append(time.perf_counter() - t0)
        timed.append((float(np.median(times) * 1e6), name))
    return timed, skipped


def autotune_bsi(grid_shape, tile, channels=3, *, candidates=None, reps=3,
                 cache_path=None, use_cache=True, measure_grad=False,
                 similarity=None, grad_impls=None, compute_dtype=None,
                 transform=None, stop=None, optimizer=None) -> BsiChoice:
    """Benchmark the candidate BSI forms and return (and cache) the winner.

    Args:
      grid_shape: stored control-grid dims ``(Tx+3, Ty+3, Tz+3)``.
      tile: control-point spacing ``(dx, dy, dz)``.
      channels: trailing channel count of the grid (3 for displacement).
      candidates: optional ``((mode, impl), ...)`` override — or, with
        ``measure_grad``, ``((mode, impl, grad_impl), ...)`` triples.
      reps: timed repetitions per candidate (after a compile+warmup call).
      cache_path: JSON cache location (``None`` -> :func:`default_cache_path`).
      use_cache: bypass both caches when False (always re-measure).
      measure_grad: time forward+backward (the registration loop's workload)
        instead of the forward alone.  Candidates that cannot differentiate
        (Pallas forwards under the plain-autodiff ``"xla"`` adjoint) are
        excluded automatically.
      similarity: optional similarity name/callable.  With ``measure_grad``,
        the timed objective becomes warp + that similarity on top of the BSI
        expansion — the measurement (and its cache entry) is per-similarity,
        since e.g. NMI's histogram backward changes the workload mix XLA
        fuses around each BSI form.
      grad_impls: adjoint implementations to cross ``(mode, impl)`` pairs
        with under ``measure_grad`` (see ``interpolate``'s ``grad_impl``).
        Defaults to ``("xla",)`` — the historical forward-only enumeration —
        so forward-only and legacy callers are unaffected; the engine passes
        :func:`default_grad_impls` to tune the full (fwd x adjoint) matrix.
      compute_dtype: optional reduced compute dtype (e.g. ``"bfloat16"``).
        The measured workload runs the BSI expansion (and warp) in that
        dtype — what the registration loop will actually execute — and the
        cache entry is per-dtype, so fp32 and bf16 callers never share a
        possibly-differently-ranked winner.
      transform: optional transform name/spec (``repro.core.transform``).
        With the velocity transform (and ``measure_grad`` + ``similarity``),
        the timed objective integrates the expansion by scaling and squaring
        before the warp — the velocity loop's actual per-step workload,
        whose composition chain changes what XLA fuses around each BSI form.
        The cache entry gains a ``|tf=...`` token only for non-displacement
        transforms, so existing displacement entries stay valid.
      stop: must stay ``None``.  The timing workload is one fixed
        forward+backward step — early stopping (``ConvergenceConfig``)
        changes how *many* steps a given pair runs, never the per-step cost
        a kernel choice should be ranked on, and a data-dependent loop
        length would make the measurement (and its cache entry) depend on
        the synthetic pair's convergence.  Engine callers resolve ``stop``
        outside the tuner; passing it here is a usage error.
      optimizer: optional optimiser name/spec (``repro.engine.optimizer``).
        The timed workload stays the one forward+backward BSI step — it is
        the per-step kernel work every registered optimiser shares (L-BFGS's
        two-loop and Gauss-Newton's CG ride on the same expansion/adjoint
        kernels) — but the cache entry gains an ``|opt=...`` token for
        non-default optimisers, so a second-order run never silently reuses
        (or overwrites) a winner recorded under a different step
        composition.  The default Adam adds no token: pre-registry disk
        cache entries stay valid without a ``SCHEMA_VERSION`` bump.
    """
    if stop is not None:
        raise ValueError(
            "autotune_bsi times a fixed-iteration workload; stop= must be "
            "None (early stopping changes step count, not per-step cost)")
    grid_shape = tuple(int(g) for g in grid_shape)
    tile = tuple(int(t) for t in tile)
    channels = int(channels)
    compute_dtype = (jnp.dtype(compute_dtype).name
                     if compute_dtype is not None else None)
    tspec = resolve_transform(transform) if transform is not None else None
    velocity = isinstance(tspec, VelocityTransform)
    opt_token = None
    if optimizer is not None:
        from repro.engine.optimizer import optimizer_token

        tok = optimizer_token(optimizer)
        opt_token = None if tok == "adam" else tok
    cands = (default_candidates() if candidates is None
             else tuple(candidates))
    gis = ("xla",) if grad_impls is None else tuple(grad_impls)
    if measure_grad:
        # cross (mode, impl) pairs with the adjoint axis; explicit triples
        # pass through as-is
        cands = tuple(c if len(c) == 3 else c + (gi,)
                      for c in cands for gi in (gis if len(c) == 2 else ("",)))
    else:
        cands = tuple(c[:2] for c in cands)
    # the key names everything that can change the measurement
    key = (_key(grid_shape, tile, channels)
           + ("|grad" if measure_grad else "")
           + ("" if similarity is None
              else f"|sim={similarity_token(similarity)}")
           + ("" if compute_dtype is None else f"|cd={compute_dtype}")
           + (f"|tf={transform_token(tspec)}" if velocity else "")
           + ("" if opt_token is None else f"|opt={opt_token}")
           + "|" + ",".join("/".join(c) for c in cands))
    cache_path = default_cache_path() if cache_path is None else cache_path
    mem_key = (cache_path, key)

    if use_cache and mem_key in _MEM_CACHE:
        return _MEM_CACHE[mem_key]
    if use_cache:
        hit = _load_disk(cache_path).get(key)
        choice = _parse_choice(hit) if hit else None
        if choice is not None:
            _MEM_CACHE[mem_key] = choice
            return choice

    # Measure on ONE device explicitly.  Mesh-sharded serving (engine.shard)
    # is pure data parallelism — each device runs the whole per-pair loop —
    # so the single-device measurement *is* the per-shard workload, and
    # pinning keeps the timing stable when the process holds a pod (or
    # XLA_FLAGS-faked multi-device) context.  The volumes are arguments of
    # the timed programs, never captured constants, so the programs stay
    # small enough for the persistent compilation cache.
    dev = jax.local_devices()[0]
    rng = np.random.default_rng(0)
    phi = jax.device_put(
        jnp.asarray(rng.standard_normal(grid_shape + (channels,)),
                    jnp.float32), dev)
    args = (phi,)
    objective = None
    if measure_grad and similarity is not None:
        _, sim_fn = resolve_similarity(similarity)
        dense_shape = tuple((g - 3) * t for g, t in zip(grid_shape, tile))
        fix = jax.device_put(jnp.asarray(rng.random(dense_shape),
                                         jnp.float32), dev)
        if channels == 3:
            # the registration loop's objective: warp a volume by the
            # expanded field, then score it against a fixed volume
            from repro.core.ffd import warp_volume

            mov = jax.device_put(jnp.asarray(rng.random(dense_shape),
                                             jnp.float32), dev)
            args = (phi, mov, fix)

            def objective(out, mov, fix):
                if velocity:
                    out = scaling_and_squaring(out, tspec.squarings)
                warped = warp_volume(mov, out, compute_dtype=compute_dtype)
                return sim_fn(warped.astype(fix.dtype), fix)
        else:
            args = (phi, fix)

            def objective(out, fix):
                return sim_fn(out[..., 0].astype(fix.dtype), fix)

    jobs, skipped = [], []
    for cand in cands:
        mode, impl = cand[0], cand[1]
        gi = cand[2] if len(cand) == 3 else "xla"
        name = "/".join(cand)
        if measure_grad and impl == "pallas" and gi == "xla":
            skipped.append((name, NO_AUTODIFF))
            continue

        def fwd(p, mode=mode, impl=impl, gi=gi):
            return interpolate(p, tile, mode=mode, impl=impl, grad_impl=gi,
                               dtype=compute_dtype)

        if measure_grad and objective is not None:
            fn = jax.jit(jax.grad(
                lambda p, *data, fwd=fwd: objective(fwd(p), *data)))
        elif measure_grad:
            fn = jax.jit(jax.grad(lambda p, fwd=fwd: fwd(p).sum()))
        else:
            fn = jax.jit(fwd)  # consumers always run the form under jit
        jobs.append((name, fn))
    timed, oom = _time_candidates(jobs, args, dev, reps)
    skipped = tuple(skipped + oom)
    if not timed:
        raise RuntimeError(
            f"no BSI candidate fits for grid={grid_shape} tile={tile}; "
            f"skipped: {skipped}")
    us, name = min(timed)
    win = name.split("/")
    best = BsiChoice(win[0], win[1], us, win[2] if len(win) == 3 else "xla",
                     skipped=skipped)

    if use_cache:
        _MEM_CACHE[mem_key] = best
        _store_disk(cache_path, key, best)
    return best


def autotune_fused(grid_shape, tile, vol_shape, *, base, similarity,
                   compute_dtype=None, reps=3, cache_path=None,
                   use_cache=True) -> BsiChoice:
    """Race the fused level step against the unfused winner ``base``.

    ``base`` is the already-resolved unfused :class:`BsiChoice` (concrete
    ``mode``/``impl``/``grad_impl``); the race times one full level-step
    gradient — BSI expansion + warp + ``similarity`` forward and backward —
    through ``core.ffd.fused_warp_loss`` versus the unfused composition, and
    returns ``base`` with ``fused`` set to the winner.

    Resolves to ``"off"`` without measuring when the fused kernel does not
    apply (custom similarity with no fused spec, volume over the VMEM
    budget) and on backends where Pallas only runs under ``interpret=True``
    (a correctness path, orders of magnitude slower — same exclusion as
    :func:`default_candidates`; set ``REPRO_AUTOTUNE_PALLAS=1`` to force the
    measurement anyway).  Cached like :func:`autotune_bsi`, keyed per
    volume/similarity/dtype/base so fp32 and bf16 (or different unfused
    winners) never share a decision.
    """
    from repro.core import ffd
    from repro.core.similarity import fused_spec
    from repro.kernels import ops as kops

    grid_shape = tuple(int(g) for g in grid_shape)
    tile = tuple(int(t) for t in tile)
    vol_shape = tuple(int(s) for s in vol_shape)
    compute_dtype = (jnp.dtype(compute_dtype).name
                     if compute_dtype is not None else None)

    spec = fused_spec(similarity)
    ok, _ = kops.fused_supported(vol_shape, spec)
    if not ok:
        return dataclasses.replace(base, fused="off")
    if kops.default_interpret() and not os.environ.get("REPRO_AUTOTUNE_PALLAS"):
        return dataclasses.replace(base, fused="off")

    key = (_key(grid_shape, tile, 3)
           + "|fused|v" + "x".join(map(str, vol_shape))
           + f"|sim={similarity_token(similarity)}"
           + ("" if compute_dtype is None else f"|cd={compute_dtype}")
           + f"|base={base.mode}/{base.impl}/{base.grad_impl}")
    cache_path = default_cache_path() if cache_path is None else cache_path
    mem_key = (cache_path, key)
    if use_cache and mem_key in _MEM_CACHE:
        return _MEM_CACHE[mem_key]
    if use_cache:
        hit = _load_disk(cache_path).get(key)
        choice = _parse_choice(hit) if hit else None
        if choice is not None:
            _MEM_CACHE[mem_key] = choice
            return choice

    _, sim_fn = resolve_similarity(similarity)
    dev = jax.local_devices()[0]
    rng = np.random.default_rng(0)
    phi = jax.device_put(
        jnp.asarray(rng.standard_normal(grid_shape + (3,)), jnp.float32), dev)
    mov = jax.device_put(jnp.asarray(rng.random(vol_shape), jnp.float32), dev)
    fix = jax.device_put(jnp.asarray(rng.random(vol_shape), jnp.float32), dev)

    def unfused_loss(p, mov, fix):
        disp = ffd.dense_field(p, tile, vol_shape, mode=base.mode,
                               impl=base.impl, grad_impl=base.grad_impl,
                               compute_dtype=compute_dtype)
        warped = ffd.warp_volume(mov, disp, compute_dtype=compute_dtype)
        return sim_fn(warped.astype(jnp.float32), fix)

    def fused_loss(p, mov, fix):
        return ffd.fused_warp_loss(p, mov, fix, tile, similarity=similarity,
                                   mode=base.mode, impl=base.impl,
                                   grad_impl=base.grad_impl,
                                   compute_dtype=compute_dtype)

    timed, skipped = _time_candidates(
        [("off", jax.jit(jax.grad(unfused_loss))),
         ("on", jax.jit(jax.grad(fused_loss)))], (phi, mov, fix), dev, reps)
    best = dataclasses.replace(base, fused="off", skipped=tuple(skipped))
    if timed:
        us, flag = min(timed)
        best = dataclasses.replace(best, fused=flag, us_per_call=us)
    if use_cache:
        _MEM_CACHE[mem_key] = best
        _store_disk(cache_path, key, best)
    return best


def _candidate_pool(mode, impl):
    """Candidates honouring explicitly fixed axes.

    An explicit ``impl`` overrides the backend-based default exclusion (a
    user asking for ``pallas`` on CPU gets interpret-mode Pallas, as the
    seed's explicit ``impl=`` did); only fully-``auto`` axes are subject to
    :func:`default_candidates`.
    """
    if impl == "jnp":
        pool = JNP_CANDIDATES
    elif impl == "pallas":
        pool = PALLAS_CANDIDATES
    else:
        pool = default_candidates()
    return tuple(c for c in pool if mode in ("auto", c[0]))


def resolve_bsi(mode, impl, grid_shape, tile, channels=3, *, grad_impl=None,
                **tune_kwargs):
    """Resolve possibly-``"auto"`` (mode, impl[, grad_impl]) to concrete values.

    Explicit choices pass through untouched; an ``"auto"`` on any axis
    narrows the candidate set to the fixed axes and autotunes the rest.
    With ``grad_impl=None`` (forward-only callers) the return is the
    historical ``(mode, impl)`` pair; passing a ``grad_impl`` — even an
    explicit one — returns ``(mode, impl, grad_impl)`` and, when any axis is
    ``"auto"``, tunes the joint forward+adjoint workload (``measure_grad``
    is implied: the adjoint axis only exists in the backward).
    """
    return _resolve(mode, impl, grid_shape, tile, channels,
                    grad_impl=grad_impl, **tune_kwargs)[0]


def _resolve(mode, impl, grid_shape, tile, channels=3, *, grad_impl=None,
             **tune_kwargs):
    """:func:`resolve_bsi` plus the candidates the race skipped."""
    if grad_impl is None:
        if mode != "auto" and impl != "auto":
            return (mode, impl), ()
        cands = _candidate_pool(mode, impl)
        if not cands:
            raise ValueError(
                f"no BSI candidates match mode={mode!r} impl={impl!r}")
        if len(cands) == 1:
            return cands[0], ()
        choice = autotune_bsi(grid_shape, tile, channels,
                              candidates=cands, **tune_kwargs)
        return (choice.mode, choice.impl), choice.skipped

    if grad_impl != "auto" and grad_impl not in GRAD_IMPLS:
        raise ValueError(
            f"unknown grad_impl {grad_impl!r}; choose from {GRAD_IMPLS}"
            " or 'auto'")
    if mode != "auto" and impl != "auto" and grad_impl != "auto":
        return (mode, impl, grad_impl), ()
    gis = default_grad_impls() if grad_impl == "auto" else (grad_impl,)
    if grad_impl == "auto" and tune_kwargs.get("compute_dtype") is not None:
        # plain autodiff of a reduced-precision forward accumulates the
        # adjoint in that precision; only the analytic adjoints keep the
        # documented fp32 accumulation, so "auto" never picks "xla" here
        # (an *explicit* grad_impl="xla" still passes through above)
        gis = tuple(g for g in gis if g != "xla") or gis
    # a Pallas forward differentiates only through an analytic adjoint
    cands = tuple(c + (gi,) for c in _candidate_pool(mode, impl)
                  for gi in gis if not (c[1] == "pallas" and gi == "xla"))
    if not cands:
        raise ValueError(f"no BSI candidates match mode={mode!r} "
                         f"impl={impl!r} grad_impl={grad_impl!r}")
    if len(cands) == 1:
        return cands[0], ()
    tune_kwargs["measure_grad"] = True
    choice = autotune_bsi(grid_shape, tile, channels,
                          candidates=cands, **tune_kwargs)
    return (choice.mode, choice.impl, choice.grad_impl), choice.skipped


@functools.lru_cache(maxsize=256)
def resolve_options(options, vol_shape):
    """Resolve a ``RegistrationOptions`` for a concrete volume shape.

    The options-first face of the tuner: canonicalises the options
    (:meth:`RegistrationOptions.normalized` — similarity key, resolved
    ``stop``) and autotunes any ``"auto"`` BSI axis for the grid this volume
    implies, returning a fully-concrete copy.  ``fused="auto"`` is resolved
    last (:func:`autotune_fused` — the fused level step races the resolved
    unfused winner on the actual volume shape); ``fused="on"`` is validated
    against the fused kernel's applicability and raises with the reason when
    it cannot run.  ``lru_cache``d on ``(options, vol_shape)`` — the
    ``RegistrationOptions`` instance IS the autotune cache key, the same
    object the compiled-runner caches and the serving buckets key on, so one
    validated configuration maps to one tuning decision everywhere.

    Every path records *why* ``fused`` resolved the way it did on the
    returned options' ``fused_reason`` field (introspection only — the
    field is excluded from equality/hash, so it never fragments the
    program caches keyed on the options instance).
    """
    from repro.core import ffd
    from repro.core.options import RegistrationOptions
    from repro.core.similarity import fused_spec
    from repro.kernels import ops as kops

    if not isinstance(options, RegistrationOptions):
        raise TypeError(
            f"resolve_options expects a RegistrationOptions, got {options!r}")
    opts = options.normalized()
    vol_shape = tuple(int(s) for s in vol_shape)
    grid_shape = ffd.grid_shape_for_volume(vol_shape, opts.tile)
    (mode, impl, grad_impl), skipped = _resolve(
        opts.mode, opts.impl, grid_shape, opts.tile,
        grad_impl=opts.grad_impl,  # the adjoint axis is tuned jointly
        measure_grad=True,  # the loop's workload is forward+backward BSI
        similarity=opts.similarity,  # ... its backward mix is per-similarity
        compute_dtype=opts.compute_dtype,  # ... measured/cached per dtype
        transform=opts.transform,  # ... velocity integrates before the warp
        optimizer=opts.optimizer)  # ... non-default optimisers key apart
    opts = opts.replace(mode=mode, impl=impl, grad_impl=grad_impl,
                        skipped=skipped)
    is_velocity = isinstance(opts.transform, VelocityTransform)
    from repro.engine.optimizer import GaussNewtonOptimizer

    is_gn = isinstance(opts.optimizer, GaussNewtonOptimizer)
    if opts.fused == "off":
        opts = opts.replace(fused_reason="forced off")
    elif opts.fused == "on":
        if is_velocity:  # unreachable via RegistrationOptions (which raises
            # at construction), but resolve_options is also a public face
            raise ValueError(
                "fused='on' is incompatible with transform='velocity': the "
                "fused level step cannot interleave scaling-and-squaring "
                "compositions; use fused='auto' or 'off'")
        if is_gn:  # same: RegistrationOptions raises at construction
            raise ValueError(
                "fused='on' is incompatible with optimizer='gauss_newton': "
                "the fused level step never materialises the residual "
                "volume Gauss-Newton linearises; use fused='auto' or 'off'")
        ok, why = kops.fused_supported(vol_shape, fused_spec(opts.similarity))
        if not ok:
            raise ValueError(
                f"fused='on' cannot run for this configuration: {why}; "
                "use fused='auto' (or 'off') to fall back to the unfused "
                "level step")
        opts = opts.replace(fused_reason="forced on")
    else:  # fused == "auto"
        if is_velocity:  # no race: the fused step has no velocity path yet
            opts = opts.replace(
                fused="off",
                fused_reason="velocity transform: the fused level step has "
                             "no scaling-and-squaring composition")
        elif is_gn:  # no race: Gauss-Newton linearises the unfused residual
            opts = opts.replace(
                fused="off",
                fused_reason="gauss_newton optimiser: the fused level step "
                             "never materialises the residual volume")
        else:
            ok, why = kops.fused_supported(vol_shape,
                                           fused_spec(opts.similarity))
            if not ok:
                opts = opts.replace(fused="off",
                                    fused_reason=f"unsupported: {why}")
            elif (kops.default_interpret()
                  and not os.environ.get("REPRO_AUTOTUNE_PALLAS")):
                opts = opts.replace(
                    fused="off",
                    fused_reason="interpret-only Pallas backend (set "
                                 "REPRO_AUTOTUNE_PALLAS=1 to race anyway)")
            else:
                choice = autotune_fused(
                    grid_shape, opts.tile, vol_shape,
                    base=BsiChoice(mode, impl, 0.0, grad_impl),
                    similarity=opts.similarity,
                    compute_dtype=opts.compute_dtype)
                opts = opts.replace(
                    fused=choice.fused,
                    fused_reason="autotune: fused level step "
                                 + ("won" if choice.fused == "on"
                                    else "lost") + " the race")
    return opts
