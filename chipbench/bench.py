"""The benchmark harness: one run of one cell, driven by data files.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
harness finds the rest by those names:

* ``configs[].file``: the deployment (volume, registration options, the
  reference's similarity and regulariser);
* ``chipbench/traffic/<traffic>.json``: how pairs are made and sent (entry
  point, pairs per call, pairs made, the deformation recipe);
* ``chipbench/limits/<cell>.json``: the limit of each number compared with
  the reference;
* ``chipbench/metrics/<metric>.py`` (or ``<metric before its first '.'>.py``):
  a reader ``read(ctx) -> float | None`` for each metric; ``None`` leaves
  the metric out of the result line.

A run: set-up (data, autotune lookup, warm-up of every program the window
uses), a window of whole calls back to back until ``seconds`` have passed
(at least ``min_calls``), then the check of a call drawn from the seed
against ``chipbench.reference``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import types

import numpy as np

CACHE = ".cache"  # under <checkout>/chipbench, listed in its .gitignore


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def log(msg):
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# --- the cell's files ---------------------------------------------------------


def find_cell(root, workload):
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    here = os.path.join(root, "chipbench")
    return types.SimpleNamespace(
        name=workload, chips=int(cell["chips"]), bench=bench,
        cache_dir=os.path.join(here, CACHE),
        config=load_json(os.path.join(root, configs[cell["config"]]["file"])),
        traffic=load_json(os.path.join(here, "traffic",
                                       cell["traffic"] + ".json")),
        limits=load_json(os.path.join(here, "limits", workload + ".json")),
        metrics_dir=os.path.join(here, "metrics"))


def cell_metrics(cell, trace):
    """The metric entries this cell reports in a run with ``trace``."""
    bench = cell.bench

    def listed(m):
        return cell.name in m.get("workloads", (cell.name,))

    e2e = [m for m in bench["end_to_end"] if listed(m)]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell.name in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in reported)]


def load_reader(metrics_dir, name):
    """``read(ctx)`` of metric ``name``: ``<name>.py``, else ``<base>.py``."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(metrics_dir, stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "chipbench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} in {metrics_dir}")


# --- the system under test ----------------------------------------------------


def registration_options(cfg):
    """The program's ``RegistrationOptions`` for a configuration file."""
    from repro.core import RegistrationOptions
    from repro.core import regularizer as regularizers
    from repro.core import similarity as similarities

    def spec(module, entry):
        params = {k: v for k, v in entry.items() if k != "name"}
        return getattr(module, entry["name"])(**params) if params else entry["name"]

    return RegistrationOptions(
        tile=tuple(cfg["tile"]), levels=cfg["levels"], iters=cfg["iters"],
        lr=cfg["lr"], similarity=spec(similarities, cfg["similarity"]),
        regularizer=spec(regularizers, cfg["regularizer"]),
        optimizer=cfg["optimizer"], mode=cfg["mode"], impl=cfg["impl"],
        grad_impl=cfg["grad_impl"], fused=cfg["fused"],
        compute_dtype=cfg.get("compute_dtype"))


def _ffd_register(request, options):
    from repro.core.registration import ffd_register

    fixed, moving = request
    r = ffd_register(fixed, moving, options=options)
    return [{"phi": r.params, "losses": np.asarray(r.losses, np.float32),
             "warped": r.warped}]


def _register_batch(request, options):
    from repro.engine.batch import register_batch

    fixed, moving = request
    r = register_batch(fixed, moving, options=options)
    return [{"phi": r.params[b], "losses": r.losses[b], "warped": r.warped[b]}
            for b in range(fixed.shape[0])]


ENTRIES = {"ffd_register": _ffd_register, "register_batch": _register_batch}
SPANS = {"ffd_register": "bench.register", "register_batch": "bench.batch"}


def make_requests(cell, seed):
    """The calls of a run: pairs from the seed, stacked ``batch`` per call."""
    import jax.numpy as jnp

    from chipbench import data

    cfg, tr = cell.config, cell.traffic
    pairs = [data.make_pair(tuple(cfg["volume"]), data.pair_seed(seed, i),
                            tile=tuple(tr["deform_tile"]),
                            magnitude=tr["magnitude"],
                            remap=cfg.get("moving_remap", "none"))
             for i in range(tr["pairs"])]
    b = int(tr["batch"])
    if tr["entry"] == "ffd_register":
        return pairs
    return [(jnp.stack([f for f, _ in pairs[i:i + b]]),
             jnp.stack([m for _, m in pairs[i:i + b]]))
            for i in range(0, len(pairs), b)]


def request_pairs(request, entry):
    """The ``(fixed, moving)`` pairs of one call."""
    if entry == "ffd_register":
        return [request]
    return list(zip(request[0], request[1]))


# --- the comparison with the reference ----------------------------------------


def compare(result, fixed, moving, cfg, ref):
    """Numbers comparing one registered pair with the reference.

    ``warp_err``: max |registered - reference warp of moving by the program's
    own final grid| (intensities); ``objective_err``: relative gap of the
    finest level's reported objective to the reference's at the program's
    grid; ``level_loss_err``: largest relative gap of a level's final
    objective to the reference registration's; ``grid_err``: norm of the
    gap of the final grids over the reference grid's norm;
    ``registered_err`` / ``registered_mae``: max / mean |registered -
    the reference registration's registered volume|.
    """
    import jax.numpy as jnp

    from chipbench import reference

    phi = jnp.asarray(result["phi"], jnp.float32)
    losses = np.asarray(result["losses"], np.float64)
    ref_losses = np.asarray(ref["losses"], np.float64)
    warp = reference.final_warp(phi, moving, tuple(cfg["tile"]))
    obj = float(reference.objective_at(phi, fixed, moving, cfg))
    gap = jnp.abs(result["warped"] - ref["warped"])
    return {
        "warp_err": float(jnp.max(jnp.abs(result["warped"] - warp))),
        "registered_err": float(jnp.max(gap)),
        "registered_mae": float(jnp.mean(gap)),
        "objective_err": abs(losses[-1] - obj) / abs(obj),
        "level_loss_err": float(np.max(np.abs(losses - ref_losses)
                                       / np.abs(ref_losses))),
        "grid_err": float(jnp.linalg.norm(phi - ref["phi"])
                          / jnp.linalg.norm(ref["phi"])),
    }


def check_call(results, request, cell):
    """Compare every pair of one call with the reference: numbers per pair."""
    from chipbench import reference

    pairs = request_pairs(request, cell.traffic["entry"])
    return [compare(result, f, m, cell.config,
                    reference.register(f, m, cell.config))
            for result, (f, m) in zip(results, pairs)]


def within(numbers, limits):
    """Whether every limited number is finite and at most its limit."""
    return all(math.isfinite(numbers[k]) and numbers[k] <= limit
               for k, limit in limits.items())


def judge(numbers, limits):
    """``(correct, checks)``: each limited number at its worst over pairs."""
    checks = {k: {"value": max(n[k] for n in numbers), "limit": limit}
              for k, limit in limits.items()}
    return all(within(n, limits) for n in numbers), checks


# --- one run --------------------------------------------------------------------


class CacheMisses:
    """Persistent compilation cache misses, as JAX reports them."""

    def __init__(self):
        import jax

        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self):
        import jax

        jax.monitoring.unregister_event_listener(self._on_event)


@contextlib.contextmanager
def caches(cache_dir):
    """The autotune cache and JAX's compilation cache at fixed paths under
    ``cache_dir``; the compilation cache keeps every program, unbounded.
    The process's previous settings come back on exit."""
    import jax

    os.makedirs(cache_dir, exist_ok=True)
    settings = {"jax_compilation_cache_dir": os.path.join(cache_dir, "jax"),
                "jax_persistent_cache_min_compile_time_secs": 0,
                "jax_persistent_cache_min_entry_size_bytes": 0,
                "jax_compilation_cache_max_size": -1}
    before = {k: getattr(jax.config, k) for k in settings}
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(cache_dir,
                                                      "autotune.json")
    for k, v in settings.items():
        jax.config.update(k, v)
    try:
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        if env is None:
            del os.environ["REPRO_AUTOTUNE_CACHE"]
        else:
            os.environ["REPRO_AUTOTUNE_CACHE"] = env


def device_info(require_tpu, chips):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} x {devs[0].platform} ({devs[0].device_kind})")
    kind = devs[0].device_kind
    return {"platform": devs[0].platform, "kind": kind, "device_kind": kind,
            "count": len(devs)}


def memory_peak(count):
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:count]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_window(call, requests, seconds, min_calls, span, keep):
    """Whole calls back to back until ``seconds`` have passed and at least
    ``min_calls`` ran.  Returns ``(t0, calls, kept)``: ``calls`` holds
    ``(start, end, losses per pair)``; ``kept`` the outputs of call ``keep``."""
    import jax
    from jax.profiler import TraceAnnotation

    calls, kept = [], None
    t0 = time.perf_counter()
    while True:
        i = len(calls)
        start = time.perf_counter()
        with TraceAnnotation(span):
            out = call(requests[i % len(requests)])
        with TraceAnnotation("bench.block"):
            jax.block_until_ready(out)
        end = time.perf_counter()
        calls.append((start, end, [np.asarray(r["losses"]) for r in out]))
        if i == keep:
            kept = out
        if end - t0 >= seconds and len(calls) >= min_calls:
            return t0, calls, kept


def run_cell(root, workload, seed, seconds, trace, *, require_tpu=True,
             t_start=None):
    """One run of a cell; returns the result line as a dict.

    Raises :class:`NoChip` (before any measurement) where ``require_tpu``
    and JAX finds no TPU or too few chips.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    cell = find_cell(root, workload)
    device = device_info(require_tpu, cell.chips)
    with caches(cell.cache_dir):
        return _run(cell, seed, seconds, trace, device, t_start)


def _run(cell, seed, seconds, trace, device, t_start):
    import jax
    from jax.profiler import TraceAnnotation
    from repro.engine.autotune import resolve_options

    from chipbench import peaks as peak_table

    cache = CacheMisses()
    peaks = (peak_table.peaks_for(device["kind"])
             if device["platform"] == "tpu" else None)
    entry = cell.traffic["entry"]
    call_fn, span = ENTRIES[entry], SPANS[entry]
    options = registration_options(cell.config)

    t = time.perf_counter()
    with TraceAnnotation("bench.data"):
        requests = make_requests(cell, seed)
        jax.block_until_ready(requests)
    data_s = time.perf_counter() - t
    t = time.perf_counter()
    resolved = resolve_options(options, tuple(cell.config["volume"]))
    autotune_s = time.perf_counter() - t
    log(f"{cell.name}: resolved mode={resolved.mode} impl={resolved.impl} "
        f"grad_impl={resolved.grad_impl} fused={resolved.fused}; "
        f"data {data_s:.3f} s, autotune {autotune_s:.3f} s")

    def call(request):
        return call_fn(request, options)

    t = time.perf_counter()
    jax.block_until_ready(call(requests[0]))  # warm-up: every program
    log(f"{cell.name}: warm-up call {time.perf_counter() - t:.3f} s")
    min_calls = int(cell.traffic["min_calls"])
    keep = int(np.random.default_rng(int(seed) % 2**64).integers(min_calls))
    trace_dir = os.path.join(cell.cache_dir, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
    setup_s = time.perf_counter() - t_start
    try:
        t0, calls, kept = run_window(call, requests, seconds, min_calls, span,
                                     keep)
    finally:
        if trace:
            jax.profiler.stop_trace()
    misses = cache.misses
    cache.close()
    mem = memory_peak(cell.chips)
    pairs = sum(len(c[2]) for c in calls)
    window_s = calls[-1][1] - t0
    log(f"{cell.name}: set-up {setup_s:.3f} s; {len(calls)} calls, {pairs} "
        f"pairs in {window_s:.3f} s; peak memory {mem}")

    summary = None
    if trace:
        from chipbench import trace as xtrace

        path = xtrace.find_xplane(trace_dir)
        if path is not None:
            summary = xtrace.summarize(*xtrace.read_xplane(path))
        shutil.rmtree(trace_dir, ignore_errors=True)

    finite = [bool(np.all(np.isfinite(l))) for c in calls for l in c[2]]
    request = requests[keep % len(requests)]
    del requests  # free the other calls' inputs before the reference runs
    numbers = check_call(kept, request, cell)
    ok, checks = judge(numbers, cell.limits)
    failed = finite.count(False) + sum(not within(n, cell.limits)
                                       for n in numbers)

    ctx = types.SimpleNamespace(
        cell=cell, setup_s=setup_s, autotune_s=autotune_s,
        cache_misses=misses, window_s=window_s, calls=len(calls),
        pairs=pairs, trace=summary, peaks=peaks)
    metrics = {}
    for m in cell_metrics(cell, trace):
        value = load_reader(cell.metrics_dir, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device["memory_peak_bytes"] = mem
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    line = {"correct": bool(ok and failed == 0), "attempted": pairs,
            "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        line["breakdown"] = {"device_ops": summary.top_ops,
                             "idle_gaps": summary.idle_gaps}
    line["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    return line


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts
