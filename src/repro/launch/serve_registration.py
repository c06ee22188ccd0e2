"""Registration-serving launcher: a Poisson load generator over engine.serve.

Plays an open-loop Poisson stream of mixed-difficulty registration requests
against a :class:`repro.engine.serve.RegistrationScheduler` and reports the
serving numbers that matter for capacity planning: p50/p99 request latency,
sustained pairs/sec, lane-recycling rate, and the compile count (which
should equal ``levels x distinct shapes`` no matter how long the run is).

    python -m repro.launch.serve_registration [--rate 4.0] [--n 32]
    python -m repro.launch.serve_registration --smoke
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m repro.launch.serve_registration --smoke --mesh

``--smoke`` is the CI serving job: 8 mixed pairs (two volume shapes, easy
and hard difficulty) pushed through the queue as fast as the scheduler
accepts them, asserting every request completes and that shape bucketing
held the compile count down.  ``--mesh`` shards the lane arrays over every
local device (fake CPU devices via ``XLA_FLAGS`` above).
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def mixed_pairs(n, shapes, hard_every=3, seed=0):
    """Alternating-shape, mixed-difficulty pairs — the serving worst case.

    Easy pairs plateau in a few Adam steps; every ``hard_every``-th needs
    the full budget.  The contrast is what exercises lane recycling, and
    the shape alternation is what exercises bucketing.
    """
    rng = np.random.default_rng(seed)
    waves = {}
    out = []
    for i in range(n):
        shape = shapes[i % len(shapes)]
        if shape not in waves:
            x, y, z = np.meshgrid(
                *[np.linspace(0, np.pi, s) for s in shape], indexing="ij")
            waves[shape] = (np.sin(x) * np.sin(y) * np.sin(z)).astype(
                np.float32)
        f = rng.normal(size=shape).astype(np.float32)
        if hard_every and i % hard_every == 0:
            m = np.roll(f, 3, axis=0) + 2.5 * waves[shape]
            m = m + 0.3 * rng.normal(size=shape).astype(np.float32)
        else:
            m = f + 0.02 * waves[shape]
        out.append((f, m.astype(np.float32)))
    return out


def play(sched, pairs, arrivals, *, timeout=None):
    """Submit ``pairs`` at ``arrivals`` (seconds) and drive to completion."""
    handles, latencies = {}, {}
    start = time.perf_counter()
    submitted = 0
    n = len(pairs)
    while len(latencies) < n:
        now = time.perf_counter() - start
        while submitted < n and arrivals[submitted] <= now:
            f, m = pairs[submitted]
            handles[submitted] = sched.submit(f, m, timeout=timeout)
            submitted += 1
        if sched.pending:
            sched.step()
        elif submitted < n:
            time.sleep(max(arrivals[submitted] - now, 0.0) + 1e-4)
        end = time.perf_counter() - start
        for i, h in handles.items():
            if h.done and i not in latencies:
                latencies[i] = end - arrivals[i]
    return handles, latencies, time.perf_counter() - start


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--shape", type=int, nargs=3, default=(28, 24, 20))
    ap.add_argument("--n", type=int, default=32,
                    help="requests in the stream")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate (requests/sec); 0 = closed "
                         "loop, submit as fast as admission allows")
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=3)
    ap.add_argument("--iters", type=int, default=32)
    ap.add_argument("--timeout", type=float, default=None,
                    help="per-request deadline in seconds")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", action="store_true",
                    help="shard lane arrays over all local devices (fake a "
                         "pod on CPU: XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: 8 mixed pairs over two shapes, assert "
                         "all complete and compiles == levels x shapes")
    args = ap.parse_args(argv)

    from repro.core.options import RegistrationOptions
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro.engine.convergence import ConvergenceConfig
    from repro.engine.serve import RegistrationScheduler

    options = RegistrationOptions(
        tile=(6, 6, 6), levels=2, iters=args.iters, lr=0.1,
        mode="separable", impl="jnp", grad_impl="xla",
        stop=ConvergenceConfig(tol=2e-3, patience=3))
    mesh = None
    lanes = args.lanes
    if args.mesh:
        import jax

        from repro.engine.shard import (batch_multiple,
                                        make_registration_mesh)

        mesh = make_registration_mesh()
        mult = batch_multiple(mesh)
        lanes = max(lanes, mult) // mult * mult  # round to an even split
        print(f"mesh: lane arrays sharded over {len(jax.devices())} "
              f"device(s), lanes={lanes}")

    shape = tuple(args.shape)
    if args.smoke:
        n = 8
        shapes = [shape, tuple(max(s - 4, 8) for s in shape)]
    else:
        n = args.n
        shapes = [shape]
    pairs = mixed_pairs(n, shapes, seed=args.seed)

    sched = RegistrationScheduler(options, lanes=lanes, chunk=args.chunk,
                                  max_queue=max(2 * n, 16), mesh=mesh)
    # warm the compiled programs outside the timed stream (one per
    # shape x level — the whole point of shape bucketing)
    for shape_ in shapes:
        f = np.zeros(shape_, np.float32)
        sched.submit(f, f)
    sched.run_until_idle()
    warm_compiles = sched.stats.compiles

    if args.rate > 0:
        rng = np.random.default_rng(args.seed + 1)
        arrivals = np.concatenate(
            [[0.0], rng.exponential(1.0 / args.rate, n - 1)]).cumsum()
    else:
        arrivals = np.zeros(n)
    handles, latencies, makespan = play(sched, pairs, arrivals,
                                        timeout=args.timeout)

    stats = sched.stats
    lat = np.asarray(sorted(latencies.values()))
    completed = sum(1 for h in handles.values() if h._error is None)
    print(f"{completed}/{n} completed in {makespan:.2f}s "
          f"({completed / makespan:.2f} pairs/s sustained)")
    print(f"latency p50 {np.percentile(lat, 50):.3f}s  "
          f"p99 {np.percentile(lat, 99):.3f}s")
    print(f"recycled lanes: {stats.recycled}; chunks: {stats.chunks}; "
          f"buckets: {stats.buckets}; compiles: {stats.compiles} "
          f"({warm_compiles} at warm-up)")
    if stats.timed_out:
        print(f"timed out: {stats.timed_out}")

    if args.smoke:
        assert completed == n, f"smoke: only {completed}/{n} completed"
        expect = options.levels * len(shapes)
        assert stats.compiles == expect, (
            f"smoke: {stats.compiles} stage compiles, expected {expect} "
            f"(levels x shapes) — shape bucketing regressed")
        print("smoke OK")


if __name__ == "__main__":
    main()
