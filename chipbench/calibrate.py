"""Readings that the limits of ``chipbench/limits/<cell>.json`` are set from.

    python chipbench/calibrate.py --workload porcine1_ssd.single \
        --seeds 11,12,13 --control-seeds 21,22,23

In one process (set-up once): for each seed, the call a run with that seed
checks (the same pairs, through the same entry and compiled programs) is
compared with the reference, and each number is printed; for each control
seed, the same with the control in the program's place: the program's own
reduced-precision path (``compute_dtype="bfloat16"``, the BSI forms
the float32 run resolved).  The last line holds the largest program reading
and the smallest control reading of every number.  The benchmark's runs do
not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def control_options(options, resolved):
    """The program in the nearest precision below float32: bfloat16 BSI and
    warp, with the forms the float32 options resolved to (no new race)."""
    return options.replace(compute_dtype="bfloat16", mode=resolved.mode,
                           impl=resolved.impl, grad_impl=resolved.grad_impl,
                           fused="off")


def readings(root, workload, seeds, control_seeds, *, require_tpu=True):
    """``{"program": [numbers per seed], "control": [...]}``."""
    import numpy as np

    from chipbench import bench, reference

    cell = bench.find_cell(root, workload)
    out = {"program": [], "control": []}
    bench.device_info(require_tpu, cell.chips)
    with bench.caches(cell.cache_dir):
        import jax

        from repro.engine.autotune import resolve_options

        options = bench.registration_options(cell.config)
        resolved = resolve_options(options, tuple(cell.config["volume"]))
        call_fn = bench.ENTRIES[cell.traffic["entry"]]
        min_calls = int(cell.traffic["min_calls"])
        for kind, opts, run_seeds in (
                ("program", options, seeds),
                ("control", control_options(options, resolved), control_seeds)):
            for seed in run_seeds:
                requests = bench.make_requests(cell, seed)
                keep = int(np.random.default_rng(int(seed) % 2**64)
                           .integers(min_calls))
                request = requests[keep % len(requests)]
                del requests
                result = call_fn(request, opts)
                jax.block_until_ready(result)
                numbers = bench.check_call(result, request, cell)
                pairs = bench.request_pairs(request, cell.traffic["entry"])
                for n, r, (f, m) in zip(numbers, result, pairs):
                    out[kind].append(n)
                    start = reference.objective_at(
                        jax.numpy.zeros_like(r["phi"]), f, m, cell.config)
                    print(json.dumps({
                        "kind": kind, "seed": seed, **n,
                        "losses": [float(v) for v in r["losses"]],
                        "unregistered": float(start)}), flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    out = readings(ROOT, args.workload, seeds, controls)
    names = sorted(out["program"][0])
    print(json.dumps({
        "workload": args.workload,
        "program_max": {k: max(n[k] for n in out["program"]) for k in names},
        "control_min": {k: min(n[k] for n in out["control"]) for k in names}
        if out["control"] else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
