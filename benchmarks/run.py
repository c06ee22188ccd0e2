"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows for:
  * bsi_speed          — paper Figs. 5-7 (time/voxel + speedup, tile sweep)
  * bsi_fused          — fused level-step megakernel vs the unfused
                         composition per similarity (ci preset)
  * bsi_accuracy       — paper Tables 3-4 (error vs float64 reference)
  * registration_bench — paper Figs. 8-9 + Table 5 (FFD time + MAE/SSIM)
  * transfer_model     — paper Appendix A (Eqs. A.1-A.4 transfer counts)
  * serving_bench      — continuous batching vs sequential register_batch
                         under a Poisson request stream (p50/p99, pairs/s)

Presets:
  * default — scaled-down volumes (CPU wall-time budget)
  * full    — the exact paper resolutions (``--full`` is an alias)
  * ci      — tiny smoke sizes; paired with ``--json BENCH_ci.json`` this is
              the CI perf-trajectory artifact, gated against the committed
              ``benchmarks/baseline_ci.json`` by ``benchmarks/compare.py``

Roofline tables (assignment §Roofline) are produced separately from the
dry-run artifacts by ``python -m repro.launch.roofline_report``.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
if str(_ROOT) not in sys.path:  # `python benchmarks/run.py` puts benchmarks/
    sys.path.insert(0, str(_ROOT))  # first, not the repo root
try:
    import repro  # noqa: F401  (installed via `pip install -e .`)
except ModuleNotFoundError:  # src-layout checkout without install
    sys.path.insert(0, str(_ROOT / "src"))


def _suites(preset):
    from benchmarks import (bsi_accuracy, bsi_speed, registration_bench,
                            serving_bench, transfer_model)
    from benchmarks.common import TINY_VOLUMES

    if preset == "ci":
        return [
            ("transfer_model", transfer_model.main),
            ("bsi_accuracy", lambda: bsi_accuracy.main(grid_pts=6,
                                                       tiles=[3, 5])),
            ("bsi_speed", lambda: bsi_speed.main(
                tiles=[3, 5], reps=2, vol_table=TINY_VOLUMES,
                volumes=tuple(TINY_VOLUMES))),
            # forward+backward per (mode, grad_impl): the custom-VJP adjoint
            # vs XLA autodiff of the same forward (ISSUE 4 acceptance rows)
            ("bsi_grad", lambda: bsi_speed.main(
                grad=True, tiles=[3, 5], reps=2, vol_table=TINY_VOLUMES,
                volumes=tuple(TINY_VOLUMES))),
            # fused level-step megakernel vs the unfused composition per
            # similarity (ISSUE 7 acceptance rows; interpret-mode on CPU)
            ("bsi_fused", lambda: bsi_speed.main(
                fused=True, tiles=[5], reps=2, vol_table=TINY_VOLUMES,
                volumes=("phantom2",))),
            ("registration_bench", lambda: registration_bench.main(
                shape=(22, 20, 18), iters=4, affine_iters=10)),
            # pluggable transform/regularizer axes: velocity + analytic
            # bending rows, and the fold-case min-Jacobian comparison
            # (velocity min_jac > 0 where displacement folds — ISSUE 8
            # acceptance)
            ("registration_transforms", lambda: registration_bench.main(
                transforms=True, shape=(22, 20, 18), iters=4,
                fold_iters=60)),
            # convergence-aware serving: steps saved + loss excess of
            # stop=ConvergenceConfig vs fixed iters (ISSUE 5 acceptance)
            ("registration_earlystop", lambda: registration_bench.main(
                earlystop=True, shape=(22, 20, 18), iters=24, batch=4)),
            # pluggable optimiser registry: second-order L-BFGS /
            # Gauss-Newton at a quarter of Adam's step budget on the
            # pure-SSD hard pair (ISSUE 10 acceptance: tol_met=yes means
            # the quarter-budget run reached <= Adam's final loss)
            ("registration_optimizers", lambda: registration_bench.main(
                optimizers=True)),
            # continuous batching (engine.serve) vs sequential
            # register_batch under a Poisson stream: asserts >= 1.5x
            # pairs/sec at <= 2% loss excess (PR 6 acceptance), and its
            # p50/p99 latency rows ride the compare.py trajectory gate
            ("serving", serving_bench.main),
        ]
    full = preset == "full"
    return [
        ("transfer_model", transfer_model.main),
        ("bsi_accuracy", bsi_accuracy.main),
        ("bsi_speed", lambda: bsi_speed.main(full=full)),
        ("registration_bench", registration_bench.main),
    ]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preset", choices=["default", "full", "ci"],
                    default=None)
    ap.add_argument("--full", action="store_true",
                    help="alias for --preset full")
    ap.add_argument("--json", metavar="PATH",
                    help="also write all rows to PATH as JSON")
    args = ap.parse_args(argv)
    preset = args.preset or ("full" if args.full else "default")

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    results = {}
    failures = []
    for name, fn in _suites(preset):
        print(f"# --- {name} ---")
        try:
            rows = fn()
            results[name] = [
                {"name": n, "us_per_call": u, "derived": d}
                for n, u, d in rows
            ]
        except Exception:
            failures.append(name)
            traceback.print_exc()

    if args.json:
        payload = {"preset": preset, "failures": failures, "suites": results}
        Path(args.json).write_text(json.dumps(payload, indent=1))
        print(f"# wrote {args.json}")
    if failures:
        raise SystemExit(f"benchmark suites failed: {failures}")


if __name__ == "__main__":
    main()
