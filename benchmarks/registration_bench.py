"""Paper Figs. 8-9 + Table 5: FFD registration wall-time and quality.

Registers synthetic phantom pairs (repro.data.volumes) with (a) affine only,
(b) FFD using the baseline ``gather`` BSI, (c) FFD using the optimized
``separable`` BSI, (d) FFD using the MXU matrix form (``matmul`` BSI),
(e) FFD using the autotuned BSI (``repro.engine``
picks the fastest form for this grid/tile), and (f) FFD with the fused
level-step megakernel forced on (``fused="on"``: BSI + warp + similarity in
one VMEM pass) — reporting total time, the BSI share (Amdahl argument of
paper §6.2) and MAE/SSIM against the fixed volume (Table 5 analogue).  The
FFD inner loop is the engine's scan-compiled path.

A multi-modal preset rides along (paper §6's CT↔CBCT case, NiftyReg's NMI
path): the moving volume gets a monotone intensity remap before
registration, so SSD demonstrably fails while NMI recovers the warp —
quality is scored by warping the *original* moving volume with each
recovered field.

``--sharded`` instead reports data-parallel serving throughput: the same
batch registered via ``register_batch(..., mesh=...)`` over growing device
counts (pairs/sec vs devices — the pod-scaling curve the ROADMAP north-star
asks for).  On a 1-device CPU host it re-executes itself with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the curve exists
on laptops and in CI; on real accelerators it uses the devices as-is.

``--earlystop`` reports the convergence-aware serving rows (Budelmann et
al.'s stop-on-plateau, ``repro.engine.convergence``): a mixed easy/hard
batch and an all-easy batch, each registered with fixed ``iters`` and with
``stop=ConvergenceConfig(...)`` — steps saved, final-loss excess vs the
fixed run, and pairs/sec.  All timings are warm (compile-cached) runs; the
mixed batch shows the per-lane step savings at matched quality, the
all-easy batch the wall-clock win when every lane converges early and the
batched ``while_loop`` exits.

CSV: name,us_per_call,derived.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
if str(_ROOT) not in sys.path:  # direct execution: python benchmarks/...py
    sys.path.insert(0, str(_ROOT))
try:
    import repro  # noqa: F401  (installed via `pip install -e .`)
except ModuleNotFoundError:  # src-layout checkout without install
    sys.path.insert(0, str(_ROOT / "src"))

from benchmarks.common import emit
from repro.core import ffd as ffd_mod
from repro.core import metrics
from repro.core.options import RegistrationOptions
from repro.core.registration import affine_register, ffd_register
from repro.data.volumes import make_pair
from repro.engine.autotune import resolve_bsi

PAIRS = [("phantom_a", 0), ("phantom_b", 1)]

TILE = (6, 6, 6)


def monotone_remap(v):
    """Monotone-decreasing intensity remap (synthetic cross-modality)."""
    return (1.0 - v) ** 1.5


def run_multimodal(shape=(48, 40, 36), iters=25, similarities=("ssd", "nmi")):
    """The multi-modal rows: register (fixed, remapped moving) per similarity.

    MAE/SSIM are computed on the original (un-remapped) moving volume warped
    by the recovered field — the honest cross-modal score.
    """
    fixed, moving, _ = make_pair(shape=shape, tile=TILE,
                                 magnitude=2.0, seed=2)
    remapped = monotone_remap(moving)
    rows = [
        ("registration/multimodal/pre_registration", 0.0,
         f"mae={float(metrics.mae(moving, fixed)):.4f}"
         f"|ssim={float(metrics.ssim(moving, fixed)):.4f}"),
    ]
    for sim in similarities:
        res = ffd_register(fixed, remapped, tile=TILE, levels=2,
                           iters=iters, similarity=sim)
        disp = ffd_mod.dense_field(res.params, TILE, shape)
        recovered = ffd_mod.warp_volume(moving, disp)
        rows.append(
            (f"registration/multimodal/ffd_{sim}",
             round(res.seconds * 1e6, 0),
             f"mae={float(metrics.mae(recovered, fixed)):.4f}"
             f"|ssim={float(metrics.ssim(recovered, fixed)):.4f}"))
    return rows


def run(shape=(48, 40, 36), iters=25, affine_iters=30, multimodal=True):
    auto_mode, auto_impl = resolve_bsi(
        "auto", "auto", ffd_mod.grid_shape_for_volume(shape, TILE), TILE,
        measure_grad=True)
    rows = []
    for name, seed in PAIRS:
        fixed, moving, _ = make_pair(shape=shape, tile=TILE,
                                     magnitude=2.0, seed=seed)
        pre = (float(metrics.mae(moving, fixed)),
               float(metrics.ssim(moving, fixed)))
        aff = affine_register(fixed, moving, iters=affine_iters)
        res = {}
        for mode, impl in (("gather", "jnp"), ("separable", "jnp"),
                           ("matmul", "jnp"), (auto_mode, auto_impl)):
            if (mode, impl) in res:
                continue
            res[(mode, impl)] = ffd_register(
                fixed, moving, tile=TILE, levels=2, iters=iters,
                mode=mode, impl=impl,
            )
        # fused level step, forced on: the dense field and warped volume
        # never hit HBM (on CPU hosts the kernel runs in interpret mode —
        # a correctness-path trajectory row, not the TPU speedup story)
        fus = ffd_register(fixed, moving, options=RegistrationOptions(
            tile=TILE, levels=2, iters=iters, fused="on"))
        base = res[("gather", "jnp")]
        opt = res[("separable", "jnp")]
        mm = res[("matmul", "jnp")]
        auto = res[(auto_mode, auto_impl)]
        rows += [
            (f"registration/{name}/affine",
             round(aff.seconds * 1e6, 0),
             f"mae={float(metrics.mae(aff.warped, fixed)):.4f}"
             f"|ssim={float(metrics.ssim(aff.warped, fixed)):.4f}"),
            (f"registration/{name}/ffd_gather",
             round(base.seconds * 1e6, 0),
             f"mae={float(metrics.mae(base.warped, fixed)):.4f}"
             f"|ssim={float(metrics.ssim(base.warped, fixed)):.4f}"),
            (f"registration/{name}/ffd_separable",
             round(opt.seconds * 1e6, 0),
             f"mae={float(metrics.mae(opt.warped, fixed)):.4f}"
             f"|ssim={float(metrics.ssim(opt.warped, fixed)):.4f}"
             f"|reg_speedup=x{base.seconds / max(opt.seconds, 1e-9):.2f}"),
            (f"registration/{name}/ffd_matmul",
             round(mm.seconds * 1e6, 0),
             f"mae={float(metrics.mae(mm.warped, fixed)):.4f}"
             f"|ssim={float(metrics.ssim(mm.warped, fixed)):.4f}"
             f"|reg_speedup=x{base.seconds / max(mm.seconds, 1e-9):.2f}"),
            (f"registration/{name}/ffd_auto",
             round(auto.seconds * 1e6, 0),
             f"mae={float(metrics.mae(auto.warped, fixed)):.4f}"
             f"|ssim={float(metrics.ssim(auto.warped, fixed)):.4f}"
             f"|chosen={auto_mode}/{auto_impl}"
             f"|reg_speedup=x{base.seconds / max(auto.seconds, 1e-9):.2f}"),
            (f"registration/{name}/ffd_fused",
             round(fus.seconds * 1e6, 0),
             f"mae={float(metrics.mae(fus.warped, fixed)):.4f}"
             f"|ssim={float(metrics.ssim(fus.warped, fixed)):.4f}"
             f"|reg_speedup=x{base.seconds / max(fus.seconds, 1e-9):.2f}"),
            (f"registration/{name}/pre_registration", 0.0,
             f"mae={pre[0]:.4f}|ssim={pre[1]:.4f}"),
        ]
    if multimodal:
        rows += run_multimodal(shape=shape, iters=iters)
    return rows


def run_transforms(shape=(22, 20, 18), tile=(4, 4, 4), iters=12,
                   fold_iters=60, fold_lr=0.5, fold_magnitude=8.0):
    """Transform/regularizer rows: diffeomorphic velocity + analytic bending.

    Two sections.  (a) A standard pair registered with the classic
    displacement FFD, the stationary-velocity-field transform
    (``transform="velocity"``: scaling-and-squaring integration) and the
    analytic bending regularizer (``regularizer="bending"``, Shah et al.'s
    closed-form gradient) — time + quality + min Jacobian determinant per
    row.  (b) The IGS-safety fold case: an aggressive synthetic
    pneumoperitoneum (``fold_magnitude``) that the *unregularised*
    displacement FFD matches only by folding space (``min_jac < 0``), where
    the velocity transform (+ analytic bending) stays fold-free
    (``min_jac > 0``) at equal-or-better similarity — the acceptance
    workload of the pluggable-transform layer.
    """
    import jax.numpy as jnp

    from repro.core.regularizer import bending
    from repro.core.transform import dense_displacement, jacobian_determinant

    def min_jac(opts, params):
        disp = dense_displacement(opts.transform, params, opts.tile, shape,
                                  mode=opts.mode, impl=opts.impl)
        return float(jnp.min(jacobian_determinant(disp)))

    base = RegistrationOptions(tile=tile, levels=2, iters=iters,
                               mode="separable", impl="jnp",
                               grad_impl="xla", fused="off")
    fixed, moving, _ = make_pair(shape=shape, tile=tile, magnitude=2.0,
                                 seed=0)
    rows = []
    for name, opts in (
            ("ffd_displacement", base),
            ("ffd_velocity", base.replace(transform="velocity")),
            ("ffd_bending", base.replace(regularizer=bending(1e-3)))):
        res = ffd_register(fixed, moving, options=opts)
        rows.append(
            (f"registration/transforms/{name}",
             round(res.seconds * 1e6, 0),
             f"mae={float(metrics.mae(res.warped, fixed)):.4f}"
             f"|ssim={float(metrics.ssim(res.warped, fixed)):.4f}"
             f"|min_jac={min_jac(opts, res.params):.3f}"))

    ffold, mfold, _ = make_pair(shape=shape, tile=tile,
                                magnitude=fold_magnitude, seed=3)
    fold_base = base.replace(iters=fold_iters, lr=fold_lr,
                             bending_weight=0.0)
    disp_res = ffd_register(ffold, mfold, options=fold_base)
    vel_opts = fold_base.replace(transform="velocity",
                                 regularizer=bending(3e-3))
    vel_res = ffd_register(ffold, mfold, options=vel_opts)
    sim_disp = float(jnp.mean((disp_res.warped - ffold) ** 2))
    sim_vel = float(jnp.mean((vel_res.warped - ffold) ** 2))
    rows += [
        ("registration/transforms/fold_displacement",
         round(disp_res.seconds * 1e6, 0),
         f"sim={sim_disp:.5f}"
         f"|min_jac={min_jac(fold_base, disp_res.params):.3f}"),
        ("registration/transforms/fold_velocity",
         round(vel_res.seconds * 1e6, 0),
         f"sim={sim_vel:.5f}"
         f"|min_jac={min_jac(vel_opts, vel_res.params):.3f}"
         f"|sim_excess={sim_vel / max(sim_disp, 1e-12) - 1:+.1%}"),
    ]
    return rows


def run_earlystop(shape=(22, 20, 18), iters=24, batch=4, lr=0.1,
                  tol=3e-4, patience=8):
    """Early-stop rows: fixed-``iters`` vs ``stop=ConvergenceConfig(...)``.

    Two batches at a serving-friendly learning rate (descent is monotone,
    so the plateau rule is meaningful): ``mixed`` alternates nearly-aligned
    (magnitude 0.3) and hard (2.5) pairs — easy lanes freeze early at
    equal-or-better loss while hard lanes keep their full budget; ``easy``
    is all nearly-aligned pairs — every lane converges early, the
    ``while_loop`` exits, and the whole batch gets the wall-clock win.
    Each arm is timed on a warm (compile-cached) second call, so the rows
    never see a compile spike (``BatchRegistrationResult.compiled``).
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.engine import ConvergenceConfig, register_batch

    kw = dict(tile=TILE, levels=2, iters=iters, lr=lr,
              mode="separable", impl="jnp")
    stop = ConvergenceConfig(tol=tol, patience=patience)
    budget = 2 * iters  # Adam steps per pair under fixed iters (2 levels)

    def warm(F, M, reps=5, **extra):
        register_batch(F, M, **kw, **extra)  # compile on miss
        times = []
        for _ in range(reps):
            res = register_batch(F, M, **kw, **extra)
            assert not res.compiled, "warm call must hit the program cache"
            times.append(res.seconds)
        res.seconds = float(np.median(times))  # de-noise the gated timing
        return res

    rows = []
    for name, mags in (("mixed", [(0.3, 2.5)[s % 2] for s in range(batch)]),
                       ("easy", [0.3 + 0.05 * (s % 2) for s in range(batch)])):
        pairs = [make_pair(shape=shape, tile=TILE, magnitude=m, seed=s)
                 for s, m in enumerate(mags)]
        F = jnp.stack([p[0] for p in pairs])
        M = jnp.stack([p[1] for p in pairs])
        fixed = warm(F, M)
        es = warm(F, M, stop=stop)
        steps = np.asarray(es.steps)
        saved = 1.0 - steps.sum() / (len(mags) * budget)
        # worst-lane final-loss excess vs the fixed-iters run (acceptance:
        # within 2%; negative = the early-stopped run ended better)
        excess = float((np.asarray(es.losses[:, -1])
                        / np.asarray(fixed.losses[:, -1]) - 1).max())
        rows += [
            (f"registration/earlystop/{name}_fixed",
             round(fixed.seconds * 1e6, 0),
             f"pairs_per_s={len(mags) / fixed.seconds:.2f}"
             f"|steps_per_pair={budget}"),
            (f"registration/earlystop/{name}_adaptive",
             round(es.seconds * 1e6, 0),
             f"pairs_per_s={len(mags) / es.seconds:.2f}"
             f"|steps_saved={saved:.0%}"
             f"|mean_steps={steps.sum(axis=1).mean():.1f}"
             f"|max_loss_excess={excess:+.1%}"
             f"|speedup=x{fixed.seconds / es.seconds:.2f}"),
        ]
    return rows


def run_optimizers(shape=(22, 20, 18), adam_iters=48, magnitude=2.5,
                   seed=1, lr=0.1):
    """Optimiser rows: second-order entries vs Adam on the hard pair.

    One magnitude-``magnitude`` deformation pair, pure-SSD objective (the
    regime where Adam's fixed per-coordinate step costs it the tail):
    ``ffd_adam`` runs the full ``adam_iters`` budget; ``ffd_lbfgs`` and
    ``ffd_gauss_newton`` get 25% of it, and their ``tol_met`` field
    records whether they still reached Adam's final loss — the
    steps-to-tolerance acceptance of the optimiser registry, with the
    tolerance defined as what Adam achieves with 4x the steps.  Wall-clock
    is a warm (compile-cached) median, so the rows gate cleanly in
    ``compare.py``; mind that a second-order *step* is costlier than an
    Adam step (line-search evals / CG solves), so ``speedup`` is the
    honest wall-clock ratio, not the step ratio.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.engine import register_batch

    f, m, _ = make_pair(shape=shape, tile=TILE, magnitude=magnitude,
                        seed=seed)
    F, M = jnp.stack([f]), jnp.stack([m])
    base = dict(tile=TILE, levels=2, lr=lr, bending_weight=0.0,
                mode="separable", impl="jnp")

    def warm(options, reps=3):
        register_batch(F, M, options=options)  # compile on miss
        times, res = [], None
        for _ in range(reps):
            res = register_batch(F, M, options=options)
            assert not res.compiled, "warm call must hit the program cache"
            times.append(res.seconds)
        return res, float(np.median(times))

    adam_res, adam_s = warm(RegistrationOptions(**base, iters=adam_iters))
    adam_loss = float(np.asarray(adam_res.losses)[0, -1])
    rows = [("registration/optimizers/ffd_adam",
             round(adam_s * 1e6, 0),
             f"final_loss={adam_loss:.6f}|steps_per_level={adam_iters}")]
    quarter = adam_iters // 4
    for name in ("lbfgs", "gauss_newton"):
        res, secs = warm(RegistrationOptions(**base, iters=quarter,
                                             optimizer=name))
        loss = float(np.asarray(res.losses)[0, -1])
        rows.append((f"registration/optimizers/ffd_{name}",
                     round(secs * 1e6, 0),
                     f"final_loss={loss:.6f}"
                     f"|steps_per_level={quarter}"
                     f"|steps_vs_adam=25%"
                     f"|tol_met={'yes' if loss <= adam_loss else 'NO'}"
                     f"|speedup=x{adam_s / secs:.2f}"))
    return rows


def run_sharded(shape=(24, 20, 18), iters=6, batch=8, device_counts=None):
    """Pairs/sec vs device count: ``register_batch(..., mesh=...)`` scaling.

    One warm (compile-cached) timed run per mesh size; ``dev1`` is the
    unsharded single-device baseline the speedup column is relative to.
    """
    import jax
    import jax.numpy as jnp

    from repro.engine import make_registration_mesh, register_batch

    ndev = len(jax.devices())
    counts = (sorted({n for n in (1, 2, 4, 8, 16) if n <= ndev} | {ndev})
              if device_counts is None else list(device_counts))
    pairs = [make_pair(shape=shape, tile=TILE, magnitude=2.0, seed=s)
             for s in range(batch)]
    fixed = jnp.stack([p[0] for p in pairs])
    moving = jnp.stack([p[1] for p in pairs])
    kw = dict(tile=TILE, levels=2, iters=iters, mode="separable", impl="jnp")

    rows = []
    base_pps = None
    for n in counts:
        mesh = None if n == 1 else make_registration_mesh(n)
        cold = register_batch(fixed, moving, mesh=mesh, **kw).seconds
        t0 = time.perf_counter()
        register_batch(fixed, moving, mesh=mesh, **kw)
        warm = time.perf_counter() - t0
        pps = batch / warm
        base_pps = pps if base_pps is None else base_pps
        rows.append(
            (f"registration/sharded/dev{n}",
             round(warm / batch * 1e6, 0),
             f"pairs_per_s={pps:.3f}|speedup=x{pps / base_pps:.2f}"
             f"|batch={batch}|cold_s={cold:.1f}"))
    return rows


def main(sharded=False, earlystop=False, transforms=False, optimizers=False,
         **kwargs):
    if sharded:
        rows = run_sharded(**kwargs)
    elif earlystop:
        rows = run_earlystop(**kwargs)
    elif transforms:
        rows = run_transforms(**kwargs)
    elif optimizers:
        rows = run_optimizers(**kwargs)
    else:
        rows = run(**kwargs)
    return emit(rows, ["name", "us_per_call", "derived"])


if __name__ == "__main__":
    import argparse
    import os
    import subprocess
    import sys

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sharded", action="store_true",
                    help="pairs/sec vs device count via register_batch(mesh=)")
    ap.add_argument("--earlystop", action="store_true",
                    help="fixed-iters vs stop=ConvergenceConfig rows "
                         "(steps saved + loss excess on mixed/easy batches)")
    ap.add_argument("--transforms", action="store_true",
                    help="velocity-transform + analytic-bending rows incl. "
                         "the fold-case min-Jacobian comparison")
    ap.add_argument("--optimizers", action="store_true",
                    help="optimizer-registry rows: ffd_lbfgs / "
                         "ffd_gauss_newton at 25% of ffd_adam's steps "
                         "(steps-to-tolerance + wall-clock)")
    # None -> each path keeps its own defaults (run(): the paper-analogue
    # (48, 40, 36) x 25 iters; run_sharded(): a CPU-budget (24, 20, 18) x 6;
    # run_earlystop(): (22, 20, 18) x 24)
    ap.add_argument("--shape", type=int, nargs=3, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None,
                    help="batch size for --sharded / --earlystop")
    args = ap.parse_args()

    kwargs = {}
    if args.shape is not None:
        kwargs["shape"] = tuple(args.shape)
    if args.iters is not None:
        kwargs["iters"] = args.iters

    if args.transforms:
        main(transforms=True, **kwargs)
    elif args.optimizers:
        main(optimizers=True, **kwargs)
    elif args.earlystop:
        main(earlystop=True,
             **({"batch": args.batch} if args.batch is not None else {}),
             **kwargs)
    elif args.sharded:
        import jax

        flags = os.environ.get("XLA_FLAGS", "")
        if (jax.default_backend() == "cpu" and len(jax.devices()) == 1
                and "xla_force_host_platform_device_count" not in flags):
            # fake an 8-device pod and re-exec: the flag must be exported
            # before jax initialises, which already happened in this process
            env = dict(os.environ)
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
            sys.exit(subprocess.call([sys.executable, __file__]
                                     + sys.argv[1:], env=env))
        main(sharded=True, batch=args.batch or 8, **kwargs)
    else:
        main(**kwargs)
