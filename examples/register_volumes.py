"""End-to-end FFD registration of a synthetic liver-phantom pair (paper §6-7).

Creates a (fixed, moving) pair with a known smooth deformation (the
synthetic pneumoperitoneum), registers with affine then FFD (BSI inner
loop in the mode of your choice — default ``auto``, the engine autotuner's
winner for this grid/tile), and reports MAE/SSIM (paper Table 5) plus the
BSI share of runtime (paper Fig. 8-9 Amdahl argument).  ``--batch N``
registers N pairs in one jitted program via ``repro.engine.register_batch``.

``--similarity`` picks the loss term the optimiser minimises (see
``repro.core.similarity``); ``--multimodal`` applies a monotone intensity
remap to the moving volume first — the synthetic CT↔CBCT case where SSD
fails and ``--similarity nmi`` recovers the warp.

``--early-stop [TOL]`` swaps the fixed-``--iters`` loops for the
convergence-aware ``lax.while_loop`` (``repro.engine.convergence``): each
pyramid level stops when the loss plateaus and the report shows the Adam
steps actually run.

    python examples/register_volumes.py [--mode auto] [--batch 4]
    python examples/register_volumes.py --multimodal --similarity nmi
    python examples/register_volumes.py --early-stop 1e-4 --batch 4
"""
import argparse
import sys
import time
from pathlib import Path

try:
    import repro  # noqa: F401
except ModuleNotFoundError:  # src-layout checkout without install
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.core import RegistrationOptions, ffd, metrics
from repro.core.registration import affine_register, ffd_register
from repro.core.similarity import available_similarities
from repro.data.volumes import make_pair
from repro.engine import ConvergenceConfig, register_batch, resolve_bsi


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "gather", "tt", "ttli", "separable",
                             "matmul"])
    ap.add_argument("--shape", type=int, nargs=3, default=(64, 56, 48))
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--batch", type=int, default=0,
                    help="also register a batch of this many pairs in one "
                         "jitted program (repro.engine.register_batch)")
    ap.add_argument("--mesh", action="store_true",
                    help="shard the --batch registrations over every local "
                         "device (engine.shard.make_registration_mesh); on "
                         "CPU fake a pod first: XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=8")
    ap.add_argument("--similarity", default="ssd",
                    choices=available_similarities(),
                    help="loss term the optimiser minimises "
                         "(repro.core.similarity registry)")
    ap.add_argument("--multimodal", action="store_true",
                    help="monotone-remap the moving volume's intensities "
                         "first (synthetic cross-modality pair; use "
                         "--similarity nmi)")
    ap.add_argument("--early-stop", type=float, nargs="?", const=1e-4,
                    default=None, metavar="TOL",
                    help="stop each pyramid level when the loss plateaus "
                         "(relative improvement < TOL for a patience "
                         "window) instead of always running --iters steps "
                         "(repro.engine.convergence.ConvergenceConfig)")
    ap.add_argument("--lr", type=float, default=None,
                    help="Adam learning rate (default: the engine's 0.5, "
                         "or 0.12 with --early-stop — the plateau rule "
                         "wants an lr at which the loss actually descends, "
                         "and 0.5 overshoots for the first ~15 steps at "
                         "this scale)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.lr is None:
        args.lr = 0.12 if args.early_stop is not None else 0.5
        if args.early_stop is not None:
            print(f"--early-stop: using lr={args.lr} (pass --lr to "
                  "override); see README 'Early stopping'")
    if args.mesh and not args.batch:
        ap.error("--mesh shards the batched path; pass --batch N with it")

    tile = (6, 6, 6)
    shape = tuple(args.shape)
    mode, impl = resolve_bsi(args.mode, "auto",
                             ffd.grid_shape_for_volume(shape, tile), tile,
                             measure_grad=True, similarity=args.similarity)
    print(f"BSI form: {mode}/{impl}"
          + (" (autotuned)" if args.mode == "auto" else "")
          + f"; similarity: {args.similarity}")

    fixed, moving, _ = make_pair(shape=shape, tile=tile,
                                 magnitude=2.2, seed=0)
    source = moving
    if args.multimodal:
        moving = (1.0 - moving) ** 1.5  # monotone intensity remap
        print("multi-modal: moving volume intensities monotonically "
              "remapped; MAE/SSIM scored on the un-remapped volume "
              "warped by the recovered field")
    print(f"pair {fixed.shape}; pre-registration: "
          f"mae={float(metrics.mae(source, fixed)):.4f} "
          f"ssim={float(metrics.ssim(source, fixed)):.4f}")

    if not args.multimodal:
        aff = affine_register(fixed, moving,
                              options=RegistrationOptions(
                                  iters=40, lr=0.02,
                                  similarity=args.similarity))
        print(f"affine      ({aff.seconds:5.1f}s): "
              f"mae={float(metrics.mae(aff.warped, fixed)):.4f} "
              f"ssim={float(metrics.ssim(aff.warped, fixed)):.4f}")

    stop = (ConvergenceConfig(tol=args.early_stop)
            if args.early_stop is not None else None)
    # one options object configures every entry point below (and is the
    # compiled-program cache key — see README "One options object")
    opts = RegistrationOptions(tile=tile, levels=2, iters=args.iters,
                               lr=args.lr, mode=mode, impl=impl,
                               similarity=args.similarity, stop=stop)
    res = ffd_register(fixed, moving, options=opts, measure_bsi_time=True)
    disp = ffd.dense_field(res.params, tile, shape, mode=mode, impl=impl)
    recovered = ffd.warp_volume(source, disp)
    steps_note = ("" if res.steps is None else
                  f", steps/level {res.steps} of {args.iters}")
    print(f"ffd/{mode:9s} ({res.seconds:5.1f}s, "
          f"~{res.bsi_seconds:.1f}s in BSI{steps_note}): "
          f"mae={float(metrics.mae(recovered, fixed)):.4f} "
          f"ssim={float(metrics.ssim(recovered, fixed)):.4f}")

    if args.batch:
        import jax.numpy as jnp

        mesh = None
        label = f"batch x{args.batch}"
        if args.mesh:
            import jax

            from repro.engine import make_registration_mesh

            mesh = make_registration_mesh()
            label += f" over {len(jax.devices())} device(s)"
        pairs = [make_pair(shape=shape, tile=tile, magnitude=2.2, seed=s)
                 for s in range(args.batch)]
        F = jnp.stack([p[0] for p in pairs])
        M = jnp.stack([p[1] for p in pairs])
        sources = M
        if args.multimodal:
            M = (1.0 - M) ** 1.5  # same monotone remap as the single pair
        batch = register_batch(F, M, options=opts, mesh=mesh)
        cold = batch.seconds  # includes the one-time compile
        t0 = time.perf_counter()
        batch = register_batch(F, M, options=opts, mesh=mesh)
        warm = time.perf_counter() - t0
        disp0 = ffd.dense_field(batch.params[0], tile, shape,
                                mode=mode, impl=impl)
        mae = float(metrics.mae(ffd.warp_volume(sources[0], disp0), F[0]))
        steps_note = ("" if batch.steps is None else
                      f", steps {batch.steps.sum(axis=1).tolist()}"
                      f" of {2 * args.iters}")
        print(f"{label} (cold {cold:5.1f}s, warm {warm:5.2f}s"
              f" = {warm / args.batch:5.2f}s/pair{steps_note}): "
              f"mae[0]={mae:.4f}")


if __name__ == "__main__":
    main()
