"""Smoke run of the registration engine on one TPU chip (or four).

    python chip_smoke.py               # one chip: phases a-d
    python chip_smoke.py --four-chips  # four chips: phase e only

Phases (one process; every number printed names the device it ran on):

a. device check: JAX must see a TPU, or the script exits non-zero and
   prints no result line;
b. ``ffd_register`` with default ``RegistrationOptions`` on a porcine1-sized
   pair (303 x 167 x 212, paper Table 2):
   the autotuned BSI forms and every candidate the race skipped, the Pallas
   kernel in the compiled level step, autotune + compile time as set-up,
   warm seconds per level, finite decreasing losses and the MAE;
c. the BSI forward and adjoint kernels at phantom1's grid (512 x 228 x 385
   voxels, the largest volume) against the gather-form reference under
   ``jax.default_matmul_precision("highest")``;
d. ``RegistrationScheduler`` answering three requests at porcine1 (fewer
   steps per level than phase b: phase b judges the registration, this
   phase that the service runs the same path);
e. (``--four-chips``) ``register_batch(mesh=)`` of four porcine1 pairs on a
   four-device mesh against one-device runs of each pair, with the Pallas
   forward and adjoint pinned (phase b shows what ``"auto"`` picks; the
   point here is the kernels under ``shard_map``).

The last line of standard output is ``{"ok": true, "device": {...}}`` and
is printed only when every phase passed.  The persistent compilation cache
goes where ``repro.launch.compile_cache`` puts it; the autotuner's result
cache goes to a fresh temporary directory, so every run races the forms.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PORCINE1 = (303, 167, 212)
PHANTOM1 = (512, 228, 385)
REL_LIMIT = 1e-4  # phase c: max|kernel - reference| / max|reference|
# phase e: what a user reads off a registration must agree between the mesh
# and one device (intensities lie in [0, 1]); params are reported, not
# checked: where the image is flat the loss gradient is rounding noise, and
# Adam's normalised step turns a different rounding into a different step
WARPED_ATOL, LOSS_RTOL = 1e-4, 1e-5
SERVE_ITERS = 10  # phase d: three lanes at porcine1 within the run's budget


def log(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def make_moving(fixed, seed, tile=(6, 6, 6), magnitude=2.5):
    """A moving volume: ``fixed`` warped by a random smooth control grid
    (the recipe of ``repro.data.volumes.make_pair``, reusing its phantom)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ffd

    rng = np.random.default_rng(seed + 1)
    gshape = ffd.grid_shape_for_volume(fixed.shape, tile)
    phi = jnp.asarray(rng.normal(0.0, magnitude, gshape + (3,)), jnp.float32)
    return ffd.warp_volume(fixed, ffd.dense_field(phi, tile, fixed.shape))


def mae(a, b):
    import jax.numpy as jnp

    return float(jnp.mean(jnp.abs(a - b)))


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def phase_register(iters):
    """b. ffd_register at porcine1 through the normal entry point."""
    import jax
    import numpy as np

    from repro.core import RegistrationOptions, ffd
    from repro.core.registration import _ffd_level_runner, ffd_register
    from repro.data.volumes import make_pair
    from repro.engine.autotune import resolve_options
    from repro.engine.batch import ffd_level_loss

    t0 = time.perf_counter()
    fixed, moving, _ = make_pair(shape=PORCINE1, seed=0)
    jax.block_until_ready(moving)
    log("b", f"pair {PORCINE1} made in {time.perf_counter() - t0:.3f} s "
             "(set-up, host)")

    options = RegistrationOptions(**iters)
    t0 = time.perf_counter()
    opts = resolve_options(options, PORCINE1)
    autotune_s = time.perf_counter() - t0
    log("b", f"resolved mode={opts.mode} impl={opts.impl} "
             f"grad_impl={opts.grad_impl} fused={opts.fused} "
             f"({opts.fused_reason})")
    for name, why in opts.skipped:
        log("b", f"skipped {name}: {why}")

    t0 = time.perf_counter()
    first = ffd_register(fixed, moving, options=options)
    jax.block_until_ready(first.warped)
    first_s = time.perf_counter() - t0

    # warm: the same pyramid through the cached per-level programs
    pyramid = [(fixed, moving)]
    for _ in range(opts.levels - 1):
        pyramid.append(tuple(ffd.downsample2(v) for v in pyramid[-1]))
    def start_loss(p, f, m):
        return ffd_level_loss(
            f, m, tile=opts.tile, bending_weight=opts.bending_weight,
            mode=opts.mode, impl=opts.impl, grad_impl=opts.grad_impl,
            similarity=opts.similarity, fused=opts.fused)(p)

    phi, warm, traces, starts = None, [], [], []
    for f, m in pyramid[::-1]:
        gshape = ffd.grid_shape_for_volume(f.shape, opts.tile)
        phi = (jax.numpy.zeros(gshape + (3,), jax.numpy.float32)
               if phi is None else ffd.upsample_grid(phi, gshape))
        starts.append(float(jax.jit(start_loss)(phi, f, m)))
        runner = _ffd_level_runner(f.shape, opts)
        t0 = time.perf_counter()
        phi, trace = runner(phi, f, m)[:2]
        jax.block_until_ready(phi)
        warm.append(time.perf_counter() - t0)
        traces.append(np.asarray(trace))
    warm_total = sum(warm)
    log("b", f"autotune {autotune_s:.3f} s, first call {first_s:.3f} s, "
             f"warm call {warm_total:.3f} s -> set-up "
             f"{autotune_s + first_s - warm_total:.3f} s")
    log("b", "warm seconds per level (coarse -> fine): "
             + ", ".join(f"{w:.4f}" for w in warm))

    if opts.impl == "pallas":
        f, m = pyramid[0]
        txt = runner.lower(jax.numpy.zeros_like(phi), f, m).compile()
        found = "tpu_custom_call" in txt.as_text()
        log("b", f"finest level step contains tpu_custom_call: {found}")
        check(found, "impl=pallas but the level step holds no Pallas kernel")

    # the objective adds the bending term to the similarity, so it can rise
    # while the image match improves; the registration is judged by the
    # similarity of the registered pair and by the MAE
    for lvl, (tr, s0) in enumerate(zip(traces, starts)):
        log("b", f"level {lvl} objective: start {s0:.6g}, after step 1 "
                 f"{tr[0]:.6g}, min {tr.min():.6g}, last {tr[-1]:.6g}")
        check(np.all(np.isfinite(tr)), f"level {lvl}: non-finite losses {tr}")
    ssd0 = float(jax.numpy.mean((moving - fixed) ** 2))
    ssd1 = float(jax.numpy.mean((first.warped - fixed) ** 2))
    before, after = mae(moving, fixed), mae(first.warped, fixed)
    log("b", f"SSD to fixed {ssd0:.6g} -> {ssd1:.6g}; MAE to fixed "
             f"{before:.6f} -> {after:.6f}")
    check(ssd1 < ssd0 and after < before,
          "the registration did not improve the match to the fixed volume")
    return fixed, options


def _gather_slabs(phi, tile, g=None, slab=4):
    """Gather-form reference forward (or, with ``g``, its VJP), computed
    in x-slabs of ``slab`` tiles so the reference fits the device."""
    import jax
    import jax.numpy as jnp

    from repro.core.interpolate import bsi_gather

    fwd = jax.jit(lambda p: bsi_gather(p, tile))
    vjp = jax.jit(lambda p, gs: jax.vjp(fwd, p)[1](gs)[0])
    tx = phi.shape[0] - 3
    out = [] if g is None else jnp.zeros_like(phi)
    for a in range(0, tx, slab):
        b = min(a + slab, tx)
        sub = phi[a: b + 3]
        if g is None:
            out.append(fwd(sub))
        else:
            out = out.at[a: b + 3].add(vjp(sub, g[a * tile[0]: b * tile[0]]))
    return jnp.concatenate(out, axis=0) if g is None else out


def phase_kernels():
    """c. BSI forward + adjoint kernels at phantom1 vs the gather form."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ffd
    from repro.kernels import ops

    tile = (5, 5, 5)
    gshape = ffd.grid_shape_for_volume(PHANTOM1, tile)
    key = jax.random.PRNGKey(0)
    phi = jax.random.normal(key, gshape + (3,), jnp.float32)
    dense = tuple((n - 3) * d for n, d in zip(gshape, tile)) + (3,)
    g = jax.random.normal(jax.random.PRNGKey(1), dense, jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref_fwd = _gather_slabs(phi, tile)
        ref_adj = _gather_slabs(phi, tile, g)
    for mode in ops.PALLAS_MODES:
        fwd = jax.jit(lambda p, mode=mode: ops.bsi_pallas(p, tile, mode=mode))
        txt = fwd.lower(phi).compile().as_text()
        out = fwd(phi)
        err = float(jnp.max(jnp.abs(out - ref_fwd)) / jnp.max(jnp.abs(ref_fwd)))
        log("c", f"forward/{mode}: grid {gshape} dense {dense[:3]} "
                 f"max|err|/max|ref| = {err:.3e} (limit {REL_LIMIT:.0e}), "
                 f"tpu_custom_call={'tpu_custom_call' in txt}")
        check(err <= REL_LIMIT and np.isfinite(err), f"forward/{mode} {err}")
        check("tpu_custom_call" in txt, f"forward/{mode} ran no kernel")
    for form in ("separable", "matmul"):
        adj = jax.jit(lambda x, form=form: ops.bsi_adjoint_pallas(
            x, tile, form=form))
        txt = adj.lower(g).compile().as_text()
        out = adj(g)
        err = float(jnp.max(jnp.abs(out - ref_adj)) / jnp.max(jnp.abs(ref_adj)))
        log("c", f"adjoint/{form}: grid {gshape} dense {dense[:3]} "
                 f"max|err|/max|ref| = {err:.3e} (limit {REL_LIMIT:.0e}), "
                 f"tpu_custom_call={'tpu_custom_call' in txt}")
        check(err <= REL_LIMIT and np.isfinite(err), f"adjoint/{form} {err}")
        check("tpu_custom_call" in txt, f"adjoint/{form} ran no kernel")


def phase_serve(fixed, options):
    """d. RegistrationScheduler answers three porcine1 requests."""
    import numpy as np

    from repro.engine.serve import RegistrationScheduler

    sched = RegistrationScheduler(options, lanes=3, chunk=options.iters)
    log("d", f"{options.iters} steps per level, 3 lanes")
    movings = [make_moving(fixed, seed) for seed in (1, 2, 3)]
    t0 = time.perf_counter()
    handles = [sched.submit(fixed, m) for m in movings]
    sched.run_until_idle()
    log("d", f"3 requests answered in {time.perf_counter() - t0:.3f} s "
             f"(set-up included); {sched.stats}")
    for h, m in zip(handles, movings):
        r = h.result()
        before, after = mae(m, fixed), mae(r.warped, fixed)
        log("d", f"request {h.id}: {r.seconds:.3f} s, losses {r.losses}, "
                 f"MAE {before:.6f} -> {after:.6f}")
        check(np.all(np.isfinite(r.losses)) and np.isfinite(after)
              and r.warped.shape == fixed.shape, f"request {h.id}: {r}")


def phase_four_chips(iters):
    """e. register_batch over a 4-device mesh vs one-device runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import RegistrationOptions
    from repro.data.volumes import make_phantom
    from repro.engine import make_registration_mesh, register_batch

    n = len(jax.devices())
    check(n == 4, f"--four-chips needs 4 devices, JAX sees {n}")
    fixed = make_phantom(PORCINE1, seed=0)
    F = jnp.stack([fixed] * n)
    M = jnp.stack([make_moving(fixed, seed) for seed in range(n)])
    options = RegistrationOptions(mode="separable", impl="pallas",
                                  grad_impl="pallas", fused="off", **iters)
    mesh = make_registration_mesh(n)
    t0 = time.perf_counter()
    sharded = register_batch(F, M, options=options, mesh=mesh)
    sharded_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    solo = [register_batch(F[i:i + 1], M[i:i + 1], options=options)
            for i in range(n)]
    solo_s = time.perf_counter() - t0
    log("e", f"B={n} {PORCINE1}: mesh of {n} {sharded_s:.3f} s, {n} "
             f"one-device B=1 runs {solo_s:.3f} s (compiles included)")
    diffs = {}
    for name in ("warped", "params", "losses"):
        a = np.asarray(getattr(sharded, name))
        b = np.concatenate([np.asarray(getattr(s, name)) for s in solo])
        diffs[name] = (float(np.max(np.abs(a - b))), int(np.sum(a != b)),
                       a.size)
    log("e", "mesh vs one device, max|diff| (differing / total): " + ", ".join(
        f"{k} {v[0]:.3e} ({v[1]}/{v[2]})" for k, v in diffs.items()))
    for i in range(n):
        log("e", f"pair {i}: MAE {mae(M[i], F[i]):.6f} -> "
                 f"{mae(sharded.warped[i], F[i]):.6f}")
    lrel = diffs["losses"][0] / float(np.max(np.abs(np.asarray(
        sharded.losses))))
    check(diffs["warped"][0] <= WARPED_ATOL and lrel <= LOSS_RTOL,
          f"mesh and one-device results differ: {diffs}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh comparison (phase e)")
    ap.add_argument("--iters", type=int, default=None,
                    help="optimiser steps per pyramid level (default: "
                         "RegistrationOptions' own)")
    args = ap.parse_args(argv)
    iters = {} if args.iters is None else {"iters": args.iters}

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {device}); nothing measured",
              file=sys.stderr)
        return 1
    log("a", f"{device['count']} x {dev.platform} ({dev.device_kind})")

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir, cache = enable_compile_cache()
    tune_dir = tempfile.mkdtemp(prefix="chip-smoke-autotune-")
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(tune_dir, "bsi.json")
    log("a", f"compile cache {cache_dir}")

    t_start = time.perf_counter()
    try:
        if args.four_chips:
            phase_four_chips(iters)
        else:
            fixed, options = phase_register(iters)
            phase_kernels()
            phase_serve(fixed, options.replace(iters=SERVE_ITERS))
    except Exception:
        import traceback

        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    finally:
        import shutil

        shutil.rmtree(tune_dir, ignore_errors=True)
    log("done", f"{time.perf_counter() - t_start:.3f} s; persistent compile "
                f"cache hits {cache.hits}, misses {cache.misses}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
