"""Paper Figs. 5-7: BSI time-per-voxel and speedup vs tile size.

Wall-time on this container is CPU (the jnp forms are the paper's CPU-analog
measurements, Fig. 7); the TPU-kernel story is carried by the roofline
dry-run (`repro.launch.dryrun_bsi`).  ``gather`` plays NiftyReg-TV (the
paper's baseline), ``tt``/``ttli`` are the paper's contributions, and
``separable`` is this repo's beyond-paper form.

``--grad`` instead times the registration loop's real workload — forward +
backward through an SSD objective on the dense field — per
``(mode, impl, grad_impl)``: ``xla`` is plain autodiff of that forward
(whose transpose of the gather form is a per-voxel scatter-add), the other
adjoints are the analytic gather-only custom VJP (``jnp`` separable-
transpose / ``pallas`` kernel).  The derived column reports the backward-
path speedup over the same forward under ``xla`` autodiff.

``--fused`` times the full level step per similarity: the fused Pallas
megakernel (``core.ffd.fused_warp_loss`` — BSI + warp + similarity in one
VMEM pass, no dense field or warped volume in HBM) against the unfused
dense-field → warp → similarity composition, forward+backward.  On CPU
hosts the fused kernel runs in interpret mode, so these rows are a
correctness-path trajectory, not the TPU speedup story; the derived column
also reports peak device memory where the backend exposes it.

CSV: name,us_per_call,derived  where derived = ns/voxel | speedup-vs-gather
(forward sweep), speedup-vs-xla-autodiff (``--grad``), or
speedup-vs-unfused (``--fused``).
"""
from __future__ import annotations

import functools
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
if str(_ROOT) not in sys.path:  # direct execution: python benchmarks/...py
    sys.path.insert(0, str(_ROOT))
try:
    import repro  # noqa: F401  (installed via `pip install -e .`)
except ModuleNotFoundError:  # src-layout checkout without install
    sys.path.insert(0, str(_ROOT / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import FULL_VOLUMES, SCALED_VOLUMES, emit, grid_for, time_fn
from repro.core import ffd
from repro.kernels.ops import PALLAS_MODES

TILES = [3, 4, 5, 6, 7]
MODES = ["gather", "tt", "ttli", "separable", "matmul"]
# pallas/matmul adjoint kernels: interpret-only on CPU hosts
GRAD_IMPLS = ["xla", "jnp"]


def run(full=False, volumes=("phantom2", "porcine1"), reps=3, tiles=None,
        vol_table=None):
    vols = vol_table or (FULL_VOLUMES if full else SCALED_VOLUMES)
    rows = []
    for t in (tiles or TILES):
        tile = (t, t, t)
        base_ns = None
        for mode in MODES:
            total_t, total_vox = 0.0, 0
            for name in volumes:
                vol = vols[name]
                phi = grid_for(vol, tile)
                fn = jax.jit(functools.partial(
                    ffd.dense_field, tile=tile, vol_shape=vol, mode=mode))
                total_t += time_fn(fn, phi, reps=reps)
                total_vox += vol[0] * vol[1] * vol[2]
            ns_per_voxel = total_t / total_vox * 1e9
            if mode == "gather":
                base_ns = ns_per_voxel
            rows.append((
                f"bsi_speed/tile{t}/{mode}",
                round(total_t / len(volumes) * 1e6, 1),
                f"{ns_per_voxel:.2f}ns/vox|x{base_ns / ns_per_voxel:.2f}",
            ))
    return rows


def run_grad(full=False, volumes=("phantom2", "porcine1"), reps=3, tiles=None,
             vol_table=None, modes=None, impls=("jnp",), grad_impls=None):
    """Forward+backward rows per ``(mode, impl, grad_impl)`` (Adam-step load).

    Times ``jit(grad(loss))`` where ``loss`` is SSD of the dense field
    against a target — the BSI share of one optimisation step.  Each
    ``(mode, impl)``'s ``xla`` row (when present) is the baseline its
    custom-VJP rows are scored against.  ``impls`` defaults to the jnp
    forwards (Pallas forwards run interpret-mode on CPU hosts; pass
    ``impls=("jnp", "pallas")`` on TPU); combinations that cannot
    differentiate — a Pallas forward under ``xla`` autodiff — are skipped.
    Row names keep the historical ``{mode}-{grad_impl}`` form for the
    default jnp forward so baseline_ci.json keys stay stable.
    """
    vols = vol_table or (FULL_VOLUMES if full else SCALED_VOLUMES)
    rows = []
    for t in (tiles or TILES):
        tile = (t, t, t)
        for mode in (modes or MODES):
            for impl in impls:
                if impl == "pallas" and mode not in PALLAS_MODES:
                    continue  # no kernel (kernels.ops.NO_KERNEL says why)
                base_t = None
                for gi in (grad_impls or GRAD_IMPLS):
                    if impl == "pallas" and gi == "xla":
                        # the one known-undifferentiable combination (Pallas
                        # forwards have no VJP under plain autodiff); any
                        # other failure is a real regression and must crash
                        # the suite so the CI gate sees it
                        continue
                    total_t = 0.0
                    for name in volumes:
                        vol = vols[name]
                        phi = grid_for(vol, tile)
                        rng = np.random.default_rng(1)
                        tgt = jnp.asarray(rng.standard_normal(vol + (3,)),
                                          jnp.float32)

                        def loss(p, tile=tile, vol=vol, mode=mode, impl=impl,
                                 gi=gi, tgt=tgt):
                            d = ffd.dense_field(p, tile, vol, mode=mode,
                                                impl=impl, grad_impl=gi)
                            return jnp.sum((d - tgt) ** 2)

                        total_t += time_fn(jax.jit(jax.grad(loss)), phi,
                                           reps=reps)
                    if gi == "xla":
                        base_t = total_t
                    label = mode if impl == "jnp" else f"{mode}/{impl}"
                    rows.append((
                        f"bsi_grad/tile{t}/{label}-{gi}",
                        round(total_t / len(volumes) * 1e6, 1),
                        (f"x{base_t / total_t:.2f}-vs-xla" if base_t
                         else "no-xla-baseline"),
                    ))
    return rows


def run_fused(full=False, volumes=("phantom2",), reps=3, tiles=(5,),
              vol_table=None, similarities=("ssd", "ncc", "lncc", "nmi")):
    """Fused vs unfused level-step rows, forward+backward per similarity.

    Each pair of rows times ``jit(grad(...))`` of the same objective — the
    unfused dense-field → warp → similarity composition and the fused
    single-pass kernel — on the same volume and grid, so the ``_fused``
    row's derived column is a direct speedup over its ``_unfused`` sibling.
    A third ``_fused_matmul`` row runs the megakernel with its displacement
    stage in the MXU matrix form (``mode="matmul"`` → ``disp_form``), scored
    against the same unfused baseline.
    """
    from benchmarks.common import peak_hbm_bytes
    from repro.core.similarity import resolve_similarity

    vols = vol_table or (FULL_VOLUMES if full else SCALED_VOLUMES)
    rows = []
    for t in tiles:
        tile = (t, t, t)
        for sim in similarities:
            _, sim_fn = resolve_similarity(sim)
            total_un, total_fu, total_mm = 0.0, 0.0, 0.0
            for name in volumes:
                vol = vols[name]
                phi = grid_for(vol, tile)
                rng = np.random.default_rng(1)
                mov = jnp.asarray(rng.random(vol), jnp.float32)
                fix = jnp.asarray(rng.random(vol), jnp.float32)

                def unfused(p, tile=tile, vol=vol, sim_fn=sim_fn,
                            mov=mov, fix=fix):
                    d = ffd.dense_field(p, tile, vol)
                    return sim_fn(ffd.warp_volume(mov, d), fix)

                def fused(p, tile=tile, sim=sim, mov=mov, fix=fix):
                    return ffd.fused_warp_loss(p, mov, fix, tile,
                                               similarity=sim)

                def fused_mm(p, tile=tile, sim=sim, mov=mov, fix=fix):
                    return ffd.fused_warp_loss(p, mov, fix, tile,
                                               similarity=sim, mode="matmul")

                total_un += time_fn(jax.jit(jax.grad(unfused)), phi, reps=reps)
                total_fu += time_fn(jax.jit(jax.grad(fused)), phi, reps=reps)
                total_mm += time_fn(jax.jit(jax.grad(fused_mm)), phi,
                                    reps=reps)
            hbm = peak_hbm_bytes()
            hbm_s = "n/a" if hbm is None else f"{hbm / 2**20:.1f}MiB"
            n = len(volumes)
            rows.append((f"bsi_fused/tile{t}/{sim}_unfused",
                         round(total_un / n * 1e6, 1), "baseline"))
            rows.append((f"bsi_fused/tile{t}/{sim}_fused",
                         round(total_fu / n * 1e6, 1),
                         f"x{total_un / total_fu:.2f}-vs-unfused"
                         f"|peak_hbm={hbm_s}"))
            rows.append((f"bsi_fused/tile{t}/{sim}_fused_matmul",
                         round(total_mm / n * 1e6, 1),
                         f"x{total_un / total_mm:.2f}-vs-unfused"))
    return rows


def main(full=False, grad=False, fused=False, **kwargs):
    if fused:
        rows = run_fused(full, **kwargs)
    elif grad:
        rows = run_grad(full, **kwargs)
    else:
        rows = run(full, **kwargs)
    return emit(rows, ["name", "us_per_call", "derived"])


if __name__ == "__main__":
    main(full="--full" in sys.argv, grad="--grad" in sys.argv,
         fused="--fused" in sys.argv)
