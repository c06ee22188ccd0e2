"""Quickstart: B-spline interpolation in all five algorithm forms.

Shows the paper's core operation — expanding a coarse control grid into a
dense deformation field — plus the generic-interpolation use from paper §8
(2-D image zoom via a 3-D grid with a flat z axis), validated against the
float-oracle and timed.

    python examples/quickstart.py [--tiny]

``--tiny`` shrinks the volumes to CI-smoke size (compile + run every form
in seconds) — the CI gate runs exactly that.
"""
import argparse
import sys
import time
from pathlib import Path

try:
    import repro  # noqa: F401
except ModuleNotFoundError:  # src-layout checkout without install
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ffd
from repro.core.interpolate import MODE_NAMES, interpolate
from repro.kernels import ops
from repro.kernels.ref import bsi_ref


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI-smoke sizes (seconds, not minutes, on CPU)")
    args = ap.parse_args()
    rng = np.random.default_rng(0)

    # --- 1. dense deformation field from a control grid (the FFD inner loop)
    tile = (5, 5, 5)                       # NiftyReg's default spacing
    vol = (30, 25, 20) if args.tiny else (80, 75, 70)
    gshape = ffd.grid_shape_for_volume(vol, tile)
    phi = jnp.asarray(rng.standard_normal(gshape + (3,)), jnp.float32)

    ref = bsi_ref(phi, tile)
    print(f"control grid {phi.shape} -> dense field {ref.shape}")
    for mode in MODE_NAMES:
        fn = jax.jit(lambda p, m=mode: interpolate(p, tile, mode=m))
        out = fn(phi)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(phi))
        dt = time.perf_counter() - t0
        err = float(jnp.max(jnp.abs(out - ref)))
        print(f"  {mode:10s}: {dt*1e3:7.1f} ms   max|err vs oracle| = {err:.2e}")

    # --- 2. the same kernels in Pallas (TPU target, interpret mode on CPU)
    for mode in ops.PALLAS_MODES:
        out = ops.bsi_pallas(phi, tile, mode=mode)
        err = float(jnp.max(jnp.abs(out - ref)))
        print(f"pallas {mode}: max|err| = {err:.2e}")

    # --- 3. generic image zoom (paper §8): pixels as control points
    img = jnp.asarray(rng.standard_normal((36, 36)), jnp.float32)
    phi2d = img[:, :, None, None]          # (nx, ny, 1-ish z, C=1)
    phi2d = jnp.broadcast_to(phi2d, (36, 36, 4, 1))
    zoom = interpolate(phi2d, (4, 4, 1), mode="separable")
    print(f"2-D zoom: {img.shape} -> {zoom.shape[:2]} (4x upsampling)")


if __name__ == "__main__":
    main()
