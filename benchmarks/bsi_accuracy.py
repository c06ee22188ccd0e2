"""Paper Tables 3-4: interpolation accuracy vs a float64 reference.

The paper compares each implementation against a double-precision CPU
reference; lerp-form implementations (TTLI / VT / VV) come out ~2x more
accurate thanks to FMA.  Here: float32 forms vs the float64 oracle
(x64 enabled locally for the reference only).

CSV: name,us_per_call,derived  where derived = mean|err| (1e-6 units).
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit
from repro.core.interpolate import MODES as JNP_MODES
from repro.kernels import ops

TILES = [3, 5, 7]


def _f64_reference(phi64, tile):
    # float64 oracle evaluated with the direct Eq. (1) weighted sum
    from repro.kernels.ref import bsi_ref

    return bsi_ref(phi64, tile)


def run(grid_pts=9, channels=3, tiles=None):
    import jax.numpy as jnp

    rows = []
    rng = np.random.default_rng(0)
    with jax.experimental.enable_x64():
        for t in (tiles or TILES):
            tile = (t, t, t)
            phi_np = rng.standard_normal((grid_pts,) * 3 + (channels,))
            ref = np.asarray(_f64_reference(jnp.asarray(phi_np, jnp.float64), tile))
            phi32 = jnp.asarray(phi_np, jnp.float32)
            for mode, fn in JNP_MODES.items():
                out = np.asarray(fn(phi32, tile), np.float64)
                err = np.mean(np.abs(out - ref)) * 1e6
                rows.append((f"bsi_accuracy/tile{t}/jnp_{mode}", 0.0,
                             f"{err:.3f}e-6"))
            for mode in ops.PALLAS_MODES:
                out = np.asarray(
                    ops.bsi_pallas(phi32, tile, mode=mode), np.float64)
                err = np.mean(np.abs(out - ref)) * 1e6
                rows.append((f"bsi_accuracy/tile{t}/pallas_{mode}", 0.0,
                             f"{err:.3f}e-6"))
    return rows


def main(**kwargs):
    return emit(run(**kwargs), ["name", "us_per_call", "derived"])


if __name__ == "__main__":
    main()
