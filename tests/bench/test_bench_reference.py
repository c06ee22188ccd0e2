"""The benchmark's plain reference agrees with the program at small sizes
(the program with its jnp BSI forms, on the CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import data, reference

SHAPE = (22, 19, 16)
TILE = (5, 5, 5)
CFG = {"tile": list(TILE), "levels": 2, "iters": 3, "lr": 0.125,
       "similarity": {"name": "ssd"},
       "regularizer": {"name": "bending", "weight": 1e-3}}


def _grid(seed=0, scale=1.5):
    g = reference.grid_shape(SHAPE, TILE) + (3,)
    return scale * jax.random.normal(jax.random.PRNGKey(seed), g, jnp.float32)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def test_bsi_matches_the_gather_form():
    from repro.core.interpolate import bsi_gather

    phi = _grid()
    ours = reference.bsi(phi, TILE, SHAPE)
    theirs = bsi_gather(phi, TILE)[:SHAPE[0], :SHAPE[1], :SHAPE[2]]
    assert _rel(ours, theirs) < 1e-6


def test_bending_energy_matches_the_analytic_form():
    from repro.core.regularizer import bending_energy_fn

    phi = _grid(1)
    energy = bending_energy_fn(phi.shape[:3], TILE)
    assert _rel(reference.bending_energy(phi, TILE), energy(phi)) < 1e-5


def test_warp_upsample_and_pyramid_match_the_program():
    from repro.core import ffd

    fixed, moving = data.make_pair(SHAPE, 2)
    disp = reference.bsi(_grid(2), TILE, SHAPE)
    assert _rel(reference.warp(moving, disp),
                ffd.warp_volume(moving, disp)) < 1e-6
    phi = _grid(3)
    new = reference.grid_shape((44, 38, 32), TILE)
    assert _rel(reference.upsample_grid(phi, new),
                ffd.upsample_grid(phi, new)) < 1e-6
    np.testing.assert_allclose(np.asarray(reference.downsample2(fixed)),
                               np.asarray(ffd.downsample2(fixed)), rtol=1e-6)


@pytest.mark.parametrize("similarity", [{"name": "ssd"},
                                        {"name": "nmi", "bins": 64}])
def test_level_objective_and_gradient_match_the_program(similarity):
    from repro.core.regularizer import bending
    from repro.core.similarity import nmi
    from repro.engine.batch import ffd_level_loss

    cfg = dict(CFG, similarity=similarity)
    fixed, moving = data.make_pair(SHAPE, 4)
    sim = "ssd" if similarity["name"] == "ssd" else nmi(bins=64)
    theirs = ffd_level_loss(fixed, moving, tile=TILE, bending_weight=0.0,
                            mode="separable", impl="jnp", grad_impl="jnp",
                            similarity=sim, regularizer=bending(1e-3))
    ours = reference.level_objective(fixed, moving, cfg)
    phi = _grid(4, 0.8)
    lo, go = jax.value_and_grad(ours)(phi)
    lt, gt = jax.value_and_grad(theirs)(phi)
    assert abs(float(lo) - float(lt)) / abs(float(lt)) < 1e-5
    assert _rel(go, gt) < 1e-3


def test_chunked_nmi_equals_one_chunk():
    fixed, moving = data.make_pair(SHAPE, 6, remap="monotone")
    whole = reference.nmi(moving, fixed, bins=64, chunk=10**9)
    parts = reference.nmi(moving, fixed, bins=64, chunk=1000)
    assert abs(float(whole) - float(parts)) < 1e-6


def test_registration_matches_ffd_register():
    from repro.core import RegistrationOptions
    from repro.core.regularizer import bending
    from repro.core.registration import ffd_register

    fixed, moving = data.make_pair(SHAPE, 8)
    opts = RegistrationOptions(tile=TILE, levels=2, iters=3, lr=0.125,
                               regularizer=bending(1e-3), mode="separable",
                               impl="jnp", grad_impl="jnp", fused="off")
    theirs = ffd_register(fixed, moving, options=opts)
    ours = reference.register(fixed, moving, CFG)
    assert _rel(ours["losses"], theirs.losses) < 1e-5
    assert _rel(ours["phi"], theirs.params) < 1e-4
    assert float(jnp.max(jnp.abs(ours["warped"] - theirs.warped))) < 1e-5
    assert float(theirs.losses[-1]) < float(reference.objective_at(
        jnp.zeros_like(ours["phi"]), fixed, moving, CFG))
