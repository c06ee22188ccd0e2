"""Mesh-sharded register_batch: rules, mesh helper, pad/strip, parity.

The in-process tests adapt to however many devices the process has — 1 in
the plain CI tests job, 8 in the ``multi-device`` job (which exports
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  The subprocess
test pins the 8-device layout so the acceptance path is exercised even in a
single-device run.
"""
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as PS

from repro.data.volumes import make_pair
from repro.distributed.sharding import REGISTRATION_RULES
from repro.engine import make_registration_mesh, register_batch
from repro.engine.batch import ffd_pipeline
from repro.engine.shard import (GRID_AXES, LOSS_AXES, VOLUME_AXES,
                                batch_mask, batch_multiple,
                                compile_sharded_batch, pad_batch)

TILE = (6, 6, 6)
SHAPE = (24, 20, 18)


def _stack(n):
    pairs = [make_pair(shape=SHAPE, tile=TILE, magnitude=1.5, seed=s)
             for s in range(n)]
    return (jnp.stack([p[0] for p in pairs]),
            jnp.stack([p[1] for p in pairs]))


def test_registration_rules_batch_over_data():
    r = REGISTRATION_RULES(("data",))
    assert r.spec(VOLUME_AXES) == PS(("data",), None, None, None)
    assert r.spec(GRID_AXES) == PS(("data",), None, None, None, None)
    assert r.spec(LOSS_AXES) == PS(("data",), None)
    # a pod axis folds into the batch shards, like TRAIN_RULES' batch
    assert REGISTRATION_RULES(("pod", "data"))["batch"] == ("pod", "data")


def test_make_registration_mesh_defaults_and_errors():
    mesh = make_registration_mesh()
    assert mesh.axis_names == ("data",)
    assert mesh.shape["data"] == len(jax.devices())
    assert batch_multiple(mesh) == len(jax.devices())
    assert make_registration_mesh(1).shape["data"] == 1
    with pytest.raises(ValueError):
        make_registration_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError):
        make_registration_mesh(0)


def test_pad_batch_and_mask_roundtrip():
    x = jnp.arange(6, dtype=jnp.float32).reshape(3, 2)
    padded, b = pad_batch(x, 4)
    assert padded.shape == (4, 2) and b == 3
    np.testing.assert_array_equal(np.asarray(padded[:b]), np.asarray(x))
    # pad rows repeat the last real pair, not zeros
    np.testing.assert_array_equal(np.asarray(padded[3]), np.asarray(x[2]))
    np.testing.assert_array_equal(
        np.asarray(batch_mask(b, padded.shape[0])),
        np.array([True, True, True, False]))
    # already-divisible batches pass through untouched
    same, b2 = pad_batch(x, 3)
    assert same.shape == (3, 2) and b2 == 3
    assert bool(batch_mask(b2, same.shape[0]).all())


def test_registration_sharding_places_batch_over_all_devices():
    """REGISTRATION_RULES + NamedSharding split a stack across every local
    device (1 in the plain job, 8 in the multi-device job)."""
    mesh = make_registration_mesh()
    n = mesh.shape["data"]
    spec = REGISTRATION_RULES(mesh.axis_names).spec(VOLUME_AXES)
    x = jnp.zeros((2 * n, 4, 4, 4), jnp.float32)
    y = jax.device_put(x, NamedSharding(mesh, spec))
    assert len({s.device for s in y.addressable_shards}) == n


def test_register_batch_b1():
    F, M = _stack(1)
    res = register_batch(F, M, tile=TILE, levels=1, iters=3,
                         mode="separable", impl="jnp")
    assert res.warped.shape == F.shape
    assert res.params.shape[0] == 1
    assert res.losses.shape == (1, 1)


def test_register_batch_mesh_matches_unsharded():
    """mesh= parity: B=3 is non-divisible for any even device count, so the
    pad+strip round-trip is exercised wherever this runs on >1 device."""
    F, M = _stack(3)
    kw = dict(tile=TILE, levels=2, iters=4, mode="separable", impl="jnp")
    base = register_batch(F, M, **kw)
    mesh = make_registration_mesh()
    res = register_batch(F, M, mesh=mesh, **kw)
    assert res.warped.shape == F.shape  # padding stripped on return
    assert res.params.shape == base.params.shape
    assert res.losses.shape == base.losses.shape
    np.testing.assert_allclose(np.asarray(res.warped),
                               np.asarray(base.warped), atol=1e-4)
    np.testing.assert_allclose(np.asarray(res.params),
                               np.asarray(base.params), atol=1e-4)
    np.testing.assert_allclose(np.asarray(res.losses),
                               np.asarray(base.losses),
                               rtol=1e-4, atol=1e-6)


def test_register_batch_b1_with_mesh():
    """B=1 pads up to the full device count and still strips back to 1."""
    F, M = _stack(1)
    kw = dict(tile=TILE, levels=1, iters=3, mode="separable", impl="jnp")
    base = register_batch(F, M, **kw)
    res = register_batch(F, M, mesh=make_registration_mesh(), **kw)
    assert res.warped.shape == F.shape
    np.testing.assert_allclose(np.asarray(res.warped),
                               np.asarray(base.warped), atol=1e-4)


def test_register_batch_mesh_rejects_bad_shapes():
    mesh = make_registration_mesh()
    v = jnp.zeros((8, 8, 8), jnp.float32)
    with pytest.raises(ValueError):
        register_batch(v, v, mesh=mesh)  # fixed.ndim != 4
    with pytest.raises(ValueError):
        register_batch(jnp.zeros((2, 8, 8, 8)), jnp.zeros((3, 8, 8, 8)),
                       mesh=mesh)


def test_compiled_sharded_outputs_stay_distributed():
    """out_shardings keep results on the mesh (no gather to one device)."""
    mesh = make_registration_mesh()
    n = mesh.shape["data"]
    pair = functools.partial(ffd_pipeline, tile=TILE, levels=1, iters=2,
                             lr=0.5, bending_weight=5e-3, mode="separable",
                             impl="jnp")
    fn = compile_sharded_batch(jax.vmap(pair), mesh)
    F, M = _stack(1)
    F = jnp.concatenate([F] * n, axis=0)
    M = jnp.concatenate([M] * n, axis=0)
    warped, phi, losses = fn(F, M)
    for out in (warped, phi, losses):
        assert len({s.device for s in out.addressable_shards}) == n


def test_sharded_8dev_subprocess():
    """Acceptance: 8 fake CPU devices, non-divisible B=3 and B=1, sharded ==
    unsharded to 1e-4 (runs in a fresh process so it holds even when the
    parent has a single device)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax, jax.numpy as jnp, numpy as np
        from repro.data.volumes import make_pair
        from repro.engine import register_batch, make_registration_mesh
        assert jax.device_count() == 8, jax.devices()
        pairs = [make_pair(shape=(18, 16, 14), tile=(5, 5, 5),
                           magnitude=1.2, seed=s) for s in range(3)]
        F = jnp.stack([p[0] for p in pairs])
        M = jnp.stack([p[1] for p in pairs])
        kw = dict(tile=(5, 5, 5), levels=2, iters=4,
                  mode="separable", impl="jnp")
        base = register_batch(F, M, **kw)
        mesh = make_registration_mesh()
        res = register_batch(F, M, mesh=mesh, **kw)
        assert res.warped.shape == F.shape
        np.testing.assert_allclose(np.asarray(res.warped),
                                   np.asarray(base.warped), atol=1e-4)
        np.testing.assert_allclose(np.asarray(res.params),
                                   np.asarray(base.params), atol=1e-4)
        np.testing.assert_allclose(np.asarray(res.losses),
                                   np.asarray(base.losses),
                                   rtol=1e-4, atol=1e-6)
        r1 = register_batch(F[:1], M[:1], mesh=mesh, **kw)
        b1 = register_batch(F[:1], M[:1], **kw)
        np.testing.assert_allclose(np.asarray(r1.warped),
                                   np.asarray(b1.warped), atol=1e-4)
        print("SHARD_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)  # the child pins its own before jax imports
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "SHARD_OK" in r.stdout, r.stderr[-2000:]
