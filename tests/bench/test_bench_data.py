"""The benchmark's seeded pairs: deterministic, and the program's recipe."""
import jax.numpy as jnp
import numpy as np

from chipbench import data

SHAPE = (20, 18, 14)


def test_pair_is_deterministic_per_seed():
    a = data.make_pair(SHAPE, 3)
    b = data.make_pair(SHAPE, 3)
    c = data.make_pair(SHAPE, 4)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert float(jnp.max(jnp.abs(a[1] - c[1]))) > 0.1


def test_pair_seeds_are_stable_and_fit_any_seed():
    seeds = [data.pair_seed(s, i) for s in (0, -5, 2**31 + 7, 10**12)
             for i in range(3)]
    assert len(set(seeds)) == len(seeds)
    assert all(0 <= s < 2**31 for s in seeds)
    assert data.pair_seed(2**31 + 7, 1) == data.pair_seed(2**31 + 7, 1)


def test_phantom_matches_the_host_recipe():
    from repro.data.volumes import make_phantom

    for seed in (0, 11):
        ours = np.asarray(data.make_phantom(SHAPE, seed))
        host = np.asarray(make_phantom(SHAPE, seed=seed))
        np.testing.assert_allclose(ours, host, atol=2e-6)


def test_pair_matches_the_host_recipe():
    from repro.data.volumes import make_pair

    fixed, moving = data.make_pair(SHAPE, 5, tile=(6, 6, 6), magnitude=2.5)
    hf, hm, _ = make_pair(SHAPE, tile=(6, 6, 6), magnitude=2.5, seed=5)
    np.testing.assert_allclose(np.asarray(fixed), np.asarray(hf), atol=2e-6)
    np.testing.assert_allclose(np.asarray(moving), np.asarray(hm), atol=2e-5)


def test_remap_is_monotone_decreasing_on_the_intensity_range():
    v = jnp.linspace(0.0, 1.0, 101)
    r = np.asarray(data.monotone_remap(v))
    assert r[0] == 1.0 and r[-1] == 0.0 and np.all(np.diff(r) < 0)
    _, moving = data.make_pair(SHAPE, 5)
    _, remapped = data.make_pair(SHAPE, 5, remap="monotone")
    np.testing.assert_allclose(np.asarray(remapped),
                               np.asarray(data.monotone_remap(moving)))
