"""The comparison that decides ``correct`` fails what it must: the control
(the program's bfloat16 path) and a run whose timed path is broken."""
import jax.numpy as jnp
import pytest

from chipbench import bench, calibrate


@pytest.fixture
def fresh_programs():
    """Compiled runners cached under a broken program must not outlive it."""
    from repro.core import registration
    from repro.engine import batch

    caches = (registration._ffd_level_runner, batch._compiled_batch)

    def clear():
        for c in caches:
            c.cache_clear()

    clear()
    yield
    clear()


@pytest.mark.parametrize("workload", ["porcine1_ssd.single",
                                      "phantom2_nmi.single"])
def test_control_is_not_correct(tiny_root, workload):
    out = calibrate.readings(tiny_root, workload, [31], [32, 33],
                             require_tpu=False)
    limits = bench.find_cell(tiny_root, workload).limits
    assert bench.judge(out["program"], limits)[0]
    assert not bench.judge(out["control"], limits)[0]


def test_a_step_that_leaves_the_grid_unchanged(tiny_root, monkeypatch,
                                               fresh_programs):
    from repro.engine import optimizer

    def unchanged(spec, obj, k, p, opt, g, *, lr):
        loss, g = obj.vg(p)
        return p, opt, g, loss, jnp.bool_(True)

    monkeypatch.setattr(optimizer, "_adam_step", unchanged)
    for workload in ("porcine1_ssd.single", "porcine1_ssd.batch"):
        line = bench.run_cell(tiny_root, workload, 5, 0.1, False,
                              require_tpu=False)
        assert not line["correct"] and line["failed"] > 0


def test_an_answer_altered_where_it_is_produced(tiny_root, monkeypatch):
    from repro.core import registration
    from repro.engine import batch

    real, real_batch = (registration.RegistrationResult,
                        batch.BatchRegistrationResult)
    monkeypatch.setattr(registration, "RegistrationResult",
                        lambda w, *a, **k: real(w.at[3, 4, 5].add(0.25),
                                                *a, **k))
    monkeypatch.setattr(batch, "BatchRegistrationResult",
                        lambda w, *a, **k: real_batch(
                            w.at[:, 3, 4, 5].add(0.25), *a, **k))
    for workload in ("porcine1_ssd.single", "porcine1_ssd.batch"):
        line = bench.run_cell(tiny_root, workload, 6, 0.1, False,
                              require_tpu=False)
        assert not line["correct"]


def test_half_the_batch_left_out(tiny_root, monkeypatch, fresh_programs):
    from repro.engine import batch

    real = batch._compiled_batch

    def half(vol_shape, options, mesh=None):
        fn = real(vol_shape, options, mesh)

        def run(fixed, moving):
            h = fixed.shape[0] // 2
            return tuple(jnp.concatenate([o, o]) for o in fn(fixed[:h],
                                                              moving[:h]))
        return run

    half.cache_info = real.cache_info
    monkeypatch.setattr(batch, "_compiled_batch", half)
    line = bench.run_cell(tiny_root, "porcine1_ssd.batch", 8, 0.1, False,
                          require_tpu=False)
    assert not line["correct"] and line["failed"] > 0
