"""engine.serve: lane recycling correctness, bucketing, and failure modes.

The load-bearing claim of continuous batching is that splicing a queued
pair into a lane freed mid-flight changes *scheduling*, not *results*: a
recycled request must match a solo ``ffd_register`` of the same pair.
Everything time-dependent runs under a fake clock so deadlines are
deterministic (device work still runs; only the scheduler's notion of
"now" is faked).
"""
import asyncio
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.options import RegistrationOptions
from repro.core.registration import ffd_register
from repro.engine.convergence import ConvergenceConfig
from repro.engine.serve import (AsyncRegistrationService, QueueFull,
                                RegistrationScheduler, RegistrationTimeout)

SHAPE = (22, 20, 18)
OPTS = RegistrationOptions(
    tile=(6, 6, 6), levels=2, iters=16, lr=0.1,
    mode="separable", impl="jnp", grad_impl="xla",
    stop=ConvergenceConfig(tol=2e-3, patience=3))


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _mixed_pairs(n, shape=SHAPE, hard_every=3, seed=0):
    """Every ``hard_every``-th pair needs the full budget; the rest plateau
    within a few steps — the contrast that makes lanes free mid-flight."""
    rng = np.random.default_rng(seed)
    base = rng.normal(size=shape).astype(np.float32)
    x, y, z = np.meshgrid(*[np.linspace(0, np.pi, s) for s in shape],
                          indexing="ij")
    wave = (np.sin(x) * np.sin(y) * np.sin(z)).astype(np.float32)
    out = []
    for i in range(n):
        f = base + 0.05 * rng.normal(size=shape).astype(np.float32)
        if i % hard_every == 0:
            m = np.roll(f, 3, axis=0) + 2.5 * wave
            m = m + 0.3 * rng.normal(size=shape).astype(np.float32)
        else:
            m = f + 0.02 * wave
        out.append((f, m.astype(np.float32)))
    return out


class TestRecycling:
    def test_recycled_matches_solo(self):
        """Requests spliced into mid-flight lane arrays reproduce solo
        ``ffd_register`` step counts exactly and warps to <= 1e-5 (the
        chunked vmapped scan fuses differently from the solo while_loop,
        so the last float digits may differ — trajectories do not)."""
        pairs = _mixed_pairs(6)
        sched = RegistrationScheduler(OPTS, lanes=2, chunk=3, max_queue=16)
        handles = [sched.submit(f, m) for f, m in pairs]
        sched.run_until_idle()
        assert sched.stats.recycled >= 1
        assert sched.stats.completed == len(pairs)
        recycled_seen = 0
        for (f, m), h in zip(pairs, handles):
            served = h.result()
            solo = ffd_register(f, m, options=OPTS)
            assert served.steps == solo.steps
            np.testing.assert_allclose(np.asarray(served.warped),
                                       np.asarray(solo.warped), atol=1e-5)
            recycled_seen += served.recycled
        assert recycled_seen == sched.stats.recycled

    def test_chunk_width_never_changes_trajectories(self):
        """chunk only sets when the host looks: step counts are identical
        across chunk widths (warps again to fusion-level 1e-5)."""
        f, m = _mixed_pairs(1)[0]
        results = []
        for chunk in (1, 5):
            sched = RegistrationScheduler(OPTS, lanes=2, chunk=chunk)
            h = sched.submit(f, m)
            sched.run_until_idle()
            results.append(h.result())
        assert results[0].steps == results[1].steps
        np.testing.assert_allclose(np.asarray(results[0].warped),
                                   np.asarray(results[1].warped), atol=1e-5)


class TestBucketing:
    def test_one_compile_per_shape_and_level(self):
        shapes = [SHAPE, (18, 16, 14)]
        sched = RegistrationScheduler(OPTS, lanes=2, chunk=4)
        rng = np.random.default_rng(1)
        for shape in shapes:
            for _ in range(2):
                f = rng.normal(size=shape).astype(np.float32)
                sched.submit(f, np.roll(f, 1, axis=0))
        sched.run_until_idle()
        assert sched.stats.buckets == len(shapes)
        assert sched.stats.compiles == OPTS.levels * len(shapes)
        assert sched.stats.completed == 2 * len(shapes)

    def test_shape_mismatch_rejected(self):
        sched = RegistrationScheduler(OPTS)
        f = np.zeros(SHAPE, np.float32)
        with pytest.raises(ValueError, match="equal shapes"):
            sched.submit(f, np.zeros((18, 16, 14), np.float32))


class TestFailureModes:
    def test_timeout_is_clean(self):
        clock = FakeClock()
        sched = RegistrationScheduler(OPTS, lanes=1, chunk=4,
                                      timeout=5.0, clock=clock)
        f, m = _mixed_pairs(1)[0]
        h = sched.submit(f, m)
        clock.advance(10.0)  # deadline passes while still queued
        sched.step()
        assert h.done and sched.pending == 0
        assert sched.stats.timed_out == 1
        with pytest.raises(RegistrationTimeout, match="expired"):
            h.result()

    def test_unexpired_requests_complete_under_fake_clock(self):
        clock = FakeClock()
        sched = RegistrationScheduler(OPTS, lanes=1, timeout=60.0,
                                      clock=clock)
        f, m = _mixed_pairs(1)[0]
        h = sched.submit(f, m)
        sched.run_until_idle()
        assert h.result().warped is not None
        assert sched.stats.timed_out == 0

    def test_backpressure_queue_full(self):
        sched = RegistrationScheduler(OPTS, lanes=1, max_queue=1)
        f, m = _mixed_pairs(1)[0]
        sched.submit(f, m)
        with pytest.raises(QueueFull, match="max_queue"):
            sched.submit(f, m)
        assert sched.stats.rejected == 1
        sched.run_until_idle()  # the admitted request still completes
        assert sched.stats.completed == 1

    def test_result_before_done_raises(self):
        sched = RegistrationScheduler(OPTS, lanes=1)
        f, m = _mixed_pairs(1)[0]
        h = sched.submit(f, m)
        with pytest.raises(RuntimeError, match="in flight"):
            h.result()
        sched.run_until_idle()
        assert h.result() is not None

    def test_lane_state_leaves_own_their_buffers(self):
        """The splice and chunk programs donate the lane state on
        accelerators, and a buffer shared by two leaves cannot be donated
        twice — so every freshly allocated leaf owns its buffer."""
        import jax

        sched = RegistrationScheduler(OPTS, lanes=2)
        bucket = sched._bucket_for(SHAPE)
        stage = bucket.stages[0]
        sched._alloc(bucket, stage, bucket.lvl_shapes[0])
        leaves = jax.tree.leaves((stage.state, stage.fixed, stage.moving))
        ptrs = [x.unsafe_buffer_pointer() for x in leaves]
        assert len(set(ptrs)) == len(ptrs)

    def test_constructor_validation(self):
        with pytest.raises(TypeError, match="RegistrationOptions"):
            RegistrationScheduler({"iters": 3})
        with pytest.raises(ValueError, match="lanes"):
            RegistrationScheduler(OPTS, lanes=0)
        with pytest.raises(ValueError, match="chunk"):
            RegistrationScheduler(OPTS, chunk=0)


class TestAsyncFacade:
    def test_concurrent_registers(self):
        pairs = _mixed_pairs(3)

        async def run():
            service = AsyncRegistrationService(
                scheduler=RegistrationScheduler(OPTS, lanes=2, chunk=4))
            return await asyncio.gather(
                *(service.register(f, m) for f, m in pairs))

        results = asyncio.run(run())
        assert len(results) == len(pairs)
        for (f, m), served in zip(pairs, results):
            solo = ffd_register(f, m, options=OPTS)
            np.testing.assert_allclose(np.asarray(served.warped),
                                       np.asarray(solo.warped), atol=1e-5)


class TestMesh:
    def test_mesh_lanes_match_one_device(self):
        """Four fake CPU devices, two lanes each: the scheduler's programs
        under ``shard_map`` (a splice into a lane held by another device, a
        recycled lane, the final warp on one device) give what one device
        gives.  A fresh process, so it holds with one device here."""
        code = textwrap.dedent("""
            import os, sys
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            os.environ["JAX_PLATFORMS"] = "cpu"
            sys.path.insert(0, "tests")
            import jax, numpy as np
            from repro.engine import make_registration_mesh
            from repro.engine.serve import RegistrationScheduler
            from test_serve import OPTS, _mixed_pairs
            assert jax.device_count() == 4, jax.devices()
            pairs = _mixed_pairs(10)
            res = []
            for mesh in (None, make_registration_mesh()):
                sched = RegistrationScheduler(OPTS, lanes=8, chunk=4,
                                              mesh=mesh)
                handles = [sched.submit(f, m) for f, m in pairs]
                sched.run_until_idle()
                res.append([h.result() for h in handles])
            assert any(r.recycled for r in res[1])
            for a, b in zip(*res):
                np.testing.assert_allclose(np.asarray(b.warped),
                                           np.asarray(a.warped), atol=1e-5)
                np.testing.assert_allclose(np.asarray(b.params),
                                           np.asarray(a.params), atol=1e-5)
                np.testing.assert_allclose(b.losses, a.losses, rtol=1e-5)
                assert b.steps == a.steps and b.recycled == a.recycled
            print("MESH_SERVE_OK")
        """)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, PYTHONPATH="src")
        env.pop("XLA_FLAGS", None)  # the child pins its own before jax loads
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, cwd=root)
        assert "MESH_SERVE_OK" in r.stdout, r.stderr[-2000:]
