"""Reduction of a profiler trace to busy time, kinds of work and gaps."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import trace
from chipbench.trace import Event


def _op(name, start, end, **stats):
    return Event(name, float(start), float(end - start), tuple(stats.items()))


def test_busy_union_of_overlapping_operations():
    spans = [_op("bench.register", 0, 80), _op("bench.block", 80, 100)]
    ops = [_op("fusion.1", 10, 30), _op("fusion.2", 20, 40),
           _op('%k.3 = f32[8] custom-call(f32[8] %a), '
               'custom_call_target="tpu_custom_call"', 35, 50),
           _op("gather.4", 60, 70), _op("fusion.5", 95, 120)]
    s = trace.summarize({"/device:TPU:0": ops}, spans)
    # union inside [0, 100]: [10, 50] + [60, 70] + [95, 100] = 55 ns
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(55e-9)
    assert s.idle_share == pytest.approx(0.45)
    assert s.kernel_s == {"k": pytest.approx(15e-9)}
    # gaps [70,95] (middle in bench.block), [0,10] and [50,60]; longest first
    assert s.idle_gaps[0] == ["bench.block", pytest.approx(25e-9)]
    assert [g[0] for g in s.idle_gaps[1:]] == ["bench.register"] * 2
    assert s.top_ops[0][0] in ("fusion.5", "fusion.1", "fusion.2")


def test_busy_time_is_averaged_over_chips_and_gaps_take_the_inner_span():
    spans = [_op("bench.batch", 0, 100), _op("bench.block", 60, 100)]
    chips = {"/device:TPU:0": [_op("a", 0, 50)],
             "/device:TPU:1": [_op("a", 0, 30), _op("b", 20, 100)]}
    s = trace.summarize(chips, spans)
    assert s.chips == 2
    assert s.busy_s == pytest.approx(75e-9)
    assert s.idle_gaps == [["bench.block", pytest.approx(50e-9)]]


def test_no_window_or_no_device_work_gives_nothing():
    assert trace.summarize({"/device:TPU:0": [_op("a", 0, 5)]}, []) is None
    assert trace.summarize({}, [_op("bench.register", 0, 10)]) is None


def test_merge_and_kinds():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    kernel = _op('%bsi_separable_pallas.8 = f32[3,80,48,128]{3,2,1,0} '
                 'custom-call(f32[48,16]{1,0} %copy-done.68), '
                 'custom_call_target="tpu_custom_call"', 0, 1)
    assert trace.kernel_name(kernel) == "bsi_separable_pallas"
    assert trace.short_name(kernel) == ("bsi_separable_pallas.8 custom-call "
                                        "tpu_custom_call -> f32[3,80,48,128]"
                                        "{3,2,1,0}")
    gather = _op("%fusion.267 = f32[10727412]{0:T(1024)} fusion(f32[303,167,"
                 "212]{2,0,1} %copy-done.1, s32[10727412]{0} %gte.1315), "
                 "kind=kCustom, calls=%fused_computation.5", 0, 1)
    assert trace.kernel_name(gather) is None
    assert trace.opcode(gather) == "fusion"
    assert trace.short_name(gather) == ("fusion.267 fusion kCustom -> "
                                        "f32[10727412]{0:T(1024)}")


def test_loops_are_not_counted_as_operations():
    spans = [_op("bench.register", 0, 100)]
    ops = [_op("%while.3 = (s32[], f32[4]) while((s32[], f32[4]) %t)", 0, 100),
           _op("%fusion.1 = f32[4] fusion(f32[4] %a), kind=kLoop", 10, 30)]
    s = trace.summarize({"/device:TPU:0": ops}, spans)
    assert s.busy_s == pytest.approx(20e-9)
    assert [name for name, _ in s.top_ops] == ["fusion.1 fusion kLoop -> f32[4]"]


def test_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.register"):
        y = f(x)
    with jax.profiler.TraceAnnotation("bench.block"):
        y.block_until_ready()
    jax.profiler.stop_trace()
    chips, spans = trace.read_xplane(trace.find_xplane(str(tmp_path)))
    assert {s.name for s in spans} >= {"bench.register", "bench.block"}
    assert all(s.dur_ns > 0 for s in spans)
    # the CPU has no device plane: nothing is attributed to a device
    assert chips == {}
    assert trace.summarize(chips, spans) is None
