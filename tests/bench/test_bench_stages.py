"""Per-stage device time and the program's host spans, read from a trace."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import stages, trace
from chipbench.trace import Event


def _op(name, start, end, **stats):
    return Event(name, float(start), float(end - start), tuple(stats.items()))


def _staged(name, start, end, op_name):
    return _op(name, start, end, tf_op=op_name)


def test_ops_are_bucketed_by_their_innermost_stage():
    spans = [_op("bench.register", 0, 100), _op("bench.block", 100, 110)]
    ops = [_staged("fusion.1", 0, 10, "jit(run)/while/body/jvp(warp)/gather"),
           _staged("fusion.2", 10, 15,
                   "jit(run)/while/body/transpose(jvp(bsi))/jit(_adjoint_jit)"
                   "/bsi_adjoint_separable/pallas_call"),
           _staged("fusion.3", 15, 22, "jit(fin)/bsi/jit(_interpolate_jit)"),
           _staged("fusion.4", 22, 30,
                   "jit(run)/jvp(similarity)/jvp(warp)/mul"),
           _staged("fusion.5", 30, 36, "jit(run)/jvp(similarity)/exp"),
           _staged("fusion.6", 36, 40, "jit(run)/bsi_separable/sub"),
           _staged("copy.7", 40, 43, ""),
           _op("fusion.8", 43, 50)]
    got = stages.stage_seconds({"/device:TPU:0": ops}, spans)
    assert got == {"warp": pytest.approx(18e-9), "bsi": pytest.approx(12e-9),
                   "similarity": pytest.approx(6e-9),
                   "other": pytest.approx(14e-9)}
    assert sum(got.values()) == pytest.approx(
        sum(e.dur_ns for e in ops) * 1e-9)
    s = trace.summarize({"/device:TPU:0": ops}, spans)
    assert sum(got.values()) == pytest.approx(s.busy_s)


def test_stage_times_are_clipped_to_the_window_and_loops_left_out():
    spans = [_op("bench.register", 10, 50), _op("bench.block", 50, 60)]
    ops = [_staged("%while.1 = (s32[]) while((s32[]) %t)", 0, 60,
                   "jit(run)/jvp(warp)/while"),
           _staged("fusion.2", 0, 20, "jit(run)/jvp(warp)/gather"),
           _staged("fusion.3", 55, 70, "jit(run)/bsi/pad")]
    got = stages.stage_seconds({"/device:TPU:0": ops}, spans)
    assert got == {"warp": pytest.approx(10e-9), "bsi": pytest.approx(5e-9)}
    s = trace.summarize({"/device:TPU:0": ops}, spans)
    assert sum(got.values()) == pytest.approx(s.busy_s)


def test_a_program_without_scopes_has_no_stage_times():
    spans = [_op("bench.register", 0, 100), _op("bench.block", 100, 110)]
    ops = [_staged("fusion.1", 0, 10, "jit(run)/while/body/jvp()/gather"),
           _op("fusion.2", 10, 20)]
    assert stages.stage_seconds({"/device:TPU:0": ops}, spans) == {}
    assert stages.stage_seconds({"/device:TPU:0": ops}, []) == {}


def test_program_spans_change_no_number_and_name_the_gaps():
    bench_spans = [_op("bench.register", 0, 100), _op("bench.block", 100, 120)]
    repro_spans = [_op("repro.level", 5, 30, level=0),
                   _op("repro.sync", 30, 60),
                   _op("repro.level", 60, 80, level=1),
                   _op("repro.final", 80, 95)]
    ops = [_op('%k.1 = f32[8] custom-call(f32[8] %a), '
               'custom_call_target="tpu_custom_call"', 0, 10),
           _staged("fusion.2", 20, 55, "jit(run)/jvp(warp)/gather"),
           _staged("fusion.3", 75, 90, "jit(fin)/warp/gather"),
           _op("fusion.4", 100, 115)]
    chips = {"/device:TPU:0": ops}
    without = trace.summarize(chips, bench_spans)
    with_ = trace.summarize(chips, bench_spans + repro_spans)
    assert with_.window_s == without.window_s
    assert with_.idle_share == without.idle_share
    assert with_.kernel_s == without.kernel_s
    assert with_.busy_s == without.busy_s
    assert with_.top_ops == without.top_ops
    assert (stages.stage_seconds(chips, bench_spans + repro_spans)
            == stages.stage_seconds(chips, bench_spans))
    # gaps [10,20] (middle 15, in repro.level 0), [55,75] (middle 65, in
    # repro.level 1), [90,100] and [115,120]
    assert [g[0] for g in without.idle_gaps] == ["bench.register"] * 3 + [
        "bench.block"]
    assert [g[0] for g in with_.idle_gaps] == [
        "repro.level", "repro.level", "bench.register", "bench.block"]


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from ``(field number, int | str | bytes)``."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def _plane(name, line, events, metadata, stat_names=()):
    """An XPlane: ``events`` are ``(metadata id, offset ps, duration ps)``,
    ``metadata`` maps an id to ``(name, [(stat id, str value), ...])``."""
    fields = [(1, 1), (2, name),
              (3, _msg(*[(1, 1), (2, line), (3, 1000)] + [
                  (4, _msg((1, m), (2, off), (3, dur)))
                  for m, off, dur in events]))]
    for mid, (ev_name, stats) in metadata.items():
        body = _msg((1, mid), (2, ev_name),
                    *[(5, _msg((1, sid), (5, v))) for sid, v in stats])
        fields.append((4, _msg((1, mid), (2, body))))
    for sid, stat_name in stat_names:
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, stat_name))))))
    return _msg(*fields)


def test_op_names_are_read_from_the_event_metadata(tmp_path):
    gather = ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a, s32[8]{0} %i), "
              "kind=kCustom, calls=%fused_computation")
    kernel = ("%bsi_adjoint_separable.2 = f32[8]{0} custom-call(f32[8]{0} %g), "
              'custom_call_target="tpu_custom_call"')
    copy = "%copy.3 = f32[8]{0} copy(f32[8]{0} %a)"
    device = _plane(
        "/device:TPU:0", "XLA Ops",
        [(7, 0, 4_000_000), (8, 5_000_000, 2_000_000),
         (7, 9_000_000, 1_000_000), (9, 11_000_000, 1_000_000)],
        {7: (gather, [(1, "jit(run)/jvp(warp)/gather:")]),
         8: (kernel, [(2, "custom-call"),
                      (1, "jit(run)/transpose(jvp(bsi))/pallas_call:")]),
         9: (copy, [])},
        stat_names=[(1, "tf_op"), (2, "hlo_category")])
    host = _plane("/host:CPU", "python",
                  [(1, 0, 15_000_000), (2, 2_000_000, 6_000_000),
                   (3, 15_000_000, 1_000_000), (4, 0, 1_000_000)],
                  {1: ("bench.register", []), 2: ("repro.level", []),
                   3: ("bench.block", []), 4: ("other.span", [])})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host)))
    chips, spans = stages.read_xplane(str(path))
    ops = chips["/device:TPU:0"]
    assert [e.name for e in ops] == [gather, kernel, gather, copy]
    assert [e.stat("tf_op") for e in ops] == [
        "jit(run)/jvp(warp)/gather", "jit(run)/transpose(jvp(bsi))/pallas_call",
        "jit(run)/jvp(warp)/gather", ""]
    assert [stages.stage(e) for e in ops] == ["warp", "bsi", "warp", "other"]
    assert sorted(s.name for s in spans) == ["bench.block", "bench.register",
                                             "repro.level"]
    assert stages.stage_seconds(chips, spans) == {
        "warp": pytest.approx(5e-6), "bsi": pytest.approx(2e-6),
        "other": pytest.approx(1e-6)}
    # the benchmark's own reader sees the same operations, without op names
    plain, bench_spans = trace.read_xplane(str(path))
    assert [e.name for e in plain["/device:TPU:0"]] == [e.name for e in ops]
    assert trace.summarize(chips, spans).kernel_s == trace.summarize(
        plain, bench_spans).kernel_s == {
        "bsi_adjoint_separable": pytest.approx(2e-6)}


def test_registrations_recorded_on_the_cpu_have_program_spans(tmp_path):
    from repro.core import RegistrationOptions
    from repro.core.registration import ffd_register
    from repro.engine.batch import register_batch

    vol = jnp.linspace(0.0, 1.0, 16 * 12 * 10).reshape(16, 12, 10)
    opts = RegistrationOptions(tile=(4, 4, 4), levels=2, iters=2,
                               mode="separable", impl="jnp", grad_impl="jnp",
                               fused="off")
    ffd_register(vol, vol, options=opts).warped.block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.register"):
        r = ffd_register(vol, vol, options=opts)
    with jax.profiler.TraceAnnotation("bench.block"):
        r.warped.block_until_ready()
    jax.profiler.stop_trace()
    _, spans = stages.read_xplane(trace.find_xplane(str(tmp_path)))
    names = [s.name for s in spans]
    assert names.count("repro.level") == 2 and names.count("repro.sync") == 2
    assert {"repro.resolve", "repro.pyramid", "repro.final"} <= set(names)
    levels = sorted(s.stat("level") for s in spans if s.name == "repro.level")
    assert levels == [0, 1]

    # the batched path: one dispatch and one sync for the whole pyramid
    stack = jnp.stack([vol, vol])
    register_batch(stack, stack, options=opts).warped.block_until_ready()
    batch_dir = tmp_path / "batch"
    jax.profiler.start_trace(str(batch_dir))
    with jax.profiler.TraceAnnotation("bench.batch"):
        register_batch(stack, stack, options=opts)
    jax.profiler.stop_trace()
    _, spans = stages.read_xplane(trace.find_xplane(str(batch_dir)))
    names = [s.name for s in spans]
    assert names.count("repro.level") == 1 and names.count("repro.sync") == 1
