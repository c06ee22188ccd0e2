"""Device time of each stage of the level step, from a profiler trace.

The program names its stages with ``jax.named_scope`` inside the traced
code: ``bsi`` (``core/ffd.dense_field``), ``warp`` (``core/ffd.warp_volume``)
and ``similarity`` (the ``sim(warped, fixed)`` call of
``engine/batch.ffd_level_loss``).  The compiler keeps the scope in each
operation's ``op_name`` (the JAX name stack, e.g.
``jit(run)/while/body/closed_call/transpose(jvp(bsi))/...``), and the trace
carries that as the ``tf_op`` stat of the event's metadata, which
``jax.profiler.ProfileData`` does not expose: :func:`op_names` reads it from
the file itself.  The program's host phases are ``repro.*``
``TraceAnnotation`` spans (``repro.resolve``, ``repro.pyramid``,
``repro.level``, ``repro.sync``, ``repro.final``), on the clock of the
device planes.

:func:`read_xplane` gives what :func:`chipbench.trace.read_xplane` gives,
with the op names and the ``repro.*`` spans added; ``trace.summarize`` of
it keeps every number and names idle gaps by the innermost ``repro.*``
span.  :func:`stage_seconds` buckets the window's operations by stage.  The
harness does not read these yet (see PERF.md, Open questions).
"""
from __future__ import annotations

import collections
import re

from chipbench import trace

STAGES = ("bsi", "warp", "similarity")
OTHER = "other"
HOST_SPANS = ("bench.", "repro.")


def read_xplane(path):
    """``(device ops by chip, host spans)`` from one ``.xplane.pb`` file:
    each device operation carries its ``op_name`` as the stat ``tf_op``
    (empty where the trace has none); the host spans are the ``bench.*`` and
    ``repro.*`` ones."""
    from jax.profiler import ProfileData

    with open(path, "rb") as fh:
        raw = fh.read()
    names = op_names(raw)
    chips, spans = {}, []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    chips[plane.name] = _named(trace._events(line),
                                               names.get(plane.name, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in trace._events(line)
                          if e.name.startswith(HOST_SPANS)]
    return chips, spans


def _named(events, ops):
    """``events`` with the ``tf_op`` of ``ops`` (``(event name, op_name)``
    in the same order) where the event names agree."""
    if len(ops) != len(events):
        return events
    return [trace.Event(e.name, e.start_ns, e.dur_ns,
                        e.stats + (("tf_op", op),)) if name == e.name else e
            for e, (name, op) in zip(events, ops)]


_SCOPE = re.compile(r"(?:[\w-]+\()*([\w-]+)\)*")


def stage(event):
    """The innermost of :data:`STAGES` among the ``/``-separated components
    of the operation's ``op_name`` (a component may wrap a scope in
    transformations, e.g. ``transpose(jvp(bsi))``), or :data:`OTHER`."""
    found = OTHER
    for part in event.stat("tf_op").split("/"):
        m = _SCOPE.fullmatch(part)
        if m and m.group(1) in STAGES:
            found = m.group(1)
    return found


def stage_seconds(chips, spans):
    """Device seconds (all chips) per stage, :data:`STAGES` or
    :data:`OTHER`, in the window and with the loops left out, as
    ``trace.summarize`` counts busy time; ``{}`` where the trace has no
    window or no operation has a stage (a program without the scopes)."""
    window = [s for s in spans if s.name in trace.WINDOW_SPANS]
    if not window:
        return {}
    lo = min(s.start_ns for s in window)
    hi = max(s.end_ns for s in window)
    ns = collections.Counter()
    for events in chips.values():
        for e in events:
            if (e.end_ns > lo and e.start_ns < hi
                    and trace.opcode(e) not in trace.CONTAINERS):
                ns[stage(e)] += min(e.end_ns, hi) - max(e.start_ns, lo)
    return {k: v * 1e-9 for k, v in ns.items()} if set(ns) - {OTHER} else {}


# --- the op names, read from the XSpace protobuf's wire format ----------------
# XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4 (map
# entries: key = 1, value = 2), stat_metadata = 5; XLine: name = 2,
# events = 4; XEvent: metadata_id = 1; XEventMetadata: id = 1, name = 2,
# stats = 5; XStatMetadata: id = 1, name = 2; XStat: metadata_id = 1,
# str_value = 5, ref_value = 7.


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for length-delimited and fixed-width values."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _text(buf):
    return bytes(buf).decode("utf-8", "replace")


def _plane_op_names(plane):
    """``(plane name, [(event name, op_name), ...])``: the events of the
    plane's ``XLA Ops`` line in order, or an empty list without one."""
    name, stat_names, metadata, ops_line = "", {}, {}, None
    for field, value in _fields(plane):
        if field == 2:
            name = _text(value)
        elif field in (4, 5):
            entry = dict(_fields(value))
            key, body = entry.get(1, 0), entry.get(2, b"")
            if field == 4:
                metadata[key] = body
            else:
                stat_names[key] = _text(dict(_fields(body)).get(2, b""))
        elif field == 3:
            line = dict(_fields(value))
            if _text(line.get(2, b"")) == trace.OPS_LINE:
                ops_line = value
    if ops_line is None:
        return name, []
    described = {}
    for key, value in metadata.items():
        event_name, tf_op = "", ""
        for field, v in _fields(value):
            if field == 2:
                event_name = _text(v)
            elif field == 5:
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) == "tf_op":
                    tf_op = (_text(stat[5]) if 5 in stat
                             else stat_names.get(stat.get(7), ""))
        described[key] = (event_name, tf_op.rpartition(":")[0] or tf_op)
    events = [v for field, v in _fields(ops_line) if field == 4]
    return name, [described.get(dict(_fields(e)).get(1, 0), ("", ""))
                  for e in events]


def op_names(raw):
    """``{plane name: [(event name, op_name), ...]}`` for each plane of a
    serialized XSpace that has an ``XLA Ops`` line (the devices'); the
    ``op_name`` is the ``tf_op`` stat of the event's metadata without its
    ``:<op type>`` suffix."""
    out = {}
    for field, plane in _fields(memoryview(raw)):
        if field == 1:
            name, ops = _plane_op_names(plane)
            if ops:
                out[name] = ops
    return out
