"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device planes (``/device:TPU:<n>``) carry one event per executed XLA
operation on their ``XLA Ops`` line, named by its whole HLO instruction;
loops (``while``) span the operations they run and are left out.  The host
plane carries the benchmark's own ``jax.profiler.TraceAnnotation`` spans
(``bench.*``) on the same clock.  From them:

* busy time: the union of the operation intervals inside the window (from
  the first ``bench.register``/``bench.batch`` span's start to the last
  ``bench.block`` span's end), averaged over the chips; the idle share is
  1 - busy/window;
* the time in each Pallas kernel (``tpu_custom_call`` custom calls);
* the operations that took most time, and the longest idle gaps, each named
  by the innermost ``bench.*`` host span that was open at the gap's middle.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
WINDOW_SPANS = ("bench.register", "bench.batch", "bench.block")
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: tuple = ()  # ((key, value), ...)

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns

    def stat(self, key, default=""):
        return dict(self.stats).get(key, default)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float            # averaged over chips
    kernel_s: dict           # Pallas kernel name -> device seconds (all chips)
    top_ops: list            # [[name, seconds], ...]
    idle_gaps: list          # [[host span, seconds], ...]
    chips: int

    @property
    def idle_share(self):
        return 1.0 - self.busy_s / self.window_s


def _events(line):
    out = []
    for e in line.events:
        stats = tuple((str(k), v if isinstance(v, (int, float)) else str(v))
                      for k, v in e.stats)
        out.append(Event(str(e.name), float(e.start_ns), float(e.duration_ns),
                         stats))
    return out


def read_xplane(path):
    """``(device ops by chip, host spans)`` from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chips[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e.name.startswith("bench.")]
    return chips, spans


def find_xplane(log_dir):
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def merge(intervals):
    """Sorted, non-overlapping union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


_HLO = re.compile(r"%?([\w.-]+) = (.*?) ([a-z][\w-]*)\(")
CONTAINERS = ("while", "conditional", "call")


def opcode(event):
    """The HLO opcode of a device operation (its event name is the HLO
    instruction), or ``""`` where the name is no instruction."""
    m = _HLO.match(event.name)
    return m.group(3) if m else ""


def short_name(event):
    """``<instruction> <opcode>[ <fusion kind>] -> <shape>``, at most 120
    characters: the event name is the whole HLO instruction."""
    m = _HLO.match(event.name)
    if not m:
        return event.name[:120]
    kind = re.search(r"kind=(k\w+)", event.name)
    target = re.search(r'custom_call_target="([^"]+)"', event.name)
    out = m.group(2) if len(m.group(2)) <= 48 else m.group(2)[:45] + "..."
    extra = (f" {kind.group(1)}" if kind else "") + (
        f" {target.group(1)}" if target else "")
    return f"{m.group(1)} {m.group(3)}{extra} -> {out}"[:120]


def kernel_name(event):
    """The Pallas kernel's name (``tpu_custom_call`` instruction without its
    ``.N`` suffix, e.g. ``bsi_adjoint_pallas_planes``), or ``None``."""
    if "tpu_custom_call" not in event.name:
        return None
    m = _HLO.match(event.name)
    return m.group(1).rsplit(".", 1)[0] if m else event.name[:64]


def innermost(spans, t):
    """Name of the shortest span open at time ``t``, or ``"none"``."""
    open_ = [s for s in spans if s.start_ns <= t < s.end_ns]
    return min(open_, key=lambda s: s.dur_ns).name if open_ else "none"


def summarize(chips, spans):
    """Reduce device operations and host spans to a :class:`Summary`.

    Returns ``None`` when the trace holds no window span or no device
    operation inside the window.
    """
    window = [s for s in spans if s.name in WINDOW_SPANS]
    if not window:
        return None
    lo = min(s.start_ns for s in window)
    hi = max(s.end_ns for s in window)
    busy, gaps = [], []
    kernel_ns, op_ns = collections.Counter(), collections.Counter()
    for events in chips.values():
        inside = [e for e in events if e.end_ns > lo and e.start_ns < hi
                  and opcode(e) not in CONTAINERS]
        merged = merge(clip([(e.start_ns, e.end_ns) for e in inside], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        for e in inside:
            op_ns[short_name(e)] += e.dur_ns
            kernel = kernel_name(e)
            if kernel is not None:
                kernel_ns[kernel] += e.dur_ns
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((innermost(spans, (s + e) / 2), (e - s) * 1e-9))
    if not busy or not any(busy):
        return None
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / len(busy) * 1e-9,
        kernel_s={k: v * 1e-9 for k, v in kernel_ns.items()},
        top_ops=[[n, v * 1e-9] for n, v in op_ns.most_common(TOP)],
        idle_gaps=[[n, s] for n, s in gaps[:TOP]],
        chips=len(busy))
