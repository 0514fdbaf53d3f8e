"""Reduce a profiler trace to device busy time, idle share and host spans.

The traced run records ``jax.profiler`` output for its window.  This module
reads it with ``jax.profiler.ProfileData`` and keeps three things:

* per chip, the merged intervals in which an operation ran on it (the "XLA
  Ops" line of each ``/device:TPU:<n>`` plane);
* the benchmark's own host spans (``TraceAnnotation`` names starting with
  ``bench.``);
* per operation name, its total device time.

Everything after loading is plain interval arithmetic on nanoseconds, so the
rules are tested on small synthetic traces: busy time is the union of a
chip's op intervals; the idle share is 1 - busy / window, averaged over the
chips; device time inside a span is the busy time within it on the busiest
chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Busy:
    """One chip's merged busy intervals, with fast overlap queries."""

    def __init__(self, intervals):
        self.iv = merge(intervals)
        self.starts = [s for s, _ in self.iv]
        self.prefix = [0.0]
        for s, e in self.iv:
            self.prefix.append(self.prefix[-1] + (e - s))

    def within(self, a: float, b: float) -> float:
        """Busy time inside ``[a, b]``."""
        if b <= a or not self.iv:
            return 0.0
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        j = bisect.bisect_left(self.starts, b)
        if j == 0:
            return 0.0
        total = self.prefix[j] - self.prefix[i]
        s, e = self.iv[i]
        total -= max(0.0, min(e, a) - s)          # part of first before a
        s, e = self.iv[j - 1]
        total -= max(0.0, e - max(s, b))          # part of last after b
        return float(max(total, 0.0))

    def gaps(self, a: float, b: float):
        """Idle intervals inside ``[a, b]``."""
        out, cur = [], a
        for s, e in self.iv:
            if e <= a:
                continue
            if s >= b:
                break
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if cur < b:
            out.append((cur, b))
        return out


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class TraceView:
    """What the per-layer metrics read: times in nanoseconds on one clock."""

    busy: list                     # [Busy] per chip, in chip order
    spans: list                    # [Span], host spans of the benchmark
    op_time: dict                  # op name -> device ns summed over chips
    window: tuple                  # (start, end) of the traced window

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def spans_named(self, name: str):
        return [s for s in self.spans if s.name == name]

    def device_in(self, span) -> float:
        """Device-busy ns inside ``span`` on the busiest chip."""
        return max(b.within(span.start, span.end) for b in self.busy)

    def busy_ns(self) -> float:
        """Busy ns inside the window, averaged over the chips."""
        a, b = self.window
        return sum(x.within(a, b) for x in self.busy) / len(self.busy)

    def idle_share(self) -> float:
        """1 - busy / window per chip, averaged over the chips."""
        a, b = self.window
        return sum(1.0 - x.within(a, b) / (b - a)
                   for x in self.busy) / len(self.busy)

    def top_ops(self, k: int = 10):
        """``[name, seconds]`` of the ops that took most device time, per
        chip on average."""
        n = len(self.busy)
        top = sorted(self.op_time.items(), key=lambda kv: -kv[1])[:k]
        return [[name, t / n / 1e9] for name, t in top]

    def idle_gaps(self, k: int = 10):
        """``[label, seconds]`` of the longest intervals in which every chip
        was idle, labelled by the innermost benchmark span around the
        gap's middle (``host`` where none is open)."""
        a, b = self.window
        union = Busy([iv for x in self.busy for iv in x.iv])
        gaps = sorted(union.gaps(a, b), key=lambda g: g[0] - g[1])[:k]
        out = []
        for s, e in gaps:
            mid = (s + e) / 2
            around = [sp for sp in self.spans
                      if sp.start <= mid <= sp.end and sp.name !=
                      SPAN_PREFIX + "window"]
            label = (min(around, key=lambda sp: sp.end - sp.start).name
                     if around else "host")
            out.append([label, (e - s) / 1e9])
        return out


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def op_name(text: str) -> str:
    """``%fusion.14`` from an op event's HLO text ``%fusion.14 = (...)``."""
    return text.split(" = ", 1)[0]


def load(path: str, n_chips: int) -> TraceView:
    """Reduce the trace at ``path`` for the first ``n_chips`` chips."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips, spans, op_time = {}, [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            if int(m.group(1)) >= n_chips:
                continue
            ivs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ivs.append((ev.start_ns, ev.end_ns))
                    name = op_name(ev.name)
                    op_time[name] = op_time.get(name, 0.0) + ev.duration_ns
            chips[int(m.group(1))] = Busy(ivs)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name, ev.start_ns, ev.end_ns))
    if len(chips) != n_chips:
        raise RuntimeError(f"trace holds {len(chips)} TPU planes, "
                           f"{n_chips} expected")
    if not any(c.iv for c in chips.values()):
        raise RuntimeError("no device operation in the trace")
    windows = [s for s in spans if s.name == SPAN_PREFIX + "window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {SPAN_PREFIX}window span, found "
                           f"{len(windows)}")
    w = windows[0]
    return TraceView(busy=[chips[i] for i in sorted(chips)], spans=spans,
                     op_time=op_time, window=(w.start, w.end))
