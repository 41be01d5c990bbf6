"""Reduction of a profiler trace to what the per-layer readers use.

A traced run records one window with ``jax.profiler`` and the harness's
own host spans (``jax.profiler.TraceAnnotation``). This module reads the
``.xplane.pb`` file with ``jax.profiler.ProfileData`` and reduces it to

- the window: the first to the last host span named ``bench.unit``;
- per device, the operations that ran on it inside the window, as
  ``(name, start_ns, end_ns)``, from the device plane's "XLA Ops" line;
- the busy time: the union of those intervals, averaged over devices;
- the idle gaps between them, each named after the innermost host event
  of the benchmark's thread (its own spans and JAX's host events) that
  covers the gap's midpoint.

The functions below on plain intervals carry the arithmetic, so that they
can be tested on synthetic traces.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

UNIT_SPAN = "bench.unit"
OPS_LINE = "XLA Ops"


def union_length(intervals: Sequence[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: Sequence[Tuple[float, float]], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    gaps, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(s, e) for s, e in gaps if e > s]


def clip(intervals, lo, hi):
    """Intervals cut to ``[lo, hi]``; those outside it are dropped."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def innermost_spans(spans: Sequence[Tuple[str, float, float]],
                    points: Sequence[float]) -> List[str]:
    """For each time in ``points``, the name of the innermost of the
    (nested, one thread's) host spans that covers it."""
    order = sorted(range(len(points)), key=lambda i: points[i])
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    out = ["outside any span"] * len(points)
    stack, j = [], 0
    for i in order:
        at = points[i]
        while j < len(spans) and spans[j][1] <= at:
            while stack and stack[-1][2] < spans[j][1]:
                stack.pop()
            stack.append(spans[j])
            j += 1
        while stack and stack[-1][2] < at:
            stack.pop()
        if stack:
            out[i] = stack[-1][0]
    return out


def self_times(events: Sequence[Tuple[str, float, float]]):
    """``[(name, self_ns)]``: each event's duration less that of the
    events nested directly inside it (a loop op holds its body's ops on
    the same line of a device trace)."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            n_, s_, e_, inner = stack.pop()
            out.append((n_, (e_ - s_) - inner))
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    out.extend((n_, (e_ - s_) - inner) for n_, s_, e_, inner in stack)
    return out


def op_label(text: str) -> str:
    """A short name for a device op: the HLO instruction's name and the
    tail of the JAX name it came from (``op_name`` of its metadata)."""
    short = text.split(" = ", 1)[0].strip().lstrip("%")
    m = re.search(r'op_name="([^"]+)"', text)
    if not m:
        return short
    return f"{short} [{'/'.join(m.group(1).split('/')[-3:])}]"


def top_by_time(pairs, k: int = 10):
    """``[[name, seconds], ...]`` of the ``k`` names with most time, from
    ``(name, ns)`` pairs."""
    dur = collections.Counter()
    for name, ns in pairs:
        dur[name] += ns
    return [[name, ns / 1e9] for name, ns in dur.most_common(k)]


@dataclasses.dataclass
class Reduced:
    """One traced window, reduced. Times are in nanoseconds on the
    profiler's clock, except the ``_s`` fields."""
    window: Tuple[float, float]
    ops: Dict[str, List[Tuple[str, float, float]]]   # device -> events
    spans: List[Tuple[str, float, float]]            # host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.ops:
            return 0.0
        lo, hi = self.window
        return sum(union_length(clip([(s, e) for _, s, e in ev], lo, hi))
                   for ev in self.ops.values()) / len(self.ops) / 1e9

    def breakdown(self, k: int = 10) -> dict:
        """The device operations that took most time and the longest idle
        gaps, named after what the host was doing in them."""
        lo, hi = self.window
        own = [(op_label(name), ns) for ev in self.ops.values()
               for name, ns in self_times(ev)]
        gaps = collections.Counter()
        for ev in self.ops.values():
            idle = idle_gaps([(s, e) for _, s, e in ev], lo, hi)
            names = innermost_spans(self.spans,
                                    [(s + e) / 2 for s, e in idle])
            for name, (s, e) in zip(names, idle):
                gaps[name] += e - s
        n = max(len(self.ops), 1)
        return {"device_ops": top_by_time(own, k),
                "idle_gaps": [[name, ns / n / 1e9]
                              for name, ns in gaps.most_common(k)]}


def reduce_xplane(path: str, device_prefix: str = "/device:TPU:") -> Reduced:
    """Read one ``.xplane.pb`` file and reduce it to a ``Reduced``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, ops = [], {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.end_ns)
                       for ev in line.events]
                # the thread that ran the benchmark: its JAX host events
                # say what the host did inside the benchmark's spans
                if any(name.startswith("bench.") for name, _, _ in evs):
                    spans.extend(evs)
        elif plane.name.startswith(device_prefix):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [(ev.name, ev.start_ns, ev.end_ns)
                                       for ev in line.events]
    units = [(s, e) for name, s, e in spans if name == UNIT_SPAN]
    if not units:
        raise ValueError(f"{path}: no {UNIT_SPAN!r} span in the trace")
    lo, hi = min(s for s, _ in units), max(e for _, e in units)
    ops = {dev: [(n, s, e) for n, s, e in ev if e > lo and s < hi]
           for dev, ev in ops.items()}
    return Reduced(window=(lo, hi), ops=ops, spans=spans)


def find_xplane(logdir: str) -> Optional[str]:
    found = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return found[0] if found else None
