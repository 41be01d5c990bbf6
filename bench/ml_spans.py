"""Device idle charged to the real-ML backend's host spans.

The vectorized engine opens ``ml.pull``, ``ml.finish`` and ``ml.eval``
around its calls into a real-ML backend, the backend ``ml.train`` around
each training dispatch and ``ml.reset`` around its reset, all inside the
run's ``sim.run``. ``idle_by_span`` charges every idle stretch of the
device to the innermost of the program's spans over it, as
``program_spans.idle_by_span`` does, with the ``ml.*`` spans counted
among them: what is left under ``sim.run`` itself is then the engine's
host slot loop.
"""
from __future__ import annotations

import bisect
import collections
from typing import Dict

from bench import program_spans, trace as tr

PREFIXES = program_spans.PREFIXES + ("ml.",)
ML = "ml."


def has_ops(trace) -> bool:
    """Whether any operation ran on a device in the window."""
    return any(trace.ops.values())


def idle_by_span(trace) -> Dict[str, float]:
    """Nanoseconds of device idle in the window, averaged over devices,
    by the innermost ``sim.*``, ``scan.*`` or ``ml.*`` span that covered
    it; a stretch under none is charged to ``bench.unit``."""
    lo, hi = trace.window
    spans = [sp for sp in trace.spans if sp[0].startswith(PREFIXES)]
    cuts = sorted({t for _, s, e in spans for t in (s, e) if lo < t < hi})
    out = collections.Counter()
    for ev in trace.ops.values():
        parts = []
        for s, e in tr.idle_gaps([(s, e) for _, s, e in ev], lo, hi):
            k = bisect.bisect_right(cuts, s)
            while k < len(cuts) and cuts[k] < e:
                parts.append((s, cuts[k]))
                s = cuts[k]
                k += 1
            parts.append((s, e))
        names = tr.innermost_spans(spans, [(s + e) / 2 for s, e in parts])
        for name, (s, e) in zip(names, parts):
            out[name if name.startswith(PREFIXES) else tr.UNIT_SPAN] += e - s
    n = max(len(trace.ops), 1)
    return {name: ns / n for name, ns in out.items()}
