#!/usr/bin/env python3
"""The program's own spans and scopes, read from a traced window.

The simulator marks its host work with ``jax.profiler.TraceAnnotation``
spans: ``sim.run`` around each ``FederatedSim.run()``, ``sim.reset``,
and the jax scan engine's ``scan.setup``, ``scan.to_device``,
``scan.chunk``, ``scan.wait``, ``scan.drain``, ``scan.overflow``,
``scan.traces`` and ``scan.finish``. The slot step's phases carry
``jax.named_scope`` names (``slot.apps``, ``slot.policy``,
``slot.push_log``, ...) in the HLO metadata of the device ops they
compile to.

``idle_by_span`` charges every idle stretch of the device to the host
span that was innermost while it lasted; the per-layer readers
``sim.host_prologue_ms`` and ``sim.host_epilogue_ms`` sum it over the
spans before and after a run's chunks. A TPU trace names each device op
after its HLO instruction but keeps no metadata, so ``hlo_scopes`` reads
the scopes from the compiled module's HLO text and
``device_ns_by_scope`` sums the device time under one scope. Run as a
script on a kept trace file and the HLO that
``XLA_FLAGS=--xla_dump_to=<dir>`` wrote:

    python3 bench/program_spans.py <file.xplane.pb> \
        --hlo <dir>/<module>.jit_simulate.<...>.after_optimizations.txt \
        [--slots N]

prints both splits as one JSON object.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import re
import sys
from pathlib import Path
from typing import Dict, Iterable, Optional

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace as tr  # noqa: E402

PREFIXES = ("sim.", "scan.")
RUN_SPAN = "sim.run"
SCOPE_PREFIX = "slot."
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]+)"',
    re.M)
# host work before a run's first chunk, and after its chunks ran
PROLOGUE = ("sim.run", "sim.reset", "scan.setup", "scan.to_device")
EPILOGUE = ("scan.drain", "scan.overflow", "scan.traces", "scan.finish")


def runs(trace) -> int:
    """The ``sim.run`` spans that lie wholly inside the window."""
    lo, hi = trace.window
    return sum(1 for name, s, e in trace.spans
               if name == RUN_SPAN and s >= lo and e <= hi)


def idle_by_span(trace) -> Dict[str, float]:
    """Nanoseconds of device idle in the window, averaged over devices,
    by the innermost program span (``sim.*``, ``scan.*``) that covered
    it. An idle stretch is cut at every span boundary inside it, so each
    part lies under one stack of spans; a part under none is charged to
    ``bench.unit``. JAX's own host events count under the program span
    around them. The values sum to the window's idle time."""
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


def idle_ms_per_run(trace, names: Iterable[str]) -> Optional[float]:
    """Device idle charged to ``names``, in milliseconds per run; None
    without device ops or without a whole ``sim.run`` in the window."""
    n = runs(trace)
    if not trace.ops or not n:
        return None
    idle = idle_by_span(trace)
    return sum(idle.get(name, 0.0) for name in names) / n / 1e6


def hlo_scopes(text: str) -> Dict[str, str]:
    """Instruction name -> scope path (``op_name`` of its metadata), from
    the text of a compiled HLO module: ``compiled.as_text()``, or the
    ``*after_optimizations.txt`` files that ``XLA_FLAGS=--xla_dump_to=<dir>``
    writes. A TPU trace names each device op after its instruction but
    carries no metadata, so the scopes come from here."""
    out: Dict[str, str] = {}
    for m in _INSTRUCTION.finditer(text):
        out.setdefault(m.group(1), m.group(2))
    return out


def _op(name: str) -> str:
    """A device op's instruction name: a TPU trace names it by the head
    of its HLO text (``%fusion.44 = f32[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def device_ns_by_scope(trace, op_scope: Dict[str, str], prefix: str) -> \
        Optional[float]:
    """Nanoseconds, averaged over devices, of the union of the window's
    device-op intervals whose scope path holds a name that starts with
    ``prefix``; None if no op's does."""
    lo, hi = trace.window
    total, hit = 0.0, False
    for ev in trace.ops.values():
        mine = [(s, e) for name, s, e in ev
                if any(p.startswith(prefix)
                       for p in op_scope.get(_op(name), "").split("/"))]
        hit = hit or bool(mine)
        total += tr.union_length(tr.clip(mine, lo, hi))
    return total / len(trace.ops) if hit else None


def span_args(path: str) -> Dict[str, list]:
    """Each program span's args in one ``.xplane.pb`` file, in order:
    span name -> [{arg: value}, ...]."""
    from jax.profiler import ProfileData

    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out[ev.name].append(dict(ev.stats))
    return dict(out)


def report(path: str, hlo_text: str = "", slots: int = 0) -> dict:
    """Device time by ``slot.*`` scope (from ``hlo_text``, the compiled
    modules' HLO) and device idle by program span, in milliseconds, with
    the span args' counters, from one trace file; device time per slot
    as well where ``slots`` is given."""
    red = tr.reduce_xplane(path)
    scopes = hlo_scopes(hlo_text)
    args = span_args(path)
    names = sorted({p for s in scopes.values() for p in s.split("/")
                    if p.startswith(SCOPE_PREFIX)})
    by_scope = {name: (device_ns_by_scope(red, scopes, name) or 0.0) / 1e6
                for name in names}
    scoped = device_ns_by_scope(red, scopes, SCOPE_PREFIX) or 0.0
    own = ((_op(name), ns) for ev in red.ops.values()
           for name, ns in tr.self_times(ev))
    out = {"window_s": red.window_s, "busy_s": red.busy_s,
           "runs": runs(red),
           "chunks": len(args.get("scan.chunk", [])),
           "pushes": sum(a.get("pushes", 0)
                         for a in args.get("scan.drain", [])),
           "overflows": len(args.get("scan.overflow", [])),
           "device_ms_by_scope": by_scope,
           "unscoped_share_of_busy": (1.0 - scoped / 1e9 / red.busy_s
                                      if red.busy_s else None),
           "idle_ms_by_span": {k: v / 1e6 for k, v in sorted(
               idle_by_span(red).items())},
           "top_ops": [[name, scopes.get(name, ""), sec]
                       for name, sec in tr.top_by_time(own)]}
    if slots:
        out["device_ms_per_slot_by_scope"] = {
            k: v / slots for k, v in by_scope.items()}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", help="a .xplane.pb file")
    ap.add_argument("--hlo", action="append", default=[],
                    help="a compiled module's HLO text (repeatable)")
    ap.add_argument("--slots", type=int, default=0,
                    help="slots the window simulated, for ms per slot")
    args = ap.parse_args(argv)
    text = "\n".join(Path(p).read_text() for p in args.hlo)
    print(json.dumps(report(args.path, text, args.slots)))


if __name__ == "__main__":
    main()
