"""Device time of the slot step's churn phase, in milliseconds a simulated
slot: the union of the window's device-op intervals whose instruction
lies under the ``slot.dynamics`` scope, over the slots the window's runs
simulated. The scopes come from ``counts["op_scope"]``, the scan chunk's
instruction names mapped by ``program_spans.hlo_scopes``; a driver that
hands none, or a program without the scope, reads nothing.

Only ops that ran while the host dispatched a chunk and waited for it
(from each ``scan.chunk`` span to the end of the ``scan.wait`` after it)
count: the other programs of a run, such as the drain's slices, run
outside those stretches and may reuse the chunk's instruction names."""
import bisect

from bench import program_spans, trace as tr

SCOPE = "slot.dynamics"


def chunk_stretches(spans):
    """``(start, end)`` of each chunk: its ``scan.chunk`` span's start to
    the end of the first ``scan.wait`` span that starts after it."""
    waits = sorted((s, e) for name, s, e in spans if name == "scan.wait")
    starts = [s for s, _ in waits]
    out = []
    for name, s, e in spans:
        if name == "scan.chunk":
            k = bisect.bisect_left(starts, e)
            if k < len(waits):
                out.append((s, waits[k][1]))
    return out


def read(trace, counts, peak):
    scopes, slots = counts.get("op_scope"), counts.get("slots")
    if not trace.ops or not scopes or not slots:
        return None
    stretches = chunk_stretches(trace.spans)
    if not stretches:
        return None
    ops = {dev: [op for op in ev
                 if any(op[2] > s and op[1] < e for s, e in stretches)]
           for dev, ev in trace.ops.items()}
    ns = program_spans.device_ns_by_scope(
        tr.Reduced(window=trace.window, ops=ops, spans=trace.spans), scopes,
        SCOPE)
    return None if ns is None else ns / 1e6 / slots
