"""The vectorized engine's host slot loop, in milliseconds a run: the
device idle whose innermost program span is ``sim.run`` itself (no
``sim.reset``, ``scan.*`` or ``ml.*`` span inside it), over the
``sim.run`` spans that lie wholly inside the traced window."""
from bench import ml_spans, program_spans


def read(trace, counts, peak):
    n = program_spans.runs(trace)
    if not ml_spans.has_ops(trace) or not n or not counts.get("pushes"):
        return None
    return ml_spans.idle_by_span(trace).get(program_spans.RUN_SPAN,
                                            0.0) / 1e6 / n
