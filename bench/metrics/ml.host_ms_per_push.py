"""Host work of the real-ML backend per applied push, in milliseconds:
the device idle whose innermost program span is an ``ml.*`` span (pull,
train dispatch, finish, eval, reset), over the pushes the window's runs
applied."""
from bench import ml_spans


def read(trace, counts, peak):
    if not ml_spans.has_ops(trace) or not counts.get("pushes"):
        return None
    idle = ml_spans.idle_by_span(trace)
    ns = sum(v for k, v in idle.items() if k.startswith(ml_spans.ML))
    return ns / 1e6 / counts["pushes"]
