"""Device busy time per applied push, in milliseconds: the union of
device-op intervals in the traced window over the pushes that the
window's runs applied (each a cohort's local round and its apply)."""
from bench import ml_spans


def read(trace, counts, peak):
    if not ml_spans.has_ops(trace) or not counts.get("pushes"):
        return None
    return 1e3 * trace.busy_s / counts["pushes"]
