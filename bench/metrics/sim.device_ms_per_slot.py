"""Device busy time per simulated slot, in milliseconds: the union of
device-op intervals in the traced window over the slots the window's
runs simulated."""


def read(trace, counts, peak):
    if not trace.ops or not counts.get("slots"):
        return None
    return 1e3 * trace.busy_s / counts["slots"]
