"""Share of the traced window in which no operation ran on the device,
in percent: 1 - (union of device-op intervals) / window."""


def read(trace, counts, peak):
    if not trace.ops or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
