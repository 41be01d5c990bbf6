"""Share of the device's peak that training uses, in percent: the FLOPs
of the samples the window's runs trained (``bench/counts_lenet.py``),
over the traced window times the chip's bfloat16 peak."""
from bench import counts_lenet, ml_spans


def read(trace, counts, peak):
    if not ml_spans.has_ops(trace) or not counts.get("samples") \
            or trace.window_s <= 0 or not peak.get("bf16_flops_per_s"):
        return None
    flops = counts["samples"] * counts_lenet.train_flops_per_sample()
    return 100.0 * flops / (trace.window_s * peak["bf16_flops_per_s"])
