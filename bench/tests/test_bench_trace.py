"""The trace reduction and the per-layer readers, on synthetic traces and
on one trace recorded on the CPU."""
from __future__ import annotations

import pytest

from bench import harness, trace

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


@pytest.mark.parametrize("intervals, total", [
    ([], 0.0),
    ([(0, 10)], 10.0),
    ([(0, 10), (5, 15)], 15.0),           # overlap
    ([(0, 10), (2, 3), (4, 9)], 10.0),     # nested
    ([(20, 30), (0, 10)], 20.0),           # apart, unsorted
    ([(0, 10), (10, 12)], 12.0),           # touching
])
def test_union_length(intervals, total):
    assert trace.union_length(intervals) == total


def test_idle_gaps_cover_what_no_op_covers():
    ops = [(2, 4), (3, 5), (8, 9)]
    assert trace.idle_gaps(ops, 0, 10) == [(0, 2), (5, 8), (9, 10)]
    assert trace.idle_gaps(ops, 2, 5) == []
    assert trace.idle_gaps([], 0, 3) == [(0, 3)]
    assert trace.clip([(0, 5), (6, 20), (30, 40)], 2, 10) == [(2, 5),
                                                               (6, 10)]


def test_self_times_subtract_nested_ops():
    # a loop op (0-100) holding two body ops, one with a nested op
    ev = [("while", 0, 100), ("a", 10, 30), ("b", 40, 90), ("c", 50, 60)]
    got = dict(trace.self_times(ev))
    assert got == {"while": 30, "a": 20, "b": 40, "c": 10}


def test_innermost_host_span_names_each_point():
    spans = [("bench.unit", 0, 100), ("PjitFunction(simulate)", 10, 20),
             ("bench.unit", 200, 300)]
    got = trace.innermost_spans(spans, [15, 50, 150, 250, 5])
    assert got == ["PjitFunction(simulate)", "bench.unit",
                   "outside any span", "bench.unit", "bench.unit"]


def test_op_names():
    text = ('%fused_weighted_apply.1 = (f32[512,128]{1,0}) custom-call(%a), '
            'custom_call_target="tpu_custom_call"')
    assert trace.op_label(text) == "fused_weighted_apply.1"
    assert trace.op_label("%while.4 = (s32[]) while(%t)") == "while.4"
    meta = '%fusion.3 = f32[8] fusion(%x), metadata={op_name="jit(f)/a/b/c"}'
    assert trace.op_label(meta) == "fusion.3 [a/b/c]"


def synthetic(ms_ops, window=(0, 10_000_000)):
    """A one-device trace; ``ms_ops`` are (name, start_ms, end_ms)."""
    ops = [(n, s * 1e6, e * 1e6) for n, s, e in ms_ops]
    spans = [("bench.unit", window[0], window[1]),
             ("bench.reference_like", 8e6, 9e6)]
    return trace.Reduced(window=window, ops={"/device:TPU:0": ops},
                         spans=spans)


def test_reduced_busy_idle_and_breakdown():
    red = synthetic([("%while.1 = x", 0, 4), ("%fusion.2 = y", 1, 3),
                     ("%fused_weighted_apply.7 = z", 5, 6)])
    assert red.window_s == pytest.approx(0.01)
    assert red.busy_s == pytest.approx(0.005)
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["fusion.2", pytest.approx(0.002)]
    assert dict(map(tuple, bd["device_ops"]))["while.1"] == \
        pytest.approx(0.002)
    gaps = dict(map(tuple, bd["idle_gaps"]))
    # 4-5 ms is named after bench.unit; 6-10 ms after the inner span
    # that covers its midpoint
    assert gaps["bench.unit"] == pytest.approx(0.001)
    assert gaps["bench.reference_like"] == pytest.approx(0.004)
    assert reader("sim.device_idle_share").read(red, {}, PEAK) == \
        pytest.approx(50.0)
    assert reader("sim.device_ms_per_slot").read(red, {"slots": 10},
                                                 PEAK) == pytest.approx(0.5)


def test_readers_find_nothing_without_device_ops():
    red = trace.Reduced(window=(0, 1e9), ops={}, spans=[])
    for name in ("sim.device_idle_share", "sim.device_ms_per_slot"):
        assert reader(name).read(red, {"slots": 5}, PEAK) is None


def test_reduce_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.unit"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    red = trace.reduce_xplane(trace.find_xplane(str(tmp_path)))
    units = [s for s in red.spans if s[0] == "bench.unit"]
    assert len(units) == 2
    assert red.window == (units[0][1], units[1][2])
    assert red.window_s > 0
    # the CPU has no TPU device plane: nothing to read, and no error
    assert red.ops == {} and red.busy_s == 0.0
    assert trace.find_xplane(str(tmp_path / "none")) is None
