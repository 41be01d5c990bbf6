"""The readers of the program's own spans and scopes, on synthetic traces
and on a fleet run traced on the CPU."""
from __future__ import annotations

import pytest

from bench import harness, program_spans as ps, trace

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1e6
DEV = "/device:TPU:0"


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def one_run(ops, extra_spans=()):
    """A 100 ms window holding one run: 10 ms of prologue (reset 2-4,
    setup 4-7, to_device 7-9), a chunk 10-12 and its wait 12-80, then a
    drain 80-85, traces 85-88 and finish 88-95; ``ops`` are (name,
    start_ms, end_ms) on one device."""
    spans = [("bench.unit", 0, 100), ("sim.run", 1, 96),
             ("sim.reset", 2, 4), ("scan.setup", 4, 7),
             ("DevicePut", 7.5, 8.5), ("scan.to_device", 7, 9),
             ("scan.chunk", 10, 12), ("scan.wait", 12, 80),
             ("scan.drain", 80, 85), ("scan.traces", 85, 88),
             ("scan.finish", 88, 95)] + list(extra_spans)
    return trace.Reduced(
        window=(0, 100 * MS),
        ops={DEV: [(n, s * MS, e * MS) for n, s, e in ops]},
        spans=[(n, s * MS, e * MS) for n, s, e in spans])


def test_an_idle_stretch_is_cut_at_every_span_boundary():
    # busy only 11-79: the idle 0-11 crosses bench.unit, sim.run, reset,
    # setup, to_device (with JAX's DevicePut inside it) and the chunk;
    # 79-100 crosses the wait, drain, traces, finish and sim.run's end
    red = one_run([("fusion.1", 11, 79)])
    idle = ps.idle_by_span(red)
    assert idle == pytest.approx({
        "bench.unit": 1 * MS + 4 * MS,      # 0-1 and 96-100
        "sim.run": 1 * MS + 1 * MS + 1 * MS,    # 1-2, 9-10, 95-96
        "sim.reset": 2 * MS, "scan.setup": 3 * MS,
        "scan.to_device": 2 * MS,           # DevicePut counts under it
        "scan.chunk": 1 * MS, "scan.wait": 1 * MS,
        "scan.drain": 5 * MS, "scan.traces": 3 * MS,
        "scan.finish": 7 * MS})
    assert sum(idle.values()) == pytest.approx(
        (red.window_s - red.busy_s) * 1e9)


def test_a_gap_across_two_spans_is_split_at_their_boundary():
    red = one_run([("fusion.1", 0, 83), ("fusion.2", 87, 100)])
    idle = ps.idle_by_span(red)
    assert idle == pytest.approx({"scan.drain": 2 * MS,
                                  "scan.traces": 2 * MS})


def test_idle_is_averaged_over_devices_and_closes():
    red = one_run([("fusion.1", 0, 100)])
    red.ops["/device:TPU:1"] = [("fusion.1", 0, 50 * MS)]
    idle = ps.idle_by_span(red)
    assert sum(idle.values()) == pytest.approx(25 * MS)
    assert sum(idle.values()) == pytest.approx(
        (red.window_s - red.busy_s) * 1e9)


def test_the_host_readers_split_idle_before_and_after_the_chunks():
    red = one_run([("fusion.1", 11, 79)])
    pro = reader("sim.host_prologue_ms").read(red, {"slots": 600}, PEAK)
    epi = reader("sim.host_epilogue_ms").read(red, {"slots": 600}, PEAK)
    # sim.run's own, reset, setup, to_device; drain, traces, finish
    assert pro == pytest.approx(3 + 2 + 3 + 2)
    assert epi == pytest.approx(5 + 3 + 7)
    # with the harness's own share and the chunk/wait parts, all idle
    idle = ps.idle_by_span(red)
    rest = (idle["bench.unit"] + idle["scan.chunk"]
            + idle["scan.wait"]) / MS
    assert pro + epi + rest == pytest.approx(
        (red.window_s - red.busy_s) * 1e3)


def test_runs_are_the_whole_sim_run_spans_in_the_window():
    red = one_run([], extra_spans=[("sim.run", 97, 140)])
    assert ps.runs(red) == 1
    red.spans.append(("sim.run", 96.5 * MS, 99 * MS))
    assert ps.runs(red) == 2
    per_run = reader("sim.host_epilogue_ms").read(red, {}, PEAK)
    assert per_run == pytest.approx(
        ps.idle_ms_per_run(red, ps.EPILOGUE))


def test_the_host_readers_find_nothing_without_ops_or_runs():
    bare = trace.Reduced(window=(0, 1e9), ops={}, spans=[])
    no_runs = trace.Reduced(window=(0, 1e9),
                            ops={DEV: [("fusion.1", 0, 1e8)]},
                            spans=[("bench.unit", 0, 1e9)])
    for name in ("sim.host_prologue_ms", "sim.host_epilogue_ms"):
        assert reader(name).read(bare, {"slots": 5}, PEAK) is None
        # a program without spans (an older tree) reads as nothing
        assert reader(name).read(no_runs, {"slots": 5}, PEAK) is None
    assert ps.idle_by_span(bare) == {}


BODY = "jit(simulate)/while/body/"
# (HLO line, instruction, scope path) as a compiled module's text has them
LINES = [
    ('  %while.1 = (s32[]) while(%t), condition=%c, body=%b, '
     'metadata={op_name="jit(simulate)/while"}',
     "while.1", "jit(simulate)/while"),
    ('  %fusion.44 = f32[8,6]{0,1} fusion(%a, %b), kind=kCustom, '
     f'metadata={{op_name="{BODY}slot.push_log/scatter" stack_frame_id=9}}',
     "fusion.44", BODY + "slot.push_log/scatter"),
    ('  ROOT %fusion.45 = s32[8] fusion(%c), kind=kLoop, '
     f'metadata={{op_name="{BODY}slot.push_log/cumsum"}}',
     "fusion.45", BODY + "slot.push_log/cumsum"),
    ('  %fusion.7 = f32[8] fusion(%d), kind=kLoop, '
     f'metadata={{op_name="{BODY}slot.policy/gather"}}',
     "fusion.7", BODY + "slot.policy/gather"),
    ("  %copy.2 = f32[8] copy(%e)", "copy.2", None),
]
HLO = "\n".join(line for line, _, _ in LINES)


@pytest.mark.parametrize("line, name, scope", LINES)
def test_scopes_come_from_the_compiled_hlo(line, name, scope):
    assert ps.hlo_scopes(line).get(name) == scope
    assert ps.hlo_scopes(HLO).get(name) == scope


def test_device_time_by_scope_is_the_union_of_its_ops():
    # a TPU trace names ops by the head of their HLO text
    red = one_run([("%while.1 = (s32[]) while(%t)", 10, 80),
                   ("%fusion.44 = f32[8,6]{0,1} fusion(%a, %b)", 20, 30),
                   ("%fusion.45 = s32[8] fusion(%c)", 25, 40),
                   ("fusion.7", 50, 55),
                   ("%copy.2 = f32[8] copy(%e)", 60, 61)])
    scopes = ps.hlo_scopes(HLO)
    assert ps.device_ns_by_scope(red, scopes, "slot.push_log") == \
        pytest.approx(20 * MS)
    assert ps.device_ns_by_scope(red, scopes, "slot.policy") == \
        pytest.approx(5 * MS)
    assert ps.device_ns_by_scope(red, scopes, "slot.") == \
        pytest.approx(25 * MS)
    assert ps.device_ns_by_scope(red, scopes, "slot.energy") is None
    assert ps.device_ns_by_scope(red, {}, "slot.") is None


def test_a_traced_fleet_run_reads_back(tmp_path):
    """Two runs of a small fleet under the profiler, as the harness
    traces its window: the reduction keeps the program's spans on the
    harness's thread, the window and the existing readers read as they
    did, and with device ops laid under each wait the idle split closes."""
    import jax

    from repro.core import Scenario

    sim = Scenario(n_users=32, horizon_s=400, seed=5, app_arrival_p=0.02,
                   engine="jax", jax_chunk=200, policy="immediate",
                   collect_push_log=True).build()
    sim.run()
    jax.profiler.start_trace(str(tmp_path))
    pushes = 0
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.unit"):
            pushes += len(sim.run().push_log)
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    red = trace.reduce_xplane(path)
    units = [s for s in red.spans if s[0] == "bench.unit"]
    assert red.window == (units[0][1], units[1][2])
    assert ps.runs(red) == 2
    names = {s[0] for s in red.spans}
    assert {"sim.run", "sim.reset", "scan.setup", "scan.to_device",
            "scan.chunk", "scan.wait", "scan.drain", "scan.traces",
            "scan.finish"} <= names
    # the CPU has no TPU plane: every reader finds nothing and none raises
    assert red.ops == {}
    for name in ("sim.device_idle_share", "sim.device_ms_per_slot",
                 "sim.host_prologue_ms", "sim.host_epilogue_ms"):
        assert reader(name).read(red, {"slots": 800}, PEAK) is None
    rep = ps.report(path, HLO, slots=800)
    assert (rep["runs"], rep["chunks"], rep["pushes"]) == (2, 4, pushes)
    # no device ops: no time under any scope
    assert rep["device_ms_by_scope"] == {"slot.policy": 0.0,
                                         "slot.push_log": 0.0}

    # the device busy exactly while the host waits on it
    red.ops[DEV] = [("fusion.1", s, e) for n, s, e in red.spans
                    if n == "scan.wait"]
    idle = ps.idle_by_span(red)
    assert idle.get("scan.wait", 0.0) == 0.0
    assert sum(idle.values()) == pytest.approx(
        (red.window_s - red.busy_s) * 1e9, rel=1e-9)
    pro = reader("sim.host_prologue_ms").read(red, {}, PEAK)
    epi = reader("sim.host_epilogue_ms").read(red, {}, PEAK)
    assert pro > 0 and epi > 0
    assert reader("sim.device_ms_per_slot").read(red, {"slots": 800},
                                                 PEAK) == \
        pytest.approx(1e3 * red.busy_s / 800)
