"""The simulator's profiler spans and the slot step's named scopes.

``FederatedSim.run`` and the jax scan engine mark their host work with
``jax.profiler.TraceAnnotation`` spans (``sim.*``, ``scan.*``) whose args
carry the run's counters; the slot step's phases carry ``slot.*``
``jax.named_scope`` names in the HLO metadata of their ops."""
from __future__ import annotations

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Scenario
from repro.core import vector_engine as ve
from repro.core.scenario import run_sweep

ALL_SCOPES = {"slot.dynamics", "slot.apps", "slot.cooldown", "slot.policy",
              "slot.train", "slot.push_log", "slot.energy", "slot.queues"}


def fleet(**kw):
    base = dict(n_users=64, horizon_s=600, seed=3, app_arrival_p=0.02,
                engine="jax", jax_chunk=320, policy="immediate",
                collect_push_log=True)
    base.update(kw)
    return Scenario(**base)


def program(name):
    return name.startswith(("sim.", "scan."))


def host_events(logdir):
    """``[(name, start_ns, end_ns, args)]`` of every host event, in
    start order, from the one trace file under ``logdir``."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((ev.name, ev.start_ns, ev.end_ns,
                            dict(ev.stats) if program(ev.name) else {})
                           for ev in line.events)
    return sorted(out, key=lambda x: (x[1], -x[2]))


def traced(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.unit"):
            result = fn()
    finally:
        jax.profiler.stop_trace()
    return result, host_events(str(tmp_path))


def named(events, name):
    return [ev for ev in events if ev[0] == name]


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_a_run_nests_its_spans_and_counts_its_pushes(tmp_path):
    sim = fleet().build()
    sim.run()                       # compiles; the traced run resets
    result, events = traced(tmp_path, sim.run)
    unit, = named(events, "bench.unit")
    run, = named(events, "sim.run")
    assert inside(run, unit)
    assert run[3] == {"engine": "jax", "n_users": 64, "slots": 600}
    for name, k in (("sim.reset", 1), ("scan.setup", 1),
                    ("scan.to_device", 1), ("scan.chunk", 2),
                    ("scan.wait", 2), ("scan.drain", 2),
                    ("scan.traces", 2), ("scan.finish", 1)):
        spans = named(events, name)
        assert len(spans) == k, name
        assert all(inside(sp, run) for sp in spans), name
    chunks = named(events, "scan.chunk")
    assert [c[3] for c in chunks] == [
        {"t0": 0, "live_slots": 320, "cap": 1024},
        {"t0": 320, "live_slots": 280, "cap": 1024}]
    assert named(events, "scan.overflow") == []
    pushes = [d[3]["pushes"] for d in named(events, "scan.drain")]
    assert len(result.push_log) > 0
    assert sum(pushes) == len(result.push_log)
    # the host phases follow one another in the run's order
    order = [named(events, n)[0][1] for n in
             ("sim.reset", "scan.setup", "scan.to_device", "scan.chunk")]
    assert order == sorted(order)
    assert named(events, "scan.finish")[0][1] > chunks[-1][2]


def slots_and_blocks(t, K):
    """Pushing slots and ``K``-row blocks, recounted from a ``t`` column."""
    _, k = np.unique(t, return_counts=True)
    return len(k), int(sum(-(-k // K)))


@pytest.mark.parametrize("sweep", [False, True], ids=["run", "sweep"])
def test_the_drain_counts_the_slots_and_blocks_it_wrote(tmp_path, sweep):
    scs = [fleet(seed=s, app_arrival_p=0.05) for s in (1, 2, 3)]
    if sweep:
        results, events = traced(tmp_path, lambda: run_sweep(scs))
    else:
        sim = scs[0].build()
        results, events = traced(tmp_path, lambda: [sim.run()])
    drains = [d[3] for d in named(events, "scan.drain")]
    assert len(drains) == 2
    K = ve._push_block(64)
    for c, args in enumerate(drains):       # chunks of 320 slots
        want = [slots_and_blocks(t[(t >= 320 * c) & (t < 320 * (c + 1))], K)
                for t in (r.push_log.arrays()[0] for r in results)]
        assert (args["push_slots"], args["blocks"]) == \
            tuple(map(sum, zip(*want)))
        assert args["push_slots"] > 0


def test_an_overflowing_chunk_is_marked_with_its_new_capacity(tmp_path):
    sim = fleet(push_log_capacity=8, app_arrival_p=0.05).build()
    result, events = traced(tmp_path, sim.run)
    overflows = named(events, "scan.overflow")
    assert overflows, "a buffer of 8 rows overflows"
    caps = [o[3]["cap"] for o in overflows]
    assert caps == sorted(caps) and all(c > 8 for c in caps)
    # each overflow re-runs its chunk: one more scan.chunk than chunks
    assert len(named(events, "scan.chunk")) == 2 + len(overflows)
    assert sum(d[3]["pushes"] for d in named(events, "scan.drain")) == \
        len(result.push_log)


def test_without_the_log_the_wait_is_on_the_traces(tmp_path):
    sim = fleet(collect_push_log=False).build()
    _, events = traced(tmp_path, sim.run)
    assert len(named(events, "scan.wait")) == 2
    assert named(events, "scan.drain") == []
    assert named(events, "sim.reset") == []     # a first run resets nothing


@pytest.mark.parametrize("engine", ["loop", "vectorized"])
def test_every_engine_runs_inside_sim_run(tmp_path, engine):
    sim = fleet(engine=engine, n_users=8, horizon_s=30).build()
    _, events = traced(tmp_path, sim.run)
    run, = named(events, "sim.run")
    assert run[3]["engine"] == engine
    assert not [ev for ev in events if ev[0].startswith("scan.")]


def test_a_batched_sweep_has_the_same_spans(tmp_path):
    scs = [fleet(seed=s) for s in (1, 2, 3)]
    results, events = traced(tmp_path, lambda: run_sweep(scs))
    run, = named(events, "sim.run")
    assert run[3] == {"engine": "jax_sweep", "n_users": 64, "slots": 600,
                      "batch": 3}
    for name, k in (("scan.setup", 1), ("scan.to_device", 1),
                    ("scan.chunk", 2), ("scan.drain", 2),
                    ("scan.finish", 1)):
        assert len(named(events, name)) == k, name
        assert all(inside(sp, run) for sp in named(events, name)), name
    assert sum(d[3]["pushes"] for d in named(events, "scan.drain")) == \
        sum(len(r.push_log) for r in results)


def scopes_in_chunk(**kw):
    sim = fleet(**kw).build()
    rs = ve._ops_to_device(ve._jax_run_setup(sim, jax, jnp), jax, jnp)
    fn = ve._jax_chunk_fn(rs.n, rs.chunk, rs.T, sim.policy, rs.overhead,
                          rs.collect, rs.cap, rs.statics, sim.agg,
                          sim.dynamics)
    state = rs.state
    if rs.collect:
        state = state.replace(events=ve.PushBuffer(
            jnp.zeros((rs.cap, 6), rs.f), jnp.asarray(0, rs.i)))
    text = fn.lower(rs.tables, rs.app_sched, rs.app_choice, rs.scalars,
                    rs.pol_ops, rs.agg_ops, rs.dyn_ops,
                    jnp.asarray(0, rs.i), state).as_text(debug_info=True)
    return set(re.findall(r"slot\.[a-z_]+", text))


@pytest.mark.parametrize("kw, missing", [
    (dict(), {"slot.dynamics"}),
    (dict(collect_push_log=False), {"slot.dynamics", "slot.push_log"}),
    (dict(dynamics="markov"), set()),
    (dict(policy="online", collect_push_log=False),
     {"slot.dynamics", "slot.push_log"}),
])
def test_the_chunk_names_every_phase_of_the_slot_step(kw, missing):
    assert scopes_in_chunk(**kw) == ALL_SCOPES - missing


def test_spans_cost_no_time_with_the_profiler_off():
    """A span the profiler does not record is a few hundred nanoseconds;
    a run opens about ten plus three or four per chunk."""
    import time

    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        with jax.profiler.TraceAnnotation("scan.chunk", t0=i,
                                          live_slots=600, cap=8):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 50e-6


@pytest.mark.parametrize("churn", [False, True], ids=["none", "markov"])
def test_the_finish_carries_the_churn_counters(tmp_path, churn):
    from repro.core import MarkovChurnDynamics

    dyn = MarkovChurnDynamics(p_off=0.02, p_on=0.1, p_net_bad=0.05) \
        if churn else "none"
    sim = fleet(dynamics=dyn, seed=5).build()
    result, events = traced(tmp_path, sim.run)
    finish, = named(events, "scan.finish")
    if not churn:
        assert finish[3] == {}
        return
    counters = {k: getattr(result, k)
                for k in ("drops", "departures", "recoveries", "lost")}
    assert finish[3] == counters
    assert counters["drops"] > 0 and counters["recoveries"] > 0


@pytest.mark.parametrize("capacity", [0, 8], ids=["fits", "overflows"])
def test_the_run_records_the_chunk_it_dispatched_last(tmp_path, capacity):
    """``sim.scan_chunk`` is the last chunk the run ran: its length and
    push capacity are those of the last ``scan.chunk`` span, after any
    overflow, and its HLO text holds that capacity's buffer."""
    sim = fleet(push_log_capacity=capacity, app_arrival_p=0.05).build()
    assert sim.scan_chunk is None
    _, events = traced(tmp_path, sim.run)
    last = named(events, "scan.chunk")[-1][3]
    rec = sim.scan_chunk
    assert (rec.chunk, rec.cap) == (320, last["cap"])
    assert bool(named(events, "scan.overflow")) == bool(capacity)
    text = rec.hlo_text()
    assert f"[{rec.cap + ve._push_block(64)},6]" in text
    assert not any(isinstance(x, jax.Array)
                   for x in jax.tree.leaves(rec.operands))
