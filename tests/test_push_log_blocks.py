"""The jax slot step writes a slot's push-log rows as contiguous blocks of
``K`` rows at the buffer cursor, and writes nothing in a slot where no user
finishes.

Pins: the lowered chunk has no scatter under the ``slot.push_log`` scope,
and in the vmapped sweep build no scatter there is indexed per user; a
burst of more finishers in one slot than one block holds logs what the
NumPy engine logs, also through the overflow retry of a one-row buffer,
and every drained chunk holds as many rows as its count."""
from __future__ import annotations

import hashlib
import re

import jax
import numpy as np
import pytest

from repro.core import Scenario
from repro.core import vector_engine as ve
from repro.core.energy import TESTBED
from repro.core.fleet import CustomCatalogFleet

from test_app_select import BASE, _chunk_and_operands
from test_profiling import named, traced


def scoped_ops(text, op):
    """``(scope, operand dims)`` of every ``op`` instruction in an HLO
    module's text: the scope is the op's ``op_name`` metadata, the dims
    those of each operand's defining instruction."""
    dims = {name: [int(d) for d in shape.split(",") if d]
            for name, shape in re.findall(
                r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]", text, re.M)}
    out = []
    for args, scope in re.findall(
            rf"= (?:\([^)]*\)|\S+) {op}\(([^)]*)\)[^\n]*"
            r'op_name="([^"]*)"', text):
        out.append((scope, [dims.get(a.strip().lstrip("%"))
                            for a in args.split(",")]))
    return out


def lowered_hlo(batch):
    fn, ops = _chunk_and_operands(
        Scenario(**BASE, collect_push_log=True), batch=batch)
    return fn.lower(*ops).as_text(dialect="hlo", debug_info=True)


def test_the_push_log_phase_has_no_scatter():
    text = lowered_hlo(batch=0)
    ops = {op: [s for s, _ in scoped_ops(text, op) if "slot.push_log" in s]
           for op in ("scatter", "sort", "dynamic-update-slice")}
    assert ops["scatter"] == []
    # the parser sees the phase: its compaction and its block write
    assert ops["sort"] and ops["dynamic-update-slice"], ops


def test_the_sweep_build_indexes_no_scatter_per_user():
    n_arr = BASE["n_users"]
    scatters = [(s, d) for s, d in scoped_ops(lowered_hlo(batch=3),
                                              "scatter")
                if "slot.push_log" in s]
    # vmap turns the block write into a scatter with one window per config
    assert scatters
    for scope, (_, index, _) in scatters:
        assert index is not None and n_arr not in index, (scope, index)


N_BURST = 9000      # more than two blocks of 4,096 finish in one slot


def schedule_digest(log):
    return hashlib.sha256(";".join(
        f'{e["t"]},{e["user"]},{e["lag"]},{int(e["corun"])}'
        for e in log).encode()).hexdigest()


def burst(engine, policy, aggregation, devices, cap=0):
    sc = Scenario(n_users=N_BURST, horizon_s=400, seed=11,
                  app_arrival_p=0.002, engine=engine, jax_chunk=64,
                  policy=policy, aggregation=aggregation,
                  push_log_capacity=cap,
                  fleet=CustomCatalogFleet(
                      [TESTBED[d] for d in devices]))
    return sc.build().run()


@pytest.fixture
def _x64():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", prev)


@pytest.mark.parametrize("policy, aggregation, devices", [
    ("immediate", "replace", ["Nexus6"]),
    ("immediate", "fedasync_poly", ["Nexus6"]),
    ("sync", "replace", ["Nexus6"]),
    ("immediate", "hetero_aware", ["Nexus6", "Hikey970"]),
], ids=["replace", "fedasync_poly", "sync", "hetero_aware"])
def test_a_multi_block_burst_logs_what_numpy_logs(_x64, tmp_path, policy,
                                                  aggregation, devices):
    ref = burst("vectorized", policy, aggregation, devices)
    res = burst("jax", policy, aggregation, devices)
    K = ve._push_block(N_BURST)
    t = ref.push_log.arrays()[0]
    _, per_slot = np.unique(t, return_counts=True)
    assert per_slot.max() > K      # one slot writes several blocks

    assert len(res.push_log) == len(ref.push_log) == res.updates
    assert schedule_digest(res.push_log) == schedule_digest(ref.push_log)
    # Eq. 4's gap and the polynomial rules' weights are powers, which
    # XLA's and NumPy's pow round apart in the last bit
    for col in (3, 5):
        np.testing.assert_allclose(res.push_log.arrays()[col],
                                   ref.push_log.arrays()[col], rtol=1e-13)

    # a one-row buffer overflows mid-burst, across block boundaries, and
    # the retried chunks log the same rows, bit for bit
    tiny, events = traced(tmp_path,
                          lambda: burst("jax", policy, aggregation, devices,
                                        cap=1))
    assert named(events, "scan.overflow")
    for a, b in zip(tiny.push_log.arrays(), res.push_log.arrays()):
        assert np.array_equal(a, b)
    # every drained chunk held exactly its count of rows
    chunk = 64
    drained = np.bincount(tiny.push_log.arrays()[0] // chunk,
                          minlength=-(-400 // chunk))
    assert [d[3]["pushes"] for d in named(events, "scan.drain")] == \
        drained.tolist()
