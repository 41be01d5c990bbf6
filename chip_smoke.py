#!/usr/bin/env python3
"""Bring-up smoke of the system's three parts on a TPU, through the entry
points a user calls. Each phase checks its result against an oracle.

    python3 chip_smoke.py               # phases a-d on one chip
    python3 chip_smoke.py --four-chips  # the paths across four chips only

Phases, all in the default f32:

a. Paper size (25 users, 3600 s, seed 0), all four policies: the jax scan
   on the chip against the loop oracle on the host.
b. Fleet scale (10^6 users, 600 s, online policy, push log on, chunk
   auto-tuned from the chip's memory): the scan against the NumPy engine.
c. Real-ML LeNet-5 (64 users, 62,006 parameters) through the Pallas push
   apply against the reference apply.
d. Serving tier over a 10^6-float parameter vector (eight kernel blocks):
   the Pallas shard apply against the reference apply.

``--four-chips`` runs phase b's scan (over a 300 s horizon) sharded over
four chips against the unsharded scan on one of them, and phase d's server
with four shards on a four-device serving mesh against one shard, and
nothing else.

The script runs in one process, catches nothing and falls back to nothing:
it exits non-zero at the first failed check, and without a TPU before any
work. Earlier lines report each phase's wall time (the first call with
the XLA compile seconds inside it, and a steady second call where the
phase is cheap enough to repeat) and each compared value with its delta. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Tolerances against each oracle.
A_ENERGY_RTOL = 1e-4        # scan vs loop oracle, 25 users (v5e: 2.8e-5)
B_UPDATES_RTOL = 1e-4       # scan vs NumPy engine, 10^6 users (v5e: 0)
B_ENERGY_RTOL = 1e-5        # (v5e: 1.8e-6)
C_FLOAT_RTOL = 2e-5         # Pallas vs reference (tests/test_kernel_hotpath)
C_GAP_ATOL = 1e-6
D_PARAM_ATOL = 1e-5         # Pallas vs reference shard apply
D_VNORM_RTOL = 1e-5
FOUR_ENERGY_RTOL = 1e-5     # sharded vs unsharded total (v5e: 4.7e-6)

# one event per XLA compile (tracing events nest, so they are not summed)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


def _on_duration(event, duration, **_):
    if event == _COMPILE_EVENT:
        _compile_s[0] += duration


def timed(fn, *args, **kwargs):
    """(result, wall seconds, compile seconds inside the call)."""
    c0 = _compile_s[0]
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kwargs))
    return out, time.perf_counter() - t0, _compile_s[0] - c0


def log(msg):
    print(msg, flush=True)


def check(ok, what):
    if not ok:
        raise AssertionError(what)


def rel(a, b):
    return abs(a - b) / max(abs(b), 1e-30)


def report_timing(label, first, compile_s, steady=None):
    log(f"  {label}: first call {first:.3f}s (compile {compile_s:.3f}s)"
        + ("" if steady is None else f", steady {steady:.3f}s"))


def assert_tpu_kernel(jitted, *args, **static):
    """The compiled program of ``jitted`` holds a Mosaic kernel."""
    text = jitted.lower(*args, **static).compile().as_text()
    check("tpu_custom_call" in text,
          f"{getattr(jitted, '__name__', jitted)} compiled without a "
          "tpu_custom_call: the Pallas kernel did not reach the chip")


def log_digest(push_log) -> str:
    """sha256 over the push log's six columns."""
    h = hashlib.sha256()
    for col in push_log.arrays():
        h.update(col.tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------- phase a
def phase_a(n_users=25, horizon_s=3600):
    from repro.core import Scenario

    log(f"phase a: paper size, n_users={n_users} horizon_s={horizon_s} "
        "seed=0, jax scan vs loop oracle")
    for pol in ("immediate", "sync", "offline", "online"):
        base = dict(policy=pol, n_users=n_users, horizon_s=horizon_s,
                    seed=0)
        sim = Scenario(engine="jax", **base).build()
        check(sim.resolve_engine() == "jax",
              f"{pol}: engine resolved to {sim.resolve_engine()!r}")
        r, first, comp = timed(sim.run)
        r2, steady, _ = timed(sim.run)
        ref, ref_s, _ = timed(Scenario(engine="loop", **base).run)
        d_e = rel(r.energy_j, ref.energy_j)
        log(f"  {pol}: engine=jax updates {r.updates} vs loop "
            f"{ref.updates}; energy {r.energy_j!r} vs {ref.energy_j!r} "
            f"(rel {d_e:.3e}); loop oracle {ref_s:.3f}s")
        report_timing(pol, first, comp, steady)
        check(r.updates == ref.updates == r2.updates,
              f"{pol}: updates {r.updates}/{r2.updates} vs {ref.updates}")
        check(d_e <= A_ENERGY_RTOL, f"{pol}: energy rel {d_e:.3e}")


# ---------------------------------------------------------------- phase b
def fleet_scenario(n_users, horizon_s, **kw):
    from repro.core import Scenario

    return Scenario(policy="online", n_users=n_users, horizon_s=horizon_s,
                    seed=0, jax_chunk=0, collect_push_log=True, **kw)


def phase_b(n_users=1_000_000, horizon_s=600):
    from repro.core.autotune import autotune_scan_params

    log(f"phase b: fleet scale, online n_users={n_users} "
        f"horizon_s={horizon_s} jax_chunk=0, push log on, scan vs NumPy")
    t0 = time.perf_counter()
    sim = fleet_scenario(n_users, horizon_s, engine="jax").build()
    log(f"  build (host arrival draws) {time.perf_counter() - t0:.3f}s")
    check(sim.resolve_engine() == "jax",
          f"engine resolved to {sim.resolve_engine()!r}")
    tune = autotune_scan_params(sim)
    log(f"  autotune: chunk {tune.jax_chunk}, push capacity "
        f"{tune.push_capacity}, budget {tune.device_budget} B, modeled "
        f"{tune.est_bytes_per_device} B/device")
    # one call: a second would double the phase's chip time
    r, first, comp = timed(sim.run)
    report_timing("scan", first, comp)
    del sim
    t0 = time.perf_counter()
    ref = fleet_scenario(n_users, horizon_s, engine="vectorized").run()
    ref_s = time.perf_counter() - t0
    d_u = rel(r.updates, ref.updates)
    d_e = rel(r.energy_j, ref.energy_j)
    log(f"  updates {r.updates} vs numpy {ref.updates} (rel {d_u:.3e}); "
        f"energy {r.energy_j!r} vs {ref.energy_j!r} (rel {d_e:.3e}); "
        f"push log {len(r.push_log)} rows; numpy engine {ref_s:.3f}s")
    # H > 0 is what sends a slot to the online hook's order-free or
    # replay branch instead of the H == 0 one
    log(f"  mean H {r.mean_H!r} vs numpy {ref.mean_H!r}; H > 0 at "
        f"{int(np.sum(r.trace_H > 0))} of {len(r.trace_H)} traced slots")
    check(r.mean_H > 0, "H stayed 0: the H > 0 branches never ran")
    check(len(r.push_log) == r.updates,
          f"push log {len(r.push_log)} rows for {r.updates} updates")
    check(d_u <= B_UPDATES_RTOL, f"updates rel {d_u:.3e}")
    check(d_e <= B_ENERGY_RTOL, f"energy rel {d_e:.3e}")
    return r


# ---------------------------------------------------------------- phase c
def lenet_scenario(n_users, horizon_s, kernel):
    from repro.core import Scenario

    # V small enough that hundreds of pushes fire; L_b relaxed keeps
    # H == 0, where the schedule does not read the momentum norm
    return Scenario(policy="online", ml="lenet", n_users=n_users,
                    horizon_s=horizon_s, app_arrival_p=0.004, V=5.0,
                    seed=0, kernel=kernel,
                    ml_kwargs=dict(n_train=4000, n_test=500))


def schedule_digest(push_log) -> str:
    """The digest tests/test_real_mode.py pins: (t, user, lag, corun)."""
    payload = json.dumps([(e["t"], e["user"], e["lag"], e["corun"])
                          for e in push_log]).encode()
    return hashlib.sha256(payload).hexdigest()


def phase_c(n_users=64, horizon_s=2400, kernel="auto"):
    from repro.core.realml import _FINISH_FN_CACHE
    from repro.kernels.fused_update import (fused_weighted_apply_pallas,
                                            kernel_interpret)

    log(f"phase c: real-ML LeNet-5, online n_users={n_users} "
        f"horizon_s={horizon_s}, kernel={kernel!r} vs 'reference'")
    runs = {}
    for k in (kernel, "reference"):
        sim = lenet_scenario(n_users, horizon_s, k).build()
        check(sim.resolve_engine() == "vectorized",
              f"engine resolved to {sim.resolve_engine()!r}")
        r, first, comp = timed(sim.run)
        _, steady, _ = timed(lenet_scenario(n_users, horizon_s, k).run)
        log(f"  kernel {k!r} -> {sim.ml_backend.kernel!r}: "
            f"{len(r.push_log)} pushes, final accuracy {r.accuracy[-1]}")
        report_timing(sim.ml_backend.kernel, first, comp, steady)
        runs[sim.ml_backend.kernel] = (sim, r)
    check(set(runs) == {"pallas", "reference"},
          f"kernel modes resolved to {sorted(runs)}")
    check(any(key[-1] == "pallas" for key in _FINISH_FN_CACHE),
          "no Pallas train+push executable was built")
    (sp, rp), (sr, rr) = runs["pallas"], runs["reference"]
    server = sp.ml_backend.server
    n_params = sum(x.size for x in jax.tree.leaves(server.params))
    log(f"  model: {n_params} parameters")
    assert_tpu_kernel(
        jax.jit(lambda p, v, n: fused_weighted_apply_pallas(
            p, v, n, w=0.5, eta=server.eta, beta=server.beta,
            interpret=kernel_interpret())),
        server.params, server._v, server.params)
    check(len(rp.push_log) >= 200, f"only {len(rp.push_log)} pushes")
    check(rp.mean_H == 0.0, f"H left 0 (mean {rp.mean_H})")
    check(schedule_digest(rp.push_log) == schedule_digest(rr.push_log),
          "schedule digests differ")
    gp, gr = rp.push_log.field("gap"), rr.push_log.field("gap")
    wp, wr = rp.push_log.field("weight"), rr.push_log.field("weight")
    d_gap = float(np.max(np.abs(gp - gr) / (C_GAP_ATOL / C_FLOAT_RTOL
                                            + np.abs(gr))))
    d_w = float(np.max(np.abs(wp - wr)))
    pp = np.concatenate([np.ravel(x) for x in
                         jax.tree.leaves(sp.ml_backend.server.params)])
    pr = np.concatenate([np.ravel(x) for x in
                         jax.tree.leaves(sr.ml_backend.server.params)])
    d_p = float(np.max(np.abs(pp - pr)))
    log(f"  schedule digest identical; gap max rel {d_gap:.3e}, weight "
        f"max abs {d_w:.3e}, final params max abs {d_p:.3e}, accuracy "
        f"{rp.accuracy[-1][1]!r} vs {rr.accuracy[-1][1]!r}")
    check(np.allclose(gp, gr, rtol=C_FLOAT_RTOL, atol=C_GAP_ATOL),
          f"gaps differ (max rel {d_gap:.3e})")
    check(np.allclose(wp, wr, rtol=C_FLOAT_RTOL, atol=1e-7),
          f"weights differ (max abs {d_w:.3e})")
    check(np.allclose(pp, pr, rtol=C_FLOAT_RTOL, atol=C_GAP_ATOL),
          f"final params differ (max abs {d_p:.3e})")


# ---------------------------------------------------------------- phase d
def serve_params(n_floats, seed=0):
    rng = np.random.default_rng(seed)
    side = int(round(n_floats ** 0.5))
    return {"w": rng.standard_normal((side, n_floats // side),
                                     dtype=np.float32)}


def serve_pushes(params, kernel, n_shards=1, mesh=None, pushes=32,
                 seed=1, n_clients=3):
    """Interleaved pull/push stream; returns (server, per-push
    (weight, v_norm))."""
    from repro.serve import ShardedAsyncParameterServer

    server = ShardedAsyncParameterServer(
        params, eta=0.05, beta=0.9, aggregation="fedasync_poly",
        n_shards=n_shards, mesh=mesh, kernel=kernel)
    rng = np.random.default_rng(seed)
    pulled, out, step = {}, [], 0
    while len(out) < pushes:
        cid = step % n_clients
        if cid not in pulled:
            p, _ = server.pull(cid)
            pulled[cid] = {k: np.asarray(v) + rng.normal(
                0, 0.1, v.shape).astype(np.float32) for k, v in p.items()}
        if step % 2 == 1:
            res = server.push(cid, pulled.pop(cid))
            out.append((res.applied_weight, float(server.v_norm)))
        step += 1
    server.assert_consistent()
    return server, out


def published(server):
    return np.asarray(server.spec.join(server.snapshot_flat()[0]))


def compare_servers(a, obs_a, b, obs_b, label):
    w_a, v_a = np.array(obs_a).T
    w_b, v_b = np.array(obs_b).T
    d_w = float(np.max(np.abs(w_a - w_b)))
    d_v = float(np.max(np.abs(v_a - v_b) / np.abs(v_b)))
    d_p = float(np.max(np.abs(published(a) - published(b))))
    log(f"  {label}: weight max abs {d_w:.3e}, v_norm max rel {d_v:.3e}, "
        f"published params max abs {d_p:.3e}")
    check(d_w <= 1e-6, f"{label}: weights differ by {d_w:.3e}")
    check(d_v <= D_VNORM_RTOL, f"{label}: v_norms differ by {d_v:.3e}")
    check(d_p <= D_PARAM_ATOL, f"{label}: params differ by {d_p:.3e}")


def phase_d(n_floats=1_000_000, kernel="auto", pushes=32):
    from repro.kernels.fused_update import fused_apply_flat, kernel_interpret

    log(f"phase d: serving tier, {n_floats} floats, 1 shard, "
        f"{pushes} pushes, kernel={kernel!r} vs 'reference'")
    params = serve_params(n_floats)
    res = {}
    for k in (kernel, "reference"):
        (srv, obs), first, comp = timed(serve_pushes, params, k,
                                        pushes=pushes)
        _, steady, _ = timed(serve_pushes, params, k, pushes=pushes)
        report_timing(f"kernel {k!r} -> {srv.kernel!r}", first, comp, steady)
        res[srv.kernel] = (srv, obs)
    check(set(res) == {"pallas", "reference"},
          f"kernel modes resolved to {sorted(res)}")
    srv = res["pallas"][0]
    st = srv._shards[0]
    assert_tpu_kernel(fused_apply_flat, st.params, st.momentum, st.params,
                      0.5, 20.0, 0.9, interpret=kernel_interpret())
    compare_servers(*res["pallas"], *res["reference"], "pallas vs reference")


# ---------------------------------------------------------- four chips
def devices_of(x):
    return {d.id for d in x.sharding.device_set}


def check_placement(r, n_devices, n_users):
    """Each reported leaf of the run's scan sat on ``n_devices`` devices,
    its user axis split evenly between them."""
    per_device = -(-n_users // n_devices)
    for name, (ids, shard_shape) in r.placement.items():
        log(f"  {name}: devices {list(ids)}, shard shape {shard_shape}")
        check(len(ids) == n_devices,
              f"{name} sat on devices {list(ids)}, not {n_devices}")
        check(shard_shape[-1] == per_device,
              f"{name} shard shape {shard_shape}: the user axis is not "
              f"split {n_devices} ways")


def four_chip_scan(n_users=1_000_000, horizon_s=300, n_devices=4):
    log(f"four chips, scan: online n_users={n_users} horizon_s={horizon_s}"
        f" n_devices={n_devices} vs n_devices=0")
    sharded = fleet_scenario(n_users, horizon_s, engine="jax",
                             n_devices=n_devices).build()
    check(sharded.resolve_engine() == "jax",
          f"engine resolved to {sharded.resolve_engine()!r}")
    r4, first, comp = timed(sharded.run)
    report_timing(f"sharded x{n_devices}", first, comp)
    check_placement(r4, n_devices, n_users)
    del sharded
    one = fleet_scenario(n_users, horizon_s, engine="jax").build()
    r1, first, comp = timed(one.run)
    report_timing("one chip", first, comp)
    check_placement(r1, 1, n_users)
    dg4, dg1 = log_digest(r4.push_log), log_digest(r1.push_log)
    d_e = rel(r4.energy_j, r1.energy_j)
    log(f"  push log {len(r4.push_log)} vs {len(r1.push_log)} rows, "
        f"digest {dg4[:16]} vs {dg1[:16]} ({'identical' if dg4 == dg1 else 'DIFFER'}); "
        f"updates {r4.updates} vs {r1.updates}; energy rel {d_e:.3e}; "
        f"Q/H traces equal: {np.array_equal(r4.trace_Q, r1.trace_Q)}/"
        f"{np.array_equal(r4.trace_H, r1.trace_H)}")
    check(dg4 == dg1, "sharded push log differs from the unsharded scan")
    check(r4.updates == r1.updates, "update counts differ")
    check(np.array_equal(r4.trace_Q, r1.trace_Q)
          and np.array_equal(r4.trace_H, r1.trace_H), "Q/H traces differ")
    check(d_e <= FOUR_ENERGY_RTOL, f"energy rel {d_e:.3e}")


def four_chip_serve(n_floats=1_000_000, n_shards=4, pushes=32):
    from repro.launch.mesh import make_serving_mesh

    log(f"four chips, serving tier: {n_floats} floats, {n_shards} shards on "
        f"make_serving_mesh({n_shards}) vs 1 shard, {pushes} pushes")
    params = serve_params(n_floats)
    (s4, o4), first, comp = timed(serve_pushes, params, "auto", n_shards,
                                  make_serving_mesh(n_shards), pushes)
    report_timing(f"{n_shards} shards, kernel {s4.kernel!r}", first, comp,
                  timed(serve_pushes, params, "auto", n_shards,
                        make_serving_mesh(n_shards), pushes)[1])
    owners = [devices_of(st.params) for st in s4._shards]
    log(f"  shard owners {[sorted(o) for o in owners]}")
    check(all(len(o) == 1 for o in owners)
          and len(set().union(*owners)) == n_shards,
          f"shards sit on {owners}, not one device each")
    (s1, o1), first, comp = timed(serve_pushes, params, "auto", 1, None,
                                  pushes)
    report_timing(f"1 shard, kernel {s1.kernel!r}", first, comp,
                  timed(serve_pushes, params, "auto", 1, None, pushes)[1])
    compare_servers(s4, o4, s1, o1, f"{n_shards} shards vs 1")


# ---------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded scan and the 4-shard "
                         "server, each against its one-chip twin")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX backend is {backend!r}, not a TPU; "
              "nothing was run", file=sys.stderr)
        return 2
    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: {len(devices)} TPU device(s), need {want}",
              file=sys.stderr)
        return 2

    from repro.kernels.fused_update import (kernel_interpret,
                                            resolve_kernel_mode)
    from repro.launch.cache import enable_compile_cache

    log(f"device: {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}; jax {jax.__version__}; compile cache "
        f"{enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    mode, interp = resolve_kernel_mode("auto"), kernel_interpret()
    log(f"kernel mode: auto -> {mode!r}, interpret={interp}")
    check(mode == "pallas" and interp is False,
          "the push apply would not run as a compiled Pallas kernel")

    phases = ([four_chip_scan, four_chip_serve] if args.four_chips
              else [phase_a, phase_b, phase_c, phase_d])
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        log(f"  {phase.__name__} done in {time.perf_counter() - t0:.3f}s")

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
