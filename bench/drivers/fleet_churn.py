"""Window driver of the churning fleet: ``FederatedSim.run()`` on the jax
scan with the Markov device dynamics.

As ``fleet_scan``, whose cell this one extends: set-up draws the inputs
from the seed, builds one simulator and runs it once, and the window runs
it again and again. The configuration's ``dynamics`` knobs build one
``MarkovChurnDynamics``; the starting battery levels are drawn per user
from the seed on a stream of their own (``numpy.random.default_rng([seed,
1])``, ``battery_draw``), so the other inputs are those of ``fleet_scan``
draw for draw. Every run's answers carry the
final battery levels, the per-user drop counts and the churn counters,
and are compared with ``bench/reference/fleet_churn.py``.

A traced window also hands its readers ``op_scope``, the instruction
names of the scan chunk the window's last run dispatched (the engine's
``sim.scan_chunk``) mapped to their scopes, so device time can be split
by the slot step's phases.
"""
from __future__ import annotations

import sys

import numpy as np

from bench import compare, program_spans
from bench.drivers import fleet_scan
from bench.reference.fleet_churn import simulate


def battery_draw(config: dict, seed: int) -> np.ndarray:
    """Each user's starting charge, a capacity fraction: a ``flat_share``
    of the fleet uniform over the ``flat`` range, the rest uniform over
    the ``charged`` range."""
    spec, n = config["battery_init"], int(config["n_users"])
    rng = np.random.default_rng([seed, 1])
    flat = rng.random(n) < spec["flat_share"]
    return np.where(flat, rng.uniform(*spec["flat"], n),
                    rng.uniform(*spec["charged"], n))


# the run totals each answer carries beside the per-user drops
RUN_TOTALS = ("departures", "recoveries", "lost")


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class Cell(fleet_scan.Cell):
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core import MarkovChurnDynamics

        self.seed = seed
        self.battery0 = battery_draw(config, seed)
        self.dynamics = MarkovChurnDynamics(battery_init=self.battery0,
                                            **config["dynamics"])
        scenario = dict(config["scenario"], dynamics=self.dynamics)
        super().__init__(dict(config, scenario=scenario), traffic, seed)
        self.traced = False

    def _answer(self):
        out = super()._answer()
        dyn = self.sim.state.dyn
        out["battery"] = np.asarray(dyn["battery"], np.float64)
        out["drops"] = np.asarray(dyn["drops"])
        for name in RUN_TOTALS:
            out[name] = getattr(self.sim_result, name)
        out["in_flight"] = int(self.sim.state.in_flight)
        return out

    def reset_counts(self):
        super().reset_counts()
        self.traced = True

    def counts(self) -> dict:
        out = super().counts()
        if self.traced:
            out["op_scope"] = self._op_scope()
        return out

    def _op_scope(self) -> dict:
        """Instruction name -> scope of the scan chunk the window's last
        run dispatched, as the engine records it (``sim.scan_chunk``)."""
        return program_spans.hlo_scopes(self.sim.scan_chunk.hlo_text())

    def reference(self, dtype=np.float64) -> dict:
        sc, inp, dyn = self.config["scenario"], self.inputs, \
            self.config["dynamics"]
        ref = simulate(
            inp["device"], inp["app_sched"], inp["app_choice"],
            self.battery0, seed=self.seed, V=sc["V"], L_b=sc["L_b"],
            epsilon=sc["epsilon"], eta=sc["eta"], beta=sc["beta"],
            t_d=self.config["t_d"], ready_delay=sc["ready_delay"],
            v_norm0=sc["v_norm0"], trace_every=sc["trace_every"],
            dtype=dtype, **dyn)
        run = self.runs[-1] if self.runs else None
        if dtype == np.float64:
            log("churn, reference: " + churn_line(ref))
            if run is not None:
                log("churn, last run: " + churn_line(run))
        return ref

    def numbers(self, answer: dict, ref: dict) -> dict:
        """``fleet_scan``'s numbers and the churn's:

        - ``drops_differ``: share of users whose drop count differs;
        - ``battery_err``: the largest error of a final battery level, as
          a share of capacity;
        - ``counters_differ``: the largest difference of a run total
          (``departures``, ``recoveries``, ``lost``), per user of the
          fleet.

        ``energy_rel_err`` and ``battery_err`` are taken over the users
        whose schedule agrees with the reference's (the same update
        count, drop count and push slots). Alg. 2 in float32 and in
        float64 can decide a near-tie apart, and a user scheduled apart
        has another energy and battery by a whole training; such users
        are held by the shares ``pushes_differ``, ``updates_differ`` and
        ``drops_differ``."""
        out = super().numbers(answer, ref)
        n = len(ref["drops"])
        if len(answer["drops"]) != n:
            return dict(out, drops_differ=1.0, battery_err=float("inf"),
                        counters_differ=float("inf"))
        drops = np.asarray(answer["drops"])
        out["drops_differ"] = float(np.count_nonzero(drops != ref["drops"])
                                    / max(n, 1))
        keep = agreeing_users(answer, ref)
        out["energy_rel_err"] = compare._rel(
            np.asarray(answer["energy"])[keep], ref["energy"][keep], 1e-30)
        dyn = self.config["dynamics"]
        # levels are whole quanta with a battery_quantum, else capacity
        unit = dyn.get("battery_quantum") or 1.0
        err = np.abs(np.asarray(answer["battery"], np.float64)[keep]
                     - np.asarray(ref["battery"], np.float64)[keep])
        out["battery_err"] = float(err.max()) * unit \
            / float(dyn["battery_capacity"]) if err.size else 0.0
        out["counters_differ"] = max(abs(answer[k] - ref[k])
                                     for k in RUN_TOTALS) / max(n, 1)
        return out


def agreeing_users(answer: dict, ref: dict) -> np.ndarray:
    """Mask of the users with the reference's update count, drop count
    and push slots (``t`` of each push; without a push log, the first
    two)."""
    n = len(ref["updates"])
    keep = (np.asarray(answer["updates"]) == ref["updates"]) \
        & (np.asarray(answer["drops"]) == ref["drops"])
    if "t" in ref and "t" in answer:
        def keys(a):
            return np.asarray(a["t"], np.int64) * n \
                + np.asarray(a["user"], np.int64)
        moved = np.setxor1d(keys(answer), keys(ref)) % n
        keep[moved] = False
    return keep


def churn_line(a: dict) -> str:
    """The churn of one run's answers, or of the reference's."""
    started = a.get("started")
    if started is None:
        started = int(np.sum(a["updates"])) + a["lost"] + a["in_flight"]
    parts = [f"started {started}", f"lost {a['lost']} "
             f"({a['lost'] / max(started, 1):.4%})",
             f"departures {a['departures']}",
             f"recoveries {a['recoveries']}",
             f"drops {int(np.sum(a['drops']))}"]
    for key in ("battery_down_peak", "battery_down_ever", "net_bad_peak"):
        if key in a:
            parts.append(f"{key} {a[key]:.4%}")
    for key in ("battery_falls", "battery_rises"):
        if key in a:
            parts.append(f"{key} {a[key]}")
    return ", ".join(parts)
