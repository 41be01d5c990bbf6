"""Window driver of the fleet cells: ``FederatedSim.run()`` on the jax scan.

Set-up draws the inputs from the seed, builds one simulator from them and
runs it once (the run that compiles). The window then runs that same
simulator again and again; each run starts from a fresh state and
simulates ``n_users`` users over the whole horizon. After the window
every run's answers are compared with the plain reference's.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

from bench import compare, traffic as gen
from bench.reference.fleet_online import simulate


class Cell:
    work_metric = "sim_user_slots_per_s"
    control_dtype = ml_dtypes.bfloat16

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core import Scenario
        from repro.core.arrivals import TraceArrivals

        self.config, self.traffic = config, traffic
        sc = config["scenario"]
        self.inputs = gen.fleet_inputs(config, traffic, seed)
        self.with_log = bool(traffic["collect_push_log"])
        self.sim = Scenario(
            policy=traffic["policy"],
            arrivals=TraceArrivals(self.inputs["app_sched"],
                                   self.inputs["app_choice"]),
            fleet=gen.program_fleet(self.inputs["device"]), seed=seed,
            collect_push_log=self.with_log, n_users=config["n_users"],
            horizon_s=config["horizon_s"], t_d=config["t_d"], **sc).build()
        engine = self.sim.resolve_engine()
        if engine != "jax":
            raise RuntimeError(f"the fleet cell resolved engine {engine!r}, "
                               "not the jax scan")
        self.n, self.T = config["n_users"], gen.n_slots(config["horizon_s"],
                                                        config["t_d"])
        self.runs = []
        self.slots = 0

    def _answer(self):
        r, st = self.sim_result, self.sim.state
        out = {"energy": np.asarray(st.energy),
               "updates": np.asarray(st.updates),
               "trace_Q": np.asarray(r.trace_Q),
               "trace_H": np.asarray(r.trace_H)}
        if self.with_log:
            for name, col in zip(("t", "user", "lag", "gap", "corun",
                                  "weight"), r.push_log.arrays()):
                out[name] = col
        return out

    def warm(self):
        self.unit()
        self.runs.clear()

    def unit(self) -> float:
        """One whole run of the horizon; returns its user-slots."""
        self.sim_result = self.sim.run()
        self.runs.append(self._answer())
        self.slots += self.T
        return float(self.n * self.T)

    def counts(self) -> dict:
        return {"slots": self.slots}

    def reset_counts(self):
        self.slots = 0

    def free(self):
        """Drop the program's objects before the reference runs."""
        self.sim = self.sim_result = None

    def reference(self, dtype=np.float64) -> dict:
        sc, inp = self.config["scenario"], self.inputs
        return simulate(inp["device"], inp["app_sched"], inp["app_choice"],
                        V=sc["V"], L_b=sc["L_b"], epsilon=sc["epsilon"],
                        eta=sc["eta"], beta=sc["beta"], t_d=self.config["t_d"],
                        ready_delay=sc["ready_delay"], v_norm0=sc["v_norm0"],
                        trace_every=sc["trace_every"], dtype=dtype)

    def numbers(self, answer: dict, ref: dict) -> dict:
        return compare.fleet_numbers(answer, ref, self.config["scenario"]["L_b"],
                                     self.with_log)
