"""Window driver of the LeNet-5 fleet cells: ``FederatedSim.run()`` on the
vectorized engine, every push a real round of training.

Set-up derives ``experiments_per_window`` experiment seeds from the seed
(the configuration says how many, and why). For each it draws the fleet's
inputs, builds one simulator whose LeNet backend makes its data and
initial weights from that experiment's seed, and runs it once (the first
run compiles; the others find every program compiled). The window then
runs the simulators in turn, again and again; each run is a whole
experiment from the initial model, the simulator resetting its backend in
between. After the window every run's answers are compared with the plain
reference's, computed once an experiment on the same inputs (its Alg. 2
reading the momentum norms the set-up run reported), and with the set-up
run's own: two runs of one simulator must be identical.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

from bench import compare_ml, traffic as gen
from bench.reference import lenet_fl


def experiment_seeds(seed: int, count: int) -> list:
    """``count`` distinct seeds below 2**31 drawn from ``seed``."""
    state = np.random.SeedSequence(seed).generate_state(count, np.uint32)
    return [int(s) >> 1 for s in state]


class _Experiment:
    """One simulator and what its runs answered."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core import Scenario
        from repro.core.arrivals import TraceArrivals

        self.seed = seed
        ml = dict(config["ml"])
        self.inputs = gen.fleet_inputs(config, traffic, seed)
        self.sim = Scenario(
            policy=traffic["policy"],
            arrivals=TraceArrivals(self.inputs["app_sched"],
                                   self.inputs["app_choice"]),
            fleet=gen.program_fleet(self.inputs["device"]), seed=seed,
            collect_push_log=True, n_users=config["n_users"],
            horizon_s=config["horizon_s"], t_d=config["t_d"],
            ml=ml.pop("backend"),
            ml_kwargs={k: ml[k] for k in ("batch_size", "n_train", "n_test",
                                          "partition", "noise",
                                          "eval_every")},
            **config["scenario"]).build()
        engine = self.sim.resolve_engine()
        if engine != "vectorized":
            raise RuntimeError(f"the real-ML cell resolved engine "
                               f"{engine!r}, not the vectorized engine")
        if not hasattr(self.sim.ml_backend, "push_v_norms"):
            raise RuntimeError("the real-ML backend keeps no per-push "
                               "momentum norms (push_v_norms), which the "
                               "comparison reads")
        self._watch_first_cohort(self.sim.ml_backend)
        self.first = None

    def _watch_first_cohort(self, backend):
        """Keep the global model as the first slot's pushes left it: the
        backend's finish is wrapped, and its parameters are immutable
        device arrays, so keeping them copies nothing."""
        finish = backend.finish_async_batch

        def watched(*args, **kwargs):
            out = finish(*args, **kwargs)
            if self._first_params is None:
                self._first_params = backend.server.params
            return out

        backend.finish_async_batch = watched
        self._first_params = None

    def run(self, index: int):
        self._first_params = None
        r = self.sim.run()
        backend = self.sim.ml_backend
        ans = {"experiment": index,
               "energy": np.asarray(self.sim.state.energy),
               "updates": np.asarray(self.sim.state.updates),
               "trace_Q": np.asarray(r.trace_Q),
               "trace_H": np.asarray(r.trace_H),
               "v_norms": backend.push_v_norms(),
               "accuracy": np.asarray(r.accuracy, np.float64),
               # device trees, flattened by free() after the window
               "params_first": self._first_params,
               "params": backend.server.params}
        ans.update(zip(compare_ml.LOG, r.push_log.arrays()))
        if self.first is None:
            self.first = ans
        return ans, int(r.updates)


class Cell:
    work_metric = "sim_user_slots_per_s"
    control_dtype = ml_dtypes.bfloat16

    def __init__(self, config: dict, traffic: dict, seed: int):
        if not traffic["collect_push_log"]:
            raise ValueError("the comparison reads the push log: a real-ML "
                             "cell keeps it on")
        self.config, self.traffic, self.seed = config, traffic, seed
        ml = config["ml"]
        self.experiments = [
            _Experiment(config, traffic, s) for s in
            experiment_seeds(seed, int(config["experiments_per_window"]))]
        self.n, self.T = config["n_users"], gen.n_slots(config["horizon_s"],
                                                        config["t_d"])
        shard = ml["n_train"] // self.n
        self.samples_per_push = shard // ml["batch_size"] * ml["batch_size"]
        self.runs = []
        self._next = 0
        self.reset_counts()

    def warm(self):
        """Each simulator's first run: it compiles, and it reports the
        norms its reference's Alg. 2 reads."""
        for _ in self.experiments:
            self.unit()
        self.runs.clear()

    def unit(self) -> float:
        """One whole experiment, the next simulator's; returns its
        user-slots."""
        k = self._next
        self._next = (k + 1) % len(self.experiments)
        ans, pushes = self.experiments[k].run(k)
        self.runs.append(ans)
        self.n_runs += 1
        self.slots += self.T
        self.pushes += pushes
        self.samples += pushes * self.samples_per_push
        return float(self.n * self.T)

    def counts(self) -> dict:
        return {"runs": self.n_runs, "slots": self.slots,
                "pushes": self.pushes, "samples": self.samples}

    def reset_counts(self):
        self.n_runs = self.slots = self.pushes = self.samples = 0

    def free(self):
        """Bring the kept models to the host and drop the program's
        objects before the reference runs."""
        for ans in self.runs + [e.first for e in self.experiments]:
            for key in ("params_first", "params"):
                if ans is not None and not isinstance(ans[key], np.ndarray):
                    ans[key] = (np.zeros(0) if ans[key] is None
                                else lenet_fl.flat(ans[key]))
        for e in self.experiments:
            e.sim = None

    def reference(self, dtype=np.float32) -> list:
        """One reference answer an experiment. ``dtype`` below float32
        computes the whole reference one precision lower: the model and
        momentum and the schedule's arithmetic (the control)."""
        import jax
        from repro.data.synthetic import cifarlike_dataset
        from repro.models.lenet import init_lenet

        ml, sc = self.config["ml"], self.config["scenario"]
        low = np.dtype(dtype).itemsize < 4
        refs = []
        for k, e in enumerate(self.experiments):
            images, labels = cifarlike_dataset(ml["n_train"], seed=e.seed,
                                               noise=ml["noise"])
            params0 = init_lenet(jax.random.PRNGKey(e.seed))
            inp = e.inputs
            ref = lenet_fl.simulate(
                inp["device"], inp["app_sched"], inp["app_choice"], images,
                labels, params0, V=sc["V"], L_b=sc["L_b"],
                epsilon=sc["epsilon"], eta=sc["eta"], beta=sc["beta"],
                t_d=self.config["t_d"], ready_delay=sc["ready_delay"],
                trace_every=sc["trace_every"], batch_size=ml["batch_size"],
                dtype=dtype, schedule_dtype=dtype if low else np.float64,
                decision_norms=e.first["v_norms"],
                train_pushes=self.config["compare"]["first_pushes"])
            ref["experiment"] = k
            refs.append(ref)
        return refs

    def numbers(self, answer, ref: list) -> dict:
        """A run's numbers against its experiment's reference; a control
        (a list, as ``reference`` gives it) reads the worst of its
        experiments."""
        if isinstance(answer, list):
            each = [self.numbers(a, ref) for a in answer]
            return {key: max(n[key] for n in each) for key in each[0]}
        k = answer["experiment"]
        out = compare_ml.lenet_numbers(
            answer, ref[k], self.config["scenario"]["L_b"],
            self.config["compare"]["first_pushes"])
        if "accuracy" in answer:
            out["repeat_differ"] = float(
                not _same(answer, self.experiments[k].first))
        return out


def _same(a: dict, b: dict) -> bool:
    """Bit for bit the same push log, accuracy trace, momentum norms and
    final model."""
    keys = compare_ml.LOG + ("accuracy", "v_norms", "params")
    return all(np.asarray(a[k]).shape == np.asarray(b[k]).shape
               and np.array_equal(a[k], b[k]) for k in keys)
