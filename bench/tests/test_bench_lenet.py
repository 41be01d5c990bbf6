"""The LeNet-5 fleet cell at a small size on the CPU: a run without a chip,
planted faults against the comparison, the FLOP count, and the cell's
per-layer readers on synthetic traces.

The small runs judge training on the limits the configuration states for
float32 products (``limits_float32_products``), which the program meets on
a CPU; a model left untrained fails the cell's own limits too."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from bench import counts_lenet, harness, run, trace

SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")
CONFIG = harness.load_json(harness.BENCH / "configs" / "lenet25.json")
LIMITS = CONFIG["limits"]
CELL = "lenet25-online"
NEW = ("ml.device_ms_per_push", "ml.host_ms_per_push", "sim.host_loop_ms",
       "ml.train_mfu")
MS = 1e6
DEV = "/device:TPU:0"
PEAK = {"bf16_flops_per_s": 197e12}


def small_config(limits: dict) -> dict:
    """4 clients on 160 samples over 900 slots, two experiments a window;
    L_b at 1 so that Alg. 2 schedules within the horizon."""
    return dict(CONFIG, n_users=4, horizon_s=900, experiments_per_window=2,
                scenario=dict(CONFIG["scenario"], L_b=1.0),
                ml=dict(CONFIG["ml"], n_train=160, n_test=64,
                        eval_every=300), limits=limits)


def spec_with(tmp_path_factory, config: dict) -> dict:
    path = tmp_path_factory.mktemp("lenet") / "lenet_small.json"
    path.write_text(json.dumps(config))
    spec = json.loads(json.dumps(SPEC))
    for c in spec["configs"]:
        if c["name"] == "lenet25":
            c["file"] = str(path)
    return spec


@pytest.fixture(scope="module")
def small_spec(tmp_path_factory):
    """The small cell judged on the limits of float32 products."""
    return spec_with(tmp_path_factory, small_config(
        dict(LIMITS, **CONFIG["limits_float32_products"])))


@pytest.fixture(scope="module")
def small_spec_chip_limits(tmp_path_factory):
    """The small cell judged on the cell's own limits."""
    return spec_with(tmp_path_factory, small_config(LIMITS))


def run_cell(spec, seed=2**31 + 77):
    return run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "0.3", "--trace", "0"], require_chip=False, spec=spec)


def test_the_cell_is_the_papers_deployment():
    c = harness.resolve(SPEC, CELL)
    assert c.chips == 1 and c.config["n_users"] == 25
    assert c.config["horizon_s"] == 10800 and c.config["reduced"] == {}
    assert c.config["ml"]["parameters"] == 62006
    assert set(c.per_layer) == {"sim.device_idle_share", *NEW}


def test_the_parameter_count_is_the_models():
    from repro.models.lenet import init_lenet, param_count

    assert param_count(init_lenet(jax.random.PRNGKey(0))) == \
        CONFIG["ml"]["parameters"]


def test_a_sound_run_is_correct(small_spec):
    res = run_cell(small_spec)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"sim_user_slots_per_s", "setup_s"}
    assert res["checks"]["repeat_differ"]["value"] == 0.0
    assert set(res["checks"]) == set(LIMITS)


def _dropped_push(monkeypatch):
    """The first push of a slot left out of the log while the log is
    empty: at least the run's first push is missing."""
    from repro.core.engine_state import PushLog

    orig = PushLog.extend

    def dropped(self, t, *cols):
        if len(self) == 0:
            cols = [np.asarray(c)[1:] for c in cols]
            if not len(cols[0]):
                return None
        return orig(self, t, *cols)

    monkeypatch.setattr(PushLog, "extend", dropped)


def _wrong_gap(monkeypatch):
    """The logged Eq. 4 gaps off by ten times their limit."""
    from repro.core import realml

    orig = realml.gradient_gap
    scale = 1 + 10 * LIMITS["gap_rel_err"]
    monkeypatch.setattr(realml, "gradient_gap",
                        lambda *a, **k: scale * orig(*a, **k))


def _after_each_finish(monkeypatch, alter):
    """``alter(server)`` after every fused finish of a run."""
    from repro.core.realml import ImageClassifierBackend

    orig = ImageClassifierBackend.finish_async_batch

    def finish(self, *a, **k):
        out = orig(self, *a, **k)
        alter(self.server)
        return out

    monkeypatch.setattr(ImageClassifierBackend, "finish_async_batch",
                        finish)


def _perturbed_params(monkeypatch):
    """The global model moved by 1,000 float32 ulps after each finish."""
    def alter(server):
        server.params = jax.tree.map(
            lambda a: a * (1 + 1e3 * np.finfo(np.float32).eps),
            server.params)

    _after_each_finish(monkeypatch, alter)


def _bfloat16_params(monkeypatch):
    """The global model and momentum held in bfloat16."""
    def held(tree):
        return jax.tree.map(
            lambda a: a.astype(ml_dtypes.bfloat16).astype(np.float32), tree)

    def alter(server):
        server.params, server._v = held(server.params), held(server._v)

    _after_each_finish(monkeypatch, alter)


@pytest.mark.parametrize("fault", [_dropped_push, _wrong_gap,
                                   _perturbed_params, _bfloat16_params])
def test_a_planted_fault_is_not_correct(small_spec, monkeypatch, fault):
    fault(monkeypatch)
    res = run_cell(small_spec)
    assert not res["correct"] and res["failed"] == res["attempted"]


def _model_left_as_it_was(monkeypatch):
    """The global model put back to the initial one, with zero momentum,
    after each finish: the run trains nothing that lasts."""
    from repro.core.realml import ImageClassifierBackend

    orig = ImageClassifierBackend.finish_async_batch

    def finish(self, *a, **k):
        out = orig(self, *a, **k)
        self.server.params = self._params0
        self.server._v = jax.tree.map(jnp.zeros_like, self.server._v)
        return out

    monkeypatch.setattr(ImageClassifierBackend, "finish_async_batch",
                        finish)


def test_a_model_left_untrained_fails_the_cells_own_limits(
        small_spec_chip_limits, monkeypatch):
    _model_left_as_it_was(monkeypatch)
    res = run_cell(small_spec_chip_limits)
    assert not res["correct"] and res["failed"] == res["attempted"]
    assert res["checks"]["cohort1_change_rel_err"]["value"] >= \
        LIMITS["cohort1_change_rel_err"]


def test_the_flop_count_is_the_hand_count():
    # conv1 28*28*6 * 5*5*3, conv2 10*10*16 * 5*5*6, 400*120, 120*84, 84*10
    hand = 352_800 + 240_000 + 48_000 + 10_080 + 840
    assert counts_lenet.forward_macs() == hand == 651_720
    assert counts_lenet.train_flops_per_sample() == 6 * hand == 3_910_320


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def one_run(ops):
    """A 100 ms window holding one run: a reset 2-4, a pull 10-11, a
    training dispatch 20-22 inside a finish 20-40, an eval 60-70; ``ops``
    are (name, start_ms, end_ms) on one device."""
    spans = [("bench.unit", 0, 100), ("sim.run", 1, 96),
             ("sim.reset", 2, 4), ("ml.reset", 2.5, 3.5),
             ("ml.pull", 10, 11), ("ml.finish", 20, 40),
             ("ml.train", 20, 22), ("ml.eval", 60, 70)]
    return trace.Reduced(
        window=(0, 100 * MS),
        ops={DEV: [(n, s * MS, e * MS) for n, s, e in ops]},
        spans=[(n, s * MS, e * MS) for n, s, e in spans])


COUNTS = {"runs": 1, "slots": 900, "pushes": 4, "samples": 640}


def test_the_readers_split_the_run():
    # busy 22-40 (training and apply) and 62-64 (eval)
    red = one_run([("fusion.1", 22, 40), ("fusion.2", 62, 64)])
    assert reader("ml.device_ms_per_push").read(red, COUNTS, PEAK) == \
        pytest.approx(20 / 4)
    # ml.* idle: reset 1, pull 1, train 2, eval 8
    assert reader("ml.host_ms_per_push").read(red, COUNTS, PEAK) == \
        pytest.approx(12 / 4)
    # sim.run itself: 1-2, 4-10, 11-20, 40-60, 70-96 less sim.reset's own
    # 2-2.5 and 3.5-4 (charged to sim.reset)
    assert reader("sim.host_loop_ms").read(red, COUNTS, PEAK) == \
        pytest.approx(1 + 6 + 9 + 20 + 26)
    mfu = reader("ml.train_mfu").read(red, COUNTS, PEAK)
    assert mfu == pytest.approx(100 * 640 * 3_910_320 / (0.1 * 197e12))
    assert 0 < mfu < 100


@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_nothing_without_ops_or_pushes(name):
    mod = reader(name)
    assert mod.read(one_run([]), COUNTS, PEAK) is None
    busy = one_run([("fusion.1", 22, 40)])
    assert mod.read(busy, dict(COUNTS, pushes=0, samples=0), PEAK) is None
    assert mod.read(busy, COUNTS, PEAK) is not None
