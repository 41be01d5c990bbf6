"""``BENCHMARK.json`` against the contract it is written to, and every cell
resolving to its files by name alone."""
from __future__ import annotations

import json
import re

import pytest

from bench import harness

SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
E2E = {m["name"]: m for m in SPEC["end_to_end"]}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((harness.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_a_full_check_fits_its_time():
    runs = 2 + 14 * 24
    total = runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_entries():
    named = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] \
        + SPEC["per_layer"]
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for group in ("configs", "workloads"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/configs/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {c["name"] for c in SPEC["configs"]} == \
        {w["config"] for w in SPEC["workloads"]}


def test_end_to_end_bounds_and_sources():
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}


def test_per_layer_metrics_move_a_metric_of_their_cells():
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = E2E[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS)
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files(cell):
    c = harness.resolve(SPEC, cell)
    assert c.chips == 1
    names = {m["name"] for m in c.end_to_end}
    assert names == {"setup_s", c.driver.Cell.work_metric}
    assert c.per_layer, "every cell reports a per-layer metric"
    for module in c.per_layer.values():
        assert callable(module.read)
    assert set(c.config["limits"]) >= {"pushes_differ", "lag_err"} or \
        not c.traffic.get("collect_push_log", True)


def test_the_harness_names_no_cell_config_mix_or_metric():
    """A later cell, mix, configuration or per-layer metric is added by
    adding files and entries: no shared file names one."""
    shared = [harness.BENCH / f for f in ("run.py", "harness.py",
                                          "trace.py", "readings.py",
                                          "compare.py", "traffic.py")]
    words = set(CELLS) | {c["name"] for c in SPEC["configs"]} \
        | {w["traffic"] for w in SPEC["workloads"]} \
        | {m["name"] for m in SPEC["per_layer"]}
    for path in shared:
        text = path.read_text()
        for w in words:
            assert f'"{w}"' not in text and f"'{w}'" not in text, (path, w)


def test_a_cell_and_configuration_added_by_a_file_and_entries(tmp_path):
    """A new deployment is its own file; the cell that runs it, and the
    metrics it reports, are entries that name files already there."""
    old = SPEC["configs"][0]
    cfg = dict(harness.load_json(harness.ROOT / old["file"]), n_users=2000)
    path = tmp_path / "fleet2k.json"
    path.write_text(json.dumps(cfg))
    like = SPEC["workloads"][0]["name"]
    cell = dict(SPEC["workloads"][0], name="fleet2k-added", config="fleet2k")

    def listed(metrics):
        return [dict(m, workloads=m["workloads"] + [cell["name"]])
                if like in m.get("workloads", ()) else m for m in metrics]

    spec = dict(SPEC, configs=SPEC["configs"] + [
        dict(old, name="fleet2k", file=str(path))],
        workloads=SPEC["workloads"] + [cell],
        end_to_end=listed(SPEC["end_to_end"]),
        per_layer=listed(SPEC["per_layer"]))
    c = harness.resolve(spec, cell["name"])
    assert c.config["n_users"] == 2000
    assert {m["name"] for m in c.end_to_end} == \
        {"setup_s", c.driver.Cell.work_metric}
    assert set(c.per_layer) == set(harness.resolve(SPEC, like).per_layer)


def test_unknown_cell_and_missing_files_fail():
    with pytest.raises(KeyError):
        harness.resolve(SPEC, "no-such-cell")
    spec = dict(SPEC, per_layer=SPEC["per_layer"] + [
        {"name": "no.such_metric", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device",
         "moves": "setup_s"}])
    with pytest.raises(FileNotFoundError):
        harness.resolve(spec, CELLS[0])


def test_peaks_are_keyed_by_device_kind():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")
