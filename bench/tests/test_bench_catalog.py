"""The benchmark's files are found by name, and BENCHMARK.json is the
catalog's rendering of them and keeps to the benchmark contract."""
import json
import re
import shutil

import pytest

from bench import catalog

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    return json.loads((catalog.ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_is_in_step_with_the_files():
    assert (catalog.ROOT / "BENCHMARK.json").read_text() == catalog.render()


def test_benchmark_json_keeps_to_the_contract():
    b = _bench()
    assert list(b) == ["command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"]
    assert 1 <= b["run_seconds"] <= 51
    cells = {w["name"]: w for w in b["workloads"]}
    # a full check of 24 cells at this run length fits in 12 hours
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    for entry in b["configs"] + b["workloads"] + b["end_to_end"] \
            + b["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert {c["name"] for c in b["configs"]} == {w["config"] for w in
                                                 b["workloads"]}
    reported = {n: {"setup_s", "peak_hbm_gb"} for n in cells}
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25 and UNIT.match(m["unit"])
        for n in m.get("workloads", cells):
            reported[n].add(m["name"])
    for m in b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["workloads"], m["name"]
        for n in m["workloads"]:
            assert m["moves"] in reported[n], (m["name"], n)
    layers = {m["layer"] for m in b["per_layer"]}
    assert all(1 <= len(x) <= 200 and "\n" not in x for x in layers)
    for n in cells:
        assert len(reported[n]) >= 3
        assert any(n in m["workloads"] for m in b["per_layer"])
    assert len(json.dumps(b)) <= 64 * 1024


def test_files_under_paths_are_named_from_name_characters():
    for p in (catalog.HERE).rglob("*"):
        if "__pycache__" in p.parts or p.is_dir():
            continue
        rel = p.relative_to(catalog.ROOT).as_posix()
        assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        catalog.cell("no_such.path")
    with pytest.raises(KeyError):
        catalog.metric("no_such_metric")


@pytest.fixture
def copied(tmp_path, monkeypatch):
    """A copy of the benchmark's data files, standing in for ``bench/``."""
    for sub in ("configs", "traffic", "metrics", "generators", "penalties"):
        shutil.copytree(catalog.HERE / sub, tmp_path / sub)
    shutil.copy(catalog.HERE / "suite.json", tmp_path / "suite.json")
    monkeypatch.setattr(catalog, "HERE", tmp_path)
    return tmp_path


def test_new_files_are_found_by_name(copied):
    cfg = json.loads((copied / "configs" / "imgdict_dpc.json").read_text())
    (copied / "configs" / "new_dict.json").write_text(json.dumps(
        dict(cfg, why="a new dictionary")))
    (copied / "traffic" / "new_dict.path.json").write_text(json.dumps({
        "config": "new_dict", "task": "path", "chips": 1,
        "warmup_responses": 1, "window_responses": 2,
        "plan": {"n_lambdas": 8, "min_ratio": 0.5, "tol": 1e-5},
        "gap_limit": 2.0, "why": "a new cell"}))
    (copied / "metrics" / "new_metric.path.py").write_text(
        'LAYER = "device"\nUNIT, BETTER, SOURCE = "s", "lower", '
        '"device_trace"\nMOVES, TASK = "path_s", "path"\n\n\n'
        'def read(run):\n    return 1.0\n')
    assert "new_dict.path" in catalog.cell_names()
    c = catalog.cell("new_dict.path")
    assert c.config["why"] == "a new dictionary" and c.task == "path"
    mod = catalog.metric("new_metric.path")
    assert mod.read(None) == 1.0 and catalog.applies(mod, c)
    b = catalog.benchmark()
    assert "new_dict" in {x["name"] for x in b["configs"]}
    path_s = next(m for m in b["end_to_end"] if m["name"] == "path_s")
    assert "new_dict.path" in path_s["workloads"]
    nm = next(m for m in b["per_layer"] if m["name"] == "new_metric.path")
    assert "new_dict.path" in nm["workloads"]
    # the nn-only kernel metric follows the new cell's penalty
    dpc = next(m for m in b["per_layer"]
               if m["name"] == "dpc_screen_roofline.path")
    assert "new_dict.path" in dpc["workloads"]
    assert "gwas_adni.path" not in dpc["workloads"]


def test_new_generator_and_penalty_files_run_a_cell(copied):
    """A configuration with a data generator and a penalty of its own runs
    from files alone: here copies of the dictionary's, under new names."""
    from bench import run
    from bench.tests.conftest import OUT_OF_BENCHMARK, TINY
    for kind, old, new in (("generators", "image_dictionary", "new_gen"),
                           ("penalties", "nn_lasso", "new_pen")):
        (copied / kind / f"{new}.py").write_text(
            (copied / kind / f"{old}.py").read_text())
    cfg = json.loads((copied / "configs" / "imgdict_dpc.json").read_text())
    (copied / "configs" / "new_cfg.json").write_text(json.dumps(
        dict(cfg, **TINY["imgdict_dpc"], generator="new_gen",
             penalty="new_pen")))
    tr = json.loads(OUT_OF_BENCHMARK["imgdict_dpc.path"].read_text())
    (copied / "traffic" / "new_cfg.path.json").write_text(json.dumps(
        dict(tr, config="new_cfg", warmup_responses=1, window_responses=2)))
    c = catalog.cell("new_cfg.path")
    assert catalog.penalty(c.penalty).NAME == "new_pen"
    assert "new_gen" in catalog.generator_names()
    res = run.run_cell(c, 3, 0.05, require_tpu=False)
    assert res["correct"] and res["attempted"] >= 1, res["checks"]
