"""The image-dictionary path cell: its traffic file is in the benchmark,
``BENCHMARK.json`` renders it after the accepted entries, and a tiny copy
of it runs end to end and certifies every row at its own limit."""
import json

import pytest

from bench import catalog, run
from bench.tests.conftest import TINY

CELL = "imgdict_dpc.path"


def _tiny():
    c = catalog.cell(CELL)
    return catalog.Cell(name=CELL,
                        traffic=dict(c.traffic, warmup_responses=1,
                                     warmup_max=1, window_responses=2),
                        config=dict(c.config, **TINY["imgdict_dpc"]))


def test_the_cell_is_in_the_rendered_benchmark():
    assert CELL in catalog.cell_names()
    assert (catalog.ROOT / "BENCHMARK.json").read_text() == catalog.render()
    b = json.loads(catalog.render())
    (w,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert (w["config"], w["chips"]) == ("imgdict_dpc", 1)
    (cfg,) = [c for c in b["configs"] if c["name"] == "imgdict_dpc"]
    assert cfg["reduced"] == []
    path_s = next(m for m in b["end_to_end"] if m["name"] == "path_s")
    assert CELL in path_s["workloads"]
    # the cell joins every metric it reports at the end of its list, and
    # the device-trace readers, which the catalog would sort ahead of the
    # accepted metrics, stay out of it (its traffic turns them off)
    assert catalog.cell(CELL).traffic["device_trace_metrics"] is False
    for m in b["per_layer"]:
        if m["source"] == "device_trace":
            assert CELL not in m["workloads"]
    for m in b["end_to_end"] + b["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL


@pytest.mark.parametrize("device_trace", [False, True])
def test_a_tiny_copy_runs_and_certifies_at_the_cells_limit(device_trace):
    c = _tiny()
    assert c.traffic["gap_limit"] == 1.05
    c = catalog.Cell(name=c.name, config=c.config,
                     traffic=dict(c.traffic,
                                  device_trace_metrics=device_trace))
    res = run.run_cell(c, 2**31 + 15, 0.05, trace=True, require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["n_uncertified"]["value"] == 0
    assert res["checks"]["worst_gap_over_tol"]["value"] <= 1.05
    m = res["metrics"]
    assert 0 < m["window_row_accept_share.path"]["value"] <= 100
    assert m["fista_iters.path"]["value"] > 0
    assert 0 <= res["device"]["busy_s"] <= res["device"]["window_s"]
    if device_trace:
        # the readers still read this cell's trace when turned on
        assert 0 <= m["device_idle_share.path"]["value"] <= 100
    else:
        assert "device_idle_share.path" not in m
