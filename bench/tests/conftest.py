"""Tiny copies of the benchmark's cells for CPU tests: the same traffic
files and configurations, with the shapes cut so a run takes a second.

The traffic files beside this file are the image-dictionary cells that are
out of the benchmark while the program leaves rows uncertified (PERF.md,
Open questions): the nonnegative-Lasso path and its K-fold CV.  The tests
keep their code paths honest."""
import json
from pathlib import Path

import pytest

from bench import catalog

OUT_OF_BENCHMARK = {p.stem: p for p in Path(__file__).parent.glob("*.json")}

TINY = {"gwas_adni": dict(n_samples=60, n_features=400, n_groups=90),
        "imgdict_dpc": dict(n_samples=64, n_features=300)}


def tiny_cell(name: str, warmup: int = 1, window: int = 2) -> catalog.Cell:
    if name in OUT_OF_BENCHMARK:
        tr = json.loads(OUT_OF_BENCHMARK[name].read_text())
        c = catalog.Cell(name=name, traffic=tr,
                         config=catalog.config(tr["config"]))
    else:
        c = catalog.cell(name)
    return catalog.Cell(name=name,
                        traffic=dict(c.traffic, warmup_responses=warmup,
                                     warmup_max=warmup,
                                     window_responses=window),
                        config=dict(c.config, **TINY[c.traffic["config"]]))


@pytest.fixture(params=catalog.cell_names() + sorted(OUT_OF_BENCHMARK))
def cell_name(request):
    return request.param
