"""``correct`` on the CPU at tiny sizes: sound runs pass; the lower-precision
control and each fault a cell can have, planted under the timed path, fail.
The harness's look for a chip is skipped; the rest of a run is driven."""
import dataclasses

import numpy as np
import pytest

from bench import run
from bench.tests.conftest import tiny_cell
from repro.core import SGLSession

SEED = 2**31 + 17


def _run(name, **kw):
    return run.run_cell(tiny_cell(name), SEED, 0.05, require_tpu=False,
                        **kw)


def test_sound_run_is_correct(cell_name):
    res = _run(cell_name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"


def test_bf16_control_is_not_correct(cell_name):
    res = _run(cell_name, control="bf16")
    assert not res["correct"]
    gap = res["checks"]["worst_gap_over_tol"]
    assert gap["value"] > gap["limit"]


def _alter(result, how):
    """A copy of a PathResult / CVResult with its answers broken."""
    field = "betas" if hasattr(result, "betas") else "fold_betas"
    B = np.array(getattr(result, field))
    if how == "answer_altered":
        idx = np.unravel_index(np.argmax(np.abs(B)), B.shape)
        B[idx] *= 1.5
    elif how == "state_unchanged":
        B[:] = 0.0                       # every row left at its start
    out = dataclasses.replace(result, **{field: B})
    if how == "uncertified":
        out.stats = dataclasses.replace(result.stats, n_uncertified=1)
    return out


@pytest.mark.parametrize("how", ["answer_altered", "state_unchanged",
                                 "uncertified"])
def test_fault_under_the_timed_path_is_not_correct(cell_name, how,
                                                   monkeypatch):
    verb = "path" if cell_name.endswith(".path") else "cv"
    real = getattr(SGLSession, verb)

    def broken(self, *a, **k):
        return _alter(real(self, *a, **k), how)

    monkeypatch.setattr(SGLSession, verb, broken)
    res = _run(cell_name)
    assert not res["correct"], (how, res["checks"])
    assert res["failed"] >= 1


def test_no_chip_means_no_result(capsys):
    with pytest.raises(run.NoChip):
        run.run_cell(tiny_cell("imgdict_dpc.path"), 0, 0.05)


def test_cli_without_a_chip_exits_nonzero_and_prints_nothing(capsys,
                                                             monkeypatch):
    monkeypatch.setattr(run, "_prepare_jax", lambda: None)
    rc = run.main(["--workload", "gwas_adni.path", "--seed", "1",
                   "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_cli_refuses_an_unknown_workload(capsys, monkeypatch):
    monkeypatch.setattr(run, "_prepare_jax", lambda: None)
    assert run.main(["--workload", "nope.path", "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("misses, calls", [((3, 0, 0, 0), 2),
                                           ((3, 2, 1, 0), 4),
                                           ((3, 2, 1, 1), 4)])
def test_warm_up_runs_on_while_it_misses_the_cache(misses, calls,
                                                    monkeypatch, capsys):
    """At least ``warmup_responses`` warm-up calls, then more while the
    last one missed the compile cache, up to ``warmup_max``."""
    class Counter:
        count = hits = 0
        seconds = 0.0

        def close(self):
            pass

    counter = Counter()
    script = iter(misses)
    real = SGLSession.path

    def path(self, *a, **k):
        counter.count += next(script, 0)
        return real(self, *a, **k)

    monkeypatch.setattr(run, "CompileCounter", lambda: counter)
    monkeypatch.setattr(SGLSession, "path", path)
    c = tiny_cell("gwas_adni.path")
    c = dataclasses.replace(c, traffic=dict(c.traffic, warmup_responses=2,
                                            warmup_max=4))
    assert run.run_cell(c, SEED, 0.05, require_tpu=False)["correct"]
    assert capsys.readouterr().err.count("warm-up call") == calls
