"""The ``window_*`` readers of the program's spans and counters, on made-up
span lists, on a program that keeps none, and on a tiny traced run."""
import collections
import sys
import warnings

import numpy as np
import pytest

from bench import catalog, run
from bench.tests.conftest import tiny_cell
from repro.core import spans as program_spans
from repro.core.spans import Span

MS = 1_000_000


def _lists(xty_ms):
    """One window call's span lists: session.init, then the path."""
    out, t = [], 0

    def s(name, parent, ms, **kw):
        nonlocal t
        out.append(Span(name, 7, parent, t, t + int(ms * MS), **kw))
        t += int(ms * MS)

    s("path", None, 2000)
    s("setup", 0, 1000)
    s("setup.xty", 1, xty_ms)
    s("setup.col_norms", 1, 50)
    s("setup.group_norms", 1, 600)
    s("setup.spectral_norm", 1, 200)
    s("host_copy", 0, 50, counters={"d2h_bytes": 1_000_000})
    s("segment", 0, 700)
    s("segment.screen", 7, 100, counters={"d2h_bytes": 2_000_000})
    s("segment.expand", 7, 20, counters={"d2h_bytes": 300_000})
    s("segment.gather", 7, 200, counters={"h2d_bytes": 4_000_000},
      compiles=2, compile_s=0.15)
    s("segment.sweep", 7, 300, compiles=1, compile_s=0.05,
      counters={"h2d_bytes": 1_000, "d2h_bytes": 5_000,
                "rows_solved": 8, "rows_accepted": 6})
    s("segment.assemble", 7, 10, counters={"h2d_bytes": 1_000})
    init = [Span("session.init", 6, None, 0, 100 * MS)]
    return [init, out]


def _unit(wall):
    return run.Unit(resp=0, wall=wall, setup=1.0, screen=0.1, solve=0.5,
                    iters=90, kept=np.zeros(3), uncertified=0,
                    lambdas=np.ones(3), betas=np.zeros((3, 10)))


def _run_data():
    return run.RunData(cell=tiny_cell("gwas_adni.path"),
                       units=[_unit(2.5), _unit(3.5)], window_compiles=0,
                       n_features=10, device_kind="TPU v5 lite")


@pytest.fixture
def history(monkeypatch):
    """A warm-up call the readers must skip, then two window calls."""
    h = collections.deque(_lists(xty_ms=9000) + _lists(xty_ms=100) * 2)
    monkeypatch.setattr(program_spans, "_history", h)
    return h


# leaves per call: session.init 0.1 + setup.* 0.95 + host_copy 0.05 +
# segment.* 0.63 = 1.73 s of a mean wall of 3.0 s
EXPECTED = {
    "window_setup_xty_s.path": 0.1,
    "window_setup_col_norms_s.path": 0.05,
    "window_setup_group_norms_s.path": 0.6,
    "window_setup_spectral_norm_s.path": 0.2,
    "window_session_init_s.path": 0.1,
    "window_host_copy_s.path": 0.05,
    "window_segment_expand_s.path": 0.02,
    "window_segment_gather_s.path": 0.2,
    "window_segment_sweep_s.path": 0.3,
    "window_segment_assemble_s.path": 0.01,
    "window_sweep_compile_s.path": 0.2,
    "window_h2d_mb.path": 4.002,
    "window_d2h_mb.path": 3.305,
    "window_row_accept_share.path": 75.0,
    "window_unspanned_s.path": 3.0 - 1.73}
SPAN_READERS = sorted(EXPECTED)


@pytest.mark.parametrize("name, value", sorted(EXPECTED.items()))
def test_span_and_counter_readers(history, name, value):
    assert catalog.metric(name).read(_run_data()) == pytest.approx(value)


def test_every_span_reader_is_tested():
    readers = [n for n in catalog.metric_names()
               if "program_spans" in (catalog.HERE / "metrics"
                                      / f"{n}.py").read_text()]
    assert sorted(readers) == SPAN_READERS


def test_span_readers_read_nothing_without_the_programs_spans(
        monkeypatch, history):
    history.clear()
    history.extend(_lists(xty_ms=100))          # one call, two expected
    for name in SPAN_READERS:
        with pytest.warns(RuntimeWarning, match="kept 2 call records"):
            assert catalog.metric(name).read(_run_data()) is None, name
    history.extend(_lists(xty_ms=100)[::-1])    # init and path swapped
    for name in SPAN_READERS:
        with pytest.warns(RuntimeWarning, match="do not pair"):
            assert catalog.metric(name).read(_run_data()) is None, name
    # a program without ``repro.core.spans``
    import repro.core
    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    history.extend(_lists(xty_ms=100))
    with warnings.catch_warnings():
        warnings.simplefilter("error")                  # and silent
        for name in SPAN_READERS:
            assert catalog.metric(name).read(_run_data()) is None, name


def test_a_traced_run_reports_the_span_readers():
    res = run.run_cell(tiny_cell("gwas_adni.path"), 2**31 + 11, 0.05,
                       trace=True, require_tpu=False)
    m = res["metrics"]
    assert res["correct"], res["checks"]
    assert set(SPAN_READERS) <= set(m)
    setup = sum(m[f"window_setup_{k}_s.path"]["value"]
                for k in ("xty", "col_norms", "group_norms",
                          "spectral_norm"))
    assert 0 < setup <= m["engine_setup_s.path"]["value"]
    assert m["window_segment_gather_s.path"]["value"] + \
        m["window_segment_sweep_s.path"]["value"] == \
        pytest.approx(m["solve_s.path"]["value"])
    assert 0 < m["window_row_accept_share.path"]["value"] <= 100
    assert m["window_unspanned_s.path"]["value"] > 0
