"""The engine's spans and counters (``core.spans``): the tree a path
records, the timers and counts taken from it, compile attribution, the
bounded history of calls, and the clock it shares with the profiler."""
import glob

import numpy as np
import pytest
import jax

from repro.core import GroupSpec, Plan, Problem, SGLSession, linalg, spans
from repro.core.path_engine import nn_lasso_path_batched, sgl_path_batched

SEGMENT_PARTS = ("segment.screen", "segment.expand", "segment.gather",
                 "segment.sweep", "segment.assemble")


def _data(seed=3, N=40, G=30, n=4):
    rng = np.random.default_rng(seed)
    p = G * n
    X = rng.standard_normal((N, p))
    beta = np.zeros(p)
    beta[rng.choice(p, 6, replace=False)] = rng.standard_normal(6)
    y = X @ beta + 0.05 * rng.standard_normal(N)
    return X, y, GroupSpec.uniform_groups(G, n)


def _sgl(**kw):
    X, y, spec = _data(**kw)
    return X, sgl_path_batched(X, y, spec, 1.0, n_lambdas=12,
                               min_ratio=0.1, tol=1e-8)


def _nn():
    X, _, _ = _data()
    X = np.abs(X)
    y = X[:, :5] @ np.ones(5)
    return X, nn_lasso_path_batched(X, y, n_lambdas=12, min_ratio=0.1,
                                    tol=1e-8)


def _children(rec, i):
    return [s for s in rec if s.parent == i]


@pytest.mark.parametrize("engine", ["sgl", "nn"])
def test_a_path_records_one_tree(engine):
    _, res = _sgl() if engine == "sgl" else _nn()
    rec = res.spans
    assert rec[0].name == "path" and rec[0].parent is None
    assert {s.call for s in rec} == {rec[0].call}
    for s in rec[1:]:
        parent = rec[s.parent]
        assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns
    top = [s.name for s in _children(rec, 0)]
    assert top[:2] == ["setup", "host_copy"]
    assert set(top[2:]) == {"segment"}
    assert len(top) - 2 == res.stats.n_segments
    setup = [s.name for s in _children(rec, 1)]
    assert setup == (["setup.xty", "setup.col_norms", "setup.group_norms"]
                     if engine == "sgl" else ["setup.xty", "setup.col_norms"])
    for i, s in enumerate(rec):
        if s.name == "segment":
            assert tuple(c.name for c in _children(rec, i)) == SEGMENT_PARTS


@pytest.mark.parametrize("engine", ["sgl", "nn"])
def test_timers_are_span_sums(engine):
    _, res = _sgl() if engine == "sgl" else _nn()
    rec = res.spans
    assert res.setup_time == spans.total(rec, "setup")
    assert res.screen_time == spans.total(rec, "segment.screen")
    assert res.solve_time == spans.total(rec, "segment.gather",
                                         "segment.sweep")
    parts = spans.total(rec, "setup.xty", "setup.col_norms",
                        "setup.group_norms")
    assert 0 < parts <= res.setup_time


@pytest.mark.parametrize("engine", ["sgl", "nn"])
def test_sweep_and_transfer_counters(engine):
    X, res = _sgl() if engine == "sgl" else _nn()
    N, p = X.shape
    rec, stats = res.spans, res.stats
    sweeps = [s.counters for s in rec if s.name == "segment.sweep"]
    assert sum(c["rows_solved"] - c["rows_accepted"] for c in sweeps) \
        == stats.n_rejected
    assert [(c["rows_solved"], c["rows_accepted"]) for c in sweeps] == \
        [(m, k) for _, _, m, k in stats.buckets]
    gathers = [s.counters.get("h2d_bytes", 0) for s in rec
               if s.name == "segment.gather"]
    item = X.dtype.itemsize
    assert gathers == [N * p_b * item if p_b < p else 0
                       for p_b, _, _, _ in stats.buckets]
    screens = [s.counters["d2h_bytes"] for s in rec
               if s.name == "segment.screen"]
    assert len(screens) == stats.n_screens and min(screens) >= p
    for s in rec:
        if s.name == "segment.assemble":
            assert s.counters == {"h2d_bytes": p * item}


@pytest.mark.parametrize("case", ["ragged", "frobenius", "wide"])
def test_group_norms_count_the_gram_groups(case):
    rng = np.random.default_rng(5)
    sizes = rng.integers(1, 9, 25)
    sizes[::4] = 1
    if case == "wide":                     # one group above GRAM_MAX_SIZE
        sizes[3] = linalg.GRAM_MAX_SIZE + 2
    spec = GroupSpec.from_sizes(sizes)
    X = rng.standard_normal((400, spec.num_features))
    y = X[:, :4] @ np.ones(4) + 0.05 * rng.standard_normal(400)
    res = sgl_path_batched(
        X, y, spec, 1.0, n_lambdas=6, min_ratio=0.3, tol=1e-8,
        specnorm_method="frobenius" if case == "frobenius" else "power")
    (norms,) = [s for s in res.spans if s.name == "setup.group_norms"]
    # only the wide group runs the per-group iteration
    n_gram = {"ragged": spec.num_groups, "frobenius": 0,
              "wide": spec.num_groups - 1}[case]
    assert norms.counters == {"gram_groups": n_gram}


def test_compiles_land_on_the_sweep_of_a_fresh_shape():
    # a shape no other test compiles: the first path compiles its sweeps,
    # a second one on the same shapes compiles nothing
    X, first = _sgl(N=37, G=29, n=3)
    _, again = _sgl(N=37, G=29, n=3)
    assert first.stats.buckets == again.stats.buckets
    sweeps = [s for s in first.spans if s.name == "segment.sweep"]
    assert sum(s.compiles for s in sweeps) > 0
    assert all(s.compile_s > 0 for s in sweeps if s.compiles)
    assert sum(s.compiles for s in again.spans
               if s.name in ("segment.gather", "segment.sweep")) == 0


def test_a_session_holds_no_record_and_the_history_is_bounded():
    X, y, _ = _data(N=30, G=10, n=4)
    sess = SGLSession(Problem.sgl(X, y, groups=[4] * 10))
    (init,) = spans.history()[-1]
    assert init.name == "session.init" and init.parent is None
    assert not hasattr(sess, "spans")
    plan = Plan(alpha=1.0, n_lambdas=6, min_ratio=0.3, tol=1e-8, n_folds=3)
    paths = []
    for verb in ("path", "cv", "refine", "stability", "path"):
        if verb == "refine":
            sess.refine(factor=4)
        elif verb == "stability":
            sess.stability(plan.with_(n_subsamples=4, batch_size=4))
        else:
            out = getattr(sess, verb)(plan)
            if verb == "path":
                # a path's record is its own call, also the newest history
                assert out.spans[0].name == "path"
                assert len({s.call for s in out.spans}) == 1
                assert spans.history()[-1] == out.spans
                paths.append(out.spans)
    assert len(paths[0]) == len(paths[1])
    for _ in range(spans.HISTORY):
        with spans.span("filler"):
            pass
    assert len(spans.history()) == spans.HISTORY


def test_fold_engine_timers_are_span_sums():
    X, y, _ = _data(N=30, G=10, n=4)
    sess = SGLSession(Problem.sgl(X, y, groups=[4] * 10))
    last = spans.history()[-1][0].call
    cv = sess.cv(Plan(alpha=1.0, n_lambdas=6, min_ratio=0.3, tol=1e-8,
                      n_folds=3))
    # each fold-engine span is a top-level call of its own
    rec = [s for call in spans.history() if call[0].call > last
           for s in call]
    assert {s.name for s in rec} == {"fold.setup", "fold.screen",
                                     "fold.solve"}
    assert cv.setup_time == spans.total(rec, "fold.setup")
    assert cv.screen_time == pytest.approx(spans.total(rec, "fold.screen"))
    assert cv.solve_time == pytest.approx(spans.total(rec, "fold.solve"))
    assert cv.solve_time > 0


def test_counters_and_history_outside_any_span():
    spans.add("h2d_bytes", 10)                    # no open span: no-op
    with spans.span("outer") as outer:
        spans.add("n", 2)
        with spans.span("inner") as inner:
            spans.add("n", 3)
            with spans.span("leaf"):
                pass
        nested = spans.record(inner)           # rebased onto ``inner``
        spans.add("n", 4)
    assert [(s.name, s.parent) for s in nested] == \
        [("inner", None), ("leaf", 0)]
    rec = spans.record(outer)
    assert [(s.name, s.parent, s.counters) for s in rec] == \
        [("outer", None, {"n": 6}), ("inner", 0, {"n": 3}),
         ("leaf", 1, {})]
    assert spans.history()[-1] == rec
    assert len(spans.history()) <= spans.HISTORY


def test_spans_share_the_profilers_clock(tmp_path):
    from jax.profiler import ProfileData
    X, y, spec = _data(N=31, G=11, n=3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = sgl_path_batched(X, y, spec, 1.0, n_lambdas=8,
                               min_ratio=0.2, tol=1e-8)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    names = {s.name for s in res.spans}
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    events.setdefault(e.name, []).append(e.start_ns)
    offsets = []
    for name in names:
        mine = sorted(s.start_ns for s in res.spans if s.name == name)
        theirs = sorted(events.get(name, []))
        assert len(theirs) == len(mine), name
        offsets += [t - m for t, m in zip(theirs, mine)]
    assert max(offsets) - min(offsets) < 1e6     # ns
