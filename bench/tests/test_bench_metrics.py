"""End-to-end and per-layer arithmetic on recorded window records."""
import numpy as np
import pytest

from bench import catalog, run
from bench.tests.conftest import tiny_cell
from bench.trace_reduce import reduce_events


def _unit(wall, setup, screen, solve, iters, kept):
    return run.Unit(resp=0, wall=wall, setup=setup, screen=screen,
                    solve=solve, iters=iters, kept=np.asarray(kept),
                    uncertified=0, lambdas=np.ones(len(kept)),
                    betas=np.zeros((len(kept), 10)))


UNITS = [_unit(2.0, 0.5, 0.25, 1.0, 100, [0, 5, 10]),
         _unit(3.0, 0.5, 0.75, 1.5, 300, [0, 0, 10])]


@pytest.mark.parametrize("task", ["path", "cv"])
def test_per_call_time_is_window_over_calls(task):
    cell = tiny_cell({"path": "imgdict_dpc.path",
                      "cv": "imgdict_dpc.cv"}[task])
    out = run._end_to_end(cell, UNITS, 7.5, 12.0, 2_500_000_000)
    assert out[f"{task}_s"] == {"value": 3.75, "unit": "s"}
    assert out["setup_s"]["value"] == 12.0
    assert out["peak_hbm_gb"]["value"] == 2.5


def _run_data(trace=None, cell="imgdict_dpc.path"):
    return run.RunData(cell=tiny_cell(cell), units=UNITS,
                       window_compiles=0, n_features=10,
                       device_kind="TPU v5 lite", trace=trace,
                       window_compile_s=1.0)


@pytest.mark.parametrize("name, value", [
    ("engine_setup_s.path", 0.5), ("screen_s.path", 0.5),
    ("solve_s.path", 1.25), ("host_other_s.path", 0.25),
    ("fista_iters.path", 200.0), ("window_compiles.path", 0),
    ("window_compile_s.path", 0.5),
    # kept 0, 5, 10, 0, 0, 10 of p = 10: rejections 1, .5, 0, 1, 1, 0
    ("rejection_ratio.path", 100.0 * 3.5 / 6)])
def test_span_and_counter_readers(name, value):
    assert catalog.metric(name).read(_run_data()) == pytest.approx(value)


def test_trace_readers_read_nothing_without_a_trace():
    for name in catalog.metric_names():
        mod = catalog.metric(name)
        if mod.SOURCE == "device_trace":
            assert mod.read(_run_data()) is None, name


def test_kernel_roofline_from_a_recorded_trace():
    N, p = 4, 10
    xtv_bytes = 4 * (N * p + N + p)        # 216 B a call
    secs = xtv_bytes / 819e9               # at exactly the roofline
    ns = secs * 1e9
    text = f"f32[1,{p}] custom-call(f32[{N},{p}] %x, f32[1,{N}] %v)"
    dev = [[("m", "%xtv.1", 0, ns, text),
            ("m", "%xtv.1", 10 * ns, 12 * ns, None)]]
    trace = reduce_events(dev, [("bench.window", 0, 20 * ns)],
                          (0, 20 * ns))
    share = catalog.metric("certify_roofline.path").read(_run_data(trace))
    # two calls, 3 x the least time in all
    assert share == pytest.approx(100.0 * 2 / 3)
    idle = catalog.metric("device_idle_share.path").read(_run_data(trace))
    assert idle == pytest.approx(100.0 * (1 - 3 / 20))


def test_dpc_screen_roofline_takes_the_stack_shape_from_stats():
    K, L, p = 1, 8, 16
    nbytes = 4 * (K * L * p + K * L + K * p) + K * L * p
    ns = nbytes / 819e9 * 1e9
    text = (f"f32[{K},{L},{p}] custom-call(f32[{K},{L},{p}] %c, "
            f"f32[{K},{L},1] %r, f32[{K},1,{p}] %n)")
    dev = [[("m", "%dpc_screen_folds.3", 0, 4 * ns, text)]]
    trace = reduce_events(dev, [], (0, 8 * ns))
    share = catalog.metric("dpc_screen_roofline.path").read(
        _run_data(trace))
    assert share == pytest.approx(25.0)
