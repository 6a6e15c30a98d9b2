"""The trace reduction on small made-up traces."""
import pytest

from bench.trace_reduce import Op, reduce_events


def _ev(name, s, e, module="m", text=""):
    return (module, name, s, e, text)


def test_busy_is_the_union_clipped_to_the_window():
    dev = [[_ev("%a", 0, 40), _ev("%b", 30, 60), _ev("%c", 80, 90),
            _ev("%d", 95, 130)]]
    s = reduce_events(dev, [], (10, 100))
    assert s.window_s == pytest.approx(90e-9)
    # covered: [10, 60) + [80, 90) + [95, 100) = 65 ns
    assert s.busy_s == pytest.approx(65e-9)


def test_busy_is_averaged_over_chips():
    dev = [[_ev("%a", 0, 100)], [_ev("%a", 0, 50)]]
    assert reduce_events(dev, [], (0, 100)).busy_s == pytest.approx(75e-9)


def test_nested_ops_count_self_time():
    # a loop [0, 100) holding two body ops, one of which holds another
    dev = [[_ev("%while.1", 0, 100), _ev("%fusion.2", 10, 30),
            _ev("%fusion.3", 40, 80), _ev("%xtv.4", 50, 70),
            _ev("%fusion.2", 85, 95)]]
    s = reduce_events(dev, [], (0, 100))
    self_ns = {o.name: o.seconds * 1e9 for o in s.ops}
    assert self_ns == pytest.approx({"%while.1": 30, "%fusion.2": 30,
                                     "%fusion.3": 20, "%xtv.4": 20})
    assert {o.name: o.count for o in s.ops}["%fusion.2"] == 2
    assert sum(self_ns.values()) == pytest.approx(s.busy_s * 1e9)


def test_ops_are_keyed_by_module_and_keep_the_first_text():
    dev = [[_ev("%k.1", 0, 10, "jit_a", "f32[4] first"),
            _ev("%k.1", 20, 25, "jit_a", None),
            _ev("%k.1", 30, 40, "jit_b", "f32[8] other")]]
    s = reduce_events(dev, [], (0, 100))
    by_key = {(o.module, o.name): o for o in s.ops}
    assert by_key[("jit_a", "%k.1")].count == 2
    assert by_key[("jit_a", "%k.1")].text == "f32[4] first"
    assert s.breakdown()["device_ops"][0] == ["jit_a:%k.1",
                                              pytest.approx(15e-9)]


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    dev = [[_ev("%a", 0, 10), _ev("%b", 50, 60)]]
    host = [("bench.window", 0, 100), ("bench.call", 0, 100),
            ("bench.harvest", 60, 100)]
    s = reduce_events(dev, host, (0, 100))
    assert [g[0] for g in s.gaps] == ["bench.call", "bench.harvest"]
    assert [g[1] for g in s.gaps] == [pytest.approx(40e-9),
                                      pytest.approx(40e-9)]


def test_base_names_and_shapes_come_from_the_hlo_text():
    op = Op("jit_sweep", "%xtv.12", 1, 1.0,
            "f32[1,11553]{1,0} custom-call(f32[1024,11553]{1,0} %x, "
            "f32[1,1024]{1,0} %v), custom_call_target=\"tpu_custom_call\"")
    assert op.base == "xtv"
    assert op.shapes() == [("f32", (1, 11553)), ("f32", (1024, 11553)),
                           ("f32", (1, 1024))]
    s = reduce_events([[("jit_sweep", "%xtv.12", 0, 5, op.text),
                        ("jit_sweep", "%dot.1", 5, 9, "")]], [], (0, 10))
    assert [o.name for o in s.matching(("xtv",))] == ["%xtv.12"]
