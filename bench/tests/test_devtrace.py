"""The trace reduction on recorded intervals with known answers."""

import pytest

import devtrace
import peaks
from devtrace import Event

DEV = "/device:TPU:0"


def op(start, dur, name="fusion"):
    return Event(DEV, devtrace.OPS_LINE, name, start, dur)


def trace():
    # window [1000, 11000) ns; ops overlap, one runs past the window's end
    return [
        Event("/host:CPU", "python", "bench_window", 1000, 10000),
        op(500, 1000),           # clipped to [1000, 1500)
        op(1200, 300, "mixer"),  # nested in the one before
        op(4000, 1000, "mixer"),
        op(5000, 500),           # busy [4000, 5500)
        op(10500, 2000),         # clipped to [10500, 11000)
        Event(DEV, devtrace.MODULES_LINE, "jit_run(7)", 1000, 4500),
        Event(DEV, devtrace.MODULES_LINE, "jit_scan(3)", 6000, 100),
    ]


def test_busy_is_the_union_of_ops_inside_the_window():
    ev = trace()
    w = devtrace.host_event(ev, "bench_window")
    assert devtrace.busy_s(ev, w.start_ns, w.end_ns) == pytest.approx(
        (500 + 1500 + 500) / 1e9)


def test_idle_gaps_are_named_by_the_innermost_open_span():
    ev = trace()
    spans = [("solve", 0, 20000), ("refine", 5500, 10500)]
    gaps = dict(devtrace.idle_gaps(ev, 1000, 11000, spans))
    # gaps: [1500, 4000) in solve only; [5500, 10500) in refine
    assert gaps == pytest.approx({"solve": 2500 / 1e9, "refine": 5000 / 1e9})


def test_module_time_counts_only_that_program():
    assert devtrace.module_s(trace(), "jit_run", 1000, 11000) == (
        pytest.approx(4500 / 1e9), 1)


def test_top_ops_sum_clipped_time_by_name():
    top = devtrace.top_ops(trace(), 1000, 11000)
    assert top[0][0] == "mixer"
    assert dict(top) == pytest.approx({"mixer": 1300 / 1e9,
                                       "fusion": 1200 / 1e9})


def test_pool_roofline_from_a_recorded_module_time():
    # 819e3 bytes over 819e9 B/s is 1 us: 50% of a 2 us program
    share, side = peaks.roofline_share(1.0, 819e3, 2e-6, "TPU v5 lite")
    assert side == "memory" and share == pytest.approx(50.0)


def test_unknown_device_has_no_roofline():
    with pytest.raises(ValueError):
        peaks.peaks("cpu")


def test_nested_ops_count_only_their_own_time():
    loop = op(0, 1000, "while")
    body = [op(100, 300, "mixer"), op(500, 200, "mixer")]
    own = {e.name: t for e, t in devtrace.self_times([loop] + body)
           if e.name == "while"}
    assert own == {"while": 500}
    top = dict(devtrace.top_ops([loop] + body, 0, 1000))
    assert top == pytest.approx({"while": 500 / 1e9, "mixer": 500 / 1e9})
