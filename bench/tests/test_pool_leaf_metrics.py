"""The readers of the pool's placing and fetching spans, on a fake run: the
mean of the matching timing over the window's solves, and None where the
program reports no such timing (a program without the spans)."""

import types

import pytest

import run

METRICS = ("pool_place_s", "pool_fetch_s")


def fake_run(timings):
    answers = [types.SimpleNamespace(timings=t) for t in timings]
    return types.SimpleNamespace(answers=answers)


@pytest.mark.parametrize("metric", METRICS)
def test_reader_means_the_timing_over_solves(metric):
    read = run.reader(metric)
    got = read(fake_run([{metric: 0.5, "solve_s": 9.0},
                         {metric: 1.5, "solve_s": 9.0}]))
    assert got == pytest.approx(1.0)


@pytest.mark.parametrize("metric", METRICS)
def test_reader_finds_nothing_without_the_timing(metric):
    read = run.reader(metric)
    assert read(fake_run([{"pool_pack_s": 1.0, "solve_s": 2.0}])) is None
    assert read(fake_run([])) is None
