"""Leaf spans of a solve, compile billing and the profiler's host plane
(DESIGN.md §8).

A solve under a recording tracer splits the two stages that mix host and
device work into leaf spans (`pool_pack` / `pool_run` under `solve_pool`,
`merge_plan` / `merge_scan` under `merge`), bills every JAX compile phase
to the span open when it ran, and, on the real clock, annotates each
stack-scoped span on the profiler's host plane.
"""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import ParaQAOAConfig, solve
from repro.core.graph import Graph
from repro.launch import solve_maxcut
from repro.obs import trace as trace_mod
from repro.obs import validate
from repro.obs.trace import Tracer, use_tracer

CFG = ParaQAOAConfig(n_qubits=7, top_k=2, p_layers=2, opt_steps=3)
LEAVES = {"pool_pack": "solve_pool", "pool_run": "solve_pool",
          "merge_plan": "merge", "merge_scan": "merge", "rescore": "solve"}


def _graph():
    return Graph.erdos_renyi(30, 0.3, seed=4)


def _traced_solve(record=True):
    tr = Tracer(record=record)
    with use_tracer(tr):
        out = solve(_graph(), CFG)
    return tr, out


def _host_event_names(profile_dir) -> set:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(
        str(profile_dir), "plugins", "profile", "*", "*.xplane.pb"))
    assert len(files) == 1, files
    data = ProfileData.from_file(files[0])
    return {ev.name for plane in data.planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events}


def test_solve_leaf_spans_nest_and_sum_to_their_stage():
    tr, out = _traced_solve()
    by_id = {s.span_id: s for s in tr.spans}
    for leaf, stage in LEAVES.items():
        found = [s for s in tr.spans if s.name == leaf]
        assert len(found) == 1, (leaf, len(found))
        assert by_id[found[0].parent_id].name == stage
    t = out.timings
    for key in ("partition_s", "solve_s", "merge_s", "refine_s", "total_s",
                "pool_pack_s", "merge_plan_s", "merge_scan_s", "compile_s"):
        assert key in t, key
    pool_run = next(s for s in tr.spans if s.name == "pool_run").duration_s
    assert t["pool_pack_s"] + pool_run == pytest.approx(t["solve_s"],
                                                        rel=0.01)
    assert t["merge_plan_s"] + t["merge_scan_s"] == pytest.approx(
        t["merge_s"], rel=0.01)
    assert t["pool_pack_s"] <= t["solve_s"]
    assert 0.0 <= t["compile_s"] <= t["total_s"]
    assert set(out.compiles) >= {"solve", "solve_pool", "merge"}
    assert validate.validate_trace_records(
        [s.as_dict() for s in tr.spans]) == []


def test_pool_place_and_fetch_are_leaves_of_pack_and_run():
    """`pool_place` (the packed batch onto the device, through the
    transfer's end) nests in `pool_pack`, `pool_fetch` (the host copy of
    the candidates, once the device result is ready) in `pool_run`; the
    `solve_pool` span names the devices and the padding rows."""
    tr, out = _traced_solve()
    by_id = {s.span_id: s for s in tr.spans}
    for leaf, parent in (("pool_place", "pool_pack"),
                         ("pool_fetch", "pool_run")):
        found = [s for s in tr.spans if s.name == leaf]
        assert len(found) == 1, (leaf, len(found))
        assert by_id[found[0].parent_id].name == parent
        assert out.timings[leaf + "_s"] == found[0].duration_s
    assert out.timings["pool_place_s"] <= out.timings["pool_pack_s"]
    pool = next(s for s in tr.spans if s.name == "solve_pool")
    assert pool.attrs["shards"] == 1 and pool.attrs["pad"] == 0


def test_partition_span_carries_the_partition_counts():
    """`_build_partition` annotates the open `partition` span with its
    subgraphs, their padded edge count and the edges left to the merge."""
    from repro.core.partition import partition_for_solver

    tr, _ = _traced_solve()
    (span,) = [s for s in tr.spans if s.name == "partition"]
    part = partition_for_solver(_graph(), CFG.n_qubits)
    assert span.attrs["m"] == part.m > 1
    assert span.attrs["e_pad"] == part.subgraphs[0].edges.shape[0]
    assert span.attrs["inter_edges"] == part.inter_edges.shape[0] > 0


def test_untraced_solve_reports_the_same_timing_keys():
    _, traced = _traced_solve()
    _, plain = _traced_solve(record=False)
    assert list(plain.timings) == list(traced.timings)


def test_fresh_jit_bills_one_backend_compile_to_its_span():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    x = jnp.arange(11.0)
    tr = Tracer(record=True)
    with use_tracer(tr), tr.span("outer") as outer:
        with tr.span("stage") as stage:
            f(x).block_until_ready()
    compiles = [s for s in tr.spans if s.name == "compile"]
    backend = [s for s in compiles if s.attrs["phase"] == "backend_compile"]
    assert len(backend) == 1
    assert backend[0].parent_id == stage.span_id
    assert "jit" in backend[0].attrs["fun_name"]
    for s in (stage, outer):
        assert s.attrs["compiles"] >= 1
        assert 0.0 < s.attrs["compile_s"] <= s.duration_s
    assert validate.validate_trace_records(
        [s.as_dict() for s in tr.spans]) == []

    # the second call hits jit's cache: nothing to bill
    tr2 = Tracer(record=True)
    with use_tracer(tr2), tr2.span("again") as again:
        f(x).block_until_ready()
    assert [s.name for s in tr2.spans] == ["again"]
    assert "compile_s" not in again.attrs and "compiles" not in again.attrs


def test_virtual_clock_tracer_takes_no_compile_billing():
    t = [0.0]
    tr = Tracer(clock=lambda: t[0], record=True)
    f = jax.jit(lambda x: jnp.cos(x) - 2.0)
    with use_tracer(tr), tr.span("stage") as stage:
        f(jnp.arange(5.0)).block_until_ready()
    assert [s.name for s in tr.spans] == ["stage"]
    assert "compile_s" not in stage.attrs


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_nested_compile_phases_count_each_second_once(monkeypatch):
    clock = _Clock()
    # stand the test clock in for the real one, so the tracer takes billing
    monkeypatch.setattr(trace_mod, "default_clock", clock)
    tr = Tracer(clock=clock, record=True)
    with tr.span("outer") as outer:
        clock.t = 10.0
        tr.bill_compile("jaxpr_trace", "inner", 2.0)  # [8, 10]
        clock.t = 12.0
        tr.bill_compile("jaxpr_to_mlir_module", "f", 5.0)  # [7, 12]
        assert outer.attrs["compile_s"] == pytest.approx(5.0)
        clock.t = 15.0
        with tr.span("inner") as inner:
            clock.t = 20.0
            tr.bill_compile("backend_compile", "f", 3.0)  # [17, 20]
            clock.t = 22.0
            tr.bill_compile("backend_compile", "g", 10.0)  # [12, 22]
            clock.t = 23.0
        clock.t = 30.0
    # union [7, 22] = 15 s for outer; inner from its start at 15: 7 s
    assert outer.attrs["compile_s"] == pytest.approx(15.0)
    assert inner.attrs["compile_s"] == pytest.approx(7.0)
    assert outer.attrs["compiles"] == inner.attrs["compiles"] == 2
    compiles = [s for s in tr.spans if s.name == "compile"]
    assert len(compiles) == 4
    # the retroactive child never starts before its parent
    assert validate.validate_trace_records(
        [s.as_dict() for s in tr.spans]) == []


@pytest.mark.parametrize("record", [True, False])
def test_profiler_host_plane_holds_the_spans_only_when_recording(
        tmp_path, record):
    _traced_solve(record=record)  # compile outside the profile
    with jax.profiler.trace(str(tmp_path)):
        _traced_solve(record=record)
    names = _host_event_names(tmp_path)
    wanted = {"partition", "merge_plan", "merge_scan"}
    if record:
        assert wanted <= names, wanted - names
    else:
        assert not wanted & names


def test_validate_cli_accepts_a_solo_trace_with_leaf_spans(tmp_path):
    tr, _ = _traced_solve()
    path = tr.export_jsonl(str(tmp_path / "solo.jsonl"))
    names = {s.name for s in tr.spans}
    assert set(LEAVES) <= names
    assert validate.main(["--trace", path]) == 0


def test_solve_maxcut_profile_dir_puts_stages_on_the_profile(
        tmp_path, capsys, monkeypatch):
    # with the variable set, `solve_maxcut` leaves JAX's cache settings alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    profile = tmp_path / "profile"
    solve_maxcut.run(["--n", "24", "--p", "0.3", "--qubits", "7",
                      "--opt-steps", "2", "--layers", "2",
                      "--profile-dir", str(profile)])
    assert f"[maxcut] profile: {profile}" in capsys.readouterr().out
    names = _host_event_names(profile)
    assert {"solve", "partition", "solve_pool", "merge"} <= names
