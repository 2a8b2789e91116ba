"""Multi-device (8 fake CPU devices) equivalence tests, run in subprocesses
so the main pytest process keeps its single-device view."""

import functools
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # subprocess-based: own CI job

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_check(name: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "repro.core._dist_checks", name],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, f"{name} failed:\n{out.stdout}\n{out.stderr}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_solve_pool_matches_single_device():
    res = _run_check("solve_pool")
    assert res["bitstrings_equal"], res
    assert res["exp_close"], res


@functools.lru_cache(maxsize=None)
def _n_solvers_check() -> dict:
    return _run_check("n_solvers")


@pytest.mark.parametrize("case", ["maxcut_even", "maxcut_pad", "qubo_even",
                                  "qubo_pad"])
def test_solve_n_solvers_matches_one_device(case):
    """`solve` with the pool over a `data` mesh of 4 devices gives the
    one-device pool's candidates, assignment and value bit for bit, with
    M a multiple of 4 and not (one padding row), with and without linear
    terms."""
    res = _n_solvers_check()
    assert res[case + "_m"] % 4 == (0 if case.endswith("even") else 3), res
    for what in ("bits", "assignment", "value"):
        assert res[f"{case}_{what}_equal"], (what, res)


def test_solve_n_solvers_above_device_count_raises():
    msg = _n_solvers_check()["too_many_solvers_error"]
    assert "16" in msg and "8" in msg, msg


def test_solve_pool_pads_to_its_mesh_and_matches():
    res = _n_solvers_check()
    assert res["pool_m"] % 4, res
    assert res["pool_bits_equal"] and res["pool_expectation_equal"], res


def test_sharded_statevector_matches_single_device():
    res = _run_check("sharded_qaoa")
    for key, ok in res.items():
        assert ok, f"{key}: {res}"


def test_engine_gradient_parity():
    """jax.grad through the sharded evolution == single-device gradient
    within float32 tolerance (emulated 2- and 4-device meshes), and the
    sharded Adam ascent beats the linear ramp, landing on the flat
    optimizer's parameters (DESIGN.md §2.6)."""
    res = _run_check("engine_grad")
    for key, ok in res.items():
        assert ok, f"{key}: {res}"


def test_engine_ops_dispatch_per_shard():
    """The sharded hot loop has no direct `ref.*` calls: every
    phase/mixer/cutvals/expectation op reaches the `kernels.ops`-
    dispatched kernels under `pallas_interpret`, agreeing with the xla
    path (cut tables bitwise; evolved state ulp-tight). This is the
    runtime half of the contract; the static half is reprolint's
    `dispatch-purity` rule (src/repro/analysis, docs/ANALYSIS.md)."""
    res = _run_check("engine_interpret")
    for key, ok in res.items():
        assert ok, f"{key}: {res}"


def test_merge_sharded_matches_exact():
    res = _run_check("merge_sharded")
    assert res["val_matches_exact"], res
    assert res["assignment_achieves_val"], res


def test_problem_families_distributed_parity():
    """QUBO and penalty-MIS `Problem`s through `solve_distributed` on an
    emulated data mesh: exact cut/assignment parity with single-device
    `solve` on the same problem, and the MIS result is a valid
    independent set (DESIGN.md §9)."""
    res = _run_check("problem_distributed")
    for key, ok in res.items():
        assert ok, f"{key}: {res}"


def test_service_mesh_backend_parity():
    """The solve service over `MeshBackend` (solve_pool on an emulated
    4-device `data` mesh) returns bit-identical cuts/assignments to the
    single-device `LocalBackend` — and to solo `core.solve` — on the
    parity mix, with per-tenant accounting and the async dispatch window
    engaged (DESIGN.md §6.5)."""
    res = _run_check("service_mesh")
    for key, ok in res.items():
        assert ok, f"{key}: {res}"


def test_solve_distributed_matches_single_device():
    """End-to-end pipeline parity on emulated devices (DESIGN.md §2.4):
    same cut value as single-device `solve` on a small fixed graph, for
    both the data-only pool mesh and the data+model routing mesh."""
    res = _run_check("solve_distributed")
    for key, ok in res.items():
        assert ok, f"{key}: {res}"
