"""reprolint (src/repro/analysis): one flagged + one clean snippet per
rule, suppression and baseline mechanics, the PR 5 cache-key regression
replayed against the *real* distributed/qaoa sources, the tier-1
repo-is-clean gate, and a CLI smoke test.

Snippets are analyzed in-memory via `run_on_sources` — same driver as
the CLI minus the filesystem walk."""

import json
import os
import re
import subprocess
import sys

import pytest
from hypothesis import given, settings, strategies as st

from repro.analysis import get_rules, run_on_sources, rule_ids

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _src(path):
    with open(os.path.join(REPO, path), encoding="utf-8") as f:
        return f.read()


def _rules_of(report):
    return [f.rule for f in report.findings]


# ---------------------------------------------------------------- registry --
def test_rule_registry_is_complete():
    assert rule_ids() == [
        "cache-key", "dispatch-purity", "tracer-hazard",
        "collective-axis", "hot-nondeterminism",
    ]


def test_unknown_rule_id_raises():
    with pytest.raises(KeyError):
        get_rules(["cache-key", "no-such-rule"])


# --------------------------------------------------------------- cache-key --
_UNKEYED_BUILDER = """
import functools
from repro.kernels import ops

@functools.lru_cache(maxsize=8)
def build(n: int):
    def run(x):
        return ops.apply_phase(x, x, None, 0.1)
    return run
"""

_KEYED_BUILDER = """
import functools
from repro.kernels import ops

@functools.lru_cache(maxsize=8)
def build(n: int, impl: str):
    def run(x):
        with ops.using_implementation(impl):
            return ops.apply_phase(x, x, None, 0.1)
    return run
"""

_GLOBAL_READ_BUILDER = """
import functools
from repro.kernels import ops

@functools.lru_cache(maxsize=8)
def build(n: int):
    def run(x):
        with ops.using_implementation(ops.get_implementation()):
            return ops.apply_phase(x, x, None, 0.1)
    return run
"""


def test_cache_key_flags_unkeyed_impl_sensitive_builder():
    rep = run_on_sources(
        {"src/repro/core/snippet.py": _UNKEYED_BUILDER}, rules=["cache-key"]
    )
    assert _rules_of(rep) == ["cache-key"]
    assert rep.findings[0].symbol == "build"


def test_cache_key_accepts_keyed_builder():
    rep = run_on_sources(
        {"src/repro/core/snippet.py": _KEYED_BUILDER}, rules=["cache-key"]
    )
    assert rep.findings == []


def test_cache_key_flags_trace_time_global_read():
    # using_implementation(ops.get_implementation()) re-reads the global
    # at trace time: the lru key cannot see it
    rep = run_on_sources(
        {"src/repro/core/snippet.py": _GLOBAL_READ_BUILDER},
        rules=["cache-key"],
    )
    assert _rules_of(rep) == ["cache-key"]


_TUNE_STATE_GLOBAL_READ = """
import functools
from repro.kernels import ops
from repro.kernels import tuning

@functools.lru_cache(maxsize=8)
def build(n: int, impl: str):
    def run(x):
        with ops.using_implementation(impl), \\
                tuning.using_state(tuning.state()):
            return ops.apply_phase(x, x, None, 0.1)
    return run
"""

_TUNE_STATE_KEYED = """
import functools
from repro.kernels import ops
from repro.kernels import tuning

@functools.lru_cache(maxsize=8)
def build(n: int, impl: str, tune: tuple):
    def run(x):
        with ops.using_implementation(impl), tuning.using_state(tune):
            return ops.apply_phase(x, x, None, 0.1)
    return run
"""


def test_cache_key_flags_trace_time_tuning_state_read():
    # tuning.using_state(tuning.state()) inside a cached builder is the
    # same cache-blindness bug as the get_implementation() re-read: the
    # block-shape table the body traces against never reaches the lru key
    rep = run_on_sources(
        {"src/repro/core/snippet.py": _TUNE_STATE_GLOBAL_READ},
        rules=["cache-key"],
    )
    assert _rules_of(rep) == ["cache-key"]
    assert "tuning.using_state()" in rep.findings[0].message


def test_cache_key_accepts_param_tuning_state():
    rep = run_on_sources(
        {"src/repro/core/snippet.py": _TUNE_STATE_KEYED}, rules=["cache-key"]
    )
    assert rep.findings == []


def test_cache_key_regression_solve_pool_program():
    """Acceptance criterion: stripping the PR 5 fix (the `impl` re-assert
    inside the cached pool/statevector builders) out of the *real*
    sources must re-raise the finding — for the one pool-program builder
    in qaoa.py (the solver pool `solve` and `solve_pool` run, one device
    or a mesh) and the statevector builder in distributed.py, whose
    dispatch reaches the kernels through the engine. The unmodified
    sources must stay clean."""
    paths = [
        "src/repro/core/distributed.py", "src/repro/core/qaoa.py",
        "src/repro/core/engine.py", "src/repro/core/merge.py",
        "src/repro/kernels/ops.py", "src/repro/compat.py",
    ]
    builders = ("src/repro/core/qaoa.py", "src/repro/core/distributed.py")
    sources = {p: _src(p) for p in paths}
    assert run_on_sources(sources, rules=["cache-key"]).findings == []

    degraded, total = dict(sources), 0
    for p in builders:
        degraded[p], n_subs = re.subn(
            r"with ops\.using_implementation\(impl\)"
            r"(?:, tuning\.using_state\(tune\))?:",
            "if True:", sources[p],
        )
        assert n_subs >= 1, f"expected a keyed builder in {p}"
        total += n_subs
    assert total >= 3
    rep = run_on_sources(degraded, rules=["cache-key"])
    flagged = {f.symbol for f in rep.findings}
    assert "_solve_subgraph_batch_program" in flagged, [
        f.render() for f in rep.findings]
    assert "_sharded_qaoa_program" in flagged

    # variant: delete `impl` from the cache signature but keep the
    # re-assert — now the with-block reads a value the lru key cannot
    # see, the other half of the same bug
    unsigned = dict(sources)
    for p in builders:
        unsigned[p], n_subs = re.subn(
            r"(?m)^(\s*)impl: str,?$|,\s*impl: str(?=\s*\))|\bimpl: str, ",
            r"\1", sources[p],
        )
        assert n_subs >= 1, f"expected an impl param in {p}"
    rep = run_on_sources(unsigned, rules=["cache-key"])
    flagged = " ".join(f.symbol or "" for f in rep.findings
                       if f.rule == "cache-key")
    assert "_solve_subgraph_batch_program" in flagged, [
        f.render() for f in rep.findings]
    assert "_sharded_qaoa_program" in flagged


# ---------------------------------------------------------- dispatch-purity --
_DIRECT_IMPORT = """
from repro.kernels import ref

def f(x):
    return ref.cutvals(4, x, x)
"""

_VIA_OPS = """
from repro.kernels import ops

def f(x):
    return ops.cutvals(4, x, x)
"""


def test_dispatch_purity_flags_direct_impl_import():
    rep = run_on_sources(
        {"src/repro/core/snippet.py": _DIRECT_IMPORT},
        rules=["dispatch-purity"],
    )
    assert _rules_of(rep) == ["dispatch-purity"]


def test_dispatch_purity_accepts_ops_and_allowed_zones():
    clean = run_on_sources(
        {"src/repro/core/snippet.py": _VIA_OPS}, rules=["dispatch-purity"]
    )
    assert clean.findings == []
    # tests/ and the kernels package itself may touch impls directly
    for path in ("tests/snippet.py", "src/repro/kernels/snippet.py"):
        rep = run_on_sources({path: _DIRECT_IMPORT}, rules=["dispatch-purity"])
        assert rep.findings == [], path


# ------------------------------------------------------------ tracer-hazard --
_TRACER_BAD = """
import jax
import numpy as np

@jax.jit
def f(x):
    if x > 0:
        x = x + 1
    y = float(x)
    return np.sum(x) + y
"""

_TRACER_CLEAN = """
import jax
import jax.numpy as jnp

@jax.jit
def f(x, n: int, mode: str = "fast"):
    if n > 2 and mode == "fast":  # static config, annotated
        x = x * 2
    for _ in range(int(x.shape[0])):  # shapes are static
        x = jnp.where(x > 0, x, -x)  # traced compare stays in jnp
    if x is None:  # identity is static even on tracers
        return x
    return x
"""


def test_tracer_hazard_flags_casts_numpy_and_control_flow():
    rep = run_on_sources(
        {"src/repro/models/snippet.py": _TRACER_BAD},
        rules=["tracer-hazard"],
    )
    msgs = " ".join(f.message for f in rep.findings)
    assert len(rep.findings) == 3, [f.render() for f in rep.findings]
    assert "float()" in msgs and "numpy" in msgs and "`if`" in msgs


def test_tracer_hazard_quiet_on_static_config_and_shapes():
    rep = run_on_sources(
        {"src/repro/models/snippet.py": _TRACER_CLEAN},
        rules=["tracer-hazard"],
    )
    assert rep.findings == [], [f.render() for f in rep.findings]


def test_tracer_hazard_only_fires_inside_traced_functions():
    # same body, no jit: plain host code may cast freely
    host = _TRACER_BAD.replace("@jax.jit\n", "")
    rep = run_on_sources(
        {"src/repro/models/snippet.py": host}, rules=["tracer-hazard"]
    )
    assert rep.findings == []


# ---------------------------------------------------------- collective-axis --
_AXIS_BAD = """
import jax

def f(x):
    return jax.lax.psum(x, "batch")
"""

_AXIS_UNBOUND = """
import jax

def f(x):
    return jax.lax.psum(x, some_axis)
"""

_AXIS_CLEAN = """
import jax

def f(x, layout, axis: str):
    a = jax.lax.psum(x, "model")
    b = jax.lax.pmean(x, layout.axis)
    c = jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0)
    d = jax.lax.axis_index(axis_name=("data", "model"))
    return a + b + c + d
"""


def test_collective_axis_flags_unknown_literal_and_unbound_name():
    rep = run_on_sources(
        {"src/repro/core/snippet.py": _AXIS_BAD}, rules=["collective-axis"]
    )
    assert _rules_of(rep) == ["collective-axis"]
    assert "batch" in rep.findings[0].message
    rep = run_on_sources(
        {"src/repro/core/snippet.py": _AXIS_UNBOUND},
        rules=["collective-axis"],
    )
    assert _rules_of(rep) == ["collective-axis"]


def test_collective_axis_accepts_mesh_axes_params_and_layout_attr():
    rep = run_on_sources(
        {"src/repro/core/snippet.py": _AXIS_CLEAN}, rules=["collective-axis"]
    )
    assert rep.findings == [], [f.render() for f in rep.findings]


# ------------------------------------------------------- hot-nondeterminism --
_NONDET_TRACED = """
import jax
import time
import random

@jax.jit
def f(x):
    return x * random.random() + time.time()
"""

_SCHED_BAD = """
import time
import random

def _pick_bucket(buckets):
    t = time.time()
    return buckets[int(t) % len(buckets)] if random.random() > 0.5 else None
"""

_SCHED_CLEAN = """
import time

def _pick_bucket(buckets):
    t0 = time.perf_counter()
    best = min(buckets)
    return best, time.perf_counter() - t0
"""


def test_nondeterminism_flags_rng_and_clock_in_traced_fn():
    rep = run_on_sources(
        {"src/repro/core/snippet.py": _NONDET_TRACED},
        rules=["hot-nondeterminism"],
    )
    assert sorted(_rules_of(rep)) == ["hot-nondeterminism"] * 2


def test_nondeterminism_guards_scheduler_path_allows_perf_counter():
    path = "src/repro/service/scheduler.py"  # module under guard
    rep = run_on_sources({path: _SCHED_BAD}, rules=["hot-nondeterminism"])
    assert len(rep.findings) == 2, [f.render() for f in rep.findings]
    rep = run_on_sources({path: _SCHED_CLEAN}, rules=["hot-nondeterminism"])
    assert rep.findings == []
    # identical code outside the guarded module (and untraced) is fine
    rep = run_on_sources(
        {"src/repro/service/solver_api.py": _SCHED_BAD},
        rules=["hot-nondeterminism"],
    )
    assert rep.findings == []


_OBS_BAD = """
import time

def begin(name):
    # direct clock read: bypasses the injectable clock, so a tracer
    # running under a VirtualClock would stamp wall time into spans
    return name, time.perf_counter()
"""

_OBS_CLEAN = """
from repro.obs.clock import default_clock

def begin(name, clock=default_clock):
    return name, clock()
"""


def test_nondeterminism_obs_package_bans_all_clock_reads():
    # inside repro.obs.* even the monotonic clocks the scheduler region
    # allows are banned — timestamps must flow through the injectable
    # clock so virtual-clock soaks stay bit-deterministic (DESIGN.md §8)
    path = "src/repro/obs/snippet.py"
    rep = run_on_sources({path: _OBS_BAD}, rules=["hot-nondeterminism"])
    assert len(rep.findings) == 1, [f.render() for f in rep.findings]
    assert "injectable clock" in rep.findings[0].message
    rep = run_on_sources({path: _OBS_CLEAN}, rules=["hot-nondeterminism"])
    assert rep.findings == []


def test_nondeterminism_obs_clock_module_is_the_sanctioned_boundary():
    # the clock module itself may touch time.* — it IS the boundary
    rep = run_on_sources(
        {"src/repro/obs/clock.py": _OBS_BAD}, rules=["hot-nondeterminism"]
    )
    assert rep.findings == []
    # the carve-out is the obs package only: an unguarded, untraced
    # module elsewhere may still read perf_counter freely
    rep = run_on_sources(
        {"src/repro/service/solver_api.py": _OBS_BAD},
        rules=["hot-nondeterminism"],
    )
    assert rep.findings == []


def test_nondeterminism_measurement_path_bans_all_clock_reads():
    # the autotune timing helper (repro.kernels.tuning) is held to the
    # obs-package contract: its timings feed the committed tuning cache,
    # so sweeps must be replayable through the injectable clock — direct
    # time.* reads (even monotonic ones) are banned (DESIGN.md §2.7)
    path = "src/repro/kernels/tuning.py"
    rep = run_on_sources({path: _OBS_BAD}, rules=["hot-nondeterminism"])
    assert len(rep.findings) == 1, [f.render() for f in rep.findings]
    assert "measurement-path" in rep.findings[0].message
    assert "injectable clock" in rep.findings[0].message
    rep = run_on_sources({path: _OBS_CLEAN}, rules=["hot-nondeterminism"])
    assert rep.findings == []
    # the guard is that one module, not the whole kernels package
    rep = run_on_sources(
        {"src/repro/kernels/snippet.py": _OBS_BAD},
        rules=["hot-nondeterminism"],
    )
    assert rep.findings == []


# ------------------------------------------------ suppression and baseline --
def test_line_suppression_with_justification():
    src = _DIRECT_IMPORT.replace(
        "from repro.kernels import ref",
        "from repro.kernels import ref"
        "  # reprolint: disable=dispatch-purity (comparing against ref)",
    )
    rep = run_on_sources(
        {"src/repro/core/snippet.py": src}, rules=["dispatch-purity"]
    )
    assert rep.findings == [] and rep.suppressed == 1


def test_file_suppression():
    src = "# reprolint: disable-file=tracer-hazard\n" + _TRACER_BAD
    rep = run_on_sources(
        {"src/repro/models/snippet.py": src}, rules=["tracer-hazard"]
    )
    assert rep.findings == [] and rep.suppressed == 3


def test_suppression_is_per_rule():
    # suppressing one rule must not silence another on the same line
    src = _DIRECT_IMPORT.replace(
        "from repro.kernels import ref",
        "from repro.kernels import ref  # reprolint: disable=cache-key",
    )
    rep = run_on_sources(
        {"src/repro/core/snippet.py": src}, rules=["dispatch-purity"]
    )
    assert _rules_of(rep) == ["dispatch-purity"]


def test_baseline_absorbs_then_releases_on_code_change():
    path = "src/repro/core/snippet.py"
    rep = run_on_sources({path: _DIRECT_IMPORT}, rules=["dispatch-purity"])
    fp = rep.findings[0].fingerprint
    rep2 = run_on_sources(
        {path: _DIRECT_IMPORT}, rules=["dispatch-purity"], baseline={fp}
    )
    assert rep2.findings == [] and rep2.baselined == 1
    # the fingerprint tracks the *code*: change the offending line and
    # the grandfathered entry no longer matches
    changed = _DIRECT_IMPORT.replace(
        "import ref", "import ref as reference"
    )
    rep3 = run_on_sources(
        {path: changed}, rules=["dispatch-purity"], baseline={fp}
    )
    assert len(rep3.findings) == 1 and rep3.baselined == 0


@given(pad=st.integers(min_value=0, max_value=12))
@settings(max_examples=10, deadline=None)
def test_fingerprint_stable_under_line_churn(pad):
    """Baseline identity must survive unrelated edits above the finding:
    fingerprints hash rule + path tail + symbol + line text, not line
    numbers."""
    base = run_on_sources(
        {"src/repro/core/snippet.py": _DIRECT_IMPORT},
        rules=["dispatch-purity"],
    ).findings[0]
    padded = "# padding\n" * pad + _DIRECT_IMPORT
    moved = run_on_sources(
        {"src/repro/core/snippet.py": padded}, rules=["dispatch-purity"]
    ).findings[0]
    assert moved.fingerprint == base.fingerprint
    assert moved.line == base.line + pad


def test_fingerprint_anchors_path_at_src():
    rel = run_on_sources(
        {"src/repro/core/snippet.py": _DIRECT_IMPORT},
        rules=["dispatch-purity"],
    ).findings[0]
    abs_ = run_on_sources(
        {"/somewhere/else/src/repro/core/snippet.py": _DIRECT_IMPORT},
        rules=["dispatch-purity"],
    ).findings[0]
    assert rel.fingerprint == abs_.fingerprint


# -------------------------------------------------------- tier-1 repo gate --
def test_repo_tree_is_reprolint_clean():
    """The CI lint job's contract, enforced from tier-1 as well: the
    whole src/repro tree passes every rule (modulo justified inline
    suppressions and the checked-in baseline)."""
    from repro.analysis import load_baseline, run

    baseline = os.path.join(REPO, "src", "repro", "analysis", "baseline.json")
    report = run(
        [os.path.join(REPO, "src", "repro")],
        baseline_path=baseline,
    )
    assert report.findings == [], "\n".join(
        f.render() for f in report.findings
    )


# --------------------------------------------------------------- CLI smoke --
def _run_cli(*args, cwd=REPO):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, env=env, cwd=cwd,
    )


def test_cli_json_on_violation(tmp_path):
    bad = tmp_path / "src" / "repro" / "core" / "snippet.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(_DIRECT_IMPORT)
    proc = _run_cli(str(bad), "--format", "json", "--baseline", "none")
    assert proc.returncode == 1, proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"][0]["rule"] == "dispatch-purity"
    assert payload["findings"][0]["fingerprint"]


def test_cli_clean_tree_exits_zero():
    proc = _run_cli("src/repro", "--format", "json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    assert payload["files"] > 50


def test_cli_list_rules():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    assert proc.stdout.split() == rule_ids()
