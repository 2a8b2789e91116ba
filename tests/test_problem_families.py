"""Problem families through the one diagonal-cost oracle (DESIGN.md §9):
weighted Max-Cut → arbitrary QUBO → penalty-encoded MIS.

Covers the oracle contract at every layer: kernel linear terms (values +
custom-vjp gradients), the `Problem` wrapper's QUBO/MIS encodings against
dense evaluation and exhaustive brute force, partition/merge linear
threading (merge made exhaustive via top_k = 2^n so the solve is provably
optimal on small instances), canonical-hash separation of linear-distinct
QUBOs, service≡solo bit-parity for weighted and QUBO traffic, and the
local-search re-score/epsilon bugfixes."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ParaQAOAConfig, solve
from repro.core.baselines.brute_force import (
    brute_force_maxcut,
    brute_force_problem,
)
from repro.core.baselines.local_search import refine
from repro.core.graph import (
    Graph,
    Problem,
    as_problem,
    cut_value,
    independent_set_violations,
    problem_value,
)
from repro.core.partition import connectivity_preserving_partition, split_linear
from repro.kernels import ops
from repro.kernels import ref
from repro.obs.trace import Tracer, use_tracer
from repro.service import SLA, ServiceConfig, SolveService
from repro.service.canonical import canonical_key
from repro.service.workload import problem_mix, relabel_problem


def _random_problem(n, p, seed, offset=0.0):
    rng = np.random.default_rng(seed)
    e = np.array(
        [(i, j) for i in range(n) for j in range(i + 1, n)
         if rng.random() < p],
        dtype=np.int32,
    ).reshape(-1, 2)
    q = rng.normal(size=e.shape[0]).astype(np.float32)
    h = rng.normal(size=n).astype(np.float32)
    return Problem.qubo(n, e, q, linear=h, offset=offset)


def _exhaustive_cfg(n_qubits: int) -> ParaQAOAConfig:
    """top_k = 2^n makes the merge frontier enumerate *every* assignment,
    so the solve is exact whenever the (uncapped) beam is exhaustive."""
    return ParaQAOAConfig(
        n_qubits=n_qubits, top_k=1 << n_qubits, p_layers=2, opt_steps=5,
        beam_cap=1 << 22,
    )


# ------------------------------------------------------------- kernels --
def test_cutvals_linear_semantics():
    """cutvals(..., linear) == quadratic cut + bits @ linear over every
    basis state, for the reference and Pallas-interpret kernels alike."""
    n = 6
    g = Graph.erdos_renyi_weighted(n, 0.5, seed=0)
    lin = np.linspace(-1.0, 1.5, n).astype(np.float32)
    idx = np.arange(1 << n)
    bits = ((idx[:, None] >> np.arange(n)) & 1).astype(np.float32)
    want = np.asarray(ref.cutvals(n, g.edges, g.weights)) + bits @ lin

    got_ref = np.asarray(ref.cutvals(n, g.edges, g.weights, jnp.asarray(lin)))
    np.testing.assert_allclose(got_ref, want, atol=1e-5)

    from repro.kernels import cutvals as kcut

    got_pl = np.asarray(
        kcut.cutvals(n, g.edges, g.weights, jnp.asarray(lin), interpret=True)
    )
    np.testing.assert_array_equal(got_pl, got_ref)

    sub = jnp.asarray([0, 3, 17, 63], jnp.int32)
    got_at = np.asarray(ref.cutvals_at(sub, g.edges, g.weights, jnp.asarray(lin)))
    np.testing.assert_allclose(got_at, want[np.asarray(sub)], atol=1e-5)


def test_cutvals_linear_grads():
    """The custom-vjp rules: d_weights[e] = <g, xor_e>, d_linear[v] =
    <g, bit_v> — checked against dense cotangent expectations."""
    n = 5
    g = Graph.erdos_renyi(n, 0.6, seed=1)
    lin = jnp.asarray(np.random.default_rng(2).normal(size=n), jnp.float32)
    ct = jnp.asarray(np.random.default_rng(3).normal(size=1 << n), jnp.float32)

    def loss(w, l):
        return jnp.vdot(ct, ops.cutvals(n, g.edges, w, l))

    d_w, d_l = jax.grad(loss, argnums=(0, 1))(g.weights, lin)

    e = np.asarray(g.edges)
    idx = np.arange(1 << n)
    crossed = (((idx[:, None] >> e[None, :, 0]) ^ (idx[:, None] >> e[None, :, 1])) & 1)
    want_w = np.asarray(ct) @ crossed.astype(np.float32)
    bits = ((idx[:, None] >> np.arange(n)) & 1).astype(np.float32)
    want_l = np.asarray(ct) @ bits
    np.testing.assert_allclose(np.asarray(d_w), want_w, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(d_l), want_l, rtol=1e-5, atol=1e-4)

    # the linear=None path keeps its own vjp (no d_linear cotangent)
    d_w0 = jax.grad(lambda w: jnp.vdot(ct, ops.cutvals(n, g.edges, w)))(g.weights)
    np.testing.assert_allclose(np.asarray(d_w0), want_w, rtol=1e-5, atol=1e-4)


# ------------------------------------------------------------ encodings --
def test_qubo_matches_dense_evaluation():
    """problem_value == x^T Q x (upper-tri) + h @ x + c for random x."""
    n = 9
    prob = _random_problem(n, 0.5, seed=4, offset=-2.5)
    rng = np.random.default_rng(5)
    # reconstruct the dense QUBO this problem was built from
    rng2 = np.random.default_rng(4)
    edges = np.array(
        [(i, j) for i in range(n) for j in range(i + 1, n)
         if rng2.random() < 0.5],
        dtype=np.int32,
    )
    q = rng2.normal(size=edges.shape[0]).astype(np.float32)
    h = rng2.normal(size=n).astype(np.float32)
    for _ in range(16):
        x = rng.integers(0, 2, size=n).astype(np.float64)
        want = float(
            sum(qq * x[i] * x[j] for (i, j), qq in zip(edges, q))
            + h @ x - 2.5
        )
        got = float(problem_value(prob, jnp.asarray(x.astype(np.int8))))
        assert abs(got - want) < 1e-4, (got, want)


def test_mis_penalty_encoding_requires_penalty_ge_2():
    g = Graph.erdos_renyi(6, 0.5, seed=6)
    with pytest.raises(ValueError):
        Problem.mis(g, penalty=1.5)


def test_brute_force_problem_matches_maxcut_oracle():
    """On a zero-linear problem the full-enumeration oracle agrees with
    the bit0=0 symmetry-exploiting Max-Cut oracle."""
    g = Graph.erdos_renyi_weighted(10, 0.4, seed=7)
    _, v_mc, _ = brute_force_maxcut(g)
    _, v_pr, _ = brute_force_problem(g)
    assert abs(v_mc - v_pr) < 1e-4, (v_mc, v_pr)


# ------------------------------------------------- end-to-end small-n --
def test_qubo_solve_matches_brute_force():
    """Exhaustive-merge solve of a random QUBO (n <= 12) lands exactly on
    the brute-force optimum — linear terms thread partition → oracle →
    merge correctly, including the broken flip symmetry."""
    prob = _random_problem(11, 0.4, seed=8, offset=1.25)
    _, opt, _ = brute_force_problem(prob)
    out = solve(prob, _exhaustive_cfg(6))
    assert abs(out.cut_value - opt) < 1e-3, (out.cut_value, opt)
    assert abs(
        float(problem_value(prob, jnp.asarray(out.assignment))) - opt
    ) < 1e-3


def test_mis_solve_valid_and_optimal():
    """Penalty-QUBO MIS on small graphs: the solved set is independent
    and its size equals the brute-force maximum independent set."""
    for seed in (9, 10):
        g = Graph.erdos_renyi(12, 0.3, seed=seed)
        prob = Problem.mis(g)
        _, opt, _ = brute_force_problem(prob)
        out = solve(prob, _exhaustive_cfg(6))
        assert independent_set_violations(g, out.assignment) == 0
        assert abs(out.cut_value - opt) < 1e-3, (seed, out.cut_value, opt)
        assert int(np.sum(out.assignment)) == int(round(opt))


def test_zero_linear_problem_bit_identical_to_graph_solve():
    """Problem.maxcut(g) must follow the exact zero-linear special case:
    bit-identical assignment and cut to solving the plain Graph."""
    g = Graph.erdos_renyi(30, 0.25, seed=11)
    cfg = ParaQAOAConfig(n_qubits=7, top_k=2, p_layers=2, opt_steps=10)
    a = solve(g, cfg)
    b = solve(Problem.maxcut(g), cfg)
    assert a.cut_value == b.cut_value
    np.testing.assert_array_equal(a.assignment, b.assignment)


def test_split_linear_covers_each_vertex_once():
    """Every vertex's h lands in exactly one subproblem (first coverage);
    shared boundary vertices see h = 0 in later ranges."""
    g = Graph.erdos_renyi(23, 0.3, seed=12)
    part = connectivity_preserving_partition(g, 4)
    lin = np.arange(1, g.n + 1, dtype=np.float32)
    subs = split_linear(part, lin)
    recovered = np.zeros(g.n, dtype=np.float64)
    for (lo, hi), li in zip(part.ranges, subs):
        assert li.shape == (hi - lo,)
        recovered[lo:hi] += li
    np.testing.assert_allclose(recovered, lin)


# -------------------------------------------------------- canonical key --
def test_canonical_linear_distinct_qubos_do_not_collide():
    prob = _random_problem(10, 0.4, seed=13)
    h2 = np.asarray(prob.linear).copy()
    h2[3] += 0.5
    other = dataclasses.replace(prob, linear=jnp.asarray(h2))
    assert canonical_key(prob) != canonical_key(other)


def test_canonical_relabeled_qubo_collides():
    prob = _random_problem(10, 0.4, seed=14)
    perm = np.random.default_rng(15).permutation(prob.n).astype(np.int32)
    assert canonical_key(prob) == canonical_key(relabel_problem(prob, perm))


def test_canonical_zero_linear_problem_matches_graph_key():
    """The zero-linear path appends nothing to the certificate: a plain
    Graph and its Problem.maxcut wrapper hash byte-identically."""
    g = Graph.erdos_renyi_weighted(14, 0.4, seed=16)
    assert canonical_key(g) == canonical_key(Problem.maxcut(g))


# ------------------------------------------------------------- service --
@pytest.mark.parametrize("weights", ["uniform", "spin"])
def test_weighted_service_bit_identical_to_solo_solve(weights):
    """The §6.1 parity contract on *weighted* instances, alongside the
    unweighted one in test_service.py."""
    svc = SolveService(ServiceConfig(batch_slots=8, max_qubits=8,
                                     enable_cache=False))
    gen = (Graph.erdos_renyi_weighted if weights == "uniform"
           else Graph.spin_glass)
    graphs = [gen(n, 0.3, seed=s) for s, n in enumerate((18, 25, 21))]
    rids = [svc.submit(g, SLA(deadline_s=30.0)) for g in graphs]
    res = svc.drain()
    for g, rid in zip(graphs, rids):
        r = res[rid]
        solo = solve(g, r.plan.to_config())
        assert r.cut_value == solo.cut_value, (rid, r.cut_value, solo.cut_value)
        np.testing.assert_array_equal(r.assignment, solo.assignment)


def test_qubo_service_bit_identical_to_solo_solve():
    """A QUBO request served through `SolveService` is bit-identical to
    solo `core.solve` on the same problem (acceptance criterion)."""
    svc = SolveService(ServiceConfig(batch_slots=8, max_qubits=8,
                                     enable_cache=False))
    probs = [_random_problem(n, 0.3, seed=20 + n, offset=0.5)
             for n in (18, 26)]
    probs.append(Problem.mis(Graph.erdos_renyi(22, 0.2, seed=21)))
    rids = [svc.submit(p, SLA(deadline_s=30.0)) for p in probs]
    res = svc.drain()
    for p, rid in zip(probs, rids):
        r = res[rid]
        solo = solve(p, r.plan.to_config())
        assert r.cut_value == solo.cut_value, (rid, r.cut_value, solo.cut_value)
        np.testing.assert_array_equal(r.assignment, solo.assignment)


def test_service_cache_separates_linear_terms():
    """Same quadratic, different linear terms → distinct keys (no false
    hit); a *relabeled* copy of the same QUBO hits."""
    svc = SolveService(ServiceConfig(batch_slots=4, max_qubits=8))
    prob = _random_problem(20, 0.3, seed=22)
    rid0 = svc.submit(prob)
    svc.drain()
    assert not svc.results[rid0].cached

    h2 = np.asarray(prob.linear).copy()
    h2[0] += 1.0
    rid1 = svc.submit(dataclasses.replace(prob, linear=jnp.asarray(h2)))
    svc.drain()
    assert not svc.results[rid1].cached

    perm = np.random.default_rng(23).permutation(prob.n).astype(np.int32)
    rid2 = svc.submit(relabel_problem(prob, perm))
    svc.drain()
    r2 = svc.results[rid2]
    assert r2.cached
    assert r2.cut_value == pytest.approx(svc.results[rid0].cut_value)


def test_problem_mix_families():
    probs = problem_mix(6, (10, 14), 0.3, 0.3, seed=24, problem="mis")
    assert all(isinstance(p, Problem) and p.kind == "mis" for p in probs)
    probs = problem_mix(6, (10, 14), 0.3, 0.3, seed=24, problem="qubo",
                        weights="spin")
    assert all(p.kind == "qubo" for p in probs)
    graphs = problem_mix(4, (10, 14), 0.3, 0.0, seed=24, weights="uniform")
    assert all(isinstance(g, Graph) for g in graphs)


# -------------------------------------------------------- local search --
def test_refine_rescore_no_drift():
    """The returned value is a from-scratch re-score of the final
    assignment: on a weighted instance with hundreds of accepted flips it
    must equal cut_value(graph, assignment) *exactly* (the old
    scan-accumulated carry drifted in float32)."""
    g = Graph.erdos_renyi_weighted(120, 0.2, seed=25, low=0.01, high=3.0)
    a0 = np.zeros(g.n, dtype=np.int8)
    a, v = refine(g, a0, steps=400)
    assert v == float(cut_value(g, jnp.asarray(a))), (
        v, float(cut_value(g, jnp.asarray(a)))
    )


def test_refine_relative_epsilon_accepts_tiny_weights():
    """Uniformly tiny weights: every real improvement is < the old
    absolute 1e-6 threshold; the relative epsilon must still accept."""
    n = 6
    e = np.array([[0, i] for i in range(1, n)], dtype=np.int32)  # star
    w = np.full(n - 1, 1e-8, dtype=np.float32)
    g = Graph.from_edges(n, e, w)
    a0 = np.zeros(n, dtype=np.int8)  # cut 0; flipping the hub gains 5e-8
    a, v = refine(g, a0, steps=5)
    assert v > 0.0, "relative epsilon rejected a real improvement"
    assert v == pytest.approx(5e-8, rel=1e-3)


def test_refine_with_linear_clears_mis_violations():
    """Dropping a violating vertex gains >= penalty - 1 > 0, so the
    linear-aware 1-flip refinement drives violations to zero."""
    g = Graph.erdos_renyi(30, 0.25, seed=26)
    prob = Problem.mis(g, penalty=2.0)
    a0 = np.ones(g.n, dtype=np.int8)  # everything selected: maximally bad
    a, v = refine(prob.graph, a0, steps=120, linear=prob.linear)
    assert independent_set_violations(g, a) == 0
    assert v == pytest.approx(
        float(problem_value(prob, jnp.asarray(a))) - prob.offset
    )


def test_refine_improves_qubo_objective():
    prob = _random_problem(40, 0.2, seed=27)
    a0 = np.zeros(prob.n, dtype=np.int8)
    v0 = float(problem_value(prob, jnp.asarray(a0)))
    _, v = refine(prob.graph, a0, steps=80, linear=prob.linear)
    assert v >= v0 - 1e-6


@functools.partial(jax.jit, static_argnums=(4, 5))
def _sweeps_from_scratch(edges, weights, linear, assignment, steps, n):
    """The refinement as it was before the incremental gains: every step
    recomputes every vertex's gain from the whole edge list."""
    eps = 1e-6 * (jnp.sum(jnp.abs(weights)) + jnp.sum(jnp.abs(linear)))

    def gains(s):
        crossed = (s[edges[:, 0]] ^ s[edges[:, 1]]).astype(weights.dtype)
        inc = jnp.zeros((n,), weights.dtype)
        inc = inc.at[edges[:, 0]].add(weights * crossed)
        inc = inc.at[edges[:, 1]].add(weights * crossed)
        deg = jnp.zeros((n,), weights.dtype)
        deg = deg.at[edges[:, 0]].add(weights)
        deg = deg.at[edges[:, 1]].add(weights)
        return deg - 2.0 * inc + linear * (1.0 - 2.0 * s.astype(weights.dtype))

    def body(s, _):
        g = gains(s)
        v = jnp.argmax(g)
        improve = g[v] > eps
        s = jnp.where(jnp.arange(n) == v,
                      jnp.where(improve, 1 - s[v], s[v]), s).astype(s.dtype)
        return s, None

    return jax.lax.scan(body, assignment, None, length=steps)[0]


def _star(n):
    return Graph.from_edges(n, [(0, i) for i in range(1, n)])


def _int_weighted(n, p, seed):
    g = Graph.erdos_renyi(n, p, seed=seed)
    w = np.random.default_rng(seed).integers(1, 10, size=g.n_edges)
    return Graph.from_edges(n, np.asarray(g.edges), w.astype(np.float32))


def _isolated(n, seed):
    """Edges among the first half of the vertices only."""
    g = Graph.erdos_renyi(n // 2, 0.2, seed=seed)
    return Graph.from_edges(n, np.asarray(g.edges), pad_to=g.n_edges + 7)


def _mis(n, p, seed):
    prob = Problem.mis(Graph.erdos_renyi(n, p, seed=seed), penalty=2.0)
    return prob.graph, prob.linear


REFINE_CASES = {
    # name: (graph, or (graph, linear); steps; expected degree bucket)
    "gnp": (lambda: Graph.erdos_renyi(150, 0.1, seed=30), 300, 128),
    "gnp-padded": (lambda: Graph.erdos_renyi(150, 0.1, seed=30, pad_to=2000),
                   300, 128),
    "gnp-partway": (lambda: Graph.erdos_renyi(150, 0.1, seed=31), 17, 128),
    "int-weighted": (lambda: _int_weighted(120, 0.15, 32), 300, 128),
    "spin-glass": (lambda: Graph.spin_glass(150, 0.1, seed=33), 300, 128),
    "mis-linear": (lambda: _mis(60, 0.15, 34), 200, 128),
    "star": (lambda: _star(300), 50, 512),
    "isolated": (lambda: _isolated(90, 35), 200, 128),
    "steps-0": (lambda: Graph.erdos_renyi(150, 0.1, seed=36), 0, 128),
}


@pytest.mark.parametrize("case", sorted(REFINE_CASES))
def test_refine_matches_from_scratch_sweeps(case):
    """Incremental gains over the CSR adjacency make the same flips as the
    from-scratch sweep: with integer weights and linear terms every gain is
    an exact float32 integer, so assignment and value agree bit for bit."""
    make, steps, bucket = REFINE_CASES[case]
    made = make()
    g, linear = made if isinstance(made, tuple) else (made, None)
    lin = jnp.zeros((g.n,), jnp.float32) if linear is None else linear
    a0 = np.random.default_rng(37).integers(0, 2, size=g.n).astype(np.int8)
    tr = Tracer(record=True)
    with use_tracer(tr), tr.span("refine") as span:
        a, v = refine(g, a0, steps, linear=linear)
    want = np.asarray(_sweeps_from_scratch(
        g.edges, g.weights, lin, jnp.asarray(a0, jnp.int32), steps, g.n))
    np.testing.assert_array_equal(a, want.astype(np.int8))
    v_want = float(cut_value(g, jnp.asarray(want)))
    if linear is not None:
        v_want += float(np.asarray(linear, np.float64) @ want)
    assert v == v_want
    assert span.attrs["degree_bucket"] == bucket
    assert 0 <= span.attrs["flips"] <= steps
    if steps == 0:
        np.testing.assert_array_equal(a, a0)


def test_refine_bucket_ignores_padding_and_reuses_its_program():
    """The (0, 0) zero-weight padding rows enter no neighbour list, so they
    do not raise the degree bucket; a second graph of the same (n, E_pad,
    bucket) runs the programs the first one compiled."""
    spans = []
    for seed in (38, 39):
        g = Graph.erdos_renyi(211, 0.05, seed=seed, pad_to=4001)
        assert 4001 - g.n_edges > 2 * 128  # vertex 0 would hold them all
        tr = Tracer(record=True)
        with use_tracer(tr), tr.span("refine") as span:
            refine(g, np.zeros(g.n, np.int8), 40)
        compiled = {s.attrs["fun_name"] for s in tr.spans
                    if s.name == "compile"
                    and s.attrs["phase"] == "backend_compile"}
        spans.append((span, compiled))
    (first, first_compiled), (second, second_compiled) = spans
    assert first.attrs["degree_bucket"] == second.attrs["degree_bucket"] == 128
    assert "jit(_sweeps)" in first_compiled
    assert second_compiled == set()
    assert second.attrs.get("compiles", 0) == 0
