"""Per-kernel validation: Pallas (interpret=True on CPU) vs ref.py oracles,
swept across shapes, plus hypothesis property tests on kernel invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.graph import Graph
from repro.kernels import cutbatch, cutvals, mixer, phase, ref


def _graph(n, p, seed, pad=None):
    return Graph.erdos_renyi(n, p, seed=seed, pad_to=pad)


# ---------------------------------------------------------------- cutvals --
@pytest.mark.parametrize("n", [3, 6, 10, 12])
@pytest.mark.parametrize("p", [0.2, 0.8])
def test_cutvals_kernel_matches_ref(n, p):
    g = _graph(n, p, seed=n)
    want = ref.cutvals(n, g.edges, g.weights)
    got = cutvals.cutvals(n, g.edges, g.weights, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_cutvals_kernel_edge_padding_boundary():
    # weighted multigraph with E > EDGE_CHUNK: exercises chunked accumulation
    n = 10
    e = cutvals.EDGE_CHUNK + 37
    rng = np.random.default_rng(0)
    pairs = rng.integers(0, n, size=(e, 2))
    pairs[pairs[:, 0] == pairs[:, 1], 1] += 1
    pairs[:, 1] %= n
    w = rng.uniform(0.1, 2.0, size=e).astype(np.float32)
    g = Graph.from_edges(n, pairs, w)
    assert g.n_edges > cutvals.EDGE_CHUNK
    want = ref.cutvals(n, g.edges, g.weights)
    got = cutvals.cutvals(n, g.edges, g.weights, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


@given(n=st.integers(2, 9), seed=st.integers(0, 20))
@settings(max_examples=10, deadline=None)
def test_cutvals_complement_symmetry(n, seed):
    # cut(b) == cut(~b): flipping every vertex preserves the cut
    g = _graph(n, 0.5, seed=seed)
    c = np.asarray(cutvals.cutvals(n, g.edges, g.weights, interpret=True))
    np.testing.assert_allclose(c, c[::-1][np.argsort(np.argsort(c))] * 0 + c[(2**n - 1) - np.arange(2**n)], rtol=1e-6)


# ------------------------------------------------------------------ phase --
@pytest.mark.parametrize("n", [6, 10, 14])
@pytest.mark.parametrize("gamma", [0.0, 0.37, -1.2])
def test_phase_kernel_matches_ref(n, gamma):
    key = jax.random.PRNGKey(n)
    k1, k2, k3 = jax.random.split(key, 3)
    dim = 2**n
    re = jax.random.normal(k1, (dim,), jnp.float32)
    im = jax.random.normal(k2, (dim,), jnp.float32)
    c = jax.random.uniform(k3, (dim,), jnp.float32) * 10
    wr, wi = ref.apply_phase(re, im, c, gamma)
    gr, gi = phase.apply_phase(re, im, c, gamma, interpret=True)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(wr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gi), np.asarray(wi), atol=1e-5)


def test_phase_preserves_norm():
    dim = 2**12
    key = jax.random.PRNGKey(0)
    re = jax.random.normal(key, (dim,), jnp.float32)
    im = jnp.zeros((dim,))
    c = jax.random.uniform(key, (dim,)) * 5
    gr, gi = phase.apply_phase(re, im, c, 0.7, interpret=True)
    np.testing.assert_allclose(
        float(jnp.sum(gr**2 + gi**2)), float(jnp.sum(re**2)), rtol=1e-5
    )


@pytest.mark.parametrize("n", [6, 12])
def test_expectation_kernel_matches_ref(n):
    key = jax.random.PRNGKey(n)
    k1, k2, k3 = jax.random.split(key, 3)
    dim = 2**n
    re = jax.random.normal(k1, (dim,), jnp.float32)
    im = jax.random.normal(k2, (dim,), jnp.float32)
    c = jax.random.uniform(k3, (dim,), jnp.float32)
    want = float(ref.expectation(re, im, c))
    got = float(phase.expectation(re, im, c, interpret=True))
    assert got == pytest.approx(want, rel=1e-5)


@pytest.mark.parametrize("n", [3, 6, 12])
def test_vdot_kernel_matches_ref(n):
    k1, k2 = jax.random.split(jax.random.PRNGKey(n))
    a = jax.random.normal(k1, (2**n,), jnp.float32)
    b = jax.random.normal(k2, (2**n,), jnp.float32)
    want = float(ref.vdot(a, b))
    got = float(phase.vdot(a, b, interpret=True))
    assert got == pytest.approx(want, rel=1e-5, abs=1e-5)


# ------------------------------------------------------------------ mixer --
@pytest.mark.parametrize("n", [3, 5, 8, 10])
@pytest.mark.parametrize("beta", [0.1, 0.9, 2.5])
def test_mixer_kernel_matches_ref(n, beta):
    key = jax.random.PRNGKey(n)
    k1, k2 = jax.random.split(key)
    dim = 2**n
    re = jax.random.normal(k1, (dim,), jnp.float32)
    im = jax.random.normal(k2, (dim,), jnp.float32)
    wr, wi = ref.apply_mixer(re, im, n, jnp.float32(beta))
    gr, gi = mixer.apply_mixer(re, im, n, jnp.float32(beta), interpret=True)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(wr), atol=2e-5)
    np.testing.assert_allclose(np.asarray(gi), np.asarray(wi), atol=2e-5)


@pytest.mark.parametrize("group", [2, 4, 7])
def test_mixer_group_sizes_agree(group):
    n = 8
    key = jax.random.PRNGKey(1)
    dim = 2**n
    re = jax.random.normal(key, (dim,), jnp.float32)
    im = jnp.zeros((dim,))
    w7r, w7i = ref.apply_mixer(re, im, n, 0.4, group=7)
    wgr, wgi = ref.apply_mixer(re, im, n, 0.4, group=group)
    np.testing.assert_allclose(np.asarray(wgr), np.asarray(w7r), atol=2e-5)
    np.testing.assert_allclose(np.asarray(wgi), np.asarray(w7i), atol=2e-5)


def test_mixer_unitarity():
    n = 9
    key = jax.random.PRNGKey(2)
    k1, k2 = jax.random.split(key)
    re = jax.random.normal(k1, (2**n,), jnp.float32)
    im = jax.random.normal(k2, (2**n,), jnp.float32)
    norm0 = float(jnp.sum(re**2 + im**2))
    gr, gi = mixer.apply_mixer(re, im, n, 1.3, interpret=True)
    assert float(jnp.sum(gr**2 + gi**2)) == pytest.approx(norm0, rel=1e-4)


def test_mixer_beta_zero_is_identity():
    n = 6
    re = jax.random.normal(jax.random.PRNGKey(3), (2**n,), jnp.float32)
    im = jnp.zeros((2**n,))
    gr, gi = mixer.apply_mixer(re, im, n, 0.0, interpret=True)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(re), atol=1e-6)
    np.testing.assert_allclose(np.asarray(gi), 0.0, atol=1e-6)


# --------------------------------------------------------------- cutbatch --
@pytest.mark.parametrize("b,v", [(4, 10), (130, 50), (64, 600)])
def test_cutbatch_kernel_matches_ref(b, v):
    g = _graph(v, 0.3, seed=b)
    adj = g.dense_adjacency()
    rng = np.random.default_rng(b)
    spins = (rng.integers(0, 2, size=(b, v)) * 2 - 1).astype(np.float32)
    want = ref.cut_batch_dense(jnp.asarray(spins), adj, g.total_weight())
    got = cutbatch.cut_batch_dense(
        jnp.asarray(spins), adj, g.total_weight(), interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_cutbatch_agrees_with_edge_list_eval():
    from repro.core.graph import cut_value_batch

    v, b = 37, 12
    g = _graph(v, 0.5, seed=5)
    rng = np.random.default_rng(7)
    assign = rng.integers(0, 2, size=(b, v)).astype(np.int8)
    spins = (assign * 2 - 1).astype(np.float32)
    want = np.asarray(cut_value_batch(g, jnp.asarray(assign)))
    got = np.asarray(
        cutbatch.cut_batch_dense(
            jnp.asarray(spins), g.dense_adjacency(), g.total_weight(), interpret=True
        )
    )
    np.testing.assert_allclose(got, want, rtol=1e-5)


# ------------------------------------------------------------ cutvals_at --
@pytest.mark.parametrize("n,m", [(6, 64), (10, 1000), (12, 5000)])
def test_cutvals_at_kernel_matches_ref(n, m):
    # arbitrary (shuffled, non-tile-multiple) basis indices — the sharded
    # layout-A/B gather pattern
    g = _graph(n, 0.5, seed=n)
    rng = np.random.default_rng(m)
    idx = jnp.asarray(rng.integers(0, 2**n, size=m), jnp.int32)
    want = ref.cutvals_at(idx, g.edges, g.weights)
    got = cutvals.cutvals_at(idx, g.edges, g.weights, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_cutvals_at_full_range_equals_cutvals():
    n = 9
    g = _graph(n, 0.4, seed=2)
    idx = jnp.arange(2**n, dtype=jnp.int32)
    got = cutvals.cutvals_at(idx, g.edges, g.weights, interpret=True)
    want = cutvals.cutvals(n, g.edges, g.weights, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# ------------------------------------------------------- apply_mixer_bits --
@pytest.mark.parametrize("n,lo,k", [(8, 0, 3), (8, 2, 3), (9, 4, 5), (10, 3, 7)])
def test_mixer_bits_kernel_matches_ref(n, lo, k):
    key = jax.random.PRNGKey(n * 100 + lo)
    k1, k2 = jax.random.split(key)
    dim = 2**n
    re = jax.random.normal(k1, (dim,), jnp.float32)
    im = jax.random.normal(k2, (dim,), jnp.float32)
    beta = jnp.float32(0.7)
    wr, wi = ref.apply_mixer_bits(re, im, n, lo, k, beta)
    gr, gi = mixer.apply_mixer_bits(re, im, n, lo, k, beta, interpret=True)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(wr), atol=2e-5)
    np.testing.assert_allclose(np.asarray(gi), np.asarray(wi), atol=2e-5)


@pytest.mark.parametrize("n,lo,k", [(8, 2, 3), (9, 4, 5), (10, 3, 7)])
def test_mixer_bits_relayout_path_matches_strided(n, lo, k):
    # the legacy moveaxis path (kept as the §Perf C11 bench baseline)
    # and the fused strided-BlockSpec kernel are the same group unitary
    key = jax.random.PRNGKey(n * 10 + k)
    k1, k2 = jax.random.split(key)
    dim = 2**n
    re = jax.random.normal(k1, (dim,), jnp.float32)
    im = jax.random.normal(k2, (dim,), jnp.float32)
    beta = jnp.float32(0.7)
    sr, si = mixer.apply_mixer_bits(re, im, n, lo, k, beta, interpret=True)
    rr, ri = mixer.apply_mixer_bits_relayout(
        re, im, n, lo, k, beta, interpret=True
    )
    np.testing.assert_allclose(np.asarray(sr), np.asarray(rr), atol=2e-5)
    np.testing.assert_allclose(np.asarray(si), np.asarray(ri), atol=2e-5)


def test_mixer_bits_composition_is_full_mixer():
    # chaining apply_mixer_bits over all groups == apply_mixer (ref oracle)
    n, group = 9, 4
    key = jax.random.PRNGKey(3)
    k1, k2 = jax.random.split(key)
    re = jax.random.normal(k1, (2**n,), jnp.float32)
    im = jax.random.normal(k2, (2**n,), jnp.float32)
    beta = jnp.float32(1.1)
    wr, wi = ref.apply_mixer(re, im, n, beta, group=group)
    gr, gi = re, im
    for g0 in range(0, n, group):
        gr, gi = ref.apply_mixer_bits(gr, gi, n, g0, min(group, n - g0), beta)
    np.testing.assert_array_equal(np.asarray(gr), np.asarray(wr))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


# ------------------------------------------------- ops dispatch integrity --
def test_ops_dispatch_pallas_interpret_equals_xla():
    from repro.kernels import ops

    n = 8
    g = _graph(n, 0.5, seed=0)
    with ops.using_implementation("xla"):
        c_x = np.asarray(ops.cutvals(n, g.edges, g.weights))
    with ops.using_implementation("pallas_interpret"):
        c_p = np.asarray(ops.cutvals(n, g.edges, g.weights))
    assert ops.get_implementation() != "pallas_interpret"  # restored on exit
    np.testing.assert_allclose(c_p, c_x, rtol=1e-6)


def test_using_implementation_restores_on_error():
    from repro.kernels import ops

    before = ops.get_implementation()
    with pytest.raises(RuntimeError):
        with ops.using_implementation("pallas_interpret"):
            raise RuntimeError("boom")
    assert ops.get_implementation() == before


def test_ops_apply_layer_dispatch_matches_xla():
    """The engine's per-layer op: the pallas_interpret path (fused
    phase+first-group kernel + grouped mixer kernels) must agree with
    the XLA reference decomposition."""
    from repro.kernels import ops

    n, group = 9, 4
    g = _graph(n, 0.5, seed=9)
    cutv = ref.cutvals(n, g.edges, g.weights)
    key = jax.random.PRNGKey(7)
    k1, k2 = jax.random.split(key)
    re = jax.random.normal(k1, (2**n,), jnp.float32)
    im = jax.random.normal(k2, (2**n,), jnp.float32)
    with ops.using_implementation("xla"):
        wr, wi = ops.apply_layer(re, im, cutv, 0.4, 0.9, n, group=group)
    with ops.using_implementation("pallas_interpret"):
        gr, gi = ops.apply_layer(re, im, cutv, 0.4, 0.9, n, group=group)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(wr), atol=2e-5)
    np.testing.assert_allclose(np.asarray(gi), np.asarray(wi), atol=2e-5)


# ------------------------------------------- impl-keyed program caches --
def test_batch_program_cache_keys_on_implementation():
    """ROADMAP follow-up from PR 4: `solve_subgraph_batch_program` (the
    solve/service/pool solver) must key its cache on the active
    `kernels.ops` implementation — dispatch is a trace-time choice, so a
    program traced under one impl silently ignores
    `ops.using_implementation` forever after. Flipping impls must yield
    distinct cached programs; re-selecting an impl must return *its*
    program and reproduce its results bit-for-bit."""
    from repro.core import qaoa as qaoa_mod
    from repro.core.partition import partition_for_solver
    from repro.kernels import ops
    from repro.kernels import tuning

    qcfg = qaoa_mod.QAOAConfig(n_qubits=6, p_layers=2, opt_steps=4, top_k=2)
    g = _graph(16, 0.4, seed=21)
    part = partition_for_solver(g, 6)
    e, w, m = qaoa_mod.pad_subgraph_arrays(part.subgraphs, 6)

    p_x = qaoa_mod.solve_subgraph_batch_program(qcfg)
    r_x = p_x(e, w, m)
    with ops.using_implementation("pallas_interpret"):
        p_i = qaoa_mod.solve_subgraph_batch_program(qcfg)
        # same impl, same config: one compiled program (cache hit)
        assert qaoa_mod.solve_subgraph_batch_program(qcfg) is p_i
        r_i = p_i(e, w, m)
    # distinct impls: distinct programs, and flipping back returns the
    # original (the pre-fix bug: one shared program for every impl)
    assert p_x is not p_i
    assert qaoa_mod.solve_subgraph_batch_program(qcfg) is p_x
    r_x2 = qaoa_mod.solve_subgraph_batch_program(qcfg)(e, w, m)
    np.testing.assert_array_equal(
        np.asarray(r_x.bitstrings), np.asarray(r_x2.bitstrings)
    )
    np.testing.assert_array_equal(
        np.asarray(r_x.probs), np.asarray(r_x2.probs)
    )
    # the two impls agree semantically (per-candidate marginals to float32
    # tolerance; exact candidate picks may flip between prob ties)
    np.testing.assert_allclose(
        np.asarray(r_i.probs), np.asarray(r_x.probs), atol=1e-6
    )


def test_batch_program_interpret_dispatch_fires_pallas_kernels():
    """Under `pallas_interpret` the impl-keyed batch program must
    actually reach the Pallas kernels (trace-time dispatch proof), and
    the service path built on it must stay bit-identical to solo
    `core.solve` under the same flipped impl."""
    import repro.kernels.fused_layer as fused_mod
    from repro.core import solve
    from repro.kernels import ops
    from repro.service import SLA, ServiceConfig, SolveService

    calls = {"n": 0}
    orig = fused_mod.fused_phase_mixer_group

    def spy(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    fused_mod.fused_phase_mixer_group = spy
    try:
        with ops.using_implementation("pallas_interpret"):
            svc = SolveService(ServiceConfig(
                batch_slots=4, max_qubits=6, enable_cache=False,
                recalibrate=False,
            ))
            g = _graph(14, 0.4, seed=22)
            rid = svc.submit(g, SLA(deadline_s=30.0))
            svc.drain()
            r = svc.results[rid]
            assert calls["n"] > 0, "pallas dispatch never fired"
            solo = solve(g, r.plan.to_config())
            assert r.cut_value == solo.cut_value
            np.testing.assert_array_equal(r.assignment, solo.assignment)
    finally:
        fused_mod.fused_phase_mixer_group = orig


def test_solve_pool_program_cache_keys_on_implementation():
    """The pool stage's shard_map program keys on the impl too (a
    1-device `data` mesh keeps this in-process); both impls' pool
    results agree semantically."""
    from repro import compat
    from repro.core import distributed as dist
    from repro.core import qaoa as qaoa_mod
    from repro.core.partition import partition_for_solver
    from repro.kernels import ops
    from repro.kernels import tuning

    qcfg = qaoa_mod.QAOAConfig(n_qubits=6, p_layers=2, opt_steps=4, top_k=2)
    mesh = compat.make_mesh((1,), ("data",))
    off = tuning.state()
    build = qaoa_mod._solve_subgraph_batch_program
    p_x = build(qcfg, "xla", off, False, mesh, ("data",))
    p_i = build(qcfg, "pallas_interpret", off, False, mesh, ("data",))
    assert p_x is not p_i
    assert build(qcfg, "xla", off, False, mesh, ("data",)) is p_x

    g = _graph(16, 0.4, seed=23)
    part = partition_for_solver(g, 6)
    e, w, m = qaoa_mod.pad_subgraph_arrays(part.subgraphs, 6)
    r_x = dist.solve_pool(e, w, m, qcfg, mesh)
    with ops.using_implementation("pallas_interpret"):
        r_i = dist.solve_pool(e, w, m, qcfg, mesh)
    np.testing.assert_allclose(
        np.asarray(r_i.probs), np.asarray(r_x.probs), atol=1e-6
    )
