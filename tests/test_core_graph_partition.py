"""Unit + property tests: graph representation and CPP partitioning."""

import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.graph import Graph, cut_value, cut_value_batch, subgraph
from repro.core.partition import (
    _build_partition,
    alg1_ranges,
    balanced_ranges,
    connectivity_preserving_partition,
    partition_for_solver,
    random_partition,
)
from repro.core.qaoa import pad_subgraph_arrays


def test_graph_from_edges_padding():
    g = Graph.from_edges(4, [(0, 1), (1, 2)], pad_to=5)
    assert g.edges.shape == (5, 2)
    assert g.n_edges == 2
    assert float(g.total_weight()) == 2.0


def test_cut_value_simple():
    # triangle: best cut = 2
    g = Graph.from_edges(3, [(0, 1), (1, 2), (0, 2)])
    assert float(cut_value(g, jnp.array([0, 1, 0]))) == 2.0
    assert float(cut_value(g, jnp.array([0, 0, 0]))) == 0.0
    assert float(cut_value(g, jnp.array([1, 1, 1]))) == 0.0


def test_cut_value_batch_matches_single():
    g = Graph.erdos_renyi(12, 0.5, seed=0)
    rng = np.random.default_rng(0)
    batch = rng.integers(0, 2, size=(7, 12))
    vb = np.asarray(cut_value_batch(g, jnp.asarray(batch)))
    for i in range(7):
        assert vb[i] == pytest.approx(float(cut_value(g, jnp.asarray(batch[i]))))


def test_padding_edges_never_contribute():
    g1 = Graph.from_edges(4, [(0, 1)], pad_to=1)
    g2 = Graph.from_edges(4, [(0, 1)], pad_to=64)
    a = jnp.array([1, 0, 1, 0])
    assert float(cut_value(g1, a)) == float(cut_value(g2, a))


@given(
    n=st.integers(6, 60),
    m=st.integers(2, 6),
)
@settings(max_examples=40, deadline=None)
def test_balanced_ranges_properties(n, m):
    if n // m < 2:
        return
    ranges = balanced_ranges(n, m)
    assert len(ranges) == m
    assert ranges[0][0] == 0 and ranges[-1][1] == n
    sizes = [hi - lo for lo, hi in ranges]
    # adjacent ranges share exactly one vertex
    for (l0, h0), (l1, h1) in zip(ranges, ranges[1:]):
        assert l1 == h0 - 1
    # sizes differ by at most 1
    assert max(sizes) - min(sizes) <= 1


def test_alg1_ranges_paper_example_overflow():
    # documents the verbatim-Alg.1 defect: |V|=400, M=16 → last partition 40
    ranges = alg1_ranges(400, 16)
    sizes = [hi - lo for lo, hi in ranges]
    assert sizes[-1] == 40  # violates the 26-qubit cap the paper assumes
    bsizes = [hi - lo for lo, hi in balanced_ranges(400, 16)]
    assert max(bsizes) <= 27


@given(n=st.integers(10, 80), p=st.floats(0.1, 0.9), m=st.integers(2, 5))
@settings(max_examples=25, deadline=None)
def test_partition_covers_every_edge_exactly_once(n, p, m):
    if n // m < 2:
        return
    g = Graph.erdos_renyi(n, p, seed=42)
    part = connectivity_preserving_partition(g, m)
    total_sub = sum(sg.n_edges for sg in part.subgraphs)
    assert total_sub + part.inter_edges.shape[0] == g.n_edges
    # every subgraph respects its range width
    for sg, (lo, hi) in zip(part.subgraphs, part.ranges):
        assert sg.n == hi - lo
        e = np.asarray(sg.edges)[: sg.n_edges]
        if e.size:
            assert e.min() >= 0 and e.max() < sg.n


def test_partition_for_solver_respects_qubit_cap():
    for n in (50, 100, 257, 400, 1001):
        g = Graph.erdos_renyi(n, 0.3, seed=1)
        part = partition_for_solver(g, 26)
        assert max(part.sizes) <= 26
        assert part.m >= int(np.ceil(n / 25))


def test_subgraph_extraction():
    g = Graph.from_edges(6, [(0, 1), (1, 2), (2, 3), (3, 4), (4, 5), (0, 5)])
    sg = subgraph(g, 1, 4)  # vertices 1,2,3 → edges (1,2),(2,3) relabelled
    assert sg.n == 3
    assert sg.n_edges == 2


def test_random_partition_preserves_cut_distribution():
    g = Graph.erdos_renyi(30, 0.4, seed=3)
    part = random_partition(g, 3, seed=7)
    # relabelled graph has the same edge count and weights
    assert part.graph.n_edges == g.n_edges
    assert float(part.graph.total_weight()) == pytest.approx(
        float(g.total_weight())
    )


def _masked_partition(graph, ranges):
    """The partition's former build, kept as the oracle: four masks over
    the whole edge list once per range, each subgraph through
    `Graph.from_edges`. Returns (subgraphs, inter_edges, inter_weights)."""
    e = np.asarray(graph.edges)[: graph.n_edges]
    w = np.asarray(graph.weights)[: graph.n_edges]
    covered = np.zeros(e.shape[0], dtype=bool)
    pieces = []
    for lo, hi in ranges:
        inside = ((e[:, 0] >= lo) & (e[:, 0] < hi)
                  & (e[:, 1] >= lo) & (e[:, 1] < hi))
        covered |= inside
        pieces.append((hi - lo, e[inside] - lo, w[inside]))
    pad = max(max(el.shape[0] for _, el, _ in pieces), 1)
    subgraphs = [Graph.from_edges(n, el, wl, pad_to=pad)
                 for n, el, wl in pieces]
    return (subgraphs, e[~covered].astype(np.int32),
            w[~covered].astype(np.float32))


def _shuffled(n, e, w, seed, pad=0):
    """The edges in random order, about half of them written v, u."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(e))
    e, w = np.array(e[perm]), w[perm]
    flip = rng.random(len(e)) < 0.5
    e[flip] = e[flip][:, ::-1]
    return Graph.from_edges(n, e, w, pad_to=len(e) + pad)


def _stratified(n, m, per_block, per_level, seed):
    """Edges drawn block by block over the chain of `balanced_ranges`: a
    fixed count inside each range and between each pair of adjacent
    ranges, integer weights; the benchmark draws its instances so."""
    rng = np.random.default_rng(seed)
    ranges = balanced_ranges(n, m)
    rows = [rng.integers(lo, hi, size=(per_block, 2)) for lo, hi in ranges]
    for (lo0, hi0), (_, hi1) in zip(ranges, ranges[1:]):
        rows.append(np.stack([rng.integers(lo0, hi0 - 1, per_level),
                              rng.integers(hi0, hi1, per_level)], axis=1))
    e = np.concatenate(rows)
    e = e[e[:, 0] != e[:, 1]]
    return _shuffled(n, e, rng.integers(1, 4, len(e)).astype(np.float32),
                     seed)


def _with_loops(n, seed):
    """G(n, 0.3), weighted, plus a self-loop on every vertex, so on every
    shared vertex of the chain."""
    g = Graph.erdos_renyi_weighted(n, 0.3, seed=seed)
    e = np.concatenate([np.asarray(g.edges)[: g.n_edges],
                        np.repeat(np.arange(n)[:, None], 2, axis=1)])
    w = np.concatenate([np.asarray(g.weights)[: g.n_edges],
                        np.full(n, 0.5, np.float32)])
    return _shuffled(n, e, w, seed)


def _swapped(g, seed):
    """The same graph, shuffled and half reversed, with padding rows."""
    return _shuffled(g.n, np.asarray(g.edges)[: g.n_edges],
                     np.asarray(g.weights)[: g.n_edges], seed, pad=7)


PARTITION_CASES = {
    "gnp_m1": lambda: (Graph.erdos_renyi(20, 0.4, seed=0), balanced_ranges(20, 1)),
    "gnp_m2": lambda: (Graph.erdos_renyi(40, 0.3, seed=1), balanced_ranges(40, 2)),
    "gnp_m7": lambda: (Graph.erdos_renyi(90, 0.2, seed=2), balanced_ranges(90, 7)),
    "gnp_sparse_m25": lambda: (Graph.erdos_renyi(300, 0.02, seed=3),
                               balanced_ranges(300, 25)),
    "u_gt_v_padded": lambda: (_swapped(Graph.erdos_renyi(70, 0.25, seed=4), 4),
                              balanced_ranges(70, 6)),
    "weighted": lambda: (Graph.erdos_renyi_weighted(80, 0.3, seed=5),
                         balanced_ranges(80, 5)),
    "pm1": lambda: (Graph.spin_glass(64, 0.3, seed=6), balanced_ranges(64, 4)),
    "alg1_uneven_last": lambda: (Graph.erdos_renyi(103, 0.2, seed=7),
                                 alg1_ranges(103, 8)),
    "self_loops_on_shared": lambda: (_with_loops(36, seed=8),
                                     balanced_ranges(36, 4)),
    "no_edges": lambda: (Graph.from_edges(12, []), balanced_ranges(12, 3)),
    "stratified": lambda: (_stratified(120, 10, 20, 6, seed=9),
                           balanced_ranges(120, 10)),
    "random_partition": "random",
}


def _assert_same_partition(part, graph, ranges):
    subs, inter_e, inter_w = _masked_partition(graph, ranges)
    assert part.ranges == list(ranges)
    assert part.sizes == [hi - lo for lo, hi in ranges]
    assert len(part.subgraphs) == len(subs)
    for got, want in zip(part.subgraphs, subs):
        assert (got.n, got.n_edges) == (want.n, want.n_edges)
        for a, b in ((got.edges, want.edges), (got.weights, want.weights)):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)
    for a, b in ((part.inter_edges, inter_e), (part.inter_weights, inter_w)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    n_q = max(part.sizes)
    for a, b in zip(pad_subgraph_arrays(part.subgraphs, n_q),
                    pad_subgraph_arrays(subs, n_q)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("case", sorted(PARTITION_CASES))
def test_sorted_pass_partition_matches_the_masked_oracle(case):
    """The one sorted pass gives the per-range masks' subgraphs, padding,
    inter-partition edges and packed pool batch, bit for bit."""
    if PARTITION_CASES[case] == "random":
        part = random_partition(Graph.erdos_renyi(60, 0.3, seed=10), 5, seed=3)
        _assert_same_partition(part, part.graph, balanced_ranges(60, 5))
        return
    graph, ranges = PARTITION_CASES[case]()
    _assert_same_partition(_build_partition(graph, ranges), graph, ranges)


def test_self_loop_on_a_shared_vertex_lies_in_both_ranges():
    g = Graph.from_edges(5, [(2, 2), (0, 1), (3, 4)])
    part = _build_partition(g, [(0, 3), (2, 5)])
    assert [sg.n_edges for sg in part.subgraphs] == [2, 2]
    np.testing.assert_array_equal(np.asarray(part.subgraphs[0].edges),
                                  [[2, 2], [0, 1]])
    np.testing.assert_array_equal(np.asarray(part.subgraphs[1].edges),
                                  [[0, 0], [1, 2]])
    assert part.inter_edges.shape == (0, 2)


def test_partition_subgraphs_live_on_the_host():
    """The subgraphs' leaves are NumPy rows, not device arrays, so packing
    the pool's batch copies nothing back from a device."""
    import jax

    part = partition_for_solver(Graph.erdos_renyi(60, 0.3, seed=11), 8)
    for sg in part.subgraphs:
        for leaf in (sg.edges, sg.weights):
            assert isinstance(leaf, np.ndarray)
            assert not isinstance(leaf, jax.Array)
