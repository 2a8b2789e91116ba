"""Connectivity-Preserving Partitioning (paper Alg. 1) and baselines.

The CPP algorithm splits vertex indices into M contiguous ranges where
adjacent ranges overlap in exactly one vertex (the "shared node"). The base
partition size is s = floor(|V|/M) - 1 and range i covers
[i*s, i*s + s + 1), with the last range absorbing the remainder — a direct
transcription of Alg. 1. Building the subgraphs is one pass over the
edges and one stable sort of their subgraph keys, O(|E| log |E|) at worst
(a radix sort of 16-bit keys below 65,536 subgraphs). The subgraphs are
rows of two packed host arrays; nothing is uploaded to a device.

The partition output also records the *inter-partition* edges (the edges
dropped from every subgraph), which the merge phase re-scores globally —
paper §3.4 eq. Cut(B*).
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from repro.core.graph import Graph
from repro.obs import trace as trace_mod


@dataclasses.dataclass(frozen=True)
class Partition:
    """Result of partitioning a graph into a chain of subgraphs.

    Attributes:
      subgraphs: list of induced subgraphs, each with *local* vertex labels;
        their leaves are NumPy rows of one (M, E_pad) packed host batch.
      ranges: list of (lo, hi) global vertex ranges per subgraph;
        ranges[i].hi - 1 == ranges[i+1].lo is the shared vertex.
      sizes: number of vertices per subgraph.
      inter_edges: (E_x, 2) int32 global-index edges not inside any subgraph.
      inter_weights: (E_x,) float32.
      graph: the original graph.
    """

    subgraphs: List[Graph]
    ranges: List[tuple]
    sizes: List[int]
    inter_edges: np.ndarray
    inter_weights: np.ndarray
    graph: Graph

    @property
    def m(self) -> int:
        return len(self.subgraphs)


def alg1_ranges(n: int, m: int) -> List[tuple]:
    """Paper Alg. 1, verbatim: s = floor(|V|/M) - 1, range i covers
    [i*s, i*s + s + 1), last range absorbs the remainder.

    NOTE: the verbatim algorithm can overflow the last partition well past
    ceil(|V|/M) (e.g. |V|=400, M=16 → last size 40 > 26 qubits), violating
    the paper's own QAOA-compatibility constraint (2). Kept for fidelity
    experiments; `balanced_ranges` below is the default.
    """
    if m < 1:
        raise ValueError("need at least one partition")
    if m == 1:
        return [(0, n)]
    s = n // m - 1
    if s < 1:
        raise ValueError(f"partition size too small: |V|={n}, M={m}")
    ranges = []
    for i in range(1, m + 1):
        start = (i - 1) * s
        end = n if i == m else start + s + 1
        ranges.append((start, end))
    return ranges


def balanced_ranges(n: int, m: int) -> List[tuple]:
    """Alg. 1 with the remainder spread across partitions instead of dumped
    on the last one: every range gets floor(n/m) or ceil(n/m) fresh vertices
    (+1 shared vertex for ranges after the first), so sizes differ by at
    most 1 and the M = ceil(|V|/(N-1)) choice really honors |V_i| <= N."""
    if m < 1:
        raise ValueError("need at least one partition")
    if m == 1:
        return [(0, n)]
    q, r = divmod(n, m)
    if q < 1 or (q == 1 and r == 0 and m > 1):
        raise ValueError(f"partition size too small: |V|={n}, M={m}")
    ranges = []
    pos = 0
    for i in range(m):
        fresh = q + (1 if i < r else 0)
        if i == 0:
            lo, hi = 0, fresh
        else:
            lo, hi = pos - 1, pos - 1 + fresh + 1
        ranges.append((lo, hi))
        pos = hi
    assert ranges[-1][1] == n, ranges
    return ranges


def connectivity_preserving_partition(graph: Graph, m: int) -> Partition:
    """Paper Alg. 1: contiguous ranges with one shared vertex per boundary."""
    return _build_partition(graph, balanced_ranges(graph.n, m))


def random_partition(graph: Graph, m: int, seed: int) -> Partition:
    """QAOA²-style randomized partitioning (baseline): random vertex order,
    then contiguous ranges over the shuffled labels. Returned subgraphs use
    the same chain/shared-vertex contract as CPP so the merge phase is
    interchangeable; the relabelling permutation is applied to the graph."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(graph.n).astype(np.int32)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(graph.n, dtype=np.int32)
    e = np.asarray(graph.edges)[: graph.n_edges]
    w = np.asarray(graph.weights)[: graph.n_edges]
    relabelled = Graph.from_edges(graph.n, inv[e], w, pad_to=graph.edges.shape[0])
    return _build_partition(relabelled, balanced_ranges(graph.n, m))


def partition_for_solver(graph: Graph, max_qubits: int) -> Partition:
    """Input-dependent parameter selection (paper §4.2):
    M = ceil(|V| / (N - 1)) so every subgraph fits an N-qubit solver."""
    if graph.n <= max_qubits:
        return connectivity_preserving_partition(graph, 1)
    m = int(np.ceil(graph.n / (max_qubits - 1)))
    while True:
        ranges = balanced_ranges(graph.n, m)
        if max(hi - lo for lo, hi in ranges) <= max_qubits:
            break
        m += 1
    part = connectivity_preserving_partition(graph, m)
    assert max(part.sizes) <= max_qubits, (
        f"partition produced subgraph of {max(part.sizes)} > N={max_qubits}"
    )
    return part


def _build_partition(graph: Graph, ranges: List[tuple]) -> Partition:
    """Assign every edge to the subgraph whose range holds both endpoints,
    in one pass. Ranges are contiguous, sorted, and any two share at most
    one vertex, so an edge (a, b), a <= b, can only lie in the last range
    with lo <= a, and does iff b < hi there; else it is an inter-partition
    edge. A self-loop on a shared vertex lies in both ranges around it, so
    it is counted twice. Each subgraph's edges, relabelled to local vertex
    labels and zero-padded to the largest subgraph's count, keep the
    graph's edge order, as do the inter-partition edges."""
    e = np.asarray(graph.edges)[: graph.n_edges]
    w = np.asarray(graph.weights)[: graph.n_edges]
    m = len(ranges)
    lo = np.asarray([r[0] for r in ranges], dtype=np.int32)
    hi = np.asarray([r[1] for r in ranges], dtype=np.int32)

    a = np.minimum(e[:, 0], e[:, 1])
    b = np.maximum(e[:, 0], e[:, 1])
    # the last range starting at or before each vertex, looked up per edge
    last = np.searchsorted(lo, np.arange(graph.n), side="right") - 1
    i = last[a]
    key = np.where(b < hi[i], i, m).astype(np.min_scalar_type(m))
    # a self-loop on the shared vertex lo[i] also lies in range i - 1: it
    # enters the sort twice, its first copy keyed i - 1, so a stable sort
    # keeps every subgraph's edges in the graph's order
    twice = (a == b) & (a == lo[i]) & (i > 0)
    copies = 1 + twice
    src = np.repeat(np.arange(e.shape[0]), copies)
    key = np.repeat(key, copies)
    key[(np.cumsum(copies) - copies)[twice]] -= 1

    order = np.argsort(key, kind="stable")
    key, src = key[order], src[order]
    counts = np.bincount(key, minlength=m + 1)
    e_pad = max(int(counts[:m].max()), 1)
    first = np.cumsum(counts) - counts
    inside = key < m
    sub, idx = key[inside].astype(np.intp), src[inside]
    slot = np.arange(sub.shape[0]) - first[sub]
    edges = np.zeros((m, e_pad, 2), dtype=np.int32)
    weights = np.zeros((m, e_pad), dtype=np.float32)
    edges[sub, slot] = e[idx] - lo[sub, None]
    weights[sub, slot] = w[idx]
    inter = src[~inside]
    trace_mod.get_tracer().annotate(m=m, e_pad=e_pad,
                                    inter_edges=int(inter.shape[0]))
    return Partition(
        subgraphs=[
            Graph(n=h - l, edges=edges[j], weights=weights[j],
                  n_edges=int(counts[j]))
            for j, (l, h) in enumerate(ranges)
        ],
        ranges=list(ranges),
        sizes=[h - l for l, h in ranges],
        inter_edges=e[inter].astype(np.int32),
        inter_weights=w[inter].astype(np.float32),
        graph=graph,
    )


def split_linear(part: Partition, linear) -> List[np.ndarray]:
    """Assign each vertex's linear term to exactly one subproblem.

    Adjacent ranges overlap in one shared vertex, so a naive per-range slice
    would double-count its ``h_v``. Vertex v's term goes to its *first*
    covering range (the same first-coverage rule `merge.build_merge_plan`
    uses for vertices); later ranges see h = 0 at the shared position.
    ``linear`` is indexed in ``part.graph``'s vertex labels; returns one
    (size_i,) float32 array per subgraph in local labels.
    """
    lin = np.asarray(linear, dtype=np.float32)
    assert lin.shape == (part.graph.n,), (lin.shape, part.graph.n)
    hi_arr = np.asarray([hi for _, hi in part.ranges], dtype=np.int64)
    level = np.searchsorted(hi_arr, np.arange(part.graph.n), side="right")
    level = np.clip(level, 0, part.m - 1)
    out: List[np.ndarray] = []
    for i, (lo, hi) in enumerate(part.ranges):
        li = np.zeros(hi - lo, dtype=np.float32)
        idx = np.nonzero(level == i)[0]
        li[idx - lo] = lin[idx]
        out.append(li)
    return out


def stitch_assignments(part: Partition, local_bits: List[np.ndarray]) -> np.ndarray:
    """Concatenate per-subgraph 0/1 assignments into a global assignment.

    Adjacent subgraphs overlap in one vertex; the caller must have oriented
    each local bitstring so the shared vertex agrees (merge.py guarantees
    this). The later subgraph's value wins on the overlap (they're equal by
    construction).
    """
    out = np.zeros(part.graph.n, dtype=np.int8)
    for (lo, hi), bits in zip(part.ranges, local_bits):
        out[lo:hi] = np.asarray(bits, dtype=np.int8)[: hi - lo]
    return out
