"""Graph representation for Max-Cut instances.

Graphs are stored as padded edge lists so every downstream JAX computation
is shape-stable: ``edges`` is ``(E_pad, 2) int32``, ``weights`` is
``(E_pad,) float32`` with zero weight on padding rows. Padding rows point at
vertex 0 on both endpoints, which contributes nothing to any cut because the
XOR of identical endpoints is zero *and* the weight is zero — both guards
hold so either representation change stays safe.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Graph:
    """A padded, undirected, weighted graph.

    Attributes:
      n: number of vertices (static).
      edges: (E_pad, 2) int32 vertex indices, padding rows are (0, 0).
      weights: (E_pad,) float32, zero on padding rows.
      n_edges: true (unpadded) edge count, static python int.

    ``edges`` and ``weights`` may be host (NumPy) arrays, as the
    partition's subgraphs are; `from_edges` puts them on the device.
    """

    n: int
    edges: jnp.ndarray
    weights: jnp.ndarray
    n_edges: int

    # -- pytree plumbing (n / n_edges are static aux data) ------------------
    def tree_flatten(self):
        return (self.edges, self.weights), (self.n, self.n_edges)

    @classmethod
    def tree_unflatten(cls, aux, children):
        edges, weights = children
        n, n_edges = aux
        return cls(n=n, edges=edges, weights=weights, n_edges=n_edges)

    # -- constructors --------------------------------------------------------
    @classmethod
    def from_edges(
        cls,
        n: int,
        edge_list: Iterable[tuple[int, int]],
        weights: Sequence[float] | None = None,
        pad_to: int | None = None,
    ) -> "Graph":
        edge_arr = np.asarray(list(edge_list), dtype=np.int32).reshape(-1, 2)
        m = edge_arr.shape[0]
        w = (
            np.ones((m,), dtype=np.float32)
            if weights is None
            else np.asarray(weights, dtype=np.float32)
        )
        if pad_to is None:
            pad_to = m
        if pad_to < m:
            raise ValueError(f"pad_to={pad_to} < n_edges={m}")
        ep = np.zeros((pad_to, 2), dtype=np.int32)
        wp = np.zeros((pad_to,), dtype=np.float32)
        ep[:m] = edge_arr
        wp[:m] = w
        return cls(n=n, edges=jnp.asarray(ep), weights=jnp.asarray(wp), n_edges=m)

    @classmethod
    def erdos_renyi(cls, n: int, p: float, seed: int, pad_to: int | None = None) -> "Graph":
        """Erdős–Rényi G(n, p), matching the paper's instance generator."""
        rng = np.random.default_rng(seed)
        iu, ju = np.triu_indices(n, k=1)
        mask = rng.random(iu.shape[0]) < p
        edge_arr = np.stack([iu[mask], ju[mask]], axis=1).astype(np.int32)
        g = cls.from_edges(n, edge_arr, pad_to=pad_to)
        return g

    @classmethod
    def erdos_renyi_weighted(
        cls,
        n: int,
        p: float,
        seed: int,
        pad_to: int | None = None,
        low: float = 0.1,
        high: float = 1.0,
    ) -> "Graph":
        """G(n, p) with edge weights drawn uniformly from [low, high).

        Same topology as :meth:`erdos_renyi` for the same seed — the weight
        draw consumes the generator *after* the edge mask, so weighted and
        unit-weight instances share an edge set.
        """
        rng = np.random.default_rng(seed)
        iu, ju = np.triu_indices(n, k=1)
        mask = rng.random(iu.shape[0]) < p
        edge_arr = np.stack([iu[mask], ju[mask]], axis=1).astype(np.int32)
        w = rng.uniform(low, high, size=edge_arr.shape[0]).astype(np.float32)
        return cls.from_edges(n, edge_arr, w, pad_to=pad_to)

    @classmethod
    def spin_glass(cls, n: int, p: float, seed: int, pad_to: int | None = None) -> "Graph":
        """G(n, p) topology with ±1 couplings (Edwards–Anderson spin glass)."""
        rng = np.random.default_rng(seed)
        iu, ju = np.triu_indices(n, k=1)
        mask = rng.random(iu.shape[0]) < p
        edge_arr = np.stack([iu[mask], ju[mask]], axis=1).astype(np.int32)
        w = rng.choice(np.asarray([-1.0, 1.0], dtype=np.float32), size=edge_arr.shape[0])
        return cls.from_edges(n, edge_arr, w.astype(np.float32), pad_to=pad_to)

    # -- basic quantities ----------------------------------------------------
    def total_weight(self) -> jnp.ndarray:
        return jnp.sum(self.weights)

    def dense_adjacency(self) -> jnp.ndarray:
        """(n, n) float32 symmetric adjacency. Only for small graphs."""
        a = jnp.zeros((self.n, self.n), dtype=jnp.float32)
        i, j = self.edges[:, 0], self.edges[:, 1]
        a = a.at[i, j].add(self.weights)
        a = a.at[j, i].add(self.weights)
        # padding rows add weight 0 at (0, 0): harmless.
        return a

    def degree(self) -> jnp.ndarray:
        d = jnp.zeros((self.n,), dtype=jnp.float32)
        d = d.at[self.edges[:, 0]].add(self.weights)
        d = d.at[self.edges[:, 1]].add(self.weights)
        return d


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Problem:
    """A diagonal-cost objective over ``n`` binary variables.

    The solver maximizes

        ``sum_{(u,v)} w_uv * (x_u XOR x_v)  +  sum_v h_v * x_v  +  offset``

    over assignments ``x in {0,1}^n``. Plain Max-Cut is the ``h = 0,
    offset = 0`` special case; arbitrary QUBOs and penalty-encoded MIS map
    onto the same (quadratic XOR + linear) form via the identity
    ``x_u * x_v = (x_u + x_v - (x_u XOR x_v)) / 2``. Every kernel and merge
    path scores the *internal* objective (quadratic + linear); the constant
    ``offset`` is applied only at reporting time (:func:`problem_value`).

    Attributes:
      graph: quadratic part as a padded XOR edge list.
      linear: (n,) float32 per-vertex linear coefficients ``h_v``.
      offset: constant term, static python float.
      kind: provenance tag ("maxcut" | "qubo" | "mis"), static.
    """

    graph: Graph
    linear: jnp.ndarray
    offset: float
    kind: str

    def tree_flatten(self):
        return (self.graph, self.linear), (self.offset, self.kind)

    @classmethod
    def tree_unflatten(cls, aux, children):
        graph, linear = children
        offset, kind = aux
        return cls(graph=graph, linear=linear, offset=offset, kind=kind)

    @property
    def n(self) -> int:
        return self.graph.n

    @property
    def has_linear(self) -> bool:
        """True when any linear coefficient is nonzero (host-side check)."""
        return bool(np.any(np.asarray(self.linear)))

    # -- constructors --------------------------------------------------------
    @classmethod
    def maxcut(cls, graph: Graph) -> "Problem":
        """Wrap a weighted Max-Cut instance (zero linear terms, zero offset)."""
        return cls(
            graph=graph,
            linear=jnp.zeros((graph.n,), dtype=jnp.float32),
            offset=0.0,
            kind="maxcut",
        )

    @classmethod
    def qubo(
        cls,
        n: int,
        quad_edges: Iterable[tuple[int, int]],
        quad_coeffs: Sequence[float],
        linear: Sequence[float] | None = None,
        offset: float = 0.0,
        pad_to: int | None = None,
    ) -> "Problem":
        """Maximize ``sum_{i<j} Q_ij x_i x_j + sum_i h_i x_i + offset``.

        Conversion: ``x_i x_j = (x_i + x_j - (x_i XOR x_j)) / 2`` turns each
        quadratic coefficient ``Q_ij`` into XOR edge weight ``-Q_ij / 2``
        plus ``+Q_ij / 2`` on the linear term of both endpoints.
        """
        e = np.asarray(list(quad_edges), dtype=np.int32).reshape(-1, 2)
        q = np.asarray(quad_coeffs, dtype=np.float64).reshape(-1)
        if e.shape[0] != q.shape[0]:
            raise ValueError(f"{e.shape[0]} quad edges but {q.shape[0]} coefficients")
        h = np.zeros((n,), dtype=np.float64)
        if linear is not None:
            h += np.asarray(linear, dtype=np.float64)
        np.add.at(h, e[:, 0], q / 2.0)
        np.add.at(h, e[:, 1], q / 2.0)
        g = Graph.from_edges(n, e, (-q / 2.0).astype(np.float32), pad_to=pad_to)
        return cls(
            graph=g,
            linear=jnp.asarray(h.astype(np.float32)),
            offset=float(offset),
            kind="qubo",
        )

    @classmethod
    def mis(cls, graph: Graph, penalty: float = 2.0) -> "Problem":
        """Maximum independent set on ``graph`` via the penalty QUBO.

        Maximize ``sum_i x_i - P * sum_{(i,j) in E} x_i x_j`` with
        ``P >= 2``: any edge inside the chosen set costs more than the two
        vertices gain, so the optimum is a maximum independent set. Edge
        weights of ``graph`` are ignored — it is a conflict graph. In XOR
        form: edge weight ``+P/2``, ``h_i = 1 - P * deg_i / 2``.
        """
        if penalty < 2.0:
            raise ValueError(f"penalty={penalty} < 2 does not guarantee independence")
        e = np.asarray(graph.edges)[: graph.n_edges]
        q = np.full((graph.n_edges,), -float(penalty))
        p = cls.qubo(graph.n, e, q, linear=np.ones((graph.n,)),
                     pad_to=graph.edges.shape[0])
        return dataclasses.replace(p, kind="mis")


def as_problem(obj: Graph | Problem) -> Problem:
    """Normalize a Graph (treated as Max-Cut) or Problem to a Problem."""
    if isinstance(obj, Problem):
        return obj
    return Problem.maxcut(obj)


def problem_value(problem: Problem, assignment: jnp.ndarray) -> jnp.ndarray:
    """Full objective (quadratic + linear + offset) of one 0/1 assignment."""
    x = assignment.astype(problem.linear.dtype)
    return cut_value(problem.graph, assignment) + problem.linear @ x + problem.offset


def problem_value_batch(problem: Problem, assignments: jnp.ndarray) -> jnp.ndarray:
    """Full objective for a batch of 0/1 assignments, shape (B, n) → (B,)."""
    x = assignments.astype(problem.linear.dtype)
    return cut_value_batch(problem.graph, assignments) + x @ problem.linear + problem.offset


def independent_set_violations(graph: Graph, assignment: np.ndarray) -> int:
    """Number of (unpadded) edges with both endpoints selected. Host-side."""
    e = np.asarray(graph.edges)[: graph.n_edges]
    x = np.asarray(assignment).astype(np.int64)
    return int(np.sum(x[e[:, 0]] * x[e[:, 1]]))


def cut_value(graph: Graph, assignment: jnp.ndarray) -> jnp.ndarray:
    """Cut value of one 0/1 assignment vector of shape (n,)."""
    s = assignment.astype(jnp.int32)
    crossed = s[graph.edges[:, 0]] ^ s[graph.edges[:, 1]]
    return jnp.sum(graph.weights * crossed.astype(graph.weights.dtype))


def cut_value_batch(graph: Graph, assignments: jnp.ndarray) -> jnp.ndarray:
    """Cut values for a batch of 0/1 assignments, shape (B, n) → (B,)."""
    s = assignments.astype(jnp.int32)
    crossed = s[:, graph.edges[:, 0]] ^ s[:, graph.edges[:, 1]]
    return crossed.astype(graph.weights.dtype) @ graph.weights


def subgraph(graph: Graph, lo: int, hi: int, pad_to: int | None = None) -> Graph:
    """Induced subgraph on the contiguous vertex range [lo, hi).

    Host-side (numpy) — partitioning is preprocessing, as in the paper.
    Vertices are relabelled to [0, hi-lo).
    """
    e = np.asarray(graph.edges)[: graph.n_edges]
    w = np.asarray(graph.weights)[: graph.n_edges]
    m = (e[:, 0] >= lo) & (e[:, 0] < hi) & (e[:, 1] >= lo) & (e[:, 1] < hi)
    sub_e = e[m] - lo
    return Graph.from_edges(hi - lo, sub_e, w[m], pad_to=pad_to)


def networkx_to_graph(nx_graph, pad_to: int | None = None) -> Graph:
    """Convert a networkx graph (integer-labelled 0..n-1) to a Graph."""
    n = nx_graph.number_of_nodes()
    edges, weights = [], []
    for u, v, data in nx_graph.edges(data=True):
        edges.append((u, v))
        weights.append(float(data.get("weight", 1.0)))
    return Graph.from_edges(n, edges, weights, pad_to=pad_to)
