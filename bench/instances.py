"""Seeded Max-Cut instances whose shapes do not depend on the seed.

G(n, p) stratified by the solver's blocks: the vertices fall into the
chain of ranges that a solver of ``n_qubits`` qubits cuts them into
(M = ceil(n / (N - 1)) contiguous ranges, sizes differing by at most one,
adjacent ranges sharing one vertex; ParaQAOA Alg. 1 with the remainder
spread). Every vertex pair (u < v) belongs to the *level* of the range that
first covers v. Each level receives exactly its expected number of edges,
round(p * pairs), and inside it the pairs of the range itself receive a
count taken from a fixed multiset of Binomial(C(size, 2), p) quantiles,
dealt to the ranges in an order drawn from the seed. Which pairs are edges
is uniform, from the seed.

So every seed yields the same edge total, the same largest block and the
same largest level: the solver programs keep one shape per configuration
and compile once, and the seed changes only which pairs are joined and
which block holds which count. Plain G(n, p) varies all three maxima from
seed to seed.
"""

from __future__ import annotations

import math

import numpy as np
from scipy import stats


def solver_ranges(n: int, n_qubits: int) -> list[tuple[int, int]]:
    """The chain of (lo, hi) vertex ranges a solver of ``n_qubits`` uses.

    M = ceil(n / (N - 1)), raised until every range holds at most N
    vertices; each range has floor(n/M) or ceil(n/M) new vertices, and
    each after the first also holds the last vertex of its predecessor.
    """
    if n <= n_qubits:
        return [(0, n)]
    m = math.ceil(n / (n_qubits - 1))
    while True:
        q, r = divmod(n, m)
        ranges, pos = [], 0
        for i in range(m):
            fresh = q + (1 if i < r else 0)
            lo = 0 if i == 0 else pos - 1
            hi = lo + fresh + (0 if i == 0 else 1)
            ranges.append((lo, hi))
            pos = hi
        if max(hi - lo for lo, hi in ranges) <= n_qubits:
            return ranges
        m += 1


def _quantile_counts(pairs: int, p: float, k: int) -> np.ndarray:
    """k edge counts at the midpoints of k equal slices of Binomial(pairs, p)."""
    q = (np.arange(k) + 0.5) / k
    return stats.binom.ppf(q, pairs, p).astype(np.int64)


def stratified_erdos_renyi(n: int, p: float, n_qubits: int, seed: int):
    """(edges (E, 2) int32 with u < v, weights (E,) float32 of ones)."""
    rng = np.random.default_rng(seed)
    ranges = solver_ranges(n, n_qubits)
    m = len(ranges)
    sizes = np.asarray([hi - lo for lo, hi in ranges])
    # the first vertex a level owns: range 0 owns all of its vertices,
    # every later range all but the one it shares with its predecessor
    first = np.asarray([0] + [lo + 1 for lo, _ in ranges[1:]])
    intra = sizes * (sizes - 1) // 2
    inter = np.asarray([lo * (hi - f) for (lo, hi), f in zip(ranges, first)])
    total = np.rint(p * (intra + inter)).astype(np.int64)

    # intra-range counts: deterministic where a level is too small to take
    # any count the multiset could deal it (level 0, and the first few
    # levels of a sparse graph); elsewhere quantiles, dealt by the seed
    # within each size class
    count = np.rint(total * intra / np.maximum(intra + inter, 1)).astype(
        np.int64)
    count[0] = total[0]
    for size in np.unique(sizes[1:]):
        pairs = size * (size - 1) // 2
        cap = int(stats.binom.ppf(1.0 - 0.5 / m, pairs, p))
        levels = [l for l in range(1, m)
                  if sizes[l] == size and total[l] >= cap]
        if levels:
            deal = _quantile_counts(pairs, p, len(levels))
            count[levels] = rng.permutation(deal)

    edges = []
    for l, (lo, hi) in enumerate(ranges):
        size, f = hi - lo, first[l]
        iu, ju = np.triu_indices(size, k=1)
        pick = rng.choice(intra[l], size=count[l], replace=False)
        edges.append(np.stack([iu[pick] + lo, ju[pick] + lo], axis=1))
        n_inter = total[l] - count[l]
        if n_inter:
            fresh = hi - f
            k = rng.choice(inter[l], size=n_inter, replace=False)
            edges.append(np.stack([k // fresh, f + k % fresh], axis=1))
    e = np.concatenate(edges).astype(np.int32)
    return e, np.ones(e.shape[0], dtype=np.float32)


FAMILIES = {"erdos_renyi_stratified": stratified_erdos_renyi}


def build(instance: dict, n_qubits: int, seed: int):
    """The instance a configuration's ``instance`` entry describes."""
    params = {k: v for k, v in instance.items() if k != "family"}
    return FAMILIES[instance["family"]](n_qubits=n_qubits, seed=seed, **params)
