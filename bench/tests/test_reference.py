"""The reference merge against brute force over every choice of candidates."""

import itertools

import numpy as np
import pytest

import instances
import reference as ref


def brute_force(n, edges, weights, ranges, candidates):
    """Best host cut over every candidate per range, each oriented so the
    vertex shared with the range before keeps its value."""
    best = -np.inf
    for choice in itertools.product(range(candidates.shape[1]),
                                     repeat=len(ranges)):
        a = np.zeros(n, np.int64)
        for i, ((lo, hi), c) in enumerate(zip(ranges, choice)):
            bits = (candidates[i, c] >> np.arange(hi - lo)) & 1
            if i and bits[0] != a[lo]:
                bits = 1 - bits
            a[lo:hi] = bits
        best = max(best, ref.host_cut(edges, weights, a))
    return best


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_exhaustive_beam_is_brute_force(seed):
    n, n_qubits, k = 24, 6, 2
    edges, weights = instances.stratified_erdos_renyi(n, 0.4, n_qubits, seed)
    ranges = instances.solver_ranges(n, n_qubits)
    rng = np.random.default_rng(seed)
    cands = np.stack([rng.choice(2**(hi - lo), k, replace=False)
                      for lo, hi in ranges])
    width = 2 * k**len(ranges)
    assign, score, _ = ref.merge_beam(n, edges, weights, ranges, cands, width)
    assert score == brute_force(n, edges, weights, ranges, cands)
    assert ref.host_cut(edges, weights, assign) == score
    # a narrower beam finds no more, and scores what it returns exactly
    assign, narrow, _ = ref.merge_beam(n, edges, weights, ranges, cands, 4)
    assert narrow <= score
    assert ref.host_cut(edges, weights, assign) == narrow
