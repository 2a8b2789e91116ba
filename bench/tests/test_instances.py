"""Instances: seeded, and the same shapes on every seed."""

import numpy as np

import instances


def shapes(n, p, q, seed):
    e, w = instances.stratified_erdos_renyi(n, p, q, seed)
    ranges = instances.solver_ranges(n, q)
    hi = np.asarray([h for _, h in ranges])
    cover = np.minimum(np.searchsorted(hi, np.arange(n), side="right"),
                       len(ranges) - 1)
    level = np.bincount(cover[e[:, 1]], minlength=len(ranges))
    block = [int(np.sum((e[:, 0] >= lo) & (e[:, 1] < h))) for lo, h in ranges]
    return e, w, (e.shape[0], int(level.max()), max(block))


def test_same_seed_same_instance():
    a, _, _ = shapes(120, 0.2, 9, 5)
    b, _, _ = shapes(120, 0.2, 9, 5)
    assert np.array_equal(a, b)


def test_every_seed_has_the_same_shapes():
    seeds = [0, 1, 2**31 + 11, 2**33 + 3]
    got = {shapes(300, 0.05, 10, s)[2] for s in seeds}
    assert len(got) == 1
    e0, _, _ = shapes(300, 0.05, 10, 0)
    e1, _, _ = shapes(300, 0.05, 10, 1)
    assert not np.array_equal(e0, e1)


def test_edges_are_distinct_pairs_at_the_density():
    n, p = 200, 0.3
    e, w, _ = shapes(n, p, 12, 7)
    assert np.all(e[:, 0] < e[:, 1])
    assert np.unique(e[:, 0] * n + e[:, 1]).size == e.shape[0]
    assert abs(e.shape[0] / (n * (n - 1) / 2) - p) < 1e-3
    assert np.all(w == 1.0)


def test_ranges_cover_with_one_shared_vertex():
    r = instances.solver_ranges(400, 20)
    assert len(r) == 22 and r[0][0] == 0 and r[-1][1] == 400
    assert all(a[1] - 1 == b[0] for a, b in zip(r, r[1:]))
    assert max(h - lo for lo, h in r) <= 20
