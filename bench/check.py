"""The comparison that decides a run's ``correct``.

Every solve in the window is compared, after the window has closed, with
the plain reference (`reference.py`), on the instance the benchmark made:

  value_gap        |host float64 cut of the returned assignment - the value
                   the solve reported|, the worst solve;
  pool_exp_gap     |pool <cut> - reference <cut> at the pool's own angles|
                   over the subgraph's weight (at least 1), the worst
                   subgraph of the worst solve;
  cand_prob_gap    how far the reference probability of the pool's k-th
                   candidate lies below the reference's k-th largest, over
                   the largest, worst subgraph and k;
  pool_ascent_gap  reference <cut> after the reference's own Adam ascent
                   minus the pool's final <cut>, over the weight, worst
                   subgraph of a third of them drawn from the seed (a pool
                   that does not ascend falls short on every one);
  merge_value_gap  |merge score - host cut of the merged assignment|;
  window_miss      subgraph windows of the merged assignment that are none
                   of that subgraph's candidates, in either orientation;
  merge_deficit    score of the reference beam over the pool's candidates,
                   at the configuration's width, minus the host cut of the
                   merged assignment (a merge that ranks or prunes wrongly
                   falls short);
  refine_deficit   (with refinement) host cut of the reference refinement
                   started from the merged assignment minus the host cut of
                   the returned assignment.

Each number has a limit in the configuration's file; a run is correct when
every number is at or below its limit and no solve failed.
"""

from __future__ import annotations

import numpy as np

import reference as ref


def _distinct(solves, key):
    """Map from the bytes of ``key(solve)`` to the first such solve."""
    seen = {}
    for s in solves:
        seen.setdefault(b"".join(np.ascontiguousarray(a).tobytes()
                                 for a in key(s)), s)
    return list(seen.values())


class Reference:
    """The reference's view of one instance, built once and reused for
    every set of answers compared with it."""

    def __init__(self, n, edges, weights, solver: dict, seed: int):
        self.n, self.edges, self.weights, self.solver = n, edges, weights, \
            solver
        self.ranges = ref.solver_ranges(n, solver["n_qubits"])
        self.subs = [(hi - lo, e, w) for (lo, hi), (e, w) in
                     zip(self.ranges, ref.induced(edges, weights,
                                                  self.ranges))]
        self.scale = np.asarray([
            max(1.0, float(np.sum(np.abs(w, dtype=np.float64))))
            for _, _, w in self.subs])
        # the subgraphs the reference ascends itself: its Adam costs 90
        # state passes a subgraph, the rest of the check a handful
        m = len(self.subs)
        self.sample = np.sort(np.random.default_rng(seed).choice(
            m, size=-(-m // 3), replace=False))
        self._ascent = None

    def pool(self, precision="float32"):
        s = self.solver
        return ref.QAOA(s["p_layers"], s["opt_steps"], s["learning_rate"],
                        s["ramp_delta"], s["top_k"], precision=precision)

    def ascent(self):
        """The reference's own (gammas, betas, <cut>) after Adam, for the
        sampled subgraphs."""
        if self._ascent is None:
            pool = self.pool()
            subs = [self.subs[i] for i in self.sample]
            g, b = pool.ascend(subs)
            e, _, _, _ = pool.evaluate(subs, g, b, np.zeros(
                (len(subs), 1), np.int64))
            self._ascent = g, b, e
        return self._ascent

    def beam(self, candidates, weights=None):
        """The reference merge over ``candidates`` at the configuration's
        width: (assignment, score, final scores)."""
        return ref.merge_beam(
            self.n, self.edges, self.weights if weights is None else weights,
            self.ranges, np.asarray(candidates, np.int64),
            ref.merge_width(self.solver, len(self.ranges)))

    def pool_numbers(self, solves) -> dict:
        pool = self.pool()
        exp_gap = cand_gap = 0.0
        for s in _distinct(solves,
                           lambda s: (s.gammas, s.betas, s.bitstrings)):
            if s.expectation.shape[0] != len(self.subs):
                raise ValueError(f"pool answered {s.expectation.shape[0]} "
                                 f"subgraphs, the instance has "
                                 f"{len(self.subs)}")
            e_ref, cand_p, top_p, _ = pool.evaluate(
                self.subs, s.gammas, s.betas, s.bitstrings.astype(np.int64))
            exp_gap = max(exp_gap, float(np.max(
                np.abs(s.expectation - e_ref) / self.scale)))
            cand_gap = max(cand_gap, float(np.max(
                (top_p - cand_p) / top_p[:, :1])))
        e_asc, i = self.ascent()[2], self.sample
        return {"pool_exp_gap": exp_gap, "cand_prob_gap": cand_gap,
                "pool_ascent_gap": max(float(np.max(
                    (e_asc - s.expectation[i]) / self.scale[i]))
                    for s in solves)}

    def compare(self, solves) -> dict:
        """Every number compared, for ``solves`` of this instance."""
        e, w = self.edges, self.weights
        out = {"value_gap": max(abs(ref.host_cut(e, w, s.assignment)
                                    - s.cut_value) for s in solves)}
        out.update(self.pool_numbers(solves))
        out["merge_value_gap"] = max(
            abs(ref.host_cut(e, w, s.merged) - s.merged_score)
            for s in solves)
        out["window_miss"] = max(_window_miss(self.ranges, s)
                                 for s in solves)
        out["merge_deficit"] = max(
            self.beam(s.bitstrings)[1] - ref.host_cut(e, w, s.merged)
            for s in _distinct(solves, lambda s: (s.bitstrings, s.merged)))
        steps = self.solver["refine_steps"]
        if steps > 0:
            out["refine_deficit"] = max(
                ref.refine(self.n, e, w, m.merged, steps)[1]
                - ref.host_cut(e, w, m.assignment)
                for m in _distinct(solves, lambda s: (s.merged,
                                                      s.assignment)))
        return out


def compare(n, edges, weights, solver: dict, solves, seed: int) -> dict:
    """The numbers compared for ``solves`` of one instance, as a dict."""
    return Reference(n, edges, weights, solver, seed).compare(solves)


def _window_miss(ranges, s) -> int:
    miss = 0
    for (lo, hi), cands in zip(ranges, s.bitstrings):
        window = s.merged[lo:hi].astype(np.int64)
        bits = (cands[:, None].astype(np.int64)
                >> np.arange(hi - lo)) & 1
        same = np.all(bits == window, axis=1) | np.all(bits != window, axis=1)
        miss += int(not same.any())
    return miss


def judge(values: dict, limits: dict, failed: int):
    """(correct, {name: {"value", "limit"}}) for the compared numbers."""
    missing = sorted(set(values) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing} in the configuration")
    checks = {k: {"value": float(v), "limit": float(limits[k])}
              for k, v in values.items()}
    ok = failed == 0 and all(c["value"] <= c["limit"] for c in
                             checks.values())
    return ok, checks
