"""What each timed solve produced, read at the program's stage seams.

`repro.core.solve` returns the final assignment, its value and the stage
timings. The checks also need the solver pool's answer (angles,
expectation, top-K candidates per subgraph) and the merge's answer (the
merged assignment and its score, which refinement then starts from). The
recorder wraps the two stage entry points the solve calls,
`repro.core.qaoa.solve_subgraph_batch_program` and
`repro.core.paraqaoa.merge_candidates`, and keeps references to what they
return; it computes nothing and moves nothing off the device inside the
timed window.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Solve:
    """One solve's answers, as numpy arrays on the host."""

    assignment: np.ndarray  # (V,) final 0/1 assignment
    cut_value: float  # the value the solve reported for it
    timings: dict  # the program's stage spans, seconds
    bitstrings: np.ndarray  # (M, K) pool candidates, basis indices
    expectation: np.ndarray  # (M,) pool <cut> at its final angles
    gammas: np.ndarray  # (M, p)
    betas: np.ndarray  # (M, p)
    merged: np.ndarray  # (V,) the merge's assignment
    merged_score: float  # the score the merge reported for it


class Recorder:
    """Keeps the pool's and the merge's outputs of every solve."""

    def __init__(self):
        self.pool = []  # device QAOAResult per pool call
        self.merge = []  # (assignment, score) per merge call
        self.pool_program = None  # the last pool program handed out
        self.pool_args = None  # and the arrays of its last call
        self._undo = []

    def install(self):
        from repro.core import paraqaoa, qaoa

        make_program = qaoa.solve_subgraph_batch_program
        merge = paraqaoa.merge_candidates

        def recorded_program(cfg, has_linear=False):
            program = make_program(cfg, has_linear)
            self.pool_program = program

            def run(*arrays):
                self.pool_args = arrays
                out = program(*arrays)
                self.pool.append(out)
                return out

            return run

        def recorded_merge(*args, **kwargs):
            out = merge(*args, **kwargs)
            self.merge.append((out[0], out[1]))
            return out

        qaoa.solve_subgraph_batch_program = recorded_program
        paraqaoa.merge_candidates = recorded_merge
        self._undo = [(qaoa, "solve_subgraph_batch_program", make_program),
                      (paraqaoa, "merge_candidates", merge)]
        return self

    def uninstall(self):
        for module, name, original in self._undo:
            setattr(module, name, original)
        self._undo = []

    def clear(self):
        self.pool, self.merge = [], []

    def solves(self, outputs) -> list[Solve]:
        """Pair each `solve` output with the stage outputs it recorded."""
        if not (len(outputs) == len(self.pool) == len(self.merge)):
            raise RuntimeError(
                f"{len(outputs)} solves but {len(self.pool)} pool and "
                f"{len(self.merge)} merge calls recorded")
        return [
            Solve(
                assignment=np.asarray(out.assignment),
                cut_value=float(out.cut_value),
                timings=dict(out.timings),
                bitstrings=np.asarray(pool.bitstrings),
                expectation=np.asarray(pool.expectation),
                gammas=np.asarray(pool.gammas),
                betas=np.asarray(pool.betas),
                merged=np.asarray(merged),
                merged_score=float(score),
            )
            for out, pool, (merged, score) in zip(outputs, self.pool,
                                                  self.merge)
        ]
