"""End-to-end ParaQAOA orchestrator: partition → solve (batched QAOA) →
level-aware merge → report. Mirrors Fig. 3 of the paper.

Parameter taxonomy (paper §4.2):
  hardware-dependent: n_solvers (N_s), n_qubits (N)
  input-dependent:    m_subgraphs (M = ceil(|V|/(N-1))), rounds (T = ceil(M/N_s))
  tunable:            top_k (K), merge_level (L) / beam_width
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import merge as merge_mod
from repro.core import qaoa as qaoa_mod
from repro.core.graph import Graph, Problem, as_problem, cut_value, problem_value
from repro.core.partition import (
    Partition,
    connectivity_preserving_partition,
    partition_for_solver,
    split_linear,
)
from repro.core.pei import SolveReport
from repro.obs import trace as trace_mod


@dataclasses.dataclass(frozen=True)
class ParaQAOAConfig:
    # hardware-dependent (paper: N_s solvers × N qubits)
    n_qubits: int = 14  # N — per-solver qubit budget (26 on the paper's GPUs)
    n_solvers: int = 1  # N_s — concurrent solver instances (mesh data-axis size)
    # tunable (paper: K, L)
    top_k: int = 2  # K — candidates kept per subgraph
    merge_level: int = 2  # L — frontier materialization level (distributed merge)
    beam_width: Optional[int] = None  # None → exact 2·K^M (capped)
    beam_cap: int = 1 << 18
    # QAOA solver knobs
    p_layers: int = 3
    opt_steps: int = 30
    learning_rate: float = 0.05
    ramp_delta: float = 0.75
    # Adam steps on oversized (model-axis sharded) subproblems, run
    # *through* the sharded evolution (engine.sharded_ascent, DESIGN.md
    # §2.6); 0 keeps the linear-ramp parameters — the pre-engine behavior
    sharded_opt_steps: int = 0
    # beyond-paper: vectorized 1-flip local-search refinement of the merged cut
    refine_steps: int = 0

    def qaoa_config(self) -> qaoa_mod.QAOAConfig:
        return qaoa_mod.QAOAConfig(
            n_qubits=self.n_qubits,
            p_layers=self.p_layers,
            opt_steps=self.opt_steps,
            learning_rate=self.learning_rate,
            ramp_delta=self.ramp_delta,
            top_k=self.top_k,
        )


@dataclasses.dataclass
class ParaQAOAOutput:
    assignment: np.ndarray
    cut_value: float
    partition: Partition
    report: SolveReport
    timings: dict
    # backend compiles (cache loads included) billed to each stage span
    compiles: dict = dataclasses.field(default_factory=dict)


# `timings` key → the span whose duration it reports. One table for
# `solve` and `distributed.solve_distributed`, so their keys cannot drift.
TIMING_SPANS = (
    ("partition_s", "partition"),
    ("solve_s", "solve_pool"),
    ("pool_pack_s", "pool_pack"),
    ("merge_s", "merge"),
    ("merge_plan_s", "merge_plan"),
    ("merge_scan_s", "merge_scan"),
    ("refine_s", "refine"),
    ("total_s", "solve"),
)


def stage_timings(spans: dict) -> tuple[dict, dict]:
    """(timings, compiles) of one solve from its ended spans by name.

    ``timings`` holds every `TIMING_SPANS` duration plus ``compile_s``,
    the seconds of JAX compiling inside the solve (the root span's
    union of compile phases; 0 on an injected clock). ``compiles``
    counts backend compiles per span.
    """
    timings = {key: spans[name].duration_s for key, name in TIMING_SPANS}
    timings["compile_s"] = spans["solve"].attrs.get("compile_s", 0.0)
    compiles = {name: s.attrs.get("compiles", 0) for name, s in spans.items()}
    return timings, compiles


def merge_inputs(
    part: Partition, bit_indices: np.ndarray, cfg: ParaQAOAConfig,
    linear=None,
) -> tuple[merge_mod.MergePlan, int]:
    """Stage-3 (plan, beam width) derivation, shared by every merge
    consumer — `merge_candidates` below and the service's anytime stream
    (DESIGN.md §6.4) — so the beam/cap rules cannot silently diverge.
    ``linear`` (V,) f32, optional, scores the QUBO/MIS linear terms in the
    beam (each vertex counted once, at its first-coverage level)."""
    plan = merge_mod.build_merge_plan(part, bit_indices, cfg.top_k,
                                      linear=linear)
    bw = cfg.beam_width or merge_mod.exact_beam_width(
        cfg.top_k, part.m, cap=cfg.beam_cap
    )
    return plan, bw


def merge_candidates(
    part: Partition, bit_indices: np.ndarray, cfg: ParaQAOAConfig,
    linear=None, spans: dict | None = None,
) -> tuple[np.ndarray, float, int]:
    """Stage-3 merge of solved candidates → (assignment, score, beam width).

    The single merge path shared by `solve` and the serve-side scheduler
    (`repro.service.scheduler`, DESIGN.md §6.1): running the identical
    plan/beam computation is what keeps service results bit-identical to
    solo `solve` runs on the same knobs. The returned score is the internal
    (offset-free) objective: quadratic cut + linear terms.

    Two leaf spans split host from device work: ``merge_plan`` (the host
    plan and beam width) and ``merge_scan`` (the scan through the host
    copy of its answer). ``spans``, when given, receives both by name.
    """
    tr = trace_mod.get_tracer()
    with tr.span("merge_plan") as sp_plan:
        plan, bw = merge_inputs(part, bit_indices, cfg, linear=linear)
    with tr.span("merge_scan", beam=bw) as sp_scan:
        merged = merge_mod.merge_scan(plan, bw)
        assignment = np.asarray(merged.assignment)
        score = float(merged.cut_value)
    if spans is not None:
        spans.update(merge_plan=sp_plan, merge_scan=sp_scan)
    return assignment, score, bw


def solve(
    graph: Graph | Problem,
    cfg: ParaQAOAConfig = ParaQAOAConfig(),
    partition: Partition | None = None,
) -> ParaQAOAOutput:
    """Solve one instance end to end on the current default device.

    ``graph`` may be a plain `Graph` (Max-Cut) or a `core.graph.Problem`
    (weighted Max-Cut / QUBO / MIS): linear terms thread through the cost
    oracle, the partition (each vertex's term to exactly one subproblem)
    and the merge beam; the reported value is the full objective including
    the constant offset. A `Graph` input follows the exact zero-linear
    special case — byte-identical traces to the linear-free solver.
    """
    prob = as_problem(graph)
    graph = prob.graph
    has_lin = prob.has_linear
    # §8: stage timings come from the ambient tracer's spans — with the
    # default (non-recording) tracer this is the same perf_counter
    # stamping as before; `solve_maxcut --trace-out` installs a
    # recording tracer and the same spans become the exported trace
    tr = trace_mod.get_tracer()
    spans = {}  # span name → ended span, for `stage_timings`
    with tr.span("solve", n=graph.n, n_edges=graph.n_edges) as spans["solve"]:
        # ---- stage 1: graph partition (paper Alg. 1) ---------------------
        with tr.span("partition", n_qubits=cfg.n_qubits) as spans["partition"]:
            part = partition or partition_for_solver(graph, cfg.n_qubits)
            sub_lins = split_linear(part, prob.linear) if has_lin else None

        # ---- stage 2: parallelized QAOA execution ------------------------
        with tr.span("solve_pool", m=part.m,
                     n_qubits=cfg.n_qubits) as spans["solve_pool"]:
            qcfg = cfg.qaoa_config()
            with tr.span("pool_pack") as spans["pool_pack"]:
                edges, weights, masks = qaoa_mod.pad_subgraph_arrays(
                    part.subgraphs, qcfg.n_qubits
                )
                if has_lin:
                    linears = qaoa_mod.pad_linear_arrays(sub_lins,
                                                         qcfg.n_qubits)
            with tr.span("pool_run") as spans["pool_run"]:
                if has_lin:
                    result = qaoa_mod.solve_subgraph_batch_program(
                        qcfg, has_linear=True
                    )(edges, weights, masks, linears)
                else:
                    result = qaoa_mod.solve_subgraph_batch_program(qcfg)(
                        edges, weights, masks
                    )
                bit_indices = np.asarray(result.bitstrings)  # (M, K)

        # ---- stage 3: level-aware parallel merge -------------------------
        with tr.span("merge", m=part.m) as spans["merge"]:
            assignment, cut, bw = merge_candidates(
                part, bit_indices, cfg,
                linear=prob.linear if has_lin else None, spans=spans,
            )

        # ---- optional beyond-paper refinement ----------------------------
        with tr.span("refine", steps=cfg.refine_steps) as spans["refine"]:
            if cfg.refine_steps > 0:
                from repro.core.baselines.local_search import refine

                assignment, cut = refine(
                    part.graph, assignment, cfg.refine_steps,
                    linear=prob.linear if has_lin else None,
                )

        # sanity: merge's incremental score must equal a from-scratch
        # evaluation of the internal (offset-free) objective; report the
        # full objective
        with tr.span("rescore") as spans["rescore"]:
            obj = float(problem_value(prob, jnp.asarray(assignment)))
    internal = obj - prob.offset
    if cfg.refine_steps == 0:
        assert abs(internal - cut) < 1e-2 * max(1.0, abs(internal)), (internal, cut)
    cut = obj

    timings, compiles = stage_timings(spans)
    report = SolveReport(
        method="paraqaoa",
        n_vertices=graph.n,
        cut_value=cut,
        runtime_s=timings["total_s"],
        extra={"m_subgraphs": part.m, "k": cfg.top_k, "beam": bw, **timings},
    )
    return ParaQAOAOutput(
        assignment=assignment,
        cut_value=cut,
        partition=part,
        report=report,
        timings=timings,
        compiles=compiles,
    )
