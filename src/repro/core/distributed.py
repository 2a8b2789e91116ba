"""Distributed execution of ParaQAOA on a device mesh.

Three shard_map programs plus the end-to-end orchestrator that wires them
into one pipeline (`solve_distributed`, DESIGN.md §2.4), matching
DESIGN.md §2:

1. `solve_pool`       — solver-pool data parallelism: the vmapped subgraph
   batch is sharded across the `data` (and `pod`) axes. This is the paper's
   "N_s QAOA solvers × T rounds" recast as SPMD.

2. `sharded_qaoa`     — statevector tensor parallelism: one subproblem's
   2^n amplitudes sharded across the `model` axis. The transverse-field
   mixer factorizes per qubit, so only the log2(axis_size) "global" qubits
   need cross-device mixing; one qubit-swap `all_to_all` rotates them into
   locality. Lifts the paper's 26-qubit/GPU cap to 26 + log2(model) qubits.
   The per-layer evolution is the shared statevector engine
   (`core/engine.py`, DESIGN.md §2.6): every op dispatches through
   `kernels.ops` per shard, the whole evolution is differentiable through
   the collectives, and `sharded_qaoa_batch` scans stacked same-n
   subproblems through one cached program.

   Two collective schedules:
     - "faithful":    swap in + swap back every layer (2 a2a/layer) — the
       direct port of a distributed gate-level simulator.
     - "alternating": keep the swapped layout between layers and evaluate
       the diagonal cost layer with *relabelled* cut values (1 a2a/layer —
       a diagonal Hamiltonian makes the layout change a pure relabelling).
       Beyond-paper optimization; measured by benchmarks/kernel_bench.py
       `run_schedules` (see EXPERIMENTS.md §Perf).

3. `merge_sharded`    — the merge frontier striped across `data` at the
   paper's starting level L: each shard prunes its own stripe locally (the
   paper's independent DFS workers); a pmax/pmin picks the global winner.

All three go through `repro.compat` (portable shard_map + mesh handling)
and are *cached compiled programs*: the jitted callable is built once per
static configuration (config, mesh, axes), not per call, with buffer
donation on backends that support it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat
from repro.core import engine
from repro.core import merge as merge_mod
from repro.core import qaoa as qaoa_mod
from repro.kernels import ops
from repro.kernels import tuning


# ---------------------------------------------------------------------------
# 1. solver-pool data parallelism
# ---------------------------------------------------------------------------
@compat.cached_program
def _solve_pool_program(
    cfg: qaoa_mod.QAOAConfig, mesh: Mesh, axes: tuple, donate: bool,
    impl: str,
    tune: tuple,
    has_lin: bool = False,
):
    # the per-shard `kernels.ops` dispatch is a trace-time choice, so
    # `ops.using_implementation` only reaches the pool if each
    # implementation gets its own compiled program; the keyed `impl` is
    # re-asserted during tracing because jit traces lazily on first call,
    # possibly outside the context the program was requested under. The
    # `kernels.tuning` block-shape state is trace-time in the same way,
    # so it is keyed and re-asserted alongside (DESIGN.md §2.7). `has_lin`
    # keys the linear-terms (QUBO/MIS) variant; False compiles the exact
    # Max-Cut program, keeping that path bit-identical.
    spec = P(axes)

    if has_lin:

        def run(e, w, mk, l):
            with ops.using_implementation(impl), tuning.using_state(tune):
                return qaoa_mod.solve_subgraph_batch_linear(e, w, mk, cfg, l)

        in_specs = (spec, spec, spec, spec)
        donate_args = (0, 1, 2, 3)
    else:

        def run(e, w, mk):
            with ops.using_implementation(impl), tuning.using_state(tune):
                return qaoa_mod.solve_subgraph_batch(e, w, mk, cfg)

        in_specs = (spec, spec, spec)
        donate_args = (0, 1, 2)

    sharded = compat.shard_map(
        run,
        mesh,
        in_specs=in_specs,
        out_specs=qaoa_mod.QAOAResult(spec, spec, spec, spec, spec),
    )
    # donate only when solve_pool owns the (freshly padded) batch arrays —
    # donating caller-owned arrays would invalidate them behind its back
    return compat.jit(sharded, donate_argnums=donate_args if donate else ())


def solve_pool(edges, weights, masks, cfg: qaoa_mod.QAOAConfig, mesh: Mesh,
               axes=("data",), linears=None):
    """Batched QAOA across the mesh: round-robin subgraphs over devices.

    Pads the batch to a multiple of the axis size (padding entries are
    empty graphs) and strips the padding on return. ``linears``
    (B, n_qubits) f32, optional, carries per-vertex diagonal terms
    (QUBO/MIS buckets); ``None`` runs the unchanged Max-Cut program.
    """
    axes = tuple(axes)
    total = int(np.prod([mesh.shape[a] for a in axes]))
    m = edges.shape[0]
    m_pad = ((m + total - 1) // total) * total
    pad = m_pad - m
    if pad:
        edges = jnp.concatenate(
            [edges, jnp.zeros((pad,) + edges.shape[1:], edges.dtype)]
        )
        weights = jnp.concatenate(
            [weights, jnp.zeros((pad,) + weights.shape[1:], weights.dtype)]
        )
        masks = jnp.concatenate([masks, jnp.ones((pad,), masks.dtype)])
        if linears is not None:
            linears = jnp.concatenate(
                [linears, jnp.zeros((pad,) + linears.shape[1:], linears.dtype)]
            )

    # normalize the cache key on non-donating backends: donate=True and
    # donate=False would otherwise compile byte-identical programs twice
    donate = bool(pad) and compat.supports_donation()
    program = _solve_pool_program(
        cfg, mesh, axes, donate, ops.get_implementation(), tuning.state(),
        linears is not None,
    )
    res = (program(edges, weights, masks) if linears is None
           else program(edges, weights, masks, linears))
    return jax.tree.map(lambda x: x[:m], res)


# ---------------------------------------------------------------------------
# 2. sharded-statevector QAOA (statevector tensor parallelism)
# ---------------------------------------------------------------------------
class ShardedQAOAResult(NamedTuple):
    bitstrings: jnp.ndarray  # (K,) int32 global basis indices (replicated)
    probs: jnp.ndarray  # (K,)
    expectation: jnp.ndarray  # scalar
    gammas: jnp.ndarray  # (p,) as run (optimized when opt_steps > 0)
    betas: jnp.ndarray  # (p,)


@compat.cached_program
def _sharded_qaoa_program(
    n: int,
    p_layers: int,
    batch: int,
    mesh: Mesh,
    axis: str,
    top_k: int,
    schedule: str,
    group: int,
    opt_steps: int,
    learning_rate: float,
    impl: str,
    tune: tuple,
    has_lin: bool = False,
):
    """Cached sharded-statevector program over the shared engine.

    ``batch`` > 1 runs a `lax.scan` over stacked same-n subgraphs — one
    compiled program for the whole oversized-subproblem group instead of
    one compile-shaped call per subgraph. ``impl`` is the `kernels.ops`
    implementation the program runs: dispatch happens at trace time, so
    it is part of the cache key *and* re-asserted inside the traced
    function (jit traces lazily on first call, possibly outside the
    context the program was requested under) for
    `ops.using_implementation` to reach the per-shard kernels. ``tune``
    keys and re-asserts the `kernels.tuning` block-shape state the same
    way (DESIGN.md §2.7).
    """
    # `p_layers` is cache-key-only (like array shapes, re-handled by
    # jit's own cache)
    del p_layers
    layout = engine.ShardedLayout(
        n=n,
        axis=axis,
        axis_size=int(mesh.shape[axis]),
        schedule=schedule,
        group=group,
    )

    def one(edges, weights, gammas, betas, linear=None):
        cut = engine.cut_table(layout, edges, weights, linear)
        if opt_steps:
            gammas, betas = engine.sharded_ascent(
                layout, cut, gammas, betas, opt_steps, learning_rate
            )
        re, im, in_b = engine.evolve(layout, cut, gammas, betas)
        exp = engine.expectation(layout, re, im, cut, in_b)
        bits, probs = engine.top_candidates(layout, re, im, cut, in_b, top_k)
        return ShardedQAOAResult(bits, probs, exp, gammas, betas)

    if batch == 1:
        local_run = one
    elif has_lin:

        def local_run(edges, weights, gammas, betas, linears):
            def body(_, ewl):
                e, w, l = ewl
                return 0, one(e, w, gammas, betas, l)

            _, res = jax.lax.scan(body, 0, (edges, weights, linears))
            return res

    else:

        def local_run(edges, weights, gammas, betas):
            def body(_, ew):
                e, w = ew
                return 0, one(e, w, gammas, betas)

            _, res = jax.lax.scan(body, 0, (edges, weights))
            return res

    if has_lin:

        def local_run_impl(edges, weights, gammas, betas, linears):
            with ops.using_implementation(impl), tuning.using_state(tune):
                return local_run(edges, weights, gammas, betas, linears)

        in_specs = (P(), P(), P(), P(), P())
    else:

        def local_run_impl(edges, weights, gammas, betas):
            with ops.using_implementation(impl), tuning.using_state(tune):
                return local_run(edges, weights, gammas, betas)

        in_specs = (P(), P(), P(), P())

    run = compat.shard_map(
        local_run_impl,
        mesh,
        in_specs=in_specs,
        out_specs=ShardedQAOAResult(P(), P(), P(), P(), P()),
    )
    return compat.jit(run)


def sharded_qaoa(
    edges,
    weights,
    n: int,
    gammas,
    betas,
    mesh: Mesh,
    axis: str = "model",
    top_k: int = 4,
    schedule: str = "alternating",
    group: int = 7,
    opt_steps: int = 0,
    learning_rate: float = 0.05,
    linear=None,
):
    """One n-qubit QAOA circuit with amplitudes sharded over `axis`.

    Layouts: A (row-sharded: device d owns global indices [d·L, (d+1)·L));
    B (after the qubit-swap all_to_all: device p owns, for every d, the
    slice [d·L + p·chunk, d·L + (p+1)·chunk)). In layout B the local flat
    index's high h bits are the *original* high qubits — so a full local
    mixer still touches each original qubit exactly once per layer.

    ``gammas``/``betas`` are the run (or, with ``opt_steps`` > 0, the
    initial) parameters; the sharded Adam ascent (`engine.sharded_ascent`,
    DESIGN.md §2.6) then optimizes them through the collective schedule
    before the final evolution. ``opt_steps=0`` runs them as given —
    bit-identical to the pre-engine behavior.
    """
    program = _sharded_qaoa_program(
        n, int(gammas.shape[0]), 1, mesh, axis, top_k, schedule, group,
        int(opt_steps), float(learning_rate), ops.get_implementation(),
        tuning.state(), linear is not None,
    )
    if linear is None:
        return program(edges, weights, gammas, betas)
    return program(edges, weights, gammas, betas, linear)


def sharded_qaoa_batch(
    edges,
    weights,
    n: int,
    gammas,
    betas,
    mesh: Mesh,
    axis: str = "model",
    top_k: int = 4,
    schedule: str = "alternating",
    group: int = 7,
    opt_steps: int = 0,
    learning_rate: float = 0.05,
    linears=None,
):
    """`sharded_qaoa` over a stacked batch of same-n subgraphs.

    ``edges`` (B, E_pad, 2) / ``weights`` (B, E_pad) padded with
    zero-weight rows (exact no-ops for the cut values); one cached
    program `lax.scan`s the B subgraphs through the sharded engine.
    ``linears`` (B, n) f32, optional per-vertex diagonal terms.
    Result fields carry a leading (B,) axis.
    """
    b = int(edges.shape[0])
    if b == 1:  # singleton batch: reuse the (scan-free) unbatched program
        res = sharded_qaoa(
            edges[0], weights[0], n, gammas, betas, mesh, axis=axis,
            top_k=top_k, schedule=schedule, group=group,
            opt_steps=opt_steps, learning_rate=learning_rate,
            linear=None if linears is None else linears[0],
        )
        return jax.tree.map(lambda x: jnp.asarray(x)[None], res)
    program = _sharded_qaoa_program(
        n, int(gammas.shape[0]), b, mesh, axis, top_k, schedule, group,
        int(opt_steps), float(learning_rate), ops.get_implementation(),
        tuning.state(), linears is not None,
    )
    if linears is None:
        return program(edges, weights, gammas, betas)
    return program(edges, weights, gammas, betas, linears)


# ---------------------------------------------------------------------------
# 3. sharded merge frontier (level-aware workers)
# ---------------------------------------------------------------------------
@compat.cached_program
def _merge_sharded_program(
    statics: merge_mod.MergePlanStatics,
    beam_width: int,
    mesh: Mesh,
    axis: str,
    split_level: int,
):
    d_ax = mesh.shape[axis]

    def local_run(lo, cand_bits, edge_u, edge_v, edge_w, lin):
        me = jax.lax.axis_index(axis)
        local_plan = merge_mod.MergePlan(
            *statics,
            lo=lo,
            cand_bits=cand_bits,
            edge_u=edge_u,
            edge_v=edge_v,
            edge_w=edge_w,
            lin=lin,
        )
        res = merge_mod.merge_scan(
            local_plan,
            beam_width,
            shard_id=me,
            n_shards=d_ax,
            split_level=split_level,
        )
        return merge_mod.global_winner(res, axis, me)

    run = compat.shard_map(
        local_run,
        mesh,
        in_specs=(P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P()),
    )
    return compat.jit(run)


def merge_sharded(
    plan: merge_mod.MergePlan,
    beam_width: int,
    mesh: Mesh,
    axis: str = "data",
    split_level: int = 1,
):
    """Level-aware merge: frontier striped across `axis` at `split_level`.

    Each shard sweeps its own beam of beam_width rows — the global frontier
    is n_shards × beam_width (the paper's "2K^L workers ⇒ runtime halves
    per doubling" regime). Returns (assignment (V,), cut value), replicated.
    """
    program = _merge_sharded_program(
        merge_mod.plan_statics(plan), beam_width, mesh, axis, split_level
    )
    return program(*merge_mod.plan_arrays(plan))


# ---------------------------------------------------------------------------
# 4. end-to-end orchestrator (DESIGN.md §2.4)
# ---------------------------------------------------------------------------
def as_mesh(mesh_spec):
    """Resolve a Mesh | parsed-spec dict | 'data=2,model=4' string | None."""
    if mesh_spec is None or isinstance(mesh_spec, Mesh):
        return mesh_spec
    from repro.launch import mesh as mesh_mod

    spec = (
        mesh_mod.parse_mesh_spec(mesh_spec)
        if isinstance(mesh_spec, str)
        else dict(mesh_spec)
    )
    return mesh_mod.build_mesh(spec)


def solve_distributed(
    graph,
    cfg,
    mesh_spec,
    partition=None,
    schedule: str = "alternating",
    split_level: int | None = None,
    merge_mode: str = "auto",
):
    """End-to-end ParaQAOA across a device mesh (paper Fig. 3, SPMD form).

    The single-device `repro.core.solve` stages, each replaced by its
    shard_map program where the mesh provides the matching axis:

      1. partition on host — with the qubit budget *lifted* to
         ``cfg.n_qubits + log2(model)`` when a `model` axis is present
         (the sharded statevector holds what one device cannot);
      2. subgraphs that fit one device solve as a padded batch through the
         cached `solve_pool` program over the `data` (and `pod`) axes;
         oversized subgraphs route, grouped by qubit count, through
         batched `sharded_qaoa_batch` programs over `model` with
         `schedule`-selected collectives — linear-ramp parameters when
         ``cfg.sharded_opt_steps == 0``, per-subgraph Adam-ascended
         through the sharded evolution otherwise (DESIGN.md §2.2, §2.6);
      3. the merge frontier stripes across the `data` axis at
         ``split_level`` (default: the paper's L knob,
         ``cfg.merge_level``) via `merge_sharded`; `global_winner`
         replicates the best assignment.
         ``merge_mode`` picks the striping policy (see the stage-3 comment
         below and DESIGN.md §2.3): "auto" stripes only when provably
         exhaustive so the cut value is identical to single-device
         `solve`; "striped" always stripes (the paper's independent
         workers); "single" keeps the merge on one device.

    ``mesh_spec`` is a `jax.sharding.Mesh`, a parsed ``{"data": 2}`` dict,
    a ``"data=2,model=4"`` CLI string, or None — None (or an empty mesh)
    falls back to the single-device `solve` unchanged. ``graph`` may be a
    `Graph` (Max-Cut) or a `core.graph.Problem` (weighted Max-Cut / QUBO /
    MIS); linear terms thread through every stage and the reported value is
    the full objective including the constant offset. Returns the same
    `ParaQAOAOutput` as `solve`.
    """
    from repro.core import paraqaoa as para_mod
    from repro.core import partition as partition_mod
    from repro.core.graph import as_problem, problem_value
    from repro.core.partition import partition_for_solver
    from repro.obs import trace as trace_mod

    mesh = as_mesh(mesh_spec)
    if mesh is None or not mesh.shape:
        return para_mod.solve(graph, cfg, partition=partition)

    prob = as_problem(graph)
    graph = prob.graph
    has_lin = prob.has_linear

    data_axes = compat.mesh_data_axes(mesh)
    model_axis = compat.mesh_model_axis(mesh)
    h = int(np.log2(mesh.shape[model_axis])) if model_axis else 0
    device_cap = cfg.n_qubits
    budget = device_cap + h

    # §8: stage timings come from the ambient tracer's spans (a
    # non-recording tracer by default; `solve_maxcut --trace-out`
    # installs a recording one)
    tr = trace_mod.get_tracer()
    spans = {}  # span name → ended span, for `paraqaoa.stage_timings`
    with tr.span("solve", n=graph.n, n_edges=graph.n_edges,
                 mesh=dict(mesh.shape)) as spans["solve"]:
        # ---- stage 1: host-side partition at the lifted budget -----------
        with tr.span("partition", n_qubits=budget) as spans["partition"]:
            part = partition or partition_for_solver(graph, budget)
            # each vertex's linear term lands in exactly one subproblem
            # (first-coverage rule; shared vertices see h = 0 downstream)
            sub_lins = (
                partition_mod.split_linear(part, prob.linear)
                if has_lin else None
            )

        # ---- stage 2: solver pool + oversized-subproblem routing ---------
        qcfg = cfg.qaoa_config()
        small = [i for i, s in enumerate(part.sizes) if s <= device_cap]
        big = [i for i, s in enumerate(part.sizes) if s > device_cap]
        if big and not model_axis:
            raise ValueError(
                f"subgraphs of {max(part.sizes)} qubits exceed the "
                f"{device_cap}-qubit device cap and the mesh has no "
                "`model` axis"
            )

        bit_indices = np.zeros((part.m, cfg.top_k), dtype=np.int64)
        with tr.span("solve_pool", m=part.m, n_small=len(small),
                     n_big=len(big)) as spans["solve_pool"]:
            with tr.span("pool_pack") as spans["pool_pack"]:
                if small:
                    edges, weights, masks = qaoa_mod.pad_subgraph_arrays(
                        [part.subgraphs[i] for i in small], device_cap
                    )
                    linears = (
                        qaoa_mod.pad_linear_arrays(
                            [sub_lins[i] for i in small], device_cap
                        )
                        if has_lin else None
                    )
            if small:
                with tr.span("pool_run") as spans["pool_run"]:
                    if data_axes:
                        res = solve_pool(edges, weights, masks, qcfg, mesh,
                                         axes=data_axes, linears=linears)
                    elif has_lin:  # model-only mesh: single-device pool
                        res = qaoa_mod.solve_subgraph_batch_program(
                            qcfg, has_linear=True
                        )(edges, weights, masks, linears)
                    else:
                        res = qaoa_mod.solve_subgraph_batch_program(qcfg)(
                            edges, weights, masks
                        )
                    bit_indices[small] = np.asarray(res.bitstrings)
            # oversized subproblems: grouped by qubit count and run as
            # stacked batches through one cached sharded-engine program per
            # n (edge arrays padded with exact-no-op zero rows) — instead
            # of one compile-shaped call per subgraph. With
            # `sharded_opt_steps > 0` the linear-ramp initialization is
            # Adam-ascended per subgraph *through* the sharded evolution
            # (DESIGN.md §2.6); 0 runs the ramp as-is.
            sharded_steps = int(getattr(cfg, "sharded_opt_steps", 0))
            gammas0, betas0 = qaoa_mod.linear_ramp_init(
                cfg.p_layers, cfg.ramp_delta
            )
            by_n: dict[int, list[int]] = {}
            for i in big:
                by_n.setdefault(part.subgraphs[i].n, []).append(i)
            for n_sub, idxs in sorted(by_n.items()):
                with tr.span("sharded_ascent", n_qubits=n_sub,
                             batch=len(idxs), opt_steps=sharded_steps):
                    subs = [part.subgraphs[i] for i in idxs]
                    b_edges, b_weights, _ = qaoa_mod.pad_subgraph_arrays(
                        subs, n_sub
                    )
                    b_linears = (
                        qaoa_mod.pad_linear_arrays(
                            [sub_lins[i] for i in idxs], n_sub
                        )
                        if has_lin else None
                    )
                    res = sharded_qaoa_batch(
                        b_edges,
                        b_weights,
                        n_sub,
                        gammas0,
                        betas0,
                        mesh,
                        axis=model_axis,
                        top_k=cfg.top_k,
                        schedule=schedule,
                        group=qcfg.mixer_group,
                        opt_steps=sharded_steps,
                        learning_rate=cfg.learning_rate,
                        linears=b_linears,
                    )
                    bit_indices[idxs] = (
                        np.asarray(res.bitstrings)
                        .reshape(len(idxs), -1)[:, : cfg.top_k]
                    )

        # ---- stage 3: merge frontier (striped when the policy allows) ----
        # "auto":    stripe only when the striped sweep is provably
        #            exhaustive (no shard ever prunes) — then the cut value
        #            is identical to the single-device merge on the same
        #            candidates;
        # "striped": always stripe (the paper's independent DFS workers).
        #            In the beam-pruned regime each shard prunes within its
        #            own stripe, a *different* heuristic from one global
        #            beam — often better, but not value-identical to
        #            `solve`;
        # "single":  keep the merge on one device (pool/statevector only).
        if merge_mode not in ("auto", "striped", "single"):
            raise ValueError(f"unknown merge_mode {merge_mode!r}")
        with tr.span("merge", m=part.m) as spans["merge"]:
            with tr.span("merge_plan") as spans["merge_plan"]:
                plan, bw = para_mod.merge_inputs(
                    part, bit_indices, cfg,
                    linear=prob.linear if has_lin else None,
                )
                # merge_sharded stripes over one axis only (the innermost
                # data axis); a `pod` axis replicates the striped sweep
                # rather than widening it
                n_shards = int(mesh.shape[data_axes[-1]]) if data_axes else 1
                sl = min(
                    cfg.merge_level if split_level is None else split_level,
                    part.m - 1,
                )
                per_shard = None
                if n_shards > 1 and part.m > 1 and merge_mode != "single":
                    w_exact = merge_mod.striped_beam_width(
                        cfg.top_k, part.m, n_shards, sl, cap=cfg.beam_cap
                    )
                    if w_exact is not None and (cfg.beam_width is None or bw >= 2 * cfg.top_k**part.m):
                        per_shard = w_exact
                    elif merge_mode == "striped":
                        per_shard = max(-(-bw // n_shards), 2 * cfg.top_k)
            with tr.span("merge_scan", beam=bw,
                         per_shard=per_shard) as spans["merge_scan"]:
                if per_shard is not None:
                    assign, val = merge_sharded(
                        plan, per_shard, mesh, axis=data_axes[-1],
                        split_level=sl,
                    )
                    assignment = np.asarray(assign).reshape(-1)[: graph.n]
                    cut = float(np.asarray(val).reshape(-1)[0])
                else:
                    merged = merge_mod.merge_scan(plan, bw)
                    assignment = np.asarray(merged.assignment)
                    cut = float(merged.cut_value)

        # ---- optional beyond-paper refinement ----------------------------
        with tr.span("refine", steps=cfg.refine_steps) as spans["refine"]:
            if cfg.refine_steps > 0:
                from repro.core.baselines.local_search import refine

                assignment, cut = refine(
                    part.graph, assignment, cfg.refine_steps,
                    linear=prob.linear if has_lin else None,
                )

        # re-score with the full objective; the merge's beam score must
        # agree on the internal (offset-free) part
        with tr.span("rescore") as spans["rescore"]:
            obj = float(problem_value(prob, jnp.asarray(assignment)))
    internal = obj - prob.offset
    if cfg.refine_steps == 0:
        assert abs(internal - cut) < 1e-2 * max(1.0, abs(internal)), (internal, cut)
    cut = obj

    timings, compiles = para_mod.stage_timings(spans)
    from repro.core.pei import SolveReport

    report = SolveReport(
        method="paraqaoa-distributed",
        n_vertices=graph.n,
        cut_value=cut,
        runtime_s=timings["total_s"],
        extra={
            "m_subgraphs": part.m,
            "k": cfg.top_k,
            "beam": bw,
            "mesh": dict(mesh.shape),
            "merge_shards": n_shards if per_shard is not None else 1,
            "merge_mode": merge_mode,
            "merge_per_shard_beam": per_shard,
            "sharded_subproblems": len(big),
            "sharded_opt_steps": sharded_steps,
            "schedule": schedule,
            **timings,
        },
    )
    return para_mod.ParaQAOAOutput(
        assignment=assignment,
        cut_value=cut,
        partition=part,
        report=report,
        timings=timings,
        compiles=compiles,
    )
