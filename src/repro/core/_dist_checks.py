"""Multi-device correctness checks, executed in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests/test_distributed.py).

Prints one JSON object; the parent test asserts on it.
"""

from __future__ import annotations

import json
import sys

from repro import compat

# standalone-friendly: emulate 8 host devices when run without the test
# harness's XLA_FLAGS (no-op if the jax backend is already initialized)
compat.ensure_host_device_count(8)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed as dist
from repro.core import merge as merge_mod
from repro.core import qaoa as qaoa_mod
from repro.core.graph import Graph, cut_value
from repro.core.partition import connectivity_preserving_partition
# the harness's whole job is comparing impls against the reference
from repro.kernels import ref  # reprolint: disable=dispatch-purity


def check_solve_pool():
    mesh = compat.make_mesh((8,), ("data",))
    g = Graph.erdos_renyi(60, 0.4, seed=0)
    part = connectivity_preserving_partition(g, 6)
    cfg = qaoa_mod.QAOAConfig(n_qubits=11, p_layers=2, opt_steps=10, top_k=2)
    edges, weights, masks = qaoa_mod.pad_subgraph_arrays(part.subgraphs, 11)
    # single-device reference: the compiled batch program `solve` runs
    want = qaoa_mod.solve_subgraph_batch_program(cfg)(edges, weights, masks)
    got = dist.solve_pool(edges, weights, masks, cfg, mesh)
    return {
        "bitstrings_equal": bool(
            np.array_equal(np.asarray(want.bitstrings), np.asarray(got.bitstrings))
        ),
        "exp_close": bool(
            np.allclose(
                np.asarray(want.expectation), np.asarray(got.expectation), atol=1e-4
            )
        ),
    }


def check_sharded_qaoa():
    out = {}
    n = 10
    g = Graph.erdos_renyi(n, 0.5, seed=1)
    gammas = jnp.asarray([0.3, 0.55], jnp.float32)
    betas = jnp.asarray([0.9, 0.4], jnp.float32)
    # single-device reference
    cutv = ref.cutvals(n, g.edges, g.weights)
    re, im = qaoa_mod.qaoa_statevector(cutv, n, gammas, betas)
    want_exp = float(ref.expectation(re, im, cutv))
    probs = re * re + im * im
    want_v, want_i = jax.lax.top_k(probs, 4)

    for axis_size in (4, 8):
        mesh = compat.make_mesh((axis_size,), ("model",))
        for schedule in ("faithful", "alternating"):
            res = dist.sharded_qaoa(
                g.edges, g.weights, n, gammas, betas, mesh,
                axis="model", top_k=4, schedule=schedule,
            )
            key = f"d{axis_size}_{schedule}"
            out[key + "_exp_close"] = bool(
                np.allclose(float(res.expectation[0] if res.expectation.ndim else res.expectation), want_exp, atol=1e-4)
            )
            # the top-1 *index* can differ under exact prob ties (|psi_b| ==
            # |psi_~b| by flip symmetry); compare its probability instead
            top1 = int(np.asarray(res.bitstrings).reshape(-1)[0])
            out[key + "_top1_match"] = bool(
                np.isclose(float(probs[top1]), float(want_v[0]), atol=1e-6)
            )
            out[key + "_probs_close"] = bool(
                np.allclose(
                    np.sort(np.asarray(res.probs).reshape(-1)),
                    np.sort(np.asarray(want_v)),
                    atol=1e-5,
                )
            )
    return out


def check_merge_sharded():
    mesh = compat.make_mesh((8,), ("data",))
    g = Graph.erdos_renyi(32, 0.5, seed=2)
    part = connectivity_preserving_partition(g, 4)
    rng = np.random.default_rng(0)
    k = 2
    cand = rng.integers(0, 2 ** min(part.sizes), size=(part.m, k))
    plan = merge_mod.build_merge_plan(part, cand, k)
    # exact single-device answer
    want = merge_mod.merge_scan(plan, merge_mod.exact_beam_width(k, part.m))
    assign, val = dist.merge_sharded(plan, 16, mesh, split_level=1)
    achieved = float(
        cut_value(g, jnp.asarray(np.asarray(assign).reshape(-1)[: g.n]))
    )
    val = float(np.asarray(val).reshape(-1)[0])
    out = {
        "val_matches_exact": bool(abs(val - float(want.cut_value)) < 1e-3),
        "assignment_achieves_val": bool(abs(achieved - val) < 1e-3),
    }
    # striped_beam_width must yield an exhaustive sweep at every split
    # level (regression: the pre-split frontier term undercounted, so
    # split_level >= 2 pruned partial-score rows and lost the optimum)
    for sl in (1, 2, 3):
        w = merge_mod.striped_beam_width(k, part.m, 8, sl)
        _, v = dist.merge_sharded(plan, w, mesh, split_level=sl)
        v = float(np.asarray(v).reshape(-1)[0])
        out[f"split{sl}_exact_at_proven_width"] = bool(
            abs(v - float(want.cut_value)) < 1e-3
        )
    return out


def check_engine_grad():
    """jax.grad through the sharded evolution vs the single-device
    gradient (float32 tolerance), plus the sharded Adam ascent improving
    on the linear ramp — the DESIGN.md §2.6 differentiability contract."""
    from jax.sharding import PartitionSpec as P

    from repro.core import engine
    from repro.kernels import ops

    out = {}
    n = 10
    g = Graph.erdos_renyi(n, 0.5, seed=3)
    gammas, betas = qaoa_mod.linear_ramp_init(3, 0.75)

    cutv = ref.cutvals(n, g.edges, g.weights)
    flat_loss = lambda p: qaoa_mod.qaoa_expectation(p, cutv, n)
    want = jax.grad(flat_loss)((gammas, betas))
    scale = max(float(jnp.max(jnp.abs(x))) for x in want)

    for d in (2, 4):
        mesh = compat.make_mesh((d,), ("model",))
        layout = engine.ShardedLayout(n=n, axis="model", axis_size=d)

        def local_grad(edges, weights, gm, bt):
            cut = engine.cut_table(layout, edges, weights)

            def local_exp(params):
                gg, bb = params
                re, im, in_b = engine.evolve(layout, cut, gg, bb)
                return ops.expectation(re, im, cut.at(in_b))

            grads = jax.grad(local_exp)((gm, bt))
            return jax.tree.map(lambda x: jax.lax.psum(x, "model"), grads)

        run = compat.jit(
            compat.shard_map(
                local_grad, mesh, in_specs=(P(),) * 4, out_specs=(P(), P())
            )
        )
        got = run(g.edges, g.weights, gammas, betas)
        err = max(
            float(jnp.max(jnp.abs(w - g_))) for w, g_ in zip(want, got)
        )
        # float32 forward/backward through p=3 layers + collectives: the
        # elementwise error is a few 1e-4 of the gradient scale
        out[f"d{d}_grad_close"] = bool(err <= 2e-3 * max(scale, 1.0))

    mesh = compat.make_mesh((4,), ("model",))
    r_ramp = dist.sharded_qaoa(g.edges, g.weights, n, gammas, betas, mesh)
    r_opt = dist.sharded_qaoa(
        g.edges, g.weights, n, gammas, betas, mesh, opt_steps=30
    )
    e_ramp = float(np.asarray(r_ramp.expectation).reshape(-1)[0])
    e_opt = float(np.asarray(r_opt.expectation).reshape(-1)[0])
    out["ascent_beats_ramp"] = bool(e_opt >= e_ramp)
    # the sharded ascent must land where the single-device optimizer lands
    cfg = qaoa_mod.QAOAConfig(n_qubits=n, p_layers=3, opt_steps=30)
    p_flat = qaoa_mod.optimize_params(cutv, n, cfg)
    out["ascent_matches_flat_optimum"] = bool(
        all(
            np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)
            for a, b in zip(p_flat, (r_opt.gammas, r_opt.betas))
        )
    )
    return out


def check_engine_interpret():
    """The sharded hot loop under `ops.using_implementation` — proves
    every phase/mixer/cutvals/expectation op goes through the
    `kernels.ops` dispatch per shard (no direct `ref.*` calls), and that
    the `pallas_interpret` and `xla` paths agree.

    Agreement grading: the cut tables are bitwise identical (integer-
    valued sums); the evolved state is ulp-tight but *not* bitwise —
    the mixer kernels generate RX^{⊗k} via runtime `pow` (MXU-friendly,
    no gather) while `ref.rx_kron_parts` uses cumprod tables, a
    deliberate last-ulp divergence (see kernels/mixer.py)."""
    # imported to *instrument* the impl modules (wrap + count calls) and
    # prove dispatch reaches them — the exception that tests the rule
    import repro.kernels.cutvals as cutvals_mod  # reprolint: disable=dispatch-purity
    import repro.kernels.fused_layer as fused_mod  # reprolint: disable=dispatch-purity
    import repro.kernels.mixer as mixer_mod  # reprolint: disable=dispatch-purity
    import repro.kernels.phase as phase_mod  # reprolint: disable=dispatch-purity
    from repro.kernels import ops

    hits = {}

    def wrap(mod, name):
        orig = getattr(mod, name)

        def wrapped(*a, **k):
            hits[name] = hits.get(name, 0) + 1
            return orig(*a, **k)

        setattr(mod, name, wrapped)

    wrap(fused_mod, "fused_phase_mixer_group")
    wrap(mixer_mod, "mixer_group_matmul")
    wrap(mixer_mod, "mixer_group_strided")
    wrap(cutvals_mod, "cutvals_at")
    wrap(phase_mod, "expectation")

    n = 8
    g = Graph.erdos_renyi(n, 0.5, seed=5)  # unit weights: exact cut sums
    gammas = jnp.asarray([0.4, 0.3], jnp.float32)
    betas = jnp.asarray([0.9, 0.5], jnp.float32)
    mesh = compat.make_mesh((4,), ("model",))

    out = {}
    for schedule in ("faithful", "alternating"):
        res_x = dist.sharded_qaoa(
            g.edges, g.weights, n, gammas, betas, mesh, schedule=schedule
        )
        before = dict(hits)
        with ops.using_implementation("pallas_interpret"):
            res_p = dist.sharded_qaoa(
                g.edges, g.weights, n, gammas, betas, mesh, schedule=schedule
            )
        fired = {k: hits.get(k, 0) - before.get(k, 0) for k in hits}
        key = schedule
        out[f"{key}_dispatch_fused_layer"] = fired.get(
            "fused_phase_mixer_group", 0
        ) > 0
        # either mixer launcher counts: mid-state groups take the fused
        # strided-BlockSpec kernel, trailing (y == 1) groups the matmul
        out[f"{key}_dispatch_mixer"] = (
            fired.get("mixer_group_matmul", 0)
            + fired.get("mixer_group_strided", 0)
        ) > 0
        out[f"{key}_dispatch_cutvals_at"] = fired.get("cutvals_at", 0) > 0
        out[f"{key}_dispatch_expectation"] = fired.get("expectation", 0) > 0
        out[f"{key}_probs_close"] = bool(
            np.allclose(
                np.asarray(res_x.probs), np.asarray(res_p.probs), atol=1e-7
            )
        )
        out[f"{key}_exp_close"] = bool(
            np.allclose(
                np.asarray(res_x.expectation),
                np.asarray(res_p.expectation),
                atol=1e-5,
            )
        )

    # regression: opt_steps > 0 must work under non-xla dispatch too —
    # the ascent pins its gradient trace to the xla path (Pallas kernels
    # have no AD rule), so pallas_interpret + ascent lands on the same
    # optimized parameters as the xla run
    with ops.using_implementation("pallas_interpret"):
        r_opt_p = dist.sharded_qaoa(
            g.edges, g.weights, n, gammas, betas, mesh, opt_steps=3
        )
    with ops.using_implementation("xla"):
        r_opt_x = dist.sharded_qaoa(
            g.edges, g.weights, n, gammas, betas, mesh, opt_steps=3
        )
    out["opt_runs_under_interpret"] = bool(
        np.allclose(
            np.asarray(r_opt_p.gammas), np.asarray(r_opt_x.gammas), atol=1e-6
        )
        and np.allclose(
            np.asarray(r_opt_p.betas), np.asarray(r_opt_x.betas), atol=1e-6
        )
    )

    # cut tables bitwise: pallas_interpret cutvals_at == ref, per layout
    from repro.core import engine

    layout = engine.ShardedLayout(n=n, axis="model", axis_size=4)
    bitwise = []
    for d in range(4):
        idx_a, idx_b = engine.layout_index_maps(layout, d)
        for idx in (idx_a, idx_b):
            idx = jnp.asarray(idx, jnp.int32)
            with ops.using_implementation("pallas_interpret"):
                got = ops.cutvals_at(idx, g.edges, g.weights)
            bitwise.append(
                np.array_equal(
                    np.asarray(got),
                    np.asarray(ref.cutvals_at(idx, g.edges, g.weights)),
                )
            )
    out["cut_tables_bitwise"] = bool(all(bitwise))
    return out


def check_solve_distributed():
    """End-to-end `solve_distributed` vs single-device `solve` parity.

    Two regimes (DESIGN.md §2.4):
      - data-only mesh: identical partition + the same compiled pool
        program + provably-exhaustive striped merge ⇒ cut values equal;
      - data+model mesh at opt_steps=0: oversized subgraphs route
        through the sharded statevector at the same linear-ramp
        parameters the (lifted-budget) single-device pool uses ⇒ equal.
    """
    import dataclasses

    from repro.core import paraqaoa as para_mod
    from repro.core import distributed as dist_mod
    from repro.core.partition import partition_for_solver

    g = Graph.erdos_renyi(48, 0.3, seed=7)
    cfg = para_mod.ParaQAOAConfig(
        n_qubits=8, top_k=2, p_layers=2, opt_steps=10
    )
    want = para_mod.solve(g, cfg)
    got = dist_mod.solve_distributed(g, cfg, {"data": 4})
    out = {
        "pool_cut_matches_single": bool(got.cut_value == want.cut_value),
        "striped_merge_engaged": bool(got.report.extra["merge_shards"] == 4),
        "assignments_consistent": bool(
            float(cut_value(g, jnp.asarray(got.assignment))) == got.cut_value
        ),
    }

    cfg0 = dataclasses.replace(cfg, opt_steps=0)
    part = partition_for_solver(g, 10)  # budget lifted by log2(model)=2
    want0 = para_mod.solve(
        g, dataclasses.replace(cfg0, n_qubits=10), partition=part
    )
    got0 = dist_mod.solve_distributed(g, cfg0, {"data": 2, "model": 4})
    out["model_cut_matches_lifted_single"] = bool(
        got0.cut_value == want0.cut_value
    )
    out["model_routed_subproblems"] = bool(
        got0.report.extra["sharded_subproblems"] > 0
    )
    return out


def check_problem_distributed():
    """QUBO/MIS linear terms through the distributed paths (DESIGN.md §9):
    `solve_distributed` on a data mesh must match single-device `solve`
    on the same `Problem` exactly (same pool program keyed has_lin=True,
    same linear-aware striped merge), and the MIS result must be a valid
    independent set."""
    from repro.core import paraqaoa as para_mod
    from repro.core import distributed as dist_mod
    from repro.core.graph import Problem, independent_set_violations

    rng = np.random.default_rng(17)
    n = 48
    e = np.array(
        [(i, j) for i in range(n) for j in range(i + 1, n)
         if rng.random() < 0.15],
        dtype=np.int32,
    )
    q = rng.normal(size=e.shape[0]).astype(np.float32)
    h = rng.normal(size=n).astype(np.float32)
    prob = Problem.qubo(n, e, q, linear=h, offset=0.25)
    cfg = para_mod.ParaQAOAConfig(
        n_qubits=8, top_k=2, p_layers=2, opt_steps=10
    )
    want = para_mod.solve(prob, cfg)
    got = dist_mod.solve_distributed(prob, cfg, {"data": 4})
    out = {
        "qubo_cut_matches_single": bool(got.cut_value == want.cut_value),
        "qubo_assignments_equal": bool(
            np.array_equal(got.assignment, want.assignment)
        ),
    }

    import dataclasses

    # beam-pruned MIS solves can leave violations; the 1-flip refinement
    # provably clears them (dropping a violating vertex gains >= P-1 > 0)
    g = Graph.erdos_renyi(40, 0.12, seed=9)
    mis = Problem.mis(g)
    cfg_r = dataclasses.replace(cfg, refine_steps=60)
    want_m = para_mod.solve(mis, cfg_r)
    got_m = dist_mod.solve_distributed(mis, cfg_r, {"data": 4})
    out["mis_cut_matches_single"] = bool(got_m.cut_value == want_m.cut_value)
    out["mis_valid_independent_set"] = bool(
        independent_set_violations(g, got_m.assignment) == 0
    )
    return out


def check_service_mesh():
    """Service-backend parity (DESIGN.md §6.5): the same request mix
    through the single-device `LocalBackend` and through `MeshBackend`
    (solve_pool over an emulated 4-device `data` mesh) must produce
    bit-identical per-request cuts and assignments — and non-cached
    requests must stay bit-identical to solo `core.solve` on their own
    planned knobs. Recalibration is pinned off so both services plan
    identically (knob choice is time-dependent with it on)."""
    from repro.core import paraqaoa as para_mod
    from repro.service import SLA, ServiceConfig, SolveService
    from repro.service.workload import request_mix, tenant_mix

    graphs = request_mix(6, (30, 60), 0.2, 0.25, seed=3)
    tenants = tenant_mix(6, 2, seed=3)
    sla = SLA(deadline_s=20.0)

    def run_service(mesh):
        svc = SolveService(ServiceConfig(
            batch_slots=8, max_qubits=8, mesh=mesh, max_inflight=2,
            recalibrate=False,
        ))
        rids = [svc.submit(g, sla, tenant=t)
                for g, t in zip(graphs, tenants)]
        svc.drain()
        return svc, rids

    svc_l, rids_l = run_service(None)
    svc_m, rids_m = run_service("data=4")

    out = {"backends_parity": True, "solo_parity": True}
    for g, rl, rm in zip(graphs, rids_l, rids_m):
        ra, rb = svc_l.results[rl], svc_m.results[rm]
        out["backends_parity"] &= bool(
            ra.cut_value == rb.cut_value
            and np.array_equal(ra.assignment, rb.assignment)
        )
        if not ra.cached:
            solo = para_mod.solve(g, ra.plan.to_config())
            out["solo_parity"] &= bool(ra.cut_value == solo.cut_value)
    out["mesh_backend_engaged"] = bool(
        svc_m.backend.describe()["devices"] == 4
        and svc_m.stats.dispatches > 0
    )
    out["tenants_accounted"] = bool(
        set(svc_m.stats.tenants) == set(tenants)
        and sum(t.completed for t in svc_m.stats.tenants.values()) == 6
    )
    out["async_window_used"] = bool(svc_m.stats.max_inflight_seen >= 2)
    return out


def main():
    checks = {
        "solve_pool": check_solve_pool,
        "sharded_qaoa": check_sharded_qaoa,
        "merge_sharded": check_merge_sharded,
        "engine_grad": check_engine_grad,
        "engine_interpret": check_engine_interpret,
        "solve_distributed": check_solve_distributed,
        "problem_distributed": check_problem_distributed,
        "service_mesh": check_service_mesh,
    }
    which = sys.argv[1] if len(sys.argv) > 1 else ""
    if which not in checks:
        print(f"usage: python -m repro.core._dist_checks {{{'|'.join(checks)}}}")
        raise SystemExit(2)
    print(json.dumps(checks[which]()))


if __name__ == "__main__":
    main()
