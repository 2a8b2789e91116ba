#!/usr/bin/env python3
"""Smoke run of the ParaQAOA solve path on a TPU, through its entry points.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # the mesh path, on four chips

One process holds the chip(s) for the whole run. Phases, each printing its
own ``[phase]`` lines; any failed check raises and the script exits non-zero:

  device   the default backend is a TPU (there is no CPU fallback), the
           kernels dispatch to compiled Pallas, and the solver program
           carries ``tpu_custom_call`` kernels;
  parity   8 subgraphs of the 16,000-vertex instance through the solver
           batch program under ``pallas`` and under the ``xla`` reference
           at ``default_matmul_precision("highest")``: final <cut> agree;
  solve    `repro.core.solve` on G(16000, 0.01) at 14 qubits; the returned
           assignment is re-scored on the host and must equal the reported
           value and beat the random-cut expectation W/2;
  service  `SolveService` on the `LocalBackend` answers 8 requests of
           200-400 vertices; each cut and assignment is bit-identical to a
           solo `solve` at the same knobs (DESIGN.md §6.1).

``--four-chips`` runs only the mesh phase: `solve_distributed` on
``data=4`` must equal the one-chip `solve` of the same instance, and the
subproblems that ``data=2,model=2`` routes to the sharded statevector
must agree with the same subproblems run flat on one chip.

Instances are made from ``--seed``; nothing is read from disk. The last
line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_QUBITS = 14
# The paper's headline instance (Fig. 12), at the density examples/solve_16k.py
# uses: about 1.28 M edges.
HEADLINE_N, HEADLINE_P = 16_000, 0.01
HEADLINE = dict(n_qubits=N_QUBITS, top_k=2, p_layers=3, opt_steps=30,
                beam_width=64, refine_steps=200)
# Final <cut> of one subgraph, Pallas vs the highest-precision XLA reference.
# Both evolve a float32 state; reordered float32 sums stay near 1e-6 of the
# subgraph weight through 3 layers and 30 Adam steps, while a single bf16
# MXU pass (8-bit mantissa) in a mixer matmul would show near 1e-3.
PARITY_RTOL = 1e-4
# Top-k probabilities of one 15-qubit subproblem, sharded over two chips vs
# flat on one: they are near 1e-3, and the two decompositions of the mixer
# reorder float32 sums at the 1e-9 level.
MESH_PROB_ATOL = 1e-6


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require(ok: bool, what) -> None:
    """A check of the run's results; unlike `assert`, it survives -O."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def check_device(want_count: int):
    """The chip is there and the solve path will run compiled Pallas."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found platform "
            f"{d0.platform!r} ({d0.device_kind}); there is no CPU fallback")
    if len(devices) < want_count:
        raise SystemExit(f"chip_smoke: needs {want_count} chips, found "
                         f"{len(devices)}")
    from repro import compat
    from repro.kernels import ops
    from repro.launch.mesh import device_info

    cache = compat.use_compile_cache()
    impl = ops.get_implementation()
    require(impl == "pallas", f"kernel dispatch resolved to {impl!r}")
    dev = device_info()
    log("device", f"{dev['platform']} ({dev['kind']}) x{dev['count']}, "
        f"kernels={impl}, compile cache {cache}")
    return dev


def host_cut(graph, assignment) -> float:
    """Cut weight of a 0/1 assignment, computed on the host in float64."""
    import numpy as np

    e = np.asarray(graph.edges)[: graph.n_edges]
    w = np.asarray(graph.weights)[: graph.n_edges].astype(np.float64)
    a = np.asarray(assignment)
    return float(np.sum(w[a[e[:, 0]] != a[e[:, 1]]]))


def headline_instance(seed: int):
    from repro.core.graph import Graph

    t0 = time.perf_counter()
    graph = Graph.erdos_renyi(HEADLINE_N, HEADLINE_P, seed=seed)
    log("instance", f"G({HEADLINE_N}, {HEADLINE_P}) seed {seed}: "
        f"{graph.n_edges} edges in {time.perf_counter() - t0:.3f} s")
    return graph


def parity(graph, cfg, n_sub: int = 8, impl: str = "pallas"):
    """Solver batch under `impl` vs the XLA reference at highest precision."""
    import jax
    import numpy as np

    from repro.core import qaoa as qaoa_mod
    from repro.core.partition import partition_for_solver
    from repro.kernels import ops

    part = partition_for_solver(graph, cfg.n_qubits)
    # the densest subgraphs: an edgeless one would agree trivially
    order = np.argsort([-s.n_edges for s in part.subgraphs], kind="stable")
    subs = [part.subgraphs[i] for i in order[:n_sub]]
    edges, weights, masks = qaoa_mod.pad_subgraph_arrays(subs, cfg.n_qubits)
    qcfg = cfg.qaoa_config()
    with ops.using_implementation(impl):
        program = qaoa_mod.solve_subgraph_batch_program(qcfg)
        hlo = program.lower(edges, weights, masks).as_text()
        require("tpu_custom_call" in hlo or impl != "pallas",
                "the solver program carries no Pallas TPU kernel")
        got = program(edges, weights, masks)
    with ops.using_implementation("xla"), jax.default_matmul_precision(
            "highest"):
        want = qaoa_mod.solve_subgraph_batch_program(qcfg)(
            edges, weights, masks)
    got_e = np.asarray(got.expectation, np.float64)
    want_e = np.asarray(want.expectation, np.float64)
    scale = np.asarray([max(1.0, float(np.sum(np.asarray(s.weights))))
                        for s in subs])
    gap = np.abs(got_e - want_e) / scale
    log("parity", f"{n_sub} subgraphs, {[s.n_edges for s in subs]} edges; "
        f"<cut> {impl} {got_e.tolist()} vs xla-highest {want_e.tolist()}")
    log("parity", f"max |gap| / subgraph weight = {float(gap.max())!r} "
        f"(tolerance {PARITY_RTOL})")
    require(float(gap.max()) <= PARITY_RTOL,
            f"kernel <cut> departs from the reference by "
            f"{float(gap.max())!r} of the subgraph weight")


def solve_headline(graph, cfg, ls_steps: int = 300):
    """`solve` end to end; the result is re-scored on the host."""
    import numpy as np

    from repro.core import solve
    from repro.core.baselines import local_search

    w_total = float(np.sum(np.asarray(graph.weights)[: graph.n_edges],
                           dtype=np.float64))
    for run in ("cold", "warm"):
        out = solve(graph, cfg)
        times = ", ".join(f"{k} {v!r}" for k, v in out.timings.items())
        log("solve", f"{run}: value {out.cut_value!r}, M={out.partition.m}; "
            f"{times}")
    rescored = host_cut(graph, out.assignment)
    log("solve", f"host re-score {rescored!r}, random-cut expectation "
        f"W/2 = {w_total / 2!r}")
    require(rescored == out.cut_value, (rescored, out.cut_value))
    require(rescored > w_total / 2, (rescored, w_total / 2))
    _, ls_cut, ls_rep = local_search(graph, restarts=1, steps=ls_steps)
    log("solve", f"local-search reference {ls_cut!r} in "
        f"{ls_rep.runtime_s!r} s; ratio {rescored / ls_cut!r}")


def service(seed: int, n_range=(200, 400), p: float = 0.15, load: int = 8,
            max_qubits: int = N_QUBITS):
    """`SolveService` answers; each answer equals a solo `solve`."""
    import numpy as np

    from repro.core import solve
    from repro.service import SLA, ServiceConfig, SolveService
    from repro.service.backend import LocalBackend
    from repro.service.workload import request_mix

    graphs = request_mix(load, n_range, p, 0.0, seed)
    svc = SolveService(
        ServiceConfig(max_qubits=max_qubits, enable_cache=False,
                      recalibrate=False, enforce_deadlines=False),
        backend=LocalBackend(),
    )
    t0 = time.perf_counter()
    rids = [svc.submit(g, SLA()) for g in graphs]
    svc.drain()
    log("service", f"{load} requests, n {[g.n for g in graphs]}, answered "
        f"in {time.perf_counter() - t0!r} s over {svc.stats.dispatches} "
        f"dispatches")
    for g, rid in zip(graphs, rids):
        r = svc.results[rid]
        require(r.status == "completed", (rid, r.status))
        solo = solve(g, r.plan.to_config())
        require(r.cut_value == solo.cut_value,
                (rid, r.cut_value, solo.cut_value))
        require(np.array_equal(r.assignment, solo.assignment), rid)
        require(host_cut(g, r.assignment) == r.cut_value, rid)
        log("service", f"req {rid}: n={g.n} knobs {r.plan.knobs} cut "
            f"{r.cut_value!r} == solo")


def mesh_phase(seed: int, n: int = 4_000, p: float = 0.01,
               n_qubits: int = N_QUBITS):
    """`solve_distributed` on data=4 and data=2,model=2 against one chip."""
    import jax
    import numpy as np

    from repro.core import ParaQAOAConfig, solve, solve_distributed
    from repro.core import distributed as dist
    from repro.core import qaoa as qaoa_mod
    from repro.core.graph import Graph
    from repro.core.partition import partition_for_solver
    from repro.kernels import ops

    graph = Graph.erdos_renyi(n, p, seed=seed)
    cfg = ParaQAOAConfig(**{**HEADLINE, "n_qubits": n_qubits})
    one = solve(graph, cfg)
    four = solve_distributed(graph, cfg, "data=4")
    extra = four.report.extra
    log("mesh", f"G({n}, {p}): one chip {one.cut_value!r}, data=4 "
        f"{four.cut_value!r} (merge {extra['merge_mode']}, "
        f"{extra['merge_shards']} shards); solve_s one "
        f"{one.timings['solve_s']!r} vs data=4 {four.timings['solve_s']!r}")
    require(four.cut_value == one.cut_value, (four.cut_value, one.cut_value))
    require(host_cut(graph, four.assignment) == four.cut_value,
            "data=4 assignment does not score its reported value")
    # every chip of the pool must have held work, not just device 0
    stats = [d.memory_stats() for d in jax.devices()[:4]]
    busy = [s.get("peak_bytes_in_use", 0) if s else None for s in stats]
    log("mesh", f"peak bytes in use per device {busy}")
    require(all(b is None for b in busy) or all(b for b in busy),
            "a device of the data=4 mesh held nothing")

    # data=2,model=2 at a lifted budget: 15-qubit subproblems exceed the
    # 14-qubit device cap and take the sharded statevector (linear-ramp
    # angles, as `sharded_opt_steps=0` runs them)
    cfg0 = dataclasses.replace(cfg, opt_steps=0)
    lifted = solve_distributed(graph, cfg0, "data=2,model=2")
    n_big = lifted.report.extra["sharded_subproblems"]
    log("mesh", f"data=2,model=2: {n_big} model-sharded subproblems, value "
        f"{lifted.cut_value!r}")
    require(n_big > 0, "no subproblem took the sharded statevector")
    require(host_cut(graph, lifted.assignment) == lifted.cut_value,
            "data=2,model=2 assignment does not score its reported value")

    part = partition_for_solver(graph, n_qubits + 1)
    big = [s for s in part.subgraphs if s.n == n_qubits + 1][:8]
    e, w, _ = qaoa_mod.pad_subgraph_arrays(big, n_qubits + 1)
    gammas, betas = qaoa_mod.linear_ramp_init(cfg.p_layers, cfg.ramp_delta)
    mesh = dist.as_mesh("data=2,model=2")
    sharded = dist.sharded_qaoa_batch(e, w, n_qubits + 1, gammas, betas, mesh,
                                      axis="model", top_k=cfg.top_k)
    worst_e = worst_p = 0.0
    for i, s in enumerate(big):
        cutv = ops.cutvals(n_qubits + 1, s.edges, s.weights)
        re, im = qaoa_mod.qaoa_statevector(cutv, n_qubits + 1, gammas, betas)
        flat_e = float(ops.expectation(re, im, cutv))
        flat_p = np.sort(np.asarray(jax.lax.top_k(re * re + im * im,
                                                  cfg.top_k)[0]))
        got_p = np.sort(np.asarray(sharded.probs[i]))
        scale = max(1.0, float(np.sum(np.asarray(s.weights))))
        worst_e = max(worst_e, abs(float(sharded.expectation[i]) - flat_e)
                      / scale)
        worst_p = max(worst_p, float(np.max(np.abs(got_p - flat_p))))
    log("mesh", f"{len(big)} sharded-vs-flat subproblems: max <cut> gap / "
        f"weight {worst_e!r}, max top-{cfg.top_k} prob gap {worst_p!r}")
    require(worst_e <= PARITY_RTOL and worst_p <= MESH_PROB_ATOL,
            (worst_e, worst_p))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated instance")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh phase, on four chips")
    args = ap.parse_args(argv)

    device = check_device(4 if args.four_chips else 1)
    from repro.core import ParaQAOAConfig

    if args.four_chips:
        mesh_phase(args.seed)
    else:
        cfg = ParaQAOAConfig(**HEADLINE)
        graph = headline_instance(args.seed)
        parity(graph, cfg)
        solve_headline(graph, cfg)
        service(args.seed)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
