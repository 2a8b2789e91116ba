"""Max-Cut solve driver: the paper's pipeline as a CLI.

Single device:

  PYTHONPATH=src python -m repro.launch.solve_maxcut --n 2000 --p 0.05 \
      --qubits 10 --k 2 --compare-gw

Distributed (the paper's pool-parallel architecture; on a laptop/CI the
mesh is CPU host-device emulation, arranged automatically):

  PYTHONPATH=src python -m repro.launch.solve_maxcut --n 400 --mesh data=2
  PYTHONPATH=src python -m repro.launch.solve_maxcut --n 400 \
      --mesh data=2,model=4 --schedule alternating

See docs/DESIGN.md §2 for the mesh axes and README.md for a quickstart.
"""

from __future__ import annotations

import argparse


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="repro.launch.solve_maxcut",
        description="Solve Max-Cut with the ParaQAOA divide-and-conquer "
        "pipeline (partition → QAOA solver pool → level-aware merge).",
    )
    ap.add_argument("--n", type=int, default=400,
                    help="vertex count of the Erdős-Rényi instance")
    ap.add_argument("--p", type=float, default=0.1,
                    help="Erdős-Rényi edge probability")
    ap.add_argument("--seed", type=int, default=0,
                    help="graph-generation seed (runs are seed-stable)")
    ap.add_argument("--problem", choices=("maxcut", "qubo", "mis"),
                    default="maxcut",
                    help="problem family: Max-Cut on the generated graph, "
                    "a random QUBO over its topology (quadratic + N(0,1) "
                    "linear terms), or penalty-encoded maximum independent "
                    "set — all through the same diagonal-cost oracle")
    ap.add_argument("--weights", choices=("unit", "uniform", "spin"),
                    default="unit",
                    help="edge-weight family: unit weights, "
                    "uniform(0.1,1) weights, or ±1 spin-glass couplings")
    ap.add_argument("--check-oracle", action="store_true",
                    help="small-n only (n <= 18): compare the solved "
                    "objective against exhaustive brute force and, for "
                    "--problem mis, assert the selected set is independent")
    ap.add_argument("--qubits", type=int, default=10,
                    help="per-device qubit budget N (paper: 26 on GPU); "
                    "a model mesh axis lifts it to N + log2(model)")
    ap.add_argument("--k", type=int, default=2,
                    help="top-K candidates kept per subgraph (paper's K)")
    ap.add_argument("--layers", type=int, default=3,
                    help="QAOA circuit depth p")
    ap.add_argument("--opt-steps", type=int, default=25,
                    help="Adam steps on <cut>; 0 keeps the linear-ramp init")
    ap.add_argument("--beam", type=int, default=None,
                    help="merge frontier width (default: exact 2*K^M, capped)")
    ap.add_argument("--refine", type=int, default=0,
                    help="1-flip local-search sweeps on the merged cut "
                    "(beyond-paper; 0 disables)")
    ap.add_argument("--mesh", type=str, default=None, metavar="SPEC",
                    help="device mesh spec, e.g. 'data=2' or 'data=2,model=4' "
                    "(axes: pod/data/model; model must be a power of two). "
                    "Omit for the single-device pipeline. On a single-CPU "
                    "host the devices are emulated (docs/TESTING.md)")
    ap.add_argument("--schedule", choices=("faithful", "alternating"),
                    default="alternating",
                    help="collective schedule for model-axis sharded "
                    "subproblems: 2 vs 1 all_to_all per layer")
    ap.add_argument("--sharded-opt-steps", type=int, default=0,
                    help="Adam steps on oversized (model-sharded) "
                    "subproblem parameters, optimized through the sharded "
                    "evolution (DESIGN.md §2.6); 0 keeps the linear ramp")
    ap.add_argument("--merge", choices=("auto", "striped", "single"),
                    default="auto", dest="merge_mode",
                    help="distributed merge policy: 'auto' stripes the "
                    "frontier across data shards only when provably "
                    "exhaustive (cut identical to the single-device run); "
                    "'striped' always stripes (the paper's independent "
                    "workers — may differ in the beam-pruned regime); "
                    "'single' keeps the merge on one device")
    ap.add_argument("--compare-gw", action="store_true",
                    help="also run the Goemans-Williamson baseline and "
                    "report AR / PEI against it")
    ap.add_argument("--trace-out", type=str, default=None, metavar="PATH",
                    help="export the pipeline span trace here (tracing is "
                    "off unless this is set; DESIGN.md §8)")
    ap.add_argument("--trace-format", choices=("jsonl", "chrome"),
                    default="jsonl",
                    help="trace export format: 'jsonl' (one span per "
                    "line) or 'chrome' (Perfetto-loadable trace events)")
    ap.add_argument("--profile-dir", type=str, default=None, metavar="DIR",
                    help="take a jax.profiler trace of the solve into DIR "
                    "with a recording tracer: the pipeline's stage spans "
                    "land on the profile's host plane, on one timeline "
                    "with the device's operations")
    return ap


def run(argv=None):
    args = build_parser().parse_args(argv)

    mesh_spec = None
    if args.mesh:
        # parse + emulate *before* the first jax backend touch (graph
        # construction below creates device arrays)
        from repro import compat
        from repro.launch.mesh import (
            mesh_spec_size,
            parse_mesh_spec,
            too_few_devices,
        )

        mesh_spec = parse_mesh_spec(args.mesh)
        need = mesh_spec_size(mesh_spec)
        have = compat.ensure_host_device_count(need)
        if have < need:
            raise SystemExit(too_few_devices(f"--mesh {args.mesh}", need, have))

    import contextlib

    import numpy as np

    from repro import compat
    from repro.core import ParaQAOAConfig, solve, solve_distributed
    from repro.core.graph import (
        Graph,
        Problem,
        independent_set_violations,
    )
    from repro.core.pei import pei
    from repro.launch.mesh import device_info
    from repro.obs.trace import Tracer, use_tracer

    compat.use_compile_cache()
    dev = device_info()
    print(f"[maxcut] device: {dev['platform']} ({dev['kind']}) "
          f"x{dev['count']}")
    if args.weights == "uniform":
        graph = Graph.erdos_renyi_weighted(args.n, args.p, seed=args.seed)
    elif args.weights == "spin":
        graph = Graph.spin_glass(args.n, args.p, seed=args.seed)
    else:
        graph = Graph.erdos_renyi(args.n, args.p, seed=args.seed)
    if args.problem == "mis":
        instance = Problem.mis(graph)
    elif args.problem == "qubo":
        rng = np.random.default_rng(args.seed + 0x9B0)
        e = np.asarray(graph.edges)[: graph.n_edges]
        q = np.asarray(graph.weights)[: graph.n_edges]
        instance = Problem.qubo(
            graph.n, e, q, linear=rng.normal(size=graph.n).astype(np.float32)
        )
    else:
        instance = graph
    print(f"[maxcut] G({args.n}, {args.p}): {graph.n_edges} edges "
          f"({args.problem}, {args.weights} weights)")
    cfg = ParaQAOAConfig(
        n_qubits=args.qubits, top_k=args.k, p_layers=args.layers,
        opt_steps=args.opt_steps, beam_width=args.beam,
        refine_steps=args.refine,
        sharded_opt_steps=args.sharded_opt_steps,
    )
    # §8: tracing is enabled only when an export path or a profile is
    # requested; the pipeline's ambient-tracer spans become the exported
    # trace and, under the profiler, annotations on its host plane
    tracer = (Tracer(record=True) if args.trace_out or args.profile_dir
              else None)
    scope = use_tracer(tracer) if tracer else contextlib.nullcontext()
    if args.profile_dir:
        import jax

        profile = jax.profiler.trace(args.profile_dir)
    else:
        profile = contextlib.nullcontext()
    with profile, scope:
        if mesh_spec is not None:
            out = solve_distributed(
                instance, cfg, mesh_spec,
                schedule=args.schedule, merge_mode=args.merge_mode,
            )
            extra = out.report.extra
            print(f"[maxcut] mesh {extra['mesh']}: "
                  f"{extra['merge_shards']} merge shards "
                  f"({extra['merge_mode']}), "
                  f"{extra['sharded_subproblems']} model-sharded subproblems "
                  f"(sharded_opt_steps={extra['sharded_opt_steps']})")
        else:
            out = solve(instance, cfg)
    if args.profile_dir:
        print(f"[maxcut] profile: {args.profile_dir}")
    if args.trace_out:
        tracer.export(args.trace_out, args.trace_format)
        print(f"[maxcut] trace ({args.trace_format}, "
              f"{len(tracer.spans)} spans): {args.trace_out}")
    print(f"[maxcut] value = {out.cut_value:.2f}  "
          f"(M={out.partition.m}, K={args.k}, {out.report.runtime_s:.2f}s)")
    for stage, t in out.timings.items():
        print(f"  {stage:12s} {t:.2f}s")

    if args.problem == "mis":
        viol = independent_set_violations(graph, out.assignment)
        size = int(np.sum(np.asarray(out.assignment)))
        print(f"[maxcut] mis: |S|={size}, conflict edges inside S: {viol}")
        assert viol == 0, (
            f"penalty-QUBO MIS produced {viol} conflict edge(s) — raise "
            "the penalty or the refine/merge budget"
        )

    if args.check_oracle:
        if args.n > 18:
            raise SystemExit("--check-oracle needs --n <= 18 (exhaustive)")
        from repro.core.baselines.brute_force import brute_force_problem

        _, opt, rep = brute_force_problem(instance)
        gap = opt - out.cut_value
        print(f"[maxcut] oracle: brute-force optimum {opt:.2f} "
              f"({rep.runtime_s:.2f}s), gap {gap:.4f}")
        assert gap > -1e-3 * max(1.0, abs(opt)), (
            "solver reported a value above the exhaustive optimum — "
            "objective accounting is broken", out.cut_value, opt,
        )

    if args.compare_gw:
        from repro.core.baselines import goemans_williamson

        _, v_gw, rep = goemans_williamson(graph, steps=250, rounds=64)
        print(f"[maxcut] GW reference: {v_gw:.0f} ({rep.runtime_s:.2f}s)  "
              f"AR={out.cut_value / v_gw:.3f}  "
              f"PEI={pei(out.cut_value, v_gw, out.report.runtime_s, rep.runtime_s):.1f}")
    return out


if __name__ == "__main__":
    run()
