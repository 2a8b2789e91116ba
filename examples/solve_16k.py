"""End-to-end driver for the paper's headline: a >10,000-vertex Max-Cut
instance solved by the full ParaQAOA pipeline (partition → batched QAOA
pool → level-aware merge → refinement), with stage timings.

  PYTHONPATH=src python examples/solve_16k.py            # 16,000 vertices
  PYTHONPATH=src python examples/solve_16k.py --n 2000   # smaller/faster
  PYTHONPATH=src python examples/solve_16k.py --n 2000 --mesh data=4

The paper solves 16k vertices in 19 min on 2×RTX4090; this container is a
single CPU core, so default edge probability is reduced (0.01 ≈ 1.3M
edges). Without ``--mesh`` the pipeline runs single-device. With
``--mesh data=N[,model=M]`` it runs through the distributed runtime in
core/distributed.py — the solver pool shard_mapped over `data`,
oversized subproblems' statevectors over `model`, and the merge frontier
striped per `--merge` policy (docs/DESIGN.md §2). On a single-CPU host
the mesh devices are emulated (docs/TESTING.md); on a real accelerator
mesh the same flags drive the pod-scale layout.
"""

import argparse
import time

ap = argparse.ArgumentParser(
    description="ParaQAOA headline instance: >10k-vertex Max-Cut, "
    "optionally through the distributed mesh runtime."
)
ap.add_argument("--n", type=int, default=16_000,
                help="vertex count (paper headline: 16,000)")
ap.add_argument("--p", type=float, default=0.01,
                help="Erdős-Rényi edge probability (CPU-scaled default)")
ap.add_argument("--qubits", type=int, default=10,
                help="per-device qubit budget; a model mesh axis lifts it "
                "by log2(model)")
ap.add_argument("--k", type=int, default=1,
                help="top-K candidates kept per subgraph")
ap.add_argument("--opt-steps", type=int, default=10,
                help="Adam steps per subgraph QAOA")
ap.add_argument("--refine", type=int, default=200,
                help="1-flip local-search sweeps on the merged cut")
ap.add_argument("--mesh", type=str, default=None, metavar="SPEC",
                help="device mesh spec, e.g. 'data=4' or 'data=2,model=4' "
                "— enables the core/distributed.py pipeline (emulated "
                "devices on a single-CPU host)")
ap.add_argument("--merge", choices=("auto", "striped", "single"),
                default="auto", dest="merge_mode",
                help="distributed merge policy (see solve_maxcut --help)")
ap.add_argument("--sharded-opt-steps", type=int, default=0,
                help="Adam steps on oversized (model-sharded) subproblem "
                "parameters, run through the sharded evolution "
                "(DESIGN.md §2.6); 0 keeps the linear ramp")
ap.add_argument("--kernel-tuning", action="store_true",
                help="resolve Pallas block shapes from the committed "
                "autotune cache (src/repro/kernels/tuning_cache.json, "
                "DESIGN.md §2.7) instead of the hard-coded defaults; "
                "regenerate with benchmarks/kernel_autotune.py "
                "--write-cache")
args = ap.parse_args()

mesh_spec = None
if args.mesh:
    # parse + arrange device emulation before the first jax backend touch
    from repro import compat
    from repro.launch.mesh import mesh_spec_size, parse_mesh_spec

    mesh_spec = parse_mesh_spec(args.mesh)
    compat.ensure_host_device_count(mesh_spec_size(mesh_spec))

from repro import compat
from repro.core import ParaQAOAConfig, solve, solve_distributed
from repro.core.baselines import local_search
from repro.core.graph import Graph
from repro.kernels import tuning
from repro.launch.mesh import device_info

compat.use_compile_cache()
dev = device_info()
print(f"device: {dev['platform']} ({dev['kind']}) x{dev['count']}")
if args.kernel_tuning:
    tuning.set_enabled(True)

t0 = time.time()
print(f"generating G({args.n}, {args.p}) ...", flush=True)
graph = Graph.erdos_renyi(args.n, args.p, seed=0)
print(f"  {graph.n_edges} edges ({time.time()-t0:.1f}s)")

cfg = ParaQAOAConfig(
    n_qubits=args.qubits, top_k=args.k, p_layers=2,
    opt_steps=args.opt_steps, beam_width=64, refine_steps=args.refine,
    sharded_opt_steps=args.sharded_opt_steps,
)
if mesh_spec is not None:
    out = solve_distributed(graph, cfg, mesh_spec, merge_mode=args.merge_mode)
    extra = out.report.extra
    print(f"mesh {extra['mesh']}: {extra['merge_shards']} merge shards "
          f"({extra['merge_mode']}), "
          f"{extra['sharded_subproblems']} model-sharded subproblems "
          f"(sharded_opt_steps={extra['sharded_opt_steps']})")
else:
    out = solve(graph, cfg)
print(f"ParaQAOA cut = {out.cut_value:.0f} on {args.n} vertices")
for stage, t in out.timings.items():
    print(f"  {stage:12s} {t:.1f}s")

# classical sanity reference at the same scale
_, ls_cut, ls_rep = local_search(graph, restarts=1, steps=300)
print(f"local-search reference: {ls_cut:.0f} ({ls_rep.runtime_s:.1f}s)")
print(f"total weight: {float(graph.total_weight()):.0f} "
      f"(random-cut expectation = {float(graph.total_weight())/2:.0f})")
