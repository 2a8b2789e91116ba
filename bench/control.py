#!/usr/bin/env python3
"""Readings that the limits of `check.py` are set from, on the chip.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--kinds a,b]

For each seed, in one process, one JSON line per reading of the numbers
`check.py` compares (all kinds, or those ``--kinds`` names):

  sound      the program solves the seed's instance once; every number;
  reference  the float32 reference itself answers in the solver pool's
             place; the pool's numbers (what the reference reads against
             itself);
  control    the reference computed with every amplitude product in three
             bf16 passes (what matmul precision ``high`` computes, the step
             below the configuration's float32 at ``highest``) answers in
             the pool's place;
  fault:*    the float32 reference's answer in the pool's place, broken as
             `reference_faults` says; and fault:merge_worst_rows, the
             reference beam keeping its worst rows over the pool's
             candidates.

`FAULTS` plants the same faults in the program itself; the benchmark's
tests run whole cells with them at a small size on the CPU.
"""

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

import run as bench_run  # noqa: E402

@contextlib.contextmanager
def adam_step_unchanged():
    """Each Adam step returns the parameters it was given."""
    from repro.core import engine, qaoa

    original = engine.adam_scan
    engine.adam_scan = lambda grad_fn, params, steps, lr: params
    qaoa._solve_subgraph_batch_program.cache_clear()
    try:
        yield
    finally:
        engine.adam_scan = original
        qaoa._solve_subgraph_batch_program.cache_clear()


@contextlib.contextmanager
def half_batch():
    """The pool solves the first half of its subgraphs; the other half is
    answered with the first subgraph's result."""
    import jax
    import jax.numpy as jnp
    from repro.core import qaoa

    original = qaoa.solve_subgraph_batch_program

    def program(cfg, has_linear=False):
        full = original(cfg, has_linear)

        def run(*arrays):
            half = max(1, arrays[0].shape[0] // 2)
            out = full(*(a[:half] for a in arrays))
            rest = arrays[0].shape[0] - half
            return jax.tree.map(
                lambda x: jnp.concatenate(
                    [x, jnp.repeat(x[:1], rest, axis=0)]), out)

        return run

    qaoa.solve_subgraph_batch_program = program
    try:
        yield
    finally:
        qaoa.solve_subgraph_batch_program = original


@contextlib.contextmanager
def answer_altered():
    """One vertex of the merged assignment is flipped where it is made."""
    from repro.core import paraqaoa

    original = paraqaoa.merge_candidates

    def merge(*args, **kwargs):
        assignment, score, bw = original(*args, **kwargs)
        assignment = assignment.copy()
        assignment[len(assignment) // 2] ^= 1
        return assignment, score, bw

    paraqaoa.merge_candidates = merge
    try:
        yield
    finally:
        paraqaoa.merge_candidates = original


@contextlib.contextmanager
def refine_unchanged():
    """Refinement returns the assignment it was given."""
    import importlib

    # the package re-exports a function of the module's name
    local_search = importlib.import_module(
        "repro.core.baselines.local_search")
    original = local_search.refine

    def refine(graph, assignment, steps, linear=None):
        return original(graph, assignment, 0, linear=linear)

    local_search.refine = refine
    try:
        yield
    finally:
        local_search.refine = original


@contextlib.contextmanager
def merge_worst_rows():
    """At every level the merge's beam keeps its worst rows, not its best;
    the best of the last level's rows is still the answer."""
    import jax.numpy as jnp
    from repro.core import merge

    original = merge._level_step
    empty = -1e29  # rows the beam has not filled score -1e30

    def level_step(carry, xs, **kwargs):
        assign, score = carry
        (lo, bits, eu, ev, ew, lin), level = xs
        negated = jnp.where(score > empty, -score, score)
        (assign, score), out = original(
            (assign, negated), ((lo, bits, eu, ev, -ew, -lin), level),
            **kwargs)
        return (assign, jnp.where(score > empty, -score, score)), out

    merge._level_step = level_step
    try:
        yield
    finally:
        merge._level_step = original


FAULTS = {"adam_step_unchanged": adam_step_unchanged,
          "half_batch": half_batch, "answer_altered": answer_altered,
          "refine_unchanged": refine_unchanged,
          "merge_worst_rows": merge_worst_rows}


def solve_once(config, seed: int):
    """(instance, the recorded solve) of one solve of the seed's instance."""
    import jax.numpy as jnp
    from repro.core import ParaQAOAConfig, solve
    from repro.core.graph import Graph

    import capture
    import instances

    solver = config["solver"]
    n = config["instance"]["n"]
    edges, weights = instances.build(config["instance"], solver["n_qubits"],
                                     seed)
    graph = Graph(n=n, edges=jnp.asarray(edges), weights=jnp.asarray(weights),
                  n_edges=int(edges.shape[0]))
    recorder = capture.Recorder().install()
    try:
        out = solve(graph, ParaQAOAConfig(**solver))
        return (n, edges, weights), recorder.solves([out])
    finally:
        recorder.uninstall()


def with_pool(solves, gammas, betas, expectation, bitstrings):
    """Copies of ``solves`` whose pool answered these instead."""
    return [dataclasses.replace(s, gammas=gammas, betas=betas,
                                expectation=expectation,
                                bitstrings=bitstrings) for s in solves]


def reference_faults(reference, answer):
    """Faults planted in the float32 reference put in the pool's place:
    (name, gammas, betas, expectation, bitstrings)."""
    gam, bet, exp, top = answer
    pool = reference.pool()
    m, p = gam.shape
    ramp_g = np.broadcast_to(pool.ramp[0], (m, p)).copy()
    ramp_b = np.broadcast_to(pool.ramp[1], (m, p)).copy()
    cand = np.zeros((m, pool.k), np.int64)
    e_ramp, _, _, top_ramp = pool.evaluate(reference.subs, ramp_g, ramp_b,
                                           cand)
    yield "adam_step_unchanged", ramp_g, ramp_b, e_ramp, top_ramp
    half = m // 2
    h = lambda x: np.concatenate([x[:half], np.repeat(x[:1], m - half, 0)])
    yield "half_batch", h(gam), h(bet), h(exp), h(top)
    altered = top.copy()
    altered[:, 0] ^= 1
    yield "answer_altered", gam, bet, exp, altered


POOL_KINDS = ("reference", "control", "fault:adam_step_unchanged",
              "fault:half_batch", "fault:answer_altered")


def merge_worst_rows_reading(reference, solves) -> dict:
    """merge_deficit of the reference beam that keeps its worst rows (the
    beam over negated weights), run over the pool's candidates."""
    cands = solves[0].bitstrings
    best = reference.beam(cands)[1]
    scores = reference.beam(cands, -np.asarray(reference.weights))[2]
    return {"merge_deficit": best + float(np.min(scores[np.isfinite(
        scores)]))}


def readings(config, seed: int, kinds=None):
    """Yield (kind, values) for one seed: the program's solve, the control,
    and the faults planted in the reference put in the pool's place; only
    the ``kinds`` named, where given ("fault:*" names every fault)."""
    import check

    def wanted(kind):
        return kinds is None or kind in kinds or (
            kind.startswith("fault:") and "fault:*" in kinds)

    instance, solves = solve_once(config, seed)
    reference = check.Reference(*instance, config["solver"], seed)
    if wanted("sound"):
        yield "sound", reference.compare(solves)
    if wanted("fault:merge_worst_rows"):
        yield "fault:merge_worst_rows", merge_worst_rows_reading(reference,
                                                                 solves)
    if not any(wanted(k) for k in POOL_KINDS):
        return
    answer = reference.pool().solve(reference.subs)
    if wanted("reference"):
        yield "reference", reference.pool_numbers(with_pool(solves, *answer))
    if wanted("control"):
        ctrl = reference.pool("bf16x3").solve(reference.subs)
        yield "control", reference.pool_numbers(with_pool(solves, *ctrl))
    for name, *answer in reference_faults(reference, answer):
        if wanted("fault:" + name):
            yield "fault:" + name, reference.pool_numbers(
                with_pool(solves, *answer))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--kinds", default=None,
                    help="comma-separated kinds of reading (default all)")
    args = ap.parse_args(argv)
    kinds = set(args.kinds.split(",")) if args.kinds else None

    _, cell, config, _ = bench_run.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = bench_run.CACHE
    bench_run.require_chips(cell["chips"])
    bench_run.use_compile_cache()
    sys.path.insert(0, os.path.join(bench_run.ROOT, "src"))
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind, values in readings(config, seed, kinds):
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": kind, "values": values,
                              "t": time.time()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
