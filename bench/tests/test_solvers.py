"""A four-solver cell on four CPU devices: the pool split over a `data` mesh
comes out correct against the reference, and a pool with one solver's rows
answered with another's does not.

`small.json`'s shape with ``n_solvers`` 4: M = 6 subgraphs, so the pool
program pads two empty rows to even the mesh out. The four devices exist
only in a process that asks for them before JAX starts, so each case runs
this file as a script in a subprocess:

    python bench/tests/test_solvers.py sound|shard_swapped
"""

import contextlib
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SOLVERS = 4


@contextlib.contextmanager
def shard_swapped():
    """The second solver's rows are answered with the first solver's."""
    import jax
    import numpy as np
    from repro.core import qaoa

    original = qaoa.solve_subgraph_batch_program

    def program(cfg, has_linear=False):
        full = original(cfg, has_linear)

        def run(*arrays):
            m = arrays[0].shape[0]
            rows = -(-m // cfg.n_solvers)  # a solver's rows, padding included
            index = np.arange(m)
            index[rows:2 * rows] = index[:min(rows, m - rows)]
            return jax.tree.map(lambda x: x[index], full(*arrays))

        return run

    qaoa.solve_subgraph_batch_program = program
    try:
        yield
    finally:
        qaoa.solve_subgraph_batch_program = original


def run_small(case: str, seed=2**31 + 41) -> dict:
    """One small four-solver cell on the CPU, sound or with the fault."""
    import tempfile

    import instances
    import run

    with open(os.path.join(HERE, "small.json")) as f:
        config = json.load(f)
    config["solver"]["n_solvers"] = SOLVERS
    with open(os.path.join(run.BENCH, "traffic", "solo.json")) as f:
        mix = json.load(f)
    cell = {"name": "small.data4", "config": "small", "traffic": "solo",
            "chips": SOLVERS}
    device = {"platform": "cpu", "kind": "cpu", "count": SOLVERS}
    fault = shard_swapped() if case == "shard_swapped" else (
        contextlib.nullcontext())
    with fault, tempfile.TemporaryDirectory() as out:
        _, _, attempted, failed, checks, correct = run.run_cell(
            cell, config, mix, seed, 0.5, False, device, time.perf_counter(),
            out_dir=out)
    m = len(instances.solver_ranges(config["instance"]["n"],
                                    config["solver"]["n_qubits"]))
    return {"correct": correct, "checks": checks, "attempted": attempted,
            "failed": failed, "m": m}


def in_subprocess(case: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={SOLVERS}")
    proc = subprocess.run([sys.executable, os.path.abspath(__file__), case],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_solvers_are_correct():
    got = in_subprocess("sound")
    assert got["m"] % SOLVERS, got  # the pool pads rows to its mesh
    assert got["correct"], got["checks"]


def test_one_solver_answered_with_another_is_caught():
    got = in_subprocess("shard_swapped")
    assert got["failed"] == 0, got
    assert not got["correct"], got["checks"]


if __name__ == "__main__":
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
    import jax

    if len(jax.devices()) < SOLVERS:
        raise SystemExit(f"needs {SOLVERS} devices, found {jax.devices()}")
    print(json.dumps(run_small(sys.argv[1])))
