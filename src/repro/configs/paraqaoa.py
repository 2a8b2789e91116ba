"""The paper's own workload as a selectable config: parameter taxonomy of
§4.2 (hardware-dependent / input-dependent / tunable)."""
from repro.core.paraqaoa import ParaQAOAConfig

# production setting: 26-qubit solvers (the paper's GPU cap), pod-scale pool
CONFIG = ParaQAOAConfig(
    n_qubits=26,
    # one per chip on a 16x16 pod: `solve` runs the pool over a `data`
    # mesh of the first 256 devices, so this config needs 256 of them
    n_solvers=256,
    top_k=2,
    merge_level=2,
    p_layers=3,
    opt_steps=60,
)

# CPU-runnable setting used by tests/benchmarks
REDUCED = ParaQAOAConfig(
    n_qubits=12,
    n_solvers=1,
    top_k=2,
    merge_level=1,
    p_layers=3,
    opt_steps=30,
)
