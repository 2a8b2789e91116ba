"""Statevector QAOA solver for Max-Cut subproblems.

Max-Cut's cost Hamiltonian is diagonal in the computational basis, so one
QAOA layer is:  (1) an elementwise phase by the per-basis-state cut value,
(2) the transverse-field mixer RX(2β)^{⊗n}, applied as grouped matmuls.
The evolution itself lives in `repro.core.engine` (DESIGN.md §2.6) — the
same engine the sharded program runs per shard — with every op dispatched
through `repro.kernels.ops` (Pallas on TPU, jnp on CPU).

The classical outer loop (paper: per-subgraph scipy-style optimizers) is a
*batched, differentiable* Adam ascent on ⟨H_C⟩ — all subgraphs optimize
simultaneously under one `vmap`, initialized from a linear ramp
[Sack & Serbyn 2021; Montañez-Barrera & Michielsen 2025].
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import engine
from repro.core.graph import Graph
from repro.kernels import ops
from repro.kernels import tuning


@dataclasses.dataclass(frozen=True)
class QAOAConfig:
    n_qubits: int  # statevector size (subgraphs padded to this)
    p_layers: int = 3
    opt_steps: int = 30
    learning_rate: float = 0.05
    ramp_delta: float = 0.75  # linear-ramp initialization scale
    top_k: int = 4  # paper's K (Selective Distribution Exploration)
    mixer_group: int = 7  # qubits per fused mixer matmul (7 → 128×128)


class QAOAResult(NamedTuple):
    bitstrings: jnp.ndarray  # (K,) int32 basis indices (pad bits forced to 0)
    probs: jnp.ndarray  # (K,) float32 marginal probabilities
    expectation: jnp.ndarray  # scalar: final ⟨cut⟩
    gammas: jnp.ndarray  # (p,) optimized
    betas: jnp.ndarray  # (p,)


def linear_ramp_init(p: int, delta: float):
    """γ_l ramps up, β_l ramps down — discretized annealing schedule."""
    l = (jnp.arange(p, dtype=jnp.float32) + 0.5) / p
    return delta * l, delta * (1.0 - l)


def qaoa_statevector(cutv, n: int, gammas, betas, group: int = 7):
    """Run the p-layer ansatz; returns (re, im) planes of the final state.

    A thin wrapper over the shared engine's `evolve` on a `FlatLayout` —
    the identical per-layer code the sharded program runs per shard
    (DESIGN.md §2.6).
    """
    layout = engine.FlatLayout(n=n, group=group)
    cut = engine.CutTable(cutv, None, None, None)
    re, im, _ = engine.evolve(layout, cut, gammas, betas)
    return re, im


def qaoa_expectation(params, cutv, n: int, group: int = 7):
    gammas, betas = params
    re, im = qaoa_statevector(cutv, n, gammas, betas, group=group)
    return ops.expectation(re, im, cutv)


def optimize_params(cutv, n: int, cfg: QAOAConfig):
    """Adam ascent on ⟨cut⟩. Returns optimized (gammas, betas).

    The update rule is the shared `engine.adam_scan` — the same scan the
    sharded ascent runs per shard (DESIGN.md §2.6). Like
    `engine.sharded_ascent`, the differentiated evolution runs under the
    caller's active implementation: the `kernels.ops` custom-vjp rules
    (DESIGN.md §2.7) make the backward trace fire the same dispatched
    kernels, so no `xla` gradient pin is needed."""
    g0, b0 = linear_ramp_init(cfg.p_layers, cfg.ramp_delta)

    neg_obj = lambda p: -qaoa_expectation(p, cutv, n, group=cfg.mixer_group)
    return engine.adam_scan(
        jax.grad(neg_obj), (g0, b0), cfg.opt_steps, cfg.learning_rate
    )


def topk_marginal(re, im, n: int, real_mask, k: int):
    """Top-k bitstrings of the *marginal* over real (non-padding) qubits.

    Padding qubits keep the statevector shape uniform across a vmapped
    subgraph batch; their amplitude mass is folded back onto the
    pad-bits-zero representative so top-k never returns duplicates that
    differ only in padding bits. ``real_mask`` is
    (2^n_real - 1) and may be traced (per-subgraph under vmap).
    """
    marg = re * re + im * im
    # fold each padding qubit (a zero bit of the mask) onto its 0 value,
    # highest first: fixed-order elementwise adds, where a scatter-add's
    # order (and so its rounding) may change with the batch it runs in
    for q in reversed(range(n)):
        v = marg.reshape(-1, 2, 2**q)
        folded = jnp.concatenate(
            [v[:, :1] + v[:, 1:], jnp.zeros_like(v[:, 1:])], axis=1
        ).reshape(-1)
        marg = jnp.where((real_mask >> q) & 1, marg, folded)
    vals, inds = jax.lax.top_k(marg, k)
    return inds, vals


def solve_subgraph(edges, weights, real_mask, cfg: QAOAConfig, linear=None) -> QAOAResult:
    """End-to-end QAOA solve of one (padded) subgraph.

    edges/weights are padded to a common (E_pad,) size; real_mask encodes the
    live qubit count. ``linear`` (n_qubits,) f32, optional, adds per-vertex
    diagonal terms (QUBO/MIS) to the cost oracle; ``None`` keeps the Max-Cut
    trace identical to the linear-free solver. Designed to be vmapped across
    a subgraph batch.
    """
    n = cfg.n_qubits
    cutv = ops.cutvals(n, edges, weights, linear)
    gammas, betas = optimize_params(cutv, n, cfg)
    re, im = qaoa_statevector(cutv, n, gammas, betas, group=cfg.mixer_group)
    exp = ops.expectation(re, im, cutv)
    bits, probs = topk_marginal(re, im, n, real_mask, cfg.top_k)
    return QAOAResult(bits, probs, exp, gammas, betas)


solve_subgraph_batch = jax.vmap(solve_subgraph, in_axes=(0, 0, 0, None))
solve_subgraph_batch_linear = jax.vmap(solve_subgraph, in_axes=(0, 0, 0, None, 0))


@compat.cached_program
def _solve_subgraph_batch_program(
    cfg: QAOAConfig, impl: str, tune: tuple, has_lin: bool = False
):
    """Impl- and tuning-keyed builder behind `solve_subgraph_batch_program`.

    The `kernels.ops` dispatch reads the active implementation at
    *trace* time, so two impls must map to two compiled programs for
    `ops.using_implementation` to reach this path (the same contract
    `_sharded_qaoa_program` keeps, DESIGN.md §2.6). The keyed ``impl``
    is re-asserted inside the traced function: jit traces lazily on
    first call, which may happen outside the context the program was
    requested under — the key and the traced dispatch must not disagree.
    ``tune`` is the `kernels.tuning` block-shape state (DESIGN.md §2.7),
    re-asserted the same way and for the same reason — tile choices are
    trace-time too, and the key makes them visible to the compile ledger.
    ``has_lin`` selects the linear-terms variant (QUBO/MIS buckets, 4th
    input array); the False key compiles the exact Max-Cut program of the
    linear-free solver, keeping that path bit-identical.
    """

    if has_lin:

        def run(e, w, m, l):
            with ops.using_implementation(impl), tuning.using_state(tune):
                return solve_subgraph_batch_linear(e, w, m, cfg, l)

    else:

        def run(e, w, m):
            with ops.using_implementation(impl), tuning.using_state(tune):
                return solve_subgraph_batch(e, w, m, cfg)

    return jax.jit(run)


def solve_subgraph_batch_program(cfg: QAOAConfig, has_linear: bool = False):
    """Cached whole-batch jit of `solve_subgraph_batch` for one config.

    The end-to-end drivers run this instead of the eager vmap: one fused
    XLA program per static config (~1.7x faster on CPU), and — because the
    distributed `solve_pool` wraps the *same* jitted computation in
    shard_map — the single-device and pool-parallel paths produce
    bit-identical candidates (XLA's eager op-by-op dispatch rounds
    differently from the fused program; the default 30 Adam steps
    (``QAOAConfig.opt_steps``) on a non-convex landscape amplify that
    last-ulp difference into different top-k picks). The underlying
    cache keys on (config, active `kernels.ops` implementation, active
    `kernels.tuning` block-shape state, linear-terms variant).
    """
    return _solve_subgraph_batch_program(
        cfg, ops.get_implementation(), tuning.state(), bool(has_linear)
    )


def index_to_bits(indices: jnp.ndarray, n: int) -> jnp.ndarray:
    """(...,) int32 basis indices → (..., n) int8 bit arrays (bit q = vertex q)."""
    shifts = jnp.arange(n, dtype=jnp.int32)
    return ((indices[..., None] >> shifts) & 1).astype(jnp.int8)


def pad_subgraph_arrays(
    subgraphs, n_qubits: int, e_pad: int | None = None,
    n_rows: int | None = None,
):
    """Stack per-subgraph (edges, weights, real_mask) into batch arrays.

    ``n_rows`` pads the batch dimension with empty-graph filler rows
    (mask 1, no edges — the same convention `solve_pool` pads with), the
    shape-stable packing the serve-side scheduler relies on (one source
    of truth for the DESIGN.md §6.1 parity contract).
    """
    import numpy as np

    if e_pad is None:
        e_pad = max(max(g.edges.shape[0] for g in subgraphs), 1)
    b = len(subgraphs)
    rows = b if n_rows is None else n_rows
    assert rows >= b, (rows, b)
    edges = np.zeros((rows, e_pad, 2), dtype=np.int32)
    weights = np.zeros((rows, e_pad), dtype=np.float32)
    masks = np.ones((rows,), dtype=np.int32)
    for i, g in enumerate(subgraphs):
        m = g.edges.shape[0]
        assert m <= e_pad, (m, e_pad)
        assert g.n <= n_qubits, (g.n, n_qubits)
        edges[i, :m] = np.asarray(g.edges)
        weights[i, :m] = np.asarray(g.weights)
        masks[i] = (1 << g.n) - 1
    return jnp.asarray(edges), jnp.asarray(weights), jnp.asarray(masks)


def pad_linear_arrays(linears, n_qubits: int, n_rows: int | None = None):
    """Stack per-subgraph linear-term vectors into one (rows, n_qubits)
    float32 batch array, zero-padded on both axes — the companion of
    `pad_subgraph_arrays` for QUBO/MIS buckets (padding qubits and filler
    rows contribute h = 0, so they stay objective-neutral)."""
    import numpy as np

    b = len(linears)
    rows = b if n_rows is None else n_rows
    assert rows >= b, (rows, b)
    out = np.zeros((rows, n_qubits), dtype=np.float32)
    for i, l in enumerate(linears):
        l = np.asarray(l, dtype=np.float32)
        assert l.shape[0] <= n_qubits, (l.shape[0], n_qubits)
        out[i, : l.shape[0]] = l
    return jnp.asarray(out)
