"""Dispatch layer: every hot op has a Pallas TPU kernel and a pure-jnp path.

``implementation``:
  - "auto":   Pallas on TPU, XLA (jnp reference) elsewhere.
  - "xla":    always the jnp reference path (fast on CPU).
  - "pallas": compiled Pallas kernels (TPU).
  - "pallas_interpret": Pallas kernels in interpret mode (CPU correctness
    validation; slow — used by tests).

The jnp reference path *is* `kernels.ref` — there is exactly one source of
truth for each op's semantics.

Differentiability (DESIGN.md §2.7): the state-evolution entry points —
`apply_phase`, `apply_mixer_bits`, `apply_layer`, `expectation` — carry
analytic `jax.custom_vjp` rules registered here, *above* the dispatch.
The QAOA layer unitaries are their own adjoints up to angle sign (the
phase is a rotation by γ·c; the mixer-group generator is even in β on its
real part and odd on its imaginary part), so every backward pass re-enters
the same dispatch with negated angles — the gradient trace runs whatever
implementation the forward ran, and the ascent loops in core/engine.py and
core/qaoa.py need no `using_implementation("xla")` pin. The angle
gradients are Σ a·b reductions through the dispatched `_vdot` (the
`phase.vdot` kernel on the Pallas path, whose fixed summation order keeps
a subgraph's gradient independent of its vmap batch); the cut-value
cotangents are elementwise.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.obs.ledger import get_ledger

_IMPL = "auto"


def set_implementation(impl: str) -> None:
    global _IMPL
    assert impl in ("auto", "xla", "pallas", "pallas_interpret"), impl
    _IMPL = impl


@contextlib.contextmanager
def using_implementation(impl: str):
    """Scoped implementation override: restores the previous selection on
    exit (even on error). Dispatch happens at *trace* time, so programs
    cached outside the context keep whatever implementation they were
    traced under — cached-program builders that must honor the override
    include `get_implementation()` in their cache key."""
    global _IMPL
    prev = _IMPL
    set_implementation(impl)
    try:
        yield
    finally:
        _IMPL = prev


def get_implementation() -> str:
    if _IMPL != "auto":
        return _IMPL
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _pallas(interpret_ok: bool = True):
    impl = get_implementation()
    if impl == "pallas":
        return dict(use=True, interpret=False)
    if impl == "pallas_interpret":
        return dict(use=True, interpret=True)
    return dict(use=False, interpret=False)


def _note(op: str, x) -> None:
    """Compile-ledger op event: dispatch happens at *trace* time, so an
    op entered with tracer-typed arguments fires exactly once per
    (re)trace of the enclosing program — retrace storms show up as op
    counts in the ledger (DESIGN.md §8). Concrete-argument (eager) calls
    record nothing."""
    if isinstance(x, jax.core.Tracer):
        get_ledger().note_op(op, get_implementation())


def _f32(x):
    """Canonicalize an angle before it crosses the custom_vjp boundary:
    python floats are weakly typed and would make the cotangent aval
    mismatch the primal's inside `defvjp`."""
    return jnp.asarray(x, jnp.float32)


# ---------------------------------------------------------------------------
# cutvals / cutvals_at — diagonal objective oracle, closed-form VJP over
# (weights, linear). The diagonal is linear in both coefficient arrays, so
# the cotangents are plain masked reductions of the output cotangent:
#   d_w[e]   = Σ_b g[b] · xor_e(b)
#   d_lin[v] = Σ_b g[b] · bit_v(b)
# — reductions left to XLA (no solve path differentiates the weights).
# Integer primals (edges, idx) get float0 symbolic-zero cotangents.
# ---------------------------------------------------------------------------

def _int_zero(x):
    """Symbolic-zero cotangent for an integer-dtype primal."""
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


def _cutvals_dispatch(n, edges, weights, linear):
    p = _pallas()
    if p["use"]:
        from repro.kernels import cutvals as k

        return k.cutvals(n, edges, weights, linear, interpret=p["interpret"])
    return ref.cutvals(n, edges, weights, linear)


def _cutvals_at_dispatch(idx, edges, weights, linear):
    p = _pallas()
    if p["use"]:
        from repro.kernels import cutvals as k

        return k.cutvals_at(idx, edges, weights, linear, interpret=p["interpret"])
    return ref.cutvals_at(idx, edges, weights, linear)


def _cutvals_grads(n_lin: int, edges, idx, g):
    """Shared (d_weights, d_linear) reductions for the cutvals VJPs."""

    def edge_body(_, e):
        i, j = e
        crossed = (((idx >> i) ^ (idx >> j)) & 1).astype(jnp.float32)
        return None, jnp.sum(g * crossed)

    _, d_w = jax.lax.scan(edge_body, None, (edges[:, 0], edges[:, 1]))

    def bit_body(_, v):
        bit = ((idx >> v) & 1).astype(jnp.float32)
        return None, jnp.sum(g * bit)

    _, d_lin = jax.lax.scan(bit_body, None, jnp.arange(n_lin, dtype=jnp.int32))
    return d_w, d_lin


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _cutvals_vjp(n, edges, weights, linear):
    return _cutvals_dispatch(n, edges, weights, linear)


def _cutvals_fwd(n, edges, weights, linear):
    return _cutvals_dispatch(n, edges, weights, linear), edges


def _cutvals_bwd(n, edges, g):
    idx = jnp.arange(2**n, dtype=jnp.int32)
    d_w, d_lin = _cutvals_grads(n, edges, idx, g)
    return _int_zero(edges), d_w, d_lin


_cutvals_vjp.defvjp(_cutvals_fwd, _cutvals_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _cutvals_vjp_nolin(n, edges, weights):
    return _cutvals_dispatch(n, edges, weights, None)


def _cutvals_nolin_fwd(n, edges, weights):
    return _cutvals_dispatch(n, edges, weights, None), edges


def _cutvals_nolin_bwd(n, edges, g):
    idx = jnp.arange(2**n, dtype=jnp.int32)
    d_w, _ = _cutvals_grads(0, edges, idx, g)
    return _int_zero(edges), d_w


_cutvals_vjp_nolin.defvjp(_cutvals_nolin_fwd, _cutvals_nolin_bwd)


@jax.custom_vjp
def _cutvals_at_vjp(idx, edges, weights, linear):
    return _cutvals_at_dispatch(idx, edges, weights, linear)


def _cutvals_at_fwd(idx, edges, weights, linear):
    return _cutvals_at_dispatch(idx, edges, weights, linear), (idx, edges, linear)


def _cutvals_at_bwd(res, g):
    idx, edges, linear = res
    d_w, d_lin = _cutvals_grads(linear.shape[0], edges, idx, g)
    return _int_zero(idx), _int_zero(edges), d_w, d_lin


_cutvals_at_vjp.defvjp(_cutvals_at_fwd, _cutvals_at_bwd)


@jax.custom_vjp
def _cutvals_at_vjp_nolin(idx, edges, weights):
    return _cutvals_at_dispatch(idx, edges, weights, None)


def _cutvals_at_nolin_fwd(idx, edges, weights):
    return _cutvals_at_dispatch(idx, edges, weights, None), (idx, edges)


def _cutvals_at_nolin_bwd(res, g):
    idx, edges = res
    d_w, _ = _cutvals_grads(0, edges, idx, g)
    return _int_zero(idx), _int_zero(edges), d_w


_cutvals_at_vjp_nolin.defvjp(_cutvals_at_nolin_fwd, _cutvals_at_nolin_bwd)


def cutvals(n: int, edges, weights, linear=None):
    """Objective value of every basis state. ``linear`` (n,) f32, optional,
    adds per-vertex diagonal terms (QUBO/MIS); ``None`` keeps the Max-Cut
    trace byte-identical to the linear-free op."""
    _note("cutvals", edges)
    if linear is None:
        return _cutvals_vjp_nolin(n, edges, weights)
    return _cutvals_vjp(n, edges, weights, jnp.asarray(linear, jnp.float32))


def cutvals_at(idx, edges, weights, linear=None):
    _note("cutvals_at", idx)
    if linear is None:
        return _cutvals_at_vjp_nolin(idx, edges, weights)
    return _cutvals_at_vjp(idx, edges, weights, jnp.asarray(linear, jnp.float32))


# ---------------------------------------------------------------------------
# apply_phase — diagonal cost rotation, VJP = same rotation at −γ
# ---------------------------------------------------------------------------

def _vdot(a, b):
    """Σ a·b — the angle-gradient reductions of the VJP rules below. On
    the Pallas path the kernel fixes the summation order, so a subgraph's
    gradient is the same whatever batch it is vmapped in."""
    p = _pallas()
    if p["use"]:
        from repro.kernels import phase as k

        return k.vdot(a, b, interpret=p["interpret"])
    return ref.vdot(a, b)


def _phase_dispatch(re, im, cutv, gamma):
    p = _pallas()
    if p["use"]:
        from repro.kernels import phase as k

        return k.apply_phase(re, im, cutv, gamma, interpret=p["interpret"])
    return ref.apply_phase(re, im, cutv, gamma)


@jax.custom_vjp
def _phase_vjp(re, im, cutv, gamma):
    return _phase_dispatch(re, im, cutv, gamma)


def _phase_fwd(re, im, cutv, gamma):
    out = _phase_dispatch(re, im, cutv, gamma)
    return out, (re, im, cutv, gamma)


def _phase_bwd(res, cot):
    re, im, cutv, gamma = res
    d_ore, d_oim = cot
    # the rotation's transpose is the rotation at −γ: same dispatched kernel
    g_re, g_im = _phase_dispatch(d_ore, d_oim, cutv, -gamma)
    t = im * g_re - re * g_im
    d_gamma = _vdot(cutv, t)
    d_cutv = gamma * t
    return g_re, g_im, d_cutv, d_gamma


_phase_vjp.defvjp(_phase_fwd, _phase_bwd)


def apply_phase(re, im, cutv, gamma):
    _note("apply_phase", re)
    return _phase_vjp(re, im, cutv, _f32(gamma))


# ---------------------------------------------------------------------------
# apply_mixer_bits — RX group, VJP = same group at −β
# ---------------------------------------------------------------------------

def _mixer_bits_dispatch(n, lo_bit, nbits, re, im, beta):
    p = _pallas()
    if p["use"]:
        from repro.kernels import mixer as k

        return k.apply_mixer_bits(
            re, im, n, lo_bit, nbits, beta, interpret=p["interpret"]
        )
    return ref.apply_mixer_bits(re, im, n, lo_bit, nbits, beta)


def _neighbor_sum_bits(v, lo_bit: int, nbits: int):
    """Σ over the group's qubits of v with that qubit flipped — the
    ∂β generator contraction (each RX factor differentiates into −i·X on
    its qubit). The reshape puts bit q on the middle axis; reversing it is
    the flip. Metadata-only reshapes, one add per qubit."""
    out = jnp.zeros_like(v)
    for q in range(lo_bit, lo_bit + nbits):
        out = out + v.reshape(-1, 2, 2**q)[:, ::-1, :].reshape(v.shape)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _mixer_bits_vjp(n, lo_bit, nbits, re, im, beta):
    return _mixer_bits_dispatch(n, lo_bit, nbits, re, im, beta)


def _mixer_bits_fwd(n, lo_bit, nbits, re, im, beta):
    ore, oim = _mixer_bits_dispatch(n, lo_bit, nbits, re, im, beta)
    return (ore, oim), (ore, oim, beta)


def _mixer_bits_bwd(n, lo_bit, nbits, res, cot):
    ore, oim, beta = res
    d_ore, d_oim = cot
    # the group unitary's transpose is the group at −β: same kernel
    g_re, g_im = _mixer_bits_dispatch(n, lo_bit, nbits, d_ore, d_oim, -beta)
    # ∂out/∂β = neighbor-sum of the *output* planes rotated by i, so
    # d_beta = Σ d_ore·N(oim) − d_oim·N(ore)
    fr = _neighbor_sum_bits(ore, lo_bit, nbits)
    fi = _neighbor_sum_bits(oim, lo_bit, nbits)
    d_beta = _vdot(d_ore, fi) - _vdot(d_oim, fr)
    return g_re, g_im, d_beta


_mixer_bits_vjp.defvjp(_mixer_bits_fwd, _mixer_bits_bwd)


def apply_mixer_bits(re, im, n: int, lo_bit: int, nbits: int, beta):
    _note("apply_mixer_bits", re)
    return _mixer_bits_vjp(n, lo_bit, nbits, re, im, _f32(beta))


def apply_mixer(re, im, n: int, beta, group: int = 7):
    """Full mixer as a chain of differentiable `apply_mixer_bits` groups —
    the identical kernels fire, and the chain rule over the groups gives
    the full-mixer gradient for free."""
    _note("apply_mixer", re)
    for g0 in range(0, n, group):
        re, im = apply_mixer_bits(re, im, n, g0, min(group, n - g0), beta)
    return re, im


# ---------------------------------------------------------------------------
# apply_layer — fused phase + full mixer, VJP = reversed layer at (−γ, −β)
# ---------------------------------------------------------------------------

def _layer_dispatch(n, group, re, im, cutv, gamma, beta):
    p = _pallas()
    if p["use"]:
        from repro.kernels import fused_layer as fl
        from repro.kernels import mixer as mk

        k = min(group, n)
        dk = 2**k
        re_m, im_m = fl.fused_phase_mixer_group(
            re.reshape(-1, dk),
            im.reshape(-1, dk),
            cutv.reshape(-1, dk),
            gamma,
            beta,
            k,
            interpret=p["interpret"],
        )
        re, im = re_m.reshape(-1), im_m.reshape(-1)
        for g0 in range(k, n, group):
            re, im = mk.apply_mixer_bits(
                re, im, n, g0, min(group, n - g0), beta,
                interpret=p["interpret"],
            )
        return re, im
    re, im = ref.apply_phase(re, im, cutv, gamma)
    return ref.apply_mixer(re, im, n, beta, group=group)


def _layer_adjoint_dispatch(n, group, re, im, cutv, gamma, beta):
    """Transpose of `_layer_dispatch` applied to a cotangent: the trailing
    mixer groups at −β in reverse order, then the fused kernel in
    ``reverse`` mode (mixer group 0 before the phase) at (−γ, −β). Same
    kernel shapes as the forward — the bwd trace compiles the same ops."""
    p = _pallas()
    if p["use"]:
        from repro.kernels import fused_layer as fl
        from repro.kernels import mixer as mk

        k = min(group, n)
        dk = 2**k
        for g0 in reversed(range(k, n, group)):
            re, im = mk.apply_mixer_bits(
                re, im, n, g0, min(group, n - g0), -beta,
                interpret=p["interpret"],
            )
        re_m, im_m = fl.fused_phase_mixer_group(
            re.reshape(-1, dk),
            im.reshape(-1, dk),
            cutv.reshape(-1, dk),
            -gamma,
            -beta,
            k,
            reverse=True,
            interpret=p["interpret"],
        )
        return re_m.reshape(-1), im_m.reshape(-1)
    re, im = ref.apply_mixer(re, im, n, -beta, group=group)
    return ref.apply_phase(re, im, cutv, -gamma)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _layer_vjp(n, group, re, im, cutv, gamma, beta):
    return _layer_dispatch(n, group, re, im, cutv, gamma, beta)


def _layer_fwd(n, group, re, im, cutv, gamma, beta):
    ore, oim = _layer_dispatch(n, group, re, im, cutv, gamma, beta)
    return (ore, oim), (re, im, cutv, gamma, beta, ore, oim)


def _layer_bwd(n, group, res, cot):
    re, im, cutv, gamma, beta, ore, oim = res
    d_ore, d_oim = cot
    # ∂β: the full n-qubit mixer acts last, so its generator contraction
    # (neighbor-sum over *all* qubits) runs on the layer output
    fr = _neighbor_sum_bits(ore, 0, n)
    fi = _neighbor_sum_bits(oim, 0, n)
    d_beta = _vdot(d_ore, fi) - _vdot(d_oim, fr)
    # state cotangent through the whole layer: reversed layer at (−γ, −β)
    g_re, g_im = _layer_adjoint_dispatch(n, group, d_ore, d_oim, cutv,
                                         gamma, beta)
    # ∂γ and ∂cutv fall out of the phase rule with (re, im) the layer
    # *input* (the phase's input) and g the fully back-propagated cotangent
    t = im * g_re - re * g_im
    d_gamma = _vdot(cutv, t)
    d_cutv = gamma * t
    return g_re, g_im, d_cutv, d_gamma, d_beta


_layer_vjp.defvjp(_layer_fwd, _layer_bwd)


def apply_layer(re, im, cutv, gamma, beta, n: int, group: int = 7):
    """One full intra-shard QAOA layer: cost phase, then the n-qubit mixer.

    This is the op the statevector engine (core/engine.py, DESIGN.md §2.6)
    runs per layer on every path — flat or per-shard. On the Pallas path
    the phase and the *first* mixer group go through the fused
    `kernels/fused_layer.py` kernel (one VMEM round-trip, §Perf C3) and
    the remaining groups through the mixer kernel; the XLA path is the
    exact phase-then-mixer reference decomposition. Differentiable under
    every implementation via the analytic layer VJP (module docstring).
    """
    _note("apply_layer", re)
    return _layer_vjp(n, group, re, im, cutv, _f32(gamma), _f32(beta))


# ---------------------------------------------------------------------------
# expectation — Σ|ψ|²·c, closed-form VJP
# ---------------------------------------------------------------------------

def _expectation_dispatch(re, im, cutv):
    p = _pallas()
    if p["use"]:
        from repro.kernels import phase as k

        return k.expectation(re, im, cutv, interpret=p["interpret"])
    return ref.expectation(re, im, cutv)


@jax.custom_vjp
def _expectation_vjp(re, im, cutv):
    return _expectation_dispatch(re, im, cutv)


def _expectation_fwd(re, im, cutv):
    return _expectation_dispatch(re, im, cutv), (re, im, cutv)


def _expectation_bwd(res, g):
    re, im, cutv = res
    return 2.0 * g * re * cutv, 2.0 * g * im * cutv, g * (re * re + im * im)


_expectation_vjp.defvjp(_expectation_fwd, _expectation_bwd)


def expectation(re, im, cutv):
    _note("expectation", re)
    return _expectation_vjp(re, im, cutv)


def cut_batch_dense(spins, adjacency, total_weight):
    _note("cut_batch_dense", spins)
    p = _pallas()
    if p["use"]:
        from repro.kernels import cutbatch as k

        return k.cut_batch_dense(spins, adjacency, total_weight, interpret=p["interpret"])
    return ref.cut_batch_dense(spins, adjacency, total_weight)
