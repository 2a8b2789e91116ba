"""Pure-jnp reference oracles for every Pallas kernel.

These are the semantic ground truth: each kernel in this package is tested
(`tests/test_kernels.py`) with ``assert_allclose`` against the function of
the same name here, across shape/dtype sweeps.

Complex statevectors are carried as (re, im) float pairs throughout —
TPU Pallas has no complex register type, and splitting the planes lets the
mixer run as real matmuls on the MXU.

Bit convention: basis index ``b`` assigns vertex/qubit ``q`` the bit
``(b >> q) & 1`` (low bits = low vertex ids).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


# Linear (per-vertex) terms fold into the XOR edge form through a virtual
# bit: h_v * bit_v(b) == h_v * (bit_v(b) XOR bit_30(b)) because bit 30 of
# any basis index is 0 (indices are int32 and n <= 29 everywhere). One
# appended row (v, 30, h_v) per vertex therefore makes the *unchanged* XOR
# kernels score quadratic + linear in a single pass.
VIRTUAL_BIT = 30


def append_linear_rows(edges: jnp.ndarray, weights: jnp.ndarray, linear: jnp.ndarray):
    """Append one (v, VIRTUAL_BIT, h_v) row per vertex to the edge arrays."""
    n = linear.shape[0]
    v = jnp.arange(n, dtype=jnp.int32)
    extra = jnp.stack([v, jnp.full((n,), VIRTUAL_BIT, dtype=jnp.int32)], axis=1)
    return (
        jnp.concatenate([edges, extra], axis=0),
        jnp.concatenate([weights, linear.astype(weights.dtype)], axis=0),
    )


def popcount(x: jnp.ndarray) -> jnp.ndarray:
    """Population count for non-negative int32 arrays (SWAR, no wraparound)."""
    x = x - ((x >> 1) & 0x55555555)
    x = (x & 0x33333333) + ((x >> 2) & 0x33333333)
    x = (x + (x >> 4)) & 0x0F0F0F0F
    x = x + (x >> 8)
    x = x + (x >> 16)
    return x & 0x3F


def cutvals(
    n: int, edges: jnp.ndarray, weights: jnp.ndarray, linear: jnp.ndarray | None = None
) -> jnp.ndarray:
    """Objective value of every basis state: (2^n,) float32.

    ``edges`` (E, 2) int32, ``weights`` (E,) float32; padding rows must be
    (0, 0) with weight 0. ``linear`` (n,) float32, when given, adds
    ``sum_v h_v * bit_v(b)`` via virtual-bit rows.
    """
    if linear is not None:
        edges, weights = append_linear_rows(edges, weights, linear)
    idx = jnp.arange(2**n, dtype=jnp.int32)

    def body(acc, ew):
        i, j, w = ew
        crossed = ((idx >> i) ^ (idx >> j)) & 1
        return acc + w * crossed.astype(jnp.float32), None

    init = jnp.zeros((2**n,), dtype=jnp.float32)
    acc, _ = jax.lax.scan(body, init, (edges[:, 0], edges[:, 1], weights))
    return acc


def cutvals_at(
    idx: jnp.ndarray,
    edges: jnp.ndarray,
    weights: jnp.ndarray,
    linear: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Objective values at arbitrary basis indices (for sharded statevectors,
    where each device owns a slice/permutation of the amplitude space)."""
    if linear is not None:
        edges, weights = append_linear_rows(edges, weights, linear)

    def body(acc, ew):
        i, j, w = ew
        crossed = ((idx >> i) ^ (idx >> j)) & 1
        return acc + w * crossed.astype(jnp.float32), None

    init = jnp.zeros(idx.shape, dtype=jnp.float32)
    acc, _ = jax.lax.scan(body, init, (edges[:, 0], edges[:, 1], weights))
    return acc


def apply_phase(re, im, cutv, gamma):
    """Diagonal cost layer: psi <- exp(-i * gamma * c) * psi, planewise."""
    c = jnp.cos(gamma * cutv)
    s = jnp.sin(gamma * cutv)
    return re * c + im * s, im * c - re * s


def rx_kron_parts(beta, k: int):
    """(C, D) with C + iD = RX(2*beta)^{⊗k} = (e^{-i beta X})^{⊗k}.

    Entry [a, b] = cos(beta)^(k-d) * (-i sin(beta))^d with d = popcount(a^b).
    """
    a = jnp.arange(2**k, dtype=jnp.int32)
    d = popcount(a[:, None] ^ a[None, :])
    cb, sb = jnp.cos(beta), jnp.sin(beta)
    # integer powers via cumprod tables (negative bases stay exact)
    cpow = jnp.cumprod(jnp.concatenate([jnp.ones((1,), cb.dtype), jnp.full((k,), cb)]))
    spow = jnp.cumprod(jnp.concatenate([jnp.ones((1,), sb.dtype), jnp.full((k,), sb)]))
    mag = cpow[k - d] * spow[d]
    rfac = jnp.asarray([1.0, 0.0, -1.0, 0.0])[d % 4]
    ifac = jnp.asarray([0.0, -1.0, 0.0, 1.0])[d % 4]
    return mag * rfac, mag * ifac


def apply_mixer_bits(re, im, n: int, lo_bit: int, nbits: int, beta):
    """RX(2β)^{⊗nbits} on qubits [lo_bit, lo_bit+nbits) of a flat 2^n state.

    One grouped unitary: a (2^nbits, 2^nbits) real-pair contraction over a
    reshaped view that exposes the target qubits on the contracted axis.
    The building block of both the full mixer below and the sharded
    engine's post-all_to_all global-qubit mix (DESIGN.md §2.6).
    """
    C, D = rx_kron_parts(beta, nbits)
    shape = (2 ** (n - lo_bit - nbits), 2**nbits, 2**lo_bit)
    re3, im3 = re.reshape(shape), im.reshape(shape)
    re_new = jnp.einsum("ab,xby->xay", C, re3) - jnp.einsum("ab,xby->xay", D, im3)
    im_new = jnp.einsum("ab,xby->xay", C, im3) + jnp.einsum("ab,xby->xay", D, re3)
    return re_new.reshape(-1), im_new.reshape(-1)


def apply_mixer(re, im, n: int, beta, group: int = 7):
    """Full transverse-field mixer U_M(beta) = prod_q e^{-i beta X_q}.

    Applied as ceil(n/group) grouped unitaries via `apply_mixer_bits`.
    """
    for g0 in range(0, n, group):
        re, im = apply_mixer_bits(re, im, n, g0, min(group, n - g0), beta)
    return re, im


def expectation(re, im, cutv):
    """<psi| diag(c) |psi> = sum_b |psi_b|^2 c_b."""
    return jnp.sum((re * re + im * im) * cutv)


def vdot(a, b):
    """sum_b a_b * b_b of two flat real vectors."""
    return jnp.sum(a * b)


def cut_batch_dense(spins: jnp.ndarray, adjacency: jnp.ndarray, total_weight):
    """Cut values for ±1 spin assignments via dense matmul (MXU form).

    spins: (B, V) float32 in {-1, +1}; adjacency: (V, V) float32 symmetric.
    cut = (W_total - 0.5 * s^T A s) / 2   [0.5 because A double-counts edges]
    """
    quad = jnp.einsum("bi,ij,bj->b", spins, adjacency, spins)
    return (total_weight - 0.5 * quad) / 2.0


# ---------------------------------------------------------------------------
# Dense-unitary oracle for the whole QAOA layer (test-only, n <= 8):
# builds the exact 2^n x 2^n unitary and applies it to a complex vector.
# ---------------------------------------------------------------------------
def dense_qaoa_layer(psi: jnp.ndarray, cutv: jnp.ndarray, gamma, beta, n: int):
    psi = jnp.exp(-1j * gamma * cutv.astype(jnp.complex64)) * psi
    c, s = np.cos(float(beta)), np.sin(float(beta))
    rx = np.array([[c, -1j * s], [-1j * s, c]], dtype=np.complex64)
    u = np.array([[1.0]], dtype=np.complex64)
    for _ in range(n):
        u = np.kron(rx, u)  # qubit q is bit q: later kron factors are higher bits
    return jnp.asarray(u) @ psi
