"""Pallas TPU kernel: fused transverse-field mixer RX(2β)^{⊗k}.

The full n-qubit mixer factorizes into ⌈n/7⌉ grouped unitaries of size
2^7 = 128 — exactly one MXU tile. The group matrix is *generated inside the
kernel* from β and popcount(a⊕b) (zero HBM traffic for the operator):

    U[a,b] = cos(β)^(k−d)·(−i sin β)^d,  d = popcount(a⊕b)
    C = Re U (d even), D = Im U (d odd) — both symmetric, so the state can
    be right-multiplied:  out = S·C ± (i) S·D  on (re, im) planes.

Two launchers cover the two layouts a group call sees:

  - `mixer_group_matmul`: the group occupies the trailing axis of a
    (R, 2^k) view — row tiles, two MXU matmuls per step.
  - `mixer_group_strided`: the group sits mid-state, i.e. the flat state
    factors as (X, 2^k, Y) with Y > 1. The strided BlockSpec index map
    carves (tx, 2^k, ty) blocks straight out of that view and contracts
    the middle axis in-kernel, so the old (X, 2^k, Y) → (X·Y, 2^k)
    moveaxis relayout (and its XLA copies on both sides of every group
    call) is gone — measured in `results/BENCH_kernel_autotune.json`
    (§Perf C11).

Block sizes resolve through `kernels.tuning` (autotuned per shape bucket
when enabled, hard defaults otherwise) as static jit arguments.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tuning
from repro.kernels.ref import popcount

ROW_TILE = 512
X_TILE = 8  # strided launcher: rows of the (X, 2^k, Y) view per block
Y_TILE = 128  # strided launcher: trailing-stride lanes per block

# In-kernel float32 matmuls. Mosaic's default contraction takes bf16
# passes, which moved a subgraph's final <cut> by ~3% of its weight
# against the float32 reference on a TPU v5e; the state needs float32.
F32_DOT = dict(preferred_element_type=jnp.float32,
               precision=jax.lax.Precision.HIGHEST)


def rx_group_mats(beta, k: int):
    """(C, D) = (Re, Im) of the 2^k RX-group unitary, generated in-registers.

    Shared by every mixer-bearing kernel (grouped, strided, fused layer).
    Integer powers via the exponent trick: lax.pow on non-negative
    magnitudes + sign bookkeeping (exact for negative bases). Both C and D
    are symmetric; C is even in β and D odd, so the adjoint of the group
    unitary is the same generator evaluated at −β — the identity the
    `kernels.ops` custom-vjp rules run on.
    """
    dk = 2**k
    a = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    d = popcount(a ^ b)
    dd = d.astype(jnp.float32)
    kk = jnp.float32(k)
    cb, sb = jnp.cos(beta), jnp.sin(beta)
    mag = (
        jnp.power(jnp.abs(cb), kk - dd)
        * jnp.power(jnp.abs(sb), dd)
        * jnp.where(cb < 0, (-1.0) ** (kk - dd), 1.0)
        * jnp.where(sb < 0, (-1.0) ** dd, 1.0)
    )
    m4 = d % 4
    cmat = mag * jnp.where(m4 == 0, 1.0, jnp.where(m4 == 2, -1.0, 0.0))
    dmat = mag * jnp.where(m4 == 1, -1.0, jnp.where(m4 == 3, 1.0, 0.0))
    return cmat, dmat


def _mixer_kernel(k: int, b_ref, re_ref, im_ref, ore_ref, oim_ref):
    cmat, dmat = rx_group_mats(b_ref[0, 0], k)
    re = re_ref[...]
    im = im_ref[...]
    ore_ref[...] = jnp.dot(re, cmat, **F32_DOT) - jnp.dot(im, dmat, **F32_DOT)
    oim_ref[...] = jnp.dot(im, cmat, **F32_DOT) + jnp.dot(re, dmat, **F32_DOT)


@functools.partial(jax.jit, static_argnames=("k", "tile", "interpret"))
def _mixer_group_matmul(re_mat, im_mat, beta, k: int, *, tile: int,
                        interpret: bool):
    r, dk = re_mat.shape
    b = jnp.asarray(beta, jnp.float32).reshape(1, 1)
    spec = pl.BlockSpec((tile, dk), lambda i: (i, 0))
    ore, oim = pl.pallas_call(
        functools.partial(_mixer_kernel, k),
        grid=(r // tile,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((r, dk), jnp.float32),
            jax.ShapeDtypeStruct((r, dk), jnp.float32),
        ],
        interpret=interpret,
    )(b, re_mat, im_mat)
    return ore, oim


def mixer_group_matmul(re_mat, im_mat, beta, k: int, *, interpret: bool = False):
    """Apply RX^{⊗k} to the trailing axis of (R, 2^k) state views."""
    r, dk = re_mat.shape
    assert dk == 2**k, (dk, k)
    tile = tuning.clamp_tile(r, tuning.param("mixer_matmul", r, "row_tile",
                                             ROW_TILE))
    return _mixer_group_matmul(re_mat, im_mat, beta, k, tile=tile,
                               interpret=interpret)


def _mixer_strided_kernel(k: int, b_ref, re_ref, im_ref, ore_ref, oim_ref):
    cmat, dmat = rx_group_mats(b_ref[0, 0], k)
    re = re_ref[...]  # (tx, 2^k, ty): group axis is the middle stride
    im = im_ref[...]
    mix = lambda v, m: jnp.einsum("xby,ba->xay", v, m, **F32_DOT)
    ore_ref[...] = mix(re, cmat) - mix(im, dmat)
    oim_ref[...] = mix(im, cmat) + mix(re, dmat)


@functools.partial(jax.jit,
                   static_argnames=("k", "tile_x", "tile_y", "interpret"))
def _mixer_group_strided(re3, im3, beta, k: int, *, tile_x: int, tile_y: int,
                         interpret: bool):
    x, dk, y = re3.shape
    b = jnp.asarray(beta, jnp.float32).reshape(1, 1)
    spec = pl.BlockSpec((tile_x, dk, tile_y), lambda i, j: (i, 0, j))
    ore, oim = pl.pallas_call(
        functools.partial(_mixer_strided_kernel, k),
        grid=(x // tile_x, y // tile_y),
        in_specs=[pl.BlockSpec((1, 1), lambda i, j: (0, 0)), spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((x, dk, y), jnp.float32),
            jax.ShapeDtypeStruct((x, dk, y), jnp.float32),
        ],
        interpret=interpret,
    )(b, re3, im3)
    return ore, oim


def mixer_group_strided(re3, im3, beta, k: int, *, interpret: bool = False):
    """Apply RX^{⊗k} to the *middle* axis of (X, 2^k, Y) state views —
    the relayout-free path for groups above the low bits."""
    x, dk, y = re3.shape
    assert dk == 2**k, (dk, k)
    rows = x * y
    tile_x = tuning.clamp_tile(
        x, tuning.param("mixer_strided", rows, "tile_x", X_TILE))
    tile_y = tuning.clamp_tile(
        y, tuning.param("mixer_strided", rows, "tile_y", Y_TILE))
    return _mixer_group_strided(re3, im3, beta, k, tile_x=tile_x,
                                tile_y=tile_y, interpret=interpret)


def apply_mixer_bits(re, im, n: int, lo_bit: int, nbits: int, beta, *,
                     interpret: bool = False):
    """RX(2β)^{⊗nbits} on qubits [lo_bit, lo_bit+nbits) of a flat 2^n state.

    lo_bit == 0 is the layout-A fast path (group on the trailing axis,
    plain row-tiled matmul). For lo_bit > 0 the strided kernel contracts
    the middle axis of the (X, 2^nbits, Y) view in place — the reshapes
    here are metadata-only, so no relayout copies are issued.
    """
    k = nbits
    x = 2 ** (n - lo_bit - k)
    y = 2**lo_bit
    re3 = re.reshape(x, 2**k, y)
    im3 = im.reshape(x, 2**k, y)
    if y == 1:
        re_m, im_m = re3.reshape(x, 2**k), im3.reshape(x, 2**k)
        re_m, im_m = mixer_group_matmul(re_m, im_m, beta, k, interpret=interpret)
        return re_m.reshape(-1), im_m.reshape(-1)
    re_m, im_m = mixer_group_strided(re3, im3, beta, k, interpret=interpret)
    return re_m.reshape(-1), im_m.reshape(-1)


def apply_mixer_bits_relayout(re, im, n: int, lo_bit: int, nbits: int, beta, *,
                              interpret: bool = False):
    """Pre-§Perf-C11 path: moveaxis the group to the trailing axis, run the
    row-tiled matmul, moveaxis back. Kept as the measured baseline for the
    autotune harness's relayout comparison (and as a parity oracle)."""
    k = nbits
    x = 2 ** (n - lo_bit - k)
    y = 2**lo_bit
    re3 = re.reshape(x, 2**k, y)
    im3 = im.reshape(x, 2**k, y)
    if y == 1:
        re_m, im_m = re3.reshape(x, 2**k), im3.reshape(x, 2**k)
        re_m, im_m = mixer_group_matmul(re_m, im_m, beta, k, interpret=interpret)
        return re_m.reshape(-1), im_m.reshape(-1)
    re_m = jnp.moveaxis(re3, 1, 2).reshape(x * y, 2**k)
    im_m = jnp.moveaxis(im3, 1, 2).reshape(x * y, 2**k)
    re_m, im_m = mixer_group_matmul(re_m, im_m, beta, k, interpret=interpret)
    re = jnp.moveaxis(re_m.reshape(x, y, 2**k), 2, 1).reshape(-1)
    im = jnp.moveaxis(im_m.reshape(x, y, 2**k), 2, 1).reshape(-1)
    return re, im


def apply_mixer(re, im, n: int, beta, group: int = 7, *, interpret: bool = False):
    """Full mixer via grouped `apply_mixer_bits` kernel calls."""
    for g0 in range(0, n, group):
        re, im = apply_mixer_bits(
            re, im, n, g0, min(group, n - g0), beta, interpret=interpret
        )
    return re, im
