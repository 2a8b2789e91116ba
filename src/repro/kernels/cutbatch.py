"""Pallas TPU kernel: batched Max-Cut evaluation of candidate assignments.

The merge phase scores huge frontiers of candidate assignments; on dense
graphs the MXU form wins:   cut_b = (W_tot − ½ s_b^T A s_b) / 2.

Grid: (batch tiles × K-dim chunks). Per step the kernel multiplies the
(BB, KV) spin slice into the (KV, V) adjacency slab, accumulating the
(BB, V) product in a VMEM scratch accumulator; the final chunk contracts
the accumulator against the full (BB, V) spin rows to the (BB, 1) output —
the classic matmul+epilogue fusion.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning
from repro.kernels.mixer import F32_DOT

BATCH_TILE = 128
K_CHUNK = 512


def _kernel(nk: int, wtot_ref, s_chunk_ref, a_ref, s_full_ref, out_ref, acc_ref):
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(s_chunk_ref[...], a_ref[...], **F32_DOT)

    @pl.when(kk == nk - 1)
    def _epilogue():
        quad = jnp.sum(acc_ref[...] * s_full_ref[...], axis=1, keepdims=True)
        out_ref[...] = (wtot_ref[0, 0] - 0.5 * quad) * 0.5


@functools.partial(jax.jit, static_argnames=("bt", "kc", "interpret"))
def _cut_batch_dense(spins, adjacency, total_weight, *, bt: int, kc: int,
                     interpret: bool):
    b, v = spins.shape
    # pad batch and V to tile multiples; padded spins=+1 rows are discarded,
    # padded adjacency rows/cols are zero so they never contribute.
    bp = tuning.round_up(b, bt)
    vp = tuning.round_up(v, kc)
    sp = jnp.ones((bp, vp), jnp.float32).at[:b, :v].set(spins)
    ap = jnp.zeros((vp, vp), jnp.float32).at[:v, :v].set(adjacency)
    wtot = jnp.asarray(total_weight, jnp.float32).reshape(1, 1)
    nk = vp // kc

    out = pl.pallas_call(
        functools.partial(_kernel, nk),
        grid=(bp // bt, nk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda ib, ik: (0, 0)),
            pl.BlockSpec((bt, kc), lambda ib, ik: (ib, ik)),  # spin K-slice
            pl.BlockSpec((kc, vp), lambda ib, ik: (ik, 0)),  # adjacency slab
            pl.BlockSpec((bt, vp), lambda ib, ik: (ib, 0)),  # full spin rows
        ],
        out_specs=pl.BlockSpec((bt, 1), lambda ib, ik: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, vp), jnp.float32)],
        interpret=interpret,
    )(wtot, sp, ap, sp)
    return out[:b, 0]


def cut_batch_dense(spins, adjacency, total_weight, *, interpret: bool = False):
    """spins (B, V) ±1 float32; adjacency (V, V) float32 → (B,) cut values."""
    b, v = spins.shape
    _, bt = tuning.pad_and_tile(
        b, tuning.param("cut_batch_dense", v, "batch_tile", BATCH_TILE))
    _, kc = tuning.pad_and_tile(
        v, tuning.param("cut_batch_dense", v, "k_chunk", K_CHUNK))
    return _cut_batch_dense(spins, adjacency, total_weight, bt=bt, kc=kc,
                            interpret=interpret)
