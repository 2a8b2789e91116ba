"""Pallas TPU kernel: cut values of all 2^n basis states.

This feeds the QAOA diagonal cost layer. The computation is recast as a
matmul so it runs on the MXU instead of a per-edge scalar sweep:

    bits[e, b] = ((b >> i_e) ^ (b >> j_e)) & 1        (VPU, int ops)
    cutv[b]    = w @ bits[:, b]                        (MXU)

Grid: (basis tiles × edge chunks); the edge chunk axis accumulates into the
output block (TPU grids iterate sequentially, so revisiting the same output
block across the inner axis is the canonical accumulation pattern).

Layout: basis states run along lanes, edges along sublanes — endpoints ride
in as (E, 1) columns, weights as a (1, E) row — and the (1, TILE_B) product
is stored as a lane-dense (TILE_B/128, 128) block of the (2^n/128, 128)
output view (`tuning.lane_view`). Every block is 2-D, so it stays legal for
the TPU compiler when `jax.vmap` prepends the subgraph-batch axis, and the
output carries no lane padding in HBM.

VMEM budget per step: EDGE_CHUNK×TILE_B int32 bits plane (256×1024×4 = 1 MiB)
plus the output block — comfortably under a v5e core's ~16 MiB.

Pad/tile arithmetic lives in `kernels.tuning` (`pad_chunks`, `pad_and_tile`,
`lane_view`) — one seam shared with cutbatch.py and phase.py — and the block
constants resolve through the same module's per-shape-bucket tuning table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref as ref_mod
from repro.kernels import tuning
from repro.kernels.mixer import F32_DOT

TILE_B = 1024  # basis states per block (8 sublanes × 128 lanes)
EDGE_CHUNK = 256  # edges per accumulation step


def _pad_edges(edges, weights, chunk: int):
    """Edge arrays padded to a chunk multiple; padding rows (0,0,w=0)
    contribute zero. Endpoints come back as (E_pad, 1) columns, weights as
    a (1, E_pad) row. Shared by `cutvals` and `cutvals_at`."""
    e = edges.shape[0]
    e_pad = tuning.pad_chunks(e, chunk)
    ei = jnp.zeros((e_pad, 1), jnp.int32).at[:e, 0].set(edges[:, 0])
    ej = jnp.zeros((e_pad, 1), jnp.int32).at[:e, 0].set(edges[:, 1])
    w = jnp.zeros((1, e_pad), jnp.float32).at[0, :e].set(weights)
    return ei, ej, w, e_pad


def _edge_specs(chunk: int):
    """Block specs of the (ei, ej, w) edge arrays over a (tiles, chunks) grid."""
    col = pl.BlockSpec((chunk, 1), lambda kb, ke: (ke, 0))
    return [col, col, pl.BlockSpec((1, chunk), lambda kb, ke: (0, ke))]


def _accumulate(idx, ei_ref, ej_ref, w_ref, out_ref):
    """Add one edge chunk's objective at basis indices ``idx`` (1, tile)
    into the lane-dense output block."""
    crossed = ((idx >> ei_ref[...]) ^ (idx >> ej_ref[...])) & 1  # (E, tile)
    # float32 contraction: a bf16 pass would round the edge weights
    partial = jnp.dot(
        w_ref[...], crossed.astype(jnp.float32), **F32_DOT
    ).reshape(out_ref.shape)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(pl.program_id(1) != 0)
    def _acc():
        out_ref[...] += partial


def _kernel(tile: int, ei_ref, ej_ref, w_ref, out_ref):
    # basis indices covered by this block: kb*tile + [0, tile)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    _accumulate(pl.program_id(0) * tile + lane, ei_ref, ej_ref, w_ref, out_ref)


@functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=("tile", "chunk", "interpret"))
def _cutvals(n: int, edges, weights, *, tile: int, chunk: int, interpret: bool):
    dim = 2**n
    rows, lanes, tile_rows = tuning.lane_view(dim, tile)
    ei, ej, w, e_pad = _pad_edges(edges, weights, chunk)
    out = pl.pallas_call(
        functools.partial(_kernel, tile),
        grid=(dim // tile, e_pad // chunk),
        in_specs=_edge_specs(chunk),
        out_specs=pl.BlockSpec((tile_rows, lanes), lambda kb, ke: (kb, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        interpret=interpret,
    )(ei, ej, w)
    return out.reshape(dim)


def cutvals(n: int, edges, weights, linear=None, *, interpret: bool = False):
    """(2^n,) float32 objective values. edges (E,2) int32, weights (E,) f32.

    ``linear`` (n,) f32, when given, folds per-vertex terms in as virtual-bit
    rows (`ref.append_linear_rows`) — the kernel body is untouched.
    """
    if linear is not None:
        edges, weights = ref_mod.append_linear_rows(edges, weights, linear)
    dim = 2**n
    tile = tuning.clamp_tile(dim, tuning.param("cutvals", dim, "tile_b", TILE_B))
    chunk = tuning.param("cutvals", dim, "edge_chunk", EDGE_CHUNK)
    return _cutvals(n, edges, weights, tile=tile, chunk=chunk,
                    interpret=interpret)


def _at_kernel(ei_ref, ej_ref, w_ref, idx_ref, out_ref):
    """Like `_kernel` but the basis indices come from an input block
    instead of the grid position — the sharded-statevector case, where
    each device owns an arbitrary slice/permutation of the amplitude
    space (DESIGN.md §2.6)."""
    idx = idx_ref[...].reshape(1, -1)  # (1, tile)
    _accumulate(idx, ei_ref, ej_ref, w_ref, out_ref)


@functools.partial(
    jax.jit, static_argnames=("tile", "chunk", "interpret"))
def _cutvals_at(idx, edges, weights, *, tile: int, chunk: int, interpret: bool):
    m = idx.shape[0]
    ei, ej, w, e_pad = _pad_edges(edges, weights, chunk)
    m_pad = tuning.round_up(m, tile)
    rows, lanes, tile_rows = tuning.lane_view(m_pad, tile)
    idx_p = jnp.zeros((m_pad,), jnp.int32).at[:m].set(idx).reshape(rows, lanes)
    block = pl.BlockSpec((tile_rows, lanes), lambda kb, ke: (kb, 0))
    out = pl.pallas_call(
        _at_kernel,
        grid=(m_pad // tile, e_pad // chunk),
        in_specs=_edge_specs(chunk) + [block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        interpret=interpret,
    )(ei, ej, w, idx_p)
    return out.reshape(m_pad)[:m]


def cutvals_at(idx, edges, weights, linear=None, *, interpret: bool = False):
    """Objective values at arbitrary basis indices: (M,) f32 for (M,) int32 idx."""
    if linear is not None:
        edges, weights = ref_mod.append_linear_rows(edges, weights, linear)
    m = idx.shape[0]
    # pad to whole lane rows first, so every tile is a whole number of rows
    _, tile = tuning.pad_and_tile(
        tuning.round_up(m, min(tuning.LANES, m)),
        tuning.param("cutvals_at", m, "tile_b", TILE_B))
    chunk = tuning.param("cutvals_at", m, "edge_chunk", EDGE_CHUNK)
    return _cutvals_at(idx, edges, weights, tile=tile, chunk=chunk,
                       interpret=interpret)
