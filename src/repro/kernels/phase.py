"""Pallas TPU kernels for the diagonal cost layer.

`apply_phase`: psi ← e^{-iγc}·psi on (re, im) planes — pure VPU elementwise,
tiled so each block streams HBM→VMEM once (memory-bound by design; the win
over XLA is fusing the sin/cos with both plane updates in one pass).

`expectation`: Σ|psi|²·c, and `vdot`: Σ a·b (the angle gradients of the
`kernels.ops` VJP rules) — tiled reductions using the sequential-grid
accumulation idiom: every grid step adds its sublane sums into a (1, lanes)
VMEM accumulator, and the last step sums its lanes. The whole sum happens
in the kernel, in an order fixed by the block shape alone, so a subgraph's
value does not depend on how many subgraphs share its `vmap` batch; an XLA
reduction can be fused differently at each batch size.

All kernels see the flat 2^n planes as a lane-dense (rows, 128) view
(`tuning.lane_view`), so their blocks stay legal for the TPU compiler when
`jax.vmap` prepends the subgraph-batch axis — the same layout the mixer and
fused-layer kernels use.

Block sizes resolve through `kernels.tuning` at trace time (autotuned per
shape bucket when tuning is enabled; the hard defaults otherwise) and are
threaded into the jitted launchers as static arguments, so a tuning-state
change can never stale-hit a kernel-level jit cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning

TILE = 8 * 1024  # elements per block (64 sublanes × 128 lanes)


def _phase_kernel(g_ref, re_ref, im_ref, c_ref, ore_ref, oim_ref):
    g = g_ref[0, 0]
    c = jnp.cos(g * c_ref[...])
    s = jnp.sin(g * c_ref[...])
    re = re_ref[...]
    im = im_ref[...]
    ore_ref[...] = re * c + im * s
    oim_ref[...] = im * c - re * s


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _apply_phase(re, im, cutv, gamma, *, tile: int, interpret: bool):
    dim = re.shape[0]
    rows, lanes, tile_rows = tuning.lane_view(dim, tile)
    g = jnp.asarray(gamma, jnp.float32).reshape(1, 1)
    spec = pl.BlockSpec((tile_rows, lanes), lambda i: (i, 0))
    ore, oim = pl.pallas_call(
        _phase_kernel,
        grid=(rows // tile_rows,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            spec,
            spec,
            spec,
        ],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
            jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        ],
        interpret=interpret,
    )(g, re.reshape(rows, lanes), im.reshape(rows, lanes),
      cutv.reshape(rows, lanes))
    return ore.reshape(dim), oim.reshape(dim)


def apply_phase(re, im, cutv, gamma, *, interpret: bool = False):
    dim = re.shape[0]
    tile = tuning.clamp_tile(dim, tuning.param("apply_phase", dim, "tile", TILE))
    return _apply_phase(re, im, cutv, gamma, tile=tile, interpret=interpret)


def _sum_kernel(term, *refs):
    """Σ term(blocks) over the grid; every lane of the output holds it."""
    *in_refs, out_ref, acc_ref = refs
    i = pl.program_id(0)
    partial = jnp.sum(term(*(r[...] for r in in_refs)), axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = partial

    @pl.when(i != 0)
    def _acc():
        acc_ref[...] += partial

    @pl.when(i == pl.num_programs(0) - 1)
    def _total():
        total = jnp.sum(acc_ref[...], axis=1, keepdims=True)  # (1, 1)
        out_ref[...] = jnp.broadcast_to(total, out_ref.shape)


def _sum_call(term, arrays, tile: int, interpret: bool):
    dim = arrays[0].shape[0]
    rows, lanes, tile_rows = tuning.lane_view(dim, tile)
    spec = pl.BlockSpec((tile_rows, lanes), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_sum_kernel, term),
        grid=(rows // tile_rows,),
        in_specs=[spec] * len(arrays),
        out_specs=pl.BlockSpec((1, lanes), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, lanes), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, lanes), jnp.float32)],
        interpret=interpret,
    )(*(a.reshape(rows, lanes) for a in arrays))
    return out[0, 0]


def _exp_term(re, im, c):
    return (re * re + im * im) * c


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _expectation(re, im, cutv, *, tile: int, interpret: bool):
    return _sum_call(_exp_term, (re, im, cutv), tile, interpret)


def expectation(re, im, cutv, *, interpret: bool = False):
    dim = re.shape[0]
    tile = tuning.clamp_tile(dim, tuning.param("expectation", dim, "tile", TILE))
    return _expectation(re, im, cutv, tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _vdot(a, b, *, tile: int, interpret: bool):
    return _sum_call(jnp.multiply, (a, b), tile, interpret)


def vdot(a, b, *, interpret: bool = False):
    """Σ a·b of two flat float32 vectors (the expectation kernel's tiles)."""
    dim = a.shape[0]
    tile = tuning.clamp_tile(dim, tuning.param("expectation", dim, "tile", TILE))
    return _vdot(a, b, tile=tile, interpret=interpret)
