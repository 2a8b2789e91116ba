"""Pallas TPU kernel: fused QAOA cost-phase + first mixer group.

One VMEM round-trip applies the whole start of a QAOA layer:

    (re, im) --e^{-iγ·c}--> phase --RX(β)^{⊗k} (right-multiply)--> out

The unfused XLA path reads/writes the statevector twice (phase pass, then
mixer pass); fusing halves the HBM traffic of that段 — exactly §Perf C3.
The U matrix is generated in-registers from β (`mixer.rx_group_mats`); the
cut-value block rides along the same row tiles.

Layout contract: state viewed as (R, 2^k) where the trailing axis is the
first mixer group (qubits 0..k-1) — the natural layout-A view, so no extra
relayout versus the unfused path.

``reverse=True`` swaps the in-kernel order to mixer-group *then* phase:
called with (−γ, −β) that is exactly the adjoint of the forward kernel,
which is how the `kernels.ops` layer custom-vjp backward runs this same
kernel for the gradient trace (DESIGN.md §2.7).

Oracle contract: ``c`` is *any* diagonal objective, not specifically a cut
value — per-vertex linear terms (QUBO/MIS, DESIGN.md §9) are folded into
``c`` upstream by ``cutvals(..., linear=...)`` via virtual-bit edge rows,
so this kernel serves all three problem families without modification.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tuning
from repro.kernels.mixer import F32_DOT, rx_group_mats

ROW_TILE = 512


def _kernel(k: int, reverse: bool, g_ref, b_ref, c_ref, re_ref, im_ref,
            ore_ref, oim_ref):
    gamma = g_ref[0, 0]
    cv = c_ref[...]
    cs = jnp.cos(gamma * cv)
    sn = jnp.sin(gamma * cv)
    cmat, dmat = rx_group_mats(b_ref[0, 0], k)

    re = re_ref[...]
    im = im_ref[...]

    def phase(pr, pi):
        return pr * cs + pi * sn, pi * cs - pr * sn

    def mixer(pr, pi):
        return (
            jnp.dot(pr, cmat, **F32_DOT) - jnp.dot(pi, dmat, **F32_DOT),
            jnp.dot(pi, cmat, **F32_DOT) + jnp.dot(pr, dmat, **F32_DOT),
        )

    if reverse:
        re, im = mixer(re, im)
        re, im = phase(re, im)
    else:
        re, im = phase(re, im)
        re, im = mixer(re, im)
    ore_ref[...] = re
    oim_ref[...] = im


@functools.partial(
    jax.jit, static_argnames=("k", "reverse", "tile", "interpret"))
def _fused_phase_mixer_group(re_mat, im_mat, cutv_mat, gamma, beta, k: int,
                             *, reverse: bool, tile: int, interpret: bool):
    r, dk = re_mat.shape
    g = jnp.asarray(gamma, jnp.float32).reshape(1, 1)
    b = jnp.asarray(beta, jnp.float32).reshape(1, 1)
    spec = pl.BlockSpec((tile, dk), lambda i: (i, 0))
    scal = pl.BlockSpec((1, 1), lambda i: (0, 0))
    ore, oim = pl.pallas_call(
        functools.partial(_kernel, k, reverse),
        grid=(r // tile,),
        in_specs=[scal, scal, spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((r, dk), jnp.float32),
            jax.ShapeDtypeStruct((r, dk), jnp.float32),
        ],
        interpret=interpret,
    )(g, b, cutv_mat, re_mat, im_mat)
    return ore, oim


def fused_phase_mixer_group(re_mat, im_mat, cutv_mat, gamma, beta, k: int,
                            *, reverse: bool = False, interpret: bool = False):
    """(R, 2^k) state planes + matching cut values → one fused pass."""
    r, dk = re_mat.shape
    assert dk == 2**k and cutv_mat.shape == (r, dk)
    tile = tuning.clamp_tile(
        r, tuning.param("fused_layer", r, "row_tile", ROW_TILE))
    return _fused_phase_mixer_group(
        re_mat, im_mat, cutv_mat, gamma, beta, k,
        reverse=reverse, tile=tile, interpret=interpret,
    )
