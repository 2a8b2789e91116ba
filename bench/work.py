"""Floating-point operations and bytes the QAOA solver pool needs, from
shapes alone (float32 state; nothing is read from the compiled program).

Per subgraph of n qubits (A = 2^n amplitudes, re and im planes):

  layer        phase e^{-i gamma C}: 6 flops per amplitude (complex times
               a unit phase); mixer RX^{(x)g} per group of g qubits
               (groups of ``group`` qubits, the last one shorter) as a
               2^g x 2^g complex matrix on the state: 4 real matmuls,
               8 * 2^g flops per amplitude. Bytes: one pass over the
               state, reading and writing both planes (16 B per
               amplitude), and reading the cut table (4 B).
  expectation  sum |a|^2 C: 4 flops, 12 B per amplitude.
  cut table    3 flops per amplitude per edge slot, 4 B written.
  Adam step    forward p layers and the expectation, backward 2p layers
               (the adjoint method un-evolves the state and evolves the
               costate), so 3p layers and one expectation.
  final        one more forward evaluation: p layers and the expectation.

A layer is counted as one pass over the state however many kernels
implement it, so the count is the least a layer-at-a-time implementation
moves, and an implementation that splits a layer into more passes shows
as a lower roofline share.
"""

from __future__ import annotations


def layer(n: int, group: int) -> tuple[float, float]:
    amps = 2.0**n
    groups = [min(group, n - g0) for g0 in range(0, n, group)]
    flops = amps * (6 + sum(8 * 2**g for g in groups))
    return flops, amps * (16 + 4)


def expectation(n: int) -> tuple[float, float]:
    return 4.0 * 2**n, 12.0 * 2**n


def pool_batch(m: int, n: int, p: int, opt_steps: int, group: int,
               e_pad: int) -> tuple[float, float]:
    """(flops, bytes) of one solver-pool call on ``m`` subgraphs."""
    lf, lb = layer(n, group)
    ef, eb = expectation(n)
    cf, cb = 3.0 * e_pad * 2**n, 4.0 * 2**n
    step_f, step_b = 3 * p * lf + ef, 3 * p * lb + eb
    final_f, final_b = p * lf + ef, p * lb + eb
    flops = cf + opt_steps * step_f + final_f
    nbytes = cb + opt_steps * step_b + final_b
    return m * flops, m * nbytes
