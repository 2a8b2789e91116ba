"""Plain reference of the solve's semantics, independent of the program.

It imports nothing of `repro` and takes nothing the program made except
the answers it checks (angles, candidates, assignments), the way a served
model's reference is run over the tokens the model served.

- Partition: the solver's chain of ranges (`instances.solver_ranges`) and
  the edges induced on each.
- QAOA: a float32 statevector per subgraph, at its own size (the solver's
  padding qubits stay in |+>, an eigenstate of every mixer, so they change
  neither probabilities nor the expectation). Layer l applies
  exp(-i gamma_l C) and then exp(-i beta_l X) on each qubit in turn,
  from |+>^n; Adam ascent on <C> from the linear ramp, with the solver's
  update rule (beta1 0.9, beta2 0.999, eps 1e-8, bias-corrected).
  Every product of state amplitudes goes through `mul`: exact float32
  products in the reference, three bf16 passes (what matmul precision
  ``high`` computes) in the control.
- Merge: a beam over the chain of ranges (ParaQAOA Alg. 2 with pruning).
  Level 0 holds both orientations of range 0's candidates; level l
  extends every row by each of range l's candidates, oriented so that the
  vertex range l shares with range l - 1 keeps its value. A row's score is
  the weight of the cut edges whose endpoints it has both assigned; each
  edge is counted at the first level that assigns both. After every level
  the ``width`` best rows stay, rows of equal score in the order (row,
  candidate) that made them.
- Refinement: best-improvement single flips, each accepted only when its
  gain exceeds 1e-6 of the total absolute weight, for a fixed number of
  steps, in float64 with gains kept up to date edge by edge.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from instances import solver_ranges  # noqa: F401  (the reference partition)

BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8


def f32_mul(a, b):
    return a * b


def _bf16_split(x):
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def bf16x3_mul(a, b):
    """a * b as three bf16 passes: hi*hi + hi*lo + lo*hi, lo*lo dropped."""
    ah, al = _bf16_split(jnp.asarray(a, jnp.float32))
    bh, bl = _bf16_split(jnp.asarray(b, jnp.float32))
    return ah * bh + (ah * bl + al * bh)


PRECISIONS = {"float32": f32_mul, "bf16x3": bf16x3_mul}


# ------------------------------------------------------------ host checks --
def host_cut(edges, weights, assignment) -> float:
    """Cut weight of a 0/1 assignment, in float64."""
    a = np.asarray(assignment)
    e = np.asarray(edges)
    w = np.asarray(weights, np.float64)
    return float(np.sum(w[a[e[:, 0]] != a[e[:, 1]]]))


def induced(edges, weights, ranges):
    """Per range (lo, hi): local (E_i, 2) edges and (E_i,) weights."""
    e = np.asarray(edges)
    w = np.asarray(weights, np.float32)
    out = []
    for lo, hi in ranges:
        inside = (e[:, 0] >= lo) & (e[:, 0] < hi) & (e[:, 1] >= lo) & (
            e[:, 1] < hi)
        out.append((e[inside] - lo, w[inside]))
    return out


def refine(n, edges, weights, assignment, steps: int):
    """(assignment, value) after ``steps`` best-improvement single flips."""
    e = np.asarray(edges, np.int64)
    w = np.asarray(weights, np.float64)
    s = np.asarray(assignment, np.int64).copy()
    eps = 1e-6 * float(np.sum(np.abs(w)))
    # adjacency in CSR form, both directions
    src = np.concatenate([e[:, 0], e[:, 1]])
    dst = np.concatenate([e[:, 1], e[:, 0]])
    ww = np.concatenate([w, w])
    order = np.argsort(src, kind="stable")
    src, dst, ww = src[order], dst[order], ww[order]
    start = np.searchsorted(src, np.arange(n + 1))
    # gain of flipping v: weight of its uncut edges minus its cut edges
    sign = np.where(s[src] == s[dst], 1.0, -1.0)
    gain = np.bincount(src, weights=ww * sign, minlength=n)
    for _ in range(steps):
        v = int(np.argmax(gain))
        if not gain[v] > eps:
            continue
        nb = slice(start[v], start[v + 1])
        u, wu = dst[nb], ww[nb]
        # edge (v, u) flips between cut and uncut: u's gain moves by 2w
        np.add.at(gain, u, np.where(s[u] == s[v], -2.0, 2.0) * wu)
        gain[v] = -gain[v]
        s[v] = 1 - s[v]
    return s.astype(np.int8), host_cut(e, w, s)


# ------------------------------------------------------------------ merge --
def merge_width(solver: dict, m: int) -> int:
    """The beam width a configuration asks for: its ``beam_width``, else
    every row of the exhaustive merge (2 K^M) up to ``beam_cap``."""
    k = solver["top_k"]
    return solver["beam_width"] or min(2 * k**m, solver["beam_cap"])


def merge_beam(n, edges, weights, ranges, candidates, width: int,
               chunk_rows: int = 1 << 14):
    """(assignment (n,) int8, score, final scores (width,)) of the beam over
    the chain of ``ranges``; ``candidates`` (M, K) are basis indices, bit q
    the value of the range's q-th vertex. Rows the beam never filled score
    -inf."""
    m, k = candidates.shape
    lo = np.asarray([a for a, _ in ranges])
    hi = np.asarray([b for _, b in ranges])
    size = hi - lo
    n_max = int(size.max())
    # an edge's level is the later of its endpoints' first ranges (vertex
    # x is first held by the first range with x < hi)
    e = np.asarray(edges, np.int64)
    w = np.asarray(weights, np.float32)
    first = np.searchsorted(hi, np.arange(n), side="right")
    level = np.maximum(first[e[:, 0]], first[e[:, 1]])
    per = np.bincount(level, minlength=m)
    e_max = max(1, int(per.max()))
    # padding edges join a range's first vertex to itself: never cut
    eu = np.repeat(lo[:, None], e_max, 1).astype(np.int32)
    ev = eu.copy()
    ew = np.zeros((m, e_max), np.float32)
    order = np.argsort(level, kind="stable")
    slot = np.arange(len(order)) - np.repeat(np.cumsum(per) - per, per)
    eu[level[order], slot] = e[order, 0]
    ev[level[order], slot] = e[order, 1]
    ew[level[order], slot] = w[order]
    inside = np.arange(n_max)[None, :] < size[:, None]
    bits = ((np.asarray(candidates, np.int64)[:, :, None]
             >> np.arange(n_max)) & 1).astype(np.int8) * inside[:, None, :]
    best, score, scores = _beam_program(n, n_max, k, width, chunk_rows)(
        jnp.asarray(lo, jnp.int32), jnp.asarray(size, jnp.int32),
        jnp.asarray(bits), jnp.asarray(eu), jnp.asarray(ev), jnp.asarray(ew))
    return np.asarray(best), float(score), np.asarray(scores)


@functools.lru_cache(maxsize=None)
def _beam_program(n: int, n_max: int, k: int, width: int, chunk: int):
    cols = jnp.arange(n_max)

    def values(rows, window, lo, x):
        """Each endpoint's value: from the row below ``lo``, from the
        window from ``lo`` on. rows (R, V), window (R, K, n_max)."""
        in_window = x >= lo
        from_row = rows[:, x][:, None, :]
        from_window = window[:, :, jnp.clip(x - lo, 0, n_max - 1)]
        return jnp.where(in_window, from_window, from_row)

    def gains(rows, window, lo, eu, ev, ew):
        """(R, C) cut weight of the level's edges for every extension."""
        def block(args):
            r, win = args
            cut = values(r, win, lo, eu) ^ values(r, win, lo, ev)
            return jnp.sum(jnp.where(cut == 1, ew, 0.0), axis=-1)

        r, c = window.shape[:2]
        size = chunk if r % chunk == 0 else r  # rows a block
        out = jax.lax.map(block, (rows.reshape(r // size, size, -1),
                                  window.reshape(r // size, size, c, n_max)))
        return out.reshape(r, c)

    def keep(rows, flat, window, lo, size):
        """The ``width`` best of the (row, candidate) extensions, ties in
        that order, with the chosen candidate written into each row."""
        pick = jnp.argsort(-flat, stable=True)[:width]
        r, c = pick // k, pick % k
        new = rows[r]
        cur = jax.lax.dynamic_slice(new, (0, lo), (width, n_max))
        win = jnp.where(cols < size, window[r, c], cur)
        return jax.lax.dynamic_update_slice(new, win, (0, lo)), flat[pick]

    def run(lo, size, bits, eu, ev, ew):
        # level 0: range 0's candidates, then their complements, in order;
        # pruned to the best ``width`` only where there are more
        seeds = jnp.concatenate([bits[0], (1 - bits[0]) * (cols < size[0])])
        seed_rows = jnp.zeros((2 * k, n + n_max), jnp.int8)
        seed_rows = seed_rows.at[:, :n_max].set(seeds)
        s0 = gains(seed_rows, seeds[:, None], 0, eu[0], ev[0], ew[0])[:, 0]
        if 2 * k > width:
            pick = jnp.argsort(-s0, stable=True)[:width]
            rows, score = seed_rows[pick], s0[pick]
        else:
            rows = jnp.zeros((width, n + n_max), jnp.int8).at[:2 * k].set(
                seed_rows)
            score = jnp.full((width,), -jnp.inf, jnp.float32).at[:2 * k].set(
                s0)

        def level(carry, xs):
            rows, score = carry
            lo_l, size_l, bits_l, eu_l, ev_l, ew_l = xs
            shared = jax.lax.dynamic_index_in_dim(rows, lo_l, 1, False)
            flip = bits_l[None, :, 0] ^ shared[:, None]
            window = (bits_l[None] ^ flip[:, :, None]) * (cols < size_l)
            flat = (score[:, None] + gains(rows, window, lo_l, eu_l, ev_l,
                                           ew_l)).reshape(-1)
            return keep(rows, flat, window, lo_l, size_l), None

        (rows, score), _ = jax.lax.scan(
            level, (rows, score),
            (lo[1:], size[1:], bits[1:], eu[1:], ev[1:], ew[1:]))
        best = jnp.argmax(score)
        return rows[best, :n], score[best], score

    return jax.jit(run)


# ------------------------------------------------------------------- QAOA --
def _basis(n: int):
    """Basis index of every amplitude, laid out as (rows, lanes) with
    index = row * lanes + lane: lane-dense on a TPU, whatever n."""
    lanes = min(2**n, 128)
    shape = (2**n // lanes, lanes)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * lanes
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def cut_table(n: int, edges, weights):
    """Cut value of every basis state of n qubits (bit q = vertex q)."""
    b = _basis(n)

    def add(c, ew):
        uv, w = ew
        cut = ((b >> uv[0]) ^ (b >> uv[1])) & 1
        return c + w * cut.astype(jnp.float32), None

    c, _ = jax.lax.scan(add, jnp.zeros(b.shape, jnp.float32), (edges, weights))
    return c


def _partner(x, b, q: int):
    """x at the basis index that differs from each one in bit q."""
    rows, lanes = x.shape
    if 2**q < lanes:  # a bit of the lane: rotate the lanes both ways
        up, down = jnp.roll(x, -2**q, 1), jnp.roll(x, 2**q, 1)
        return jnp.where((b >> q) & 1 == 1, down, up)
    # a bit of the row: swap the two halves of each block of rows
    j = 2**q // lanes
    return x.reshape(rows // (2 * j), 2, j, lanes)[:, ::-1].reshape(
        rows, lanes)


def evolve(n: int, p: int, c, gammas, betas, mul):
    """(re, im) of the p-layer QAOA state, as (rows, lanes) planes."""
    b = _basis(n)
    re = jnp.full(b.shape, 2.0 ** (-n / 2), jnp.float32)
    im = jnp.zeros(b.shape, jnp.float32)

    @jax.checkpoint
    def layer(state, gb):
        re, im = state
        g, bt = gb
        cs, sn = jnp.cos(g * c), jnp.sin(g * c)
        re, im = mul(re, cs) + mul(im, sn), mul(im, cs) - mul(re, sn)
        cb, sb = jnp.cos(bt), jnp.sin(bt)
        for q in range(n):
            # exp(-i beta X_q): a_b <- cos(beta) a_b - i sin(beta) a_{b^q}
            rp, ip = _partner(re, b, q), _partner(im, b, q)
            re, im = mul(cb, re) + mul(sb, ip), mul(cb, im) - mul(sb, rp)
        return (re, im), None

    (re, im), _ = jax.lax.scan(layer, (re, im), (gammas, betas))
    return re, im


def expectation(re, im, c, mul):
    return jnp.sum((mul(re, re) + mul(im, im)) * c)


def linear_ramp(p: int, delta: float):
    l = (np.arange(p, dtype=np.float32) + 0.5) / p
    return (delta * l).astype(np.float32), (delta * (1.0 - l)).astype(
        np.float32)


@functools.lru_cache(maxsize=None)
def _programs(n: int, p: int, k: int, steps: int, lr: float, precision: str):
    mul = PRECISIONS[precision]

    def energy(params, c):
        re, im = evolve(n, p, c, params[0], params[1], mul)
        return expectation(re, im, c, mul)

    def ascend(c, g0, b0):
        grad = jax.grad(lambda prm: -energy(prm, c))

        def step(state, i):
            prm, m, v = state
            g = grad(prm)
            m = jax.tree.map(lambda a, b: BETA1 * a + (1 - BETA1) * b, m, g)
            v = jax.tree.map(lambda a, b: BETA2 * a + (1 - BETA2) * b * b, v,
                             g)
            t = i + 1
            prm = jax.tree.map(
                lambda x, a, b: x - lr * (a / (1 - BETA1**t))
                / (jnp.sqrt(b / (1 - BETA2**t)) + ADAM_EPS), prm, m, v)
            return (prm, m, v), None

        zeros = (jnp.zeros_like(g0), jnp.zeros_like(b0))
        (prm, _, _), _ = jax.lax.scan(
            step, ((g0, b0), zeros, zeros), jnp.arange(steps, dtype=jnp.float32))
        return prm

    def evaluate(c, gammas, betas):
        re, im = evolve(n, p, c, gammas, betas, mul)
        probs = (mul(re, re) + mul(im, im)).reshape(-1)
        top = jax.lax.top_k(probs, k)
        return expectation(re, im, c, mul), probs, top[0], top[1]

    def table(edges, weights):
        return cut_table(n, edges, weights)

    return (jax.jit(jax.vmap(table)), jax.jit(jax.vmap(ascend)),
            jax.jit(jax.vmap(evaluate)))


class QAOA:
    """The reference solver pool for one configuration."""

    def __init__(self, p_layers: int, opt_steps: int, learning_rate: float,
                 ramp_delta: float, top_k: int, precision: str = "float32",
                 chunk_amplitudes: int = 1 << 23):
        self.p, self.steps, self.lr = p_layers, opt_steps, learning_rate
        self.k, self.precision = top_k, precision
        self.ramp = linear_ramp(p_layers, ramp_delta)
        self.chunk_amplitudes = chunk_amplitudes

    def _groups(self, subgraphs):
        """Indices of the subgraphs of each size, in chunks that fit."""
        sizes = np.asarray([s for s, _, _ in subgraphs])
        for n in np.unique(sizes):
            idx = np.nonzero(sizes == n)[0]
            step = max(1, self.chunk_amplitudes >> int(n))
            for i in range(0, len(idx), step):
                yield int(n), idx[i:i + step]

    def _tables(self, n, subgraphs, idx):
        e_pad = max(1, max(subgraphs[i][1].shape[0] for i in idx))
        edges = np.zeros((len(idx), e_pad, 2), np.int32)
        weights = np.zeros((len(idx), e_pad), np.float32)
        for j, i in enumerate(idx):
            e, w = subgraphs[i][1], subgraphs[i][2]
            edges[j, :len(e)], weights[j, :len(w)] = e, w
        table, _, _ = _programs(n, self.p, self.k, self.steps, self.lr,
                                self.precision)
        return table(jnp.asarray(edges), jnp.asarray(weights))

    def ascend(self, subgraphs):
        """Adam-ascended (gammas, betas), each (M, p), for (size, edges,
        weights) subgraphs."""
        m = len(subgraphs)
        gam = np.zeros((m, self.p), np.float32)
        bet = np.zeros((m, self.p), np.float32)
        for n, idx in self._groups(subgraphs):
            c = self._tables(n, subgraphs, idx)
            _, asc, _ = _programs(n, self.p, self.k, self.steps, self.lr,
                                  self.precision)
            g0 = jnp.broadcast_to(self.ramp[0], (len(idx), self.p))
            b0 = jnp.broadcast_to(self.ramp[1], (len(idx), self.p))
            g, b = asc(c, g0, b0)
            gam[idx], bet[idx] = np.asarray(g), np.asarray(b)
        return gam, bet

    def evaluate(self, subgraphs, gammas, betas, candidates):
        """At the given angles: expectation (M,), the probabilities of the
        given candidate indices (M, K) and the K largest (M, K), float64."""
        m = len(subgraphs)
        exp = np.zeros(m)
        cand_p = np.zeros((m, candidates.shape[1]))
        top_p = np.zeros((m, self.k))
        top_i = np.zeros((m, self.k), np.int64)
        for n, idx in self._groups(subgraphs):
            c = self._tables(n, subgraphs, idx)
            _, _, ev = _programs(n, self.p, self.k, self.steps, self.lr,
                                 self.precision)
            e, probs, tv, ti = ev(c, jnp.asarray(gammas[idx]),
                                  jnp.asarray(betas[idx]))
            exp[idx] = np.asarray(e, np.float64)
            cidx = np.clip(candidates[idx], 0, 2**n - 1)
            cp = np.take_along_axis(np.asarray(probs), cidx, axis=1)
            # an index with bits beyond the subgraph is no basis state of it
            cand_p[idx] = np.where(candidates[idx] < 2**n, cp, 0.0)
            top_p[idx] = np.asarray(tv, np.float64)
            top_i[idx] = np.asarray(ti)
        return exp, cand_p, top_p, top_i

    def solve(self, subgraphs):
        """The pool's answer computed by the reference itself: angles,
        expectation at them and the top-K candidates (the control)."""
        gam, bet = self.ascend(subgraphs)
        top_k = np.zeros((len(subgraphs), self.k), np.int64)
        exp, _, _, top_i = self.evaluate(subgraphs, gam, bet, top_k)
        return gam, bet, exp, top_i
