"""Per-kernel block-shape autotune harness (§Perf C11).

Sweeps Pallas block/tile candidates per (op, shape-bucket) — always
including the kernel's hard-coded default, so the winning config is never
slower than the default by construction — and, on a chip whose peaks are
in `repro.roofline.analysis.KERNEL_PEAKS`, records achieved time vs that
single-kernel peak model (the roofline columns are null elsewhere). Winning configs
land in `src/repro/kernels/tuning_cache.json` (``--write-cache``), the
committed table `kernels.tuning` serves at trace time when tuning is
enabled; measured rows land in `results/BENCH_kernel_autotune.json`
(``--write``).

Off-TPU the sweep runs the kernels in Pallas interpret mode (recorded
honestly as ``mode=pallas_interpret``): grid-step count still dominates
interpreter wall-clock, so tile choice is measurable, but the committed
cache is keyed per backend — a TPU run writes separate `|tpu` entries.

All timing flows through `kernels.tuning.measure`, i.e. the injectable
`repro.obs.clock` boundary (the reprolint hot-nondeterminism contract).
"""

from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp

from benchmarks.common import er_graph, write_bench_json
from repro.kernels import cutbatch, cutvals, fused_layer, mixer, phase, tuning
from repro.roofline.analysis import (
    KERNEL_PEAKS,
    achieved_fraction,
    kernel_bound_s,
)

SUITE = "kernel_autotune"


def _pow2_divisors(dim: int, lo: int = 1):
    t = lo
    out = []
    while t <= dim:
        if dim % t == 0:
            out.append(t)
        t *= 2
    return out


def _dedup(cands):
    seen, out = set(), []
    for c in cands:
        key = tuple(sorted(c.items()))
        if key not in seen:
            seen.add(key)
            out.append(c)
    return out


def _sweep(op, dim, call, candidates, flops, nbytes, repeats, backend):
    """Time every candidate config (default first); returns the row dict
    plus the winning config for the cache writer."""
    results = []
    for cand in candidates:
        key = tuning.cache_key(op, dim, backend)
        with tuning.using_overrides({key: cand}):
            _, t = tuning.measure(call, repeats=repeats)
        results.append((t, cand))
    default_s = results[0][0]
    tuned_s, best = min(results, key=lambda r: r[0])
    bucket = tuning.shape_bucket(dim)
    # the roofline columns exist only on a chip with published peaks
    kind = jax.devices()[0].device_kind
    bound = (kernel_bound_s(flops, nbytes, kind)
             if kind in KERNEL_PEAKS else None)
    cfg_str = ";".join(f"{k}={v}" for k, v in sorted(best.items()))
    row = {
        "name": f"{SUITE}/{op}_{bucket}",
        "runtime_s": tuned_s,
        "op": op,
        "bucket": bucket,
        "mode": "pallas" if backend == "tpu" else "pallas_interpret",
        "default_s": default_s,
        "tuned_s": tuned_s,
        "speedup_vs_default": default_s / tuned_s if tuned_s else 1.0,
        "config": best,
        "candidates": len(candidates),
        "flops": flops,
        "bytes_accessed": nbytes,
        "model_bound_s": bound,
        "achieved_frac": (achieved_fraction(flops, nbytes, tuned_s, kind)
                          if bound is not None else None),
        "derived": f"{cfg_str};default_s={default_s:.3e};bucket={bucket}",
    }
    return row, (tuning.cache_key(op, dim, backend), best)


def _state(n, seed=0):
    dim = 2**n
    key = jax.random.PRNGKey(seed)
    kr, kc = jax.random.split(key)
    re = jax.random.normal(kr, (dim,), jnp.float32)
    im = jnp.zeros((dim,), jnp.float32)
    cutv = jax.random.uniform(kc, (dim,), jnp.float32) * n
    return re, im, cutv


def sweep_all(dims, repeats: int):
    backend = jax.default_backend()
    interp = backend != "tpu"
    rows, entries = [], {}

    def record(row_entry):
        row, (key, cfg) = row_entry
        rows.append(row)
        entries[key] = cfg

    def swept(op, dim):
        # several qubit counts can hit one (op, shape-bucket) — e.g. the
        # trailing mixer group is (1, 2^k, 128) for every n ≥ 14 — so
        # skip re-sweeping a cache key that already has a winner
        return tuning.cache_key(op, dim, backend) in entries

    for n in dims:
        dim = 2**n
        re, im, cutv = _state(n)

        tiles = [min(phase.TILE, dim)] + _pow2_divisors(dim, lo=min(128, dim))
        record(_sweep(
            "apply_phase", dim,
            lambda: phase.apply_phase(re, im, cutv, 0.37, interpret=interp),
            _dedup([{"tile": t} for t in tiles]),
            flops=8.0 * dim, nbytes=20.0 * dim,
            repeats=repeats, backend=backend,
        ))
        record(_sweep(
            "expectation", dim,
            lambda: phase.expectation(re, im, cutv, interpret=interp),
            _dedup([{"tile": t} for t in tiles]),
            flops=4.0 * dim, nbytes=12.0 * dim,
            repeats=repeats, backend=backend,
        ))

        # trailing-axis mixer group + the fused layer share geometry
        k = min(7, n)
        dk = 2**k
        r = dim // dk
        re_m, im_m = re.reshape(r, dk), im.reshape(r, dk)
        cv_m = cutv.reshape(r, dk)
        rtiles = [min(mixer.ROW_TILE, r)] + _pow2_divisors(r)
        record(_sweep(
            "mixer_matmul", r,
            lambda: mixer.mixer_group_matmul(re_m, im_m, 0.7, k,
                                             interpret=interp),
            _dedup([{"row_tile": t} for t in rtiles]),
            flops=8.0 * r * dk * dk, nbytes=16.0 * r * dk,
            repeats=repeats, backend=backend,
        ))
        record(_sweep(
            "fused_layer", r,
            lambda: fused_layer.fused_phase_mixer_group(
                re_m, im_m, cv_m, 0.37, 0.7, k, interpret=interp),
            _dedup([{"row_tile": t} for t in rtiles]),
            flops=8.0 * r * dk * dk + 8.0 * r * dk, nbytes=20.0 * r * dk,
            repeats=repeats, backend=backend,
        ))

        # mid-state mixer group (lo_bit=7): the strided kernel's shape
        if n >= 9:
            k2 = min(7, n - 7)
            x, y = 2 ** (n - 7 - k2), 2**7
            re3 = re.reshape(x, 2**k2, y)
            im3 = im.reshape(x, 2**k2, y)
            cands = [{"tile_x": min(mixer.X_TILE, x),
                      "tile_y": min(mixer.Y_TILE, y)}]
            cands += [{"tile_x": tx, "tile_y": ty}
                      for tx in _pow2_divisors(x)
                      for ty in _pow2_divisors(y, lo=min(32, y))]
            if not swept("mixer_strided", x * y):
                record(_sweep(
                    "mixer_strided", x * y,
                    lambda: mixer.mixer_group_strided(re3, im3, 0.7, k2,
                                                      interpret=interp),
                    _dedup(cands),
                    flops=8.0 * x * y * (2**k2) ** 2,
                    nbytes=16.0 * dim,
                    repeats=repeats, backend=backend,
                ))

        # relayout fusion: strided in-kernel contraction vs the old
        # moveaxis-to-trailing-axis path, both under default tiles. Use a
        # mid-state group with a real leading axis (x = 16) — that is the
        # large-n regime the fusion targets; the trailing-group x = 1
        # shapes have almost no relayout to elide and just measure noise.
        if n >= 12:
            k_r = min(7, n - 11)
            fused_fn = jax.jit(lambda a, b: mixer.apply_mixer_bits(
                a, b, n, 7, k_r, 0.7, interpret=interp))
            unfused_fn = jax.jit(lambda a, b: mixer.apply_mixer_bits_relayout(
                a, b, n, 7, k_r, 0.7, interpret=interp))
            # the two paths differ by tens of microseconds here, so use
            # enough repeats that best-of-N converges below that spread
            rr = max(repeats, 9)
            _, t_fused = tuning.measure(fused_fn, re, im, repeats=rr)
            _, t_unf = tuning.measure(unfused_fn, re, im, repeats=rr)
            bucket = tuning.shape_bucket(dim)
            rows.append({
                "name": f"{SUITE}/mixer_relayout_{bucket}",
                "runtime_s": t_fused,
                "op": "mixer_relayout",
                "bucket": bucket,
                "mode": "pallas" if backend == "tpu" else "pallas_interpret",
                "fused_s": t_fused,
                "unfused_s": t_unf,
                "relayout_speedup": t_unf / t_fused if t_fused else 1.0,
                "fused_ge_unfused": bool(t_fused <= t_unf),
                "derived": f"fused_s={t_fused:.3e};unfused_s={t_unf:.3e}",
            })

        # cutvals over the same dim; cutvals_at over a candidate slice
        g = er_graph(n, 0.5, seed=3)
        edges = jnp.asarray(g.edges, jnp.int32)
        weights = jnp.asarray(g.weights, jnp.float32)
        e = int(edges.shape[0])
        cv_cands = [{"tile_b": min(cutvals.TILE_B, dim),
                     "edge_chunk": cutvals.EDGE_CHUNK}]
        cv_cands += [{"tile_b": t, "edge_chunk": cutvals.EDGE_CHUNK}
                     for t in _pow2_divisors(dim, lo=min(256, dim))]
        cv_cands += [{"tile_b": min(cutvals.TILE_B, dim), "edge_chunk": c}
                     for c in (64, 128, 256, 512)]
        record(_sweep(
            "cutvals", dim,
            lambda: cutvals.cutvals(n, edges, weights, interpret=interp),
            _dedup(cv_cands),
            flops=2.0 * dim * e, nbytes=4.0 * dim + 12.0 * e,
            repeats=repeats, backend=backend,
        ))

        m = min(dim, 1024)
        idx = jnp.arange(m, dtype=jnp.int32)
        at_cands = [{"tile_b": min(cutvals.TILE_B, m),
                     "edge_chunk": cutvals.EDGE_CHUNK}]
        at_cands += [{"tile_b": t, "edge_chunk": cutvals.EDGE_CHUNK}
                     for t in _pow2_divisors(m, lo=min(128, m))]
        if not swept("cutvals_at", m):
            record(_sweep(
                "cutvals_at", m,
                lambda: cutvals.cutvals_at(idx, edges, weights,
                                           interpret=interp),
                _dedup(at_cands),
                flops=2.0 * m * e, nbytes=8.0 * m + 12.0 * e,
                repeats=repeats, backend=backend,
            ))

    # merge-phase batch scorer: one representative (B, V) shape
    bsz, v = 256, 512
    key = jax.random.PRNGKey(7)
    spins = jax.random.bernoulli(key, 0.5, (bsz, v)).astype(jnp.float32) * 2 - 1
    gg = er_graph(v, 0.05, seed=5)
    adj = jnp.asarray(gg.dense_adjacency(), jnp.float32)
    wtot = float(gg.weights.sum())
    cb_cands = [{"batch_tile": min(cutbatch.BATCH_TILE, bsz),
                 "k_chunk": min(cutbatch.K_CHUNK, v)}]
    cb_cands += [{"batch_tile": bt, "k_chunk": kc}
                 for bt in _pow2_divisors(bsz, lo=32)
                 for kc in _pow2_divisors(v, lo=128)]
    backend = jax.default_backend()
    record(_sweep(
        "cut_batch_dense", v,
        lambda: cutbatch.cut_batch_dense(spins, adj, wtot, interpret=interp),
        _dedup(cb_cands),
        flops=2.0 * bsz * v * v + 3.0 * bsz * v,
        nbytes=4.0 * (bsz * v + v * v + bsz),
        repeats=repeats, backend=backend,
    ))

    # summary: the tuned-vs-default acceptance claim (tuned config can
    # never lose — the default is in every candidate set)
    swept = [r for r in rows if "speedup_vs_default" in r]
    speedups = [r["speedup_vs_default"] for r in swept]
    rows.append({
        "name": f"{SUITE}/tuned_vs_default",
        "runtime_s": sum(r["tuned_s"] for r in swept),
        "ops_swept": len(swept),
        "tuned_ge_default": bool(all(s >= 1.0 for s in speedups)),
        "mean_speedup": sum(speedups) / len(speedups) if speedups else 1.0,
        "max_speedup": max(speedups) if speedups else 1.0,
        "derived": f"ops={len(swept)};mean_speedup="
                   f"{sum(speedups) / len(speedups):.3f}",
    })
    return rows, entries


def write_cache(entries, path=tuning.CACHE_PATH):
    payload = {
        "version": 1,
        "generated_by": "benchmarks/kernel_autotune.py",
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "entries": {k: entries[k] for k in sorted(entries)},
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    tuning.invalidate_committed()
    return path


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, nargs="*", default=None,
                    help="qubit counts to sweep (default: 10 12 14)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny dims, 1 repeat, no files unless asked")
    ap.add_argument("--write", action="store_true",
                    help="write results/BENCH_kernel_autotune.json")
    ap.add_argument("--write-cache", action="store_true",
                    help="write src/repro/kernels/tuning_cache.json")
    args = ap.parse_args()

    dims = args.n if args.n else ([8, 9] if args.smoke else [10, 12, 14])
    repeats = 1 if args.smoke and args.repeats == 3 else args.repeats

    rows, entries = sweep_all(dims, repeats)
    for r in rows:
        extra = (f" speedup={r['speedup_vs_default']:.2f}x {r['config']}"
                 if "config" in r else "")
        print(f"{r['name']},{r['runtime_s'] * 1e6:.1f}us{extra}")

    if args.write:
        print("wrote", write_bench_json(SUITE, rows))
    if args.write_cache:
        print("wrote", write_cache(entries))


if __name__ == "__main__":
    main()
