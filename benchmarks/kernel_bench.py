"""Kernel microbenchmarks: XLA reference path timings on CPU (the Pallas
path targets TPU; interpret mode is a correctness tool, not a timing one).
Derived column reports achieved GFLOP/s or GB/s on this host.

`run_schedules` measures the distributed-statevector collective schedules
(faithful 2-a2a/layer vs alternating 1-a2a/layer) on an emulated host
mesh — the measurement behind the optimization claimed in the
`sharded_qaoa` docstring."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import er_graph, timed, write_bench_json
from repro.kernels import ref


def run(n_qubits: int = 16, repeats: int = 3):
    rows = []
    g = er_graph(n_qubits, 0.5, seed=0)
    dim = 2**n_qubits

    cv = jax.jit(lambda e, w: ref.cutvals(n_qubits, e, w))
    _, t = timed(cv, g.edges, g.weights, repeats=repeats)
    rows.append({
        "name": "kernel/cutvals",
        "runtime_s": t,
        "derived": f"Melem_per_s={dim * g.n_edges / t / 1e6:.0f}",
    })

    key = jax.random.PRNGKey(0)
    re = jax.random.normal(key, (dim,), jnp.float32)
    im = jnp.zeros((dim,))
    c = jax.random.uniform(key, (dim,))

    ph = jax.jit(lambda r, i: ref.apply_phase(r, i, c, 0.3))
    _, t = timed(ph, re, im, repeats=repeats)
    rows.append({
        "name": "kernel/phase",
        "runtime_s": t,
        "derived": f"GBps={dim * 4 * 5 / t / 1e9:.2f}",
    })

    mx = jax.jit(lambda r, i: ref.apply_mixer(r, i, n_qubits, 0.7))
    _, t = timed(mx, re, im, repeats=repeats)
    flops = 4 * 2 * dim * 128 * (n_qubits / 7)
    rows.append({
        "name": "kernel/mixer",
        "runtime_s": t,
        "derived": f"GFLOPs={flops / t / 1e9:.2f}",
    })

    spins = jax.random.rademacher(key, (256, 512), jnp.float32)
    g2 = er_graph(512, 0.2, seed=1)
    adj = g2.dense_adjacency()
    cb = jax.jit(lambda s: ref.cut_batch_dense(s, adj, g2.total_weight()))
    _, t = timed(cb, spins, repeats=repeats)
    rows.append({
        "name": "kernel/cutbatch",
        "runtime_s": t,
        "derived": f"GFLOPs={2 * 256 * 512 * 512 / t / 1e9:.2f}",
    })
    return rows


def run_schedules(
    n_qubits: int = 14,
    axis_sizes=(4, 8),
    p_layers: int = 3,
    repeats: int = 10,
    save: bool = True,
):
    """Time sharded_qaoa's faithful vs alternating collective schedules.

    Requires a multi-device view (real, or CPU host-device emulation —
    see docs/TESTING.md); axis sizes larger than the visible device count
    are skipped with a note so the suite degrades gracefully.

    On emulated CPU devices an all_to_all is a local memcpy, so the
    1-vs-2 a2a/layer difference shows up as only a few percent of wall
    clock (the a2a_total column records the collective count halving —
    the quantity that matters on a real interconnect); treat the CPU
    numbers as a harness smoke-check, not the paper claim.
    """
    from repro import compat
    from repro.core import distributed as dist

    rows = []
    g = er_graph(n_qubits, 0.4, seed=3)
    gammas = jnp.linspace(0.2, 0.8, p_layers).astype(jnp.float32)
    betas = jnp.linspace(0.8, 0.2, p_layers).astype(jnp.float32)
    for d in axis_sizes:
        if compat.device_count() < d:
            print(f"# skip axis={d}: only {compat.device_count()} devices")
            continue
        mesh = compat.make_mesh((d,), ("model",))
        times = {}
        for schedule in ("faithful", "alternating"):
            def call():
                return dist.sharded_qaoa(
                    g.edges, g.weights, n_qubits, gammas, betas, mesh,
                    axis="model", top_k=4, schedule=schedule,
                )
            call()  # compile outside the timed region
            _, t = timed(call, repeats=repeats)
            times[schedule] = t
            a2a = (2 if schedule == "faithful" else 1) * p_layers
            rows.append({
                "name": f"schedules/sched_{schedule}_d{d}",
                "runtime_s": t,
                "derived": f"a2a_total={a2a}",
                "n_qubits": n_qubits,
                "p_layers": p_layers,
                "axis_size": d,
                "schedule": schedule,
            })
        if len(times) == 2:
            rows.append({
                "name": f"schedules/sched_speedup_d{d}",
                "runtime_s": 0.0,
                "derived": (
                    f"alt_vs_faithful={times['faithful'] / times['alternating']:.3f}x"
                ),
                "axis_size": d,
            })
    if save and rows:
        path = write_bench_json("schedules", rows)
        print(f"# wrote {path}")
    return rows


def run_sharded_engine(
    n_qubits: int = 14,
    axis_size: int = 4,
    p_layers: int = 2,
    opt_steps: int = 20,
    repeats: int = 5,
    save: bool = True,
):
    """Statevector-engine benchmark (DESIGN.md §2.6, §Perf C7).

    Two measurements:

    (a) fused vs unfused per-shard layer: one jitted `ops.apply_layer`
        program (phase fused into the mixer pipeline — the CPU-measurable
        form of the §Perf C3 fusion; on TPU the fused Pallas kernel fires
        on the same dispatch) vs separate phase/mixer programs with a
        statevector round trip between them.
    (b) opt-vs-ramp cut quality: `sharded_qaoa` at linear-ramp parameters
        vs `opt_steps` of the sharded Adam ascent on the same instance —
        the accuracy knob the engine unlocks for oversized subproblems.
        Asserts ⟨cut⟩_opt >= ⟨cut⟩_ramp before persisting.
    """
    import numpy as np

    from repro import compat
    from repro.core import distributed as dist
    from repro.core import qaoa as qaoa_mod
    from repro.kernels import ops, ref as ref_mod

    rows = []
    h = int(np.log2(axis_size))
    n_local = n_qubits - h
    dim = 2**n_local
    g_loc = er_graph(n_local, 0.4, seed=7)
    cutv = ref_mod.cutvals(n_local, g_loc.edges, g_loc.weights)
    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    re = jax.random.normal(k1, (dim,), jnp.float32) * 2.0 ** (-n_local / 2)
    im = jax.random.normal(k2, (dim,), jnp.float32) * 2.0 ** (-n_local / 2)

    gamma, beta = 0.4, 0.9
    phase_prog = jax.jit(lambda r, i: ref_mod.apply_phase(r, i, cutv, gamma))
    mixer_prog = jax.jit(lambda r, i: ref_mod.apply_mixer(r, i, n_local, beta))

    def unfused():
        r, i = phase_prog(re, im)
        return mixer_prog(r, i)  # separate program: state round-trips

    fused_prog = jax.jit(
        lambda r, i: ops.apply_layer(r, i, cutv, gamma, beta, n_local)
    )

    def fused():
        return fused_prog(re, im)

    unfused(), fused()  # compile outside the timed region
    _, t_unfused = timed(unfused, repeats=repeats)
    _, t_fused = timed(fused, repeats=repeats)
    bytes_moved = dim * 4 * 4  # two planes in + out, per pass
    rows.append({
        "name": f"sharded_engine/layer_unfused_n{n_local}",
        "runtime_s": t_unfused,
        "derived": f"GBps={2 * bytes_moved / t_unfused / 1e9:.2f}",
        "n_local": n_local,
    })
    rows.append({
        "name": f"sharded_engine/layer_fused_n{n_local}",
        "runtime_s": t_fused,
        "derived": f"GBps={bytes_moved / t_fused / 1e9:.2f}",
        "n_local": n_local,
    })
    rows.append({
        "name": "sharded_engine/layer_fusion_speedup",
        "runtime_s": 0.0,
        "derived": f"fused_vs_unfused={t_unfused / t_fused:.3f}x",
        "n_local": n_local,
    })

    quality_ran = False
    if compat.device_count() < axis_size:
        print(f"# skip opt-vs-ramp: only {compat.device_count()} devices")
    else:
        mesh = compat.make_mesh((axis_size,), ("model",))
        g_big = er_graph(n_qubits, 0.4, seed=3)
        gammas, betas = qaoa_mod.linear_ramp_init(p_layers, 0.75)
        results = {}
        for label, steps in (("ramp", 0), ("opt", opt_steps)):
            def call():
                return dist.sharded_qaoa(
                    g_big.edges, g_big.weights, n_qubits, gammas, betas,
                    mesh, top_k=4, opt_steps=steps,
                )
            res = call()  # compile outside the timed region
            _, t = timed(call, repeats=max(2, repeats // 2))
            exp = float(np.asarray(res.expectation).reshape(-1)[0])
            results[label] = exp
            rows.append({
                "name": f"sharded_engine/{label}_d{axis_size}",
                "runtime_s": t,
                "derived": f"exp={exp:.4f};opt_steps={steps}",
                "n_qubits": n_qubits,
                "axis_size": axis_size,
                "p_layers": p_layers,
            })
        assert results["opt"] >= results["ramp"], results
        rows.append({
            "name": f"sharded_engine/opt_vs_ramp_d{axis_size}",
            "runtime_s": 0.0,
            "derived": (
                f"exp_ramp={results['ramp']:.4f};exp_opt={results['opt']:.4f};"
                f"improvement={results['opt'] / results['ramp']:.4f}x"
            ),
            "opt_ge_ramp": True,
        })
        quality_ran = True

    if save and quality_ran:
        path = write_bench_json("sharded_engine", rows)
        print(f"# wrote {path}")
    elif save:
        # don't clobber the committed record with a quality-less partial
        # file (tests/test_bench_schema.py asserts the opt_vs_ramp row)
        print("# skip save: opt-vs-ramp rows missing "
              f"(need >= {axis_size} devices)")
    return rows


if __name__ == "__main__":
    import sys

    from benchmarks.common import emit

    if "--schedules" in sys.argv:
        # emulation only for the multi-device suite: forcing 8 devices
        # would distort the single-device microbenchmark timings
        from repro import compat

        compat.ensure_host_device_count(8)
        emit(run_schedules())
    elif "--sharded-engine" in sys.argv:
        from repro import compat

        compat.ensure_host_device_count(8)
        emit(run_sharded_engine())
    else:
        emit(run())
