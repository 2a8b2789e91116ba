#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by the names in ``BENCHMARK.json`` at the checkout's
root: the cell's configuration file (``bench/configs/<config>.json``), its
traffic mix (``bench/traffic/<traffic>.json``, driven by `loop.py`) and one
reader per metric (``bench/metrics/<metric>.py``, a ``read(run)`` that
returns the value, or None where it finds nothing to read).

The run makes the instance from the seed, warms up with one solve (that is
set-up), measures for ``--seconds`` (with the profiler on when ``--trace
1``), then, with the program's state released, compares every solve of the
window with the plain reference (`check.py`). Standard error ends with each
compared number beside its limit; the last line of standard output is one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and ``checks`` last. With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.

JAX's persistent compilation cache is kept in ``.bench/jax_cache`` inside
the checkout, so only a cell's first run there compiles. Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and prints
no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench")
CACHE = os.path.join(OUT, "jax_cache")
sys.path.insert(0, BENCH)

WINDOW = "bench_window"  # the profiler annotation around the window


def note(msg: str) -> None:
    """A progress line on standard error, stamped from process start."""
    print(f"bench [{time.perf_counter() - T_START:8.2f} s] {msg}",
          file=sys.stderr, flush=True)


def stages(timings) -> str:
    return ", ".join(f"{k} {v:.3f}" for k, v in timings.items())


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str):
    """(benchmark spec, cell, configuration, traffic mix) by name."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    mix = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    return spec, cell, config, mix


def cell_metrics(spec, cell_name: str, traced: bool) -> list:
    """The metric entries this cell reports in a run of this kind."""
    entries = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in entries if cell_name in m.get("workloads",
                                                     [cell_name])]


def reader(metric: str):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", metric), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


def require_chips(count: int) -> dict:
    """The device record; exits non-zero without ``count`` TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found "
                         f"{devices[0].platform!r}; there is no CPU fallback")
    if len(devices) < count:
        raise SystemExit(f"bench: the cell needs {count} chips, JAX found "
                         f"{len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def use_compile_cache():
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts programs JAX compiles or loads from its cache while on."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration_secs, **kwargs):
        if self.on and event == self.EVENT:
            self.count += 1


def pool_module(recorder) -> str:
    """The HLO module name of the pool's program, from its lowering."""
    text = recorder.pool_program.lower(*recorder.pool_args).as_text()
    found = re.search(r"module @([\w.\-]+)", text)
    if not found:
        raise ValueError("no module name in the pool program's lowering")
    return found.group(1)


def run_cell(cell, config, mix, seed: int, seconds: float, traced: bool,
             device: dict, t_start: float, out_dir: str = OUT):
    """Set up, measure, check. Returns (run record for the metric readers,
    device record, solves attempted, solves failed, checks, correct)."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ParaQAOAConfig, solve
    from repro.core.graph import Graph
    from repro.obs import trace as obs_trace

    import capture
    import check
    import instances
    import loop
    import work

    solver = config["solver"]
    n = config["instance"]["n"]
    edges, weights = instances.build(config["instance"], solver["n_qubits"],
                                     seed)
    graph = Graph(n=n, edges=jnp.asarray(edges), weights=jnp.asarray(weights),
                  n_edges=int(edges.shape[0]))
    cfg = ParaQAOAConfig(**solver)
    note(f"instance: {n} vertices, {edges.shape[0]} edges")
    recorder = capture.Recorder().install()
    counter = CompileCounter()
    try:
        warm = solve(graph, cfg)  # warm-up: compiles or loads every program
        note(f"warm-up solve: {stages(warm.timings)}")
        del warm
        module = pool_module(recorder) if traced else None
        m_sub, e_pad = (int(d) for d in recorder.pool_args[0].shape[:2])
        recorder.clear()
        tracer = obs_trace.Tracer(record=traced)
        trace_dir = os.path.join(out_dir, "trace", cell["name"])
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        setup_s = time.perf_counter() - t_start
        counter.on = True
        with obs_trace.use_tracer(tracer):
            t_window = time.perf_counter()
            annotation = (jax.profiler.TraceAnnotation(WINDOW) if traced
                          else contextlib.nullcontext())
            with annotation:
                window_s, answers, attempted, failed, error = loop.drive(
                    mix, lambda: solve(graph, cfg), seconds)
        counter.on = False
        if traced:
            jax.profiler.stop_trace()
        note(f"window {window_s:.3f} s, {len(answers)} solves, "
             f"{counter.count} compiles; last: "
             f"{stages(answers[-1].timings) if answers else '-'}")
        stats = jax.devices()[0].memory_stats() or {}
        device = dict(device, memory_peak_bytes=int(
            stats.get("peak_bytes_in_use", 0)))
        solves = recorder.solves(answers)
    finally:
        recorder.uninstall()
    del graph
    recorder.clear()
    gc.collect()

    if error:
        print(f"bench: solve {attempted} failed: {error}", file=sys.stderr)
    run = types.SimpleNamespace(
        setup_s=setup_s, window_s=window_s, answers=answers,
        host_cuts=[check.ref.host_cut(edges, weights, a.assignment)
                   for a in answers],
        total_weight=float(np.sum(weights, dtype=np.float64)),
        compiles=counter.count, solver=solver, device_kind=device["kind"],
        pool_work=work.pool_batch(m_sub, solver["n_qubits"],
                                  solver["p_layers"], solver["opt_steps"],
                                  config["mixer_group"], e_pad),
        trace=None, breakdown=None)
    if traced:
        run.trace, run.breakdown = reduce_trace(trace_dir, module, tracer,
                                                t_window)
        note(f"trace read: {run.trace}")
        device = dict(device, busy_s=run.trace.busy_s,
                      window_s=run.trace.window_s)

    note("checking against the reference")
    values = (check.compare(n, edges, weights, solver, solves, seed)
              if solves else {})
    note("checked")
    correct, checks = check.judge(values, config["limits"], failed)
    correct = correct and bool(solves)
    return run, device, attempted, failed, checks, correct


def reduce_trace(trace_dir, module, tracer, t_window):
    import devtrace

    events = devtrace.load(trace_dir)
    window = devtrace.host_event(events, WINDOW)
    lo, hi = window.start_ns, window.end_ns
    pool_s, pool_calls = devtrace.module_s(events, module, lo, hi)
    # the program's spans, moved onto the trace's clock through the window
    offset = lo - t_window * 1e9
    spans = [(s.name, s.t0 * 1e9 + offset, s.t1 * 1e9 + offset)
             for s in tracer.spans]
    summary = types.SimpleNamespace(
        busy_s=devtrace.busy_s(events, lo, hi), window_s=(hi - lo) / 1e9,
        pool_s=pool_s, pool_calls=pool_calls, module=module)
    breakdown = {"device_ops": devtrace.top_ops(events, lo, hi),
                 "idle_gaps": devtrace.idle_gaps(events, lo, hi, spans)}
    return summary, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec, cell, config, mix = load_cell(args.workload)
    metrics = cell_metrics(spec, cell["name"], bool(args.trace))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    device = require_chips(cell["chips"])
    use_compile_cache()
    run, device, attempted, failed, checks, correct = run_cell(
        cell, config, mix, args.seed, args.seconds, bool(args.trace), device,
        T_START)

    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": device}
    for m in metrics:
        value = reader(m["name"])(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    if args.trace:
        result["breakdown"] = run.breakdown
    result["checks"] = checks
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
