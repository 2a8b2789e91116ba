"""Compile ledger: every cached-program build, first-call-per-shape
compile, and trace-time kernel dispatch, recorded (DESIGN.md §8).

Compile time is the dominant *hidden* cost of the pipeline — the
PR 7 SLA soak had to hand-warm every solver and merge program because a
multi-second XLA compile landing mid-soak reads as an SLA miss of the
service. The ledger makes that cost a measurable, regression-gated
quantity:

  - ``build``   — a `compat.cached_program` builder ran (lru-cache
    miss): one jit wrapper constructed for a novel static
    configuration. Key = the builder's arguments.
  - ``compile`` — a cached program's *first call at a novel shape
    signature*: the call that pays trace + XLA compile (duration
    includes that first execution — the cost the caller actually
    waits out). Subsequent same-shape calls hit jit's own cache and
    record nothing.
  - ``op``      — a `kernels.ops` entry point dispatched on tracer
    arguments: fires once per (re)trace per call site, so retrace
    storms (e.g. `merge_scan` retracing per novel graph shape) show up
    as op-event counts with the implementation that was active.

Beside these, the ledger listens to JAX's own compile events
(``/jax/core/compile/{jaxpr_trace,jaxpr_to_mlir_module,backend_compile}
_duration``, one listener registered at import of `repro.obs`), which
see every compile, not only the cached programs', and bills each to the
ambient tracer's open spans (`Tracer.bill_compile`): the span that
caused a compile gets a ``compile`` child, and each open span its
``compile_s`` / ``compiles`` attributes.

A warm system is therefore *provably* warm: re-running a workload after
`reset()` with all caches intact records zero build and zero compile
events (the acceptance gate in tests/test_obs.py and
`benchmarks/obs_bench.py` → `results/BENCH_obs.json`).

The ledger itself never reads a clock (the `repro.obs.clock` contract:
durations are stamped by `compat` against `default_clock` and passed
in), keeps bounded memory via an event cap, and is process-global —
program caches it mirrors are process-global too.
"""

from __future__ import annotations

import dataclasses

from repro.obs import trace as trace_mod

# JAX's compile-path duration events, by the phase name billed to spans
JAX_COMPILE_EVENTS = {
    f"/jax/core/compile/{phase}_duration": phase
    for phase in ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile")
}

# op events dedup per (op, impl) with counts, but build/compile events
# are kept verbatim; a runaway shape storm stops recording (and starts
# counting drops) past this bound rather than growing without limit
MAX_EVENTS = 4096


@dataclasses.dataclass(frozen=True)
class LedgerEvent:
    """One recorded compile-path event."""

    kind: str  # "build" | "compile"
    name: str  # builder name (e.g. "_solve_pool_program")
    key: str  # repr of the builder's cache-key arguments
    signature: str  # arg shape/dtype signature ("" for build events)
    duration_s: float

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class CompileLedger:
    def __init__(self):
        self.events: list[LedgerEvent] = []
        self.dropped = 0
        # (op, impl) → trace-time dispatch count
        self.op_traces: dict[tuple[str, str], int] = {}

    # ------------------------------------------------------------- recording --
    def _append(self, event: LedgerEvent) -> None:
        if len(self.events) >= MAX_EVENTS:
            self.dropped += 1
            return
        self.events.append(event)

    def note_build(self, name: str, key: str, duration_s: float) -> None:
        self._append(LedgerEvent("build", name, key, "", float(duration_s)))

    def note_compile(
        self, name: str, key: str, signature: str, duration_s: float
    ) -> None:
        self._append(
            LedgerEvent("compile", name, key, signature, float(duration_s))
        )

    def note_op(self, op: str, impl: str) -> None:
        k = (op, impl)
        self.op_traces[k] = self.op_traces.get(k, 0) + 1

    def on_jax_event(self, event: str, duration_secs: float,
                     **kwargs) -> None:
        """`jax.monitoring` duration listener: bill a compile phase to
        the span open in the ambient tracer when it ended."""
        phase = JAX_COMPILE_EVENTS.get(event)
        if phase is not None:
            trace_mod.get_tracer().bill_compile(
                phase, str(kwargs.get("fun_name", "")), float(duration_secs))

    # --------------------------------------------------------------- reading --
    def count(self, kind: str) -> int:
        return sum(1 for e in self.events if e.kind == kind)

    @property
    def builds(self) -> list[LedgerEvent]:
        return [e for e in self.events if e.kind == "build"]

    @property
    def compiles(self) -> list[LedgerEvent]:
        return [e for e in self.events if e.kind == "compile"]

    def total_compile_s(self) -> float:
        return sum(e.duration_s for e in self.compiles)

    def snapshot(self) -> dict:
        """JSON-able view for metrics exports and the obs bench."""
        return {
            "builds": self.count("build"),
            "compiles": self.count("compile"),
            "compile_s": round(self.total_compile_s(), 6),
            "dropped": self.dropped,
            "op_traces": {
                f"{op}[{impl}]": n
                for (op, impl), n in sorted(self.op_traces.items())
            },
            "events": [e.as_dict() for e in self.events],
        }

    def reset(self) -> None:
        """Start a fresh accounting window. Does NOT clear any program
        cache — that is the point: a warm re-run after `reset()` must
        record zero build/compile events."""
        self.events.clear()
        self.op_traces.clear()
        self.dropped = 0


# process-global, mirroring the process-global program caches it audits
_LEDGER = CompileLedger()


def get_ledger() -> CompileLedger:
    return _LEDGER


def listen_to_jax() -> None:
    """Register the global ledger's listener for JAX's compile events;
    called once, at import of `repro.obs`. Without JAX (the stdlib-only
    validator's setting) there is nothing to listen to."""
    try:
        from jax import monitoring
    except ImportError:
        return
    monitoring.register_event_duration_secs_listener(_LEDGER.on_jax_event)
