"""Structured span tracer for the request-to-kernel lifecycle
(DESIGN.md §8).

A `Span` is one named, timed interval with a parent pointer and a flat
attribute dict; a `Tracer` mints them against an injectable clock (the
`repro.obs.clock` contract — a `workload.VirtualClock` makes whole
traced soaks bit-deterministic). Two usage shapes coexist because the
solve service interleaves many request lifecycles on one thread:

  - **explicit-parent** ``begin(name, parent=...)`` / ``end(span)`` for
    long-lived spans that outlive any call frame (a request's root span
    opens at `submit` and closes at its terminal state, with admission,
    dispatch, and merge spans from other requests in between);
  - **stack-scoped** ``with tracer.span(name):`` for synchronous stages
    (partition, merge levels) — the context manager keeps an implicit
    parent stack, and ``attach(span)`` pushes an existing span so
    nested library code (e.g. `core.merge.merge_stream`) parents its
    spans under the caller's without threading tracer arguments through
    every signature.

``record=False`` (the default everywhere) keeps no spans: `begin`/`end`
still stamp the clock — the scheduler derives its recalibration
observations and latency stamps from span durations, so the stamps must
exist unconditionally — but nothing is retained or exported, which is
what keeps tracing-off overhead at zero allocation growth. `--trace-out`
on the launch drivers constructs the tracer with ``record=True``.

A tracer on the real clock (`default_clock`) shares its timeline with
JAX: a recording one enters a `jax.profiler.TraceAnnotation` for every
stack-scoped span, so a profiler trace shows the program's stages around
the device's operations, and every JAX compile phase is billed to the
open spans (`bill_compile`, fed by the compile ledger's listener).

Retained spans export as JSON-lines (one span object per line, sorted
by ``(t0, span_id)`` so identical runs produce byte-identical files)
and as Chrome trace-event format (``ph: "X"`` complete events,
microsecond units) loadable in Perfetto — see README "Observability".

Module-global accessors (`get_tracer` / `set_tracer` / `use_tracer`)
let the core pipeline stages emit spans without a tracer parameter:
the default global tracer records nothing, and the service/driver
swaps its own in scope-bound via `use_tracer`.
"""

from __future__ import annotations

import contextlib
import json

from repro.obs.clock import default_clock

# sentinel for `begin(parent=ROOT)`: force a parentless span even when
# the implicit stack is non-empty (e.g. a request submitted from inside
# another request's streaming callback must still root its own tree)
ROOT = object()


class Span:
    """One named, timed interval. ``t1 is None`` until ended."""

    __slots__ = ("span_id", "parent_id", "name", "t0", "t1", "attrs")

    def __init__(self, span_id, parent_id, name, t0, attrs):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.t0 = t0
        self.t1 = None
        self.attrs = attrs

    @property
    def duration_s(self) -> float:
        if self.t1 is None:
            raise ValueError(f"span {self.name!r} not ended")
        return self.t1 - self.t0

    def as_dict(self) -> dict:
        return {
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "t0": self.t0,
            "t1": self.t1,
            "attrs": self.attrs,
        }

    def __repr__(self) -> str:  # debugging aid, never parsed
        return (
            f"Span({self.name!r}, id={self.span_id}, "
            f"parent={self.parent_id}, t0={self.t0}, t1={self.t1})"
        )


class Tracer:
    """Mints spans against one injected clock; retains them only when
    ``record=True`` (tracing is disabled by default — DESIGN.md §8)."""

    def __init__(self, clock=default_clock, record: bool = False):
        self._clock = clock
        self.record = bool(record)
        self.spans: list[Span] = []  # ended spans, when recording
        self._stack: list[Span] = []  # implicit-parent stack
        self._next_id = 1
        # disjoint, sorted compile intervals still inside an open span
        self._compile_union: list[tuple[float, float]] = []

    # ------------------------------------------------------------ lifecycle --
    def begin(self, name: str, parent: Span | None = None, **attrs) -> Span:
        """Open a span. ``parent=None`` adopts the top of the implicit
        stack (or roots the span if the stack is empty); ``parent=ROOT``
        forces a parentless span regardless of the stack."""
        if parent is ROOT:
            parent = None
        elif parent is None and self._stack:
            parent = self._stack[-1]
        span = Span(
            self._next_id,
            None if parent is None else parent.span_id,
            name,
            self._clock(),
            attrs,
        )
        self._next_id += 1
        return span

    def end(self, span: Span, **attrs) -> Span:
        """Close a span (exactly once), merging any final attributes."""
        if span.t1 is not None:
            raise ValueError(f"span {span.name!r} ended twice")
        if attrs:
            span.attrs.update(attrs)
        span.t1 = self._clock()
        if self.record:
            self.spans.append(span)
        return span

    def span_at(
        self, name: str, t0: float, t1: float,
        parent: Span | None = None, **attrs,
    ) -> Span:
        """A retroactive complete span over caller-supplied stamps.

        The scheduler's solve window is reconstructed at harvest time
        (``max(issue, previous harvest)`` → land, DESIGN.md §6.5), so
        the span cannot be opened live; the stamps must come from the
        same injected clock for nesting invariants to hold.
        """
        if parent is ROOT:
            parent = None
        elif parent is None and self._stack:
            parent = self._stack[-1]
        span = Span(
            self._next_id,
            None if parent is None else parent.span_id,
            name,
            float(t0),
            attrs,
        )
        self._next_id += 1
        span.t1 = float(t1)
        if self.record:
            self.spans.append(span)
        return span

    @property
    def on_real_clock(self) -> bool:
        """True when stamps come from `default_clock`, the clock JAX's
        compile events and the profiler's host plane share."""
        return self._clock is default_clock

    @contextlib.contextmanager
    def span(self, name: str, parent: Span | None = None, **attrs):
        """Stack-scoped span: children begun inside the block nest
        under it implicitly. A recording tracer on the real clock also
        opens a `jax.profiler.TraceAnnotation` of the span's name, so a
        profiler trace taken around the block shows the span on its host
        plane."""
        s = self.begin(name, parent=parent, **attrs)
        self._stack.append(s)
        try:
            if self.record and self.on_real_clock:
                import jax.profiler

                with jax.profiler.TraceAnnotation(name):
                    yield s
            else:
                yield s
        finally:
            self._stack.pop()
            self.end(s)

    def bill_compile(self, phase: str, fun_name: str,
                     duration_s: float) -> None:
        """Bill one finished JAX compile phase to the open spans.

        JAX reports a phase when it ends, with its duration, so the
        phase is the interval ``[now - duration_s, now]``. The innermost
        open span gets a retroactive ``compile`` child (when recording);
        every open span adds the phase's seconds to its ``compile_s``
        attribute and, for a backend compile (a cache load included), one
        to ``compiles``. Phases nest (a trace inside a lowering), so
        ``compile_s`` counts the union of the intervals, each second once.
        Only a tracer on the real clock is billed: an injected clock's
        stamps share no timeline with JAX's.
        """
        if not self._stack or not self.on_real_clock:
            return
        t1 = self._clock()
        t0 = t1 - duration_s
        pieces = self._union_add(t0, t1)
        for s in self._stack:
            gained = sum(max(0.0, b - max(a, s.t0)) for a, b in pieces)
            s.attrs["compile_s"] = s.attrs.get("compile_s", 0.0) + gained
            if phase == "backend_compile":
                s.attrs["compiles"] = s.attrs.get("compiles", 0) + 1
        if self.record:
            inner = self._stack[-1]
            self.span_at("compile", max(t0, inner.t0), t1, parent=inner,
                         fun_name=fun_name, phase=phase)

    def _union_add(self, t0: float, t1: float) -> list[tuple[float, float]]:
        """Merge ``[t0, t1]`` into the compile-interval union; return the
        pieces of it the union did not already cover."""
        floor = self._stack[0].t0  # nothing before the oldest open span
        pieces, cur, lo, hi = [], t0, t0, t1
        kept = []
        for a, b in self._compile_union:
            if b < floor:
                continue
            if b < t0 or a > t1:
                kept.append((a, b))
                continue
            if a > cur:
                pieces.append((cur, a))
            cur = max(cur, b)
            lo, hi = min(lo, a), max(hi, b)
        if cur < t1:
            pieces.append((cur, t1))
        kept.append((lo, hi))
        self._compile_union = sorted(kept)
        return pieces

    def annotate(self, **attrs) -> None:
        """Merge attributes into the innermost open stack-scoped span, so
        library code reports what it did (e.g. refinement's accepted
        flips) without a span parameter; outside any span, nothing."""
        if self._stack:
            self._stack[-1].attrs.update(attrs)

    @contextlib.contextmanager
    def attach(self, span: Span):
        """Push an *existing* (still-open) span onto the implicit stack
        without ending it — nested library spans parent under it."""
        self._stack.append(span)
        try:
            yield span
        finally:
            self._stack.pop()

    # --------------------------------------------------------------- export --
    def _sorted(self) -> list[Span]:
        return sorted(self.spans, key=lambda s: (s.t0, s.span_id))

    def to_jsonl(self) -> str:
        """One JSON object per line, byte-stable across identical runs."""
        return "\n".join(
            json.dumps(s.as_dict(), sort_keys=True) for s in self._sorted()
        )

    def export_jsonl(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.to_jsonl())
            f.write("\n")
        return path

    def to_chrome(self) -> dict:
        """Chrome trace-event format: ``ph: "X"`` complete events in
        microseconds, Perfetto-loadable (README "Observability")."""
        events = []
        for s in self._sorted():
            args = dict(s.attrs)
            args["span_id"] = s.span_id
            if s.parent_id is not None:
                args["parent_id"] = s.parent_id
            events.append({
                "name": s.name,
                "ph": "X",
                "ts": s.t0 * 1e6,
                "dur": (s.t1 - s.t0) * 1e6,
                "pid": 1,
                "tid": 1,
                "args": args,
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, sort_keys=True)
        return path

    def export(self, path: str, fmt: str = "jsonl") -> str:
        if fmt == "jsonl":
            return self.export_jsonl(path)
        if fmt == "chrome":
            return self.export_chrome(path)
        raise ValueError(f"unknown trace format {fmt!r}")


# ------------------------------------------------------- global accessors --
# the ambient tracer core pipeline stages emit against; records nothing
# until a driver/service installs its own (tracing off by default)
_GLOBAL = Tracer()


def get_tracer() -> Tracer:
    return _GLOBAL


def set_tracer(tracer: Tracer) -> Tracer:
    global _GLOBAL
    prev = _GLOBAL
    _GLOBAL = tracer
    return prev


@contextlib.contextmanager
def use_tracer(tracer: Tracer):
    """Scope-bound global-tracer override (restores on exit, even on
    error) — the service installs its own tracer around merge/solve
    stages so library spans land in the request's trace."""
    prev = set_tracer(tracer)
    try:
        yield tracer
    finally:
        set_tracer(prev)
