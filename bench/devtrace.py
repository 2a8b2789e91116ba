"""From a profiler trace to device busy time, program time and a breakdown.

`load` reads the ``.xplane.pb`` the JAX profiler writes into flat `Event`s;
everything else works on those, so tests can feed recorded intervals.

Conventions of the TPU trace (one plane per chip, ``/device:TPU:<i>``):
the ``XLA Ops`` line holds one event per device operation and the
``XLA Modules`` line one per program execution, named after the program's
HLO module. Host threads sit on ``/host:CPU``; the benchmark's own
``jax.profiler.TraceAnnotation`` events are found there by name.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    return [Event(plane.name, line.name, ev.name, float(ev.start_ns),
                  float(ev.duration_ns))
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def host_event(events, name: str) -> Event:
    """The one host event of this name (a benchmark annotation)."""
    found = [e for e in events if e.name == name and
             not e.plane.startswith(DEVICE_PREFIX)]
    if len(found) != 1:
        raise ValueError(f"{len(found)} host events named {name!r}")
    return found[0]


def device_ops(events, lo: float, hi: float) -> dict:
    """Per chip plane: device operations overlapping [lo, hi)."""
    out = collections.defaultdict(list)
    for e in events:
        if (e.plane.startswith(DEVICE_PREFIX) and e.line == OPS_LINE
                and e.end_ns > lo and e.start_ns < hi):
            out[e.plane].append(e)
    return dict(out)


def busy_intervals(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the operations' intervals, clipped to [lo, hi), sorted."""
    spans = sorted((max(e.start_ns, lo), min(e.end_ns, hi)) for e in ops)
    merged = []
    for a, b in spans:
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def busy_s(events, lo: float, hi: float) -> float:
    """Seconds in [lo, hi) during which an operation ran on the device,
    averaged over the chips that ran any."""
    per_chip = device_ops(events, lo, hi)
    if not per_chip:
        return 0.0
    total = sum(b - a for ops in per_chip.values()
                for a, b in busy_intervals(ops, lo, hi))
    return total / len(per_chip) / 1e9


def module_s(events, module: str, lo: float, hi: float) -> tuple[float, int]:
    """(device seconds, executions) of the program whose HLO module is
    ``module``, over chips, within [lo, hi)."""
    hits = [e for e in events
            if e.plane.startswith(DEVICE_PREFIX) and e.line == MODULES_LINE
            and (e.name == module or e.name.startswith(module + "("))
            and e.start_ns >= lo and e.end_ns <= hi]
    return sum(e.dur_ns for e in hits) / 1e9, len(hits)


def self_times(ops) -> list[tuple[Event, float]]:
    """Each operation with its own time: its duration less that of the
    operations nested in it (a loop's body runs inside the loop's event)."""
    out, stack = [], []  # stack of [event, self time] still open
    for e in sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= e.dur_ns
        stack.append([e, e.dur_ns])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def top_ops(events, lo: float, hi: float, k: int = 10, width: int = 160):
    """[[op, device seconds]] of the k operations whose own time (nested
    operations excluded) summed most; names cut to ``width`` letters."""
    total = collections.Counter()
    for ops in device_ops(events, lo, hi).values():
        clipped = [dataclasses.replace(e, start_ns=max(e.start_ns, lo),
                                       dur_ns=min(e.end_ns, hi)
                                       - max(e.start_ns, lo)) for e in ops]
        for e, own in self_times(clipped):
            total[e.name[:width]] += own / 1e9
    return [[name, s] for name, s in total.most_common(k)]


def idle_gaps(events, lo: float, hi: float, spans, k: int = 10):
    """[[host activity, idle seconds]]: the device's idle time in [lo, hi)
    summed by the innermost host span open at each gap's midpoint.

    ``spans`` are (name, start_ns, end_ns) on the trace's clock; a gap that
    no span covers is named ``outside_spans``.
    """
    per_chip = device_ops(events, lo, hi)
    ops = next(iter(per_chip.values()), [])
    busy = busy_intervals(ops, lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    total = collections.Counter()
    for a, b in gaps:
        mid = (a + b) / 2
        inner = [s for s in spans if s[1] <= mid < s[2]]
        name = (min(inner, key=lambda s: s[2] - s[1])[0] if inner
                else "outside_spans")
        total[name] += (b - a) / 1e9
    return [[name, s] for name, s in total.most_common(k)]
