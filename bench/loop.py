"""The traffic generator: drives the solve entry point as a traffic mix's
data file (``traffic/<mix>.json``) describes.

Keys of a mix:
  loop      "closed": a client sends its next solve only when the last
            one has returned.
  clients   number of closed-loop clients (1: whole solves back to back).
  instance  "per_seed": every solve is of the one instance the run's seed
            made, so the window holds no compile and no instance work.

The window opens at the first solve. A solve starts only while the window
is open, and the window ends when the last started solve returns, so the
window holds whole solves only.
"""

from __future__ import annotations

import time
import types

MIX_KEYS = {"loop": {"closed"}, "clients": {1}, "instance": {"per_seed"}}


def validate(mix: dict) -> dict:
    for key, allowed in MIX_KEYS.items():
        if mix.get(key) not in allowed:
            raise ValueError(f"traffic {key}={mix.get(key)!r}: this "
                             f"generator runs {sorted(allowed)}")
    extra = sorted(set(mix) - set(MIX_KEYS))
    if extra:
        raise ValueError(f"unknown traffic keys {extra}")
    return mix


def drive(mix: dict, solve_once, seconds: float):
    """Run the window: (window seconds, answers, attempted, failed, error).

    ``solve_once()`` returns the solve's output; its host values
    (assignment, value, timings) are kept, which also waits for the
    device. A solve that raises ends the window and counts as failed.
    """
    validate(mix)
    answers, attempted, error = [], 0, None
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        attempted += 1
        try:
            out = solve_once()
        except Exception as exc:  # a failed solve is a result of the run
            error = f"{type(exc).__name__}: {exc}"
            break
        answers.append(types.SimpleNamespace(
            assignment=out.assignment, cut_value=out.cut_value,
            timings=out.timings))
    window = time.perf_counter() - t0
    return window, answers, attempted, attempted - len(answers), error
