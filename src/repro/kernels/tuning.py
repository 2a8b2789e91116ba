"""Block-shape tuning for the Pallas kernel layer (DESIGN.md §2.7).

Every kernel resolves its tile constants through `param(op, dim, name,
default)` at *trace* time. With tuning disabled (the default) the lookup
returns the hard-coded default, so the compiled programs — and their
numerics, including reduction order in `expectation` — are bit-identical
to the untuned kernels. With tuning enabled, lookups consult a config
table keyed per ``(op, shape-bucket, backend)``:

  - the committed cache (`tuning_cache.json`, regenerated by
    ``benchmarks/kernel_autotune.py --write-cache``), or
  - an explicit override table (`using_overrides`, used by the autotune
    sweep itself).

Because dispatch happens at trace time, cached-program builders that must
honor the active tuning state take `state()` as an explicit cache-key
parameter and re-assert it with `using_state()` inside the traced body —
the same contract `kernels.ops.using_implementation` follows, and the one
the reprolint `cache-key` rule checks.

This module is also the autotune harness's measurement path: `measure`
times kernel launches through the injectable `repro.obs.clock` boundary
(reprolint's `hot-nondeterminism` rule bans direct ``time.*`` reads here).
"""

from __future__ import annotations

import contextlib
import json
import os
from typing import Callable, Dict, Optional, Tuple

import jax

from repro.obs.clock import default_clock

CACHE_PATH = os.path.join(os.path.dirname(__file__), "tuning_cache.json")

# Ops with tunable block shapes and their tunable parameter names; the
# autotune harness and the cache validator both enumerate from here.
TUNABLE_OPS = {
    "apply_phase": ("tile",),
    "expectation": ("tile",),
    "mixer_matmul": ("row_tile",),
    "mixer_strided": ("tile_x", "tile_y"),
    "fused_layer": ("row_tile",),
    "cutvals": ("tile_b", "edge_chunk"),
    "cutvals_at": ("tile_b", "edge_chunk"),
    "cut_batch_dense": ("batch_tile", "k_chunk"),
}


# ---------------------------------------------------------------------------
# tile / padding arithmetic — the one shared seam for every kernel wrapper
# ---------------------------------------------------------------------------

def round_up(x: int, multiple: int) -> int:
    """Smallest multiple of `multiple` that is >= x."""
    return ((x + multiple - 1) // multiple) * multiple


def clamp_tile(dim: int, tile: int) -> int:
    """Clamp a tile to the dimension it blocks; the dim must tile evenly
    (all statevector dims here are powers of two, as are the tiles)."""
    t = min(tile, dim)
    assert dim % t == 0, (dim, tile)
    return t


LANES = 128  # TPU vector lane width: the minor dimension of a lane-dense block


def lane_view(count: int, tile: int) -> Tuple[int, int, int]:
    """(rows, lanes, tile_rows) of the lane-dense 2-D view of a flat
    vector of ``count`` elements blocked ``tile`` elements at a time
    (``tile`` divides ``count`` and is a multiple of the lane count).

    A 2-D block keeps its last two dimensions legal for the TPU compiler
    (full dims, or multiples of (8, 128)) when `jax.vmap` prepends a batch
    axis; a 1-D block would not be. Tiles shorter than one lane row use
    the tile as the row width.
    """
    lanes = min(LANES, tile)
    assert count % tile == 0 and tile % lanes == 0, (count, tile)
    return count // lanes, lanes, tile // lanes


def pad_chunks(count: int, chunk: int) -> int:
    """Pad a count up to a chunk multiple, with at least one full chunk
    (the edge-list case: padding entries are zero-weight no-ops)."""
    return max(chunk, round_up(count, chunk))


def pad_and_tile(count: int, tile: int) -> Tuple[int, int]:
    """(padded_count, tile) for a dimension that may not divide the tile:
    clamp the tile to the count, then pad the count to a tile multiple."""
    t = min(tile, count)
    return round_up(count, t), t


# ---------------------------------------------------------------------------
# (op, shape-bucket, backend) config table
# ---------------------------------------------------------------------------

def shape_bucket(dim: int) -> str:
    """Power-of-two shape bucket: the smallest 2^b >= dim, as '2^b'.

    Matches the service's shape-bucketing discipline — one tuned config
    per compiled program shape class, not per exact size.
    """
    assert dim >= 1, dim
    return f"2^{(dim - 1).bit_length()}"


def cache_key(op: str, dim: int, backend: Optional[str] = None) -> str:
    return f"{op}|{shape_bucket(dim)}|{backend or jax.default_backend()}"


_ENABLED = False
_OVERRIDES: Optional[Dict[str, Dict[str, int]]] = None  # None → committed cache
_COMMITTED: Optional[Dict[str, Dict[str, int]]] = None  # lazy-loaded JSON


def _committed() -> Dict[str, Dict[str, int]]:
    global _COMMITTED
    if _COMMITTED is None:
        try:
            with open(CACHE_PATH) as f:
                _COMMITTED = dict(json.load(f).get("entries", {}))
        except (OSError, ValueError):
            _COMMITTED = {}
    return _COMMITTED


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def active_config() -> Dict[str, Dict[str, int]]:
    return _OVERRIDES if _OVERRIDES is not None else _committed()


def param(op: str, dim: int, name: str, default: int) -> int:
    """Resolve one tunable block parameter at trace time."""
    if not _ENABLED:
        return default
    entry = active_config().get(cache_key(op, dim))
    if not entry:
        return default
    return int(entry.get(name, default))


def state() -> tuple:
    """The active tuning state as a hashable cache-key component.

    ``("off",)`` when disabled; otherwise the full flattened config table,
    so two processes with different caches (or override tables) never
    share a cached program. `using_state` reconstructs the exact state
    from this value inside a traced builder body.
    """
    if not _ENABLED:
        return ("off",)
    cfg = active_config()
    return (
        "on",
        tuple(
            (key, tuple(sorted(entry.items())))
            for key, entry in sorted(cfg.items())
        ),
    )


@contextlib.contextmanager
def using_state(st: tuple):
    """Re-assert a tuning state captured by `state()` for the duration of a
    traced builder body (the cache-key contract — see module docstring)."""
    global _ENABLED, _OVERRIDES
    prev = (_ENABLED, _OVERRIDES)
    if st == ("off",):
        _ENABLED, _OVERRIDES = False, None
    else:
        assert st and st[0] == "on", st
        _ENABLED = True
        _OVERRIDES = {key: dict(items) for key, items in st[1]}
    try:
        yield
    finally:
        _ENABLED, _OVERRIDES = prev


@contextlib.contextmanager
def using_overrides(cfg: Dict[str, Dict[str, int]]):
    """Enable tuning with an explicit config table (the autotune sweep)."""
    global _ENABLED, _OVERRIDES
    prev = (_ENABLED, _OVERRIDES)
    _ENABLED, _OVERRIDES = True, dict(cfg)
    try:
        yield
    finally:
        _ENABLED, _OVERRIDES = prev


def invalidate_committed() -> None:
    """Drop the lazy-loaded committed cache (after --write-cache)."""
    global _COMMITTED
    _COMMITTED = None


# ---------------------------------------------------------------------------
# measurement — the autotune harness's only timing boundary
# ---------------------------------------------------------------------------

def measure(fn: Callable, *args, repeats: int = 3,
            clock: Callable[[], float] = default_clock):
    """(result, best_seconds) over `repeats` synchronized launches.

    One warmup launch pays compile/trace cost; timed launches block on the
    result so device work is inside the measured window. The clock is the
    injectable `repro.obs.clock.default_clock` (tests substitute a virtual
    clock; no direct ``time.*`` reads on this path).
    """
    result = jax.block_until_ready(fn(*args))
    best = None
    for _ in range(max(1, repeats)):
        t0 = clock()
        result = jax.block_until_ready(fn(*args))
        dt = clock() - t0
        best = dt if best is None else min(best, dt)
    return result, best
