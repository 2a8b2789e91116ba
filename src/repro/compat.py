"""The one seam between the runtime and JAX's SPMD and platform APIs.

The distributed runtime (core/distributed.py, launch/mesh.py,
launch/sharding.py) is written for the installed JAX (0.9) and runs on a
real TPU mesh or on CPU host-device emulation
(``--xla_force_host_platform_device_count``). Everything platform-
conditional funnels through this module so call sites stay clean:

  ``shard_map(f, mesh, in_specs, out_specs, check=False)``
      ``jax.shard_map`` with ``check`` passed as ``check_vma``.
  ``jit(f, donate_argnums=...)``
      ``jax.jit`` that drops buffer donation on backends that do not
      implement it (CPU), avoiding per-call "donation not usable" warnings.
  ``make_mesh(shape, axis_names, devices=None)``
      ``jax.make_mesh`` with every axis ``Auto``: the runtime's programs
      state their shardings through shard_map specs, not sharding-in-types.
  ``ensure_host_device_count(n)``
      Idempotent CPU host-device emulation: appends the XLA flag if the
      backend is not yet initialized (no-op, with the actual count
      returned, when it is).
  ``use_compile_cache()``
      JAX's persistent compilation cache at a fixed path, for the drivers.

See docs/TESTING.md for how the CPU emulation is used in the tests.
"""

from __future__ import annotations

import functools
import os
from typing import Callable, Sequence

import jax
from jax.sharding import AxisType

# <checkout>/src/repro/compat.py → <checkout>
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ------------------------------------------------------------- shard_map --
def shard_map(f: Callable, mesh, in_specs, out_specs, *, check: bool = False):
    """``jax.shard_map`` with the replication check off by default.

    The merge winner-select and top-k reductions produce values that *are*
    replicated but that the static checker cannot prove so.
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )


# -------------------------------------------------------------------- jit --
def supports_donation(platform: str | None = None) -> bool:
    """Buffer donation is implemented on TPU/GPU; CPU silently ignores it
    and warns per call."""
    platform = platform or jax.default_backend()
    return platform in ("tpu", "gpu", "cuda", "rocm")


def jit(f: Callable, *, donate_argnums: Sequence[int] = (), **kwargs):
    """jax.jit that applies ``donate_argnums`` only where donation works."""
    if donate_argnums and supports_donation():
        kwargs["donate_argnums"] = tuple(donate_argnums)
    return jax.jit(f, **kwargs)


# ------------------------------------------------------------------- mesh --
def make_mesh(shape: Sequence[int], axis_names: Sequence[str],
              devices: Sequence | None = None):
    """Dense device mesh over ``devices`` (default: all of the default
    backend's), every axis ``Auto``.

    ``jax.make_mesh`` alone makes ``Explicit`` axes, under which indexing a
    sharded array raises unless each gather names its output sharding.
    """
    names = tuple(axis_names)
    return jax.make_mesh(tuple(shape), names,
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def _backend_initialized() -> bool:
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)


_HOST_COUNT_FLAG = "--xla_force_host_platform_device_count"


def ensure_host_device_count(n: int) -> int:
    """Arrange for >= n devices on the host platform (CPU emulation).

    Must run before the first jax backend touch (device queries, array
    creation). Idempotent; returns the device count that will be (or
    already is) visible. When the backend is already up with fewer
    devices, returns that smaller count — callers should size their mesh
    by the return value or skip.
    """
    if _backend_initialized():
        return len(jax.devices())
    flags = os.environ.get("XLA_FLAGS", "")
    if _HOST_COUNT_FLAG in flags:
        # operator already chose a count: the environment wins
        return int(flags.split(f"{_HOST_COUNT_FLAG}=")[1].split()[0])
    os.environ["XLA_FLAGS"] = f"{flags} {_HOST_COUNT_FLAG}={n}".strip()
    return n


def device_count() -> int:
    return len(jax.devices())


def mesh_data_axes(mesh) -> tuple:
    """All batch-shardable axes present in the mesh, in canonical order."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def mesh_model_axis(mesh) -> str | None:
    return "model" if "model" in mesh.shape else None


# ------------------------------------------------------- program caching --
_PROGRAM_CACHE_SIZE = 32


def _arg_signature(args: tuple, kwargs: dict) -> str:
    """Shape/dtype signature of a program call — the axis jit's own cache
    keys on beyond the builder's static key."""
    parts = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}{list(shape)}")
        else:
            parts.append(type(leaf).__name__)
    return ";".join(parts)


class _LedgerProgram:
    """Pass-through wrapper over a built program that records each first
    call at a novel shape signature in the compile ledger — the call that
    pays trace + XLA compile. Same-signature calls are ledger-free."""

    __slots__ = ("_program", "_name", "_key", "_seen")

    def __init__(self, program: Callable, name: str, key: str):
        self._program = program
        self._name = name
        self._key = key
        self._seen: set = set()

    def __call__(self, *args, **kwargs):
        sig = _arg_signature(args, kwargs)
        if sig in self._seen:
            return self._program(*args, **kwargs)
        self._seen.add(sig)
        from repro.obs.clock import default_clock
        from repro.obs.ledger import get_ledger

        t0 = default_clock()
        out = self._program(*args, **kwargs)
        get_ledger().note_compile(self._name, self._key, sig,
                                  default_clock() - t0)
        return out

    def __getattr__(self, attr):
        return getattr(self._program, attr)


def cached_program(builder: Callable) -> Callable:
    """LRU-cache a compiled-program builder keyed on its (hashable) args.

    The per-call ``jax.jit(shard_map(...))`` pattern builds a *new* jit
    wrapper every call, so every ``solve_pool`` call re-traces and
    re-compiles — a hidden hot-path cost once the solver pool serves
    repeated partitions. Builders decorated with this return the same
    compiled callable for the same static configuration; jit's own cache
    then handles shape/dtype polymorphism.

    Bounded (not maxsize=None): cache keys include the Mesh, and an
    elastic job that re-meshes after failures would otherwise pin every
    historical mesh + compiled executable forever. LRU eviction drops the
    oldest program (and its jit wrapper) once more than
    ``_PROGRAM_CACHE_SIZE`` static configurations have been seen.

    Every cache miss records a ``build`` event in the compile ledger
    (`repro.obs.ledger`), and the returned program records a ``compile``
    event on its first call at each novel shape signature — so a warm
    re-run provably records nothing (DESIGN.md §8). Identity semantics
    are unchanged: same key → the same wrapper object.
    """
    @functools.wraps(builder)
    def build(*key):
        from repro.obs.clock import default_clock
        from repro.obs.ledger import get_ledger

        t0 = default_clock()
        program = builder(*key)
        get_ledger().note_build(builder.__name__, repr(key),
                                default_clock() - t0)
        return _LedgerProgram(program, builder.__name__, repr(key))

    return functools.lru_cache(maxsize=_PROGRAM_CACHE_SIZE)(build)


# ------------------------------------------------------ compilation cache --
def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Call before the first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX reads it itself and nothing is set here. Otherwise the cache
    is ``<checkout>/.jax_cache``: a fixed path, because the path is part of
    each entry's key and a directory that moves never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
