"""Production mesh builders and the `--mesh` CLI spec (DESIGN.md §2.1).

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run (and the CPU
host-device emulation in repro.compat) must set XLA_FLAGS before any jax
initialization. All construction goes through `repro.compat.make_mesh`, so
every mesh has `Auto` axes.

The CLI mesh spec is a comma-separated `axis=size` list, e.g.
``data=8``, ``data=2,model=4``, ``pod=2,data=16,model=16``. Axis names are
restricted to the runtime's three roles (`pod`/`data`/`model`) and
normalized to that canonical order regardless of how the flag spells them;
`model` must be a power of two (the sharded-statevector qubit-swap
all_to_all of core/distributed.py rotates log2(model) qubits).
"""

from __future__ import annotations

from repro import compat

#: Canonical mesh axis order — every mesh the runtime builds uses a
#: (sub)tuple of these names, outermost first.
AXIS_ORDER = ("pod", "data", "model")


def parse_mesh_spec(spec: str) -> dict:
    """Parse ``"data=2,model=4"`` into ``{"data": 2, "model": 4}``.

    Pure string processing (no jax): safe to call before backend init, so
    drivers can size CPU host-device emulation from the parsed product.
    Raises ValueError on malformed specs: unknown/duplicate axis names,
    missing ``=``, non-integer or non-positive sizes, a non-power-of-two
    `model` axis, or an empty spec.
    """
    if not isinstance(spec, str) or not spec.strip():
        raise ValueError(f"empty mesh spec: {spec!r} (expected e.g. 'data=2,model=4')")
    axes: dict = {}
    for item in spec.split(","):
        item = item.strip()
        if "=" not in item:
            raise ValueError(
                f"malformed mesh spec entry {item!r}: expected 'axis=size'"
            )
        name, _, size_s = item.partition("=")
        name = name.strip()
        if name not in AXIS_ORDER:
            raise ValueError(
                f"unknown mesh axis {name!r}: expected one of {AXIS_ORDER}"
            )
        if name in axes:
            raise ValueError(f"duplicate mesh axis {name!r} in {spec!r}")
        try:
            size = int(size_s)
        except ValueError:
            raise ValueError(
                f"mesh axis size must be an integer: {item!r}"
            ) from None
        if size < 1:
            raise ValueError(f"mesh axis size must be >= 1: {item!r}")
        axes[name] = size
    if "model" in axes and axes["model"] & (axes["model"] - 1):
        raise ValueError(
            f"model axis size must be a power of two (got {axes['model']}): "
            "the sharded statevector rotates log2(model) qubits per all_to_all"
        )
    return {a: axes[a] for a in AXIS_ORDER if a in axes}


def mesh_spec_size(spec: dict) -> int:
    """Total device count a parsed mesh spec requires."""
    total = 1
    for s in spec.values():
        total *= s
    return total


def too_few_devices(what: str, need: int, have: int) -> str:
    """Error text for a mesh that needs more devices than are visible.

    Only the CPU backend can emulate more devices, so only there does the
    text advise the host-device flag.
    """
    import jax

    msg = f"{what} needs {need} devices but only {have} are visible"
    if jax.default_backend() == "cpu":
        msg += (f" — set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{need} (or call compat.ensure_host_device_count before jax "
                "initializes)")
    return msg


def device_info() -> dict:
    """Platform, device kind and device count of the default backend."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def build_mesh(spec: dict):
    """Device mesh for a parsed spec, over the first prod(sizes) devices.

    Tolerates a backend exposing more devices than the spec asks for —
    the CLI case where `ensure_host_device_count` found the backend
    already initialized with a larger emulated count.
    """
    import jax

    total = mesh_spec_size(spec)
    devices = jax.devices()
    if len(devices) < total:
        raise ValueError(too_few_devices(f"mesh spec {spec}", total,
                                         len(devices)))
    return compat.make_mesh(tuple(spec.values()), tuple(spec.keys()),
                            devices=devices[:total])


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4):
    """Small mesh for multi-device CPU tests (8 fake devices by default).

    Run under `XLA_FLAGS=--xla_force_host_platform_device_count=8` (or call
    `compat.ensure_host_device_count(8)` before jax initializes).
    """
    return compat.make_mesh((data, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """All batch-shardable axes present in the mesh."""
    return compat.mesh_data_axes(mesh)


def model_axis(mesh) -> str:
    return compat.mesh_model_axis(mesh) or "model"
