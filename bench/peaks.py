"""Published peaks of one chip, keyed by `jax.Device.device_kind`.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s. The bf16 rate is the chip's highest, so a
float32 program's share of it is a lower bound of its share of its own
ceiling. A device that is not in the table has no roofline: asking for
one is an error, never another chip's numbers.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for {device_kind!r}; known: "
                         f"{sorted(PEAKS)}") from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> tuple[float, str]:
    """(percent of the roofline bound reached in ``seconds``, the bound's
    side: ``compute`` or ``memory``)."""
    pk = peaks(device_kind)
    t_flops, t_bytes = flops / pk["flops_per_s"], nbytes / pk["bytes_per_s"]
    side = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, side
