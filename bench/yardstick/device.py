"""The chip: refuse to run without one, describe it, read its memory peak."""

from __future__ import annotations


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> list:
    """The first ``chips`` TPU devices; raises :class:`NoChip` otherwise.
    There is no fallback to another platform."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def describe(devices: list) -> dict:
    import jax

    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def peak_bytes(devices: list) -> int:
    """``peak_bytes_in_use`` of the fullest chip (0 where the backend keeps
    no statistics).  It cannot be reset: it covers warm-up too."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))
