"""Operations and bytes of the DAG kernels, the chips' peaks, and a
kernel's share of its roofline.

The f32 blocks' dot runs as one bf16 pass on the MXU (``jnp.dot`` at
default precision inside ``kernels/matmul.py``), so the compute bound uses
the bf16 peak.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_per_s: float  # bf16 matrix peak
    bytes_per_s: float  # HBM bandwidth
    source: str


_V5E = Peak(
    197e12, 819e9, "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, 819 GB/s HBM"
)

# keyed by jax.Device.device_kind
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}") from None


def kernel_flops_bytes(op: str, n: int, dtype_bytes: int = 4) -> tuple[float, float]:
    """FLOPs and the least HBM bytes of one square-block kernel of side n:
    two blocks read, one written."""
    if op == "matmul":
        return 2.0 * n**3, 3.0 * n * n * dtype_bytes
    if op == "matadd":
        return 1.0 * n * n, 3.0 * n * n * dtype_bytes
    raise KeyError(f"no operation count for kernel {op!r}")


def roofline_share(
    op: str, n: int, calls: int, seconds: float, device_kind: str, dtype_bytes: int = 4
) -> tuple[float, str]:
    """-> (percent of the roofline, the bound that applies: ``compute`` or
    ``memory``).  The least time of ``calls`` kernels over the device time
    they took."""
    p = peak(device_kind)
    flops, nbytes = kernel_flops_bytes(op, n, dtype_bytes)
    t_compute, t_memory = flops / p.flops_per_s, nbytes / p.bytes_per_s
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * calls * max(t_compute, t_memory) / seconds, bound
