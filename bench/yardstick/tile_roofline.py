"""Operations and bytes of the tile Cholesky's kernels, and their share of
the chip's roofline.

Each count is the work its op needs, whatever implements it: a call of
``gemm_nt`` (C - A B^T) 2 n^3 FLOPs and four tiles moved (C, A, B read,
the result written); ``potrf`` n^3 / 3 FLOPs and two tiles; ``trsm`` n^3
FLOPs and three tiles.  The peaks are those of
:func:`yardstick.roofline.peak`, the compute bound at the bf16 peak.

``gemm_nt`` is a Pallas kernel, timed over its ops in the trace.
``potrf`` and ``trsm`` are XLA programs of many ops: their time is every
device op of the module ``jit_<kernel>``, and their calls are the
program's own count of the kernels of those ops.
"""

from __future__ import annotations

from yardstick.roofline import peak

_TILES_MOVED = {"gemm_nt": 4, "potrf": 2, "trsm": 3}


def flops_bytes(kernel: str, n: int, dtype_bytes: int = 4) -> tuple[float, float]:
    flops = {"gemm_nt": 2.0 * n**3, "potrf": n**3 / 3.0, "trsm": 1.0 * n**3}
    if kernel not in flops:
        raise KeyError(f"no operation count for kernel {kernel!r}")
    return flops[kernel], _TILES_MOVED[kernel] * n * n * dtype_bytes


def share(kernel: str, n: int, calls: int, seconds: float, device_kind: str) -> float:
    """Percent of the roofline: the least time of ``calls`` kernels over the
    device time they took."""
    p = peak(device_kind)
    flops, nbytes = flops_bytes(kernel, n)
    return 100.0 * calls * max(flops / p.flops_per_s, nbytes / p.bytes_per_s) / seconds


def read_pallas(run, kernel: str):
    """Share over every Pallas op named ``kernel`` in the window; None where
    the cell runs no such kernel or the trace has none."""
    if run.trace is None or kernel not in run.kernels.values():
        return None
    calls, seconds = run.trace.kernel(kernel)
    if not calls or seconds <= 0:
        return None
    return share(kernel, run.side, calls, seconds, run.device_kind)


def read_module(run, kernel: str):
    """Share over the device ops of the module ``jit_<kernel>``, with the
    calls the program counted (``StepReport.op_calls``) for the ops that
    run as ``kernel``; None where either is missing."""
    if run.trace is None or kernel not in run.kernels.values():
        return None
    prefix = f"jit_{kernel}/"
    seconds = sum(s for name, s in run.trace.op_s.items() if name.startswith(prefix))
    ops = [op for op, k in run.kernels.items() if k == kernel]
    calls = sum(getattr(r, "op_calls", {}).get(op, 0) for r in run.reports for op in ops)
    if not calls or seconds <= 0:
        return None
    return share(kernel, run.side, calls, seconds, run.device_kind)
