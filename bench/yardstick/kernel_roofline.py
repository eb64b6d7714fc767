"""A Pallas kernel's roofline share from the device trace."""

from __future__ import annotations

from yardstick.roofline import roofline_share


def read_kernel(run, kernel: str):
    """Percent of the roofline over every event of ``kernel`` in the
    window; None where the cell runs no such kernel or the trace has none."""
    if run.trace is None or kernel not in run.kernels.values():
        return None
    calls, seconds = run.trace.kernel(kernel)
    if not calls or seconds <= 0:
        return None
    share, _ = roofline_share(kernel, run.side, calls, seconds, run.device_kind)
    return share
