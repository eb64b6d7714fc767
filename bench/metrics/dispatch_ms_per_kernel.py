"""Executor and session time per kernel: the interval's wall time less the
policy's (``wall_ms - offline_ms - decision_ms``), summed over the window
and divided by the kernels executed."""


def read(run):
    kernels = sum(r.n_kernels for r in run.reports)
    if not kernels:
        return None
    rest = sum(r.wall_ms - r.offline_ms - r.decision_ms for r in run.reports)
    return rest / kernels
