"""Share of kernel executions the program timed, in %: Σ
``StepReport.timed_kernels`` / Σ ``n_kernels`` over the window.  How far
the sampled timing engages (100 % where every kernel is synced).  None
where no report carries the counter."""


def read(run):
    reports = [r for r in run.reports if hasattr(r, "timed_kernels")]
    kernels = sum(r.n_kernels for r in reports)
    if not kernels:
        return None
    return 100.0 * sum(r.timed_kernels for r in reports) / kernels
