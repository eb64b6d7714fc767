"""Host time enqueueing kernels per kernel: the program's ``exec.launch``
spans (jit dispatch of a kernel or fused chain), self time summed over the
window's ``StepReport.span_ms`` and divided by the kernels executed.  None
where the program records no spans."""

SPANS = ("exec.launch",)


def read(run):
    reports = [r for r in run.reports if getattr(r, "span_ms", None)]
    kernels = sum(r.n_kernels for r in reports)
    if not kernels:
        return None
    return sum(r.span_ms.get(s, 0.0) for r in reports for s in SPANS) / kernels
