"""The serving loop's and the executor's own Python bookkeeping per kernel:
the self time of the program's ``serve.plan``, ``serve.account``,
``serve.feedback``, ``exec.select`` and ``exec.account`` spans, summed over
the window's ``StepReport.span_ms`` and divided by the kernels executed.
None where the program records no spans."""

SPANS = (
    "serve.plan",
    "serve.account",
    "serve.feedback",
    "exec.select",
    "exec.account",
)


def read(run):
    reports = [r for r in run.reports if getattr(r, "span_ms", None)]
    kernels = sum(r.n_kernels for r in reports)
    if not kernels:
        return None
    return sum(r.span_ms.get(s, 0.0) for r in reports for s in SPANS) / kernels
