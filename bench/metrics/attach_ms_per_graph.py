"""Input preparation per DAG: the program's ``serve.attach`` span around
its ``attach`` hook (the program's own draws and, here, the harness's
seeded redraw), summed over the window's ``StepReport.span_ms`` and divided
by the DAGs completed.  None where the program records no spans."""

SPANS = ("serve.attach",)


def read(run):
    reports = [r for r in run.reports if getattr(r, "span_ms", None)]
    if not reports or not run.graphs:
        return None
    return sum(r.span_ms.get(s, 0.0) for r in reports for s in SPANS) / run.graphs
