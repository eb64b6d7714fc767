"""Host time in the executor's ``device_put`` pulls per DAG: the program's
``exec.pull`` spans (demand and prefetch), summed over the window's
``StepReport.span_ms`` and divided by the DAGs completed.  Only where the
classes sit on more than one chip, as ``moved_MiB_per_graph``: on one chip
every pull stays on the chip.  None where the program records no spans."""

SPANS = ("exec.pull",)


def read(run):
    reports = [r for r in run.reports if getattr(r, "span_ms", None)]
    if run.devices < 2 or not reports or not run.graphs:
        return None
    return sum(r.span_ms.get(s, 0.0) for r in reports for s in SPANS) / run.graphs
