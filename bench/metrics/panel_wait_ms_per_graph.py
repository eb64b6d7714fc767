"""Host time blocked on the panel kernels per DAG: the program's
``exec.wait`` self ms of the steps that ran ``potrf`` and ``trsm`` kernels
(``StepReport.op_wait_ms``), the tile Cholesky's critical path, summed over
the window's DAGs and divided by their number.  None where the program
counts no waits per op, or its DAGs run no panel kernel."""

PANEL = ("potrf", "trsm")


def read(run):
    reports = [r for r in run.reports if getattr(r, "op_wait_ms", None)]
    if not any(op in r.op_wait_ms for r in reports for op in PANEL):
        return None
    return sum(r.op_wait_ms.get(op, 0.0) for r in reports for op in PANEL) / len(reports)
