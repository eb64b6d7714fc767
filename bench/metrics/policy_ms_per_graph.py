"""Policy time per DAG: ``policy.prepare`` plus admissions and elastic
hooks (``StepReport.offline_ms + decision_ms``), over the window's DAGs."""


def read(run):
    if not run.graphs:
        return None
    return sum(r.offline_ms + r.decision_ms for r in run.reports) / run.graphs
