"""90th percentile (nearest rank) of per-request completion time: each
request's ms from ``run_step`` entry until its last kernel's output was
ready, over every ``StepReport.request_done_ms`` value of the window.
None where no request retired (DAGs without ``meta["req"]``) or the program
does not record it."""

from yardstick.window import nearest_rank


def read(run):
    done = [
        ms for r in run.reports for ms in getattr(r, "request_done_ms", {}).values()
    ]
    if not done:
        return None
    return nearest_rank(done, 90)
