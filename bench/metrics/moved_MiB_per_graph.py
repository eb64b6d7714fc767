"""MiB the executor's pulls moved between device groups, per DAG.  Only
where the classes sit on more than one chip: on one chip every pull stays
on the chip."""


def read(run):
    if run.devices < 2 or not run.graphs:
        return None
    return sum(r.bytes_transferred for r in run.reports) / 2**20 / run.graphs
