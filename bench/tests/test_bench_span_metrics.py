"""The readers of the program's spans and per-request completion times:
each gives a number on hand-made ``StepReport``s, and nothing where its
cell has no such data, including a program that records no spans."""

import dataclasses

import pytest
from helpers import run_cell

from repro.core.serving import StepReport
from yardstick.registry import Registry
from yardstick.runner import RunView

SPAN_METRICS = (
    "launch_ms_per_kernel",
    "sync_wait_ms_per_kernel",
    "host_ms_per_kernel",
    "attach_ms_per_graph",
    "pull_ms_per_graph",
    "request_p90_ms",
)


def _report(n_kernels, span_ms, done=None):
    return StepReport(
        tag="t", n_kernels=n_kernels, makespan_ms=0.0, wall_ms=sum(span_ms.values()),
        n_transfers=0, bytes_transferred=0, offline_ms=0.0, decision_ms=0.0,
        admitted_late=0, redispatched=0, reexecuted=0, kernel_ms_by_class={},
        dropped=[], added=[], events_missed=[], span_ms=dict(span_ms),
        span_calls={n: 1 for n in span_ms}, request_done_ms=dict(done or {}),
    )


@dataclasses.dataclass
class _Unspanned:
    """A report of a program that records no spans."""

    n_kernels: int
    wall_ms: float = 10.0
    offline_ms: float = 0.0
    decision_ms: float = 0.0
    bytes_transferred: int = 0


def _view(reports, devices=1):
    return RunView(reports=reports, graphs=len(reports), compiles_in_window=0,
                   trace=None, side=64, device_kind="cpu", devices=devices,
                   kernels={})


SPANS = {
    "serve.attach": 6.0, "serve.plan": 2.0, "serve.prepare": 5.0,
    "serve.account": 1.0, "serve.feedback": 0.5, "exec.select": 0.25,
    "exec.pull": 3.0, "exec.wait": 8.0, "exec.launch": 4.0, "exec.account": 0.25,
}


def _read(name, view):
    return Registry().reader(name).read(view)


def test_readers_on_hand_made_reports():
    reports = [
        _report(4, SPANS, {"r0": 10.0, "r1": 30.0}),
        _report(4, SPANS, {"r2": 20.0, "r3": 40.0, "r4": 50.0}),
    ]
    view = _view(reports, devices=4)
    assert _read("launch_ms_per_kernel", view) == pytest.approx(1.0)
    assert _read("sync_wait_ms_per_kernel", view) == pytest.approx(2.0)
    # plan + account + feedback + select + exec.account = 4 ms per 4 kernels
    assert _read("host_ms_per_kernel", view) == pytest.approx(1.0)
    assert _read("attach_ms_per_graph", view) == pytest.approx(6.0)
    assert _read("pull_ms_per_graph", view) == pytest.approx(3.0)
    # nearest rank: the 5th of 5 sorted values
    assert _read("request_p90_ms", view) == pytest.approx(50.0)


def test_pulls_only_across_chips():
    assert _read("pull_ms_per_graph", _view([_report(4, SPANS)], devices=1)) is None


def test_no_requests_no_request_tail():
    assert _read("request_p90_ms", _view([_report(4, SPANS)])) is None


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_nothing_to_read(name):
    assert _read(name, _view([])) is None
    # a program without spans, as before they were added
    assert _read(name, _view([_Unspanned(4), _Unspanned(4)], devices=4)) is None


@pytest.mark.parametrize("workload", ["serve-flat.churn", "paper-task.mm"])
def test_traced_cell_reports_its_span_metrics(workload):
    reg = Registry()
    listed = {m["name"] for m in reg.metrics(workload, "per_layer")} & set(SPAN_METRICS)
    r = run_cell(workload, seconds=0.4, trace=True)
    assert r["correct"], r["checks"]
    got = {n for n in r["metrics"] if n in SPAN_METRICS}
    # one chip: no pulls between chips
    assert got == listed - {"pull_ms_per_graph"}
    for name in got:
        assert r["metrics"][name]["value"] > 0
