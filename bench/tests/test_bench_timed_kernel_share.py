"""The reader of the program's timed-kernel counter: a share on hand-made
``StepReport``s, nothing where no report carries the counter, and a share
in traced runs of the cells on the CPU."""

import dataclasses
import shutil

import pytest
from helpers import run_cell

from repro.core.serving import StepReport
from yardstick.registry import ROOT, Registry
from yardstick.runner import RunView

NAME = "timed_kernel_share"


def _report(n_kernels, timed):
    return StepReport(
        tag="t", n_kernels=n_kernels, makespan_ms=0.0, wall_ms=1.0,
        n_transfers=0, bytes_transferred=0, offline_ms=0.0, decision_ms=0.0,
        admitted_late=0, redispatched=0, reexecuted=0, kernel_ms_by_class={},
        dropped=[], added=[], events_missed=[], timed_kernels=timed,
    )


@dataclasses.dataclass
class _Uncounted:
    """A report of a program that counts no timed kernels."""

    n_kernels: int
    wall_ms: float = 10.0


def _view(reports):
    return RunView(reports=reports, graphs=len(reports), compiles_in_window=0,
                   trace=None, side=64, device_kind="cpu", devices=1, kernels={})


def _read(view):
    return Registry().reader(NAME).read(view)


def test_share_on_hand_made_reports():
    assert _read(_view([_report(40, 10), _report(10, 10)])) == pytest.approx(40.0)
    assert _read(_view([_report(3, 3)])) == pytest.approx(100.0)


@pytest.mark.parametrize("reports", [[], [_Uncounted(4), _Uncounted(4)]],
                         ids=["no_reports", "no_counter"])
def test_nothing_to_read(reports):
    assert _read(_view(reports)) is None


# the paper task's 38 kernels fall in groups of about 10, several timed in
# full; the larger DAGs time about 5 kernels a group
@pytest.mark.parametrize("workload, most", [
    ("serve-flat.churn", 12.0),
    ("paper-task.mm", 100.0),
    ("tile-cholesky.potrf", 12.0),
])
def test_traced_cell_reports_the_share(tmp_path, workload, most):
    # a root of its own: the trace directory is the workload's, under the
    # root, and other tests trace the same cells
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    assert NAME in {m["name"] for m in Registry(tmp_path).metrics(workload, "per_layer")}
    r = run_cell(workload, root=tmp_path, seconds=0.4, trace=True)
    assert r["correct"], r["checks"]
    assert 0 < r["metrics"][NAME]["value"] < most
