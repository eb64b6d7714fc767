"""The tile Cholesky cell on the CPU at a small side: correct as the
program runs it, not correct under the control or with the updates left
out, its DAG the program's, and its readers right on hand-made reports and
traces and silent where they find nothing."""

import pytest
from helpers import run_cell

from repro.core.graph import tile_cholesky_dag
from repro.core.serving import StepReport
from repro.kernels import ops
from yardstick import reference
from yardstick.registry import Registry
from yardstick.runner import RunView
from yardstick.tile_roofline import flops_bytes
from yardstick.trace import Summary

CELL = "tile-cholesky.potrf"
READERS = ("gemm_nt_roofline", "potrf_roofline", "trsm_roofline", "panel_wait_ms_per_graph")


def test_cell_is_correct_at_a_small_side():
    r = run_cell(CELL, seconds=0.5)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["checks"]["max_rel_err"]["value"] <= 1e-5
    assert set(r["metrics"]) == {"graphs_per_s", "graph_p90_ms", "setup_s"}


def test_fp8_control_fails():
    r = run_cell(CELL, side=128, seconds=0.3, arithmetic=reference.CONTROLS["fp8_dot"])
    c = r["checks"]["max_rel_err"]
    assert not r["correct"] and r["failed"] >= 1
    assert c["value"] > c["limit"]


def test_updates_left_out_are_caught(monkeypatch):
    """A gemm_nt that returns C unchanged: every gemm and syrk is skipped."""
    monkeypatch.setattr(ops, "gemm_nt", lambda c, a, b: c)
    r = run_cell(CELL, seconds=0.3)
    assert not r["correct"] and r["failed"] >= 1
    assert r["checks"]["max_rel_err"]["value"] > 1e-2


@pytest.mark.parametrize("task", ["gemm_7_3_0", "syrk_9_2", "trsm_8_4"])
def test_one_task_left_out_is_caught(monkeypatch, task):
    """One update, or one solve, that returns its first input unchanged, far
    from the exit's row: its error reaches the exit tile damped, and still
    above the limit (the least such reading, a gemm's, is 8.5e-5 here and
    2.7e-5 at the cell's side, against the limit's 2e-5)."""
    from repro.core import executor

    attach = executor.attach_tile_kernels

    def faulty(g, n, dtype="float32"):
        inputs = attach(g, n, dtype)
        g.nodes[task].fn = lambda first, *_: first
        return inputs

    monkeypatch.setattr(executor, "attach_tile_kernels", faulty)
    r = run_cell(CELL, seconds=0.3)
    c = r["checks"]["max_rel_err"]
    assert not r["correct"] and r["failed"] >= 1
    assert c["limit"] < c["value"] < 1e-2


def test_family_builds_the_programs_dag():
    reg = Registry()
    cfg = reg.config("tile-cholesky-2048")
    traffic = reg.traffic("potrf")
    fam = reg.family(traffic["dag"]).Family(cfg, traffic, None, 0)
    _, step = fam[0]
    ours = step.graph
    theirs = tile_cholesky_dag(traffic["tiles"], shift=traffic["shift"])
    assert list(ours.nodes) == list(theirs.nodes)
    for n, k in theirs.nodes.items():
        assert (ours.nodes[n].op, ours.nodes[n].meta) == (k.op, k.meta)
        assert ours.predecessors(n) == theirs.predecessors(n)
    assert [(e.src, e.dst, e.blocks) for e in ours.edges] == [
        (e.src, e.dst, e.blocks) for e in theirs.edges]
    assert len(fam.spec.ops) == 376 and fam.spec.exits == ["potrf_11"]
    assert len(fam.spec.inputs) == 78
    assert fam.scale == pytest.approx((12 * 2048) ** -0.5)


def _report(op_calls, op_wait_ms):
    return StepReport(
        tag="t", n_kernels=sum(op_calls.values()), makespan_ms=0.0, wall_ms=1.0,
        n_transfers=0, bytes_transferred=0, offline_ms=0.0, decision_ms=0.0,
        admitted_late=0, redispatched=0, reexecuted=0, kernel_ms_by_class={},
        dropped=[], added=[], events_missed=[], op_calls=dict(op_calls),
        op_wait_ms=dict(op_wait_ms),
    )


def _view(reports, trace, kernels=None):
    registry = Registry()
    fam = registry.family("tile_cholesky")
    return RunView(reports=reports, graphs=len(reports), compiles_in_window=0,
                   trace=trace, side=2048, device_kind="TPU v5 lite", devices=1,
                   kernels=fam.KERNELS if kernels is None else kernels)


def _summary(op_s, pallas=()):
    return Summary(window_s=1.0, busy_s=0.5, chips=1, op_s=dict(op_s),
                   op_calls={k: 10 for k in op_s}, pallas=set(pallas),
                   idle_by_host={}, clock_shift_ns={})


def _read(name, view):
    return Registry().reader(name).read(view)


CALLS = {"diag_shift": 3, "potrf": 3, "trsm": 3, "syrk": 3, "gemm": 1}
WAITS = {"potrf": 6.0, "trsm": 3.0, "gemm": 50.0}


def test_readers_on_hand_made_reports_and_trace():
    # the least time of each kernel at 2048 on a v5e, at 50 % of its roofline
    least = {k: max(f / 197e12, b / 819e9) for k, (f, b) in
             ((k, flops_bytes(k, 2048)) for k in ("gemm_nt", "potrf", "trsm"))}
    op_s = {
        "jit_gemm_nt/gemm_nt.1": 10 * least["gemm_nt"] * 2,
        "jit_potrf/fusion.3": 6 * least["potrf"],
        "jit_potrf/custom-call.4": 6 * least["potrf"],
        "jit_trsm/fusion.1": 6 * least["trsm"] * 2,
        "jit_matmul/matmul.1": 1.0,
    }
    view = _view([_report(CALLS, WAITS), _report(CALLS, WAITS)],
                 _summary(op_s, pallas=["jit_gemm_nt/gemm_nt.1", "jit_matmul/matmul.1"]))
    assert _read("gemm_nt_roofline", view) == pytest.approx(50.0)
    assert _read("potrf_roofline", view) == pytest.approx(50.0)
    assert _read("trsm_roofline", view) == pytest.approx(50.0)
    assert _read("panel_wait_ms_per_graph", view) == pytest.approx(9.0)


def test_gemm_nt_is_compute_bound_and_the_panel_memory_bound_at_2048():
    f, b = flops_bytes("gemm_nt", 2048)
    assert f / 197e12 > b / 819e9
    for k in ("potrf", "trsm"):
        f, b = flops_bytes(k, 2048)
        assert f / 197e12 < b / 819e9
    with pytest.raises(KeyError):
        flops_bytes("getrf", 8)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read(name):
    # no trace, an empty trace, no reports, and another cell's kernels
    assert _read(name, _view([], None)) is None
    assert _read(name, _view([], _summary({}))) is None
    assert _read(name, _view([_report({}, {})], _summary({}))) is None
    other = _view([_report({"matmul": 38}, {"matmul": 5.0})],
                  _summary({"jit_matmul/matmul.1": 0.1}, pallas=["jit_matmul/matmul.1"]),
                  kernels={"matmul": "matmul"})
    assert _read(name, other) is None


class _Uncounted:
    """A report of a program that counts nothing per op."""

    n_kernels = 376


def test_a_program_without_op_counters_reads_nothing():
    trace = _summary({"jit_potrf/fusion.1": 0.01, "jit_trsm/fusion.1": 0.01})
    for name in ("potrf_roofline", "trsm_roofline", "panel_wait_ms_per_graph"):
        assert _read(name, _view([_Uncounted(), _Uncounted()], trace)) is None


def test_traced_cell_reports_the_panel_wait():
    r = run_cell(CELL, seconds=0.3, trace=True)
    assert r["correct"], r["checks"]
    assert r["metrics"]["panel_wait_ms_per_graph"]["value"] > 0
    # the CPU trace has no chip: the device readers find nothing to read
    assert not {"gemm_nt_roofline", "potrf_roofline", "trsm_roofline"} & set(r["metrics"])
