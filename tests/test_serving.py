"""Online serving executor: streams on real device groups (core/serving.py),
the incremental executor session (core/executor.py), and the measured-cost
feedback loop into the online policy's targets.

Plain pytest, CPU-only: all device groups alias the single CPU device, so
transfers are no-op-counted but the full dispatch / eviction / re-dispatch
machinery is exercised for real."""

import copy
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.gate_serve import check as gate_check

from repro.core.arena import make_request_stream
from repro.core.cost import MeasuredCostModel
from repro.core.executor import JaxExecutor, attach_request_kernels
from repro.core.graph import TaskGraph
from repro.core.online import IncrementalGpPolicy
from repro.core.reference import interval_error
from repro.core.schedulers import make_policy
from repro.core.serving import ServingExecutor, groups_for_platform, subgraph_of
from repro.core.simulate import WorkerDrop
from repro.ft.elastic import (Heartbeat, HeartbeatMonitor, feed_policy,
                              throughput_targets)
from repro.launch.serve import (heterogeneous_platform, run_arena_executed,
                                write_bench)

DEV = jax.devices()[0]
KV = 1 << 20


def _serving_executor(plat, **kw):
    kw.setdefault("side", 16)
    return ServingExecutor(groups_for_platform(plat), plat, **kw)


def _chain_graph():
    """a (prefill) -> b -> c (decode chain), real request-shaped ops."""
    g = TaskGraph()
    g.add("a", op="prefill", costs={"big": 2.0, "small": 6.0}, out_bytes=KV)
    g.add("b", op="decode", costs={"big": 1.0, "small": 3.0}, out_bytes=KV)
    g.add("c", op="decode", costs={"big": 1.0, "small": 3.0}, out_bytes=KV)
    g.add_edge("a", "b", nbytes=KV)
    g.add_edge("b", "c", nbytes=KV)
    g.validate()
    return g


# -- executor session: timing, host group, eviction ---------------------------

def test_host_group_default_is_deterministic_and_explicit_works():
    ex = JaxExecutor({"zeta": DEV, "alpha": DEV})
    assert ex.resolve_host_group() == "alpha"      # lexicographic, not dict order
    assert ex.resolve_host_group("zeta") == "zeta"
    with pytest.raises(KeyError):
        ex.resolve_host_group("nope")
    g = _chain_graph()
    inputs = attach_request_kernels(g, 8)
    res = ex.run(g, {n: "zeta" for n in g.nodes}, inputs, host_group="zeta")
    assert sum(res.kernels_per_group.values()) == 3
    assert res.n_transfers == 0                    # host block born on zeta
    res2 = ex.run(g, {n: "zeta" for n in g.nodes}, inputs)
    assert res2.n_transfers == 1                   # seeded on alpha -> 1 pull


def test_session_times_kernels_and_evicts_with_recompute():
    g = _chain_graph()
    inputs = attach_request_kernels(g, 8)
    ex = JaxExecutor({"g0": DEV, "g1": DEV})
    s = ex.session(g, {"a": "g0", "b": "g1", "c": "g0"}, inputs,
                   time_kernels=True)
    assert s.step().name == "a"
    assert s.step().name == "b"
    # g1 dies holding the only copy of b's output, which pending c needs
    assert s.evict_group("g1") == ["b"]
    s.reassign({"b": "g0", "c": "g0"})
    s.run_all()
    res = s.result()
    assert s.done()
    assert res.reexecuted == ["b"]
    assert sum(res.kernels_per_group.values()) == 4      # 3 kernels + 1 rerun
    assert set(res.kernel_ms) == {"a", "b", "c"}
    assert all(ms >= 0.0 for ms in res.kernel_ms.values())


def test_session_arrival_gate():
    g = _chain_graph()
    inputs = attach_request_kernels(g, 8)
    ex = JaxExecutor({"g0": DEV})
    s = ex.session(g, {n: "g0" for n in g.nodes}, inputs, gated={"a"})
    assert s.next_ready() is None          # whole chain blocked on the gate
    s.admit(["a"])
    s.run_all()
    assert s.done()


# -- measured-cost plumbing ----------------------------------------------------

def test_measured_cost_model_observe_ewma():
    m = MeasuredCostModel(impls={})
    assert m.observe("decode", 16, "big", 10.0) == pytest.approx(10.0)
    assert m.observe("decode", 16, "big", 20.0) == pytest.approx(13.0)
    assert m.kernel_ms("decode", 16, "big") == pytest.approx(13.0)


def test_throughput_targets_scaling_and_dead():
    t = throughput_targets({"big": 1.0, "small": 3.0})
    assert t["big"] == pytest.approx(0.75)
    t = throughput_targets({"big": 1.0, "small": 3.0},
                           workers={"small": 3})
    assert t["big"] == pytest.approx(0.5)
    t = throughput_targets({"big": 1.0, "small": 3.0}, dead=["small"])
    assert t == {"big": pytest.approx(1.0)}


def test_feedback_shifts_targets_toward_measured_throughput():
    g = _chain_graph()
    plat = heterogeneous_platform()
    pol = IncrementalGpPolicy(scale_by_workers=True)
    static = pol._targets_for(g, plat)
    assert static == pol.targets_for(g, plat)      # no feedback -> identical
    # live measurement says "big" is a straggler (far slower than its table)
    pol.observe_step_ms({"big": 50.0, "small": 0.5})
    live = pol._targets_for(g, plat)
    assert live["big"] < static["big"]
    assert live["small"] > static["small"]
    assert sum(live.values()) == pytest.approx(1.0)


def test_monitor_feeds_policy_view():
    mon = HeartbeatMonitor(["big", "small"])
    mon.report(Heartbeat("big", 0, 4.0, t_wall=0.0))
    mon.report(Heartbeat("small", 0, 9.0, t_wall=0.0))
    pol = IncrementalGpPolicy()
    view = feed_policy(pol, mon)
    assert view == {"big": 4.0, "small": 9.0}
    assert pol.live_step_ms == view


# -- executor-backed stream end-to-end ----------------------------------------

def test_executed_stream_end_to_end_counters():
    stream = make_request_stream(3, base_requests=4, decode_chunks=3,
                                 kv_bytes=KV, seed=0)
    plat = heterogeneous_platform()
    sx = _serving_executor(plat)
    pol = make_policy("incremental-gp", scale_by_workers=True)
    rep = sx.run_stream(stream, pol)
    assert rep.policy == "incremental-gp"
    assert len(rep.steps) == len(stream)
    for step, s in zip(stream, rep.steps):
        assert s.n_kernels == step.graph.num_nodes()
        assert s.makespan_ms > 0.0
        assert s.kernel_ms_by_class            # per-class measurements exist
    d = rep.to_dict()
    assert d["kernels"] == sum(s.graph.num_nodes() for s in stream)
    assert d["transfers"] >= 0 and d["bytes_moved"] >= 0
    row = rep.to_row()
    assert row.steps == len(stream)
    assert row.total_makespan_ms == pytest.approx(
        sum(s.makespan_ms for s in rep.steps))
    # the measurement loop closed: policy saw live per-class step times
    assert set(pol.live_step_ms) >= set(d["mean_kernel_ms"])
    assert all(v > 0 for v in pol.live_step_ms.values())
    # ... and the cost model history filled from observed kernels
    assert any(k[0] in ("prefill", "decode") for k in sx.cost_model._cache)


def test_worker_drop_mid_stream_redispatches_in_flight():
    events_at = {
        0: (WorkerDrop(1e-6, "small0"), WorkerDrop(2e-6, "small1")),
        1: (WorkerDrop(0.0, "small0"), WorkerDrop(0.0, "small1")),
    }
    stream = make_request_stream(2, base_requests=6, decode_chunks=3,
                                 kv_bytes=KV, seed=3, events_at=events_at)
    plat = heterogeneous_platform()
    sx = _serving_executor(plat)
    pol = make_policy("incremental-gp", scale_by_workers=True)
    rep = sx.run_stream(stream, pol)
    s0, s1 = rep.steps
    # the whole small pod died just after the first kernel of step 0
    assert s0.dropped == ["small0", "small1"]
    assert s0.redispatched > 0                 # in-flight kernels moved off it
    assert s0.n_kernels >= stream[0].graph.num_nodes()   # all work completed
    # step 1 starts without the pod at all: everything runs on the big group
    assert set(s1.kernel_ms_by_class) == {"big"}
    assert s1.n_kernels == stream[1].graph.num_nodes()


def test_late_arrivals_are_admitted_and_run():
    stream = make_request_stream(2, base_requests=4, decode_chunks=2,
                                 kv_bytes=KV, seed=1, churn=0.5,
                                 arrival_spread_ms=5.0)
    assert any(s.arrivals for s in stream), "stream must stagger arrivals"
    plat = heterogeneous_platform()
    sx = _serving_executor(plat)
    pol = make_policy("incremental-gp", scale_by_workers=True)
    rep = sx.run_stream(stream, pol)
    assert rep.to_dict()["admitted_late"] > 0
    assert pol.stats["admitted"] > 0
    for step, s in zip(stream, rep.steps):
        assert s.n_kernels == step.graph.num_nodes()


def test_subgraph_of_induces_consistent_prefix():
    g = _chain_graph()
    sub = subgraph_of(g, ["a", "b"])
    assert set(sub.nodes) == {"a", "b"}
    assert sub.num_edges() == 1
    assert sub.edge("a", "b").nbytes == KV
    sub.validate()


# -- executed arena + bench artifact + gate -----------------------------------

ALL_EXECUTED = {"eager", "dmda", "heft", "gp", "incremental-gp"}


def test_run_arena_executed_rows_and_bench_gate(tmp_path):
    rows, arena = run_arena_executed(3, 2, steps=2, kv_mb=1.0, seed=0,
                                     drop_step=None, side=16)
    assert {r.policy for r in rows} == ALL_EXECUTED
    for r in rows:
        assert r.steps == 2
        assert r.total_makespan_ms > 0.0
    out = tmp_path / "BENCH_serve.json"
    doc = write_bench(str(out), meta={"test": True}, sim_rows=[], arena=arena)
    assert out.exists()
    assert set(doc["executed"]) == ALL_EXECUTED
    # the gate passes a run against itself, fails a clear regression
    doc["simulated"] = {"incremental-gp":
                        {"total_makespan_ms": 100.0, "transfers": 5}}
    assert gate_check(doc, doc, 0.20) == []
    worse = copy.deepcopy(doc)
    worse["simulated"]["incremental-gp"]["total_makespan_ms"] = 200.0
    assert gate_check(worse, doc, 0.20)
    incomplete = copy.deepcopy(doc)
    incomplete["executed"]["gp"]["kernels"] -= 1
    assert gate_check(incomplete, doc, 0.20)


# -- executed outputs vs the plain reference ----------------------------------

@pytest.mark.parametrize("mode", [{}, {"fused": True},
                                  {"fused": True, "async_groups": True}],
                         ids=["unfused", "fused", "fused+waves"])
def test_executed_outputs_match_reference(mode):
    """Every interval's exit outputs — through a mid-stream drop that kills
    the big class (its group is evicted) — equal a plain topological
    evaluation of its DAG, handed to the check hook as each interval ends."""
    seen = []

    def check(step, report, outputs):
        assert set(outputs) == set(step.graph.exit_nodes())
        assert set(report.ran_on) == set(step.graph.nodes)
        seen.append(interval_error(step, outputs, 16))

    _, arena = run_arena_executed(5, 3, steps=3, drop_step=1, drop_proc="big0",
                                  drop_t_ms=0.05, seed=0, side=16, check=check,
                                  policies=("incremental-gp", "dmda"), **mode)
    assert len(seen) == 2 * 3 and max(seen) < 1e-6
    for rep in arena.reports.values():
        assert "big0" in rep.steps[1].dropped
        assert "ran_on" not in rep.to_dict()


def test_reference_catches_a_wrong_output():
    def check(step, report, outputs):
        outs = dict(outputs)
        name = sorted(outs)[0]
        outs[name] = outs[name].at[0, 0].add(1e3)
        assert interval_error(step, outs, 16) > 1e-3
        del outs[name]
        with pytest.raises(AssertionError, match="exit blocks differ"):
            interval_error(step, outs, 16)
        checked.append(step.tag)

    checked = []
    run_arena_executed(2, 2, steps=1, side=16, policies=("incremental-gp",),
                       check=check)
    assert len(checked) == 1


def test_executed_stream_keeps_no_outputs():
    """Without a check hook nothing of an interval's outputs outlives it;
    the fused calls consume their donated buffers."""
    _, arena = run_arena_executed(8, 4, steps=3, seed=0, side=16,
                                  policies=("dmda", "incremental-gp"),
                                  fused=True, async_groups=True)
    reps = arena.reports.values()
    assert not any(hasattr(s, "outputs") for r in reps for s in r.steps)
    assert sum(r.total("n_donated") for r in reps) > 0
