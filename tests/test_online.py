"""Online incremental re-partition scheduler (core/online.py + core/arena.py).

Plain pytest — must run without hypothesis (the tier-1 floor)."""

import math
import statistics

import pytest

from repro.core.arena import (ArenaStep, SchedulerArena, format_table,
                              make_request_stream)
from repro.core.cost import Link, paper_calibrated_model
from repro.core.graph import Kernel, generate_paper_dag
from repro.core.online import IncrementalGpPolicy, OnlinePartitioner
from repro.core.simulate import (Platform, Processor, WorkerDrop, simulate,
                                 make_cpu_gpu_platform)
from repro.ft.elastic import Heartbeat, HeartbeatMonitor, feed_policy

KV = 1 << 20


def _chain_kernels(part, rid, n, cost_ms=(4.0, 12.0), refine=True):
    prev = None
    for c in range(n):
        name = f"r{rid}.d{c}"
        deps = [(prev, KV)] if prev else []
        part.add_task(Kernel(name, op="decode",
                             costs={"big": cost_ms[0], "small": cost_ms[1]},
                             out_bytes=KV), deps, refine=refine)
        prev = name


def _fresh_partitioner(**kw):
    kw.setdefault("epsilon", 0.05)
    kw.setdefault("seed", 1)
    kw.setdefault("edge_ms", lambda nb: nb / 6.25e9 * 1e3)
    return OnlinePartitioner({"big": 0.6, "small": 0.4}, **kw)


# -- balance across deltas ----------------------------------------------------

def test_balance_within_trigger_after_arrivals():
    part = _fresh_partitioner()
    for rid in range(12):  # many short chains: fine enough granularity
        _chain_kernels(part, rid, 4)
    assert part.imbalance() <= part.imbalance_trigger + 1e-9
    # every task is placed on a live class
    assert set(part.assignment.values()) <= {"big", "small"}
    assert set(part.assignment) == set(part.g.nodes)


def test_balance_preserved_after_retirement():
    part = _fresh_partitioner()
    for rid in range(12):
        _chain_kernels(part, rid, 4)
    for rid in range(5):
        for c in range(4):
            part.retire_task(f"r{rid}.d{c}")
    assert set(part.assignment) == set(part.g.nodes)
    assert part.imbalance() <= part.imbalance_trigger + 1e-9


def test_worker_drop_evacuates_dead_class_and_rebalances():
    part = _fresh_partitioner()
    for rid in range(10):
        _chain_kernels(part, rid, 4)
    # the whole "big" pod leaves: everything must evacuate to "small"
    rec = part.set_targets({"big": 0.0, "small": 1.0}, reason="big died")
    assert "big" not in set(part.assignment.values())
    assert math.isfinite(part.imbalance())
    assert part.imbalance() <= part.imbalance_trigger + 1e-9
    assert rec.kind in ("incremental", "full")


def test_incremental_cheaper_than_full_on_steady_stream():
    """The amortization claim: warm ingest mostly skips repartitioning."""
    part = _fresh_partitioner()
    for rid in range(20):
        _chain_kernels(part, rid, 4)
    fulls_before = part.n_full
    for rid in range(20, 40):  # steady state: one in, one out
        _chain_kernels(part, rid, 4)
        for c in range(4):
            part.retire_task(f"r{rid - 20}.d{c}")
    skipped = sum(1 for r in part.history if r.kind == "none")
    acted = sum(1 for r in part.history if r.kind != "none")
    assert skipped > acted, (skipped, acted)
    # full repartitions stay rare relative to the 160 deltas applied
    assert part.n_full - fulls_before < 20


# -- IncrementalGpPolicy in the simulator ------------------------------------

def test_policy_survives_class_death_in_sim():
    M = paper_calibrated_model()
    g = M.weight_graph(generate_paper_dag("matmul"), {"matmul": 512})
    plat = make_cpu_gpu_platform()
    pol = IncrementalGpPolicy(seed=1)
    r = simulate(g, pol, plat, events=[WorkerDrop(1.0, "gpu0")])
    names = sorted(t for (t, *_ ) in r.trace)
    assert names == sorted(g.nodes)
    for task, proc, start, finish in r.trace:
        assert not (proc == "gpu0" and finish > 1.0 + 1e-9)


# -- arena ranking + determinism ----------------------------------------------

def _paper_stream(n_steps=3):
    M = paper_calibrated_model()
    g = M.weight_graph(generate_paper_dag("matmul"), {"matmul": 1024})
    return [ArenaStep(graph=g, tag=f"s{i}") for i in range(n_steps)]


def test_arena_ranks_gp_at_least_eager_on_fig6_graph():
    arena = SchedulerArena(make_cpu_gpu_platform(),
                           ("eager", "gp", "incremental-gp"))
    rows = arena.run(_paper_stream())
    by = {r.policy: r for r in rows}
    assert by["gp"].total_makespan_ms <= by["eager"].total_makespan_ms + 1e-6
    assert by["incremental-gp"].total_makespan_ms \
        <= by["eager"].total_makespan_ms + 1e-6
    # table includes every policy and renders
    table = format_table(rows)
    for name in ("eager", "gp", "incremental-gp"):
        assert name in table


def test_arena_deterministic_under_fixed_seed():
    def run_once():
        stream = make_request_stream(4, base_requests=6, decode_chunks=4,
                                     seed=7, arrival_spread_ms=5.0)
        plat = Platform([Processor("big0", "big", 0),
                         Processor("small0", "small", 1)],
                        link=Link("dcn", bw=6.25e9, latency_ms=0.05))
        arena = SchedulerArena(plat, ("eager", "gp", "incremental-gp"),
                               policy_kwargs={
                                   "gp": {"seed": 3},
                                   "incremental-gp": {"seed": 3}})
        rows = arena.run(stream)
        return [(r.policy, round(r.total_makespan_ms, 6), r.transfers,
                 r.bytes_moved) for r in rows]

    assert run_once() == run_once()


def test_incremental_policy_assignment_deterministic():
    M = paper_calibrated_model()
    g = M.weight_graph(generate_paper_dag("matmul"), {"matmul": 1024})
    plat = make_cpu_gpu_platform()
    a = IncrementalGpPolicy(seed=5)
    b = IncrementalGpPolicy(seed=5)
    a.prepare(g, plat)
    b.prepare(g, plat)
    assert a.assignment == b.assignment


def test_incremental_policy_carries_assignments_across_stream():
    stream = make_request_stream(3, base_requests=10, decode_chunks=4,
                                 churn=0.2, seed=2)
    plat = Platform([Processor("big0", "big", 0),
                     Processor("small0", "small", 1)],
                    link=Link("dcn", bw=6.25e9, latency_ms=0.05))
    pol = IncrementalGpPolicy(seed=1)
    prev_assignment = None
    for step in stream:
        simulate(step.graph, pol, plat)
        if prev_assignment is not None:
            common = prev_assignment.keys() & pol.assignment.keys()
            assert common, "stream revisions must overlap"
            carried = sum(1 for n in common
                          if prev_assignment[n] == pol.assignment[n])
            # warm ingest keeps the vast majority of persisting placements
            assert carried / len(common) >= 0.9
        prev_assignment = dict(pol.assignment)
    assert pol.stats["prepare_warm"] == len(stream) - 1


# -- measured feedback on a resubmitted static DAG -----------------------------

def _tile_graph():
    from repro.core.graph import tile_cholesky_dag

    return paper_calibrated_model().weight_graph(
        tile_cholesky_dag(6, shift=3.0),
        {op: 512 for op in ("diag_shift", "potrf", "trsm", "syrk", "gemm")})


def test_feedback_ratio_compares_a_class_with_the_kernels_it_ran():
    """Classes that run exactly as the table says keep the static targets,
    whatever mix of ops each was given."""
    g = _tile_graph()
    plat = make_cpu_gpu_platform()
    pol = IncrementalGpPolicy(scale_by_workers=True)
    pol.prepare(g.copy(), plat)
    ran: dict[str, list[float]] = {}
    for n, c in pol.assignment.items():
        if g.nodes[n].op != "source":
            ran.setdefault(c, []).append(g.nodes[n].costs[c])
    assert {g.nodes[n].op for n, c in pol.assignment.items() if c == "cpu"} \
        != {g.nodes[n].op for n, c in pol.assignment.items() if c == "gpu"}
    pol.observe_step_ms({c: statistics.median(v) for c, v in ran.items()})
    live = pol._targets_for(g, plat)
    static = pol.targets_for(g, plat)
    assert live == pytest.approx(static)


@pytest.mark.parametrize("slow", ["cpu", "gpu"])
def test_straggler_on_a_resubmitted_static_dag_is_rebalanced(slow):
    """The executor's heartbeats (median kernel ms per class) of a class
    that turns 3x slower shrink its target, and the resubmitted DAG ends
    balanced under the trigger; a host pause in one kernel moves nothing."""
    g = _tile_graph()
    plat = make_cpu_gpu_platform()
    pol = IncrementalGpPolicy(scale_by_workers=True)
    mon = HeartbeatMonitor(list(plat.classes))
    # both classes alias one device: the same ms per op, whatever the table
    ms = {"diag_shift": 0.6, "potrf": 1.6, "trsm": 1.8, "syrk": 1.0, "gemm": 1.0}
    before = None
    for i in range(16):
        pol.prepare(g.copy(), plat)
        if i == 5:
            before = dict(pol.targets)
            rev = pol.revision
        if 5 <= i < 8:
            assert pol.revision == rev  # steady: no repartition
        factor = 3.0 if i >= 8 else 1.0
        ran: dict[str, list[float]] = {}
        for n, c in pol.assignment.items():
            if g.nodes[n].op != "source":
                ran.setdefault(c, []).append(
                    ms[g.nodes[n].op] * (factor if c == slow else 1.0))
        ran["cpu"][0] += 40.0  # a host pause inside one kernel's wall time
        for c, v in ran.items():
            mon.report(Heartbeat(group=c, step=i,
                                 step_time_ms=statistics.median(v), t_wall=0.0))
        feed_policy(pol, mon)
    p = pol.partitioner
    odds = lambda t: t[slow] / (1.0 - t[slow])  # noqa: E731
    assert odds(pol.targets) < odds(before) / 2.0  # ~3x slower, ~3x less
    assert p.imbalance() <= p.imbalance_trigger + 1e-9
