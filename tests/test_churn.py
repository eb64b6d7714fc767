"""WorkerPullPolicy under worker churn: executed-mode replay with a
mid-stream WorkerDrop for every reactive queue policy, asserting eviction +
re-dispatch conservation — no kernel lost, no untracked double-run.

Plain pytest — must run without hypothesis (the tier-1 floor).
"""

import pytest

from repro.core.arena import make_request_stream
from repro.launch.serve import run_arena_executed

STEPS = 3
DROP_STEP = 1
REQUESTS = 3
CHUNKS = 2
KV_MB = 1.0
SEED = 0


def _stream_kernel_counts() -> list[int]:
    """Non-source kernel count per step of the exact stream
    run_arena_executed builds (same generator, same knobs)."""
    stream = make_request_stream(
        STEPS,
        base_requests=REQUESTS,
        decode_chunks=CHUNKS,
        churn=0.3,
        kv_bytes=int(KV_MB * 2**20),
        seed=SEED,
        arrival_spread_ms=0.5,
    )
    return [
        sum(1 for k in s.graph.nodes.values() if k.op != "source") for s in stream
    ]


@pytest.fixture(scope="module")
def churn_reports():
    rows, arena = run_arena_executed(
        REQUESTS,
        CHUNKS,
        steps=STEPS,
        kv_mb=KV_MB,
        seed=SEED,
        side=16,
        drop_step=DROP_STEP,
        drop_proc="small1",
        # early in the interval: a fast host drains a 3-request interval
        # in under a virtual millisecond, and a later drop would miss it
        drop_t_ms=0.05,
        policies=("eager", "dmda", "heft", "affinity-steal"),
    )
    return rows, arena


@pytest.mark.parametrize(
    "policy", ("eager", "dmda", "heft", "affinity-steal"))
def test_no_kernel_lost_no_double_run(churn_reports, policy):
    """Every kernel of every revision executes exactly once, plus only the
    re-executions the session tracked after the drop's group eviction."""
    _, arena = churn_reports
    rep = arena.reports[policy]
    expected = _stream_kernel_counts()
    assert len(rep.steps) == STEPS
    for step, want in zip(rep.steps, expected):
        assert step.n_kernels == want + step.reexecuted, (
            f"{policy} {step.tag}: ran {step.n_kernels} kernels for "
            f"{want} graph kernels with {step.reexecuted} re-executions"
        )
        assert step.makespan_ms > 0


@pytest.mark.parametrize(
    "policy", ("eager", "dmda", "heft", "affinity-steal"))
def test_drop_is_applied_and_stream_completes(churn_reports, policy):
    """The drop fires at the drop step (and pre-applies afterwards), and the
    shim re-plans: the stream still drains every step."""
    _, arena = churn_reports
    rep = arena.reports[policy]
    assert "small1" in rep.steps[DROP_STEP].dropped
    for step in rep.steps[DROP_STEP:]:
        assert not step.events_missed


def test_all_policies_ran_same_stream(churn_reports):
    rows, arena = churn_reports
    kernels = {
        name: rep.to_dict()["kernels"] - rep.to_dict()["reexecuted"]
        for name, rep in arena.reports.items()
    }
    assert len(set(kernels.values())) == 1, kernels
    assert {r.policy for r in rows} == {
        "eager", "dmda", "heft", "affinity-steal"}
