"""Sampled kernel timing (core/executor.py ``ExecSession.step`` with
``time_kernels=True``, fed back by ``ServingExecutor.run_step``): which
kernels are timed, what a timed kernel waits on, what the feedback loop
reads from the sample, and when a request counts as done.  CPU-only."""

import time
import types

import jax
import jax.numpy as jnp
import pytest

from repro.core import executor as executor_mod
from repro.core import serving as serving_mod
from repro.core.arena import ArenaStep, make_request_stream
from repro.core.cost import MeasuredCostModel
from repro.core.executor import ExecSession, JaxExecutor, attach_request_kernels
from repro.core.graph import TaskGraph
from repro.core.schedulers import make_policy
from repro.core.serving import ServingExecutor, groups_for_platform
from repro.launch.serve import heterogeneous_platform

DEV = jax.devices()[0]
KV = 1 << 20


@jax.jit
def _bump(x):
    return jnp.tanh(x) * 1.5 + 0.25


def _chain(groups, ops=None):
    """k0 -> k1 -> ... on the given groups, one real op per kernel."""
    g = TaskGraph()
    names = [f"k{i}" for i in range(len(groups))]
    for n, op in zip(names, ops or ["bump"] * len(names)):
        g.add(n, op=op, costs={}, out_bytes=KV)
    for a, b in zip(names, names[1:]):
        g.add_edge(a, b, nbytes=KV)
    for k in g.nodes.values():
        k.fn = _bump
    inputs = {"k0/in": jnp.linspace(-1.0, 1.0, 64).reshape(8, 8)}
    return g, dict(zip(names, groups)), inputs


def _expected_timed(order, asg):
    """The rule: per group, its 1st kernel and every max(1, n_g // 5)-th."""
    n_g: dict = {}
    for n in order:
        n_g[asg[n]] = n_g.get(asg[n], 0) + 1
    seen: dict = {}
    out = set()
    for n in order:
        grp = asg[n]
        if seen.get(grp, 0) % max(1, n_g[grp] // 5) == 0:
            out.add(n)
        seen[grp] = seen.get(grp, 0) + 1
    return out


@pytest.mark.parametrize(
    "groups",
    [
        ["g0", "g1", "g0"] * 20,  # 60 kernels: 40 on g0 (stride 8), 20 on g1 (4)
        ["g0"] * 60,  # stride 12
        ["g0"] * 10,  # stride 2
        ["g0"] * 9,  # timed in full
        ["g0", "g1", "g0"],  # timed in full
    ],
    ids=["60-two-groups", "60-one-group", "10", "9", "3"],
)
def test_sampling_rule_and_outputs_bit_identical(groups):
    g, asg, inputs = _chain(groups)
    ex = JaxExecutor({"g0": DEV, "g1": DEV})
    s = ex.session(g, asg, inputs, time_kernels=True)
    runs = []
    while (run := s.step()) is not None:
        runs.append(run)
    res = s.result()
    expected = _expected_timed([r.name for r in runs], asg)
    assert {r.name for r in runs if r.timed} == expected
    assert set(res.kernel_ms) == expected
    for grp in set(groups):
        n = groups.count(grp)
        timed = sum(r.timed for r in runs if r.group == grp)
        assert timed == (n if n <= 9 else -(-n // (n // 5)))
    if len(groups) <= 9:
        assert all(r.timed for r in runs)
    # an untimed kernel's ms is the last timed ms of its (op, group)
    last: dict = {}
    for r in runs:
        if r.timed:
            last[r.group] = r.ms
        else:
            assert r.ms == last[r.group]
    plain = ex.session(g, asg, inputs)
    plain.run_all()
    ref = plain.result()
    assert ref.kernel_ms == {}
    for name, out in ref.outputs.items():
        assert (res.outputs[name] == out).all()


@pytest.mark.parametrize("seeded", [None, 123.0])
def test_untimed_estimate_falls_back_to_the_seeded_history(seeded):
    # stride 2 on one group: k0 (op a) timed, k1 (op b) untimed before any
    # b was timed, k2 (b) timed, k3 (b) untimed
    g, asg, inputs = _chain(["g0"] * 10, ops=["a"] + ["b"] * 9)
    s = JaxExecutor({"g0": DEV}).session(g, asg, inputs, time_kernels=True)
    if seeded is not None:
        s.op_ms[("b", "g0")] = seeded
    runs = [s.step() for _ in range(4)]
    assert [r.timed for r in runs] == [True, False, True, False]
    assert runs[1].ms == (seeded or 0.0)
    assert runs[3].ms == runs[2].ms  # the session's own timing wins
    assert s.op_ms[("b", "g0")] == runs[2].ms


class _Probe:
    """A kernel output that logs when the host waits on it."""

    def __init__(self, name, log):
        self.name, self.log = name, log

    def block_until_ready(self):
        self.log.append(("wait", self.name))
        return self


def test_timed_kernel_drains_its_device_first():
    # 20 independent kernels alternating over two groups on ONE device: a
    # timed kernel's inputs are its own, so any wait on another kernel's
    # output is the drain of the device's queue
    log: list = []
    g = TaskGraph()
    names = [f"k{i}" for i in range(20)]
    for n in names:
        g.add(n, op="probe", costs={}, out_bytes=KV)
        g.nodes[n].fn = lambda x, n=n: (log.append(("launch", n)), _Probe(n, log))[1]
    inputs = {f"{n}/in": jnp.ones((2, 2)) for n in names}
    asg = {n: f"g{i % 2}" for i, n in enumerate(names)}
    ex = JaxExecutor({"g0": DEV, "g1": DEV})
    s = ex.session(g, asg, inputs, time_kernels=True)
    runs = []
    while (run := s.step()) is not None:
        runs.append(run)
    assert sum(r.timed for r in runs) == 10  # 5 a group, stride 2
    for prev, run in zip(runs, runs[1:]):
        before = log[log.index(("launch", run.name)) - 1]
        if run.timed:
            # the device's last launched output, whichever group it was
            assert before == ("wait", prev.name)
        elif not prev.timed:
            assert before == ("launch", prev.name)  # no sync in between
    # time_kernels=False never waits inside step()
    log.clear()
    s = ex.session(g, asg, inputs)
    while s.step() is not None:
        pass
    assert not [e for e in log if e[0] == "wait"]


# -- what run_step feeds back -------------------------------------------------


class _ByOp:
    """A policy that puts each op on a class of its own."""

    def __init__(self, cls_of):
        self.cls_of = cls_of
        self.assignment = {}

    def prepare(self, g, platform):
        self.assignment = {
            n: self.cls_of[k.op] for n, k in g.nodes.items() if k.op in self.cls_of
        }
        return 0.0


def _sleepy_step(sleep_ms, pause=None):
    """Two independent 30-kernel chains, ``fast`` and ``slow``; each kernel
    sleeps on the host for its op's ``sleep_ms``, ``pause`` = (kernel, ms)
    adds a one-off pause to one kernel."""
    g = TaskGraph()
    for op in ("fast", "slow"):
        names = [f"{op}{i}" for i in range(30)]
        for n in names:
            g.add(n, op=op, costs={"big": 1.0, "small": 1.0}, out_bytes=KV)
        for a, b in zip(names, names[1:]):
            g.add_edge(a, b, nbytes=KV)

    def attach(graph, side):
        for n, k in graph.nodes.items():
            s = sleep_ms[k.op] + (pause[1] if pause and n == pause[0] else 0.0)
            k.fn = lambda *xs, s=s: (time.sleep(s / 1e3), xs[0])[1]
        return {"fast0/in": jnp.ones((4, 4)), "slow0/in": jnp.ones((4, 4))}

    return ArenaStep(graph=g, tag="sleepy"), attach


def _run_sleepy(sleep_ms, pause=None):
    step, attach = _sleepy_step(sleep_ms, pause)
    plat = heterogeneous_platform()
    sx = ServingExecutor(groups_for_platform(plat), plat, side=4, attach=attach)
    rep = sx.run_step(step, _ByOp({"fast": "big", "slow": "small"}))
    return sx, rep


def test_heartbeat_follows_a_slower_class_from_the_sample():
    sx, rep = _run_sleepy({"fast": 4.0, "slow": 12.0})
    assert rep.n_kernels == 60
    assert rep.timed_kernels == 10  # 30 a class, stride 6
    hb = {c: sx.monitor.last[c].step_time_ms for c in ("big", "small")}
    assert hb["small"] >= 2.5 * hb["big"]


def test_one_paused_timed_kernel_leaves_the_median():
    # fast6 is the fast class's 7th kernel: timed (stride 6)
    sx, rep = _run_sleepy({"fast": 4.0, "slow": 4.0}, pause=("fast6", 40.0))
    hb = sx.monitor.last["big"].step_time_ms
    # the mean of the five samples reads the pause, the median does not
    assert rep.kernel_ms_by_class["big"] >= 4.0 + 40.0 / 5
    assert hb < 10.0


def test_final_wait_on_the_exit_goes_to_its_op():
    # a 12-kernel chain on one class (stride 2): its exit, the 12th kernel,
    # is untimed, so its step waits on nothing and only run_step's final
    # wait on the exit is host time blocked on its op
    g, _, inputs = _chain(["big"] * 12, ops=["bump"] * 11 + ["tail"])
    plat = heterogeneous_platform()
    sx = ServingExecutor(groups_for_platform(plat), plat, side=8,
                         attach=lambda graph, side: inputs)
    rep = sx.run_step(ArenaStep(graph=g, tag="tail"), _ByOp({"bump": "big", "tail": "big"}))
    assert rep.timed_kernels == 6
    assert rep.op_calls == {"bump": 11, "tail": 1}
    assert rep.op_wait_ms["tail"] > 0
    assert sum(rep.op_wait_ms.values()) <= rep.span_ms["exec.wait"] + 1e-9


def _churn_stream():
    return make_request_stream(3, base_requests=8, decode_chunks=6, kv_bytes=KV,
                               seed=0, churn=0.5, arrival_spread_ms=0.5)


_ADMIT = ExecSession.admit
TICK = 2.0**-14  # s; every sum of ticks, and a tick in ms, is exact


def _admissions(monkeypatch, stream):
    """Each admission as (kernels executed before it, names), with a clock
    that advances one tick a reading: every timed kernel reads one tick, and
    the cost model starts from that, so the virtual timeline is exact."""
    clock = types.SimpleNamespace(t=0.0)

    def perf_counter():
        clock.t += TICK
        return clock.t

    monkeypatch.setattr(executor_mod, "time", types.SimpleNamespace(
        perf_counter=perf_counter))
    admitted: list = []

    def spy(self, names, at=None):
        names = list(names)
        admitted.append((len(self._done), names))
        return _ADMIT(self, names, at)

    monkeypatch.setattr(ExecSession, "admit", spy)
    cm = MeasuredCostModel(impls={})
    for op in ("prefill", "decode"):
        for cls in ("big", "small"):
            cm.observe(op, 16, cls, TICK * 1e3)
    plat = heterogeneous_platform()
    sx = ServingExecutor(groups_for_platform(plat), plat, side=16, cost_model=cm)
    pol = make_policy("incremental-gp", scale_by_workers=True)
    # run_step by run_step: run_stream would reset the seeded cost model
    return admitted, [sx.run_step(step, pol, i) for i, step in enumerate(stream)]


def test_late_arrivals_admitted_as_with_every_kernel_timed(monkeypatch):
    stream = _churn_stream()
    sampled, reps = _admissions(monkeypatch, stream)
    assert all(s.timed_kernels < s.n_kernels for s in reps)
    monkeypatch.setattr(executor_mod, "TIMED_PER_GROUP", 10**9)  # stride 1
    every, reps_all = _admissions(monkeypatch, stream)
    assert all(s.timed_kernels == s.n_kernels for s in reps_all)
    assert sum(s.admitted_late for s in reps) > 0
    # the same requests, in the same order, at the same points of the stream
    assert sampled == every
    assert [s.makespan_ms for s in reps] == [s.makespan_ms for s in reps_all]


# -- when a request is done ---------------------------------------------------


def test_request_done_is_a_ready_time_and_exits_are_ready(monkeypatch):
    clock: list = []

    def perf_counter():
        t = time.perf_counter()
        clock.append(t)
        return t

    # run_step's first clock reading is its entry time
    monkeypatch.setattr(
        serving_mod, "time", types.SimpleNamespace(perf_counter=perf_counter,
                                                   time=time.time)
    )
    launched: dict = {}
    outs: dict = {}

    def attach(g, side):
        inputs = attach_request_kernels(g, side)
        for n, k in g.nodes.items():
            if k.fn is not None:
                def fn(*xs, f=k.fn, n=n):
                    out = f(*xs)
                    launched[n] = time.perf_counter()
                    outs[n] = out
                    return out
                k.fn = fn
        return inputs

    plat = heterogeneous_platform()
    sx = ServingExecutor(groups_for_platform(plat), plat, side=16, attach=attach)
    pol = make_policy("incremental-gp", scale_by_workers=True)
    for i, step in enumerate(_churn_stream()):
        clock.clear()
        launched.clear()
        outs.clear()
        s = sx.run_step(step, pol, i)
        wall0 = clock[0]
        assert s.timed_kernels < s.n_kernels  # the sample engaged
        reqs: dict = {}
        for n, k in step.graph.nodes.items():
            reqs.setdefault(k.meta["req"], []).append(n)
        # stamped for every retired request, with no check hook set
        assert set(s.request_done_ms) == set(reqs)
        for r, names in reqs.items():
            last = max(launched[n] for n in names)
            assert s.request_done_ms[r] >= (last - wall0) * 1e3
            assert s.request_done_ms[r] <= s.wall_ms
        # run_step returns only once its exits are ready
        for n in step.graph.exit_nodes():
            assert outs[n].is_ready()
