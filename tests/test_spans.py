"""Host spans of the executed serving path (core/spans.py): self time and
call counts per name, and what ``ServingExecutor.run_step`` records with
them on every interval.  CPU-only."""

import jax
import pytest

from repro.core import spans as spans_mod
from repro.core.arena import make_request_stream
from repro.core.executor import SuperStepCache
from repro.core.schedulers import as_executed, make_policy
from repro.core.serving import (ServeReport, ServingExecutor,
                                groups_for_platform, merge_serve_reports)
from repro.core.spans import NAMES, Spans
from repro.launch.serve import heterogeneous_platform

KV = 1 << 20


class _Clock:
    """A perf_counter that moves only when told to."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(spans_mod, "perf_counter", c)
    return c


# -- the helper ---------------------------------------------------------------

def test_self_time_excludes_child_spans(clock):
    sp = Spans()
    with sp("outer"):
        clock.t += 1.0
        with sp("inner"):
            clock.t += 2.0
            with sp("leaf"):
                clock.t += 4.0
        clock.t += 0.5
        with sp("inner"):
            clock.t += 3.0
    assert sp.ms == pytest.approx({"outer": 1500.0, "inner": 5000.0,
                                   "leaf": 4000.0})
    # self times partition the outermost span's wall time
    assert sum(sp.ms.values()) == pytest.approx(10_500.0)


def test_counts_add_up_and_metadata_spans_share_totals(clock):
    sp = Spans()
    for i in range(5):
        with sp("exec.account"):
            with sp("exec.launch", kernel=f"k{i}", req="r0"):
                clock.t += 1e-3
            with sp("exec.wait"):
                clock.t += 2e-3
    assert sp.calls == {"exec.account": 5, "exec.launch": 5, "exec.wait": 5}
    assert sp.ms["exec.launch"] == pytest.approx(5.0)
    assert sp.ms["exec.wait"] == pytest.approx(10.0)
    assert sp.ms["exec.account"] == pytest.approx(0.0, abs=1e-9)


def test_span_closes_on_exception(clock):
    sp = Spans()
    with pytest.raises(ValueError):
        with sp("outer"):
            with sp("inner"):
                clock.t += 1.0
                raise ValueError("boom")
    with sp("after"):
        clock.t += 1.0
    assert sp.calls == {"outer": 1, "inner": 1, "after": 1}
    assert sp.ms["outer"] == pytest.approx(0.0)
    assert sp.ms["after"] == pytest.approx(1000.0)


def test_the_documented_names():
    assert len(NAMES) == len(set(NAMES)) <= 12
    assert all(n.split(".")[0] in ("serve", "exec") for n in NAMES)
    for n in NAMES:
        assert f"``{n}``" in spans_mod.__doc__


def test_spans_appear_in_a_profiler_trace(tmp_path):
    sp = Spans()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with sp("serve.account"):
            with sp("exec.launch", kernel="r0.prefill", req="r0"):
                pass
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {e.name for p in ProfileData.from_file(str(path)).planes
             for line in p.lines for e in line.events}
    assert {"serve.account", "exec.launch"} <= names
    assert sp.calls == {"serve.account": 1, "exec.launch": 1}


# -- what run_step records ----------------------------------------------------

def _run(n_steps=3, **kw):
    stream = make_request_stream(n_steps, base_requests=4, decode_chunks=3,
                                 kv_bytes=KV, seed=0, churn=0.5,
                                 arrival_spread_ms=2.0)
    plat = heterogeneous_platform()
    sx = ServingExecutor(groups_for_platform(plat), plat, side=16, **kw)
    pol = make_policy("incremental-gp", scale_by_workers=True)
    return stream, sx.run_stream(stream, pol)


@pytest.mark.parametrize("mode", [{}, {"fused": True},
                                  {"fused": True, "async_groups": True}],
                         ids=["unfused", "fused", "fused+waves"])
def test_run_step_spans_cover_the_interval(mode):
    stream, rep = _run(**mode)
    for s in rep.steps:
        assert set(s.span_ms) <= set(NAMES)
        assert set(s.span_calls) == set(s.span_ms)
        total = sum(s.span_ms.values())
        assert total <= s.wall_ms
        assert total >= 0.9 * s.wall_ms
        for name in ("serve.attach", "serve.plan", "serve.prepare",
                     "serve.account", "serve.feedback"):
            assert s.span_calls[name] == 1
        assert s.span_calls["exec.launch"] >= 1


def test_unfused_run_step_launches_once_per_kernel():
    stream, rep = _run()
    for step, s in zip(stream, rep.steps):
        assert s.span_calls["exec.launch"] == s.n_kernels
        assert s.span_calls["exec.select"] >= s.n_kernels
        assert "exec.compile" not in s.span_calls
    # late arrivals were admitted
    assert sum(s.span_calls.get("serve.admit", 0) for s in rep.steps) > 0


def test_fused_run_step_compiles_only_on_a_cache_miss():
    cache = SuperStepCache()
    stream = make_request_stream(1, base_requests=4, decode_chunks=3,
                                 kv_bytes=KV, seed=0)
    plat = heterogeneous_platform()
    sx = ServingExecutor(groups_for_platform(plat), plat, side=16, fused=True,
                         superstep_cache=cache)
    pol = as_executed(make_policy("dmda"))
    reps = [sx.run_step(stream[0], pol, i) for i in range(3)]
    assert reps[0].cache_misses > 0
    for s in reps:
        assert s.span_calls.get("exec.compile", 0) == s.cache_misses
        # one launch per dispatched chain, fewer than the kernels
        assert s.span_calls["exec.launch"] == s.fused_steps < s.n_kernels
    warm = [s for s in reps if not s.cache_misses]
    assert warm and all(s.cache_hits for s in warm)
    assert not any("exec.compile" in s.span_calls for s in warm)


@pytest.mark.parametrize("mode", [{}, {"fused": True}], ids=["unfused", "fused"])
def test_request_done_ms_one_per_retired_request(mode):
    stream, rep = _run(**mode)
    for step, s in zip(stream, rep.steps):
        reqs = {k.meta["req"] for k in step.graph.nodes.values()}
        assert set(s.request_done_ms) == reqs
        assert all(0.0 < ms <= s.wall_ms for ms in s.request_done_ms.values())


def test_serve_report_exports_spans_and_request_p90():
    _, rep = _run()
    d = rep.to_dict()
    assert d["span_ms"] == pytest.approx(
        {n: sum(s.span_ms.get(n, 0.0) for s in rep.steps)
         for n in {n for s in rep.steps for n in s.span_ms}})
    done = sorted(ms for s in rep.steps for ms in s.request_done_ms.values())
    assert d["request_p90_ms"] in done
    assert sum(v <= d["request_p90_ms"] for v in done) >= 0.9 * len(done)
    assert ServeReport("idle").to_dict()["request_p90_ms"] is None


def test_merged_reports_sum_spans():
    _, a = _run(n_steps=2)
    _, b = _run(n_steps=2)
    m = merge_serve_reports([a, b])
    for i, s in enumerate(m.steps):
        assert s.span_calls["exec.launch"] == (
            a.steps[i].span_calls["exec.launch"]
            + b.steps[i].span_calls["exec.launch"])
        assert s.span_ms["serve.plan"] == pytest.approx(
            a.steps[i].span_ms["serve.plan"] + b.steps[i].span_ms["serve.plan"])
        assert s.request_done_ms.keys() == a.steps[i].request_done_ms.keys()
