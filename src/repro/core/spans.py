"""Named host spans of the executed serving path.

Every span is recorded twice, by one helper:

* in the profiler's trace, as ``jax.profiler.TraceAnnotation(name)`` on the
  calling thread (only while a trace is being taken), so the device's idle
  gaps in a trace are named after the innermost span;
* in memory, as **self** milliseconds (a span's time less its child spans')
  and a call count per name, which :class:`~repro.core.serving.StepReport`
  carries as ``span_ms`` / ``span_calls``.

Spans nest; the names partition ``ServingExecutor.run_step``'s wall time up
to the helper's own cost (about a microsecond per span).

==================  =========================================================
``serve.attach``    the ``attach`` hook: kernels' callables and input blocks
``serve.plan``      per-interval set-up: graph copy, arrival split, platform
                    copy, comm model, session construction (input seeding)
``serve.prepare``   ``policy.prepare``, the policy's offline pass
``serve.admit``     due admissions (``admit_task``) and elastic hooks
``serve.account``   the per-kernel serving loop: residency ledger,
                    ``cost_model.observe``, the scan for due events
``serve.feedback``  heartbeats, ``feed_policy``, the ``StepReport``
``exec.select``     choosing the next kernel or fused chain
``exec.pull``       host time in ``device_put`` pulls (demand and prefetch)
``exec.wait``       the host blocked on the device (``block_until_ready``)
``exec.launch``     enqueueing a kernel or chain (jit dispatch); its trace
                    event carries the ``kernel``, its ``op`` (``superstep``
                    for a fused chain) and its ``req``
``exec.compile``    a fused chain's compile (a ``SuperStepCache`` miss)
``exec.account``    the rest of ``ExecSession.step``: virtual clock, channel
                    drains, prefetch planning, output bookkeeping
==================  =========================================================
"""

from __future__ import annotations

from time import perf_counter

from jax.profiler import TraceAnnotation

SERVE_ATTACH = "serve.attach"
SERVE_PLAN = "serve.plan"
SERVE_PREPARE = "serve.prepare"
SERVE_ADMIT = "serve.admit"
SERVE_ACCOUNT = "serve.account"
SERVE_FEEDBACK = "serve.feedback"
EXEC_SELECT = "exec.select"
EXEC_PULL = "exec.pull"
EXEC_WAIT = "exec.wait"
EXEC_LAUNCH = "exec.launch"
EXEC_COMPILE = "exec.compile"
EXEC_ACCOUNT = "exec.account"

NAMES = (
    SERVE_ATTACH, SERVE_PLAN, SERVE_PREPARE, SERVE_ADMIT, SERVE_ACCOUNT,
    SERVE_FEEDBACK, EXEC_SELECT, EXEC_PULL, EXEC_WAIT, EXEC_LAUNCH,
    EXEC_COMPILE, EXEC_ACCOUNT,
)

_tracing = TraceAnnotation.is_enabled


class Spans:
    """Self time and calls per span name: ``with spans(NAME): ...``."""

    __slots__ = ("_open", "_acc", "_named")

    def __init__(self):
        self._open: list[list] = []  # [child s, totals, trace event, start]
        self._acc: dict[str, list] = {}  # name -> [self s, calls]
        self._named: dict[str, _Span] = {}

    def __call__(self, name: str, **meta) -> "_Span":
        """A span named ``name``; ``meta`` goes on its trace event."""
        if meta and _tracing():
            return _Span(self, name, meta)
        span = self._named.get(name)
        if span is None:
            span = self._named[name] = _Span(self, name, {})
        return span

    def totals(self, name: str) -> list:
        """The live ``[self s, calls]`` of ``name``: a caller that reads it
        around a stretch of work gets that stretch's self time."""
        totals = self._acc.get(name)
        if totals is None:
            totals = self._acc[name] = [0.0, 0]
        return totals

    @property
    def ms(self) -> dict[str, float]:
        return {n: a[0] * 1e3 for n, a in self._acc.items() if a[1]}

    @property
    def calls(self) -> dict[str, int]:
        return {n: a[1] for n, a in self._acc.items() if a[1]}


class _Span:
    __slots__ = ("_open", "_totals", "name", "meta")

    def __init__(self, rec: Spans, name: str, meta: dict):
        self._open = rec._open
        totals = rec._acc.get(name)
        if totals is None:
            totals = rec._acc[name] = [0.0, 0]
        self._totals, self.name, self.meta = totals, name, meta

    def __enter__(self) -> None:
        event = None
        if _tracing():
            event = TraceAnnotation(self.name, **self.meta)
            event.__enter__()
        self._open.append([0.0, self._totals, event, perf_counter()])

    def __exit__(self, kind, value, tb) -> None:
        t = perf_counter()
        opened = self._open
        child, totals, event, t0 = opened.pop()
        dt = t - t0
        if opened:
            opened[-1][0] += dt
        totals[0] += dt - child
        totals[1] += 1
        if event is not None:
            event.__exit__(kind, value, tb)
