"""From a profiler trace to device busy time, per-op device time and the
host activity behind each idle gap.

Device ops are the events of each TPU plane's ``XLA Ops`` line; an event's
name is the op's HLO text, and the op is named ``<module>/<op>`` after the
``XLA Modules`` event that holds it (``jit_matmul/matmul.1``).  Busy time
is the union of the ops' intervals inside the window, the span of the
harness's ``bench.window`` annotation; idle is the rest of the window.  An
idle gap is named after the innermost host event running at its middle on
the thread that drives the program.

The device's clock is not the host's: on a v5e the trace puts a module
up to milliseconds before the host call that launched it.  Each chip's
events are shifted by the least amount that puts every module after its
call, pairing the k-th outermost ``PjitFunction(f)`` on the host with the
k-th ``jit_f`` module on that chip, for each f called as often as it ran
there; a chip with no such f takes the largest shift of the others.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = "/device:TPU:"
DEVICE_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
PALLAS = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over the chips
    chips: int
    op_s: dict[str, float]  # "<module>/<op>" -> seconds, summed over chips
    op_calls: dict[str, int]  # "<module>/<op>" -> events, summed over chips
    pallas: set[str]  # the ops that are Pallas kernels (TPU custom calls)
    idle_by_host: dict[str, float]  # host activity -> idle seconds, mean over chips
    clock_shift_ns: dict[str, float]  # chip -> ns added to its device times

    def kernel(self, name: str) -> tuple[int, float]:
        """(events, seconds) of the Pallas ops named ``name`` (``name.N`` in
        HLO), in whatever module they ran."""
        pat = re.compile(rf"/{re.escape(name)}(\.\d+)?$")
        ops = [n for n in self.pallas if pat.search(n)]
        return sum(self.op_calls[n] for n in ops), sum(self.op_s[n] for n in ops)

    def breakdown(self, top: int = 10) -> dict:
        def head(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": head(self.op_s), "idle_gaps": head(self.idle_by_host)}


def load(logdir: Path | str) -> list[Event]:
    """The device planes' op and module events, and the host thread that
    holds the window span, from the newest ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData

    paths = sorted(Path(logdir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no trace under {logdir}")
    data = ProfileData.from_file(str(paths[-1]))
    out: list[Event] = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name not in (DEVICE_LINE, MODULE_LINE):
                continue
            evs = [Event(plane.name, line.name, e.name, e.start_ns, e.duration_ns)
                   for e in line.events]
            if device or any(e.name == WINDOW_SPAN for e in evs):
                out += evs
    return out


def _short(hlo: str) -> str:
    """``%matmul.1 = f32[...] custom-call(...)`` -> ``matmul.1``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _module_of(ops: list[Event], modules: list[Event]) -> list[str]:
    """The name (hash dropped) of the module event holding each op."""
    modules = sorted(modules, key=lambda e: e.start_ns)
    starts = [m.start_ns for m in modules]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        m = modules[i] if i >= 0 and modules[i].end_ns >= op.start_ns else None
        out.append(m.name.split("(", 1)[0] if m else "?")
    return out


def _clock_shift(host: list[Event], modules: list[Event]) -> float | None:
    """ns to add to one chip's times so that no module starts before its
    host call; None where no function pairs up."""
    calls: dict[str, list[Event]] = {}
    for e in host:
        m = re.fullmatch(r"PjitFunction\((.+)\)", e.name)
        if m:
            seen = calls.setdefault(m.group(1), [])
            if not seen or seen[-1].end_ns < e.start_ns:  # outermost only
                seen.append(e)
    runs: dict[str, list[Event]] = {}
    for m in sorted(modules, key=lambda e: e.start_ns):
        name = m.name.split("(", 1)[0]
        if name.startswith("jit_"):
            runs.setdefault(name[4:], []).append(m)
    shifts = [
        h.start_ns - d.start_ns
        for f, devs in runs.items()
        if len(calls.get(f, ())) == len(devs)
        for h, d in zip(calls[f], devs)
    ]
    return max(shifts) if shifts else None


def union_ns(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """-> (covered length, the gaps between the covered stretches)."""
    total = 0.0
    gaps: list[tuple[float, float]] = []
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                gaps.append((end, s))
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total, gaps


def _host_thread(events: list[Event]) -> list[Event]:
    """Host events of the thread that holds the window span."""
    span = next(e for e in events if e.name == WINDOW_SPAN)
    return sorted(  # an outer event before the inner ones that start with it
        (e for e in events if e.plane == span.plane and e.line == span.line),
        key=lambda e: (e.start_ns, -e.dur_ns),
    )


def _innermost(host: list[Event], starts: list[float], t: float) -> str:
    i = bisect.bisect_right(starts, t)
    while i > 0:
        i -= 1
        if host[i].end_ns >= t:
            return host[i].name
    return "(outside any span)"


def summarize(events: list[Event]) -> Summary:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = spans[0].start_ns, spans[0].end_ns
    host = _host_thread(events)
    starts = [e.start_ns for e in host]
    planes = sorted({e.plane for e in events if e.plane.startswith(DEVICE_PLANE)})
    found = {
        p: _clock_shift(host, [e for e in events if e.plane == p and e.line == MODULE_LINE])
        for p in planes
    }
    known = [v for v in found.values() if v is not None]
    shift = {p: v if v is not None else max(known, default=0.0) for p, v in found.items()}
    events = [
        dataclasses.replace(e, start_ns=e.start_ns + shift[e.plane]) if e.plane in shift else e
        for e in events
    ]
    op_s: dict[str, float] = {}
    op_calls: dict[str, int] = {}
    pallas: set[str] = set()
    idle: dict[str, float] = {}
    busy = 0.0
    for plane in planes:
        ops = [
            e
            for e in events
            if e.plane == plane and e.line == DEVICE_LINE and e.end_ns > w0 and e.start_ns < w1
        ]
        covered, gaps = union_ns([(max(e.start_ns, w0), min(e.end_ns, w1)) for e in ops])
        if ops:
            first, last = min(e.start_ns for e in ops), max(e.end_ns for e in ops)
            gaps = [(w0, max(w0, first))] + gaps + [(min(w1, last), w1)]
        else:
            gaps = [(w0, w1)]
        busy += covered
        modules = [e for e in events if e.plane == plane and e.line == MODULE_LINE]
        for e, module in zip(ops, _module_of(ops, modules)):
            key = f"{module}/{_short(e.name)}"
            op_s[key] = op_s.get(key, 0.0) + e.dur_ns * 1e-9
            op_calls[key] = op_calls.get(key, 0) + 1
            if PALLAS in e.name:
                pallas.add(key)
        for s, t in gaps:
            if t > s:
                who = _innermost(host, starts, (s + t) / 2)
                idle[who] = idle.get(who, 0.0) + (t - s) * 1e-9
    n = max(len(planes), 1)
    return Summary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy * 1e-9 / n,
        chips=len(planes),
        op_s=op_s,
        op_calls=op_calls,
        pallas=pallas,
        idle_by_host={k: v / n for k, v in idle.items()},
        clock_shift_ns=shift,
    )
