"""The trace reduction: busy union, idle share, clock alignment, per-kernel
time and roofline share, on hand-made events and on a recorded chip trace."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from yardstick import trace
from yardstick.kernel_roofline import read_kernel
from yardstick.roofline import kernel_flops_bytes

DATA = Path(__file__).resolve().parent / "data" / "trace_serve_flat_v5e.json"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, start, dur):
    return trace.Event(plane, line, name, float(start), float(dur))


@pytest.mark.parametrize(
    "intervals,covered,gaps",
    [
        ([], 0, []),
        ([(0, 10)], 10, []),
        ([(0, 10), (5, 20)], 20, []),
        ([(0, 10), (10, 20)], 20, []),
        ([(30, 40), (0, 10), (2, 3)], 20, [(10, 30)]),
        ([(0, 5), (8, 9), (20, 25)], 11, [(5, 8), (9, 20)]),
    ],
)
def test_union(intervals, covered, gaps):
    assert trace.union_ns(intervals) == (covered, gaps)


def test_hand_made_window():
    mm = '%matmul.1 = custom-call(), custom_call_target="tpu_custom_call"'
    events = [
        _ev(HOST, "py", "bench.window", 0, 1000),
        _ev(HOST, "py", "bench.run_step", 0, 1000),
        _ev(HOST, "py", "PjitFunction(matmul)", 100, 50),
        _ev(HOST, "py", "PjitFunction(matmul)", 500, 50),
        _ev(HOST, "py", "policy", 700, 200),
        # the chip's clock runs 40 ns behind the host's
        _ev(DEV, "XLA Modules", "jit_matmul(123)", 70, 200),
        _ev(DEV, "XLA Ops", mm, 70, 200),
        _ev(DEV, "XLA Modules", "jit_matmul(123)", 470, 100),
        _ev(DEV, "XLA Ops", mm, 470, 100),
        _ev(DEV, "Steps", "ignored", 0, 1000),
    ]
    s = trace.summarize(events)
    assert s.clock_shift_ns == {DEV: 30.0}
    assert s.chips == 1
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(300e-9)
    assert s.kernel("matmul") == (2, pytest.approx(300e-9))
    assert s.kernel("matadd") == (0, 0)
    # gaps [0,100) [300,500) [600,1000): the last one's middle is in "policy"
    assert s.idle_by_host == pytest.approx({"bench.run_step": 300e-9, "policy": 400e-9})
    assert s.breakdown()["device_ops"] == [["jit_matmul/matmul.1", pytest.approx(300e-9)]]


def test_recorded_chip_trace():
    doc = json.loads(DATA.read_text())
    events = [trace.Event(*e) for e in doc["events"]]
    s = trace.summarize(events)
    # one interval of the churn mix: 32 prefills and 256 decodes
    assert s.kernel("matmul")[0] == 32
    assert s.kernel("matadd")[0] == 256
    shift = s.clock_shift_ns[DEV]
    w = s.window_s * 1e9
    clipped = [
        min(e.end_ns + shift, w) - max(e.start_ns + shift, 0)
        for e in events
        if e.line == trace.DEVICE_LINE
    ]
    # one TensorCore runs one op at a time: busy is the ops' summed time
    assert s.busy_s == pytest.approx(sum(t for t in clipped if t > 0) * 1e-9, rel=1e-9)
    assert 0 < s.busy_s < s.window_s
    # after the shift no module starts before its host call
    calls = sorted(e.start_ns for e in events if e.name == "PjitFunction(matmul)")
    mods = sorted(e.start_ns for e in events if e.name.startswith("jit_matmul("))
    outer = [c for i, c in enumerate(calls) if i % 2 == 0]  # each call nests one more
    assert len(outer) == len(mods) == 32
    assert all(m + shift >= c for m, c in zip(mods, outer))
    assert max(c - m for m, c in zip(mods, outer)) == shift

    run = SimpleNamespace(trace=s, kernels={"prefill": "matmul", "decode": "matadd"},
                          side=2048, device_kind="TPU v5 lite")
    for kernel in ("matmul", "matadd"):
        flops, nbytes = kernel_flops_bytes(kernel, 2048)
        least = max(flops / 197e12, nbytes / 819e9)
        calls, secs = s.kernel(kernel)
        assert read_kernel(run, kernel) == pytest.approx(100 * calls * least / secs)
        assert 0 < read_kernel(run, kernel) <= 100
    run.kernels = {"matmul": "matmul"}
    assert read_kernel(run, "matadd") is None
