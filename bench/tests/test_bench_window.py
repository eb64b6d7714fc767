"""The window's rate and tail are taken over all of its DAGs and time."""

import pytest

from yardstick.window import closed_loop, nearest_rank


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _loop(stalls: dict[int, float], seconds=10.0):
    clock = FakeClock()

    def submit(i):
        clock.t += 0.125 + stalls.get(i, 0.0)

    return closed_loop(submit, seconds, clock=clock)


def test_steady_window():
    w = _loop({})
    assert w.attempted == 80
    assert w.rate() == pytest.approx(8.0)
    assert w.percentile_ms(90) == pytest.approx(125.0)


def test_stall_moves_rate_and_tail():
    steady = _loop({})
    # a stall that holds back 15 of the window's DAGs (as an in-window
    # compile or a repartition escalation would)
    stalled = _loop({i: 0.25 for i in range(40, 52)})
    assert stalled.rate() < steady.rate()
    assert stalled.percentile_ms(90) > steady.percentile_ms(90)
    assert stalled.seconds == pytest.approx(steady.seconds, abs=0.376)


def test_failed_graph_counts_in_tail_not_in_rate():
    clock = FakeClock()

    def submit(i):
        clock.t += 0.1
        if i % 10 == 0:
            clock.t += 0.5
            raise RuntimeError("boom")

    w = closed_loop(submit, 10.0, clock=clock)
    assert len(w.failed) == w.attempted // 10 + (w.attempted % 10 > 0)
    assert w.completed == w.attempted - len(w.failed)
    assert w.rate() == pytest.approx(w.completed / w.seconds)
    assert w.percentile_ms(95) == pytest.approx(600.0)
    assert w.errors and "boom" in w.errors[0]


def test_prepare_is_outside_latency_inside_window():
    clock = FakeClock()

    def prepare(i):
        clock.t += 0.05

    def submit(i):
        clock.t += 0.1

    w = closed_loop(submit, 3.0, prepare=prepare, clock=clock)
    assert w.percentile_ms(90) == pytest.approx(100.0)
    assert w.rate() == pytest.approx(1 / 0.15, rel=0.02)


@pytest.mark.parametrize(
    "values,q,want",
    [([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90, 9), ([5], 90, 5), ([3, 1, 2], 50, 2),
     (list(range(1, 101)), 90, 90), (list(range(1, 101)), 99, 99)],
)
def test_nearest_rank(values, q, want):
    assert nearest_rank(values, q) == want


def test_check_hook_waits_for_every_exit():
    """``run_step`` returns only once the DAG's exits are ready: the
    harness's check hook waits for each of them, sampled or not."""
    from yardstick.runner import Sample

    class Exit:
        ready = False

        def block_until_ready(self):
            self.ready = True
            return self

    for active in (False, True):
        exits = {"a": Exit(), "b": Exit()}
        sample = Sample(1, seed=3)
        sample.active = active
        sample(None, None, exits)
        assert all(e.ready for e in exits.values())
