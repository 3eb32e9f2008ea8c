"""The window's loops on a fake device that runs calls in order, each
taking a fixed time once the one before has finished: what each loop
keeps in flight, that every call sent is waited for and counted, and
that the window closes after the last answer."""
import time

import pytest

from bench import harness


class FakeDevice:
    def __init__(self, call_s: float):
        self.call_s = call_s
        self.free = 0.0              # when the last call sent finishes
        self.in_flight = 0           # sent, not yet waited for
        self.most_in_flight = 0
        self.answered: list[tuple[int, float]] = []


class Answer:
    def __init__(self, dev: FakeDevice, i: int, ready_at: float):
        self.dev, self.i, self.ready_at = dev, i, ready_at
        self.waited = False

    def is_ready(self) -> bool:
        return time.perf_counter() >= self.ready_at

    def block_until_ready(self):
        delay = self.ready_at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        if not self.waited:
            self.waited = True
            self.dev.in_flight -= 1
            self.dev.answered.append((self.i, time.perf_counter()))
        return self


class FakeSystem:
    def __init__(self, dev: FakeDevice):
        self.dev = dev

    def call(self, i: int):
        d = self.dev
        if d.in_flight >= 100:
            raise RuntimeError("100 calls in flight: the loop does not "
                               "bound what it sends ahead")
        d.free = max(d.free, time.perf_counter()) + d.call_s
        d.in_flight += 1
        d.most_in_flight = max(d.most_in_flight, d.in_flight)
        return Answer(d, i, d.free)

    def unit_tokens(self, i: int):
        return None

    def unit_work(self, i: int) -> dict:
        return {"flops": 2.0, "spmm": []}


def _run(loop, seconds, call_s):
    dev = FakeDevice(call_s)
    sampler = harness.Sampler(2, 7)
    w = harness.LOOPS[loop](FakeSystem(dev), seconds, sampler)
    return w, dev, sampler


@pytest.mark.parametrize("loop", sorted(harness.LOOPS))
def test_every_call_sent_is_waited_for_and_counted(loop, monkeypatch):
    monkeypatch.setattr(harness, "AHEAD_S", 0.05)
    w, dev, sampler = _run(loop, 0.4, 0.005)
    assert dev.in_flight == 0
    assert [i for i, _ in dev.answered] == list(range(w.units))
    assert w.units == w.attempted == sampler.seen > 1
    assert w.flops == 2.0 * w.units
    assert w.tokens is None
    # The window closes after the last answer, and not long after the
    # time is up: whatever was sent when it was fits in the time ahead.
    assert w.t_end >= dev.answered[-1][1]
    assert w.seconds >= 0.4
    assert w.seconds < 0.4 + 0.05 + 0.25


def test_closed_batch_keeps_one_call_in_flight():
    _, dev, _ = _run("closed_batch", 0.2, 0.005)
    assert dev.most_in_flight == 1


def test_pipelined_keeps_the_time_ahead_in_flight(monkeypatch):
    # 50 ms ahead of 5 ms calls: at most 10 waiting behind the one
    # waited for, and more than one once the first has come back.
    monkeypatch.setattr(harness, "AHEAD_S", 0.05)
    _, dev, _ = _run("pipelined", 0.4, 0.005)
    assert 2 <= dev.most_in_flight <= 11


def test_pipelined_holds_no_answer_already_back(monkeypatch):
    # A device that answers at once: every answer is taken when it is
    # seen, whatever the time ahead allows.
    monkeypatch.setattr(harness, "AHEAD_S", 100.0)
    _, dev, _ = _run("pipelined", 0.2, 0.0)
    assert dev.most_in_flight == 1
