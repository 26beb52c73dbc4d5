"""The window's arithmetic: whole dispatches, two in flight, all the
work over all the time."""

import math

import pytest

from benchmark import window


class FakeDevice:
    """A serial device: each dispatch takes ``durations[k]`` seconds
    after the later of its enqueue and the previous one's end. The
    clock only moves when the host reads."""

    def __init__(self, durations):
        self.durations = list(durations)
        self.now = 0.0
        self.free_at = 0.0
        self.log = []
        self.n = 0

    def dispatch(self):
        k = self.n
        self.n += 1
        start = max(self.now, self.free_at)
        self.free_at = start + self.durations[k % len(self.durations)]
        self.log.append(("dispatch", k))
        return (k, self.free_at)

    def read(self, handle):
        k, done = handle
        self.now = max(self.now, done)
        self.log.append(("read", k))
        return 1.0

    def clock(self):
        return self.now


def test_two_in_flight_and_whole_dispatches():
    dev = FakeDevice([1.0])
    out = window.run_window(dev.dispatch, dev.read, 3.5, clock=dev.clock)
    # dispatch k+1 is always enqueued before dispatch k is read
    assert dev.log[:5] == [("dispatch", 0), ("dispatch", 1), ("read", 0),
                           ("dispatch", 2), ("read", 1)]
    # the window ends with the first dispatch completing at or after
    # --seconds: four whole dispatches, 4.0 s, never 3.5
    assert out["completions"] == [1.0, 2.0, 3.0, 4.0]
    # the one still in flight is drained and read, and counts nothing
    assert dev.log[-1] == ("read", 4)
    assert window.rate(out["completions"], 8, 100, 1) == 4 * 8 * 100 / 4.0
    assert window.rate(out["completions"], 8, 100, 4) == 4 * 8 * 100 / 16.0


def test_a_stalled_dispatch_lowers_the_rate():
    steady = FakeDevice([1.0])
    a = window.run_window(steady.dispatch, steady.read, 10, clock=steady.clock)
    stalled = FakeDevice([1.0, 1.0, 1.0, 3.0] + [1.0] * 20)
    b = window.run_window(stalled.dispatch, stalled.read, 10,
                          clock=stalled.clock)
    ra = window.rate(a["completions"], 1, 1, 1)
    rb = window.rate(b["completions"], 1, 1, 1)
    assert ra == 1.0
    # the 2 s stall cost two dispatches of the ten: no median or
    # best-of hides it
    assert rb == pytest.approx(8 / 10.0)
    iv = window.intervals(b["completions"])
    assert max(iv) == 3.0 and sorted(iv)[len(iv) // 2] == 1.0
    assert sum(iv) == pytest.approx(b["completions"][-1])


def test_a_host_pause_shorter_than_a_dispatch_never_reaches_the_device():
    dev = FakeDevice([1.0])
    reads = []

    def slow_read(h):
        v = dev.read(h)
        if len(reads) == 2:
            dev.now += 0.6          # the host stalls after a readback
        reads.append(v)
        return v

    out = window.run_window(dev.dispatch, slow_read, 5, clock=dev.clock)
    # the next dispatch was already queued: the host sees one
    # completion late, the device never waits, the window ends on time
    assert out["completions"][-1] == pytest.approx(5.0)
    assert len(out["completions"]) == 5


def test_non_finite_loss_fails_the_run():
    dev = FakeDevice([1.0])

    def read(h):
        dev.read(h)
        return math.nan if h[0] == 2 else 1.0

    with pytest.raises(FloatingPointError):
        window.run_window(dev.dispatch, read, 10, clock=dev.clock)
