"""The closed loop under an injected clock, the request streams (every
seed offers the same work in another order) and the pools (every seed
makes the same images; no region of one stands for the whole)."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import data  # noqa: E402
from chipbench.traffic import closed  # noqa: E402


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def now(self):
        return self.t


class FakeEngine:
    """The slice of GLCMEngine the closed loop uses: a full batch launches
    on submit and takes ``service_s`` of the clock; an answer is its
    image's sum."""

    def __init__(self, clock, batch, service_s):
        self.clock, self.batch, self.service_s = clock, batch, service_s
        self.queue, self.results = [], {}
        self.batches_dispatched = 0
        self.next_ticket = 0

    def submit(self, image):
        t = self.next_ticket
        self.next_ticket += 1
        self.queue.append((t, image))
        if len(self.queue) == self.batch:
            self.clock.t += self.service_s
            for queued, img in self.queue:
                self.results[queued] = np.asarray([float(np.sum(img))])
            self.queue = []
            self.batches_dispatched += 1
        return t

    def result(self, ticket):
        return self.results.pop(ticket)


def nospan(_):
    import contextlib

    return contextlib.nullcontext()


def test_request_stream_serves_every_entry_once_per_round():
    pool = [np.full((2, 2), i) for i in range(5)]
    stream = data.request_stream(pool, 7)
    idx = [next(stream)[0] for _ in range(15)]
    for r in range(3):
        assert sorted(idx[5 * r:5 * r + 5]) == list(range(5))
    again = data.request_stream(pool, 7)
    assert [next(again)[0] for _ in range(15)] == idx


def test_closed_loop_rounds_of_clients():
    clock = FakeClock()
    eng = FakeEngine(clock, batch=3, service_s=0.25)
    pool = [np.full((2,), i, np.float32) for i in range(3)]
    win = closed.run(eng, data.request_stream(pool, 5), {"clients": 3}, 1.0, 5,
                     clock, nospan)
    assert len(win.records) == 12 and win.seconds == pytest.approx(1.0)
    assert eng.batches_dispatched == 4
    for r in win.records:
        assert r.answer[0] == 2 * r.pool_index and r.done > r.sent


@pytest.mark.parametrize("kind", data.KINDS)
def test_pool_repeats_per_seed_with_distinct_entries(kind):
    shape = (40, 48)
    group = [{"kind": kind, "count": 2}]
    a = data.make_pool(group, shape, 2**40 + 9)
    b = data.make_pool(group, shape, 2**40 + 9)
    c = data.make_pool(group, shape, 10)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], a[1]) and not np.array_equal(a[0], c[0])
    assert all(x.dtype == np.uint8 and x.shape == shape for x in a)
    assert all(x.min() == 0 and x.max() == 255 for x in a + c)


@pytest.mark.parametrize("kind", data.KINDS)
def test_pool_regions_have_their_own_gray_windows(kind):
    """Each of the 4 x 4 rectangles of an entry keeps to its own window of
    levels, and the windows differ from rectangle to rectangle."""
    (img,) = data.make_pool([{"kind": kind, "count": 1}], (64, 96), 2**40 + 3)
    blocks = img.reshape(4, 16, 4, 24).transpose(0, 2, 1, 3).reshape(16, -1)
    lows, highs = blocks.min(axis=1).astype(int), blocks.max(axis=1).astype(int)
    assert np.all(highs - lows >= 8) and np.all(highs - lows <= 256)
    assert np.ptp(lows) > 32 and np.ptp(highs - lows) > 32
