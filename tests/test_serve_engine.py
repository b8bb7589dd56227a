"""Continuous-batching GLCMEngine: deadline dispatch, multi-spec
multiplexing, priorities, backpressure, bounded results, and stream
coexistence.

Deadline tests inject a fake clock (``GLCMEngine(cfg, clock=...)``) so
deadline expiry is deterministic virtual time, never a sleep."""

import numpy as np
import pytest

from repro.core.plan import bucket_sizes, pick_bucket, plan_cache_clear
from repro.core.pipeline import pad_stack
from repro.core.spec import GLCMSpec
from repro.serve.engine import GLCMEngine, GLCMServeConfig, QueueFullError

RNG = np.random.default_rng(7)
SHAPE = (32, 32)
IMGS = RNG.random((16, *SHAPE), np.float32)
VOLS = RNG.random((8, 4, 16, 16), np.float32)

SPEC_2D = GLCMSpec(levels=8, pairs=((1, 0), (1, 45)), quantize="uniform")
SPEC_EQ = GLCMSpec(levels=8, pairs=((1, 0),), quantize="equalized")
SPEC_TILES = GLCMSpec(
    levels=8, pairs=((1, 0),), quantize="uniform",
    region="tiles", region_shape=(16, 16),
)
SPEC_VOL = GLCMSpec(levels=8, pairs=((1, 0),), quantize="uniform", ndim=3)


def _cfg(**kw):
    kw.setdefault("levels", 8)
    kw.setdefault("image_shape", SHAPE)
    kw.setdefault("pairs", ((1, 0),))
    return GLCMServeConfig(**kw)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, ms):
        self.t += ms * 1e-3


# ---------------------------------------------------------------------------
# bucket helpers
# ---------------------------------------------------------------------------


def test_bucket_sizes_default_powers_of_two():
    assert bucket_sizes(8) == (1, 2, 4, 8)
    assert bucket_sizes(6) == (1, 2, 4, 6)
    assert bucket_sizes(1) == (1,)


def test_bucket_sizes_explicit_validated():
    assert bucket_sizes(8, (2, 8)) == (2, 8)
    with pytest.raises(ValueError, match="ascending"):
        bucket_sizes(8, (4, 2, 8))
    with pytest.raises(ValueError, match="end at the batch size"):
        bucket_sizes(8, (1, 2, 4))
    with pytest.raises(ValueError, match="positive"):
        bucket_sizes(8, (0, 8))


def test_pick_bucket_smallest_fit():
    assert pick_bucket((1, 2, 4, 8), 1) == 1
    assert pick_bucket((1, 2, 4, 8), 3) == 4
    assert pick_bucket((1, 2, 4, 8), 8) == 8
    with pytest.raises(ValueError, match="exceed"):
        pick_bucket((1, 2), 3)


def test_pad_stack_repeats_last():
    stack, k = pad_stack([IMGS[0], IMGS[1]], 4)
    assert stack.shape == (4, *SHAPE) and k == 2
    np.testing.assert_array_equal(stack[2], IMGS[1])
    np.testing.assert_array_equal(stack[3], IMGS[1])
    with pytest.raises(ValueError, match="1..2"):
        pad_stack([IMGS[0]] * 3, 2)


# ---------------------------------------------------------------------------
# deadline-driven dispatch
# ---------------------------------------------------------------------------


def test_deadline_dispatches_single_queued_request():
    """The tentpole behavior: ONE queued request launches alone (padded to
    the smallest bucket) once its age reaches max_wait_ms — it never
    stalls behind an unfilled batch."""
    clock = FakeClock()
    eng = GLCMEngine(_cfg(batch_size=8, max_wait_ms=5.0), clock=clock)
    t = eng.submit(IMGS[0])
    assert eng.batches_dispatched == 0
    clock.advance(4.9)
    assert eng.poll() == 0          # deadline not reached: still queued
    clock.advance(0.2)
    assert eng.poll() == 1          # expired: partial dispatch fires
    entry = eng.dispatch_log[-1]
    assert entry["deadline"] and entry["bucket"] == 1 and entry["occupancy"] == 1
    assert eng.stats()["workloads"][0]["deadline_dispatches"] == 1
    ref = GLCMEngine(_cfg(batch_size=1)).map(IMGS[:1])[0]
    np.testing.assert_array_equal(eng.result(t), ref)


def test_deadline_none_preserves_legacy_wait_until_full():
    eng = GLCMEngine(_cfg(batch_size=4))
    for im in IMGS[:3]:
        eng.submit(im)
    assert eng.poll() == 0 and eng.batches_dispatched == 0
    eng.submit(IMGS[3])             # 4th request: full batch auto-dispatches
    assert eng.batches_dispatched == 1


def test_deadline_dispatch_takes_largest_full_bucket():
    """A deadline launch with 3 queued takes a FULL bucket-2 launch (the
    leftover's own deadline is later), not a padded bucket-4 — deadline
    dispatches stay at ~100% occupancy."""
    clock = FakeClock()
    eng = GLCMEngine(_cfg(batch_size=8, max_wait_ms=1.0), clock=clock)
    for im in IMGS[:3]:
        eng.submit(im)
    clock.advance(1.1)
    eng.poll()
    entry = eng.dispatch_log[-1]
    assert entry["bucket"] == 2 and entry["occupancy"] == 2
    occ = eng.stats()["workloads"][0]["batch_occupancy"]
    assert occ == {2: {2: 1}}
    # the leftover request is younger: its deadline fires later, alone
    clock.advance(1.1)
    eng.poll()
    assert eng.dispatch_log[-1]["bucket"] == 1
    # padding only below the smallest bucket: explicit buckets (2, 8),
    # one queued request past deadline → padded bucket-2 launch
    eng2 = GLCMEngine(
        _cfg(batch_size=8, buckets=(2, 8), max_wait_ms=1.0), clock=clock)
    eng2.submit(IMGS[0])
    clock.advance(1.1)
    eng2.poll()
    entry = eng2.dispatch_log[-1]
    assert entry["bucket"] == 2 and entry["occupancy"] == 1


def test_deadline_fires_inside_submit_too():
    clock = FakeClock()
    eng = GLCMEngine(_cfg(batch_size=8, max_wait_ms=1.0), clock=clock)
    eng.submit(IMGS[0])
    clock.advance(2.0)
    eng.submit(IMGS[1])             # submit advances the loop: both dispatch
    assert eng.batches_dispatched == 1
    assert eng.dispatch_log[-1]["occupancy"] == 2


def test_next_deadline_reports_earliest_expiry():
    clock = FakeClock()
    eng = GLCMEngine(_cfg(batch_size=8, max_wait_ms=5.0), clock=clock)
    assert eng.next_deadline() is None
    eng.submit(IMGS[0])
    clock.advance(2.0)
    eng.submit(IMGS[1])
    assert eng.next_deadline() == pytest.approx(5e-3)   # oldest sets it
    clock.t = eng.next_deadline()
    assert eng.poll() == 1
    assert eng.next_deadline() is None
    # no deadline configured → never reports one
    eng2 = GLCMEngine(_cfg(batch_size=8))
    eng2.submit(IMGS[0])
    assert eng2.next_deadline() is None


def test_per_workload_deadline_override():
    clock = FakeClock()
    eng = GLCMEngine(_cfg(batch_size=8), clock=clock)   # engine: no deadline
    wid = eng.register(SPEC_2D, SHAPE, max_wait_ms=1.0)
    eng.submit(IMGS[0])
    eng.submit(IMGS[1], workload=wid)
    clock.advance(5.0)
    assert eng.poll() == 1          # only the deadline workload fires
    assert eng.dispatch_log[-1]["workload"] == wid
    assert len(eng._workloads[0].queue) == 1


# ---------------------------------------------------------------------------
# multi-spec multiplexing
# ---------------------------------------------------------------------------


def test_mixed_spec_interleaved_bit_identical_to_dedicated_engines():
    """One engine serving 2-D + equalized + tiles-region + volume specs,
    submits interleaved, must return results bit-identical to four
    dedicated single-spec engines (acceptance criterion)."""
    plan_cache_clear()
    eng = GLCMEngine(_cfg(spec=SPEC_2D, batch_size=2))
    wid_eq = eng.register(SPEC_EQ, SHAPE, batch_size=2)
    wid_tl = eng.register(SPEC_TILES, SHAPE, batch_size=2)
    wid_vol = eng.register(SPEC_VOL, (4, 16, 16), batch_size=2)
    assert eng.workloads() == (0, wid_eq, wid_tl, wid_vol)

    tickets = []
    for i in range(4):              # interleave: round-robin across specs
        tickets.append((eng.submit(IMGS[i]), 0, i))
        tickets.append((eng.submit(IMGS[i], workload=wid_eq), wid_eq, i))
        tickets.append((eng.submit(IMGS[i], workload=wid_tl), wid_tl, i))
        tickets.append((eng.submit(VOLS[i], workload=wid_vol), wid_vol, i))
    eng.flush()
    got = {(w, i): eng.result(t) for t, w, i in tickets}

    dedicated = {
        0: GLCMEngine(_cfg(spec=SPEC_2D, batch_size=2)).map(IMGS[:4]),
        wid_eq: GLCMEngine(_cfg(spec=SPEC_EQ, batch_size=2)).map(IMGS[:4]),
        wid_tl: GLCMEngine(_cfg(spec=SPEC_TILES, batch_size=2)).map(IMGS[:4]),
        wid_vol: GLCMEngine(
            _cfg(spec=SPEC_VOL, image_shape=(4, 16, 16), batch_size=2)
        ).map(VOLS[:4]),
    }
    for (w, i), out in got.items():
        np.testing.assert_array_equal(out, dedicated[w][i])
    # region workload really produced a texture map (grid axes present)
    assert got[(wid_tl, 0)].shape[:2] == (2, 2)


def test_workload_stats_are_per_workload():
    eng = GLCMEngine(_cfg(batch_size=2))
    wid = eng.register(SPEC_VOL, (4, 16, 16), batch_size=4)
    eng.map(IMGS[:4])
    eng.map(VOLS[:2], workload=wid)
    st = eng.stats()
    assert st["workloads"][0]["served"] == 4
    assert st["workloads"][0]["batches"] == 2
    assert st["workloads"][wid]["served"] == 2
    assert st["workloads"][wid]["ndim"] == 3
    for w in st["workloads"].values():
        for k in ("queue_ms", "service_ms", "e2e_ms"):
            assert {"p50", "p95", "p99", "mean", "n"} <= set(w[k])
        assert {"queue_depth", "shed", "batch_occupancy",
                "results_evicted"} <= set(w)
    assert 0.0 <= st["plan_cache"]["hit_rate"] <= 1.0


def test_register_validates_spec_and_shape():
    eng = GLCMEngine(_cfg())
    with pytest.raises(ValueError, match="GLCMSpec"):
        eng.register("scatter", SHAPE)
    with pytest.raises(ValueError, match="rank"):
        eng.register(SPEC_VOL, SHAPE)       # ndim=3 spec, 2-D shape
    with pytest.raises(KeyError, match="not registered"):
        eng.submit(IMGS[0], workload=99)


def test_shared_plan_cache_across_engine_instances():
    """Two engines with equal specs share compiled programs — the
    registry resolves through the global LRU plan cache."""
    plan_cache_clear()
    a = GLCMEngine(_cfg(batch_size=4))
    b = GLCMEngine(_cfg(batch_size=4))
    assert a.plan is b.plan


# ---------------------------------------------------------------------------
# priorities + backpressure
# ---------------------------------------------------------------------------


def test_backpressure_sheds_at_max_queue_depth():
    eng = GLCMEngine(_cfg(batch_size=8, max_queue_depth=3))
    for im in IMGS[:3]:
        eng.submit(im)
    with pytest.raises(QueueFullError, match="max_queue_depth"):
        eng.submit(IMGS[3])
    st = eng.stats()["workloads"][0]
    assert st["shed"] == 1 and st["queue_depth"] == 3
    eng.flush()                      # draining reopens the queue
    eng.submit(IMGS[3])
    assert eng.stats()["workloads"][0]["shed"] == 1


def test_priorities_drain_high_before_low_under_load():
    eng = GLCMEngine(_cfg(batch_size=2))
    eng.pause()                      # build a backlog deterministically
    low = [eng.submit(im, priority=0) for im in IMGS[:4]]
    high = [eng.submit(im, priority=10) for im in IMGS[4:8]]
    assert eng.batches_dispatched == 0
    eng.resume()                     # backlog drains in priority order
    assert eng.batches_dispatched == 4
    order = [t for d in eng.dispatch_log for t in d["tickets"]]
    assert order[:4] == high and order[4:] == low
    # results are still correct per ticket despite reordering
    ref = GLCMEngine(_cfg(batch_size=2)).map(IMGS[:8])
    for i, t in enumerate(low):
        np.testing.assert_array_equal(eng.result(t), ref[i])


def test_priority_ageing_prevents_starvation():
    """With a deadline configured, queued age counts toward priority, and a
    deadline launch ALWAYS carries the oldest request — a priority-0
    request cannot be starved by an endless priority-1 stream."""
    clock = FakeClock()
    eng = GLCMEngine(_cfg(batch_size=2, max_wait_ms=10.0), clock=clock)
    eng.pause()
    old = eng.submit(IMGS[0], priority=0)
    clock.advance(9.0)
    for im in IMGS[1:4]:
        eng.submit(im, priority=1)
    clock.advance(2.0)               # old request is past its deadline
    eng.resume()
    assert old in eng.dispatch_log[0]["tickets"]


# ---------------------------------------------------------------------------
# bounded result store (regression: _results grew forever)
# ---------------------------------------------------------------------------


def test_result_store_bounded_evicts_oldest_and_counts():
    eng = GLCMEngine(_cfg(batch_size=1, max_results=4))
    tickets = [eng.submit(im) for im in IMGS[:7]]
    st = eng.stats()
    assert st["results_held"] == 4
    assert st["workloads"][0]["results_evicted"] == 3
    for t in tickets[:3]:            # oldest three evicted
        with pytest.raises(KeyError, match="evicted"):
            eng.result(t)
    for t in tickets[3:]:            # newest four retrievable
        eng.result(t)
    assert eng.stats()["results_held"] == 0


def test_result_is_one_shot_and_unknown_raises():
    eng = GLCMEngine(_cfg(batch_size=2))
    t = eng.submit(IMGS[0])
    eng.result(t)
    with pytest.raises(KeyError, match="already retrieved"):
        eng.result(t)
    with pytest.raises(KeyError, match="unknown"):
        eng.result(12345)


# ---------------------------------------------------------------------------
# streams coexist with continuous batch traffic
# ---------------------------------------------------------------------------


def test_stream_sessions_coexist_with_continuous_batching():
    clock = FakeClock()
    eng = GLCMEngine(
        _cfg(batch_size=4, temporal_window=2, max_wait_ms=1.0), clock=clock
    )
    sid = eng.open_stream()
    frames = [eng.push(sid, IMGS[i]) for i in range(3)]
    t = eng.submit(IMGS[5])          # batch request between pushes
    clock.advance(2.0)
    assert eng.poll() == 1           # deadline fires with the stream open
    frames.append(eng.push(sid, IMGS[3]))
    state = eng.close_stream(sid)

    # stream outputs unaffected by the interleaved batch traffic
    ref_eng = GLCMEngine(_cfg(batch_size=4, temporal_window=2))
    ref_sid = ref_eng.open_stream()
    for i, frame in zip((0, 1, 2, 3), frames):
        np.testing.assert_array_equal(frame, ref_eng.push(ref_sid, IMGS[i]))
    # batch result unaffected by the open stream
    np.testing.assert_array_equal(
        eng.result(t), GLCMEngine(_cfg(batch_size=1)).map(IMGS[5:6])[0]
    )
    assert state.window == 2
    assert eng.stats()["frames_streamed"] == 4


# ---------------------------------------------------------------------------
# config validation + misc
# ---------------------------------------------------------------------------


def test_config_validates_new_knobs_eagerly():
    with pytest.raises(ValueError, match="max_wait_ms"):
        _cfg(max_wait_ms=0.0)
    with pytest.raises(ValueError, match="max_queue_depth"):
        _cfg(max_queue_depth=0)
    with pytest.raises(ValueError, match="max_results"):
        _cfg(max_results=0)
    with pytest.raises(ValueError, match="buckets"):
        _cfg(batch_size=8, buckets=(3, 1, 8))
    with pytest.raises(ValueError, match="rank"):
        _cfg(spec=SPEC_VOL)          # ndim=3 spec, default 2-D image_shape


def test_warmup_precompiles_every_bucket():
    eng = GLCMEngine(_cfg(batch_size=4))
    eng.warmup()
    assert set(eng._workloads[0].plans) == {1, 2, 4}


def test_warmup_compiles_for_the_request_dtype():
    # Programs are specialized on the input dtype: a float32 warm-up leaves
    # the first uint8 request to compile, a uint8 warm-up does not.
    spec = GLCMSpec(levels=8, pairs=((1, 0),), quantize="uniform",
                    scheme="onehot")
    img = (IMGS[0] * 255).astype(np.uint8)
    for dtype, compiles in ((np.float32, 1), (np.uint8, 0)):
        plan_cache_clear()
        eng = GLCMEngine(GLCMServeConfig(spec=spec, image_shape=SHAPE,
                                         batch_size=1))
        eng.warmup(dtype=dtype)
        fn = eng._workloads[0].plans[1].fn
        before = fn._cache_size()
        eng.map([img])
        assert fn._cache_size() - before == compiles


def test_latencies_accessor():
    eng = GLCMEngine(_cfg(batch_size=2))
    eng.map(IMGS[:4])
    assert eng.latencies(0, "e2e").shape == (4,)
    assert eng.latencies(0, "service").shape == (4,)
    with pytest.raises(ValueError, match="kind"):
        eng.latencies(0, "bogus")
