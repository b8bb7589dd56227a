"""Incremental temporal GLCM: the rolling-window path must be BIT-exact
against full recompute for every supported spec (the whole point of integer
add/subtract streaming), the ring buffer must wrap correctly over long
streams, state must checkpoint/resume losslessly, and the pipeline/serving
streaming surfaces must agree with the underlying plan.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.pipeline import glcm_feature_stream
from repro.core.plan import compile_plan, plan_cache_clear
from repro.core.spec import GLCMSpec
from repro.core.stream_state import GLCMStreamState, init_state, stream_step
from repro.serve.engine import GLCMEngine, GLCMServeConfig

LEVELS = 8
SHAPE = (20, 16)
WINDOW = 4
T = 3 * WINDOW + 2  # the ring wraps three times
PAIRS = ((1, 0), (1, 135))
# Features are float32 Haralick statistics of the same integer counts; the
# scan, stepped and engine programs may reassociate their float reductions,
# so features agree to float32 rounding, not bit for bit. Counts are always
# compared exactly.
FEATURE_TOL = {"rtol": 1e-5, "atol": 1e-6}


def _video(t=T, shape=SHAPE, levels=LEVELS, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, levels, (t, *shape)).astype(np.int32)


def _windowed_sums(per_frame: np.ndarray, window: int) -> np.ndarray:
    """The recompute reference: at step t, the exact sum of the last
    min(t+1, window) frames' counts (warm-up = growing window)."""
    out = np.empty_like(per_frame)
    for t in range(per_frame.shape[0]):
        out[t] = per_frame[max(0, t + 1 - window): t + 1].sum(axis=0)
    return out


def _per_frame_counts(spec: GLCMSpec, video: np.ndarray) -> np.ndarray:
    plan = compile_plan(spec, video.shape[1:])
    return np.stack([np.asarray(plan(jnp.asarray(f))) for f in video])


# ---------------------------------------------------------------------------
# Bit-exactness: rolling window vs full recompute
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("accum", ["auto", "int"])
@pytest.mark.parametrize(
    "region_kw",
    [
        {},
        {"region": "tiles", "region_shape": (10, 8)},
        {"region": "window", "region_shape": 12, "region_stride": 8},
    ],
    ids=["global", "tiles", "window"],
)
def test_rolling_bit_exact_vs_recompute(region_kw, accum):
    video = _video()
    spec = GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="onehot",
                    accum=accum, **region_kw)
    plan = compile_plan(spec, SHAPE, temporal_window=WINDOW)
    ref = _windowed_sums(_per_frame_counts(spec, video), WINDOW)
    got = np.asarray(plan.rolling(jnp.asarray(video)))
    np.testing.assert_array_equal(got, ref)


def test_symmetric_normalize_tail_applies_to_accumulated_counts():
    """symmetric/normalize must act on the WINDOW's counts (lazily, after
    accumulation) — not be baked into the per-frame deltas."""
    video = _video()
    raw = GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="onehot")
    plan = compile_plan(
        raw.replace(symmetric=True, normalize=True), SHAPE,
        temporal_window=WINDOW,
    )
    counts = _windowed_sums(_per_frame_counts(raw, video), WINDOW)
    sym = counts + np.swapaxes(counts, -1, -2)
    ref = sym / np.maximum(sym.sum(axis=(-1, -2), keepdims=True), 1e-12)
    got = np.asarray(plan.rolling(jnp.asarray(video)))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize(
    "scheme", ["scatter", "onehot", "blocked", "native", "pallas",
               "pallas_fused"]
)
def test_all_schemes_agree(scheme):
    """Every 2-D backend serves the stream path; all agree bit-exactly."""
    video = _video(t=WINDOW + 3)
    ref_plan = compile_plan(
        GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="onehot"), SHAPE,
        temporal_window=WINDOW,
    )
    plan = compile_plan(
        GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme=scheme), SHAPE,
        temporal_window=WINDOW,
    )
    np.testing.assert_array_equal(
        np.asarray(plan.rolling(jnp.asarray(video))),
        np.asarray(ref_plan.rolling(jnp.asarray(video))),
    )


def test_fused_quantize_stream_matches_prequantized():
    """Raw float frames through the fused quantize→delta path must match
    quantizing on the host first and streaming the int frames."""
    rng = np.random.default_rng(3)
    raw = rng.random((WINDOW + 4, *SHAPE), dtype=np.float32) * 255.0
    spec = GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="pallas_fused",
                    quantize="uniform", vrange=(0.0, 255.0))
    plan = compile_plan(spec, SHAPE, temporal_window=WINDOW)
    got = np.asarray(plan.rolling(jnp.asarray(raw)))

    pre = np.clip(
        np.floor(raw / 255.0 * LEVELS), 0, LEVELS - 1
    ).astype(np.int32)
    ref_plan = compile_plan(
        GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="onehot"), SHAPE,
        temporal_window=WINDOW,
    )
    np.testing.assert_array_equal(got, np.asarray(ref_plan.rolling(pre)))


def test_online_stepping_equals_scan():
    video = _video()
    spec = GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="onehot",
                    normalize=True)
    plan = compile_plan(spec, SHAPE, features=True, temporal_window=WINDOW)
    rolled = np.asarray(plan.rolling(jnp.asarray(video)))
    counts = _windowed_sums(
        _per_frame_counts(spec.replace(normalize=False), video), WINDOW)
    state = plan.init_state()
    for t, frame in enumerate(video):
        state, out = plan.update(state, jnp.asarray(frame))
        np.testing.assert_array_equal(np.asarray(state.counts), counts[t])
        np.testing.assert_allclose(np.asarray(out), rolled[t], **FEATURE_TOL)
    assert int(state.seen) == T


# ---------------------------------------------------------------------------
# Ring-buffer mechanics
# ---------------------------------------------------------------------------


def test_ring_wraparound_long_stream():
    """stream_step alone, driven far past several ring turnovers: counts
    must equal the sliding-window sum and pos must cycle mod window."""
    rng = np.random.default_rng(1)
    deltas = rng.integers(0, 100, (23, 2, 5, 5)).astype(np.int32)
    window = 3
    state = init_state(window, (), 2, 5)
    step = jax.jit(lambda s, d: stream_step(s, d, window))
    for t, d in enumerate(deltas):
        state = step(state, jnp.asarray(d))
        expect = deltas[max(0, t + 1 - window): t + 1].sum(axis=0)
        np.testing.assert_array_equal(np.asarray(state.counts), expect)
        assert int(state.pos) == (t + 1) % window
        assert int(state.seen) == t + 1


def test_warmup_counts_are_partial_sums():
    """Before the ring fills, counts are the exact sum of ALL frames seen."""
    video = _video(t=WINDOW - 1)
    spec = GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="onehot")
    plan = compile_plan(spec, SHAPE, temporal_window=WINDOW)
    per = _per_frame_counts(spec, video)
    got = np.asarray(plan.rolling(jnp.asarray(video)))
    np.testing.assert_array_equal(got, np.cumsum(per, axis=0))


# ---------------------------------------------------------------------------
# (De)serialization / checkpoint-resume
# ---------------------------------------------------------------------------


def test_state_roundtrip_mid_stream(tmp_path):
    video = _video()
    spec = GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="onehot")
    plan = compile_plan(spec, SHAPE, temporal_window=WINDOW)
    full = np.asarray(plan.rolling(jnp.asarray(video)))

    cut = WINDOW + 2  # past the first wraparound
    _, state = plan.rolling(jnp.asarray(video[:cut]), return_state=True)

    # dict round-trip re-pins dtypes to the signed-int32 contract
    sd = state.state_dict()
    assert all(isinstance(v, np.ndarray) for v in sd.values())
    revived = GLCMStreamState.from_state_dict(
        {k: v.astype(np.float64) for k, v in sd.items()}
    )
    assert revived.counts.dtype == jnp.int32
    assert revived.ring.dtype == jnp.int32

    # npz round-trip, then resume: the tail must match the uninterrupted run
    path = tmp_path / "stream.npz"
    state.save(path)
    loaded = GLCMStreamState.load(path)
    assert loaded.window == WINDOW
    tail = plan.rolling(jnp.asarray(video[cut:]), init=loaded)
    np.testing.assert_array_equal(np.asarray(tail), full[cut:])


def test_state_is_a_pytree():
    state = init_state(WINDOW, (), len(PAIRS), LEVELS)
    leaves, tree = jax.tree_util.tree_flatten(state)
    assert len(leaves) == 4
    rebuilt = jax.tree_util.tree_unflatten(tree, leaves)
    assert isinstance(rebuilt, GLCMStreamState)
    assert rebuilt.window == WINDOW


# ---------------------------------------------------------------------------
# compile_plan surface
# ---------------------------------------------------------------------------


def test_compile_plan_validates_temporal_args():
    spec = GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="onehot")
    with pytest.raises(ValueError, match="temporal_window"):
        compile_plan(spec, SHAPE, temporal_window=0)
    with pytest.raises(ValueError, match="unbatched frames"):
        compile_plan(spec, (2, *SHAPE), temporal_window=WINDOW)


def test_stream_plans_cache_separately_from_batch_plans():
    plan_cache_clear()
    spec = GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="onehot")
    stream = compile_plan(spec, SHAPE, temporal_window=WINDOW)
    batch = compile_plan(spec, SHAPE)
    assert stream is not batch
    assert compile_plan(spec, SHAPE, temporal_window=WINDOW) is stream
    assert compile_plan(spec, SHAPE, temporal_window=WINDOW + 1) is not stream


def test_rolling_rejects_wrong_frame_shape():
    spec = GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="onehot")
    plan = compile_plan(spec, SHAPE, temporal_window=WINDOW)
    with pytest.raises(ValueError, match="stream plan"):
        plan.rolling(jnp.zeros((5, 8, 8), jnp.int32))


# ---------------------------------------------------------------------------
# Streaming pipeline + serving sessions
# ---------------------------------------------------------------------------


def test_glcm_feature_stream_temporal_mode():
    video = _video()
    spec = GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="onehot",
                    normalize=True)
    plan = compile_plan(spec, SHAPE, features=True, temporal_window=WINDOW)
    ref = np.asarray(plan.rolling(jnp.asarray(video)))
    outs = list(glcm_feature_stream(iter(video), spec=spec,
                                    temporal_window=WINDOW))
    assert len(outs) == T
    np.testing.assert_allclose(
        np.stack([np.asarray(o) for o in outs]), ref, **FEATURE_TOL)
    with pytest.raises(ValueError, match="batch_size must be 1"):
        list(glcm_feature_stream(iter(video), spec=spec,
                                 temporal_window=WINDOW, batch_size=2))


def test_engine_stream_sessions_and_checkpoint():
    video = _video()
    spec = GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="onehot",
                    normalize=True)
    cfg = GLCMServeConfig(spec=spec, image_shape=SHAPE, batch_size=2,
                          temporal_window=WINDOW)
    eng = GLCMEngine(cfg)
    ref = np.asarray(eng.stream_plan.rolling(jnp.asarray(video)))

    sid = eng.open_stream()
    cut = WINDOW + 1
    counts = _windowed_sums(
        _per_frame_counts(spec.replace(normalize=False), video), WINDOW)
    for t in range(cut):
        np.testing.assert_allclose(eng.push(sid, video[t]), ref[t], **FEATURE_TOL)
    state = eng.close_stream(sid)
    np.testing.assert_array_equal(np.asarray(state.counts), counts[cut - 1])
    with pytest.raises(KeyError):
        eng.push(sid, video[0])

    # resume from the checkpoint (as a state_dict) in a NEW session
    sid2 = eng.open_stream(state=state.state_dict())
    for t in range(cut, T):
        np.testing.assert_allclose(eng.push(sid2, video[t]), ref[t], **FEATURE_TOL)
    assert eng.frames_streamed == T

    # the one-shot batch path still serves alongside the sessions
    assert eng.map(video[:2]).shape[0] == 2

    # validation is shared with submit: malformed frames fail at push time
    with pytest.raises(ValueError, match="frame shape"):
        eng.push(sid2, video[0][:-1])
    np.testing.assert_array_equal(
        np.asarray(eng.close_stream(sid2).counts), counts[T - 1])


def test_engine_stream_guards():
    spec = GLCMSpec(levels=LEVELS, pairs=PAIRS, scheme="onehot")
    plain = GLCMEngine(GLCMServeConfig(spec=spec, image_shape=SHAPE,
                                       batch_size=2))
    assert plain.stream_plan is None
    with pytest.raises(ValueError, match="temporal_window"):
        plain.open_stream()

    with pytest.raises(ValueError, match="temporal_window"):
        GLCMServeConfig(spec=spec, image_shape=SHAPE, temporal_window=0)

    eng = GLCMEngine(GLCMServeConfig(spec=spec, image_shape=SHAPE,
                                     batch_size=2, temporal_window=WINDOW))
    other = init_state(WINDOW + 2, (), len(PAIRS), LEVELS)
    with pytest.raises(ValueError, match="window"):
        eng.open_stream(state=other)
