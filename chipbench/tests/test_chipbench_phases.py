"""chipbench.phases: the breakdown of one traced window by the program's
engine-phase spans (span time, dispatch self time, idle time by phase), on
a hand-made trace, on the engine traced on the CPU, and on traces recorded
on a TPU v5e (16384^2 closed-loop cell). The span readings themselves are
``chipbench.trace``'s, tested in ``test_chipbench_trace.py``."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

from chipbench import phases, trace  # noqa: E402
from cpu_cells import shrink  # noqa: E402
from test_chipbench_trace import hand_made, load  # noqa: E402


def test_hand_made_trace():
    """The dispatch span's self time is its time less its four phases; the
    breakdown carries the trace's span time and idle split per request."""
    t = hand_made()
    spans = trace.reduce(t).span_ns
    assert phases.dispatch_self_ns(spans) == 62 - 28 - 6 - 24 - 1
    out = phases.summarize(t, requests=2)
    assert out["engine_self_ms"] == 3 / 1e6 / 2 and out["h2d_ms"] == 6 / 1e6 / 2
    assert out["span_ms"]["glcm.pad"] == 28 / 1e6 / 2
    assert out["idle_gaps"][0] == ["glcm.pad", 28 / 1e9]
    assert out["harness_idle_gaps"] == [["submit", 42 / 1e9], ["result", 20 / 1e9]]
    assert out["device_idle_pct"] == pytest.approx(100 * 62 / 90)


def test_trace_without_program_spans_reads_as_before():
    """The parent's program writes no ``repro.*`` spans: the idle time keeps
    the harness's labels, to the nanosecond, and the phase metrics are left
    out rather than read as 0."""
    t = load("trace_16k_closed.json")
    r = trace.reduce(t)
    assert r.span_ns == {} and trace.span_ns(t) == {}
    assert r.idle_by_label() == r.gap_totals()
    out = phases.summarize(t, requests=7)
    assert "h2d_ms" not in out and "engine_self_ms" not in out
    assert out["idle_gaps"] == out["harness_idle_gaps"]


def test_recorded_chip_trace_with_spans():
    """Three seconds of the 16384^2 cell on a TPU v5e, engine phases on: the
    idle time lies in the host pad and copy, and each dispatch's own
    bookkeeping is small."""
    t = load("trace_16k_closed_spans.json")
    r = trace.reduce(t)
    t0, t1 = trace._window(t)
    whole = [(s, s + d) for n, s, d in t["program"]
             if n == phases.DISPATCH and t0 <= s and s + d <= t1]
    assert len(whole) >= 5
    # whole dispatches only: the phases of the ones cut by the window edges
    # are left out by cutting the trace to the first and last whole one
    inner = dict(t, host=[["window", whole[0][0], whole[-1][1] - whole[0][0]]]
                 + [h for h in t["host"] if h[0] != "window"])
    spans = trace.span_ns(inner)
    n = len(whole)
    h2d_ms = spans["glcm.h2d"] / 1e6 / n
    pad_ms = spans["glcm.pad"] / 1e6 / n
    self_ms = phases.dispatch_self_ns(spans) / 1e6 / n
    assert 40 < h2d_ms < 120 and 200 < pad_ms < 400
    assert 0 <= self_ms < 2
    by_label = dict(r.idle_by_label())
    assert [k for k, _ in r.idle_by_label()[:2]] == ["glcm.pad", "glcm.h2d"]
    assert np.isclose(sum(by_label.values()), r.window_ns - r.busy_ns)
    assert 0.8 < r.idle_share < 0.95


def test_engine_phases_on_the_cpu_profiler(tmp_path):
    """The engine at 64^2 under a CPU capture, read back as the harness
    reads a chip trace: one dispatch span around pad, h2d, launch and
    readback per batch, in order, inside the harness's submit spans; the
    loader keeps them apart from the harness's own host spans."""
    import jax

    from repro.core.spec import GLCMSpec
    from repro.serve.engine import GLCMEngine, GLCMServeConfig

    eng = GLCMEngine(GLCMServeConfig(
        spec=GLCMSpec(levels=8, pairs=((1, 0),)), image_shape=(64, 64),
        batch_size=2, buckets=(2,)))
    imgs = np.random.default_rng(0).integers(0, 256, (6, 64, 64), np.uint8)
    eng.warmup(dtype=np.uint8)

    def span(label):
        return jax.profiler.TraceAnnotation(trace.PREFIX + label)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span(trace.WINDOW):
            for i in range(0, 6, 2):
                with span("submit"):
                    tickets = [eng.submit(im) for im in imgs[i:i + 2]]
                with span("result"):
                    [eng.result(tk) for tk in tickets]
    finally:
        jax.profiler.stop_trace()
    loaded = trace.load(trace.find_xplane(str(tmp_path)))
    assert {h[0] for h in loaded["host"]} == {"window", "submit", "result"}
    program = loaded["program"]
    assert {p[0] for p in program} == {phases.DISPATCH, *phases.PHASES}
    submits = [(s, s + d) for n, s, d in loaded["host"] if n == "submit"]
    dispatches = sorted((s, s + d) for n, s, d in program if n == phases.DISPATCH)
    assert len(dispatches) == len(submits) == 3
    for (ds, de), (ss, se) in zip(dispatches, submits):
        assert ss <= ds and de <= se
        inside = sorted((s, n) for n, s, d in program
                        if n != phases.DISPATCH and ds <= s and s + d <= de)
        assert [n for _, n in inside] == list(phases.PHASES)
    # a CPU capture has no device plane: stand in one that never works, so
    # that the whole window is idle and goes to the spans open over it
    cpu = {**loaded, "devices": [{"name": "cpu", "ops": []}]}
    out = phases.summarize(cpu, requests=6)
    assert out["h2d_ms"] > 0 and out["engine_self_ms"] >= 0
    assert out["device_idle_pct"] == 100.0
    by_label = dict(out["idle_gaps"])
    assert {"glcm.pad", "glcm.h2d", "glcm.launch", "submit"} <= set(by_label)
    assert sum(by_label.values()) == pytest.approx(out["window_s"])


def test_cell_window_at_small_size(monkeypatch):
    """``trace_window`` drives a cell as ``run.py --trace 1`` does, shrunk
    to the CPU: one dispatch span per request of the closed loop's single
    client, inside the harness's window."""
    shrink(monkeypatch)
    loaded, requests = phases.trace_window("paper_table3.16k_closed", 2**40 + 23, 0.5)
    assert requests > 0
    cpu = dict(loaded, devices=[{"name": "cpu", "ops": []}])
    out = phases.summarize(cpu, requests)
    assert set(out["span_ms"]) == {phases.DISPATCH, *phases.PHASES}
    assert sum(n == phases.DISPATCH for n, _, _ in loaded["program"]) == requests
    assert out["h2d_ms"] > 0 and 0 <= out["engine_self_ms"] < out["span_ms"][phases.DISPATCH]


def test_cli_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/phases.py", "--workload", "paper_table3.16k_closed",
         "--seed", str(2**40 + 1), "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
