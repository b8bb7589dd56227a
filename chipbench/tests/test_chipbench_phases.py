"""chipbench.phases: the program's engine-phase spans read from a profiler
trace, the idle time put down to them, and the harness's own reading of a
trace left as it was, on a hand-made trace, on the engine traced on the
CPU, and on traces recorded on a TPU v5e (16384^2 closed-loop cell)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

from chipbench import phases, trace  # noqa: E402
from chipbench.metrics import reader  # noqa: E402
from chipbench.run import Context  # noqa: E402
from cpu_cells import shrink  # noqa: E402

FIXTURES = Path(__file__).parent / "fixtures"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def hand_made():
    return {
        "devices": [{"name": "/device:TPU:0", "ops": [
            ["convert.1", 12, 8],                 # 12..20
            ["glcm_fused_pallas.1", 60, 20],      # 60..80
        ]}],
        "host": [["window", 10, 90], ["submit", 10, 75], ["result", 85, 10]],
        "program": [
            ["glcm.dispatch", 20, 62],            # 20..82
            ["glcm.pad", 22, 28],                 # 22..50
            ["glcm.h2d", 50, 6],                  # 50..56
            ["glcm.launch", 56, 24],              # 56..80
            ["glcm.readback", 80, 1],             # 80..81
            ["glcm.stream_push", 95, 10],         # 95..105, clipped to 95..100
        ],
    }


def test_hand_made_trace():
    t = hand_made()
    r = trace.reduce(t)
    # idle: 10..12 (submit), 20..60 (submit), 80..100 (result: 85..95
    # overlaps it more than submit's 80..85)
    assert r.busy_ns == 28 and r.window_ns == 90
    assert phases.span_ns(t) == {"glcm.dispatch": 62, "glcm.pad": 28,
                                 "glcm.h2d": 6, "glcm.launch": 24,
                                 "glcm.readback": 1, "glcm.stream_push": 5}
    assert phases.dispatch_self_ns(phases.span_ns(t)) == 62 - 28 - 6 - 24 - 1
    by_label = dict(phases.idle_by_label(t, r))
    # each idle piece goes to the innermost span open over it: dispatch
    # 20..22 and 81..82, pad 22..50, h2d 50..56, launch 56..60, readback
    # 80..81, stream_push 95..100; the rest keeps its gap's harness label
    # (10..12 submit; 82..95 result)
    assert by_label == {"glcm.pad": 28, "glcm.h2d": 6, "glcm.launch": 4,
                        "glcm.dispatch": 3, "glcm.readback": 1,
                        "glcm.stream_push": 5, "submit": 2, "result": 13}
    assert sum(by_label.values()) == r.window_ns - r.busy_ns


def test_spans_that_do_not_nest_are_refused():
    t = hand_made()
    t["program"].append(["glcm.h2d", 70, 20])   # 70..90 crosses the dispatch
    with pytest.raises(ValueError, match="not nested"):
        phases.idle_by_label(t, trace.reduce(t))


def load(name):
    return json.loads((FIXTURES / name).read_text())


def context(t, served):
    return Context(cell={}, config={}, records=[None] * served, served=served,
                   phase_ms={"pad": 0.0, "launch": 0.0, "readback": 0.0},
                   trace=trace.reduce(t), device_kind="TPU v5 lite",
                   work=(0, 0))


def test_trace_without_program_spans_reads_as_before():
    """The parent's program writes no ``repro.*`` spans: the idle time keeps
    the harness's labels, to the nanosecond, and the phase metrics are left
    out rather than read as 0."""
    t = load("trace_16k_closed.json")
    r = trace.reduce(t)
    assert phases.span_ns(t) == {}
    assert phases.idle_by_label(t, r) == r.gap_totals()
    out = phases.summarize(t, requests=7)
    assert "h2d_ms" not in out and "engine_self_ms" not in out
    assert out["idle_gaps"] == out["harness_idle_gaps"]


def with_program_spans(t):
    """The trace with a dispatch span and its four phases inside each of the
    harness's submit spans, as the engine writes them."""
    program = []
    for name, s, d in t["host"]:
        if name == "submit":
            cuts = [s + d * f for f in (0.01, 0.02, 0.72, 0.91, 0.92, 0.99)]
            program.append([phases.DISPATCH, cuts[0], cuts[-1] - cuts[0]])
            program += [[p, a, b - a] for p, a, b in zip(phases.PHASES, cuts[1:], cuts[2:])]
    return dict(t, program=program)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_program_spans_leave_the_harness_readings_unchanged(metric):
    """Every reader of the benchmark reads a trace with the program's spans
    exactly as it reads the same trace without them."""
    without = load("trace_16k_closed.json")
    with_spans = with_program_spans(without)
    assert phases.span_ns(with_spans)
    read = reader(metric)
    assert read(context(with_spans, 7)) == read(context(without, 7))
    assert trace.reduce(with_spans).gap_totals() == trace.reduce(without).gap_totals()


def test_recorded_chip_trace_with_spans():
    """Three seconds of the 16384^2 cell on a TPU v5e, engine phases on: the
    idle time lies in the host pad and copy, and each dispatch's own
    bookkeeping is small."""
    t = load("trace_16k_closed_spans.json")
    r = trace.reduce(t)
    t0, t1 = phases._window(t)
    whole = [(s, s + d) for n, s, d in t["program"]
             if n == phases.DISPATCH and t0 <= s and s + d <= t1]
    assert len(whole) >= 5
    # whole dispatches only: the phases of the ones cut by the window edges
    # are left out by cutting the trace to the first and last whole one
    inner = dict(t, host=[["window", whole[0][0], whole[-1][1] - whole[0][0]]]
                 + [h for h in t["host"] if h[0] != "window"])
    spans = phases.span_ns(inner)
    n = len(whole)
    h2d_ms = spans["glcm.h2d"] / 1e6 / n
    pad_ms = spans["glcm.pad"] / 1e6 / n
    self_ms = phases.dispatch_self_ns(spans) / 1e6 / n
    assert 40 < h2d_ms < 120 and 200 < pad_ms < 400
    assert 0 <= self_ms < 2
    by_label = dict(phases.idle_by_label(t, r))
    assert [k for k, _ in phases.idle_by_label(t, r)[:2]] == ["glcm.pad", "glcm.h2d"]
    assert np.isclose(sum(by_label.values()), r.window_ns - r.busy_ns)
    assert 0.8 < r.idle_share < 0.95


def test_engine_phases_on_the_cpu_profiler(tmp_path):
    """The engine at 64^2 under a CPU capture, read back as the harness
    reads a chip trace: one dispatch span around pad, h2d, launch and
    readback per batch, in order, inside the harness's submit spans; the
    harness's own loader sees none of them."""
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
    path = trace.find_xplane(str(tmp_path))
    loaded = trace.load(path)
    assert {h[0] for h in loaded["host"]} == {"window", "submit", "result"}
    program = phases.load_program(path)
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
    cpu = {**loaded, "program": program, "devices": [{"name": "cpu", "ops": []}]}
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
