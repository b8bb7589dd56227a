"""chipbench.trace.reduce: busy and idle time, kernel time, the labels of
idle gaps, the program's span time and the idle time put down to its spans,
on small hand-made traces whose answers are known and on three seconds of
traces recorded on a TPU v5e (16384^2 closed-loop cell); and the readers
of the benchmark on them."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import trace  # noqa: E402
from chipbench.metrics import ANY_KERNEL, KERNELS, reader  # noqa: E402
from chipbench.run import Context  # noqa: E402

FIXTURES = Path(__file__).parent / "fixtures"
FIXTURE = FIXTURES / "trace_16k_closed.json"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
DISPATCH = "glcm.dispatch"
PHASES = ("glcm.pad", "glcm.h2d", "glcm.launch", "glcm.readback")


def load(name):
    return json.loads((FIXTURES / name).read_text())


def hand_made():
    """A window with one dispatch: the engine's spans under "program"."""
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
    t = {
        "devices": [{"name": "/device:TPU:0", "ops": [
            ["convert.1", 5, 10],                 # clipped to the window: 10..15
            ["glcm_fused_pallas.1", 20, 30],      # 20..50
            ["fusion.2", 40, 20],                 # 40..60, overlaps the kernel
            ["glcm_fused_pallas.1", 80, 10],      # 80..90
            ["fusion.3", 95, 20],                 # clipped: 95..100
        ]}],
        "host": [["window", 10, 90], ["submit", 10, 45], ["result", 55, 10],
                 ["wait", 65, 30]],
    }
    r = trace.reduce(t)
    assert r.window_ns == 90
    assert r.busy_ns == 5 + 40 + 10 + 5
    assert r.idle_share == pytest.approx(30 / 90)
    assert r.matching_ns(KERNELS["fused"]) == 40
    assert r.op_ns["convert.1"] == 5 and r.op_ns["fusion.3"] == 5
    # gaps 15..20 (submit), 60..80 (wait 65..80 beats result 60..65),
    # 90..95 (wait)
    assert sorted(r.gaps, key=lambda g: g[1]) == [("submit", 5), ("wait", 5), ("wait", 20)]
    assert dict(r.gap_totals()) == {"wait": 25, "submit": 5}


def test_union_over_two_devices_is_averaged():
    t = {"devices": [{"name": "a", "ops": [["k", 0, 10]]},
                     {"name": "b", "ops": [["k", 0, 4], ["k", 2, 4]]}],
         "host": [["window", 0, 20]]}
    r = trace.reduce(t)
    assert r.busy_ns == (10 + 6) / 2 and r.n_devices == 2
    assert [label for label, _ in r.gaps] == ["other", "other"]


def test_window_span_is_required():
    with pytest.raises(ValueError, match="window"):
        trace.reduce({"devices": [{"name": "a", "ops": []}], "host": []})


def sweep_busy(ops, t0, t1):
    """Busy ns by an event sweep over sorted interval edges."""
    edges = []
    for _, s, d in ops:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort()
    busy, depth, last = 0.0, 0, None
    for x, step in edges:
        if depth > 0:
            busy += x - last
        depth += step
        last = x
    return busy


def test_recorded_chip_trace():
    t = json.loads(FIXTURE.read_text())
    r = trace.reduce(t)
    (w,) = [h for h in t["host"] if h[0] == "window"]
    ops = t["devices"][0]["ops"]
    assert r.window_ns == w[2] == 3e9
    assert r.busy_ns == pytest.approx(sweep_busy(ops, w[1], w[1] + w[2]))
    kernel = sum(min(s + d, w[1] + w[2]) - max(s, w[1]) for n, s, d in ops
                 if n.startswith("glcm_fused_pallas"))
    assert r.matching_ns(KERNELS["fused"]) == pytest.approx(kernel)
    assert r.matching_ns(ANY_KERNEL) == pytest.approx(kernel)
    assert r.matching_ns(r"glcm_volume_pallas(\.\d+)?$") == 0
    # one 16384^2 request is ~38 ms of kernel and ~0.4 s of host work: the
    # device sits idle most of the window, while the client is in submit()
    assert 0.8 < r.idle_share < 0.95
    assert r.gap_totals()[0][0] == "submit"
    assert np.isclose(sum(ns for _, ns in r.gaps), r.window_ns - r.busy_ns)
    assert r.top_ops(1)[0][0] == "glcm_fused_pallas.1"


def test_hand_made_trace_with_program_spans():
    t = hand_made()
    r = trace.reduce(t)
    # idle: 10..12 (submit), 20..60 (submit), 80..100 (result: 85..95
    # overlaps it more than submit's 80..85)
    assert r.busy_ns == 28 and r.window_ns == 90
    assert r.span_ns == trace.span_ns(t) == {
        "glcm.dispatch": 62, "glcm.pad": 28, "glcm.h2d": 6, "glcm.launch": 24,
        "glcm.readback": 1, "glcm.stream_push": 5}
    by_label = dict(r.idle_by_label())
    # each idle piece goes to the innermost span open over it: dispatch
    # 20..22 and 81..82, pad 22..50, h2d 50..56, launch 56..60, readback
    # 80..81, stream_push 95..100; the rest keeps its gap's harness label
    # (10..12 submit; 82..95 result)
    assert by_label == {"glcm.pad": 28, "glcm.h2d": 6, "glcm.launch": 4,
                        "glcm.dispatch": 3, "glcm.readback": 1,
                        "glcm.stream_push": 5, "submit": 2, "result": 13}
    assert sum(by_label.values()) == r.window_ns - r.busy_ns
    # the harness's own labels are as they were without program spans
    assert r.gap_totals() == trace.reduce(dict(t, program=[])).gap_totals()


def test_spans_that_do_not_nest_are_refused():
    t = hand_made()
    t["program"].append(["glcm.h2d", 70, 20])   # 70..90 crosses the dispatch
    with pytest.raises(ValueError, match="not nested"):
        trace.reduce(t)


def context(t, served):
    r = trace.reduce(t)
    return Context(cell={}, config={}, records=[None] * served, served=served,
                   phase_ms={"pad": 0.0, "launch": 0.0, "readback": 0.0},
                   trace=r, device_kind="TPU v5 lite", work=(0, 0), span_ns=r.span_ns)


def with_program_spans(t):
    """The trace with a dispatch span and its four phases inside each of the
    harness's submit spans, as the engine writes them."""
    program = []
    for name, s, d in t["host"]:
        if name == "submit":
            cuts = [s + d * f for f in (0.01, 0.02, 0.72, 0.91, 0.92, 0.99)]
            program.append([DISPATCH, cuts[0], cuts[-1] - cuts[0]])
            program += [[p, a, b - a] for p, a, b in zip(PHASES, cuts[1:], cuts[2:])]
    return dict(t, program=program)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_program_spans_leave_the_harness_readings_unchanged(metric):
    """Every reader of the benchmark that reads something in a trace
    without the program's spans reads the same trace with them exactly as
    before; a reader of the spans reads nothing without them."""
    without = load("trace_16k_closed.json")
    with_spans = with_program_spans(without)
    assert trace.span_ns(with_spans)
    read = reader(metric)
    before = read(context(without, 7))
    if before is None:
        assert read(context(with_spans, 7)) is not None
    else:
        assert read(context(with_spans, 7)) == before
    assert trace.reduce(with_spans).gap_totals() == trace.reduce(without).gap_totals()


def whole_dispatches(t):
    """The trace cut to its first and last dispatch wholly inside the
    window, and the number of dispatches in it: the phases of the ones cut
    by the window's edges are left out."""
    t0, t1 = trace._window(t)
    whole = [(s, s + d) for n, s, d in t["program"]
             if n == DISPATCH and t0 <= s and s + d <= t1]
    inner = dict(t, host=[["window", whole[0][0], whole[-1][1] - whole[0][0]]]
                 + [h for h in t["host"] if h[0] != "window"])
    return inner, len(whole)


def test_h2d_reader_on_a_recorded_chip_trace():
    """Three seconds of the 16384^2 cell on a TPU v5e with the engine's
    spans: ``h2d_ms.closed`` reads the copy's span time per request served,
    the copy of a 268 MB image at tens of ms."""
    inner, n = whole_dispatches(load("trace_16k_closed_spans.json"))
    assert n >= 5
    ctx = context(inner, n)
    h2d = reader("h2d_ms.closed")(ctx)
    copies = [d for name, s, d in inner["program"] if name == "glcm.h2d"
              and inner["host"][0][1] <= s and s + d <= inner["host"][0][1] + inner["host"][0][2]]
    assert len(copies) == n
    assert h2d == pytest.approx(sum(copies) / 1e6 / n, rel=1e-12)
    assert 40 < h2d < 120
    assert reader("h2d_ms.closed")(context(load("trace_16k_closed.json"), 7)) is None


def test_idle_by_label_on_a_recorded_chip_trace():
    """The same slice: the idle time lies in the host pad and copy (the
    program as it stood when it was recorded), it adds up to the window's
    idle time, and the harness's labels, which the program's spans refine,
    put all of it in submit."""
    t = load("trace_16k_closed_spans.json")
    r = trace.reduce(t)
    labels = r.idle_by_label()
    assert [k for k, _ in labels[:2]] == ["glcm.pad", "glcm.h2d"]
    assert np.isclose(sum(ns for _, ns in labels), r.window_ns - r.busy_ns)
    assert r.gap_totals()[0][0] == "submit"
    phase_idle = sum(ns for k, ns in labels if k.startswith("glcm."))
    assert phase_idle > 0.9 * (r.window_ns - r.busy_ns)
