"""chipbench.trace.reduce: busy and idle time, kernel time and the labels of
idle gaps, on a small hand-made trace whose answers are known and on three
seconds of a trace recorded on a TPU v5e (16384^2 closed-loop cell)."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import trace  # noqa: E402
from chipbench.metrics import ANY_KERNEL, KERNELS  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "trace_16k_closed.json"


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
