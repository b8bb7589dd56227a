"""Observability threaded through the stack: a submit() correlation ID
traceable end-to-end as one span tree, per-phase dispatch stats, the
flight recorder firing on shed/dispatch failures, plan-cache and
autotuner instrumentation, and the ``repro.obs.report`` CLI."""

import json

import numpy as np
import pytest

from repro.core import autotune
from repro.core.plan import compile_plan, plan_cache_clear
from repro.core.spec import GLCMSpec
from repro.obs import report as obs_report
from repro.obs.metrics import get_registry
from repro.obs.trace import Tracer, set_tracer
from repro.serve.engine import GLCMEngine, GLCMServeConfig, QueueFullError

RNG = np.random.default_rng(3)
SHAPE = (32, 32)
IMGS = RNG.random((16, *SHAPE), np.float32)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, ms):
        self.t += ms * 1e-3


def _cfg(**kw):
    kw.setdefault("levels", 8)
    kw.setdefault("image_shape", SHAPE)
    kw.setdefault("pairs", ((1, 0),))
    return GLCMServeConfig(**kw)


@pytest.fixture
def tracer():
    """A live tracer installed globally (so compile_plan/autotune spans
    are captured too), restored afterwards."""
    tr = Tracer(enabled=True)
    prev = set_tracer(tr)
    yield tr
    set_tracer(prev)


# ---------------------------------------------------------------------------
# end-to-end request span trees
# ---------------------------------------------------------------------------


def test_submit_correlation_id_traceable_end_to_end(tracer):
    """One submit() ticket = one span tree: queue wait, padding, the
    host→device copy, launch (device-synced), readback — every span
    carrying the ticket as its correlation id, children linked to the
    request root."""
    clock = FakeClock()
    eng = GLCMEngine(_cfg(batch_size=4), clock=clock, tracer=tracer)
    tickets = []
    for i in range(4):
        tickets.append(eng.submit(IMGS[i]))
        clock.advance(1.0)

    spans = tracer.spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)

    # submit() marked each arrival with an instant carrying the ticket
    assert [s.attrs["ticket"] for s in by_name["glcm.submit"]] == tickets

    # one request tree per ticket, phases parented to the root
    roots = {s.corr: s for s in by_name["glcm.request"]}
    assert sorted(roots) == sorted(tickets)
    for t in tickets:
        root = roots[t]
        children = [s for s in spans
                    if s.parent == root.id and s.corr == t]
        names = {s.name for s in children}
        assert names == {"glcm.queue_wait", "glcm.pad", "glcm.h2d",
                         "glcm.launch", "glcm.readback"}
        assert len(children) == 5
        phases = {s.name: s for s in children}
        # contiguous phase boundaries: wait→pad→h2d→launch→readback
        assert root.t0 == phases["glcm.queue_wait"].t0
        assert phases["glcm.queue_wait"].t1 == phases["glcm.pad"].t0
        assert phases["glcm.pad"].t1 == phases["glcm.h2d"].t0
        assert phases["glcm.h2d"].t1 == phases["glcm.launch"].t0
        assert phases["glcm.launch"].t1 == phases["glcm.readback"].t0
        assert phases["glcm.readback"].t1 == root.t1
        # the launch duration is device-synced (block_until_ready)
        assert phases["glcm.launch"].attrs["synced"] is True
        assert phases["glcm.launch"].attrs["backend"]

    # plus one live dispatch span, its four phases nested in order inside
    (disp,) = by_name["glcm.dispatch"]
    assert disp.attrs == {"workload": "default", "bucket": 4, "occupancy": 4,
                          "deadline": False}
    disp_children = [s for s in spans if s.parent == disp.id]
    assert [s.name for s in disp_children] == [
        "glcm.pad", "glcm.h2d", "glcm.launch", "glcm.readback"]
    assert disp.t0 <= disp_children[0].t0
    for a, b in zip(disp_children, disp_children[1:]):
        assert a.t1 <= b.t0
    assert disp_children[-1].t1 <= disp.t1

    # results still served normally
    assert eng.result(tickets[0]).shape[0] == 1


def test_untraced_engine_records_no_spans():
    tr = Tracer(enabled=False)
    eng = GLCMEngine(_cfg(batch_size=2), tracer=tr)
    eng.submit(IMGS[0])
    eng.submit(IMGS[1])
    assert len(tr) == 0


def test_deadline_dispatch_spans_marked(tracer):
    clock = FakeClock()
    eng = GLCMEngine(_cfg(batch_size=8, max_wait_ms=5.0), clock=clock,
                     tracer=tracer)
    t = eng.submit(IMGS[0])
    clock.advance(6.0)
    eng.poll()
    root = next(s for s in tracer.spans()
                if s.name == "glcm.request" and s.corr == t)
    assert root.attrs["deadline"] is True
    assert root.attrs["occupancy"] == 1


def test_stream_push_span_carries_stream_correlation(tracer):
    eng = GLCMEngine(_cfg(batch_size=2, temporal_window=2), tracer=tracer)
    sid = eng.open_stream()
    eng.push(sid, IMGS[0])
    eng.push(sid, IMGS[1])
    pushes = [s for s in tracer.spans() if s.name == "glcm.stream_push"]
    assert len(pushes) == 2
    assert {s.corr for s in pushes} == {f"stream-{sid}"}
    assert pushes[-1].attrs["frames_seen"] == 2


# ---------------------------------------------------------------------------
# per-phase stats and metrics
# ---------------------------------------------------------------------------


def test_stats_expose_per_phase_dispatch_breakdown():
    eng = GLCMEngine(_cfg(batch_size=2))
    eng.submit(IMGS[0])
    eng.submit(IMGS[1])
    w = eng.stats()["workloads"][0]
    for phase in ("pad_ms", "h2d_ms", "launch_ms", "readback_ms"):
        assert w[phase]["n"] == 1, phase
        assert w[phase]["p50"] >= 0.0
    # the four phases are disjoint parts of the service time
    phases_ms = sum(w[ph]["mean"] for ph in ("h2d_ms", "launch_ms",
                                              "readback_ms"))
    assert phases_ms == pytest.approx(w["service_ms"]["mean"])
    st = eng.stats()
    assert st["flight_records"] >= 1  # dispatch record always kept
    assert st["incidents"] == 0
    (rec,) = eng.flight.records()
    assert rec["kind"] == "dispatch" and rec["tickets"] == [0, 1]
    for phase in ("pad_ms", "h2d_ms", "launch_ms", "readback_ms"):
        assert rec[phase] >= 0.0, phase


def test_serve_metrics_populate_global_registry():
    reg = get_registry()
    reg.clear()
    eng = GLCMEngine(_cfg(batch_size=2))  # registers fresh series
    eng.submit(IMGS[0])
    eng.submit(IMGS[1])
    snap = reg.snapshot()
    by_labels = {tuple(sorted(s["labels"].items())): s["value"]
                 for s in snap["repro_serve_submitted_total"]["series"]}
    assert by_labels[(("workload", "default"),)] == 2
    assert snap["repro_serve_served_total"]["series"][0]["value"] == 2
    assert snap["repro_serve_batches_total"]["series"][0]["value"] == 1
    # a full batch of two is stacked, not handed over as one request's buffer
    assert snap["repro_serve_unstacked_batches_total"]["series"][0]["value"] == 0
    phase_series = snap["repro_serve_phase_ms"]["series"]
    phases = {s["labels"]["phase"] for s in phase_series}
    assert phases == {"queue", "pad", "h2d", "launch", "readback"}
    counts = {s["labels"]["phase"]: s["count"] for s in phase_series}
    assert counts == {"queue": 2, "pad": 1, "h2d": 1, "launch": 1,
                      "readback": 1}
    # scrape-ready exposition includes the histogram series
    assert "repro_serve_phase_ms_bucket" in reg.to_prometheus()


# ---------------------------------------------------------------------------
# flight recorder incidents
# ---------------------------------------------------------------------------


def test_queue_full_dumps_flight_recorder():
    eng = GLCMEngine(_cfg(batch_size=8, max_queue_depth=2))
    eng.submit(IMGS[0])
    eng.submit(IMGS[1])
    with pytest.raises(QueueFullError):
        eng.submit(IMGS[2])
    inc = eng.last_incident
    assert inc is not None
    assert "QueueFullError" in inc["reason"]
    assert inc["records"][-1]["kind"] == "shed"
    assert eng.stats()["incidents"] == 1


def test_dispatch_error_dumps_flight_recorder(monkeypatch):
    eng = GLCMEngine(_cfg(batch_size=2))
    eng.submit(IMGS[0])  # queued, no dispatch yet

    def boom(w, bucket):
        raise RuntimeError("device fell over")

    monkeypatch.setattr(eng, "_plan_for", boom)
    with pytest.raises(RuntimeError, match="device fell over"):
        eng.submit(IMGS[1])  # fills the batch → dispatch → failure
    inc = eng.last_incident
    assert inc is not None and "dispatch error" in inc["reason"]
    err = inc["records"][-1]
    assert err["kind"] == "dispatch_error"
    assert err["tickets"] == [0, 1]


# ---------------------------------------------------------------------------
# plan-cache and autotuner instrumentation
# ---------------------------------------------------------------------------


def test_plan_compile_and_cache_hit_instrumented(tracer):
    plan_cache_clear()
    reg = get_registry()
    reg.clear()
    spec = GLCMSpec(levels=8, pairs=((1, 0),))
    compile_plan(spec, (16, 16))   # miss → plan.compile span
    compile_plan(spec, (16, 16))   # hit → plan.cache_hit event
    names = [s.name for s in tracer.spans()]
    assert "plan.compile" in names
    assert "plan.cache_hit" in names
    comp = next(s for s in tracer.spans() if s.name == "plan.compile")
    assert comp.attrs["scheme"]  # the RESOLVED scheme, not "auto"
    assert comp.attrs["shape"] == "(16, 16)"
    snap = reg.snapshot()
    lookups = {s["labels"]["result"]: s["value"]
               for s in snap["repro_plan_cache_lookups_total"]["series"]}
    assert lookups == {"miss": 1, "hit": 1}
    assert snap["repro_plan_compile_ms"]["series"][0]["count"] == 1


def test_plan_lint_instrumented(tracer):
    plan_cache_clear()
    spec = GLCMSpec(levels=8, pairs=((1, 0),))
    compile_plan(spec, (16, 16), check="lint")
    lint = next(s for s in tracer.spans() if s.name == "plan.lint")
    assert lint.dur >= 0.0 and "findings" in lint.attrs


def test_autotune_emits_run_and_candidate_spans(tracer, tmp_path,
                                                monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_PATH", str(tmp_path / "tune.json"))
    autotune.autotune_clear()
    plan_cache_clear()
    reg = get_registry()
    reg.clear()
    spec = GLCMSpec(levels=8, pairs=((1, 0),), quantize="uniform")
    choice = autotune.autotune(spec, (16, 16), trials=1, persist=False)
    spans = tracer.spans()
    run = next(s for s in spans if s.name == "autotune.run")
    cands = [s for s in spans if s.name == "autotune.candidate"]
    assert cands, "every measured candidate records a span"
    assert run.attrs["winner"] == choice.backend
    assert run.attrs["candidates"] == len(cands)
    # candidate runtimes land in the µs-scale histogram, per backend
    snap = reg.snapshot()
    series = snap["repro_autotune_candidate_us"]["series"]
    assert sum(s["count"] for s in series) == len(cands)
    assert {s["labels"]["backend"] for s in series} <= {
        s.attrs["backend"] for s in cands} | set()
    autotune.autotune_clear()
    plan_cache_clear()


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------


def _traced_engine_run(tracer):
    clock = FakeClock()
    eng = GLCMEngine(_cfg(batch_size=2), clock=clock, tracer=tracer)
    for i in range(4):
        eng.submit(IMGS[i])
        clock.advance(1.0)
    eng.flush()


def test_report_cli_summarizes_native_trace(tracer, tmp_path, capsys):
    _traced_engine_run(tracer)
    path = tmp_path / "trace.json"
    tracer.save(str(path))
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "per-phase breakdown" in out
    assert "glcm.request" in out
    assert "dispatch timeline" in out
    assert "example span tree" in out


def test_report_cli_renders_requested_tree(tracer, tmp_path, capsys):
    _traced_engine_run(tracer)
    path = tmp_path / "trace.json"
    tracer.save(str(path))
    assert obs_report.main([str(path), "--request", "2"]) == 0
    out = capsys.readouterr().out
    assert "span tree of request" in out and "glcm.queue_wait" in out


def test_report_cli_converts_and_validates_chrome(tracer, tmp_path, capsys):
    _traced_engine_run(tracer)
    native = tmp_path / "trace.json"
    chrome = tmp_path / "chrome.json"
    tracer.save(str(native))
    assert obs_report.main([str(native), "--chrome", str(chrome)]) == 0
    doc = json.loads(chrome.read_text())
    assert obs_report.validate_chrome(doc) == []
    # --validate accepts both formats (native is converted first)
    assert obs_report.main([str(chrome), "--validate"]) == 0
    assert obs_report.main([str(native), "--validate"]) == 0
    capsys.readouterr()


def test_report_cli_validate_fails_on_broken_trace(tmp_path, capsys):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "a", "ts": 1}]}))  # X without dur
    assert obs_report.main([str(bad), "--validate"]) == 1
    assert "INVALID" in capsys.readouterr().out
