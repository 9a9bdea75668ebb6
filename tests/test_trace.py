"""End-to-end trace propagation, the flight recorder's tail-sampling
invariants, histogram exemplars, structured logging, and the always-on
tracing overhead bound."""

import io
import json
import logging
import threading
import time
import urllib.request

import numpy as np
import pytest

from reporter_tpu.obs import flight as obs_flight
from reporter_tpu.obs import log as obs_log
from reporter_tpu.obs import trace as obs_trace
from reporter_tpu.obs.flight import FlightRecorder
from reporter_tpu.obs.metrics import Registry, merge
from reporter_tpu.obs.trace import Span


# -- trace context ----------------------------------------------------------


def test_trace_id_accept_and_generate():
    assert obs_trace.accept_trace_id("abc-123.X_z") == "abc-123.X_z"
    assert obs_trace.accept_trace_id("  padded  ") == "padded"
    assert obs_trace.accept_trace_id(None) is None
    assert obs_trace.accept_trace_id("") is None
    assert obs_trace.accept_trace_id("bad id with spaces") is None
    assert obs_trace.accept_trace_id("x" * 65) is None  # too long
    assert obs_trace.accept_trace_id('evil"header\r\n') is None
    generated = obs_trace.new_trace_id()
    assert obs_trace.accept_trace_id(generated) == generated


def test_span_context_binding():
    assert obs_trace.current_span() is None
    assert obs_trace.current_trace_id() is None
    span = Span("outer", trace_id="tid-outer")
    with obs_trace.bind(span):
        assert obs_trace.current_span() is span
        assert obs_trace.current_trace_id() == "tid-outer"
        with obs_trace.bind(Span("inner")):
            assert obs_trace.current_span().name == "inner"
        assert obs_trace.current_span() is span
        # bind(None) is a no-op, not a reset
        with obs_trace.bind(None):
            assert obs_trace.current_span() is span
    assert obs_trace.current_span() is None


def test_context_is_per_thread():
    seen = {}

    def worker():
        seen["in_thread"] = obs_trace.current_trace_id()

    with obs_trace.bind(Span("main", trace_id="main-tid")):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert seen["in_thread"] is None  # fresh thread, fresh context


def test_span_fail_and_breakdown():
    span = Span("report", trace_id="t1")
    span.mark("queue_wait_s", 0.001)
    span.fail(ValueError("boom"))
    span.finish()
    out = span.breakdown()
    assert out["trace_id"] == "t1" and len(out["span_id"]) == 16
    assert out["timings"]["total_s"] >= 0
    assert span.status == "error" and "boom" in span.error


# -- flight recorder tail sampling -----------------------------------------


def _mk_span(status="ok", total_s=0.001, name="report"):
    span = Span(name)
    if status != "ok":
        span.fail("synthetic", status=status)
    span.timings["total_s"] = total_s
    return span


def test_tail_sampling_errors_and_slow_always_retained():
    rec = FlightRecorder(capacity=16, slow_ms=100.0, sample_every=5)
    err = _mk_span(status="error")
    slow = _mk_span(total_s=0.5)
    assert rec.record(err) == "error"
    assert rec.record(slow) == "slow"
    # flood with healthy fast traffic: the error/slow entries must survive
    for _ in range(500):
        rec.record(_mk_span())
    ids = {t["trace_id"] for t in rec.snapshot(64)}
    assert err.trace_id in ids and slow.trace_id in ids


def test_tail_sampling_one_in_n_and_bounded():
    rec = FlightRecorder(capacity=8, slow_ms=10_000.0, sample_every=10)
    decisions = [rec.record(_mk_span()) for _ in range(100)]
    assert decisions.count("sampled") == 10
    assert decisions.count("dropped") == 90
    # ring bounded under load regardless of volume
    for _ in range(1000):
        rec.record(_mk_span())
        rec.record(_mk_span(status="error"))
    s = rec.summary()
    assert s["retained_errors_slow"] <= 8 and s["retained_sampled"] <= 8
    assert len(rec.snapshot(1000)) <= 16


def test_snapshot_prefers_kept_traces_on_cut():
    rec = FlightRecorder(capacity=8, slow_ms=100.0, sample_every=1)
    errs = [_mk_span(status="error") for _ in range(4)]
    for e in errs:
        rec.record(e)
    for _ in range(8):
        rec.record(_mk_span())  # sample_every=1: all retained as sampled
    cut = rec.snapshot(4)
    assert len(cut) == 4
    assert {t["trace_id"] for t in cut} == {e.trace_id for e in errs}


def test_flight_dump_roundtrip(tmp_path):
    rec = FlightRecorder(capacity=4, slow_ms=100.0, sample_every=1)
    span = _mk_span(status="error")
    rec.record(span)
    path = str(tmp_path / "flight.json")
    assert rec.dump(path) == path
    data = json.loads(open(path).read())
    assert data["summary"]["capacity"] == 4
    assert data["traces"][0]["trace_id"] == span.trace_id
    # empty recorder: no file written
    assert FlightRecorder(capacity=4).dump(str(tmp_path / "empty.json")) is None


def test_shutdown_hook_runs_dump(monkeypatch, tmp_path):
    from reporter_tpu.utils import shutdown

    calls = []
    monkeypatch.setattr(shutdown, "_HOOKS", [])
    shutdown.on_shutdown(lambda: calls.append(1))
    shutdown.on_shutdown(lambda: (_ for _ in ()).throw(RuntimeError("x")))
    shutdown.run_shutdown_hooks()  # hook failures are swallowed
    assert calls == [1]


# -- histogram exemplars ----------------------------------------------------


def test_histogram_exemplars_in_snapshot_not_render():
    reg = Registry()
    h = reg.histogram("t_lat_seconds", "latency", buckets=(0.01, 0.1, 1.0))
    h.observe(0.005)                        # no exemplar
    h.observe(0.05, exemplar="trace-a")
    h.observe(0.07, exemplar="trace-b")     # slower: replaces trace-a's bucket
    h.observe(0.5, exemplar="trace-c")
    s = reg.snapshot()["t_lat_seconds"]["samples"][0][1]
    assert s["exemplars"] == [[1, 0.07, "trace-b"], [2, 0.5, "trace-c"]]
    # 0.0.4 text exposition carries no exemplar syntax
    assert "trace-" not in reg.render()


def test_histogram_exemplars_merge_keeps_slowest():
    rega, regb = Registry(), Registry()
    for reg, v, tid in ((rega, 0.03, "a"), (regb, 0.09, "b")):
        reg.histogram("t_lat", buckets=(0.01, 0.1)).observe(v, exemplar=tid)
    merged = merge(rega.snapshot(), regb.snapshot())
    assert merged["t_lat"]["samples"][0][1]["exemplars"] == [[1, 0.09, "b"]]
    # a snapshot without exemplars merges cleanly with one that has them
    regc = Registry()
    regc.histogram("t_lat", buckets=(0.01, 0.1)).observe(0.02)
    merged = merge(regc.snapshot(), rega.snapshot())
    assert merged["t_lat"]["samples"][0][1]["count"] == 2
    assert merged["t_lat"]["samples"][0][1]["exemplars"] == [[1, 0.03, "a"]]


# -- structured logging -----------------------------------------------------


def _capture_logger(fmt):
    stream = io.StringIO()
    handler = logging.StreamHandler(stream)
    handler.setFormatter(obs_log.JsonFormatter() if fmt == "json"
                         else obs_log.TextFormatter(obs_log.TEXT_FORMAT))
    logger = logging.getLogger("test_trace.%s.%d" % (fmt, id(stream)))
    logger.handlers[:] = [handler]
    logger.setLevel(logging.DEBUG)
    logger.propagate = False
    return logger, stream


def test_json_log_attaches_current_trace_id():
    logger, stream = _capture_logger("json")
    with obs_trace.bind(Span("report", trace_id="tid-json")):
        logger.info("inside %d", 42)
    logger.info("outside")
    lines = [json.loads(l) for l in stream.getvalue().strip().splitlines()]
    assert lines[0]["msg"] == "inside 42"
    assert lines[0]["trace_id"] == "tid-json"
    assert lines[0]["level"] == "INFO"
    assert "trace_id" not in lines[1]


def test_event_fields_json_and_text():
    logger, stream = _capture_logger("json")
    obs_log.event(logger, "door_probe", open=False, ports=[], skipme=None)
    line = json.loads(stream.getvalue().strip())
    assert line["event"] == "door_probe"
    assert line["open"] is False and line["ports"] == []
    assert "skipme" not in line  # None fields dropped

    logger, stream = _capture_logger("text")
    with obs_trace.bind(Span("s", trace_id="tid-text")):
        obs_log.event(logger, "compile_stall", shape="64x64", seconds=1.5)
    text = stream.getvalue().strip()
    assert "compile_stall" in text
    assert "shape=64x64" in text and "seconds=1.5" in text
    assert "trace_id=tid-text" in text


def test_configure_idempotent_and_forced(monkeypatch):
    import reporter_tpu.obs.log as log_mod

    monkeypatch.setattr(log_mod, "_configured", False)
    stream_a, stream_b = io.StringIO(), io.StringIO()
    monkeypatch.setenv("REPORTER_LOG_FORMAT", "json")
    monkeypatch.setenv("REPORTER_LOG_LEVEL", "DEBUG")
    saved = logging.getLogger().handlers[:]
    saved_level = logging.getLogger().level
    try:
        obs_log.configure(stream=stream_a)
        assert logging.getLogger().level == logging.DEBUG
        assert isinstance(logging.getLogger().handlers[0].formatter,
                          obs_log.JsonFormatter)
        obs_log.configure(stream=stream_b)  # idempotent: still stream_a
        assert logging.getLogger().handlers[0].stream is stream_a
        obs_log.configure(stream=stream_b, fmt="text", force=True)
        assert logging.getLogger().handlers[0].stream is stream_b
        assert isinstance(logging.getLogger().handlers[0].formatter,
                          obs_log.TextFormatter)
    finally:
        logging.getLogger().handlers[:] = saved
        logging.getLogger().setLevel(saved_level)


# -- overhead: always-on tracing -------------------------------------------


class _StubMatcher:
    backend = "cpu"

    def match_many_async(self, traces):
        results = [{"segments": []} for _ in traces]
        return lambda: results


def test_overhead_with_always_on_spans():
    """The 1k-request ≤10% overhead bound must hold with tracing always on:
    a Span per request riding the batcher plus a flight-recorder decision
    per request, vs the fully uninstrumented span-less path."""
    from reporter_tpu.serve.service import MicroBatcher

    n = 1000
    traces = [{"uuid": "u%d" % i, "trace": []} for i in range(n)]
    rec = FlightRecorder(capacity=64, slow_ms=250.0, sample_every=10)

    def wall(instrument: bool) -> float:
        mb = MicroBatcher(_StubMatcher(), max_batch=64, max_wait_ms=0.0,
                          instrument=instrument)
        t0 = time.perf_counter()
        if instrument:
            spans = [Span("report") for _ in range(n)]
            futures = [mb.submit(t, span=sp) for t, sp in zip(traces, spans)]
            for f, sp in zip(futures, spans):
                f.result()
                sp.finish()
                rec.record(sp)
        else:
            futures = [mb.submit(t) for t in traces]
            for f in futures:
                f.result()
        return time.perf_counter() - t0

    # best-of-5 with an absolute epsilon wide enough for the scheduler
    # jitter a loaded single-CPU full-suite run adds (PR 18 deflake);
    # the 10% relative bound is the documented claim and stands
    t_plain = min(wall(False) for _ in range(5))
    t_traced = min(wall(True) for _ in range(5))
    assert t_traced <= 1.10 * t_plain + 0.075, (t_traced, t_plain)


# -- service end-to-end -----------------------------------------------------


@pytest.fixture(scope="module")
def trace_service():
    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.serve import ReporterService
    from reporter_tpu.tiles.arrays import build_graph_arrays
    from reporter_tpu.tiles.network import grid_city
    from reporter_tpu.tiles.ubodt import build_ubodt

    city = grid_city(rows=5, cols=5, spacing_m=150.0)
    arrays = build_graph_arrays(city, cell_size=100.0)
    ubodt = build_ubodt(arrays, delta=2000.0)
    matcher = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=MatcherConfig())
    service = ReporterService(matcher, max_wait_ms=5.0)
    httpd = service.make_server("127.0.0.1", 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield "http://127.0.0.1:%d" % httpd.server_port, arrays, service
    httpd.shutdown()


def _street_trace(arrays, n=10):
    nodes = [2 * 5 + c for c in range(5)]
    t = np.linspace(0.05, 0.9, n)
    xs = np.interp(t, np.linspace(0, 1, 5), arrays.node_x[nodes])
    ys = np.interp(t, np.linspace(0, 1, 5), arrays.node_y[nodes])
    lat, lon = arrays.proj.to_latlon(xs, ys)
    return {
        "uuid": "veh-trace",
        "trace": [{"lat": float(a), "lon": float(o), "time": 1000 + 15 * i}
                  for i, (a, o) in enumerate(zip(lat, lon))],
        "match_options": {"mode": "auto", "report_levels": [0, 1],
                          "transition_levels": [0, 1]},
    }


def _post(url, payload, headers=None):
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    req = urllib.request.Request(url, data=json.dumps(payload).encode(),
                                 headers=h)
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, dict(r.headers), json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read().decode())


def _get_traces(url, n=100):
    with urllib.request.urlopen(url + "/debug/traces?n=%d" % n, timeout=30) as r:
        return json.loads(r.read().decode())


def test_client_header_echoed_and_recorded(trace_service):
    """The acceptance path: a tagged request gets the same id echoed and is
    retrievable from GET /debug/traces with a per-stage breakdown."""
    url, arrays, _svc = trace_service
    tid = "acceptance-" + obs_trace.new_trace_id()[:8]
    code, headers, out = _post(url + "/report", _street_trace(arrays),
                               headers={"X-Reporter-Trace": tid})
    assert code == 200
    assert headers.get("X-Reporter-Trace") == tid
    assert "debug" not in out  # always-on tracing does NOT opt the payload in
    # healthy fast traces are tail-sampled 1-in-N; keep posting until this
    # id lands or every-Nth cycles through (bounded)
    found = None
    for _ in range(2 * obs_flight.RECORDER.sample_every):
        entries = _get_traces(url)["traces"]
        found = next((t for t in entries if t["trace_id"] == tid), None)
        if found:
            break
        code, headers, _o = _post(url + "/report", _street_trace(arrays),
                                  headers={"X-Reporter-Trace": tid})
        assert code == 200 and headers.get("X-Reporter-Trace") == tid
    assert found, "tagged trace never surfaced in the flight recorder"
    assert found["status"] == "ok" and found["endpoint"] == "report"
    assert {"queue_wait_s", "device_step_s", "report_fn_s",
            "total_s"} <= set(found["timings"])
    assert found["batch_size"] >= 1


def test_generated_id_echoed_without_header(trace_service):
    url, arrays, _svc = trace_service
    code, headers, _out = _post(url + "/report", _street_trace(arrays))
    assert code == 200
    tid = headers.get("X-Reporter-Trace")
    assert tid and obs_trace.accept_trace_id(tid) == tid


def test_malformed_header_replaced(trace_service):
    url, arrays, _svc = trace_service
    code, headers, _out = _post(url + "/report", _street_trace(arrays),
                                headers={"X-Reporter-Trace": "bad id!!"})
    assert code == 200
    tid = headers.get("X-Reporter-Trace")
    assert tid and tid != "bad id!!"


def test_invalid_request_always_in_recorder(trace_service):
    url, arrays, _svc = trace_service
    tid = "invalid-" + obs_trace.new_trace_id()[:8]
    bad = _street_trace(arrays)
    del bad["uuid"]
    code, headers, out = _post(url + "/report", bad,
                               headers={"X-Reporter-Trace": tid})
    assert code == 400 and headers.get("X-Reporter-Trace") == tid
    entry = next(t for t in _get_traces(url)["traces"]
                 if t["trace_id"] == tid)
    assert entry["status"] == "invalid"
    assert "uuid is required" in entry["error"]


def test_error_request_always_in_recorder(trace_service):
    """A 500 (engine failure) is always retained, whatever the load."""
    url, arrays, svc = trace_service
    tid = "error-" + obs_trace.new_trace_id()[:8]

    class _Boom:
        def match(self, trace, span=None):
            raise RuntimeError("synthetic engine failure")

    real = svc.batcher
    svc.batcher = _Boom()
    try:
        code, headers, out = _post(url + "/report", _street_trace(arrays),
                                   headers={"X-Reporter-Trace": tid})
    finally:
        svc.batcher = real
    assert code == 500 and headers.get("X-Reporter-Trace") == tid
    entry = next(t for t in _get_traces(url)["traces"]
                 if t["trace_id"] == tid)
    assert entry["status"] == "error"
    assert "synthetic engine failure" in entry["error"]


def test_batch_endpoint_traced(trace_service):
    url, arrays, _svc = trace_service
    tid = "batch-" + obs_trace.new_trace_id()[:8]
    code, headers, out = _post(
        url + "/trace_attributes_batch",
        {"traces": [_street_trace(arrays), _street_trace(arrays)]},
        headers={"X-Reporter-Trace": tid})
    assert code == 200 and len(out["results"]) == 2
    assert headers.get("X-Reporter-Trace") == tid


def test_statusz_flight_summary_and_exemplars(trace_service):
    url, arrays, _svc = trace_service
    _post(url + "/report", _street_trace(arrays))
    with urllib.request.urlopen(url + "/statusz", timeout=30) as r:
        out = json.loads(r.read().decode())
    assert out["flight"]["capacity"] >= 1
    assert "sample_every" in out["flight"]
    # the queue-wait histogram carries per-bucket exemplars linking to ids
    qw = out["metrics"]["reporter_microbatch_queue_wait_seconds"]["samples"][0][1]
    assert qw.get("exemplars"), "no exemplars on a served histogram"
    for _i, _v, ex_tid in qw["exemplars"]:
        assert obs_trace.accept_trace_id(ex_tid) == ex_tid


def test_debug_traces_param_validation(trace_service):
    url, _arrays, _svc = trace_service
    code, _h, out = _get_json_code(url + "/debug/traces?n=notanint")
    assert code == 400 and "integer" in out["error"]


def _get_json_code(url):
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, dict(r.headers), json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read().decode())


def test_debug_response_carries_trace_id(trace_service):
    url, arrays, _svc = trace_service
    tid = "debug-" + obs_trace.new_trace_id()[:8]
    code, _h, out = _post(url + "/report?debug=1", _street_trace(arrays),
                          headers={"X-Reporter-Trace": tid})
    assert code == 200
    assert out["debug"]["trace_id"] == tid
    assert len(out["debug"]["span_id"]) == 16


# -- trace_top helpers ------------------------------------------------------


def test_trace_top_parse_and_quantiles():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "trace_top.py")
    spec = importlib.util.spec_from_file_location("trace_top", path)
    tt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tt)

    text = "\n".join([
        "# HELP t_wait_seconds Wait",
        "# TYPE t_wait_seconds histogram",
        't_wait_seconds_bucket{le="0.01"} 10',
        't_wait_seconds_bucket{le="0.1"} 90',
        't_wait_seconds_bucket{le="+Inf"} 100',
        "t_wait_seconds_sum 5.0",
        "t_wait_seconds_count 100",
        "t_depth 7",
        't_req_total{endpoint="report",outcome="ok"} 42',
    ])
    m = tt.parse_metrics(text)
    assert m["t_depth"][()] == 7
    assert m["t_req_total"][(("endpoint", "report"), ("outcome", "ok"))] == 42
    buckets = tt.hist_buckets(m, "t_wait_seconds")
    assert buckets[-1] == (float("inf"), 100)
    # p50 lands mid second bucket: 0.01 + (50-10)/(90-10)*0.09 = 0.055
    assert tt.hist_quantile(buckets, 0.50) == pytest.approx(0.055)
    # p99 lands in +Inf: clamps to the last finite bound
    assert tt.hist_quantile(buckets, 0.99) == pytest.approx(0.1)
    assert tt.hist_quantile([], 0.5) is None
    # interval deltas: server restart (negative) falls back to cumulative
    prev = [(0.01, 5), (0.1, 20), (float("inf"), 25)]
    d = tt.delta_buckets(buckets, prev)
    assert d == [(0.01, 5), (0.1, 70), (float("inf"), 75)]
    assert tt.delta_buckets(prev, buckets) == prev
    # a frame renders without a live service
    frame = tt.render_frame(m, None, [
        {"trace_id": "abc", "name": "report", "status": "ok",
         "timings": {"queue_wait_s": 0.004, "total_s": 0.31}}], 2.0)
    assert "queue wait" in frame and "abc" in frame


def test_check_metrics_endpoint_sync():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "check_metrics.py")
    spec = importlib.util.spec_from_file_location("check_metrics_ep", path)
    chk = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chk)
    actions = chk.served_actions()
    assert "traces" in actions and "report" in actions
    assert actions - chk.documented_actions() == set(), (
        "endpoints missing from docs/http-api.md")
