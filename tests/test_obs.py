"""The obs subsystem: registry semantics, Prometheus rendering, snapshot
merging, endpoint end-to-end, microbatcher histogram population, span
breakdowns, bounded instrumentation overhead, and the batch head's
cross-process snapshot merge."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from reporter_tpu.obs import metrics as obs_metrics
from reporter_tpu.obs.metrics import Registry, merge
from reporter_tpu.obs.trace import Span


# -- registry semantics -----------------------------------------------------


def test_counter_concurrency_exact():
    reg = Registry()
    c = reg.counter("t_hits_total", "hits")
    n_threads, per_thread = 8, 5000

    def spin():
        for _ in range(per_thread):
            c.inc()

    ts = [threading.Thread(target=spin) for _ in range(n_threads)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.value == n_threads * per_thread


def test_label_semantics():
    reg = Registry()
    fam = reg.counter("t_req_total", "reqs", ("endpoint", "outcome"))
    a = fam.labels("report", "ok")
    assert fam.labels("report", "ok") is a  # same combination -> same child
    assert fam.labels(endpoint="report", outcome="ok") is a  # kwargs too
    b = fam.labels("report", "error")
    assert b is not a
    a.inc(2)
    b.inc()
    snap = reg.snapshot()["t_req_total"]
    assert snap["labelnames"] == ["endpoint", "outcome"]
    assert [["report", "error"], 1] in [[lv, v] for lv, v in snap["samples"]]
    with pytest.raises(ValueError):
        fam.labels("only-one")
    with pytest.raises(ValueError):
        fam.inc()  # labeled family has no default child
    with pytest.raises(ValueError):
        reg.gauge("t_req_total")  # kind conflict
    assert reg.counter("t_req_total", labelnames=("endpoint", "outcome")) is fam


def test_gauge_and_histogram_basics():
    reg = Registry()
    g = reg.gauge("t_depth", "queue depth")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value == 6
    h = reg.histogram("t_lat_seconds", "latency", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    s = reg.snapshot()["t_lat_seconds"]["samples"][0][1]
    assert s["counts"] == [1, 1, 1, 1] and s["count"] == 4
    assert s["sum"] == pytest.approx(5.555)
    with pytest.raises(ValueError):
        reg.histogram("t_bad", buckets=(1.0, 0.5))
    with pytest.raises(ValueError):
        reg.counter("bad name!")


def test_prometheus_render_golden():
    reg = Registry()
    c = reg.counter("t_req_total", "Requests served", ("route",))
    c.labels("a").inc(3)
    c.labels('q"uo\\te').inc()
    reg.gauge("t_depth", "Depth").set(2.5)
    h = reg.histogram("t_wait_seconds", "Wait", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(50.0)
    assert reg.render() == (
        '# HELP t_req_total Requests served\n'
        '# TYPE t_req_total counter\n'
        't_req_total{route="a"} 3\n'
        't_req_total{route="q\\"uo\\\\te"} 1\n'
        '# HELP t_depth Depth\n'
        '# TYPE t_depth gauge\n'
        't_depth 2.5\n'
        '# HELP t_wait_seconds Wait\n'
        '# TYPE t_wait_seconds histogram\n'
        't_wait_seconds_bucket{le="0.1"} 1\n'
        't_wait_seconds_bucket{le="1"} 2\n'
        't_wait_seconds_bucket{le="+Inf"} 3\n'
        't_wait_seconds_sum 50.55\n'
        't_wait_seconds_count 3\n'
    )


def test_snapshot_merge():
    rega, regb = Registry(), Registry()
    for reg, n in ((rega, 2), (regb, 3)):
        reg.counter("t_total", "", ("who",)).labels("x").inc(n)
        reg.gauge("t_inflight").set(n)
        h = reg.histogram("t_lat", buckets=(1.0, 10.0))
        h.observe(0.5)
        h.observe(float(n * 10))
    regb.counter("t_total", "", ("who",)).labels("y").inc(7)
    merged = merge(rega.snapshot(), regb.snapshot())
    samples = {tuple(lv): v for lv, v in merged["t_total"]["samples"]}
    assert samples[("x",)] == 5 and samples[("y",)] == 7
    assert merged["t_inflight"]["samples"][0][1] == 5  # gauges sum
    hist = merged["t_lat"]["samples"][0][1]
    assert hist["count"] == 4 and hist["counts"] == [2, 0, 2]
    # merge is json-safe round-trip
    assert merge(json.loads(json.dumps(rega.snapshot()))) == merge(rega.snapshot())


def test_collect_callback_runs_on_read():
    reg = Registry()
    g = reg.gauge("t_live")
    state = {"v": 0}
    reg.register_collect(lambda: g.set(state["v"]))
    state["v"] = 42
    assert reg.snapshot()["t_live"]["samples"][0][1] == 42
    state["v"] = 7
    assert "t_live 7" in reg.render()


# -- microbatcher instrumentation ------------------------------------------


class _StubMatcher:
    """match_many_async-compatible stub: instant device fn."""

    backend = "cpu"

    def match_many_async(self, traces):
        results = [{"segments": []} for _ in traces]
        return lambda: results


def _snap_hist(name):
    fam = obs_metrics.REGISTRY.snapshot().get(name)
    return fam["samples"][0][1]["count"] if fam else 0


def test_microbatcher_populates_histograms():
    from reporter_tpu.serve.service import MicroBatcher

    before = {n: _snap_hist(n) for n in (
        "reporter_microbatch_queue_wait_seconds",
        "reporter_microbatch_batch_fill",
        "reporter_microbatch_device_step_seconds",
    )}
    mb = MicroBatcher(_StubMatcher(), max_batch=8, max_wait_ms=1.0)
    out = mb.match_many([{"uuid": "u%d" % i, "trace": []} for i in range(20)])
    assert len(out) == 20
    # the finisher observes device-step after resolving futures; allow a tick
    deadline = time.monotonic() + 5.0
    while (_snap_hist("reporter_microbatch_device_step_seconds")
           <= before["reporter_microbatch_device_step_seconds"]):
        assert time.monotonic() < deadline, "device-step histogram never populated"
        time.sleep(0.01)
    after_wait = _snap_hist("reporter_microbatch_queue_wait_seconds")
    assert after_wait >= before["reporter_microbatch_queue_wait_seconds"] + 20
    assert (_snap_hist("reporter_microbatch_batch_fill")
            > before["reporter_microbatch_batch_fill"])


def test_microbatcher_clamps_nonpositive_inflight():
    from reporter_tpu.serve.service import MicroBatcher

    # maxsize<=0 would make the hand-off queue UNBOUNDED (ADVICE r05)
    assert MicroBatcher(_StubMatcher(), max_inflight=0)._finish_q.maxsize == 1
    assert MicroBatcher(_StubMatcher(), max_inflight=-3)._finish_q.maxsize == 1
    assert MicroBatcher(_StubMatcher(), max_inflight=5)._finish_q.maxsize == 5


def test_span_rides_through_batcher():
    from reporter_tpu.serve.service import MicroBatcher

    mb = MicroBatcher(_StubMatcher(), max_wait_ms=1.0)
    span = Span("report")
    mb.match({"uuid": "u", "trace": []}, span=span)
    span.finish()
    out = span.breakdown()
    assert out["span_id"] and out["batch_size"] >= 1
    assert {"queue_wait_s", "device_step_s", "total_s"} <= set(out["timings"])


def test_microbatcher_overhead():
    """Instrumentation must stay within 10% of the uninstrumented path over
    >= 1k requests against a stub device fn (plus a small absolute epsilon
    for scheduler jitter on loaded CI hosts)."""
    from reporter_tpu.serve.service import MicroBatcher

    n = 1000
    traces = [{"uuid": "u%d" % i, "trace": []} for i in range(n)]

    def wall(instrument: bool) -> float:
        mb = MicroBatcher(_StubMatcher(), max_batch=64, max_wait_ms=0.0,
                          instrument=instrument)
        t0 = time.perf_counter()
        mb.match_many(traces)
        return time.perf_counter() - t0

    # alternate and take the best of several runs so a one-off scheduler
    # stall can't decide the verdict in either direction; the absolute
    # epsilon absorbs the single-CPU scheduler jitter a full-suite run
    # layers on top of the 10% relative bound (PR 18 deflake)
    t_plain = min(wall(False) for _ in range(5))
    t_instr = min(wall(True) for _ in range(5))
    assert t_instr <= 1.10 * t_plain + 0.075, (t_instr, t_plain)


# -- service endpoints end-to-end ------------------------------------------


@pytest.fixture(scope="module")
def obs_service_url():
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
    yield "http://127.0.0.1:%d" % httpd.server_port, arrays
    httpd.shutdown()


def _street_trace(arrays, n=10):
    nodes = [2 * 5 + c for c in range(5)]
    t = np.linspace(0.05, 0.9, n)
    xs = np.interp(t, np.linspace(0, 1, 5), arrays.node_x[nodes])
    ys = np.interp(t, np.linspace(0, 1, 5), arrays.node_y[nodes])
    lat, lon = arrays.proj.to_latlon(xs, ys)
    return {
        "uuid": "veh-obs",
        "trace": [{"lat": float(a), "lon": float(o), "time": 1000 + 15 * i}
                  for i, (a, o) in enumerate(zip(lat, lon))],
        "match_options": {"mode": "auto", "report_levels": [0, 1],
                          "transition_levels": [0, 1]},
    }


def _post(url, payload):
    req = urllib.request.Request(url, data=json.dumps(payload).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.status, json.loads(r.read().decode())


_PROM_LINE = (
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})? -?[0-9.e+-]+(\n|$)')


def test_metrics_endpoint_exposition(obs_service_url):
    import re

    url, arrays = obs_service_url
    code, _ = _post(url + "/report", _street_trace(arrays))
    assert code == 200
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    # the acceptance set: every operating signal a batched service needs
    assert "reporter_microbatch_queue_wait_seconds_bucket{" in text
    assert "reporter_microbatch_device_step_seconds_bucket{" in text
    assert "reporter_microbatch_batch_fill_bucket{" in text
    assert 'reporter_compile_total{shape="' in text
    assert 'reporter_requests_total{endpoint="report",outcome="ok"}' in text
    # every non-comment line is valid exposition syntax
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*", line), line
        else:
            assert re.match(_PROM_LINE, line), line
    # histogram invariants on a served family: cumulative and capped by count
    cum = [int(m.group(1)) for m in re.finditer(
        r'reporter_microbatch_batch_fill_bucket\{le="[^"]*"\} (\d+)', text)]
    assert cum == sorted(cum) and cum[-1] == int(re.search(
        r"reporter_microbatch_batch_fill_count (\d+)", text).group(1))


def test_statusz_snapshot(obs_service_url):
    url, _arrays = obs_service_url
    with urllib.request.urlopen(url + "/statusz", timeout=30) as r:
        out = json.loads(r.read().decode())
    assert out["uptime_s"] >= 0 and out["backend"] == "jax"
    assert out["latency_buckets_s"] == list(obs_metrics.LATENCY_BUCKETS_S)
    assert "max_batch" in out["batch"]
    assert "reporter_requests_total" in out["metrics"]
    assert out["metrics"]["reporter_requests_total"]["type"] == "counter"


def test_report_debug_breakdown(obs_service_url):
    url, arrays = obs_service_url
    code, out = _post(url + "/report?debug=1", _street_trace(arrays))
    assert code == 200
    dbg = out["debug"]
    assert len(dbg["span_id"]) == 16 and dbg["batch_size"] >= 1
    t = dbg["timings"]
    assert {"queue_wait_s", "device_step_s", "report_fn_s", "total_s"} <= set(t)
    assert t["total_s"] >= t["device_step_s"] >= 0
    # without the opt-in, no debug payload rides along
    code, out = _post(url + "/report", _street_trace(arrays))
    assert code == 200 and "debug" not in out


def test_profile_endpoint(obs_service_url):
    import os

    url, _arrays = obs_service_url
    with urllib.request.urlopen(url + "/debug/profile?seconds=0.05", timeout=60) as r:
        out = json.loads(r.read().decode())
    assert r.status == 200
    assert os.path.isdir(out["trace_dir"])
    # the capture actually wrote a trace artifact under the dir
    found = [f for _r, _d, fs in os.walk(out["trace_dir"]) for f in fs]
    assert found, "profiler capture produced no files"


# -- cross-process snapshot merge (batch pipeline) --------------------------


def test_batch_worker_snapshots_merge(tmp_path):
    from reporter_tpu.batch import pipeline

    arch = tmp_path / "arch"
    arch.mkdir()
    for i in range(2):
        with open(str(arch / ("day%d.csv" % i)), "w") as f:
            for j in range(3):
                f.write("veh-%d-%d,%d,37.75,-122.45,5\n" % (i, j, 1000 + j))

    pipeline.WORKER_SNAPSHOTS.clear()
    out = pipeline.get_traces(
        str(arch),
        valuer='lambda l: tuple(l.split(","))',
        time_pattern=None,
        concurrency=2,
        dest_dir=str(tmp_path / "traces"),
    )
    assert len(pipeline.WORKER_SNAPSHOTS) == 2, "one snapshot per spawn worker"
    merged = merge(*pipeline.WORKER_SNAPSHOTS)
    files = {tuple(lv): v for lv, v in
             merged["reporter_batch_source_files_total"]["samples"]}
    assert files[("ok",)] == 2  # one archive file per worker, summed
    points = merged["reporter_batch_points_gathered_total"]["samples"][0][1]
    assert points == 6
    assert len(list((tmp_path / "traces").iterdir())) >= 1
    assert out == str(tmp_path / "traces")


def _sample(snap, family, labels=()):
    fam = snap.get(family)
    if not fam:
        return 0
    for lv, v in fam["samples"]:
        if tuple(lv) == tuple(labels):
            return v
    return 0


def test_batch_head_metrics_flag(tmp_path, capsys):
    """python -m reporter_tpu.batch --metrics prints ONE merged JSON
    snapshot covering the head and every fan-out worker process."""
    from reporter_tpu.batch import pipeline
    from reporter_tpu.batch.__main__ import main as batch_main

    # the head registry is process-wide and other tests feed it: assert on
    # deltas, and drop worker snapshots collected by earlier tests
    pipeline.WORKER_SNAPSHOTS.clear()
    before = obs_metrics.REGISTRY.snapshot()

    conf = tmp_path / "conf.json"
    conf.write_text(json.dumps({
        "network": {"type": "grid", "rows": 3, "cols": 3, "spacing_m": 150.0},
        "backend": "cpu",
    }))
    arch = tmp_path / "arch"
    arch.mkdir()
    # two short same-vehicle drives near the grid origin (cpu oracle backend:
    # no device, fast) split over two archive files for the phase-1 fan-out
    for i in range(2):
        with open(str(arch / ("part%d.csv" % i)), "w") as f:
            for j in range(4):
                f.write("veh-%d,%d,%.6f,%.6f,5\n"
                        % (i, 1000 + 15 * j, 37.7502, -122.4498 + 0.0002 * j))
    rc = batch_main([
        "--src", str(arch),
        "--match-config", str(conf),
        "--src-time-pattern", "",
        "--src-valuer", 'lambda l: tuple(l.split(","))',
        "--dest", "dir:" + str(tmp_path / "out"),
        "--concurrency", "2",
        "--privacy", "1",
        "--metrics",
    ])
    assert rc == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    snap = json.loads(last)
    # both workers' counts merged into one dump (delta over the head's
    # pre-run registry: this run added 2 ok files / 8 points, all of them
    # counted in worker processes)
    assert (_sample(snap, "reporter_batch_source_files_total", ("ok",))
            == _sample(before, "reporter_batch_source_files_total", ("ok",)) + 2)
    assert (_sample(snap, "reporter_batch_points_gathered_total")
            == _sample(before, "reporter_batch_points_gathered_total") + 8)
    # phase 2 ran in the head process; its counters ride the same snapshot
    assert "reporter_batch_windows_matched_total" in snap
    # and every window it matched went to the matcher with its columns
    matched = (_sample(snap, "reporter_batch_windows_matched_total")
               - _sample(before, "reporter_batch_windows_matched_total"))
    assert matched == 2
    assert (_sample(snap, "reporter_batch_columnar_windows_total")
            - _sample(before, "reporter_batch_columnar_windows_total")) == matched
