"""tools/loadgen.py: open-loop schedule generation, session affinity,
archive replay, pacing, artifact schema — and the coordinated-omission
regression: a stalled server must show up in the reported tail because
latencies are measured against the SCHEDULED send time, not the moment a
backlogged client finally got the request out."""

import importlib.util
import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(TOOLS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def loadgen():
    return _load("loadgen")


# -- schedule ---------------------------------------------------------------

def test_build_schedule_poisson_and_uniform(loadgen):
    import random

    rng = random.Random(42)
    sched = loadgen.build_schedule(2000, 50.0, "poisson", rng)
    assert len(sched) == 2000
    assert all(b > a for a, b in zip(sched, sched[1:]))
    # mean inter-arrival ~ 1/rate (law of large numbers, seeded)
    assert sched[-1] / 2000 == pytest.approx(1 / 50.0, rel=0.15)
    # deterministic under the same seed
    assert sched == loadgen.build_schedule(2000, 50.0, "poisson",
                                           random.Random(42))
    uni = loadgen.build_schedule(10, 10.0, "uniform", rng)
    assert uni == pytest.approx([0.1 * (i + 1) for i in range(10)])
    with pytest.raises(ValueError):
        loadgen.build_schedule(5, 0.0, "poisson", rng)


def test_interleave_preserves_session_order(loadgen):
    sessions = [
        ("a", [{"uuid": "a", "w": 0}, {"uuid": "a", "w": 1}, {"uuid": "a", "w": 2}]),
        ("b", [{"uuid": "b", "w": 0}]),
        ("c", [{"uuid": "c", "w": 0}, {"uuid": "c", "w": 1}]),
    ]
    flat = loadgen.interleave(sessions)
    assert len(flat) == 6
    for uuid in "abc":
        ws = [r["w"] for r in flat if r["uuid"] == uuid]
        assert ws == sorted(ws), "uuid affinity: windows out of order"


def test_archive_sessions_and_time_warp(loadgen, tmp_path):
    rows = []
    for veh in ("v1", "v2"):
        for i in range(6):
            t = 1000 + i * 30 + (500 if veh == "v2" else 0)
            rows.append("%s|%d|37.75%d|-122.44%d|5" % (veh, t, i, i))
    (tmp_path / "part.csv").write_text("\n".join(rows) + "\n")
    sessions = loadgen.archive_sessions(
        str(tmp_path), "|", 0, 1, 2, 3, window=3)
    assert [u for u, _r in sessions] == ["v1", "v2"]
    for _u, reqs in sessions:
        assert all(len(r["trace"]) >= 2 for r in reqs)
        t0s = [r["_t0"] for r in reqs]
        assert t0s == sorted(t0s)
    reqs = loadgen.interleave(sessions)
    sched = loadgen.timeline_schedule(reqs, warp=10.0)
    # original span: v1 t0=1000 .. v2 last-window t0=1590 -> 59 s warped
    assert sched[0] == 0.0
    assert sched[-1] == pytest.approx((1590 - 1000) / 10.0)
    assert all(b >= a for a, b in zip(sched, sched[1:]))
    # requests were reordered onto the warped timeline
    assert reqs[0]["_t0"] == 1000


def test_synth_sessions_shape(loadgen):
    sessions = loadgen.synth_sessions(vehicles=3, points=8, window=4,
                                      grid=5, seed=1)
    assert len(sessions) == 3
    for uuid, reqs in sessions:
        assert uuid.startswith("loadgen-veh-")
        for r in reqs:
            assert r["uuid"] == uuid and len(r["trace"]) >= 2
            assert r["match_options"]["report_levels"] == [0, 1]


# -- make_requests pacing ---------------------------------------------------

def test_make_requests_paced_rate_and_limit():
    mr = _load("make_requests")
    sleeps = []
    clock = {"t": 0.0}

    def fake_clock():
        return clock["t"]

    def fake_sleep(s):
        sleeps.append(s)
        clock["t"] += s

    out = list(mr.paced(iter(range(5)), rate=10.0, limit=0,
                        clock=fake_clock, sleep=fake_sleep))
    assert out == [0, 1, 2, 3, 4]
    # open-loop metronome: record i released at t0 + i/rate
    assert sleeps == pytest.approx([0.1, 0.1, 0.1, 0.1])
    # a slow consumer gets NO extra sleeps (backlog, not rate decay)
    sleeps.clear()
    clock["t"] = 0.0

    def slow_consume():
        for i, rec in enumerate(mr.paced(iter(range(4)), rate=10.0,
                                         clock=fake_clock, sleep=fake_sleep)):
            clock["t"] += 0.25  # consumer burns 250 ms per record
            yield rec

    assert list(slow_consume()) == [0, 1, 2, 3]
    assert sleeps == []  # always behind schedule: paced never sleeps
    # limit stops the stream
    assert list(mr.paced(iter(range(100)), rate=0.0, limit=3)) == [0, 1, 2]


def test_make_requests_cli_rate_limit(tmp_path, capsys):
    mr = _load("make_requests")
    src = tmp_path / "probes.csv"
    src.write_text("\n".join(
        "veh-%d|%d|37.75|-122.44|5" % (i, 1000 + i) for i in range(10)) + "\n")
    rc = mr.main(["--src", str(src), "--salt", "s1", "--dry-run",
                  "--limit", "4", "--rate", "1000"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 4
    assert all("veh-" not in line for line in out), "uuid not salted"


# -- a controllable stub server ---------------------------------------------

class _Stub:
    """Single-threaded HTTP stub: requests serialize, per-request delay is
    scriptable, and the status code is switchable — the deterministic
    stand-in for a stalled serving tier."""

    def __init__(self, delays=(), code=200):
        stub = self

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                n = self.headers.get("Content-Length")
                if n:
                    self.rfile.read(int(n))
                i = stub.count
                stub.count += 1
                if i < len(stub.delays):
                    time.sleep(stub.delays[i])
                body = json.dumps({"ok": True}).encode()
                self.send_response(stub.code)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                body = json.dumps({"status": "ok", "backend": "stub",
                                   "edges": 80}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, fmt, *args):
                pass

        self.count = 0
        self.delays = list(delays)
        self.code = code
        self.httpd = HTTPServer(("127.0.0.1", 0), Handler)
        self.url = "http://127.0.0.1:%d" % self.httpd.server_port
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()

    def close(self):
        self.httpd.shutdown()


# -- the coordinated-omission regression ------------------------------------

def test_scheduled_time_latency_not_response_gap(loadgen):
    """One 0.8 s stall at the head of a 50 req/s schedule with a
    single-connection client: every later request is SENT late, and the
    reported (scheduled-time) latency must carry that backlog while the
    send-to-response gap stays flat — the exact lie a closed-loop
    generator would tell."""
    stub = _Stub(delays=[0.8])
    try:
        reqs = [{"uuid": "v", "trace": [], "match_options": {}}] * 10
        sched = [i / 50.0 for i in range(10)]
        samples, _t0 = loadgen.run_load(stub.url + "/report", reqs, sched,
                                        concurrency=1, timeout_s=10.0)
    finally:
        stub.close()
    assert len(samples) == 10
    assert all(s.code == 200 for s in samples)
    late = samples[1:]
    # the flattering number: every post-stall response came back fast
    assert max(s.service_s for s in late) < 0.4
    # the honest number: the backlog rides the scheduled-time latency
    assert min(s.latency_s for s in late) > 0.4
    q_sched = loadgen.quantiles_ms([s.latency_s for s in samples])
    q_gap = loadgen.quantiles_ms([s.service_s for s in samples])
    assert q_sched["p50_ms"] > 400 > q_gap["p50_ms"]
    # and the artifact stats carry BOTH, so omission is falsifiable
    st = loadgen.step_stats(samples, 50.0)
    assert st["quantiles"]["p50_ms"] > st["service_time_quantiles"]["p50_ms"]
    assert st["max_send_lag_s"] > 0.4


def test_loadgen_reports_device_hang_tail(loadgen, monkeypatch):
    """The ISSUE-pinned regression: loadgen against a real service with a
    faults.py device_hang must report scheduled-time latencies — the
    injected stall visibly degrades the reported tail even though each
    individual post-stall response is fast."""
    import numpy as np

    from reporter_tpu import faults
    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.serve import ReporterService
    from reporter_tpu.tiles.arrays import build_graph_arrays
    from reporter_tpu.tiles.network import grid_city
    from reporter_tpu.tiles.ubodt import build_ubodt

    city = grid_city(rows=5, cols=5, spacing_m=150.0)
    arrays = build_graph_arrays(city, cell_size=100.0)
    ubodt = build_ubodt(arrays, delta=2000.0)
    matcher = SegmentMatcher(arrays=arrays, ubodt=ubodt,
                             config=MatcherConfig())
    service = ReporterService(matcher, max_wait_ms=5.0)
    httpd = service.make_server("127.0.0.1", 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = "http://127.0.0.1:%d" % httpd.server_port

    nodes = [2 * 5 + c for c in range(5)]
    t = np.linspace(0.05, 0.9, 6)
    xs = np.interp(t, np.linspace(0, 1, 5), arrays.node_x[nodes])
    ys = np.interp(t, np.linspace(0, 1, 5), arrays.node_y[nodes])
    lat, lon = arrays.proj.to_latlon(xs, ys)
    body = {
        "uuid": "veh-hang",
        "trace": [{"lat": float(a), "lon": float(o), "time": 1000 + 15 * i}
                  for i, (a, o) in enumerate(zip(lat, lon))],
        "match_options": {"mode": "auto", "report_levels": [0, 1],
                          "transition_levels": [0, 1]},
    }
    try:
        # warm the dispatch path BEFORE arming the fault so compile time
        # doesn't masquerade as the hang
        matcher.match_many([dict(body)])
        monkeypatch.setenv("REPORTER_FAULT_DEVICE_HANG", "0.7:2")
        faults.reset()
        reqs = [dict(body) for _ in range(15)]
        sched = [i / 30.0 for i in range(15)]
        samples, _t0 = loadgen.run_load(url + "/report", reqs, sched,
                                        concurrency=2, timeout_s=30.0)
    finally:
        httpd.shutdown()
        monkeypatch.delenv("REPORTER_FAULT_DEVICE_HANG", raising=False)
        faults.reset()
    assert len(samples) == 15
    assert all(s.code == 200 for s in samples)
    q_sched = loadgen.quantiles_ms([s.latency_s for s in samples])
    q_gap = loadgen.quantiles_ms([s.service_s for s in samples])
    # the two injected 0.7 s hangs are visible in the scheduled-time tail
    assert q_sched["p95_ms"] > 700
    # and strictly exceed the response-gap view (the backlog is real)
    assert q_sched["p95_ms"] > q_gap["p95_ms"]
    assert max(s.sent - s.sched for s in samples) > 0.4


# -- artifact + verdict semantics -------------------------------------------

def test_main_artifact_schema_and_perf_gate_consumable(loadgen, tmp_path):
    stub = _Stub()
    out = tmp_path / "loadgen.json"
    try:
        rc = loadgen.main([
            "--url", stub.url, "--rate", "60", "--duration", "0.4",
            "--vehicles", "2", "--points", "6", "--window", "3",
            "--grid", "5", "--seed", "3", "--concurrency", "8",
            "--slo-availability", "0.5", "--slo-p99-ms", "60000",
            "--out", str(out),
        ])
    finally:
        stub.close()
    assert rc == 0
    art = json.loads(out.read_text())
    # perf_gate header keys (docs/bench-schema.md shape)
    for key in ("metric", "value", "unit", "platform", "attrib",
                "attrib_reason"):
        assert key in art, key
    assert art["edges"] == 80  # picked up from /health
    assert art["requests"] >= 1 and art["status"].get("200")
    assert art["slo"]["client"]["ok"] is True
    assert art["quantiles"]["p99_ms"] is not None
    # the artifact passes the real perf gate (like-provenance aware)
    pg = _load("perf_gate")
    repo = os.path.join(os.path.dirname(__file__), "..")
    history = sorted(
        os.path.join(repo, f) for f in os.listdir(repo)
        if f.startswith("BENCH_r0") and f.endswith(".json"))
    rc2, verdict = pg.gate(history, fresh=str(out), require_attrib=True)
    assert rc2 == 0, verdict


def test_main_rc_nonzero_on_slo_violation(loadgen, tmp_path):
    stub = _Stub(code=500)
    out = tmp_path / "loadgen.json"
    try:
        rc = loadgen.main([
            "--url", stub.url, "--rate", "50", "--duration", "0.2",
            "--vehicles", "1", "--points", "4", "--window", "2",
            "--grid", "5", "--slo-availability", "0.9",
            "--slo-p99-ms", "60000", "--out", str(out),
        ])
    finally:
        stub.close()
    assert rc == 1
    art = json.loads(out.read_text())
    assert art["slo"]["client"]["ok"] is False
    assert art["status"].get("500")


# -- streaming scenario (docs/performance.md "The session matcher") ---------


def test_stream_points_per_point_corpus(loadgen):
    sessions = [("a", [{"uuid": "a", "trace": [{"t": i} for i in range(4)]}]),
                ("b", [{"uuid": "b", "trace": [{"t": i} for i in range(2)]}])]
    pts = loadgen.stream_points(sessions)
    assert len(pts) == 6
    assert all(r["stream"] is True and len(r["trace"]) == 1 for r in pts)
    for uuid, n in (("a", 4), ("b", 2)):
        ts = [r["trace"][0]["t"] for r in pts if r["uuid"] == uuid]
        assert ts == list(range(n)), "per-uuid point order broken"


def test_fold_stream_windows_per_point_scheds(loadgen):
    """The windowed-rebatch baseline: requests fold per-uuid at the SAME
    per-point schedule, each point keeping its own arrival slot in
    _scheds, windows sent at their LAST point's slot, <2-point tails
    dropped and counted."""
    pts = []
    sched = []
    for k in range(5):  # a:3 points then a:2 more; b:2 points total
        for uuid in ("a", "b")[: 2 if k < 2 else 1]:
            pts.append({"uuid": uuid, "stream": True,
                        "trace": [{"t": k}],
                        "match_options": {}})
            sched.append(0.1 * len(sched))
    reqs, out_sched, dropped = loadgen.fold_stream_windows(pts, sched, 2)
    # a had 5 points -> two 2-windows + 1 dropped tail; b had 2 -> one
    assert dropped == 1
    assert len(reqs) == 3 and out_sched == sorted(out_sched)
    for r, s in zip(reqs, out_sched):
        assert "stream" not in r  # the baseline is the CLASSIC windowed path
        assert len(r["trace"]) == 2
        assert len(r["_scheds"]) == 2
        assert s == r["_scheds"][-1]  # sent at the last point's slot


def test_main_stream_scenario_per_point_samples(loadgen, tmp_path):
    """--stream end to end against the stub: every POINT lands as one
    sample (stream mode) and the artifact carries the stream block +
    scenario-specific metric name."""
    stub = _Stub()
    out = tmp_path / "stream.json"
    try:
        rc = loadgen.main([
            "--url", stub.url, "--stream", "--rate", "60",
            "--duration", "0.4", "--vehicles", "2", "--points", "6",
            "--window", "6", "--grid", "5", "--seed", "3",
            "--concurrency", "8", "--slo-availability", "0.5",
            "--slo-p99-ms", "60000", "--out", str(out),
        ])
    finally:
        stub.close()
    assert rc == 0
    art = json.loads(out.read_text())
    assert art["mode"] == "stream"
    assert art["metric"] == "loadgen_stream_p99_latency"
    assert art["stream"] == {"window": 1, "points": art["requests"],
                             "points_dropped_tail": 0}
    # one HTTP request per point: the stub counted exactly the samples
    assert stub.count == art["requests"]

    # the windowed-rebatch baseline: HTTP requests fold ~window-fold but
    # SAMPLES stay per-point, so the quantiles compare like with like
    stub2 = _Stub()
    out2 = tmp_path / "windowed.json"
    try:
        rc = loadgen.main([
            "--url", stub2.url, "--stream", "--stream-window", "3",
            "--rate", "60", "--duration", "0.4", "--vehicles", "2",
            "--points", "6", "--window", "6", "--grid", "5", "--seed", "3",
            "--concurrency", "8", "--slo-availability", "0.5",
            "--slo-p99-ms", "60000", "--out", str(out2),
        ])
    finally:
        stub2.close()
    assert rc == 0
    art2 = json.loads(out2.read_text())
    assert art2["mode"] == "stream-windowed"
    assert art2["metric"] == "loadgen_stream_windowed_p99_latency"
    assert art2["requests"] + art2["stream"]["points_dropped_tail"] \
        == art["requests"]
    assert stub2.count < stub.count  # fewer wire requests, same points
    # the baseline's per-point latency includes the window-fill wait, so
    # its p50 must exceed the per-point path's against the same stub
    assert art2["quantiles"]["p50_ms"] > art["quantiles"]["p50_ms"]


def test_profile_schedules_flash_and_diurnal(loadgen):
    import random

    rng = random.Random(1)
    # flash: the burst window carries ~mult x the baseline arrival rate
    s = loadgen.profile_schedule(20.0, 10.0, "flash:0.3:0.7:5",
                                 "poisson", rng)
    mid = sum(1 for t in s if 3.0 <= t < 7.0) / 4.0
    edge = sum(1 for t in s if t < 3.0 or t >= 7.0) / 6.0
    assert mid > 3.0 * edge
    assert all(0.0 <= t < 10.0 for t in s)
    # diurnal: a deterministic (uniform) schedule starts at the trough
    # (sparse arrivals) and peaks mid-run (dense arrivals)
    d = loadgen.profile_schedule(20.0, 10.0, "diurnal", "uniform", rng)
    gaps_start = d[1] - d[0]
    mid_i = min(range(len(d)), key=lambda i: abs(d[i] - 5.0))
    gaps_mid = d[mid_i + 1] - d[mid_i]
    assert gaps_mid < gaps_start / 2.0
    with pytest.raises(ValueError):
        loadgen.profile_rate_fn("flash:bad", 1.0, 1.0)
    with pytest.raises(ValueError):
        loadgen.profile_rate_fn("nope", 1.0, 1.0)


def test_skewed_requests_concentrate_and_preserve_order(loadgen):
    import random

    rng = random.Random(2)
    per_uuid = [("veh-%d" % i,
                 [{"uuid": "veh-%d" % i, "trace": [j]} for j in range(4)])
                for i in range(10)]
    reqs = loadgen.skewed_requests(per_uuid, 400, share=0.8,
                                   hot_frac=0.1, rng=rng, stream=False)
    assert len(reqs) == 400
    counts = {}
    for r in reqs:
        counts[r["uuid"]] = counts.get(r["uuid"], 0) + 1
    # ~80% of traffic on the single hot vehicle (hot_frac 0.1 of 10)
    assert counts["veh-0"] > 0.6 * 400
    # per-vehicle order preserved within each recycle
    for u in counts:
        seq = [r["trace"][0] for r in reqs if r["uuid"] == u]
        for k in range(1, len(seq)):
            assert seq[k] == (seq[k - 1] + 1) % 4
    # stream recycles rename the uuid so an open session never rewinds
    sreqs = loadgen.skewed_requests(
        [("veh-s", [{"uuid": "veh-s", "trace": [0]},
                    {"uuid": "veh-s", "trace": [1]}])],
        5, share=1.0, hot_frac=1.0, rng=rng, stream=True)
    assert [r["uuid"] for r in sreqs] == [
        "veh-s", "veh-s", "veh-s~c1", "veh-s~c1", "veh-s~c2"]


def test_step_stats_admitted_view(loadgen):
    mk = loadgen.Sample
    samples = [mk(0.0, 0.0, 0.1, 200, False)] * 8 + \
              [mk(0.0, 0.0, 0.01, 429, False)] * 2
    st = loadgen.step_stats(samples, offered_rate=10.0)
    assert st["shed_fraction"] == pytest.approx(0.2)
    assert st["admitted_quantiles"]["p99_ms"] is not None
    # the admitted tail excludes the fast sheds entirely
    assert st["admitted_quantiles"]["p50_ms"] > 50.0
