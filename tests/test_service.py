import json
import threading
import urllib.parse
import urllib.request

import numpy as np
import pytest

from reporter_tpu.matching import SegmentMatcher, MatcherConfig
from reporter_tpu.serve import ReporterService
from reporter_tpu.tiles.network import grid_city
from reporter_tpu.tiles.arrays import build_graph_arrays
from reporter_tpu.tiles.ubodt import build_ubodt


@pytest.fixture(scope="module")
def service_url():
    city = grid_city(rows=5, cols=5, spacing_m=150.0)
    arrays = build_graph_arrays(city, cell_size=100.0)
    ubodt = build_ubodt(arrays, delta=2000.0)
    matcher = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=MatcherConfig())
    service = ReporterService(matcher, max_wait_ms=5.0)
    httpd = service.make_server("127.0.0.1", 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = "http://127.0.0.1:%d" % httpd.server_port
    yield url, arrays
    httpd.shutdown()


def get_json(url):
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def post_json(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), headers={"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def street_trace(arrays, row=2, n=10, t0=1000):
    nodes = [row * 5 + c for c in range(5)]
    t = np.linspace(0.05, 0.9, n)
    xs = np.interp(t, np.linspace(0, 1, 5), arrays.node_x[nodes])
    ys = np.interp(t, np.linspace(0, 1, 5), arrays.node_y[nodes])
    lat, lon = arrays.proj.to_latlon(xs, ys)
    return {
        "uuid": "veh-%d" % row,
        "trace": [
            {"lat": float(a), "lon": float(o), "time": t0 + 15 * i}
            for i, (a, o) in enumerate(zip(lat, lon))
        ],
        "match_options": {"mode": "auto", "report_levels": [0, 1, 2], "transition_levels": [0, 1, 2]},
    }


class TestReportEndpoint:
    def test_get_report(self, service_url):
        url, arrays = service_url
        trace = street_trace(arrays)
        q = urllib.parse.quote(json.dumps(trace))
        code, out = get_json("%s/report?json=%s" % (url, q))
        assert code == 200
        assert "datastore" in out and "segment_matcher" in out and "stats" in out
        assert out["datastore"]["mode"] == "auto"
        assert out["datastore"]["reports"], "expected reports from a clean drive"
        for r in out["datastore"]["reports"]:
            assert set(r) >= {"id", "t0", "t1", "length", "queue_length"}

    def test_post_report(self, service_url):
        url, arrays = service_url
        code, out = post_json(url + "/report", street_trace(arrays))
        assert code == 200 and out["datastore"]["reports"]

    def test_missing_uuid(self, service_url):
        url, arrays = service_url
        trace = street_trace(arrays)
        del trace["uuid"]
        code, out = post_json(url + "/report", trace)
        assert code == 400 and out["error"] == "uuid is required"

    def test_short_trace(self, service_url):
        url, arrays = service_url
        trace = street_trace(arrays)
        trace["trace"] = trace["trace"][:1]
        code, out = post_json(url + "/report", trace)
        assert code == 400 and "non zero length array" in out["error"]

    def test_missing_levels(self, service_url):
        url, arrays = service_url
        trace = street_trace(arrays)
        del trace["match_options"]["report_levels"]
        code, out = post_json(url + "/report", trace)
        assert code == 400 and "report_levels" in out["error"]
        trace = street_trace(arrays)
        del trace["match_options"]["transition_levels"]
        code, out = post_json(url + "/report", trace)
        assert code == 400 and "transition_levels" in out["error"]

    def test_bad_action(self, service_url):
        url, _ = service_url
        code, out = post_json(url + "/bogus", {})
        assert code == 400 and "valid action" in out["error"]

    def test_bad_json(self, service_url):
        url, _ = service_url
        code, out = get_json(url + "/report?json=%7Bnot")
        assert code == 400


class TestBatchEndpoint:
    def test_batch(self, service_url):
        url, arrays = service_url
        traces = [street_trace(arrays, row=r) for r in range(4)]
        code, out = post_json(url + "/trace_attributes_batch", {"traces": traces})
        assert code == 200
        assert len(out["results"]) == 4
        for res in out["results"]:
            assert res["datastore"]["reports"]

    def test_batch_matches_single(self, service_url):
        url, arrays = service_url
        trace = street_trace(arrays, row=1)
        _, single = post_json(url + "/report", trace)
        _, batch = post_json(url + "/trace_attributes_batch", {"traces": [trace]})
        assert batch["results"][0]["datastore"] == single["datastore"]

    def test_batch_validation(self, service_url):
        url, arrays = service_url
        code, out = post_json(url + "/trace_attributes_batch", {"traces": []})
        assert code == 400
        bad = street_trace(arrays)
        del bad["uuid"]
        code, out = post_json(url + "/trace_attributes_batch", {"traces": [bad]})
        assert code == 400 and "trace 0" in out["error"]

    def test_concurrent_singles_share_batches(self, service_url):
        url, arrays = service_url
        results = [None] * 8

        def hit(i):
            results[i] = post_json(url + "/report", street_trace(arrays, row=i % 4))

        threads = [threading.Thread(target=hit, args=(i,)) for i in range(8)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        assert all(code == 200 and out["datastore"]["reports"] for code, out in results)


def test_non_object_body_gets_400(service_url):
    url, _ = service_url
    code, out = post_json(url + "/report", [1, 2])
    assert code == 400 and "object" in out["error"]


@pytest.fixture(scope="module")
def service_matcher():
    city = grid_city(rows=5, cols=5, spacing_m=150.0)
    arrays = build_graph_arrays(city, cell_size=100.0)
    ubodt = build_ubodt(arrays, delta=2000.0)
    return SegmentMatcher(arrays=arrays, ubodt=ubodt, config=MatcherConfig())


def test_thread_pool_env_bounds_concurrency(monkeypatch, service_matcher):
    """THREAD_POOL_COUNT=1 (reference env, reporter_service.py:37-45) must
    serialise request handling: with two concurrent requests, the second
    enters only after the first leaves."""
    import threading
    import time as _time

    from reporter_tpu.serve.service import ReporterService

    monkeypatch.setenv("THREAD_POOL_COUNT", "1")
    svc = ReporterService(service_matcher, max_wait_ms=1.0)
    srv = svc.make_server("127.0.0.1", 0)
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        import urllib.request

        active = []
        peaks = []
        lock = threading.Lock()
        orig = svc.handle_report

        def tracked(trace):
            with lock:
                active.append(1)
                peaks.append(len(active))
            _time.sleep(0.15)
            out = orig(trace)
            with lock:
                active.pop()
            return out

        svc.handle_report = tracked
        body = json.dumps({
            "uuid": "v", "match_options": {"report_levels": [0, 1],
                                           "transition_levels": [0, 1]},
            "trace": [{"lat": 37.75, "lon": -122.45, "time": 0},
                      {"lat": 37.7501, "lon": -122.4501, "time": 15}],
        }).encode()

        def hit():
            urllib.request.urlopen(urllib.request.Request(
                "http://127.0.0.1:%d/report" % port, data=body), timeout=30).read()

        ts = [threading.Thread(target=hit) for _ in range(3)]
        for x in ts:
            x.start()
        for x in ts:
            x.join()
        assert peaks and max(peaks) == 1, peaks
    finally:
        srv.shutdown()
        srv.server_close()


class TestDeferredBoot:
    """The CLI binds the socket with NO engine and builds it behind the
    socket (a slow accelerator init must not leave the service dark --
    no bind, no /health)."""

    def test_deferred_service_health_then_attach(self, service_matcher):
        from reporter_tpu.serve.service import ReporterService

        svc = ReporterService(None)
        code, h = svc.handle_health()
        assert code == 200 and h["status"] == "ok"
        assert h["warming"] is True and h["backend"] is None
        code, out = svc.handle_report({"uuid": "v"})
        assert code == 503 and "initialising" in out["error"]
        code, out = svc.handle_batch({"traces": [{"uuid": "v"}]})
        assert code == 503 and "initialising" in out["error"]

        svc.attach_matcher(service_matcher)
        code, h = svc.handle_health()
        assert h["warming"] is False and h["backend"] == service_matcher.backend
        assert h["edges"] == int(service_matcher.arrays.num_edges)
        assert svc.threshold_sec == service_matcher.cfg.threshold_sec
        # a real request now round-trips
        trace = street_trace(service_matcher.arrays)
        code, out = svc.handle_report(trace)
        assert code == 200 and "segment_matcher" in out

    def test_cli_engine_build_failure_exits_nonzero(self, tmp_path):
        """A failed engine build (missing network file) must stop the
        bound listener and exit 1, not serve 503s forever."""
        import reporter_tpu.serve.__main__ as cli

        conf = tmp_path / "conf.json"
        conf.write_text(json.dumps({
            "network": {"type": "file", "path": str(tmp_path / "missing.json")},
            "warmup": False,
        }))
        rc = cli.main(["serve", str(conf), "127.0.0.1:0"])
        assert rc == 1

    def test_cli_warmup_failure_exits_nonzero(self, tmp_path, monkeypatch):
        """A failed --warmup pass must stop the server with exit 1, not
        attach an engine that could not run its warm set."""
        import reporter_tpu.serve.__main__ as cli
        from reporter_tpu import faults

        monkeypatch.setenv("REPORTER_FAULT_DISPATCH", "always")
        faults.reset()
        conf = tmp_path / "conf.json"
        conf.write_text(json.dumps({
            "network": {"type": "grid", "rows": 3, "cols": 3},
            "backend": "jax"}))
        try:
            rc = cli.main(["serve", "--warmup", str(conf), "127.0.0.1:0"])
        finally:
            monkeypatch.delenv("REPORTER_FAULT_DISPATCH")
            faults.reset()
        assert rc == 1


def test_max_inflight_plumbs_to_batcher(service_matcher):
    """batch.max_inflight (config) must bound the MicroBatcher's dispatch
    -> finisher hand-off queue: that depth is what overlaps host
    association with device compute (4 by default on an
    accelerator)."""
    from reporter_tpu.serve.service import ReporterService

    svc = ReporterService(service_matcher, max_inflight=3)
    assert svc.batcher._finish_q.maxsize == 3
    # default resolves by physical platform: tests run on cpu devices,
    # where host compute and association share cores -> 2 (4 on real
    # accelerators; see MicroBatcher.__init__)
    svc_default = ReporterService(service_matcher)
    assert svc_default.batcher._finish_q.maxsize == 2


def test_concurrent_requests_micro_batch(service_url):
    """32 parallel /report calls must all succeed and be aggregated into
    fewer device batches than requests (the MicroBatcher's whole point:
    concurrent singles share one [B, T] device program)."""
    url, arrays = service_url
    body = json.dumps(street_trace(arrays)).encode()

    results = []
    errors = []

    def hit():
        try:
            r = urllib.request.urlopen(urllib.request.Request(
                url + "/report", data=body,
                headers={"Content-Type": "application/json"}), timeout=60)
            results.append(json.loads(r.read()))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    ts = [threading.Thread(target=hit) for _ in range(32)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors, errors[:3]
    assert len(results) == 32
    assert all("datastore" in r and "stats" in r for r in results)
    # identical input -> identical output across every concurrent response
    assert all(r == results[0] for r in results[1:])


def test_wire_garbage_does_not_kill_the_listener(service_url):
    """Protocol-level fuzz: raw-socket garbage, truncated frames, lying
    Content-Lengths, oversized header lines, abrupt resets, and pipelined
    request bytes must never take the listener down or wedge a handler --
    after every abuse the server still answers a clean /report."""
    import socket

    url, arrays = service_url
    host, port = url.split("//")[1].rsplit(":", 1)
    port = int(port)

    def raw(payload: bytes, read: bool = True, wait_s: float = 5.0):
        s = socket.create_connection((host, port), timeout=10)
        try:
            # the provoked closes can RST mid-send/mid-recv; any OSError
            # here IS the abuse landing, not a test failure
            try:
                s.sendall(payload)
                if read:
                    s.settimeout(wait_s)
                    s.recv(4096)
            except OSError:
                pass
        finally:
            s.close()

    abuses = [
        (b"\x00\xff\x17garbage that is not http at all\r\n\r\n", True, 5.0),
        (b"GET /health HTTP/1.1\r\nHost: x\r\n" + b"X-Pad: " + b"a" * 70000
         + b"\r\n\r\n", True, 5.0),
        # lying Content-Length: the server blocks reading a body that never
        # comes -- don't wait for a response it cannot send
        (b"POST /report HTTP/1.1\r\nHost: x\r\nContent-Length: 99999\r\n\r\n"
         b"{\"uuid\"", True, 0.5),
        (b"POST /report HTTP/1.1\r\nHost: x\r\nContent-Length: -5\r\n\r\nxxxxx",
         True, 5.0),
        (b"POST /report HTTP/1.1\r\nHost: x\r\nContent-Length: notanumber\r\n\r\n{}",
         True, 5.0),
        (b"GET /health HTTP/1.1\r\nHost: x\r\n\r\nGET /health HTTP/1.1\r\n"
         b"Host: x\r\n\r\n", True, 5.0),
        (b"POST /report HTTP/1.0\r\n\r\n", True, 5.0),
    ]
    for i, (payload, read, wait_s) in enumerate(abuses):
        raw(payload, read=read, wait_s=wait_s)
        # an abrupt reset mid-request too
        raw(payload[: max(4, len(payload) // 3)], read=False)
        # the listener still serves a full valid request after each abuse
        code, out = post_json(url + "/report", street_trace(arrays))
        assert code == 200, (i, code, out)
        assert out["datastore"]["reports"], (i, "no reports")


class TestHealthEndpoint:
    def test_health_snapshot(self, service_url):
        url, arrays = service_url
        code, out = get_json(url + "/health")
        assert code == 200
        assert out["status"] == "ok" and out["backend"] == "jax"
        assert out["edges"] > 0 and out["ubodt_rows"] > 0
        assert out["uptime_s"] >= 0
        before = out["requests"]
        # a served /report increments the counter; /health itself does not
        code, _ = post_json(url + "/report", street_trace(arrays))
        assert code == 200
        code, after = get_json(url + "/health")
        assert code == 200 and after["requests"] == before + 1
        assert after["errors"] == out["errors"]

    def test_keepalive_survives_post_with_body_to_health(self, service_url):
        """POST /health (and any early-400 path) must drain the request body:
        the server speaks HTTP/1.1 keep-alive, so leftover body bytes would
        be parsed as the next request line on the same socket."""
        import http.client

        url, arrays = service_url
        host_port = url.split("//")[1]
        conn = http.client.HTTPConnection(host_port, timeout=30)
        try:
            body = json.dumps({"junk": "x" * 256})
            conn.request("POST", "/health", body=body,
                         headers={"Content-Type": "application/json"})
            r1 = conn.getresponse()
            assert r1.status == 200
            assert json.loads(r1.read())["status"] == "ok"
            # the SAME socket must serve a valid follow-up request
            conn.request("POST", "/report", body=json.dumps(street_trace(arrays)),
                         headers={"Content-Type": "application/json"})
            r2 = conn.getresponse()
            assert r2.status == 200
            assert json.loads(r2.read())["datastore"]["reports"]
            # and an early-400 path (bad action) must drain too
            conn.request("POST", "/nonsense", body=body,
                         headers={"Content-Type": "application/json"})
            r3 = conn.getresponse()
            assert r3.status == 400 and "valid action" in json.loads(r3.read())["error"]
            conn.request("GET", "/health")
            r4 = conn.getresponse()
            assert r4.status == 200
        finally:
            conn.close()
