"""Fleet tier chaos suite (docs/serving-fleet.md): the session-affine
router + replicas are driven through the real HTTP seam and the fault
contracts are asserted end to end:

  (a) rendezvous affinity: the same vehicle uuid keeps landing on the
      same replica, and a killed replica remaps ONLY its own vehicles
  (b) kill-mid-load failover: requests keep succeeding through the
      router while a replica is hard-killed (passive ejection + active
      probing take it out of rotation)
  (c) graceful drain: a SIGTERM'd replica finishes its inflight work,
      answers new requests 503 {"status": "draining"} with Retry-After,
      exits 0, and the router rotates traffic off it (rolling restart
      brings the vehicle back to its primary)
  (d) the new faults.py points: router->replica connect refused is
      absorbed by failover, a flapped health probe is debounced, a
      slow-accepting replica is hedged around
  (e) keep-alive connection reuse on the shared pool is real (counted)
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from reporter_tpu import faults
from reporter_tpu.matching import MatcherConfig, SegmentMatcher
from reporter_tpu.serve import router as router_mod
from reporter_tpu.serve.router import FleetRouter, Replica, rendezvous_score
from reporter_tpu.serve.service import ReporterService
from reporter_tpu.stream.client import _post_json
from reporter_tpu.tiles.arrays import build_graph_arrays
from reporter_tpu.tiles.network import grid_city
from reporter_tpu.tiles.ubodt import build_ubodt
from reporter_tpu.utils.httppool import C_CONN_OPENED, C_CONN_REUSED, HttpPool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    for p in faults.POINTS:
        monkeypatch.delenv("REPORTER_FAULT_" + p.upper(), raising=False)
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def engine():
    city = grid_city(rows=5, cols=5, spacing_m=150.0)
    arrays = build_graph_arrays(city, cell_size=100.0)
    ubodt = build_ubodt(arrays, delta=2000.0)
    return arrays, ubodt


def street_trace(arrays, uuid, row=2, n=8, t0=1000):
    nodes = [row * 5 + c for c in range(5)]
    t = np.linspace(0.05, 0.9, n)
    xs = np.interp(t, np.linspace(0, 1, 5), arrays.node_x[nodes])
    ys = np.interp(t, np.linspace(0, 1, 5), arrays.node_y[nodes])
    lat, lon = arrays.proj.to_latlon(xs, ys)
    return {
        "uuid": uuid,
        "trace": [
            {"lat": float(a), "lon": float(o), "time": t0 + 15 * i}
            for i, (a, o) in enumerate(zip(lat, lon))
        ],
        "match_options": {"mode": "auto", "report_levels": [0, 1],
                          "transition_levels": [0, 1]},
    }


class _Replica:
    """One in-process serve replica with a pinned replica id."""

    def __init__(self, arrays, ubodt, rid, port=0, **svc_kw):
        self.rid = rid
        prev = os.environ.get("REPORTER_REPLICA_ID")
        os.environ["REPORTER_REPLICA_ID"] = rid
        try:
            matcher = SegmentMatcher(arrays=arrays, ubodt=ubodt,
                                     config=MatcherConfig(), backend="cpu")
            self.svc = ReporterService(matcher, max_wait_ms=2.0, **svc_kw)
        finally:
            if prev is None:
                os.environ.pop("REPORTER_REPLICA_ID", None)
            else:
                os.environ["REPORTER_REPLICA_ID"] = prev
        self.httpd = self.svc.make_server("127.0.0.1", port)
        self.port = self.httpd.server_port
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.url = "http://127.0.0.1:%d" % self.port

    def kill(self):
        """Hard kill at the HTTP layer: stop accepting AND cut every
        live connection (what a SIGKILL's socket teardown looks like to
        the router)."""
        self.httpd.shutdown()
        self.httpd.close_lingering()
        self.httpd.server_close()

    def close(self):
        try:
            self.kill()
        except Exception:  # noqa: BLE001 - already killed by the test
            pass


class _Fleet:
    def __init__(self, arrays, ubodt, n=3, router_kw=None, **svc_kw):
        self.replicas = [
            _Replica(arrays, ubodt, "rep-%d" % i, **svc_kw)
            for i in range(n)]
        self.router = FleetRouter([r.url for r in self.replicas],
                                  probe_interval_s=0.2,
                                  **(router_kw or {}))
        self.router.start()
        self.httpd = self.router.make_server("127.0.0.1", 0)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.url = "http://127.0.0.1:%d" % self.httpd.server_port

    def by_id(self, rid):
        return next(r for r in self.replicas if r.rid == rid)

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self.router.stop()
        for r in self.replicas:
            r.close()


@pytest.fixture
def fleet_factory(engine):
    arrays, ubodt = engine
    fleets = []

    def make(n=3, router_kw=None, **svc_kw):
        f = _Fleet(arrays, ubodt, n=n, router_kw=router_kw, **svc_kw)
        fleets.append(f)
        return f

    yield make
    for f in fleets:
        f.close()


def post_json(url, payload, headers=None, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers=dict({"Content-Type": "application/json"},
                     **(headers or {})))
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read().decode())


def get_json(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, dict(r.headers), json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read().decode())


# -- rendezvous hashing: the remap-confinement property ----------------------


def test_rendezvous_remap_confined_to_lost_replica():
    urls = ["http://h%d:8000" % i for i in range(5)]
    uuids = ["veh-%04d" % i for i in range(400)]

    def top(uuid, pool):
        return max(pool, key=lambda u: rendezvous_score(uuid, u))

    before = {u: top(u, urls) for u in uuids}
    dead = urls[2]
    survivors = [u for u in urls if u != dead]
    after = {u: top(u, survivors) for u in uuids}
    moved = {u for u in uuids if before[u] != after[u]}
    # EXACTLY the dead replica's vehicles move, nobody else's
    assert moved == {u for u in uuids if before[u] == dead}
    assert moved  # the dead replica did own some vehicles
    # and a removal never concentrates them on one survivor (HRW spreads)
    landed = {after[u] for u in moved}
    assert len(landed) > 1


def test_affinity_stable_and_replica_header(engine, fleet_factory):
    arrays, _ = engine
    fleet = fleet_factory()
    st, _hd, health = get_json(fleet.url + "/health")
    assert st == 200 and health["available"] == 3
    seen = {}
    for k in range(12):
        u = "veh-%d" % k
        st, hd, _body = post_json(fleet.url + "/report",
                                  street_trace(arrays, u))
        assert st == 200
        assert hd.get("X-Reporter-Replica") in ("rep-0", "rep-1", "rep-2")
        seen[u] = hd["X-Reporter-Replica"]
    assert len(set(seen.values())) > 1  # traffic actually spreads
    for u, rid in seen.items():
        st, hd, _body = post_json(fleet.url + "/report",
                                  street_trace(arrays, u))
        assert st == 200 and hd["X-Reporter-Replica"] == rid
    # the batch endpoint routes too (by its first trace's uuid)
    u0 = "veh-0"
    st, hd, body = post_json(
        fleet.url + "/trace_attributes_batch",
        {"traces": [street_trace(arrays, u0), street_trace(arrays, u0)]})
    assert st == 200 and len(body["results"]) == 2
    assert hd["X-Reporter-Replica"] == seen[u0]


def test_kill_mid_load_failover_and_bounded_remap(engine, fleet_factory):
    arrays, _ = engine
    fleet = fleet_factory()
    uuids = ["veh-%d" % k for k in range(18)]
    before = {}
    for u in uuids:
        st, hd, _ = post_json(fleet.url + "/report", street_trace(arrays, u))
        assert st == 200
        before[u] = hd["X-Reporter-Replica"]
    dead_rid = before[uuids[0]]
    fleet.by_id(dead_rid).kill()
    after = {}
    for u in uuids:  # no failed requests during the failover window
        st, hd, _ = post_json(fleet.url + "/report", street_trace(arrays, u))
        assert st == 200, u
        after[u] = hd["X-Reporter-Replica"]
    moved = {u for u in uuids if after[u] != before[u]}
    assert moved == {u for u in uuids if before[u] == dead_rid}
    assert dead_rid not in after.values()
    # the prober notices and /health reports the hole
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        st, _hd, health = get_json(fleet.url + "/health")
        if health["available"] == 2:
            break
        time.sleep(0.1)
    assert health["available"] == 2


def test_drain_rotates_off_and_rolling_restart_returns(engine, fleet_factory):
    arrays, ubodt = engine
    fleet = fleet_factory()
    uuids = ["veh-%d" % k for k in range(12)]
    before = {}
    for u in uuids:
        st, hd, _ = post_json(fleet.url + "/report", street_trace(arrays, u))
        assert st == 200
        before[u] = hd["X-Reporter-Replica"]
    target_rid = before[uuids[0]]
    target = fleet.by_id(target_rid)
    target.svc.begin_drain()
    # the replica itself now answers 503 "draining" (distinct from
    # unhealthy) with a Retry-After hint
    st, hd, body = get_json(target.url + "/health")
    assert st == 503 and body["status"] == "draining"
    st, hd, body = post_json(target.url + "/report",
                             street_trace(arrays, uuids[0]))
    assert st == 503 and body.get("status") == "draining"
    assert int(hd.get("Retry-After", 0)) >= 1
    # through the router: its vehicles keep succeeding (failover
    # re-dispatch absorbs the 503s), nobody else moves
    for u in uuids:
        st, hd, _ = post_json(fleet.url + "/report", street_trace(arrays, u))
        assert st == 200, u
        if before[u] != target_rid:
            assert hd["X-Reporter-Replica"] == before[u]
        else:
            assert hd["X-Reporter-Replica"] != target_rid
    # the prober sees the drain (no ejection bookkeeping: deliberate)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        rep = next(r for r in fleet.router.replicas
                   if (r.id or "") == target_rid)
        if rep.state == "draining":
            break
        time.sleep(0.1)
    assert rep.state == "draining"
    # rolling restart: the drained process goes away, a fresh replica
    # binds the SAME port/url — the vehicle comes back to its primary
    port = target.port
    target.kill()
    replacement = _Replica(arrays, ubodt, target_rid, port=port)
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            fleet.router.probe_all()
            if rep.available():
                break
            time.sleep(0.1)
        assert rep.available()
        st, hd, _ = post_json(fleet.url + "/report",
                              street_trace(arrays, uuids[0]))
        assert st == 200 and hd["X-Reporter-Replica"] == target_rid
    finally:
        replacement.close()


# -- the new fault-injection points ------------------------------------------


def test_router_connect_refused_absorbed_by_failover(
        engine, fleet_factory, monkeypatch):
    arrays, _ = engine
    fleet = fleet_factory()
    n0 = router_mod.C_FAILOVER.labels("network").value
    monkeypatch.setenv("REPORTER_FAULT_ROUTER_CONNECT", "refused:1")
    st, hd, _ = post_json(fleet.url + "/report",
                          street_trace(arrays, "veh-0"))
    assert st == 200  # the injected refusal never reached the client
    assert router_mod.C_FAILOVER.labels("network").value >= n0 + 1


def test_health_flap_is_debounced_then_sustained_failure_ejects(
        engine, fleet_factory, monkeypatch):
    fleet = fleet_factory()
    first = fleet.router.replicas[0]
    assert first.available()
    # ONE flapped probe: below the unhealthy_after=2 debounce, the
    # replica must stay in rotation
    monkeypatch.setenv("REPORTER_FAULT_HEALTH_FLAP", "1")
    fleet.router.probe_all()
    assert first.available() and first.state == "healthy"
    # sustained flapping: now it must go
    monkeypatch.setenv("REPORTER_FAULT_HEALTH_FLAP", "always")
    faults.reset()
    fleet.router.probe_all()
    fleet.router.probe_all()
    assert first.state == "unhealthy" and not first.available()
    # recovery is debounced too (healthy_after=2): one good probe is not
    # enough, two are
    monkeypatch.delenv("REPORTER_FAULT_HEALTH_FLAP")
    fleet.router.probe_all()
    assert first.state == "unhealthy"
    fleet.router.probe_all()
    assert first.state == "healthy" and first.available()


def test_slow_accept_is_hedged_around(engine, fleet_factory, monkeypatch):
    arrays, _ = engine
    fleet = fleet_factory(router_kw={"hedge_ms": 100.0})
    hedges0 = router_mod.C_HEDGES.value
    wins0 = router_mod.C_HEDGE_WINS.value
    # the primary's NEXT /report stalls 1.2 s at the door; the hedge
    # fires at 100 ms and the second-ranked replica answers instead
    monkeypatch.setenv("REPORTER_FAULT_REPLICA_SLOW_ACCEPT", "1.2:1")
    t0 = time.monotonic()
    st, _hd, _ = post_json(fleet.url + "/report",
                           street_trace(arrays, "veh-7"))
    took = time.monotonic() - t0
    assert st == 200
    assert took < 1.0, "hedge did not cut the straggler (took %.2fs)" % took
    assert router_mod.C_HEDGES.value >= hedges0 + 1
    assert router_mod.C_HEDGE_WINS.value >= wins0 + 1


def test_router_sheds_when_saturated(engine, fleet_factory, monkeypatch):
    arrays, _ = engine
    fleet = fleet_factory(router_kw={"max_inflight": 1})
    shed0 = router_mod.C_SHED.value
    monkeypatch.setenv("REPORTER_FAULT_REPLICA_SLOW_ACCEPT", "0.8:1")
    results = []

    def hit(u):
        results.append(post_json(fleet.url + "/report",
                                 street_trace(arrays, u)))

    t1 = threading.Thread(target=hit, args=("veh-1",))
    t1.start()
    time.sleep(0.25)  # the slow request is now holding the only slot
    st, hd, body = post_json(fleet.url + "/report",
                             street_trace(arrays, "veh-2"))
    t1.join()
    assert st == 429
    assert int(hd.get("Retry-After", 0)) >= 1
    assert router_mod.C_SHED.value >= shed0 + 1
    assert results[0][0] == 200  # the accepted request still succeeded


def test_no_replica_available_is_503(engine):
    router = FleetRouter(["http://127.0.0.1:9"])  # discard port: refused
    router.probe_all()
    httpd = router.make_server("127.0.0.1", 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = "http://127.0.0.1:%d" % httpd.server_port
    try:
        st, hd, body = get_json(url + "/health")
        assert st == 503 and body["status"] == "unavailable"
        st, hd, body = post_json(
            url + "/report", {"uuid": "v", "trace": [], "match_options": {}})
        assert st == 503
        assert int(hd.get("Retry-After", 0)) >= 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        router.stop()


# -- health statuses are distinct --------------------------------------------


def test_health_draining_vs_unhealthy_statuses(engine):
    arrays, ubodt = engine
    rep = _Replica(arrays, ubodt, "rep-x")
    try:
        st, body = rep.svc.handle_health()
        assert st == 200 and body["status"] == "ok"
        assert body["replica"] == "rep-x"
        rep.svc.unhealthy_reason = "batcher thread died: boom"
        st, body = rep.svc.handle_health()
        assert st == 503 and body["status"] == "unhealthy"
        rep.svc.unhealthy_reason = None
        rep.svc.begin_drain()
        st, body = rep.svc.handle_health()
        assert st == 503 and body["status"] == "draining"
        # unhealthy outranks draining (a crashed batcher needs a restart
        # even mid-drain)
        rep.svc.unhealthy_reason = "batcher thread died: boom"
        st, body = rep.svc.handle_health()
        assert st == 503 and body["status"] == "unhealthy"
    finally:
        rep.close()


# -- keep-alive connection reuse ---------------------------------------------


def test_connection_reuse_is_real_and_counted(engine):
    arrays, ubodt = engine
    rep = _Replica(arrays, ubodt, "rep-ka")
    try:
        opened0 = C_CONN_OPENED.labels("matcher").value
        reused0 = C_CONN_REUSED.labels("matcher").value
        for k in range(6):
            out = _post_json(rep.url + "/report",
                             street_trace(arrays, "veh-%d" % k))
            assert out is not None and "segment_matcher" in out
        opened = C_CONN_OPENED.labels("matcher").value - opened0
        reused = C_CONN_REUSED.labels("matcher").value - reused0
        # 6 sequential requests: one connect, five keep-alive reuses
        assert opened == 1
        assert reused >= 5
    finally:
        rep.close()


def test_pool_recovers_transparently_from_stale_keepalive(engine):
    arrays, ubodt = engine
    pool = HttpPool()
    rep = _Replica(arrays, ubodt, "rep-stale")
    body = json.dumps(street_trace(arrays, "veh-1")).encode()
    try:
        st, _h, _b = pool.request(
            "POST", rep.url + "/report", body=body,
            headers={"Content-Type": "application/json"}, target="t")
        assert st == 200
        # the server cuts the pooled connection behind our back (idle
        # keep-alive churn); the next request must transparently retry
        # on a fresh connection, not error
        rep.httpd.close_lingering()
        time.sleep(0.1)
        st, _h, _b = pool.request(
            "POST", rep.url + "/report", body=body,
            headers={"Content-Type": "application/json"}, target="t")
        assert st == 200
    finally:
        pool.close()
        rep.close()


# -- graceful drain, full process contract -----------------------------------


def test_sigterm_drain_finishes_inflight_then_exits_zero(engine, tmp_path):
    """The acceptance contract: SIGTERM -> inflight request completes
    (no client-visible reset), new requests answer 503 "draining" with
    Retry-After, /health flips to "draining", exit code 0."""
    arrays, _ = engine
    conf = {
        "network": {"type": "grid", "rows": 5, "cols": 5,
                    "spacing_m": 150.0},
        "matcher": {"search_radius": 50.0},
        "backend": "cpu",
        # a 1.5 s batch-fill window makes every /report spend ~1.5 s
        # inside the batcher: the inflight request the drain must finish
        "batch": {"max_batch": 64, "max_wait_ms": 1500},
        "warmup": False,
    }
    conf_path = tmp_path / "config.json"
    conf_path.write_text(json.dumps(conf))
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               REPORTER_REPLICA_ID="rep-drain",
               REPORTER_DRAIN_GRACE_S="15")
    proc = subprocess.Popen(
        [sys.executable, "-m", "reporter_tpu.serve", str(conf_path),
         "127.0.0.1:0"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        # the CLI binds :0; recover the bound port from the log line
        port = None
        deadline = time.monotonic() + 60
        buf = b""
        while time.monotonic() < deadline and port is None:
            line = proc.stdout.readline()
            if not line:
                time.sleep(0.05)
                continue
            buf += line
            if b"service on 127.0.0.1:" in line:
                port = int(line.split(b"127.0.0.1:")[1].split()[0])
        assert port, "no bind line in serve output: %r" % buf
        url = "http://127.0.0.1:%d" % port
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            try:
                st, _h, h = get_json(url + "/health", timeout=2)
                if st == 200 and h.get("backend"):
                    break
            except Exception:  # noqa: BLE001 - still booting
                pass
            time.sleep(0.25)
        else:
            pytest.fail("service never became healthy")

        inflight = {}

        def slow_request():
            inflight["result"] = post_json(
                url + "/report", street_trace(arrays, "veh-inflight"),
                timeout=30)

        t = threading.Thread(target=slow_request)
        t.start()
        time.sleep(0.6)  # the request is inside its 1.5 s batch window
        proc.send_signal(signal.SIGTERM)
        time.sleep(0.3)
        # new request during the drain window: refused, retryable,
        # explicitly "draining"
        st, hd, body = post_json(url + "/report",
                                 street_trace(arrays, "veh-late"),
                                 timeout=10)
        assert st == 503 and body.get("status") == "draining"
        assert int(hd.get("Retry-After", 0)) >= 1
        st, _hd, body = get_json(url + "/health", timeout=10)
        assert st == 503 and body["status"] == "draining"
        # the inflight request finished normally — no reset, no 5xx
        t.join(timeout=20)
        assert not t.is_alive()
        st, hd, body = inflight["result"]
        assert st == 200 and "segment_matcher" in body
        assert hd.get("X-Reporter-Replica") == "rep-drain"
        assert proc.wait(timeout=20) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


# -- geo-aware ranking (flag-gated; docs/serving-fleet.md "Sharded
# tables") -----------------------------------------------------------------


def test_geo_off_is_bitforbit_rendezvous(monkeypatch):
    """With REPORTER_ROUTER_GEO unset the ranking is the PR 9 rendezvous
    hash exactly, for every uuid — even when replicas advertise shards
    and requests carry coordinates."""
    monkeypatch.delenv("REPORTER_ROUTER_GEO", raising=False)
    router = FleetRouter(
        ["http://127.0.0.1:1", "http://127.0.0.1:2", "http://127.0.0.1:3"],
        probe_interval_s=3600.0)
    try:
        assert router.geo_routing is False
        for i, r in enumerate(router.replicas):
            r.shard = "%d/3" % i
        for k in range(50):
            uuid = "veh-%d" % k
            want = sorted(
                router.replicas,
                key=lambda r: rendezvous_score(uuid, r.url), reverse=True)
            assert [r.url for r in router.ranked(uuid)] == \
                [r.url for r in want]
            # geo is never even computed with the flag off: the caller
            # passes None, and an explicit geo changes nothing either
            assert [r.url for r in router.ranked(uuid, (52.5, 13.4))] == \
                [r.url for r in want]
    finally:
        router.stop()


def test_geo_on_prefers_shard_owner(monkeypatch):
    """Flag on: the replica whose advertised shard covers the request's
    geographic cell ranks first; the rendezvous hash still orders the
    rest, the mapping is stable per cell, and uuids without coordinates
    keep plain rendezvous ranking."""
    from reporter_tpu.serve.router import C_GEO, geo_cell

    monkeypatch.setenv("REPORTER_ROUTER_GEO", "1")
    monkeypatch.setenv("REPORTER_ROUTER_GEO_CELL_DEG", "0.25")
    router = FleetRouter(
        ["http://127.0.0.1:1", "http://127.0.0.1:2", "http://127.0.0.1:3"],
        probe_interval_s=3600.0)
    try:
        assert router.geo_routing is True
        for i, r in enumerate(router.replicas):
            r.shard = "%d/3" % i
        geo = (52.5, 13.4)
        cell = geo_cell(geo[0], geo[1], 0.25)
        owner = next(r for r in router.replicas
                     if router._geo_pref(r, cell))
        g0 = sum(C_GEO.labels(o).value for o in ("steered", "aligned"))
        for k in range(20):
            order = router.ranked("veh-%d" % k, geo)
            assert order[0] is owner
            # the tail is still rendezvous-ordered
            tail = [r for r in router.replicas if r is not owner]
            want = sorted(tail, key=lambda r: rendezvous_score(
                "veh-%d" % k, r.url), reverse=True)
            assert [r.url for r in order[1:]] == [r.url for r in want]
        assert sum(C_GEO.labels(o).value
                   for o in ("steered", "aligned")) == g0 + 20
        # no coordinate -> plain rendezvous, even with the flag on
        for k in range(20):
            uuid = "veh-%d" % k
            want = sorted(
                router.replicas,
                key=lambda r: rendezvous_score(uuid, r.url), reverse=True)
            assert [r.url for r in router.ranked(uuid)] == \
                [r.url for r in want]
        # a replica with no (or junk) shard never gets the bonus
        assert router._geo_pref(Replica("http://x:1"), cell) == 0
        junk = Replica("http://x:2")
        junk.shard = "weird"
        assert router._geo_pref(junk, cell) == 0
    finally:
        router.stop()


def _fleet_tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fleet_tool", os.path.join(REPO, "tools", "fleet.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_accelerator_replicas_take_one_chip_each(tmp_path, monkeypatch):
    """Without --cpu-default each replica owns one chip; more replicas
    than visible chips is refused before anything spawns (checked on the
    CPU: the chips are named through TPU_VISIBLE_CHIPS)."""
    fleet = _fleet_tool()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1")
    argv = ["--config", str(tmp_path / "c.json"), "--workdir",
            str(tmp_path / "w")]
    f = fleet.Fleet(fleet.build_parser().parse_args(argv + ["--replicas", "2"]))
    assert [c.chip for c in f.replicas] == [0, 1]
    assert [c.env["TPU_VISIBLE_CHIPS"] for c in f.replicas] == ["0", "1"]
    with pytest.raises(RuntimeError, match="no free chip"):
        f._make_replica()
    with pytest.raises(SystemExit, match="2 chip"):
        fleet.Fleet(fleet.build_parser().parse_args(
            argv + ["--replicas", "3"]))
    cpu = fleet.Fleet(fleet.build_parser().parse_args(
        argv + ["--replicas", "3", "--cpu-default"]))
    assert all(c.chip is None for c in cpu.replicas)
