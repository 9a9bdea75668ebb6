"""Host-stage attribution: every host stage of the serving and batch paths
raises ``reporter_host_stage_seconds_total{stage}`` and writes a
``reporter.<stage>`` span onto the profiler's clock, carrying the request's
trace id; captures leave the Python function tracer off unless asked."""

import glob
import gzip
import json
import os
import subprocess
import sys
import threading
import urllib.request

import pytest

from reporter_tpu.obs import attrib
from reporter_tpu.obs import profiler
from reporter_tpu.obs import trace as obs_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TILE_FIXTURE = os.path.join(REPO, "tests", "fixtures", "host_stage_tiles.json")

SERVE_STAGES = {"parse", "pack", "dispatch", "fetch", "associate", "report",
                "serialize"}
BATCH_STAGES = {"read", "pack", "dispatch", "fetch", "associate", "report",
                "tile"}


def _raised(before: dict) -> set:
    after = attrib.host_snapshot()
    return {s for s, v in after.items() if v > before.get(s, 0.0)}


def _trace_events(trace_dir: str) -> list:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    assert len(paths) == 1, paths
    with gzip.open(paths[0]) as f:
        return [e for e in json.load(f)["traceEvents"] if e.get("ph") == "X"]


def _python_tracer_events(events: list) -> list:
    # the Python function tracer names its events "$<file>:<line> <func>"
    return [e for e in events if str(e.get("name", "")).startswith("$")]


# -- a tiny archive and its tile rows ----------------------------------------


def _grid_matcher():
    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.tiles.network import grid_city

    return SegmentMatcher(network=grid_city(rows=5, cols=5, spacing_m=150.0),
                          config=MatcherConfig(), backend="jax")


def _tile_archive(matcher, root: str) -> None:
    """One shard file: 4 vehicles x 2 drives of 12-26 points at 15 s, the
    drives two hours apart, so make_matches splits 8 windows."""
    from reporter_tpu.synth.generator import TraceSynthesizer

    synth = TraceSynthesizer(matcher.arrays, seed=5)
    os.makedirs(root, exist_ok=True)
    with open(os.path.join(root, "shard0"), "w") as f:
        for v in range(4):
            for d in range(2):
                st = synth.synthesize(12 + 2 * (2 * v + d), dt=15.0, sigma=3.0,
                                      uuid="hs-%d" % v,
                                      t0=1_460_000_000.0 + 7200.0 * d)
                for p in st.trace["trace"]:
                    f.write("hs-%d,%d,%.7f,%.7f,%d\n" % (
                        v, int(p["time"]), p["lat"], p["lon"], p["accuracy"]))


def _tile_rows(matcher, root: str, dest: str) -> dict:
    """make_matches over the archive in micro-batches of 3 windows:
    {tile file: [rows in written order]}."""
    from reporter_tpu.batch.pipeline import make_matches

    make_matches(root, matcher, dest_dir=dest, microbatch=3,
                 report_levels={0, 1, 2}, transition_levels={0, 1, 2},
                 source="HS")
    out = {}
    for path in sorted(glob.glob(os.path.join(dest, "**", "*"),
                                 recursive=True)):
        if os.path.isfile(path):
            with open(path) as f:
                out[os.path.relpath(path, dest)] = f.read().splitlines()
    return out


@pytest.fixture(scope="module")
def grid_matcher():
    return _grid_matcher()


def test_make_matches_raises_batch_stages(grid_matcher, tmp_path):
    _tile_archive(grid_matcher, str(tmp_path / "arch"))
    before = attrib.host_snapshot()
    rows = _tile_rows(grid_matcher, str(tmp_path / "arch"),
                      str(tmp_path / "out"))
    assert rows
    assert BATCH_STAGES <= _raised(before)


def test_two_pass_split_writes_the_same_tile_rows(grid_matcher, tmp_path):
    """report() and the tile fan-out run as two passes per micro-batch;
    the tile files hold the rows, in the order, that the one-pass loop
    wrote (the fixture was recorded from it)."""
    _tile_archive(grid_matcher, str(tmp_path / "arch"))
    rows = _tile_rows(grid_matcher, str(tmp_path / "arch"),
                      str(tmp_path / "out"))
    with open(TILE_FIXTURE) as f:
        expected = json.load(f)
    assert sum(len(v) for v in expected.values()) > 10
    assert rows == expected


# -- the served path ----------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.serve import ReporterService
    from reporter_tpu.tiles.arrays import build_graph_arrays
    from reporter_tpu.tiles.network import grid_city
    from reporter_tpu.tiles.ubodt import build_ubodt

    arrays = build_graph_arrays(grid_city(rows=5, cols=5, spacing_m=150.0),
                                cell_size=100.0)
    matcher = SegmentMatcher(arrays=arrays, ubodt=build_ubodt(arrays,
                                                              delta=2000.0),
                             config=MatcherConfig())
    service = ReporterService(matcher, max_wait_ms=2.0)
    httpd = service.make_server("127.0.0.1", 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield "http://127.0.0.1:%d" % httpd.server_port, arrays, service, matcher
    httpd.shutdown()


def _street_trace(arrays, uuid="hs-veh", n=10):
    import numpy as np

    nodes = [2 * 5 + c for c in range(5)]
    t = np.linspace(0.05, 0.9, n)
    xs = np.interp(t, np.linspace(0, 1, 5), arrays.node_x[nodes])
    ys = np.interp(t, np.linspace(0, 1, 5), arrays.node_y[nodes])
    lat, lon = arrays.proj.to_latlon(xs, ys)
    return {
        "uuid": uuid,
        "trace": [{"lat": float(a), "lon": float(o), "time": 1000 + 15 * i}
                  for i, (a, o) in enumerate(zip(lat, lon))],
        "match_options": {"mode": "auto", "report_levels": [0, 1],
                          "transition_levels": [0, 1]},
    }


def _post_report(url, payload, trace_id):
    req = urllib.request.Request(
        url + "/report", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json",
                 "X-Reporter-Trace": trace_id})
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.status, json.loads(r.read().decode())


def test_report_raises_serving_stages(served):
    url, arrays, _service, _m = served
    before = attrib.host_snapshot()
    code, body = _post_report(url, _street_trace(arrays), "hs-serve-1")
    assert code == 200 and "datastore" in body
    assert SERVE_STAGES <= _raised(before)


def test_capture_names_host_stages_with_trace_id(served, tmp_path):
    """A capture taken while a /report runs holds reporter.<stage> host
    events carrying that request's trace id, and no Python-tracer
    events."""
    url, arrays, _service, _m = served
    with profiler.session("test", out_dir=str(tmp_path / "prof")) as d:
        code, _body = _post_report(url, _street_trace(arrays, "hs-cap"),
                                   "hs-capture-1")
    assert code == 200
    events = _trace_events(d)
    mine = {e["name"] for e in events
            if (e.get("args") or {}).get("trace_id") == "hs-capture-1"}
    assert {attrib.HOST_SPAN_PREFIX + s for s in SERVE_STAGES} <= mine
    fetch = [e for e in events if e["name"] == "reporter.fetch"
             and (e.get("args") or {}).get("trace_id") == "hs-capture-1"]
    assert fetch and all(e["args"].get("n") == "1" for e in fetch)
    assert any(e["name"] == "reporter.batch_fill" for e in events)
    assert not _python_tracer_events(events)


def test_matcher_stage_spans_carry_the_bound_trace_id(served, tmp_path):
    """The fetch and association spans of a multi-chunk call run on the
    match-collect thread and the caller's: both carry the bound id."""
    _url, arrays, _service, matcher = served
    traces = [_street_trace(arrays, "hs-m%d" % i, n=n)
              for i, n in enumerate((10, 40, 10, 40))]
    span = obs_trace.Span("test", trace_id="hs-bound-1")
    with profiler.session("test", out_dir=str(tmp_path / "prof")) as d:
        with obs_trace.bind(span):
            out = matcher.match_many(traces)
    assert len(out) == 4
    ids = {(e["name"], (e.get("args") or {}).get("trace_id"))
           for e in _trace_events(d)}
    for stage in ("pack", "dispatch", "fetch", "associate"):
        assert ("reporter." + stage, "hs-bound-1") in ids, stage


@pytest.mark.parametrize("python", [False, True])
def test_debug_profile_python_tracer_only_on_request(served, python):
    _url, _arrays, service, _m = served
    query = {"seconds": ["0.2"]}
    if python:
        query["python"] = ["1"]
    code, out = service.handle_profile(query)
    assert code == 200 and out["python"] is python
    assert bool(_python_tracer_events(_trace_events(out["trace_dir"]))) \
        is python


def test_router_passes_python_query_through():
    from reporter_tpu.serve.router import FleetRouter

    class Pool:
        def request(self, method, url, headers=None, timeout=None,
                    target=None):
            self.url = url
            return 200, {}, b"{}"

        def close(self):
            pass

    pool = Pool()
    router = FleetRouter(["http://127.0.0.1:1"], pool=pool)
    router.replicas[0].id = "rep-a"
    code, _h, _b = router.proxy_debug(
        "profile", {"replica": ["rep-a"], "seconds": ["1"], "python": ["1"]})
    assert code == 200
    assert pool.url.endswith("/debug/profile?seconds=1&python=1")


# -- the helper itself --------------------------------------------------------


def test_host_stage_never_imports_jax():
    code = (
        "import sys\n"
        "from reporter_tpu.obs import attrib\n"
        "with attrib.host_stage('parse', trace_id='t'):\n"
        "    pass\n"
        "with attrib.host_span('batch_fill'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "assert attrib.host_snapshot()['parse'] > 0\n"
        "print('ok')\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == "ok"


def test_host_stage_counts_on_error_and_reraises():
    before = attrib.host_snapshot().get("tile", 0.0)
    with pytest.raises(ValueError):
        with attrib.host_stage("tile"):
            raise ValueError("boom")
    assert attrib.host_snapshot()["tile"] > before


def test_host_summary_leaves_the_fetch_wait_out_of_host_work(monkeypatch):
    monkeypatch.setattr(attrib, "host_snapshot", lambda: {
        "pack": 1.0, "fetch": 3.0, "associate": 1.0})
    out = attrib.host_summary()
    assert out["stages_s"]["fetch"] == 3.0
    assert out["total_s"] == 2.0
    assert out["split"] == {"pack": 0.5, "associate": 0.5}
