#!/usr/bin/env python3
"""Serve the metro matcher on the chip once, through the normal entry point.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the dp2 x gp2 mesh path on four chips

Phases, in order; any failure exits non-zero and prints no result:

  1. device   a short child process asks JAX for the visible devices; a
              platform other than ``tpu`` (or fewer chips than asked for)
              stops here.  This process pins itself to ``JAX_PLATFORMS=cpu``
              and never touches the chip: the server is the only process
              that does.
  2. data     the metro scenario of bench.py, from seeds inside the
              checkout: ``realistic_city_network(120, 120, spacing_m=150,
              seed=3)`` written as a "file" network config (the server
              builds its UBODT at delta 3000 m from it, ~10.7 M rows), and
              street-following traces from ``TraceSynthesizer(seed=7)``.
  3. server   ``python -m reporter_tpu.serve --warmup <cfg> 127.0.0.1:<port>``
              with ``"backend": "jax"`` and ``"cpu_fallback": false``.
  4. requests /report: 64-pt and 256-pt traces, 1024-pt traces (past the
              largest length bucket: the long-trace path), some on the
              binary columnar wire, and streaming session points (the
              arena step).  Sent one at a time, so every device batch is a
              shape the warm pass compiled.
  5. answers  every response 200, documented schema, no ``degraded``; the
              matched segments compared with the native CPU oracle
              (``backend="cpu"``) run here on the same traces: at least 95 %
              identical records.
  6. engine   /statusz and /metrics: platform, device_kind, device count,
              mesh shape and where the device-resident state lives; zero
              watchdog trips, zero degraded requests, warm-up seconds, and
              zero XLA compiles while the requests were answered.
  7. shutdown SIGTERM, and the server must exit 0.

The last line of stdout is the one JSON result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

# the metro scenario (bench.py build_scenario)
GRID = 120
SPACING_M = 150.0
CITY_SEED = 3
TRACE_SEED = 7
# (cohort, points per trace, traces): 1024 > the largest length bucket
COHORTS = (("short", 64, 24), ("med", 256, 8), ("long", 1024, 4))
N_BINARY = 4          # short-cohort traces sent on the binary columnar wire
SESSION_VEHICLES = 4  # streaming vehicles x steps x points per step
SESSION_STEPS = 6
STEP_POINTS = 4
MIN_IDENTICAL = 0.95
HEALTH_TIMEOUT_S = 900.0
REQUEST_TIMEOUT_S = 300.0
# the four-chip path: dp2 x gp2 with the UBODT sharded over gp, and a
# tiered table (hot arena per chip, host cold pages) so tier placement is
# exercised across the mesh too
MESH_MATCHER = {"devices": 4, "graph_devices": 2,
                "ubodt_hot_bytes": 128 << 20}
MATCH_OPTIONS = {"mode": "auto", "report_levels": [0, 1],
                 "transition_levels": [0, 1]}
# the documented /report schema (docs/http-api.md); shape_used appears
# only when truthy (reference parity)
REPORT_KEYS = ("datastore", "segment_matcher", "stats")


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


# -- phase 1: the device ----------------------------------------------------

_PROBE = ("import json, jax; d = jax.devices(); print(json.dumps("
          "{'platform': d[0].platform, 'kind': d[0].device_kind, "
          "'count': len(d)}))")


def probe_device(env: dict, timeout: float = 300.0) -> dict:
    """The visible devices as JAX reports them, asked in a child that
    exits before the server starts (a chip belongs to one process)."""
    try:
        out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                             capture_output=True, timeout=timeout,
                             text=True)
    except subprocess.TimeoutExpired:
        raise SmokeFailure("device probe timed out after %.0fs" % timeout)
    if out.returncode != 0:
        raise SmokeFailure("device probe failed (rc %d): %s"
                           % (out.returncode, out.stderr.strip()[-2000:]))
    return json.loads(out.stdout.strip().splitlines()[-1])


# -- phase 2: the data ------------------------------------------------------

def write_network(workdir: str, grid: int = GRID) -> str:
    """The metro city as a "file" network config; returns its path."""
    from reporter_tpu.synth.osm_city import realistic_city_network

    city = realistic_city_network(grid, grid, spacing_m=SPACING_M,
                                  seed=CITY_SEED)
    path = os.path.join(workdir, "network.json")
    with open(path, "w") as f:
        json.dump(city.to_dict(), f)
    return path


def server_config(network_path: str, matcher: dict = None) -> dict:
    return {
        "network": {"type": "file", "path": network_path},
        "matcher": dict({"cpu_fallback": False}, **(matcher or {})),
        "backend": "jax",
        "batch": {"max_batch": 64, "max_wait_ms": 5},
    }


def build_oracle(conf: dict):
    """The native CPU oracle over the same network file and matcher
    config the server builds from."""
    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.tiles.network import RoadNetwork

    with open(conf["network"]["path"]) as f:
        net = RoadNetwork.from_dict(json.load(f))
    return SegmentMatcher(network=net, backend="cpu",
                          config=MatcherConfig.from_dict(conf["matcher"]))


def make_traces(arrays, cohorts=COHORTS, vehicles=SESSION_VEHICLES,
                steps=SESSION_STEPS, step_points=STEP_POINTS):
    """(cohort traces, session vehicles), street-following, from the seed."""
    from reporter_tpu.synth import TraceSynthesizer

    synth = TraceSynthesizer(arrays, seed=TRACE_SEED)
    traces = []
    for name, T, n in cohorts:
        for i, s in enumerate(synth.batch(n, T, dt=5.0, sigma=5.0,
                                          max_tries=400)):
            traces.append(dict(s.trace, uuid="%s-%d" % (name, i),
                               match_options=MATCH_OPTIONS))
    sessions = [dict(s.trace, uuid="vehicle-%d" % i)
                for i, s in enumerate(synth.batch(
                    vehicles, steps * step_points, dt=5.0, sigma=5.0))]
    return traces, sessions


# -- phase 3: the server ----------------------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def start_server(cfg_path: str, port: int, env: dict, log_path: str):
    log = open(log_path, "w")
    try:
        return subprocess.Popen(
            [sys.executable, "-m", "reporter_tpu.serve", "--warmup",
             cfg_path, "127.0.0.1:%d" % port],
            cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT)
    finally:
        log.close()


def get_json(url: str, timeout: float = 30.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode("utf-8"))


def wait_healthy(base: str, proc, timeout: float = HEALTH_TIMEOUT_S) -> float:
    """Seconds until /health says the warmed engine is attached."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if proc.poll() is not None:
            raise SmokeFailure("server exited (rc %s) before it was healthy"
                               % proc.returncode)
        try:
            code, body = get_json(base + "/health", timeout=5.0)
            if code == 200 and body.get("status") == "ok" \
                    and not body.get("warming"):
                return time.monotonic() - t0
        except (OSError, ValueError):
            pass
        time.sleep(1.0)
    raise SmokeFailure("server not healthy after %.0fs" % timeout)


def stop_server(proc, timeout: float = 120.0) -> int:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise SmokeFailure("server ignored SIGTERM for %.0fs" % timeout)
    return proc.returncode


# -- phase 4: requests ------------------------------------------------------

def post_report(base: str, body: dict, binary: bool = False):
    from reporter_tpu.serve import wire

    if binary:
        data = wire.encode_request(body)
        headers = {"Content-Type": wire.CONTENT_TYPE,
                   "Accept": wire.CONTENT_TYPE}
    else:
        data = json.dumps(body).encode("utf-8")
        headers = {"Content-Type": "application/json"}
    req = urllib.request.Request(base + "/report", data=data,
                                 headers=headers, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as r:
            raw, ctype = r.read(), r.headers.get("Content-Type", "")
            code = r.status
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode("utf-8", "replace")}
    if wire.is_wire(ctype):
        return code, wire.decode_response(raw)
    if binary:
        raise SmokeFailure("a binary-wire request was answered as %r" % ctype)
    return code, json.loads(raw.decode("utf-8"))


def send_requests(base: str, traces, sessions, n_binary: int = N_BINARY,
                  step_points: int = STEP_POINTS):
    """Every trace once (the first ``n_binary`` on the binary wire), then
    the session vehicles point-batch by point-batch.  Returns
    ([(trace, code, body)], [(code, body)])."""
    answered = []
    for i, tr in enumerate(traces):
        code, body = post_report(base, tr, binary=i < n_binary)
        answered.append((tr, code, body))
    streamed = []
    steps = max(len(v["trace"]) for v in sessions) // step_points
    for j in range(steps):
        for v in sessions:
            pts = v["trace"][j * step_points:(j + 1) * step_points]
            if pts:
                streamed.append(post_report(base, {
                    "uuid": v["uuid"], "stream": True, "trace": pts,
                    "match_options": MATCH_OPTIONS}))
    return answered, streamed


# -- phase 5: the answers ---------------------------------------------------

def _check_report(code: int, body: dict, what: str) -> None:
    if code != 200:
        raise SmokeFailure("%s answered %d: %s" % (what, code, body))
    missing = [k for k in REPORT_KEYS if k not in body]
    if missing:
        raise SmokeFailure("%s lacks %s" % (what, missing))
    if body.get("degraded"):
        raise SmokeFailure("%s was answered degraded" % what)


def check_answers(answered, streamed, oracle) -> dict:
    """Schema + oracle comparison; raises SmokeFailure below the bar."""
    for tr, code, body in answered:
        _check_report(code, body, "trace %s" % tr["uuid"])
    for i, (code, body) in enumerate(streamed):
        _check_report(code, body, "session step %d" % i)
        if not isinstance(body.get("session"), dict):
            raise SmokeFailure("session step %d has no session block" % i)
    expect = oracle.match_many([tr for tr, _c, _b in answered])
    ids = lambda segs: [s.get("segment_id") for s in segs]
    served = [b["segment_matcher"]["segments"] for _t, _c, b in answered]
    identical = sum(s == e["segments"] for s, e in zip(served, expect))
    same_ids = sum(ids(s) == ids(e["segments"])
                   for s, e in zip(served, expect))
    n = len(answered)
    out = {"traces": n, "identical_records": identical,
           "identical_segment_ids": same_ids,
           "session_steps": len(streamed)}
    if identical < MIN_IDENTICAL * n:
        raise SmokeFailure("only %d/%d records identical to the CPU oracle "
                           "(need %.0f%%)" % (identical, n,
                                              100 * MIN_IDENTICAL))
    return out


# -- phase 6: the engine ----------------------------------------------------

def metric_total(snapshot: dict, name: str) -> float:
    fam = snapshot.get(name) or {}
    return float(sum(v for _lv, v in fam.get("samples", [])
                     if isinstance(v, (int, float))))


def check_engine(statusz: dict, platform: str, chips: int) -> dict:
    """The served engine's device and health, from /statusz."""
    dev = statusz.get("device") or {}
    if dev.get("platform") != platform:
        raise SmokeFailure("engine runs on %r, not %r"
                           % (dev.get("platform"), platform))
    if dev.get("count") != chips:
        raise SmokeFailure("engine spans %s device(s), not %d"
                           % (dev.get("count"), chips))
    for what, ids in (dev.get("placement") or {}).items():
        if len(set(ids)) != chips:
            raise SmokeFailure("%s lives on devices %s, not on %d"
                               % (what, ids, chips))
    snap = statusz.get("metrics") or {}
    trips = metric_total(snap, "reporter_watchdog_trips_total")
    degraded = metric_total(snap, "reporter_degraded_requests_total")
    if trips or degraded or statusz.get("degraded"):
        raise SmokeFailure("%d watchdog trip(s), %d degraded request(s)"
                           % (trips, degraded))
    out = {"platform": dev["platform"], "device_kind": dev["device_kind"],
           "count": dev["count"], "mesh": dev.get("mesh"),
           "placement": dev.get("placement"),
           "watchdog_trips": int(trips), "degraded_requests": int(degraded),
           "warmup_seconds": round(metric_total(
               snap, "reporter_warmup_seconds_total"), 3)}
    tier = statusz.get("ubodt_tier")
    if tier:  # the tiered table: residency and how often the cold path ran
        out["ubodt_tier"] = {
            "residency_frac": tier.get("residency_frac"),
            "cold_memory_kind": tier.get("cold_memory_kind"),
            "hits": int(metric_total(snap, "reporter_ubodt_tier_hits_total")),
            "misses": int(metric_total(
                snap, "reporter_ubodt_tier_misses_total"))}
    return out


# -- the run ----------------------------------------------------------------

def run(workdir: str, conf: dict, server_env: dict, platform: str,
        chips: int, prepare) -> dict:
    """Phases 3-7 against a config; ``prepare()`` -> (traces, sessions,
    oracle) runs here while the server builds and warms.  Returns the
    summary."""
    cfg_path = os.path.join(workdir, "service.json")
    with open(cfg_path, "w") as f:
        json.dump(conf, f)
    port = free_port()
    base = "http://127.0.0.1:%d" % port
    log_path = os.path.join(workdir, "server.log")
    t0 = time.monotonic()
    proc = start_server(cfg_path, port, server_env, log_path)
    try:
        traces, sessions, oracle = prepare()
        ready_s = wait_healthy(base, proc)
        say("server healthy after %.1fs (build + --warmup)" % ready_s)
        _c, before = get_json(base + "/statusz")
        answered, streamed = send_requests(base, traces, sessions)
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            if "reporter_xla_compiles_total" not in r.read().decode():
                raise SmokeFailure("/metrics lacks reporter_xla_compiles_total")
        _c, after = get_json(base + "/statusz")
        answers = check_answers(answered, streamed, oracle)
        say("answers: %s" % json.dumps(answers))
        engine = check_engine(after, platform, chips)
        compiles = (metric_total(after["metrics"], "reporter_xla_compiles_total")
                    - metric_total(before["metrics"],
                                   "reporter_xla_compiles_total"))
        engine["request_path_compiles"] = int(compiles)
        engine["ready_seconds"] = round(ready_s, 1)
        say("engine: %s" % json.dumps(engine))
        if compiles:
            raise SmokeFailure("%d XLA compile(s) on the request path after "
                               "--warmup" % compiles)
        rc = stop_server(proc)
        say("server exit code %s after SIGTERM (%.1fs total)"
            % (rc, time.monotonic() - t0))
        if rc != 0:
            raise SmokeFailure("server exited %s on SIGTERM" % rc)
        return {"answers": answers, "engine": engine}
    except BaseException:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        with open(log_path) as f:
            sys.stderr.write("--- server log tail ---\n%s\n"
                             % f.read()[-6000:])
        raise


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = serve on a dp2 x gp2 mesh over four chips")
    args = ap.parse_args(argv)
    # the server gets the environment as it came; this process stays off
    # the chip for good before anything below can load libtpu
    server_env = dict(os.environ)
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, REPO)
    try:
        import reporter_tpu  # noqa: F401
    except ImportError as e:
        sys.stderr.write("chip_smoke: not inside a checkout: %s\n" % (e,))
        return 2
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        dev = probe_device(server_env)
        say("device: %s" % json.dumps(dev))
        if dev["platform"] != "tpu":
            raise SmokeFailure("JAX finds no TPU (platform %r)"
                               % dev["platform"])
        if dev["count"] < args.chips:
            raise SmokeFailure("%d chip(s) visible, %d asked for"
                               % (dev["count"], args.chips))
        conf = server_config(write_network(workdir),
                             MESH_MATCHER if args.chips == 4 else None)

        def prepare():
            t0 = time.monotonic()
            oracle = build_oracle(conf)
            traces, sessions = make_traces(oracle.arrays)
            say("data: %d nodes / %d edges, UBODT %d rows, %d traces + %d "
                "session vehicles (%.1fs)"
                % (oracle.arrays.num_nodes, oracle.arrays.num_edges,
                   oracle.ubodt.num_rows, len(traces), len(sessions),
                   time.monotonic() - t0))
            return traces, sessions, oracle

        out = run(workdir, conf, server_env, "tpu", args.chips, prepare)
        eng = out["engine"]
        if eng["device_kind"] != dev["kind"]:
            raise SmokeFailure("server sees %r, probe saw %r"
                               % (eng["device_kind"], dev["kind"]))
    except SmokeFailure as e:
        sys.stderr.write("chip_smoke: FAILED: %s\n" % (e,))
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": eng["platform"], "kind": eng["device_kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
