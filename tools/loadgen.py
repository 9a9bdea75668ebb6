#!/usr/bin/env python3
"""Coordinated-omission-free open-loop traffic-replay load generator.

Drives the real HTTP matching service the way a probe firehose does —
requests arrive on a SCHEDULE (Poisson by default), not when the previous
response happens to return — and measures every latency against the
*scheduled* send time.  That is the whole point: a closed-loop client
that waits for responses before sending again silently stops load exactly
when the server stalls, so a wedged device step (``faults.py``
device_hang) barely moves its "p99".  Here a stall backs the schedule up,
and every delayed request records the backlog it actually suffered
(tests/test_loadgen.py pins this against an injected hang).

Traffic is per-vehicle sessions with uuid affinity: a synthesized fleet
(``--vehicles/--points/--window``, no accelerator needed) or a
``make_requests.py``-style probe archive (``--archive``, same column
flags) grouped by uuid, windowed in timestamp order, optionally
replayed on its own recorded timeline compressed ``--time-warp``-fold.

Verdicts come from the SAME implementation the server uses: the
client-side samples feed a ``reporter_tpu.obs.slo.SLOEngine`` (shared
classification policy, shared log-bucket quantile math), and with
``--server-slo`` the server's ``GET /debug/slo`` verdict is fetched and
must AGREE with the client's — exiting nonzero on violation or
disagreement, which is what makes the CI ``slo-rehearsal`` leg gating.

One JSON artifact (stdout or ``--out``): schedule + achieved rate,
status breakdown, p50/p95/p99/p99.9, per-step ramp table and knee,
client + server SLO verdicts.  Schema-complete for tools/perf_gate.py
(metric/value/unit/platform + attrib keys).

Usage (synth fleet, 30 req/s for 10 s):
    python tools/loadgen.py --url http://localhost:8002 \
        --rate 30 --duration 10 --vehicles 16 --points 24 --window 8 \
        --slo-availability 0.99 --slo-p99-ms 2500 --server-slo

Ramp to find the knee (5 steps, 10 -> 200 req/s):
    python tools/loadgen.py --url ... --ramp 10:200:5 --duration 5

Exit codes: 0 = objectives met (and server agrees, with --server-slo),
1 = SLO violated or verdicts disagree, 2 = setup/infra error.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import random
import sys
import threading
import time
import urllib.request
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from reporter_tpu.obs.quantile import SLO_BUCKETS_S, bucket_index, cumulate, hist_quantile  # noqa: E402
from reporter_tpu.obs.slo import Objective, SLOEngine  # noqa: E402
from reporter_tpu.utils.httppool import HttpPool  # noqa: E402

# keep-alive pool shared by every worker thread: an open-loop generator
# that reconnects per request measures TCP handshakes, not the service
_POOL = HttpPool(max_idle_per_host=64)

MATCH_OPTIONS = {"mode": "auto", "report_levels": [0, 1],
                 "transition_levels": [0, 1]}


# -- request corpus ---------------------------------------------------------

def synth_sessions(vehicles: int, points: int, window: int, grid: int,
                   seed: int,
                   gaps: Optional[List[float]] = None,
                   gap_jitter: float = 0.0) -> List[Tuple[str, List[dict]]]:
    """Per-vehicle sessions from the in-repo synthesizer (numpy only — no
    accelerator): each vehicle is one route walk, windowed into
    ``window``-point /report bodies in drive order.  ``gaps`` (seconds)
    cycles per vehicle over the listed inter-point sampling gaps —
    ``--gap-s 45,60`` synthesizes a fleet at the reference
    BatchingProcessor's sparse operating point, the cohort whose
    agreement cliff ROADMAP open item 4 chases (the quality plane labels
    its shadow samples by exactly these gap buckets).  ``gap_jitter``
    (fraction of the gap, --gap-jitter) draws each inter-point gap from
    [dt*(1-j), dt*(1+j)] so sparse corpora stop being suspiciously
    metronomic; 0 keeps the seeded corpus bit-identical to before."""
    from reporter_tpu.synth import TraceSynthesizer
    from reporter_tpu.tiles.arrays import build_graph_arrays
    from reporter_tpu.tiles.network import grid_city

    city = grid_city(rows=grid, cols=grid, spacing_m=200.0)
    arrays = build_graph_arrays(city, cell_size=100.0)
    synth = TraceSynthesizer(arrays, seed=seed)
    gaps = [g for g in (gaps or []) if g > 0]
    sessions = []
    for i in range(vehicles):
        dt = gaps[i % len(gaps)] if gaps else 5.0
        # sparse gaps need long drives: scale the synthesizer's
        # route-chaining budget with the drive time so a 45-60 s fleet on
        # a small grid can still stitch enough legs together
        s = synth.synthesize(points, dt=dt, sigma=5.0,
                             uuid="loadgen-veh-%04d" % i,
                             max_tries=max(20, int(points * dt / 10.0)),
                             dt_jitter=gap_jitter)
        uuid = "loadgen-veh-%04d" % i
        pts = s.trace["trace"]
        reqs = []
        for j in range(0, len(pts), window):
            chunk = pts[j:j + window]
            if len(chunk) < 2:
                break
            reqs.append({"uuid": uuid, "trace": chunk,
                         "match_options": dict(MATCH_OPTIONS)})
        if reqs:
            sessions.append((uuid, reqs))
    return sessions


def realized_gaps(sessions) -> Optional[dict]:
    """The corpus's ACTUAL inter-point gap distribution — bucketed on the
    quality plane's gap-cohort boundaries plus min/median/max — recorded
    in the artifact so a \"sparse\" run proves its sparseness (and a
    --gap-jitter run its spread) instead of asserting it."""
    gaps: List[float] = []
    for _uuid, reqs in sessions:
        times: List[float] = []
        for r in reqs:
            times.extend(float(p["time"]) for p in r.get("trace", ()))
        gaps.extend(b - a for a, b in zip(times, times[1:]) if b > a)
    if not gaps:
        return None
    arr = sorted(gaps)
    buckets = {"lt15": 0, "15-30": 0, "30-45": 0, "45-60": 0, "ge60": 0}
    for g in arr:
        if g < 15:
            buckets["lt15"] += 1
        elif g < 30:
            buckets["15-30"] += 1
        elif g < 45:
            buckets["30-45"] += 1
        elif g < 60:
            buckets["45-60"] += 1
        else:
            buckets["ge60"] += 1
    return {
        "count": len(arr),
        "min_s": round(arr[0], 2),
        "median_s": round(arr[len(arr) // 2], 2),
        "max_s": round(arr[-1], 2),
        "buckets": buckets,
    }


def archive_sessions(src: str, sep: str, uuid_col: int, time_col: int,
                     lat_col: int, lon_col: int, window: int,
                     limit: int = 0) -> List[Tuple[str, List[dict]]]:
    """make_requests.py-style probe rows -> per-uuid sessions in timestamp
    order, windowed into /report bodies.  Each request carries ``_t0``:
    the window's first original epoch, the replay-timeline anchor
    ``--time-warp`` scales."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "make_requests", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                      "make_requests.py"))
    mr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mr)

    by_uuid: Dict[str, List[Tuple[float, float, float]]] = {}
    n = 0
    for line in mr.iter_lines(src):
        cols = line.split(sep)
        try:
            t = float(cols[time_col])
            lat = float(cols[lat_col])
            lon = float(cols[lon_col])
            uuid = cols[uuid_col]
        except (IndexError, ValueError):
            continue
        by_uuid.setdefault(uuid, []).append((t, lat, lon))
        n += 1
        if limit and n >= limit:
            break
    sessions = []
    for uuid in sorted(by_uuid):
        rows = sorted(by_uuid[uuid])
        reqs = []
        for j in range(0, len(rows), window):
            chunk = rows[j:j + window]
            if len(chunk) < 2:
                break
            reqs.append({
                "uuid": uuid,
                "trace": [{"lat": la, "lon": lo, "time": int(t), "accuracy": 5}
                          for t, la, lo in chunk],
                "match_options": dict(MATCH_OPTIONS),
                "_t0": chunk[0][0],
            })
        if reqs:
            sessions.append((uuid, reqs))
    return sessions


def interleave(sessions: List[Tuple[str, List[dict]]]) -> List[dict]:
    """Round-robin across vehicles, preserving each vehicle's window
    order (uuid affinity: window k+1 never precedes window k)."""
    out = []
    k = 0
    while True:
        layer = [reqs[k] for _u, reqs in sessions if k < len(reqs)]
        if not layer:
            return out
        out.extend(layer)
        k += 1


def stream_sessions(sessions: List[Tuple[str, List[dict]]]) -> List[Tuple[str, List[dict]]]:
    """Per-vehicle single-point ``"stream": true`` request lists in
    point order (the per-uuid form both the round-robin interleave and
    the skewed sampler consume)."""
    per_uuid = []
    for uuid, reqs in sessions:
        flat = [p for r in reqs for p in r["trace"]]
        per_uuid.append((uuid, [
            {"uuid": uuid, "stream": True, "trace": [p],
             "match_options": dict(MATCH_OPTIONS)} for p in flat]))
    return per_uuid


def stream_points(sessions: List[Tuple[str, List[dict]]]) -> List[dict]:
    """The per-point streaming corpus (docs/performance.md "The session
    matcher"): every probe of every vehicle becomes ONE single-point
    ``"stream": true`` /report body, round-robin across vehicles with
    each vehicle's point order preserved — the open-loop firehose the
    session matcher answers at point latency."""
    return interleave(stream_sessions(sessions))


def skewed_requests(per_uuid: List[Tuple[str, List[dict]]], n: int,
                    share: float, hot_frac: float, rng: random.Random,
                    stream: bool) -> List[dict]:
    """Regional-skew corpus (the hot-city scenario, docs/serving-fleet.md
    "Self-driving fleet"): ``share`` of the offered traffic is drawn
    from the hottest ``hot_frac`` of vehicles, so a few uuids
    concentrate load on their rendezvous-affine replicas while the rest
    of the fleet idles — the affinity-stressing shape uniform replay
    never produces.  Each vehicle's own request order is preserved; an
    exhausted vehicle recycles (streams recycle as a fresh uuid so an
    open session's clock never rewinds)."""
    k = max(1, min(len(per_uuid) - 1, int(round(hot_frac * len(per_uuid))))) \
        if len(per_uuid) > 1 else 1
    hot, cold = per_uuid[:k], per_uuid[k:]
    state = {u: {"i": 0, "cyc": 0} for u, _reqs in per_uuid}
    out = []
    for _ in range(n):
        pool = hot if (not cold or rng.random() < share) else cold
        uuid, reqs = pool[rng.randrange(len(pool))]
        st = state[uuid]
        if st["i"] >= len(reqs):
            st["i"] = 0
            st["cyc"] += 1
        r = dict(reqs[st["i"]])
        st["i"] += 1
        if st["cyc"] and stream:
            r["uuid"] = "%s~c%d" % (r["uuid"], st["cyc"])
        out.append(r)
    return out


def fold_stream_windows(point_reqs: List[dict], schedule: List[float],
                        window: int):
    """The windowed-rebatch BASELINE at the same per-point offered rate:
    buffer each vehicle's points client-side the way the stream topology
    re-batches micro-traces, send a classic windowed /report when
    ``window`` points accumulate (at the LAST point's slot), and record
    every point's latency against ITS OWN arrival slot via ``_scheds`` —
    so the per-point p99 honestly includes the window-fill wait the
    session path eliminates.  Returns (requests, schedule, n_dropped):
    points stranded in a tail window of < 2 points cannot form a valid
    windowed request and are dropped (counted in the artifact)."""
    buf: Dict[str, dict] = {}
    out_reqs: List[dict] = []
    out_sched: List[float] = []

    def flush(uuid: str, b: dict) -> None:
        out_reqs.append({"uuid": uuid, "trace": b["pts"],
                         "match_options": dict(MATCH_OPTIONS),
                         "_scheds": b["scheds"]})
        out_sched.append(b["scheds"][-1])

    for r, off in zip(point_reqs, schedule):
        b = buf.setdefault(r["uuid"], {"pts": [], "scheds": []})
        b["pts"].extend(r["trace"])
        b["scheds"].append(off)
        if len(b["pts"]) >= window:
            flush(r["uuid"], b)
            buf[r["uuid"]] = {"pts": [], "scheds": []}
    dropped = 0
    for uuid, b in buf.items():
        if len(b["pts"]) >= 2:
            flush(uuid, b)
        else:
            dropped += len(b["pts"])
    order = sorted(range(len(out_reqs)), key=lambda i: out_sched[i])
    return ([out_reqs[i] for i in order],
            [out_sched[i] for i in order], dropped)


# -- schedule ---------------------------------------------------------------

def build_schedule(n: int, rate: float, arrival: str,
                   rng: random.Random) -> List[float]:
    """Offsets (seconds from t0) for ``n`` arrivals at ``rate``/s.
    "poisson" = exponential inter-arrivals (the open-loop firehose
    model); "uniform" = a metronome."""
    if rate <= 0:
        raise ValueError("rate must be > 0")
    out, t = [], 0.0
    for _ in range(n):
        t += rng.expovariate(rate) if arrival == "poisson" else 1.0 / rate
        out.append(t)
    return out


def profile_rate_fn(profile: str, base_rate: float, duration: float):
    """Time-varying offered-rate profiles (docs/serving-fleet.md
    "Self-driving fleet" — the shapes production actually sees):

      "diurnal"             one compressed day: a sinusoid from 0.25x
                            (night) through 1.75x (peak) of --rate,
                            starting at the trough
      "flash:<f0>:<f1>:<m>" a flash crowd: --rate baseline, multiplied
                            by <m> between fractions <f0> and <f1> of
                            the duration (e.g. flash:0.3:0.7:5)
      "schedule:<path>"     replay a RECORDED demand shape: a JSON file
                            ({"points": [[t, mult], ...]}, what
                            tools/demand_export.py writes from a
                            /debug/history window) — the recorded span
                            is stretched onto --duration and the
                            multiplier piecewise-linearly interpolated
                            against --rate
    """
    import math

    if profile == "diurnal":
        return lambda t: base_rate * (
            1.0 - 0.75 * math.cos(2.0 * math.pi * t / max(duration, 1e-9)))
    if profile.startswith("schedule:"):
        path = profile[len("schedule:"):]
        try:
            with open(path) as f:
                spec = json.load(f)
            pts = sorted((float(t), float(m)) for t, m in spec["points"])
            assert pts and all(m >= 0 for _, m in pts)
        except (OSError, ValueError, KeyError, TypeError, AssertionError):
            raise ValueError(
                "--profile schedule:<path> wants a JSON file with "
                '{"points": [[t, mult], ...]} '
                "(tools/demand_export.py writes one)") from None
        span = pts[-1][0] - pts[0][0]
        t_base = pts[0][0]
        xs = [t - t_base for t, _ in pts]
        ms = [m for _, m in pts]

        def fn(t):
            x = (t / max(duration, 1e-9)) * span if span > 0 else 0.0
            i = bisect.bisect_right(xs, x)
            if i <= 0:
                return base_rate * ms[0]
            if i >= len(xs):
                return base_rate * ms[-1]
            x0, x1 = xs[i - 1], xs[i]
            w = (x - x0) / (x1 - x0) if x1 > x0 else 0.0
            return base_rate * (ms[i - 1] + w * (ms[i] - ms[i - 1]))

        return fn
    if profile.startswith("flash:"):
        try:
            _, f0, f1, mult = profile.split(":")
            f0, f1, mult = float(f0), float(f1), float(mult)
            assert 0.0 <= f0 < f1 <= 1.0 and mult > 0
        except (ValueError, AssertionError):
            raise ValueError("--profile flash wants flash:<f0>:<f1>:<mult> "
                             "with 0 <= f0 < f1 <= 1") from None
        t0, t1 = f0 * duration, f1 * duration
        return lambda t: base_rate * (mult if t0 <= t < t1 else 1.0)
    raise ValueError("unknown --profile %r (diurnal | flash:f0:f1:mult | "
                     "schedule:path)" % profile)


def profile_schedule(rate: float, duration: float, profile: str,
                     arrival: str, rng: random.Random) -> List[float]:
    """Arrival offsets under a time-varying rate.  Poisson arrivals come
    from inhomogeneous thinning against the profile's peak rate (exact
    for piecewise shapes, unbiased for the sinusoid); uniform arrivals
    integrate the rate stepwise."""
    fn = profile_rate_fn(profile, rate, duration)
    peak = max(fn(duration * i / 1000.0) for i in range(1001))
    if peak <= 0:
        raise ValueError("profile rate must be > 0 somewhere")
    out: List[float] = []
    t = 0.0
    if arrival == "poisson":
        while True:
            t += rng.expovariate(peak)
            if t >= duration:
                return out
            if rng.random() < fn(t) / peak:
                out.append(t)
    while True:
        r = max(fn(t), 1e-9)
        t += 1.0 / r
        if t >= duration:
            return out
        out.append(t)


def timeline_schedule(requests: List[dict], warp: float) -> List[float]:
    """Replay the archive's own recorded timeline, compressed
    ``warp``-fold (the time-warp rate scaling path)."""
    t0s = [r.get("_t0") for r in requests]
    if any(t is None for t in t0s):
        raise ValueError("timeline replay needs archive requests (_t0)")
    base = min(t0s)
    sched = [(t - base) / max(warp, 1e-9) for t in t0s]
    order = sorted(range(len(requests)), key=lambda i: sched[i])
    requests[:] = [requests[i] for i in order]
    return sorted(sched)


# -- the open-loop run ------------------------------------------------------

class Sample:
    __slots__ = ("sched", "sent", "done", "code", "degraded",
                 "replica", "uuid")

    def __init__(self, sched, sent, done, code, degraded,
                 replica=None, uuid=None):
        self.sched = sched
        self.sent = sent
        self.done = done
        self.code = code
        self.degraded = degraded
        # the X-Reporter-Replica id the answering replica echoed: the
        # per-replica distribution and the fleet rehearsal's affinity
        # assertions (tests/fleet_rehearsal.sh) key on it
        self.replica = replica
        self.uuid = uuid

    @property
    def latency_s(self) -> float:
        """Against the SCHEDULED send time — the coordinated-omission-free
        number (a late send records the backlog it suffered)."""
        return self.done - self.sched

    @property
    def service_s(self) -> float:
        """Send-to-response only — the flattering number a closed-loop
        client would report; kept so the regression test can PROVE the
        two diverge under a stall."""
        return self.done - self.sent


def _post(url: str, body: bytes, timeout: float,
          headers: Optional[dict] = None) -> Tuple[int, bool, Optional[str]]:
    try:
        status, hdrs, data = _POOL.request(
            "POST", url, body=body,
            headers=headers or {"Content-Type": "application/json"},
            timeout=timeout, target="loadgen")
    except Exception:  # noqa: BLE001 - timeout/reset: code 0, still counted
        return 0, False, None
    replica = hdrs.get("X-Reporter-Replica")
    degraded = False
    if status == 200:
        if data[:4] == b"RPTC":  # binary columnar response frame
            from reporter_tpu.serve import wire as _wire
            degraded = _wire.response_degraded(data)
        else:
            try:
                degraded = bool(json.loads(data.decode()).get("degraded"))
            except (ValueError, UnicodeDecodeError):
                degraded = False
    return status, degraded, replica


def run_load(url: str, requests: List[dict], schedule: List[float],
             concurrency: int = 32, timeout_s: float = 10.0,
             wire_mode: str = "json",
             gzip_body: bool = False) -> Tuple[List[Sample], float]:
    """Send every request at its scheduled offset (or as soon after as a
    worker frees up — the backlog then SHOWS in the recorded latency).
    The whole schedule is always drained: a hung server cannot make the
    tail disappear by never being measured.  Returns the samples plus the
    wall-clock epoch of offset 0 (so a rehearsal script can correlate
    sample offsets with externally-timed kill/restart events).

    A request may carry ``"_scheds"``: a list of PER-POINT schedule
    offsets (the streaming scenario's windowed-rebatch baseline buffers
    points client-side the way the stream topology does, so each point's
    latency is measured against ITS OWN arrival slot, not the window
    flush).  Underscore keys never reach the wire.

    ``wire_mode="binary"`` encodes requests as columnar frames and
    negotiates binary responses (serve/wire.py — the docs/http-api.md
    "Wire formats" contract); ``gzip_body`` gzips whichever wire is in
    use (Content-Encoding: gzip)."""
    clean = [{k: v for k, v in r.items() if not str(k).startswith("_")}
             for r in requests]
    headers = {"Content-Type": "application/json"}
    if wire_mode == "binary":
        from reporter_tpu.serve import wire as _wire
        bodies = [_wire.encode_request(c) for c in clean]
        headers = {"Content-Type": _wire.CONTENT_TYPE,
                   "Accept": _wire.CONTENT_TYPE}
    else:
        bodies = [json.dumps(c, separators=(",", ":")).encode()
                  for c in clean]
    if gzip_body:
        import gzip as _gzip
        bodies = [_gzip.compress(b, compresslevel=1) for b in bodies]
        headers["Content-Encoding"] = "gzip"
    samples: List[Optional[List[Sample]]] = [None] * len(requests)
    it = {"i": 0}
    lock = threading.Lock()
    t0 = time.monotonic() + 0.05  # everyone references the same epoch
    t0_epoch = time.time() + (t0 - time.monotonic())

    def worker():
        while True:
            with lock:
                i = it["i"]
                if i >= len(bodies):
                    return
                it["i"] = i + 1
            sched = t0 + schedule[i]
            delay = sched - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            sent = time.monotonic()
            code, degraded, replica = _post(url, bodies[i], timeout_s,
                                            headers=headers)
            done = time.monotonic()
            scheds = requests[i].get("_scheds") or [schedule[i]]
            samples[i] = [
                Sample(off, sent - t0, done - t0, code, degraded,
                       replica=replica, uuid=requests[i].get("uuid"))
                for off in scheds]
    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(max(1, concurrency))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [s for group in samples if group is not None for s in group], \
        t0_epoch


# -- evaluation -------------------------------------------------------------

def quantiles_ms(lats: List[float]) -> Dict[str, Optional[float]]:
    """Quantiles via the SHARED log-bucket table + interpolation rule —
    the same arithmetic the server's /debug/slo runs, so the two sides
    can only disagree about traffic, never about math."""
    counts = [0] * (len(SLO_BUCKETS_S) + 1)
    for v in lats:
        counts[bucket_index(SLO_BUCKETS_S, v)] += 1
    cum = cumulate(SLO_BUCKETS_S, counts)
    out = {}
    for q, key in ((0.50, "p50_ms"), (0.95, "p95_ms"),
                   (0.99, "p99_ms"), (0.999, "p999_ms")):
        v = hist_quantile(cum, q)
        out[key] = round(v * 1000.0, 1) if v is not None else None
    return out


def objectives_from_args(args) -> List[Objective]:
    out = []
    if args.slo_availability > 0:
        out.append(Objective("availability", "availability",
                             args.slo_availability))
    if args.slo_p99_ms > 0:
        out.append(Objective("p99_latency", "latency",
                             args.slo_p99_ms / 1000.0, quantile=0.99))
    if args.slo_p999_ms > 0:
        out.append(Objective("p999_latency", "latency",
                             args.slo_p999_ms / 1000.0, quantile=0.999))
    if args.slo_degraded_frac > 0:
        out.append(Objective("degraded_fraction", "degraded_fraction",
                             args.slo_degraded_frac))
    return out


def evaluate(samples: List[Sample], objectives: List[Objective],
             window_s: float) -> dict:
    """Client-side verdict through the REAL SLOEngine (no re-implemented
    budget math): every sample is observed at its completion offset on
    an injected clock, then report() renders the same objective states
    the server would."""
    clock = {"t": 0.0}
    eng = SLOEngine(objectives, window_s=window_s, instrument=False,
                    clock=lambda: clock["t"])
    for s in sorted(samples, key=lambda x: x.done):
        clock["t"] = s.done
        eng.observe("report", s.code if s.code else 503, s.latency_s,
                    degraded=s.degraded)
    clock["t"] = max((s.done for s in samples), default=0.0)
    return eng.report()


def step_stats(samples: List[Sample], offered_rate: float) -> dict:
    lats = [s.latency_s for s in samples]
    span = (max(s.done for s in samples) - min(s.sched for s in samples)
            if samples else 0.0)
    codes: Dict[str, int] = {}
    replicas: Dict[str, int] = {}
    for s in samples:
        k = str(s.code) if s.code else "timeout"
        codes[k] = codes.get(k, 0) + 1
        if s.replica:
            replicas[s.replica] = replicas.get(s.replica, 0) + 1
    # the overload ledger: admitted traffic (200s) judged on its own —
    # "shed exactly down to capacity" means the admitted tail holds its
    # objective while shed_fraction tracks the excess offered load
    # (docs/serving-fleet.md "Self-driving fleet")
    admitted = [s for s in samples if s.code == 200]
    shed = sum(1 for s in samples if s.code in (429, 503))
    return {
        "n": len(samples),
        "offered_rps": round(offered_rate, 3),
        "achieved_rps": round(len(samples) / span, 3) if span > 0 else None,
        "admitted_rps": (round(len(admitted) / span, 3)
                         if span > 0 else None),
        "admitted_quantiles": quantiles_ms([s.latency_s for s in admitted]),
        "shed_fraction": (round(shed / len(samples), 4)
                          if samples else None),
        "status": dict(sorted(codes.items())),
        # per-replica request distribution (X-Reporter-Replica echoes):
        # the fleet rehearsal's affinity and failover assertions read this
        "replicas": dict(sorted(replicas.items())),
        "degraded": sum(1 for s in samples if s.degraded),
        "quantiles": quantiles_ms(lats),
        # the flattering closed-loop number, kept ONLY so coordinated
        # omission is falsifiable from the artifact itself
        "service_time_quantiles": quantiles_ms([s.service_s for s in samples]),
        "max_send_lag_s": round(max((s.sent - s.sched for s in samples),
                                    default=0.0), 3),
    }


def fetch_json(url: str, timeout: float = 10.0) -> Optional[dict]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return json.loads(r.read().decode())
    except Exception as e:  # noqa: BLE001 - surfaced in the artifact
        sys.stderr.write("loadgen: GET %s failed: %s\n" % (url, e))
        return None


def cost_block(base: str) -> dict:
    """The artifact's cost block (docs/economics.md): the server's own
    chip-second ledger via GET /debug/cost, normalized to one header
    whether the target is a single replica or the fleet router."""
    rep = fetch_json(base + "/debug/cost")
    if rep is None:
        return {"source": "unavailable"}
    if rep.get("scope") == "fleet":
        f = rep.get("fleet") or {}
        return {
            "source": "server", "scope": "fleet",
            "chips": f.get("chips"),
            "chip_seconds": f.get("chip_seconds_total"),
            "usd": f.get("usd"),
            "points_total": f.get("points_total"),
            "usd_per_million_points": f.get("usd_per_million_points"),
            "headroom_traces_per_sec": f.get("headroom_traces_per_sec"),
            "per_replica": rep.get("replicas"),
        }
    return {
        "source": "server", "scope": "replica",
        "chips": rep.get("chips"),
        "price_per_chip_hour": rep.get("price_per_chip_hour"),
        "chip_seconds": (rep.get("chip_seconds") or {}).get("total"),
        "usd": rep.get("usd"),
        "points_total": rep.get("points_total"),
        "usd_per_million_points": rep.get("usd_per_million_points"),
        "headroom_traces_per_sec": (rep.get("capacity") or {})
        .get("headroom_traces_per_sec"),
    }


def session_arena_block(base: str) -> "Optional[dict]":
    """The artifact's device-resident session-arena evidence
    (docs/performance.md "Device-resident session arenas"): occupancy +
    the promotion/eviction/readback counters, scraped from the target's
    metrics AFTER the run so a streaming artifact carries its own
    zero-per-step-readback proof.  Works against a single replica
    (/metrics) and the fleet router (/metrics?pull=1 federates every
    replica's families); None when the target serves no arena."""
    from reporter_tpu.obs.quantile import parse_metrics

    out = None
    for q in ("/metrics?pull=1", "/metrics"):
        try:
            with urllib.request.urlopen(base + q, timeout=15) as r:
                fams = parse_metrics(r.read().decode())
        except Exception:  # noqa: BLE001 - surfaced as None in the artifact
            continue
        if "reporter_session_arena_readbacks_total" not in fams:
            continue
        def _tot(name):
            return int(sum(fams.get(name, {}).values()))
        # summed across replicas on a federated scrape (each row carries
        # a prepended replica label); a single replica's scrape has one
        # row per tier already
        resident: dict = {}
        for lv, v in fams.get(
                "reporter_sessions_resident_per_chip", {}).items():
            tier = dict(lv).get("tier", "?")
            resident[tier] = round(resident.get(tier, 0.0) + float(v), 2)
        out = {
            "sessions_resident_per_chip": resident or None,
            "promotions": _tot("reporter_session_arena_promotions_total"),
            "evictions": _tot("reporter_session_arena_evictions_total"),
            "readbacks": _tot("reporter_session_arena_readbacks_total"),
        }
        break
    return out


# -- main -------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--url", required=True, help="service base url")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="offered request rate /s (ignored with --ramp or "
                         "--time-warp timeline replay)")
    ap.add_argument("--duration", type=float, default=10.0,
                    help="seconds per step (schedule length = rate*duration)")
    ap.add_argument("--ramp", default=None,
                    help="r0:r1:steps — ramp the offered rate to find the "
                         "knee (achieved/offered and SLO per step)")
    ap.add_argument("--arrival", choices=("poisson", "uniform"),
                    default="poisson")
    ap.add_argument("--profile", default=None,
                    help="time-varying offered rate over --duration: "
                         "diurnal (compressed day, 0.25x..1.75x of "
                         "--rate) or flash:<f0>:<f1>:<mult> (flash "
                         "crowd between fractions f0..f1 of the run); "
                         "ignored with --ramp / --time-warp")
    ap.add_argument("--skew", default=None,
                    help="regional skew <share>:<hot_frac> — <share> of "
                         "requests drawn from the hottest <hot_frac> of "
                         "vehicles (e.g. 0.8:0.1: 80%% of traffic on "
                         "10%% of uuids, the hot-city affinity stress)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--concurrency", type=int, default=32)
    ap.add_argument("--timeout-s", type=float, default=10.0)
    # synth fleet
    ap.add_argument("--vehicles", type=int, default=16)
    ap.add_argument("--points", type=int, default=24,
                    help="points per synth vehicle")
    ap.add_argument("--window", type=int, default=8,
                    help="points per request window")
    ap.add_argument("--grid", type=int, default=8,
                    help="synth grid size (must match the served network "
                         "for sensible matches)")
    ap.add_argument("--gap-s", default=None,
                    help="comma list of inter-point sampling gaps in "
                         "seconds, cycled per vehicle (e.g. 45,60 — the "
                         "reference BatchingProcessor operating point; "
                         "default: dense 5 s sampling)")
    ap.add_argument("--gap-jitter", type=float, default=0.0,
                    help="per-point gap noise as a fraction of --gap-s: "
                         "each gap draws uniform from [g*(1-j), g*(1+j)] "
                         "so sparse corpora stop being suspiciously "
                         "uniform; the artifact records the realized gap "
                         "histogram either way (0 = off, bit-identical "
                         "seeded corpus)")
    # streaming session scenario (docs/performance.md "The session
    # matcher"): open-loop per-POINT sends on uuid-affine sessions, each
    # point's latency against its own scheduled arrival
    ap.add_argument("--stream", action="store_true",
                    help="per-point streaming scenario: every probe is "
                         "one single-point \"stream\": true /report on "
                         "its vehicle's open session; --rate is the "
                         "fleet-wide POINT rate and every quantile below "
                         "is per-point")
    ap.add_argument("--stream-window", type=int, default=1,
                    help="with --stream: client-side points buffered per "
                         "send.  1 (default) = the pure session path; "
                         "N>=2 = the windowed-REBATCH baseline at the "
                         "same per-point offered rate (classic windowed "
                         "/report sent when N points accumulate, each "
                         "point still measured against its own arrival "
                         "slot) — the comparison that shows the window-"
                         "fill wait the session matcher eliminates")
    # archive replay (make_requests.py-style rows)
    ap.add_argument("--archive", default=None, help="probe dir or glob")
    ap.add_argument("--sep", default="|")
    ap.add_argument("--uuid-col", type=int, default=0)
    ap.add_argument("--time-col", type=int, default=1)
    ap.add_argument("--lat-col", type=int, default=2)
    ap.add_argument("--lon-col", type=int, default=3)
    ap.add_argument("--limit", type=int, default=0,
                    help="max archive rows to load (0 = all)")
    ap.add_argument("--time-warp", type=float, default=0.0,
                    help="replay the archive's own timeline compressed "
                         "N-fold instead of a fixed --rate")
    # objectives (<=0 drops one)
    ap.add_argument("--slo-availability", type=float, default=0.99)
    ap.add_argument("--slo-p99-ms", type=float, default=2500.0)
    ap.add_argument("--slo-p999-ms", type=float, default=0.0)
    ap.add_argument("--slo-degraded-frac", type=float, default=0.0)
    ap.add_argument("--server-slo", action="store_true",
                    help="fetch GET /debug/slo after the run and require "
                         "the server verdict to AGREE with the client's")
    ap.add_argument("--wire", choices=("json", "binary"), default="json",
                    help="request/response wire: json (default) or the "
                         "binary columnar frame (serve/wire.py; the "
                         "service must advertise wire-columnar)")
    ap.add_argument("--gzip", action="store_true",
                    help="gzip request bodies (Content-Encoding: gzip)")
    ap.add_argument("--platform", default="cpu",
                    help="artifact provenance tag (cpu|tpu)")
    ap.add_argument("--out", default=None, help="artifact path (default "
                    "stdout)")
    ap.add_argument("--dump-samples", default=None,
                    help="write one JSONL row per request (uuid, replica, "
                         "code, sched/done epoch) — the fleet rehearsal's "
                         "affinity/failover assertions consume it")
    args = ap.parse_args(argv)

    rng = random.Random(args.seed)
    base = args.url.rstrip("/")
    health = fetch_json(base + "/health") or {}

    # corpus
    gaps = None
    if args.gap_s:
        try:
            gaps = [float(g) for g in str(args.gap_s).split(",") if g.strip()]
            assert all(g > 0 for g in gaps) and gaps
        except (ValueError, AssertionError):
            ap.error("--gap-s wants a comma list of positive seconds")
    try:
        if args.archive:
            sessions = archive_sessions(
                args.archive, args.sep, args.uuid_col, args.time_col,
                args.lat_col, args.lon_col, args.window, args.limit)
        else:
            if not (0.0 <= args.gap_jitter < 1.0):
                ap.error("--gap-jitter wants a fraction in [0, 1)")
            sessions = synth_sessions(args.vehicles, args.points,
                                      args.window, args.grid, args.seed,
                                      gaps=gaps, gap_jitter=args.gap_jitter)
    except Exception as e:  # noqa: BLE001 - setup failure is rc 2
        sys.stderr.write("loadgen: corpus build failed: %s\n" % (e,))
        return 2
    if not sessions:
        sys.stderr.write("loadgen: empty request corpus\n")
        return 2
    if args.stream_window < 1:
        ap.error("--stream-window must be >= 1")
    per_uuid = stream_sessions(sessions) if args.stream else sessions
    corpus = interleave(per_uuid)
    skew = None
    if args.skew:
        try:
            share, hot_frac = (float(x) for x in args.skew.split(":"))
            assert 0.0 < share <= 1.0 and 0.0 < hot_frac <= 1.0
            skew = (share, hot_frac)
        except (ValueError, AssertionError):
            ap.error("--skew wants <share>:<hot_frac>, both in (0, 1]")

    # rate steps
    if args.ramp:
        try:
            r0, r1, steps = args.ramp.split(":")
            r0, r1, steps = float(r0), float(r1), int(steps)
            assert steps >= 1 and r0 > 0 and r1 >= r0
        except (ValueError, AssertionError):
            ap.error("--ramp wants r0:r1:steps")
        rates = [r0 + (r1 - r0) * i / max(1, steps - 1) for i in range(steps)]
    else:
        rates = [args.rate]

    objectives = objectives_from_args(args)
    steps_out = []
    all_samples: List[Sample] = []
    dump_rows: List[dict] = []
    stream_dropped = 0
    knee = None
    for rate in rates:
        if args.time_warp > 0 and not args.ramp:
            reqs = [dict(r) for r in corpus]
            schedule = timeline_schedule(reqs, args.time_warp)
            for r in reqs:
                r.pop("_t0", None)
            offered = (len(schedule) / schedule[-1]) if schedule and schedule[-1] > 0 else 0.0
        else:
            if args.profile and not args.ramp:
                try:
                    schedule = profile_schedule(rate, args.duration,
                                                args.profile,
                                                args.arrival, rng)
                except ValueError as e:
                    ap.error(str(e))
                if not schedule:
                    sys.stderr.write("loadgen: profile produced an empty "
                                     "schedule\n")
                    return 2
                n = len(schedule)
                offered = n / max(args.duration, 1e-9)
            else:
                n = max(1, int(rate * args.duration))
                schedule = build_schedule(n, rate, args.arrival, rng)
                offered = rate
            if skew is not None:
                reqs = skewed_requests(per_uuid, n, skew[0], skew[1],
                                       rng, args.stream)
            else:
                reqs = []
                for i in range(n):
                    r = dict(corpus[i % len(corpus)])
                    cyc = i // len(corpus)
                    if cyc and args.stream:
                        # a re-cycled stream point must not rewind an
                        # open session's clock: each pass over the
                        # corpus streams as a fresh fleet of vehicles
                        r["uuid"] = "%s~c%d" % (r["uuid"], cyc)
                    reqs.append(r)
            for r in reqs:
                r.pop("_t0", None)
        if args.stream and args.stream_window > 1:
            reqs, schedule, dropped = fold_stream_windows(
                reqs, schedule, args.stream_window)
            stream_dropped += dropped
        samples, t0_epoch = run_load(base + "/report", reqs, schedule,
                                     concurrency=args.concurrency,
                                     timeout_s=args.timeout_s,
                                     wire_mode=args.wire,
                                     gzip_body=args.gzip)
        if not samples:
            sys.stderr.write("loadgen: no samples recorded\n")
            return 2
        if args.dump_samples:
            dump_rows.extend(
                {"uuid": s.uuid, "replica": s.replica, "code": s.code,
                 "sched_epoch": round(t0_epoch + s.sched, 3),
                 "done_epoch": round(t0_epoch + s.done, 3),
                 "latency_s": round(s.latency_s, 4)}
                for s in sorted(samples, key=lambda x: x.sched))
        st = step_stats(samples, offered)
        verdict = evaluate(samples, objectives,
                           window_s=max(60.0, schedule[-1] + 60.0))
        st["slo_ok"] = verdict["ok"]
        ach = st["achieved_rps"] or 0.0
        if verdict["ok"] and ach >= 0.9 * offered:
            knee = offered
        steps_out.append(st)
        all_samples.extend(samples)

    # the headline evaluation covers the WHOLE run (every step's samples)
    client = evaluate(all_samples, objectives,
                      window_s=max(60.0, max(s.done for s in all_samples) + 60.0))
    head = step_stats(all_samples, rates[-1] if not args.ramp else 0.0)

    server_slo = None
    agree = None
    masking_debt = None
    server_quality = None
    if args.server_slo:
        span_s = max(60.0, max(s.done for s in all_samples) + 30.0)
        server_slo = fetch_json(base + "/debug/slo?window=%d" % int(span_s))
        if server_slo is not None:
            agree = bool(server_slo.get("ok")) == bool(client["ok"])
            # the quality objective rides the server verdict (the client
            # cannot measure agreement — only the shadow oracle can), so
            # its section is surfaced verbatim in the artifact and a
            # violating agreement objective fails the agreement check
            # above through server ok=false
            server_quality = server_slo.get("quality")
            agr_obj = next((o for o in server_slo.get("objectives", ())
                            if o.get("kind") == "agreement"), None)
            if agr_obj is not None and agr_obj.get("value") is not None:
                sys.stderr.write(
                    "loadgen: server agreement %.4f (target %.2f, %s)\n"
                    % (agr_obj["value"], agr_obj["target"],
                       "ok" if agr_obj["ok"] else "VIOLATING"))
            # a fleet router's verdict carries the masking-debt gauge
            # (obs/federation.py): replica budget failover hid from this
            # client.  Surfaced loudly — a PASSING run with a fat debt
            # means a replica is rotting behind successful failovers.
            masking_debt = server_slo.get("masking_debt")
            hot = {k: v for k, v in (masking_debt or {}).items() if v}
            if hot:
                sys.stderr.write(
                    "loadgen: fleet masking debt %s — replica-level burn "
                    "masked by failover (fleet verdict unaffected)\n"
                    % json.dumps(hot))

    artifact = {
        # perf_gate-consumable header (docs/bench-schema.md shape); the
        # stream scenarios carry their own metric names so like-provenance
        # regression judging never mixes per-point and per-request tails
        "metric": ("loadgen_p99_latency" if not args.stream else
                   "loadgen_stream_p99_latency" if args.stream_window <= 1
                   else "loadgen_stream_windowed_p99_latency"),
        "value": head["quantiles"]["p99_ms"],
        "unit": "ms",
        "platform": args.platform,
        "edges": health.get("edges"),
        "attrib": None,
        "attrib_reason": "loadgen artifact (no profiler capture)",
        # the run itself
        "url": base,
        "arrival": args.arrival,
        "wire": args.wire,
        "gzip": bool(args.gzip),
        "seed": args.seed,
        "mode": (("stream" if args.stream_window <= 1 else "stream-windowed")
                 if args.stream else
                 ("archive" if args.archive else "synth")),
        # per-point streaming scenario provenance: quantiles above are
        # PER-POINT against each point's own scheduled arrival; window>1
        # is the windowed-rebatch baseline at the same point rate
        "stream": ({"window": args.stream_window,
                    "points": len(all_samples),
                    "points_dropped_tail": stream_dropped}
                   if args.stream else None),
        "gap_s": gaps,
        "gap_jitter": args.gap_jitter or None,
        "gap_histogram": realized_gaps(sessions),
        "time_warp": args.time_warp or None,
        "profile": args.profile,
        "skew": args.skew,
        "sessions": len(sessions),
        "requests": len(all_samples),
        "offered_rps": steps_out[-1]["offered_rps"],
        "achieved_rps": head["achieved_rps"],
        "admitted_rps": head["admitted_rps"],
        "admitted_quantiles": head["admitted_quantiles"],
        "shed_fraction": head["shed_fraction"],
        "status": head["status"],
        "replica_distribution": head["replicas"],
        "degraded": head["degraded"],
        "quantiles": head["quantiles"],
        "service_time_quantiles": head["service_time_quantiles"],
        "max_send_lag_s": head["max_send_lag_s"],
        "slo": {
            "objectives": [
                {"name": o.name, "kind": o.kind, "target": o.target,
                 "quantile": o.quantile if o.kind == "latency" else None}
                for o in objectives],
            "client": {"ok": client["ok"], "verdict": client["verdict"],
                       "objectives": client["objectives"]},
            "server": server_slo,
            "server_quality": server_quality,
            "agree": agree,
            "masking_debt": masking_debt,
        },
        "ramp": steps_out if args.ramp else None,
        "knee_rps": knee if args.ramp else None,
        # what this load COST: the serving side's own chip-second ledger
        # (docs/economics.md) — every loadgen artifact carries it so a
        # perf number is never quoted without its price
        "cost": cost_block(base),
        # device-resident session arenas (docs/performance.md): occupancy
        # by tier + the transfer counters, so a streaming artifact proves
        # the zero-per-step-readback claim it rides on; None when the
        # target serves host-carried sessions
        "session_arena": session_arena_block(base),
    }
    if args.dump_samples:
        with open(args.dump_samples, "w") as f:
            for row in dump_rows:
                f.write(json.dumps(row, separators=(",", ":")) + "\n")
        sys.stderr.write("loadgen: %d sample rows -> %s\n"
                         % (len(dump_rows), args.dump_samples))
    blob = json.dumps(artifact, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(blob + "\n")
        sys.stderr.write("loadgen: artifact -> %s\n" % args.out)
    else:
        print(blob)

    if not client["ok"]:
        sys.stderr.write("loadgen: SLO VIOLATED (client verdict)\n")
        return 1
    if args.server_slo and agree is not True:
        sys.stderr.write("loadgen: server verdict %s does not agree\n"
                         % (None if server_slo is None
                            else server_slo.get("verdict")))
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
