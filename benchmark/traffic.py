"""The one traffic generator: a mix's data file + the city + a seed -> work.

A mix is ``benchmark/traffic/<name>.json``.  Its ``kind`` picks one of the
two shapes of work users send:

  stream    the Kafka reporter's POSTs to /report.  Each request is one
            vehicle's window, flushed at >= ``flush.min_points`` points and
            >= ``flush.min_metres`` and >= ``flush.min_seconds``
            (BatchingProcessor.java:26-29), with a re-sent tail of up to
            ``resend_tail_s`` seconds (Batch.java:56-80); vehicles sample at
            the intervals of ``sample_interval_s`` in equal shares.
            Arrivals are open loop: ``poisson`` at ``rate_rps``.
  backfill  the batch job's phase-1 shard files: ``vehicles_per_file``
            vehicles, each with ``drives_per_vehicle`` drives separated by
            stops longer than the 120 s split, sampled every
            ``sample_interval_s``; drive lengths from ``drive_minutes``.

Drives are random walks over the directed road graph (no U-turn unless the
road ends), driven at each edge's speed times a per-drive factor from
``speed_factor``, sampled along the edge polylines, with Gaussian GPS noise of
``gps_sigma_m``.  Every seed gets the same sizes and gaps (stratified
quantiles of the stated distributions) in its own order, on its own routes,
so seeds change the work's place on the map but not its amount.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
EPOCH0 = 1767225600  # 2026-01-01T00:00:00Z
R_EARTH = 6371000.0
DEG = math.pi / 180.0


def rng_for(seed: int, *salt):
    """A numpy Generator for any whole-number seed (negative or past 64
    bits included) and a salt naming its use."""
    s = int(seed)
    return np.random.default_rng([s & (2**64 - 1), (s >> 64) & (2**64 - 1), *salt])


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


class Roads:
    """The walkable graph of a network dict, in metres about its bbox centre."""

    def __init__(self, net: dict):
        lat = np.asarray(net["nodes"]["lat"], np.float64)
        lon = np.asarray(net["nodes"]["lon"], np.float64)
        self.lat0 = 0.5 * (lat.min() + lat.max())
        self.lon0 = 0.5 * (lon.min() + lon.max())
        self.coslat0 = math.cos(self.lat0 * DEG)
        edges = net["edges"]
        self.efrom = np.array([e["from"] for e in edges], np.int64)
        self.eto = np.array([e["to"] for e in edges], np.int64)
        self.speed = np.array([e["speed_kph"] / 3.6 for e in edges])
        px, py, pc, start = [], [], [], [0]
        for e in edges:
            sh = np.asarray(e["shape"], np.float64)
            x = R_EARTH * (sh[:, 1] - self.lon0) * DEG * self.coslat0
            y = R_EARTH * (sh[:, 0] - self.lat0) * DEG
            c = np.concatenate([[0.0], np.cumsum(np.hypot(np.diff(x), np.diff(y)))])
            px.append(x); py.append(y); pc.append(c); start.append(start[-1] + len(x))
        self.px, self.py = np.concatenate(px), np.concatenate(py)
        self.pcum = np.concatenate(pc)
        self.pstart = np.array(start, np.int64)
        self.elen = self.pcum[self.pstart[1:] - 1]
        order = np.argsort(self.efrom, kind="stable")
        self.out_edges = order
        self.out_start = np.searchsorted(self.efrom[order], np.arange(len(lat) + 1))
        deg = np.diff(self.out_start)
        # edges into a node with no way out end a walk: never take one
        self.sink = deg[self.eto] == 0
        self.starts = np.array([i for i, e in enumerate(edges)
                                if not e["internal"] and not self.sink[i]], np.int64)

    def to_latlon(self, x, y):
        return (y / (R_EARTH * DEG) + self.lat0,
                x / (R_EARTH * DEG * self.coslat0) + self.lon0)

    def drives(self, rng, seconds, dt, sigma, speed_factor):
        """One drive per entry of ``seconds``, sampled every ``dt`` s from
        t = 0: [(t int[n], lat[n], lon[n])], lat/lon on a 1e-7 degree grid."""
        seconds = np.asarray(seconds, np.float64)
        n = len(seconds)
        cur = rng.choice(self.starts, n)
        f = rng.uniform(speed_factor[0], speed_factor[1], n)
        t_in = np.zeros(n)
        alive = np.ones(n, bool)
        steps_e, steps_t = [cur.copy()], [t_in.copy()]
        while alive.any():
            t_in = t_in + np.where(alive, self.elen[cur] / (self.speed[cur] * f), 0.0)
            node = self.eto[cur]
            lo, deg = self.out_start[node], self.out_start[node + 1] - self.out_start[node]
            alive &= (t_in <= seconds) & (deg > 0)
            k = np.minimum((rng.random(n) * np.maximum(deg, 1)).astype(np.int64),
                           np.maximum(deg - 1, 0))
            top = len(self.out_edges) - 1
            nxt = self.out_edges[np.minimum(lo + k, top)]
            for r in range(1, 4):  # no U-turn and no sink while another way exists
                bad = (((self.eto[nxt] == self.efrom[cur]) | self.sink[nxt]) & (deg > r))
                alt = self.out_edges[np.minimum(lo + (k + r) % np.maximum(deg, 1), top)]
                nxt = np.where(bad, alt, nxt)
            cur = np.where(alive, nxt, cur)
            steps_e.append(cur.copy())
            steps_t.append(np.where(alive, t_in, np.inf))
        E = np.stack(steps_e, 1)          # [n, steps] edge entered at step j
        Ts = np.stack(steps_t, 1)         # [n, steps] time it was entered
        out = []
        for i in range(n):
            ts = np.arange(0.0, seconds[i] + 1e-9, dt)
            j = np.searchsorted(Ts[i], ts, side="right") - 1
            e = E[i, j]
            d = np.minimum((ts - Ts[i, j]) * self.speed[e] * f[i], self.elen[e])
            # the polyline piece holding distance d along edge e
            base = self.pstart[e]
            last = self.pstart[e + 1] - 2
            p = self._piece(base, last, d)
            seg = self.pcum[p + 1] - self.pcum[p]
            w = np.where(seg > 0, (d - self.pcum[p]) / np.where(seg > 0, seg, 1.0), 0.0)
            x = self.px[p] + w * (self.px[p + 1] - self.px[p]) + rng.normal(0, sigma, len(ts))
            y = self.py[p] + w * (self.py[p + 1] - self.py[p]) + rng.normal(0, sigma, len(ts))
            lat, lon = self.to_latlon(x, y)
            out.append((ts.astype(np.int64), np.rint(lat * 1e7) / 1e7,
                        np.rint(lon * 1e7) / 1e7))
        return out

    def _piece(self, base, last, d):
        p = base.copy()
        while True:
            step = (p < last) & (self.pcum[np.minimum(p + 1, len(self.pcum) - 1)] <= d)
            if not step.any():
                return p
            p = p + step


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified draws (quantiles at (k + 0.5) / n) of a stated distribution."""
    u = (np.arange(n) + 0.5) / n
    if "uniform" in spec:
        lo, hi = spec["uniform"]
        return lo + u * (hi - lo)
    if "lognormal_median" in spec:
        from statistics import NormalDist

        z = np.array([NormalDist().inv_cdf(v) for v in u])
        v = spec["lognormal_median"] * np.exp(spec["lognormal_sigma"] * z)
        return np.clip(v, spec.get("min", 0.0), spec.get("max", np.inf))
    if "exponential_mean" in spec:
        return -np.log1p(-u) * spec["exponential_mean"]
    raise ValueError("unknown distribution %r" % (spec,))


# -- stream: open-loop /report requests --------------------------------------


def arrivals(mix: dict, seconds: float, rng) -> np.ndarray:
    """Due times in [0, seconds) of the mix's open-loop schedule."""
    a = mix["arrivals"]
    if a["pattern"] != "poisson":
        raise ValueError("unknown arrival pattern %r" % a["pattern"])
    rate = float(a["rate_rps"])
    n = int(round(rate * seconds))
    gaps = _quantiles({"exponential_mean": 1.0 / rate}, n)
    tau = np.cumsum(rng.permutation(gaps))
    return tau * (seconds / tau[-1]) * (1 - 0.5 / n) if n else tau


def stream_requests(roads: Roads, mix: dict, seconds: float, seed: int):
    """[(due_s, request)] for a window of ``seconds``, sorted by due time."""
    rng = rng_for(seed, 1)
    due = arrivals(mix, seconds, rng)
    n = len(due)
    dts = list(mix["sample_interval_s"])
    cls = rng.permutation(np.arange(n) % len(dts))
    tail = rng.permutation(_quantiles({"uniform": mix["resend_tail_s"]}, n))
    fl = mix["flush"]
    reqs = [None] * n
    for c, dt in enumerate(dts):
        idx = np.nonzero(cls == c)[0]
        # long enough for the flush rule at the slowest speed, plus the tail
        need = (max(fl["min_seconds"], (fl["min_points"] - 1) * dt,
                    fl["min_metres"] / 4.0) + mix["resend_tail_s"][1] + 2 * dt)
        drives = roads.drives(rng, np.full(len(idx), need), dt,
                              mix["gps_sigma_m"], mix["speed_factor"])
        starts = rng.integers(0, 86400 - int(need), len(idx))
        for i, (t, lat, lon), t0 in zip(idx, drives, starts):
            reqs[i] = _window(i, dt, t + EPOCH0 + t0, lat, lon, fl, tail[i], mix)
    return list(zip(due.tolist(), reqs))


def _window(i, dt, t, lat, lon, fl, tail_s, mix):
    """The flushed window: ``tail_s`` of re-sent points, then the fewest new
    points that meet the flush rule."""
    m = int(tail_s // dt)
    x = lon * DEG * R_EARTH * math.cos(lat[0] * DEG)
    y = lat * DEG * R_EARTH
    step = np.concatenate([[0.0], np.hypot(np.diff(x), np.diff(y))])
    end = m + 1
    while end < len(t) and not (end - m >= fl["min_points"]
                                and t[end - 1] - t[m] >= fl["min_seconds"]
                                and step[m + 1:end].sum() >= fl["min_metres"]):
        end += 1
    return {"uuid": "veh-%06d" % i,
            "trace": [{"lat": float(a), "lon": float(o), "time": int(tt)}
                      for a, o, tt in zip(lat[:end], lon[:end], t[:end])],
            "match_options": dict(mix["match_options"])}


# -- backfill: phase-1 shard files -------------------------------------------


def backfill_files(roads: Roads, mix: dict, n_files: int, seed: int, first: int = 0):
    """``n_files`` shard files, each [(uuid, t int[], lat[], lon[])] of drives
    in vehicle order.  Every file has the same multiset of drive lengths."""
    out = []
    per = int(mix["drives_per_vehicle"])
    nveh = int(mix["vehicles_per_file"])
    minutes = _quantiles(mix["drive_minutes"], per * nveh)
    stops = _quantiles({"uniform": mix["stop_minutes"]}, per * nveh)
    dt = float(mix["sample_interval_s"])
    for k in range(first, first + n_files):
        rng = rng_for(seed, 2, k)
        secs = rng.permutation(minutes) * 60.0
        gaps = rng.permutation(stops) * 60.0
        drives = roads.drives(rng, secs, dt, mix["gps_sigma_m"], mix["speed_factor"])
        day0 = EPOCH0 + rng.integers(0, 8 * 3600, nveh)
        rows = []
        for v in range(nveh):
            t_at = int(day0[v])
            for j in range(per):
                t, lat, lon = drives[v * per + j]
                rows.append(("%03x-%04d" % (k % 4096, v), t + t_at, lat, lon))
                t_at = int(t[-1] + t_at + gaps[v * per + j])
        out.append(rows)
    return out


def write_shard(path: str, drives, accuracy: int) -> int:
    """Write one shard file of ``uuid,epoch,lat,lon,acc`` rows; returns points."""
    n = 0
    with open(path, "w") as f:
        for uuid, t, lat, lon in drives:
            f.write("".join("%s,%d,%.7f,%.7f,%d\n" % (uuid, a, b, c, accuracy)
                            for a, b, c in zip(t.tolist(), lat.tolist(), lon.tolist())))
            n += len(t)
    return n
