"""The plain reference: what a /report answer and a backfill tile row should be.

Independent of the program (it imports nothing from ``reporter_tpu`` and takes
no table the program built): the same semantics, written straightforwardly
in float64 from the configuration's stated model.

  geometry     equirectangular projection about the map's bbox centre
               (R = 6371000 m); edge and segment lengths are planar sums
               over the edge polylines
  candidates   every edge with a polyline piece within ``search_radius`` of
               the point, nearest ``beam_k`` kept, distance-sorted
  routes       exact Dijkstra (scipy) over the directed graph, bounded by
               ``ubodt_delta`` (Meili's UBODT: a route longer than delta is
               not found) and by the transition's own distance limit
  HMM          Meili's Gaussian emission and |route - great-circle| / beta
               transition, ``max_route_distance_factor`` and
               ``max_route_time_factor`` cuts, same-edge forward moves and
               small backward jitter, breaks at ``breakage_distance`` or
               when no transition is feasible; Viterbi with backtrace per
               unbroken run
  association  matched points joined by the shortest route into OSMLR
               segment traversals with linearly interpolated boundary times
  report       the reference reporter's walk (reporter_service.py:79-179):
               held-back tail, complete non-internal segments at the report
               levels, next_id at the transition levels, validity cuts
  tile rows    each usable report spread over its quantised time buckets
               (simple_reporter.py:176-196)

``Reference(net, matcher, precision="bfloat16")`` is the control: the same
code with the matching arithmetic (coordinates, distances, lengths, scores)
rounded to bfloat16 after every step.  Timestamps stay float64.
"""

from __future__ import annotations

import math

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

R_EARTH = 6371000.0
DEG = math.pi / 180.0
NEG = -1e30
INVALID_SEGMENT_ID = (((1 << 21) - 1) << 25) | (((1 << 22) - 1) << 3) | 7


def _rounder(precision: str):
    if precision == "float64":
        return (lambda v: v), (lambda a: np.asarray(a, np.float64))
    import ml_dtypes

    dt = {"bfloat16": ml_dtypes.bfloat16, "float32": np.float32}[precision]
    return ((lambda v: float(dt(v))),
            (lambda a: np.asarray(a, np.float64).astype(dt).astype(np.float64)))


class Reference:
    def __init__(self, net: dict, matcher: dict, precision: str = "float64"):
        self.cfg = dict(matcher)
        self.r, ra = _rounder(precision)
        self.ra = ra
        lat = np.asarray(net["nodes"]["lat"], np.float64)
        lon = np.asarray(net["nodes"]["lon"], np.float64)
        self.lat0 = 0.5 * (lat.min() + lat.max())
        self.lon0 = 0.5 * (lon.min() + lon.max())
        self.coslat0 = math.cos(self.lat0 * DEG)
        edges = net["edges"]
        E = len(edges)
        self.efrom = np.array([e["from"] for e in edges], np.int64)
        self.eto = np.array([e["to"] for e in edges], np.int64)
        self.speed = np.array([e["speed_kph"] / 3.6 for e in edges])
        self.internal = np.array([bool(e["internal"]) for e in edges])
        self.seg = [e["segment_id"] for e in edges]
        self.way = [e["way_id"] for e in edges]
        ax, ay, bx, by, pe, po, pl = [], [], [], [], [], [], []
        self.elen = np.zeros(E)
        for i, e in enumerate(edges):
            sh = np.asarray(e["shape"], np.float64)
            x, y = self.xy(sh[:, 0], sh[:, 1])
            x, y = ra(x), ra(y)
            off = 0.0
            for k in range(len(x) - 1):
                L = self.r(math.hypot(x[k + 1] - x[k], y[k + 1] - y[k]))
                ax.append(x[k]); ay.append(y[k]); bx.append(x[k + 1])
                by.append(y[k + 1]); pe.append(i); po.append(off); pl.append(L)
                off = self.r(off + L)
            self.elen[i] = off
        self.ax, self.ay = np.array(ax), np.array(ay)
        self.vx, self.vy = np.array(bx) - self.ax, np.array(by) - self.ay
        self.pedge = np.array(pe, np.int64)
        self.poff, self.plen = np.array(po), np.array(pl)
        # segment length and each member edge's offset within it (members
        # chained head to tail)
        members = {}
        for i, s in enumerate(self.seg):
            if s is not None and not self.internal[i]:
                members.setdefault(s, []).append(i)
        self.seg_len = {}
        self.eseg_off = np.zeros(E)
        for s, ids in members.items():
            off = 0.0
            for i in _chain(ids, self.efrom, self.eto):
                self.eseg_off[i] = off
                off += self.elen[i]
            self.seg_len[s] = off
        # the directed graph for routing; of parallel edges the shorter wins
        self.edge_of = {}
        for i in np.argsort(-self.elen, kind="stable"):
            self.edge_of[(int(self.efrom[i]), int(self.eto[i]))] = int(i)
        ids = np.array(list(self.edge_of.values()), np.int64)
        self.adj = csr_matrix((np.maximum(self.elen[ids], 1e-9),
                               (self.efrom[ids], self.eto[ids])),
                              shape=(len(lat), len(lat)))
        # uniform grid over polyline pieces, for exact radius queries
        self.cell = 100.0
        lo_x = np.minimum(self.ax, self.ax + self.vx)
        hi_x = np.maximum(self.ax, self.ax + self.vx)
        lo_y = np.minimum(self.ay, self.ay + self.vy)
        hi_y = np.maximum(self.ay, self.ay + self.vy)
        grid = {}
        for p in range(len(self.ax)):
            for cx in range(int(lo_x[p] // self.cell), int(hi_x[p] // self.cell) + 1):
                for cy in range(int(lo_y[p] // self.cell), int(hi_y[p] // self.cell) + 1):
                    grid.setdefault((cx, cy), []).append(p)
        self.grid = {k: np.array(v, np.int64) for k, v in grid.items()}
        self._routes = {}

    # -- geometry -----------------------------------------------------------

    def xy(self, lat, lon):
        lat = np.asarray(lat, np.float64)
        lon = np.asarray(lon, np.float64)
        dlon = np.mod(lon - self.lon0 + 180.0, 360.0) - 180.0
        return (R_EARTH * dlon * DEG * self.coslat0,
                R_EARTH * (lat - self.lat0) * DEG)

    def candidates(self, x, y, radius, k):
        """[(edge, offset, dist)] nearest first, at most k."""
        c = self.cell
        ids = [self.grid.get((cx, cy)) for cx in range(int((x - radius) // c), int((x + radius) // c) + 1)
               for cy in range(int((y - radius) // c), int((y + radius) // c) + 1)]
        ids = [a for a in ids if a is not None]
        if not ids:
            return []
        p = np.unique(np.concatenate(ids))
        ax, ay, vx, vy = self.ax[p], self.ay[p], self.vx[p], self.vy[p]
        L2 = vx * vx + vy * vy
        t = np.where(L2 == 0.0, 0.0, np.clip(((x - ax) * vx + (y - ay) * vy)
                                             / np.where(L2 == 0.0, 1.0, L2), 0.0, 1.0))
        d = self.ra(np.hypot(x - (ax + t * vx), y - (ay + t * vy)))
        best = {}
        for j in np.nonzero(d <= radius)[0]:
            e = int(self.pedge[p[j]])
            if e not in best or d[j] < best[e][0]:
                best[e] = (float(d[j]), self.r(self.poff[p[j]] + t[j] * self.plen[p[j]]))
        out = sorted(((e, off, dd) for e, (dd, off) in best.items()),
                     key=lambda c_: (c_[2], c_[0]))
        return out[:k]

    # -- routes (bounded exact Dijkstra, memoised per source) ----------------

    def _tree(self, src, limit):
        """(bound, dist[N], pred[N]) of an exact Dijkstra from src, complete
        up to ``bound`` metres; memoised per source."""
        hit = self._routes.get(src)
        if hit is not None and hit[0] >= limit:
            return hit
        limit = max(limit, hit[0] * 2 if hit else 0.0)
        dist, pred = dijkstra(self.adj, indices=src, limit=limit,
                              return_predecessors=True)
        out = (limit, dist, pred)
        if len(self._routes) >= 512:
            self._routes.clear()
        self._routes[src] = out
        return out

    def _path(self, src, dst, pred):
        out = []
        n = dst
        while n != src:
            p = int(pred[n])
            out.append(self.edge_of[(p, n)])
            n = p
        return out[::-1]

    def route(self, src, dst, limit):
        """(distance, time) of the shortest route, or None past limit."""
        if src == dst:
            return 0.0, 0.0
        _lim, dist, pred = self._tree(src, limit)
        if not dist[dst] <= limit:
            return None
        es = self._path(src, dst, pred)
        return self.r(float(dist[dst])), sum(self.elen[e] / max(self.speed[e], 0.1) for e in es)

    def path_edges(self, src, dst):
        """Edges of the shortest route src -> dst within ubodt_delta."""
        if src == dst:
            return []
        delta = float(self.cfg["ubodt_delta"])
        lim = 400.0
        while True:  # the tree is the same at any bound; grow it as needed
            _lim, dist, pred = self._tree(src, lim)
            if dist[dst] <= lim or lim >= delta:
                break
            lim = min(delta, lim * 4)
        if not dist[dst] <= delta:
            return None
        return self._path(src, dst, pred)

    # -- HMM ----------------------------------------------------------------

    def _transition(self, ca, cb, gc, dt):
        cfg, r = self.cfg, self.r
        ea, oa, _ = ca
        eb, ob, _ = cb
        sigma = float(cfg["sigma_z"])
        cap = float(cfg["max_route_distance_factor"]) * (gc + float(cfg["search_radius"]))
        if ea == eb and ob >= oa:
            route = r(ob - oa)
            rtime = route / max(self.speed[ea], 0.1)
        elif ea == eb and (oa - ob) <= 2.0 * sigma + 5.0:
            route = r((oa - ob) * 1.05 + 1.0)
            rtime = (oa - ob) / max(self.speed[ea], 0.1)
        else:
            found = self.route(int(self.eto[ea]), int(self.efrom[eb]),
                               min(cap, float(cfg["ubodt_delta"])))
            if found is None:
                return NEG
            sp, st = found
            route = r(r(r(self.elen[ea] - oa) + sp) + ob)
            rtime = ((self.elen[ea] - oa) / max(self.speed[ea], 0.1) + st
                     + ob / max(self.speed[eb], 0.1))
        if route > cap:
            return NEG
        if dt > 0 and rtime > float(cfg["max_route_time_factor"]) * max(dt, 1.0):
            return NEG
        return r(-abs(route - gc) / float(cfg["beta"]))

    def match_points(self, lat, lon, times):
        """(edge[T], offset[T], breaks[T]); edge -1 = unmatched."""
        cfg, r = self.cfg, self.r
        x, y = self.xy(lat, lon)
        x, y = self.ra(x), self.ra(y)
        T = len(x)
        edge = np.full(T, -1, np.int64)
        offset = np.zeros(T)
        breaks = np.zeros(T, bool)
        sigma = float(cfg["sigma_z"])
        cands = [self.candidates(float(x[t]), float(y[t]), float(cfg["search_radius"]),
                                 int(cfg["beam_k"])) for t in range(T)]

        def emis(c):
            return r(-0.5 * r(c[2] / sigma) ** 2)

        score = [[emis(c) for c in cands[0]]]
        bptr = [[-1] * len(cands[0])]
        bounds = [0]
        for t in range(1, T):
            gc = r(math.hypot(float(x[t] - x[t - 1]), float(y[t] - y[t - 1])))
            dt = float(times[t] - times[t - 1])
            prev, cur = cands[t - 1], cands[t]
            sc = [NEG] * len(cur)
            bp = [-1] * len(cur)
            broke = (gc > float(cfg["breakage_distance"]) or not prev or not cur
                     or max(score[-1], default=NEG) <= NEG / 2)
            if not broke:
                for j, cj in enumerate(cur):
                    for i, ci in enumerate(prev):
                        if score[-1][i] <= NEG / 2:
                            continue
                        tr = self._transition(ci, cj, gc, dt)
                        if tr <= NEG / 2:
                            continue
                        v = r(score[-1][i] + tr)
                        if v > sc[j]:
                            sc[j], bp[j] = v, i
                broke = all(v <= NEG / 2 for v in sc)
            if broke:
                bounds.append(t)
                sc = [emis(c) for c in cur]
                bp = [-1] * len(cur)
                breaks[t] = True
            else:
                sc = [r(v + emis(cur[j])) if v > NEG / 2 else NEG for j, v in enumerate(sc)]
            score.append(sc)
            bptr.append(bp)
        bounds.append(T)
        for s0, s1 in zip(bounds, bounds[1:]):
            sc = score[s1 - 1]
            if not sc or max(sc) <= NEG / 2:
                continue
            j = int(np.argmax(sc))
            for t in range(s1 - 1, s0 - 1, -1):
                if j < 0 or not cands[t]:
                    break
                edge[t], offset[t] = cands[t][j][0], cands[t][j][1]
                j = bptr[t][j] if t > s0 else -1
        breaks[0] = True
        return edge, offset, breaks

    # -- association ----------------------------------------------------------

    def segments(self, edge, offset, breaks, times):
        """The wire-format segment records of one matched trace."""
        back_tol = 2.0 * float(self.cfg["sigma_z"]) + 5.0
        q_mps = float(self.cfg["queue_speed_threshold_kph"]) / 3.6
        paths, spans, pins = [], [], []
        pos = 0.0
        prev = None
        for t in range(len(edge)):
            e, off, tm = int(edge[t]), float(offset[t]), float(times[t])
            if e < 0:
                if spans:
                    paths.append((spans, pins))
                spans, pins, prev = [], [], None
                continue
            if prev is None or breaks[t]:
                if spans:
                    paths.append((spans, pins))
                spans, pins, pos, prev = [[e, off, off, 0.0]], [(0.0, tm, t)], 0.0, e
                continue
            cur = spans[-1]
            if e == prev and off >= cur[2]:
                pos += off - cur[2]
                cur[2] = off
            elif e == prev and cur[2] - off <= back_tol:
                pass
            else:
                mid = self.path_edges(int(self.eto[prev]), int(self.efrom[e]))
                if mid is None:
                    paths.append((spans, pins))
                    spans, pins, pos, prev = [[e, off, off, 0.0]], [(0.0, tm, t)], 0.0, e
                    continue
                pos += self.elen[prev] - cur[2]
                cur[2] = self.elen[prev]
                for me in mid:
                    spans.append([me, 0.0, self.elen[me], pos])
                    pos += self.elen[me]
                spans.append([e, 0.0, off, pos])
                pos += off
            pins.append((pos, tm, t))
            prev = e
        if spans:
            paths.append((spans, pins))
        out = []
        for spans, pins in paths:
            out.extend(self._records(spans, pins, q_mps))
        return out

    def _records(self, spans, pins, q_mps):
        def time_at(p):
            if p <= pins[0][0]:
                return pins[0][1]
            for a, b in zip(pins, pins[1:]):
                if p <= b[0]:
                    if b[0] <= a[0]:
                        return a[1]
                    return a[1] + (p - a[0]) / (b[0] - a[0]) * (b[1] - a[1])
            return pins[-1][1]

        def shape_index_at(p):
            out = pins[0][2]
            for pp in pins:
                if pp[0] <= p + 1e-6:
                    out = pp[2]
                else:
                    break
            return out

        def queue(entry, exit_):
            q, at = 0.0, exit_
            for a, b in zip(reversed(pins[:-1]), reversed(pins[1:])):
                if b[0] <= entry:
                    break
                lo, hi = max(a[0], entry), min(b[0], exit_)
                if hi <= lo:
                    continue
                if hi < at - 1e-6:
                    break
                dt, dr = b[1] - a[1], b[0] - a[0]
                if (dr / dt if dt > 0 else math.inf) < q_mps:
                    q += hi - lo
                    at = lo
                else:
                    break
            return q

        recs = []
        i = 0
        while i < len(spans):
            e0 = spans[i][0]
            key = (self._seg_of(e0), bool(self.internal[e0]))
            j = i
            while j < len(spans) and (self._seg_of(spans[j][0]), bool(self.internal[spans[j][0]])) == key:
                j += 1
            group = spans[i:j]
            first, last = group[0], group[-1]
            entry = first[3]
            exit_ = last[3] + (last[2] - last[1])
            ways = []
            for g in group:
                w = self.way[g[0]]
                if w is not None and w >= 0 and w not in ways:
                    ways.append(w)
            rec = {"way_ids": ways, "internal": key[1],
                   "queue_length": round(queue(entry, exit_), 1),
                   "begin_shape_index": shape_index_at(entry),
                   "end_shape_index": shape_index_at(exit_)}
            seg = key[0]
            if seg is not None and not key[1]:
                total = self.seg_len[seg]
                at_start = self.eseg_off[first[0]] + first[1] <= 1e-3
                at_end = self.eseg_off[last[0]] + last[2] >= total - 1e-3
                rec["segment_id"] = seg
                rec["start_time"] = round(time_at(entry), 3) if at_start else -1
                rec["end_time"] = round(time_at(exit_), 3) if at_end else -1
                rec["length"] = round(total, 3) if (at_start and at_end) else -1
            else:
                rec["start_time"] = round(time_at(entry), 3)
                rec["end_time"] = round(time_at(exit_), 3)
                rec["length"] = -1
            recs.append(rec)
            i = j
        return recs

    def _seg_of(self, e):
        return None if self.internal[e] else self.seg[e]

    # -- the answers ----------------------------------------------------------

    def report(self, trace: dict, threshold_sec: int, report_levels, transition_levels):
        """The /report answer's checked parts: (segments, reports, shape_used)."""
        pts = trace["trace"]
        times = [float(p["time"]) for p in pts]
        edge, off, brk = self.match_points([p["lat"] for p in pts],
                                           [p["lon"] for p in pts], times)
        segs = self.segments(edge, off, brk, times)
        return segs, *walk_reports(segs, pts[-1]["time"], threshold_sec,
                                   set(report_levels), set(transition_levels))

    def tile_rows(self, trace: dict, threshold_sec, report_levels, transition_levels,
                  quantisation, source, vehicle_type):
        """[(tile path, csv row)] of one backfill window."""
        _segs, reports, _su = self.report(trace, threshold_sec, report_levels,
                                          transition_levels)
        pts = trace["trace"]
        max_buckets = (pts[-1]["time"] - pts[0]["time"]) // quantisation + 1
        rows = []
        for rp in reports:
            if not (rp["t0"] > 0 and rp["t1"] > 0 and rp["t1"] - rp["t0"] > 0.5
                    and rp["length"] > 0 and rp["queue_length"] >= 0):
                continue
            start, end = int(math.floor(rp["t0"])), int(math.ceil(rp["t1"]))
            lo, hi = start // quantisation, end // quantisation
            if hi - lo > max_buckets:
                continue
            row = (rp["id"], rp.get("next_id", INVALID_SEGMENT_ID),
                   int(math.floor(rp["t1"] - rp["t0"] + 0.5)), 1, rp["length"],
                   rp["queue_length"], start, end, source, vehicle_type)
            tile = rp["id"] & ((1 << 25) - 1)
            for b in range(lo, hi + 1):
                path = "%d_%d/%d/%d" % (b * quantisation, b * quantisation + quantisation - 1,
                                        tile & 7, tile >> 3)
                rows.append((path, row))
        return rows


def walk_reports(segs, end_time, threshold_sec, report_levels, transition_levels):
    """(datastore reports, shape_used): the reference reporter's walk."""
    last = len(segs) - 1
    while last >= 0 and end_time - segs[last]["start_time"] < threshold_sec:
        last -= 1
    shape_used = segs[last]["begin_shape_index"] if last >= 0 else None
    reports = []
    prior = None
    for idx in range(last + 1):
        s = segs[idx]
        sid = s.get("segment_id")
        internal = bool(s.get("internal"))
        level = (sid & 7) if sid is not None else -1
        if (prior is not None and prior["segment_id"] is not None
                and prior["length"] is not None and prior["length"] > 0 and not internal):
            if prior["level"] in report_levels:
                rp = {"id": prior["segment_id"], "t0": prior["start_time"],
                      "t1": s["start_time"] if level in transition_levels else prior["end_time"],
                      "length": prior["length"], "queue_length": prior["queue_length"]}
                if level in transition_levels and sid is not None:
                    rp["next_id"] = sid
                dt = float(rp["t1"]) - float(rp["t0"])
                if not (dt <= 0 or math.isinf(dt) or math.isnan(dt)) \
                        and not (prior["length"] / dt) * 3.6 > 160:
                    reports.append(rp)
        if not (internal and idx > 0):
            prior = {"segment_id": sid, "start_time": s["start_time"],
                     "end_time": s["end_time"], "length": s["length"],
                     "queue_length": s["queue_length"], "level": level}
    return reports, (shape_used or None)


def _chain(ids, efrom, eto):
    if len(ids) <= 1:
        return ids
    tos = {int(eto[e]) for e in ids}
    by_from = {int(efrom[e]): e for e in ids}
    starts = [e for e in ids if int(efrom[e]) not in tos]
    if len(starts) != 1 or len(by_from) != len(ids):
        return ids
    out = [starts[0]]
    while len(out) < len(ids):
        nxt = by_from.get(int(eto[out[-1]]))
        if nxt is None or nxt in out:
            return ids
        out.append(nxt)
    return out
