"""The plain reference of the sparse-gap model: ``reference.Reference`` plus
the matcher's answer to sparse sampling, in float64.

Like ``reference.py`` it imports nothing from ``reporter_tpu``.  The model
is written from the equations the configuration states (its
``sparse_model`` block), with the knobs its ``matcher`` block gives:

  cohort       a window whose median gap between fixes is at least
               ``sparse_gap_s`` is sparse; any other window is decoded by
               ``Reference`` unchanged
  candidates   the nearest ``sparse_beam_k`` edges within
               ``sparse_search_radius`` (0 = ``search_radius``)
  beta(dt)     beta * min(1 + beta_scale * max(dt - beta_ref, 0) /
               max(beta_ref, 1), beta_max) for a gap dt > 0; beta otherwise
  breakage     a step breaks where the straight line between the fixes is
               longer than max(breakage_distance, break_speed * max(dt, 0))
  plausibility for dt > 0 a transition loses plaus_weight * max(route /
               max(dt, 1) - vmax, 0) / max(vmax, 1) of log-probability
  transition   -|route - great circle| / beta(dt) minus that term, after
               the same distance and time cuts and same-edge rules as the
               dense model; emission as the dense model

``SparseReference(net, matcher, precision="bfloat16")`` is the control, as
for ``Reference``.
"""

from __future__ import annotations

import math

import numpy as np

from reference import NEG, Reference


class SparseReference(Reference):
    def __init__(self, net: dict, matcher: dict, precision: str = "float64"):
        super().__init__(net, matcher, precision)
        c = self.cfg
        self.on = bool(c.get("sparse", False))
        self.gap_s = float(c.get("sparse_gap_s", 40.0))
        self.k_sparse = max(1, int(c.get("sparse_beam_k", 0) or c["beam_k"]))
        self.radius_sparse = float(c.get("sparse_search_radius", 0.0) or c["search_radius"])
        self.beta_ref = float(c.get("sparse_beta_ref_s", 15.0))
        self.beta_scale = float(c.get("sparse_beta_scale", 1.0))
        self.beta_max = float(c.get("sparse_beta_max", 8.0))
        self.break_speed = float(c.get("sparse_break_speed_mps", 34.0))
        self.vmax = float(c.get("sparse_vmax_mps", 45.0))
        self.plaus_weight = float(c.get("sparse_plaus_weight", 3.0))

    def is_sparse(self, times) -> bool:
        """The cohort rule: the median gap between fixes is >= sparse_gap_s."""
        if not self.on or len(times) < 2:
            return False
        return float(np.median(np.diff(np.asarray(times, np.float64)))) >= self.gap_s

    # -- the model's terms ----------------------------------------------------

    def beta_at(self, dt: float) -> float:
        beta = float(self.cfg["beta"])
        if dt <= 0:
            return beta
        mult = 1.0 + self.beta_scale * max(dt - self.beta_ref, 0.0) / max(self.beta_ref, 1.0)
        return self.r(beta * min(mult, self.beta_max))

    def breakage_at(self, dt: float) -> float:
        return max(float(self.cfg["breakage_distance"]), self.break_speed * max(dt, 0.0))

    def plausibility(self, route: float, dt: float) -> float:
        """Log-probability lost for the speed a route implies over gap dt."""
        if dt <= 0:
            return 0.0
        excess = max(route / max(dt, 1.0) - self.vmax, 0.0) / max(self.vmax, 1.0)
        return self.r(self.plaus_weight * excess)

    def _route(self, ca, cb, gc, dt, radius):
        """The dense model's route between two candidates under its cuts:
        metres, or None where the transition is infeasible."""
        cfg, r = self.cfg, self.r
        ea, oa, _ = ca
        eb, ob, _ = cb
        sigma = float(cfg["sigma_z"])
        cap = float(cfg["max_route_distance_factor"]) * (gc + radius)
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
                return None
            sp, st = found
            route = r(r(r(self.elen[ea] - oa) + sp) + ob)
            rtime = ((self.elen[ea] - oa) / max(self.speed[ea], 0.1) + st
                     + ob / max(self.speed[eb], 0.1))
        if route > cap:
            return None
        if dt > 0 and rtime > float(cfg["max_route_time_factor"]) * max(dt, 1.0):
            return None
        return route

    def sparse_transition(self, ca, cb, gc, dt, radius):
        route = self._route(ca, cb, gc, dt, radius)
        if route is None:
            return NEG
        r = self.r
        return r(r(-abs(route - gc) / self.beta_at(dt)) - self.plausibility(route, dt))

    # -- decoding ---------------------------------------------------------------

    def match_points(self, lat, lon, times):
        """(edge[T], offset[T], breaks[T]); a dense window as ``Reference``."""
        if not self.is_sparse(times):
            return super().match_points(lat, lon, times)
        r = self.r
        x, y = self.xy(lat, lon)
        x, y = self.ra(x), self.ra(y)
        T = len(x)
        edge = np.full(T, -1, np.int64)
        offset = np.zeros(T)
        breaks = np.zeros(T, bool)
        sigma = float(self.cfg["sigma_z"])
        radius = self.radius_sparse
        cands = [self.candidates(float(x[t]), float(y[t]), radius, self.k_sparse)
                 for t in range(T)]

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
            broke = (gc > self.breakage_at(dt) or not prev or not cur
                     or max(score[-1], default=NEG) <= NEG / 2)
            if not broke:
                for j, cj in enumerate(cur):
                    for i, ci in enumerate(prev):
                        if score[-1][i] <= NEG / 2:
                            continue
                        tr = self.sparse_transition(ci, cj, gc, dt, radius)
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
