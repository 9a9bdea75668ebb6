"""Sparse-gap matching model: cohort resolution, calibrated parameters,
and route-consistent interpolation (docs/match-quality.md "Sparse gaps").

ROADMAP open item 4: agreement against the brute-force f64 oracle falls
0.969 -> 0.899 at the 45-60 s sampling gaps the reference's
BatchingProcessor actually emits, and the PR 11 delta sweep localised the
loss in the MODEL, not the UBODT table.  This module is the host-side
brain of the fix; the device math lives in ops/viterbi.py (SparseParams +
the *_packed_sparse entry points):

  * **Cohort resolution.**  A trace whose median inter-point gap is at/
    above ``sparse_gap_s`` belongs to a sparse cohort, labeled with the
    same gap buckets the quality plane uses (obs/quality.GAP_BUCKETS), so
    the calibration table, the agreement gauges, and the quality gate all
    speak one vocabulary.

  * **Calibrated parameters.**  ``tools/calibrate.py`` sweeps (sigma_z,
    beta(dt) family, search radius, candidate budget K) per gap cohort
    against the brute-force f64 oracle and pins the winners in
    CALIBRATION.json; this module loads it ($REPORTER_CALIBRATION /
    cfg.calibration) and serves per-cohort device params — MatchParams and
    SparseParams are traced scalars, so every cohort shares one compiled
    program per shape.  Without a calibration file the config-default
    family applies (the "uncalibrated" control the CI leg proves the gate
    catches).

  * **Route-consistent interpolation.**  The post-decode engine: each
    matched point-pair expands into its full UBODT shortest-path segment
    sequence (matching/segments.py already walks it) and traversal time is
    re-allocated across the intermediate spans by free-flow time
    (length/speed) instead of linearly by route distance — a 60 s gap
    crossing a slow side street and a fast arterial no longer reports the
    same dwell on both.  The record shape is byte-compatible with the
    classic association (same keys, same rounding), so the report /
    anonymise / tiles pipeline consumes it unchanged.

Flag-gating contract: with the model disabled (REPORTER_SPARSE=0 / the
cfg default) no dispatch, association, or wire byte differs from PR 14 —
tests/test_sparse.py pins it across both kernels x both layouts including
the session path.
"""

from __future__ import annotations

import json
import logging
import math
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..obs import log as obs_log
from ..obs import metrics as obs
from .segments import _build_paths, _Pin, _segment_records, _TimeLine

log = logging.getLogger(__name__)

C_RADIUS_CLAMPED = obs.counter(
    "reporter_candidates_radius_clamped_total",
    "search_radius values silently clamped to cell_size/2 (the 2x2 "
    "quadrant candidate sweep bound, ops/candidates.py) by source: "
    "request = per-request match_options, sparse = a sparse-cohort / "
    "calibrated radius, config = the matcher's own configured radius",
    ("source",))
C_SPARSE_DISPATCH = obs.counter(
    "reporter_sparse_dispatch_total",
    "Traces dispatched through the sparse-gap program variants, by gap "
    "cohort (docs/match-quality.md \"Sparse gaps\")",
    ("cohort",))
C_SPARSE_POINTS = obs.counter(
    "reporter_sparse_points_total",
    "GPS points of the traces dispatched through the sparse-gap program "
    "variants, by gap cohort: the sparse path's share of the points "
    "matched (beside reporter_sparse_dispatch_total's trace count)",
    ("cohort",))
G_CALIBRATED = obs.gauge(
    "reporter_sparse_calibrated",
    "1 when the sparse model is running per-cohort CALIBRATION.json "
    "parameters, 0 when enabled on uncalibrated config defaults, -1 when "
    "the sparse model is disabled")
C_INTERPOLATED = obs.counter(
    "reporter_interpolated_traces_total",
    "Traces associated through the route-consistent interpolation engine "
    "(match_options.interpolate / cfg.interpolate)")

# calibration keys understood per cohort; anything else in the file is
# provenance and ignored at load
_COHORT_KEYS = (
    "sigma_z", "beta", "search_radius", "k",
    "beta_ref_s", "beta_scale", "beta_max",
    "break_speed_mps", "vmax_mps", "plaus_weight",
)


def gap_label(times: List[float], gap_s: float) -> Optional[str]:
    """The sparse cohort label for a trace's timestamps, or None when the
    trace is dense (median gap below ``gap_s``).  Labels match the quality
    plane's gap buckets so calibration rows, agreement gauges, and the
    quality gate share one vocabulary."""
    if len(times) < 2:
        return None
    gaps = np.diff(np.asarray(times, np.float64))
    med = float(np.median(gaps))
    if med < gap_s:
        return None
    from ..obs.quality import GAP_BUCKETS

    for bound, label in GAP_BUCKETS:
        if med < bound:
            return label
    return GAP_BUCKETS[-1][1]


def load_calibration(path: str) -> Optional[dict]:
    """Parse a CALIBRATION.json: {"cohorts": {label: {param: value}}}.
    Returns None (logged) on any problem — a corrupt calibration must
    degrade to the config family, never take the matcher down."""
    try:
        with open(path) as f:
            d = json.load(f)
        cohorts = d.get("cohorts")
        if not isinstance(cohorts, dict) or not cohorts:
            raise ValueError("no cohorts")
        out = {}
        for label, row in cohorts.items():
            if not isinstance(row, dict):
                raise ValueError("cohort %r is not an object" % label)
            clean = {k: row[k] for k in _COHORT_KEYS if k in row}
            for k, v in clean.items():
                if k != "k" and not (isinstance(v, (int, float))
                                     and math.isfinite(float(v))):
                    raise ValueError("cohort %r key %r = %r" % (label, k, v))
            out[str(label)] = clean
        return {"cohorts": out, "path": path,
                "generated": d.get("generated"),
                "corpus": d.get("corpus")}
    except (OSError, ValueError, json.JSONDecodeError) as e:
        log.warning("calibration %s unusable (%s); sparse model runs the "
                    "config-default family", path, e)
        return None


class SparseModel:
    """Per-matcher sparse-gap model state: the enable flag, the calibration
    table, and the per-cohort device-params cache.  Built by
    SegmentMatcher.__init__; ``enabled`` False costs one attribute check
    per match_many call and nothing else."""

    def __init__(self, cfg, cell_size: float, mesh: bool = False):
        # ``mesh`` is accepted for call-site compatibility but no longer
        # disables anything: partitioning became a first-class axis of the
        # (kind, kernel) program family (parallel/rules.py), so the sparse
        # variants dispatch through the same rule-table-sharded programs
        # as dense traffic on any mesh topology.
        del mesh
        self.cfg = cfg
        self.cell_size = float(cell_size)
        env = os.environ.get("REPORTER_SPARSE", "").strip().lower()
        if env:
            self.enabled = env not in ("0", "false", "off", "no")
        else:
            self.enabled = bool(getattr(cfg, "sparse", False))
        self.gap_s = float(getattr(cfg, "sparse_gap_s", 40.0) or 40.0)
        self.calibration: Optional[dict] = None
        if self.enabled:
            path = (os.environ.get("REPORTER_CALIBRATION", "").strip()
                    or getattr(cfg, "calibration", "") or "")
            if path:
                self.calibration = load_calibration(path)
            if self.calibration:
                obs_log.event(
                    log, "sparse_calibration_loaded", path=path,
                    cohorts=sorted(self.calibration["cohorts"]))
        G_CALIBRATED.set(
            (1 if self.calibration else 0) if self.enabled else -1)
        # (label, pkey) -> (MatchParams, SparseParams, k) device pytrees
        self._params: Dict[tuple, tuple] = {}
        self._clamp_warned: set = set()

    # -- cohorts -----------------------------------------------------------

    def label_for_times(self, times: List[float]) -> Optional[str]:
        if not self.enabled:
            return None
        return gap_label(times, self.gap_s)

    def label_for_trace(self, trace: dict) -> Optional[str]:
        if not self.enabled:
            return None
        try:
            times = [float(p["time"]) for p in trace["trace"]]
        except (KeyError, TypeError, ValueError):
            return None
        return gap_label(times, self.gap_s)

    # -- parameters --------------------------------------------------------

    def cohort_values(self, label: str, pkey: tuple = ()) -> dict:
        """The effective sparse-model values for one cohort as plain
        floats: config family defaults, overlaid by the cohort's
        calibration row, overlaid by per-request match_options overrides
        (pkey = the matcher's (sigma_z, beta, search_radius) grouping key
        — explicit wire values win over calibration, reference
        precedence).  The radius is clamped to cell_size/2 with the clamp
        counted (docs/match-quality.md)."""
        cfg = self.cfg
        vals = {
            "sigma_z": float(cfg.sigma_z),
            "beta": float(cfg.beta),
            "search_radius": float(
                getattr(cfg, "sparse_search_radius", 0.0) or
                cfg.search_radius),
            "k": int(getattr(cfg, "sparse_beam_k", 0) or cfg.beam_k),
            "beta_ref_s": float(getattr(cfg, "sparse_beta_ref_s", 15.0)),
            "beta_scale": float(getattr(cfg, "sparse_beta_scale", 1.0)),
            "beta_max": float(getattr(cfg, "sparse_beta_max", 8.0)),
            "break_speed_mps": float(
                getattr(cfg, "sparse_break_speed_mps", 34.0)),
            "vmax_mps": float(getattr(cfg, "sparse_vmax_mps", 45.0)),
            "plaus_weight": float(getattr(cfg, "sparse_plaus_weight", 3.0)),
        }
        if self.calibration:
            row = self.calibration["cohorts"].get(label)
            if row is None:
                # nearest calibrated cohort stands in (a ge60 table also
                # serves an uncovered 30-45 trace rather than nothing)
                for alt in ("45-60", "ge60", "30-45"):
                    row = self.calibration["cohorts"].get(alt)
                    if row is not None:
                        break
            if row:
                vals.update({k: (int(v) if k == "k" else float(v))
                             for k, v in row.items()})
        if pkey:
            vals["sigma_z"], vals["beta"], vals["search_radius"] = (
                float(pkey[0]), float(pkey[1]), float(pkey[2]))
        vals["search_radius"] = self.clamp_radius(
            vals["search_radius"], source="sparse")
        vals["k"] = max(1, int(vals["k"]))
        return vals

    def params_for(self, label: str, pkey: tuple = ()) -> tuple:
        """Device (MatchParams, SparseParams, k) for one cohort, cached.
        Bounded like the matcher's per-request params cache."""
        key = (label, pkey)
        hit = self._params.get(key)
        if hit is not None:
            return hit
        import dataclasses

        from ..ops.viterbi import MatchParams, SparseParams

        if len(self._params) >= 64:
            self._params.clear()
        vals = self.cohort_values(label, pkey)
        cfg = dataclasses.replace(
            self.cfg, sigma_z=vals["sigma_z"], beta=vals["beta"],
            search_radius=vals["search_radius"])
        p = MatchParams.from_config(cfg)
        sp = SparseParams.from_values(
            vals["beta_ref_s"], vals["beta_scale"], vals["beta_max"],
            vals["break_speed_mps"], vals["vmax_mps"], vals["plaus_weight"])
        out = (p, sp, int(vals["k"]))
        self._params[key] = out
        return out

    def oracle_values(self, label: str, pkey: tuple = ()) -> dict:
        """The float values an f64 oracle twin needs for this cohort —
        identical resolution to params_for, host floats (obs/quality.py
        builds the BruteForceMatcher from these)."""
        return self.cohort_values(label, pkey)

    # -- the quadrant-sweep radius bound -----------------------------------

    def clamp_radius(self, radius: float, source: str = "sparse") -> float:
        """Clamp a search radius to cell_size/2 (the bound that keeps the
        2x2 quadrant candidate sweep exhaustive, ops/candidates.py) —
        counted and warned instead of silent (the clamp used to be
        invisible even in ?debug=1)."""
        return clamp_radius(radius, self.cell_size, source=source,
                            warned=self._clamp_warned)

    def summary(self) -> dict:
        """The /statusz-ready one-liner."""
        return {
            "enabled": self.enabled,
            "gap_s": self.gap_s,
            "calibrated": bool(self.calibration),
            "calibration": (self.calibration or {}).get("path"),
        }


_MODULE_CLAMP_WARNED: set = set()


def clamp_radius(radius: float, cell_size: float, source: str = "request",
                 warned: Optional[set] = None) -> float:
    """Shared radius clamp: min(radius, cell_size/2), with the clamp
    counted per source and warned once per distinct (source, radius) so a
    fleet of identical overrides cannot flood the log."""
    max_radius = float(cell_size) / 2.0
    if radius <= max_radius:
        return float(radius)
    C_RADIUS_CLAMPED.labels(source).inc()
    seen = _MODULE_CLAMP_WARNED if warned is None else warned
    key = (source, round(float(radius), 3))
    if key not in seen:
        if len(seen) >= 256:
            seen.clear()
        seen.add(key)
        obs_log.event(
            log, "search_radius_clamped", level=logging.WARNING,
            source=source, requested=round(float(radius), 3),
            clamped=round(max_radius, 3),
            reason="2x2 quadrant sweep requires radius <= cell_size/2; "
                   "rebuild the grid with a larger cell_size for a wider "
                   "radius")
    return max_radius


# -- route-consistent interpolation ------------------------------------------


def _retime_by_speed(arrays, spans, tl: _TimeLine) -> _TimeLine:
    """Insert pins at every span boundary between consecutive matched-point
    pins, with times allocated by cumulative FREE-FLOW traversal time
    (span length / edge speed) instead of linearly by route distance.
    Original pins keep their measured times bit-for-bit; only the
    in-between boundary times move, so a pair of edges at 30 vs 70 km/h
    splits a 60 s gap 70/30 instead of by metres."""
    pins = tl.pins
    if len(pins) < 2 or not spans:
        return tl
    # span boundaries as (route_pos, edge) in path order
    bounds: List[Tuple[float, int]] = []
    for s in spans:
        end = s.route_start + (s.exit_off - s.enter_off)
        bounds.append((end, s.edge))
    out: List[_Pin] = [pins[0]]
    bi = 0
    for a, b in zip(pins, pins[1:]):
        seg_total = b.route_pos - a.route_pos
        inner: List[Tuple[float, int]] = []
        while bi < len(bounds) and bounds[bi][0] <= b.route_pos + 1e-9:
            pos, edge = bounds[bi]
            bi += 1
            if a.route_pos + 1e-6 < pos < b.route_pos - 1e-6:
                inner.append((pos, edge))
        if inner and seg_total > 1e-9 and b.time > a.time:
            # free-flow time of each sub-interval: walk the spans covering
            # (a.route_pos, b.route_pos), weight by length/speed
            cuts = [a.route_pos] + [pos for pos, _e in inner] + [b.route_pos]
            ff = []
            for lo, hi in zip(cuts, cuts[1:]):
                t_ff = 0.0
                for s in spans:
                    s_lo = s.route_start
                    s_hi = s.route_start + (s.exit_off - s.enter_off)
                    o_lo, o_hi = max(lo, s_lo), min(hi, s_hi)
                    if o_hi > o_lo:
                        speed = max(float(arrays.edge_speed[s.edge]), 0.1)
                        t_ff += (o_hi - o_lo) / speed
                ff.append(t_ff)
            total_ff = sum(ff)
            dt = b.time - a.time
            acc = 0.0
            for (pos, _edge), t_piece in zip(inner, ff[:-1]):
                acc += t_piece
                frac = acc / total_ff if total_ff > 1e-12 else (
                    (pos - a.route_pos) / seg_total)
                out.append(_Pin(pos, a.time + frac * dt, a.shape_index))
        out.append(b)
    return _TimeLine(out)


def associate_interpolated(arrays, ubodt, match_points: List[dict],
                           queue_thresh_mps: float = 20.0 / 3.6,
                           back_tol: float = 15.0) -> List[dict]:
    """matching/segments.associate_segments with route-consistent
    interpolation: the SAME path reconstruction (every traversed UBODT
    shortest-path edge becomes a span — nothing new is invented), but the
    piecewise time line gains speed-weighted pins at intermediate span
    boundaries before the records render.  Record shape, key order, and
    rounding are identical to the classic walk, so report()/anonymise/
    tiles consume the output unchanged (tests/test_sparse.py pins the
    schema)."""
    out: List[dict] = []
    for spans, tl in _build_paths(arrays, ubodt, match_points,
                                  back_tol=back_tol):
        tl2 = _retime_by_speed(arrays, spans, tl)
        out.extend(_segment_records(arrays, spans, tl2, queue_thresh_mps))
    C_INTERPOLATED.inc()
    return out
