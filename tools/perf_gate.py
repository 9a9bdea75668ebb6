#!/usr/bin/env python3
"""Noise-aware performance-regression gate over the bench history.

Loads the ``BENCH_r*.json`` history (driver wrapper files with the bench
line under ``parsed``, or raw one-line bench artifacts — both accepted),
picks the candidate run (``--fresh FILE``, else the last argument), and
judges it against LIKE-PROVENANCE history only:

  * same ``platform`` — a CPU bank is NEVER judged against an on-chip
    bank (the round-5 failure this gate exists to prevent: the official
    record said 0.57x from an rc-124 CPU corpse while the same-day
    on-chip capture said 168x);
  * comparable scenario scale — ``edges`` within one power of two
    (benches across rounds vary grid size; throughput does not transfer
    across scales).  Rows without ``edges`` cannot establish
    comparability and are excluded from the baseline set;
  * honest artifacts only — wrapper rows with a nonzero ``rc`` (timeout
    corpses) and rows without a headline ``value`` are excluded.

The judged metrics are ``points_per_sec`` (the work-normalised headline
basis), ``vs_baseline`` (self-normalising on CPU, where absolute rates
move with machine load), ``kernel_points_per_sec`` when both sides
carry it, and ``cost_usd_per_million_points`` (flattened from the
artifact ``cost`` block, docs/economics.md) — the one LOWER-is-better
metric: a run that got faster by burning disproportionately more chips
fails on cost, judged against the same like-provenance median.  Noise awareness: the baseline is the like-provenance history
MEDIAN, and the failure threshold is max(--threshold, the history's own
relative spread) — two historical runs that disagree by 30% cannot
justify failing a fresh run 15% below their median.

Schema validity is asserted on the candidate: the required keys
(incl. the round-6 ``attrib`` block — present, or an explicit null with
``attrib_reason``) must exist.

Exit codes: 0 = no regression (incl. the explicit no-like-provenance-
history verdict), 1 = regression, 2 = invalid input/schema.  The verdict
renders as one JSON object on stdout.

CI: the perf-gate leg runs a CPU smoke bench and gates it here with wide
CPU thresholds (.github/workflows/ci.yml).

    python tools/perf_gate.py BENCH_r0*.json
    python tools/perf_gate.py BENCH_r0*.json --fresh /tmp/bench_fresh.json
"""

from __future__ import annotations

import argparse
import json
import math
import sys

# keys every emitted bench line must carry (docs/bench-schema.md).  The
# round-6 attrib key (it may be null but the KEY must exist, with an
# attrib_reason when null) is asserted with
# --require-attrib, which the CI leg sets; pre-round-6 history predates
# them and must stay judgeable.
REQUIRED_KEYS = ("metric", "value", "unit", "platform")
ATTRIB_KEYS = ("attrib",)
# judged metrics -> the GOOD direction.  Throughput families regress
# when they DROP; cost families (docs/economics.md — the chip-second
# ledger's $-per-million-matched-points rides every artifact) regress
# when they RISE.  Nested artifact cost blocks are flattened to the
# ``cost_usd_per_million_points`` key by load_bench_line.
METRICS = {
    "points_per_sec": "higher",
    "vs_baseline": "higher",
    "kernel_points_per_sec": "higher",
    "cost_usd_per_million_points": "lower",
    # device-resident session arenas (docs/performance.md): streaming
    # sessions held per chip by the hot/cold arena tiers — residency
    # regresses when it DROPS (fewer vehicles fit before the host-carry
    # fallback), so higher is better like the throughput families
    "sessions_resident_per_chip": "higher",
    # mesh scaling leg (docs/performance.md "One logical matcher per
    # pod"): (mesh tps / single-matcher tps) / devices, flattened from
    # the artifact ``mesh`` block.  Regresses when it DROPS — a sharding
    # change that stops chips from adding capacity shows up here even if
    # the single-device headline holds.  Judged like-provenance only:
    # CPU virtual devices share host cores, so CPU-bank efficiencies
    # (~1/devices) are only ever compared with other CPU banks.
    "mesh_scaling_efficiency": "higher",
    # columnar host data plane (docs/performance.md "The columnar host
    # data plane"): the padded-batch packer's points/s at the canonical
    # [512, 64] shape, flattened from the artifact ``host_pipeline``
    # block — the host-side throughput the vectorized scatter bought;
    # regresses when it DROPS
    "host_pack_points_per_sec": "higher",
    # host share of (host + device) wall over a live match_many capture
    # — the fraction the columnar plane exists to shrink; regresses when
    # it RISES (host Python creeping back between the device dispatches)
    "host_frac": "lower",
}

# default relative-drop thresholds per provenance: CPU rates move with
# machine load (bench-schema.md interpretation guardrails), so the CPU
# gate is wide by default; --threshold overrides both
DEFAULT_THRESHOLD = {"tpu": 0.15, "cpu": 0.40}


def load_bench_line(path: str) -> dict:
    """A bench line from either artifact shape: the driver wrapper
    ({"n", "rc", "parsed", "tail"}) or a raw one-line bench JSON.  The
    wrapper's ``rc`` rides along as ``_rc`` (0 for raw artifacts)."""
    with open(path) as f:
        d = json.load(f)
    if "parsed" in d or "tail" in d:  # driver wrapper
        line = d.get("parsed")
        if line is None:
            # fall back to the last parseable line of the tail
            for ln in reversed(str(d.get("tail", "")).strip().splitlines()):
                try:
                    line = json.loads(ln)
                    break
                except (json.JSONDecodeError, ValueError):
                    continue
        line = dict(line or {})
        line["_rc"] = d.get("rc", 0)
    else:
        line = dict(d)
        line.setdefault("_rc", 0)
    cost = line.get("cost")
    if isinstance(cost, dict) and isinstance(
            cost.get("usd_per_million_points"), (int, float)):
        line.setdefault("cost_usd_per_million_points",
                        cost["usd_per_million_points"])
    mesh = line.get("mesh")
    if isinstance(mesh, dict) and isinstance(
            mesh.get("scaling_efficiency"), (int, float)):
        line.setdefault("mesh_scaling_efficiency",
                        mesh["scaling_efficiency"])
    hp = line.get("host_pipeline")
    if isinstance(hp, dict):
        pack = hp.get("pack")
        if isinstance(pack, dict) and isinstance(
                pack.get("host_pack_points_per_sec"), (int, float)):
            line.setdefault("host_pack_points_per_sec",
                            pack["host_pack_points_per_sec"])
        if isinstance(hp.get("host_frac"), (int, float)):
            line.setdefault("host_frac", hp["host_frac"])
    line["_path"] = path
    return line


def scale_bucket(line: dict):
    """log2 bucket of the scenario's edge count — rows in the same bucket
    ran comparable scenario scales.  None when the line carries no edges
    (not comparable to anything)."""
    edges = line.get("edges")
    if not edges:
        return None
    return int(round(math.log2(float(edges))))


def usable_baseline(line: dict) -> "tuple[bool, str]":
    if line.get("_rc", 0) != 0:
        return False, "rc=%s (timeout/corpse artifact)" % line["_rc"]
    if line.get("value") is None:
        return False, "no headline value"
    if scale_bucket(line) is None:
        return False, "no edges field (scenario scale unknown)"
    return True, ""


def like_provenance(candidate: dict, history: "list[dict]") -> "tuple[list, list]":
    """(baselines, excluded) — the history rows the candidate may honestly
    be judged against, plus the exclusion log."""
    cplat = candidate.get("platform")
    cscale = scale_bucket(candidate)
    used, excluded = [], []
    for h in history:
        ok, why = usable_baseline(h)
        if not ok:
            excluded.append({"file": h["_path"], "reason": why})
            continue
        if h.get("platform") != cplat:
            excluded.append({"file": h["_path"],
                             "reason": "platform %r != candidate %r (CPU "
                                       "banks are never judged against "
                                       "on-chip banks)"
                                       % (h.get("platform"), cplat)})
            continue
        if cscale is None or abs(scale_bucket(h) - cscale) > 1:
            excluded.append({"file": h["_path"],
                             "reason": "scenario scale %s edges vs candidate "
                                       "%s: not comparable"
                                       % (h.get("edges"), candidate.get("edges"))})
            continue
        hs, cs = h.get("scenario"), candidate.get("scenario")
        if hs and cs and hs != cs:
            excluded.append({"file": h["_path"],
                             "reason": "scenario %r != candidate %r" % (hs, cs)})
            continue
        used.append(h)
    return used, excluded


def _median(xs: "list[float]") -> float:
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def judge(candidate: dict, baselines: "list[dict]", threshold: float) -> dict:
    """Per-metric comparison against the like-provenance median with the
    history's own relative spread widening the threshold."""
    comparisons = {}
    regressed = False
    for key, direction in METRICS.items():
        cv = candidate.get(key)
        hv = [h[key] for h in baselines if isinstance(h.get(key), (int, float))]
        if not isinstance(cv, (int, float)) or not hv:
            comparisons[key] = {"verdict": "no-data"}
            continue
        med = _median(hv)
        spread = (max(hv) - min(hv)) / med if med > 0 and len(hv) > 1 else 0.0
        tol = max(threshold, spread)
        ratio = cv / med if med > 0 else None
        if direction == "lower":
            bad = ratio is not None and ratio > 1.0 + tol
        else:
            bad = ratio is not None and ratio < 1.0 - tol
        comparisons[key] = {
            "candidate": cv,
            "direction": direction,
            "history_median": round(med, 3),
            "history_n": len(hv),
            "history_spread": round(spread, 3),
            "threshold": round(tol, 3),
            "ratio": round(ratio, 3) if ratio is not None else None,
            "verdict": "REGRESSION" if bad else "ok",
        }
        regressed = regressed or bad
    return {"regressed": regressed, "metrics": comparisons}


def gate(paths: "list[str]", fresh: "str | None" = None,
         threshold: "float | None" = None,
         require_attrib: bool = False) -> "tuple[int, dict]":
    """The whole gate as a function (unit-tested directly).  Returns
    (exit_code, verdict_dict)."""
    if fresh is None:
        if len(paths) < 1:
            return 2, {"error": "no input files"}
        paths, fresh = paths[:-1], paths[-1]
    try:
        candidate = load_bench_line(fresh)
        history = [load_bench_line(p) for p in paths]
    except (OSError, json.JSONDecodeError, ValueError) as e:
        return 2, {"error": "unreadable input: %s" % e}

    required = REQUIRED_KEYS + (ATTRIB_KEYS if require_attrib else ())
    missing = [k for k in required if k not in candidate]
    verdict: dict = {
        "candidate": fresh,
        "platform": candidate.get("platform"),
        "edges": candidate.get("edges"),
        "history_files": [h["_path"] for h in history],
    }
    if missing:
        verdict["verdict"] = "INVALID"
        verdict["error"] = "candidate missing required keys: %s" % missing
        return 2, verdict
    if (require_attrib and candidate.get("attrib") is None
            and "attrib_reason" not in candidate):
        verdict["verdict"] = "INVALID"
        verdict["error"] = ("candidate attrib is null without an "
                            "attrib_reason (schema-complete lines carry one)")
        return 2, verdict
    if candidate.get("_rc", 0) != 0:
        verdict["verdict"] = "INVALID"
        verdict["error"] = ("candidate is an rc=%s corpse artifact — not a "
                            "judgeable run" % candidate["_rc"])
        return 2, verdict

    baselines, excluded = like_provenance(candidate, history)
    verdict["baselines"] = [h["_path"] for h in baselines]
    verdict["excluded"] = excluded
    if not baselines:
        # the explicit missing-history verdict: schema was valid, nothing
        # comparable exists — a pass, stated rather than silent
        verdict["verdict"] = "NO-LIKE-PROVENANCE-HISTORY"
        return 0, verdict

    if threshold is None:
        threshold = DEFAULT_THRESHOLD.get(candidate.get("platform"), 0.40)
    verdict.update(judge(candidate, baselines, threshold))
    verdict["verdict"] = "REGRESSION" if verdict["regressed"] else "OK"
    return (1 if verdict["regressed"] else 0), verdict


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="like-provenance bench regression gate")
    ap.add_argument("files", nargs="+",
                    help="bench history files; the LAST is the candidate "
                         "unless --fresh is given")
    ap.add_argument("--fresh", default=None,
                    help="the candidate run (history is then every "
                         "positional file)")
    ap.add_argument("--threshold", type=float, default=None,
                    help="relative drop that fails the gate (default: 0.15 "
                         "tpu / 0.40 cpu; widened by the history's own "
                         "spread either way)")
    ap.add_argument("--require-attrib", action="store_true",
                    help="assert the round-6 schema on the candidate: "
                         "the attrib key present (attrib null "
                         "only with an attrib_reason) — the CI leg sets "
                         "this")
    args = ap.parse_args(argv)
    rc, verdict = gate(args.files, args.fresh, args.threshold,
                       args.require_attrib)
    print(json.dumps(verdict, indent=1))
    return rc


if __name__ == "__main__":
    sys.exit(main())
