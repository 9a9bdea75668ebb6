"""What every driver shares: paths, the cached city, counters, percentiles."""

from __future__ import annotations

import hashlib
import importlib.util
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".cache")


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """A module from a file named after a benchmark name (dots allowed)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def city_file(config: dict) -> str:
    """The configuration's city as a "file" network, built once per checkout
    and keyed by the city's parameters."""
    import city

    key = hashlib.sha256(json.dumps(config["city"], sort_keys=True).encode()
                         ).hexdigest()[:16]
    path = os.path.join(CACHE, "city", "%s-%s.json" % (config["name"], key))
    if not os.path.exists(path):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = "%s.%d.tmp" % (path, os.getpid())
        with open(tmp, "w") as f:
            json.dump(city.network(config["city"]), f)
        os.replace(tmp, path)
    return path


def load_city(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# -- metrics snapshots (reporter_tpu.obs.metrics JSON form) --------------------


def family_total(snap: dict, name: str, labels=None) -> float:
    """Sum of a counter/gauge family's samples, optionally only those whose
    first label is in ``labels``."""
    fam = (snap or {}).get(name) or {}
    out = 0.0
    for lv, v in fam.get("samples", []):
        if labels is not None and (not lv or lv[0] not in labels):
            continue
        if isinstance(v, (int, float)):
            out += float(v)
    return out


def hist_delta(s0: dict, s1: dict, name: str):
    """(bounds, counts, sum, count) of a histogram family over a window,
    summed over label sets; None when the family is absent."""
    def merged(snap):
        fam = (snap or {}).get(name)
        if not fam:
            return None
        bounds, counts, tot, n = None, None, 0.0, 0
        for _lv, s in fam.get("samples", []):
            if bounds is None:
                bounds, counts = list(s["buckets"]), [0] * len(s["counts"])
            counts = [a + b for a, b in zip(counts, s["counts"])]
            tot += s["sum"]
            n += s["count"]
        return bounds, counts, tot, n
    a, b = merged(s0), merged(s1)
    if b is None:
        return None
    if a is None:
        return b
    return b[0], [y - x for x, y in zip(a[1], b[1])], b[2] - a[2], b[3] - a[3]


def hist_quantile(bounds, counts, q: float):
    """Upper bucket bound holding the q-quantile (bucket resolution)."""
    n = sum(counts)
    if n == 0:
        return None
    rank = math.ceil(q * n)
    acc = 0
    for i, c in enumerate(counts):
        acc += c
        if acc >= rank:
            return bounds[i] if i < len(bounds) else math.inf
    return math.inf


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 1]); inf stays inf."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(q * len(v)) - 1)]


def fail(msg: str) -> "None":
    sys.stderr.write("benchmark: %s\n" % msg)
    raise SystemExit(1)


# -- readers shared by metrics of several cell families ------------------------


def idle_share(rec):
    """1 - (union of device-operation intervals / traced window)."""
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]


def window_compiles(rec):
    """XLA executables built inside the measured window."""
    name = "reporter_xla_compiles_total"
    if name not in rec["snap1"]:
        return None
    return family_total(rec["snap1"], name) - family_total(rec["snap0"], name)
