#!/usr/bin/env python3
"""One benchmark run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration (``benchmark/configs/<config>.json``, whose ``entry`` names the
driver ``benchmark/drivers/<entry>.py``), its traffic mix
(``benchmark/traffic/<traffic>.json``) and one reader per metric
(``benchmark/metrics/<metric>.py``).  The driver sets up, measures for
``--seconds`` and checks the answers against the reference; the readers turn
its record into numbers.  ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a traced run.  The last
stdout line is the JSON result; the numbers compared for ``correct`` end
stderr and the result line.  No TPU, fewer chips than the cell asks for, or
no program in the checkout: exit 1 and no result.

``--rehearse`` (not used by the driver): the configuration's and the mix's
``rehearsal`` blocks shrink the city and the load, and a CPU is accepted;
the result is labelled with the platform it ran on.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import common  # noqa: E402
import traffic  # noqa: E402


def cell_metrics(bench: dict, cell: str):
    """(end-to-end, per-layer) metric entries that this cell reports."""
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return e2e, per


def _overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _overlay(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(common.ROOT, "reporter_tpu")):
        common.fail("no program (reporter_tpu/) in this checkout")
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        common.fail("no workload %r in BENCHMARK.json" % args.workload)
    config = dict(common.load_json("configs", cell["config"] + ".json"), name=cell["config"])
    mix = traffic.load(cell["traffic"])
    if args.rehearse:
        config = _overlay(config, config.get("rehearsal", {}))
        mix = _overlay(mix, mix.get("rehearsal", {}))
    driver = common.load_module(os.path.join(HERE, "drivers", config["entry"] + ".py"),
                                config["entry"])
    os.makedirs(os.path.join(common.CACHE, "runs"), exist_ok=True)
    run_dir = tempfile.mkdtemp(prefix="run_", dir=os.path.join(common.CACHE, "runs"))
    ctx = {"args": args, "t_start": T_START, "run_dir": run_dir, "cell": cell,
           "config": config, "mix": mix, "chips": int(cell["chips"]),
           "peaks": common.load_json("peaks.json")}
    try:
        rec = driver.run(ctx)
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)

    dev = rec["device"]
    if dev["platform"] != "tpu" and not args.rehearse:
        common.fail("JAX finds no TPU (platform %r)" % dev["platform"])
    e2e, per = cell_metrics(bench, cell["name"])
    metrics = {}
    for m in (per if args.trace else e2e):
        reader = common.load_module(os.path.join(HERE, "metrics", m["name"] + ".py"), m["name"])
        v = reader.read(rec)
        if v is None:
            if not args.trace:
                common.fail("end-to-end metric %s has no reading" % m["name"])
            continue
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
              "memory_peak_bytes": dev["memory_peak_bytes"]}
    line = {"correct": all(c["ok"] for c in rec["checks"].values()),
            "attempted": rec["attempted"], "failed": rec["failed"],
            "metrics": metrics, "device": device}
    tr = rec.get("trace")
    if args.trace and tr:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"][:10],
                             "idle_gaps": tr["idle_gaps"][:10]}
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in rec["checks"].items()}
    for k, c in rec["checks"].items():
        sys.stderr.write("check %s %s limit %s%s\n" % (
            k, c["value"], c["limit"], "" if c["ok"] else "  FAILED"))
    sys.stderr.flush()
    print(json.dumps(line, allow_nan=False, default=_finite))
    return 0


def _finite(v):
    raise TypeError("not JSON: %r" % (v,))


if __name__ == "__main__":
    sys.exit(main())
