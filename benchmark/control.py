#!/usr/bin/env python3
"""The control of a cell's ``correct``: the reference put in the program's
place, computed in bfloat16 (the precision below the configuration's
float32), must come out not correct.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--seconds S]
        [--files N] [--rehearse]

For each seed it builds the cell's traffic exactly as a run does, draws the
run's sample (the same functions), answers it with the bfloat16 reference
and compares those answers with the float64 reference's by the run's own
comparison.  Prints each seed's numbers beside the cell's limits; exits 0
only when every seed fails one of them.  The benchmark's runs never run
this; it is the upper reading behind each limit (PERF.md).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import common  # noqa: E402
import compare  # noqa: E402
import run as bench_run  # noqa: E402
import traffic  # noqa: E402
from reference import Reference  # noqa: E402


def stream_numbers(cfg, mix, net, roads, seconds, seed, precision):
    serve = common.load_module(os.path.join(HERE, "drivers", "serve.py"), "serve")
    window = traffic.stream_requests(roads, mix, seconds, seed)
    pick = serve.sample(window, list(range(len(window))), mix, seed)
    ref, low = Reference(net, cfg["matcher"]), Reference(net, cfg["matcher"], precision)
    opts = mix["match_options"]
    differ = 0
    for i in pick:
        req = window[i][1]
        args = (req, cfg["threshold_sec"], opts["report_levels"], opts["transition_levels"])
        segs, reports, su = low.report(*args)
        body = {"segment_matcher": {"segments": segs}, "datastore": {"reports": reports}}
        if su is not None:
            body["shape_used"] = su
        differ += not compare.answer_agrees(body, req, ref.report(*args))
    return {"answers_differing": differ / max(1, len(pick))}


def backfill_numbers(cfg, mix, net, roads, n_files, seed, precision):
    batch = common.load_module(os.path.join(HERE, "drivers", "batch.py"), "batch")
    i = batch.pick_file(range(n_files), seed)
    drives = traffic.backfill_files(roads, mix, 1, seed, first=i)[0]
    files = {i: (None, sum(len(d[1]) for d in drives), drives)}
    ref, low = Reference(net, cfg["matcher"]), Reference(net, cfg["matcher"], precision)
    pl = cfg["pipeline"]
    got = {}
    for j in range(len(drives)):
        for path, row in batch.ref_rows(low, pl, batch.window_trace(files, i, j)):
            got.setdefault(path, []).append(",".join(str(v) for v in row))
    want, missing, n_got, extra = batch.compare_file(ref, pl, files, i,
                                                     compare.index_rows(got))
    return {"rows_missing": missing / max(1, want), "rows_extra": extra / max(1, n_got)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--files", type=int, default=None)
    ap.add_argument("--precision", default="bfloat16")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = dict(common.load_json("configs", cell["config"] + ".json"), name=cell["config"])
    mix = traffic.load(cell["traffic"])
    if args.rehearse:
        cfg = bench_run._overlay(cfg, cfg.get("rehearsal", {}))
        mix = bench_run._overlay(mix, mix.get("rehearsal", {}))
    net = common.load_city(common.city_file(cfg))
    roads = traffic.Roads(net)
    all_over = True
    for seed in (int(s) for s in args.seeds.split(",")):
        if cfg["entry"] == "serve":
            nums = stream_numbers(cfg, mix, net, roads,
                                  args.seconds or bench["run_seconds"], seed, args.precision)
        else:
            nums = backfill_numbers(cfg, mix, net, roads, args.files or int(mix["files"]),
                                    seed, args.precision)
        # the control has to fail one of the cell's numbers, not each
        fails = any(v > cfg["limits"][k] for k, v in nums.items())
        all_over &= fails
        common.say(json.dumps({"workload": args.workload, "seed": seed, "control": args.precision,
                               "numbers": nums, "limits": cfg["limits"], "fails": fails}))
    return 0 if all_over else 1


if __name__ == "__main__":
    sys.exit(main())
