#!/usr/bin/env python3
"""The knee sweep of a stream cell: one server life, the cell's mix offered
open loop at each rate in turn, latency and rate printed per step.

    python3 benchmark/knee.py --workload stream-mixed --rates 100,200,300 \
        --step-seconds 15 [--seed N] [--rehearse]

The knee is the highest rate whose p95 stays within 1.5 times the lowest
rate's p95, with no backlog growing through the step (the generator's
lateness and the last answers' latency say so).  A cell's rate is then written into its mix as a
number; the benchmark's runs never search for one.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import common  # noqa: E402
import run as bench_run  # noqa: E402
import traffic  # noqa: E402

serve = common.load_module(os.path.join(HERE, "drivers", "serve.py"), "serve")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="stream-mixed")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--step-seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = dict(common.load_json("configs", cell["config"] + ".json"), name=cell["config"])
    mix = traffic.load(cell["traffic"])
    if args.rehearse:
        cfg = bench_run._overlay(cfg, cfg.get("rehearsal", {}))
    os.makedirs(common.CACHE, exist_ok=True)
    run_dir = tempfile.mkdtemp(prefix="knee_", dir=common.CACHE)
    proc, base, _log, _peak = serve.start_server(cfg, run_dir)
    port = int(base.rsplit(":", 1)[1])
    try:
        roads = traffic.Roads(common.load_city(common.city_file(cfg)))
        serve._wait_healthy(base, proc)
        common.say("device: %s" % json.dumps(serve._get(base, "/statusz").get("device")))
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            m = dict(mix, arrivals={"pattern": "poisson", "rate_rps": rate})
            reqs = traffic.stream_requests(roads, m, args.step_seconds, args.seed + k)
            s0 = serve._get(base, "/statusz")["metrics"]
            res = serve.drive(port, [(serve._bodies(reqs), None)], float(mix["timeout_s"]))[0]
            s1 = serve._get(base, "/statusz")["metrics"]
            lat = sorted(((r[2] - r[0]) * 1e3 if r[2] is not None and r[3] == 200
                          else float("inf")) for r in res)
            late = sorted(r[1] - r[0] for r in res)
            last = [(r[2] - r[0]) * 1e3 for r in res[-max(1, len(res) // 10):]
                    if r[2] is not None]
            pts = sum(len(q["trace"]) for (_d, q), r in zip(reqs, res) if r[3] == 200)
            done = max((r[2] for r in res if r[2] is not None), default=args.step_seconds)
            fill = common.hist_delta(s0, s1, "reporter_microbatch_batch_fill")
            comp = (common.family_total(s1, "reporter_xla_compiles_total")
                    - common.family_total(s0, "reporter_xla_compiles_total"))
            common.say(json.dumps({
                "rate_rps": rate, "requests": len(res), "failed": sum(1 for v in lat if v == float("inf")),
                "p50_ms": round(common.percentile(lat, 0.5), 2),
                "p95_ms": round(common.percentile(lat, 0.95), 2),
                "p99_ms": round(common.percentile(lat, 0.99), 2),
                "last_tenth_mean_ms": round(sum(last) / max(1, len(last)), 2),
                "late_p99_s": round(common.percentile(late, 0.99), 4),
                "points_per_s": round(pts / max(done, args.step_seconds), 1),
                "batch_fill_mean": round(fill[2] / fill[3], 2) if fill and fill[3] else None,
                "compiles": comp,
                "adaptive": serve._get(base, "/statusz").get("adaptive")}))
            time.sleep(2.0)
        rc = serve._stop(proc)
        common.say("server exit code %s" % rc)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        shutil.rmtree(run_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
