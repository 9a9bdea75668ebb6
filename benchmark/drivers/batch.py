"""The ``batch`` entry: the backfill job's phase 2 over seeded shard files.

This process never imports JAX.  It starts one child (``batch_child.py``),
the only process on the chip, which builds the matcher from the
configuration and warms it on two shard files of the same mix while this
process writes the window's shard files.  The child then calls
``reporter_tpu.batch.pipeline.make_matches`` on one shard file after another
(cycling if it runs out) until ``--seconds`` have passed, finishing the file
in flight: the rate is every point matched over the time from the window's
start to that last completion.  One seeded file of those it finished is then
checked, every tile row the program wrote against every row the reference
gives for its windows (drives): rows missing and rows extra.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import common
import compare
import trace_reduce
import traffic
from reference import Reference

CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "batch_child.py")


def _shards(roads, mix, seed, n, first, root, prefix):
    """Write n shard files, each in its own directory; [(dir, points, drives)]."""
    out = []
    for k, drives in enumerate(traffic.backfill_files(roads, mix, n, seed, first)):
        d = os.path.join(root, "%s%04d" % (prefix, k))
        os.makedirs(d)
        pts = traffic.write_shard(os.path.join(d, "%03x" % ((first + k) % 4096)), drives,
                                  int(mix["accuracy_m"]))
        out.append((d, pts, drives))
    return out


def _read(proc) -> dict:
    while True:
        line = proc.stdout.readline()
        if not line:
            common.fail("batch child exited (rc %s)" % proc.wait())
        if line.startswith("@@"):
            return json.loads(line[2:])
        sys.stdout.write(line)


def _reduce(ctx, trace_dir):
    """The traced window, reduced; a CPU rehearsal has no device plane."""
    try:
        return trace_reduce.reduce_dir(trace_dir)
    except trace_reduce.NoDevicePlane:
        if not ctx["args"].rehearse:
            raise
        return None


def run(ctx: dict) -> dict:
    cfg, mix, args = ctx["config"], ctx["mix"], ctx["args"]
    net_path = common.city_file(cfg)
    root = ctx["run_dir"]
    plan = {"network": net_path, "matcher": cfg["matcher"], "pipeline": cfg["pipeline"],
            "rehearse": bool(args.rehearse), "chips": ctx["chips"], "out": root}
    proc = subprocess.Popen([sys.executable, CHILD, json.dumps(plan)], cwd=common.ROOT,
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                            # one hash seed for every run (see serve.start_server)
                            env=dict(os.environ, TMPDIR=root, PYTHONHASHSEED="0"))
    try:
        net = common.load_city(net_path)
        roads = traffic.Roads(net)
        warm = _shards(roads, mix, args.seed ^ 0x5EED, int(mix["warm_files"]), 0, root, "w")
        proc.stdin.write(json.dumps({"warm": [d for d, _p, _x in warm]}) + "\n")
        proc.stdin.flush()
        files = _shards(roads, mix, args.seed, int(mix["files"]), 0, root, "f")
        ready = _read(proc)
        dev = ready["device"]
        if dev["platform"] != "tpu" and not args.rehearse:
            common.fail("JAX finds no TPU (platform %r)" % dev["platform"])
        if dev["count"] < ctx["chips"]:
            common.fail("%d chip(s) visible, cell asks for %d" % (dev["count"], ctx["chips"]))
        setup_s = time.monotonic() - ctx["t_start"]
        proc.stdin.write(json.dumps({
            "files": [d for d, _p, _x in files], "seconds": float(args.seconds),
            "trace": mix["trace_window"] if args.trace else None}) + "\n")
        proc.stdin.flush()
        res = _read(proc)
        proc.stdin.close()
        rc = proc.wait(timeout=120)
        if rc != 0:
            common.fail("batch child exited %s" % rc)
    except BaseException:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        raise
    done = res["done"]  # [(file index, seconds since window start)]
    points = sum(files[i % len(files)][1] for i, _t in done)
    rec = {"kind": "backfill", "attempted": len(done), "failed": res["failed"],
           "setup_s": setup_s, "points": points, "window_s": done[-1][1] if done else 0.0,
           "snap0": res["snap0"], "snap1": res["snap1"], "peaks": ctx["peaks"],
           "device": {"platform": dev["platform"], "kind": dev["kind"],
                      "count": ctx["chips"], "memory_peak_bytes": res["memory_peak_bytes"]}}
    common.say("window: %d shard files, %d points in %.3f s (%d distinct files)"
               % (len(done), points, rec["window_s"], len(files)))
    if args.trace:
        rec["trace"] = _reduce(ctx, res["trace_dir"]) if res.get("trace_dir") else None
        tr = res.get("traced") or []
        rec["traced_points"] = sum(files[i % len(files)][1] for i in tr)
        rec["traced_files_s"] = res.get("traced_s")
    rec["checks"] = _check(ctx, net, files, done, res["failed"])
    return rec


def _tiles(dest: str) -> dict:
    out = {}
    for r, _d, fs in os.walk(dest):
        for f in fs:
            p = os.path.join(r, f)
            with open(p) as fh:
                out[os.path.relpath(p, dest)] = fh.read().splitlines()
    return out


def pick_file(finished, seed) -> int:
    """The seeded file whose every tile row is checked.  Every file holds
    the same multiset of drive lengths, so each holds the longest window."""
    finished = sorted(finished)
    return finished[int(traffic.rng_for(seed, 4).integers(len(finished)))]


def window_trace(files, i, j) -> dict:
    uuid, t, lat, lon = files[i][2][j]
    return {"uuid": uuid, "trace": [{"lat": a, "lon": o, "time": int(s)} for a, o, s
                                    in zip(lat.tolist(), lon.tolist(), t.tolist())]}


def ref_rows(ref, pl, trace):
    return ref.tile_rows(trace, pl["threshold_sec"], pl["report_levels"],
                         pl["transition_levels"], pl["quantisation"], pl["source"],
                         pl["mode"].upper())


def compare_file(ref, pl, files, i, got: dict):
    """(reference rows, missing, program rows, extra) of file ``i``: every
    reference row of its windows looked up in ``got`` (``compare.index_rows``
    of the program's tiles for that file, consumed as rows match); what is
    left of ``got`` afterwards is extra."""
    n_got = sum(len(v) for v in got.values())
    want, missing = 0, 0
    for j in range(len(files[i][2])):
        trace = window_trace(files, i, j)
        rows = ref_rows(ref, pl, trace)
        want += len(rows)
        missing += compare.rows_missing(got, rows, trace)
    return want, missing, n_got, sum(len(v) for v in got.values())


def _check(ctx, net, files, done, failures) -> dict:
    """Every tile row of one seeded finished file against the reference's."""
    cfg, args = ctx["config"], ctx["args"]
    finished = {i for i, _t in done if i < len(files)}
    i = pick_file(finished, args.seed)
    got = compare.index_rows(_tiles(os.path.join(ctx["run_dir"], "out%d" % i)))
    t0 = time.monotonic()
    want, missing, n_got, extra = compare_file(Reference(net, cfg["matcher"]),
                                               cfg["pipeline"], files, i, got)
    common.say("reference: file %d, %d windows, %d points, %d tile rows (program %d) in %.1f s"
               % (i, len(files[i][2]), files[i][1], want, n_got, time.monotonic() - t0))
    lim = cfg["limits"]
    s_miss, s_extra = missing / max(1, want), extra / max(1, n_got)
    return {"rows_missing": {"value": s_miss, "limit": lim["rows_missing"],
                             "ok": s_miss <= lim["rows_missing"]},
            "rows_extra": {"value": s_extra, "limit": lim["rows_extra"],
                           "ok": s_extra <= lim["rows_extra"]},
            # the guarantee: no window dropped (reporter_batch_report_failures_total)
            "report_failures": {"value": failures, "limit": 0, "ok": failures == 0},
            "sample_rows": {"value": want, "limit": 1, "ok": want >= 1}}
