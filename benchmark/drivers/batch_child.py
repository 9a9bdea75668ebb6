"""The one process on the chip for the ``batch`` entry (see batch.py).

    python batch_child.py '<plan json>'

stdin, one JSON line each: {"warm": [dirs]} then {"files": [dirs], "seconds":
s, "trace": [start, length] | null}.  stdout: "@@"-prefixed JSON lines, first
{"device": ...} once built and warm, then the window's record.  The tile rows of the
window's i-th file go under ``<out>/out<i>``.
"""

from __future__ import annotations

import json
import os
import sys
import time


def emit(obj) -> None:
    sys.stdout.write("@@" + json.dumps(obj) + "\n")
    sys.stdout.flush()


def main() -> int:
    plan = json.loads(sys.argv[1])
    sys.path.insert(0, os.getcwd())
    from reporter_tpu.utils.jaxenv import ensure_platform

    ensure_platform()
    import jax

    from reporter_tpu.batch.pipeline import make_matches
    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.obs import metrics as obs
    from reporter_tpu.tiles.network import RoadNetwork

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if device["platform"] != "tpu" and not plan["rehearse"]:
        emit({"device": device})
        return 0
    with open(plan["network"]) as f:
        net = RoadNetwork.from_dict(json.load(f))
    matcher = SegmentMatcher(network=net, config=MatcherConfig.from_dict(plan["matcher"]),
                             backend="jax")
    pl = plan["pipeline"]
    kw = dict(mode=pl["mode"], report_levels=frozenset(pl["report_levels"]),
              transition_levels=frozenset(pl["transition_levels"]),
              quantisation=pl["quantisation"], inactivity=pl["inactivity_s"],
              source=pl["source"], threshold_sec=pl["threshold_sec"],
              microbatch=pl["microbatch"])
    out = plan["out"]
    warm = json.loads(sys.stdin.readline())["warm"]
    for k, d in enumerate(warm):
        make_matches(d, matcher, dest_dir=os.path.join(out, "warm_out%d" % k), **kw)
    emit({"device": device})

    job = json.loads(sys.stdin.readline())
    files, seconds, trace = job["files"], float(job["seconds"]), job["trace"]
    snap0 = obs.REGISTRY.snapshot()
    fails0 = obs.REGISTRY.snapshot().get("reporter_batch_report_failures_total")
    done, traced, trace_dir, t_tr = [], [], None, None
    tracing = False
    t0 = time.monotonic()
    i = 0
    while True:
        el = time.monotonic() - t0
        if trace and not tracing and trace_dir is None and el >= trace[0] * seconds:
            trace_dir = os.path.join(out, "trace")
            # device and host planes, without the Python function tracer,
            # which slows every Python call of the host path it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing, t_tr = True, time.monotonic()
        make_matches(files[i % len(files)], matcher,
                     dest_dir=os.path.join(out, "out%d" % i), **kw)
        t = time.monotonic() - t0
        done.append((i, t))
        if tracing:
            traced.append(i)
            if time.monotonic() - t_tr >= trace[1] * seconds:
                jax.profiler.stop_trace()
                tracing, t_tr = False, time.monotonic() - t_tr
        i += 1
        if t >= seconds:
            break
    if tracing:
        jax.profiler.stop_trace()
        t_tr = time.monotonic() - t_tr
    snap1 = obs.REGISTRY.snapshot()
    fam0 = (fails0 or {}).get("samples", [])
    fam1 = (snap1.get("reporter_batch_report_failures_total") or {}).get("samples", [])
    failed = sum(v for _l, v in fam1) - sum(v for _l, v in fam0)
    ms = devs[0].memory_stats() or {}
    emit({"done": done, "failed": int(failed), "snap0": snap0, "snap1": snap1,
          "memory_peak_bytes": int(ms.get("peak_bytes_in_use", 0)),
          "trace_dir": trace_dir, "traced": traced, "traced_s": t_tr})
    return 0


if __name__ == "__main__":
    sys.exit(main())
