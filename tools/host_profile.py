#!/usr/bin/env python3
"""Attribute the host-side cost of a fleet match_many: packing, device
kernel, C++ association walk, and Python record materialisation.

The round-4 bench measured device_util 0.45 on chip -- the device idles
while the host packs/associates.  This tool sizes each host stage on the
bench fleet so the overlap/optimisation work targets the real bottleneck
instead of the assumed one (VERDICT r04 next #2).

Runs on the CPU jax backend (association cost is backend-independent; the
device sections are labelled so TPU numbers can be substituted).
"""

import cProfile
import io
import os
import pstats
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main():
    os.environ.setdefault("BENCH_GRID", "60")  # smaller city: fast build
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from reporter_tpu.utils.jaxenv import ensure_platform

    ensure_platform(os.environ.get("JAX_PLATFORMS") or "cpu")
    import numpy as np

    from bench import build_scenario
    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.matching.assoc_native import associate_segments_batch

    scenario, arrays, ubodt, cohorts = build_scenario()
    cfg = MatcherConfig()
    m = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg)
    traces = [s.trace for _, _, ss in cohorts for s in ss]
    n_pts = sum(len(t["trace"]) for t in traces)
    print("fleet: %d traces, %d points" % (len(traces), n_pts))

    # warm compile
    m.match_many(traces)

    # 1) e2e -- full fleet, and bucketed-only (the stage timings below skip
    # the long/carry path, so only the bucketed number is stage-comparable)
    t0 = time.time()
    m.match_many(traces)
    e2e = time.time() - t0
    print("e2e (cpu-jax): %.2fs  (%.0f pts/s)" % (e2e, n_pts / e2e))
    max_b = m.cfg.length_buckets[-1]
    bucketed_traces = [t for t in traces if len(t["trace"]) <= max_b]
    t0 = time.time()
    m.match_many(bucketed_traces)
    e2e_b = time.time() - t0
    print("e2e bucketed-only (%d traces): %.2fs" % (len(bucketed_traces), e2e_b))

    # 2) fill_rows + pack only (replicate match_many's bucketing)
    buckets = {}
    long_idxs = []
    max_bucket = cfg.length_buckets[-1]
    for i, tr in enumerate(traces):
        n = len(tr["trace"])
        if n > max_bucket:
            long_idxs.append(i)
        else:
            buckets.setdefault(m._bucket_len(n), []).append(i)
    t0 = time.time()
    packed = []
    for blen, idxs in sorted(buckets.items()):
        cap = m._device_cap(blen)
        for i in range(0, len(idxs), cap):
            chunk = idxs[i : i + cap]
            px, py, tm, valid, times = m._fill_rows(traces, chunk, blen)
            packed.append((chunk, m._pad_batch(px, py, tm, valid), times))
    t_fill = time.time() - t0
    print("fill_rows+pad (bucketed %d traces): %.3fs" % (len(traces) - len(long_idxs), t_fill))

    # 3) device compute (cpu backend -- for reference only)
    t0 = time.time()
    handles = [(chunk, m._dispatch_batch(*args), times) for chunk, args, times in packed]
    outs = [(chunk, m._collect_batch(h), times) for chunk, h, times in handles]
    t_dev = time.time() - t0
    print("device dispatch+collect (cpu backend, not TPU-representative): %.3fs" % t_dev)

    # 4) association: C++ walk + record build, timed together then split
    def assoc_all(reps=3):
        for _ in range(reps):
            for chunk, (edge, offset, breaks), times in outs:
                B = len(chunk)
                T = edge.shape[1]
                abs_tm = np.zeros((B, T), np.float64)
                npts = np.zeros(B, np.int32)
                for row in range(B):
                    npts[row] = len(times[row])
                    abs_tm[row, : npts[row]] = times[row]
                associate_segments_batch(
                    arrays, ubodt, edge[:B], offset[:B], breaks[:B], abs_tm, npts,
                    queue_thresh_mps=cfg.queue_speed_threshold_kph / 3.6,
                    back_tol=2.0 * cfg.sigma_z + 5.0)

    t0 = time.time()
    assoc_all(reps=3)
    t_assoc = (time.time() - t0) / 3
    print("association total (bucketed): %.3fs per fleet" % t_assoc)

    # profile the association to split C++ call vs python record build
    pr = cProfile.Profile()
    pr.enable()
    assoc_all(reps=3)
    pr.disable()
    s = io.StringIO()
    ps = pstats.Stats(pr, stream=s).sort_stats("cumulative")
    ps.print_stats(18)
    print(s.getvalue())

    # record count for context
    res = m.match_many(traces)
    n_rec = sum(len(r["segments"]) for r in res)
    print("records: %d (%.1f per trace)" % (n_rec, n_rec / len(traces)))


if __name__ == "__main__":
    main()
