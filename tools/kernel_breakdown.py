"""Named-stage device-time breakdown of the match kernel on the visible
device — a thin CLI over ``reporter_tpu.obs.attrib``.

Historically this tool timed hand-built stage-subset programs
(candidates-only / candidates+transitions / full) and attributed kernel
time to the deltas; that duplicated attribution logic is retired — the
kernels now self-report through their ``jax.named_scope`` labels, and
this tool just captures N reps of the REAL dispatched compact program
under a profiler window and prints the parsed per-stage table (the same
parse /debug/attrib and bench.py's ``attrib`` block serve).

WARNING: stage ratios measured on the CPU backend DO NOT transfer to the
chip (round 4 measured "transitions ~95%" here; the on-chip traces said
candidates ~57% — docs/onchip-attribution.md).  The table is labelled
with the platform it measured; only platform "tpu" rows are chip claims.

Run:  python tools/kernel_breakdown.py [--platform tpu|cpu] [--scenario osm]
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--scenario", default=os.environ.get("BENCH_SCENARIO", "osm"))
    ap.add_argument("--grid", type=int, default=int(os.environ.get("BENCH_GRID", "120")))
    ap.add_argument("--delta", type=float, default=float(os.environ.get("BENCH_DELTA", "3000")))
    ap.add_argument("--b", type=int, default=16)
    ap.add_argument("--t", type=int, default=1024)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--kernel", default="scan", choices=("scan", "assoc"))
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from reporter_tpu.utils.jaxenv import ensure_platform

    ensure_platform(args.platform or os.environ.get("JAX_PLATFORMS") or "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.obs import attrib
    from reporter_tpu.ops.viterbi import pack_inputs
    from reporter_tpu.synth import TraceSynthesizer
    from reporter_tpu.synth.generator import cohort_xy
    from reporter_tpu.synth.osm_city import realistic_city_network
    from reporter_tpu.tiles.arrays import build_graph_arrays
    from reporter_tpu.tiles.network import grid_city
    from reporter_tpu.tiles.ubodt import build_ubodt

    print("platform:", jax.devices()[0], flush=True)
    if jax.devices()[0].platform != "tpu":
        print("WARNING: CPU-backend stage ratios do not transfer to the chip "
              "(docs/onchip-attribution.md); for device claims run on the "
              "real chip (--platform tpu) or analyse an on-chip capture "
              "with trace_analyze.py", flush=True)
    cfg = MatcherConfig(viterbi_kernel=args.kernel)
    t0 = time.time()
    if args.scenario == "grid":
        city = grid_city(rows=args.grid, cols=args.grid, spacing_m=150.0)
    else:
        city = realistic_city_network(rows=args.grid, cols=args.grid)
    arrays = build_graph_arrays(city, cell_size=100.0)
    ubodt = build_ubodt(arrays, delta=args.delta)
    print("scenario %s: %d edges, ubodt %d rows (%.1fs)"
          % (args.scenario, arrays.num_edges, ubodt.num_rows, time.time() - t0), flush=True)

    matcher = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg)
    synth = TraceSynthesizer(arrays, seed=7)
    B, T = args.b, args.t
    # same packing as the bench's cohorts: identical inputs, comparable times
    px, py, tm, valid = cohort_xy(
        arrays, synth.batch(B, T, dt=5.0, sigma=5.0, max_tries=400), T)
    px, py, tm, valid = SegmentMatcher._pad_batch(px, py, tm, valid)
    xin = jnp.asarray(pack_inputs(px, py, tm, valid))
    fn = matcher._get_jit("compact", args.kernel)
    cargs = (matcher._dg, matcher._du, xin, matcher._params, cfg.beam_k)

    t0 = time.time()
    np.asarray(fn(*cargs)[0])  # compile + warm; [1] = probe-path counts
    compile_s = time.time() - t0
    t0 = time.time()
    res = attrib.capture(lambda: np.asarray(fn(*cargs)[0]), reps=args.reps,
                         programs=[(fn, cargs)])
    wall = time.time() - t0
    total = res["device_total_ms"]
    print("full kernel  %8.2f ms/rep device  (%d reps in %.1fs wall; "
          "compile %.1fs; %.0f pts/s)"
          % (total / args.reps, args.reps, wall, compile_s,
             B * T * args.reps / max(wall, 1e-9)), flush=True)
    for name, ms in res["stages_ms"].items():
        print("%-18s %8.2f ms  %5.1f%%" % (name, ms, 100.0 * ms / max(total, 1e-9)),
              flush=True)
    named = {k: v for k, v in res["stages_ms"].items()
             if k != attrib.UNATTRIBUTED}
    top = sorted(named.items(), key=lambda kv: -kv[1])[:3]
    print("attribution (%s): %s" % (
        res["platform"],
        "  ".join("%s %.0f%%" % (k, 100.0 * v / max(total, 1e-9))
                  for k, v in top)), flush=True)


if __name__ == "__main__":
    main()
