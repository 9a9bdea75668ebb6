#!/usr/bin/env python3
"""Benchmark: GPS traces map-matched per second per chip.

Prints exactly ONE JSON line to stdout:
  {"metric": "traces_matched_per_sec_per_chip", "value": N,
   "unit": "traces/s", "vs_baseline": R, "platform": ..., ...}

One process drives the visible device.  It must be a TPU: on any other
platform the bench exits non-zero without printing a number, unless the
caller set ``JAX_PLATFORMS=cpu`` themselves (a CPU rehearsal, labelled
``"platform": "cpu"`` in the line).

The CPU-oracle baseline (the single-process Meili-style engine,
reporter_service.py:52,240; BASELINE.json config 1) runs for
>= BENCH_BASELINE_SECS (default 60, VERDICT r02 weak #3) on the same
scenario in a child process started with ``JAX_PLATFORMS=cpu`` before
this process touches JAX, so only one process ever wants the chip.  The
device sections start once the baseline's timed window is over, so the
two never share cores.

Device sections: end-to-end throughput, p50/p95 single-trace latency,
per-cohort kernel-only throughput and agreement, device utilisation,
stage attribution, the session / host-pipeline / mesh legs.

Scenario: metro-scale realistic city (OSM ingestion path; ~57k edges) --
UBODT in the millions of rows (native builder, full delta), mixed
64/256/1024-pt cohorts;
the 1024-pt cohort exceeds the largest length bucket and exercises
carried-state streaming.

vs_baseline semantics (ADVICE r02): the headline "vs_baseline" is a
POINTS/S ratio (work-normalised; the cpu subset's length mix differs
slightly from the fleet's); "vs_baseline_traces" is the raw traces/s ratio;
"vs_baseline_basis" names the basis.  p50/p95 latency is measured on the
64-pt short cohort ("latency_cohort").
"""

import json
import os
import subprocess
import sys
import time


def _stderr(msg: str) -> None:
    sys.stderr.write("bench: %s\n" % msg)
    sys.stderr.flush()


# ---------------------------------------------------------------------------
# shared scenario


def build_scenario():
    """Metro-scale city + UBODT + mixed trace cohorts.  numpy + native C++
    only -- safe to run while the jax backend is still initialising.

    BENCH_SCENARIO=osm (default): realistic OSM-extract city ingested
    through the PBF codec path (synth/osm_city.py — jittered curved grid,
    road-class hierarchy, one-ways, river + sparse bridges, orbital
    motorway with internal ramps), so candidates and UBODT see real-map
    topology rather than a uniform lattice (VERDICT r03 next #7).
    BENCH_SCENARIO=grid keeps the round-3 uniform lattice for comparison."""
    from reporter_tpu.synth import TraceSynthesizer
    from reporter_tpu.tiles.arrays import build_graph_arrays
    from reporter_tpu.tiles.network import grid_city
    from reporter_tpu.tiles.ubodt import build_ubodt

    scenario = os.environ.get("BENCH_SCENARIO", "osm")
    rows = cols = int(os.environ.get("BENCH_GRID", "120"))
    delta = float(os.environ.get("BENCH_DELTA", "3000"))
    # UBODT memory layout (docs/performance.md): built here with the same
    # env the matcher resolves, so the table is packed once, not repacked
    # at matcher construction
    layout = (os.environ.get("REPORTER_UBODT_LAYOUT", "").strip().lower()
              or "cuckoo")
    t0 = time.time()
    if scenario == "osm":
        from reporter_tpu.synth.osm_city import realistic_city_network

        city = realistic_city_network(rows, cols, spacing_m=150.0, seed=3)
    else:
        city = grid_city(rows=rows, cols=cols, spacing_m=150.0)
    arrays = build_graph_arrays(city, cell_size=100.0)
    t_graph = time.time() - t0
    t0 = time.time()
    ubodt = build_ubodt(arrays, delta=delta, layout=layout)
    _stderr(
        "scenario %s: graph %d nodes / %d edges (%.1fs); ubodt %d rows, "
        "table %.0f MB (%s), load %.2f, max kick chain %d (%.1fs native "
        "build)"
        % (scenario, arrays.num_nodes, arrays.num_edges, t_graph,
           ubodt.num_rows, ubodt.packed.nbytes / 1e6, ubodt.layout,
           ubodt.num_rows / max(
               ubodt.packed.shape[0] * ubodt.bucket_entries, 1),
           ubodt.max_kicks,
           time.time() - t0)
    )

    # fleet sizing: the CPU baseline amortises its fixed costs over a 60 s
    # continuous window, so the device fleet must be big enough to amortise
    # per-dispatch sync costs too or the comparison under-reports the chip
    # (steady-state throughput is the metric, BASELINE.md).  Counts sit ON
    # matcher._BATCH_LADDER rungs so the e2e dispatch pads nothing and the
    # kernel-only section times exactly the programs e2e runs.
    n_short = int(os.environ.get("BENCH_TRACES", "512"))
    n_med = int(os.environ.get("BENCH_TRACES_MED", "128"))
    n_long = int(os.environ.get("BENCH_TRACES_LONG", "16"))
    cohorts = []
    synth = TraceSynthesizer(arrays, seed=7)
    t0 = time.time()
    cohorts.append(("short", 64, synth.batch(n_short, 64, dt=5.0, sigma=5.0)))
    cohorts.append(("med", 256, synth.batch(n_med, 256, dt=5.0, sigma=5.0)))
    # long drives chain many route legs; raise the leg cap so they fit even
    # on small override grids
    cohorts.append(("long", 1024, synth.batch(n_long, 1024, dt=5.0, sigma=5.0, max_tries=400)))
    n_pts = sum(n * len(s) for _, n, s in cohorts)
    _stderr(
        "synthesized %d traces (%d pts, %.1fs)"
        % (sum(len(s) for _, _, s in cohorts), n_pts, time.time() - t0)
    )
    return scenario, arrays, ubodt, cohorts


def _cohort_xy(arrays, straces, T):
    from reporter_tpu.synth.generator import cohort_xy

    return cohort_xy(arrays, straces, T)


# ---------------------------------------------------------------------------
# device sections


def _cost_block(pps: float, chips: int) -> dict:
    """The bench line's cost block (docs/economics.md): the configured
    chip price (REPORTER_COST_PER_CHIP_HOUR > config > default) folded
    to $-per-million-matched-points at this run's sustained e2e rate.
    "assumed" provenance — a bench prices its own steady state; the
    serving ledger's measured spend rides loadgen artifacts instead."""
    from reporter_tpu.obs.economics import resolve_price

    price = resolve_price()
    chips = max(1, int(chips))
    usd_per_m = (price / 3600.0 * chips / pps * 1e6) if pps > 0 else None
    return {
        "source": "assumed",
        "price_per_chip_hour": price,
        "chips": chips,
        "usd_per_million_points": (round(usd_per_m, 6)
                                   if usd_per_m is not None else None),
    }


def _memory_block(matcher):
    """Device/host memory accounting for the artifact (same families as
    the serving /statusz "memory" block)."""
    try:
        from reporter_tpu.obs.economics import memory_summary

        return memory_summary(matcher) or None
    except Exception as e:  # noqa: BLE001 - accounting must not sink a bench
        _stderr("memory accounting failed: %s" % (e,))
        return None


def _mesh_measure(arrays, ubodt, traces, n_traces, n_points_total,
                  primary_kernel, mesh_devs, reps):
    """The timed mesh pass shared by the in-process accelerator path and
    the BENCH_ROLE=mesh CPU worker: the same mixed fleet dispatched
    synchronously (one execution wave at a time — the dispatch pattern
    the mesh differential suites pin as rendezvous-safe) on a dp mesh
    over mesh_devs devices."""
    import time as _time

    from reporter_tpu.matching import MatcherConfig, SegmentMatcher

    mcfg = MatcherConfig(viterbi_kernel=primary_kernel, devices=mesh_devs)
    mm = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=mcfg)
    mm.match_many(traces)  # compile + shard-upload round
    t0 = _time.time()
    for _ in range(reps):
        mm.match_many(traces)
    mesh_wall = _time.time() - t0
    mtps = n_traces * reps / mesh_wall
    mpps = n_points_total * reps / mesh_wall
    cap = mm.capacity_summary()
    return {
        "devices": mesh_devs,
        "mesh": cap.get("mesh"),
        "traces_per_sec": round(mtps, 2),
        "points_per_sec": round(mpps, 1),
        "traces_per_sec_per_device": round(mtps / mesh_devs, 2),
        "capacity": {
            "max_device_batch": cap.get("max_device_batch"),
            "max_device_points": cap.get("max_device_points"),
        },
    }


def run_mesh() -> int:
    """BENCH_ROLE=mesh: the mesh scaling leg in a FRESH process (a CPU
    rehearsal re-execs this on the CPU platform so a wedged
    virtual-mesh rendezvous is killable from outside).  Rebuilds the
    same scenario from the inherited env and prints the partial mesh
    block as the one JSON line; the parent grafts the single-device
    comparison fields on."""
    from reporter_tpu.utils.jaxenv import ensure_platform

    ensure_platform()
    scenario, arrays, ubodt, cohorts = build_scenario()
    primary_kernel = (os.environ.get("BENCH_KERNEL", "").strip().lower()
                      or "scan")
    mesh_devs = int(os.environ["BENCH_MESH_DEVICES_RESOLVED"])
    reps = int(os.environ.get("BENCH_REPS", "10"))
    traces = [s.trace for _, _, ss in cohorts for s in ss]
    n_points_total = sum(T * len(ss) for _, T, ss in cohorts)
    block = _mesh_measure(arrays, ubodt, traces, len(traces),
                          n_points_total, primary_kernel, mesh_devs, reps)
    print(json.dumps(block))
    return 0


def run_device(platform: str, scenario, arrays, ubodt, cohorts) -> dict:
    """Every device section on the visible device; returns the device
    half of the bench line."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.synth.generator import segment_agreement

    # --kernel scan|assoc (env BENCH_KERNEL; main() sets it from the flag,
    # and the mesh worker inherits it through the environment): the named
    # kernel drives the e2e/latency sections, and the kernel-only section
    # additionally times BOTH viterbi forwards so one run yields the
    # crossover (docs/performance.md; recorded by BENCH_r06)
    bench_kernel = os.environ.get("BENCH_KERNEL", "").strip().lower()
    if bench_kernel and bench_kernel not in ("scan", "assoc"):
        raise ValueError("BENCH_KERNEL must be scan|assoc, got %r"
                         % bench_kernel)
    primary_kernel = bench_kernel or "scan"

    cfg = MatcherConfig(viterbi_kernel=primary_kernel)
    matcher = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg)
    traces = [s.trace for _, _, ss in cohorts for s in ss]
    n_traces = len(traces)
    n_points_total = sum(T * len(ss) for _, T, ss in cohorts)
    n_short = len(cohorts[0][2])

    def _tree_bytes(tree) -> int:
        return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree) if hasattr(x, "nbytes"))

    hbm_mb = (_tree_bytes(matcher._dg) + _tree_bytes(matcher._du)) / 1e6
    _stderr("device-resident graph+ubodt: %.0f MB" % hbm_mb)

    t0 = time.time()
    # warm only the single-trace latency shape (bucket 64); the fleet
    # pass below compiles every batched shape the bench actually dispatches
    matcher.warmup(lengths=[64])
    matcher.match_many(traces)
    warmup_s = time.time() - t0
    _stderr("warmup/compile %.1fs" % warmup_s)

    # end-to-end throughput, steady-state pipelined: fleet rep N+1 (and up
    # to BENCH_INFLIGHT-1 more) dispatched before rep N's association
    # finishes -- the service MicroBatcher's operating mode (its
    # max_inflight resolves by platform exactly like the default below).
    # Round 4 measured the reps serially, so the device idled through
    # every rep's association + fetch quanta -- device_util 0.45 with a
    # kernel twice as fast as e2e (VERDICT r04 next #2b).
    reps = int(os.environ.get("BENCH_REPS", "10"))
    # in-flight fleet reps: N+1 (and N+2, ...) dispatched before rep N's
    # association finishes, pinning one extra fleet's packed arrays per
    # slot — the serving MicroBatcher's default depth (4 on an
    # accelerator).  On the cpu backend the "device" and the association
    # share host cores, so deep pipelining only adds contention there
    # (measured same-machine: 16.0 tr/s at depth 2 vs 14.7 at depth 4).
    inflight_default = "4" if platform != "cpu" else "2"
    inflight = max(1, int(os.environ.get("BENCH_INFLIGHT", inflight_default)))
    from collections import deque as _deque

    finishes: "_deque" = _deque()
    t0 = time.time()
    for _ in range(reps):
        finishes.append(matcher.match_many_async(traces))
        if len(finishes) >= inflight:
            finishes.popleft()()  # associate rep N-k under rep N's compute
    while finishes:
        finishes.popleft()()
    e2e_wall = time.time() - t0
    tps = n_traces * reps / e2e_wall
    pps = n_points_total * reps / e2e_wall

    # p50/p95 per-trace latency at the streaming operating point (~64-pt
    # window, BatchingProcessor-style flush) -- short cohort only, named in
    # the JSON (ADVICE r02)
    lat_reps = int(os.environ.get("BENCH_LAT_REPS", "40"))
    matcher.match_many([traces[0]])
    lats = []
    for i in range(lat_reps):
        t0 = time.time()
        matcher.match_many([traces[i % n_short]])
        lats.append(time.time() - t0)
    p50_ms = float(np.percentile(np.asarray(lats), 50) * 1000.0)
    p95_ms = float(np.percentile(np.asarray(lats), 95) * 1000.0)

    # dispatch/sync floor: wall time of an empty jitted program including
    # the host round-trip — reported so p50 can be read as floor + kernel
    # + association.
    _noop = jax.jit(lambda a: a + 1.0)
    _na = jnp.zeros((8,), jnp.float32)
    np.asarray(_noop(_na))
    t0 = time.time()
    for _ in range(10):
        np.asarray(_noop(_na))  # fetch = the sync a real caller pays
    floor_ms = (time.time() - t0) / 10 * 1000.0
    _stderr("per-trace latency p50 %.1f ms / p95 %.1f ms (%d reps, short "
            "cohort; dispatch floor %.1f ms)"
            % (p50_ms, p95_ms, lat_reps, floor_ms))

    # kernel-only per cohort: the exact device programs the matcher
    # dispatches, timed without host association.  Sums to the fleet's
    # device time -> device_util = device_time / e2e wall (association and
    # dispatch overhead are the rest).
    dg, du, params = matcher._dg, matcher._du, matcher._params

    forward_by_cohort = {}

    from reporter_tpu.ops.viterbi import pack_inputs, unpack_compact

    def _compact_args(px, py, tm, valid, cohort=None, kernel=None):
        # mirror SegmentMatcher._dispatch_batch's batch padding so the
        # kernel-only timing measures exactly the shapes/program e2e
        # dispatches even when env overrides pick off-rung cohort sizes.
        # The forward speaks the packed transport ([4,B,T] in, [3,B,T] out;
        # the dispatched program also returns its probe-path step counts,
        # dropped here).
        px, py, tm, valid = SegmentMatcher._pad_batch(px, py, tm, valid)
        kernel = kernel or primary_kernel
        jit = matcher._get_jit("compact", kernel)

        def fn(*a):
            return jit(*a)[0]

        if hasattr(jit, "lower"):
            fn.lower = jit.lower
        if cohort:
            forward_by_cohort[cohort] = kernel
        return fn, (dg, du, jnp.asarray(pack_inputs(px, py, tm, valid)), params)

    # HBM-traffic model for the roofline (VERDICT r03 weak #5): the two
    # dominant gather streams per trace are the UBODT transition probes
    # (max_probes bucket rows per [T-1, K, K] entry: 2 x 512 B cuckoo /
    # 1 x 1 KB wide32) and the 2x2 quadrant candidate sweep (4 cell rows
    # of cap 32-byte records per point).  The accounting lives in
    # obs/attrib.roofline_block, shared with the probe tools; probe dedup
    # lowers the EXECUTED row count (reported as rows_per_rep) below the
    # byte model, so with dedup on the GB/s figure is an upper bound on
    # probe traffic.
    from reporter_tpu.obs import attrib as obs_attrib

    grid_cap = int(arrays.grid_items.shape[1])
    # the device kind's published peak (obs/attrib.HBM_PEAK_GBS); an
    # unknown accelerator kind raises, and a CPU rehearsal has no roofline
    hbm_gbs = (None if platform == "cpu"
               else obs_attrib.hbm_peak_gbs(jax.devices()[0].device_kind))

    def _roofline(T: int, n: int, secs: float):
        if hbm_gbs is None:
            return None
        return obs_attrib.roofline_block(
            n, T, cfg.beam_k, secs,
            bucket_entries=ubodt.bucket_entries, max_probes=ubodt.max_probes,
            grid_cap=grid_cap, hbm_gbs=hbm_gbs,
            dedup=bool(getattr(matcher, "_probe_dedup", False)))

    kernel_secs = 0.0
    kernel_by_cohort = {}
    kernel_secs_by_cohort = {}
    roofline = {}
    cohort_xy = {}
    for name, T, ss in cohorts:
        px, py, tm, valid = _cohort_xy(arrays, ss, T)
        cohort_xy[name] = (px, py, tm, valid)
        if name == "long":
            continue  # long runs through the carry kernel below
        fn, args = _compact_args(px, py, tm, valid, cohort=name)
        np.asarray(fn(*args, cfg.beam_k))
        t0 = time.time()
        for _ in range(reps):
            r = fn(*args, cfg.beam_k)
        # fetch the last result: device work is in-order, so its fetch
        # bounds every rep
        np.asarray(r)
        dt = (time.time() - t0) / reps
        kernel_secs += dt
        kernel_by_cohort[name] = len(ss) / dt
        kernel_secs_by_cohort[name] = round(dt, 4)
        roofline[name] = _roofline(T, len(ss), dt)
    # long cohort: W-window chunks with carried state, exactly the program
    # set SegmentMatcher._dispatch_long dispatches — the hoisted
    # chunk-batched precompute + chain pipeline by default, the legacy
    # fused per-chunk carry program with REPORTER_LONG_PRECOMPUTE=0
    # (docs/performance.md, chunk-batched carry chain)
    name, T, ss = cohorts[2]
    px, py, tm, valid = cohort_xy["long"]
    W = cfg.length_buckets[-1]
    n_chunks = T // W

    # ladder-pad like _dispatch_long so the timed program is the dispatched
    # one even when BENCH_TRACES_LONG picks an off-rung count
    xin_long = pack_inputs(*SegmentMatcher._pad_batch(px, py, tm, valid))

    def _long_pass(collect: bool = False, kernel=None):
        # dispatch every program of the group before fetching anything: the
        # carry chains the chunks on device, so only the final fetch pays
        # the host sync cost.  Sizes come from xin_long, not the enclosing
        # px — later sections rebind px to other cohorts (the profiler
        # section used to crash on exactly that shadowing).
        host_parts, outs, _aux, _probes = matcher._dispatch_long_group(
            xin_long, n_chunks, W, kernel=kernel or primary_kernel)
        if collect:
            # device-side concat -> one fetch (mirrors _fetch_long)
            parts = list(host_parts)
            if outs:
                parts.append(unpack_compact(
                    jnp.concatenate(outs, axis=2) if len(outs) > 1 else outs[0]))
            return np.concatenate([p[0] for p in parts], axis=1)
        return outs[-1]

    np.asarray(_long_pass())
    t0 = time.time()
    for _ in range(reps):
        r = _long_pass()
    np.asarray(r)  # in-order device queue: fetching the last bounds all reps
    dt = (time.time() - t0) / reps
    kernel_secs += dt
    kernel_by_cohort["long"] = len(ss) / dt
    kernel_secs_by_cohort["long"] = round(dt, 4)
    roofline["long"] = _roofline(T, len(ss), dt)

    # named-stage attribution (obs/attrib; BENCH_PROFILE=0 disables): one
    # kernel rep per cohort, each in its OWN jax.profiler window, parsed
    # into the per-(stage, cohort) device-time table — the automated
    # replacement for the hand-run round-4/5 attribution ritual
    # (docs/onchip-attribution.md).  Runs on EVERY platform: a CPU capture
    # resolves stages through the compiled modules' op-name metadata, so
    # the full round-trip works without a chip (stage RATIOS measured on
    # the cpu backend still do not transfer to the chip — the platform
    # label rides the block).  The raw traces stay on disk for
    # tools/trace_analyze.py.
    profile_dir = None
    attrib_block = None
    attrib_reason = None
    if os.environ.get("BENCH_PROFILE", "1") != "0":
        try:
            # under the ignored scratch dir, not the repo root (VERDICT r05
            # weak #5: profiler output was a root-level dropping)
            profile_dir = os.path.abspath(os.environ.get(
                "BENCH_PROFILE_DIR", os.path.join("scratch", "bench_profile")))
            stages_by_cohort = {}
            totals_ms = {}
            plat_seen = None
            for cname, T, ss in cohorts:
                if cname == "long":
                    # the pre/chain programs registered with obs/attrib at
                    # their first dispatch; the capture maps through them
                    run, programs = (lambda: np.asarray(_long_pass())), None
                else:
                    px, py, tm, valid = cohort_xy[cname]
                    fn, args = _compact_args(px, py, tm, valid)
                    cargs = args + (cfg.beam_k,)
                    run = lambda fn=fn, cargs=cargs: np.asarray(fn(*cargs))
                    programs = [(fn, cargs)]
                res = obs_attrib.capture(
                    run, reps=1, out_dir=os.path.join(profile_dir, cname),
                    programs=programs)
                stages_by_cohort[cname] = res["stages_ms"]
                totals_ms[cname] = res["device_total_ms"]
                plat_seen = res["platform"]
            attrib_block = {
                "platform": plat_seen,
                "captured": time.strftime("%Y-%m-%d"),
                "scenario": scenario,
                "edges": int(arrays.num_edges),
                "kernel": primary_kernel,
                "ubodt_layout": ubodt.layout,
                "probe_dedup": bool(getattr(matcher, "_probe_dedup", False)),
                "stages_ms_by_cohort": stages_by_cohort,
                "device_total_ms_by_cohort": totals_ms,
                "roofline": roofline,
                "trace_dir": profile_dir,
            }
            attrib_block["archived"] = obs_attrib.archive(
                attrib_block, plat_seen)
            _stderr("stage attribution captured per cohort under %s "
                    "(archived: %s)" % (profile_dir, attrib_block["archived"]))
        except Exception as e:  # noqa: BLE001 - diagnostics must not sink the bench
            _stderr("attribution capture failed: %s" % (e,))
            attrib_block, profile_dir = None, None
            attrib_reason = "capture failed: %s" % (e,)
    else:
        attrib_reason = "BENCH_PROFILE=0"

    # --kernel comparison: time BOTH viterbi forwards over the same cohorts
    # (same padded shapes, same fetch discipline) so one bench line carries
    # the scan/assoc crossover.
    kernel_compare = None
    if bench_kernel:
        kernel_compare = {}
        for kern in ("scan", "assoc"):
            secs = 0.0
            by_cohort = {}
            for cname, T, ss in cohorts:
                px, py, tm, valid = cohort_xy[cname]
                if cname == "long":
                    np.asarray(_long_pass(kernel=kern))
                    t0 = time.time()
                    for _ in range(reps):
                        r = _long_pass(kernel=kern)
                else:
                    fn, args = _compact_args(px, py, tm, valid, kernel=kern)
                    np.asarray(fn(*args, cfg.beam_k))
                    t0 = time.time()
                    for _ in range(reps):
                        r = fn(*args, cfg.beam_k)
                np.asarray(r)  # in-order queue: the last fetch bounds all reps
                dt = (time.time() - t0) / reps
                secs += dt
                by_cohort[cname] = round(len(ss) / dt, 1)
            kernel_compare[kern] = {
                "traces_per_sec": round(n_traces / secs, 1),
                "points_per_sec": round(n_points_total / secs, 1),
                "by_cohort": by_cohort,
            }
        _stderr("kernel compare: %s" % (kernel_compare,))

    kernel_tps = n_traces / kernel_secs
    kernel_pps = n_points_total / kernel_secs
    device_util = min(1.0, kernel_secs / (e2e_wall / reps))
    forward_by_cohort["long"] = (
        "pre+chain-" if matcher._long_pre else "carry-") + primary_kernel
    _stderr("kernel-only %.1f traces/s / %.0f pts/s; e2e %.1f "
            "traces/s (%.0f pts/s); device util %.2f"
            % (kernel_tps, kernel_pps, tps, pps, device_util))

    # per-cohort dispatch counters accumulated over the whole run (e2e +
    # kernel sections): how many device programs each cohort cost, by kind
    # — for the long cohort this shows the pre/chain split the hoisted
    # carry chain dispatches (docs/bench-schema.md)
    from reporter_tpu.obs import metrics as _obs_metrics

    _snap = _obs_metrics.REGISTRY.snapshot().get(
        "reporter_dispatch_cohort_total", {"samples": []})
    dispatch_by_cohort = {
        "/".join(lv): int(v) for lv, v in _snap["samples"]}

    # accuracy: segment agreement vs ground truth, every cohort (VERDICT r02
    # weak #8) -- matched edges from the same compact/carry programs.
    # Per-trace values are kept so the oracle section below can subset them
    # for an apples-to-apples device-vs-oracle agreement comparison.
    agreement = {}
    agr_per_trace = {}
    for cname, T, ss in cohorts:
        px, py, tm, valid = cohort_xy[cname]
        if cname == "long":
            edge = _long_pass(collect=True)[: len(ss)]
        else:
            fn, args = _compact_args(px, py, tm, valid)
            edge = unpack_compact(fn(*args, cfg.beam_k))[0][: len(ss)]
        agr_per_trace[cname] = [
            segment_agreement(arrays, edge[i], ss[i]) for i in range(len(ss))
        ]
        agreement[cname] = round(float(np.mean(agr_per_trace[cname])), 4)
    agr_mean = float(np.mean(list(agreement.values())))
    _stderr("segment agreement vs truth: %s (mean %.3f)" % (agreement, agr_mean))

    # UBODT coverage: how often the fleet drives into the delta bound
    # (VERDICT r04 next #4).  costly_miss = misses that force a transition
    # break (pair within breakage distance); provable_delta_trunc = the
    # subset whose straight-line distance alone proves the table could not
    # hold the route at this delta.  docs/ubodt-delta.md carries the
    # delta-sweep evidence behind the default.
    ubodt_miss = None
    probe_dedup = None
    try:
        from reporter_tpu.ops.diagnostics import ubodt_probe_stats

        jstats = jax.jit(ubodt_probe_stats, static_argnums=(4,))
        delta_m = float(os.environ.get("BENCH_DELTA", "3000"))
        tot = np.zeros(5, np.int64)
        by_cohort_distinct = {}
        for cname, T, ss in cohorts:
            px, py, tm, valid = cohort_xy[cname]
            xin = jnp.asarray(pack_inputs(px, py, tm, valid))
            st = np.asarray(
                jstats(dg, du, xin, params, cfg.beam_k, delta_m), np.int64)
            tot += st
            by_cohort_distinct[cname] = round(
                int(st[0]) / max(int(st[4]), 1), 2)
        pairs = int(tot[0])
        ubodt_miss = {
            "probe_pairs": pairs,
            "miss_frac": round(int(tot[1]) / max(pairs, 1), 5),
            "costly_miss_frac": round(int(tot[2]) / max(pairs, 1), 5),
            "provable_delta_trunc_frac": round(int(tot[3]) / max(pairs, 1), 5),
            "delta_m": delta_m,
        }
        # in-batch probe redundancy: pairs / distinct per dispatch — the
        # factor the dedup path removes (docs/performance.md memory-system
        # section; the ratio is per-cohort because dedup sorts per
        # dispatch, and summing distinct counts across dispatches would
        # overstate the redundancy)
        probe_dedup = {
            "enabled": bool(getattr(matcher, "_probe_dedup", False)),
            "probe_pairs": pairs,
            "distinct_pairs": int(tot[4]),
            "dedup_ratio_by_cohort": by_cohort_distinct,
        }
        _stderr("ubodt probes: %s  dedup: %s" % (ubodt_miss, probe_dedup))
    except Exception as e:  # noqa: BLE001 - diagnostics must not sink the bench
        _stderr("ubodt probe stats failed: %s" % (e,))

    # device-vs-oracle on real fleet traces (the "at equal OSMLR-segment
    # agreement" clause of the north star, BASELINE.md): diff the
    # wire-format segment sequences the two backends emit over >= 100
    # traces (VERDICT r04 next #3; round 4's 6-trace sample was too thin to
    # carry the clause), and report the oracle's own agreement-vs-truth
    # next to the device's on the SAME subset so "at equal agreement" is
    # shown, not asserted.
    oracle_cmp = None
    try:
        from reporter_tpu.matching import SegmentMatcher as _SM

        n_sub = {"short": int(os.environ.get("BENCH_ORACLE_SHORT", "80")),
                 "med": int(os.environ.get("BENCH_ORACLE_MED", "16")),
                 "long": int(os.environ.get("BENCH_ORACLE_LONG", "4"))}
        subset = []
        for cname, _T, ss in cohorts:
            subset.extend(s.trace for s in ss[: n_sub[cname]])
        cpum = _SM(arrays=arrays, ubodt=ubodt, config=cfg, backend="cpu")
        dev_out = matcher.match_many(subset)
        t0 = time.time()
        cpu_out = cpum.match_many(subset)
        oracle_secs = time.time() - t0
        ids = lambda r: [s.get("segment_id") for s in r["segments"]]
        exact = sum(d == c for d, c in zip(dev_out, cpu_out))
        id_match = sum(ids(d) == ids(c) for d, c in zip(dev_out, cpu_out))

        # oracle-vs-truth per cohort on the subset rows, next to the
        # device-vs-truth values for the same rows
        oracle_agr = {}
        device_agr_sub = {}
        for cname, T, ss in cohorts:
            k = min(n_sub[cname], len(ss))
            if not k:
                continue
            px, py, tm, valid = cohort_xy[cname]
            cedge, _coff, _cbrk = cpum._cpu.run_batch(
                px[:k], py[:k], tm[:k], valid[:k])
            oracle_agr[cname] = round(float(np.mean(
                [segment_agreement(arrays, cedge[i], ss[i]) for i in range(k)]
            )), 4)
            device_agr_sub[cname] = round(
                float(np.mean(agr_per_trace[cname][:k])), 4)
        oracle_cmp = {
            "traces": len(subset),
            "identical_records": exact,
            "identical_segment_ids": id_match,
            "oracle_agreement_by_cohort": oracle_agr,
            "device_agreement_by_cohort": device_agr_sub,
            "oracle_secs": round(oracle_secs, 1),
        }
        _stderr("device vs cpu oracle: %d/%d identical records, %d/%d "
                "identical segment-id sequences (%.1fs oracle); "
                "agreement oracle %s vs device %s"
                % (exact, len(subset), id_match, len(subset), oracle_secs,
                   oracle_agr, device_agr_sub))
    except Exception as e:  # noqa: BLE001 - diagnostics must not sink the bench
        _stderr("oracle comparison failed: %s" % (e,))

    # streaming session leg (kind="session"; ROADMAP item 1's BENCH_r06
    # session entry): a fleet of per-vehicle sessions streamed step by
    # step through the SessionEngine with the device-resident arena on —
    # the serving entrypoint's configuration — so the artifact carries
    # per-point step latency, session throughput, and the arena-residency
    # sizing signal (sessions_resident_per_chip) next to the batch
    # numbers.  The readback counter is sampled across the timed window:
    # a steady-state packed step performs zero per-step host<->device
    # beam transfers, so the delta must stay 0 (docs/performance.md
    # "Device-resident session arenas").  BENCH_SESSION=0 skips the leg.
    session_bench = None
    if os.environ.get("BENCH_SESSION", "1").lower() not in (
            "0", "false", "no", "off"):
        try:
            from reporter_tpu.matching.session import (
                SessionEngine, SessionStore)

            n_veh = int(os.environ.get("BENCH_SESSION_VEHICLES", "256"))
            step_pts = int(os.environ.get("BENCH_SESSION_STEP_POINTS", "4"))
            scfg = MatcherConfig(viterbi_kernel=primary_kernel,
                                 session_arena=True)
            sm = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=scfg)
            store = SessionStore(max_sessions=scfg.max_sessions)
            eng = SessionEngine(sm, store,
                                tail_points=scfg.session_tail_points)
            smo = {"mode": "auto", "report_levels": [0, 1],
                   "transition_levels": [0, 1]}
            # vehicles ride the short cohort's traces, tiled to n_veh
            short = [s.trace for s in cohorts[0][2]]
            fleet = [dict(short[i % len(short)], uuid="bench-sess-%d" % i)
                     for i in range(n_veh)]
            pts = min(len(t["trace"]) for t in fleet)
            rounds = pts // step_pts
            arena = getattr(sm, "session_arena", None)

            def _round(j):
                eng.match_many([
                    {"uuid": t["uuid"],
                     "trace": t["trace"][j * step_pts:(j + 1) * step_pts],
                     "match_options": smo} for t in fleet])

            _round(0)  # compile + upload round, outside the timed window
            rb0 = arena.readbacks if arena is not None else None
            t0 = time.time()
            for j in range(1, rounds):
                _round(j)
            secs = time.time() - t0
            timed = rounds - 1
            devs = max(1, getattr(scfg, "devices", 1))
            tiers = (arena.tier_counts() if arena is not None
                     else {"hot": 0, "cold": 0})
            resident = tiers["hot"] + tiers["cold"]
            session_bench = {
                "vehicles": n_veh,
                "rounds": timed,
                "step_points": step_pts,
                "traces_per_sec": round(n_veh * timed / secs, 1),
                "points_per_sec": round(n_veh * timed * step_pts / secs, 1),
                "step_latency_ms_per_vehicle": round(
                    secs / (n_veh * timed) * 1e3, 4),
                "step_latency_us_per_point": round(
                    secs / (n_veh * timed * step_pts) * 1e6, 2),
                "sessions_resident_per_chip": round(resident / devs, 1),
                "tiers": tiers,
                "steady_readbacks": (arena.readbacks - rb0
                                     if arena is not None else None),
                "arena": arena.summary() if arena is not None else None,
            }
            _stderr("session leg: %s" % (session_bench,))
        except Exception as e:  # noqa: BLE001 - the leg must not sink the bench
            _stderr("session leg failed: %s" % (e,))

    # host pipeline leg (docs/performance.md "The columnar host data
    # plane"; BENCH_HOST_PIPELINE=0 skips): the host side of the serving
    # path, measured in isolation at the canonical [512, 64] shape —
    # (a) packer: legacy per-trace _fill_rows loop vs the columnar
    #     extract+pack and vs pack alone (the binary-wire ingress case,
    #     where the _columns side channel already paid extraction);
    # (b) wire codec: JSON vs binary request encode/decode rates and
    #     body sizes for the same batch;
    # (c) host_frac: a dedicated attribution capture through the REAL
    #     match_many path (the per-cohort captures above run raw jitted
    #     fns, which accrue no host stages), so the artifact's headline
    #     host share covers pack/dispatch/associate of live dispatches.
    host_pipeline = None
    if os.environ.get("BENCH_HOST_PIPELINE", "1").lower() not in (
            "0", "false", "no", "off"):
        try:
            from reporter_tpu.matching import columnar
            from reporter_tpu.serve import wire

            hp_B = int(os.environ.get("BENCH_HOST_PIPELINE_BATCH", "512"))
            hp_T = int(os.environ.get("BENCH_HOST_PIPELINE_POINTS", "64"))
            short = [s.trace for s in cohorts[0][2]]
            hp_traces = []
            for i in range(hp_B):
                t = dict(short[i % len(short)])
                t["uuid"] = "bench-hp-%d" % i
                t["trace"] = t["trace"][:hp_T]
                hp_traces.append(t)
            hp_idxs = list(range(hp_B))
            hp_pts = sum(len(t["trace"]) for t in hp_traces)

            def _hp_time(fn, budget=0.3, min_reps=3):
                fn()  # warm (allocator, caches)
                n, secs = 0, 0.0
                while n < min_reps or secs < budget:
                    t0 = time.time()
                    fn()
                    secs += time.time() - t0
                    n += 1
                return secs / n

            legacy_s = _hp_time(
                lambda: matcher._fill_rows(hp_traces, hp_idxs, hp_T))

            def _extract_pack():
                cols = columnar.extract_columns(hp_traces)
                matcher._fill_rows(hp_traces, hp_idxs, hp_T, cols=cols)

            extract_pack_s = _hp_time(_extract_pack)
            # pack alone: fresh TraceColumns from pre-extracted arrays
            # each rep, so the projection stays INSIDE the timed region
            # (ensure_xy caches) while the dict walk stays outside — the
            # binary-ingress cost, where _columns already paid extraction
            _c0 = columnar.extract_columns(hp_traces)

            def _pack_only():
                cols = columnar.TraceColumns(
                    _c0.lens, _c0.lat, _c0.lon, _c0.time)
                matcher._fill_rows(hp_traces, hp_idxs, hp_T, cols=cols)

            pack_only_s = _hp_time(_pack_only)

            hp_body = {"traces": hp_traces}
            jbytes = json.dumps(hp_body).encode("utf-8")
            wbytes = wire.encode_request(hp_body)
            json_enc_s = _hp_time(
                lambda: json.dumps(hp_body).encode("utf-8"))
            json_dec_s = _hp_time(lambda: json.loads(jbytes))
            wire_enc_s = _hp_time(lambda: wire.encode_request(hp_body))
            wire_dec_s = _hp_time(lambda: wire.decode_request(wbytes))

            host_pipeline = {
                "batch": hp_B,
                "max_points": hp_T,
                "points": hp_pts,
                "pack": {
                    "legacy_ms": round(legacy_s * 1e3, 3),
                    "extract_pack_ms": round(extract_pack_s * 1e3, 3),
                    "pack_only_ms": round(pack_only_s * 1e3, 3),
                    "host_pack_points_per_sec": round(hp_pts / pack_only_s, 1),
                    "extract_pack_points_per_sec": round(
                        hp_pts / extract_pack_s, 1),
                    "legacy_points_per_sec": round(hp_pts / legacy_s, 1),
                    "speedup_pack_only": round(legacy_s / pack_only_s, 2),
                    "speedup_extract_pack": round(
                        legacy_s / extract_pack_s, 2),
                },
                "wire": {
                    "json_bytes": len(jbytes),
                    "binary_bytes": len(wbytes),
                    "bytes_ratio": round(len(wbytes) / len(jbytes), 3),
                    "json_encode_ms": round(json_enc_s * 1e3, 3),
                    "json_decode_ms": round(json_dec_s * 1e3, 3),
                    "binary_encode_ms": round(wire_enc_s * 1e3, 3),
                    "binary_decode_ms": round(wire_dec_s * 1e3, 3),
                    "binary_decode_points_per_sec": round(
                        hp_pts / wire_dec_s, 1),
                    "json_decode_points_per_sec": round(
                        hp_pts / json_dec_s, 1),
                },
            }
            try:
                # programs=[] keeps the CPU op->stage bridge off (we only
                # need the device total + the host window here)
                hres = obs_attrib.capture(
                    lambda: matcher.match_many(hp_traces[:128]),
                    reps=2, store=False, programs=[],
                    out_dir=os.path.join(
                        profile_dir or os.path.join(
                            "scratch", "bench_profile"), "host_pipeline"))
                host_pipeline["host_frac"] = hres.get("host_frac")
                host_pipeline["host_stages_s"] = hres.get("host_stages_s")
            except Exception as e:  # noqa: BLE001
                _stderr("host_frac capture failed: %s" % (e,))
            _stderr("host pipeline leg: %s" % (host_pipeline,))
        except Exception as e:  # noqa: BLE001 - the leg must not sink the bench
            _stderr("host pipeline leg failed: %s" % (e,))

    # mesh scaling leg (docs/performance.md "One logical matcher per
    # pod"; BENCH_MESH=0 skips): the SAME mixed fleet e2e pass on a dp
    # mesh over the local devices — aggregate and per-device rates plus
    # scaling_efficiency = (mesh tps / single tps) / devices.  On a real
    # pod each dp rank is its own chip and efficiency near 1.0 means
    # adding chips raised the replica's capacity linearly; on the CPU
    # backend the "devices" are virtual and SHARE host cores, so
    # efficiency ~1/devices is the healthy reading there (the platform
    # label rides the artifact; docs/bench-schema.md).
    mesh_bench = None
    if os.environ.get("BENCH_MESH", "1").lower() not in (
            "0", "false", "no", "off"):
        try:
            n_local = len(jax.devices())
            mesh_devs = int(os.environ.get("BENCH_MESH_DEVICES",
                                           str(n_local)))
            if mesh_devs >= 2 and mesh_devs <= n_local:
                if platform == "cpu":
                    # fresh subprocess, timeout-bounded: a virtual-mesh
                    # cross-module collective can wedge its rendezvous when
                    # it shares the process with earlier legs' still-in-
                    # flight executions (observed 2026-08-07: AllGather
                    # participants stuck forever after the pipelined e2e
                    # pass) — and a stuck XLA execution thread cannot be
                    # killed from inside the process.  A chip belongs to
                    # one process, so only the CPU path re-execs.
                    rc, mesh_bench = _finish(
                        _spawn("mesh",
                               {"BENCH_MESH_DEVICES_RESOLVED": str(mesh_devs)}),
                        float(os.environ.get("BENCH_MESH_TIMEOUT", "900")))
                    if rc != 0 or not isinstance(mesh_bench, dict):
                        _stderr("mesh worker failed (rc %s)" % (rc,))
                        mesh_bench = None
                else:
                    mesh_bench = _mesh_measure(
                        arrays, ubodt, traces, n_traces, n_points_total,
                        primary_kernel, mesh_devs, reps)
                if mesh_bench is not None:
                    mtps = mesh_bench["traces_per_sec"]
                    mesh_bench["single_device_traces_per_sec"] = round(tps, 2)
                    mesh_bench["scaling_efficiency"] = round(
                        mtps / tps / mesh_devs, 3)
                    _stderr("mesh leg (%d devices): %s"
                            % (mesh_devs, mesh_bench))
            else:
                _stderr("mesh leg skipped: %d local device(s), need >= 2"
                        % n_local)
        except Exception as e:  # noqa: BLE001 - the leg must not sink the bench
            _stderr("mesh leg failed: %s" % (e,))

    return {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "value": round(tps, 2),
        "points_per_sec": round(pps, 1),
        "p50_latency_ms": round(p50_ms, 2),
        "p95_latency_ms": round(p95_ms, 2),
        "dispatch_floor_ms": round(floor_ms, 2),
        "latency_cohort": "short64",
        "e2e_mode": "pipelined_overlap%d" % inflight,
        "viterbi_kernel": primary_kernel,
        "kernel_compare": kernel_compare,
        "forward_by_cohort": forward_by_cohort,
        "kernel_traces_per_sec": round(kernel_tps, 1),
        "kernel_points_per_sec": round(kernel_pps, 1),
        "kernel_by_cohort": {k: round(v, 1) for k, v in kernel_by_cohort.items()},
        "kernel_secs_by_cohort": kernel_secs_by_cohort,
        "dispatch_by_cohort": dispatch_by_cohort,
        "roofline": roofline,
        "attrib": attrib_block,
        "attrib_reason": attrib_reason,
        "profile_dir": profile_dir,
        "device_util": round(device_util, 3),
        "warmup_s": round(warmup_s, 1),
        "agreement": round(agr_mean, 4),
        "ubodt_miss": ubodt_miss,
        "probe_dedup": probe_dedup,
        "oracle_cmp": oracle_cmp,
        "agreement_by_cohort": agreement,
        "device_mb": round(hbm_mb, 1),
        "fleet": {name: len(ss) for name, _, ss in cohorts},
        "scenario": scenario,
        "edges": int(arrays.num_edges),
        "ubodt_rows": int(ubodt.num_rows),
        "ubodt_layout": ubodt.layout,
        "ubodt_load": round(ubodt.num_rows / max(
            ubodt.packed.shape[0] * ubodt.bucket_entries, 1), 3),
        "ubodt_max_probes": ubodt.max_probes,
        "ubodt_max_kicks": int(ubodt.max_kicks),
        "session": session_bench,
        "host_pipeline": host_pipeline,
        "host_frac": (host_pipeline or {}).get("host_frac"),
        "mesh": mesh_bench,
        "sessions_resident_per_chip": (
            session_bench["sessions_resident_per_chip"]
            if session_bench else None),
        "cost": _cost_block(pps, getattr(matcher.cfg, "devices", 1)),
        "memory": _memory_block(matcher),
    }


# ---------------------------------------------------------------------------
# baseline worker


def run_baseline() -> int:
    from reporter_tpu.utils.jaxenv import ensure_platform

    ensure_platform()
    scenario, arrays, ubodt, cohorts = build_scenario()

    from reporter_tpu.matching import MatcherConfig, SegmentMatcher

    cfg = MatcherConfig()
    cpum = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg, backend="cpu")

    # cohort-proportional subset, looped until the time budget is spent --
    # the multiplier the project is judged on must not rest on a sub-second
    # sample (VERDICT r02 weak #3)
    budget = float(os.environ.get("BENCH_BASELINE_SECS", "60"))
    subset = ([s.trace for s in cohorts[0][2][:9]]
              + [s.trace for s in cohorts[1][2][:2]]
              + [s.trace for s in cohorts[2][2][:1]])
    sub_pts = sum(len(t["trace"]) for t in subset)
    cpum.match_many(subset[:1])  # warm lazy paths
    t0 = time.time()
    n_done = 0
    pts_done = 0
    while time.time() - t0 < budget:
        cpum.match_many(subset)
        n_done += len(subset)
        pts_done += sub_pts
    wall = time.time() - t0
    _stderr("cpu baseline %.2f traces/s / %.0f pts/s (%d traces over %.1fs)"
            % (n_done / wall, pts_done / wall, n_done, wall))
    print(json.dumps({
        "cpu_traces_per_sec": round(n_done / wall, 3),
        "cpu_points_per_sec": round(pts_done / wall, 1),
        "baseline_secs": round(wall, 1),
        "baseline_traces": n_done,
    }))
    return 0


# ---------------------------------------------------------------------------
# driver


def _spawn(role: str, env_updates: dict):
    env = dict(os.environ)
    env["BENCH_ROLE"] = role
    env.update(env_updates)
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env, stdout=subprocess.PIPE, stderr=sys.stderr,
    )


def _finish(proc, timeout):
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
    line = (out or b"").decode(errors="replace").strip().splitlines()
    for ln in reversed(line):
        try:
            return proc.returncode, json.loads(ln)
        except (json.JSONDecodeError, ValueError):
            continue
    return proc.returncode, None


def main() -> int:
    # the shared structured-log switch; handlers write to stderr, so the
    # one-JSON-line stdout contract is untouched in every role
    from reporter_tpu.obs import log as obs_log

    obs_log.configure()
    # --kernel scan|assoc: primary viterbi kernel for the e2e sections, and
    # the device sections additionally time both kernels (kernel_compare
    # in the JSON line).  Rides the environment so the mesh worker sees it.
    argv = sys.argv[1:]
    if "--kernel" in argv:
        i = argv.index("--kernel")
        if i + 1 >= len(argv) or argv[i + 1] not in ("scan", "assoc"):
            sys.stderr.write("usage: bench.py [--kernel scan|assoc]\n")
            return 2
        os.environ["BENCH_KERNEL"] = argv[i + 1]
    role = os.environ.get("BENCH_ROLE", "")
    if role == "baseline":
        return run_baseline()
    if role == "mesh":
        return run_mesh()

    cpu_requested = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    # the baseline child starts before this process touches JAX: it is
    # pinned to the CPU, so the chip stays this process's alone
    baseline = _spawn("baseline", {"JAX_PLATFORMS": "cpu"})
    try:
        from reporter_tpu.utils.jaxenv import ensure_platform

        ensure_platform()
        import jax

        devs = jax.devices()
        platform = devs[0].platform
        _stderr("device: %s %r x%d" % (platform, devs[0].device_kind,
                                       len(devs)))
        if platform != "tpu" and not cpu_requested:
            _stderr("FATAL: platform %r is not a TPU (set JAX_PLATFORMS=cpu "
                    "for a CPU rehearsal)" % platform)
            return 3
        scenario, arrays, ubodt, cohorts = build_scenario()
        run_budget = float(os.environ.get("BENCH_RUN_BUDGET", "2400"))
        rc, baseline_json = _finish(baseline, run_budget)
        if not baseline_json:
            _stderr("baseline worker died (rc %s)" % rc)
            baseline_json = {}
        device_json = run_device(platform, scenario, arrays, ubodt, cohorts)
    finally:
        if baseline.poll() is None:
            baseline.kill()
            baseline.wait()

    cpu_pps = baseline_json.get("cpu_points_per_sec") or 0
    cpu_tps = baseline_json.get("cpu_traces_per_sec") or 0
    out = {
        "metric": "traces_matched_per_sec_per_chip",
        "value": device_json.get("value"),
        "unit": "traces/s",
        "vs_baseline": round(device_json.get("points_per_sec", 0) / cpu_pps, 2) if cpu_pps else None,
        "vs_baseline_basis": "points_per_sec",
        "vs_baseline_traces": round(device_json.get("value", 0) / cpu_tps, 2) if cpu_tps else None,
        # device-program-only ratio: what the chip does when the host
        # association overhead is excluded
        "kernel_vs_baseline": round(
            device_json.get("kernel_points_per_sec", 0) / cpu_pps, 2) if cpu_pps else None,
    }
    out.update({k: v for k, v in device_json.items() if k != "value"})
    out.update({k: baseline_json[k] for k in
                ("cpu_traces_per_sec", "cpu_points_per_sec", "baseline_secs") if k in baseline_json})
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
