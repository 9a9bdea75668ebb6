#!/usr/bin/env python3
"""Measure the accuracy cost of the UBODT delta bound (VERDICT r04 next #4).

Meili routes between candidates on-line, up to
``max_route_distance_factor * (gc + search_radius)`` — about 10.25 km for a
pair near the 2000 m breakage default (/root/reference/Dockerfile:42-48).
This framework precomputes routes into a delta-bounded table instead; any
pair whose true route exceeds ``ubodt_delta`` hard-misses and becomes a
transition break.  Dense 5 s sampling never stresses that bound; sparse
sampling (30-60 s gaps, 300-900 m hops) can.

This tool sweeps delta over {1.5, 3, 6 km} x {dense 5 s, sparse 45 s}
cohorts on the bench's realistic-city scenario and reports, per cell:
segment agreement vs synthesized ground truth, the probe miss rates
(ops/diagnostics.ubodt_probe_stats), and the table build cost.  Output:
one JSON to stdout; save it under docs/measurements/ and summarise in
docs/ubodt-delta.md.

Runs on the CPU jax backend by default (the bound is a table property, not
a device property).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from reporter_tpu.utils.jaxenv import ensure_platform  # noqa: E402


def main() -> int:
    ensure_platform(os.environ.get("JAX_PLATFORMS") or "cpu")
    import numpy as np
    import jax
    import jax.numpy as jnp

    from reporter_tpu.matching import MatcherConfig
    from reporter_tpu.ops.diagnostics import ubodt_probe_stats
    from reporter_tpu.ops.viterbi import (
        MatchParams, match_batch_compact_packed, pack_inputs, unpack_compact,
    )
    from reporter_tpu.synth import TraceSynthesizer
    from reporter_tpu.synth.generator import cohort_xy, segment_agreement
    from reporter_tpu.synth.osm_city import realistic_city_network
    from reporter_tpu.tiles.arrays import build_graph_arrays
    from reporter_tpu.tiles.ubodt import build_ubodt

    grid = int(os.environ.get("DELTA_GRID", "60"))
    n_traces = int(os.environ.get("DELTA_TRACES", "48"))
    T = int(os.environ.get("DELTA_T", "64"))
    deltas = [float(d) for d in os.environ.get(
        "DELTA_SWEEP", "1500,3000,6000").split(",")]

    t0 = time.time()
    city = realistic_city_network(grid, grid, spacing_m=150.0, seed=3)
    arrays = build_graph_arrays(city, cell_size=100.0)
    sys.stderr.write("city: %d edges (%.1fs)\n"
                     % (arrays.num_edges, time.time() - t0))

    synth = TraceSynthesizer(arrays, seed=11)
    cohorts = {
        "dense_dt5": synth.batch(n_traces, T, dt=5.0, sigma=5.0, max_tries=400),
        "sparse_dt45": synth.batch(n_traces, T, dt=45.0, sigma=5.0, max_tries=400),
    }

    cfg0 = MatcherConfig()
    dg = arrays.to_device()
    out = {"grid": grid, "traces_per_cohort": n_traces, "T": T,
           "search_radius": cfg0.search_radius,
           "max_route_distance_factor": cfg0.max_route_distance_factor,
           "breakage_distance": cfg0.breakage_distance,
           "meili_online_bound_m_at_breakage": cfg0.max_route_distance_factor
           * (cfg0.breakage_distance + cfg0.search_radius),
           "cells": []}

    jit_match = jax.jit(match_batch_compact_packed, static_argnums=(4,))
    jit_stats = jax.jit(ubodt_probe_stats, static_argnums=(4,))

    for delta in deltas:
        t0 = time.time()
        ubodt = build_ubodt(arrays, delta=delta)
        build_s = time.time() - t0
        du = ubodt.to_device()
        cfg = MatcherConfig(ubodt_delta=delta)
        p = MatchParams.from_config(cfg)
        for cname, straces in cohorts.items():
            px, py, tm, valid = cohort_xy(arrays, straces, T)
            xin = jnp.asarray(pack_inputs(px, py, tm, valid))
            edge, _offset, breaks = unpack_compact(
                jit_match(dg, du, xin, p, cfg.beam_k)[0])
            agr = float(np.mean([
                segment_agreement(arrays, edge[i], straces[i])
                for i in range(len(straces))
            ]))
            stats = np.asarray(
                jit_stats(dg, du, xin, p, cfg.beam_k, delta), np.int64)
            pairs = int(stats[0])
            cell = {
                "delta_m": delta,
                "cohort": cname,
                "agreement": round(agr, 4),
                "breaks_per_trace": round(float(np.sum(breaks)) / len(straces), 2),
                "probe_pairs": pairs,
                "miss_frac": round(int(stats[1]) / max(pairs, 1), 5),
                "costly_miss_frac": round(int(stats[2]) / max(pairs, 1), 5),
                "provable_delta_trunc_frac": round(int(stats[3]) / max(pairs, 1), 5),
                "ubodt_rows": int(ubodt.num_rows),
                "table_mb": round(ubodt.packed.nbytes / 1e6, 1),
                "build_s": round(build_s, 1),
            }
            out["cells"].append(cell)
            sys.stderr.write("delta %.0f %s: agreement %.4f, miss %.4f, "
                             "costly-miss %.4f, provable-trunc %.4f "
                             "(%d rows, %.0f MB)\n"
                             % (delta, cname, agr, cell["miss_frac"],
                                cell["costly_miss_frac"],
                                cell["provable_delta_trunc_frac"],
                                ubodt.num_rows, cell["table_mb"]))

    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
