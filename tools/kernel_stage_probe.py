"""Stage isolation for the compact kernel's UBODT probe cost.

Times `match_batch_compact_packed` at the short-cohort fleet shape
[512, 64] across the memory-system configs:
  full        -- as shipped (cuckoo layout, no dedup)
  noprobe     -- ubodt_lookup stubbed to constants (gathers + select removed)
  noselect    -- gathers kept, select trivialised
  rollsel     -- select's spread-matmul replaced by static lane rolls
  dedup       -- cuckoo + in-batch probe dedup (the REAL dedup path)
  wide32      -- the REAL wide32 single-hash layout (ops/hashtable.py), not
                 a mock: one 1 KB row gather per probe
  wide32_dedup-- both knobs, the round-6 end state

The timed tables are random REAL-SIZED images ([2^20, 128] cuckoo /
[2^20, 256] wide32 int32) so the gather physics (row count, table
footprint) match the bench; results are all-miss garbage, which costs the
same as hits.  A small REAL table additionally feeds the probe-stats
program so the reported ``probe_pairs``/``distinct_pairs`` numbers (the
dedup headroom) are measured, not assumed.

Each config also reports ``rows_per_rep``: the executed bucket-row gather
count per kernel rep, the row-count-bound cost model the relayout targets
(docs/gather-experiments.md: rows/s is flat across row widths).  This is
the CPU-measurable proxy for the on-chip stage win — run with
``JAX_PLATFORMS=cpu`` for the accounting + dedup measurements without a
chip (timings then reflect the CPU backend and are labelled so).

Measurement traps (formerly doc lore, now asserted in-run):
  * memoisation -- an access path that memoises identical calls would
    hide the kernel from the wall clock.  The
    probe times in-jit 8x repeats with per-iteration input perturbation;
    it ALSO times one identical-args repeat and RAISES if the perturbed
    path is indistinguishable from the memoised one (tainted measurement).
  * DRAM-page locality -- a `+i` index walk gives consecutive iterations
    page locality that inflates gather rates ~8x; the in-jit loop salts
    indices multiplicatively (see tools/gather_probe.py, which asserts the
    walk-vs-salt inflation directly).

Usage: python tools/kernel_stage_probe.py                      # on the chip
       JAX_PLATFORMS=cpu python tools/kernel_stage_probe.py   # proxy mode
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    on_chip = jax.devices()[0].platform == "tpu"
    print("device:", jax.devices()[0].device_kind, file=sys.stderr)

    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.ops import hashtable as ht
    from reporter_tpu.ops import viterbi as vt
    from reporter_tpu.tiles.arrays import build_graph_arrays
    from reporter_tpu.tiles.network import grid_city
    from reporter_tpu.tiles.ubodt import DeviceUBODT, build_ubodt

    net = grid_city(rows=16, cols=16, spacing_m=150.0)
    arrays = build_graph_arrays(net)
    ubodt = build_ubodt(arrays, delta=2000.0)
    cfg = MatcherConfig()
    matcher = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg)
    dg = matcher._dg
    params = matcher._params

    rng = np.random.default_rng(0)
    # real-sized garbage tables for gather physics; CPU proxy mode shrinks
    # them (the accounting below is size-independent)
    n_buckets = 1 << (20 if on_chip else 14)
    du_cuckoo = DeviceUBODT(
        jnp.asarray(rng.integers(0, 1 << 30, (n_buckets, 128),
                                 dtype=np.int32)),
        n_buckets - 1)
    du_wide = DeviceUBODT(
        jnp.asarray(rng.integers(0, 1 << 30, (n_buckets, 256),
                                 dtype=np.int32)),
        n_buckets - 1, layout="wide32")

    B, T = (512, 64) if on_chip else (64, 64)
    K = cfg.beam_k
    # plausible in-bbox tracks so the candidate stage does real work
    x0 = float(np.mean(arrays.node_x)); y0 = float(np.mean(arrays.node_y))
    px = x0 + rng.normal(0, 400, (B, T)).cumsum(axis=1) * 0.1
    py = y0 + rng.normal(0, 400, (B, T)).cumsum(axis=1) * 0.1
    tm = np.arange(T, dtype=np.float32)[None, :].repeat(B, 0) * 5.0
    valid = np.ones((B, T), np.float32)
    xin0 = np.asarray(vt.pack_inputs(px, py, tm, valid))

    LOOPS = 8
    memo_evidence = {}

    def timeit(fn, label, dux):
        # Repeat the kernel in-jit with a per-iteration input
        # perturbation (no identical execution to memoise) and time one
        # scalar fetch; the dispatch floor is shared by every config and
        # the 8x kernel repetition dominates the differences.
        def looped(dgx, dx, xin, p, k):
            def body(i, acc):
                r = fn(dgx, dx, xin + i.astype(jnp.float32) * 1e-3, p, k)
                return acc + jnp.sum(r)
            return jax.lax.fori_loop(0, LOOPS, body, jnp.int32(0))

        f = jax.jit(looped, static_argnums=(4,))
        xin = jnp.asarray(xin0)
        np.asarray(f(dg, dux, xin, params, cfg.beam_k))  # compile + warm
        ts = []
        for i in range(1, 4):
            xv = jnp.asarray(xin0 + np.float32(i) * 1e-2)
            t0 = time.time()
            np.asarray(f(dg, dux, xv, params, cfg.beam_k))
            ts.append(time.time() - t0)
        ms = round(min(ts) * 1000 / LOOPS, 1)
        # memoisation trap, asserted in-run: an IDENTICAL-args repeat must
        # not be what we measured.  If a path memoises (repeat much
        # cheaper than a fresh perturbed call) that is fine -- the timed
        # calls above perturb -- but if the perturbed calls are themselves
        # indistinguishable from the memoised floor, the measurement is
        # tainted and the tool refuses to print a number for it.
        xv = jnp.asarray(xin0 + np.float32(3) * 1e-2)  # same as last call
        t0 = time.time()
        np.asarray(f(dg, dux, xv, params, cfg.beam_k))
        memo_ms = (time.time() - t0) * 1000
        memo_detected = memo_ms < 0.25 * min(ts) * 1000
        memo_evidence[label] = {
            "memo_repeat_ms": round(memo_ms, 1),
            "memo_detected": bool(memo_detected),
        }
        if memo_detected and min(ts) * 1000 < 2.0 * memo_ms:
            raise RuntimeError(
                "%s: perturbed-call time (%.1f ms) is within 2x of the "
                "memoised repeat (%.1f ms) -- memoisation is "
                "swallowing the kernel; measurement tainted"
                % (label, min(ts) * 1000, memo_ms))
        print("%-12s min %.1f ms/iter  (calls %s ms)" %
              (label, ms, [round(t * 1000) for t in ts]), file=sys.stderr)
        return ms

    # executed bucket-row gathers per kernel rep: the row-count-bound cost
    # model (docs/gather-experiments.md), shared with bench's roofline via
    # obs/attrib (dedup_budget / executed_rows — the same _DEDUP_* maths
    # as ops/hashtable).  Dedup's budget is the static compacted capacity
    # -- the data-dependent distinct count is measured separately below
    # and must fit it for the deduped gather to run.
    from reporter_tpu.obs import attrib

    n_pairs = B * (T - 1) * K * K
    dedup_m = attrib.dedup_budget(n_pairs)
    rows_per_rep = {
        "full": attrib.executed_rows(n_pairs, 2),
        "noprobe": 0,
        "noselect": attrib.executed_rows(n_pairs, 2),
        "rollsel": attrib.executed_rows(n_pairs, 2),
        "dedup": attrib.executed_rows(n_pairs, 2, dedup=True),
        "wide32": attrib.executed_rows(n_pairs, 1),
        "wide32_dedup": attrib.executed_rows(n_pairs, 1, dedup=True),
    }

    out = {"shape": [B, T], "probe_pairs_per_rep": n_pairs,
           "dedup_budget": dedup_m, "rows_per_rep": rows_per_rep,
           "platform": "tpu" if on_chip else "cpu-proxy"}

    # measured dedup headroom on the REAL (small) table: distinct pairs per
    # dispatch from the probe-stats program -- if distinct exceeded the
    # budget the deduped configs would run their full-width fallback, so
    # assert the accounting is honest for THIS batch
    from reporter_tpu.ops.diagnostics import ubodt_probe_stats

    st = np.asarray(jax.jit(
        functools.partial(ubodt_probe_stats, delta=2000.0),
        static_argnums=(4,))(
            dg, matcher._du, jnp.asarray(xin0), params, cfg.beam_k))
    out["measured"] = {"probe_pairs": int(st[0]),
                       "distinct_pairs": int(st[4]),
                       "dedup_ratio": round(int(st[0]) / max(int(st[4]), 1), 2)}
    if int(st[4]) > dedup_m:
        out["note"] = ("distinct_pairs exceed the dedup budget on this "
                       "batch: deduped configs fell back to full width")
    print("dedup headroom: %s" % (out["measured"],), file=sys.stderr)

    out["full"] = timeit(vt.match_batch_compact_packed, "full", du_cuckoo)

    real_lookup = ht.ubodt_lookup
    real_select = ht._select

    def stub_lookup(u, src, dst, dedup=False):
        s, d = jnp.broadcast_arrays(src, dst)
        z = (s + d).astype(jnp.float32)
        return z * 0 + 750.0, z * 0 + 30.0, jnp.zeros_like(s)

    try:
        vt.ubodt_lookup = stub_lookup
        out["noprobe"] = timeit(vt.match_batch_compact_packed, "noprobe",
                                du_cuckoo)
    finally:
        vt.ubodt_lookup = real_lookup

    def cheap_select(rows, src, dst):
        vf = jax.lax.bitcast_convert_type(rows, jnp.float32)
        dist = jnp.min(jnp.abs(vf), axis=-1)
        return dist, dist * 0.1, jnp.max(rows, axis=-1)

    try:
        ht._select = cheap_select
        out["noselect"] = timeit(vt.match_batch_compact_packed, "noselect",
                                 du_cuckoo)
    finally:
        ht._select = real_select

    from reporter_tpu.tiles.ubodt import (
        F_DIST, F_DST, F_FE, F_SRC, F_TIME, ROW_W)

    def roll_select(rows, src, dst):
        # per-entry src AND dst via a static +1 lane roll instead of the
        # [LANES, LANES] spread matmul; field values picked by rolling the
        # hit flag onto each field lane
        fld = jax.lax.iota(jnp.int32, rows.shape[-1]) % ROW_W
        m_src = (rows == src[..., None]) & (fld == F_SRC)
        m_dst = (rows == dst[..., None]) & (fld == F_DST)
        hit = jnp.roll(m_src, F_DST - F_SRC, axis=-1) & m_dst
        vf = jax.lax.bitcast_convert_type(rows, jnp.float32)
        dist = jnp.min(jnp.where(
            jnp.roll(hit, F_DIST - F_DST, axis=-1), vf, jnp.inf), axis=-1)
        time_ = jnp.min(jnp.where(
            jnp.roll(hit, F_TIME - F_DST, axis=-1), vf, jnp.inf), axis=-1)
        first = jnp.max(jnp.where(
            jnp.roll(hit, F_FE - F_DST, axis=-1), rows, -1), axis=-1)
        return dist, time_, first

    try:
        ht._select = roll_select
        out["rollsel"] = timeit(vt.match_batch_compact_packed, "rollsel",
                                du_cuckoo)
    finally:
        ht._select = real_select

    # the real dedup + wide32 code paths (ops/hashtable.py) -- the round-5
    # "wide32" mock this tool used to carry became product code in round 6
    dedup_fn = functools.partial(vt.match_batch_compact_packed, dedup=True)
    out["dedup"] = timeit(dedup_fn, "dedup", du_cuckoo)
    out["wide32"] = timeit(vt.match_batch_compact_packed, "wide32", du_wide)
    out["wide32_dedup"] = timeit(dedup_fn, "wide32_dedup", du_wide)

    out["traps"] = memo_evidence
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
