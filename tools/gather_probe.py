"""On-chip microbenchmark of UBODT-style row gathers.

The round-5 on-chip attribution pins 123 of 199 ms device time on the two
bucket-row gathers (`ops/hashtable.py:99-100`), with an application-level
rate of ~24 GB/s of useful rows.  This probe measures raw `table[idx]`
row-gather rates on the real chip across layouts to answer ONE question:
is the gather row-count-bound (each 512 B row fetch pays a full (8,128)
tile / fixed DMA cost, so halving row bytes buys nothing) or
byte-bound (smaller rows => proportionally faster)?

Variants, all reading the same total ~2 GB of useful rows:
  r128        [2^20, 128] i32 table, 4M random rows   (the real layout)
  r128_sorted same, indices sorted                     (locality effect)
  r128_x2     two 2M gathers (the real two-probe shape)
  r64         [2^21, 64] i32 table, 8M random rows    (half-size rows)
  r256        [2^19, 256] i32 table, 2M random rows   (the wide32 rows)
  r256_dedup  r256 with the sort->compact->gather->scatter scaffolding of
              the in-batch probe dedup (ops/hashtable._lookup_dedup)
              around a HALF-count gather: measures what the dedup buys
              net of its sort/scatter overhead at ratio 2

Measurement traps.  Two honest-variant traps used to live only in this
docstring; they now assert themselves per run:
  (1) XLA rewrites `sum(f(t[ix]))` into a per-row precompute plus a
      scalar gather unless the consumer depends on a per-query value —
      the first version of this probe read "33 TB/s".  Worked around by
      the per-query key compare; no longer assertable once worked around
      (the rewrite leaves no observable).
  (2) memoisation + DRAM-page locality: an access path that memoises
      identical calls returns host-side repeats in ~0.1 ms, so
      the repeat loop lives in-jit with per-iteration index
      decorrelation — and a `+i` index walk gives consecutive iterations
      page locality that inflates the rate ~8x ("946 GB/s").  The probe
      now MEASURES the walk variant next to the salted one
      (`traps.walk_inflation_x`) and raises if the headline salted
      variant is the inflated one; it also times one identical-args
      repeat (`traps.memo_repeat_ms`) and raises if the timed fresh
      calls sit within 2x of the memoised floor.

Usage: python tools/gather_probe.py   (on the chip)
"""
from __future__ import annotations

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

    dev = jax.devices()[0]
    print("device:", dev.platform, dev.device_kind, file=sys.stderr)

    rng = np.random.default_rng(0)
    out = {"traps": {}}

    def bench(name, n_buckets, row_w, n_idx, n_gathers=1, sort=False,
              walk=False):
        shape = (n_idx,) if isinstance(n_idx, int) else tuple(n_idx)
        size = int(np.prod(shape))
        table = jnp.asarray(
            rng.integers(0, 1 << 30, (n_buckets, row_w), dtype=np.int32))
        idx_np = rng.integers(0, n_buckets, (n_gathers,) + shape,
                              dtype=np.int32)
        if sort:
            idx_np = np.sort(idx_np.reshape(n_gathers, -1), axis=1).reshape(
                idx_np.shape)
        idx = jnp.asarray(idx_np)

        # per-query keys: the consumer must depend on BOTH the row content
        # and the query, or XLA rewrites sum(f(t[ix])) into a per-row
        # precompute + scalar gather and the row reads vanish (observed:
        # "33 TB/s" on the first version of this probe).  The repeat loop
        # lives INSIDE the jit with per-iteration index perturbation: a
        # memoised host-side repeat of an identical call would return in
        # ~0.1 ms, which no wall clock can see through.
        q = jnp.asarray(rng.integers(0, 1 << 30, (n_gathers,) + shape,
                                     dtype=np.int32))
        LOOPS = 8

        @jax.jit
        def run(t, ix, qq):
            def body(i, acc):
                a = acc
                # decorrelate iterations with a multiplicative hash: a +i
                # walk gives consecutive iterations DRAM-page locality and
                # inflates the measured rate ~8x (observed: "946 GB/s").
                # walk=True keeps the naive +i variant ON PURPOSE: it is
                # the measured half of the locality trap assert below.
                if walk:
                    salt = i
                else:
                    salt = (i * jnp.int32(-1640531527)) >> 7
                for g in range(ix.shape[0]):
                    rows = t[(ix[g] ^ salt) & (t.shape[0] - 1)]
                    m = jnp.where(rows == qq[g][..., None], rows, 0)
                    a = a + jnp.sum(m, dtype=jnp.int32)
                return a
            return jax.lax.fori_loop(0, LOOPS, body, jnp.int32(0))

        np.asarray(run(table, idx, q))  # compile + warm (fetch = the sync)
        t0 = time.time()
        np.asarray(run(table, jnp.asarray(idx_np ^ 1), q))
        dt = (time.time() - t0) / LOOPS
        # memoisation trap, asserted: one identical-args repeat.  A path
        # memoising it is harmless (the timed call above
        # used fresh indices); the timed call sitting at the memoised floor
        # is NOT, and means the wall clock never saw the gathers.
        t0 = time.time()
        np.asarray(run(table, jnp.asarray(idx_np ^ 1), q))
        memo_dt = (time.time() - t0) / LOOPS
        if memo_dt < 0.25 * dt:
            out["traps"].setdefault("memo_detected_on", []).append(name)
            if dt < 2.0 * memo_dt:  # pragma: no cover - memoising path only
                raise RuntimeError(
                    "%s: fresh-call time within 2x of the memoised repeat "
                    "(%.1f vs %.1f ms) -- measurement tainted" %
                    (name, dt * 1000, memo_dt * 1000))
        useful_gb = n_gathers * size * row_w * 4 / 1e9
        rec = {
            "rows_per_s_m": round(n_gathers * size / dt / 1e6, 1),
            "useful_gb_per_s": round(useful_gb / dt, 1),
            "ms": round(dt * 1000, 1),
        }
        out[name] = rec
        print("%-12s -> %s" % (name, rec), file=sys.stderr)
        del table, idx
        return rec

    def bench_dedup_overhead(name, n_buckets, row_w, n_idx, ratio=2):
        """The in-batch dedup data path at gather granularity: sort the
        keys, gather ONLY n_idx//ratio compacted rows, scatter results
        back through segment ids (ops/hashtable._lookup_dedup's shape).
        Against the plain r-variant this prices the sort+scatter
        scaffolding the dedup win must clear."""
        m = n_idx // ratio
        table = jnp.asarray(
            rng.integers(0, 1 << 30, (n_buckets, row_w), dtype=np.int32))
        idx_np = rng.integers(0, n_buckets, (n_idx,), dtype=np.int32)
        idx = jnp.asarray(idx_np)
        q = jnp.asarray(rng.integers(0, 1 << 30, (n_idx,), dtype=np.int32))

        @jax.jit
        def run(t, ix, qq):
            def body(i, acc):
                salt = (i * jnp.int32(-1640531527)) >> 7
                keys = (ix ^ salt) & (t.shape[0] - 1)
                sk, perm = jax.lax.sort((keys, jax.lax.iota(jnp.int32, n_idx)),
                                        num_keys=1)
                head = jnp.concatenate(
                    [jnp.ones((1,), bool), sk[1:] != sk[:-1]])
                seg = jnp.cumsum(head.astype(jnp.int32)) - 1
                tgt = jnp.where(head & (seg < m), seg, m)
                ck = jnp.zeros((m,), jnp.int32).at[tgt].set(sk, mode="drop")
                rows = t[ck]  # m row gathers instead of n_idx
                v = jnp.sum(jnp.where(rows == qq[:m, None], rows, 0),
                            axis=-1, dtype=jnp.int32)
                back = v[jnp.minimum(seg, m - 1)]
                inv = jnp.zeros((n_idx,), jnp.int32).at[perm].set(
                    jax.lax.iota(jnp.int32, n_idx))
                return acc + jnp.sum(back[inv], dtype=jnp.int32)
            return jax.lax.fori_loop(0, LOOPS, body, jnp.int32(0))

        np.asarray(run(table, idx, q))
        t0 = time.time()
        np.asarray(run(table, jnp.asarray(idx_np ^ 1), q))
        dt = (time.time() - t0) / LOOPS
        rec = {
            "rows_per_s_m": round(m / dt / 1e6, 1),
            "gathered_rows": m,
            "scattered_back": n_idx,
            "ms": round(dt * 1000, 1),
        }
        out[name] = rec
        print("%-12s -> %s" % (name, rec), file=sys.stderr)

    N = 1 << 22  # 4M rows of 512 B = 2.1 GB useful per measurement
    r128 = bench("r128", 1 << 20, 128, N)
    # DRAM-page-locality trap, asserted: the naive +i walk must be the
    # INFLATED variant; the headline numbers above use the salted one.
    walk = bench("r128_walk", 1 << 20, 128, N, walk=True)
    inflation = walk["rows_per_s_m"] / max(r128["rows_per_s_m"], 0.1)
    out["traps"]["walk_inflation_x"] = round(inflation, 2)
    if inflation < 1.0:  # pragma: no cover - would mean the lore inverted
        raise RuntimeError(
            "+i index walk measured SLOWER than the salted variant "
            "(%.1fx) -- the locality-trap model no longer holds on this "
            "device; re-derive the honest variant before trusting rates"
            % inflation)
    bench("r128_sorted", 1 << 20, 128, N, sort=True)
    bench("r128_x2", 1 << 20, 128, N // 2, n_gathers=2)
    bench("r128_4d", 1 << 20, 128, (512, 63, 8, 8))  # the kernel's shape
    bench("r64", 1 << 21, 64, N * 2)
    bench("r256", 1 << 19, 256, N // 2)
    bench_dedup_overhead("r256_dedup", 1 << 19, 256, N // 2)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
