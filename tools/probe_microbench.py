"""Microbenchmark: UBODT probe layouts on the real device.

Compares the round-3 layout (linear probing, 5 SoA arrays, max_probes
unrolled gathers x 5 arrays each) against the round-4 production layout
(2-choice bucketed cuckoo, one 128-lane [buckets, 128] int32 row-gather per
probe — tiles/ubodt.py) on a synthetic table sized like the bench scenario.

Every table is passed to the jitted probe as an ARGUMENT, never captured in
a closure: a closed-over device array becomes an XLA *constant*, and compile
time then grows with the table size (measured on a v5e in round 3: 2 s at
2^16 slots, 18 s at 2^20, >13 min at 2^25 — the production-size table).  The
product code (ops/hashtable.py via DeviceUBODT pytree args) already does
this; the rule matters for any future kernel too.

Timing fetches a scalar reduction of the result to the host per
repetition, so the host fetch bounds the device time.  Inputs are
rotated across repetitions so no call can be served from a cache.

Run:  python tools/probe_microbench.py [--platform tpu|cpu]
(default platform: $JAX_PLATFORMS, else cpu)
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=1 << 25)  # 32M (r03 bench size)
    ap.add_argument("--lookups", type=int, default=8 * 1023 * 64)  # B=8,T=1024,KxK=64
    ap.add_argument("--probes", type=int, default=26)  # measured r03 max_probes would go here
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--skip-r03", action="store_true",
                    help="only run the production cuckoo layout (the r03 "
                         "layouts are slow by design and dominate wall time)")
    ap.add_argument("--platform", default=None,
                    help="jax platform allow-list (default $JAX_PLATFORMS, else cpu)")
    args = ap.parse_args()

    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from reporter_tpu.utils.jaxenv import ensure_platform

    # default the platform to cpu when nothing is requested
    ensure_platform(args.platform or os.environ.get("JAX_PLATFORMS") or "cpu")
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print("platform:", dev.platform, dev)

    S = args.slots
    N = args.lookups
    rng = np.random.default_rng(0)

    # --- r03 layout: 5 SoA int32/f32 arrays (only if they will be timed:
    # at the default 2^25 slots this is ~640 MB of host RNG + HBM) ---------
    soa = None
    if not args.skip_r03:
        soa = tuple(
            jax.device_put(a) for a in (
                rng.integers(0, 1 << 20, S, dtype=np.int32),   # src
                rng.integers(0, 1 << 20, S, dtype=np.int32),   # dst
                rng.random(S, dtype=np.float32),               # dist
                rng.random(S, dtype=np.float32),               # time
                rng.integers(0, 1 << 20, S, dtype=np.int32),   # first_edge
            )
        )

    # --- r04 layout: one 128-lane row per BUCKET-entry bucket --------------
    from reporter_tpu.tiles.ubodt import BUCKET, ROW_W

    BKT = S // BUCKET
    packed = jax.device_put(
        rng.integers(0, 1 << 20, (BKT, BUCKET * ROW_W), dtype=np.int32))

    # one fresh input pair per timed repetition (plus one for warmup) so no
    # call repeats inputs seen earlier — any result cache keyed on content
    # would otherwise serve reps silently
    n_inputs = args.reps + 1
    srcs = [jax.device_put(rng.integers(0, 1 << 20, N, dtype=np.int32))
            for _ in range(n_inputs)]
    dsts = [jax.device_put(rng.integers(0, 1 << 20, N, dtype=np.int32))
            for _ in range(n_inputs)]
    mask = S - 1
    bmask = BKT - 1

    # the production hash mixes — measure exactly what the product probes
    from reporter_tpu.ops.hashtable import device_pair_hash as hash1
    from reporter_tpu.ops.hashtable import device_pair_hash2 as hash2

    def probe_r03(tabs, src, dst, n_probes):
        t_src, t_dst, t_dist, t_time, t_fe = tabs
        h = hash1(src, dst, mask)
        dist = jnp.full(h.shape, jnp.inf, jnp.float32)
        tim = jnp.full(h.shape, jnp.inf, jnp.float32)
        first = jnp.full(h.shape, -1, jnp.int32)
        found = jnp.zeros(h.shape, jnp.bool_)
        for p in range(n_probes):
            idx = (h + p) & mask
            ts = t_src[idx]
            td = t_dst[idx]
            hit = (ts == src) & (td == dst) & (~found)
            dist = jnp.where(hit, t_dist[idx], dist)
            tim = jnp.where(hit, t_time[idx], tim)
            first = jnp.where(hit, t_fe[idx], first)
            found = found | hit | (ts == -1)
        return dist, tim, first

    def probe_cuckoo(packed, src, dst):
        b1 = hash1(src, dst, bmask)
        b2 = hash2(src, dst, bmask)
        r1 = packed[b1]  # [N, 128]: one aligned row DMA per probe
        r2 = packed[b2]
        rows = jnp.concatenate([r1, r2], axis=-1).reshape(-1, 2 * BUCKET, ROW_W)
        hit = (rows[..., 0] == src[..., None]) & (rows[..., 1] == dst[..., None])
        dist = jnp.min(
            jnp.where(hit, jax.lax.bitcast_convert_type(rows[..., 2], jnp.float32), jnp.inf),
            axis=-1,
        )
        tim = jnp.min(
            jnp.where(hit, jax.lax.bitcast_convert_type(rows[..., 3], jnp.float32), jnp.inf),
            axis=-1,
        )
        first = jnp.max(jnp.where(hit, rows[..., 4], -1), axis=-1)
        return dist, tim, first

    def probe_r03_interleaved(packed, src, dst, n_probes):
        # linear probing but one narrow row-gather per probe
        h = hash1(src, dst, mask)
        flat = packed.reshape(-1, ROW_W)[:S]
        dist = jnp.full(h.shape, jnp.inf, jnp.float32)
        tim = jnp.full(h.shape, jnp.inf, jnp.float32)
        first = jnp.full(h.shape, -1, jnp.int32)
        found = jnp.zeros(h.shape, jnp.bool_)
        for p in range(n_probes):
            idx = (h + p) & mask
            row = flat[idx]  # [N, 8]
            hit = (row[..., 0] == src) & (row[..., 1] == dst) & (~found)
            dist = jnp.where(hit, jax.lax.bitcast_convert_type(row[..., 2], jnp.float32), dist)
            tim = jnp.where(hit, jax.lax.bitcast_convert_type(row[..., 3], jnp.float32), tim)
            first = jnp.where(hit, row[..., 4], first)
            found = found | hit | (row[..., 0] == -1)
        return dist, tim, first

    def bench(name, fn, tabs):
        # scalar-fetch per rep: bounds real device time even where
        # block_until_ready is optimistic (see module docstring)
        def fetch(tabs, src, dst):
            # consume ALL outputs: an unused output lets XLA dead-code-
            # eliminate its whole gather stream, biasing the comparison
            d, t, f = fn(tabs, src, dst)
            return (jnp.sum(jnp.where(jnp.isfinite(d), d, 0.0))
                    + jnp.sum(jnp.where(jnp.isfinite(t), t, 0.0))
                    + jnp.sum(f.astype(jnp.float32)))

        jf = jax.jit(fetch)
        t0 = time.time()
        float(jf(tabs, srcs[args.reps], dsts[args.reps]))  # warmup-only pair
        compile_s = time.time() - t0
        t0 = time.time()
        for i in range(args.reps):
            float(jf(tabs, srcs[i], dsts[i]))
        dt = (time.time() - t0) / args.reps
        print(
            "%-22s %8.2f ms   %8.1f M lookups/s   (compile+first %.1fs)"
            % (name, dt * 1e3, N / dt / 1e6, compile_s)
        )
        return dt

    bench("cuckoo-2probe", probe_cuckoo, packed)
    if not args.skip_r03:
        bench("linear-interleaved-8",
              lambda t, s, d: probe_r03_interleaved(t, s, d, 8), packed)
        bench("linear-soa-8", lambda t, s, d: probe_r03(t, s, d, 8), soa)
        bench("linear-soa-%d" % args.probes,
              lambda t, s, d: probe_r03(t, s, d, args.probes), soa)


if __name__ == "__main__":
    main()
