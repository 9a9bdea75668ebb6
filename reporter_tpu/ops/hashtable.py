"""Device-side hash probe for the UBODT, for both table layouts.

``cuckoo`` (the shipped round-4 layout): the route-distance lookup inside
the HMM transition is exactly **two row-gathers** — hash the (src, dst)
node pair with two independent mixes, pull each candidate bucket as one
interleaved 128-lane int32 row (a 512-byte aligned window — exactly one
TPU tile row, the unit the memory system moves anyway), and select the hit
with a masked reduce over the 2*BUCKET candidate entries.

``wide32`` (round 6, docs/gather-experiments.md): **one row-gather** —
a single hash pulls one 256-lane (1 KB) row of 32 candidate entries.
Random row gathers are row-count-bound on TPU (~20-38 M rows/s regardless
of row width, tools/gather_probe.py), so the single wide row halves the
dominant gather stage while the wider select costs one extra 256-wide
matmul pass.

Neither layout has data-dependent control flow or probe chains: the probe
count is an architectural constant of the table layout, not a function of
load.  (Round 3 used linear probing: up to 64 unrolled probes x 5 separate
scalar gathers into five ~32M-slot arrays, which made the transition
matrix HBM-random-access-bound and left the TPU ~15x slower than host CPU
on the same program.)

**In-batch probe dedup** (``dedup=True``): a dispatch's (src, dst) probe
pairs are massively redundant — consecutive trace points share candidate
edges, so the same pair is probed at many (t, k, k') sites (measured
~2.1 M pairs per bench fleet rep).  Because gathers are row-count-bound,
the win is to gather each *distinct* pair once: fixed-shape sort →
unique-flag → segmented gather over a compacted key buffer → scatter-back
through segment ids.  The compacted buffer is a static fraction of the
pair count (``_DEDUP_CAP_RATIO``); should a batch's distinct-pair count
overflow it (adversarial/random inputs), a ``lax.cond`` falls back to the
plain full-width probe — results stay bit-identical in every case, only
the executed row count changes.  Dedup only applies at the top level of a
jitted program (it sorts across the whole key set); under the gp-sharded
probe it is skipped (the bucket-range masking already drops remote rows).

Must mirror tiles/ubodt.py's host-side layouts and hashes exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs.attrib import stage
from ..tiles.ubodt import (
    F_DIST, F_DST, F_FE, F_SRC, F_TIME, ROW_W, DeviceUBODT,
)

# compacted-unique capacity = pair count // ratio: the static budget the
# deduped gather runs at.  2 is conservative — realistic fleet batches
# measure 4-8x redundant (the reporter_probe_dedup_ratio gauge / bench
# probe_dedup field carry the live number) — so the capacity practically
# never overflows while still halving the executed row count even before
# the wide32 halving.
_DEDUP_CAP_RATIO = 2
# below this many pairs the sort scaffolding costs more than the gathers
# it saves; the plain probe is used regardless of the dedup flag
_DEDUP_MIN_PAIRS = 1024


def device_pair_hash(src: jnp.ndarray, dst: jnp.ndarray, mask: int) -> jnp.ndarray:
    """uint32 mix identical to tiles.ubodt.pair_hash (bucket choice 1, and
    the single wide32 bucket)."""
    s = src.astype(jnp.uint32)
    d = dst.astype(jnp.uint32)
    h = s * jnp.uint32(0x9E3779B1) + d * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(15))
    h = h * jnp.uint32(0x2C1B3C6D)
    h = h ^ (h >> jnp.uint32(12))
    return (h & jnp.uint32(mask)).astype(jnp.int32)


def device_pair_hash2(src: jnp.ndarray, dst: jnp.ndarray, mask: int) -> jnp.ndarray:
    """uint32 mix identical to tiles.ubodt.pair_hash2 (cuckoo bucket 2)."""
    s = src.astype(jnp.uint32)
    d = dst.astype(jnp.uint32)
    h = s * jnp.uint32(0x85EBCA77) + d * jnp.uint32(0xC2B2AE3D)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0x27D4EB2F)
    h = h ^ (h >> jnp.uint32(16))
    return (h & jnp.uint32(mask)).astype(jnp.int32)


def _entry_spread_matrix(lanes: int) -> jnp.ndarray:
    """[lanes, lanes] 0/1 matrix: column l' sums the F_SRC and F_DST lanes
    of l's own entry, so (mask @ A) == 2 marks EVERY lane of a hit entry.
    lanes = BUCKET*ROW_W (128, cuckoo) or WIDE_BUCKET*ROW_W (256, wide32)."""
    l = jnp.arange(lanes)
    same_entry = (l[:, None] // ROW_W) == (l[None, :] // ROW_W)
    is_key = (l[:, None] % ROW_W == F_SRC) | (l[:, None] % ROW_W == F_DST)
    return (same_entry & is_key).astype(jnp.float32)


def _select(rows: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray):
    """rows: [..., entries*ROW_W] interleaved lane rows -> (dist, time,
    first) with +inf / -1 on miss.  Keys are unique so at most one entry
    hits.  Works for any whole-row lane count (128 cuckoo / 256 wide32).

    Works entirely in the native lane layout: lane l holds field
    (l % ROW_W) of entry (l // ROW_W).  The per-entry src AND dst match is
    resolved by summing the two key-lane indicators with one static 0/1
    matmul over the lane axis (sums are small integers, exact at any matmul
    precision), then min/max lane-reduces pick each result field.  The
    previous reshape to (..., entries, ROW_W) minor dims tile-pads 16-128x
    on TPU and blew HBM at fleet shapes (s32[512,63,8,8,16,8] padded
    1008 MB -> 15.75 GB; measured compile OOM on v5e, 2026-07-31).
    """
    lanes = rows.shape[-1]
    fld = jax.lax.iota(jnp.int32, lanes) % ROW_W
    m = ((rows == src[..., None]) & (fld == F_SRC)) | (
        (rows == dst[..., None]) & (fld == F_DST))
    both = jnp.dot(m.astype(jnp.float32), _entry_spread_matrix(lanes)) == 2.0
    vf = jax.lax.bitcast_convert_type(rows, jnp.float32)
    dist = jnp.min(jnp.where(both & (fld == F_DIST), vf, jnp.inf), axis=-1)
    time = jnp.min(jnp.where(both & (fld == F_TIME), vf, jnp.inf), axis=-1)
    first = jnp.max(jnp.where(both & (fld == F_FE), rows, -1), axis=-1)
    return dist, time, first


def _bucket_rows(u, b: jnp.ndarray, valid=None) -> jnp.ndarray:
    """One bucket-row fetch [..., 128 or 256] — a plain gather from a
    device-resident packed table, or the hot-arena / host-paged two-tier
    path when the table is tiered (tiles/tiering.py: bit-identical rows
    either way, only the executed memory traffic changes; the host pages
    meet the device-memory index operand only after an explicit move into
    device memory, which is what JAX 0.9 accepts).  ``valid``
    (None = all) marks which probes are real: the gp-sharded probe clamps
    remote buckets to a local index and masks the rows afterwards, and
    the tiered path must neither count those phantom probes in its EWMA
    stats nor let them force the cold-page fallback."""
    if getattr(u, "tier", None) is None:
        return u.packed[b]
    from ..tiles.tiering import tiered_bucket_rows

    return tiered_bucket_rows(u, b, valid)


def _lookup_plain(u: DeviceUBODT, src: jnp.ndarray, dst: jnp.ndarray):
    """The architectural-constant probe: one aligned row DMA per hash
    function (wide32: one; cuckoo: two, merged elementwise)."""
    with stage("ubodt-probe"):
        b1 = device_pair_hash(src, dst, u.bmask)
        r1 = _bucket_rows(u, b1)  # [..., 128 or 256]: one aligned lane-row DMA per probe
    if u.layout == "wide32":
        with stage("select"):
            return _select(r1, src, dst)
    with stage("ubodt-probe"):
        b2 = device_pair_hash2(src, dst, u.bmask)
        r2 = _bucket_rows(u, b2)
    # select per bucket and combine: keys are unique, so at most one bucket
    # hits and an elementwise min/max merges exactly.  (Concatenating the
    # two row sets first materialised a [..., 2*BUCKET*ROW_W] array — ~11 ms
    # of pure layout work per kernel rep on chip, docs/onchip-attribution.md)
    with stage("select"):
        d1, t1, f1 = _select(r1, src, dst)
        d2, t2, f2 = _select(r2, src, dst)
        return jnp.minimum(d1, d2), jnp.minimum(t1, t2), jnp.maximum(f1, f2)


def _lookup_dedup(u: DeviceUBODT, src: jnp.ndarray, dst: jnp.ndarray):
    """Sort-unique-gather-scatter probe: each DISTINCT (src, dst) pair pays
    one plain probe (1 row gather wide32 / 2 cuckoo) per dispatch instead
    of one per occurrence.  Bit-identical to _lookup_plain by construction:
    duplicates copy their segment head's result, and the (rare) overflow of
    the static unique budget falls back to the plain probe via lax.cond.

    Fixed shapes throughout: the pair count N and the compact budget M are
    trace-time constants, so this composes with jit/sharded-jit like any
    other op.  Do NOT call under vmap — the sort would silently become
    per-slice (callers hoist the probe to the top of the batched program;
    ops/viterbi.precompute_batch)."""
    shape = src.shape
    s = src.reshape(-1).astype(jnp.int32)
    d = dst.reshape(-1).astype(jnp.int32)
    n = s.shape[0]
    m = max(_DEDUP_MIN_PAIRS // 2, n // _DEDUP_CAP_RATIO)
    if m >= n:  # tiny batch: nothing to save
        dist, time, fe = _lookup_plain(u, s, d)
        return dist.reshape(shape), time.reshape(shape), fe.reshape(shape)

    with stage("dedup-sort"):
        iota = jax.lax.iota(jnp.int32, n)
        # lexicographic stable sort carrying the original position
        sk, dk, perm = jax.lax.sort((s, d, iota), num_keys=2)
        head = jnp.concatenate([
            jnp.ones((1,), bool), (sk[1:] != sk[:-1]) | (dk[1:] != dk[:-1])])
        seg = jnp.cumsum(head.astype(jnp.int32)) - 1  # [n] segment id, ascending
        n_unique = seg[-1] + 1

    with stage("dedup-compact"):
        # compact segment-head keys into the M-slot buffer (drop-mode scatter:
        # non-heads and beyond-budget heads target index m = out of bounds).
        # Unfilled tail slots stay (0, 0) — probed but never read back.
        tgt = jnp.where(head & (seg < m), seg, m)
        cs = jnp.zeros((m,), jnp.int32).at[tgt].set(sk, mode="drop")
        cd = jnp.zeros((m,), jnp.int32).at[tgt].set(dk, mode="drop")

    def _deduped(_):
        dist_u, time_u, fe_u = _lookup_plain(u, cs, cd)  # M row gathers
        with stage("dedup-scatter"):
            idx = jnp.minimum(seg, m - 1)
            # scatter-back: sorted-order values, then undo the sort permutation
            inv = jnp.zeros((n,), jnp.int32).at[perm].set(iota)
            return dist_u[idx][inv], time_u[idx][inv], fe_u[idx][inv]

    def _full(_):
        return _lookup_plain(u, s, d)

    dist, time, fe = jax.lax.cond(n_unique <= m, _deduped, _full, None)
    return dist.reshape(shape), time.reshape(shape), fe.reshape(shape)


def count_distinct_pairs(src: jnp.ndarray, dst: jnp.ndarray,
                         valid: jnp.ndarray) -> jnp.ndarray:
    """Scalar i32: distinct (src, dst) pairs among positions where ``valid``
    — the numerator of the probe-dedup redundancy diagnostics
    (ops/diagnostics.ubodt_probe_stats -> reporter_probe_dedup_ratio)."""
    s = jnp.where(valid, src, -1).reshape(-1).astype(jnp.int32)
    d = jnp.where(valid, dst, -1).reshape(-1).astype(jnp.int32)
    sk, dk = jax.lax.sort((s, d), num_keys=2)
    head = jnp.concatenate([
        jnp.ones((1,), bool), (sk[1:] != sk[:-1]) | (dk[1:] != dk[:-1])])
    # the invalid sentinel (-1, -1) sorts first and collapses to one
    # segment; subtract it when any position was invalid
    distinct = jnp.sum(head.astype(jnp.int32))
    has_invalid = jnp.any(~valid).astype(jnp.int32)
    return distinct - has_invalid


def ubodt_lookup(u: DeviceUBODT, src: jnp.ndarray, dst: jnp.ndarray,
                 dedup: bool = False):
    """Vectorised table probe.  src/dst: any (broadcastable) int32 shape.

    Returns (dist, time, first_edge): dist/time = +inf and first_edge = -1
    on miss.  ``dedup`` (static) routes through the in-batch
    sort-unique-gather-scatter path — only meaningful at the top level of a
    batched program (see _lookup_dedup).  When ``u.shard_axis`` is set the
    packed table leaf is a local bucket-range slice inside a shard_map and
    the result is resolved with collectives (dedup is skipped there).
    """
    if u.shard_axis is not None:
        return _ubodt_lookup_sharded(u, src, dst)
    src, dst = jnp.broadcast_arrays(src, dst)
    if dedup and src.size >= _DEDUP_MIN_PAIRS:
        return _lookup_dedup(u, src, dst)
    return _lookup_plain(u, src, dst)


def _ubodt_lookup_sharded(u: DeviceUBODT, src: jnp.ndarray, dst: jnp.ndarray):
    """Probe a bucket-range-sharded table from inside a shard_map.

    Each rank gathers the candidate bucket(s) only when they fall in its
    local range; keys are unique, so at most one rank hits and a pmin/pmax
    over the shard axis resolves every query exactly.  Communication is three
    small collectives per lookup batch, riding the ICI — the table itself
    never moves.  Works for the plain packed table AND the tiered one: the
    local row fetch routes through _bucket_rows, so a rank's bucket range
    can itself be a hot-arena + cold-pages tier (the contiguous-bucket
    partition is the same shard_bucket_range either way).
    """
    L = u.local_buckets  # local bucket-range length
    lo = jax.lax.axis_index(u.shard_axis) * L
    src, dst = jnp.broadcast_arrays(src, dst)
    b1 = device_pair_hash(src, dst, u.bmask)

    def local_rows(b):
        with stage("ubodt-probe"):
            loc = b - lo
            inr = (loc >= 0) & (loc < L)
            r = _bucket_rows(u, jnp.where(inr, loc, 0), valid=inr)
            # out-of-range buckets contribute entries that match nothing (-2)
            return jnp.where(inr[..., None], r, -2)

    if u.layout == "wide32":
        with stage("select"):
            d1, t1, f1 = _select(local_rows(b1), src, dst)
    else:
        b2 = device_pair_hash2(src, dst, u.bmask)
        # per-bucket select + min/max merge, like the unsharded path: avoids
        # materialising the concatenated [..., 2*BUCKET*ROW_W] layout
        with stage("select"):
            da, ta, fa = _select(local_rows(b1), src, dst)
            db, tb, fb = _select(local_rows(b2), src, dst)
            d1 = jnp.minimum(da, db)
            t1 = jnp.minimum(ta, tb)
            f1 = jnp.maximum(fa, fb)
    dist = jax.lax.pmin(d1, u.shard_axis)
    time = jax.lax.pmin(t1, u.shard_axis)
    first = jax.lax.pmax(f1, u.shard_axis)
    return dist, time, first
