"""Hot/cold tiered UBODT: continent tables bigger than device memory.

Every bench so far ran city graphs whose UBODT fits in one device's
memory; a continent OSM extract's precomputed routing table does not
(ROADMAP open item 3).  This module keeps the table device-resident
*where it is hot* and host-paged *where it is cold*:

  hot tier    a device-resident **arena** of packed bucket rows (the same
              128/256-lane rows ops/hashtable.py gathers from a resident
              table), sized by ``REPORTER_UBODT_HOT_BYTES``, plus a
              device ``slot_map`` [n_buckets] i32 mapping each bucket to
              its arena slot (-1 = cold);
  cold tier   the FULL packed table as a **host-memory-kind array leaf**
              (``pinned_host`` where the backend supports XLA host
              offload — the pages stay in host DRAM and a cold gather
              rides the PCIe/ICI transfer XLA inserts; the CPU backend's
              arrays are host memory already, so the same program is the
              CPU-verifiable twin).

The device probe (``tiered_bucket_rows``, called by ops/hashtable's
``_bucket_rows`` seam) follows the exact ``lax.cond`` full-width
fallback pattern of the PR 5 probe-dedup overflow: the common case (every
probed bucket hot) runs entirely from the arena and the cold pages are
never touched; any miss takes the full-width fallback — gather EVERY
probed bucket's row from the host pages and select per element.  Either
way the gathered bytes are identical (the arena rows are copies of the
host pages), so match output is **bit-identical** to an untiered table in
every case — both viterbi kernels, both table layouts, any tier state
(differential-tested in tests/test_tiering.py).

Deliberately NOT a host callback: converting a callback operand to numpy
mid-execution can deadlock the CPU client when every executor thread is
parked in a callback (computation waits on callback, callback's
conversion waits on an executor — observed under the matcher's pipelined
dispatch, tools/tiering_probe.py).  The memory-kind leaf keeps the cold
fetch a pure in-program gather.

Admission/eviction is a probe-frequency EWMA: every dispatch's bucket
set feeds per-bucket counters (a ``jax.debug.callback`` side channel
that only PARKS its operand handles — a separate drain thread converts
them after the fact, so callback context never blocks on the runtime),
folded into an exponentially-weighted score on each maintenance pass;
the top-scored buckets hold the arena.  A fleet shard assignment
(``REPORTER_UBODT_SHARD=i/N``, docs/serving-fleet.md) SEEDS the hot set
with the replica's bucket-range partition — the same contiguous
partition the gp-sharded shard_map probe and the distributed builder
use — but admission stays EWMA-driven after boot, so a mis-sharded
traffic mix converges to the real working set instead of thrashing.

Observability (docs/observability.md): ``reporter_ubodt_tier_hits_total``
/ ``_misses_total`` / ``_evictions_total`` counters plus resident-row /
residency-fraction gauges.
"""

from __future__ import annotations

import logging
import threading
from collections import deque
from typing import Optional, Tuple

import numpy as np

from ..obs import metrics as obs
from .ubodt import ROW_W, UBODT, bucket_entries

log = logging.getLogger(__name__)

C_TIER_HITS = obs.counter(
    "reporter_ubodt_tier_hits_total",
    "UBODT probes answered from the device-resident hot-bucket arena "
    "(docs/performance.md \"Continent-scale data plane\")")
C_TIER_MISSES = obs.counter(
    "reporter_ubodt_tier_misses_total",
    "UBODT probes whose bucket was cold — served bit-identically through "
    "the host-paged full-width fallback")
C_TIER_EVICTIONS = obs.counter(
    "reporter_ubodt_tier_evictions_total",
    "Hot-arena bucket rows evicted by the probe-frequency EWMA "
    "maintenance pass")
G_TIER_ROWS = obs.gauge(
    "reporter_ubodt_tier_resident_rows",
    "Bucket rows currently resident in the device hot arena")
G_TIER_FRAC = obs.gauge(
    "reporter_ubodt_tier_residency_frac",
    "Fraction of the table's buckets resident in the device hot arena "
    "(resident rows / n_buckets)")


def parse_shard(spec: str) -> Optional[Tuple[int, int]]:
    """``"i/N"`` -> (i, N), or None for empty/unset.  Raises on nonsense —
    a typo'd shard assignment must fail the boot, not silently serve the
    wrong partition."""
    spec = (spec or "").strip()
    if not spec:
        return None
    try:
        idx_s, n_s = spec.split("/", 1)
        idx, n = int(idx_s), int(n_s)
    except ValueError:
        raise ValueError("ubodt shard must be 'i/N', got %r" % (spec,))
    if n < 1 or not 0 <= idx < n:
        raise ValueError("ubodt shard index out of range: %r" % (spec,))
    return idx, n


def shard_bucket_range(idx: int, n_shards: int,
                       n_buckets: int) -> Tuple[int, int]:
    """Contiguous bucket range [lo, hi) of shard ``idx`` of ``n_shards`` —
    the SAME partition function everywhere the table splits: the
    gp-sharded shard_map probe (each rank's local range starts at
    axis_index * L), the distributed builder's shard outputs, and the
    serving fleet's hot-set seeding."""
    if not 0 <= idx < n_shards:
        raise ValueError("shard %d/%d out of range" % (idx, n_shards))
    lo = idx * n_buckets // n_shards
    hi = (idx + 1) * n_buckets // n_shards
    return lo, hi


def cold_sharding(sharding, dev, owner: str):
    """(``sharding`` in the backend's ``pinned_host`` memory, that kind),
    or ``sharding`` unchanged with ``dev``'s default memory kind where the
    backend has no pinned host memory — host DRAM itself on the CPU
    backend; on an accelerator a capacity concession, logged.  The cold
    tiers of the UBODT and of the session arena both place through here;
    their pages reach a program only after an explicit move into device
    memory."""
    if any(m.kind == "pinned_host" for m in dev.addressable_memories()):
        return sharding.with_memory_kind("pinned_host"), "pinned_host"
    kind = dev.default_memory().kind
    if dev.platform != "cpu":
        log.warning("%s: backend %s lacks pinned_host memory; cold pages "
                    "are %s-resident", owner, dev.platform, kind)
    return sharding, kind


class TieredDeviceUBODT:
    """The device-side face of a tiered table: pytree whose leaves are the
    hot arena + slot map, with (bmask, layout, manager) as static aux —
    the jitted probes specialise on the manager identity exactly once per
    matcher, and a maintenance pass swaps leaf *contents* (same shapes)
    without recompiling.

    ``hot`` resolves through the manager for the long-lived instance the
    matcher holds (so maintenance is visible to the next dispatch), and
    holds the traced leaves for instances the tracer reconstructs.

    ``shard_axis`` names a mesh axis when the hot leaves are 1/N
    bucket-range slices inside a shard_map (parallel/rules.py: the tier
    shards by the SAME contiguous shard_bucket_range partition the fleet
    sharding uses, so each gp rank's local slot_map holds LOCAL slot ids
    into its local arena block and its local cold pages)."""

    def __init__(self, hot, bmask: int, layout: str, tier: "TieredTable",
                 shard_axis=None):
        self._hot = hot
        self.bmask = int(bmask)
        self.layout = layout
        self.tier = tier
        self.shard_axis = shard_axis

    @property
    def hot(self):
        return self._hot if self._hot is not None else self.tier._hot_dev

    @property
    def max_probes(self) -> int:
        return 1 if self.layout == "wide32" else 2

    @property
    def local_buckets(self) -> int:
        """Bucket count of THIS view's slot map — the full table, or the
        1/N local range inside a shard_map (the sharded prober's L)."""
        return self.hot[1].shape[0]

    def with_shard_axis(self, axis: str):
        return TieredDeviceUBODT(self._hot, self.bmask, self.layout,
                                 self.tier, shard_axis=axis)

    def tree_flatten(self):
        return ((self.hot,), (self.bmask, self.layout, self.tier,
                              self.shard_axis))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def _register_tiered():
    from jax import tree_util

    tree_util.register_pytree_node(
        TieredDeviceUBODT,
        lambda u: u.tree_flatten(),
        TieredDeviceUBODT.tree_unflatten,
    )


try:
    _register_tiered()
except ImportError:  # pragma: no cover - host-only usage without jax
    pass


class TieredTable:
    """Host-side manager of one tiered table: owns the full host pages,
    the EWMA scores, and the device arena/slot-map pair.

    Thread-safety: the stats callback runs on dispatch threads and
    ``maintain`` may run from it; both serialise on one lock.  The data
    path needs no locking at all — the host pages are immutable, and a
    dispatch that interleaves with an arena swap still reads correct rows
    from whichever (arena, slot_map) pair it captured (every arena row is
    a copy of its host page, so ANY consistent pair yields identical
    probe results)."""

    def __init__(self, ubodt: UBODT, hot_bytes: int,
                 shard: Optional[Tuple[int, int]] = None,
                 maintain_every: int = 8, ewma_decay: float = 0.8,
                 mesh=None, n_gp: int = 1):
        self.ubodt = ubodt
        self.hot_bytes = int(hot_bytes)
        self.shard = shard
        self.maintain_every = max(1, int(maintain_every))
        self.ewma_decay = float(ewma_decay)
        self.lanes = bucket_entries(ubodt.layout) * ROW_W
        self.n_buckets = ubodt.n_buckets
        # the replica's device mesh (parallel/rules.py): with a gp axis of
        # size n_gp the tier's leaves shard by contiguous bucket range —
        # each rank holds 1/n_gp of the slot map + cold pages and its OWN
        # hot arena block, so the per-chip budget multiplies into a
        # pod-level one.  A dp-only mesh replicates the leaves (the specs
        # resolve gp away), which is what GSPMD needs to keep the plain
        # jits SPMD.
        self.mesh = mesh
        self.n_gp = max(1, int(n_gp))
        if self.n_buckets % self.n_gp:
            raise ValueError(
                "UBODT bucket count %d not divisible by gp=%d (use a "
                "power-of-two gp axis)" % (self.n_buckets, self.n_gp))
        self.shard_len = self.n_buckets // self.n_gp
        # the host pages: the FULL packed table, rank-2 contiguous so the
        # cold-fetch fancy-index is one C-level gather
        self.pages = np.ascontiguousarray(
            ubodt.packed.reshape(self.n_buckets, self.lanes), np.int32)
        row_bytes = self.lanes * 4
        # hot capacity in bucket rows PER DEVICE (hot_bytes is the
        # per-chip budget; a gp mesh holds capacity rows on EACH rank);
        # a budget smaller than one row is a legal (if silly)
        # configuration — everything cold, output still bit-identical
        # (tests/test_tiering.py pins it)
        self.capacity = min(self.shard_len, self.hot_bytes // row_bytes)
        self._lock = threading.Lock()
        self._ewma = np.zeros(self.n_buckets, np.float64)
        self._counts = np.zeros(self.n_buckets, np.int64)
        self._dispatches_since_maintain = 0
        self._misses_since_maintain = 0
        # probe-stats pipeline: the debug.callback only PARKS its operand
        # handles (touching the runtime from callback context can
        # deadlock against a concurrent device fetch on the CPU client —
        # observed with tools/tiering_probe.py); this drain thread
        # converts and accumulates afterwards, the same dispatch-side /
        # collect-side split matcher._record_probe_stats uses.  Bounded:
        # under a stats backlog old samples drop, never dispatches.
        self._stats_q: "deque" = deque(maxlen=256)
        self._stats_wake = threading.Event()
        self._stats_thread = threading.Thread(
            target=self._stats_loop, daemon=True, name="ubodt-tier-stats")
        self._stats_thread.start()
        self._hot_set = np.zeros(0, np.int64)
        # seed: the replica's shard partition (as much of it as fits),
        # so a sharded fleet boots with its own bucket range resident.
        # Under a gp mesh every rank seeds the prefix of ITS bucket range
        # (intersected with the fleet shard when both are set) — the gp
        # partition IS a shard assignment, and booting with all ranks'
        # arenas resident is what the mesh-rehearsal /statusz asserts.
        if self.capacity > 0 and (shard is not None or self.n_gp > 1):
            if shard is not None:
                s_lo, s_hi = shard_bucket_range(shard[0], shard[1],
                                                self.n_buckets)
            else:
                s_lo, s_hi = 0, self.n_buckets
            parts = []
            for g in range(self.n_gp):
                lo = max(s_lo, g * self.shard_len)
                hi = min(s_hi, (g + 1) * self.shard_len)
                if lo < hi:
                    parts.append(np.arange(
                        lo, min(hi, lo + self.capacity), dtype=np.int64))
            if parts:
                self._hot_set = np.concatenate(parts)
        # the cold tier: the full pages as ONE immutable array leaf in
        # host memory where the backend offers it (TPU pinned_host = XLA
        # host offload; the CPU backend's default memory IS host DRAM)
        self._pages_dev, self.cold_memory_kind = self._put_pages()
        self._hot_dev = self._build_hot(self._hot_set)
        self._publish_gauges()
        log.info(
            "ubodt tiering: %d/%d bucket rows hot (%d B budget, %d B row, "
            "table %d B)%s", len(self._hot_set), self.n_buckets,
            self.hot_bytes, row_bytes, self.table_bytes,
            " shard %d/%d seeded" % shard if shard else "")

    @property
    def table_bytes(self) -> int:
        return self.n_buckets * self.lanes * 4

    def device(self) -> TieredDeviceUBODT:
        """The matcher-facing device table (hot leaves resolve live
        through this manager, so maintenance is visible to the next
        dispatch without re-plumbing)."""
        return TieredDeviceUBODT(None, self.ubodt.bmask, self.ubodt.layout,
                                 self)

    def _put_pages(self):
        """The cold pages as one array in host memory where the backend
        has it (XLA host offload: the bytes stay in host DRAM and device
        memory holds only the arena between misses)."""
        import jax

        if self.mesh is not None:
            dev = next(iter(self.mesh.devices.flat))
            sharding = self._leaf_sharding()
        else:
            dev = jax.devices()[0]
            sharding = jax.sharding.SingleDeviceSharding(dev)
        sharding, kind = cold_sharding(sharding, dev, "ubodt tiering")
        return jax.device_put(self.pages, sharding), kind

    def _leaf_sharding(self):
        """The rule table's placement for a tier leaf on this mesh:
        bucket-range over "gp" (axis 0) when the mesh carries that axis,
        replicated otherwise (parallel/rules.py: the du rule)."""
        from ..parallel.rules import sharding_for

        return sharding_for("du", self.mesh)

    def _build_hot(self, hot_set: np.ndarray):
        """(arena, slot_map) device arrays for a hot bucket set.  The
        arena always has >= 1 row so the hot-path gather's clamped index
        is in bounds even at capacity 0.

        Under a gp mesh the arena is laid out in n_gp equal per-rank
        blocks (rank g's hot rows at [g*rows, g*rows+len)) and the slot
        map holds LOCAL slot ids — inside the shard_map each rank sees
        exactly its own (arena block, slot-map range, page range) triple,
        and the contiguous block split IS shard_bucket_range."""
        import jax
        import jax.numpy as jnp

        if self.n_gp <= 1:
            arena = np.zeros((max(1, len(hot_set)), self.lanes), np.int32)
            if len(hot_set):
                arena[: len(hot_set)] = self.pages[hot_set]
            slot_map = np.full(self.n_buckets, -1, np.int32)
            slot_map[hot_set] = np.arange(len(hot_set), dtype=np.int32)
        else:
            rows = max(1, self.capacity)
            arena = np.zeros((rows * self.n_gp, self.lanes), np.int32)
            slot_map = np.full(self.n_buckets, -1, np.int32)
            L = self.shard_len
            for g in range(self.n_gp):
                mine = hot_set[(hot_set >= g * L)
                               & (hot_set < (g + 1) * L)][: self.capacity]
                arena[g * rows: g * rows + len(mine)] = self.pages[mine]
                slot_map[mine] = np.arange(len(mine), dtype=np.int32)
        if self.mesh is None:
            return jnp.asarray(arena), jnp.asarray(slot_map), self._pages_dev
        sh = self._leaf_sharding()
        return (jax.device_put(arena, sh), jax.device_put(slot_map, sh),
                self._pages_dev)

    # -- the stats side-channel (device program -> host) --------------------

    def _note(self, b, hot):
        """debug.callback target: park the probe's (buckets, hot-mask)
        handles for the drain thread.  MUST NOT touch the jax runtime
        (no np.asarray on device arrays) — callback context."""
        self._stats_q.append((b, hot))
        self._stats_wake.set()

    def _stats_loop(self) -> None:
        while True:
            self._stats_wake.wait()
            self._stats_wake.clear()
            try:
                self.drain_stats()
            except Exception:  # noqa: BLE001 - stats must never die
                log.exception("ubodt tier stats drain failed")

    def drain_stats(self) -> None:
        """Convert and accumulate every parked probe sample, then run a
        maintenance pass when one is due.  Runs on the drain thread;
        also callable directly (tests, the measurement harness) to make
        the counters deterministic at a sync point."""
        due = False
        while True:
            try:
                b, hot = self._stats_q.popleft()
            except IndexError:
                break
            b = np.asarray(b).reshape(-1)
            hot = np.asarray(hot).reshape(-1)
            # mask phantom samples: the gp-sharded probe reports remote
            # buckets as -1 (they are some OTHER rank's probes, counted
            # there), and any out-of-range id would corrupt the bincount
            keep = (b >= 0) & (b < self.n_buckets)
            n_hit = int(np.count_nonzero(hot & keep))
            n_miss = int(np.count_nonzero(keep)) - n_hit
            C_TIER_HITS.inc(n_hit)
            C_TIER_MISSES.inc(n_miss)
            with self._lock:
                self._counts += np.bincount(b[keep],
                                            minlength=self.n_buckets)
                self._dispatches_since_maintain += 1
                self._misses_since_maintain += n_miss
                due = due or (
                    self._misses_since_maintain > 0 and
                    self._dispatches_since_maintain >= self.maintain_every)
        if due:
            self.maintain()

    # -- maintenance --------------------------------------------------------

    def maintain(self) -> dict:
        """One admission/eviction pass: fold the window's probe counts
        into the EWMA, take the top-``capacity`` buckets as the new hot
        set, rebuild the arena, and publish it.  Under a gp mesh the
        selection runs independently per rank's bucket range (capacity
        rows EACH), so one rank's traffic storm cannot evict another
        rank's working set.  Returns counters (tests and /statusz)."""
        with self._lock:
            self._ewma *= self.ewma_decay
            self._ewma += self._counts
            self._counts[:] = 0
            self._dispatches_since_maintain = 0
            self._misses_since_maintain = 0
            if self.capacity <= 0:
                return {"hot_rows": 0, "admitted": 0, "evicted": 0}
            if self.n_gp <= 1:
                new_set = self._select_range(0, self.n_buckets,
                                             self._hot_set)
            else:
                L = self.shard_len
                new_set = np.concatenate([
                    self._select_range(
                        g * L, (g + 1) * L,
                        self._hot_set[(self._hot_set >= g * L)
                                      & (self._hot_set < (g + 1) * L)])
                    for g in range(self.n_gp)])
            evicted = int(np.count_nonzero(
                ~np.isin(self._hot_set, new_set)))
            admitted = int(np.count_nonzero(
                ~np.isin(new_set, self._hot_set)))
            if admitted or evicted or not len(self._hot_set):
                self._hot_set = new_set
                self._hot_dev = self._build_hot(new_set)
            C_TIER_EVICTIONS.inc(evicted)
            self._publish_gauges()
            return {"hot_rows": int(len(self._hot_set)),
                    "admitted": admitted, "evicted": evicted}

    def _select_range(self, lo: int, hi: int,
                      incumbent: np.ndarray) -> np.ndarray:
        """Top-``capacity`` buckets of [lo, hi) by EWMA (caller holds the
        lock).  Ties resolve to the lowest bucket index (stable, so an
        all-zero score keeps the seeded set ordering deterministic), and
        a probed bucket is never evicted for an unprobed one: zero-score
        winners yield to the range's incumbent hot set (the seeded shard
        must not churn out under zero traffic)."""
        n = hi - lo
        if self.capacity >= n:
            return np.arange(lo, hi, dtype=np.int64)
        top = np.argpartition(-self._ewma[lo:hi], self.capacity - 1)[
            : self.capacity]
        new_set = np.sort(top).astype(np.int64) + lo
        zero = self._ewma[new_set] <= 0.0
        n_zero = int(np.count_nonzero(zero))
        if n_zero and len(incumbent):
            keep_old = incumbent[~np.isin(incumbent, new_set)]
            fill = keep_old[:n_zero]
            new_set = np.sort(np.concatenate(
                [new_set[~zero],
                 new_set[zero][: n_zero - len(fill)],
                 fill])).astype(np.int64)
        return new_set

    def _publish_gauges(self) -> None:
        G_TIER_ROWS.set(len(self._hot_set))
        G_TIER_FRAC.set(len(self._hot_set) / max(1, self.n_buckets))

    # -- introspection ------------------------------------------------------

    def hot_buckets(self) -> np.ndarray:
        with self._lock:
            return self._hot_set.copy()

    def summary(self) -> dict:
        """The /statusz tier block (docs/http-api.md)."""
        with self._lock:
            hot_rows = int(len(self._hot_set))
        return {
            "hot_bytes": self.hot_bytes,
            "hot_bytes_total": self.hot_bytes * self.n_gp,
            "table_bytes": self.table_bytes,
            "n_buckets": self.n_buckets,
            "hot_rows": hot_rows,
            "capacity_rows": self.capacity,
            "capacity_rows_total": self.capacity * self.n_gp,
            "devices": self.n_gp,
            "residency_frac": round(hot_rows / max(1, self.n_buckets), 4),
            "layout": self.ubodt.layout,
            "cold_memory_kind": self.cold_memory_kind,
            "shard": ("%d/%d" % self.shard) if self.shard else None,
        }


def tiered_bucket_rows(u: TieredDeviceUBODT, b, valid=None):
    """One bucket-row fetch [..., lanes] through the two-tier path — the
    ops/hashtable ``_bucket_rows`` seam for tiered tables.

    The exact lax.cond full-width fallback pattern of the PR 5 dedup
    overflow: predicate = "every probed bucket is hot".  True: one arena
    gather, the cold pages are never touched.  False: the cold pages move
    EXPLICITLY into device memory (a gather takes all its operands from
    one memory space), the FULL bucket set gathers from them, and a
    per-element select keeps the arena rows where they exist.  Both sides
    produce identical bytes (arena rows are copies of the pages), so
    downstream selects — and therefore match output — are bit-identical
    to an untiered table.  The move stages the local page table in device
    memory for the length of the fallback, so the capacity bound is the
    page table's size, not only the arena's.  Probe-frequency accounting
    rides a park-only debug.callback OUTSIDE the data path.

    Under vmap (the carry/session seam transitions) the fetch is NOT
    vmapped as a cond — that would turn into a select over every operand,
    host pages included, which JAX refuses: a ``custom_vmap`` rule runs
    the fetch once over the batched indices instead (it is shape-
    polymorphic in the leading dims), so the fast-path skip holds there
    too.

    ``valid`` (None = all) marks which probes are real: under the
    gp-sharded probe remote buckets arrive clamped to local index 0 with
    valid=False — they must not force the cold fallback (they are some
    other rank's probes) and they report the -1 sentinel to the stats
    drain instead of polluting bucket 0's EWMA.  Stats carry GLOBAL
    bucket ids (local + axis_index * L), so the manager's counters mean
    the same thing sharded or not."""
    if valid is None:
        return _rows_all(u, b)
    return _rows_masked(u, b, valid)


def _tiered_rows(u: TieredDeviceUBODT, b, valid):
    import jax
    import jax.numpy as jnp

    from ..obs.attrib import stage

    arena, slot_map, pages = u.hot
    slot = slot_map[b]
    hot = slot >= 0
    with stage("tier-arena"):
        rows_hot = arena[jnp.where(hot, slot, 0)]
    b_stat = b
    if u.shard_axis is not None:
        b_stat = b + jax.lax.axis_index(u.shard_axis) * slot_map.shape[0]
    if valid is None:
        hot_eff = hot
        hot_stat = hot
    else:
        hot_eff = hot | ~valid
        b_stat = jnp.where(valid, b_stat, -1)
        hot_stat = hot & valid
    jax.debug.callback(u.tier._note, b_stat, hot_stat)

    def _all_hot(_):
        return rows_hot

    def _paged(_):
        with stage("tier-page"):
            rows_cold = jax.device_put(pages, jax.memory.Space.Device)[b]
        return jnp.where(hot[..., None], rows_hot, rows_cold)

    return jax.lax.cond(jnp.all(hot_eff), _all_hot, _paged, None)


def _batched_rule(fn):
    """custom_vmap rule: broadcast unbatched index operands and call
    ``fn`` once on the batch (tier leaves are never batched)."""
    def rule(axis_size, in_batched, u, *arrays):
        import jax
        import jax.numpy as jnp

        if any(jax.tree_util.tree_leaves(in_batched[0])):
            raise NotImplementedError("tiered table leaves cannot be vmapped")
        arrays = [a if bat else jnp.broadcast_to(a, (axis_size,) + a.shape)
                  for a, bat in zip(arrays, in_batched[1:])]
        return fn(u, *arrays), True
    return rule


def _make_fetches():
    from jax import custom_batching

    @custom_batching.custom_vmap
    def rows_all(u, b):
        return _tiered_rows(u, b, None)

    @custom_batching.custom_vmap
    def rows_masked(u, b, valid):
        return _tiered_rows(u, b, valid)

    rows_all.def_vmap(_batched_rule(rows_all))
    rows_masked.def_vmap(_batched_rule(rows_masked))
    return rows_all, rows_masked


try:
    _rows_all, _rows_masked = _make_fetches()
except ImportError:  # pragma: no cover - host-only usage without jax
    pass
