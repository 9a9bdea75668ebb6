"""Device-resident session arena: carried Viterbi beams as slot-mapped
HBM state (docs/performance.md "Device-resident session arenas"; ROADMAP
open item 2's "millions of concurrent vehicles per chip" made measurable).

The PR 12 session matcher answers at point latency but still round-trips
every carried beam host<->device on every step: ``_carry_batch`` uploads
[B, K] carry leaves before the dispatch and ``_carry_rows`` fetches the
successors after it — a per-point interconnect tax and a hard ceiling on
concurrent sessions per chip.  This module keeps the beams ON the device,
reusing the hot/cold shape of the PR 14 UBODT tiering
(tiles/tiering.py): a hot ``TraceCarry`` slab with leading [S] lives in
device memory and is addressed by slot index; idle beams page to
``pinned_host`` cold storage (XLA host offload where the backend has it —
the CPU backend's default memory IS host DRAM, so the fallback is the
semantically-identical twin); beams squeezed out of both tiers detach
into their handle as a plain host dict, which is exactly the
``SessionStore`` wire form.  A cold page never enters a program: its
promotion moves it explicitly into device memory first (no program mixes
memory spaces, which JAX 0.9 refuses).  ``ops/viterbi.session_step_arena`` gathers a
step's rows by slot, decodes, and scatters the successors back with the
slab DONATED — one in-place dispatch, zero per-step beam transfers.

The session plane stays jax-free by duck-typing: ``SessionState.carry``
may now hold an :class:`ArenaRef` instead of a host dict, and everything
that needs host bytes (checkpoint, export/handoff, drain) goes through
``carry_host`` — a counted readback of exactly the touched slot.  Slot
moves (promotion / demotion / spill) follow a probe-frequency EWMA, and
every maintenance move swaps whole array leaves of unchanged shape, so
the step programs never recompile (the tiering jit-cache-stability
contract).

Concurrency: ONE re-entrant ``lock`` serialises every slab access — the
dispatcher holds it across acquire -> dispatch -> slab swap (the donated
buffer is invalid the instant the step is enqueued, so a concurrent
reader must never see it), and the checkpoint/export readers take it for
their row reads.  Lock order is store-lock -> arena-lock; arena code
never calls back into the store.

Gather/scatter move f32/i32 leaves verbatim and a fresh slot decodes from
the same inactive carry ``_carry_batch`` builds for ``None`` rows, so the
arena path's wire output is bit-identical to the host-carry path — the
differential suite (tests/test_session_arena.py) pins it across kernels,
layouts, sparse on/off, and eviction churn; ``REPORTER_SESSION_ARENA=0``
reverts bit-for-bit.
"""

from __future__ import annotations

import logging
import threading
import warnings
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..obs import metrics as obs

log = logging.getLogger(__name__)

# arena flow counters (docs/observability.md "Sessions"): promotions =
# beams entering the hot slab (fresh uploads, cold-page promotions,
# handed-off dicts), evictions = beams leaving it (hot->cold demotions
# and cold->host spills), readbacks = device->host beam copies — the
# zero-per-step-transfer property the rehearsal gates is "readbacks stays
# flat under steady-state streaming; it grows only on checkpoint, drain,
# export, or spill".
C_ARENA_PROMOTIONS = obs.counter(
    "reporter_session_arena_promotions_total",
    "Carried beams promoted into the hot session-arena slab (fresh "
    "uploads, cold-page promotions, imported handoff beams)")
C_ARENA_EVICTIONS = obs.counter(
    "reporter_session_arena_evictions_total",
    "Carried beams demoted out of the hot session-arena slab (to "
    "pinned_host cold pages, or spilled to the host wire form)")
C_ARENA_READBACKS = obs.counter(
    "reporter_session_arena_readbacks_total",
    "Device->host beam readbacks from the session arena (checkpoint / "
    "export / drain / spill reads of touched slots — steady-state "
    "streaming performs none)")

# the EWMA decay per arena step tick: a session untouched for ~10 steps
# of other traffic has its frequency halved ~3 times — idle vehicles sink
# below active ones quickly without per-tick sweeps
_EWMA_DECAY = 0.8


class ArenaRef:
    """One session's handle into the arena — what ``SessionState.carry``
    holds while the beam is device-resident.  Duck-typed for the session
    plane: ``read()`` returns the host carry dict (a counted readback),
    ``free()`` releases the slot.  When the arena spills or frees the
    uuid, the beam detaches INTO the ref, so a handle captured before the
    move (an in-flight step's item, a popped session's wire read) still
    resolves to the exact bytes it referenced."""

    __slots__ = ("arena", "uuid", "_detached")

    def __init__(self, arena: "SessionArena", uuid: str):
        self.arena = arena
        self.uuid = uuid
        self._detached: Optional[dict] = None

    def read(self) -> Optional[dict]:
        if self._detached is not None:
            return self._detached
        return self.arena.read_uuid(self.uuid)

    def free(self) -> None:
        self.arena.free_uuid(self.uuid)


def carry_host(c) -> Optional[dict]:
    """The session plane's carry normaliser: a host dict (or None) from
    either carry representation.  Reading a live ref is a counted
    readback — callers are the checkpoint/export/drain/fallback paths."""
    if c is None or isinstance(c, dict):
        return c
    return c.read()


def carry_free(c) -> None:
    """Release a carry's arena slot if it holds one (no-op for host
    dicts/None).  Every removal site in the session store calls this so a
    dead session can never leak a slot."""
    if c is not None and not isinstance(c, dict):
        c.free()


class SessionArena:
    """The slot-mapped beam store: a hot ``TraceCarry`` slab (leading
    [S_hot]) in device memory, per-uuid cold pages in ``pinned_host``,
    and detach-on-spill into the refs.  All methods are safe under
    ``self.lock``; ``acquire_batch`` and the dispatcher's slab swap must
    run inside ONE ``with arena.lock:`` section."""

    def __init__(self, beam_k: int, hot_bytes: int = 0,
                 cold_bytes: int = 0, max_sessions: int = 65536,
                 mesh=None, devices: int = 1):
        import jax
        import jax.numpy as jnp

        from ..ops.viterbi import initial_carry_batch

        self.beam_k = int(beam_k)
        # the replica's device mesh (parallel/rules.py): the slab's slot
        # axis shards over "dp", so a replica's carried beams live in
        # POD-level HBM and the per-chip byte budget multiplies by the
        # local device count — adding chips raises the hot-slot ceiling
        # (docs/performance.md "One logical matcher per pod")
        self.mesh = mesh
        self.devices = max(1, int(devices))
        n_dp = 1
        if mesh is not None:
            from ..parallel.rules import BATCH_AXIS

            n_dp = mesh.shape.get(BATCH_AXIS, 1)
        # exact per-slot payload bytes: scores/edge/offset [K] at 4 B +
        # x/y/t/committed scalars at 4 B + active at 1 B — the same
        # field-width arithmetic SessionStore.resident_bytes uses
        self.slot_bytes = 12 * self.beam_k + 17
        cap = max(1, int(max_sessions))
        if hot_bytes and int(hot_bytes) > 0:
            budget = int(hot_bytes) * self.devices
            self.hot_slots = max(1, min(cap, budget // self.slot_bytes))
        else:
            self.hot_slots = cap
        # the sharded slab splits its slot axis evenly over dp ranks
        self.hot_slots = -(-self.hot_slots // n_dp) * n_dp
        if cold_bytes and int(cold_bytes) > 0:
            self.cold_slots = max(0, int(cold_bytes) // self.slot_bytes)
        else:
            self.cold_slots = 4 * self.hot_slots
        self.lock = threading.RLock()
        self._hot = jax.tree_util.tree_map(
            jnp.asarray, initial_carry_batch(self.hot_slots, self.beam_k))
        # uuid -> hot slot / cold page; slots free-listed so churn reuses
        # rows without ever changing a leaf shape (jit-cache stable)
        self._slot_of: Dict[str, int] = {}
        self._free: List[int] = list(range(self.hot_slots - 1, -1, -1))
        self._cold: Dict[str, object] = {}
        self._refs: Dict[str, ArenaRef] = {}
        # probe-frequency EWMA (the tiering promotion/demotion signal):
        # uuid -> (ewma, last tick); decay applies lazily at touch and at
        # victim scans, so idle sessions cost nothing
        self._freq: Dict[str, Tuple[float, int]] = {}
        self._tick = 0
        self.promotions = 0
        self.evictions = 0
        self.readbacks = 0
        from ..tiles.tiering import cold_sharding

        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from ..parallel.rules import sharding_for

            dev = next(iter(mesh.devices.flat))
            # the slab itself: slot axis over "dp" (the rules table's
            # ``slab`` row), committed so the plain jits run SPMD and the
            # shard_map builder sees matching layouts
            self._hot = jax.device_put(self._hot,
                                       sharding_for("slab", mesh))
            # single rows (promotions, handoff imports) replicate over the
            # mesh — a row committed to one chip cannot feed a jit whose
            # other operand spans eight
            self._default_sharding = NamedSharding(mesh, PartitionSpec())
        else:
            dev = jax.devices()[0]
            self._default_sharding = jax.sharding.SingleDeviceSharding(dev)
        # cold pages in pinned host memory where the backend has it
        # (the tiering placement rule)
        self._cold_sharding, self.cold_memory_kind = cold_sharding(
            self._default_sharding, dev, "session arena")
        # donated buffers the backend cannot reuse (CPU) warn per
        # dispatch; the donation is still correct, just not a win there
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        # single-row maintenance programs: slot index is traced, so every
        # promotion/demotion replays ONE compiled program per direction
        self._jit_set = jax.jit(
            lambda slab, row, j: jax.tree_util.tree_map(
                lambda s, r: s.at[j].set(r), slab, row),
            donate_argnums=(0,))
        self._jit_get = jax.jit(
            lambda slab, j: jax.tree_util.tree_map(lambda s: s[j], slab))
        log.info(
            "session arena: %d hot slots (%d B budget, %d B/slot), %d cold "
            "pages (%s)", self.hot_slots,
            self.hot_slots * self.slot_bytes, self.slot_bytes,
            self.cold_slots, self.cold_memory_kind)

    # -- handles -------------------------------------------------------------

    def ref_for(self, uuid: str) -> ArenaRef:
        with self.lock:
            r = self._refs.get(uuid)
            if r is None or r._detached is not None:
                r = self._refs[uuid] = ArenaRef(self, uuid)
            return r

    # -- the EWMA ------------------------------------------------------------

    def _eff_freq(self, uuid: str) -> float:
        f = self._freq.get(uuid)
        if f is None:
            return 0.0
        ewma, last = f
        return ewma * (_EWMA_DECAY ** max(0, self._tick - last))

    def _touch(self, uuid: str) -> None:
        self._freq[uuid] = (self._eff_freq(uuid) + 1.0, self._tick)

    # -- row plumbing --------------------------------------------------------

    def _row_from_dict(self, c: dict):
        import jax.numpy as jnp

        from ..ops.viterbi import TraceCarry

        return TraceCarry(
            scores=jnp.asarray(c["scores"], jnp.float32),
            edge=jnp.asarray(c["edge"], jnp.int32),
            offset=jnp.asarray(c["offset"], jnp.float32),
            x=jnp.float32(c["x"]), y=jnp.float32(c["y"]),
            t=jnp.float32(c["t"]),
            active=jnp.asarray(bool(c["active"])),
            committed=jnp.int32(c["committed"]),
        )

    @staticmethod
    def _dict_from_row(row) -> dict:
        return {
            "scores": np.asarray(row.scores),
            "edge": np.asarray(row.edge),
            "offset": np.asarray(row.offset),
            "x": np.asarray(row.x)[()], "y": np.asarray(row.y)[()],
            "t": np.asarray(row.t)[()],
            "active": bool(np.asarray(row.active)),
            "committed": np.asarray(row.committed)[()],
        }

    def _set_row_locked(self, slot: int, row) -> None:
        import jax.numpy as jnp

        self._hot = self._jit_set(self._hot, row, jnp.int32(slot))

    def _victim_locked(self, pinned) -> Optional[str]:
        """The lowest-effective-frequency hot uuid outside ``pinned`` —
        an O(hot) scan, paid only when the slab is full."""
        best_u, best_f = None, None
        for u in self._slot_of:
            if u in pinned:
                continue
            f = self._eff_freq(u)
            if best_f is None or f < best_f:
                best_u, best_f = u, f
        return best_u

    def _spill_cold_locked(self) -> None:
        """Detach the coldest cold page into its ref (host wire form) —
        the arena's floor tier is the SessionStore itself."""
        best_u, best_f = None, None
        for u in self._cold:
            f = self._eff_freq(u)
            if best_f is None or f < best_f:
                best_u, best_f = u, f
        if best_u is None:
            return
        row = self._cold.pop(best_u)
        ref = self._refs.get(best_u)
        if ref is not None:
            ref._detached = self._dict_from_row(row)
            self.readbacks += 1
            C_ARENA_READBACKS.inc()
            self._refs.pop(best_u, None)
        self._freq.pop(best_u, None)
        self.evictions += 1
        C_ARENA_EVICTIONS.inc()

    def _demote_locked(self, uuid: str) -> None:
        """hot -> cold: move one beam to a pinned_host page (or straight
        to a host detach when the cold tier is disabled/full-and-smaller)."""
        import jax

        slot = self._slot_of.pop(uuid)
        row = self._jit_get(self._hot, np.int32(slot))
        self._free.append(slot)
        if self.cold_slots > 0:
            if len(self._cold) >= self.cold_slots:
                self._spill_cold_locked()
            if len(self._cold) < self.cold_slots:
                self._cold[uuid] = jax.device_put(row, self._cold_sharding)
                self.evictions += 1
                C_ARENA_EVICTIONS.inc()
                return
        ref = self._refs.get(uuid)
        if ref is not None:
            ref._detached = self._dict_from_row(row)
            self.readbacks += 1
            C_ARENA_READBACKS.inc()
            self._refs.pop(uuid, None)
        self._freq.pop(uuid, None)
        self.evictions += 1
        C_ARENA_EVICTIONS.inc()

    def _alloc_slot_locked(self, pinned) -> Optional[int]:
        if self._free:
            return self._free.pop()
        victim = self._victim_locked(pinned)
        if victim is None:
            return None
        self._demote_locked(victim)
        return self._free.pop()

    # -- the dispatcher's surface -------------------------------------------

    def acquire_batch(self, entries):
        """Resolve one dispatch group's (uuid, carry_in) pairs to hot
        slots.  MUST be called (and the subsequent dispatch + ``swap_hot``
        performed) under ``with arena.lock:``.

        carry_in is whatever the SessionEngine captured at item-build
        time: None (fresh/rebuild — the slot decodes from the inactive
        carry), a host dict (an imported handoff beam, or a beam from a
        previous matcher's arena), or an :class:`ArenaRef`.  Returns
        ``(slots, use_carry, refs)`` — parallel lists — or None when the
        group cannot fit the hot slab at once (the caller falls back to
        the host-carry path for the whole group, bit-identical either
        way)."""
        if len(entries) > self.hot_slots:
            return None
        self._tick += 1
        pinned = {u for u, _c in entries}
        slots: List[int] = []
        use: List[bool] = []
        refs: List[ArenaRef] = []
        for uuid, c in entries:
            if isinstance(c, ArenaRef) and c.arena is self \
                    and c._detached is None:
                slot = self._slot_of.get(uuid)
                if slot is None:
                    cold = self._cold.pop(uuid, None)
                    if cold is not None:
                        import jax

                        slot = self._alloc_slot_locked(pinned)
                        assert slot is not None
                        self._set_row_locked(
                            slot, jax.device_put(cold,
                                                 self._default_sharding))
                        self._slot_of[uuid] = slot
                        self.promotions += 1
                        C_ARENA_PROMOTIONS.inc()
                if slot is None:
                    # the ref went stale (freed between item-build and
                    # dispatch — the session itself is gone); decode
                    # fresh exactly like a carry-less step
                    slot = self._alloc_slot_locked(pinned)
                    assert slot is not None
                    self._slot_of[uuid] = slot
                    use.append(False)
                else:
                    slot = self._slot_of[uuid]
                    use.append(True)
            else:
                host = carry_host(c) if c is not None else None
                slot = self._slot_of.get(uuid)
                if slot is None:
                    self._cold.pop(uuid, None)
                    slot = self._alloc_slot_locked(pinned)
                    assert slot is not None
                    self._slot_of[uuid] = slot
                if host is not None:
                    self._set_row_locked(slot, self._row_from_dict(host))
                    self.promotions += 1
                    C_ARENA_PROMOTIONS.inc()
                    use.append(True)
                else:
                    use.append(False)
            self._touch(uuid)
            slots.append(slot)
            refs.append(self.ref_for(uuid))
        return slots, use, refs

    @property
    def hot(self):
        """The live hot slab (read under ``lock``; donated by the step)."""
        return self._hot

    def swap_hot(self, slab) -> None:
        """Install the step's scattered-successor slab (under ``lock``,
        immediately after the dispatch that donated the old one)."""
        self._hot = slab

    # -- host reads / frees --------------------------------------------------

    def read_uuid(self, uuid: str) -> Optional[dict]:
        """One beam's host dict — the counted readback behind checkpoint
        / export / drain / fallback reads.  Blocks on the in-flight step
        if the slab is still computing (correct: the slot's bytes are the
        committed successors)."""
        with self.lock:
            slot = self._slot_of.get(uuid)
            if slot is not None:
                row = self._jit_get(self._hot, np.int32(slot))
            else:
                row = self._cold.get(uuid)
                if row is None:
                    ref = self._refs.get(uuid)
                    return ref._detached if ref is not None else None
            out = self._dict_from_row(row)
            self.readbacks += 1
            C_ARENA_READBACKS.inc()
            return out

    def free_uuid(self, uuid: str, detach: bool = True) -> None:
        """Release a uuid's residency.  The beam detaches into the live
        ref first (one readback) so handles captured before the free —
        an in-flight step's item, a popped session about to serialise —
        still resolve to the exact bytes.  ``detach=False`` skips that
        (warmup's throwaway slots)."""
        with self.lock:
            ref = self._refs.get(uuid)
            if detach and ref is not None and ref._detached is None:
                detached = self.read_uuid(uuid)
                if detached is not None:
                    ref._detached = detached
            slot = self._slot_of.pop(uuid, None)
            if slot is not None:
                self._free.append(slot)
            self._cold.pop(uuid, None)
            self._refs.pop(uuid, None)
            self._freq.pop(uuid, None)

    # -- accounting ----------------------------------------------------------

    def tier_counts(self) -> Dict[str, int]:
        with self.lock:
            return {"hot": len(self._slot_of), "cold": len(self._cold)}

    def summary(self) -> dict:
        with self.lock:
            return {
                "hot_slots": self.hot_slots,
                "hot_used": len(self._slot_of),
                "cold_slots": self.cold_slots,
                "cold_used": len(self._cold),
                "slot_bytes": self.slot_bytes,
                "hot_bytes": self.hot_slots * self.slot_bytes,
                "cold_bytes": len(self._cold) * self.slot_bytes,
                "cold_memory_kind": self.cold_memory_kind,
                "devices": self.devices,
                # per-chip views: the slab is sharded, so a chip holds
                # 1/devices of the slots/bytes (the gauge-semantics
                # contract in obs/economics.py)
                "hot_slots_per_chip": self.hot_slots // self.devices,
                "hot_bytes_per_chip":
                    self.hot_slots * self.slot_bytes // self.devices,
                "promotions": self.promotions,
                "evictions": self.evictions,
                "readbacks": self.readbacks,
            }
