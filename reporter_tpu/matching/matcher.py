"""SegmentMatcher: the public matching API.

Wire-compatible with the valhalla extension the reference calls
(reporter_service.py:52,240: ``SegmentMatcher().Match(json) -> json``), plus
the micro-batch entry point ``match_many`` that the /trace_attributes_batch
endpoint and the batch pipeline feed with many traces at once — that is where
the TPU earns its keep: traces are bucketed by length, padded, stacked
[B, T] and matched in one vmapped device program.

Backends:
  jax  -- candidates/emission/transition/Viterbi on device (ops/)
  cpu  -- pure numpy+Dijkstra oracle (baseline/cpu_matcher.py), same host
          post-processing, used for segment-for-segment diffing
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time as _time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import faults
from ..obs import attrib as obs_attrib
from ..obs import log as obs_log
from ..obs import metrics as obs
from ..obs import trace as obs_trace
from ..tiles.arrays import GraphArrays, build_graph_arrays
from ..tiles.network import RoadNetwork
from ..tiles.ubodt import UBODT, build_ubodt
from . import columnar
from .assoc_native import associate_segments_batch
from .config import MatcherConfig
from .sparse import (
    C_SPARSE_DISPATCH, C_SPARSE_POINTS, SparseModel, associate_interpolated,
    clamp_radius,
)

log = logging.getLogger(__name__)

# compile visibility (docs/observability.md): the jitted kernels compile
# once per padded (B, T) shape, and a shape-set regression shows up as
# nothing BUT compile stalls — invisible in throughput aggregates.  A
# "compile" here is the first dispatch of a shape: that call blocks on XLA
# tracing+compilation, so its wall time is the stall a request actually saw
# (with the persistent compilation cache it is the cache-replay cost).
C_COMPILES = obs.counter(
    "reporter_compile_total",
    "First-dispatch (compiling) device calls per padded shape bucket",
    ("shape", "kernel"))
C_COMPILE_S = obs.counter(
    "reporter_compile_seconds_total",
    "Wall seconds spent blocked in first-dispatch (compiling) calls",
    ("shape", "kernel"))
# every XLA executable this process builds (jit-cache misses, eager ops
# and persistent-cache loads alike), counted from JAX's own compile event:
# after serve --warmup it must stay flat while requests are answered
C_XLA_COMPILES = obs.counter(
    "reporter_xla_compiles_total",
    "XLA executables built by this process (JAX backend-compile events, "
    "persistent-cache loads included); flat after serve --warmup = no "
    "request compiled")
_COMPILE_LISTENER_LOCK = threading.Lock()
_COMPILE_LISTENER: list = []


def _count_xla_compiles() -> None:
    """Register the process-wide JAX listener behind C_XLA_COMPILES once."""
    import jax

    with _COMPILE_LISTENER_LOCK:
        if _COMPILE_LISTENER:
            return

        def _on_event(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                C_XLA_COMPILES.inc()

        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _COMPILE_LISTENER.append(_on_event)
C_DISPATCHES = obs.counter(
    "reporter_dispatch_total",
    "Device batch dispatches by viterbi kernel (scan / assoc)",
    ("kernel",))
C_DISPATCH_COHORT = obs.counter(
    "reporter_dispatch_cohort_total",
    "Device dispatches by trace cohort (bucketed = length-bucket batches, "
    "long = carry-chain groups, session = per-vehicle incremental steps) "
    "and program kind (compact / pre / chain / carry / step; "
    "docs/performance.md)",
    ("cohort", "kind"))
# UBODT probe engagement (ops/hashtable.ubodt_lookup_steps): every valid
# step of a batched precompute, by the path that answered it; the program
# returns the counts beside its result and they are counted at its fetch
C_PROBE_STEPS = obs.counter(
    "reporter_ubodt_probe_steps_total",
    "Valid HMM steps of the batched programs by the UBODT probe path that "
    "answered them: compact (the step's distinct node pairs), overflow "
    "(the dispatch's K x K lane) or full (the whole dispatch at K x K)",
    ("path",))
C_WARM_SHAPES = obs.counter(
    "reporter_warmup_shapes_total",
    "Shapes pre-dispatched by warmup, by viterbi kernel",
    ("kernel",))
C_WARM_S = obs.counter(
    "reporter_warmup_seconds_total",
    "Wall seconds spent in warmup pre-dispatch passes")
C_TRACES = obs.counter(
    "reporter_traces_matched_total", "Traces run through host association")
C_POINTS = obs.counter(
    "reporter_points_matched_total", "Valid trace points run through host association")
C_BREAKS = obs.counter(
    "reporter_transition_breaks_total",
    "Points flagged as HMM discontinuities (includes window starts)")


def _count_probe_steps(counts) -> None:
    """Add fetched [rows, 3] probe-path step counts (one per dispatch) to
    C_PROBE_STEPS; pad rows hold no valid step."""
    from ..ops.hashtable import PROBE_PATHS

    tot = np.zeros(3, np.int64)
    for c in counts:
        tot += np.asarray(c).sum(axis=0)
    for path, n in zip(PROBE_PATHS, tot.tolist()):
        if n:
            C_PROBE_STEPS.labels(path).inc(n)


# chunks allowed in flight on the device while the host associates earlier
# ones.  Each in-flight chunk pins its packed input + result,
# (16 + 12) * max_device_points bytes <= ~3.7 MB at the default budget, so 8
# bounds pinned transport memory at ~30 MB per match_many call — and the
# MicroBatcher's composite worst case is (max_inflight + 2) * depth chunks
# (~178 MB at its defaults; see serve/service.py), which must fit HBM
# headroom next to the graph + UBODT.  Depth matters doubly on deployments
# with a fixed per-sync cost: a fleet whose chunk count fits the depth
# dispatches entirely before the first blocking fetch, so the whole batch
# pays one sync quantum instead of one per early drain.
PIPELINE_DEPTH = 8

# long-trace streaming: chunk results allowed to accumulate on device before
# a concat+fetch wave.  Each deferred chunk pins its packed output
# (12*B_pad*W bytes) PLUS its queued packed input (16*B_pad*W bytes) until
# the wave flushes — ~3.7 MB per chunk at the default max_device_points
# budget, so 64 bounds the deferred pool at ~235 MB while keeping the
# host-sync count at one per wave rather than one per chunk.
MAX_DEFERRED_CHUNKS = 64


def _pad_rows(pad: int, *arrays):
    """Append ``pad`` all-zero (= all-invalid) rows to each [B, ...] array."""
    return tuple(
        np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)]) for a in arrays
    )


# One collective-bearing mesh execution in flight at a time, PROCESS-wide:
# the CPU backend runs a virtual mesh's cross-module collectives through an
# in-process thread rendezvous, and two concurrently-executing sharded
# programs can each park half their participant threads at the other's
# rendezvous — a deadlock the async dispatch pipeline makes a matter of
# time (observed 2026-08-07: bench's pipelined mesh leg wedged in an
# AllGather after ~2000 clean runs; a real accelerator's hardware
# collectives and strict per-device stream order cannot interleave this
# way).  The lock is module-level because two matchers in one process
# (serve's windowed + session batchers) share the same device threads.
_MESH_CPU_DISPATCH_LOCK = threading.Lock()


class _SerialDispatch:
    """Wraps a jitted mesh program so each call dispatches under the
    process-wide lock and blocks until ready before releasing it —
    serialising collective-bearing executions on the CPU virtual mesh.
    Attribute access (``.lower``, AOT inspection) passes through."""

    def __init__(self, fn):
        self._fn = fn

    def __call__(self, *args, **kwargs):
        import jax

        with _MESH_CPU_DISPATCH_LOCK:
            return jax.block_until_ready(self._fn(*args, **kwargs))

    def __getattr__(self, name):
        return getattr(self._fn, name)


class SegmentMatcher:
    def __init__(
        self,
        network: Optional[RoadNetwork] = None,
        config: Optional[MatcherConfig] = None,
        backend: str = "jax",
        arrays: Optional[GraphArrays] = None,
        ubodt: Optional[UBODT] = None,
    ):
        self.cfg = config or MatcherConfig()
        if arrays is None:
            if network is None:
                raise ValueError("need a network or prebuilt arrays")
            arrays = build_graph_arrays(
                network, cell_size=max(100.0, 2.0 * self.cfg.search_radius)
            )
        if arrays.cell_size < 2.0 * self.cfg.search_radius:
            raise ValueError(
                "spatial grid cell_size %.1f < 2*search_radius %.1f: the 2x2 "
                "quadrant candidate sweep (ops/candidates.py) would miss "
                "candidates; rebuild the grid with a larger cell_size"
                % (arrays.cell_size, 2.0 * self.cfg.search_radius)
            )
        self.arrays = arrays
        # UBODT memory layout + in-batch probe dedup (docs/performance.md
        # "The UBODT memory system").  $REPORTER_UBODT_LAYOUT /
        # $REPORTER_PROBE_DEDUP override the config; a prebuilt table whose
        # layout differs from the resolved one is repacked in place (row
        # extraction + re-hash, no graph re-search).
        env_layout = os.environ.get("REPORTER_UBODT_LAYOUT", "").strip().lower()
        self._ubodt_layout = env_layout or getattr(
            self.cfg, "ubodt_layout", "cuckoo") or "cuckoo"
        if self._ubodt_layout not in ("cuckoo", "wide32"):
            raise ValueError(
                "REPORTER_UBODT_LAYOUT/ubodt_layout must be cuckoo|wide32, "
                "got %r" % (self._ubodt_layout,))
        env_dd = os.environ.get("REPORTER_PROBE_DEDUP", "").strip().lower()
        if env_dd:
            self._probe_dedup = env_dd not in ("0", "false", "off", "no")
        else:
            self._probe_dedup = bool(getattr(self.cfg, "probe_dedup", False))
        if ubodt is None:
            ubodt = build_ubodt(arrays, delta=self.cfg.ubodt_delta,
                                layout=self._ubodt_layout)
        elif getattr(ubodt, "layout", "cuckoo") != self._ubodt_layout:
            ubodt = ubodt.relayout(self._ubodt_layout)
        self.ubodt = ubodt
        # hot/cold tiering + fleet shard assignment (docs/performance.md
        # "Continent-scale data plane"): $REPORTER_UBODT_HOT_BYTES > 0
        # keeps only a hot-bucket arena device-resident (host-paged cold
        # rows, bit-identical output); $REPORTER_UBODT_SHARD="i/N" seeds
        # that arena with this replica's bucket-range partition
        env_hot = os.environ.get("REPORTER_UBODT_HOT_BYTES", "").strip()
        try:
            self._ubodt_hot_bytes = int(env_hot) if env_hot else int(
                getattr(self.cfg, "ubodt_hot_bytes", 0) or 0)
        except ValueError:
            raise ValueError(
                "REPORTER_UBODT_HOT_BYTES must be an integer byte count, "
                "got %r" % (env_hot,))
        from ..tiles.tiering import parse_shard

        self.ubodt_shard = parse_shard(
            os.environ.get("REPORTER_UBODT_SHARD", "").strip()
            or getattr(self.cfg, "ubodt_shard", "") or "")
        self.tiering = None
        self.backend = backend
        # viterbi forward selection (docs/performance.md): scan = sequential
        # lax.scan (O(T) depth), assoc = log-depth associative max-plus scan,
        # auto = pick per padded bucket length against the measured
        # crossover.  $REPORTER_VITERBI overrides the config.
        env_kernel = os.environ.get("REPORTER_VITERBI", "").strip().lower()
        self._kernel_mode = env_kernel or getattr(
            self.cfg, "viterbi_kernel", "scan") or "scan"
        if self._kernel_mode not in ("scan", "assoc", "auto"):
            raise ValueError(
                "REPORTER_VITERBI/viterbi_kernel must be scan|assoc|auto, "
                "got %r" % (self._kernel_mode,))
        self._assoc_threshold = int(
            getattr(self.cfg, "viterbi_assoc_threshold", 256))
        # long-trace carry chain: hoisted chunk-batched precompute (default)
        # vs the legacy fused per-chunk program.  $REPORTER_LONG_PRECOMPUTE
        # overrides the config for differential testing / rollback.
        env_lp = os.environ.get("REPORTER_LONG_PRECOMPUTE", "").strip().lower()
        if env_lp:
            self._long_pre = env_lp not in ("0", "false", "off", "no")
        else:
            self._long_pre = bool(getattr(self.cfg, "long_precompute", True))
        # kernel confidence diagnostics (docs/match-quality.md): when on,
        # dispatches route through the *_aux packed programs and every
        # match result carries a "_quality" block (per-point edges,
        # winner-vs-runner-up margins, pool-exhaustion fraction).  Off by
        # default — library callers and the bit-exact differential suites
        # must see byte-identical results; serve turns it on.
        env_qa = os.environ.get("REPORTER_QUALITY_AUX", "").strip().lower()
        if env_qa:
            self._quality_aux = env_qa not in ("0", "false", "off", "no")
        else:
            self._quality_aux = bool(getattr(self.cfg, "quality_aux", False))
        # sparse-gap matching model (docs/match-quality.md "Sparse gaps"):
        # traces at the reference BatchingProcessor's sparse operating
        # point dispatch through the time-adaptive "sparse" program
        # variants with per-cohort (optionally CALIBRATION.json-pinned)
        # parameters.  Off by default — the dense programs, the bit-exact
        # differential suites, and PR 14 wire output are untouched; the
        # serve entrypoint enables it ($REPORTER_SPARSE=0 reverts).
        self.sparse = SparseModel(self.cfg, arrays.cell_size)
        # device-resident session arena (docs/performance.md
        # "Device-resident session arenas"): carried session beams live
        # in a hot HBM slab (+ pinned_host cold pages) and the packed
        # step gathers/scatters by slot with the slab donated — zero
        # per-step host<->device beam transfers.  Off by default (the
        # host-carry wire output is the differential reference); the
        # serve entrypoint enables it ($REPORTER_SESSION_ARENA=0
        # reverts bit-for-bit).
        env_ar = os.environ.get("REPORTER_SESSION_ARENA", "").strip().lower()
        if env_ar:
            self._session_arena_on = env_ar not in ("0", "false", "off", "no")
        else:
            self._session_arena_on = bool(
                getattr(self.cfg, "session_arena", False))
        self.session_arena = None
        # columnar host packing (docs/performance.md "The columnar host
        # data plane"): match_many/session batches pack through the
        # vectorized matching/columnar.py plane — one column extraction
        # per call, one fancy-indexed scatter per group — instead of the
        # per-trace Python loop.  Bit-identical output either way (the
        # packer equivalence suite pins it); on by default, and
        # $REPORTER_HOST_PACK=0 reverts to the legacy loop as the
        # differential reference.
        env_hp = os.environ.get("REPORTER_HOST_PACK", "").strip().lower()
        if env_hp:
            self._host_pack = env_hp not in ("0", "false", "off", "no")
        else:
            self._host_pack = bool(getattr(self.cfg, "host_pack", True))
        # route-consistent interpolation default (per-request
        # match_options.interpolate overrides either way)
        env_ip = os.environ.get("REPORTER_INTERPOLATE", "").strip().lower()
        if env_ip:
            self._interpolate = env_ip not in ("0", "false", "off", "no")
        else:
            self._interpolate = bool(getattr(self.cfg, "interpolate", False))
        # per-request MatchParams (ROADMAP open item 4's tuning surface):
        # the reference wire contract's sigma_z / beta / search_radius /
        # gps_accuracy ride match_options; MatchParams are traced scalars,
        # so a custom value is the SAME compiled program with different
        # inputs — requests group by effective-params key and dispatch as
        # separate batches, no recompile.  Bounded caches.
        self._params_cache: Dict[tuple, object] = {}
        self._cpu_params_cache: Dict[tuple, object] = {}
        # per-(B_pad,...) pinned staging buffers for batch-dimension padding:
        # the dp-remainder and ladder pads run on every dispatch, and a fresh
        # np.concatenate per call reallocated (and re-faulted) the same
        # megabytes each time.  Dispatches are single-threaded per matcher
        # (the MicroBatcher's one worker / the batch driver), and every
        # consumer copies out synchronously (pack_inputs / the cpu oracle),
        # so reuse is safe.
        self._staging: Dict[tuple, np.ndarray] = {}
        # first-dispatch shape tracking for the compile counters
        self._compiled_shapes: set = set()
        if backend == "jax":
            self._init_jax()
        elif backend == "cpu":
            self._init_cpu()
        else:
            raise ValueError("unknown backend %r" % (backend,))

    # -- backends ----------------------------------------------------------

    def _init_jax(self):
        import jax

        from ..ops.viterbi import MatchParams

        _count_xla_compiles()
        self._dg = self.arrays.to_device()
        self._params = MatchParams.from_config(self.cfg)

        # device mesh FIRST (docs/performance.md "One logical matcher per
        # pod"): the tiered UBODT arena, the table placement, and the
        # session arena all size and shard against it, so it must exist
        # before any of them.  With cfg.devices > 1 the graph/params live
        # replicated over the mesh and every batch array is device_put with
        # a dp sharding before dispatch — computation follows data, so the
        # same jits below run SPMD across chips with XLA inserting the
        # collectives.  This is the TPU equivalent of the reference scaling
        # by Kafka partitions (README.md:169-173).  With cfg.graph_devices
        # > 1 the mesh gains a gp axis: the UBODT table lives in 1/gp
        # bucket-range slices per chip (HBM scaling for region tables
        # bigger than one chip) and every program runs under the generic
        # shard_map builder (_build_program) so probes resolve with
        # pmin/pmax over the ICI (ops/hashtable._ubodt_lookup_sharded).
        # Which sharding each program argument gets is the
        # parallel/rules.py table's single decision — NOT per-call-site
        # hand lists.
        self._mesh = None
        self._batch_sharding = None
        self._carry_sharding = None
        # REPORTER_DEVICES / REPORTER_GRAPH_DEVICES override the config
        # (the serve-tier env convention): the mesh-rehearsal leg forces
        # an 8-virtual-device replica onto a stock config this way.
        # Written back into cfg so capacity_summary, the economics
        # ledger, and /health all see the resolved topology.
        for env_key, field_name in (("REPORTER_DEVICES", "devices"),
                                    ("REPORTER_GRAPH_DEVICES",
                                     "graph_devices")):
            raw = os.environ.get(env_key, "").strip()
            if raw:
                try:
                    setattr(self.cfg, field_name, int(raw))
                except ValueError:
                    raise ValueError("%s must be an integer device count, "
                                     "got %r" % (env_key, raw))
        n_total = max(1, int(self.cfg.devices))
        self._n_gp = max(1, int(self.cfg.graph_devices))
        if n_total & (n_total - 1) or self._n_gp & (self._n_gp - 1):
            raise ValueError(
                "cfg.devices/graph_devices must be powers of two, got %d/%d"
                % (n_total, self._n_gp))
        if n_total % self._n_gp:
            raise ValueError("cfg.graph_devices=%d must divide devices=%d"
                             % (self._n_gp, n_total))
        self._n_dp = n_total // self._n_gp
        if n_total > 1 or self._n_gp > 1:
            from ..parallel.mesh import (
                check_ubodt_shardable, make_mesh, make_mesh2,
            )
            from ..parallel.rules import sharding_for

            if self._n_gp > 1:
                check_ubodt_shardable(self.ubodt, self._n_gp)
                self._mesh = make_mesh2(self._n_dp, self._n_gp)
            else:
                self._mesh = make_mesh(self._n_dp)
            # packed [4, B, T] batch arrays shard over axis 1; carry pytrees
            # (leading [B]) over axis 0 — the rule table's xin/carry rows
            self._batch_sharding = sharding_for("xin", self._mesh)
            self._carry_sharding = sharding_for("carry", self._mesh)
            self._dg = jax.device_put(self._dg, sharding_for("dg", self._mesh))
            self._params = jax.device_put(
                self._params, sharding_for("p", self._mesh))
        # CPU virtual meshes serialise program dispatch (_SerialDispatch:
        # the in-process collective rendezvous deadlocks under concurrent
        # sharded executions); REPORTER_MESH_SERIAL=0/1 overrides the
        # platform default for diagnosis
        env_ms = os.environ.get("REPORTER_MESH_SERIAL", "").strip().lower()
        if env_ms in ("0", "false", "no", "off"):
            self._serial_dispatch = False
        elif env_ms in ("1", "true", "yes", "on"):
            self._serial_dispatch = self._mesh is not None
        else:
            self._serial_dispatch = (
                self._mesh is not None
                and jax.devices()[0].platform == "cpu")
        if self._ubodt_hot_bytes > 0:
            # tiered table: hot-bucket arena on device, cold rows paged
            # from host behind the lax.cond full-width fallback
            # (tiles/tiering.py; output bit-identical to the resident
            # table).  On a gp mesh the arena/slot-map/pages shard by the
            # SAME contiguous-bucket partition the sharded probe uses, so
            # hot_bytes is a PER-CHIP budget and adding gp ranks multiplies
            # the resident set.
            from ..tiles.tiering import TieredTable

            self.tiering = TieredTable(
                self.ubodt, self._ubodt_hot_bytes, shard=self.ubodt_shard,
                mesh=self._mesh, n_gp=self._n_gp)
            self._du = self.tiering.device()
        else:
            self._du = self.ubodt.to_device()
            if self._mesh is not None:
                from ..parallel.rules import sharding_for

                self._du = jax.device_put(
                    self._du, sharding_for("du", self._mesh))
        # device-resident session arena: on a mesh the beam slab's slot
        # axis shards over dp (parallel/rules.py "slab"), so the
        # per-chip byte budget multiplies into pod-level HBM and the
        # donated in-place gather/scatter contract survives intact
        # (ops/viterbi.session_step_arena_mesh)
        if self._session_arena_on:
            from .arena import SessionArena

            env_b = os.environ.get(
                "REPORTER_SESSION_ARENA_BYTES", "").strip()
            env_cb = os.environ.get(
                "REPORTER_SESSION_ARENA_COLD_BYTES", "").strip()
            try:
                hot_b = int(env_b) if env_b else int(
                    getattr(self.cfg, "session_arena_bytes", 0) or 0)
                cold_b = int(env_cb) if env_cb else int(
                    getattr(self.cfg, "session_arena_cold_bytes", 0)
                    or 0)
            except ValueError:
                raise ValueError(
                    "REPORTER_SESSION_ARENA_BYTES/_COLD_BYTES must be "
                    "integer byte counts, got %r/%r" % (env_b, env_cb))
            self.session_arena = SessionArena(
                self.cfg.beam_k, hot_b, cold_b,
                max_sessions=int(
                    getattr(self.cfg, "max_sessions", 65536)),
                mesh=self._mesh, devices=n_total)
        # all forwards speak the packed transport: one [4, B, T] f32 array in,
        # one [3, B, T] i32 array out (ops/viterbi.pack_inputs/pack_compact).
        # Each host<->device crossing pays a fixed dispatch/sync cost, so
        # the 4-put + 3-fetch unpacked calling convention cost single-trace
        # latency.
        #
        # Two selectable Viterbi forwards per program kind ("compact" /
        # "carry"), built lazily per kernel so a scan-only deployment never
        # traces the assoc program (and vice versa).  A hand-written pallas
        # Viterbi forward was carried (and measured) for three rounds and
        # never beat the scan on chip -- XLA already fuses this program's
        # hot loops, and the kernel's 128-row block constraint hurt
        # single-trace latency; it was deleted per VERDICT r04 next #5
        # (measurements and design notes: docs/pallas-decision.md).  The
        # assoc kernel is the log-depth associative-scan formulation
        # (ops/viterbi._forward_assoc, docs/performance.md).
        self._jits: Dict[tuple, object] = {}

    def _get_jit(self, kind: str, kernel: str):
        """Lazily-built jitted forward for (kind in compact|carry|pre|
        chain|session, kernel in scan|assoc).  "pre" is the
        carry-independent long-trace precompute — it contains no viterbi
        forward, so it is kernel-independent and cached under kernel
        "none"; "chain" is the carry-dependent remainder it feeds;
        "session" is the per-vehicle incremental step (ops/viterbi
        .session_step_packed — always aux: the streaming path is the
        ambiguity-sensitive one).  The sparse-gap model's variants live
        under their own kinds ("sparse" / "sparse_pre" / "sparse_chain" /
        "sparse_session", docs/match-quality.md) so dense traffic keeps
        replaying the byte-identical classic programs.  Programs that
        need collectives (any kind on a gp mesh; the slot-sharded arena
        step on any mesh) are built through the generic rule-table
        shard_map builder (_build_program); all expose packed calling
        conventions."""
        if kind in ("pre", "sparse_pre"):
            kernel = "none"
        # the aux (confidence-diagnostics) flag selects program VARIANTS
        # for the compact/chain kinds, so it is part of the cache key — a
        # matcher whose flag flips mid-life (quality engine attach) pays
        # one fresh compile instead of replaying the wrong program
        qa = self._quality_aux and kind in ("compact", "chain")
        key = (kind, kernel, qa)
        fn = self._jits.get(key)
        if fn is None:
            if self._mesh is not None and (
                    self._n_gp > 1
                    or kind in ("arena_session", "sparse_arena_session")):
                # collective-needing programs go through the generic
                # rule-table shard_map builder: the gp-sharded probe's
                # axis_index/pmin and the slot-sharded arena slab's
                # psum-bit-pattern gather are not expressible in plain
                # GSPMD.  Everything else on a dp-only mesh runs the
                # unmodified jits below SPMD via committed input
                # shardings (computation follows data).
                self._jits[key] = self._build_program(kind, kernel, qa)
                return self._finish_jit(key)
            if kind in ("arena_session", "sparse_arena_session"):
                # the device-resident session-arena step: the carry slab
                # rides as a DONATED argument, so the scatter is in-place
                # — one dispatch, zero per-step beam transfers
                import functools

                import jax

                from ..ops.viterbi import (
                    session_step_arena, session_step_arena_sparse,
                )

                if kind == "arena_session":
                    self._jits[key] = jax.jit(
                        functools.partial(session_step_arena, kernel=kernel),
                        static_argnums=(4,), donate_argnums=(5,))
                else:
                    self._jits[key] = jax.jit(
                        functools.partial(
                            session_step_arena_sparse, kernel=kernel),
                        static_argnums=(5,), donate_argnums=(6,))
                return self._finish_jit(key)
            if kind.startswith("sparse"):
                import functools

                import jax

                from ..ops.viterbi import (
                    chain_batch_carry_packed_sparse,
                    match_batch_compact_packed_sparse,
                    precompute_batch_packed_sparse,
                    session_step_packed_sparse,
                )

                if kind == "sparse":
                    self._jits[key] = jax.jit(
                        functools.partial(
                            match_batch_compact_packed_sparse,
                            kernel=kernel, dedup=self._probe_dedup),
                        static_argnums=(5,))
                elif kind == "sparse_pre":
                    self._jits[key] = jax.jit(
                        functools.partial(
                            precompute_batch_packed_sparse,
                            dedup=self._probe_dedup),
                        static_argnums=(5,))
                elif kind == "sparse_chain":
                    self._jits[key] = jax.jit(
                        functools.partial(
                            chain_batch_carry_packed_sparse, kernel=kernel),
                        static_argnums=(6,))
                else:  # sparse_session
                    self._jits[key] = jax.jit(
                        functools.partial(
                            session_step_packed_sparse, kernel=kernel),
                        static_argnums=(5,))
                return self._finish_jit(key)
            import functools

            import jax

            from ..ops.viterbi import (
                chain_batch_carry_packed, chain_batch_carry_packed_aux,
                match_batch_carry_packed, match_batch_compact_packed,
                match_batch_compact_packed_aux, precompute_batch_packed,
                session_step_packed,
            )

            # in-batch probe dedup applies where the UBODT probe sees a
            # whole dispatch's key set: the bucketed "compact" program
            # and the long-trace "pre" precompute.  The chain/carry
            # programs probe only tiny seam [K, K] sets (and the legacy
            # fused carry is the dedup-off differential reference).
            if kind == "pre":
                self._jits[key] = jax.jit(
                    functools.partial(
                        precompute_batch_packed,
                        dedup=self._probe_dedup),
                    static_argnums=(4,))
            elif kind == "compact":
                base = (match_batch_compact_packed_aux if qa
                        else match_batch_compact_packed)
                self._jits[key] = jax.jit(
                    functools.partial(
                        base, kernel=kernel,
                        dedup=self._probe_dedup),
                    static_argnums=(4,))
            else:
                base, k_argnum = {
                    "carry": (match_batch_carry_packed, 4),
                    "chain": (chain_batch_carry_packed_aux if qa
                              else chain_batch_carry_packed, 5),
                    "session": (session_step_packed, 4),
                }[kind]
                self._jits[key] = jax.jit(
                    functools.partial(base, kernel=kernel),
                    static_argnums=(k_argnum,))
            fn = self._jits[key]
        return self._finish_jit(key)

    def _finish_jit(self, key):
        """Cache tail for _get_jit: on the CPU virtual mesh, wrap the
        program in the process-wide serial-dispatch guard (idempotent —
        the wrapped object replaces the raw jit in the cache)."""
        fn = self._jits[key]
        if self._serial_dispatch and not isinstance(fn, _SerialDispatch):
            fn = self._jits[key] = _SerialDispatch(fn)
        return fn

    # back-compat accessors (bench.py / tools use these to time the exact
    # dispatched programs): the scan-kernel jits
    @property
    def _jit_match_scan(self):
        return self._get_jit("compact", "scan")

    @property
    def _jit_match_carry(self):
        return self._get_jit("carry", "scan")

    def _kernel_for(self, T: int) -> str:
        """Resolve the viterbi kernel for a padded window length.  "auto"
        picks assoc at/above the measured crossover bucket length (the
        log-depth kernel does O(K) more work per step, so it only wins once
        the sequential chain is long enough; docs/performance.md)."""
        if self._kernel_mode != "auto":
            return self._kernel_mode
        return "assoc" if T >= self._assoc_threshold else "scan"

    # -- per-request match parameters (reference wire contract parity) -----
    #
    # The reference accepts sigma_z / beta / search_radius / gps_accuracy
    # per request in match_options (valhalla trace_options).  MatchParams
    # are traced jnp scalars, so honoring them costs no recompile: traces
    # group by effective-params key and dispatch as separate batches of
    # the same compiled programs.  This is the live tuning surface for the
    # sparse-sampling accuracy chase (ROADMAP open item 4), and quality
    # samples are labeled with it (obs/quality.py).

    _PARAM_KEYS = ("sigma_z", "beta", "search_radius", "gps_accuracy")

    def effective_match_options(self, match_options) -> dict:
        """The HMM parameters this matcher would actually use for a
        request carrying ``match_options`` — overrides applied, invalid
        values ignored (the service 400s them first; library callers
        degrade to the config), search_radius clamped to cell_size/2 so
        the 2x2 quadrant candidate sweep stays exhaustive.  The serve
        tier echoes this dict in ?debug=1 responses."""
        mo = match_options if isinstance(match_options, dict) else {}

        def _num(key, default):
            v = mo.get(key)
            try:
                v = float(v)
            except (TypeError, ValueError):
                return float(default)
            return v if v > 0 and np.isfinite(v) else float(default)

        # gps_accuracy is the wire's sigma-like knob: it sets sigma_z only
        # when sigma_z itself is absent (valhalla precedence)
        sigma = _num("sigma_z", _num("gps_accuracy", self.cfg.sigma_z))
        radius = _num("search_radius", self.cfg.search_radius)
        max_radius = float(self.arrays.cell_size) / 2.0
        out = {
            "sigma_z": sigma,
            "beta": _num("beta", self.cfg.beta),
            "search_radius": clamp_radius(
                radius, self.arrays.cell_size, source="request"),
            "shape_match": mo.get("shape_match", "map_snap"),
        }
        if radius > max_radius:
            # the clamp used to be invisible even in ?debug=1; now it is a
            # counter, a structured warning (clamp_radius), and this flag
            # riding the debug echo (docs/http-api.md)
            out["search_radius_clamped"] = True
        return out

    def _params_key(self, trace) -> tuple:
        """Effective-params grouping key for one trace: () = the config
        defaults (the fast path: no override keys present), else the
        (sigma_z, beta, search_radius) float triple."""
        mo = trace.get("match_options") if isinstance(trace, dict) else None
        if not isinstance(mo, dict) or not any(
                k in mo for k in self._PARAM_KEYS):
            return ()
        eff = self.effective_match_options(mo)
        key = (eff["sigma_z"], eff["beta"], eff["search_radius"])
        if key == (float(self.cfg.sigma_z), float(self.cfg.beta),
                   float(self.cfg.search_radius)):
            return ()
        return key

    def _params_for(self, pkey: tuple):
        """Device MatchParams for a params key (() = the shared default).
        Cached per key (bounded) and replicated over the mesh like the
        default params."""
        if not pkey:
            return self._params
        mp = self._params_cache.get(pkey)
        if mp is None:
            import dataclasses

            import jax

            from ..ops.viterbi import MatchParams

            if len(self._params_cache) >= 64:
                self._params_cache.clear()
            cfg = dataclasses.replace(
                self.cfg, sigma_z=pkey[0], beta=pkey[1],
                search_radius=pkey[2])
            mp = MatchParams.from_config(cfg)
            if self._mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                mp = jax.device_put(mp, NamedSharding(self._mesh, P()))
            self._params_cache[pkey] = mp
        return mp

    def _cpu_for(self, pkey: tuple):
        """The cpu-backend twin of _params_for: a CPUViterbiMatcher over
        the SAME arrays + UBODT with the effective params baked into its
        config (the oracle is config-bound, not traced)."""
        if not pkey:
            return self._cpu
        cpu = self._cpu_params_cache.get(pkey)
        if cpu is None:
            import dataclasses

            from ..baseline.cpu_matcher import CPUViterbiMatcher

            if len(self._cpu_params_cache) >= 16:
                self._cpu_params_cache.clear()
            cfg = dataclasses.replace(
                self.cfg, sigma_z=pkey[0], beta=pkey[1],
                search_radius=pkey[2])
            cpu = CPUViterbiMatcher(self.arrays, self.ubodt, cfg)
            self._cpu_params_cache[pkey] = cpu
        return cpu

    # the (kind, kernel) program family's calling conventions, by argument
    # NAME: the names are what the parallel/rules.py partition table keys
    # on, so adding a program kind means one row here and (at most) one
    # rule there — never a hand-written in_specs list.  "k" is the static
    # beam width (excluded from the traced signature); argument order IS
    # the plain-jit calling convention, so dispatch sites stay oblivious.
    _PROGRAM_ARGS = {
        "compact": ("dg", "du", "xin", "p", "k"),
        "carry": ("dg", "du", "xin", "p", "k", "carry"),
        "pre": ("dg", "du", "xin", "p", "k"),
        "chain": ("dg", "du", "pre", "xin", "p", "k", "carry"),
        "session": ("dg", "du", "xin", "p", "k", "carry"),
        "sparse": ("dg", "du", "xin", "p", "sp", "k"),
        "sparse_pre": ("dg", "du", "xin", "p", "sp", "k"),
        "sparse_chain": ("dg", "du", "pre", "xin", "p", "sp", "k", "carry"),
        "sparse_session": ("dg", "du", "xin", "p", "sp", "k", "carry"),
        "arena_session": ("dg", "du", "xin", "p", "k",
                          "slab", "slots", "use"),
        "sparse_arena_session": ("dg", "du", "xin", "p", "sp", "k",
                                 "slab", "slots", "use"),
    }
    # result names per kind (qa variants insert "aux" after "packed");
    # resolved against the same rule table for out_specs
    _PROGRAM_OUTS = {
        "compact": ("packed", "probe"),
        "carry": ("packed", "carry"),
        "pre": ("pre",),
        "chain": ("packed", "carry"),
        "session": ("packed", "aux", "carry"),
        "sparse": ("packed", "aux", "probe"),
        "sparse_pre": ("pre",),
        "sparse_chain": ("packed", "aux", "carry"),
        "sparse_session": ("packed", "aux", "carry"),
        "arena_session": ("packed", "aux", "slab"),
        "sparse_arena_session": ("packed", "aux", "slab"),
    }

    def _build_program(self, kind: str, kernel: str, qa: bool):
        """Generic mesh program builder: ONE shard_map construction for
        every (kind, kernel) program, with in/out specs resolved from the
        parallel/rules.py partition table by argument name — this replaced
        the bespoke _make_gp_* twins that hand-listed specs per program
        and could not express sparse, tiering, or the session arena.

        Batch arrays split over dp, the UBODT's bucket ranges over gp
        (probes resolve with collectives inside — the plain sharded-jit
        path cannot express the axis_index/pmin the sharded probe needs),
        the session-arena slab's slot axis over dp with the donated
        in-place contract intact (ops/viterbi.session_step_arena_mesh).
        Each returned fn keeps the (…, params, k[, …]) calling convention
        of the plain jits so the dispatch sites stay oblivious; since
        shard_map bodies close over the static beam width, programs cache
        per k inside (the sparse cohorts' k_sp varies)."""
        import jax

        from ..ops import viterbi as V
        from ..parallel.rules import (
            BATCH_AXIS, GRAPH_AXIS, shard_map, spec_for,
        )

        mesh = self._mesh
        gp = self._n_gp > 1
        dedup = self._probe_dedup
        args = self._PROGRAM_ARGS[kind]
        outs = self._PROGRAM_OUTS[kind]
        if qa and kind in ("compact", "chain"):
            outs = (outs[:1] + ("aux",) + outs[1:])

        def _du_local(du):
            # the bucket-range-sharded probe path only exists on a gp
            # mesh; a dp-only mesh replicates the table and the plain
            # lookup is the bit-identical (and collective-free) program
            return du.with_shard_axis(GRAPH_AXIS) if gp else du

        def _body(k):
            if kind == "compact":
                f = (V.match_batch_compact_packed_aux if qa
                     else V.match_batch_compact_packed)
                return lambda dg, du, xin, p: f(
                    dg, _du_local(du), xin, p, k, kernel, dedup=dedup)
            if kind == "carry":
                return lambda dg, du, xin, p, carry: \
                    V.match_batch_carry_packed(
                        dg, _du_local(du), xin, p, k, carry, kernel)
            if kind == "pre":
                return lambda dg, du, xin, p: V.precompute_batch_packed(
                    dg, _du_local(du), xin, p, k, dedup=dedup)
            if kind == "chain":
                f = (V.chain_batch_carry_packed_aux if qa
                     else V.chain_batch_carry_packed)
                return lambda dg, du, pre, xin, p, carry: f(
                    dg, _du_local(du), pre, xin, p, k, carry, kernel)
            if kind == "session":
                return lambda dg, du, xin, p, carry: V.session_step_packed(
                    dg, _du_local(du), xin, p, k, carry, kernel)
            if kind == "sparse":
                return lambda dg, du, xin, p, sp: \
                    V.match_batch_compact_packed_sparse(
                        dg, _du_local(du), xin, p, sp, k, kernel=kernel,
                        dedup=dedup)
            if kind == "sparse_pre":
                return lambda dg, du, xin, p, sp: \
                    V.precompute_batch_packed_sparse(
                        dg, _du_local(du), xin, p, sp, k, dedup=dedup)
            if kind == "sparse_chain":
                return lambda dg, du, pre, xin, p, sp, carry: \
                    V.chain_batch_carry_packed_sparse(
                        dg, _du_local(du), pre, xin, p, sp, k, carry,
                        kernel=kernel)
            if kind == "sparse_session":
                return lambda dg, du, xin, p, sp, carry: \
                    V.session_step_packed_sparse(
                        dg, _du_local(du), xin, p, sp, k, carry,
                        kernel=kernel)
            if kind == "arena_session":
                return lambda dg, du, xin, p, slab, slots, use: \
                    V.session_step_arena_mesh(
                        dg, _du_local(du), xin, p, k, slab, slots, use,
                        kernel=kernel, batch_axis=BATCH_AXIS)
            if kind == "sparse_arena_session":
                return lambda dg, du, xin, p, sp, slab, slots, use: \
                    V.session_step_arena_mesh(
                        dg, _du_local(du), xin, p, k, slab, slots, use,
                        kernel=kernel, sp=sp, batch_axis=BATCH_AXIS)
            raise ValueError("unknown program kind %r" % (kind,))

        dyn = tuple(a for a in args if a != "k")
        in_specs = tuple(spec_for(a, mesh) for a in dyn)
        out_specs = (spec_for(outs[0], mesh) if len(outs) == 1
                     else tuple(spec_for(o, mesh) for o in outs))
        # the arena slab is donated exactly like the plain arena jits:
        # the scatter is in-place, zero per-step beam transfers
        donate = (dyn.index("slab"),) if "slab" in dyn else ()
        per_k: Dict[int, object] = {}

        def _built(k: int):
            fn = per_k.get(k)
            if fn is None:
                fn = jax.jit(
                    shard_map(_body(k), mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs),
                    donate_argnums=donate)
                per_k[k] = fn
            return fn

        k_pos = args.index("k")

        def dispatch(*call_args):
            k = int(call_args[k_pos])
            return _built(k)(*(call_args[:k_pos] + call_args[k_pos + 1:]))

        dispatch.jitted = _built  # per-k jit (AOT compile checks)
        return dispatch

    def _init_cpu(self):
        from ..baseline.cpu_matcher import CPUViterbiMatcher

        self._serial_dispatch = False
        self._cpu = CPUViterbiMatcher(self.arrays, self.ubodt, self.cfg)

    def _put_packed(self, xin: np.ndarray):
        """Packed [4, B, T] batch array -> device, dp-sharded over the batch
        axis (axis 1) when a mesh is configured.  Sharded host arrays go
        straight to their owner devices (device_put on the host array);
        routing through a single-device jnp.asarray first would double the
        transfer."""
        import jax
        import jax.numpy as jnp

        if self._batch_sharding is not None:
            return jax.device_put(xin, self._batch_sharding)
        return jnp.asarray(xin)

    def _interp_indices(self, traces) -> "set | None":
        """Trace indices to associate through the route-consistent
        interpolation engine: per-request match_options.interpolate wins,
        else the matcher default (cfg.interpolate / $REPORTER_INTERPOLATE).
        None when nothing interpolates (the fast path)."""
        out = None
        for i, tr in enumerate(traces):
            mo = tr.get("match_options") if isinstance(tr, dict) else None
            want = self._interpolate
            if isinstance(mo, dict) and "interpolate" in mo:
                want = bool(mo["interpolate"])
            if want:
                if out is None:
                    out = set()
                out.add(i)
        return out

    def _sparse_row_factor(self, slabel: str, pkey: tuple = ()) -> int:
        """How many dense rows one sparse row costs in the B*T device
        budget: the transition tensor is [B, T, K, K], so a cohort's wider
        K inflates memory by (K_sp/K)^2 — fold that into the length passed
        to _device_cap."""
        if not slabel:
            return 1
        _p, _sp, k_sp = self.sparse.params_for(slabel, pkey)
        k0 = max(1, int(self.cfg.beam_k))
        return max(1, (k_sp * k_sp) // (k0 * k0))

    def _dispatch_batch(self, px: np.ndarray, py: np.ndarray, times: np.ndarray, valid: np.ndarray,
                        pkey: tuple = (), slabel: str = ""):
        """Queue one [B, T] padded batch on the backend without blocking.
        Returns an opaque handle for _collect_batch.  ``pkey`` selects a
        per-request effective-params group (see _params_key; () = the
        config defaults): MatchParams are traced scalars, so a custom
        group runs the SAME compiled program with different inputs.
        ``slabel`` selects a sparse gap cohort (docs/match-quality.md):
        the batch dispatches through the time-adaptive "sparse" program
        variant with the cohort's calibrated MatchParams + SparseParams
        (traced too) and candidate budget K."""
        # chaos seam: a UBODT probe-program failure surfaces mid-call, per
        # chunk, unlike the dispatch point at match_many_async entry
        faults.maybe_raise("ubodt_probe")
        if self.backend == "jax":
            from ..ops.viterbi import pack_inputs

            B = px.shape[0]
            kernel = self._kernel_for(px.shape[1])
            qa = self._quality_aux
            if self._mesh is not None and px.shape[0] % self._n_dp:
                # dp sharding splits the batch axis evenly across chips
                px, py, times, valid = self._stage_rows(
                    px.shape[0] + self._n_dp - px.shape[0] % self._n_dp,
                    px, py, times, valid
                )
            xin = self._put_packed(pack_inputs(px, py, times, valid))
            if slabel:
                p, sp, k_sp = self.sparse.params_for(slabel, pkey)
                fn = self._get_jit("sparse", kernel)
                t0 = _time.monotonic()
                res, aux, probe = fn(self._dg, self._du, xin, p, sp, k_sp)
                C_DISPATCHES.labels(kernel).inc()
                C_DISPATCH_COHORT.labels("bucketed", "sparse").inc()
                self._note_dispatch(
                    px.shape, _time.monotonic() - t0, kind="sparse",
                    kernel=kernel, fn=fn,
                    args=(self._dg, self._du, xin, p, sp, k_sp))
                if not qa:
                    aux = None
                self._start_host_copy(res)
                self._start_host_copy(probe)
                return ("jax", B, res, aux, probe)
            p = self._params_for(pkey)
            fn = self._get_jit("compact", kernel)
            t0 = _time.monotonic()
            res = fn(self._dg, self._du, xin, p, self.cfg.beam_k)
            aux = None
            if qa:
                res, aux, probe = res
            else:
                res, probe = res
            C_DISPATCHES.labels(kernel).inc()
            C_DISPATCH_COHORT.labels("bucketed", "compact").inc()
            self._note_dispatch(
                px.shape, _time.monotonic() - t0, kernel=kernel, fn=fn,
                args=(self._dg, self._du, xin, p, self.cfg.beam_k))
            self._start_host_copy(res)
            self._start_host_copy(probe)
            return ("jax", B, res, aux, probe)
        cpu = self._cpu if not pkey else self._cpu_for(pkey)
        return ("cpu", cpu.run_batch(px, py, times, valid))

    def _note_dispatch(self, shape, dt: float, kind: str = "",
                       kernel: str = "scan", fn=None, args=None) -> None:
        """Feed the compile counters on a shape's first dispatch (the call
        that blocked on XLA).  ``shape`` is the padded (B, T) the kernel
        compiled for; ``kind`` distinguishes the carry-chain program and
        ``kernel`` the viterbi forward (scan / assoc) that compiled.
        ``fn``/``args`` (the dispatched jit and its call arguments)
        register the program with obs/attrib for named-stage attribution —
        array args are abstracted to ShapeDtypeStructs immediately, so
        nothing stays pinned."""
        key = (kind, kernel) + tuple(shape)
        if key in self._compiled_shapes:
            return
        self._compiled_shapes.add(key)
        lbl = kind + "%dx%d" % tuple(shape)
        if fn is not None and args is not None:
            from ..obs import attrib

            attrib.register_program("%s:%s" % (lbl, kernel), fn, args)
        C_COMPILES.labels(lbl, kernel).inc()
        C_COMPILE_S.labels(lbl, kernel).inc(dt)
        # structured compile event: the dispatch thread is bound to the
        # batch's lead span (serve) or the micro-batch span (batch
        # pipeline), so this stall is attributable to a real request id
        obs_log.event(log, "compile_stall", shape=lbl, kernel=kernel,
                      seconds=round(dt, 3))

    def compiled_shape_count(self, T: int, kind: str = "",
                             kernel: "str | None" = None) -> int:
        """How many padded shapes with window length T (any batch rung) have
        already paid their first dispatch — the warmup acceptance probe: a
        warmed (T, kernel) bucket answers > 0, so the first real request of
        that bucket cannot record a compile stall."""
        if kernel is None:
            kernel = self._kernel_for(T)
        return sum(
            1 for key in self._compiled_shapes
            if key[0] == kind and key[1] == kernel and key[-1] == T
        )

    _host_copy_ok = True  # class-wide: disabled after the first failure

    @classmethod
    def _start_host_copy(cls, res) -> None:
        """Begin the device->host transfer without blocking, so the later
        np.asarray finds the bytes already moving.  On deployments with a
        fixed per-sync round-trip cost this overlaps the transfer with
        whatever the host does next.  Purely an accelerant: a backend
        without (or with a broken) PJRT async-copy hook disables it after
        the first failure and the blocking fetch path is unaffected."""
        if not cls._host_copy_ok:
            return
        try:
            res.copy_to_host_async()
        except Exception:  # noqa: BLE001 - never fail a dispatch over a hint
            cls._host_copy_ok = False
            log.info("copy_to_host_async unavailable; async host-copy hint "
                     "disabled", exc_info=True)

    def _collect_batch(self, handle):
        """Block on a _dispatch_batch handle -> (edge, offset, break) numpy.
        One fetch: the device result is a packed [3, B, T] i32 array."""
        return self._collect_batch_aux(handle)[0]

    def _collect_batch_aux(self, handle):
        """_collect_batch plus the per-trace confidence block: ((edge,
        offset, break), aux [B, 4] numpy or None) — None on the cpu
        backend and whenever quality diagnostics are off."""
        if handle[0] == "jax":
            from ..ops.viterbi import unpack_compact

            _, B, res, aux, probe = handle
            with obs_attrib.host_stage("fetch", n=B):
                edge, offset, breaks = unpack_compact(res)
                if aux is not None:
                    aux = np.asarray(aux)[:B]
                _count_probe_steps([probe])
            return (edge[:B], offset[:B], breaks[:B]), aux
        return handle[1], None

    def _run_batch(self, px: np.ndarray, py: np.ndarray, times: np.ndarray, valid: np.ndarray):
        """[B, T] padded batch -> per-point (edge, offset, break) numpy arrays."""
        return self._collect_batch(self._dispatch_batch(px, py, times, valid))

    # -- public API --------------------------------------------------------

    def match_many(self, traces: Sequence[dict]) -> List[dict]:
        """Each trace: {"uuid":..., "trace":[{"lat","lon","time",...},...]}.
        Returns one match dict {"segments": [...]} per trace, in order."""
        return self.match_many_async(traces)()

    def match_many_async(self, traces: Sequence[dict]):
        """Dispatch the device work for ``traces`` and return a zero-arg
        ``finish()`` that blocks on the device, runs host association, and
        returns the results list.

        The split lets a caller (serve/service.MicroBatcher) run finish() on
        a different thread than dispatch, so host association of batch N
        overlaps device compute of batch N+1 instead of serialising behind it
        (VERDICT r02 weak #7).  Per call, at most PIPELINE_DEPTH chunks are
        in flight -- excess chunks are drained inline during dispatch,
        exactly like the synchronous path.  NOTE: a caller that overlaps
        several async calls multiplies that bound (each unfinished call can
        pin up to PIPELINE_DEPTH chunks); MicroBatcher bounds its overlap
        with max_inflight and documents the composite worst case."""
        # chaos seam (docs/robustness.md): armed only by REPORTER_FAULT_
        # env knobs; the uuid: form fires for any batch containing the
        # poison trace, which is what the MicroBatcher's bisect-retry
        # quarantine isolates against
        faults.maybe_raise("dispatch", key=",".join(
            str(t.get("uuid", "")) for t in traces if isinstance(t, dict)))
        results: List[Optional[dict]] = [None] * len(traces)

        # bucket by (effective-params group, sparse gap cohort, padded
        # length); traces beyond the largest bucket stream through fixed
        # windows with carried Viterbi state (jax backend) instead of
        # compiling ever-larger shapes.  The params key is () and the
        # sparse label "" for default dense traffic (the fast path), so a
        # fleet without overrides batches exactly as before.  Sparse
        # cohorts (median gap >= cfg.sparse_gap_s, model enabled) dispatch
        # through the time-adaptive "sparse" program variants with their
        # cohort's calibrated params (docs/match-quality.md).
        sparse_on = self.sparse.enabled and self.backend == "jax"
        buckets: Dict[tuple, List[int]] = {}
        long_map: Dict[tuple, List[int]] = {}
        interp_idx = self._interp_indices(traces)
        # columnar host plane: every point dict is walked ONCE here (or
        # not at all, when the binary wire decode attached "_columns"),
        # and each chunk below packs with one fancy-indexed scatter
        cols = None
        if self._host_pack:
            with obs_attrib.host_stage("pack", n=len(traces)):
                cols = columnar.extract_columns(traces)
        max_bucket = self.cfg.length_buckets[-1] if self.cfg.length_buckets else 256
        for i, tr in enumerate(traces):
            n = len(tr["trace"])
            if n == 0:
                results[i] = {"segments": []}
                continue
            pkey = self._params_key(tr)
            slabel = (self.sparse.label_for_trace(tr) or "") if sparse_on \
                else ""
            if n > max_bucket and self.backend == "jax":
                long_map.setdefault((pkey, slabel), []).append(i)
                continue
            buckets.setdefault((pkey, slabel, self._bucket_len(n)),
                               []).append(i)

        # cap the device batch: the kernel materialises [B, T, K, K]
        # transition arrays, so bound B*T (and rows on top); rounded down to a
        # power of two so the pow2 batch padding below cannot overshoot it.
        # A sparse cohort's wider K grows the transition tensor by
        # (K_sp/K)^2, so its cap shrinks by the same factor.
        chunks = []
        for (pkey, slabel, blen), idxs in sorted(buckets.items()):
            cap = self._device_cap(blen * self._sparse_row_factor(
                slabel, pkey))
            if slabel:
                C_SPARSE_DISPATCH.labels(slabel).inc(len(idxs))
                C_SPARSE_POINTS.labels(slabel).inc(
                    sum(len(traces[i]["trace"]) for i in idxs))
            chunks.extend(
                (pkey, slabel, blen, idxs[i : i + cap])
                for i in range(0, len(idxs), cap)
            )
        # pipeline: keep a few chunks in flight on the device (jax dispatch
        # is async) so host association of chunk i overlaps device compute of
        # the next ones.  Depth is bounded -- each in-flight chunk pins its
        # input buffers on the device, so unbounded queueing would defeat the
        # max_device_points HBM bound.
        from collections import deque

        pending: deque = deque()

        def drain_one():
            idxs_, handle_, times_ = pending.popleft()
            res, aux = self._collect_batch_aux(handle_)
            self._associate_and_store(idxs_, *res, times_, results, aux=aux,
                                      interp=interp_idx)

        for pkey, slabel, blen, idxs in chunks:
            with obs_attrib.host_stage("pack", n=len(idxs)):
                px, py, tm, valid, times = self._fill_rows(
                    traces, idxs, blen, cols=cols)
                args = self._pad_batch_staged(px, py, tm, valid)
            with obs_attrib.host_stage("dispatch", n=len(idxs)):
                handle = self._dispatch_batch(*args, pkey=pkey,
                                              slabel=slabel)
            pending.append((idxs, handle, times))
            if len(pending) >= PIPELINE_DEPTH:
                drain_one()

        # long traces dispatch their whole carry chains now too (the carry
        # chains on device, so this enqueues without blocking): by the time
        # finish() starts associating the first chunk, EVERY device program
        # of this call is already queued -- the device never idles behind
        # host association (VERDICT r04 next #2b: device_util 0.45 because
        # long compute serialised after bucketed association).
        long_handles = []
        for (pkey, slabel), lidx in sorted(long_map.items()):
            if slabel:
                C_SPARSE_DISPATCH.labels(slabel).inc(len(lidx))
                C_SPARSE_POINTS.labels(slabel).inc(
                    sum(len(traces[i]["trace"]) for i in lidx))
            long_handles.extend(self._dispatch_long(traces, lidx, pkey=pkey,
                                                    slabel=slabel, cols=cols))

        # the fetch and association host stages run on the finisher (and
        # collector) threads: they carry the dispatching request's, or the
        # batch's lead, trace id
        span = obs_trace.current_span()

        def _finish() -> List[dict]:
            # chaos seam: a wedged device step (the serve watchdog's prey)
            # is simulated here, inside the blocking finish the finisher
            # thread and the re-attach probe both run through
            faults.hang("device_hang")
            # fetch on a collector thread so the device->host sync cost of
            # chunk i+1 hides under host association of chunk i (every
            # blocking fetch pays a fixed sync cost; serialising 3+ of them
            # behind association adds up).  The queue bound keeps at most
            # two fetched-but-unassociated chunk results pinned on the host.
            import queue as _queue
            import threading

            work = list(pending)
            pending.clear()
            if not work and not long_handles:
                return results  # type: ignore[return-value]
            if len(work) + len(long_handles) == 1:
                # single chunk: nothing to overlap -- fetch inline rather
                # than taxing the streaming latency path with a thread
                if work:
                    idxs_, handle_, times_ = work[0]
                    res, aux = self._collect_batch_aux(handle_)
                else:
                    idxs_, res, times_, aux = self._fetch_long_aux(
                        long_handles[0])
                self._associate_and_store(idxs_, *res, times_, results,
                                          aux=aux, interp=interp_idx)
                return results  # type: ignore[return-value]
            fetched: "_queue.Queue" = _queue.Queue(maxsize=2)

            def _fetch_all():
                # every item is (row_indices, (edge, offset, breaks),
                # times, aux); None terminates, an exception object relays
                # failure
                try:
                    with obs_trace.bind(span):
                        for idxs_, handle_, times_ in work:
                            res_, aux_ = self._collect_batch_aux(handle_)
                            fetched.put((idxs_, res_, times_, aux_))
                        for h in long_handles:
                            fetched.put(self._fetch_long_aux(h))
                    fetched.put(None)
                except BaseException as e:  # noqa: BLE001 - relayed to caller
                    fetched.put(e)

            collector = threading.Thread(
                target=_fetch_all, daemon=True, name="match-collect")
            collector.start()
            try:
                while True:
                    item = fetched.get()
                    if item is None:
                        break
                    if isinstance(item, BaseException):
                        raise item
                    idxs_, res, times_, aux = item
                    self._associate_and_store(idxs_, *res, times_, results,
                                              aux=aux, interp=interp_idx)
            except BaseException:
                # unblock the collector (it may be parked on the bounded
                # queue) and let it run its remaining fetches to completion
                # -- a blocked collector would pin fetched results and leak
                # the thread for the life of the process
                while collector.is_alive():
                    try:
                        fetched.get_nowait()
                    except _queue.Empty:
                        collector.join(0.05)
                raise
            collector.join()
            return results  # type: ignore[return-value]

        def finish() -> List[dict]:
            with obs_trace.bind(span):
                return _finish()

        return finish

    def _device_cap(self, blen: int) -> int:
        """Rows per device batch for window length blen: bound B*T (the
        kernel materialises [B, T, K, K]) with a row cap on top, rounded
        DOWN to a _BATCH_LADDER rung so batch padding (which rounds UP to a
        rung) can never overshoot the configured memory bound.  The
        max_device_batch / max_device_points budgets are PER CHIP: a dp
        mesh splits every batch 1/n_dp per device, so the replica-level
        cap multiplies by the dp width — adding chips raises admission
        capacity (docs/performance.md "One logical matcher per pod").
        Never below the dp mesh width: a chunk must split evenly across
        devices."""
        n_dp = self._n_dp if self.backend == "jax" else 1
        cap = max(1, min(int(self.cfg.max_device_batch) * n_dp,
                         int(self.cfg.max_device_points) * n_dp // blen))
        rung = self._BATCH_LADDER[0]
        for r in self._BATCH_LADDER:
            if r <= cap:
                rung = r
        if cap > self._BATCH_LADDER[-1]:  # beyond the ladder: power of two
            rung = cap
            while rung & (rung - 1):
                rung &= rung - 1
        return max(rung, self._n_dp if self.backend == "jax" else 1)

    def capacity_summary(self) -> dict:
        """The replica's capacity plane (docs/http-api.md /health
        "capacity"): mesh topology, per-chip-budget-scaled admission
        caps, and the byte budgets of the device-resident state
        (UBODT tiering arena, session-beam slab).  Everything here
        scales with the local device count — it is what the router's
        capacity-aware ranking and the autoscaler's headroom model
        consume, and what the committed measurement artifact
        (docs/measurements/) pins against chip count."""
        if self.backend != "jax":
            return {"devices": 1, "mesh": {"dp": 1, "gp": 1},
                    "max_device_batch": int(self.cfg.max_device_batch),
                    "max_device_points": int(self.cfg.max_device_points)}
        out = {
            "devices": self._n_dp * self._n_gp,
            "mesh": {"dp": self._n_dp, "gp": self._n_gp},
            # replica-level admission caps: per-chip config budgets x the
            # dp width (the same scaling _device_cap applies per dispatch)
            "max_device_batch": int(self.cfg.max_device_batch) * self._n_dp,
            "max_device_points":
                int(self.cfg.max_device_points) * self._n_dp,
        }
        if self.tiering is not None:
            out["ubodt"] = self.tiering.summary()
        if self.session_arena is not None:
            out["session_arena"] = self.session_arena.summary()
        return out

    def device_summary(self) -> dict:
        """The engine's devices as JAX reports them (/statusz "device"):
        platform, device_kind, how many devices its programs span, the
        mesh shape, and the device ids each piece of device-resident
        state actually occupies (read from the arrays, not the config)."""
        if self.backend != "jax":
            return {"platform": "cpu", "device_kind": None, "count": 0,
                    "mesh": {"dp": 1, "gp": 1}, "placement": {}}
        import jax

        def ids(tree):
            return sorted({d.id for x in jax.tree_util.tree_leaves(tree)
                           if hasattr(x, "devices") for d in x.devices()})

        placement = {"graph": ids(self._dg), "ubodt": ids(self._du)}
        if self.session_arena is not None:
            placement["session_arena"] = ids(self.session_arena.hot)
        devs = (list(self._mesh.devices.flat) if self._mesh is not None
                else jax.devices()[:1])
        return {
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "count": len(devs),
            "mesh": {"dp": self._n_dp, "gp": self._n_gp},
            "placement": placement,
        }

    def _fill_rows(self, traces, idxs, T, cols=None):
        """Pack traces[idxs] into padded [B, T] device arrays + times lists.
        With ``cols`` (the call-wide TraceColumns of the columnar host
        plane) the pack is one fancy-indexed scatter per column and
        ``times`` is a PackedTimes (list-of-lists compatible);
        bit-identical to the legacy per-row loop below either way."""
        if cols is not None:
            px, py, tm, valid, times = cols.pack(self.arrays.proj, idxs, T)
            return self._skew_rows(px, py, tm, valid, times)
        B = len(idxs)
        px = np.zeros((B, T), np.float32)
        py = np.zeros((B, T), np.float32)
        tm = np.zeros((B, T), np.float32)
        valid = np.zeros((B, T), bool)
        times = []
        for row, i in enumerate(idxs):
            pts = traces[i]["trace"]
            lats = np.array([p["lat"] for p in pts], np.float64)
            lons = np.array([p["lon"] for p in pts], np.float64)
            x, y = self.arrays.proj.to_xy(lats, lons)
            px[row, : len(pts)] = x
            py[row, : len(pts)] = y
            ts = [float(p["time"]) for p in pts]
            # rebase to the trace start before the float32 cast: epoch
            # seconds (~1.7e9) have ~2 minute float32 resolution, which
            # would destroy the dt used by the time-factor cut; only
            # deltas matter on device
            tm[row, : len(pts)] = np.asarray(ts) - ts[0]
            valid[row, : len(pts)] = True
            times.append(ts)
        return self._skew_rows(px, py, tm, valid, times)

    @staticmethod
    def _skew_rows(px, py, tm, valid, times):
        # chaos seam (docs/match-quality.md): an armed quality_skew fault
        # perturbs the projected coordinates the DEVICE sees — equivalent
        # to corrupting every emission score — while the shadow oracle
        # re-matches the original trace.  Deterministic noise so the
        # injected degradation is reproducible run to run; with the knob
        # unset this is one dict lookup.
        tok = faults.fire("quality_skew")
        if tok is not None:
            try:
                mag = float(tok)
            except ValueError:
                mag = 25.0  # integer specs parse as the raise-N grammar
            rng = np.random.default_rng(12345)
            px = px + rng.normal(0.0, mag, px.shape).astype(np.float32)
            py = py + rng.normal(0.0, mag, py.shape).astype(np.float32)
        return px, py, tm, valid, times

    # batch-dimension padding ladder: the jitted kernels compile once per
    # (B, T) shape, so B snaps up to a small fixed set instead of every
    # power of two (VERDICT r03 next #3: prune the compiled shape set).
    # Sparse low rungs bound worst-case row waste at 4x, only where the
    # absolute cost is small; dense pow2 rungs above.
    _BATCH_LADDER = (1, 4, 16, 64, 128, 256, 512, 1024, 2048)

    @classmethod
    def _ladder_rung(cls, B: int) -> int:
        """Smallest _BATCH_LADDER rung >= B (next power of two beyond)."""
        B_pad = next((r for r in cls._BATCH_LADDER if r >= B), None)
        if B_pad is None:  # beyond the ladder: next power of two
            B_pad = 1
            while B_pad < B:
                B_pad <<= 1
        return B_pad

    @classmethod
    def _pad_batch(cls, px, py, tm, valid):
        """Pad the batch dimension up to the next ladder rung; dummy rows
        are all-invalid and sliced off by the caller.  Allocating variant
        for classmethod callers (bench/tools); the dispatch hot paths use
        _pad_batch_staged."""
        B = px.shape[0]
        B_pad = cls._ladder_rung(B)
        if B_pad == B:
            return px, py, tm, valid
        return _pad_rows(B_pad - B, px, py, tm, valid)

    def _pad_batch_staged(self, px, py, tm, valid):
        """_pad_batch through the per-shape pinned staging buffers."""
        B_pad = self._ladder_rung(px.shape[0])
        if B_pad == px.shape[0]:
            return px, py, tm, valid
        return self._stage_rows(B_pad, px, py, tm, valid)

    def _stage_rows(self, b_pad: int, *arrays):
        """Batch-pad [B, ...] arrays to b_pad rows through reused pinned
        staging buffers keyed by (slot, shape): the hot dispatch path pads
        on EVERY call (ladder rung + dp remainder) and fresh np.concatenate
        copies reallocated the same megabytes each time.  The pad tail is
        re-zeroed per call (all-zero rows = all-invalid).  Safe because
        dispatches are single-threaded per matcher and every consumer
        (pack_inputs' np.stack, the cpu oracle) copies the rows out before
        the next dispatch can touch the buffer."""
        out = []
        for slot, a in enumerate(arrays):
            if a.shape[0] == b_pad:
                out.append(a)
                continue
            key = (slot, b_pad) + tuple(a.shape[1:])
            buf = self._staging.get(key)
            if buf is None or buf.dtype != a.dtype:
                if len(self._staging) >= 128:
                    # long-trace groups key by (B_pad, n_chunks*W): bound the
                    # pool rather than let exotic shape traffic pin memory
                    self._staging.clear()
                buf = np.zeros((b_pad,) + a.shape[1:], a.dtype)
                self._staging[key] = buf
            buf[: a.shape[0]] = a
            buf[a.shape[0]:] = 0
            out.append(buf)
        return tuple(out)

    def _associate_and_store(self, idxs, edge, offset, breaks, times, results,
                             aux=None, interp=None):
        """Wire-format association for B rows (edge may carry pow2 pad rows;
        only the first len(idxs) are read).  times: per-row epoch-sec lists.
        ``aux``: optional [B, 4] confidence block (see MatchResult.aux);
        with quality diagnostics on, each result additionally carries a
        ``"_quality"`` dict (per-point edges, margin stats, pool-exhaustion
        fraction) the serve tier pops off before rendering the report —
        it never reaches the wire contract.  ``interp``: optional set of
        trace indices whose association runs through the route-consistent
        interpolation engine (matching/sparse.py) instead of the batch
        walk — same record shape, speed-weighted boundary times."""
        with obs_attrib.host_stage("associate", n=len(idxs)):
            B = len(idxs)
            T = edge.shape[1]
            abs_tm = np.zeros((B, T), np.float64)
            n_pts = np.zeros(B, np.int32)
            if isinstance(times, columnar.PackedTimes):
                times.fill_abs(abs_tm, n_pts)  # vectorized scatter
            else:
                for row in range(B):
                    n_pts[row] = len(times[row])
                    abs_tm[row, : n_pts[row]] = times[row]
            seg_lists = associate_segments_batch(
                self.arrays, self.ubodt,
                edge[:B], offset[:B], breaks[:B], abs_tm, n_pts,
                queue_thresh_mps=self.cfg.queue_speed_threshold_kph / 3.6,
                back_tol=2.0 * self.cfg.sigma_z + 5.0,
            )
            in_trace = np.arange(T)[None, :] < n_pts[:, None]
            C_TRACES.inc(B)
            C_POINTS.inc(int(n_pts.sum()))
            C_BREAKS.inc(int(np.count_nonzero((breaks[:B] != 0) & in_trace)))
            for row, i in enumerate(idxs):
                results[i] = {"segments": seg_lists[row]}
            if interp:
                off32 = np.asarray(offset, np.float32)
                for row, i in enumerate(idxs):
                    if i not in interp:
                        continue
                    n = int(n_pts[row])
                    mps = [
                        {"edge": int(edge[row, t]),
                         "offset": float(off32[row, t]),
                         "time": float(abs_tm[row, t]),
                         "break": bool(breaks[row, t]),
                         "shape_index": t}
                        for t in range(n)
                    ]
                    results[i] = {"segments": associate_interpolated(
                        self.arrays, self.ubodt, mps,
                        queue_thresh_mps=(
                            self.cfg.queue_speed_threshold_kph / 3.6),
                        back_tol=2.0 * self.cfg.sigma_z + 5.0,
                    )}
        if not self._quality_aux:
            return
        for row, i in enumerate(idxs):
            n = int(n_pts[row])
            q: dict = {
                "edge": [int(e) for e in edge[row, :n]],
                "n_points": n,
                "breaks": int(np.count_nonzero(breaks[row, :n])),
            }
            if aux is not None:
                mn, sm, nm, nx = (float(v) for v in aux[row])
                q["margin_min"] = (round(mn, 4) if nm > 0 else None)
                q["margin_mean"] = (round(sm / nm, 4) if nm > 0 else None)
                q["pool_exhausted_frac"] = (round(nx / n, 4) if n else 0.0)
            results[i]["_quality"] = q

    def _dispatch_long(self, traces, idxs, pkey: tuple = (),
                       slabel: str = "", cols=None):
        """Dispatch carry chains for traces longer than the largest bucket:
        fixed [B, W]-windows with carried Viterbi state (ops/viterbi
        .TraceCarry), one compile set regardless of trace length, no HMM
        restart at window boundaries.  All chunks of a group are DISPATCHED
        without fetching: the carry dependency chains them on device, so
        this enqueues asynchronously and returns handles for _fetch_long --
        the caller decides when to pay the host<->device sync.
        Mid-dispatch wave flushes (the MAX_DEFERRED_CHUNKS device-memory
        bound) still fetch inline; only the final wave stays deferred.
        Per-group program dispatch (hoisted chunk-batched precompute vs the
        legacy fused per-chunk forward) lives in _dispatch_long_group.
        ``pkey`` selects the effective-params group like _dispatch_batch."""
        import jax
        import jax.numpy as jnp

        from ..ops.viterbi import pack_inputs, unpack_compact

        W = self.cfg.length_buckets[-1] if self.cfg.length_buckets else 256
        # rows per device batch for this window (a sparse cohort's wider K
        # shrinks the cap by (K_sp/K)^2, same B*T*K*K budget)
        cap = self._device_cap(W * self._sparse_row_factor(slabel, pkey))

        # longest-first so rows in one group need similar chunk counts
        order = sorted(idxs, key=lambda i: -len(traces[i]["trace"]))
        handles = []
        for g in range(0, len(order), cap):
            # bound pinned device memory across groups: before dispatching
            # group k, force-fetch group k-2's deferred tail (group-serial
            # behaviour had this bound implicitly; fully-async dispatch of
            # many groups would pin every group's inputs + tail at once)
            if len(handles) >= 2:
                grp, parts, tail, tms, gaux, gprobe = handles[-2]
                if tail:
                    parts.extend(unpack_compact(o) for o in tail)
                    handles[-2] = (grp, parts, [], tms, gaux, gprobe)
            group = order[g : g + cap]
            T_max = max(len(traces[i]["trace"]) for i in group)
            n_chunks = -(-T_max // W)
            with obs_attrib.host_stage("pack", n=len(group)):
                px, py, tm, valid, times = self._fill_rows(
                    traces, group, n_chunks * W, cols=cols)
            px, py, tm, valid = self._pad_batch_staged(px, py, tm, valid)
            if self._mesh is not None and px.shape[0] % self._n_dp:
                px, py, tm, valid = self._stage_rows(
                    px.shape[0] + self._n_dp - px.shape[0] % self._n_dp,
                    px, py, tm, valid
                )
            xin = pack_inputs(px, py, tm, valid)  # [4, B_pad, n_chunks*W]
            host_parts, outs, aux_dev, probes = self._dispatch_long_group(
                xin, n_chunks, W, params=self._params_for(pkey),
                pkey=pkey, slabel=slabel)
            # chunk outputs come back one host copy each and join on the
            # host: a device-side concatenate would compile once per
            # chunk count, i.e. on the request path for every new length
            for o in outs + probes:
                self._start_host_copy(o)
            handles.append((group, host_parts, outs, times, aux_dev, probes))
        return handles

    def _dispatch_long_group(self, xin, n_chunks: int, W: int,
                             kernel: "str | None" = None, params=None,
                             pkey: tuple = (), slabel: str = ""):
        """Dispatch every device program for ONE padded long-trace group.
        xin: packed [4, B_pad, n_chunks*W] numpy.  Returns (host_parts,
        outs, aux, probes): already-fetched (edge, offset, breaks) wave
        tuples, the still-on-device packed chunk outputs in chunk order,
        the group's on-device [B_pad, 4] confidence block (seam-combined
        across chunks as the chain advances; None with quality
        diagnostics off or on the legacy fused path), and each pre
        dispatch's on-device [rows, 3] probe-path step counts (none on the
        legacy fused path).  Everything
        enqueues asynchronously; bench.py times exactly this entry point so
        the measured programs are the dispatched ones.

        Hoisted mode (cfg.long_precompute / $REPORTER_LONG_PRECOMPUTE,
        default on): the carry-independent work — candidate quadrant sweep,
        emissions, the [W-1, K, K] transition build — runs BATCHED ACROSS
        CHUNKS.  The chunk axis folds into the batch axis ([B, n_chunks, W]
        -> chunk-major [n_chunks*B, W] rows, snapped to the same
        _BATCH_LADDER rungs as bucketed traffic), so a group's whole
        precompute is a few wide "pre" dispatches sized by the
        max_device_points budget, and only the lightweight score recursion
        ("chain" programs, fixed [B_pad, W] shape) chains through the
        TraceCarry.  Legacy mode dispatches the fused per-chunk "carry"
        program, which rebuilds all of the above inside every carry step.

        Chunk outputs accumulate ON DEVICE and are fetched in bounded
        waves: concat-on-device then one host sync per wave, instead of one
        sync per chunk.  The wave cap bounds deferred output memory
        (12*B_pad*W bytes per chunk) so an arbitrarily long trace cannot
        OOM the accelerator with pinned results."""
        import jax
        import jax.numpy as jnp

        from ..ops.viterbi import initial_carry_batch, unpack_compact

        B_pad = xin.shape[1]
        k = self.cfg.beam_k
        p = self._params if params is None else params
        sp = None
        if slabel:
            # sparse cohort: the cohort's calibrated params + candidate
            # budget ride the sparse pre/chain programs.  The legacy fused
            # carry has no sparse variant — a sparse group always takes
            # the hoisted path regardless of cfg.long_precompute (the
            # REPORTER_SPARSE=0 differential covers the legacy program).
            p, sp, k = self.sparse.params_for(slabel, pkey)
        if kernel is None:
            kernel = self._kernel_for(W)
        carry = initial_carry_batch(B_pad, k)
        if self._carry_sharding is not None:
            carry = jax.device_put(carry, self._carry_sharding)

        outs, host_parts, probes = [], [], []
        # confidence aux rides the hoisted chain programs only (the legacy
        # fused carry is the bit-exact differential reference and stays
        # untouched); components combine across seams as min / + / + / +
        qa = (self._quality_aux and self._long_pre) or bool(slabel)
        aux_acc = None

        def _fold_aux(aux_c):
            nonlocal aux_acc
            if aux_acc is None:
                aux_acc = aux_c
            else:
                aux_acc = jnp.concatenate(
                    [jnp.minimum(aux_acc[:, :1], aux_c[:, :1]),
                     aux_acc[:, 1:] + aux_c[:, 1:]], axis=1)

        def _bank(out):
            outs.append(out)  # device handle; fetch deferred
            if len(outs) >= MAX_DEFERRED_CHUNKS:
                host_parts.extend(unpack_compact(o) for o in outs)
                outs.clear()

        if not self._long_pre and not slabel:
            fn_carry = self._get_jit("carry", kernel)
            for c in range(n_chunks):
                t0 = _time.monotonic()
                out, carry = fn_carry(
                    self._dg, self._du,
                    self._put_packed(xin[:, :, c * W : (c + 1) * W]),
                    p, k, carry,
                )
                C_DISPATCHES.labels(kernel).inc()
                C_DISPATCH_COHORT.labels("long", "carry").inc()
                self._note_dispatch(
                    (B_pad, W), _time.monotonic() - t0, kind="carry",
                    kernel=kernel, fn=fn_carry,
                    args=(self._dg, self._du,
                          xin[:, :, :W], p, k, carry))
                _bank(out)
            return host_parts, outs, None, []

        fn_pre = self._get_jit("sparse_pre" if slabel else "pre", "none")
        fn_chain = self._get_jit("sparse_chain" if slabel else "chain",
                                 kernel)
        # chunk-major rows for the precompute: row c*B_pad + b is chunk c of
        # trace b, so one chunk's rows are a contiguous slice of a wave
        rows_all = np.ascontiguousarray(
            xin.reshape(4, B_pad, n_chunks, W)
            .transpose(0, 2, 1, 3).reshape(4, n_chunks * B_pad, W))
        # wave sizing: as many chunks per pre dispatch as the device-batch
        # cap allows — the same B*T memory bound the fused program obeyed,
        # since the pre wave materialises the [rows, W-1, K, K] transition
        # tensors the fused program held transiently
        cpw = max(1, self._device_cap(
            W * self._sparse_row_factor(slabel, pkey)) // B_pad)
        for c0 in range(0, n_chunks, cpw):
            m = min(cpw, n_chunks - c0)
            rows = m * B_pad
            rung = self._ladder_rung(rows)
            seg = rows_all[:, c0 * B_pad : c0 * B_pad + rows]
            if rung != rows:
                # all-zero pad rows = all-invalid; their TracePre slots are
                # never sliced into a chain below
                seg = np.concatenate(
                    [seg, np.zeros((4, rung - rows, W), np.float32)], axis=1)
            t0 = _time.monotonic()
            if slabel:
                pre = fn_pre(self._dg, self._du, self._put_packed(seg),
                             p, sp, k)
                pre_args = (self._dg, self._du, seg, p, sp, k)
            else:
                pre = fn_pre(self._dg, self._du, self._put_packed(seg),
                             p, k)
                pre_args = (self._dg, self._du, seg, p, k)
            probes.append(pre.probe)
            C_DISPATCH_COHORT.labels("long", "pre").inc()
            self._note_dispatch((rung, W), _time.monotonic() - t0,
                                kind="sparse_pre" if slabel else "pre",
                                kernel="none", fn=fn_pre, args=pre_args)
            for i in range(m):
                c = c0 + i
                pre_c = jax.tree_util.tree_map(
                    lambda a: a[i * B_pad : (i + 1) * B_pad], pre)
                t0 = _time.monotonic()
                if slabel:
                    out = fn_chain(
                        self._dg, self._du, pre_c,
                        self._put_packed(xin[:, :, c * W : (c + 1) * W]),
                        p, sp, k, carry,
                    )
                    chain_args = (self._dg, self._du, pre_c,
                                  xin[:, :, :W], p, sp, k, carry)
                else:
                    out = fn_chain(
                        self._dg, self._du, pre_c,
                        self._put_packed(xin[:, :, c * W : (c + 1) * W]),
                        p, k, carry,
                    )
                    chain_args = (self._dg, self._du, pre_c,
                                  xin[:, :, :W], p, k, carry)
                if qa:
                    out, aux_c, carry = out
                    _fold_aux(aux_c)
                else:
                    out, carry = out
                C_DISPATCHES.labels(kernel).inc()
                C_DISPATCH_COHORT.labels("long", "chain").inc()
                self._note_dispatch((B_pad, W), _time.monotonic() - t0,
                                    kind="sparse_chain" if slabel
                                    else "chain",
                                    kernel=kernel, fn=fn_chain,
                                    args=chain_args)
                _bank(out)
        return host_parts, outs, aux_acc, probes

    def _fetch_long(self, handle):
        """Block on one _dispatch_long group handle -> (group, (edge,
        offset, break) numpy, times)."""
        return self._fetch_long_aux(handle)[:3]

    def _fetch_long_aux(self, handle):
        """_fetch_long plus the group's seam-combined confidence block
        ([B, 4] numpy or None), trimmed of batch-pad rows."""
        from ..ops.viterbi import unpack_compact

        group, host_parts, dev_tail, times, aux_dev, probes = handle
        with obs_attrib.host_stage("fetch", n=len(group)):
            _count_probe_steps(probes)
            parts = list(host_parts)
            parts.extend(unpack_compact(o) for o in dev_tail)
            if len(parts) == 1:
                edge, offset, breaks = parts[0]
            else:
                edge = np.concatenate([p[0] for p in parts], axis=1)
                offset = np.concatenate([p[1] for p in parts], axis=1)
                breaks = np.concatenate([p[2] for p in parts], axis=1)
            aux = (None if aux_dev is None
                   else np.asarray(aux_dev)[: len(group)])
        return group, (edge, offset, breaks), times, aux

    # -- per-vehicle session steps (docs/performance.md "The session
    # matcher"): the carried beam as first-class serving state.  Each call
    # folds the newly-arrived points of MANY sessions into fixed-shape
    # [B, small-W] dispatches of ops/viterbi.session_step_packed — B snaps
    # to the same _BATCH_LADDER rungs as bucketed traffic, W to the
    # session_buckets list, and the programs live in the same
    # (kind, kernel) jit cache, so single-point latency and cross-vehicle
    # batch throughput coexist on one compile set.

    def _session_bucket(self, n: int) -> int:
        """Smallest session window bucket >= n (next power of two beyond
        the largest — the rebuild-from-replay path's occasional wide
        step)."""
        buckets = list(getattr(self.cfg, "session_buckets", ()) or (4, 16))
        for b in buckets:
            if n <= int(b):
                return int(b)
        b = int(buckets[-1])
        while b < n:
            b <<= 1
        return b

    def _fill_session_rows(self, items, idxs, W, cols=None):
        """Pack items[idxs]' points into padded [B, W] device arrays.
        Times rebase against each session's own t0 epoch (not the step's
        first point) so the carried beam's f32 time frame stays coherent
        across the whole session (matcher._fill_rows rationale)."""
        if cols is not None:
            t0 = np.array([float(items[i]["t0"]) for i in idxs], np.float64)
            px, py, tm, valid, times = cols.pack(
                self.arrays.proj, idxs, W, t0=t0)
            return px, py, tm, valid, [int(n) for n in times.lens]
        B = len(idxs)
        px = np.zeros((B, W), np.float32)
        py = np.zeros((B, W), np.float32)
        tm = np.zeros((B, W), np.float32)
        valid = np.zeros((B, W), bool)
        ns = []
        for row, i in enumerate(idxs):
            pts = items[i]["points"]
            n = len(pts)
            lats = np.array([p["lat"] for p in pts], np.float64)
            lons = np.array([p["lon"] for p in pts], np.float64)
            x, y = self.arrays.proj.to_xy(lats, lons)
            px[row, :n] = x
            py[row, :n] = y
            tm[row, :n] = (np.array([float(p["time"]) for p in pts],
                                    np.float64)
                           - float(items[i]["t0"]))
            valid[row, :n] = True
            ns.append(n)
        return px, py, tm, valid, ns

    def _carry_batch(self, carries, b_pad: int):
        """Host carry dicts (None = inactive) -> one device TraceCarry
        with leading [b_pad].  Exact f32 round trip: the pinned-host
        session store and the device see identical bits."""
        import jax
        import jax.numpy as jnp

        from ..ops.viterbi import NEG_INF, TraceCarry

        k = self.cfg.beam_k
        scores = np.full((b_pad, k), NEG_INF, np.float32)
        edge = np.full((b_pad, k), -1, np.int32)
        offset = np.zeros((b_pad, k), np.float32)
        x = np.zeros(b_pad, np.float32)
        y = np.zeros(b_pad, np.float32)
        t = np.zeros(b_pad, np.float32)
        active = np.zeros(b_pad, bool)
        committed = np.full(b_pad, -1, np.int32)
        for i, c in enumerate(carries):
            if c is None:
                continue
            scores[i] = c["scores"]
            edge[i] = c["edge"]
            offset[i] = c["offset"]
            x[i] = c["x"]
            y[i] = c["y"]
            t[i] = c["t"]
            active[i] = bool(c["active"])
            committed[i] = c["committed"]
        carry = TraceCarry(scores=scores, edge=edge, offset=offset,
                           x=x, y=y, t=t, active=active, committed=committed)
        if self._carry_sharding is not None:
            return jax.device_put(carry, self._carry_sharding)
        return jax.tree_util.tree_map(jnp.asarray, carry)

    @staticmethod
    def _carry_rows(carry, b: int):
        """Device TraceCarry (leading [B_pad]) -> per-row host dicts,
        trimmed to the first b live rows.  One sync wave (np.asarray per
        leaf), on the collect side."""
        scores = np.asarray(carry.scores)[:b]
        edge = np.asarray(carry.edge)[:b]
        offset = np.asarray(carry.offset)[:b]
        x = np.asarray(carry.x)[:b]
        y = np.asarray(carry.y)[:b]
        t = np.asarray(carry.t)[:b]
        active = np.asarray(carry.active)[:b]
        committed = np.asarray(carry.committed)[:b]
        return [
            {"scores": scores[i], "edge": edge[i], "offset": offset[i],
             "x": x[i], "y": y[i], "t": t[i], "active": bool(active[i]),
             "committed": committed[i]}
            for i in range(b)
        ]

    def match_sessions_async(self, items):
        """Dispatch incremental session steps for ``items`` and return a
        zero-arg ``finish()`` resolving to one result per item:
        ``((edge[n], offset[n], breaks[n]) numpy, aux [4] | None,
        carry_host | None)``.

        items: [{"points": [{"lat","lon","time"}...] (1..n, the arriving
        delta — replay-prefixed by the rebuild path), "carry": host carry
        dict or None (fresh/rebuilding session), "t0": rebase epoch,
        "pkey": effective-params key}].

        Items group by (pkey, session window bucket) and dispatch as
        fixed-shape [B_rung, W] session_step_packed programs — the same
        ladder rungs, compile counters and params grouping as bucketed
        traffic.  On the cpu backend the step is a stateless windowed
        rematch (no carry machinery in the numpy oracle): callers keep
        continuity by replay-prefixing every step (SessionEngine does)."""
        from ..ops.viterbi import pack_inputs

        w_max = int((list(getattr(self.cfg, "session_buckets", ()) or ())
                     or [16])[-1])
        scols = None
        if self._host_pack:
            with obs_attrib.host_stage("pack", n=len(items)):
                scols = columnar.extract_columns(items, key="points")
        groups: Dict[tuple, List[int]] = {}
        handles = []
        for i, it in enumerate(items):
            n = max(1, len(it["points"]))
            slabel = self._session_label(it)
            if n > w_max and self.backend == "jax":
                # an over-bucket step (rebuild-from-replay, or a fat
                # delta) CHAINS through the largest warmed [B, W] session
                # shape instead of compiling a wider one — the same
                # fixed-compile-set property the long-trace path has, and
                # the same decode the windowed long path produces (carry
                # seams at W boundaries)
                handles.append(self._dispatch_session_chain(
                    it, i, w_max, slabel=slabel))
                continue
            groups.setdefault(
                (it["pkey"], slabel, self._session_bucket(n)), []).append(i)
        for (pkey, slabel, W), idxs in sorted(groups.items()):
            cap = self._device_cap(W)
            for g in range(0, len(idxs), cap):
                sub = idxs[g : g + cap]
                # same chaos seam as the windowed per-chunk dispatch: a
                # transient device-program failure surfaces here and the
                # session batcher's bisect-retry isolates it
                faults.maybe_raise("ubodt_probe")
                with obs_attrib.host_stage("pack", n=len(sub)):
                    px, py, tm, valid, ns = self._fill_session_rows(
                        items, sub, W, cols=scols)
                if self.backend != "jax":
                    cpu = self._cpu if not pkey else self._cpu_for(pkey)
                    res = cpu.run_batch(px, py, tm, valid)
                    handles.append(("cpu", sub, ns, res))
                    continue
                # NB allocating pads, not the pinned staging pool: the
                # session batcher dispatches on ITS OWN worker thread next
                # to the windowed batcher's, and _stage_rows assumes one
                # dispatch thread per matcher.  Session windows are tiny
                # ([B, 4..16]), so the copy is noise.
                px, py, tm, valid = self._pad_batch(px, py, tm, valid)
                if self._mesh is not None and px.shape[0] % self._n_dp:
                    px, py, tm, valid = _pad_rows(
                        self._n_dp - px.shape[0] % self._n_dp,
                        px, py, tm, valid)
                b_pad = px.shape[0]
                kernel = self._kernel_for(W)
                arena = self.session_arena
                if arena is not None and all(
                        "uuid" in items[i] for i in sub):
                    h = self._dispatch_session_arena(
                        arena, items, sub, ns, pkey, slabel, kernel,
                        (px, py, tm, valid), b_pad, W)
                    if h is not None:
                        handles.append(h)
                        continue
                # host-carry path: the arena is off, disabled for this
                # group (no uuids — direct library callers), or the group
                # exceeds the hot slab (arena smaller than one beam
                # page).  carry_host normalises any arena refs captured
                # in the items (a counted readback — the fallback seam)
                from .arena import carry_host

                carry = self._carry_batch(
                    [carry_host(items[i]["carry"]) for i in sub]
                    + [None] * (b_pad - len(sub)), b_pad)
                xin = self._put_packed(pack_inputs(px, py, tm, valid))
                if slabel:
                    # sparse streaming step: the time-adaptive model with
                    # the cohort's calibrated params, K pinned to the
                    # carried beam width (a session's beam cannot change
                    # width mid-life — the wider candidate budget is a
                    # windowed-dispatch lever; docs/match-quality.md)
                    p, sp, _k_sp = self.sparse.params_for(slabel, pkey)
                    fn = self._get_jit("sparse_session", kernel)
                    C_SPARSE_DISPATCH.labels(slabel).inc(len(sub))
                    C_SPARSE_POINTS.labels(slabel).inc(sum(ns))
                    t0 = _time.monotonic()
                    packed, aux, carry_out = fn(
                        self._dg, self._du, xin, p, sp, self.cfg.beam_k,
                        carry)
                    C_DISPATCHES.labels(kernel).inc()
                    C_DISPATCH_COHORT.labels("session", "sparse").inc()
                    self._note_dispatch(
                        (b_pad, W), _time.monotonic() - t0,
                        kind="sparse_session", kernel=kernel, fn=fn,
                        args=(self._dg, self._du, xin, p, sp,
                              self.cfg.beam_k, carry))
                    self._start_host_copy(packed)
                    handles.append(("jax", sub, ns, packed, aux, carry_out))
                    continue
                p = self._params_for(pkey)
                fn = self._get_jit("session", kernel)
                t0 = _time.monotonic()
                packed, aux, carry_out = fn(
                    self._dg, self._du, xin, p, self.cfg.beam_k, carry)
                C_DISPATCHES.labels(kernel).inc()
                C_DISPATCH_COHORT.labels("session", "step").inc()
                self._note_dispatch(
                    (b_pad, W), _time.monotonic() - t0, kind="session",
                    kernel=kernel, fn=fn,
                    args=(self._dg, self._du, xin, p, self.cfg.beam_k,
                          carry))
                self._start_host_copy(packed)
                handles.append(("jax", sub, ns, packed, aux, carry_out))

        def finish():
            # chaos seam: a wedged device step hangs the session finisher
            # exactly like the windowed one — the watchdog's prey, and the
            # degraded CPU-oracle answering path's trigger
            faults.hang("device_hang")
            out = [None] * len(items)
            from ..ops.viterbi import unpack_compact

            for h in handles:
                if h[0] == "cpu":
                    _kind, sub, ns, res = h
                    edge, offset, breaks = res
                    for row, i in enumerate(sub):
                        n = ns[row]
                        out[i] = ((edge[row, :n], offset[row, :n],
                                   breaks[row, :n]), None, None)
                    continue
                if h[0] == "jax_arena":
                    # the carried beams stayed on device: the answer is
                    # the packed result + a slot handle per session — no
                    # carry readback on the finish side
                    _kind, sub, ns, packed, aux, refs = h
                    edge, offset, breaks = unpack_compact(packed)
                    aux_np = np.asarray(aux)
                    for row, i in enumerate(sub):
                        n = ns[row]
                        out[i] = ((edge[row, :n], offset[row, :n],
                                   breaks[row, :n]), aux_np[row], refs[row])
                    continue
                if h[0] == "chain_arena":
                    _kind, i, chunk_outs, ref = h
                    E, O, B, aux_rows = [], [], [], []
                    for packed, aux_dev, nc in chunk_outs:
                        e_, o_, b_ = unpack_compact(packed)
                        E.append(e_[0, :nc])
                        O.append(o_[0, :nc])
                        B.append(b_[0, :nc])
                        aux_rows.append(np.asarray(aux_dev)[0])
                    aux = np.concatenate([
                        [min(r[0] for r in aux_rows)],
                        np.sum([r[1:] for r in aux_rows], axis=0)])
                    out[i] = ((np.concatenate(E), np.concatenate(O),
                               np.concatenate(B)), aux, ref)
                    continue
                if h[0] == "chain":
                    _kind, i, chunk_outs, carry_out = h
                    E, O, B, aux_rows = [], [], [], []
                    for packed, aux_dev, nc in chunk_outs:
                        e_, o_, b_ = unpack_compact(packed)
                        E.append(e_[0, :nc])
                        O.append(o_[0, :nc])
                        B.append(b_[0, :nc])
                        aux_rows.append(np.asarray(aux_dev)[0])
                    # aux components combine across seams as min/+/+/+
                    aux = np.concatenate([
                        [min(r[0] for r in aux_rows)],
                        np.sum([r[1:] for r in aux_rows], axis=0)])
                    out[i] = ((np.concatenate(E), np.concatenate(O),
                               np.concatenate(B)), aux,
                              self._carry_rows(carry_out, 1)[0])
                    continue
                _kind, sub, ns, packed, aux, carry_out = h
                edge, offset, breaks = unpack_compact(packed)
                aux_np = np.asarray(aux)
                rows = self._carry_rows(carry_out, len(sub))
                for row, i in enumerate(sub):
                    n = ns[row]
                    out[i] = ((edge[row, :n], offset[row, :n],
                               breaks[row, :n]), aux_np[row], rows[row])
            return out

        return finish

    def _session_label(self, item) -> str:
        """The sparse gap cohort of one session step ("" = dense).  The
        seam gap counts: a stream delivering one point per minute has a
        one-element delta, and its dt lives between the carried last point
        and the arriving one."""
        if self.backend != "jax" or not self.sparse.enabled:
            return ""
        try:
            times = [float(p["time"]) for p in item["points"]]
            c = item.get("carry")
            if c is not None:
                times = [float(item["t0"]) + float(c["t"])] + times
        except (KeyError, TypeError, ValueError):
            return ""
        return self.sparse.label_for_times(times) or ""

    def _dispatch_session_arena(self, arena, items, sub, ns, pkey,
                                slabel, kernel, arrays, b_pad: int, W: int):
        """One session group through the device-resident arena
        (docs/performance.md "Device-resident session arenas"): resolve
        each session to a hot slot, then ONE donated in-place dispatch of
        ops/viterbi.session_step_arena — the beams never cross the
        interconnect.  Returns the dispatch handle, or None when the
        group cannot fit the hot slab at once (caller falls back to the
        host-carry path, bit-identical either way).  Acquire, dispatch
        and slab swap run under ONE arena lock section: the old slab is
        donated the instant the step enqueues, so no concurrent reader
        may see it."""
        from ..ops.viterbi import pack_inputs

        px, py, tm, valid = arrays
        with arena.lock:
            acq = arena.acquire_batch(
                [(str(items[i]["uuid"]), items[i].get("carry"))
                 for i in sub])
            if acq is None:
                return None
            slot_l, use_l, refs = acq
            # padding rows carry slot == hot_slots: the gather clamps
            # them in-bounds, the mode="drop" scatter discards them
            slots = np.full(b_pad, arena.hot_slots, np.int32)
            slots[: len(sub)] = slot_l
            use = np.zeros(b_pad, bool)
            use[: len(sub)] = use_l
            xin = self._put_packed(pack_inputs(px, py, tm, valid))
            t0 = _time.monotonic()
            if slabel:
                p, sp, _k_sp = self.sparse.params_for(slabel, pkey)
                fn = self._get_jit("sparse_arena_session", kernel)
                C_SPARSE_DISPATCH.labels(slabel).inc(len(sub))
                C_SPARSE_POINTS.labels(slabel).inc(
                    sum(len(items[i]["points"]) for i in sub))
                packed, aux, slab_out = fn(
                    self._dg, self._du, xin, p, sp, self.cfg.beam_k,
                    arena.hot, slots, use)
                cohort, kindname = "sparse", "sparse_arena_session"
            else:
                p = self._params_for(pkey)
                fn = self._get_jit("arena_session", kernel)
                packed, aux, slab_out = fn(
                    self._dg, self._du, xin, p, self.cfg.beam_k,
                    arena.hot, slots, use)
                cohort, kindname = "step", "arena_session"
            arena.swap_hot(slab_out)
        C_DISPATCHES.labels(kernel).inc()
        C_DISPATCH_COHORT.labels("session", cohort).inc()
        # fn=None: the attrib probe re-executes registered programs,
        # which would consume an already-donated slab
        self._note_dispatch((b_pad, W), _time.monotonic() - t0,
                            kind=kindname, kernel=kernel)
        self._start_host_copy(packed)
        return ("jax_arena", sub, ns, packed, aux, refs)

    def _dispatch_session_chain_arena(self, item, idx: int, W: int,
                                      slabel: str = ""):
        """The over-bucket (rebuild-from-replay / fat-delta) step with
        the arena on: the carry chains IN PLACE through one hot slot —
        every chunk gathers the previous chunk's scattered successor, so
        the whole chain performs zero beam transfers and lands the final
        beam already resident."""
        from ..ops.viterbi import pack_inputs

        arena = self.session_arena
        pts = item["points"]
        kernel = self._kernel_for(W)
        sp = None
        if slabel:
            p, sp, _k_sp = self.sparse.params_for(slabel, item["pkey"])
            fn = self._get_jit("sparse_arena_session", kernel)
            C_SPARSE_DISPATCH.labels(slabel).inc()
            C_SPARSE_POINTS.labels(slabel).inc(len(pts))
        else:
            p = self._params_for(item["pkey"])
            fn = self._get_jit("arena_session", kernel)
        kindname = "sparse_arena_session" if slabel else "arena_session"
        # B = 1 padded to the dp width like _dispatch_session_chain; pad
        # rows carry the out-of-range slot sentinel (gather clamps them,
        # the mode="drop" scatter discards them)
        b_pad = max(1, self._n_dp)
        chunk_outs = []
        with arena.lock:
            acq = arena.acquire_batch(
                [(str(item["uuid"]), item.get("carry"))])
            (slot,), (use0,), (ref,) = acq
            slots = np.full(b_pad, arena.hot_slots, np.int32)
            slots[0] = slot
            use = np.zeros(b_pad, bool)
            use[0] = use0
            for c0 in range(0, len(pts), W):
                chunk = dict(item, points=pts[c0 : c0 + W])
                px, py, tm, valid, ns = self._fill_session_rows(
                    [chunk], [0], W)
                if b_pad > 1:
                    px, py, tm, valid = _pad_rows(
                        b_pad - 1, px, py, tm, valid)
                xin = self._put_packed(pack_inputs(px, py, tm, valid))
                t0 = _time.monotonic()
                if sp is not None:
                    packed, aux, slab_out = fn(
                        self._dg, self._du, xin, p, sp, self.cfg.beam_k,
                        arena.hot, slots, use)
                else:
                    packed, aux, slab_out = fn(
                        self._dg, self._du, xin, p, self.cfg.beam_k,
                        arena.hot, slots, use)
                arena.swap_hot(slab_out)
                use = use.copy()
                use[0] = True
                C_DISPATCHES.labels(kernel).inc()
                C_DISPATCH_COHORT.labels("session", "chain").inc()
                self._note_dispatch((b_pad, W), _time.monotonic() - t0,
                                    kind=kindname, kernel=kernel)
                chunk_outs.append((packed, aux, ns[0]))
        self._start_host_copy(chunk_outs[-1][0])
        return ("chain_arena", idx, chunk_outs, ref)

    def _dispatch_session_chain(self, item, idx: int, W: int,
                                slabel: str = ""):
        """One over-bucket session step as a carry chain of [B, W]
        session-program dispatches (B = 1 padded to the dp width): the
        rebuild-from-replay path's occasional wide window rides the SAME
        warmed shapes as normal streaming, and its decode equals the
        windowed long-trace path's (carry seams at W boundaries) — the
        differential suite pins it.  All chunks enqueue asynchronously;
        the carry chains on device."""
        from ..ops.viterbi import pack_inputs

        from .arena import carry_host

        if self.session_arena is not None and "uuid" in item:
            return self._dispatch_session_chain_arena(item, idx, W,
                                                      slabel=slabel)
        pts = item["points"]
        b_pad = max(1, self._n_dp)
        carry = self._carry_batch(
            [carry_host(item["carry"])] + [None] * (b_pad - 1), b_pad)
        sp = None
        if slabel:
            p, sp, _k_sp = self.sparse.params_for(slabel, item["pkey"])
            fn = self._get_jit("sparse_session", self._kernel_for(W))
            C_SPARSE_DISPATCH.labels(slabel).inc()
            C_SPARSE_POINTS.labels(slabel).inc(len(pts))
        else:
            p = self._params_for(item["pkey"])
            fn = self._get_jit("session", self._kernel_for(W))
        kernel = self._kernel_for(W)
        chunk_outs = []
        for c0 in range(0, len(pts), W):
            chunk = dict(item, points=pts[c0 : c0 + W])
            px, py, tm, valid, ns = self._fill_session_rows([chunk], [0], W)
            if b_pad > 1:
                px, py, tm, valid = _pad_rows(b_pad - 1, px, py, tm, valid)
            xin = self._put_packed(pack_inputs(px, py, tm, valid))
            t0 = _time.monotonic()
            if sp is not None:
                packed, aux, carry = fn(
                    self._dg, self._du, xin, p, sp, self.cfg.beam_k, carry)
                note_args = (self._dg, self._du, xin, p, sp,
                             self.cfg.beam_k, carry)
            else:
                packed, aux, carry = fn(
                    self._dg, self._du, xin, p, self.cfg.beam_k, carry)
                note_args = (self._dg, self._du, xin, p, self.cfg.beam_k,
                             carry)
            C_DISPATCHES.labels(kernel).inc()
            C_DISPATCH_COHORT.labels("session", "chain").inc()
            self._note_dispatch(
                (b_pad, W), _time.monotonic() - t0,
                kind="sparse_session" if sp is not None else "session",
                kernel=kernel, fn=fn, args=note_args)
            chunk_outs.append((packed, aux, ns[0]))
        self._start_host_copy(chunk_outs[-1][0])
        return ("chain", idx, chunk_outs, carry)

    def match_sessions(self, items):
        """Synchronous match_sessions_async (tests/tools)."""
        return self.match_sessions_async(items)()

    def warmup(self, lengths: "Sequence[int] | None" = None,
               batch_sizes: "Sequence[int] | None" = None,
               kernels: "Sequence[str] | None" = None,
               carry_chain: bool = False,
               session_step: bool = False) -> float:
        """Pre-compile the hot dispatch shapes so the first real request
        doesn't pay XLA compilation (the streaming operating point is a
        single ~64-pt window per call; a cold compile there blows the
        reference client's 10 s socket budget, HttpClient.java:80-88).

        Warms one batch per (batch rung, length bucket, viterbi kernel) by
        matching dummy traces along the graph's first edge — the full
        dispatch path, so the jit cache, the staging buffers, and the
        compile counters all see exactly what a real request would.

          lengths      length buckets to warm (default: cfg.length_buckets)
          batch_sizes  batch rungs to warm per bucket (default:
                       cfg.warmup_batch_sizes, i.e. [1]); each entry snaps
                       UP to its _BATCH_LADDER rung like real traffic
          kernels      viterbi kernels to warm (default: whatever
                       _kernel_for resolves per bucket — exactly the
                       programs live traffic will hit)
          carry_chain  also warm the carried-state streaming programs
                       (one trace of 2x the largest bucket).  In the
                       default hoisted mode that pre-dispatches BOTH long
                       programs: the chunk-batched "pre" precompute (its
                       chunk rows snap to the same batch ladder, so the
                       warmed rung covers the streaming operating point of
                       1-4 chunks per dispatch wave) and the "chain" score
                       recursion at [1, W]; legacy mode warms the fused
                       "carry" program as before
          session_step also warm the per-vehicle incremental session-step
                       programs: one (batch rung, session bucket) grid of
                       ops/viterbi.session_step_packed dispatches (serve
                       --warmup turns this on so the first streaming
                       point never compiles inline)

        With the persistent compilation cache enabled
        ($JAX_COMPILATION_CACHE_DIR, utils/jaxenv) a warm restart replays the
        compiles from disk, so this pass costs dispatch time, not XLA time.
        Returns seconds spent."""
        import time as _time

        if self.backend != "jax":
            return 0.0
        t0 = _time.time()
        if lengths is None:
            lengths = list(self.cfg.length_buckets)
        if batch_sizes is None:
            batch_sizes = list(
                getattr(self.cfg, "warmup_batch_sizes", None) or (1,))
        _dummy_traces = self.dummy_traces
        n_shapes = 0
        for n in lengths:
            n = max(2, int(n))
            want = kernels if kernels is not None else [
                self._kernel_for(self._bucket_len(n))]
            for kern in want:
                prev_mode = self._kernel_mode
                self._kernel_mode = kern
                try:
                    for b in batch_sizes:
                        b = self._ladder_rung(max(1, int(b)))
                        self.match_many(_dummy_traces(n, b))
                        n_shapes += 1
                        C_WARM_SHAPES.labels(kern).inc()
                        if self.sparse.enabled:
                            # the sparse-cohort program variant for the
                            # same shape: a dummy trace at the sparse
                            # operating gap routes through the "sparse"
                            # dispatch kind, so the first real ≥45 s-gap
                            # request cannot hit a compile stall either
                            self.match_many(_dummy_traces(
                                n, b, dt=max(60.0, self.sparse.gap_s)))
                            n_shapes += 1
                            C_WARM_SHAPES.labels(kern).inc()
                finally:
                    self._kernel_mode = prev_mode
        if carry_chain and self.cfg.length_buckets:
            w = int(self.cfg.length_buckets[-1])
            self.match_many(_dummy_traces(2 * w, 1))
            n_shapes += 1
            C_WARM_SHAPES.labels(self._kernel_for(w)).inc()
            if self._long_pre:
                # the hoisted path dispatched two programs: the chain above
                # plus the kernel-independent chunk-batched precompute
                n_shapes += 1
                C_WARM_SHAPES.labels("none").inc()
                # on a dp mesh the pre wave's rows are chunks * n_dp, so
                # the 3-4-chunk streaming operating point lands on a
                # HIGHER ladder rung than the 2-chunk trace above warmed
                # — dispatch a 4-chunk trace too (a free re-dispatch when
                # the rungs coincide, as on a single device)
                if self._n_dp > 1:
                    self.match_many(_dummy_traces(4 * w, 1))
                    n_shapes += 1
                    C_WARM_SHAPES.labels("none").inc()
        if session_step:
            # pre-dispatch the per-vehicle incremental-step shapes: one
            # (batch rung, session bucket) grid through the REAL session
            # dispatch path, so the first streaming point of a fresh boot
            # never hits a compile stall (asserted like the carry-chain
            # programs in tests/test_warmup_cache.py)
            for w in (getattr(self.cfg, "session_buckets", ()) or (4, 16)):
                w = max(1, int(w))
                kern = self._kernel_for(w)
                pts = _dummy_traces(max(2, w), 1)[0]["trace"][:w]
                for b in batch_sizes:
                    b = self._ladder_rung(max(1, int(b)))
                    warm_items = [
                        {"points": pts, "carry": None,
                         "t0": float(pts[0]["time"]), "pkey": ()}
                        for _ in range(b)
                    ]
                    if self.session_arena is not None:
                        # distinct uuids route through the arena program
                        # (the serving path); throwaway slots freed
                        # without a detach readback
                        for j, it in enumerate(warm_items):
                            it["uuid"] = "_warmup%d" % j
                    self.match_sessions(warm_items)
                    if self.session_arena is not None:
                        for j in range(b):
                            self.session_arena.free_uuid(
                                "_warmup%d" % j, detach=False)
                    n_shapes += 1
                    C_WARM_SHAPES.labels(kern).inc()
        dt = _time.time() - t0
        C_WARM_S.inc(dt)
        log.info("matcher warmup: %d shapes in %.1fs", n_shapes, dt)
        return dt

    def dummy_traces(self, n: int, b: int, dt: float = 5.0) -> List[dict]:
        """``b`` copies of an ``n``-point synthetic trace along the graph's
        first edge — the same full-dispatch-path probe warmup uses, also
        driven by obs/attrib.capture_matcher (/debug/attrib's on-demand
        capture) so the profiled programs are exactly the serving ones.
        ``dt`` sets the inter-point gap: warmup passes the sparse
        operating gap to pre-compile the sparse-cohort program variants."""
        ax, ay, bx, by = self._probe_edge_coords()
        xs = np.linspace(ax, bx, n)
        ys = np.linspace(ay, by, n)
        lat, lon = self.arrays.proj.to_latlon(xs, ys)
        tr = {
            "uuid": "_warmup",
            "trace": [
                {"lat": float(a), "lon": float(o),
                 "time": 1.0 + float(dt) * i}
                for i, (a, o) in enumerate(zip(lat, lon))
            ],
        }
        return [tr] * b

    def _probe_edge_coords(self):
        """Endpoints of the graph's first edge — the dummy-trace span used
        by warmup."""
        return (
            float(self.arrays.node_x[self.arrays.edge_from[0]]),
            float(self.arrays.node_y[self.arrays.edge_from[0]]),
            float(self.arrays.node_x[self.arrays.edge_to[0]]),
            float(self.arrays.node_y[self.arrays.edge_to[0]]),
        )

    def match(self, trace: dict) -> dict:
        return self.match_many([trace])[0]

    def Match(self, trace_json: str) -> str:
        """Wire-compatible single-trace entry (valhalla SegmentMatcher.Match)."""
        trace = json.loads(trace_json)
        return json.dumps(self.match(trace), separators=(",", ":"))

    def _bucket_len(self, n: int) -> int:
        for b in self.cfg.length_buckets:
            if n <= b:
                return b
        # beyond the largest bucket: next power of two (compiles once per size)
        b = self.cfg.length_buckets[-1] if self.cfg.length_buckets else 1
        while b < n:
            b <<= 1
        return b
