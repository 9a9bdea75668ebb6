"""Matcher configuration.

Honours the same tunables the reference bakes into its meili config
(Dockerfile:14-17,42-48 and py/generate_test_trace.py:37-52): sigma_z, beta,
search_radius, breakage_distance, max_route_distance_factor,
max_route_time_factor, turn_penalty_factor.  Adds the TPU-side knobs
(beam width K, UBODT delta, padding buckets).
"""

from __future__ import annotations

from dataclasses import dataclass, field, asdict
from typing import List


@dataclass
class MatcherConfig:
    # HMM parameters (reference defaults, Dockerfile:42-48)
    sigma_z: float = 4.07
    beta: float = 3.0
    search_radius: float = 50.0
    breakage_distance: float = 2000.0
    max_route_distance_factor: float = 5.0
    max_route_time_factor: float = 2.0
    turn_penalty_factor: float = 0.0
    # distance (m) from a segment's end within which trace speeds below
    # queue_speed_threshold_kph count as queueing (queue_length reporting)
    queue_speed_threshold_kph: float = 20.0
    # TPU kernel shape knobs
    beam_k: int = 8
    ubodt_delta: float = 3000.0
    # UBODT memory layout (docs/performance.md "The UBODT memory system"):
    # "cuckoo" = 2-choice 16-entry buckets, two 512 B row gathers per probe
    # (the shipped round-4 layout, the differential reference); "wide32" =
    # single-hash 32-entry buckets, ONE 1 KB row gather per probe — half
    # the gathered row count of the row-count-bound dominant kernel stage.
    # $REPORTER_UBODT_LAYOUT overrides at runtime; a prebuilt table whose
    # layout differs is repacked (rows extracted, no graph re-search).
    ubodt_layout: str = "cuckoo"
    # in-batch probe-pair dedup (same doc section): sort-unique-gather-
    # scatter over the dispatch's packed (src, dst) probe keys inside the
    # jitted program, so each distinct pair pays one row gather per
    # dispatch (fleet batches measure 4-8x redundant; the bench
    # probe_dedup field carries the number).  Bit-identical output either
    # way — an overflow of the static unique budget falls back to the
    # plain probe in-program.
    # $REPORTER_PROBE_DEDUP=0|1 overrides at runtime.
    probe_dedup: bool = False
    # hot/cold UBODT tiering (docs/performance.md "Continent-scale data
    # plane"): > 0 = the device holds a hot-bucket arena of at most this
    # many bytes while the full table stays host-paged behind the
    # lax.cond full-width fallback (tiles/tiering.py) — for tables bigger
    # than resident device memory.  0 = the whole table device-resident
    # (every bench and test default).  $REPORTER_UBODT_HOT_BYTES
    # overrides; match output is bit-identical either way.
    ubodt_hot_bytes: int = 0
    # fleet shard assignment "i/N" (docs/serving-fleet.md "Sharded
    # tables"): seeds the hot arena with this replica's contiguous
    # bucket-range partition — the same partition the gp shard_map probe
    # and the distributed builder use — and is advertised on /health so
    # the router's optional geo-aware ranking can steer matching traffic
    # here.  "" = unsharded.  $REPORTER_UBODT_SHARD overrides.
    ubodt_shard: str = ""
    # viterbi forward selection (docs/performance.md): "scan" = sequential
    # lax.scan (O(T) depth, least work), "assoc" = log-depth associative
    # max-plus scan, "auto" = assoc for padded window lengths >=
    # viterbi_assoc_threshold (the measured crossover; provisional until a
    # BENCH_r06 --kernel run pins it per deployment).  $REPORTER_VITERBI
    # overrides at runtime.
    viterbi_kernel: str = "scan"
    viterbi_assoc_threshold: int = 256
    # long-trace carry chain (docs/performance.md): True = hoist the
    # carry-independent work (candidate sweep, emissions, [W-1, K, K]
    # transition build) out of the per-chunk carry loop and dispatch it
    # batched across all chunks of a trace group, leaving only the score
    # recursion to chain; False = the legacy fused per-chunk program.
    # $REPORTER_LONG_PRECOMPUTE=0|1 overrides at runtime.
    long_precompute: bool = True
    # per-trace kernel confidence diagnostics (docs/match-quality.md):
    # True routes dispatches through the *_aux packed programs, which
    # additionally return a [B, 4] confidence block (winner-vs-runner-up
    # viterbi margins, candidate-pool exhaustion counts) attached to each
    # match result as "_quality".  Off by default so library callers and
    # the bit-exact differential suites see byte-identical results; the
    # serve entrypoint turns it on ($REPORTER_QUALITY_AUX overrides).
    # Margins carry the kernels' documented float-associativity ULP
    # wiggle and are diagnostics only.
    quality_aux: bool = False
    # per-vehicle session matcher (docs/performance.md "The session
    # matcher"; ROADMAP open item 2): padded window buckets for the
    # incremental session step — a streaming submit of n new points snaps
    # to the smallest bucket >= n (beyond the largest: next power of two,
    # the rebuild-from-replay path).  The session store is bounded
    # (max_sessions, LRU) and TTL-evicted; session_tail_points bounds the
    # rolling association tail + replay buffer per vehicle.
    session_buckets: List[int] = field(default_factory=lambda: [4, 16])
    session_tail_points: int = 64
    max_sessions: int = 65536
    session_ttl_s: float = 3600.0
    # device-resident session arena (docs/performance.md "Device-resident
    # session arenas"): carried Viterbi beams live in a hot HBM slab (+
    # pinned_host cold pages), so a packed session step gathers/scatters
    # by slot index in ONE donated in-place dispatch — zero per-step
    # host<->device beam transfers.  Off by default (library callers and
    # the bit-exact differential suites see the host-carry wire output
    # unchanged); the serve entrypoint turns it on
    # ($REPORTER_SESSION_ARENA=0 reverts bit-for-bit).
    # session_arena_bytes sizes the hot slab (0 = a max_sessions-sized
    # slab); session_arena_cold_bytes bounds the pinned_host cold tier
    # (0 = 4x the hot capacity).  $REPORTER_SESSION_ARENA[_BYTES,
    # _COLD_BYTES] override.
    session_arena: bool = False
    session_arena_bytes: int = 0
    session_arena_cold_bytes: int = 0
    # sparse-gap matching model (docs/match-quality.md "Sparse gaps";
    # ROADMAP open item 4): traces whose MEDIAN inter-point gap is at/
    # above sparse_gap_s dispatch through the time-adaptive "sparse"
    # program variants — beta scaled by the gap, a drivable-speed
    # plausibility term, gap-conditioned breakage, and a per-cohort
    # candidate budget/radius — while dense traffic keeps the
    # byte-identical classic programs.  Off by default so library callers
    # and the bit-exact differential suites see PR 14 output unchanged;
    # the serve entrypoint turns it on ($REPORTER_SPARSE=0 reverts
    # bit-for-bit).  Per-cohort calibrated values load from
    # $REPORTER_CALIBRATION / ``calibration`` (tools/calibrate.py emits
    # the pinned CALIBRATION.json); the knobs below are the uncalibrated
    # family defaults.
    sparse: bool = False
    sparse_gap_s: float = 40.0
    sparse_beam_k: int = 16
    # 0 = inherit search_radius; any value clamps to cell_size/2 (the 2x2
    # quadrant sweep bound) with the clamp counted + warned
    sparse_search_radius: float = 0.0
    sparse_beta_ref_s: float = 15.0
    sparse_beta_scale: float = 1.0
    sparse_beta_max: float = 8.0
    sparse_break_speed_mps: float = 34.0
    sparse_vmax_mps: float = 45.0
    sparse_plaus_weight: float = 3.0
    calibration: str = ""
    # route-consistent interpolation (docs/match-quality.md): when on (or
    # per request via match_options.interpolate), the post-decode engine
    # re-times each matched point-pair's UBODT shortest-path segment
    # sequence by free-flow traversal time (length/speed) instead of
    # linear route distance, so a sparse trace's intermediate segments
    # carry drivable boundary times — the way Meili's interpolation
    # reports every traversed segment.  Same wire record shape either way.
    interpolate: bool = False
    # columnar host packing (matching/columnar.py; docs/performance.md
    # "The columnar host data plane"): pack padded device batches with
    # one vectorized scatter over flat per-point columns instead of the
    # legacy per-trace Python loop.  Bit-identical output (the packer
    # equivalence suite enforces it), so it defaults on; =False (or
    # $REPORTER_HOST_PACK=0) keeps the legacy loop as the differential
    # reference.
    host_pack: bool = True
    # batch rungs pre-dispatched per length bucket by warmup passes
    # (serve --warmup / batch --warmup); each snaps up to a ladder rung
    warmup_batch_sizes: List[int] = field(default_factory=lambda: [1])
    # padded trace-length buckets for batched matching
    length_buckets: List[int] = field(default_factory=lambda: [16, 32, 64, 128, 256])
    # device-batch caps: the kernel materialises [B, T, K, K] transition
    # arrays, so the binding bound is on points (B*T), with a row cap on top
    max_device_batch: int = 2048
    max_device_points: int = 2048 * 64
    # devices to shard the trace batch over (dp axis of a jax Mesh).  1 =
    # single device; >1 routes every match_many batch through dp-sharded
    # jits (parallel/mesh.py semantics in the product path).  Must be a
    # power of two <= visible devices.
    devices: int = 1
    # of those devices, how many shard the UBODT table (gp axis): the
    # route-distance table splits into bucket ranges of 1/graph_devices per
    # chip and probes resolve with pmin/pmax collectives over the ICI — for
    # region tables larger than one chip's HBM.  Must be a power of two
    # dividing ``devices``; 1 = table replicated.
    graph_devices: int = 1
    # serve-tier graceful degradation (docs/robustness.md): when the
    # device watchdog trips on a wedged/failed device step, the service
    # detaches the engine and answers from the CPU oracle
    # (baseline/cpu_matcher) with "degraded": true until a re-attach probe
    # finds the accelerator healthy again.  False fails hard instead
    # (wedged requests get retryable 503s) — for deployments where a slow
    # right answer is worse than a fast retry against another replica.
    cpu_fallback: bool = True
    # report() business-logic default (reporter_service.py:54-58)
    threshold_sec: int = 15
    mode: str = "auto"

    def to_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "MatcherConfig":
        known = {f for f in cls.__dataclass_fields__}
        return cls(**{k: v for k, v in d.items() if k in known})

    @classmethod
    def from_meili(cls, meili: dict) -> "MatcherConfig":
        """Accept a valhalla-style config json ({'meili': {'default': {...}}})."""
        d = meili.get("meili", meili).get("default", meili.get("default", meili))
        c = cls()
        # meili's interpolation_distance historically had no analogue here
        # (the batched kernel matches every point rather than collapsing
        # near-duplicates).  A config carrying the key now enables the
        # route-consistent interpolation engine (matching/sparse.py): the
        # part of meili's interpolation sparse traces actually depend on —
        # every traversed segment reported with drivable boundary times —
        # is honoured, while near-duplicate collapsing remains
        # intentionally absent (the kernel is batched; dense duplicate
        # points cost nothing).
        if "interpolation_distance" in d:
            c.interpolate = True
        for key in (
            "sigma_z", "beta", "search_radius", "breakage_distance",
            "max_route_distance_factor", "max_route_time_factor",
            "turn_penalty_factor",
        ):
            if key in d:
                setattr(c, key, type(getattr(c, key))(d[key]))
        return c
