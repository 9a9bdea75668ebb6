"""Batched HMM map-matching kernel: emission, transition, Viterbi.

This is the framework's replacement for the Meili C++ engine's per-trace
matcher (reference boundary: reporter_service.py:240 Match()).  The whole
dynamic program runs on device with static shapes:

    candidates   [T, K]    (ops/candidates.py gathers)
    emission     [T, K]    Gaussian in point->candidate distance (sigma_z)
    transition   [K, K]    per step, |route - great_circle| / beta, with the
                           route distance a pure UBODT hash-table gather
    viterbi      two selectable forwards (the ``kernel`` static arg):
                   scan   lax.scan over T of a max-plus [K] x [K,K]
                          contraction — O(T) depth, minimal work
                   assoc  segmented jax.lax.associative_scan over per-step
                          max-plus [K, K] affine maps — O(log T) depth for
                          the score chain (arXiv:2102.05743's max-plus
                          matrix-product formulation), O(T K^3 log T) work
    backtrace    reverse lax.scan over stored backpointers (scan kernel) or
                 log-depth associative composition of [K+1] index maps
                 (assoc kernel)

vmap over the batch axis gives [B, T, K]; pjit/shard_map over a device mesh
shards B (reporter_tpu/parallel).  No data-dependent control flow anywhere.

Long traces stream through fixed [B, W] windows with a TraceCarry chained
across chunks.  Only the score recursion actually depends on the carry, so
the pipeline is split in two: precompute_trace (candidates, emissions, the
[T-1, K, K] transition build — batched ACROSS chunks by folding the chunk
axis into B) and chain_trace (seam transition + recursion + backtrace),
composed back into match_trace for the bucketed path.

Discontinuity semantics follow Meili: if consecutive points are further apart
than ``breakage_distance``, or no feasible route connects any candidate pair,
the HMM restarts at that point and the break is recorded (these surface as
`begin/end discontinuities in the match, reporter_service.py:114-116).

Deviation from strict Meili: *small* backward movement within one edge
(< ~2 sigma_z) is treated as lightly-penalised jitter rather than a full loop
route — GPS noise on a stopped vehicle otherwise produces spurious breaks.
Large backward movement does pay the loop route, so the wrong direction of a
two-way road cannot win.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs.attrib import stage
from ..tiles.arrays import DeviceGraph
from ..tiles.ubodt import DeviceUBODT
from .candidates import Candidates, find_candidates_batch
from .hashtable import ubodt_lookup, ubodt_lookup_steps

NEG_INF = -1e30


class MatchParams(NamedTuple):
    """Traced HMM scalars (jnp f32), shared across the batch."""

    sigma_z: jnp.ndarray
    beta: jnp.ndarray
    search_radius: jnp.ndarray
    breakage_distance: jnp.ndarray
    max_route_distance_factor: jnp.ndarray
    max_route_time_factor: jnp.ndarray
    turn_penalty_factor: jnp.ndarray

    @classmethod
    def from_config(cls, cfg) -> "MatchParams":
        return cls(
            sigma_z=jnp.float32(cfg.sigma_z),
            beta=jnp.float32(cfg.beta),
            search_radius=jnp.float32(cfg.search_radius),
            breakage_distance=jnp.float32(cfg.breakage_distance),
            max_route_distance_factor=jnp.float32(cfg.max_route_distance_factor),
            max_route_time_factor=jnp.float32(cfg.max_route_time_factor),
            turn_penalty_factor=jnp.float32(cfg.turn_penalty_factor),
        )


class SparseParams(NamedTuple):
    """Traced scalars of the sparse-gap matching model (docs/match-quality.md
    "Sparse gaps"; ROADMAP open item 4).  Presence is STATIC: every kernel
    entry point takes ``sp=None`` by default and the None path is the
    byte-identical pre-sparse program — the sparse variants live under their
    own (kind, kernel) jit cache keys, so dense traffic never pays for (or
    risks) the model.  All values are traced f32, so per-cohort calibrated
    values (tools/calibrate.py -> CALIBRATION.json) dispatch through ONE
    compiled program per shape, exactly like per-request MatchParams.

    The model, per point-pair with measurement gap dt seconds:

      * time-adaptive beta — the HMM's route-vs-great-circle tolerance
        scales with the gap: beta_eff = beta * min(1 + beta_scale *
        max(0, dt - beta_ref)/beta_ref, beta_max).  At a 5 s gap the route
        hugs the straight line; at 60 s the vehicle legitimately turned
        corners, and keeping the dense beta makes the true route lose to
        geometrically-flattering wrong ones.
      * drivable-speed plausibility — a transition whose route implies a
        speed above vmax (m/s) pays plaus_weight * (implied - vmax)/vmax
        log-prob units: at sparse gaps the time-factor cut alone is loose
        (max_route_time_factor * dt grows with the gap), and implausibly
        fast "shortcut" pairings are exactly the decodes the f64 oracle
        rejects.
      * gap-conditioned breakage — the fixed breakage_distance is replaced
        by max(breakage_distance, break_speed * dt): a vehicle at highway
        speed covers 2 km in under a minute, so the dense 2000 m teleport
        rule misfires on honest ≥60 s gaps (the restart then truncates the
        HMM evidence on both sides).
    """

    beta_ref: jnp.ndarray  # s; gaps at/below leave beta unchanged
    beta_scale: jnp.ndarray  # growth rate of the beta multiplier
    beta_max: jnp.ndarray  # cap on the beta multiplier
    break_speed: jnp.ndarray  # m/s; breakage = max(base, break_speed*dt)
    vmax: jnp.ndarray  # m/s drivable-speed plausibility knee
    plaus_weight: jnp.ndarray  # log-prob units per vmax of excess speed

    @classmethod
    def from_values(cls, beta_ref, beta_scale, beta_max, break_speed, vmax,
                    plaus_weight) -> "SparseParams":
        return cls(
            beta_ref=jnp.float32(beta_ref),
            beta_scale=jnp.float32(beta_scale),
            beta_max=jnp.float32(beta_max),
            break_speed=jnp.float32(break_speed),
            vmax=jnp.float32(vmax),
            plaus_weight=jnp.float32(plaus_weight),
        )

    @classmethod
    def from_config(cls, cfg) -> "SparseParams":
        return cls.from_values(
            getattr(cfg, "sparse_beta_ref_s", 15.0),
            getattr(cfg, "sparse_beta_scale", 1.0),
            getattr(cfg, "sparse_beta_max", 8.0),
            getattr(cfg, "sparse_break_speed_mps", 34.0),
            getattr(cfg, "sparse_vmax_mps", 45.0),
            getattr(cfg, "sparse_plaus_weight", 3.0),
        )


def sparse_beta(p: MatchParams, sp: SparseParams, dt):
    """The time-adaptive beta(dt) family (shared with the f64 oracle's
    re-derivation in baseline/brute_matcher.py — keep in lock-step)."""
    mult = 1.0 + sp.beta_scale * jnp.maximum(dt - sp.beta_ref, 0.0) \
        / jnp.maximum(sp.beta_ref, 1.0)
    return p.beta * jnp.minimum(mult, sp.beta_max)


def sparse_breakage(p: MatchParams, sp: "SparseParams | None", dt):
    """Gap-conditioned breakage threshold; sp None = the fixed rule."""
    if sp is None:
        return p.breakage_distance
    return jnp.maximum(p.breakage_distance, sp.break_speed * jnp.maximum(dt, 0.0))


class MatchResult(NamedTuple):
    cand: Candidates  # [T, K] candidate pool per point
    idx: jnp.ndarray  # [T] i32 chosen candidate slot, -1 = unmatched
    breaks: jnp.ndarray  # [T] bool, True where a new HMM segment starts
    route_dist: jnp.ndarray  # [T] f32 route distance from previous chosen candidate
    # (NEG_INF-free) final per-point viterbi score of the chosen slot
    score: jnp.ndarray  # [T] f32
    # per-trace confidence aux (docs/match-quality.md): [4] f32 —
    # (min winner-vs-runner-up margin, sum of margins, margin point count,
    # candidate-pool-exhausted point count).  All four components combine
    # across chunk seams (min / + / + / +), so the long-trace path can sum
    # them per chunk.  Purely diagnostic: never feeds back into the match.
    aux: jnp.ndarray
    # [3] i32 steps of this row by the UBODT probe path that answered them
    # (hashtable.PROBE_PATHS); set by the batched programs, None per trace
    probe: "jnp.ndarray | None" = None


def transition_matrix(dg: DeviceGraph, du: DeviceUBODT, src: Candidates, dst: Candidates,
                      gc: jnp.ndarray, dt: jnp.ndarray, p: MatchParams,
                      pre=None, sp: "SparseParams | None" = None):
    """[K, K] transition log-probs and route distances for one step.

    gc: great-circle (projected straight-line) metres between the two points.
    dt: measurement seconds between them (<= 0 disables the time-factor cut).
    pre: optional (era, erb, sp, sp_time) — the step's gathered edge rows
    ([K, 8] each) and UBODT probe results ([K, K] each), precomputed by a
    batched caller (precompute_batch hoists the gathers above the vmap so
    the probe sees the whole dispatch's key set and can dedup it); None =
    self-contained (the seam transition and the per-trace/oracle paths).
    sp: optional SparseParams — the time-adaptive sparse-gap model
    (beta(dt) + drivable-speed plausibility); None (static) keeps the
    byte-identical dense program.
    """
    with stage("transition-build"):
        return _transition_matrix(dg, du, src, dst, gc, dt, p, pre, sp)


def _transition_matrix(dg: DeviceGraph, du: DeviceUBODT, src: Candidates,
                       dst: Candidates, gc: jnp.ndarray, dt: jnp.ndarray,
                       p: MatchParams, pre=None,
                       sp: "SparseParams | None" = None):
    ea, oa = src.edge, src.offset  # [K]
    eb, ob = dst.edge, dst.offset  # [K]
    if pre is None:
        safe_ea = jnp.where(ea >= 0, ea, 0)
        safe_eb = jnp.where(eb >= 0, eb, 0)

        # one interleaved row-gather per edge instead of seven scalar gathers
        # (to-bits, from-bits, len, speed, head0, head1 — tiles/arrays.py)
        era = dg.edge_rows[safe_ea]  # [K, 8]
        erb = dg.edge_rows[safe_eb]
        to_a = jax.lax.bitcast_convert_type(era[:, 0], jnp.int32)
        from_b = jax.lax.bitcast_convert_type(erb[:, 1], jnp.int32)
        sp_dist, sp_time, _ = ubodt_lookup(du, to_a[:, None], from_b[None, :])
    else:
        era, erb, sp_dist, sp_time = pre
    len_a = era[:, 2]
    remain = (len_a - oa)[:, None]
    route = remain + sp_dist + ob[None, :]
    # same 0.1 m/s floor as the UBODT builder and CPU oracle: a zero-speed
    # edge must not produce inf/NaN travel times
    speed_a = jnp.maximum(era[:, 3], 0.1)
    speed_b = jnp.maximum(erb[:, 3], 0.1)
    rtime = remain / speed_a[:, None] + sp_time + (ob / speed_b)[None, :]

    # Same-edge handling.  Forward progress is the plain offset delta.  A
    # *small* backward delta (GPS jitter on a stopped/slow vehicle) is allowed
    # with a slight penalty so the true forward direction of a two-way road
    # wins ties; a large backward delta must really route the loop
    # (to[a] -> ... -> from[a]), which the general UBODT formula above
    # already expresses because from[b] == from[a].
    same = (ea[:, None] == eb[None, :]) & (ea[:, None] >= 0)
    delta = ob[None, :] - oa[:, None]
    back_tol = 2.0 * p.sigma_z + 5.0
    same_fwd = same & (delta >= 0)
    same_jitter = same & (delta < 0) & (-delta <= back_tol)
    route = jnp.where(same_fwd, delta, route)
    route = jnp.where(same_jitter, -delta * 1.05 + 1.0, route)
    same_known = same_fwd | same_jitter
    rtime = jnp.where(same_known, jnp.abs(delta) / speed_a[:, None], rtime)

    valid = (ea[:, None] >= 0) & (eb[None, :] >= 0)
    max_route = p.max_route_distance_factor * (gc + p.search_radius)
    feasible = valid & jnp.isfinite(route) & (route <= max_route)
    # free-flow travel time along the route must fit in the measurement gap
    # scaled by max_route_time_factor (meili's max-route-time cut)
    feasible &= (dt <= 0) | (rtime <= p.max_route_time_factor * jnp.maximum(dt, 1.0))

    if sp is None:
        beta_t = p.beta
    else:
        beta_t = sparse_beta(p, sp, dt)
    logp = -jnp.abs(route - gc) / beta_t
    # turn penalty: scaled by the heading change between leaving the source
    # edge and entering the destination edge (0..pi); factor 0 (the reference
    # default, Dockerfile:45) disables it
    turn = jnp.abs(angle_diff(era[:, 5][:, None], erb[:, 4][None, :]))
    logp = logp - jnp.where(same_known, 0.0, p.turn_penalty_factor * turn / (jnp.pi * beta_t))
    if sp is not None:
        # drivable-speed plausibility (sparse model): a pairing whose route
        # implies a speed above vmax is penalised smoothly — the hard
        # time-factor cut above scales with dt and goes loose exactly where
        # sparse decodes need discrimination.  dt <= 0 (no measurement gap)
        # disables it like the time cut.
        implied = route / jnp.maximum(dt, 1.0)
        excess = jnp.maximum(implied - sp.vmax, 0.0) / jnp.maximum(sp.vmax, 1.0)
        logp = logp - jnp.where(dt > 0, sp.plaus_weight * excess, 0.0)
    logp = jnp.where(feasible, logp, NEG_INF)
    return logp, jnp.where(feasible, route, jnp.inf)


def angle_diff(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Signed smallest difference between two angles, in (-pi, pi]."""
    d = b - a
    return jnp.mod(d + jnp.pi, 2.0 * jnp.pi) - jnp.pi


class TraceCarry(NamedTuple):
    """Viterbi state carried across chunks of one long trace (the sequence
    axis analogue of carrying attention state between blocks).  The next
    chunk's first transition runs from these candidates instead of an HMM
    restart, so a trace of any length streams through fixed [T]-window
    compiles with state intact (the reference's incremental-matching
    contract: shape_used trims consumed points and keeps a rolling tail,
    reporter_service.py:83-92, Batch.java:73-80)."""

    scores: jnp.ndarray  # [K] running viterbi scores at the last valid point
    edge: jnp.ndarray  # [K] i32 candidate edges at the last valid point
    offset: jnp.ndarray  # [K] f32 offsets along those edges
    x: jnp.ndarray  # f32 last valid point position
    y: jnp.ndarray
    t: jnp.ndarray  # f32 last valid point time
    active: jnp.ndarray  # bool: False = no live state (first chunk / all-pad)
    # slot the previous chunk's backtrace *committed* at the seam point.  The
    # next chunk re-checks that its own first choice is route-reachable from
    # this committed slot and raises a truthful break flag if not (the beam
    # transition below propagates scores from all slots, so the committed one
    # need not be the argmax source).
    committed: jnp.ndarray  # i32, -1 = none

    @classmethod
    def inactive(cls, k: int) -> "TraceCarry":
        return cls(
            scores=jnp.full((k,), NEG_INF, jnp.float32),
            edge=jnp.full((k,), -1, jnp.int32),
            offset=jnp.zeros((k,), jnp.float32),
            x=jnp.float32(0.0), y=jnp.float32(0.0), t=jnp.float32(0.0),
            active=jnp.array(False),
            committed=jnp.int32(-1),
        )


class TracePre(NamedTuple):
    """Carry-independent precompute for one trace window: everything the
    Viterbi forward consumes that does NOT depend on carried state.  For
    long traces these leaves are built batched across ALL chunks of a group
    (the chunk axis folded into the batch axis of the bucketed machinery)
    while only the lightweight score recursion chains through the carry —
    see matcher._dispatch_long_group and docs/performance.md."""

    cand: Candidates  # [T, K] candidate pool per point
    emis: jnp.ndarray  # [T, K] emission log-probs
    logp: jnp.ndarray  # [T-1, K, K] transition log-probs per step
    route: jnp.ndarray  # [T-1, K, K] route distances per step
    gc: jnp.ndarray  # [T-1] great-circle metres between consecutive points
    # [3] i32 valid steps by the UBODT probe path that answered them
    # (hashtable.PROBE_PATHS): precompute_batch only, None per trace
    probe: "jnp.ndarray | None" = None


def precompute_trace(dg: DeviceGraph, du: DeviceUBODT, px, py, times, valid,
                     p: MatchParams, k: int,
                     sp: "SparseParams | None" = None) -> TracePre:
    """The carry-independent stage of match_trace: candidate quadrant sweep,
    emission scores, and the [T-1, K, K] max-plus transition-matrix build.
    px/py/times/valid: [T].  vmap over batch (precompute_batch_packed).
    ``sp`` (static presence) selects the sparse-gap transition model."""
    cand = find_candidates_batch(dg, px, py, k, p.search_radius)  # [T, K]

    with stage("emission"):
        emis = -0.5 * jnp.square(cand.dist / p.sigma_z)  # [T, K]
        emis = jnp.where(jnp.isfinite(cand.dist), emis, NEG_INF)
        emis = jnp.where(valid[:, None], emis, NEG_INF)

    gc = jnp.hypot(px[1:] - px[:-1], py[1:] - py[:-1])  # [T-1]
    dts = times[1:] - times[:-1]  # [T-1]

    # All transition matrices at once: the UBODT hash probes and graph gathers
    # become one [T-1, K, K] op (further batched [B, ...] by the vmap in
    # match_batch) instead of T-1 sequential small gathers inside the scan —
    # the scan in chain_trace carries only the tiny max-plus recursion.
    src_c = jax.tree_util.tree_map(lambda a: a[:-1], cand)
    dst_c = jax.tree_util.tree_map(lambda a: a[1:], cand)
    if sp is None:
        logp_all, route_all = jax.vmap(
            transition_matrix, in_axes=(None, None, 0, 0, 0, 0, None)
        )(dg, du, src_c, dst_c, gc, dts, p)  # [T-1, K, K]
    else:
        logp_all, route_all = jax.vmap(
            transition_matrix, in_axes=(None, None, 0, 0, 0, 0, None, None,
                                        None)
        )(dg, du, src_c, dst_c, gc, dts, p, None, sp)
    return TracePre(cand=cand, emis=emis, logp=logp_all, route=route_all, gc=gc)


def precompute_batch(dg: DeviceGraph, du: DeviceUBODT, px, py, times, valid,
                     p: MatchParams, k: int, dedup: bool = False,
                     sp: "SparseParams | None" = None) -> TracePre:
    """Batched precompute: [B, T] leaves -> TracePre with leading [B].

    Identical math (bit-identical results) to vmapping precompute_trace,
    but with the two gather streams HOISTED above the per-trace vmap:

      * each candidate's graph edge row is gathered ONCE per point slot
        ([B, T, K] rows) and sliced into the src/dst views, instead of
        twice per step (as transition src, again as transition dst);
      * the UBODT route-distance probe runs as ONE call over the batch's
        entire [B, T-1, K, K] key set, compacted per step to each step's
        distinct node pairs (ops/hashtable.ubodt_lookup_steps); in-batch
        probe dedup (``dedup=True`` -> ops/hashtable._lookup_dedup's
        sort-unique-gather-scatter) applies to the compacted key set of
        the whole dispatch.

    ``TracePre.probe`` ([B, 3] i32) counts each row's valid steps by the
    probe path that answered them.

    The per-step transition arithmetic then runs with ``pre`` supplied, so
    XLA sees the same elementwise ops as the fused per-trace program.
    """
    cand = jax.vmap(
        find_candidates_batch, in_axes=(None, 0, 0, None, None)
    )(dg, px, py, k, p.search_radius)  # [B, T, K]

    with stage("emission"):
        emis = -0.5 * jnp.square(cand.dist / p.sigma_z)  # [B, T, K]
        emis = jnp.where(jnp.isfinite(cand.dist), emis, NEG_INF)
        emis = jnp.where(valid[..., None], emis, NEG_INF)

    gc = jnp.hypot(px[:, 1:] - px[:, :-1], py[:, 1:] - py[:, :-1])  # [B, T-1]
    dts = times[:, 1:] - times[:, :-1]

    with stage("transition-build"):
        er = dg.edge_rows[jnp.where(cand.edge >= 0, cand.edge, 0)]  # [B, T, K, 8]
        era, erb = er[:, :-1], er[:, 1:]  # [B, T-1, K, 8]
        to_a = jax.lax.bitcast_convert_type(era[..., 0], jnp.int32)
        from_b = jax.lax.bitcast_convert_type(erb[..., 1], jnp.int32)
    sp_dist, sp_time, probe = ubodt_lookup_steps(
        du, to_a, from_b, valid[:, 1:], dedup=dedup)  # [B, T-1, K, K]

    src_c = jax.tree_util.tree_map(lambda a: a[:, :-1], cand)
    dst_c = jax.tree_util.tree_map(lambda a: a[:, 1:], cand)
    if sp is None:
        step_axes = (None, None, 0, 0, 0, 0, None, 0)
        tm = jax.vmap(jax.vmap(transition_matrix, in_axes=step_axes),
                      in_axes=step_axes)
        logp_all, route_all = tm(
            dg, du, src_c, dst_c, gc, dts, p, (era, erb, sp_dist, sp_time))
    else:
        step_axes = (None, None, 0, 0, 0, 0, None, 0, None)
        tm = jax.vmap(jax.vmap(transition_matrix, in_axes=step_axes),
                      in_axes=step_axes)
        logp_all, route_all = tm(
            dg, du, src_c, dst_c, gc, dts, p, (era, erb, sp_dist, sp_time),
            sp)
    return TracePre(cand=cand, emis=emis, logp=logp_all, route=route_all,
                    gc=gc, probe=probe)


def match_trace(dg: DeviceGraph, du: DeviceUBODT, px, py, times, valid, p: MatchParams, k: int,
                carry: "TraceCarry | None" = None, kernel: str = "scan",
                sp: "SparseParams | None" = None):
    """Match one trace of T (padded) points.  px/py/times/valid: [T].
    vmap over batch.  With ``carry`` (static presence), the first step
    transitions from the carried candidate beam instead of restarting, and
    the updated carry is returned: (MatchResult, TraceCarry).

    ``kernel`` (static) selects the Viterbi forward: "scan" (sequential
    lax.scan, O(T) depth) or "assoc" (log-depth associative max-plus scan,
    see _forward_assoc).  Both implement identical break/restart/padding
    semantics; they may differ only by float-associativity ULPs in the
    scores, never in the alive/dead or break classification.

    ``valid`` must be a contiguous True-prefix (all-False allowed): padding
    lives only at trace tails; traces with interior gaps are split host-side
    (the reference's inactivity-gap split, simple_reporter.py:149-163).

    Composition of precompute_trace (carry-independent) + chain_trace
    (carry-dependent) — the long-trace path dispatches the two stages as
    separate programs so the precompute batches across chunks; fused here,
    XLA sees the exact same ops for the bucketed path."""
    pre = precompute_trace(dg, du, px, py, times, valid, p, k, sp)
    return chain_trace(dg, du, pre, px, py, times, valid, p, k, carry, kernel,
                       sp)


def chain_trace(dg: DeviceGraph, du: DeviceUBODT, pre: TracePre, px, py, times,
                valid, p: MatchParams, k: int,
                carry: "TraceCarry | None" = None, kernel: str = "scan",
                sp: "SparseParams | None" = None):
    """The carry-dependent stage of match_trace: seam transition from the
    carried beam (one [K, K] transition_matrix call — ~1/T of the hoisted
    transition work), score recursion, backtrace, and carry-out.  Consumes
    a TracePre; semantics identical to the fused match_trace by
    construction (it IS the tail of that function)."""
    T = px.shape[0]
    cand, emis, logp_all, route_all, gc = pre[:5]
    # gap-conditioned breakage (sparse model): each step's teleport
    # threshold scales with its measurement gap.  With sp None the
    # per-step threshold is the traced scalar and the step function below
    # closes over it exactly as before — the dense program is untouched.
    brk_thresh = None
    if sp is not None:
        brk_thresh = sparse_breakage(p, sp, times[1:] - times[:-1])  # [T-1]

    def step(scores, inputs):
        """scores: [K] running viterbi scores.  One timestep t (1..T-1)."""
        logp, route, emis_t, gc_t, valid_t = inputs[:5]
        brk_t = p.breakage_distance if sp is None else inputs[5]
        total = scores[:, None] + logp  # [K src, K dst]
        best_src = jnp.argmax(total, axis=0)  # [K]
        best_val = jnp.max(total, axis=0)
        connected = best_val > NEG_INF / 2
        # breakage: too far apart, or nothing connects
        broke = (gc_t > brk_t) | ~jnp.any(connected)
        new_scores = jnp.where(broke, emis_t, best_val + emis_t)
        new_scores = jnp.where(valid_t, new_scores, scores)  # padding: freeze
        backptr = jnp.where(broke | ~connected, -1, best_src)
        backptr = jnp.where(valid_t, backptr, jnp.full_like(backptr, -2))  # -2 = padded step
        chosen_route = jnp.where(connected, route[best_src, jnp.arange(route.shape[1])], jnp.inf)
        return new_scores, (new_scores, backptr, broke & valid_t, chosen_route)

    if carry is None:
        init_scores = emis[0]
        first_break = jnp.array(True)
        first_route = jnp.full((k,), jnp.inf)
    else:
        # first step transitions from the carried beam (chunk boundary)
        src_c = Candidates(
            edge=carry.edge, offset=carry.offset,
            dist=jnp.zeros((k,), jnp.float32),
            cx=jnp.zeros((k,), jnp.float32), cy=jnp.zeros((k,), jnp.float32),
        )
        dst_c = jax.tree_util.tree_map(lambda a: a[0], cand)
        gc0 = jnp.hypot(px[0] - carry.x, py[0] - carry.y)
        dt0 = times[0] - carry.t
        logp0, route0 = transition_matrix(dg, du, src_c, dst_c, gc0, dt0, p,
                                          sp=sp)
        brk0 = sparse_breakage(p, sp, dt0)
        total0 = carry.scores[:, None] + logp0  # [K src, K dst]
        best_src0 = jnp.argmax(total0, axis=0)
        best_val0 = jnp.max(total0, axis=0)
        connected0 = best_val0 > NEG_INF / 2
        broke0 = (gc0 > brk0) | ~jnp.any(connected0) | ~carry.active
        init_scores = jnp.where(broke0, emis[0], best_val0 + emis[0])
        first_break = broke0
        first_route = jnp.where(
            connected0 & ~broke0,
            route0[best_src0, jnp.arange(k)], jnp.inf,
        )
    if kernel == "assoc" and T >= 2:
        with stage("assoc-recursion"):
            all_scores, all_backptr, all_broke, all_route = _forward_assoc(
                init_scores, logp_all, route_all, emis, gc, valid, p,
                brk_thresh)
    elif kernel in ("scan", "assoc"):  # assoc degenerates to scan at T < 2
        xs = (logp_all, route_all, emis[1:], gc, valid[1:])
        if sp is not None:
            xs = xs + (brk_thresh,)
        with stage("scan-recursion"):
            _, (all_scores, all_backptr, all_broke, all_route) = jax.lax.scan(step, init_scores, xs)
    else:
        raise ValueError("unknown viterbi kernel %r" % (kernel,))

    # prepend step 0
    scores_mat = jnp.concatenate([init_scores[None], all_scores], axis=0)  # [T, K]
    backptr = jnp.concatenate([jnp.full((1, k), -1, all_backptr.dtype), all_backptr], axis=0)
    breaks = jnp.concatenate([first_break[None], all_broke], axis=0) & valid
    route_in = jnp.concatenate([first_route[None], all_route], axis=0)  # [T, K]

    with stage("backtrace"):
        if kernel == "assoc" and T >= 2:
            idx = backtrace_assoc(scores_mat, backptr, valid)  # [T]
        else:
            idx = backtrace(scores_mat, backptr, valid)  # [T]

    chosen_score = jnp.take_along_axis(scores_mat, jnp.maximum(idx, 0)[:, None], axis=1)[:, 0]
    chosen_score = jnp.where(idx >= 0, chosen_score, NEG_INF)
    chosen_route = jnp.take_along_axis(route_in, jnp.maximum(idx, 0)[:, None], axis=1)[:, 0]
    chosen_route = jnp.where((idx >= 0) & ~breaks, chosen_route, jnp.inf)

    # per-trace confidence diagnostics, computed from state already in
    # registers (docs/match-quality.md "Kernel confidence"): the
    # winner-vs-runner-up viterbi margin per point (small margin = the
    # decode was nearly a coin flip between two paths — the ambiguity
    # signal the flight recorder retains low-margin traces on) and the
    # candidate-pool exhaustion flag (all K slots filled: the quadrant
    # sweep may have truncated the true pool).  O(T K) next to the
    # O(T K^2) transition build; XLA dead-code-eliminates it in programs
    # that do not output aux.  Margins inherit the kernels' documented
    # float-associativity ULP wiggle, so they are diagnostics, never part
    # of any bit-exact differential contract.
    with stage("confidence"):
        top1 = jnp.max(scores_mat, axis=1)  # [T]
        am = jnp.argmax(scores_mat, axis=1)
        masked = jnp.where(jnp.arange(k)[None, :] == am[:, None],
                           NEG_INF, scores_mat)
        top2 = jnp.max(masked, axis=1)
        two_alive = (top1 > NEG_INF / 2) & (top2 > NEG_INF / 2) & valid
        marg = top1 - top2
        exhausted = (cand.edge[:, k - 1] >= 0) & valid
        aux = jnp.stack([
            jnp.min(jnp.where(two_alive, marg, jnp.inf)),
            jnp.sum(jnp.where(two_alive, marg, 0.0)),
            jnp.sum(two_alive).astype(jnp.float32),
            jnp.sum(exhausted).astype(jnp.float32),
        ])

    result = MatchResult(cand=cand, idx=idx, breaks=breaks,
                         route_dist=chosen_route, score=chosen_score, aux=aux)
    if carry is None:
        return result

    # seam consistency check: the committed choice of the previous chunk must
    # actually reach this chunk's first chosen candidate, else the "no break"
    # claim at the seam is a lie and association would hit a defensive split
    # with times silently dropped.  Flag it truthfully instead.
    seam_ok = jnp.where(
        (carry.committed >= 0) & (idx[0] >= 0) & ~breaks[0],
        logp0[jnp.maximum(carry.committed, 0), jnp.maximum(idx[0], 0)] > NEG_INF / 2,
        True,
    )
    breaks = breaks.at[0].set(breaks[0] | (~seam_ok & valid[0]))
    result = result._replace(breaks=breaks)

    # carry out: beam state at the last valid point (padded steps froze the
    # scores, so scores_mat[T-1] is already that state).  Renormalise by the
    # running max (argmax-invariant) so float32 magnitude cannot grow without
    # bound over an arbitrarily long streamed trace.
    last = (T - 1) - jnp.argmax(valid[::-1])  # index of last valid point
    any_valid = jnp.any(valid)
    safe_last = jnp.where(any_valid, last, 0)
    out_scores = scores_mat[T - 1]
    smax = jnp.max(out_scores)
    out_scores = jnp.where(
        (out_scores > NEG_INF / 2) & (smax > NEG_INF / 2),
        out_scores - smax, NEG_INF,
    )
    carry_out = TraceCarry(
        scores=out_scores,
        edge=cand.edge[safe_last],
        offset=cand.offset[safe_last],
        x=px[safe_last], y=py[safe_last], t=times[safe_last],
        active=any_valid,
        committed=jnp.where(any_valid, idx[safe_last], jnp.int32(-1)).astype(jnp.int32),
    )
    return result, carry_out


def backtrace(scores_mat: jnp.ndarray, backptr: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Reverse scan over stored backpointers for one trace.  scores_mat/backptr
    [T, K], valid [T] -> chosen slot per point [T] (-1 unmatched).  Segment
    boundaries: padded or unmatched successors restart the chain at the local
    argmax."""
    T = scores_mat.shape[0]

    def back(carry, inputs):
        nxt_idx = carry  # chosen slot at t+1, or -1
        scores_t, backptr_next, valid_next, valid_t = inputs
        local = jnp.argmax(scores_t)
        local = jnp.where(scores_t[local] > NEG_INF / 2, local, -1)
        from_next = jnp.where(nxt_idx >= 0, backptr_next[jnp.where(nxt_idx >= 0, nxt_idx, 0)], -1)
        idx_t = jnp.where(valid_next & (nxt_idx >= 0) & (from_next >= 0), from_next, local)
        idx_t = jnp.where(valid_t, idx_t, -1)
        return idx_t, idx_t

    last_local = jnp.argmax(scores_mat[T - 1])
    last_idx = jnp.where((scores_mat[T - 1, last_local] > NEG_INF / 2) & valid[T - 1], last_local, -1)
    ys = (
        scores_mat[: T - 1][::-1],
        backptr[1:][::-1],
        valid[1:][::-1],
        valid[: T - 1][::-1],
    )
    _, idx_rev = jax.lax.scan(back, last_idx, ys)
    return jnp.concatenate([idx_rev[::-1], last_idx[None]], axis=0)  # [T]


# -- log-depth (assoc) forward ------------------------------------------------
#
# "Temporal Parallelization of Inference in Hidden Markov Models"
# (arXiv:2102.05743): the Viterbi forward recursion is a max-plus matrix
# chain, so all T prefixes can be computed in O(log T) depth with
# jax.lax.associative_scan.  Two extensions are needed for this matcher's
# semantics:
#
#   * break/restart: a step whose sources are all dead (or whose points are
#     further apart than breakage_distance) RESTARTS the HMM at that step's
#     emissions.  Restarts with known positions fold into the scan as
#     segmented max-plus *affine* maps f(s) = flag ? c : s (x) M, which are
#     closed under composition: (f2 . f1) = (flag1|flag2, M1 (x) M2,
#     flag2 ? c2 : c1 (x) M2).  Break POSITIONS, however, depend on score
#     liveness, which no tropical-affine element can express (the restart
#     fires when scores are all dead — an anti-monotone condition).  They
#     are recovered exactly by a separate alive-support recursion over
#     [K] booleans: per-step cost is a [K,K] boolean mask product, ~100x
#     lighter than the scan kernel's max-plus step, and exact because
#     aliveness is a pure reachability property (alive == score above
#     NEG_INF/2; the gap between live and dead scores is ~1e21, so float
#     rounding can never flip it).
#
#   * padding: frozen steps become the identity map (0-diagonal tropical
#     identity), which composes bit-exactly (s[j] + 0.0 == s[j]).
#
# Work/depth tradeoff vs the scan kernel: O(T K^3 log T) flops at O(log T)
# depth against O(T K^2) at O(T) depth — the assoc kernel trades idle
# sequential steps for dense [K,K]x[K,K] contractions the MXU can chew.
# Backpointers need no companion chain: with every prefix score s_{t-1} in
# hand, backptr_t = argmax_i(s_{t-1}[i] + logp_t[i,j]) is one parallel
# batched op over t, bit-identical to the scan kernel's per-step argmax
# whenever the prefix scores agree.


def _forward_assoc(init_scores, logp_all, route_all, emis, gc, valid, p: MatchParams,
                   brk_thresh=None):
    """Log-depth equivalent of the lax.scan forward in match_trace.
    init_scores [K]; logp_all/route_all [T-1, K, K]; emis [T, K]; gc [T-1];
    valid [T].  Returns (all_scores, all_backptr, all_broke, all_route),
    each with leading [T-1], exactly like the sequential scan's stacked
    outputs.  ``brk_thresh`` ([T-1], static presence): the sparse model's
    gap-conditioned per-step breakage thresholds; None = the fixed rule."""
    k = emis.shape[1]
    valid_t = valid[1:]  # [T-1]
    feasible = logp_all > NEG_INF / 2  # [T-1, K, K]
    emis_alive = emis > NEG_INF / 2  # [T, K]
    hard = gc > (p.breakage_distance if brk_thresh is None
                 else brk_thresh)  # [T-1]

    # (1) alive-support recursion -> exact break flags.  Sequential, but the
    # carried state is [K] booleans and the per-step op a mask product — the
    # heavy tropical chain below is what moves to log depth.
    def sstep(alive, inputs):
        feas_t, ealive_t, hard_t, valid_step = inputs
        conn = jnp.any(alive[:, None] & feas_t, axis=0)  # [K]
        broke = hard_t | ~jnp.any(conn)
        new_alive = jnp.where(broke, ealive_t, conn & ealive_t)
        new_alive = jnp.where(valid_step, new_alive, alive)  # padding: freeze
        return new_alive, broke

    alive0 = init_scores > NEG_INF / 2
    _, broke_all = jax.lax.scan(
        sstep, alive0, (feasible, emis_alive[1:], hard, valid_t))  # [T-1]

    # (2) segmented tropical affine maps: element t is f_t(s) =
    # flag_t ? emis_t : s (x) M_t, with M_t folding the emission into the
    # transition and padded steps the tropical identity (freeze).
    eye = jnp.where(jnp.eye(k, dtype=bool), 0.0, NEG_INF)
    M = logp_all + emis[1:][:, None, :]  # [T-1, K src, K dst]
    M = jnp.where(valid_t[:, None, None], M, eye[None])
    flag = broke_all & valid_t
    c = jnp.where(flag[:, None], emis[1:], NEG_INF)

    def combine(a, b):
        fa, ma, ca = a
        fb, mb, cb = b
        mab = jnp.max(ma[..., :, :, None] + mb[..., None, :, :], axis=-2)
        ca_b = jnp.max(ca[..., :, None] + mb, axis=-2)
        return fa | fb, mab, jnp.where(fb[..., None], cb, ca_b)

    flags, ms, cs = jax.lax.associative_scan(combine, (flag, M, c), axis=0)
    prop = jnp.max(init_scores[None, :, None] + ms, axis=1)  # [T-1, K]
    all_scores = jnp.where(flags[:, None], cs, prop)

    # (3) backpointers/routes in parallel from the prefix scores — the same
    # formulas as the sequential step, batched over t.
    prev_scores = jnp.concatenate([init_scores[None], all_scores[:-1]], axis=0)
    total = prev_scores[:, :, None] + logp_all  # [T-1, K src, K dst]
    best_src = jnp.argmax(total, axis=1).astype(jnp.int32)  # [T-1, K]
    best_val = jnp.max(total, axis=1)
    connected = best_val > NEG_INF / 2
    backptr = jnp.where(broke_all[:, None] | ~connected, -1, best_src)
    backptr = jnp.where(valid_t[:, None], backptr,
                        jnp.full_like(backptr, -2))  # -2 = padded step
    all_broke = broke_all & valid_t
    chosen = jnp.take_along_axis(route_all, best_src[:, None, :], axis=1)[:, 0, :]
    all_route = jnp.where(connected, chosen, jnp.inf)
    return all_scores, backptr, all_broke, all_route


def backtrace_assoc(scores_mat: jnp.ndarray, backptr: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Log-depth backtrace: same contract as ``backtrace``.  Each reverse
    step is a function idx_{t+1} -> idx_t over the finite domain
    {-1, 0..K-1}; such maps are [K+1] index vectors (slot K encodes -1) and
    compose by gather, so the whole chain is one associative_scan."""
    T, k = scores_mat.shape
    local = jnp.argmax(scores_mat[: T - 1], axis=1)  # [T-1]
    local_score = jnp.take_along_axis(
        scores_mat[: T - 1], local[:, None], axis=1)[:, 0]
    local = jnp.where(local_score > NEG_INF / 2, local, -1).astype(jnp.int32)
    bp_next = backptr[1:]  # [T-1, K]
    # image of n in 0..K-1 (a chosen slot at t+1), then of n = -1 (slot K)
    maps = jnp.where(valid[1:, None] & (bp_next >= 0),
                     bp_next.astype(jnp.int32), local[:, None])
    maps = jnp.concatenate([maps, local[:, None]], axis=1)  # [T-1, K+1]
    maps = jnp.where(valid[: T - 1, None], maps, -1)

    def compose(a, b):
        # reverse=True scans the flipped sequence, so ``a`` accumulates the
        # LATER maps and ``b`` is the next earlier one; the chain walks from
        # T-1 down (later maps apply first), hence comp[n] = b[enc(a[n])]
        enc = jnp.where(a >= 0, a, k)
        return jnp.take_along_axis(b, enc, axis=-1)

    suffix = jax.lax.associative_scan(compose, maps, axis=0, reverse=True)
    last_local = jnp.argmax(scores_mat[T - 1])
    last_idx = jnp.where(
        (scores_mat[T - 1, last_local] > NEG_INF / 2) & valid[T - 1],
        last_local, -1).astype(jnp.int32)
    head = suffix[:, jnp.where(last_idx >= 0, last_idx, k)]  # [T-1]
    return jnp.concatenate([head, last_idx[None]], axis=0)  # [T]


def match_batch(dg: DeviceGraph, du: DeviceUBODT, px, py, times, valid, p: MatchParams, k: int,
                kernel: str = "scan", dedup: bool = False,
                sp: "SparseParams | None" = None) -> MatchResult:
    """px/py/times/valid: [B, T] -> MatchResult leaves with leading [B].

    precompute_batch (hoisted gathers, optional in-batch probe dedup) +
    the vmapped carry-free chain — the same composition match_trace fuses
    per trace, with the gather-bound stage at batch level.  ``sp`` (static
    presence) selects the sparse-gap model; its traced scalars are shared
    across the batch like MatchParams."""
    import functools

    pre = precompute_batch(dg, du, px, py, times, valid, p, k, dedup, sp)
    fn = functools.partial(chain_trace, kernel=kernel, sp=sp)
    res = jax.vmap(fn, in_axes=(None, None, 0, 0, 0, 0, 0, None, None))(
        dg, du, pre, px, py, times, valid, p, k
    )
    return res._replace(probe=pre.probe)


class CompactMatch(NamedTuple):
    """Per-point chosen match, gathered on device so only [B, T] arrays cross
    the host boundary (the full MatchResult is [B, T, K] — K times the
    transfer for fields the host never reads).  ``aux`` is the per-trace
    confidence diagnostic block ([B, 4] f32, see MatchResult.aux); it rides
    the *_aux packed entry points only and stays None on the classic
    transport, whose [3, B, T] wire layout is pinned by tests."""

    edge: jnp.ndarray  # [B, T] i32 matched edge, -1 unmatched
    offset: jnp.ndarray  # [B, T] f32 metres along edge
    breaks: jnp.ndarray  # [B, T] bool
    aux: "jnp.ndarray | None" = None  # [B, 4] f32 confidence diagnostics
    probe: "jnp.ndarray | None" = None  # [B, 3] i32 steps by probe path


def match_batch_compact(dg: DeviceGraph, du: DeviceUBODT, px, py, times, valid, p: MatchParams, k: int,
                        kernel: str = "scan", dedup: bool = False,
                        sp: "SparseParams | None" = None) -> CompactMatch:
    """match_batch + on-device gather of the chosen candidate per point."""
    res = match_batch(dg, du, px, py, times, valid, p, k, kernel, dedup, sp)
    return _compact(res)


def _compact(res: MatchResult) -> CompactMatch:
    with stage("compact-gather"):
        sel = jnp.maximum(res.idx, 0)[..., None]  # [B, T, 1]
        edge = jnp.take_along_axis(res.cand.edge, sel, axis=-1)[..., 0]
        offset = jnp.take_along_axis(res.cand.offset, sel, axis=-1)[..., 0]
        edge = jnp.where(res.idx >= 0, edge, -1)
        return CompactMatch(edge=edge, offset=offset, breaks=res.breaks,
                            aux=res.aux, probe=res.probe)


def match_batch_carry(dg: DeviceGraph, du: DeviceUBODT, px, py, times, valid,
                      p: MatchParams, k: int, carry: TraceCarry,
                      kernel: str = "scan"):
    """One chunk of B long traces with carried state.  px/py/times/valid:
    [B, T]; carry leaves have leading [B].  Returns (CompactMatch, carry')."""
    import functools

    fn = functools.partial(match_trace, kernel=kernel)
    res, carry_out = jax.vmap(
        fn, in_axes=(None, None, 0, 0, 0, 0, None, None, 0)
    )(dg, du, px, py, times, valid, p, k, carry)
    return _compact(res), carry_out


# -- packed host<->device transport ------------------------------------------
#
# Every host<->device boundary crossing pays a fixed dispatch/sync cost on top
# of the bytes (~10-100 us on a co-located chip).  The unpacked forward crossed
# that boundary seven times per batch (4 input device_puts + 3 result
# fetches); the packed transport crosses it twice: one [4, B, T] f32 input
# array in, one [3, B, T] i32 result out.  The stack/bitcast work fuses into
# the surrounding program on device and is one numpy stack/view on host.

def pack_inputs(px, py, times, valid):
    """Host-side: one [4, B, T] f32 array from the four [B, T] batch arrays
    (valid encoded as 0.0/1.0).  numpy in, numpy out — feed to device_put."""
    import numpy as np

    return np.stack([
        np.asarray(px, np.float32), np.asarray(py, np.float32),
        np.asarray(times, np.float32),
        np.asarray(valid).astype(np.float32),
    ])


def unpack_inputs(xin):
    """Device-side inverse of pack_inputs: [4, B, T] -> (px, py, times, valid)."""
    return xin[0], xin[1], xin[2], xin[3] != 0


def pack_compact(cm: CompactMatch) -> jnp.ndarray:
    """Device-side: one [3, B, T] i32 array from a CompactMatch (offset
    bitcast to preserve the f32 payload; breaks as 0/1)."""
    return jnp.stack([
        cm.edge.astype(jnp.int32),
        jax.lax.bitcast_convert_type(cm.offset.astype(jnp.float32), jnp.int32),
        cm.breaks.astype(jnp.int32),
    ])


def unpack_compact(out):
    """Host-side inverse of pack_compact: [3, B, T] numpy i32 ->
    (edge i32, offset f32, breaks bool) numpy arrays."""
    import numpy as np

    out = np.asarray(out)
    return out[0], out[1].view(np.float32), out[2] != 0


def match_batch_compact_packed(dg: DeviceGraph, du: DeviceUBODT, xin,
                               p: MatchParams, k: int,
                               kernel: str = "scan",
                               dedup: bool = False):
    """match_batch_compact over a packed [4, B, T] input -> (packed
    [3, B, T], probe [B, 3]): the result and each row's valid steps by the
    UBODT probe path that answered them (TracePre.probe)."""
    px, py, times, valid = unpack_inputs(xin)
    cm = match_batch_compact(dg, du, px, py, times, valid, p, k, kernel,
                             dedup)
    return pack_compact(cm), cm.probe


def match_batch_compact_packed_aux(dg: DeviceGraph, du: DeviceUBODT, xin,
                                   p: MatchParams, k: int,
                                   kernel: str = "scan",
                                   dedup: bool = False):
    """match_batch_compact_packed + the per-trace confidence block:
    (packed [3, B, T], aux [B, 4], probe [B, 3]).  Same match program (the
    packed wire layout is untouched); the aux output merely keeps the
    confidence ops live through XLA's DCE.  The serving matcher dispatches
    this variant when quality diagnostics are enabled
    (docs/match-quality.md)."""
    px, py, times, valid = unpack_inputs(xin)
    cm = match_batch_compact(dg, du, px, py, times, valid, p, k, kernel,
                             dedup)
    return pack_compact(cm), cm.aux, cm.probe


def match_batch_carry_packed(dg: DeviceGraph, du: DeviceUBODT, xin,
                             p: MatchParams, k: int, carry: TraceCarry,
                             kernel: str = "scan"):
    """match_batch_carry over a packed [4, B, T] input -> (packed [3, B, T],
    carry').  The carry pytree stays on device between chunks, so it never
    crosses the transport boundary inside a chunk loop."""
    px, py, times, valid = unpack_inputs(xin)
    cm, carry_out = match_batch_carry(dg, du, px, py, times, valid, p, k, carry,
                                      kernel)
    return pack_compact(cm), carry_out


def precompute_batch_packed(dg: DeviceGraph, du: DeviceUBODT, xin,
                            p: MatchParams, k: int,
                            dedup: bool = False) -> TracePre:
    """Carry-independent precompute over a packed [4, B, T] input ->
    TracePre with leading [B] on every leaf.  For long traces B is
    B_trace x chunks_per_wave: the chunk axis of a trace group folds into
    the batch axis, so the candidate sweep, emissions, and the
    [T-1, K, K] transition build for MANY chunks run as ONE dispatch
    instead of once per carry step — and, with ``dedup``, the UBODT probe
    deduplicates across ALL those chunks' keys at once.  The result stays
    on device and feeds chain_batch_carry_packed chunk by chunk."""
    px, py, times, valid = unpack_inputs(xin)
    return precompute_batch(dg, du, px, py, times, valid, p, k, dedup)


def chain_batch_carry_packed(dg: DeviceGraph, du: DeviceUBODT, pre: TracePre,
                             xin, p: MatchParams, k: int, carry: TraceCarry,
                             kernel: str = "scan"):
    """The carry-dependent remainder of match_batch_carry_packed: seam
    transition + score recursion + backtrace + compact gather over an
    already-precomputed TracePre (leading [B]).  Returns (packed [3, B, T],
    carry').  precompute_batch_packed + this == match_batch_carry_packed,
    op for op."""
    import functools

    px, py, times, valid = unpack_inputs(xin)
    fn = functools.partial(chain_trace, kernel=kernel)
    res, carry_out = jax.vmap(
        fn, in_axes=(None, None, 0, 0, 0, 0, 0, None, None, 0)
    )(dg, du, pre, px, py, times, valid, p, k, carry)
    return pack_compact(_compact(res)), carry_out


def chain_batch_carry_packed_aux(dg: DeviceGraph, du: DeviceUBODT,
                                 pre: TracePre, xin, p: MatchParams, k: int,
                                 carry: TraceCarry, kernel: str = "scan"):
    """chain_batch_carry_packed + the per-chunk confidence block: (packed
    [3, B, T], aux [B, 4], carry').  Aux components are seam-combinable
    (min / + / + / +), so the matcher folds each chunk's block into a
    per-trace total as the chain advances."""
    import functools

    px, py, times, valid = unpack_inputs(xin)
    fn = functools.partial(chain_trace, kernel=kernel)
    res, carry_out = jax.vmap(
        fn, in_axes=(None, None, 0, 0, 0, 0, 0, None, None, 0)
    )(dg, du, pre, px, py, times, valid, p, k, carry)
    return pack_compact(_compact(res)), res.aux, carry_out


def session_step_packed(dg: DeviceGraph, du: DeviceUBODT, xin,
                        p: MatchParams, k: int, carry: TraceCarry,
                        kernel: str = "scan"):
    """The per-vehicle session matcher's incremental step (ROADMAP item 2,
    docs/performance.md "The session matcher"): fold the newly-arrived
    points of B open sessions into ONE fixed-shape [B, W] dispatch.  Each
    row is one session's delta (1..W points, contiguous valid prefix) and
    its carried Viterbi beam; the first transition of every row runs from
    that beam exactly like a long-trace chunk seam, so a stream of W=1
    steps is the same recursion as one windowed decode — the carry-seam
    differential suite pins the two bit-exact.

    Same math as match_batch_carry_packed; the separate entry point exists
    so the serving matcher caches it under its own (kind="session",
    kernel) jit key and always keeps the confidence block live (the
    streaming path is the ambiguity-sensitive one).  Returns
    (packed [3, B, W], aux [B, 4], carry') — the carry pytree is fetched
    to the pinned-host session store between steps ([B, K] floats, exact
    f32 round trip), which is what makes a session serialisable for the
    drain-time beam handoff."""
    import functools

    px, py, times, valid = unpack_inputs(xin)
    fn = functools.partial(match_trace, kernel=kernel)
    res, carry_out = jax.vmap(
        fn, in_axes=(None, None, 0, 0, 0, 0, None, None, 0)
    )(dg, du, px, py, times, valid, p, k, carry)
    return pack_compact(_compact(res)), res.aux, carry_out


def session_step_arena(dg: DeviceGraph, du: DeviceUBODT, xin,
                       p: MatchParams, k: int, slab: TraceCarry,
                       slots: jnp.ndarray, use_carry: jnp.ndarray,
                       kernel: str = "scan"):
    """session_step_packed against a device-resident carry slab
    (docs/performance.md "Device-resident session arenas"): instead of
    uploading a [B, K] carry batch and reading the successor back every
    step, the carried beams live in an [S]-slot arena pytree that stays
    on device across steps.  The step gathers each row's beam by slot
    index, runs the identical per-row recursion, and scatters the
    successor beams back in place — with the slab donated
    (``donate_argnums``) the whole step is ONE dispatch whose only
    host↔device traffic is the packed inputs in and the match results
    out; the beams never cross the interconnect.

    ``slots`` is [B] i32 arena rows (padding rows carry slot == S, which
    the gather clamps and the ``mode="drop"`` scatter discards), and
    ``use_carry`` is [B] bool — False rows decode from the inactive
    carry exactly like a fresh session, so a slot's stale contents
    cannot leak into a rebuilt stream.  Dispatchers must pass each live
    slot at most once per step (the SessionEngine folds a batch to one
    row per session), keeping the scatter well-defined.  Gather/scatter
    moves f32/i32 leaves verbatim, so outputs are bit-identical to the
    host-carry path — the arena differential suite pins that."""
    import functools

    px, py, times, valid = unpack_inputs(xin)
    s_cap = slab.scores.shape[0]
    idx = jnp.minimum(slots, s_cap - 1)
    gathered = jax.tree_util.tree_map(lambda a: a[idx], slab)
    inact = initial_carry_batch(px.shape[0], k)
    use = use_carry

    def _sel(g, i):
        return jnp.where(use.reshape((-1,) + (1,) * (g.ndim - 1)), g, i)

    carry = jax.tree_util.tree_map(_sel, gathered, inact)
    fn = functools.partial(match_trace, kernel=kernel)
    res, carry_out = jax.vmap(
        fn, in_axes=(None, None, 0, 0, 0, 0, None, None, 0)
    )(dg, du, px, py, times, valid, p, k, carry)
    slab_out = jax.tree_util.tree_map(
        lambda s, c: s.at[slots].set(c, mode="drop"), slab, carry_out)
    return pack_compact(_compact(res)), res.aux, slab_out


def _arena_gather_mesh(slab: TraceCarry, slots: jnp.ndarray,
                       batch_axis: str) -> TraceCarry:
    """Gather global-slot beam rows from a slot-sharded slab inside a
    shard_map: the slab's leading [S] axis is split over ``batch_axis``
    (S_local rows per shard) while ``slots`` is the replicated global
    [B] slot map.  Exactly one shard owns any live slot, so each shard
    contributes its owned rows as int32 bit patterns (zero elsewhere)
    and a psum over the shard axis reconstructs the owner's bytes — a
    sum of one nonzero pattern and zeros is EXACT, so the gathered
    carry is bit-identical to a single-device ``slab[slots]`` gather
    (including -0.0 and NaN payloads a float max-select would mangle).
    Padding rows (slot == global S, owned by nobody) come back as
    zeros; callers mask them with ``use_carry`` exactly like the
    single-device step."""
    s_local = slab.scores.shape[0]
    lo = jax.lax.axis_index(batch_axis) * s_local
    loc = jnp.clip(slots - lo, 0, s_local - 1)
    owned = (slots >= lo) & (slots < lo + s_local)

    def _one(leaf):
        x = leaf[loc]
        m = owned.reshape((-1,) + (1,) * (x.ndim - 1))
        if jnp.issubdtype(x.dtype, jnp.floating):
            bits = jax.lax.bitcast_convert_type(x, jnp.int32)
            out = jax.lax.psum(jnp.where(m, bits, 0), batch_axis)
            return jax.lax.bitcast_convert_type(out, x.dtype)
        if x.dtype == jnp.bool_:
            out = jax.lax.psum(
                jnp.where(m, x.astype(jnp.int32), 0), batch_axis)
            return out > 0
        return jax.lax.psum(jnp.where(m, x, 0), batch_axis)

    return jax.tree_util.tree_map(_one, slab)


def _arena_scatter_mesh(slab: TraceCarry, carry_out: TraceCarry,
                        slots: jnp.ndarray, batch_axis: str) -> TraceCarry:
    """Scatter a shard's successor beams back into the slot-sharded
    slab: the local [b_local] carry block is all_gather'd to the global
    [B] batch (every shard needs rows whose slots IT owns, wherever
    they were decoded), then each shard writes exactly its owned rows —
    unowned and padding rows target the out-of-bounds local index and
    the ``mode="drop"`` scatter discards them, the same contract as the
    single-device step."""
    s_local = slab.scores.shape[0]
    lo = jax.lax.axis_index(batch_axis) * s_local
    owned = (slots >= lo) & (slots < lo + s_local)
    tgt = jnp.where(owned, jnp.clip(slots - lo, 0, s_local - 1), s_local)

    def _one(s, c):
        cg = jax.lax.all_gather(c, batch_axis, axis=0, tiled=True)
        return s.at[tgt].set(cg, mode="drop")

    return jax.tree_util.tree_map(_one, slab, carry_out)


def session_step_arena_mesh(dg: DeviceGraph, du: DeviceUBODT, xin,
                            p: MatchParams, k: int, slab: TraceCarry,
                            slots: jnp.ndarray, use_carry: jnp.ndarray,
                            kernel: str = "scan", sp=None,
                            batch_axis: str = "dp"):
    """session_step_arena inside a shard_map over a device mesh
    (docs/performance.md "One logical matcher per pod"): the beam slab's
    slot axis is sharded over ``batch_axis`` so a replica's carried
    beams live in POD-level HBM, while the packed inputs ride the batch
    axis as usual and ``slots``/``use_carry`` arrive replicated (every
    shard needs the full slot map to resolve ownership).  Gather and
    scatter move exact int32 bit patterns through one psum/all_gather
    pair over tiny [B, K] blocks, so the step's wire output — and the
    slab bytes — are bit-identical to the single-device arena step; the
    slab is still donated by the dispatcher, so the in-place zero-
    per-step-transfer contract survives the mesh.  ``sp`` selects the
    sparse-cohort transition model (None = dense), mirroring the
    session_step_arena / session_step_arena_sparse pair."""
    import functools

    px, py, times, valid = unpack_inputs(xin)
    b_local = px.shape[0]
    i0 = jax.lax.axis_index(batch_axis) * b_local
    gathered_g = _arena_gather_mesh(slab, slots, batch_axis)
    gathered = jax.tree_util.tree_map(
        lambda g: jax.lax.dynamic_slice_in_dim(g, i0, b_local, axis=0),
        gathered_g)
    use = jax.lax.dynamic_slice_in_dim(use_carry, i0, b_local, axis=0)
    inact = initial_carry_batch(b_local, k)

    def _sel(g, i):
        return jnp.where(use.reshape((-1,) + (1,) * (g.ndim - 1)), g, i)

    carry = jax.tree_util.tree_map(_sel, gathered, inact)
    fn = functools.partial(match_trace, kernel=kernel, sp=sp)
    res, carry_out = jax.vmap(
        fn, in_axes=(None, None, 0, 0, 0, 0, None, None, 0)
    )(dg, du, px, py, times, valid, p, k, carry)
    slab_out = _arena_scatter_mesh(slab, carry_out, slots, batch_axis)
    return pack_compact(_compact(res)), res.aux, slab_out


# -- sparse-gap packed entry points -------------------------------------------
#
# The sparse-gap matching model (docs/match-quality.md "Sparse gaps") rides
# its own packed entry points so the serving matcher caches them under
# distinct (kind, kernel) jit keys: dense traffic keeps dispatching the
# byte-identical classic programs, while sparse cohorts pay one extra
# compile per shape for the time-adaptive variants.  SparseParams (and the
# per-cohort MatchParams they ride next to) are traced scalars, so every
# calibrated cohort shares ONE compiled program per shape.  All sparse
# entries return the confidence aux block — sparse decodes are the
# ambiguity-sensitive ones, and the calibration plane scores them.


def match_batch_compact_packed_sparse(dg: DeviceGraph, du: DeviceUBODT, xin,
                                      p: MatchParams, sp: SparseParams,
                                      k: int, kernel: str = "scan",
                                      dedup: bool = False):
    """The sparse-cohort twin of match_batch_compact_packed_aux: packed
    [4, B, T] in -> (packed [3, B, T], aux [B, 4], probe [B, 3]), with the
    time-adaptive transition model and gap-conditioned breakage applied."""
    px, py, times, valid = unpack_inputs(xin)
    cm = match_batch_compact(dg, du, px, py, times, valid, p, k, kernel,
                             dedup, sp)
    return pack_compact(cm), cm.aux, cm.probe


def precompute_batch_packed_sparse(dg: DeviceGraph, du: DeviceUBODT, xin,
                                   p: MatchParams, sp: SparseParams, k: int,
                                   dedup: bool = False) -> TracePre:
    """precompute_batch_packed under the sparse transition model — the
    long-trace chunk-batched precompute for sparse cohorts."""
    px, py, times, valid = unpack_inputs(xin)
    return precompute_batch(dg, du, px, py, times, valid, p, k, dedup, sp)


def chain_batch_carry_packed_sparse(dg: DeviceGraph, du: DeviceUBODT,
                                    pre: TracePre, xin, p: MatchParams,
                                    sp: SparseParams, k: int,
                                    carry: TraceCarry, kernel: str = "scan"):
    """chain_batch_carry_packed_aux under the sparse model: the seam
    transition and per-step breakage are gap-conditioned.  Returns
    (packed [3, B, T], aux [B, 4], carry')."""
    import functools

    px, py, times, valid = unpack_inputs(xin)
    fn = functools.partial(chain_trace, kernel=kernel, sp=sp)
    res, carry_out = jax.vmap(
        fn, in_axes=(None, None, 0, 0, 0, 0, 0, None, None, 0)
    )(dg, du, pre, px, py, times, valid, p, k, carry)
    return pack_compact(_compact(res)), res.aux, carry_out


def session_step_packed_sparse(dg: DeviceGraph, du: DeviceUBODT, xin,
                               p: MatchParams, sp: SparseParams, k: int,
                               carry: TraceCarry, kernel: str = "scan"):
    """session_step_packed under the sparse model: the per-vehicle
    incremental step at the reference BatchingProcessor's sparse operating
    point (≥ 45 s between points IS the streaming regime).  K stays the
    carried beam width — a session's beam cannot change width mid-life —
    so of the sparse levers, sessions get the time-adaptive transitions,
    the gap-conditioned breakage, and the widened radius, while the wider
    candidate budget applies to windowed dispatches only
    (docs/match-quality.md)."""
    import functools

    px, py, times, valid = unpack_inputs(xin)
    fn = functools.partial(match_trace, kernel=kernel, sp=sp)
    res, carry_out = jax.vmap(
        fn, in_axes=(None, None, 0, 0, 0, 0, None, None, 0)
    )(dg, du, px, py, times, valid, p, k, carry)
    return pack_compact(_compact(res)), res.aux, carry_out


def session_step_arena_sparse(dg: DeviceGraph, du: DeviceUBODT, xin,
                              p: MatchParams, sp: SparseParams, k: int,
                              slab: TraceCarry, slots: jnp.ndarray,
                              use_carry: jnp.ndarray, kernel: str = "scan"):
    """session_step_arena under the sparse model: the device-resident
    slab step for sparse cohorts, gap-conditioned exactly like
    session_step_packed_sparse.  Same gather → decode → in-place scatter
    contract; the slab is donated by the dispatcher."""
    import functools

    px, py, times, valid = unpack_inputs(xin)
    s_cap = slab.scores.shape[0]
    idx = jnp.minimum(slots, s_cap - 1)
    gathered = jax.tree_util.tree_map(lambda a: a[idx], slab)
    inact = initial_carry_batch(px.shape[0], k)
    use = use_carry

    def _sel(g, i):
        return jnp.where(use.reshape((-1,) + (1,) * (g.ndim - 1)), g, i)

    carry = jax.tree_util.tree_map(_sel, gathered, inact)
    fn = functools.partial(match_trace, kernel=kernel, sp=sp)
    res, carry_out = jax.vmap(
        fn, in_axes=(None, None, 0, 0, 0, 0, None, None, 0)
    )(dg, du, px, py, times, valid, p, k, carry)
    slab_out = jax.tree_util.tree_map(
        lambda s, c: s.at[slots].set(c, mode="drop"), slab, carry_out)
    return pack_compact(_compact(res)), res.aux, slab_out


def initial_carry_batch(b: int, k: int) -> TraceCarry:
    """Inactive carry for a batch of b traces."""
    one = TraceCarry.inactive(k)
    return jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (b,) + a.shape), one)
