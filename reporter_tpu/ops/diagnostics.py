"""Device-side diagnostic counters — off the product hot path.

``ubodt_probe_stats`` quantifies the accuracy bound the delta-truncated
UBODT imposes (VERDICT r04 next #4): the table only holds routes up to
``ubodt_delta`` metres, while Meili routes on-line up to
``max_route_distance_factor * (gc + search_radius)`` (~10 km near the
2000 m breakage default, /root/reference/Dockerfile:42-48) — so any
candidate pair whose true route exceeds delta hard-misses and becomes a
transition break.  This counter measures how often the fleet actually
drives into that bound, which is the evidence the default needs
(docs/ubodt-delta.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .candidates import find_candidates_batch
from .hashtable import count_distinct_pairs, ubodt_lookup
from .viterbi import MatchParams, unpack_inputs


def ubodt_probe_stats(dg, du, xin, p: MatchParams, k: int,
                      delta: float) -> jnp.ndarray:
    """Count transition-probe outcomes over a packed [4, B, T] batch.

    ``delta``: the table's build bound (metres) — a table property, so it
    is a parameter here, not a MatchParams field.

    Returns int32 [5]:
      [0] pairs        valid candidate pairs needing a table probe
                       (same-edge pairs resolve without the table and are
                       excluded)
      [1] misses       probes the table could not answer (no row: either no
                       path at all, or true route > delta)
      [2] costly_miss  misses on pairs the HMM would otherwise have kept
                       (gc <= breakage_distance): each one forces a
                       transition break, whether the cause is a genuine
                       no-path or the delta bound — the transition
                       infeasibility actually fed by table misses
      [3] beyond_delta subset of costly_miss with gc > delta: any existing
                       route is at least gc, hence > delta — these are
                       PROVABLE delta truncations (lower bound on the
                       bound's accuracy cost; the [2]-[3] remainder is
                       no-path or truncation, indistinguishable without an
                       on-line router)
      [4] distinct     distinct (src, dst) node pairs among [0] across the
                       WHOLE batch — pairs/distinct is the in-batch probe
                       redundancy the dedup path exploits
                       (bench ``probe_dedup``;
                       docs/performance.md memory-system section)
    """
    px, py, tm, valid = unpack_inputs(xin)

    def one(px, py, v):
        cand = find_candidates_batch(dg, px, py, k, p.search_radius)
        ea, eb = cand.edge[:-1], cand.edge[1:]  # [T-1, K]
        era = dg.edge_rows[jnp.maximum(ea, 0)]
        erb = dg.edge_rows[jnp.maximum(eb, 0)]
        to_a = jax.lax.bitcast_convert_type(era[..., 0], jnp.int32)
        from_b = jax.lax.bitcast_convert_type(erb[..., 1], jnp.int32)
        sp, _sp_t, _ = ubodt_lookup(
            du, to_a[:, :, None], from_b[:, None, :])  # [T-1, K, K]
        gc = jnp.hypot(px[1:] - px[:-1], py[1:] - py[:-1])[:, None, None]
        pv = ((ea[:, :, None] >= 0) & (eb[:, None, :] >= 0)
              & (v[:-1] & v[1:])[:, None, None])
        same = (ea[:, :, None] == eb[:, None, :]) & (ea[:, :, None] >= 0)
        need = pv & ~same
        miss = need & ~jnp.isfinite(sp)
        costly = miss & (gc <= p.breakage_distance)
        beyond = costly & (gc > delta)
        cnt = lambda m: jnp.sum(m.astype(jnp.int32))
        counts = jnp.stack([cnt(need), cnt(miss), cnt(costly), cnt(beyond)])
        keys = (jnp.broadcast_to(to_a[:, :, None], need.shape),
                jnp.broadcast_to(from_b[:, None, :], need.shape))
        return counts, keys, need

    counts, keys, need = jax.vmap(one)(px, py, valid)
    # distinct pairs are a batch-level property (the dedup path sorts the
    # whole dispatch's key set), so count OUTSIDE the vmap
    distinct = count_distinct_pairs(keys[0], keys[1], need)
    return jnp.concatenate(
        [jnp.sum(counts, axis=0), distinct[None].astype(jnp.int32)])
