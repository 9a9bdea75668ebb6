"""The packed host<->device transport (ops/viterbi.pack_inputs /
pack_compact and their inverses).

Batches cross the device boundary as ONE [4, B, T] f32 array and results
come back as ONE [3, B, T] i32 array because every crossing pays a fixed
dispatch/sync cost (the r03 unpacked convention paid it 4 puts + 3
fetches per batch).  These tests pin the roundtrip semantics the matcher and bench
both rely on.
"""

import numpy as np
import pytest

from reporter_tpu.matching import MatcherConfig, SegmentMatcher
from reporter_tpu.tiles.arrays import build_graph_arrays
from reporter_tpu.tiles.network import grid_city
from reporter_tpu.tiles.ubodt import build_ubodt


@pytest.fixture(scope="module")
def arrays():
    return build_graph_arrays(grid_city(rows=5, cols=5, spacing_m=150.0), cell_size=100.0)


@pytest.fixture(scope="module")
def ubodt(arrays):
    return build_ubodt(arrays, delta=2000.0)


def test_pack_inputs_roundtrip():
    from reporter_tpu.ops.viterbi import pack_inputs, unpack_inputs

    rng = np.random.default_rng(3)
    px = rng.normal(size=(5, 7)).astype(np.float32)
    py = rng.normal(size=(5, 7)).astype(np.float32)
    tm = rng.uniform(0, 1e4, size=(5, 7)).astype(np.float32)
    valid = rng.integers(0, 2, size=(5, 7)).astype(bool)

    xin = pack_inputs(px, py, tm, valid)
    assert xin.shape == (4, 5, 7) and xin.dtype == np.float32

    ux, uy, ut, uv = unpack_inputs(xin)  # works on numpy too
    np.testing.assert_array_equal(np.asarray(ux), px)
    np.testing.assert_array_equal(np.asarray(uy), py)
    np.testing.assert_array_equal(np.asarray(ut), tm)
    np.testing.assert_array_equal(np.asarray(uv), valid)


def test_pack_compact_roundtrip_preserves_float_payload():
    import jax.numpy as jnp

    from reporter_tpu.ops.viterbi import CompactMatch, pack_compact, unpack_compact

    rng = np.random.default_rng(4)
    edge = rng.integers(-1, 1 << 30, size=(3, 6)).astype(np.int32)
    # offsets include negatives, denormal-ish smalls, and exact values that
    # must survive bit-exactly through the i32 bitcast
    offset = np.array([
        [0.0, -0.0, 1.5, 3.1415927, 1e-38, 2.5e4],
        [7.25, -13.5, 0.1, 1e30, -1e-30, 5.0],
        [123.456, 0.333, 9.75, -2.0, 6.1e-5, 8e7],
    ], np.float32)
    breaks = rng.integers(0, 2, size=(3, 6)).astype(bool)

    packed = pack_compact(CompactMatch(
        edge=jnp.asarray(edge), offset=jnp.asarray(offset), breaks=jnp.asarray(breaks)))
    assert packed.shape == (3, 3, 6) and packed.dtype == jnp.int32

    e, o, b = unpack_compact(np.asarray(packed))
    np.testing.assert_array_equal(e, edge)
    assert o.dtype == np.float32
    np.testing.assert_array_equal(o.view(np.int32), offset.view(np.int32))  # bit-exact
    np.testing.assert_array_equal(b, breaks)


def _mk_trace(arrays, uuid, n, seed=0, jitter=3.0):
    rng = np.random.default_rng(seed)
    ax = float(arrays.node_x[arrays.edge_from[0]])
    ay = float(arrays.node_y[arrays.edge_from[0]])
    bx = float(arrays.node_x[arrays.edge_to[0]])
    by = float(arrays.node_y[arrays.edge_to[0]])
    xs = np.linspace(ax, bx, n) + rng.normal(0, jitter, n)
    ys = np.linspace(ay, by, n) + rng.normal(0, jitter, n)
    lat, lon = arrays.proj.to_latlon(xs, ys)
    return {"uuid": uuid, "trace": [
        {"lat": float(a), "lon": float(o), "time": 1000.0 + 5.0 * i}
        for i, (a, o) in enumerate(zip(lat, lon))]}


def test_matcher_output_unchanged_by_wave_size(arrays, ubodt, monkeypatch):
    """Long traces must produce identical results whether chunk outputs are
    fetched in one wave or many (MAX_DEFERRED_CHUNKS bounds device memory,
    never semantics)."""
    import reporter_tpu.matching.matcher as mm

    m = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=MatcherConfig())
    trace = _mk_trace(arrays, "wave", 1400, seed=11)
    ref = m.match(trace)
    assert ref["segments"]
    monkeypatch.setattr(mm, "MAX_DEFERRED_CHUNKS", 1)
    assert m.match(trace) == ref
    monkeypatch.setattr(mm, "MAX_DEFERRED_CHUNKS", 2)
    assert m.match(trace) == ref


@pytest.mark.parametrize("kernel", ["scan", "assoc"])
def test_precompute_chain_composition_bit_identical(arrays, ubodt, kernel):
    """precompute_batch_packed + chain_batch_carry_packed (the hoisted
    long-trace program pair) must equal match_batch_carry_packed (the fused
    legacy program) BIT-exactly: packed outputs and every carry leaf, and
    again on a second chunk fed the first chunk's carry.  This is the
    ops-level contract the matcher-level differential
    (tests/test_fuzz_differential.py) rides on."""
    import functools

    import jax
    import jax.numpy as jnp

    from reporter_tpu.ops.viterbi import (
        MatchParams, chain_batch_carry_packed, initial_carry_batch,
        match_batch_carry_packed, pack_inputs, precompute_batch_packed,
    )

    cfg = MatcherConfig()
    p = MatchParams.from_config(cfg)
    k = cfg.beam_k
    dg, du = arrays.to_device(), ubodt.to_device()

    rng = np.random.default_rng(9)
    B, T = 4, 20
    px = rng.uniform(arrays.node_x.min(), arrays.node_x.max(),
                     (B, T)).astype(np.float32)
    py = rng.uniform(arrays.node_y.min(), arrays.node_y.max(),
                     (B, T)).astype(np.float32)
    tm = np.tile(np.arange(T, dtype=np.float32) * 5.0, (B, 1))
    valid = np.ones((B, T), bool)
    valid[2, 7:] = False  # padded tail mid-batch
    valid[3, :] = False  # all-pad row
    xin = jnp.asarray(pack_inputs(px, py, tm, valid))

    fused = jax.jit(functools.partial(match_batch_carry_packed, kernel=kernel),
                    static_argnums=(4,))
    jpre = jax.jit(precompute_batch_packed, static_argnums=(4,))
    jchain = jax.jit(functools.partial(chain_batch_carry_packed, kernel=kernel),
                     static_argnums=(5,))

    carry_f = carry_s = initial_carry_batch(B, k)
    pre = jpre(dg, du, xin, p, k)
    for _chunk in range(2):  # second round exercises an ACTIVE carry seam
        out_f, carry_f = fused(dg, du, xin, p, k, carry_f)
        out_s, carry_s = jchain(dg, du, pre, xin, p, k, carry_s)
        np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_s))
        for a, b in zip(jax.tree_util.tree_leaves(carry_f),
                        jax.tree_util.tree_leaves(carry_s)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_matcher_jax_vs_cpu_after_packing(arrays, ubodt):
    """The packed transport must not perturb the device/oracle diffability
    contract (segment-for-segment identical on clean traces)."""
    cfg = MatcherConfig()
    mj = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg)
    mc = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg, backend="cpu")
    traces = [_mk_trace(arrays, "t%d" % i, 12 + 9 * i, seed=i) for i in range(4)]
    out_j = mj.match_many(traces)
    out_c = mc.match_many(traces)
    ids = lambda r: [s.get("segment_id") for s in r["segments"]]
    assert [ids(r) for r in out_j] == [ids(r) for r in out_c]
