"""Per-step compaction of the UBODT probe (ops/hashtable.ubodt_lookup_steps).

The compacted probe gathers each step's distinct (src, dst) node pairs once
and rebuilds the K x K answers by exact selects; a step with more than C
distinct nodes a side goes to a static overflow lane probed at K x K, and a
dispatch whose lane overflows probes every step at K x K.  Every case here
holds the answers bit-identical to the plain probe at every valid step and
checks which path the counts say answered each step: on crafted steps with
exactly C and C + 1 distinct nodes, on real candidate sets, under the
gp-sharded probe, in whole programs against the uncompacted precompute, and
through the matcher's counter.
"""

import functools

import numpy as np
import pytest

from reporter_tpu.matching.config import MatcherConfig
from reporter_tpu.tiles.arrays import build_graph_arrays
from reporter_tpu.tiles.network import grid_city
from reporter_tpu.tiles.ubodt import build_ubodt


@pytest.fixture(scope="module")
def world():
    # 100 m blocks under a 50 m radius: a point near a junction sees its
    # four streets both ways, as on the metro city
    city = grid_city(rows=8, cols=8, spacing_m=100.0)
    arrays = build_graph_arrays(city, cell_size=100.0)
    ubodt = build_ubodt(arrays, delta=2000.0)
    return city, arrays, ubodt, arrays.to_device(), ubodt.to_device()


def _bits(x):
    return np.asarray(x).view(np.int32)


def _plain(du, src, dst):
    from reporter_tpu.ops.hashtable import ubodt_lookup

    d, t, _ = ubodt_lookup(du, src[..., :, None], dst[..., None, :])
    return d, t


def _compacted(du, src, dst, valid):
    import jax

    from reporter_tpu.ops import hashtable as H

    # a fresh jit per call: monkeypatched widths must retrace
    return jax.jit(lambda *a: H.ubodt_lookup_steps(*a))(du, src, dst, valid)


def _crafted(rng, n_nodes, distinct, k):
    """[B, S, k] node ids; step (b, s) holds exactly distinct[b, s]
    different nodes, repeated over its k slots in random order."""
    nb, ns = distinct.shape
    out = np.empty((nb, ns, k), np.int32)
    for b in range(nb):
        for s in range(ns):
            vals = rng.choice(n_nodes, distinct[b, s], replace=False)
            slots = np.concatenate([vals, rng.choice(vals, k - len(vals))])
            out[b, s] = rng.permutation(slots)
    return out


def _assert_same(du, src, dst, valid, got):
    d0, t0 = _plain(du, src, dst)
    d1, t1, _ = got
    m = np.asarray(valid)
    np.testing.assert_array_equal(_bits(d1)[m], _bits(d0)[m])
    np.testing.assert_array_equal(_bits(t1)[m], _bits(t0)[m])


def test_widths():
    from reporter_tpu.ops.hashtable import compact_width, lane_width

    assert [compact_width(k) for k in (4, 8, 12, 16)] == [4, 6, 7, 8]
    assert lane_width(1) == 8 and lane_width(124) == 8
    assert lane_width(64 * 256) == 256 and lane_width(64 * 256 + 1) == 264


def _lane_case(rng, n_nodes, k, nb, ns, n_over):
    """(src, dst, valid, n_over): steps of 1..C distinct nodes, some at
    exactly C, ``n_over`` valid steps at C + 1 on one side, and invalid
    tail steps of K distinct nodes, which must not take the lane."""
    from reporter_tpu.ops.hashtable import compact_width

    c = compact_width(k)
    ds = rng.integers(1, c + 1, (nb, ns))
    dd = rng.integers(1, c + 1, (nb, ns))
    ds[:, ::5] = c
    dd[:, 1::5] = c
    valid = np.ones((nb, ns), bool)
    valid[nb // 2:, ns - 4:] = False
    ds[~valid] = k
    sites = rng.permutation(np.argwhere(valid))[:n_over]
    for i, (b, s) in enumerate(sites):
        (ds if i % 2 else dd)[b, s] = c + 1
    return (_crafted(rng, n_nodes, ds, k), _crafted(rng, n_nodes, dd, k),
            valid)


@pytest.mark.parametrize("k", [8, 16])
def test_lane_exact(world, k):
    """Steps at exactly C stay compact, C + 1 valid steps take the lane,
    invalid ones do not."""
    import jax.numpy as jnp

    from reporter_tpu.ops.hashtable import lane_width

    _city, arrays, _u, _dg, du = world
    rng = np.random.default_rng(k)
    nb, ns = 4, 31
    n_over = lane_width(nb * ns) - 1
    src, dst, valid = _lane_case(rng, arrays.num_nodes, k, nb, ns, n_over)
    got = _compacted(du, jnp.asarray(src), jnp.asarray(dst),
                     jnp.asarray(valid))
    _assert_same(du, src, dst, valid, got)
    n_valid = int(valid.sum())
    assert np.asarray(got[2]).sum(axis=0).tolist() == [
        n_valid - n_over, n_over, 0]


@pytest.mark.parametrize("k,nb", [(8, 4), (16, 4), (16, 3)])
def test_lane_overflow_falls_back(world, k, nb):
    """More overflowing steps than the lane holds: the whole dispatch is
    probed at K x K (in row slices), every step bit-identical."""
    import jax.numpy as jnp

    from reporter_tpu.ops.hashtable import lane_width

    _city, arrays, _u, _dg, du = world
    rng = np.random.default_rng(100 + k + nb)
    ns = 31
    src, dst, valid = _lane_case(rng, arrays.num_nodes, k, nb, ns,
                                 lane_width(nb * ns) + 1)
    got = _compacted(du, jnp.asarray(src), jnp.asarray(dst),
                     jnp.asarray(valid))
    _assert_same(du, src, dst, np.ones_like(valid), got)
    assert np.asarray(got[2]).sum(axis=0).tolist() == [0, 0, int(valid.sum())]


def _candidate_nodes(world, k, nb, t, seed):
    """(to_a, from_b, valid) as precompute_batch builds them from real
    candidate sets of random points on the city, tails padded."""
    import jax
    import jax.numpy as jnp

    from reporter_tpu.ops.candidates import find_candidates_batch

    _city, arrays, _u, dg, _du = world
    rng = np.random.default_rng(seed)
    px = rng.uniform(arrays.node_x.min(), arrays.node_x.max(), (nb, t))
    py = rng.uniform(arrays.node_y.min(), arrays.node_y.max(), (nb, t))
    cand = find_candidates_batch(dg, jnp.asarray(px, jnp.float32),
                                 jnp.asarray(py, jnp.float32), k,
                                 jnp.float32(50.0))
    er = dg.edge_rows[jnp.where(cand.edge >= 0, cand.edge, 0)]
    to_a = jax.lax.bitcast_convert_type(er[:, :-1, :, 0], jnp.int32)
    from_b = jax.lax.bitcast_convert_type(er[:, 1:, :, 1], jnp.int32)
    valid = np.ones((nb, t - 1), bool)
    valid[nb // 2:, t // 2:] = False
    return to_a, from_b, valid


@pytest.mark.parametrize("k", [8, 16])
def test_real_candidates(world, k):
    import jax.numpy as jnp

    _city, _arrays, _u, _dg, du = world
    src, dst, valid = _candidate_nodes(world, k, 16, 40, seed=k)
    got = _compacted(du, src, dst, jnp.asarray(valid))
    _assert_same(du, src, dst, valid, got)
    counts = np.asarray(got[2]).sum(axis=0)
    assert counts.sum() == valid.sum()
    assert counts[0] > 0 and counts[2] == 0


@pytest.mark.parametrize("fallback", [False, True])
def test_gp_sharded(world, fallback):
    """The compacted probe inside the gp-sharded shard_map (dp 2 x gp 4):
    each dp shard keeps its own lane; with ``fallback`` one shard
    overflows its lane and probes at K x K while the other does not."""
    import jax
    import jax.numpy as jnp

    from reporter_tpu.ops.hashtable import lane_width, ubodt_lookup_steps
    from reporter_tpu.parallel import make_mesh2
    from reporter_tpu.parallel.rules import shard_map
    from jax.sharding import PartitionSpec as P

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual CPU devices")
    _city, arrays, _u, _dg, du = world
    k, nb, ns = 16, 4, 31
    rng = np.random.default_rng(7 + fallback)
    r = lane_width(nb // 2 * ns)
    halves = [_lane_case(rng, arrays.num_nodes, k, nb // 2, ns,
                         r + 1 if fallback and h == 0 else 3)
              for h in range(2)]
    src, dst, valid = (np.concatenate(x) for x in zip(*halves))
    mesh = make_mesh2(2, 4)
    fn = jax.jit(shard_map(
        lambda u, s, d, v: ubodt_lookup_steps(u.with_shard_axis("gp"),
                                              s, d, v),
        mesh=mesh, in_specs=(P("gp"), P("dp"), P("dp"), P("dp")),
        out_specs=(P("dp"), P("dp"), P("dp"))))
    got = fn(du, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(valid))
    _assert_same(du, src, dst, valid, got)
    counts = np.asarray(got[2])
    lo = counts[: nb // 2].sum(axis=0).tolist()
    hi = counts[nb // 2:].sum(axis=0).tolist()
    n_lo = int(valid[: nb // 2].sum())
    assert lo == ([0, 0, n_lo] if fallback else [n_lo - 3, 3, 0])
    assert hi == [int(valid[nb // 2:].sum()) - 3, 3, 0]


# -- whole programs against the uncompacted precompute -----------------------


def _uncompacted_steps(du, src, dst, step_valid, dedup=False):
    """The probe as precompute_batch made it before compaction."""
    import jax.numpy as jnp

    from reporter_tpu.ops.hashtable import ubodt_lookup

    d, t, _ = ubodt_lookup(du, src[..., :, None], dst[..., None, :],
                           dedup=dedup)
    return d, t, jnp.zeros(src.shape[:1] + (3,), jnp.int32)


def _program_input(world, nb=12, t=32, seed=5):
    from reporter_tpu.ops.viterbi import pack_inputs
    from reporter_tpu.synth import TraceSynthesizer

    _city, arrays, _u, _dg, _du = world
    synth = TraceSynthesizer(arrays, seed=seed)
    px = np.zeros((nb, t), np.float32)
    py = np.zeros((nb, t), np.float32)
    tm = np.zeros((nb, t), np.float32)
    valid = np.zeros((nb, t), bool)
    for b in range(nb):
        n = t - 3 * (b % 4)
        tr = synth.synthesize(n, dt=30.0, sigma=4.0,
                              max_tries=300).trace["trace"]
        x, y = arrays.proj.to_xy(np.array([p["lat"] for p in tr]),
                                 np.array([p["lon"] for p in tr]))
        px[b, :n], py[b, :n] = x, y
        tm[b, :n] = np.array([p["time"] for p in tr]) - tr[0]["time"]
        valid[b, :n] = True
    return pack_inputs(px, py, tm, valid), valid


def _run_programs(world, kind):
    import jax
    import jax.numpy as jnp

    from reporter_tpu.ops import viterbi as V
    from reporter_tpu.matching.sparse import SparseModel

    _city, arrays, _u, dg, du = world
    cfg = MatcherConfig()
    p = V.MatchParams.from_config(cfg)
    xin, _valid = _program_input(world)
    xin = jnp.asarray(xin)
    if kind == "compact":
        fn = jax.jit(functools.partial(V.match_batch_compact_packed,
                                       kernel="scan"), static_argnums=(4,))
        return fn(dg, du, xin, p, 8)
    if kind == "sparse":
        model = SparseModel(MatcherConfig(sparse=True), arrays.cell_size)
        sp_p, sp, k = model.params_for("ge60", ())
        fn = jax.jit(functools.partial(V.match_batch_compact_packed_sparse,
                                       kernel="assoc"), static_argnums=(5,))
        return fn(dg, du, xin, sp_p, sp, k)
    # fresh partials: a jit of the bare function would replay the trace
    # made under the other probe
    pre = jax.jit(functools.partial(V.precompute_batch_packed),
                  static_argnums=(4,))(dg, du, xin, p, 8)
    carry = V.initial_carry_batch(xin.shape[1], 8)
    out, carry = jax.jit(functools.partial(V.chain_batch_carry_packed),
                         static_argnums=(5,))(dg, du, pre, xin, p, 8, carry)
    return pre, out, carry


def _flat(tree):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("kind", ["compact", "sparse", "pre"])
@pytest.mark.parametrize("path", ["compact", "overflow", "full"])
def test_programs_equal_uncompacted(world, monkeypatch, kind, path):
    """Compact, sparse and pre/chain program outputs equal the programs'
    without compaction, whichever path answered: the widths the matcher
    derives (compact), a C of 3 with a lane over all but one step (so
    steps overflow into it), and a C of 2 with an 8-step lane (so the
    dispatch falls back)."""
    from reporter_tpu.ops import hashtable as H
    from reporter_tpu.ops import viterbi as V

    with monkeypatch.context() as mp:
        mp.setattr(V, "ubodt_lookup_steps", _uncompacted_steps)
        want = _run_programs(world, kind)
    if path == "overflow":
        monkeypatch.setattr(H, "compact_width", lambda k: 3)
        monkeypatch.setattr(H, "lane_width", lambda n: n - 1)
    elif path == "full":
        monkeypatch.setattr(H, "compact_width", lambda k: 2)
        monkeypatch.setattr(H, "lane_width", lambda n: 8)
    got = _run_programs(world, kind)
    _xin, valid = _program_input(world)
    n_valid = int(valid[:, 1:].sum())
    if kind == "pre":
        pre_w, *rest_w = want
        pre_g, *rest_g = got
        for a, b in zip(_flat(rest_w), _flat(rest_g)):
            np.testing.assert_array_equal(a, b)
        m = valid[:, 1:]
        for name in ("cand", "emis", "gc"):
            for a, b in zip(_flat(getattr(pre_w, name)),
                            _flat(getattr(pre_g, name))):
                np.testing.assert_array_equal(a, b)
        for name in ("logp", "route"):
            np.testing.assert_array_equal(
                np.asarray(getattr(pre_w, name))[m],
                np.asarray(getattr(pre_g, name))[m])
        counts = np.asarray(pre_g.probe).sum(axis=0)
    else:
        for a, b in zip(_flat(want[:-1]), _flat(got[:-1])):
            np.testing.assert_array_equal(a, b)
        counts = np.asarray(got[-1]).sum(axis=0)
    assert counts.sum() == n_valid
    if path == "compact":
        assert counts[0] > 0 and counts[2] == 0
    elif path == "overflow":
        assert counts[1] > 0 and counts[2] == 0
    else:
        assert counts.tolist() == [0, 0, n_valid]


def test_matcher_counts_probe_paths(world):
    """The matcher counts every valid step of its bucketed and long-trace
    dispatches under reporter_ubodt_probe_steps_total, at the fetch."""
    from reporter_tpu.matching import SegmentMatcher
    from reporter_tpu.matching.matcher import C_PROBE_STEPS
    from reporter_tpu.synth import TraceSynthesizer

    city, arrays, ubodt, _dg, _du = world
    m = SegmentMatcher(arrays=arrays, ubodt=ubodt,
                       config=MatcherConfig(length_buckets=[16, 32]))
    synth = TraceSynthesizer(arrays, seed=11)
    lens = [9, 16, 23, 32, 70]
    traces = [synth.synthesize(n, dt=15.0, sigma=4.0, uuid="v%d" % i,
                               max_tries=300).trace
              for i, n in enumerate(lens)]

    def counts():
        return [C_PROBE_STEPS.labels(p).value
                for p in ("compact", "overflow", "full")]

    before = counts()
    m.match_many(traces)
    delta = [a - b for a, b in zip(counts(), before)]
    # bucketed windows: n - 1 steps; the 70-point one runs as 32-point
    # chunks whose seams are chain steps, not precompute steps
    assert sum(delta) == sum(n - 1 for n in lens[:4]) + (70 - 3)
    assert delta[0] > 0
