"""AOT compiles of the serving programs for the v5e, with no chip.

The TPU compiler is installed here and compiles for a described
``v5e:2x2`` topology: what it refuses (a layout that pads past HBM, a
program that cannot be partitioned) costs no chip time.  Every program
is compiled at the metro scenario's table shapes (chip_smoke.py / bench:
49,926 edges, 36,672 grid cells x 20 candidates, a 2^20-bucket cuckoo
UBODT of 10.7 M rows) from ShapeDtypeStructs — nothing is placed on a
device.  Nothing here is a chip measurement.

The topology is described inside a module fixture, never at import: a
TPU library loaded while pytest-xdist workers collect would lock the
others out (on-chip-measurement guide, section 2).  The persistent
compilation cache is off around these compiles (an entry written here
could not be read back without a chip).
"""

import numpy as np
import pytest

# the metro scenario's device-table shapes (chip_smoke.py logs them)
EDGES = 49_926
CELLS, CELL_LANES = 36_672, 8 * 20
UBODT_BUCKETS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler to describe it
        pytest.skip("no v5e:2x2 topology can be described here: %s" % (e,))
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def matcher():
    """A small CPU matcher with the serving defaults on: the source of
    the jitted programs and of the small parameter pytrees."""
    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.tiles.network import grid_city

    m = SegmentMatcher(network=grid_city(rows=4, cols=4, spacing_m=200),
                       config=MatcherConfig(session_arena=True, sparse=True))
    m._quality_aux = True  # the serve entrypoint's default variants
    return m


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _like(tree, sharding, lead=None):
    """ShapeDtypeStructs of ``tree``'s array leaves on ``sharding``;
    ``lead`` replaces the leading dimension."""
    import jax

    def one(x):
        shape = tuple(x.shape)
        if lead is not None:
            shape = (lead,) + shape[1:]
        return _sds(shape, x.dtype, sharding)

    return jax.tree_util.tree_map(one, tree)


def _tables(dg_sharding, du_sharding):
    import jax.numpy as jnp

    from reporter_tpu.tiles.arrays import DeviceGraph
    from reporter_tpu.tiles.ubodt import DeviceUBODT

    dg = DeviceGraph(
        edge_rows=_sds((EDGES, 8), jnp.float32, dg_sharding),
        edge_seg=_sds((EDGES,), jnp.int32, dg_sharding),
        cell_rows=_sds((CELLS, CELL_LANES), jnp.float32, dg_sharding),
        grid_origin=_sds((2,), jnp.float32, dg_sharding),
        grid_dims=_sds((2,), jnp.int32, dg_sharding),
        cell_size=_sds((), jnp.float32, dg_sharding))
    du = DeviceUBODT(_sds((UBODT_BUCKETS, 128), jnp.int32, du_sharding),
                     UBODT_BUCKETS - 1)
    return dg, du


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    if mem is not None:  # fits the v5e's 16 GB HBM with room to spare
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        assert used < 12e9, used
    return compiled


@pytest.mark.parametrize("B,T", [(512, 64), (128, 256)])
def test_compact(matcher, one_chip, B, T):
    import jax.numpy as jnp

    dg, du = _tables(one_chip, one_chip)
    xin = _sds((4, B, T), jnp.float32, one_chip)
    _compile(matcher._get_jit("compact", "scan"), dg, du, xin,
             _like(matcher._params, one_chip), matcher.cfg.beam_k)


def test_long_pre_chain(matcher, one_chip):
    """The long-trace pair at 16 traces x 4 chunks of 256 points."""
    import jax
    import jax.numpy as jnp

    B, chunks, W = 16, 4, 256
    dg, du = _tables(one_chip, one_chip)
    p = _like(matcher._params, one_chip)
    k = matcher.cfg.beam_k
    pre_fn = matcher._get_jit("pre", "none")
    _compile(pre_fn, dg, du, _sds((4, B * chunks, W), jnp.float32, one_chip),
             p, k)
    xin = _sds((4, B, W), jnp.float32, one_chip)
    pre = _like(jax.eval_shape(pre_fn, dg, du, xin, p, k), one_chip)
    carry = _like(matcher.session_arena.hot, one_chip, lead=B)
    _compile(matcher._get_jit("chain", "scan"), dg, du, pre, xin, p, k,
             carry)


def test_session_arena_step(matcher, one_chip):
    """The streaming step against the serving-size slab (65,536 slots)."""
    import jax.numpy as jnp

    dg, du = _tables(one_chip, one_chip)
    B, W = 64, 4
    _compile(matcher._get_jit("arena_session", "scan"), dg, du,
             _sds((4, B, W), jnp.float32, one_chip),
             _like(matcher._params, one_chip), matcher.cfg.beam_k,
             _like(matcher.session_arena.hot, one_chip),
             _sds((B,), jnp.int32, one_chip), _sds((B,), jnp.bool_, one_chip))


def test_sparse(matcher, one_chip):
    import jax.numpy as jnp

    from reporter_tpu.obs.quality import GAP_BUCKETS

    dg, du = _tables(one_chip, one_chip)
    p, sp, k_sp = matcher.sparse.params_for(GAP_BUCKETS[-1][1], ())
    _compile(matcher._get_jit("sparse", "scan"), dg, du,
             _sds((4, 128, 64), jnp.float32, one_chip),
             _like(p, one_chip), _like(sp, one_chip), k_sp)


@pytest.mark.parametrize("tiered", [False, True])
def test_dp2_gp2_sharded_probe(topo, tiered):
    """The mesh path's compact program on the four described chips: batch
    over dp, the UBODT's bucket ranges over gp, probes resolved with
    collectives — built by the matcher's own rule-table builder.
    ``tiered``: the chip_smoke --chips 4 table, a per-chip hot arena over
    pinned_host cold pages moved into device memory on a miss."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.parallel.rules import sharding_for
    from reporter_tpu.tiles.network import grid_city
    from reporter_tpu.tiles.tiering import TieredDeviceUBODT

    m = SegmentMatcher(network=grid_city(rows=4, cols=4, spacing_m=200),
                       config=MatcherConfig(
                           devices=4, graph_devices=2,
                           ubodt_hot_bytes=4096 if tiered else 0))
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2), ("dp", "gp"))
    m._mesh = mesh
    prog = m._build_program("compact", "scan", False)
    dg, du = _tables(sharding_for("dg", mesh), sharding_for("du", mesh))
    if tiered:
        host = NamedSharding(mesh, PartitionSpec("gp"),
                             memory_kind="pinned_host")
        hot_rows = 2 * (128 << 20) // (128 * 4)  # 128 MiB per gp rank
        du = TieredDeviceUBODT(
            (_sds((hot_rows, 128), jnp.int32, sharding_for("du", mesh)),
             _sds((UBODT_BUCKETS,), jnp.int32, sharding_for("du", mesh)),
             _sds((UBODT_BUCKETS, 128), jnp.int32, host)),
            UBODT_BUCKETS - 1, "cuckoo", m.tiering)
    xin = _sds((4, 512, 64), jnp.float32, sharding_for("xin", mesh))
    compiled = _compile(prog.jitted(m.cfg.beam_k), dg, du, xin,
                        _like(m._params, sharding_for("p", mesh)))
    text = compiled.as_text()
    assert "all-reduce" in text or "all-gather" in text
