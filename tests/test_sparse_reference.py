"""The sparse-gap backfill against the benchmark's float64 sparse reference
(``benchmark/reference_sparse.py``), at a 12 x 12 city on the CPU.

  * ``make_matches`` with the sparse model on, over seeded 60 s shard files,
    writes the tile rows the reference gives, within the configuration's
    limits (``benchmark/compare.py``'s rule); the reference computed in
    bfloat16 (the control) does not, nor do the same files decoded with the
    model off;
  * the reference's beta(dt), breakage and drivable-speed terms are those
    of the brute-force oracle's twin (``baseline/brute_matcher.py``) over a
    grid of gaps;
  * Δ``reporter_sparse_points_total`` is the points of the sparse windows
    matched, and 0 with the model off.
"""

import math
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)

import city  # noqa: E402
import common  # noqa: E402
import compare  # noqa: E402
import traffic  # noqa: E402
from reference_sparse import SparseReference  # noqa: E402

from reporter_tpu.baseline.brute_matcher import BruteForceMatcher  # noqa: E402
from reporter_tpu.batch.pipeline import make_matches  # noqa: E402
from reporter_tpu.matching import MatcherConfig, SegmentMatcher  # noqa: E402
from reporter_tpu.matching import sparse as sparse_mod  # noqa: E402
from reporter_tpu.tiles.network import RoadNetwork  # noqa: E402

CONFIG = common.load_json("configs", "metro-backfill-sparse.json")
BATCH = common.load_module(os.path.join(BENCH, "drivers", "batch.py"), "batch_for_tests")
SEED = 2**31 + 25
# each window 10-40 min: 11-41 fixes at 60 s
MIX_60 = dict(traffic.load("archive-60s"), vehicles_per_file=8, drives_per_vehicle=2,
              drive_minutes={"lognormal_median": 20, "lognormal_sigma": 0.5, "min": 10, "max": 40})
MIX_15 = dict(traffic.load("archive-15s"), vehicles_per_file=3, drives_per_vehicle=2,
              drive_minutes={"lognormal_median": 6, "lognormal_sigma": 0.3, "min": 4, "max": 8})


@pytest.fixture(autouse=True)
def _library_sparse_env(monkeypatch):
    """The configuration, not a serve CLI's leftover environment, turns the
    model on (tests/test_sparse.py says why)."""
    for var in ("REPORTER_SPARSE", "REPORTER_CALIBRATION", "REPORTER_INTERPOLATE"):
        monkeypatch.delenv(var, raising=False)


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    net = city.network(dict(CONFIG["city"], **CONFIG["rehearsal"]["city"]))
    road_net = RoadNetwork.from_dict(net)
    matchers = {on: SegmentMatcher(network=road_net, backend="jax",
                                   config=MatcherConfig.from_dict(dict(CONFIG["matcher"], sparse=on)))
                for on in (True, False)}
    root = str(tmp_path_factory.mktemp("shards"))
    roads = traffic.Roads(net)
    files = {"60s": BATCH._shards(roads, MIX_60, SEED, 2, 0, root, "a"),
             "15s": BATCH._shards(roads, MIX_15, SEED, 1, 0, root, "b")}
    return net, matchers, files, root


def _pipeline_kw():
    pl = CONFIG["pipeline"]
    return dict(mode=pl["mode"], report_levels=frozenset(pl["report_levels"]),
                transition_levels=frozenset(pl["transition_levels"]),
                quantisation=pl["quantisation"], inactivity=pl["inactivity_s"],
                source=pl["source"], threshold_sec=pl["threshold_sec"],
                microbatch=pl["microbatch"])


@pytest.fixture(scope="module")
def tiles_by_model(world):
    """Tile files of every 60 s shard file, matched with the model on (True)
    and off (False: the dense decode the guarantee rules out)."""
    _net, matchers, files, root = world
    out = {}
    for on in (True, False):
        out[on] = []
        for i, (d, _pts, _drives) in enumerate(files["60s"]):
            dest = os.path.join(root, "out%d-%s" % (i, on))
            make_matches(d, matchers[on], dest_dir=dest, **_pipeline_kw())
            out[on].append(BATCH._tiles(dest))
    return out


@pytest.mark.parametrize("precision,on,within", [
    ("float64", True, True), ("bfloat16", True, False), ("float64", False, False)])
def test_sparse_backfill_against_reference(world, tiles_by_model, precision, on, within):
    net, _matchers, files, _root = world
    ref = SparseReference(net, CONFIG["matcher"], precision)
    lim = CONFIG["limits"]
    for i, tiles in enumerate(tiles_by_model[on]):
        assert all(ref.is_sparse(t) for _u, t, _la, _lo in files["60s"][i][2])
        want, missing, n_got, extra = BATCH.compare_file(
            ref, CONFIG["pipeline"], files["60s"], i, compare.index_rows(tiles))
        assert want > 0 and n_got > 0
        ok = missing / want <= lim["rows_missing"] and extra / n_got <= lim["rows_extra"]
        assert ok is within, (precision, on, i, missing, want, extra, n_got)


# the model's knobs at their defaults, and a slow vmax so that the
# drivable-speed term bites on a city block
@pytest.mark.parametrize("knobs", [
    {},
    {"sparse_vmax_mps": 2.0, "sparse_plaus_weight": 5.0, "sparse_beta_ref_s": 30.0,
     "sparse_beta_scale": 0.5, "sparse_beta_max": 3.0, "sparse_break_speed_mps": 20.0},
])
def test_reference_terms_match_brute_twin(world, knobs):
    net, matchers, _files, _root = world
    ref = SparseReference(net, dict(CONFIG["matcher"], **knobs))
    m = matchers[True]
    brute = BruteForceMatcher(m.arrays, m.cfg, sparse={
        "beta_ref_s": ref.beta_ref, "beta_scale": ref.beta_scale, "beta_max": ref.beta_max,
        "break_speed_mps": ref.break_speed, "vmax_mps": ref.vmax,
        "plaus_weight": ref.plaus_weight})
    # a long non-internal edge: forward moves along it route exactly
    # offset to offset in both, whatever their geometry
    e = int(np.argmax(np.where(ref.internal, 0.0, ref.elen)))
    assert abs(ref.elen[e] - float(brute.a.edge_len[e])) < 0.5
    L = float(ref.elen[e])
    gaps = [-5.0, 0.0, 1.0, 5.0, 15.0, 20.0, 30.0, 45.0, 60.0, 90.0, 120.0, 300.0]
    plaus_seen = 0
    for dt in gaps:
        assert math.isclose(ref.beta_at(dt), brute._beta(dt), rel_tol=1e-12)
        assert math.isclose(ref.breakage_at(dt), brute._breakage(dt), rel_tol=1e-12)
        for frac in (0.05, 0.4, 0.9):
            ca, cb = (e, 0.02 * L, 1.0), (e, frac * L, 2.0)
            gc = 0.8 * (frac - 0.02) * L
            got = ref.sparse_transition(ca, cb, gc, dt, ref.radius_sparse)
            want = brute._transition(ca, cb, gc, dt)
            if want <= -1e29:
                assert got <= -1e29
                continue
            assert math.isclose(got, want, rel_tol=1e-9, abs_tol=1e-9), (dt, frac)
            plaus_seen += ref.plausibility((frac - 0.02) * L, dt) > 0
    assert plaus_seen > 0 or not knobs


@pytest.mark.parametrize("on", [True, False])
def test_sparse_points_counter(world, on):
    """Δ reporter_sparse_points_total is the points of the 60 s windows of a
    mixed micro-batch (the 15 s ones stay dense), and 0 with the model off."""
    _net, matchers, files, _root = world
    traces, sparse_pts = [], 0
    for key in ("60s", "15s"):
        for d, _p, drives in files[key]:
            for j in range(len(drives)):
                traces.append(BATCH.window_trace([(d, _p, drives)], 0, j))
                sparse_pts += len(drives[j][1]) if key == "60s" else 0
    fam = sparse_mod.C_SPARSE_POINTS

    def total():
        return sum(fam.labels(c).value for c in ("30-45", "45-60", "ge60"))

    before = total()
    matchers[on].match_many(traces)
    assert total() - before == (sparse_pts if on else 0)
    assert sparse_pts > 0
