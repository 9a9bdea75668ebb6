"""Sparse-gap matching model (docs/match-quality.md "Sparse gaps").

Three contracts pinned here:

  1. FLAG-GATING — with the model disabled (the config default, or an
     explicit REPORTER_SPARSE=0 over a sparse-configured matcher) every
     wire byte equals the pre-sparse output, across both viterbi kernels
     x both UBODT layouts, including the per-vehicle session/streaming
     path; and with the model ENABLED, dense traffic is untouched (the
     sparse kinds are separate jit cache entries).

  2. THE MODEL — time-adaptive beta grows with the gap and caps;
     gap-conditioned breakage keeps honest ≥60 s teleports connected
     where the fixed rule restarts; the drivable-speed plausibility term
     (the measured lever of the calibration sweep) improves agreement
     against the brute-force f64 oracle on a sparse corpus, and the
     oracle speaks the same model (baseline/brute_matcher sparse=).

  3. THE PLANE — CALIBRATION.json loads per cohort (corrupt files
     degrade loudly to the config family), the silent radius clamp is
     now a counter + ?debug=1 flag, the route-consistent interpolation
     engine re-times intermediate segments by free-flow speed while
     keeping the record schema byte-compatible, and loadgen's
     --gap-jitter produces genuinely non-uniform gaps recorded in the
     realized-gap histogram.
"""

import dataclasses
import json

import numpy as np
import pytest

from reporter_tpu.matching import MatcherConfig, SegmentMatcher
from reporter_tpu.matching import sparse as sparse_mod
from reporter_tpu.synth import TraceSynthesizer
from reporter_tpu.synth.generator import dryrun_scenario


@pytest.fixture(autouse=True)
def _clean_sparse_env(monkeypatch):
    """The serve CLI entrypoint setdefaults REPORTER_SPARSE=1 /
    REPORTER_QUALITY_AUX=1 into the process env, and test_service runs it
    in-process earlier in the tier-1 order — the differential tests here
    need the LIBRARY defaults, so every test starts from a clean env."""
    for var in ("REPORTER_SPARSE", "REPORTER_QUALITY_AUX",
                "REPORTER_CALIBRATION", "REPORTER_INTERPOLATE"):
        monkeypatch.delenv(var, raising=False)


@pytest.fixture(scope="module")
def world():
    cfg, arrays, ubodt = dryrun_scenario(rows=6, cols=6, spacing_m=200.0,
                                         delta=3000.0)
    cfg = dataclasses.replace(cfg, length_buckets=[16, 32])
    return cfg, arrays, ubodt


def corpus(arrays, seed=5, dense_n=3, sparse_n=3):
    synth = TraceSynthesizer(arrays, seed=seed)
    traces = []
    for i in range(dense_n):
        traces.append(synth.synthesize(
            12, dt=5.0, uuid="dense-%d" % i, max_tries=60).trace)
    for i in range(sparse_n):
        traces.append(synth.synthesize(
            12, dt=60.0, uuid="sparse-%d" % i, max_tries=300).trace)
    return traces


def wire(results):
    return json.dumps(results, sort_keys=True)


# -- 1. flag-gating -----------------------------------------------------------

@pytest.mark.parametrize("kernel", ["scan", "assoc"])
@pytest.mark.parametrize("layout", ["cuckoo", "wide32"])
def test_sparse_off_bit_identical(world, kernel, layout, monkeypatch):
    """REPORTER_SPARSE=0 over a sparse-configured matcher reproduces the
    default matcher's wire output byte-for-byte — kernels x layouts."""
    cfg, arrays, ubodt = world
    cfg = dataclasses.replace(cfg, viterbi_kernel=kernel,
                              ubodt_layout=layout)
    traces = corpus(arrays)
    ref = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg)
    base = ref.match_many(traces)

    cfg_sp = dataclasses.replace(
        cfg, sparse=True, sparse_beam_k=16, sparse_beta_scale=1.0,
        sparse_vmax_mps=16.0)
    monkeypatch.setenv("REPORTER_SPARSE", "0")
    off = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg_sp)
    assert not off.sparse.enabled
    assert wire(off.match_many(traces)) == wire(base)


def test_sparse_on_dense_unchanged(world):
    """With the model ON, dense traces still dispatch the classic kind and
    their bytes are untouched; sparse-cohort traces actually change."""
    cfg, arrays, ubodt = world
    traces = corpus(arrays)
    base = SegmentMatcher(arrays=arrays, ubodt=ubodt,
                          config=cfg).match_many(traces)
    cfg_sp = dataclasses.replace(cfg, sparse=True, sparse_vmax_mps=16.0)
    on = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg_sp)
    assert on.sparse.enabled
    res = on.match_many(traces)
    for i in range(3):  # dense third: bit-identical
        assert wire([res[i]]) == wire([base[i]])
    assert any(wire([res[3 + i]]) != wire([base[3 + i]])
               for i in range(3)), "sparse model never engaged"
    assert sparse_mod.C_SPARSE_DISPATCH.labels("ge60").value > 0


@pytest.mark.parametrize("devices", [
    pytest.param(2, marks=pytest.mark.slow), 8])
def test_sparse_mesh_identical(world, devices):
    """The sparse cohort dispatch under a dp mesh (docs/performance.md
    "One logical matcher per pod"): mixed dense+sparse batches on N
    devices reproduce the 1-device sparse wire byte-for-byte."""
    import jax

    if len(jax.devices()) < devices:
        pytest.skip("needs >= %d virtual devices" % devices)
    cfg, arrays, ubodt = world
    traces = corpus(arrays)
    cfg_sp = dataclasses.replace(cfg, sparse=True, sparse_vmax_mps=16.0)
    want = wire(SegmentMatcher(arrays=arrays, ubodt=ubodt,
                               config=cfg_sp).match_many(traces))
    cfg_m = dataclasses.replace(cfg_sp, devices=devices)
    m = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg_m)
    assert m.sparse.enabled and m._mesh is not None
    assert wire(m.match_many(traces)) == want


def test_session_sparse_off_identical(world, monkeypatch):
    """The streaming path under REPORTER_SPARSE=0: bit-identical session
    step results (the satellite's session-path differential)."""
    cfg, arrays, ubodt = world
    synth = TraceSynthesizer(arrays, seed=9)
    pts = synth.synthesize(8, dt=60.0, uuid="s",
                           max_tries=300).trace["trace"]

    def run(matcher):
        out = []
        carry = None
        for p in pts:
            items = [{"points": [p], "carry": carry,
                      "t0": float(pts[0]["time"]), "pkey": ()}]
            (res, aux, carry) = matcher.match_sessions(items)[0]
            out.append((res[0].tolist(), res[1].tolist(), res[2].tolist()))
        return out, carry

    ref = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg)
    base, carry_b = run(ref)
    monkeypatch.setenv("REPORTER_SPARSE", "0")
    off = SegmentMatcher(
        arrays=arrays, ubodt=ubodt,
        config=dataclasses.replace(cfg, sparse=True, sparse_vmax_mps=16.0))
    got, carry_g = run(off)
    assert got == base
    for k in ("scores", "edge", "offset"):
        assert np.array_equal(carry_b[k], carry_g[k]), k


def test_session_sparse_engages(world):
    """A sparse-gap stream dispatches the sparse_session kind and its
    decode differs from the dense model where the model matters; a dense
    stream through the same matcher is bit-identical to the classic
    path."""
    cfg, arrays, ubodt = world
    synth = TraceSynthesizer(arrays, seed=10)
    sp_pts = synth.synthesize(8, dt=60.0, uuid="sp",
                              max_tries=300).trace["trace"]
    de_pts = synth.synthesize(8, dt=5.0, uuid="de",
                              max_tries=60).trace["trace"]
    cfg_sp = dataclasses.replace(cfg, sparse=True, sparse_vmax_mps=12.0,
                                 sparse_beta_scale=1.0)
    on = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg_sp)
    ref = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg)

    def step_all(matcher, pts):
        carry = None
        outs = []
        for p in pts:
            (res, _aux, carry) = matcher.match_sessions(
                [{"points": [p], "carry": carry,
                  "t0": float(pts[0]["time"]), "pkey": ()}])[0]
            outs.append([a.tolist() for a in res])
        return outs

    assert step_all(on, de_pts) == step_all(ref, de_pts)
    # the sparse stream engaged the sparse kind (dispatch counter moved)
    before = sparse_mod.C_SPARSE_DISPATCH.labels("ge60").value
    step_all(on, sp_pts)
    assert sparse_mod.C_SPARSE_DISPATCH.labels("ge60").value > before


# -- 2. the model -------------------------------------------------------------

def test_time_adaptive_beta_family():
    import jax.numpy as jnp

    from reporter_tpu.ops.viterbi import (
        MatchParams, SparseParams, sparse_beta, sparse_breakage,
    )

    p = MatchParams.from_config(MatcherConfig())
    sp = SparseParams.from_values(15.0, 1.0, 4.0, 34.0, 20.0, 3.0)
    b0 = float(sparse_beta(p, sp, jnp.float32(5.0)))
    b15 = float(sparse_beta(p, sp, jnp.float32(15.0)))
    b60 = float(sparse_beta(p, sp, jnp.float32(60.0)))
    b600 = float(sparse_beta(p, sp, jnp.float32(600.0)))
    assert b0 == b15 == pytest.approx(float(p.beta))  # at/below ref: base
    assert b60 > b15  # grows with the gap
    assert b600 == pytest.approx(float(p.beta) * 4.0)  # capped
    # breakage: fixed rule below, speed-conditioned above
    assert float(sparse_breakage(p, sp, jnp.float32(10.0))) == pytest.approx(
        float(p.breakage_distance))
    assert float(sparse_breakage(p, sp, jnp.float32(90.0))) == pytest.approx(
        34.0 * 90.0)
    assert float(sparse_breakage(p, None, jnp.float32(90.0))) == \
        pytest.approx(float(p.breakage_distance))


def test_gap_conditioned_breakage_connects():
    """An honest long-gap hop beyond the fixed breakage distance stays
    connected under the sparse model and restarts under the dense rule —
    pinned at the kernel level on a long-row grid where a 90 s drive
    really covers > breakage_distance metres."""
    import jax.numpy as jnp

    from reporter_tpu.ops import viterbi as V
    from reporter_tpu.synth.generator import dryrun_scenario

    # a 2 x 16 grid @ 200 m: one straight 3 km road; breakage shrunk so
    # the hop exceeds it while staying inside the UBODT delta
    cfg, arrays, ubodt = dryrun_scenario(rows=2, cols=16, spacing_m=200.0,
                                         delta=3000.0)
    cfg = dataclasses.replace(cfg, breakage_distance=800.0)
    dg = arrays.to_device()
    du = ubodt.to_device()
    p = V.MatchParams.from_config(cfg)
    sp = V.SparseParams.from_values(15.0, 0.0, 8.0, 34.0, 45.0, 0.0)
    brk_dense = V.sparse_breakage(p, None, jnp.float32(90.0))
    brk_sparse = V.sparse_breakage(p, sp, jnp.float32(90.0))
    assert float(brk_dense) == pytest.approx(800.0)
    assert float(brk_sparse) == pytest.approx(34.0 * 90.0)
    # two points 1200 m apart along the straight road, 90 s apart:
    # gc > 800 (dense restarts) but < 3060 (sparse connects)
    n0 = float(arrays.node_x[0]), float(arrays.node_y[0])
    px = np.array([[n0[0] + 10.0, n0[0] + 1210.0]], np.float32)
    py = np.array([[n0[1], n0[1]]], np.float32)
    tm = np.array([[0.0, 90.0]], np.float32)
    valid = np.ones((1, 2), bool)
    xin = V.pack_inputs(px, py, tm, valid)
    out_d = V.unpack_compact(V.match_batch_compact_packed(
        dg, du, xin, p, cfg.beam_k)[0])
    out_s, _aux, _probe = V.match_batch_compact_packed_sparse(
        dg, du, xin, p, sp, cfg.beam_k)
    out_s = V.unpack_compact(out_s)
    assert bool(out_d[2][0, 1]) is True  # dense: the hop restarts the HMM
    assert bool(out_s[2][0, 1]) is False  # sparse: honest drive, connected


def test_sparse_agreement_improves_vs_oracle(world):
    """The headline: on a 60-90 s corpus, the calibrated sparse model
    agrees with its f64 oracle twin better than the dense model agrees
    with its own — the implementation-robustness the calibration sweep
    optimises (tools/calibrate.py; the committed CALIBRATION.json and
    QUALITY_BASELINE.json carry the full-size result)."""
    cfg, arrays, ubodt = world
    from reporter_tpu.baseline.brute_matcher import BruteForceMatcher

    synth = TraceSynthesizer(arrays, seed=21)
    traces = [synth.synthesize(16, dt=90.0, uuid="a%d" % i,
                               max_tries=400).trace for i in range(6)]

    def agreement(matcher, oracle):
        matcher._quality_aux = True
        agree = total = 0
        for tr in traces:
            m = matcher.match_many([tr])[0]
            edges = m["_quality"]["edge"]
            pts = tr["trace"]
            lats = np.array([p["lat"] for p in pts])
            lons = np.array([p["lon"] for p in pts])
            xs, ys = arrays.proj.to_xy(lats, lons)
            oe, _oo, _ob = oracle.match_points(
                xs, ys, [p["time"] for p in pts])
            seg_m = np.where(np.asarray(edges) >= 0,
                             arrays.edge_seg[np.maximum(edges, 0)], -1)
            seg_o = np.where(oe >= 0,
                             arrays.edge_seg[np.maximum(oe, 0)], -1)
            agree += int((seg_m == seg_o).sum())
            total += len(edges)
        return agree / total

    base = agreement(
        SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg),
        BruteForceMatcher(arrays, cfg))
    vals = {"sigma_z": cfg.sigma_z, "beta": cfg.beta,
            "search_radius": cfg.search_radius, "k": cfg.beam_k,
            "beta_ref_s": 15.0, "beta_scale": 0.0, "beta_max": 8.0,
            "break_speed_mps": 34.0, "vmax_mps": 16.0, "plaus_weight": 3.0}
    cfg_sp = dataclasses.replace(
        cfg, sparse=True, sparse_vmax_mps=16.0, sparse_beta_scale=0.0)
    calibrated = agreement(
        SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg_sp),
        BruteForceMatcher(arrays, cfg, sparse=vals))
    assert calibrated >= base, (calibrated, base)


# -- 3. the plane -------------------------------------------------------------

def test_calibration_load(world, tmp_path, monkeypatch):
    cfg, arrays, ubodt = world
    cal = {"version": 1, "cohorts": {
        "45-60": {"sigma_z": 5.0, "k": 12, "vmax_mps": 18.0},
        "ge60": {"beta_scale": 0.5, "vmax_mps": 14.0},
    }}
    path = tmp_path / "cal.json"
    path.write_text(json.dumps(cal))
    monkeypatch.setenv("REPORTER_CALIBRATION", str(path))
    m = SegmentMatcher(arrays=arrays, ubodt=ubodt,
                       config=dataclasses.replace(cfg, sparse=True))
    assert m.sparse.calibration is not None
    p, sp, k = m.sparse.params_for("45-60")
    assert float(p.sigma_z) == pytest.approx(5.0)
    assert k == 12
    assert float(sp.vmax) == pytest.approx(18.0)
    p2, sp2, k2 = m.sparse.params_for("ge60")
    assert float(sp2.beta_scale) == pytest.approx(0.5)
    assert float(sp2.vmax) == pytest.approx(14.0)
    assert k2 == cfg.sparse_beam_k  # unlisted keys: config family
    # per-request overrides win over calibration (reference precedence)
    p3, _sp3, _k3 = m.sparse.params_for("ge60", (9.0, 4.0, 30.0))
    assert float(p3.sigma_z) == pytest.approx(9.0)
    assert float(p3.search_radius) == pytest.approx(30.0)
    # the gauge says calibrated
    from reporter_tpu.matching.sparse import G_CALIBRATED

    assert G_CALIBRATED.value == 1.0


def test_calibration_corrupt_degrades(world, tmp_path, monkeypatch):
    cfg, arrays, ubodt = world
    path = tmp_path / "bad.json"
    path.write_text("{not json")
    monkeypatch.setenv("REPORTER_CALIBRATION", str(path))
    m = SegmentMatcher(arrays=arrays, ubodt=ubodt,
                       config=dataclasses.replace(cfg, sparse=True))
    assert m.sparse.enabled and m.sparse.calibration is None
    _p, sp, _k = m.sparse.params_for("ge60")
    assert float(sp.vmax) == pytest.approx(cfg.sparse_vmax_mps)


def test_radius_clamp_counted(world):
    cfg, arrays, ubodt = world
    from reporter_tpu.matching.sparse import C_RADIUS_CLAMPED

    m = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg)
    before = C_RADIUS_CLAMPED.labels("request").value
    eff = m.effective_match_options({"search_radius": 5000.0})
    assert eff["search_radius"] == pytest.approx(arrays.cell_size / 2.0)
    assert eff.get("search_radius_clamped") is True
    assert C_RADIUS_CLAMPED.labels("request").value == before + 1
    # an in-bounds radius carries no flag and no count
    eff2 = m.effective_match_options({"search_radius": 10.0})
    assert "search_radius_clamped" not in eff2
    assert C_RADIUS_CLAMPED.labels("request").value == before + 1
    # a sparse-cohort radius clamps through the same seam
    cfg_sp = dataclasses.replace(cfg, sparse=True,
                                 sparse_search_radius=9999.0)
    m2 = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg_sp)
    vals = m2.sparse.cohort_values("ge60")
    assert vals["search_radius"] == pytest.approx(arrays.cell_size / 2.0)
    assert C_RADIUS_CLAMPED.labels("sparse").value > 0


def test_interpolation_speed_weighted(world):
    """Two edges at different speeds between two matched points: the
    interpolated boundary time splits by free-flow TIME share, the
    classic walk by distance share — and the record schema (keys,
    rounding) is identical."""
    cfg, arrays, ubodt = world
    from reporter_tpu.matching.segments import associate_segments
    from reporter_tpu.matching.sparse import associate_interpolated

    # find two consecutive edges with different speeds
    es = np.asarray(arrays.edge_speed)
    el = np.asarray(arrays.edge_len)
    pair = None
    for e1 in range(arrays.num_edges):
        for e2 in range(arrays.num_edges):
            if int(arrays.edge_to[e1]) == int(arrays.edge_from[e2]) \
                    and es[e1] != es[e2] and e1 != e2:
                pair = (e1, e2)
                break
        if pair:
            break
    assert pair, "grid has mixed speeds by construction"
    e1, e2 = pair
    t0, t1 = 1000.0, 1000.0 + 60.0
    mps = [
        {"edge": e1, "offset": 0.0, "time": t0, "break": True,
         "shape_index": 0},
        {"edge": e2, "offset": float(el[e2]), "time": t1, "break": False,
         "shape_index": 1},
    ]
    classic = associate_segments(arrays, ubodt, mps)
    interp = associate_interpolated(arrays, ubodt, mps)
    assert [sorted(r.keys()) for r in classic] == \
        [sorted(r.keys()) for r in interp]
    assert [type(v).__name__ for r in classic for v in r.values()] == \
        [type(v).__name__ for r in interp for v in r.values()]
    # boundary time between the two edges: classic = distance-linear,
    # interpolated = free-flow time share
    d1, d2 = float(el[e1]), float(el[e2])
    ff1 = d1 / max(float(es[e1]), 0.1)
    ff2 = d2 / max(float(es[e2]), 0.1)
    lin = t0 + 60.0 * d1 / (d1 + d2)
    spd = t0 + 60.0 * ff1 / (ff1 + ff2)
    assert lin != pytest.approx(spd)  # speeds differ so the shares differ

    def boundary_time(records):
        # end_time of the first fully-exited segment record
        for r in records:
            if r.get("end_time", -1) != -1:
                return r["end_time"]
        return None

    bt_classic = boundary_time(classic)
    bt_interp = boundary_time(interp)
    if bt_classic is not None and bt_interp is not None \
            and bt_classic not in (t0, t1):
        assert bt_interp == pytest.approx(spd, abs=0.51)
        assert bt_classic == pytest.approx(lin, abs=0.51)


def test_interpolate_match_option_end_to_end(world):
    """match_options.interpolate routes a trace's association through the
    engine; absent, bytes are the PR 14 walk."""
    cfg, arrays, ubodt = world
    traces = corpus(arrays, seed=6, dense_n=0, sparse_n=2)
    m = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg)
    base = m.match_many(traces)
    ti = [dict(t, match_options={"interpolate": True}) for t in traces]
    res = m.match_many(ti)
    # same segments traversed (the engine re-times, never re-routes)
    for b, r in zip(base, res):
        assert [s.get("segment_id") for s in b["segments"]] == \
            [s.get("segment_id") for s in r["segments"]]
    # explicit false == absent
    tf = [dict(t, match_options={"interpolate": False}) for t in traces]
    assert wire(m.match_many(tf)) == wire(base)
    # config default applies without per-request keys
    m2 = SegmentMatcher(arrays=arrays, ubodt=ubodt,
                        config=dataclasses.replace(cfg, interpolate=True))
    assert wire(m2.match_many(traces)) == wire(res)


def test_gap_jitter_corpus():
    """loadgen --gap-jitter: non-uniform realized gaps, recorded in the
    artifact histogram; jitter 0 keeps the seeded corpus identical."""
    import importlib.util
    import os as _os

    spec = importlib.util.spec_from_file_location(
        "loadgen", _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), "tools", "loadgen.py"))
    lg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lg)

    plain_a = lg.synth_sessions(4, 16, 8, 6, seed=3, gaps=[60.0])
    plain_b = lg.synth_sessions(4, 16, 8, 6, seed=3, gaps=[60.0],
                                gap_jitter=0.0)
    assert json.dumps(plain_a) == json.dumps(plain_b)
    jit = lg.synth_sessions(4, 16, 8, 6, seed=3, gaps=[60.0],
                            gap_jitter=0.25)
    h = lg.realized_gaps(jit)
    assert h["count"] > 0
    assert h["max_s"] > h["min_s"] + 1.0, h  # genuinely non-uniform
    assert 45.0 <= h["median_s"] <= 75.0, h  # centred on the nominal gap
    h0 = lg.realized_gaps(plain_a)
    assert h0["max_s"] == pytest.approx(h0["min_s"])  # metronomic before


def test_quality_oracle_sparse_keying(world):
    """The shadow-oracle plane builds a sparse-model oracle for
    sparse-cohort traces (same model both sides — a model improvement
    must not score as a regression)."""
    cfg, arrays, ubodt = world
    from reporter_tpu.obs.quality import QualityEngine

    cfg_sp = dataclasses.replace(cfg, sparse=True, sparse_vmax_mps=16.0)
    m = SegmentMatcher(arrays=arrays, ubodt=ubodt, config=cfg_sp)
    eng = QualityEngine(m, sample_every=1, start_worker=False,
                        slo_feed=lambda v, w: None)
    tr = corpus(arrays, seed=7, dense_n=0, sparse_n=1)[0]
    m._quality_aux = True
    match = m.match_many([tr])[0]
    frac = eng.compare(tr, match["_quality"]["edge"])
    assert frac is not None
    # a sparse-cohort oracle was built, keyed by its gap label, and
    # carries the sparse model
    keys = list(eng._oracles)
    assert any(sl for _pk, sl in keys), keys
    oracle = next(v for (pk, sl), v in eng._oracles.items() if sl)
    assert oracle.sparse is not None
    assert oracle.sparse["vmax_mps"] == pytest.approx(16.0)
