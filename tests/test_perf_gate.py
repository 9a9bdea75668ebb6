"""tools/perf_gate.py — the noise-aware like-provenance regression gate.

Pins the behaviours the CI leg relies on: noise thresholds (incl. the
history-spread widening), provenance filtering (platform / scenario
scale / corpse artifacts), the explicit missing-history verdict, and the
round-6 schema assertions (--require-attrib)."""

import importlib.util
import json
import os

import pytest


def _load():
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "perf_gate.py")
    spec = importlib.util.spec_from_file_location("perf_gate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def pg():
    return _load()


def _line(platform="cpu", value=20.0, pps=2500.0, vsb=2.0, edges=50_000,
          **extra):
    out = {
        "metric": "traces_matched_per_sec_per_chip", "value": value,
        "unit": "traces/s", "platform": platform, "points_per_sec": pps,
        "vs_baseline": vsb, "edges": edges, "scenario": "osm",
        "attrib": {"stages_ms_by_cohort": {}},
    }
    out.update(extra)
    return out


def _write(tmp_path, name, line, wrap_rc=None):
    p = tmp_path / name
    if wrap_rc is None:
        p.write_text(json.dumps(line))
    else:
        p.write_text(json.dumps({"n": 1, "rc": wrap_rc, "parsed": line,
                                 "tail": ""}))
    return str(p)


def test_loads_both_artifact_shapes(pg, tmp_path):
    raw = _write(tmp_path, "raw.json", _line())
    wrapped = _write(tmp_path, "wrap.json", _line(), wrap_rc=0)
    assert pg.load_bench_line(raw)["value"] == 20.0
    w = pg.load_bench_line(wrapped)
    assert w["value"] == 20.0 and w["_rc"] == 0


def test_regression_detected(pg, tmp_path):
    hist = [_write(tmp_path, "h%d.json" % i, _line(pps=2500.0 + i, vsb=2.0))
            for i in range(3)]
    fresh = _write(tmp_path, "fresh.json", _line(pps=1000.0, vsb=0.8))
    rc, verdict = pg.gate(hist, fresh)
    assert rc == 1
    assert verdict["verdict"] == "REGRESSION"
    assert verdict["metrics"]["points_per_sec"]["verdict"] == "REGRESSION"


def test_within_noise_passes(pg, tmp_path):
    hist = [_write(tmp_path, "h%d.json" % i, _line(pps=2500.0, vsb=2.0))
            for i in range(3)]
    # 20% below median is inside the wide CPU default (40%)
    fresh = _write(tmp_path, "fresh.json", _line(pps=2000.0, vsb=1.7))
    rc, verdict = pg.gate(hist, fresh)
    assert rc == 0
    assert verdict["verdict"] == "OK"


def test_history_spread_widens_threshold(pg, tmp_path):
    # history disagrees with itself by 2x: a fresh run 40% below the
    # median must NOT fail even past the CLI threshold
    hist = [_write(tmp_path, "h0.json", _line(pps=1500.0)),
            _write(tmp_path, "h1.json", _line(pps=2500.0)),
            _write(tmp_path, "h2.json", _line(pps=3500.0))]
    fresh = _write(tmp_path, "fresh.json", _line(pps=1500.0, vsb=2.0))
    rc, verdict = pg.gate(hist, fresh, threshold=0.10)
    assert rc == 0, verdict
    m = verdict["metrics"]["points_per_sec"]
    assert m["threshold"] > 0.10  # widened by the observed spread


def test_cpu_never_judged_against_tpu(pg, tmp_path):
    hist = [_write(tmp_path, "h0.json", _line(platform="tpu", pps=400_000.0))]
    fresh = _write(tmp_path, "fresh.json", _line(platform="cpu", pps=2000.0))
    rc, verdict = pg.gate(hist, fresh)
    assert rc == 0
    assert verdict["verdict"] == "NO-LIKE-PROVENANCE-HISTORY"
    assert "platform" in verdict["excluded"][0]["reason"]


def test_scale_mismatch_excluded(pg, tmp_path):
    hist = [_write(tmp_path, "h0.json", _line(edges=400))]  # smoke-scale
    fresh = _write(tmp_path, "fresh.json", _line(edges=50_000))
    rc, verdict = pg.gate(hist, fresh)
    assert rc == 0
    assert verdict["verdict"] == "NO-LIKE-PROVENANCE-HISTORY"


def test_corpse_history_excluded(pg, tmp_path):
    good = _write(tmp_path, "h0.json", _line(pps=2500.0))
    corpse = _write(tmp_path, "h1.json", _line(pps=100.0), wrap_rc=124)
    fresh = _write(tmp_path, "fresh.json", _line(pps=2400.0))
    rc, verdict = pg.gate([good, corpse], fresh)
    assert rc == 0, verdict
    assert verdict["baselines"] == [good]
    assert any("corpse" in e["reason"] for e in verdict["excluded"])


def test_corpse_candidate_invalid(pg, tmp_path):
    hist = [_write(tmp_path, "h0.json", _line())]
    fresh = _write(tmp_path, "fresh.json", _line(), wrap_rc=124)
    rc, verdict = pg.gate(hist, fresh)
    assert rc == 2
    assert verdict["verdict"] == "INVALID"


def test_missing_history_is_explicit_pass(pg, tmp_path):
    fresh = _write(tmp_path, "fresh.json", _line())
    rc, verdict = pg.gate([], fresh)
    assert rc == 0
    assert verdict["verdict"] == "NO-LIKE-PROVENANCE-HISTORY"
    assert verdict["baselines"] == []


def test_schema_invalid_candidate(pg, tmp_path):
    bad = dict(_line())
    del bad["value"]
    fresh = _write(tmp_path, "fresh.json", bad)
    rc, verdict = pg.gate([], fresh)
    assert rc == 2
    assert "value" in verdict["error"]


def test_require_attrib_schema(pg, tmp_path):
    # missing attrib key entirely -> invalid under --require-attrib
    noattrib = {k: v for k, v in _line().items() if k != "attrib"}
    fresh = _write(tmp_path, "f1.json", noattrib)
    rc, verdict = pg.gate([], fresh, require_attrib=True)
    assert rc == 2 and "attrib" in verdict["error"]
    # an explicit null attrib needs a reason (the SIGTERM/no-result paths)
    fresh = _write(tmp_path, "f2.json", _line(attrib=None))
    rc, verdict = pg.gate([], fresh, require_attrib=True)
    assert rc == 2 and "attrib_reason" in verdict["error"]
    fresh = _write(tmp_path, "f3.json",
                   _line(attrib=None, attrib_reason="BENCH_PROFILE=0"))
    rc, _ = pg.gate([], fresh, require_attrib=True)
    assert rc == 0
    # without the flag, pre-round-6 lines stay judgeable
    rc, _ = pg.gate([], _write(tmp_path, "f4.json", noattrib))
    assert rc == 0


def test_candidate_defaults_to_last_positional(pg, tmp_path):
    h = _write(tmp_path, "h0.json", _line(pps=2500.0))
    f = _write(tmp_path, "f.json", _line(pps=100.0, vsb=0.1))
    rc, verdict = pg.gate([h, f])
    assert rc == 1
    assert verdict["candidate"] == f


def test_repo_history_renders_verdict(pg):
    """The acceptance-criteria invocation: perf_gate over the real
    BENCH_r0*.json bank renders a verdict."""
    import glob

    repo = os.path.join(os.path.dirname(__file__), "..")
    files = sorted(glob.glob(os.path.join(repo, "BENCH_r0*.json")))
    assert len(files) >= 2
    rc, verdict = pg.gate(files)
    assert verdict["verdict"] in ("OK", "REGRESSION", "INVALID",
                                  "NO-LIKE-PROVENANCE-HISTORY")


def test_host_pipeline_flattening_and_directions(pg, tmp_path):
    """The columnar-host-plane metrics: host_pack_points_per_sec and
    host_frac flatten out of the artifact host_pipeline block, pack rate
    regresses on a DROP and host_frac regresses on a RISE."""
    hp = {"pack": {"host_pack_points_per_sec": 1_000_000.0},
          "host_frac": 0.10}
    line = pg.load_bench_line(_write(
        tmp_path, "hp.json", _line(host_pipeline=hp)))
    assert line["host_pack_points_per_sec"] == 1_000_000.0
    assert line["host_frac"] == 0.10
    assert pg.METRICS["host_pack_points_per_sec"] == "higher"
    assert pg.METRICS["host_frac"] == "lower"

    hist = [_write(tmp_path, "hh%d.json" % i,
                   _line(host_pipeline=hp, host_frac=0.10))
            for i in range(3)]
    # pack rate collapse fails the gate
    slow = _line(host_pipeline={"pack": {"host_pack_points_per_sec": 1e5},
                                "host_frac": 0.10}, host_frac=0.10)
    rc, verdict = pg.gate(hist, _write(tmp_path, "f_slow.json", slow))
    assert rc == 1
    assert verdict["metrics"]["host_pack_points_per_sec"][
        "verdict"] == "REGRESSION"
    # host share creeping UP fails the gate (lower-is-better direction)
    hosty = _line(host_pipeline=hp, host_frac=0.60)
    rc, verdict = pg.gate(hist, _write(tmp_path, "f_hosty.json", hosty))
    assert rc == 1
    assert verdict["metrics"]["host_frac"]["verdict"] == "REGRESSION"
    # matching numbers pass
    rc, _ = pg.gate(hist, _write(tmp_path, "f_ok.json",
                                 _line(host_pipeline=hp, host_frac=0.10)))
    assert rc == 0
