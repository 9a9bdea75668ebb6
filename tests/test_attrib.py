"""Named-stage device-time attribution (reporter_tpu/obs/attrib.py).

Three layers, all chip-free:

  * the trace-event parser driven end-to-end by a checked-in synthetic
    TPU profile (tests/fixtures/attrib_trace.json) — stage table, legacy
    per-file/module groupings, and the CPU hlo_op->stage bridge;
  * the shared roofline/row accounting against ops/hashtable's own
    dedup constants;
  * the live capture round-trip on the CPU backend: a real matcher's
    dispatches profiled, parsed, and served — gauges, /statusz summary,
    /debug/attrib (incl. the single-flight 409 carrying the in-flight
    trace_id), and the differential guarantee that annotated kernels are
    bit-identical to unannotated ones.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from reporter_tpu.obs import attrib, profiler
from reporter_tpu.obs import metrics as obs_metrics

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "attrib_trace.json")


# ---------------------------------------------------------------------------
# parser, on the synthetic TPU fixture


class TestParser:
    def test_fixture_stage_table(self):
        out = attrib.parse_trace_file(FIXTURE)
        assert out["platform"] == "tpu"
        assert out["devices"] == 1
        assert out["device_total_ms"] == pytest.approx(4.5)
        assert out["stages_ms"] == {
            "candidate-sweep": pytest.approx(2.0),
            "ubodt-probe": pytest.approx(1.5),  # incl. the args-less repeat
            "select": pytest.approx(0.5),
            "scan-recursion": pytest.approx(0.25),
            attrib.UNATTRIBUTED: pytest.approx(0.25),
        }
        # every named stage the parser found is a canonical scope label
        assert set(out["stages_ms"]) - {attrib.UNATTRIBUTED} <= set(attrib.STAGES)

    def test_fixture_legacy_groupings(self):
        out = attrib.parse_trace_file(FIXTURE)
        # module time comes from the "XLA Modules" thread, outside the total
        assert out["by_module_ms"] == {"jit_fn": pytest.approx(4.5)}
        assert out["by_file_ms"]["candidates.py"] == pytest.approx(2.0)
        assert out["by_file_ms"]["hashtable.py"] == pytest.approx(2.0)
        assert out["by_file_ms"]["(no source)"] == pytest.approx(0.5)
        assert out["top_lines_ms"]["reporter_tpu/ops/candidates.py:104"] == \
            pytest.approx(2.0)

    def test_innermost_scope_wins(self):
        # nested scopes (transition-build > ubodt-probe) attribute to the
        # innermost label — fusion.2's path carries both
        out = attrib.parse_trace_file(FIXTURE)
        assert "transition-build" not in out["stages_ms"]
        assert out["stages_ms"]["ubodt-probe"] > 0

    def test_cpu_events_via_op_stage_map(self):
        events = [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "python"}},
            {"ph": "X", "pid": 1, "tid": 7, "name": "gather_fusion",
             "dur": 3000, "args": {"hlo_module": "jit_fn",
                                   "hlo_op": "gather_fusion"}},
            {"ph": "X", "pid": 1, "tid": 7, "name": "dot.17", "dur": 1000,
             "args": {"hlo_module": "jit_fn", "hlo_op": "dot.17"}},
            {"ph": "X", "pid": 1, "tid": 7, "name": "mystery.1", "dur": 500,
             "args": {"hlo_module": "jit_fn", "hlo_op": "mystery.1"}},
            # module-level executor events carry no hlo_op: excluded
            {"ph": "X", "pid": 1, "tid": 7, "name": "ThunkExecutor::Execute",
             "dur": 99999},
        ]
        m = {("jit_fn", "gather_fusion"): "ubodt-probe", "dot.17": "select"}
        out = attrib.parse_trace_events(events, m)
        assert out["platform"] == "cpu"
        assert out["device_total_ms"] == pytest.approx(4.5)
        assert out["stages_ms"] == {
            "ubodt-probe": pytest.approx(3.0),
            "select": pytest.approx(1.0),
            attrib.UNATTRIBUTED: pytest.approx(0.5),
        }

    def test_op_stage_map_from_hlo(self):
        txt = """HloModule jit_fn, entry_computation_layout={()->f32[]}
  %gather_fusion = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(fn)/jit(main)/rs.ubodt-probe/gather" source_file="x.py"}
  ROOT %dot.17 = f32[] dot(), metadata={op_name="jit(fn)/rs.candidate-sweep/rs.select/dot_general"}
  %plain.1 = f32[] add(), metadata={op_name="jit(fn)/add"}
"""
        m = attrib.op_stage_map_from_hlo([txt])
        assert m[("jit_fn", "gather_fusion")] == "ubodt-probe"
        assert m["dot.17"] == "select"  # innermost of the nested path
        assert "plain.1" not in m

    def test_parse_dir_merges(self, tmp_path):
        d = tmp_path / "cap" / "plugins" / "profile" / "t1"
        d.mkdir(parents=True)
        with open(FIXTURE) as f:
            tr = json.load(f)
        (d / "a.trace.json").write_text(json.dumps(tr))
        (d / "b.trace.json").write_text(json.dumps(tr))
        out = attrib.parse_trace_dir(str(tmp_path / "cap"))
        assert out["devices"] == 2
        assert out["device_total_ms"] == pytest.approx(9.0)
        assert out["stages_ms"]["candidate-sweep"] == pytest.approx(4.0)

    def test_parse_dir_empty_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            attrib.parse_trace_dir(str(tmp_path))

    def test_trace_analyze_keeps_output_format(self):
        import importlib.util

        path = os.path.join(os.path.dirname(__file__), "..", "tools",
                            "trace_analyze.py")
        spec = importlib.util.spec_from_file_location("trace_analyze", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out = mod.analyze(FIXTURE)
        # the historical keys survive, stages_ms rides along
        for key in ("path", "devices", "device_total_ms", "by_module_ms",
                    "by_file_ms", "top_lines_ms", "stages_ms"):
            assert key in out, key


# ---------------------------------------------------------------------------
# shared roofline / row accounting


class TestAccounting:
    def test_dedup_budget_matches_hashtable(self):
        from reporter_tpu.ops.hashtable import (
            _DEDUP_CAP_RATIO, _DEDUP_MIN_PAIRS)

        for n in (100, 1024, 10_000, 2_000_000):
            assert attrib.dedup_budget(n) == max(
                _DEDUP_MIN_PAIRS // 2, n // _DEDUP_CAP_RATIO)

    def test_executed_rows(self):
        n = 512 * 63 * 8 * 8
        assert attrib.executed_rows(n, 2) == 2 * n
        assert attrib.executed_rows(n, 1) == n
        assert attrib.executed_rows(n, 2, dedup=True) == \
            2 * attrib.dedup_budget(n)
        # the bench fleet numbers from docs/measurements (4.13M -> 1.03M)
        assert attrib.executed_rows(n, 2) == 4_128_768
        assert attrib.executed_rows(n, 1, dedup=True) == 1_032_192

    def test_hbm_peak_by_device_kind(self):
        assert attrib.hbm_peak_gbs("TPU v5 lite") == 819.0
        with pytest.raises(ValueError, match="no published HBM peak"):
            attrib.hbm_peak_gbs("cpu")

    def test_roofline_block(self):
        from reporter_tpu.tiles.ubodt import ROW_W

        blk = attrib.roofline_block(
            512, 64, 8, 1.0, bucket_entries=16, max_probes=2, grid_cap=32,
            hbm_gbs=attrib.hbm_peak_gbs("TPU v5 lite"))
        pairs = 512 * 63 * 64
        expect_bytes = pairs * 2 * 16 * ROW_W * 4 + 512 * 64 * 4 * 32 * 32
        assert blk["est_gather_gb_per_s"] == pytest.approx(
            expect_bytes / 1e9, rel=0.01)
        assert blk["hbm_frac"] == pytest.approx(
            expect_bytes / 1e9 / 819.0, abs=1e-3)
        assert blk["rows_per_rep"] == 2 * pairs
        dblk = attrib.roofline_block(
            512, 64, 8, 1.0, bucket_entries=32, max_probes=1, grid_cap=32,
            hbm_gbs=819.0, dedup=True)
        assert dblk["rows_per_rep"] == attrib.dedup_budget(pairs)


# ---------------------------------------------------------------------------
# live capture round-trip on the CPU backend (no chip required)


@pytest.fixture(scope="module")
def tiny_matcher():
    from reporter_tpu.matching import MatcherConfig, SegmentMatcher
    from reporter_tpu.tiles.network import grid_city

    return SegmentMatcher(network=grid_city(rows=4, cols=4, spacing_m=200.0),
                          config=MatcherConfig())


class TestCaptureRoundTrip:
    def test_capture_matcher_stage_table(self, tiny_matcher):
        res = attrib.capture_matcher(tiny_matcher, reps=2)
        assert res["platform"] == "cpu"
        assert res["device_total_ms"] > 0
        named = set(res["stages_ms"]) - {attrib.UNATTRIBUTED}
        # the CPU bridge resolved real named stages, and every name is a
        # canonical jax.named_scope label
        assert named, "no stage resolved — the op->stage bridge broke"
        assert named <= set(attrib.STAGES)
        assert {"candidate-sweep", "ubodt-probe"} & named
        # published: gauges + age + the /statusz summary line
        snap = obs_metrics.REGISTRY.snapshot()
        stage_samples = dict(
            (tuple(lv), v) for lv, v in
            snap["reporter_stage_device_seconds"]["samples"])
        for name in named:
            assert stage_samples[(name,)] == pytest.approx(
                res["stages_ms"][name] / 1e3)
        [(_, age)] = snap["reporter_attrib_age_seconds"]["samples"]
        assert 0 <= age < 120
        summ = attrib.summary()
        assert summ["captured"] and summ["platform"] == "cpu"
        assert summ["top_stage"]["stage"] in attrib.STAGES

    def test_lower_text_bypasses_and_restores_compilation_cache(self,
                                                                tiny_matcher):
        """The op->stage bridge must compile OUTSIDE the persistent cache
        (jax's cache key ignores metadata, so a warm cache replays
        pre-annotation executables with no stage labels) and must restore
        the config afterwards."""
        import jax

        import jax.numpy as jnp

        prev = jax.config.jax_compilation_cache_dir
        fn = tiny_matcher._get_jit("compact", "scan")
        cargs = (tiny_matcher._dg, tiny_matcher._du,
                 jnp.zeros((4, 1, 16), jnp.float32), tiny_matcher._params,
                 tiny_matcher.cfg.beam_k)
        try:
            jax.config.update("jax_compilation_cache_dir", "/tmp/attrib_cc")
            txt = attrib._lower_text(fn, attrib._abstract_args(cargs))
            assert txt and attrib.STAGE_PREFIX + "candidate-sweep" in txt
            assert jax.config.jax_compilation_cache_dir == "/tmp/attrib_cc"
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)

    def test_matcher_registered_programs(self, tiny_matcher):
        tiny_matcher.match_many(tiny_matcher.dummy_traces(16, 1))
        labels = attrib.registered_program_labels()
        assert any(lbl.endswith(":scan") for lbl in labels)

    def test_stale_stage_gauges_zeroed(self):
        attrib.store_result({"captured_unix": time.time(),
                             "stages_ms": {"select": 3.0}})
        attrib.store_result({"captured_unix": time.time(),
                             "stages_ms": {"backtrace": 1.0}})
        snap = obs_metrics.REGISTRY.snapshot()
        samples = dict((tuple(lv), v) for lv, v in
                       snap["reporter_stage_device_seconds"]["samples"])
        assert samples[("select",)] == 0.0
        assert samples[("backtrace",)] == pytest.approx(0.001)

    def test_single_flight_busy_carries_trace_id(self, tiny_matcher):
        entered = threading.Event()
        release = threading.Event()

        def hold():
            with profiler.session("profile", trace_id="owner-123",
                                  seconds=1.0):
                entered.set()
                release.wait(10)

        t = threading.Thread(target=hold, daemon=True)
        t.start()
        assert entered.wait(10)
        try:
            with pytest.raises(profiler.ProfilerBusy) as ei:
                attrib.capture_matcher(tiny_matcher, reps=1)
            assert ei.value.inflight["trace_id"] == "owner-123"
            assert ei.value.inflight["kind"] == "profile"
        finally:
            release.set()
            t.join(10)

    def test_age_gauge_minus_one_before_any_capture(self):
        # a fresh registry collector run with no capture reports -1
        saved = attrib._LAST
        try:
            attrib._LAST = None
            attrib._update_age()
            assert attrib.G_ATTRIB_AGE.value == -1.0
        finally:
            attrib._LAST = saved
            attrib._update_age()


class TestDifferential:
    def test_annotated_bit_identical_to_unannotated(self, tiny_matcher,
                                                    monkeypatch):
        """The acceptance differential: kernels with scope annotation
        emit bit-identical outputs to unannotated ones, both viterbi
        forwards, dedup on."""
        import functools

        import jax
        import jax.numpy as jnp

        from reporter_tpu.ops import viterbi as vt

        m = tiny_matcher
        rng = np.random.default_rng(0)
        B, T = 4, 32
        x0 = float(np.mean(m.arrays.node_x))
        y0 = float(np.mean(m.arrays.node_y))
        px = (x0 + rng.normal(0, 60, (B, T)).cumsum(1)).astype(np.float32)
        py = (y0 + rng.normal(0, 60, (B, T)).cumsum(1)).astype(np.float32)
        tm = np.arange(T, dtype=np.float32)[None].repeat(B, 0) * 5
        valid = np.ones((B, T), np.float32)
        valid[:, T - 3:] = 0  # padded tail
        xin = jnp.asarray(vt.pack_inputs(px, py, tm, valid))

        for kernel in ("scan", "assoc"):
            outs = {}
            for flag in ("1", "0"):
                monkeypatch.setenv("REPORTER_STAGE_SCOPES", flag)
                fn = jax.jit(functools.partial(
                    vt.match_batch_compact_packed, kernel=kernel, dedup=True),
                    static_argnums=(4,))
                outs[flag] = np.asarray(
                    fn(m._dg, m._du, xin, m._params, m.cfg.beam_k)[0])
            assert np.array_equal(outs["1"], outs["0"]), kernel


class TestServiceEndpoints:
    @pytest.fixture(scope="class")
    def service(self, tiny_matcher):
        from reporter_tpu.serve import ReporterService

        return ReporterService(tiny_matcher, max_wait_ms=2.0)

    def test_debug_attrib_get_serves_last(self, service):
        attrib.store_result({"captured_unix": time.time(),
                             "platform": "cpu", "device_total_ms": 1.0,
                             "stages_ms": {"select": 1.0}})
        code, out = service.handle_attrib({})
        assert code == 200
        assert out["attrib"]["stages_ms"] == {"select": 1.0}
        assert out["summary"]["captured"] is True

    def test_debug_attrib_capture_on_demand(self, service):
        code, out = service.handle_attrib({"capture": ["1"], "reps": ["1"]})
        assert code == 200
        named = set(out["attrib"]["stages_ms"]) - {attrib.UNATTRIBUTED}
        assert named and named <= set(attrib.STAGES)

    def test_debug_attrib_busy_409(self, service):
        entered = threading.Event()
        release = threading.Event()

        def hold():
            with profiler.session("attrib", trace_id="cap-owner"):
                entered.set()
                release.wait(10)

        t = threading.Thread(target=hold, daemon=True)
        t.start()
        assert entered.wait(10)
        try:
            code, out = service.handle_attrib(
                {"capture": ["1"], "reps": ["1"]})
            assert code == 409
            assert out["inflight"]["trace_id"] == "cap-owner"
            # the /debug/profile single-flight shares the same guard and
            # names the same owner
            code, out = service.handle_profile({"seconds": ["0.05"]})
            assert code == 409
            assert out["inflight"]["trace_id"] == "cap-owner"
        finally:
            release.set()
            t.join(10)

    def test_debug_attrib_bad_reps(self, service):
        code, out = service.handle_attrib({"capture": ["1"], "reps": ["x"]})
        assert code == 400

    def test_statusz_carries_attrib_summary(self, service):
        code, out = service.handle_statusz()
        assert code == 200
        assert "attrib" in out
        assert "captured" in out["attrib"] and "host" in out["attrib"]
