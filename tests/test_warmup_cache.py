"""Warmup / compile-stall elimination (docs/performance.md).

The acceptance contract: with a full warmup pass (serve --warmup semantics)
and a persistent XLA cache dir configured, a (re)started server's first
/report request records ZERO compile_stall events for configured buckets —
every first-dispatch compile is paid in the warmup phase, visible in the
warmup counters, and the request-path compile counters stay flat across
real traffic on warmed shapes.  Asserted via the obs registry
(reporter_compile_total / reporter_warmup_shapes_total).
"""

import numpy as np
import pytest

from reporter_tpu.matching import MatcherConfig, SegmentMatcher
from reporter_tpu.obs import metrics as obs
from reporter_tpu.tiles.arrays import build_graph_arrays
from reporter_tpu.tiles.network import grid_city
from reporter_tpu.tiles.ubodt import build_ubodt


@pytest.fixture(scope="module")
def engine():
    city = grid_city(rows=5, cols=5, spacing_m=150.0)
    arrays = build_graph_arrays(city, cell_size=100.0)
    ubodt = build_ubodt(arrays, delta=2000.0)
    return arrays, ubodt


CFG = dict(length_buckets=[16, 32], warmup_batch_sizes=[1])


def _compile_total() -> float:
    snap = obs.REGISTRY.snapshot().get(
        "reporter_compile_total", {"samples": []})
    return sum(v for _lv, v in snap["samples"])


def _warm_shapes_total() -> float:
    snap = obs.REGISTRY.snapshot().get(
        "reporter_warmup_shapes_total", {"samples": []})
    return sum(v for _lv, v in snap["samples"])


def _trace(arrays, n=10, uuid="wm"):
    xs = np.linspace(float(arrays.node_x.min()) + 5.0,
                     float(arrays.node_x.max()) - 5.0, n)
    ys = np.full(n, float(arrays.node_y.min()) + 1.0)
    lat, lon = arrays.proj.to_latlon(xs, ys)
    return {
        "uuid": uuid,
        "match_options": {"mode": "auto", "report_levels": [0, 1],
                          "transition_levels": [0, 1]},
        "trace": [{"lat": float(a), "lon": float(o), "time": 1000 + 5 * i,
                   "accuracy": 5} for i, (a, o) in enumerate(zip(lat, lon))],
    }


def test_warmed_server_first_request_sees_no_compile_stall(engine, tmp_path, monkeypatch):
    """serve --warmup + JAX_COMPILATION_CACHE_DIR: after the warm pass, the
    first real request of every configured bucket records zero new
    compile_stall events — across a simulated restart too."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    from reporter_tpu.utils.jaxenv import enable_compilation_cache

    assert enable_compilation_cache() == str(tmp_path / "xla")

    arrays, ubodt = engine
    for restart in range(2):  # second round = the restarted server
        matcher = SegmentMatcher(
            arrays=arrays, ubodt=ubodt, config=MatcherConfig(**CFG))
        warmed_before = _warm_shapes_total()
        matcher.warmup()
        assert _warm_shapes_total() > warmed_before
        for bucket in matcher.cfg.length_buckets:
            assert matcher.compiled_shape_count(bucket) > 0, (restart, bucket)

        from reporter_tpu.serve.service import ReporterService

        service = ReporterService(matcher, max_wait_ms=1.0)
        before = _compile_total()
        for n in (10, 16, 30):  # both configured buckets, first requests
            code, data = service.handle_report(_trace(arrays, n))
            assert code == 200, data
        assert _compile_total() == before, (
            "restart %d: a warmed bucket paid a request-path compile stall"
            % restart)


def test_unwarmed_request_does_record_compile(engine):
    """Control: without warmup the first request of a bucket IS a compile
    stall — the counter the warmed path must keep flat actually fires."""
    arrays, ubodt = engine
    matcher = SegmentMatcher(
        arrays=arrays, ubodt=ubodt, config=MatcherConfig(**CFG))
    before = _compile_total()
    matcher.match_many([_trace(arrays, 10)])
    assert _compile_total() == before + 1


def test_warmup_covers_kernels_and_batch_rungs(engine, monkeypatch):
    """warmup(kernels=..., batch_sizes=...) pre-dispatches the full
    (B, T, kernel) grid, and auto mode warms exactly the kernels live
    traffic will pick per bucket."""
    # auto-mode behaviour under test: the assoc-forcing CI leg must not
    # override the config this test pins
    monkeypatch.delenv("REPORTER_VITERBI", raising=False)
    arrays, ubodt = engine
    matcher = SegmentMatcher(
        arrays=arrays, ubodt=ubodt,
        config=MatcherConfig(viterbi_kernel="auto", viterbi_assoc_threshold=32,
                             length_buckets=[16, 32], warmup_batch_sizes=[1, 4]))
    matcher.warmup()
    # auto: bucket 16 -> scan, bucket 32 -> assoc; two rungs each
    assert matcher.compiled_shape_count(16, kernel="scan") == 2
    assert matcher.compiled_shape_count(32, kernel="assoc") == 2
    before = _compile_total()
    matcher.match_many([_trace(arrays, 12, uuid="a%d" % i) for i in range(3)])
    matcher.match_many([_trace(arrays, 28, uuid="b%d" % i) for i in range(2)])
    assert _compile_total() == before

    # explicit kernels warm both forwards for the same shapes
    m2 = SegmentMatcher(
        arrays=arrays, ubodt=ubodt, config=MatcherConfig(**CFG))
    m2.warmup(lengths=[16], kernels=("scan", "assoc"))
    assert m2.compiled_shape_count(16, kernel="scan") == 1
    assert m2.compiled_shape_count(16, kernel="assoc") == 1


def test_warmup_carry_chain_covers_streaming(engine):
    arrays, ubodt = engine
    matcher = SegmentMatcher(
        arrays=arrays, ubodt=ubodt, config=MatcherConfig(**CFG))
    matcher.warmup(carry_chain=True)
    before = _compile_total()
    matcher.match_many([_trace(arrays, 80)])  # > largest bucket: carry chain
    assert _compile_total() == before, "carry chain paid a request-path compile"


def test_warmup_covers_batched_precompute(engine, monkeypatch):
    """The hoisted long-trace path dispatches TWO programs per group — the
    chunk-batched precompute ("pre", kernel-independent) and the score
    recursion ("chain") — and warmup(carry_chain=True) must cover both:
    zero request-path compiles for a streamed long trace, across every
    chunk count whose pre rows snap to the warmed ladder rung."""
    monkeypatch.delenv("REPORTER_LONG_PRECOMPUTE", raising=False)
    arrays, ubodt = engine
    matcher = SegmentMatcher(
        arrays=arrays, ubodt=ubodt, config=MatcherConfig(**CFG))
    assert matcher._long_pre, "hoisted mode must be the default"
    matcher.warmup(carry_chain=True)
    W = matcher.cfg.length_buckets[-1]
    assert matcher.compiled_shape_count(W, kind="pre", kernel="none") > 0, (
        "warmup did not pre-dispatch the batched-precompute program")
    assert matcher.compiled_shape_count(W, kind="chain") > 0, (
        "warmup did not pre-dispatch the chain program")
    assert matcher.compiled_shape_count(W, kind="carry") == 0, (
        "hoisted mode compiled the legacy fused carry program")
    before = _compile_total()
    # 2, 3 and 4 chunks all share the warmed pre rung (rows 2..4 -> rung 4)
    # and the [1, W] chain shape: first requests must be compile-free
    for n in (2 * W + 9, 3 * W - 1, 4 * W - 2):
        matcher.match_many([_trace(arrays, n)])
    assert _compile_total() == before, (
        "a warmed long trace paid a request-path compile")


def test_warmup_session_step_covers_streaming(engine):
    """warmup(session_step=True) — the serve --warmup semantics — must
    pre-dispatch every (batch rung, session bucket) incremental-step
    shape, so the FIRST streaming point of a fresh boot records zero
    request-path compile stalls (the session matcher's whole point is
    point latency; an inline XLA compile there is the stall the carry
    chain already eliminated for long traces)."""
    from reporter_tpu.matching.session import SessionEngine, SessionStore
    from reporter_tpu.serve.service import ReporterService

    arrays, ubodt = engine
    matcher = SegmentMatcher(
        arrays=arrays, ubodt=ubodt,
        config=MatcherConfig(session_buckets=[4, 16], **CFG))
    matcher.warmup(lengths=[], session_step=True)
    for w in matcher.cfg.session_buckets:
        assert matcher.compiled_shape_count(w, kind="session") > 0, w
    before = _compile_total()
    # the real streaming submit path: single point (bucket 4), then a
    # wider delta (bucket 16) — both warmed, neither may compile
    service = ReporterService(matcher, max_wait_ms=1.0, session_wait_ms=1.0)
    tr = _trace(arrays, 12, uuid="wm-stream")
    code, data = service.handle_report(
        dict(tr, stream=True, trace=tr["trace"][:1]))
    assert code == 200, data
    code, data = service.handle_report(
        dict(tr, stream=True, trace=tr["trace"][1:]))
    assert code == 200, data
    assert _compile_total() == before, (
        "a warmed session step paid a request-path compile stall")

    # control: an UNwarmed matcher's first streaming step IS a compile
    m2 = SegmentMatcher(
        arrays=arrays, ubodt=ubodt,
        config=MatcherConfig(session_buckets=[4, 16], **CFG))
    eng = SessionEngine(m2, SessionStore(), tail_points=64)
    before = _compile_total()
    eng.match_many([{"uuid": "wm-cold", "trace": tr["trace"][:1],
                     "match_options": tr["match_options"]}])
    assert _compile_total() == before + 1


@pytest.mark.slow  # the mesh rehearsal leg boots serve --warmup on the dp-8 topology
def test_warmup_covers_mesh_programs(engine):
    """A mesh matcher's warmup (serve --warmup on the pod topology,
    docs/performance.md "One logical matcher per pod") pre-dispatches the
    dp-sharded program variants through the REAL dispatch path, so the
    first requests of a warmed mesh replica — bucketed, carry-chain long,
    and streaming session step — pay zero request-path compiles."""
    import jax

    from reporter_tpu.matching.session import SessionEngine, SessionStore

    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device CPU backend")
    arrays, ubodt = engine
    matcher = SegmentMatcher(
        arrays=arrays, ubodt=ubodt,
        config=MatcherConfig(devices=2, session_buckets=[4, 16], **CFG))
    assert matcher._mesh is not None
    matcher.warmup(carry_chain=True, session_step=True)
    before = _compile_total()
    matcher.match_many([_trace(arrays, 12, uuid="mesh-a")])
    matcher.match_many([_trace(arrays, 80, uuid="mesh-b")])  # carry chain
    eng = SessionEngine(matcher, SessionStore(), tail_points=64)
    tr = _trace(arrays, 12, uuid="mesh-stream")
    eng.match_many([{"uuid": tr["uuid"], "trace": tr["trace"][:1],
                     "match_options": tr["match_options"]}])
    assert _compile_total() == before, (
        "a warmed mesh program paid a request-path compile stall")


def test_legacy_long_path_still_selectable(engine, monkeypatch):
    """REPORTER_LONG_PRECOMPUTE=0 forces the legacy fused per-chunk carry
    program — the differential reference must stay dispatchable."""
    monkeypatch.setenv("REPORTER_LONG_PRECOMPUTE", "0")
    arrays, ubodt = engine
    matcher = SegmentMatcher(
        arrays=arrays, ubodt=ubodt, config=MatcherConfig(**CFG))
    assert not matcher._long_pre
    out = matcher.match_many([_trace(arrays, 80)])
    assert out[0]["segments"]
    assert any(k[0] == "carry" for k in matcher._compiled_shapes)
    assert all(k[0] not in ("pre", "chain") for k in matcher._compiled_shapes)


def test_stage_rows_reuses_pinned_buffers(engine):
    """The batch-pad hot path must stop reallocating: same shape in, same
    staging buffer out, with the pad tail re-zeroed between uses."""
    arrays, ubodt = engine
    matcher = SegmentMatcher(
        arrays=arrays, ubodt=ubodt, config=MatcherConfig(**CFG))
    a = np.ones((3, 16), np.float32)
    out1 = matcher._stage_rows(4, a, a * 2.0)
    buf_ids = [id(o) for o in out1]
    assert all(o.shape == (4, 16) for o in out1)
    assert (out1[0][3] == 0).all()
    # poison the tail, restage: same buffers, tail re-zeroed
    out1[1][3] = 7.0
    b = np.full((2, 16), 5.0, np.float32)
    out2 = matcher._stage_rows(4, b, b)
    assert [id(o) for o in out2] == buf_ids
    assert (out2[1][2:] == 0).all() and (out2[1][:2] == 5.0).all()
    # distinct slots never share a buffer even at identical shape/dtype
    assert id(out2[0]) != id(out2[1])


def test_probe_stats_deferred_off_dispatch(engine, monkeypatch):
    """The sampled UBODT probe is dispatched on the hot thread but its
    np.asarray sync happens on the collect side: after a dispatch tick the
    probe sits in _probe_pending; the collect drains it into the outcome
    counters."""
    monkeypatch.setenv("REPORTER_OBS_PROBE_EVERY", "1")
    arrays, ubodt = engine
    matcher = SegmentMatcher(
        arrays=arrays, ubodt=ubodt, config=MatcherConfig(**CFG))
    assert matcher._probe_every == 1

    def _pairs_total():
        snap = obs.REGISTRY.snapshot().get(
            "reporter_ubodt_probe_total", {"samples": []})
        return sum(v for lv, v in snap["samples"] if lv == ["pairs"])

    before = _pairs_total()
    t = _trace(arrays, 10)
    px, py, tm, valid, _times = matcher._fill_rows([t], [0], 16)
    handle = matcher._dispatch_batch(px, py, tm, valid)
    assert len(matcher._probe_pending) == 1, "probe sync ran on dispatch"
    matcher._collect_batch(handle)
    assert not matcher._probe_pending
    assert _pairs_total() > before
