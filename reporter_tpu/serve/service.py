"""HTTP matching service.

Wire-compatible with the reference's reporter_service
(py/reporter_service.py:182-274):

  GET  /report?json={...}   and   POST /report
      -> {"datastore": ..., "segment_matcher": ..., "shape_used": ...,
          "stats": ...}
      with the same validation errors (uuid required, >= 2 points,
      report_levels / transition_levels required).

Plus the TPU-native addition (BASELINE.json north star):

  POST /trace_attributes_batch   {"traces": [trace, ...]}
      -> {"results": [report-output, ...]}

Architecture difference from the reference, on purpose: the reference keeps
one C++ matcher per thread and matches traces one at a time
(reporter_service.py:51-58).  Here a single shared matcher owns the device,
and a MicroBatcher aggregates concurrent requests into padded [B, T] batches
for one vmapped device program -- single /report requests arriving together
are batched transparently, which is where the TPU throughput comes from.

THRESHOLD_SEC is honoured like the reference (:54-58).
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import queue
import socket as _socket
import threading
import time as _time
import zlib
from concurrent.futures import Future
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import List, Optional, Tuple
from urllib.parse import parse_qs, urlsplit

from .. import faults
from ..matching import MatcherConfig, SegmentMatcher
from ..matching.matcher import C_POINTS as C_POINTS_MATCHED
from ..matching.session import SessionCheckpointer, SessionEngine, SessionStore
from ..obs import adaptive as obs_adaptive
from ..obs import attrib as obs_attrib
from ..obs import economics as obs_econ
from ..obs import flight as obs_flight
from ..obs import log as obs_log
from ..obs import metrics as obs
from ..obs import quality as obs_quality
from ..obs import slo as obs_slo
from ..obs import trace as obs_trace
from ..obs.quantile import SLO_BUCKETS_S
from ..obs.trace import Span
from ..report import report as report_fn
from ..tiles.network import RoadNetwork, grid_city
from . import wire

log = logging.getLogger(__name__)

ACTIONS = {"report", "trace_attributes_batch", "health", "sessions",
           "metrics", "statusz", "profile", "traces", "attrib", "slo",
           "cost", "history"}

# gzip request bodies (Content-Encoding: gzip, docs/http-api.md): bound
# on the DECOMPRESSED size so a tiny zip bomb cannot balloon a handler
# thread — comfortably above any real batch body, refused with a 400
# beyond it ($REPORTER_MAX_INFLATE_MB overrides)
try:
    _MAX_INFLATE = int(float(os.environ["REPORTER_MAX_INFLATE_MB"])) << 20
except (KeyError, ValueError):
    _MAX_INFLATE = 256 << 20


def _gunzip(raw: bytes, limit: int = 0) -> bytes:
    """Bounded gzip-body inflate (stdlib zlib, 16+MAX_WBITS accepts the
    gzip header).  Raises ValueError past ``limit`` decompressed bytes."""
    limit = limit or _MAX_INFLATE
    d = zlib.decompressobj(16 + zlib.MAX_WBITS)
    out = d.decompress(raw, limit)
    if d.unconsumed_tail:
        raise ValueError(
            "gzip body exceeds %d decompressed bytes" % limit)
    return out + d.flush()


def _env_num(name: str, default: float) -> float:
    """Numeric env knob with a safe fallback (a typo'd value must degrade
    to the default, not refuse to boot)."""
    try:
        return float(os.environ[name])
    except (KeyError, ValueError):
        return float(default)


def _resolve_num(env_name: str, param, default: float) -> float:
    """Knob resolution order, matching the matcher's convention
    (REPORTER_UBODT_LAYOUT et al.): env var > config/constructor value >
    default — an operator can retune a live deployment's robustness knobs
    without a config rollout."""
    if os.environ.get(env_name, "").strip():
        return _env_num(env_name, default if param is None else param)
    return float(default if param is None else param)

# metric families (docs/observability.md): the batch-fill/wait tradeoff and
# the device-step tail are THE operating signals of a batched-accelerator
# service — aggregate throughput alone cannot show a queue-wait regression
M_QUEUE_WAIT = obs.histogram(
    "reporter_microbatch_queue_wait_seconds",
    "Per-trace wait from submit to micro-batch formation",
    buckets=SLO_BUCKETS_S)
M_BATCH_FILL = obs.histogram(
    "reporter_microbatch_batch_fill",
    "Traces per dispatched device micro-batch",
    buckets=obs.BATCH_FILL_BUCKETS)
M_DEVICE_STEP = obs.histogram(
    "reporter_microbatch_device_step_seconds",
    "Per-batch finish() wall: device wait + host segment association")
G_INFLIGHT = obs.gauge(
    "reporter_microbatch_inflight",
    "Micro-batches dispatched to the device and not yet finished")
G_QDEPTH = obs.gauge(
    "reporter_microbatch_queue_depth",
    "Submit-queue depth sampled at each batch formation")
C_BATCHES = obs.counter(
    "reporter_microbatch_batches_total",
    "Device micro-batches dispatched")
C_REQUESTS = obs.counter(
    "reporter_requests_total",
    "Requests by endpoint and outcome (ok / invalid / error / shed / "
    "expired / quarantined / degraded)",
    ("endpoint", "outcome"))
# fault-domain surfaces (docs/robustness.md): load shedding, queue-expiry,
# poison isolation, the device watchdog and the degraded CPU fallback each
# get their own family so an incident reads directly off /metrics
C_SHED = obs.counter(
    "reporter_requests_shed_total",
    "Requests rejected 429 at admission (submit queue full)")
C_EXPIRED = obs.counter(
    "reporter_requests_expired_total",
    "Requests whose deadline expired in the queue, dropped before "
    "dispatch (504)")
C_POISON = obs.counter(
    "reporter_poison_isolated_total",
    "Traces isolated as batch poison by the bisect-retry quarantine")
C_QUAR_REJ = obs.counter(
    "reporter_quarantine_rejected_total",
    "Requests rejected at admission because their uuid is quarantined "
    "as a repeat poison offender")
C_WD_TRIPS = obs.counter(
    "reporter_watchdog_trips_total",
    "Device-step watchdog trips (a finish() exceeded the bound; the "
    "batcher is wedged and the service degrades to the CPU fallback)")
C_CRASHES = obs.counter(
    "reporter_batcher_crashes_total",
    "MicroBatcher loop-thread crashes (dispatch worker or finisher died "
    "on an unexpected error; pending futures failed, /health unhealthy)")
G_DEGRADED = obs.gauge(
    "reporter_degraded_mode",
    "1 while the service answers from the CPU fallback after a device "
    "watchdog trip, 0 when the accelerator engine is attached")
C_DEGRADED_REQ = obs.counter(
    "reporter_degraded_requests_total",
    "Requests answered by the CPU fallback (responses carry "
    "degraded: true)")
C_REATTACH = obs.counter(
    "reporter_engine_reattach_total",
    "Successful engine re-attach events after degraded-mode probes found "
    "the device healthy again")
# graceful-drain surfaces (docs/serving-fleet.md): the router reads the
# drain off /health; these make the same lifecycle visible on /metrics
G_DRAINING = obs.gauge(
    "reporter_draining",
    "1 from SIGTERM (drain start) until the process exits: new work is "
    "refused 503 \"draining\" while inflight requests finish")
C_DRAIN_REFUSED = obs.counter(
    "reporter_drain_refused_total",
    "Requests refused 503 \"draining\" after drain start (retryable: the "
    "router re-dispatches them to a live replica)")


class Overloaded(RuntimeError):
    """Submit queue full: shed with 429 + Retry-After (retryable)."""


class DeadlineExpired(RuntimeError):
    """The request's deadline passed while it sat in the queue: 504,
    dropped before it could waste a device slot."""


class TraceQuarantined(RuntimeError):
    """The uuid is a repeat poison offender: rejected at admission with a
    non-retryable 422 (the reference client only retries 5xx)."""


class PoisonTrace(RuntimeError):
    """This trace made its device batch fail while its co-batched
    neighbours succeeded on bisect-retry."""


class DeviceWedged(RuntimeError):
    """The watchdog tripped: the device step is wedged and this batcher
    no longer accepts work (the service falls back to CPU)."""


class BatcherCrashed(RuntimeError):
    """A MicroBatcher loop thread died on an unexpected error; the
    batcher is dead and /health reports unhealthy."""


class MicroBatcher:
    """Aggregates traces from concurrent requests into one device batch.

    Traces are enqueued with a Future; a single worker drains the queue,
    waits up to ``max_wait_ms`` to fill ``max_batch`` slots, runs
    matcher.match_many once, and resolves the futures.  Batching across
    requests is what keeps the TPU busy when clients send one trace per call.

    The worker is split in two stages (VERDICT r02 next #3): the dispatch
    thread only forms batches and queues device work
    (matcher.match_many_async), while a separate finisher thread blocks on
    the device and runs host segment association.  Association of batch N
    therefore overlaps device compute of batch N+1 instead of stalling the
    dispatch loop.  The hand-off queue is bounded to keep device-pinned
    input memory in check (backpressure on dispatch, not unbounded queueing).

    Device-memory bound: each undrained async call can pin up to
    matcher.PIPELINE_DEPTH chunks, and (max_inflight + 2) calls can overlap
    in the worst case (one dispatching, max_inflight queued, one finishing)
    -- so size max_device_points for (max_inflight + 2) * PIPELINE_DEPTH
    chunks, not PIPELINE_DEPTH alone.  At the defaults (depth 8,
    max_inflight 4, ~3.7 MB of packed transport per chunk) that composite
    is ~178 MB of HBM next to the graph + UBODT.  Depth 4 hid the
    dispatch sync cost and the whole of host association under device
    compute in round 5's chip sweep (depth 2/3/4; a capture deleted in
    PR 21 with its access path — re-measure in the benchmark).

    Fault domains (docs/robustness.md): the submit queue is BOUNDED and
    sheds at admission (Overloaded -> 429), every entry carries a deadline
    and is dropped before dispatch once it expires (DeadlineExpired ->
    504), a failed batch is bisect-retried so one poison trace fails alone
    while its co-batched neighbours succeed (repeat offenders by uuid are
    then rejected at admission: TraceQuarantined -> 422), a watchdog
    bounds every device-blocking section and wedges the batcher on a hung
    device step (DeviceWedged; the service's on_wedged hook degrades to
    the CPU fallback), and both loop threads are crash-loud — an
    unexpected loop error fails every pending future and marks the
    batcher dead (BatcherCrashed; /health flips unhealthy) instead of
    stranding the peer thread on the bounded hand-off queue.
    """

    def __init__(self, matcher: SegmentMatcher, max_batch: int = 64, max_wait_ms: float = 10.0,
                 max_inflight: Optional[int] = None, instrument: bool = True,
                 max_queue: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 watchdog_s: Optional[float] = None,
                 quarantine_after: Optional[int] = None,
                 quarantine_ttl_s: Optional[float] = None,
                 on_wedged=None, on_crashed=None, name: str = "batch"):
        if max_inflight is None:
            # 4 on an accelerator (round 5's depth sweep: hides host
            # association under device compute); when the
            # compute actually runs on host cores (the numpy cpu backend,
            # or the jax backend on cpu devices) it shares them with
            # association and deep pipelining only adds contention --
            # same platform split, same measurements as bench.py's
            # BENCH_INFLIGHT default
            plat = "cpu"
            if getattr(matcher, "backend", "cpu") != "cpu":
                import jax

                plat = jax.devices()[0].platform
            max_inflight = 4 if plat != "cpu" else 2
        # maxsize<=0 means UNBOUNDED to queue.Queue — a configured 0 would
        # silently invert the backpressure bound on device-pinned memory
        # (ADVICE r05); clamp rather than reject so a sloppy config degrades
        # to the strictest bound instead of refusing to boot
        max_inflight = max(1, int(max_inflight))
        self.matcher = matcher
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1000.0
        # metrics off only for A/B overhead measurement (tests); spans
        # always flow — tracing is always on, one span per request, and
        # ?debug=1 only controls whether the breakdown rides the response
        self._obs = bool(instrument)
        # adaptive fill window (docs/serving-fleet.md "Self-driving
        # fleet"): the live windowed p95s of queue wait vs device step
        # steer max_wait — shrink when queue wait dominates the tail
        # (holding the window open IS the latency), grow when the device
        # step dwarfs it and batches still fill (amortisation wins).
        # Clamped to [0.2x, 4x] the static knob, hysteresis-damped, and
        # entirely absent with REPORTER_ADAPTIVE=0 (bit-for-bit static).
        self._wait_ctl = None
        self._h_qwait = self._h_dstep = None
        # adaptive max_batch (the third knob the PR 13 controllers left
        # static): when the device-step p95 dominates the tail on batches
        # that actually fill to the cap, the batch is the latency — the
        # controller narrows it toward max_batch/4 and glides back to the
        # static cap when the step stops dominating.  Clamped, deadbanded
        # and cooldown-limited like every adaptive control; absent with
        # REPORTER_ADAPTIVE=0 (bit-for-bit static).
        self._batch_ctl = None
        self._static_max_batch = max_batch
        if obs_adaptive.enabled() and self.max_wait > 0:
            static = self.max_wait
            self._wait_ctl = obs_adaptive.Controller(
                "%s_wait_s" % name, static,
                lo=max(0.0005, 0.2 * static), hi=4.0 * static,
                cooldown_s=1.0)
            self._h_qwait = obs_adaptive.WindowedQuantile(window_s=30.0)
            self._h_dstep = obs_adaptive.WindowedQuantile(window_s=60.0)
            if max_batch > 1:
                self._batch_ctl = obs_adaptive.Controller(
                    "%s_max_batch" % name, float(max_batch),
                    lo=max(1.0, max_batch / 4.0), hi=float(max_batch),
                    cooldown_s=1.0)
        # fault-domain knobs (docs/robustness.md), env-overridable so a
        # deployment can retune without a config rollout.  deadline_ms<=0
        # disables the server default (client-sent deadlines still apply);
        # watchdog_s<=0 disables the watchdog.
        self.max_queue = int(_resolve_num(
            "REPORTER_MAX_QUEUE", max_queue, 1024))
        self.deadline_s = _resolve_num(
            "REPORTER_DEADLINE_MS", deadline_ms, 30000.0) / 1000.0
        self.watchdog_s = _resolve_num(
            "REPORTER_WATCHDOG_S", watchdog_s, 120.0)
        self.quarantine_after = int(_resolve_num(
            "REPORTER_QUARANTINE_AFTER", quarantine_after, 2))
        self.quarantine_ttl_s = _resolve_num(
            "REPORTER_QUARANTINE_TTL_S", quarantine_ttl_s, 300.0)
        # fault-domain state: wedged = watchdog tripped (device stuck),
        # crashed = a loop thread died on a bug.  Both are terminal for
        # this batcher — the service swaps in a new one on re-attach.
        self.wedged = False
        self._wedge_reason: Optional[str] = None
        self._crashed = False
        self._crash_reason: Optional[str] = None
        self._on_wedged = on_wedged
        self._on_crashed = on_crashed
        self._offender_lock = threading.Lock()
        self._offenders: dict = {}    # uuid -> poison isolations
        self._quarantine: dict = {}   # uuid -> monotonic expiry
        # device-blocking sections under watchdog watch: tid -> (t0, batch)
        self._step_lock = threading.Lock()
        self._steps: dict = {}
        self._q: "queue.Queue[tuple]" = queue.Queue(maxsize=max(1, self.max_queue))
        self._finish_q: "queue.Queue[tuple]" = queue.Queue(maxsize=max_inflight)
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()
        self._finisher = threading.Thread(target=self._finish_worker, daemon=True)
        self._finisher.start()
        if self.watchdog_s > 0:
            self._watchdog_thread = threading.Thread(
                target=self._watchdog, daemon=True, name="batch-watchdog")
            self._watchdog_thread.start()

    def submit(self, trace: dict, span: Optional[Span] = None,
               deadline: Optional[float] = None) -> Future:
        """Admission control happens HERE, before any queueing: a dead
        batcher refuses loudly, quarantined repeat-poison uuids are
        rejected (non-retryable), and a full queue sheds (retryable) —
        an overloaded server must answer fast, not queue unboundedly.
        ``deadline`` is an absolute time.monotonic() bound; None applies
        the server default."""
        if self._crashed:
            raise BatcherCrashed(self._crash_reason or "batcher thread died")
        if self.wedged:
            raise DeviceWedged(self._wedge_reason or "device step wedged")
        uuid = str(trace.get("uuid") or "") if isinstance(trace, dict) else ""
        if uuid and self._is_quarantined(uuid):
            C_QUAR_REJ.inc()
            raise TraceQuarantined(
                "uuid %r is quarantined after repeated poison-batch "
                "isolation" % uuid)
        if deadline is None and self.deadline_s > 0:
            deadline = _time.monotonic() + self.deadline_s
        f: Future = Future()
        try:
            self._q.put_nowait((trace, f, _time.monotonic(), span, deadline))
        except queue.Full:
            C_SHED.inc()
            raise Overloaded(
                "submit queue full (%d waiting)" % self._q.qsize()) from None
        return f

    def match(self, trace: dict, span: Optional[Span] = None,
              deadline: Optional[float] = None) -> dict:
        return self.submit(trace, span, deadline=deadline).result()

    def match_many(self, traces: List[dict],
                   deadline: Optional[float] = None) -> List[dict]:
        futures = [self.submit(t, deadline=deadline) for t in traces]
        return [f.result() for f in futures]

    def _adapt_wait(self, fill: int) -> None:
        """One adaptive-control tick for the fill window (no-op with
        REPORTER_ADAPTIVE=0).  Signals are the live windowed p95s:

          * queue wait dominating the device step means holding the
            window open IS the client-visible tail — shrink it;
          * a device step that dwarfs both the wait and the queue tail,
            on batches that actually fill, means per-dispatch
            amortisation is the win — grow it.

        The Controller clamps to [0.2x, 4x] the static knob, ignores
        in-deadband noise, and rate-limits moves, so short tests and
        steady traffic never see the knob move."""
        ctl = self._wait_ctl
        if ctl is None:
            return
        if self._h_qwait.count() < 32 or self._h_dstep.count() < 8:
            return  # not enough live signal to steer by
        q95 = self._h_qwait.quantile(0.95)
        d95 = self._h_dstep.quantile(0.95)
        if q95 is None or d95 is None:
            return
        if q95 > 2.0 * d95 and q95 > self.max_wait:
            self.max_wait = ctl.propose(0.7 * self.max_wait)
        elif d95 > 4.0 * max(q95, self.max_wait) \
                and fill >= max(2, self.max_batch // 2):
            self.max_wait = ctl.propose(1.3 * self.max_wait)
        self._adapt_batch(fill, q95, d95)

    def _adapt_batch(self, fill: int, q95: float, d95: float) -> None:
        """One adaptive-control tick for the batch width (no-op with
        REPORTER_ADAPTIVE=0).  A device step whose p95 dominates the
        queue tail ON BATCHES THAT FILL TO THE CAP means the batch width
        itself is the client-visible latency — shrink it; once the step
        stops dominating, glide back toward the static cap (the
        throughput configuration the operator chose).  The controller
        clamps to [max_batch/4, max_batch]: the adaptive knob can narrow
        a batch, never widen past the operator's memory bound."""
        ctl = self._batch_ctl
        if ctl is None:
            return
        if d95 > 4.0 * max(q95, 1e-4) and fill >= self.max_batch:
            self.max_batch = max(1, int(round(
                ctl.propose(0.7 * ctl.value))))
        elif d95 < 2.0 * max(q95, 1e-4) \
                and ctl.value < self._static_max_batch:
            self.max_batch = max(1, int(round(
                ctl.propose(1.3 * ctl.value))))

    def retry_after_s(self) -> int:
        """Backoff hint for shed (429) responses: deeper queue, longer
        hint, capped so clients re-probe within their retry budget."""
        return max(1, min(30, 1 + self._q.qsize() // max(1, self.max_batch)))

    # -- future resolution (idempotent: the watchdog may have failed a
    # future that a stuck thread later tries to resolve) ------------------

    @staticmethod
    def _resolve_exc(f: Future, e: BaseException) -> None:
        try:
            if f.set_running_or_notify_cancel():
                f.set_exception(e)
        except Exception:  # noqa: BLE001 - already resolved elsewhere
            pass

    @staticmethod
    def _resolve_result(f: Future, r) -> None:
        try:
            if f.set_running_or_notify_cancel():
                f.set_result(r)
        except Exception:  # noqa: BLE001 - already resolved elsewhere
            pass

    @classmethod
    def _fail_batch(cls, batch, e: Exception) -> None:
        for entry in batch:
            cls._resolve_exc(entry[1], e)

    # -- loop threads (crash-loud: an unexpected loop error fails every
    # pending future and marks the batcher dead, instead of stranding the
    # peer thread on a bounded queue forever) -----------------------------

    def _worker(self):
        try:
            self._worker_loop()
        except BaseException as e:  # noqa: BLE001 - crash-loud by design
            self._crash("dispatch worker", e)

    def _finish_worker(self):
        try:
            self._finisher_loop()
        except BaseException as e:  # noqa: BLE001 - crash-loud by design
            self._crash("finisher", e)

    def _worker_loop(self):
        while True:
            entry = self._q.get()
            batch = [entry]
            # opportunistically fill the batch within one absolute window so
            # the first request's extra latency is bounded by max_wait; the
            # profiler span names this by-design wait on the host timeline
            # (the queue-wait histogram already counts it)
            deadline = _time.monotonic() + self.max_wait
            with obs_attrib.host_span("batch_fill", entry[3]):
                while len(batch) < self.max_batch:
                    remaining = deadline - _time.monotonic()
                    if remaining <= 0:
                        break
                    try:
                        batch.append(self._q.get(timeout=remaining))
                    except queue.Empty:
                        break
            now = _time.monotonic()
            # deadline scrub BEFORE dispatch: an entry whose budget died in
            # the queue answers 504 now and never wastes a device slot (its
            # client has already given up; matching it would starve the
            # still-live requests behind it).  The chaos clock_skew point
            # scales each entry's ELAPSED time (factor 1.0 disarmed, so
            # the comparison is bit-identical without it).
            skew = faults.scale("clock_skew")
            live = []
            for e_ in batch:
                dl = e_[4]
                eff = now if skew == 1.0 else e_[2] + (now - e_[2]) * skew
                if dl is not None and eff > dl:
                    C_EXPIRED.inc()
                    self._resolve_exc(e_[1], DeadlineExpired(
                        "deadline expired after %.3fs in queue"
                        % (now - e_[2])))
                else:
                    live.append(e_)
            batch = live
            if not batch:
                continue
            # the batch's lead span: its trace_id becomes the histogram
            # exemplar for batch-level observations, and the dispatch
            # thread binds it so a compile stall logged inside the matcher
            # carries a real request's id
            lead = next((e[3] for e in batch if e[3] is not None), None)
            if self._obs:
                G_QDEPTH.set(self._q.qsize())
                M_BATCH_FILL.observe(
                    len(batch), exemplar=lead.trace_id if lead else None)
                C_BATCHES.inc()
            for _t, _f, t_enq, sp, _dl in batch:
                wait = now - t_enq
                if self._obs:
                    M_QUEUE_WAIT.observe(
                        wait, exemplar=sp.trace_id if sp else None)
                if self._h_qwait is not None:
                    self._h_qwait.observe(wait)
                if sp is not None:
                    sp.mark("queue_wait_s", wait)
                    sp.meta["batch_size"] = len(batch)
            self._adapt_wait(len(batch))
            try:
                t_d0 = _time.monotonic()
                with obs_trace.bind(lead):
                    finish = self.matcher.match_many_async(
                        [e[0] for e in batch])
                dispatch_s = _time.monotonic() - t_d0
                for _t, _f, _te, sp, _dl in batch:
                    if sp is not None:
                        # dispatch is async EXCEPT when a shape compiles:
                        # this mark is where a cold-start stall shows up
                        sp.mark("dispatch_s", dispatch_s)
            except Exception as e:
                log.exception("batch dispatch failed")
                self._contain_failure(batch, e)
                continue
            if self._obs:
                G_INFLIGHT.inc()
            # bounded hand-off (blocks when the finisher lags), abandoned
            # when the batcher dies so this thread never wedges on a queue
            # nobody drains
            while True:
                try:
                    self._finish_q.put((batch, finish), timeout=0.25)
                    break
                except queue.Full:
                    if self.wedged or self._crashed:
                        self._fail_batch(batch, DeviceWedged(
                            self._wedge_reason or "batcher dead"))
                        if self._obs:
                            G_INFLIGHT.dec()
                        break

    def _finisher_loop(self):
        while True:
            batch, finish = self._finish_q.get()
            try:
                t0 = _time.monotonic()
                with self._watched(batch):
                    results = finish()
                step_s = _time.monotonic() - t0
                if self._h_dstep is not None:
                    self._h_dstep.observe(step_s)
                if self._obs:
                    lead = next(
                        (e[3] for e in batch if e[3] is not None), None)
                    M_DEVICE_STEP.observe(
                        step_s, exemplar=lead.trace_id if lead else None)
                for (t, f, _te, sp, _dl), r in zip(batch, results):
                    if sp is not None:
                        sp.mark("device_step_s", step_s)
                    self._resolve_result(f, r)
            except Exception as e:  # contain: bisect for poison, else fail
                log.exception("batch match failed")
                self._contain_failure(batch, e)
            finally:
                if self._obs:
                    G_INFLIGHT.dec()

    # -- device watchdog ---------------------------------------------------

    @contextlib.contextmanager
    def _watched(self, batch):
        """Register the calling thread's device-blocking section with the
        watchdog (finish() in the finisher, match_many in bisect-retry)."""
        tid = threading.get_ident()
        with self._step_lock:
            self._steps[tid] = (_time.monotonic(), batch)
        try:
            yield
        finally:
            with self._step_lock:
                self._steps.pop(tid, None)

    def _watchdog(self):
        """Bound every device-blocking section: a wedged device step must
        become a visible, contained failure (degraded CPU serving via the
        service's on_wedged hook), not a silently hung server."""
        tick = max(0.02, min(1.0, self.watchdog_s / 8.0))
        while not (self.wedged or self._crashed):
            _time.sleep(tick)
            now = _time.monotonic()
            with self._step_lock:
                stuck = [b for (t0, b) in self._steps.values()
                         if now - t0 > self.watchdog_s]
            if stuck:
                self._trip("device step exceeded the %.1fs watchdog"
                           % self.watchdog_s, stuck)
                return

    def _trip(self, reason: str, stuck_batches=()) -> None:
        C_WD_TRIPS.inc()
        self.wedged = True
        self._wedge_reason = reason
        obs_log.event(log, "watchdog_trip", level=logging.ERROR,
                      reason=reason)
        exc = DeviceWedged(reason)
        # flip the service into degraded mode FIRST: handlers whose futures
        # fail below re-check it and answer from the CPU fallback instead
        # of bouncing a retryable 503 back at the client
        cb = self._on_wedged
        if cb is not None:
            try:
                cb(reason)
            except Exception:  # noqa: BLE001 - never lose the trip itself
                log.exception("on_wedged callback failed")
        # the stuck thread cannot be interrupted (it is blocked inside the
        # device runtime); fail its batch's futures so no handler waits on
        # it — if it ever completes, its resolutions are no-ops
        for b in stuck_batches:
            self._fail_batch(b, exc)
        self._drain_fail(exc)

    def _crash(self, who: str, e: BaseException) -> None:
        if self._crashed:
            return
        self._crashed = True
        self._crash_reason = "%s thread died: %s" % (who, e)
        C_CRASHES.inc()
        log.critical("MicroBatcher %s; failing all pending futures",
                     self._crash_reason, exc_info=True)
        obs_log.event(log, "batcher_crash", level=logging.CRITICAL,
                      thread=who, error=str(e)[:200])
        # fail what nobody will ever process: the submit queue always (the
        # dispatch worker is the only consumer and the batcher is now
        # dead to new work), the dispatched hand-off queue only when the
        # FINISHER died — on a worker crash the live finisher still
        # completes batches already dispatched
        self._drain_fail(BatcherCrashed(self._crash_reason),
                         include_dispatched=(who == "finisher"))
        cb = self._on_crashed
        if cb is not None:
            try:
                cb(who, e)
            except Exception:  # noqa: BLE001
                log.exception("on_crashed callback failed")

    def _drain_fail(self, exc: Exception,
                    include_dispatched: bool = True) -> None:
        """Fail everything queued anywhere in the batcher: the submit
        queue, and (unless the finisher is still alive to complete them)
        the dispatched-but-unfinished hand-off queue."""
        while True:
            try:
                entry = self._q.get_nowait()
            except queue.Empty:
                break
            self._resolve_exc(entry[1], exc)
        if not include_dispatched:
            return
        while True:
            try:
                batch, _finish = self._finish_q.get_nowait()
            except queue.Empty:
                break
            self._fail_batch(batch, exc)
            if self._obs:
                G_INFLIGHT.dec()

    # -- poison-batch quarantine -------------------------------------------

    def _contain_failure(self, batch, exc: Exception) -> None:
        """A dispatched batch failed.  One malformed trace must not fail
        its up-to-63 co-batched neighbours: bisect-retry synchronously to
        isolate the poison (≤ ~2·B extra dispatches, and only on the
        already-rare failure path), fail ONLY the offender(s), and resolve
        everyone else with their real results."""
        if (self.wedged or self._crashed
                or isinstance(exc, (DeviceWedged, BatcherCrashed))):
            self._fail_batch(batch, exc)
            return
        if len(batch) == 1:
            self._fail_poison(batch[0], exc)
            return
        obs_log.event(log, "poison_bisect", level=logging.WARNING,
                      batch_size=len(batch), error=str(exc)[:200])
        budget = [2 * len(batch) + 4]
        self._bisect(batch, exc, budget)

    def _bisect(self, batch, exc: Exception, budget) -> None:
        if len(batch) == 1:
            self._fail_poison(batch[0], exc)
            return
        if budget[0] <= 0:
            # systemic failure (every retry fails): stop paying for
            # retries and fail the remainder with the underlying error
            self._fail_batch(batch, exc)
            return
        mid = len(batch) // 2
        for half in (batch[:mid], batch[mid:]):
            budget[0] -= 1
            try:
                with self._watched(half):
                    results = self.matcher.match_many([e[0] for e in half])
            except Exception as e2:  # noqa: BLE001 - recurse to isolate
                self._bisect(half, e2, budget)
            else:
                for entry, r in zip(half, results):
                    self._resolve_result(entry[1], r)

    def _fail_poison(self, entry, exc: Exception) -> None:
        trace, f, _te, sp, _dl = entry
        uuid = str(trace.get("uuid") or "") if isinstance(trace, dict) else ""
        C_POISON.inc()
        if uuid:
            self._record_offender(uuid)
        if sp is not None:
            sp.meta["poison"] = True
        # flight-recorded via the handler's error path; this event makes
        # the isolation visible server-side with the offending trace_id
        obs_log.event(log, "poison_trace", level=logging.ERROR,
                      uuid=uuid[:64],
                      trace_id=sp.trace_id if sp else None,
                      error=str(exc)[:200])
        self._resolve_exc(f, PoisonTrace(
            "trace %r failed its device batch alone (co-batched requests "
            "succeeded): %s" % (uuid, exc)))

    def _record_offender(self, uuid: str) -> None:
        with self._offender_lock:
            n = self._offenders.get(uuid, 0) + 1
            self._offenders[uuid] = n
            if n >= self.quarantine_after:
                self._quarantine[uuid] = (
                    _time.monotonic() + self.quarantine_ttl_s)
                obs_log.event(log, "uuid_quarantined", level=logging.WARNING,
                              uuid=uuid[:64], offences=n,
                              ttl_s=self.quarantine_ttl_s)

    def _is_quarantined(self, uuid: str) -> bool:
        with self._offender_lock:
            exp = self._quarantine.get(uuid)
            if exp is None:
                return False
            if _time.monotonic() > exp:
                del self._quarantine[uuid]
                self._offenders.pop(uuid, None)
                return False
            return True


class ReporterService:
    """Owns the matcher + batcher and implements the request semantics."""

    def __init__(
        self,
        matcher: Optional[SegmentMatcher],
        threshold_sec: Optional[int] = None,
        max_batch: int = 64,
        max_wait_ms: float = 10.0,
        max_inflight: Optional[int] = None,
        robustness: Optional[dict] = None,
        slo: Optional[dict] = None,
        quality: Optional[dict] = None,
        session_max_batch: int = 256,
        session_wait_ms: float = 2.0,
        economics: Optional[dict] = None,
    ):
        """``matcher=None`` defers the engine: the HTTP socket can bind and
        /health can answer before the accelerator backend is even
        initialised (a wedged PJRT init was observed to leave the old
        bind-after-init boot dark indefinitely, 2026-07-31).  /report and
        /trace_attributes_batch return 503 until ``attach_matcher`` runs,
        which the reference's client treats as a retryable failure
        (HttpClient.java:80-88: 3 retries on its 10 s budget).

        ``robustness`` (config key of the same name, docs/robustness.md)
        passes the fault-domain knobs through to the MicroBatcher
        (max_queue / deadline_ms / watchdog_s / quarantine_after /
        quarantine_ttl_s) plus the service-level ``reattach_probe_s``;
        every knob also has a REPORTER_* env override.

        ``slo`` (config key "slo", docs/observability.md "The SLO
        engine") declares the serving objectives — availability,
        per-route latency quantiles, degraded-mode fraction — the engine
        measures every terminal outcome against (GET /debug/slo, the
        /statusz burn-rate line, reporter_slo_* families).  None keeps
        the env-tuned defaults (REPORTER_SLO_*) without touching an
        engine another embedder already configured in-process."""
        self._batch_params = dict(max_batch=max_batch, max_wait_ms=max_wait_ms,
                                  max_inflight=max_inflight)
        # streaming session submits batch on their OWN MicroBatcher with a
        # much shorter fill window: the whole point of a session is point
        # latency, so the batcher only aggregates steps that are already
        # concurrently in flight (REPORTER_SESSION_WAIT_MS overrides)
        self._session_params = dict(
            max_batch=max(1, int(_resolve_num(
                "REPORTER_SESSION_MAX_BATCH", session_max_batch, 256))),
            max_wait_ms=_resolve_num(
                "REPORTER_SESSION_WAIT_MS", session_wait_ms, 2.0))
        rb = dict(robustness or {})
        self._reattach_probe_s = _resolve_num(
            "REPORTER_REATTACH_PROBE_S", rb.pop("reattach_probe_s", None),
            15.0)
        # preemption-tolerant sessions (docs/serving-fleet.md
        # "Self-driving fleet"): dirty session wire-state checkpointed to
        # atomic per-uuid files so a SIGKILL'd replica's beams re-home
        # from disk.  Off unless a cadence AND a directory are set (the
        # fleet supervisor sets both for its children).
        self._ckpt_s = _resolve_num(
            "REPORTER_SESSION_CHECKPOINT_S",
            rb.pop("session_checkpoint_s", None), 0.0)
        sync_raw = os.environ.get("REPORTER_SESSION_CHECKPOINT_SYNC", "")
        self._ckpt_sync = (sync_raw.strip().lower()
                           not in ("", "0", "off", "false", "no")
                           if sync_raw.strip()
                           else bool(rb.pop("session_checkpoint_sync",
                                            False)))
        self._ckpt_dir = (
            os.environ.get("REPORTER_SESSION_CHECKPOINT_DIR", "").strip()
            or rb.pop("session_checkpoint_dir", None))
        self.session_checkpointer: Optional[SessionCheckpointer] = None
        self._robust_params = {
            k: rb[k] for k in ("max_queue", "deadline_ms", "watchdog_s",
                               "quarantine_after", "quarantine_ttl_s")
            if k in rb
        }
        if slo is not None:
            obs_slo.configure(slo)
        # match-quality plane (docs/match-quality.md): the shadow-oracle
        # sampling engine builds at attach time (it needs the matcher's
        # arrays + confidence-aux programs); the config "quality" block /
        # REPORTER_QUALITY_* env knobs tune it, sample_every 0 = off
        self._quality_spec = dict(quality or {})
        self.quality: "Optional[obs_quality.QualityEngine]" = None
        self._margin_keep = _resolve_num(
            "REPORTER_QUALITY_MARGIN_KEEP",
            self._quality_spec.get("margin_keep"), 1.0)
        self._threshold_arg = threshold_sec
        self.matcher = None
        self.batcher = None
        # the per-vehicle session plane (docs/performance.md "The session
        # matcher"): the store and engine build at attach time; streaming
        # /report submits ("stream": true) run through session_batcher,
        # whose MicroBatcher machinery gives them the same fault domains
        # as windowed traffic (docs/robustness.md)
        self.session_store: Optional[SessionStore] = None
        self.session_engine: Optional[SessionEngine] = None
        self.session_batcher: Optional[MicroBatcher] = None
        self.threshold_sec = None
        # degraded mode: after a device watchdog trip the engine is
        # detached and requests are answered by the CPU oracle with
        # "degraded": true until a probe re-attaches the accelerator
        self.degraded = False
        self._degraded_lock = threading.Lock()
        self._cpu_matcher = None
        self._cpu_lock = threading.Lock()
        self.unhealthy_reason: Optional[str] = None
        # stable replica identity resolved BEFORE attach (the session
        # checkpointer's directory is keyed on it); echoed as
        # X-Reporter-Replica on every response
        self.replica_id = (
            os.environ.get("REPORTER_REPLICA_ID", "").strip()
            or "%s-%d" % (_socket.gethostname()[:32], os.getpid()))
        # binary columnar wire (serve/wire.py, docs/http-api.md): accepted
        # and emitted when the client negotiates it (Content-Type /
        # Accept); $REPORTER_WIRE=0 is the emergency off switch — the
        # service then answers binary-speaking clients in JSON and 400s
        # binary bodies, and /health stops advertising the capability
        self.wire_enabled = (os.environ.get("REPORTER_WIRE", "")
                             .strip().lower()
                             not in ("0", "false", "off", "no"))
        # fleet economics (docs/economics.md): the chip-second cost
        # ledger, on-disk demand history (REPORTER_HISTORY_DIR, or the
        # config "economics" block's history_dir), and the measured
        # capacity-headroom estimator — the sensor plane behind
        # GET /debug/cost and /debug/history
        econ_spec = dict(economics or {})
        hist_dir = (os.environ.get("REPORTER_HISTORY_DIR", "").strip()
                    or econ_spec.get("history_dir"))
        self.economics = obs_econ.EconomicsEngine(
            self.replica_id, chips=1, spec=econ_spec,
            history_path=(os.path.join(hist_dir,
                                       "%s.jsonl" % self.replica_id)
                          if hist_dir else None))
        # the tick thread and scrape-time collectors arm in make_server()
        # — a service object that never serves must not leak either
        if matcher is not None:
            self.attach_matcher(matcher)
        self._t_boot = _time.time()
        self._counter_lock = threading.Lock()
        self._n_requests = 0
        self._n_errors = 0
        # graceful-shutdown drain (docs/serving-fleet.md): once True, new
        # /report and /trace_attributes_batch requests are refused 503
        # {"status": "draining"} (+Retry-After — the router re-dispatches
        # them), /health answers 503 "draining" so the router rotates
        # traffic off, inflight requests run to completion, and every
        # handler closes its connection after its in-flight request so
        # server_close's join of non-daemon handler threads stays bounded
        # even for clients actively streaming keep-alive requests
        self.draining = False
        # inflight /report + /trace_attributes_batch handler count: the
        # drain loop in serve/__main__ waits on this before shutting the
        # listener down, which is what "finish inflight batches" means
        self._active_lock = threading.Lock()
        self._n_active = 0

    def begin_drain(self) -> None:
        """Enter graceful drain (idempotent): refuse new matching work,
        flip /health to 503 "draining", keep finishing inflight requests.
        serve/__main__ calls this on the first SIGTERM."""
        if self.draining:
            return
        self.draining = True
        G_DRAINING.set(1)
        self.economics.ledger.set_draining(True)
        obs_log.event(log, "drain_begin", level=logging.WARNING,
                      replica=self.replica_id)

    @contextlib.contextmanager
    def _track_active(self):
        with self._active_lock:
            self._n_active += 1
        # the cost ledger's serving/idle attribution seam: chip-seconds
        # bill as "serving" while any matching handler is inflight
        self.economics.ledger.note_active(True)
        try:
            yield
        finally:
            self.economics.ledger.note_active(False)
            with self._active_lock:
                self._n_active -= 1

    def idle(self) -> bool:
        """No /report or /trace_attributes_batch handler is inflight (the
        drain loop's exit condition; admission is already closed)."""
        with self._active_lock:
            return self._n_active == 0

    def attach_matcher(self, matcher: SegmentMatcher) -> None:
        """Bring a deferred service live: resolve the report threshold and
        start the MicroBatcher.  ``batcher`` is assigned last — handlers
        read it once, so a request races either to 503 or to a fully
        wired engine, never halfway."""
        threshold = self._threshold_arg
        if threshold is None:
            threshold = int(os.environ.get("THRESHOLD_SEC", matcher.cfg.threshold_sec))
        self.threshold_sec = int(threshold)
        self.matcher = matcher
        # chip-second accrual scales with the replica's LOCAL mesh size:
        # prefer the matcher's resolved device count (dp x gp) over the
        # configured one so a mesh-inside-replica bills every chip it spans
        try:
            chips = int((matcher.capacity_summary() or {}).get("devices", 1))
        except Exception:  # noqa: BLE001 - cpu/legacy matchers lack the summary
            chips = int(getattr(matcher.cfg, "devices", 1))
        self.economics.ledger.set_chips(max(1, chips))
        self.batcher = self._make_batcher(matcher)
        # session plane: the store survives matcher/batcher swaps (carries
        # live pinned-host), so a degraded window or re-attach never drops
        # an open session
        if self.session_store is None:
            self.session_store = SessionStore(
                max_sessions=int(getattr(matcher.cfg, "max_sessions", 65536)),
                ttl_s=float(getattr(matcher.cfg, "session_ttl_s", 3600.0)))
            if self._ckpt_s > 0 and self._ckpt_dir:
                # per-replica subdirectory: one shared fleet workdir, one
                # owned directory per replica id (the supervisor re-homes
                # exactly the dead replica's files)
                self.session_checkpointer = SessionCheckpointer(
                    self.session_store,
                    os.path.join(self._ckpt_dir, self.replica_id),
                    cadence_s=self._ckpt_s, sync=self._ckpt_sync)
                self.session_checkpointer.start()
        self.session_engine = SessionEngine(
            matcher, self.session_store,
            tail_points=int(getattr(matcher.cfg, "session_tail_points", 64)))
        self.session_batcher = self._make_session_batcher()
        try:
            self.quality = obs_quality.configure(matcher, self._quality_spec)
        except Exception:  # noqa: BLE001 - diagnostics must not block boot
            log.exception("quality engine configure failed; sampling off")
            self.quality = None

    def _make_batcher(self, matcher: SegmentMatcher) -> MicroBatcher:
        return MicroBatcher(
            matcher, **self._batch_params, **self._robust_params,
            on_wedged=self._enter_degraded, on_crashed=self._note_crash)

    def _make_session_batcher(self) -> MicroBatcher:
        """The streaming twin: same fault-domain machinery (bounded queue
        + shedding, deadlines, watchdog, poison bisect quarantine, crash-
        loud loops) over the SessionEngine instead of the raw matcher."""
        return MicroBatcher(
            self.session_engine, **self._session_params,
            **self._robust_params, name="session",
            on_wedged=self._enter_degraded, on_crashed=self._note_crash)

    # -- fault domains: degraded mode + re-attach --------------------------

    def _note_crash(self, who: str, e: BaseException) -> None:
        """MicroBatcher loop-thread crash: flip /health unhealthy so the
        orchestrator restarts this replica (a crashed batcher is a bug,
        not a device fault — no CPU fallback, fail loud)."""
        self.unhealthy_reason = "batcher %s thread died: %s" % (who, e)
        if self.session_engine is not None:
            # in-flight session steps had their futures failed: their late
            # finishes must not commit (zero-duplication contract)
            self.session_engine.invalidate_inflight()

    def _enter_degraded(self, reason: str) -> None:
        """Device watchdog trip: detach the engine, serve from the CPU
        oracle (responses carry ``degraded: true``), and probe for
        re-attach in the background."""
        with self._degraded_lock:
            if self.degraded:
                return
            self.degraded = True
        if self.session_engine is not None:
            # a wedged device step may WAKE long after its futures were
            # failed: bump the engine generation FIRST so the late finish
            # commits nothing — the degraded path re-applies the points
            self.session_engine.invalidate_inflight()
        G_DEGRADED.set(1)
        self.economics.ledger.set_degraded(True)
        obs_log.event(log, "degraded_enter", level=logging.ERROR,
                      reason=reason)
        if self._reattach_probe_s > 0:
            threading.Thread(target=self._probe_loop, daemon=True,
                             name="reattach-probe").start()

    def _cpu_fallback(self) -> SegmentMatcher:
        """The degraded-mode engine: the numpy oracle over the SAME graph
        arrays + UBODT (no rebuild, no device).  Built lazily on first
        degraded request; serialised by _cpu_lock (the matcher's staging
        reuse assumes single-threaded dispatch)."""
        m = self.matcher
        if m is None or not getattr(m.cfg, "cpu_fallback", True):
            raise DeviceWedged("device wedged and cpu_fallback disabled")
        with self._cpu_lock:
            if self._cpu_matcher is None:
                self._cpu_matcher = SegmentMatcher(
                    arrays=m.arrays, ubodt=m.ubodt, config=m.cfg,
                    backend="cpu")
                # degraded answers keep the quality plane fed: per-point
                # edges still attach (margins stay None — the cpu oracle
                # computes no runner-up scores)
                self._cpu_matcher._quality_aux = m._quality_aux
            return self._cpu_matcher

    def _probe_loop(self) -> None:
        """Periodically probe the wedged engine with a dummy dispatch
        through the real match path; on a healthy answer within the
        watchdog bound, swap in a fresh MicroBatcher and leave degraded
        mode (the probe itself re-warms the dispatch path)."""
        wd = self.batcher.watchdog_s if self.batcher is not None else 120.0
        timeout = max(1.0, wd if wd > 0 else 120.0)
        while self.degraded and not self.draining:
            _time.sleep(self._reattach_probe_s)
            if not self.degraded or self.draining:
                return
            if self._probe_device(timeout):
                self._reattach()
                return

    def _probe_device(self, timeout_s: float) -> bool:
        m = self.matcher
        if m is None:
            return False
        ok: list = []
        done = threading.Event()

        def _try():
            try:
                m.match_many(m.dummy_traces(4, 1))
                ok.append(True)
            except Exception as e:  # noqa: BLE001 - probe failure = stay degraded
                log.info("re-attach probe failed: %s", e)
            finally:
                done.set()

        # the probe may hang exactly like the wedged step did: run it on a
        # disposable daemon thread and give up at the watchdog bound (one
        # leaked parked thread per failed probe, bounded by probe spacing)
        threading.Thread(target=_try, daemon=True,
                         name="reattach-probe-dispatch").start()
        done.wait(timeout=timeout_s)
        return bool(ok)

    def _reattach(self) -> None:
        self.batcher = self._make_batcher(self.matcher)
        if self.session_engine is not None:
            # fresh batcher over the SAME engine/store: open sessions kept
            # their replay buffers through the degraded window and rebuild
            # their beams on the next healthy step
            self.session_batcher = self._make_session_batcher()
        with self._degraded_lock:
            self.degraded = False
        G_DEGRADED.set(0)
        self.economics.ledger.set_degraded(False)
        C_REATTACH.inc()
        obs_log.event(log, "engine_reattach", level=logging.WARNING,
                      backend=self.matcher.backend)

    # -- request handling --------------------------------------------------

    @staticmethod
    def _terminal(route: str, code: int, span: Span,
                  degraded: bool = False) -> None:
        """EVERY terminal request outcome flows through here: the SLO
        engine classifies it against-budget or excluded per the
        documented policy (obs/slo.py), and any violated objective names
        mark the span BEFORE it is offered to the flight recorder — so
        an SLO-violating trace_id is retained like an error, even on a
        200 that merely blew the latency objective."""
        if "total_s" not in span.timings:
            span.finish()
        violated = obs_slo.observe(
            route, code, span.timings.get("total_s"),
            degraded=degraded, trace_id=span.trace_id)
        if violated:
            span.meta["slo_violation"] = violated
        obs_flight.record(span)

    def _note_quality(self, trace, match, span: Span) -> Optional[dict]:
        """Pop the matcher's ``"_quality"`` block off a match dict (it must
        never reach the wire renderer — report_fn embeds the match dict as
        ``segment_matcher``), feed the confidence metrics, mark low-margin
        spans for flight retention, and offer the request to the
        shadow-oracle sampler (docs/match-quality.md).  Cheap: dict pops,
        two metric updates, one non-blocking enqueue at most."""
        if isinstance(trace, dict):
            # transport state from the binary wire decode (numpy arrays)
            # — already consumed by the packer, must never reach a
            # serializer
            trace.pop("_columns", None)
        if not isinstance(match, dict):
            return None
        q = match.pop("_quality", None)
        if not isinstance(q, dict):
            return None
        mm = q.get("margin_mean")
        if mm is not None:
            obs_quality.H_MARGIN.observe(mm, exemplar=span.trace_id)
            # the keep signal compares the MEAN margin: min is routinely 0
            # on two-way streets (both directions of one edge tie exactly)
            # while a low mean means the whole decode was ambiguous
            if mm < self._margin_keep:
                obs_quality.C_LOW_MARGIN.inc()
                span.meta["low_margin"] = round(float(mm), 4)
        if self.quality is not None:
            self.quality.maybe_sample(trace, q)
        return q

    def validate(self, trace: dict) -> Tuple[Optional[str], Optional[set], Optional[set]]:
        """Returns (error, report_levels, transition_levels).  A streaming
        submit (``"stream": true``) may carry a SINGLE point — the session
        provides the rest of the shape; windowed requests keep the
        reference's >= 2-point contract."""
        if trace.get("uuid") is None:
            return "uuid is required", None, None
        try:
            trace["trace"][0 if trace.get("stream") else 1]
        except Exception:
            return (
                "trace must be a non zero length array of object each of which must "
                "have at least lat, lon and time"
            ), None, None
        try:
            rl = set(trace["match_options"]["report_levels"])
        except Exception:
            return "match_options must include report_levels array", None, None
        try:
            tl = set(trace["match_options"]["transition_levels"])
        except Exception:
            return "match_options must include transition_levels array", None, None
        # per-request HMM parameter overrides (reference wire contract,
        # docs/http-api.md): values are validated HERE so a bad one is a
        # clean 400 instead of failing (and poison-quarantining) a whole
        # device batch; the matcher applies the effective values with no
        # recompile and ?debug=1 echoes them
        mo = trace["match_options"]
        if isinstance(mo, dict):
            for key in ("sigma_z", "beta", "search_radius", "gps_accuracy"):
                if key not in mo:
                    continue
                try:
                    v = float(mo[key])
                except (TypeError, ValueError):
                    v = float("nan")
                if not (v > 0 and v == v and v != float("inf")):
                    return ("match_options.%s must be a positive finite "
                            "number" % key), None, None
            sm = mo.get("shape_match")
            if sm is not None and sm != "map_snap":
                return ("match_options.shape_match %r is not supported "
                        "(this matcher map-snaps; use \"map_snap\" or omit "
                        "the key)" % (sm,)), None, None
            # route-consistent interpolation opt-in/out (docs/http-api.md:
            # speed-weighted boundary times over the full UBODT path
            # segment sequence, matching/sparse.py); booleans only so a
            # typo'd string cannot silently pick a default
            ip = mo.get("interpolate")
            if ip is not None and not isinstance(ip, bool):
                return ("match_options.interpolate must be a boolean"
                        ), None, None
        return None, rl, tl

    def handle_report(self, trace: dict, debug: bool = False,
                      deadline: Optional[float] = None) -> Tuple[int, dict]:
        # always-on tracing: the HTTP handler binds a Span carrying the
        # (accepted or generated) trace_id before calling in; embedders
        # that call handle_report(trace) directly get a self-made trace.
        # ?debug=1 only opts the breakdown onto the response — every
        # outcome is offered to the flight recorder regardless.
        # ``deadline`` is the absolute monotonic bound parsed from
        # X-Reporter-Deadline-Ms at ingestion (None -> server default).
        # streaming session submits ("stream": true) are the SAME wire
        # endpoint but their own route: they batch on the session
        # MicroBatcher (point latency, not window fill) and their terminal
        # outcomes classify under "report_stream" so the per-point-latency
        # SLO objective can gate them separately (docs/http-api.md)
        stream = isinstance(trace, dict) and bool(trace.get("stream"))
        route = "report_stream" if stream else "report"
        span = obs_trace.current_span() or Span(route)
        span.meta.setdefault("endpoint", route)
        if isinstance(trace, dict) and trace.get("uuid") is not None:
            span.meta.setdefault("uuid", str(trace["uuid"])[:64])
        if self.draining:
            C_DRAIN_REFUSED.inc()
            span.fail("draining", status="draining")
            self._terminal(route, 503, span)
            return 503, {"error": "draining", "status": "draining",
                         "retry_after": 1}
        batcher = self.session_batcher if stream else self.batcher
        if batcher is None:
            span.fail("service initialising", status="unavailable")
            self._terminal(route, 503, span)
            return 503, {"error": "service initialising", "retry_after": 1}
        # chaos seam: an injected admission shed — the canonical
        # failover-MASKED failure (the replica burns its own SLO budget
        # on the 429 while the router re-dispatches and the client sees
        # 200; the fleet masking-debt gauge must bill the difference)
        if faults.fire("replica_shed") is not None:
            span.fail("injected admission shed", status="shed")
            self._terminal(route, 429, span)
            C_REQUESTS.labels(route, "shed").inc()
            return 429, {"error": "injected admission shed",
                         "retry_after": 1}
        err, rl, tl = self.validate(trace)
        if err:
            C_REQUESTS.labels(route, "invalid").inc()
            span.fail(err, status="invalid")
            self._terminal(route, 400, span)
            return 400, {"error": err}
        if self.degraded:
            return self._finish_report(trace, rl, tl, span, debug,
                                       degraded=True, route=route)
        try:
            # deadline is forwarded only when the request set one (stub and
            # embedder batchers keep their two-arg match contract); the
            # server default is applied inside submit() either way
            mkw = {} if deadline is None else {"deadline": deadline}
            with obs_trace.bind(span):
                match = batcher.match(trace, span=span, **mkw)
        except Overloaded as e:
            span.fail(e, status="shed")
            self._terminal(route, 429, span)
            C_REQUESTS.labels(route, "shed").inc()
            return 429, {"error": str(e),
                         "retry_after": batcher.retry_after_s()}
        except DeadlineExpired as e:
            span.fail(e, status="expired")
            self._terminal(route, 504, span)
            C_REQUESTS.labels(route, "expired").inc()
            return 504, {"error": str(e)}
        except TraceQuarantined as e:
            span.fail(e, status="quarantined")
            self._terminal(route, 422, span)
            C_REQUESTS.labels(route, "quarantined").inc()
            return 422, {"error": str(e)}
        except (DeviceWedged, BatcherCrashed) as e:
            if self.degraded:
                # raced the watchdog trip: answer from the CPU fallback
                return self._finish_report(trace, rl, tl, span, debug,
                                           degraded=True, route=route)
            span.fail(e, status="unavailable")
            self._terminal(route, 503, span)
            self._count(ok=False)
            C_REQUESTS.labels(route, "error").inc()
            return 503, {"error": str(e), "retry_after": 1}
        except Exception as e:
            log.exception("match failed")
            span.fail(e)
            self._terminal(route, 500, span)
            self._count(ok=False)
            C_REQUESTS.labels(route, "error").inc()
            return 500, {"error": str(e)}
        return self._finish_report(trace, rl, tl, span, debug, match=match,
                                   route=route)

    def _finish_report(self, trace, rl, tl, span, debug,
                       match: Optional[dict] = None,
                       degraded: bool = False,
                       route: str = "report") -> Tuple[int, dict]:
        """Render the report (matching first via the CPU fallback on the
        degraded path); degraded answers carry ``"degraded": true``.  A
        streaming answer (route "report_stream") renders over the
        session's accumulated window — the rolling tail + the new points
        — exactly the incremental shape the reference's threshold/
        shape_used contract expects, and carries a ``"session"`` block."""
        stream = route == "report_stream"
        try:
            with obs_trace.bind(span):
                if degraded:
                    m = self._cpu_fallback()
                    t_m = _time.monotonic()
                    with self._cpu_lock:
                        if stream:
                            # sessions SURVIVE the degraded window: the cpu
                            # oracle answers over replay + new points and
                            # the beam rebuilds on the next healthy step
                            match = self.session_engine.degraded_step(
                                m, trace)
                        else:
                            match = m.match_many([trace])[0]
                    span.mark("cpu_fallback_s", _time.monotonic() - t_m)
                st = match.pop("_stream", None) if isinstance(match, dict) \
                    else None
                render_trace = trace
                if st is not None:
                    # the answer window: session tail + this step's points
                    render_trace = {
                        "uuid": trace.get("uuid"), "trace": st["trace"],
                        "match_options": trace.get("match_options") or {}}
                quality = self._note_quality(render_trace, match, span)
                t_rep = _time.monotonic()
                with obs_attrib.host_stage("report", span):
                    data = report_fn(match, render_trace, self.threshold_sec,
                                     rl, tl,
                                     mode=(trace.get("match_options") or {})
                                     .get("mode", "auto"))
            span.mark("report_fn_s", _time.monotonic() - t_rep)
            span.finish()
            if st is not None:
                data["session"] = st["session"]
            if degraded:
                data["degraded"] = True
                span.meta["degraded"] = True
                C_DEGRADED_REQ.inc()
            if debug:
                data["debug"] = span.breakdown()
                if quality is not None:
                    data["debug"]["quality"] = {
                        k: v for k, v in quality.items() if k != "edge"}
                m_ = self.matcher
                if m_ is not None:
                    # effective HMM parameters this request actually ran
                    # with (per-request match_options applied + clamped)
                    data["debug"]["match_options"] = (
                        m_.effective_match_options(
                            trace.get("match_options") or {}))
            self._terminal(route, 200, span, degraded=degraded)
            self._count(ok=True)
            C_REQUESTS.labels(
                route, "degraded" if degraded else "ok").inc()
            return 200, data
        except Exception as e:
            log.exception("match failed")
            span.fail(e)
            code = 503 if isinstance(e, (DeviceWedged, BatcherCrashed)) else 500
            self._terminal(route, code, span)
            self._count(ok=False)
            C_REQUESTS.labels(route, "error").inc()
            out = {"error": str(e)}
            if code == 503:
                out["retry_after"] = 1
            return code, out

    def _count(self, ok: bool) -> None:
        with self._counter_lock:
            self._n_requests += 1
            if not ok:
                self._n_errors += 1

    def _econ_sample(self) -> dict:
        """The economics tick's signal read (obs/economics.py): cheap
        live-registry/state reads only — the engine differences the
        cumulative counters itself.  Admitted = terminal ok+degraded,
        shed = terminal 429s; the device-step histogram feeds the
        capacity ceiling's windowed p95."""
        b = self.batcher
        step = None
        try:
            samp = M_DEVICE_STEP._default()._sample()
            if samp["count"] or b is not None:
                step = (samp["buckets"], samp["counts"])
        except Exception:  # noqa: BLE001 - a sensor read must never raise
            pass
        burn = None
        max_burn = None
        try:
            objectives = obs_slo.engine().summary()["objectives"]
            burn = {}
            for name, st in objectives.items():
                rates = [float(v) for v in (st.get("burn") or {}).values()
                         if isinstance(v, (int, float))]
                burn[name] = round(max(rates), 4) if rates else None
            rates = [v for v in burn.values() if v is not None]
            max_burn = max(rates) if rates else None
        except Exception:  # noqa: BLE001
            pass
        return {
            "queue_depth": b._q.qsize() if b is not None else 0,
            "admitted_total": obs_econ.counter_total(
                C_REQUESTS, {"outcome": ("ok", "degraded")}),
            "shed_total": obs_econ.counter_total(
                C_REQUESTS, {"outcome": "shed"}),
            "points_total": C_POINTS_MATCHED.value,
            "device_step": step,
            "max_batch": float(b.max_batch) if b is not None else None,
            "burn": burn,
            "max_burn": max_burn,
            "sessions": (self.session_store.summary()["sessions"]
                         if self.session_store is not None else None),
            "session_tiers": self._session_tiers(),
        }

    def _session_tiers(self) -> Optional[dict]:
        """Per-tier resident-session counts for the economics tick: hot/
        cold straight from the arena's slot maps, host = everything the
        store carries that is not device-resident (wire-form carries,
        arena-off deployments).  None when no session plane exists."""
        if self.session_store is None:
            return None
        total = self.session_store.summary()["sessions"]
        arena = (getattr(self.matcher, "session_arena", None)
                 if self.matcher is not None else None)
        if arena is None:
            return {"hot": 0, "cold": 0, "host": total}
        t = arena.tier_counts()
        return {"hot": t["hot"], "cold": t["cold"],
                "host": max(0, total - t["hot"] - t["cold"])}

    def handle_cost(self, query: dict) -> Tuple[int, dict]:
        """GET /debug/cost — the replica's cost ledger: chip-seconds by
        lifecycle state, accrued dollars, $-per-million-matched-points,
        the measured capacity block, and the demand-history ring's
        location/size (docs/economics.md)."""
        return 200, self.economics.cost_report()

    def handle_history(self, query: dict) -> Tuple[int, dict]:
        """GET /debug/history[?window=S] — the on-disk demand-history
        ring's records (oldest first), optionally clipped to the last
        ``window`` seconds.  404-free: history disabled just returns an
        empty series with an explanation."""
        window = None
        raw = query.get("window", [None])[0]
        if raw is not None:
            try:
                window = max(1.0, float(raw))
            except (TypeError, ValueError):
                return 400, {"error": "window must be a number (seconds)"}
        return 200, self.economics.history_report(window_s=window)

    def handle_health(self) -> Tuple[int, dict]:
        """Liveness/ops snapshot (additive: the reference exposes no such
        endpoint, so nothing on the wire contract changes).  A crashed
        batcher thread flips the status to "unhealthy" with a 503 so an
        orchestrator probe restarts the replica; degraded (CPU fallback)
        mode stays 200 "ok" — the service IS answering, just slower."""
        m = self.matcher
        b = self.batcher
        if self.unhealthy_reason or (b is not None and b._crashed):
            return 503, {
                "status": "unhealthy",
                "reason": self.unhealthy_reason
                or (b._crash_reason if b is not None else None),
                "replica": self.replica_id,
                "uptime_s": round(_time.time() - self._t_boot, 1),
            }
        # chaos seam: a flapping health probe (docs/serving-fleet.md) —
        # the router's streak thresholds must debounce it
        if faults.fire("health_flap") is not None:
            return 503, {
                "status": "unhealthy",
                "reason": "injected health flap",
                "replica": self.replica_id,
                "uptime_s": round(_time.time() - self._t_boot, 1),
            }
        if self.draining:
            # SAME code as unhealthy (a generic orchestrator needs only
            # the 503), DIFFERENT status: the router treats draining as
            # "rotate traffic off, the exit is deliberate" — no passive
            # ejection, no restart
            with self._active_lock:
                inflight = self._n_active
            return 503, {
                "status": "draining",
                "replica": self.replica_id,
                "inflight": inflight,
                "uptime_s": round(_time.time() - self._t_boot, 1),
            }
        return 200, {
            "status": "ok",
            "replica": self.replica_id,
            # wire-level opt-ins a client/router may negotiate
            # (docs/http-api.md "Wire formats"): gzip request bodies are
            # always accepted; the binary columnar wire drops out when
            # $REPORTER_WIRE=0
            "capabilities": (["gzip", "wire-columnar"]
                             if self.wire_enabled else ["gzip"]),
            "degraded": bool(self.degraded),
            # True while boot-time work is still in flight: backend init +
            # engine build (matcher fields below are null until attached)
            # and the background shape warmup.  The service answers either
            # way (requests racing the warmup just compile inline), so
            # warming is informational, not a failure state
            "warming": bool(getattr(self, "warming", False)) or m is None,
            "backend": m.backend if m else None,
            "viterbi_kernel": getattr(m, "_kernel_mode", None) if m else None,
            "devices": int(getattr(m.cfg, "devices", 1)) if m else None,
            "graph_devices": int(getattr(m.cfg, "graph_devices", 1)) if m else None,
            # the capacity plane (docs/http-api.md, docs/performance.md
            # "One logical matcher per pod"): in-replica mesh topology,
            # admission caps and device-state byte budgets, all scaled by
            # the local device count.  The router's capacity-aware ranking
            # term and the autoscaler's headroom model consume this —
            # a pod-sized replica advertises pod-sized capacity.
            "capacity": (m.capacity_summary()
                         if m is not None and
                         hasattr(m, "capacity_summary") else None),
            "edges": int(m.arrays.num_edges) if m else None,
            "ubodt_rows": int(m.ubodt.num_rows) if m else None,
            # fleet shard assignment + hot/cold tiering (docs/serving-
            # fleet.md "Sharded tables"): the router learns each
            # replica's shard from this probe payload, which is what the
            # flag-gated geo-aware ranking term steers by
            "ubodt_shard": ("%d/%d" % m.ubodt_shard
                            if m and getattr(m, "ubodt_shard", None)
                            else None),
            "ubodt_tiered": bool(getattr(m, "tiering", None)) if m else None,
            "uptime_s": round(_time.time() - self._t_boot, 1),
            "requests": self._n_requests,
            "errors": self._n_errors,
        }

    def handle_sessions(self, query: dict,
                        body: Optional[dict] = None) -> Tuple[int, dict]:
        """The session-store ops surface (docs/http-api.md, docs/
        serving-fleet.md "Beam handoff"):

          GET  /sessions              store summary (count, points)
          GET  /sessions?uuid=U       one session's meta (404 if absent)
          GET  /sessions?export=1     summary + every live session's wire
                                      snapshot — the drain-time handoff
                                      payload the router pulls
          POST /sessions {"sessions": [...]}
                                      import handed-off sessions; a uuid
                                      already live locally wins over the
                                      import (a racing re-dispatch has
                                      newer points), beam-less payloads
                                      rebuild from replay on their next
                                      step
        """
        store = self.session_store
        if store is None:
            return 503, {"error": "service initialising", "retry_after": 1}
        if body is not None:
            drop = body.get("drop")
            if drop is not None:
                if not isinstance(drop, list):
                    return 400, {"error": "drop must be an array of uuids"}
                dropped = sum(1 for u in drop if store.drop(str(u)))
                return 200, {"dropped": dropped,
                             "replica": self.replica_id}
            pop = body.get("pop")
            if pop is not None:
                # atomic remove-and-serialise: the recovery rebalance's
                # exact transfer (export + delete in one locked sweep)
                if not isinstance(pop, list):
                    return 400, {"error": "pop must be an array of uuids"}
                wires = store.pop_wire(pop)
                return 200, {"sessions": wires,
                             "replica": self.replica_id}
            wires = body.get("sessions")
            if not isinstance(wires, list):
                return 400, {"error": "sessions must be an array"}
            res = store.import_wire(wires)
            obs_log.event(log, "sessions_imported", replica=self.replica_id,
                          imported=res["imported"], merged=res["merged"],
                          skipped=res["skipped"],
                          rebuild_pending=res["rebuild_pending"])
            return 200, dict(res, replica=self.replica_id)
        uuid = (query.get("uuid") or [None])[0]
        if uuid:
            s = store.peek(str(uuid))
            if s is None:
                return 404, {"error": "no session for uuid %r" % uuid}
            return 200, dict(s.meta(), replica=self.replica_id)
        if query.get("export", ["0"])[0] not in ("", "0", "false"):
            # chaos seam: a crawling drain — the beam-handoff export
            # stalls while the router's handoff retries wait it out
            # (docs/robustness.md; the overload rehearsal arms it to
            # prove scale-down never loses a beam)
            faults.hang("slow_drain")
            if self.draining:
                # the handoff race: steps admitted before drain-begin may
                # still be committing — snapshot only once the report
                # handlers have gone idle (bounded), so the exported beams
                # carry every answered point
                deadline = _time.monotonic() + 2.0
                while not self.idle() and _time.monotonic() < deadline:
                    _time.sleep(0.02)
            out = dict(store.summary(), replica=self.replica_id,
                       draining=bool(self.draining))
            out["sessions"] = store.export_all()
            return 200, out
        return 200, dict(store.summary(), replica=self.replica_id,
                         draining=bool(self.draining))

    def handle_batch(self, body: dict,
                     deadline: Optional[float] = None) -> Tuple[int, dict]:
        # one span for the whole batch request (per-trace fan-out would
        # multiply flight entries); stage marks cover the pooled match and
        # the report loop
        span = obs_trace.current_span() or Span("trace_attributes_batch")
        span.meta.setdefault("endpoint", "trace_attributes_batch")
        if self.draining:
            C_DRAIN_REFUSED.inc()
            span.fail("draining", status="draining")
            self._terminal("trace_attributes_batch", 503, span)
            return 503, {"error": "draining", "status": "draining",
                         "retry_after": 1}
        batcher = self.batcher
        if batcher is None:
            span.fail("service initialising", status="unavailable")
            self._terminal("trace_attributes_batch", 503, span)
            return 503, {"error": "service initialising", "retry_after": 1}
        traces = body.get("traces")
        if not isinstance(traces, list) or not traces:
            span.fail("traces must be a non-empty array", status="invalid")
            self._terminal("trace_attributes_batch", 400, span)
            return 400, {"error": "traces must be a non-empty array"}
        span.meta["n_traces"] = len(traces)
        validated = []
        for i, trace in enumerate(traces):
            err, rl, tl = self.validate(trace)
            if err:
                C_REQUESTS.labels("trace_attributes_batch", "invalid").inc()
                span.fail("trace %d: %s" % (i, err), status="invalid")
                self._terminal("trace_attributes_batch", 400, span)
                return 400, {"error": "trace %d: %s" % (i, err)}
            validated.append((trace, rl, tl))
        try:
            with obs_trace.bind(span):
                t0 = _time.monotonic()
                if self.degraded:
                    m = self._cpu_fallback()
                    with self._cpu_lock:
                        matches = m.match_many([t for t, _, _ in validated])
                    C_DEGRADED_REQ.inc()
                    span.meta["degraded"] = True
                else:
                    mkw = {} if deadline is None else {"deadline": deadline}
                    matches = batcher.match_many(
                        [t for t, _, _ in validated], **mkw)
                span.mark("match_s", _time.monotonic() - t0)
                for m_, (t_, _rl, _tl) in zip(matches, validated):
                    self._note_quality(t_, m_, span)
                t0 = _time.monotonic()
                with obs_attrib.host_stage("report", span, n=len(validated)):
                    results = [
                        report_fn(m, t, self.threshold_sec, rl, tl,
                                  mode=t.get("match_options", {})
                                  .get("mode", "auto"))
                        for m, (t, rl, tl) in zip(matches, validated)
                    ]
                span.mark("report_fn_s", _time.monotonic() - t0)
            degraded = bool(span.meta.get("degraded"))
            self._terminal("trace_attributes_batch", 200, span,
                           degraded=degraded)
            self._count(ok=True)
            out = {"results": results}
            if degraded:
                out["degraded"] = True
                C_REQUESTS.labels("trace_attributes_batch", "degraded").inc()
            else:
                C_REQUESTS.labels("trace_attributes_batch", "ok").inc()
            return 200, out
        except Overloaded as e:
            span.fail(e, status="shed")
            self._terminal("trace_attributes_batch", 429, span)
            C_REQUESTS.labels("trace_attributes_batch", "shed").inc()
            return 429, {"error": str(e),
                         "retry_after": batcher.retry_after_s()}
        except DeadlineExpired as e:
            span.fail(e, status="expired")
            self._terminal("trace_attributes_batch", 504, span)
            C_REQUESTS.labels("trace_attributes_batch", "expired").inc()
            return 504, {"error": str(e)}
        except TraceQuarantined as e:
            span.fail(e, status="quarantined")
            self._terminal("trace_attributes_batch", 422, span)
            C_REQUESTS.labels("trace_attributes_batch", "quarantined").inc()
            return 422, {"error": str(e)}
        except (DeviceWedged, BatcherCrashed) as e:
            span.fail(e, status="unavailable")
            self._terminal("trace_attributes_batch", 503, span)
            self._count(ok=False)
            C_REQUESTS.labels("trace_attributes_batch", "error").inc()
            return 503, {"error": str(e), "retry_after": 1}
        except Exception as e:
            log.exception("batch failed")
            span.fail(e)
            self._terminal("trace_attributes_batch", 500, span)
            self._count(ok=False)
            C_REQUESTS.labels("trace_attributes_batch", "error").inc()
            return 500, {"error": str(e)}

    def handle_statusz(self) -> Tuple[int, dict]:
        """JSON ops snapshot: uptime + config + the engine's device +
        bucket tables + every metric family (the dict form of /metrics,
        for humans and scripts).  The ``attrib`` line carries the last
        capture's age, platform and top stage, so a stale (or CPU-only)
        attribution headline is visible at a glance."""
        from ..obs import attrib as obs_attrib

        m = self.matcher
        b = self.batcher
        return 200, {
            "uptime_s": round(_time.time() - self._t_boot, 1),
            "replica": self.replica_id,
            "draining": bool(self.draining),
            "warming": bool(getattr(self, "warming", False)) or m is None,
            "backend": m.backend if m else None,
            # the engine's devices as JAX reports them: platform,
            # device_kind, count, mesh shape, per-state placement
            "device": m.device_summary() if m else None,
            "viterbi_kernel": getattr(m, "_kernel_mode", None) if m else None,
            "threshold_sec": self.threshold_sec,
            "batch": dict(self._batch_params),
            # fault-domain state (docs/robustness.md): degraded = CPU
            # fallback serving after a watchdog trip; wedged/crashed name
            # the batcher's terminal states; robustness echoes the knobs
            "degraded": bool(self.degraded),
            "wedged": bool(b.wedged) if b is not None else None,
            "crashed": bool(b._crashed) if b is not None else None,
            "robustness": {
                "max_queue": b.max_queue,
                "deadline_ms": round(b.deadline_s * 1000.0, 1),
                "watchdog_s": b.watchdog_s,
                "quarantine_after": b.quarantine_after,
                "quarantine_ttl_s": b.quarantine_ttl_s,
                "reattach_probe_s": self._reattach_probe_s,
                "quarantined_uuids": len(b._quarantine),
            } if b is not None else None,
            "latency_buckets_s": list(obs.LATENCY_BUCKETS_S),
            "batch_fill_buckets": list(obs.BATCH_FILL_BUCKETS),
            "flight": obs_flight.RECORDER.summary(),
            "attrib": obs_attrib.summary(),
            # the burn-rate line: per-objective value/target/burn/budget
            # so an on-call eye catches a fast burn without /debug/slo
            "slo": obs_slo.engine().summary(),
            # the quality line: shadow-oracle agreement + sampler health
            # (None until a quality engine is configured)
            "quality": (self.quality.summary()
                        if self.quality is not None else None),
            # the sparse-gap matching model (docs/match-quality.md
            # "Sparse gaps"): enabled + calibration provenance; None
            # until the engine attaches
            "sparse": (m.sparse.summary()
                       if m is not None
                       and getattr(m, "sparse", None) is not None
                       else None),
            # the session plane: open per-vehicle sessions + folded points
            "sessions": (self.session_store.summary()
                         if self.session_store is not None else None),
            # device-resident session arenas (docs/performance.md
            # "Device-resident session arenas"): slab geometry, per-tier
            # occupancy, and the promotion/eviction/readback counters;
            # None = arena off (host-carried sessions)
            "session_arena": (
                m.session_arena.summary()
                if m is not None
                and getattr(m, "session_arena", None) is not None
                else None),
            # the continent-scale data plane (docs/performance.md): hot
            # arena residency + shard assignment; None = untiered table
            "ubodt_tier": (
                self.matcher.tiering.summary()
                if self.matcher is not None
                and getattr(self.matcher, "tiering", None) is not None
                else None),
            # the adaptive-control plane (docs/serving-fleet.md
            # "Self-driving fleet"): live effective knob values next to
            # their static configuration; None = that controller is off
            "adaptive": {
                "enabled": obs_adaptive.enabled(),
                "batch_wait_s": (round(b.max_wait, 5)
                                 if b is not None else None),
                "session_wait_s": (
                    round(self.session_batcher.max_wait, 5)
                    if self.session_batcher is not None else None),
                # the third knob (this PR): live effective batch widths
                "max_batch": (b.max_batch if b is not None else None),
                "session_max_batch": (
                    self.session_batcher.max_batch
                    if self.session_batcher is not None else None),
            },
            # the preemption plane: checkpoint dir/cadence/dirty backlog
            "checkpoint": (self.session_checkpointer.summary()
                           if self.session_checkpointer is not None
                           else None),
            # fleet economics (docs/economics.md): accrued chip-seconds /
            # $, $/M points, and the measured headroom line — ceiling,
            # headroom, time-to-exhaustion
            "economics": self.economics.summary(),
            # the memory plane: device in_use/limit + exact host bytes
            # for the UBODT tiers and the session store
            "memory": obs_econ.memory_summary(m, self.session_store),
            "metrics": obs.REGISTRY.snapshot(),
        }

    def handle_traces(self, query: dict) -> Tuple[int, dict]:
        """GET /debug/traces?n=K — the flight recorder's most recent
        retained traces (errors and over-threshold always present, plus
        the 1-in-N sample), newest first, with per-stage breakdowns.
        ``?id=<trace_id>`` instead returns every retained entry for that
        one trace (404 with an empty list when it was not retained) —
        the fetch the fleet router's cross-hop stitching makes against
        the replica named in ``X-Reporter-Replica``."""
        rec = obs_flight.RECORDER
        tid = obs_trace.accept_trace_id(query.get("id", [None])[0])
        if tid:
            entries = rec.find(tid)
            code = 200 if entries else 404
            out = {"trace_id": tid, "replica": self.replica_id,
                   "traces": entries}
            if not entries:
                out["error"] = "trace %r not retained" % tid
            return code, out
        try:
            n = int(query.get("n", ["50"])[0])
        except (TypeError, ValueError):
            return 400, {"error": "n must be an integer"}
        n = max(1, min(n, 2 * rec.capacity))
        return 200, {"summary": rec.summary(), "traces": rec.snapshot(n)}

    def handle_slo(self, query: dict) -> Tuple[int, dict]:
        """GET /debug/slo[?window=S] — the SLO engine's full verdict:
        every objective's current value vs target, multi-window burn
        rates, remaining error budget, per-route traffic/quantiles, and
        the retained SLO-violating trace_ids.  ``window`` narrows the
        aggregation window (clamped to the engine's maximum) so a load
        run can ask about exactly its own duration."""
        window = None
        raw = query.get("window", [None])[0]
        if raw is not None:
            try:
                window = max(1.0, float(raw))
            except (TypeError, ValueError):
                return 400, {"error": "window must be a number (seconds)"}
        out = obs_slo.engine().report(window_s=window)
        # the match-quality section (docs/match-quality.md): cohort
        # agreement windows + sampler state; tools/quality_gate.py judges
        # exactly this block against the pinned baseline profile
        if self.quality is not None:
            out["quality"] = self.quality.report()
        return 200, out

    def handle_profile(self, query: dict) -> Tuple[int, dict]:
        """GET /debug/profile?seconds=N[&python=1] — record a jax.profiler
        trace to a temp dir and return its path (TensorBoard-loadable).
        The Python function tracer is off unless ``python=1``."""
        from ..obs import profiler

        try:
            seconds = float(query.get("seconds", ["2"])[0])
        except (TypeError, ValueError):
            return 400, {"error": "seconds must be a number"}
        python = query.get("python", ["0"])[0] not in ("", "0", "false")
        m = self.matcher
        if m is not None and m.backend != "jax":
            return 501, {"error": "profiling needs the jax backend (got %r)" % m.backend}
        try:
            trace_dir, recorded = profiler.capture(seconds, python=python)
        except profiler.ProfilerBusy as e:
            # single-flight: the in-flight capture's trace_id rides the 409
            # so the caller can find (or wait out) the owner
            return 409, {"error": str(e), "inflight": e.inflight}
        except Exception as e:  # noqa: BLE001 - surfaced to the caller
            log.exception("profiler capture failed")
            return 500, {"error": str(e)}
        return 200, {"trace_dir": trace_dir, "seconds": recorded,
                     "python": python}

    def handle_attrib(self, query: dict) -> Tuple[int, dict]:
        """GET /debug/attrib — the last parsed named-stage attribution
        (plus its age), or with ``?capture=1[&reps=N]`` an on-demand
        capture: ``reps`` dummy dispatches through the real dispatch path
        under a jax.profiler window, parsed into the per-stage table and
        published to the gauges.  Single-flight with /debug/profile: a
        concurrent capture gets 409 with the in-flight capture's
        trace_id."""
        from ..obs import attrib as obs_attrib
        from ..obs import profiler

        capture = query.get("capture", ["0"])[0] not in ("", "0", "false")
        if not capture:
            res = obs_attrib.last()
            out = {"attrib": res, "summary": obs_attrib.summary()}
            return 200, out
        m = self.matcher
        if m is None:
            return 503, {"error": "service initialising"}
        if m.backend != "jax":
            return 501, {"error": "attribution needs the jax backend (got %r)"
                                  % m.backend}
        try:
            reps = int(query.get("reps", ["3"])[0])
        except (TypeError, ValueError):
            return 400, {"error": "reps must be an integer"}
        reps = max(1, min(reps, 20))
        try:
            res = obs_attrib.capture_matcher(m, reps=reps)
        except profiler.ProfilerBusy as e:
            return 409, {"error": str(e), "inflight": e.inflight}
        except Exception as e:  # noqa: BLE001 - surfaced to the caller
            log.exception("attribution capture failed")
            return 500, {"error": str(e)}
        return 200, {"attrib": res, "summary": obs_attrib.summary()}

    # -- server ------------------------------------------------------------

    def make_server(self, host: str = "0.0.0.0", port: int = 8002) -> ThreadingHTTPServer:
        service = self
        # the economics sensor plane (docs/economics.md) arms with the
        # real server: the per-tick sampler thread plus the scrape-time
        # ledger/memory collectors (the memory lambda reads whatever
        # matcher/store are attached at scrape time)
        self.economics.start(
            self._econ_sample,
            collect=(lambda: obs_econ.publish_memory(self.matcher,
                                                     self.session_store),))

        # connection-concurrency bound, honouring the reference's env knobs
        # (reporter_service.py:37-45: THREAD_POOL_COUNT, or
        # THREAD_POOL_MULTIPLIER x cpus; the reference sizes a hand-rolled
        # pool, here a semaphore bounds the per-connection threads)
        try:
            pool = int(os.environ["THREAD_POOL_COUNT"])
        except (KeyError, ValueError):
            mult = os.environ.get("THREAD_POOL_MULTIPLIER")
            try:
                pool = int(float(mult) * (os.cpu_count() or 1)) if mult else 0
            except ValueError:
                pool = 0
        gate = threading.BoundedSemaphore(pool) if pool > 0 else None

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # idle keep-alive connections time out: without this, a handler
            # thread blocks forever in readline() between requests, and a
            # graceful shutdown joining non-daemon handlers (serve/__main__)
            # would hang on any idle persistent client
            timeout = 30

            def _encode(self, code: int, payload: dict):
                """(body bytes, content type) of one answer."""
                if code == 200 and getattr(self, "_accept_wire", False):
                    # the client negotiated the binary columnar wire
                    # (Accept: application/x-reporter-columnar) — only
                    # 200 report payloads encode; every error shape
                    # stays JSON so clients keep one error parser
                    try:
                        return (wire.encode_response(
                            payload, single=self._wire_single),
                            wire.CONTENT_TYPE)
                    except Exception:  # noqa: BLE001 - fall back to JSON
                        pass
                return (json.dumps(payload, separators=(",", ":"))
                        .encode("utf-8"), "application/json;charset=utf-8")

            def _answer(self, code: int, payload: dict):
                if getattr(self, "_timed_route", False):
                    with obs_attrib.host_stage("serialize",
                                               trace_id=self._trace_id):
                        body, ctype = self._encode(code, payload)
                else:
                    body, ctype = self._encode(code, payload)
                self.send_response(code)
                self.send_header("Access-Control-Allow-Origin", "*")
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                if code in (429, 503):
                    # shed/unavailable responses carry a backoff hint both
                    # as a header (RFC 9110, what generic clients read) and
                    # in the body (docs/http-api.md error semantics)
                    ra = payload.get("retry_after") if isinstance(payload, dict) else None
                    try:
                        ra = max(1, int(ra))
                    except (TypeError, ValueError):
                        ra = 1
                    self.send_header("Retry-After", str(ra))
                self._echo_trace_header()
                # the stable replica id rides EVERY response (including
                # errors and drain refusals): the router's affinity
                # bookkeeping and loadgen's per-replica distribution key
                # on it (docs/serving-fleet.md)
                self.send_header("X-Reporter-Replica", service.replica_id)
                self.end_headers()
                self.wfile.write(body)

            def _answer_text(self, code: int, text: str):
                """Prometheus exposition is text, not JSON."""
                body = text.encode("utf-8")
                self.send_response(code)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self._echo_trace_header()
                self.send_header("X-Reporter-Replica", service.replica_id)
                self.end_headers()
                self.wfile.write(body)

            def _echo_trace_header(self):
                """Every response echoes the request's trace id (accepted
                from X-Reporter-Trace, or generated at ingestion), so the
                client can pull the trace from GET /debug/traces."""
                tid = getattr(self, "_trace_id", None)
                if tid:
                    self.send_header("X-Reporter-Trace", tid)

            def _content_length(self):
                """Parsed Content-Length, or None for a malformed header.
                Malformed means the body extent is unknowable: the caller
                must close the connection (keep-alive framing is lost)."""
                raw = self.headers.get("Content-Length", "0")
                try:
                    n = int(raw)
                except (TypeError, ValueError):
                    self.close_connection = True
                    return None
                if n < 0:
                    # a negative length is as malformed as a non-numeric
                    # one: clamping it to 0 would leave the request's body
                    # bytes unread on a keep-alive socket, to be parsed as
                    # the next request line (ADVICE r04)
                    self.close_connection = True
                    return None
                return n

            def _drain_body(self, post: bool):
                """Consume any request body before an early answer: the
                server speaks HTTP/1.1 keep-alive, so unread body bytes
                would be parsed as the NEXT request line on this socket."""
                if post:
                    n = self._content_length()
                    if n:
                        self.rfile.read(n)

            def _route(self, post: bool):
                if service.draining:
                    self.close_connection = True  # answer, then drain out
                # trace ingestion: accept the client's id or mint one; the
                # id is echoed on EVERY response (_echo_trace_header)
                self._trace_id = (
                    obs_trace.accept_trace_id(
                        self.headers.get("X-Reporter-Trace"))
                    or obs_trace.new_trace_id())
                # per-request wire state: the handler object lives for the
                # whole keep-alive connection, so negotiation flags MUST
                # reset here or one binary request would flip every later
                # request on the socket
                self._accept_wire = False
                self._wire_single = False
                self._timed_route = False
                try:
                    split = urlsplit(self.path)
                    action = split.path.split("/")[-1]
                    query = parse_qs(split.query)
                    if action in ("report", "trace_attributes_batch"):
                        self._timed_route = True
                        if service.wire_enabled and wire.CONTENT_TYPE in (
                                self.headers.get("Accept") or ""):
                            self._accept_wire = True
                            self._wire_single = action == "report"
                    if action not in ACTIONS:
                        self._drain_body(post)
                        return self._answer(
                            400, {"error": "Try a valid action: %s" % sorted(ACTIONS)}
                        )
                    if action in ("report", "trace_attributes_batch"):
                        # chaos seam: a slow-ACCEPTING replica — matching
                        # work stalls at the door while /health stays
                        # snappy, exactly the straggler shape the
                        # router's hedging races (docs/serving-fleet.md)
                        faults.hang("replica_slow_accept")
                    if action == "health":  # no payload required
                        self._drain_body(post)
                        return self._answer(*service.handle_health())
                    if action == "metrics":
                        self._drain_body(post)
                        return self._answer_text(200, obs.REGISTRY.render())
                    if action == "statusz":
                        self._drain_body(post)
                        return self._answer(*service.handle_statusz())
                    if action in ("profile", "attrib"):
                        # GET /debug/profile?seconds=N | /debug/attrib
                        # [?capture=1&reps=N] — bound to a span so the
                        # single-flight guard can name the owning request's
                        # trace_id on a concurrent caller's 409
                        self._drain_body(post)
                        with obs_trace.bind(
                                Span(action, trace_id=self._trace_id)):
                            handler = (service.handle_profile
                                       if action == "profile"
                                       else service.handle_attrib)
                            return self._answer(*handler(query))
                    if action == "sessions":
                        # GET /sessions[?export=1|uuid=U] | POST /sessions
                        # {"sessions": [...]} — the beam-handoff surface;
                        # export stays answerable DURING drain (that is
                        # when the router pulls it)
                        if post:
                            n = self._content_length()
                            if n is None:
                                return self._answer(
                                    400, {"error": "invalid Content-Length"})
                            try:
                                body = json.loads(
                                    self.rfile.read(n).decode("utf-8"))
                            except Exception as e:  # noqa: BLE001
                                return self._answer(400, {"error": str(e)})
                            if not isinstance(body, dict):
                                return self._answer(
                                    400, {"error": "request body must be a "
                                          "json object"})
                            return self._answer(
                                *service.handle_sessions(query, body))
                        return self._answer(*service.handle_sessions(query))
                    if action == "traces":  # GET /debug/traces?n=K
                        self._drain_body(post)
                        return self._answer(*service.handle_traces(query))
                    if action == "slo":  # GET /debug/slo?window=S
                        self._drain_body(post)
                        return self._answer(*service.handle_slo(query))
                    if action == "cost":  # GET /debug/cost
                        self._drain_body(post)
                        return self._answer(*service.handle_cost(query))
                    if action == "history":  # GET /debug/history?window=S
                        self._drain_body(post)
                        return self._answer(*service.handle_history(query))
                    if post:
                        n = self._content_length()
                        if n is None:  # malformed header: framing unknown
                            return self._answer(
                                400, {"error": "invalid Content-Length"})
                        raw = self.rfile.read(n)
                        enc = (self.headers.get("Content-Encoding")
                               or "").strip().lower()
                        if enc not in ("", "identity", "gzip"):
                            return self._answer(
                                415, {"error": "unsupported "
                                      "Content-Encoding %r (gzip or "
                                      "identity)" % enc})
                        binary = wire.is_wire(
                            self.headers.get("Content-Type"))
                        if binary and not service.wire_enabled:
                            return self._answer(
                                415, {"error": "binary wire disabled "
                                      "(REPORTER_WIRE=0)"})
                        # body decode = the "parse" host stage: gzip
                        # inflate (bounded), then the negotiated wire —
                        # binary columnar frames by Content-Type, JSON
                        # otherwise (docs/http-api.md "Wire formats")
                        with obs_attrib.host_stage(
                                "parse", trace_id=self._trace_id):
                            if enc == "gzip":
                                raw = _gunzip(raw)
                            if binary:
                                payload = wire.decode_request(raw)
                            else:
                                payload = json.loads(raw.decode("utf-8"))
                    else:
                        if "json" not in query:
                            return self._answer(400, {"error": "No json provided"})
                        payload = json.loads(query["json"][0])
                except OSError as e:
                    # the BODY read failed (idle/stall timeout, reset): the
                    # stream position is unknown, so a keep-alive follow-up
                    # would parse leftover bytes as a request line — close.
                    # The reply is best-effort: on a peer reset the write
                    # raises too, and a dropped client must not traceback.
                    self.close_connection = True
                    try:
                        return self._answer(400, {"error": str(e)})
                    except OSError:
                        return None
                except Exception as e:
                    # parse errors AFTER a complete read leave the stream
                    # clean; the connection stays usable
                    return self._answer(400, {"error": str(e)})

                try:
                    if not isinstance(payload, dict):
                        code, out = 400, {"error": "request body must be a json object"}
                    else:
                        # per-request deadline: X-Reporter-Deadline-Ms is
                        # the client's remaining budget; converted to an
                        # absolute monotonic bound AT INGESTION so queue
                        # time counts against it.  Malformed values are
                        # ignored (server default applies), like a
                        # malformed trace header.
                        deadline = None
                        raw_dl = self.headers.get("X-Reporter-Deadline-Ms")
                        if raw_dl:
                            try:
                                deadline = (_time.monotonic()
                                            + max(0.0, float(raw_dl)) / 1000.0)
                            except ValueError:
                                deadline = None
                        # the request's span: handle_report/handle_batch pick
                        # it up from the context (their own signatures stay
                        # embedder-compatible)
                        span = Span(action, trace_id=self._trace_id)
                        # the router pins re-dispatched/hedged legs with
                        # X-Reporter-Flight-Keep so THIS side of a
                        # failed-over request is guaranteed retained for
                        # cross-hop stitching (validated like a trace id;
                        # garbage is ignored, not an error)
                        fk = obs_trace.accept_trace_id(
                            self.headers.get("X-Reporter-Flight-Keep"))
                        if fk:
                            span.meta["flight_keep"] = fk
                        # kwargs are only passed when set, so embedders
                        # wrapping handle_report(trace) keep working
                        kw = {}
                        if deadline is not None:
                            kw["deadline"] = deadline
                        # _track_active: the drain loop (serve/__main__)
                        # waits for this count to reach zero before the
                        # listener closes — inflight work always finishes
                        with service._track_active(), obs_trace.bind(span):
                            if action == "report":
                                # ?debug=1 opts the breakdown onto the
                                # response
                                debug = query.get("debug", ["0"])[0] not in ("", "0", "false")
                                if debug:
                                    kw["debug"] = True
                                code, out = service.handle_report(payload, **kw)
                            else:
                                code, out = service.handle_batch(payload, **kw)
                except Exception as e:  # belt-and-braces: never drop the socket
                    log.exception("unhandled request error")
                    code, out = 500, {"error": str(e)}
                self._answer(code, out)

            def setup(self):
                super().setup()
                self.server._track(self.connection)

            def finish(self):
                self.server._untrack(self.connection)
                super().finish()

            def do_GET(self):
                if gate is None:
                    return self._route(post=False)
                with gate:
                    self._route(post=False)

            def do_POST(self):
                if gate is None:
                    return self._route(post=True)
                with gate:
                    self._route(post=True)

            def log_request(self, code="-", size="-"):
                # structured per-request line at DEBUG (method / path /
                # status / trace_id) instead of the silenced stdlib format:
                # request logs are recoverable with REPORTER_LOG_LEVEL=DEBUG
                # without flooding the default INFO stream
                obs_log.event(
                    log, "http_request", level=logging.DEBUG,
                    method=self.command, path=self.path,
                    status=int(code) if isinstance(code, int) else str(code),
                    trace_id=getattr(self, "_trace_id", None))

            def log_message(self, fmt, *args):
                log.debug("http: " + fmt, *args)

        class Server(ThreadingHTTPServer):
            # socketserver's default listen backlog is 5: a burst of
            # concurrent clients (the micro-batcher's whole operating
            # point) overflows it and the kernel RSTs the excess connects
            request_queue_size = 128

            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                self._conn_lock = threading.Lock()
                self._conns: set = set()

            def _track(self, sock) -> None:
                with self._conn_lock:
                    self._conns.add(sock)

            def _untrack(self, sock) -> None:
                with self._conn_lock:
                    self._conns.discard(sock)

            def close_lingering(self) -> None:
                """Half-close every tracked connection: a graceful drain
                must not wait out the 30 s idle timeout of keep-alive
                clients (the router's pooled sockets!) before
                server_close's non-daemon handler join can return.
                Called AFTER the inflight count drains to zero, so only
                idle connections are left to cut."""
                with self._conn_lock:
                    conns = list(self._conns)
                for sock in conns:
                    try:
                        sock.shutdown(_socket.SHUT_RDWR)
                    except OSError:
                        pass

        return Server((host, port), Handler)


def parse_service_config(path: str) -> Tuple["MatcherConfig", dict]:
    """Parse + validate the cheap half of the config (no jax, no network
    IO): malformed JSON, bad matcher keys, and an unknown network type all
    fail HERE so a deferred boot still rejects a broken config before the
    socket binds."""
    with open(path) as f:
        conf = json.load(f)
    mconf = conf.get("matcher", {})
    if "meili" in mconf or "default" in mconf:
        cfg = MatcherConfig.from_meili(mconf)
    else:
        cfg = MatcherConfig.from_dict(mconf)
    kind = conf.get("network", {"type": "grid"}).get("type", "grid")
    if kind not in ("grid", "file", "tiles"):
        raise ValueError("unknown network type %r" % (kind,))
    return cfg, conf


def build_matcher(cfg: "MatcherConfig", conf: dict,
                  backend: Optional[str] = None) -> SegmentMatcher:
    """The expensive half: load/build the network, build the UBODT, and
    initialise the device backend.  Safe to run on a background thread
    behind an already-bound socket (__main__'s deferred boot)."""
    netspec = conf.get("network", {"type": "grid"})
    kind = netspec.get("type", "grid")
    if kind == "grid":
        net = grid_city(
            rows=netspec.get("rows", 8),
            cols=netspec.get("cols", 8),
            spacing_m=netspec.get("spacing_m", 200.0),
            origin=tuple(netspec.get("origin", (37.75, -122.45))),
        )
    elif kind == "file":
        with open(netspec["path"]) as f:
            net = RoadNetwork.from_dict(json.load(f))
    else:  # "tiles" -- parse_service_config rejected anything else
        from ..tiles.codec import load_network_tiles

        net = load_network_tiles(netspec["path"])
    return SegmentMatcher(
        network=net, config=cfg, backend=backend or conf.get("backend", "jax")
    )


def load_service_config(path: str, backend: Optional[str] = None) -> Tuple[SegmentMatcher, dict]:
    """Service config JSON:

    {
      "network": {"type": "grid", "rows": 8, "cols": 8, "spacing_m": 200}
               | {"type": "file", "path": "network.json"}
               | {"type": "tiles", "path": "tiles_dir"}        (native codec)
      "matcher": { MatcherConfig fields / meili keys },
      "backend": "jax" | "cpu",
      "batch": {"max_batch": 64, "max_wait_ms": 10, "max_inflight": 4}
    }

    Eager parse + build in one call (library/tests convenience); the
    service CLI uses parse_service_config + build_matcher so the socket
    binds before the expensive half runs.
    """
    cfg, conf = parse_service_config(path)
    return build_matcher(cfg, conf, backend), conf
