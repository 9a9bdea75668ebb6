"""On-demand ``jax.profiler`` capture (backs ``GET /debug/profile`` and
the attribution windows of ``obs/attrib.py``).

The capture is synchronous in the calling (handler) thread: the device
keeps serving from the other threads while the trace records, which is
exactly what a production capture wants to see.  One capture at a time —
``jax.profiler.start_trace`` is process-global, so a second concurrent
request (either endpoint, any kind) gets ``ProfilerBusy`` carrying the
in-flight capture's trace_id (HTTP 409) instead of corrupting the first.
jax is imported lazily: the obs package stays importable (and the metrics
registry usable) in processes that never touch the device.

Captures record the device planes and the host's TraceMe events (the
``reporter.<stage>`` host spans among them) without the Python function
tracer, which hooks every Python call of the host path it would measure;
``python=True`` turns it back on.
"""

from __future__ import annotations

import contextlib
import tempfile
import threading
import time

MAX_SECONDS = 60.0
MIN_SECONDS = 0.05

_capture_lock = threading.Lock()
# metadata of the capture currently holding the lock (read without the
# lock on the 409 path: a fresh reader may see the previous capture's
# block for an instant, which is still an honest "busy with <id>")
_inflight: "dict | None" = None


class ProfilerBusy(RuntimeError):
    """A capture is already in flight.  ``inflight`` describes it:
    {"kind", "trace_id", "started_unix", "seconds"} (seconds only for
    fixed-window /debug/profile captures)."""

    def __init__(self, msg: str, inflight: "dict | None" = None):
        super().__init__(msg)
        self.inflight = inflight


def inflight() -> "dict | None":
    return dict(_inflight) if _inflight else None


@contextlib.contextmanager
def session(kind: str, trace_id: "str | None" = None,
            out_dir: "str | None" = None, seconds: "float | None" = None,
            python: bool = False):
    """Single-flight jax.profiler window: acquires the process-global
    capture lock (non-blocking; raises ProfilerBusy with the in-flight
    capture's metadata), starts the trace, yields the trace dir, and
    stops the trace on exit.  ``trace_id`` defaults to the caller's bound
    span so a 409 can name the request that owns the capture.
    ``python`` turns on the Python function tracer (off by default)."""
    global _inflight
    if trace_id is None:
        from . import trace as obs_trace

        trace_id = obs_trace.current_trace_id()
    if not _capture_lock.acquire(blocking=False):
        raise ProfilerBusy(
            "a profiler capture is already running", inflight())
    import jax

    try:
        _inflight = {"kind": kind, "trace_id": trace_id,
                     "started_unix": round(time.time(), 3),
                     "seconds": seconds}
        d = out_dir or tempfile.mkdtemp(prefix="reporter_jax_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1 if python else 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            yield d
        finally:
            jax.profiler.stop_trace()
    finally:
        _inflight = None
        _capture_lock.release()


def capture(seconds: float, out_dir: str = None,
            python: bool = False) -> "tuple[str, float]":
    """Record a jax profiler trace for ~``seconds`` (clamped to
    [MIN_SECONDS, MAX_SECONDS]), with the Python function tracer only when
    ``python``.  Returns (trace_dir, seconds_recorded); the dir holds a
    TensorBoard-loadable trace."""
    seconds = min(max(float(seconds), MIN_SECONDS), MAX_SECONDS)
    with session("profile", out_dir=out_dir, seconds=seconds,
                 python=python) as d:
        time.sleep(seconds)
    return d, seconds
