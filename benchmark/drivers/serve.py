"""The ``serve`` entry: open-loop /report traffic against the HTTP server.

This process never imports JAX.  It writes the configuration's city as a
"file" network, starts ``python -m reporter_tpu.serve --warmup`` (through
``serve_child.py``, which adds only the read of the device's memory peak) as
the one process on the chip, waits on /health, sends the mix's warm-up traffic,
then drives the measured window from an asyncio open-loop client over
keep-alive connections (each request timed from when it was due, so a stall
also delays the requests behind it).  /statusz before and after the window
gives the counters and the device; with a trace, /debug/profile records a
short stretch of the window.  SIGTERM must stop the server with exit code 0.  Then a seeded
sample of the window's answers is checked against the reference.
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import common
import compare
import trace_reduce
import traffic
from reference import Reference

CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve_child.py")
HEALTH_TIMEOUT_S = 900.0
LATE_WAIT_S = 60.0  # how long past the window's close an answer is awaited


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def start_server(cfg: dict, run_dir: str):
    """The server for configuration ``cfg``, started on a free port:
    (process, base url, log path, peak file)."""
    conf = {"network": {"type": "file", "path": common.city_file(cfg)},
            "matcher": cfg["matcher"], "backend": "jax", "batch": cfg["server"]["batch"]}
    conf_path = os.path.join(run_dir, "service.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)
    # one hash seed for every run, so no run draws a slower set layout
    env = dict(os.environ, TMPDIR=run_dir, PYTHONHASHSEED="0", **cfg["server"].get("env", {}))
    port = _free_port()
    log_path = os.path.join(run_dir, "server.log")
    peak_path = os.path.join(run_dir, "peak.json")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, CHILD, peak_path, "--warmup", conf_path, "127.0.0.1:%d" % port],
            cwd=common.ROOT, env=env, stdout=log, stderr=subprocess.STDOUT)
    return proc, "http://127.0.0.1:%d" % port, log_path, peak_path


def _get(base: str, path: str, timeout: float = 30.0):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return json.loads(r.read().decode("utf-8"))


def _wait_healthy(base: str, proc) -> None:
    t0 = time.monotonic()
    while time.monotonic() - t0 < HEALTH_TIMEOUT_S:
        if proc.poll() is not None:
            common.fail("server exited (rc %s) before it was healthy" % proc.returncode)
        try:
            body = _get(base, "/health", timeout=5.0)
            if body.get("status") == "ok" and not body.get("warming"):
                return
        except (OSError, ValueError):
            pass
        time.sleep(0.25)
    common.fail("server not healthy after %.0fs" % HEALTH_TIMEOUT_S)


def _stop(proc, timeout: float = 120.0) -> int:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            common.fail("server ignored SIGTERM for %.0fs" % timeout)
    return proc.returncode


class _Pool:
    """Keep-alive connections to the server, as the Kafka reporter's HTTP
    client keeps them: a request takes an idle connection or opens one, so
    the pool grows to the offered concurrency and no request pays a connect.

    The server closes a connection idle for 30 s (its handler's timeout).
    Like a pooling HTTP client, this one never hands a request to such a
    connection: one the server has closed, or one idle for ``IDLE_S``, is
    closed here and passed over; a request that finds its connection closed
    before any byte of an answer is sent again once, on a new connection."""

    IDLE_S = 10.0  # a third of the server's keep-alive timeout

    def __init__(self, port: int):
        self.port = port
        self.idle = []  # (reader, writer, monotonic time it went idle)
        self.evicted = self.evicted_30s = self.resent = 0

    def _take(self):
        """A live pooled connection, or None."""
        while self.idle:
            reader, writer, t_idle = self.idle.pop()
            idle_s = time.monotonic() - t_idle
            if reader.at_eof() or writer.is_closing() or idle_s > self.IDLE_S:
                writer.close()
                self.evicted += 1
                self.evicted_30s += idle_s > 30.0
                continue
            return reader, writer
        return None

    async def post(self, body: bytes, timeout: float):
        """(status, response bytes) of one POST /report."""
        for fresh in (False, True):
            conn = None if fresh else self._take()
            pooled = conn is not None
            reader, writer = conn or await asyncio.open_connection("127.0.0.1", self.port)
            head = None
            try:
                writer.write(b"POST /report HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                             b"Content-Type: application/json\r\n"
                             b"Content-Length: %d\r\n\r\n" % len(body) + body)
                await writer.drain()
                head = await asyncio.wait_for(reader.readuntil(b"\r\n\r\n"), timeout)
                status = int(head.split(b" ", 2)[1])
                n = 0
                for line in head.split(b"\r\n"):
                    if line.lower().startswith(b"content-length:"):
                        n = int(line.split(b":", 1)[1])
                data = await asyncio.wait_for(reader.readexactly(n), timeout)
            except (ConnectionError, asyncio.IncompleteReadError) as e:
                writer.close()
                if pooled and head is None and not getattr(e, "partial", b""):
                    self.resent += 1
                    continue  # the server had closed it: once more, on a new one
                raise
            except BaseException:
                writer.close()
                raise
            if b"connection: close" in head.lower():
                writer.close()
            else:
                self.idle.append((reader, writer, time.monotonic()))
            return status, data

    def close(self) -> None:
        for _r, w, _t in self.idle:
            w.close()
        self.idle = []


def drive(port: int, phases, timeout_s: float, pool_stats=None):
    """Send each phase's schedule [(due_s, body bytes)] open loop, on one pool
    of connections; a phase starts when its ``before()`` (blocking, run in a
    thread; or None) returns.  Returns, per phase, [(due, sent, done, status,
    response)] with times from the phase's start; done is None for a request
    with no answer by LATE_WAIT_S past its timeout, and its response is then
    the error's repr.  ``pool_stats`` (a dict) gets the pool's counts of
    connections passed over and of requests sent again."""

    async def one(pool, out, i, due, body, t0, loop):
        await asyncio.sleep(max(0.0, t0 + due - loop.time()))
        sent = loop.time()
        try:
            status, data = await pool.post(body, LATE_WAIT_S + timeout_s)
            out[i] = (due, sent - t0, loop.time() - t0, status, data)
        except (OSError, asyncio.TimeoutError, asyncio.IncompleteReadError, ValueError) as e:
            out[i] = (due, sent - t0, None, 0, repr(e).encode())

    async def main():
        loop = asyncio.get_running_loop()
        pool = _Pool(port)
        outs = []
        try:
            for schedule, before in phases:
                if before is not None:
                    await loop.run_in_executor(None, before)
                out = [None] * len(schedule)
                t0 = loop.time() + 0.05
                await asyncio.gather(*(one(pool, out, i, d, b, t0, loop)
                                       for i, (d, b) in enumerate(schedule)))
                outs.append(out)
        finally:
            pool.close()
            if pool_stats is not None:
                pool_stats.update(evicted=pool.evicted, evicted_30s=pool.evicted_30s,
                                  resent=pool.resent)
        return outs

    # this process holds the whole city; a full collection of it would pause
    # the generator for a tenth of a second and more, and every request due
    # in that pause would count it as latency
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        return asyncio.run(main())
    finally:
        gc.enable()
        gc.unfreeze()


def _bodies(reqs):
    return [(due, json.dumps(r, separators=(",", ":")).encode()) for due, r in reqs]


def _reduce(ctx, trace_dir):
    """The traced window, reduced; a CPU rehearsal has no device plane."""
    try:
        return trace_reduce.reduce_dir(trace_dir)
    except trace_reduce.NoDevicePlane:
        if not ctx["args"].rehearse:
            raise
        return None


def run(ctx: dict) -> dict:
    cfg, mix, args = ctx["config"], ctx["mix"], ctx["args"]
    proc, base, log_path, peak_path = start_server(cfg, ctx["run_dir"])
    port = int(base.rsplit(":", 1)[1])
    try:
        # the traffic is made while the server builds and warms
        net = common.load_city(common.city_file(cfg))
        roads = traffic.Roads(net)
        seconds = float(args.seconds)
        window = traffic.stream_requests(roads, mix, seconds, args.seed)
        warm = traffic.stream_requests(roads, mix, float(mix["warmup_s"]), args.seed ^ 0x5EED)
        sched, warm_sched = _bodies(window), _bodies(warm)
        _wait_healthy(base, proc)
        st = _get(base, "/statusz")
        dev = st.get("device") or {}
        if dev.get("platform") != "tpu" and not args.rehearse:
            common.fail("server runs on %r, not tpu" % dev.get("platform"))
        if int(dev.get("count") or 0) < ctx["chips"]:
            common.fail("server spans %s device(s), cell asks for %d"
                        % (dev.get("count"), ctx["chips"]))
        marks, prof = {}, {}
        th = None
        if args.trace:
            start, length = mix["trace_window"]

            def profile():
                time.sleep(start * seconds)
                prof.update(_get(base, "/debug/profile?seconds=%g" % (length * seconds),
                                 timeout=120 + length * seconds))

            th = threading.Thread(target=profile, daemon=True)

        def open_window():
            marks["snap0"] = _get(base, "/statusz")["metrics"]
            marks["setup_s"] = time.monotonic() - ctx["t_start"]
            if th is not None:
                th.start()

        pool_stats = {}
        _warm, results = drive(port, [(warm_sched, None), (sched, open_window)],
                               float(mix["timeout_s"]), pool_stats)
        if th is not None:
            th.join(timeout=300)
        snap0, setup_s = marks["snap0"], marks["setup_s"]
        st1 = _get(base, "/statusz")
        snap1 = st1["metrics"]
        common.say("server adaptive control at the window's close: %s"
                   % json.dumps(st1.get("adaptive")))
        rc = _stop(proc)
        if rc != 0:
            common.fail("server exited %s on SIGTERM" % rc)
        with open(peak_path) as f:
            mem = json.load(f)["memory_peak_bytes"]
    except BaseException:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        with open(log_path) as f:
            sys.stderr.write("--- server log tail ---\n%s\n" % f.read()[-6000:])
        raise
    late = sorted(r[1] - r[0] for r in results)
    common.say("generator lateness: p50 %.4f s, p99 %.4f s, max %.4f s over %d requests"
               % (common.percentile(late, 0.5), common.percentile(late, 0.99), late[-1],
                  len(late)))
    common.say("client pool: %d connections passed over as closed or idle > %g s (%d idle"
               " > 30 s), %d requests sent again on a new connection"
               % (pool_stats["evicted"], _Pool.IDLE_S, pool_stats["evicted_30s"],
                  pool_stats["resent"]))
    timeout_s = float(mix["timeout_s"])
    late = [i for i, res in enumerate(results)
            if _answered(res) and _outcome(res, timeout_s) is None]
    common.say("answered after the %g s client timeout: %d requests" % (timeout_s, len(late)))
    bad = [(i, res) for i, res in enumerate(results) if not _answered(res)]
    for i, (due, sent, done, status, data) in bad[:10]:
        sys.stderr.write("failed request %d: due %.3f s, sent %.3f s, answered %s, status %d,"
                         " %d points: %s\n" % (i, due, sent, "never" if done is None else
                                               "%.3f s" % done, status,
                                               len(window[i][1]["trace"]),
                                               data[:300].decode("utf-8", "replace")))
    rec = _record(ctx, window, results, snap0, snap1, setup_s, dev, mem)
    if args.trace:
        rec["trace"] = (_reduce(ctx, prof["trace_dir"])
                        if prof.get("trace_dir") else None)
        rec["traced_seconds"] = prof.get("seconds")
    rec["checks"] = _check(ctx, net, window, results)
    return rec


def _degraded(res) -> bool:
    return b'"degraded": true' in res[4] or b'"degraded":true' in res[4]


def _answered(res) -> bool:
    """The request got an answer, 200 and undegraded, however late."""
    return res[2] is not None and res[3] == 200 and not _degraded(res)


def _outcome(res, timeout_s: float):
    """Latency (s, from the due time) of a request answered within the
    client's timeout; None for one answered late or not at all."""
    due, _sent, done, _status, _data = res
    if not _answered(res) or done - due > timeout_s:
        return None
    return done - due


def _record(ctx, window, results, snap0, snap1, setup_s, dev, mem):
    timeout_s = float(ctx["mix"]["timeout_s"])
    lat_ms, pts_ok, last_done = [], 0, 0.0
    for (_due, r), res in zip(window, results):
        lat = _outcome(res, timeout_s)
        # a failed request reads as above any limit: the longest the client waits
        lat_ms.append((LATE_WAIT_S + timeout_s) * 1e3 if lat is None else lat * 1e3)
        if lat is not None:
            pts_ok += len(r["trace"])
        if res[2] is not None:
            last_done = max(last_done, res[2])
    return {"kind": "stream", "attempted": len(results),
            "failed": sum(1 for res in results if not _answered(res)),
            "setup_s": setup_s, "points": pts_ok, "latency_ms": lat_ms,
            "window_s": max(float(ctx["args"].seconds), last_done),
            "snap0": snap0, "snap1": snap1, "peaks": ctx["peaks"],
            "device": {"platform": dev.get("platform"), "kind": dev.get("device_kind"),
                       "count": ctx["chips"], "memory_peak_bytes": int(mem)}}


def sample(window, answered, mix, seed):
    """A seeded sample of the answered requests, with the longest among them."""
    rng = traffic.rng_for(seed, 3)
    n = min(int(mix["sample_requests"]), len(answered))
    pick = set(rng.choice(answered, n, replace=False).tolist()) if n else set()
    if answered:
        pick.add(max(answered, key=lambda i: len(window[i][1]["trace"])))
    return sorted(pick)


def _check(ctx, net, window, results) -> dict:
    """The numbers compared for ``correct``, each with its limit."""
    cfg, mix, args = ctx["config"], ctx["mix"], ctx["args"]
    # the guarantee: every request answered 200 from the chip.  An answer
    # past the client's timeout is late, not wrong: latency counts it, and
    # it is checked like any other
    ok = [i for i, res in enumerate(results) if _answered(res)]
    unanswered = sum(1 for res in results if res[2] is None or res[3] != 200)
    degraded = sum(1 for res in results if _degraded(res))
    pick = sample(window, ok, mix, args.seed)
    ref = Reference(net, cfg["matcher"])
    t0 = time.monotonic()
    differ, points = 0, 0
    opts = mix["match_options"]
    for i in pick:
        req = window[i][1]
        body = json.loads(results[i][4])
        want = ref.report(req, cfg["threshold_sec"], opts["report_levels"],
                          opts["transition_levels"])
        differ += not compare.answer_agrees(body, req, want)
        points += len(req["trace"])
    common.say("reference: %d answers, %d points in %.1f s"
               % (len(pick), points, time.monotonic() - t0))
    limit = cfg["limits"]["answers_differing"]
    share = differ / max(1, len(pick))
    return {
        "answers_differing": {"value": share, "limit": limit, "ok": share <= limit},
        "requests_unanswered": {"value": unanswered, "limit": 0, "ok": unanswered == 0},
        "degraded_answers": {"value": degraded, "limit": 0, "ok": degraded == 0},
        "sample_answers": {"value": len(pick), "limit": 1, "ok": len(pick) >= 1},
    }
