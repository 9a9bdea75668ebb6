"""CLI: python -m reporter_tpu.serve [--warmup] <config.json> <host:port>

Mirrors the reference service invocation
(py/reporter_service.py:278-299: ``reporter_service.py conf address``).
Env: MATCHER_BIND_ADDR / MATCHER_LISTEN_PORT override the address like the
reference's container env (README.md Env Var Overrides); THRESHOLD_SEC as in
reporter_service.py:55-57.

``--warmup``: pre-dispatch EVERY configured (batch rung, length bucket,
viterbi kernel) shape plus the carried-state streaming program before the
engine attaches — /report answers retryable 503s until the warm set is
compiled, and the first accepted request can no longer hit a compile
stall (docs/performance.md); a failed warm pass exits non-zero.  With
the persistent compilation cache (utils/jaxenv) the restart cost is a
disk replay, not an XLA compile.  Without the flag the
background per-bucket warm of the deferred boot runs as before (config
key "warmup": false disables that entirely).

A replica may span a local device mesh (matcher config keys ``devices``
/ ``graph_devices``, or the REPORTER_DEVICES / REPORTER_GRAPH_DEVICES
env overrides): one logical matcher per replica, mesh-inside-replica x
fleet-across-replicas (docs/serving-fleet.md).  /health advertises the
resolved "capacity" block — mesh shape, scaled admission caps, and the
device-resident byte budgets — which the fleet router's weighted
ranking and the supervisor's autoscaler consume; with --warmup the
pre-dispatched programs ARE the mesh-sharded ones, so the first
mesh-sharded request never compiles inline.
"""

import logging
import os
import sys

from ..obs import flight as obs_flight
from ..obs import log as obs_log
from ..utils.jaxenv import ensure_platform
from .service import ReporterService, build_matcher, parse_service_config


def main(argv):
    # the serve-entrypoint env defaults below must not outlive main(): an
    # in-process caller (tests drive the CLI error paths directly) would
    # otherwise leak serving defaults into library-default code, silently
    # flipping e.g. the session arena on for every matcher built after
    # (the serving process itself never notices — it lives inside main()).
    _env_defaulted = [k for k in ("REPORTER_QUALITY_AUX", "REPORTER_SPARSE",
                                  "REPORTER_SESSION_ARENA", "REPORTER_WIRE",
                                  "REPORTER_HOST_PACK")
                      if k not in os.environ]
    try:
        return _main(argv)
    finally:
        for k in _env_defaulted:
            os.environ.pop(k, None)


def _main(argv):
    # the shared log switch (REPORTER_LOG_FORMAT=json|text,
    # REPORTER_LOG_LEVEL) + the flight recorder's SIGTERM/fatal disk dump
    obs_log.configure()
    obs_flight.install_shutdown_dump()
    ensure_platform()
    # kernel confidence diagnostics default ON for the serving entrypoint
    # (library callers and the bit-exact differential suites keep the
    # config default of off): the matcher built below reads this env, so
    # every served match carries margins and the flight recorder can
    # retain ambiguous decodes (docs/match-quality.md).  An explicit
    # REPORTER_QUALITY_AUX=0 still disables.
    os.environ.setdefault("REPORTER_QUALITY_AUX", "1")
    # the sparse-gap matching model defaults ON for the serving entrypoint
    # (docs/match-quality.md "Sparse gaps"): traces at the reference
    # BatchingProcessor's ≥45 s operating point dispatch through the
    # time-adaptive program variants (calibrated per cohort when
    # $REPORTER_CALIBRATION points at a CALIBRATION.json).  Library
    # callers and the bit-exact differential suites keep the config
    # default of off; an explicit REPORTER_SPARSE=0 reverts the serving
    # path bit-for-bit to the dense model.
    os.environ.setdefault("REPORTER_SPARSE", "1")
    # device-resident session arenas default ON for the serving entrypoint
    # (docs/performance.md "Device-resident session arenas"): carried
    # Viterbi beams stay in a hot device slab between streaming submits,
    # so a packed session step is one donated in-place dispatch with zero
    # per-step host<->device beam transfers.  Library callers and the
    # bit-exact differential suites keep the config default of off; an
    # explicit REPORTER_SESSION_ARENA=0 reverts the serving path
    # bit-for-bit to the host-carried wire form.
    os.environ.setdefault("REPORTER_SESSION_ARENA", "1")
    # columnar host data plane knobs (docs/performance.md "The columnar
    # host data plane"): both default ON everywhere (the packer is
    # bit-identical; the binary wire is negotiated per request), so these
    # setdefaults only make the serving defaults EXPLICIT for /statusz
    # readers and child processes.  REPORTER_WIRE=0 stops advertising/
    # accepting the binary wire; REPORTER_HOST_PACK=0 reverts packing to
    # the legacy per-row loop bit-for-bit.  Both restore on main() return
    # (_env_defaulted above) so in-process CLI callers don't leak them.
    os.environ.setdefault("REPORTER_WIRE", "1")
    os.environ.setdefault("REPORTER_HOST_PACK", "1")
    # conf path: positional arg, else $MATCHER_CONF_FILE — the reference's
    # container default (README.md Env Var Overrides: MATCHER_CONF_FILE).
    # With the env set, the single positional may be the bind address.
    args = list(argv[1:])
    full_warm = "--warmup" in args
    if full_warm:
        args = [a for a in args if a != "--warmup"]
    env_conf = os.environ.get("MATCHER_CONF_FILE")

    def _parses_as_addr(a):
        # host:port, :port, or a bare port -- a typo'd config path with a
        # ':' in it must NOT silently become a bind address (ADVICE r04)
        _host, _sep, port = a.rpartition(":")
        return (port or a).isdigit()

    if args and not (env_conf and len(args) == 1 and _parses_as_addr(args[0])
                     and not os.path.exists(args[0])):
        conf_path, addr_args = args[0], args[1:]
        chosen = "positional argument"
    else:
        conf_path, addr_args = env_conf, args
        chosen = "MATCHER_CONF_FILE"
    if conf_path:
        logging.info("config: %s (from %s)", conf_path, chosen)
    if not conf_path:
        sys.stderr.write(
            "usage: python -m reporter_tpu.serve [--warmup] <config.json> [host:port]\n"
            "       (or set MATCHER_CONF_FILE)\n")
        return 1
    try:
        # cheap half only (no jax, no network IO): a broken config still
        # fails fast, before the socket binds
        cfg, conf = parse_service_config(conf_path)
    except Exception as e:
        sys.stderr.write("Problem with config file: %s\n" % (e,))
        return 1

    if addr_args:
        if ":" in addr_args[0]:
            host, port = addr_args[0].rsplit(":", 1)
        else:
            host, port = "0.0.0.0", addr_args[0]
    else:
        host = os.environ.get("MATCHER_BIND_ADDR", "0.0.0.0")
        port = os.environ.get("MATCHER_LISTEN_PORT", "8002")

    # deferred boot: bind the socket with NO matcher, then build the
    # engine (network + UBODT + backend init) on the warmup thread, so a
    # slow backend init or UBODT build never leaves the service dark:
    # /health answers "warming" from the first second and /report
    # returns retryable 503s until the engine attaches.
    batch = conf.get("batch", {})
    service = ReporterService(
        None,
        max_batch=int(batch.get("max_batch", 64)),
        max_wait_ms=float(batch.get("max_wait_ms", 10.0)),
        max_inflight=(int(batch["max_inflight"])
                      if "max_inflight" in batch else None),
        # the streaming session batcher's fill knobs: a much shorter wait
        # than the windowed batcher (point latency is the product)
        session_max_batch=int(batch.get("session_max_batch", 256)),
        session_wait_ms=float(batch.get("session_wait_ms", 2.0)),
        # fault-domain knobs (docs/robustness.md): bounded submit queue +
        # shedding, server deadline, device watchdog, poison quarantine,
        # degraded-mode re-attach probing; REPORTER_* env overrides apply
        # on top of the config block
        robustness=conf.get("robustness", {}),
        # serving objectives (docs/observability.md "The SLO engine"):
        # availability / latency quantiles / degraded fraction measured
        # over sliding windows at GET /debug/slo; REPORTER_SLO_* env
        # knobs tune the defaults when the config has no "slo" block
        slo=conf.get("slo"),
        # match-quality plane (docs/match-quality.md): shadow-oracle
        # sampling cadence + agreement objective; REPORTER_QUALITY_*
        # env knobs override the config "quality" block
        quality=conf.get("quality"),
        # fleet economics (docs/economics.md): price-per-chip-hour,
        # demand-history dir/bounds, capacity window; REPORTER_COST_* /
        # REPORTER_HISTORY_* env knobs override the config block
        economics=conf.get("economics"),
    )
    httpd = service.make_server(host, int(port))
    # log the BOUND port: with port 0 the OS picks one, and supervisors /
    # tests recover it from this line
    logging.info("reporter_tpu service on %s:%s (engine deferred)",
                 host, httpd.server_port)

    # containers stop with SIGTERM; the contract is a GRACEFUL DRAIN
    # (docs/serving-fleet.md): on the first SIGTERM/SIGINT the service
    # stops admitting (new /report requests answer 503 {"status":
    # "draining"} + Retry-After; /health flips to 503 "draining" so the
    # fleet router rotates traffic off), inflight requests run to
    # completion, then the listener closes, the flight recorder flushes,
    # and the process exits 0.  The drain window is bounded by
    # REPORTER_DRAIN_GRACE_S (default 30; keep it under the container
    # runtime's stop grace period).  The handler disarms after the first
    # signal, so a second SIGTERM force-terminates rather than unwinding
    # the cleanup.
    import signal
    import threading
    import time as _time

    httpd.daemon_threads = False
    httpd.block_on_close = True
    try:
        drain_grace = float(os.environ.get("REPORTER_DRAIN_GRACE_S", 30.0))
    except ValueError:
        drain_grace = 30.0
    drained = threading.Event()

    try:
        drain_linger = float(os.environ.get("REPORTER_DRAIN_LINGER_S", 1.5))
    except ValueError:
        drain_linger = 1.5

    def _drain_then_stop():
        service.begin_drain()
        deadline = _time.monotonic() + max(0.0, drain_grace)
        while _time.monotonic() < deadline:
            if service.idle():
                break
            _time.sleep(0.05)
        if not service.idle():
            logging.warning(
                "drain grace (%.1fs) expired with requests still inflight; "
                "closing anyway", drain_grace)
        # beam-handoff window (docs/serving-fleet.md): with open sessions,
        # linger briefly after going idle so the router's prober can see
        # the draining /health and pull GET /sessions?export=1 before the
        # listener closes — exiting the instant inflight work finishes
        # would race the handoff and force rebuild-from-replay on the
        # inheriting replica.  Bounded by the remaining grace; 0 disables.
        store = getattr(service, "session_store", None)
        if store is not None and len(store) > 0 and drain_linger > 0:
            linger_until = min(_time.monotonic() + drain_linger, deadline)
            logging.info("drain: lingering up to %.1fs for session handoff "
                         "(%d open sessions)", drain_linger, len(store))
            while _time.monotonic() < linger_until:
                _time.sleep(0.05)
        httpd.shutdown()
        # a request may have slipped past the last idle() sample while
        # the accept loop wound down: give it a moment to finish before
        # cutting sockets (cutting an active one would reset its client)
        deadline = _time.monotonic() + 2.0
        while _time.monotonic() < deadline and not service.idle():
            _time.sleep(0.05)
        # cut the now-idle keep-alive connections so server_close's
        # handler join returns promptly instead of waiting out the 30 s
        # idle timeout (the router holds pooled sockets to every replica)
        getattr(httpd, "close_lingering", lambda: None)()

    def _on_stop_signal(signum, frame):
        # only spawn a thread from the handler: the drain loop must not
        # run in signal context.  Disarm so the SECOND signal kills.
        try:
            signal.signal(signum, signal.SIG_DFL)
        except (ValueError, OSError):  # pragma: no cover
            pass
        if not drained.is_set():
            drained.set()
            threading.Thread(target=_drain_then_stop, daemon=True,
                             name="drain").start()

    for _sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(_sig, _on_stop_signal)
        except ValueError:  # pragma: no cover - not the main thread
            pass

    try:
        # build the engine, then pre-compile the hot shapes, all BEHIND
        # the bound socket on a background thread: the service accepts
        # (and /health answers, with "warming": true) from the first
        # second, while backend init + cold-start compiles proceed -- a
        # cold boot must not leave clients dark (the reference client's
        # socket budget is 10 s, HttpClient.java:80-88).  Requests racing
        # the warmup just compile their shape inline, exactly as with
        # warmup disabled; the jit cache dedups.  "warmup": false skips
        # only the shape pre-compiles.
        import threading

        service.warming = True
        stop_warm = threading.Event()

        def _warm():
            try:
                try:
                    matcher = build_matcher(cfg, conf)
                except Exception:
                    # a failed engine build must not leave a zombie
                    # listener returning 503s forever: log and stop the
                    # serve loop (main exits nonzero on batcher is None)
                    logging.exception("engine build failed; shutting down")
                    threading.Thread(target=httpd.shutdown,
                                     daemon=True).start()
                    return
                if full_warm:
                    # --warmup: compile EVERY configured (batch rung,
                    # length bucket, kernel) shape plus the long-trace
                    # streaming programs (the chunk-batched precompute +
                    # chain pair by default, the legacy fused carry with
                    # REPORTER_LONG_PRECOMPUTE=0) BEFORE the engine
                    # attaches, so the first accepted request cannot hit a
                    # compile stall.  Shape by shape so a shutdown can
                    # stop between compiles.  A failed pass stops the
                    # server (exit non-zero): a device that cannot run the
                    # warm set must not take traffic.
                    try:
                        for n in matcher.cfg.length_buckets:
                            if stop_warm.is_set():
                                break
                            matcher.warmup(lengths=[n])
                        if not stop_warm.is_set():
                            # the long-trace streaming programs AND the
                            # per-vehicle session-step shapes: the first
                            # streaming point of a fresh boot must not
                            # compile inline (tests/test_warmup_cache.py)
                            matcher.warmup(lengths=[], carry_chain=True,
                                           session_step=True)
                    except Exception:
                        logging.exception("--warmup pass failed; shutting down")
                        threading.Thread(target=httpd.shutdown,
                                         daemon=True).start()
                        return
                service.attach_matcher(matcher)
                cap = (matcher.capacity_summary()
                       if hasattr(matcher, "capacity_summary") else {})
                mesh_shape = cap.get("mesh") or {}
                logging.info(
                    "engine live (backend=%s, %d edges, %d device(s), "
                    "mesh dp=%d gp=%d)", matcher.backend,
                    matcher.arrays.num_edges, int(cap.get("devices") or 1),
                    int(mesh_shape.get("dp") or 1),
                    int(mesh_shape.get("gp") or 1))
                if conf.get("warmup", True) and not full_warm:
                    # background warm of the deferred boot: requests racing
                    # it just compile their shape inline, exactly as with
                    # warmup disabled.  Shape-by-shape so a shutdown can
                    # stop between compiles (an in-flight XLA compile
                    # itself is not interruptible).  A warmup failure past
                    # this point is non-fatal: the engine serves, shapes
                    # compile inline.
                    try:
                        for n in matcher.cfg.length_buckets:
                            if stop_warm.is_set():
                                break
                            matcher.warmup(lengths=[n])
                    except Exception:
                        logging.exception(
                            "shape warmup failed; serving without pre-compiles")
            finally:
                service.warming = False

        warm_thread = threading.Thread(
            target=_warm, daemon=True, name="warmup")
        warm_thread.start()
        httpd.serve_forever()
        if drained.is_set():
            logging.info("drained (signal); shutting down")
            # let the in-flight engine build / warmup compile finish
            # before tearing down the runtime under it (bounded: anything
            # longer is the container's SIGKILL to take)
            stop_warm.set()
            warm_thread.join(timeout=120.0)
            # flush the flight recorder on the way out — the drain
            # window's own traces (refusals, last completions) included
            from ..utils.shutdown import run_shutdown_hooks

            run_shutdown_hooks()
        elif service.batcher is None:
            # serve loop ended with no engine: the build failed — dump the
            # flight recorder like any other fatal exit before bailing
            from ..utils.shutdown import run_shutdown_hooks

            run_shutdown_hooks()
            return 1
    except KeyboardInterrupt:
        # belt-and-braces: an interrupt that bypassed the drain handler
        # (e.g. raised before the signal hookup) still exits cleanly
        logging.info("shutting down (interrupt)")
        service.draining = True
        stop_warm.set()
        warm_thread.join(timeout=120.0)
    finally:
        # EVERY exit path releases the listening socket — an external
        # httpd.shutdown() used to fall through to `return 0` with the
        # socket still open (ADVICE r05)
        httpd.server_close()
        # flush the demand-history ring and drop the scrape collectors
        service.economics.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
