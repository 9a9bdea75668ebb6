"""The stream client: its keep-alive pool against a server that closes idle
connections (a burst that finds the connections of an earlier burst closed is
still answered in full; the server's 30 s idle timeout, shortened here), and
how it tells a late answer from none."""

import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                "drivers"))

import serve  # noqa: E402


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    timeout = 0.5  # idle keep-alive connections close, as serve's do after 30 s

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        time.sleep(0.02)
        body = b'{"ok": 1}'
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *_args):
        pass


def test_bursts_over_idle_closed_connections():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _Handler)
    srv.daemon_threads = True
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    try:
        # a burst opens 20 connections, a trickle keeps one busy while the
        # server closes the others, and a second burst comes
        sched = sorted([(0.0, b"{}")] * 20 + [(0.1 * k, b"{}") for k in range(1, 16)]
                       + [(2.0, b"{}")] * 20, key=lambda r: r[0])
        stats = {}
        out = serve.drive(srv.server_address[1], [(sched, None)], 10.0, stats)[0]
    finally:
        srv.shutdown()
        srv.server_close()
        th.join()
    assert [r[3] for r in out] == [200] * len(sched)
    assert stats["evicted"] + stats["resent"] > 0


def test_late_answer_counts_as_latency_not_as_unanswered():
    ok = (1.0, 1.0, 1.02, 200, b'{"datastore": {}}')
    late = (1.0, 3.5, 12.5, 200, b'{"datastore": {}}')
    shed = (1.0, 1.0, 1.01, 429, b'{"error": "overloaded"}')
    never = (1.0, 1.0, None, 0, b"TimeoutError()")
    degraded = (1.0, 1.0, 1.03, 200, b'{"degraded": true}')
    assert [serve._answered(r) for r in (ok, late, shed, never, degraded)] == [
        True, True, False, False, False]
    assert serve._outcome(ok, 10.0) == pytest.approx(0.02)
    assert [serve._outcome(r, 10.0) for r in (late, shed, never, degraded)] == [None] * 4
