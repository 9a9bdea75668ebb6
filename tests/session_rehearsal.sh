#!/usr/bin/env bash
# Session-matcher gating rehearsal (the CI `session-rehearsal` leg;
# runnable locally): tools/fleet.py boots 3 warmed serve replicas behind
# the session-affine router, tools/loadgen.py streams an open-loop
# PER-POINT fleet ("stream": true single-point /report bodies on
# uuid-affine sessions) against the ROUTER, and mid-stream one replica is
# SIGTERMed — a graceful drain, the lifecycle the beam handoff rides:
#
#   t+8s   replica rep-1 gets SIGTERM: it refuses new work 503
#          "draining", the router rotates its vehicles off, pulls
#          GET /sessions?export=1 and POSTs each serialised beam to the
#          replica that now inherits the uuid; the supervisor respawns
#          the drained process and the router's recovery sweep
#          rebalances the sessions back (dropping the source copies so
#          the fleet points ledger stays exact)
#
# and the verdict must hold:
#
#   1. loadgen rc 0 over the WHOLE run: availability + the per-POINT
#      stream p99 objective met, with the router's client-truth fleet
#      /debug/slo verdict agreeing (--server-slo), and the
#      stream_p99_latency objective non-vacuous and ok on the server
#   2. zero lost or duplicated session answers: every scheduled point
#      got exactly one answer, all of them 200/shed-class, and the
#      fleet-wide session ledger (router GET /sessions points_total)
#      equals the count of 200-answered points EXACTLY — every point
#      folded into exactly one live session store, across the drain,
#      the handoff and the rebalance
#   3. the handoff actually moved beams: the router's
#      reporter_router_session_handoffs_total{outcome="moved"|"rebalanced"}
#      counted > 0 and some replica imported sessions
#      (reporter_sessions_total{event="imported"} > 0 on the federated
#      scrape)
#   4. the headline: per-point p99 of the streaming path is >= 5x lower
#      than the windowed-rebatch baseline (--stream-window 8) at the
#      SAME offered point rate — the window-fill wait the session
#      matcher exists to eliminate (ISSUE 12 acceptance)
#   5. the arena leg (ISSUE 18): the whole run holds with the
#      device-resident session arena ON (REPORTER_SESSION_ARENA=1, the
#      serving default) — every /statusz shows a live session_arena
#      block, a mid-stream steady-state window shows the
#      reporter_session_arena_readbacks_total counter FLAT (a packed
#      step performs zero per-step host readbacks; the counter may grow
#      only on checkpoint/drain/export), and after the drain + rebalance
#      the surviving replicas' counters HAVE grown (the handoff's
#      pop/export reads are exactly the reads the counter exists for)
#
# Usage: tests/session_rehearsal.sh [workdir]
set -euo pipefail

# shared spawn/trap/cleanup/wait helpers (tests/rehearsal_lib.sh)
. "$(dirname "$0")/rehearsal_lib.sh"
export REPORTER_RETRY_BASE_S="${REPORTER_RETRY_BASE_S:-0.05}"
# snappy probing: the drain window is short, the handoff rides the probe
export REPORTER_ROUTER_PROBE_S="${REPORTER_ROUTER_PROBE_S:-0.25}"
# the drained replica lingers after idle so the router can pull its
# sessions before the listener closes (docs/serving-fleet.md)
export REPORTER_DRAIN_LINGER_S="${REPORTER_DRAIN_LINGER_S:-2.0}"
# the serving objectives BOTH sides state (loadgen --server-slo compares
# like with like); the stream objective is the per-point gate
export REPORTER_SLO_AVAILABILITY=0.95
export REPORTER_SLO_P99_MS=8000
export REPORTER_SLO_P999_MS=0
export REPORTER_SLO_DEGRADED_FRAC=0
export REPORTER_SLO_STREAM_P99_MS=2500
# the arena leg: carried beams device-resident (the serving default —
# pinned explicitly so this gate keeps meaning it even if the default
# moves); the whole drain/handoff/ledger arc below runs with slot-handle
# sessions and must not move a bit
export REPORTER_SESSION_ARENA=1
reh_init "${1:-}" reporter-session
export JAX_COMPILATION_CACHE_DIR="$WORK/xla-cache"
ROUTER_PORT=18081
BASE_PORT=18082
echo "session rehearsal workdir: $WORK"

cat > "$WORK/config.json" <<EOF
{
  "network": {"type": "grid", "rows": 8, "cols": 8, "spacing_m": 200},
  "matcher": {"sigma_z": 4.07, "beta": 3.0, "search_radius": 50.0,
              "length_buckets": [16],
              "session_buckets": [4, 16],
              "session_tail_points": 64,
              "warmup_batch_sizes": [1, 4, 16]},
  "backend": "jax",
  "batch": {"max_batch": 64, "max_wait_ms": 5, "session_wait_ms": 2}
}
EOF

# ---- boot the fleet -------------------------------------------------------
python tools/fleet.py --config "$WORK/config.json" --replicas 3 \
    --base-port "$BASE_PORT" --router-port "$ROUTER_PORT" \
    --workdir "$WORK" --warmup --cpu-default --drain-grace 20 \
    > "$WORK/fleet.log" 2>&1 &
FLEET_PID=$!
reh_track_fleet "$FLEET_PID" "$WORK"

if ! reh_wait_fleet "http://127.0.0.1:$ROUTER_PORT" 3 "$BASE_PORT" 3 600 warmed; then
    echo "FAIL: fleet never reached 3 warmed replicas; fleet log tail:"
    tail -30 "$WORK/fleet.log"
    for f in "$WORK"/replica-*.log "$WORK"/router.log; do
        echo "--- $f"; tail -10 "$f" 2>/dev/null || true
    done
    exit 1
fi
echo "fleet up: 3 warmed replicas behind the router"

# every replica serves with a live arena: /statusz session_arena non-null
python - "$BASE_PORT" <<'EOF'
import json, sys, urllib.request

base = int(sys.argv[1])
for i in range(3):
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/statusz" % (base + i), timeout=15) as f:
        st = json.loads(f.read().decode())
    a = st.get("session_arena")
    assert a is not None, "replica %d serves without a session arena" % i
    assert a["hot_slots"] >= 1 and a["slot_bytes"] > 0, a
print("session arena live on all 3 replicas (hot_slots=%d, slot_bytes=%d)"
      % (a["hot_slots"], a["slot_bytes"]))
EOF

# ---- phase 1: the windowed-rebatch BASELINE at the same point rate --------
# (short, chaos-free: the number the streaming path is judged against)
python tools/loadgen.py --url "http://127.0.0.1:$ROUTER_PORT" \
    --stream --stream-window 8 \
    --rate 25 --duration 12 --vehicles 24 --points 64 --window 16 --grid 8 \
    --seed 7 --concurrency 32 --timeout-s 8 \
    --slo-availability 0.95 --slo-p99-ms 120000 \
    --out "$WORK/loadgen_windowed.json"
echo "windowed-rebatch baseline captured"

# ---- phase 2: per-point streaming, SIGTERM drain mid-stream ---------------
python tools/loadgen.py --url "http://127.0.0.1:$ROUTER_PORT" \
    --stream \
    --rate 25 --duration 30 --vehicles 24 --points 64 --window 16 --grid 8 \
    --seed 11 --concurrency 32 --timeout-s 8 \
    --slo-availability 0.95 --slo-p99-ms 8000 --server-slo \
    --dump-samples "$WORK/stream_samples.jsonl" \
    --out "$WORK/loadgen_stream.json" &
LOADGEN_PID=$!

# steady-state transfer-counter window: two scrapes of every replica's
# reporter_session_arena_readbacks_total mid-stream, BEFORE any drain or
# export — the delta must be ZERO (a packed session step moves no beam
# bytes host-side; only checkpoint/drain/export may grow the counter)
_scrape_readbacks() {
    python - "$BASE_PORT" <<'EOF'
import sys, urllib.request

sys.path.insert(0, ".")
from reporter_tpu.obs.quantile import parse_metrics

base = int(sys.argv[1])
tot = 0
for i in range(3):
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % (base + i), timeout=15) as f:
        m = parse_metrics(f.read().decode())
    for _lv, v in m.get("reporter_session_arena_readbacks_total",
                        {}).items():
        tot += int(v)
print(tot)
EOF
}
sleep 3
RB_STEADY_0=$(_scrape_readbacks)
sleep 4
RB_STEADY_1=$(_scrape_readbacks)
if [ "$RB_STEADY_0" != "$RB_STEADY_1" ]; then
    echo "FAIL: arena readbacks grew $RB_STEADY_0 -> $RB_STEADY_1 during"
    echo "      steady-state streaming — a per-step host transfer leaked"
    exit 1
fi
echo "steady-state transfer counter flat: $RB_STEADY_0 readbacks across" \
     "both mid-stream scrapes (zero per-step host readbacks)"

sleep 1
VICTIM_PID=$(python -c "
import json; s = json.load(open('$WORK/fleet.json'))
print(s['replicas'][1]['pid'])")
DRAIN_EPOCH=$(python -c "import time; print(time.time())")
kill -TERM "$VICTIM_PID"
echo "SIGTERMed replica rep-1 (pid $VICTIM_PID) at $DRAIN_EPOCH — graceful drain + beam handoff"

set +e
wait "$LOADGEN_PID"
LOADGEN_RC=$?
set -e
if [ "$LOADGEN_RC" != 0 ]; then
    echo "FAIL: loadgen rc $LOADGEN_RC — the streaming SLO did not survive"
    echo "      a graceful drain (artifact: loadgen_stream.json)"
    python -c "
import json; a = json.load(open('$WORK/loadgen_stream.json'))
print(json.dumps({k: a[k] for k in ('status', 'quantiles', 'slo')}, indent=1))" \
        2>/dev/null || true
    tail -20 "$WORK/router.log"
    exit 1
fi
echo "loadgen streaming SLO verdict: PASS (rc 0) across the drain"

# let the recovery rebalance + source drops settle before reading ledgers
sleep 3

# ---- assertions -----------------------------------------------------------
python - "$WORK" "http://127.0.0.1:$ROUTER_PORT" "$DRAIN_EPOCH" <<'EOF'
import json, sys, urllib.request

work, router, drain_epoch = sys.argv[1], sys.argv[2], float(sys.argv[3])
sys.path.insert(0, ".")
from reporter_tpu.obs.quantile import parse_metrics

def get(url):
    with urllib.request.urlopen(url, timeout=15) as f:
        return json.loads(f.read().decode())

art = json.load(open(work + "/loadgen_stream.json"))
base = json.load(open(work + "/loadgen_windowed.json"))
rows = [json.loads(l) for l in open(work + "/stream_samples.jsonl")]

# 1b. the per-point stream objective on the SERVER side: non-vacuous, ok
server = art["slo"]["server"]
assert server and server.get("ok") is True, "router fleet verdict not ok"
obj = next((o for o in server.get("objectives", ())
            if o.get("name") == "stream_p99_latency"), None)
assert obj is not None, "stream_p99_latency objective missing on the router"
assert obj.get("value") is not None, "stream objective vacuous (no traffic)"
assert obj.get("ok") is True, obj
print("per-point fleet SLO: stream p99 %.1f ms <= %.0f ms target"
      % (obj["value"] * 1000.0, obj["target"] * 1000.0))

# 2. zero lost / duplicated answers, and the exact fleet points ledger
assert len(rows) == art["requests"], "sample rows != scheduled points"
allowed = {200, 429, 503}
bad = [r for r in rows if r["code"] not in allowed]
assert not bad, "non-shed client errors: %r" % bad[:5]
n200 = sum(1 for r in rows if r["code"] == 200)
assert n200 >= 0.95 * len(rows), (n200, len(rows))
fleet = get(router + "/sessions")
assert fleet["points_total"] == n200, (
    "session points ledger %d != %d answered points — a point was lost "
    "or duplicated across the drain/handoff (%r)"
    % (fleet["points_total"], n200, fleet["replicas"]))
print("ledger exact: %d answered points == %d points in %d live sessions "
      "across %s" % (n200, fleet["points_total"], fleet["sessions"],
                     sorted(fleet["replicas"])))

# 3. the handoff moved beams (drain export -> import, or the recovery
# rebalance) and a replica imported them
with urllib.request.urlopen(router + "/metrics?pull=1", timeout=15) as f:
    m = parse_metrics(f.read().decode())
ho = {dict(lv).get("outcome"): v
      for lv, v in m.get("reporter_router_session_handoffs_total",
                         {}).items()}
moved = int(ho.get("moved", 0)) + int(ho.get("rebalanced", 0))
assert moved > 0, "no session beams moved across the drain: %r" % ho
imported = sum(
    v for lv, v in m.get("reporter_sessions_total", {}).items()
    if dict(lv).get("event") == "imported" and "replica" in dict(lv))
assert imported > 0, "no replica imported handed-off sessions"
assert int(ho.get("import_failed", 0)) == 0, ho
print("beam handoff: %d moved/rebalanced (%r), %d imported replica-side"
      % (moved, ho, imported))

# 4. the headline: streaming per-point p99 >= 5x lower than the
# windowed-rebatch baseline at the same offered point rate
sp99 = art["quantiles"]["p99_ms"]
wp99 = base["quantiles"]["p99_ms"]
assert sp99 and wp99, (sp99, wp99)
ratio = wp99 / sp99
assert ratio >= 5.0, (
    "streaming per-point p99 %.1f ms vs windowed-rebatch %.1f ms: "
    "only %.1fx (< 5x acceptance)" % (sp99, wp99, ratio))
print("per-point p99: stream %.1f ms vs windowed-rebatch %.1f ms "
      "(%.1fx lower; >= 5x required)" % (sp99, wp99, ratio))
EOF

# ...and the counter DOES grow on export — the only sanctioned readback.
# (The drain's own export readbacks died with the drained process, and
# the recovery rebalance may still be waiting on the respawn's warmup,
# so drive the seam explicitly: a wire export on every live replica must
# read each resident beam off the device exactly where the streaming
# steps read nothing.)
RB_BEFORE_EXPORT=$(_scrape_readbacks)
for i in 0 1 2; do
    curl -sf "http://127.0.0.1:$((BASE_PORT + i))/sessions?export=1" \
        > /dev/null || true
done
RB_AFTER_EXPORT=$(_scrape_readbacks)
if [ "$RB_AFTER_EXPORT" -le "$RB_BEFORE_EXPORT" ]; then
    echo "FAIL: arena readbacks $RB_BEFORE_EXPORT -> $RB_AFTER_EXPORT"
    echo "      across a fleet-wide wire export — the export did not read"
    echo "      the resident beams off device (are sessions resident?)"
    exit 1
fi
echo "arena readbacks grow only on export: $RB_BEFORE_EXPORT ->" \
     "$RB_AFTER_EXPORT across an explicit fleet-wide wire export" \
     "(steady-state window above stayed flat)"

# ---- graceful fleet drain: exit 0, nothing stranded -----------------------
reh_stop_fleet
echo "session rehearsal OK (artifacts in $WORK)"
