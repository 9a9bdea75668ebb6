#!/usr/bin/env bash
# Self-driving-fleet gating rehearsal (the CI `overload-rehearsal` leg;
# runnable locally): tools/fleet.py boots ONE warmed replica behind the
# router with the burn-rate autoscaler, session checkpointing (sync) and
# the adaptive controllers ON, then drives the traffic shapes the other
# rehearsals never exercise (docs/serving-fleet.md "Self-driving
# fleet"):
#
#   phase 0  diurnal ramp at a survivable rate — the green baseline: the
#            stated objectives hold on the minimum fleet (rc 0)
#   phase A  flash crowd (regional-skewed): offered rate jumps ~10x; the
#            fleet burn alert AND the sustained-queue gate fire together
#            and the autoscaler spawns a --warmup replica that the
#            router HOLDS OUT of the rendezvous ring until /health
#            reports attached+warmed — gated on the scale-up happening
#            and on ZERO requests served by the new replica before its
#            admission instant
#   phase B  sustained overload: offered rate far above capacity with a
#            small bounded queue — the only acceptable outcome is
#            shedding exactly down to capacity: every response is
#            200/429/503 (no timeouts, no 5xx), sheds are real, and the
#            ADMITTED traffic's p99 still meets the latency objective
#   phase C  preemption + crawling drain under a per-point stream:
#            SIGKILL one replica mid-stream (its sync-mode checkpoint is
#            re-homed through the router by the supervisor), then
#            SIGTERM another (graceful drain whose beam-handoff export
#            is STALLED by the slow_drain chaos point) — gated on the
#            fleet session ledger equalling every 200-answered point
#            EXACTLY (zero lost, zero duplicated), with the rehome and
#            handoff counters proving the beams actually moved
#
# Usage: tests/overload_rehearsal.sh [workdir]
set -euo pipefail

# shared spawn/trap/cleanup/wait helpers (tests/rehearsal_lib.sh)
. "$(dirname "$0")/rehearsal_lib.sh"
export REPORTER_RETRY_BASE_S="${REPORTER_RETRY_BASE_S:-0.05}"
export REPORTER_ROUTER_PROBE_S="${REPORTER_ROUTER_PROBE_S:-0.25}"
export REPORTER_DRAIN_LINGER_S="${REPORTER_DRAIN_LINGER_S:-2.0}"
# snappy SLO windows so the multi-window burn gates can fire inside a
# CI-sized run (fast pair 6 s / slow 60 s)
export REPORTER_SLO_WINDOW_S=60
export REPORTER_SLO_AVAILABILITY=0.95
export REPORTER_SLO_P99_MS=1500
export REPORTER_SLO_P999_MS=0
export REPORTER_SLO_DEGRADED_FRAC=0
export REPORTER_SLO_STREAM_P99_MS=2500
# a small bounded submit queue makes the overload shed crisp (429 fast,
# never deep queueing) — the shape "shed down to capacity" needs
export REPORTER_MAX_QUEUE=48
# deterministic per-replica capacity for phases 0/A/B: every device-step
# finish() pays a fixed 150 ms (the slo-rehearsal device_hang pattern),
# so with max_batch 4 one replica serves ~15-25 req/s REGARDLESS of how
# fast the CI box is — a "flash crowd" and a "sustained overload" mean
# the same thing on every machine.  Phase C boots its OWN fleet with the
# throttle unset (streaming latency is its gate).
export REPORTER_FAULT_DEVICE_HANG="0.15"
# the fleet-economics plane (docs/economics.md): a pinned price so the
# ledger assertions are deterministic, and a fast history tick so the
# phase-B headroom-vs-shed-onset gate has per-second resolution.  The
# supervisor defaults REPORTER_HISTORY_DIR to <workdir>/history for its
# children and writes the cross-checked ledger to <workdir>/cost_ledger.json
export REPORTER_COST_PER_CHIP_HOUR=3.60
export REPORTER_HISTORY_TICK_S=0.5
reh_init "${1:-}" reporter-overload
export JAX_COMPILATION_CACHE_DIR="$WORK/xla-cache"
ROUTER_PORT=18091
BASE_PORT=18092
ROUTER_PORT_C=18097
BASE_PORT_C=18098
ROUTER_URL="http://127.0.0.1:$ROUTER_PORT"
ROUTER_URL_C="http://127.0.0.1:$ROUTER_PORT_C"
echo "overload rehearsal workdir: $WORK"

cat > "$WORK/config.json" <<EOF
{
  "network": {"type": "grid", "rows": 8, "cols": 8, "spacing_m": 200},
  "matcher": {"sigma_z": 4.07, "beta": 3.0, "search_radius": 50.0,
              "length_buckets": [16],
              "session_buckets": [4, 16],
              "session_tail_points": 64,
              "warmup_batch_sizes": [1, 4, 16]},
  "backend": "jax",
  "batch": {"max_batch": 4, "max_wait_ms": 5, "session_wait_ms": 2}
}
EOF

# ---- boot fleet A: ONE throttled replica, autoscaler armed ----------------
python tools/fleet.py --config "$WORK/config.json" --replicas 1 \
    --base-port "$BASE_PORT" --router-port "$ROUTER_PORT" \
    --workdir "$WORK" --warmup --cpu-default --drain-grace 20 \
    --autoscale --min-replicas 1 --max-replicas 3 \
    --scale-poll 0.5 --scale-cooldown 15 --scale-queue-high 4 \
    --scale-window 12 --scale-down-after 600 \
    > "$WORK/fleet.log" 2>&1 &
FLEET_PID=$!
reh_track_fleet "$FLEET_PID" "$WORK"

if ! reh_wait_fleet "$ROUTER_URL" 1 "$BASE_PORT" 1 600 warmed; then
    echo "FAIL: fleet never reached 1 warmed replica; fleet log tail:"
    tail -30 "$WORK/fleet.log"
    for f in "$WORK"/replica-*.log "$WORK"/router.log; do
        echo "--- $f"; tail -10 "$f" 2>/dev/null || true
    done
    exit 1
fi
echo "fleet up: 1 warmed replica behind the router (autoscaler armed)"

# ---- phase 0: diurnal ramp — the green baseline on the minimum fleet ------
python tools/loadgen.py --url "$ROUTER_URL" \
    --profile diurnal --rate 6 --duration 20 \
    --vehicles 24 --points 48 --window 16 --grid 8 \
    --seed 5 --concurrency 32 --timeout-s 8 \
    --slo-availability 0.95 --slo-p99-ms 8000 \
    --out "$WORK/loadgen_diurnal.json"
echo "phase 0 diurnal: objectives met on the minimum fleet"

# ---- phase A: flash crowd -> warmup-gated scale-up ------------------------
python tools/loadgen.py --url "$ROUTER_URL" \
    --profile flash:0.15:1.0:12 --rate 4 --duration 75 \
    --skew 0.7:0.25 \
    --vehicles 24 --points 48 --window 16 --grid 8 \
    --seed 7 --concurrency 64 --timeout-s 8 \
    --slo-availability 0 --slo-p99-ms 0 \
    --dump-samples "$WORK/flash_samples.jsonl" \
    --out "$WORK/loadgen_flash.json"

python - "$WORK" "$ROUTER_URL" <<'EOF'
import json, sys, urllib.request

work, router = sys.argv[1], sys.argv[2]
sys.path.insert(0, ".")
from reporter_tpu.obs.quantile import parse_metrics

events = [json.loads(l) for l in open(work + "/scale_events.jsonl")]
spawned = [e for e in events
           if e.get("event") == "spawned" and e.get("direction") == "up"]
admitted = [e for e in events if e.get("event") == "admitted"]
assert spawned, "the flash crowd never triggered a scale-up: %r" % events
assert admitted, "a spawned replica was never admitted (warmup gate): %r" \
    % events

# the router's scale-events counter billed the decision with its reason
with urllib.request.urlopen(router + "/metrics", timeout=10) as f:
    m = parse_metrics(f.read().decode())
ups = sum(v for lv, v in
          m.get("reporter_fleet_scale_events_total", {}).items()
          if dict(lv).get("direction") == "up"
          and dict(lv).get("reason") == "burn_and_queue")
assert ups >= 1, "no burn_and_queue scale-up on the router counter"

# ZERO cold-replica-served requests: no sample answered by a scaled-up
# replica before that replica's admission instant (/health
# attached+warmed — the router's hold-out releases it only then)
rows = [json.loads(l) for l in open(work + "/flash_samples.jsonl")]
admit_t = {e["replica"]: e["t_unix"] for e in admitted}
new_rids = set(admit_t)
# 2 s slack: the router's prober and the supervisor's admission poll
# both OBSERVE "warmed" slightly after it happens, the router first —
# a genuinely cold serve would precede admission by the whole 10 s+
# spawn-to-warm window, far outside this tolerance
cold = [r for r in rows
        if r["replica"] in new_rids
        and r["done_epoch"] < admit_t[r["replica"]] - 2.0]
assert not cold, "cold-replica-served requests: %r" % cold[:5]
served_new = sum(1 for r in rows if r["replica"] in new_rids)
# only shed-class residue is acceptable while one replica absorbs a 10x
# flash (the router answers 429/503 fast instead of queueing)
bad = [r for r in rows if r["code"] not in (200, 429, 503)]
assert not bad, "non-shed errors under the flash: %r" % bad[:5]
print("phase A flash: scale-up %d (admitted %s), %d requests served by "
      "the new replica(s), ZERO cold serves"
      % (ups, sorted(new_rids), served_new))
EOF

# ---- phase B: sustained overload -> shed exactly down to capacity ---------
# offered rate must beat the THROTTLED fleet ceiling (~80/s: 3 replicas
# x max_batch 4 / 0.15 s hang) through the router's failover-on-429,
# which effectively chains all three bounded queues (3 x 48 slots)
# before a shed ever reaches the client — so the client needs enough
# workers to keep the whole chain full (in-flight ~ rate x queue wait)
T_B0=$(date +%s)
python tools/loadgen.py --url "$ROUTER_URL" \
    --rate 140 --duration 25 \
    --vehicles 24 --points 48 --window 16 --grid 8 \
    --seed 11 --concurrency 320 --timeout-s 8 \
    --slo-availability 0 --slo-p99-ms 0 \
    --out "$WORK/loadgen_overload.json"

python - "$WORK" <<'EOF'
import json, sys

art = json.load(open(sys.argv[1] + "/loadgen_overload.json"))
status = art["status"]
# the ONLY acceptable outcome: 200s and fast sheds — no timeouts (the
# queue bound answers immediately), no 5xx, nothing dropped
assert set(status) <= {"200", "429", "503"}, status
n = sum(status.values())
n200 = status.get("200", 0)
shed = n - n200
assert art["shed_fraction"] is not None
assert abs(art["shed_fraction"] - shed / n) < 1e-3  # 4-decimal artifact
assert shed > 0.05 * n, (
    "the offered overload produced almost no sheds (%d/%d) — not an "
    "overload" % (shed, n))
# the fleet kept serving AT capacity while shedding the excess: the
# shed fraction tracks the excess offered load (offered minus the
# admitted throughput the fleet actually sustained)
assert art["admitted_rps"] and art["admitted_rps"] >= 5.0, art["admitted_rps"]
excess = 1.0 - art["admitted_rps"] / art["offered_rps"]
assert abs(art["shed_fraction"] - excess) < 0.15, (
    "shed fraction %.3f does not track the excess offered load %.3f"
    % (art["shed_fraction"], excess))
p99 = art["admitted_quantiles"]["p99_ms"]
assert p99 is not None and p99 <= 8000.0, (
    "admitted-traffic p99 %.0f ms blew the objective under overload "
    "— shedding is not protecting the served tail" % p99)
print("phase B overload: %d requests, shed %.1f%%, admitted %.1f/s at "
      "p99 %.0f ms — shed down to capacity, admitted tail protected"
      % (n, 100.0 * shed / n, art["admitted_rps"], p99))
EOF

# the capacity estimator is judged against observed truth: replaying the
# persistent demand-history rings (docs/economics.md leg 3), measured
# headroom must cross <= 0 within a bounded window of the replica's REAL
# first shed in phase B — an estimator that never goes negative under a
# genuine overload (or only long after the shedding started) is lying
python - "$WORK" "$T_B0" <<'EOF'
import glob, sys

sys.path.insert(0, ".")
from reporter_tpu.obs.economics import read_ring

work, t_b0 = sys.argv[1], float(sys.argv[2])
ONSET_SLACK_S = 15.0
verdicts = []
for ring in sorted(glob.glob(work + "/history/rep-*.jsonl")):
    ticks = [r for r in read_ring(ring) if r.get("t", 0) >= t_b0]
    t_shed = next((r["t"] for r in ticks
                   if (r.get("shed_rps") or 0) > 0), None)
    if t_shed is None:
        continue  # this replica never shed in phase B (e.g. late spawn)
    t_zero = next((r["t"] for r in ticks
                   if r.get("headroom") is not None
                   and r["headroom"] <= 0.0), None)
    verdicts.append((ring.rsplit("/", 1)[1], t_shed,
                     None if t_zero is None else t_zero - t_shed))
assert verdicts, (
    "phase B shed on the client but NO replica history ring recorded a "
    "shed tick — the demand history is not persisting what happened")
ok = [(name, dt) for name, _, dt in verdicts
      if dt is not None and abs(dt) <= ONSET_SLACK_S]
assert ok, (
    "measured headroom never crossed zero within %.0fs of the real shed "
    "onset on any shedding replica: %r" % (ONSET_SLACK_S, verdicts))
print("phase B headroom: crossed zero within %.0fs of shed onset on %s "
      "(all shedding replicas: %r)"
      % (ONSET_SLACK_S, [n for n, _ in ok], verdicts))
EOF

# ---- phase C: SIGKILL preemption + crawling drain under a stream ----------
# its OWN fleet: the capacity throttle comes off (streaming point
# latency is this phase's gate), sync session checkpointing and ONE
# stalled beam-handoff export per replica process go on, and the shared
# XLA cache makes the second boot a disk replay
reh_stop_fleet
echo "fleet A drained; booting fleet C (checkpoint sync + slow_drain)"
unset REPORTER_FAULT_DEVICE_HANG
export REPORTER_FAULT_SLOW_DRAIN="1.5:1"
WORKC="$WORK/fleetC"
mkdir -p "$WORKC"
python tools/fleet.py --config "$WORK/config.json" --replicas 3 \
    --base-port "$BASE_PORT_C" --router-port "$ROUTER_PORT_C" \
    --workdir "$WORKC" --warmup --cpu-default --drain-grace 20 \
    --session-checkpoint 1.0 --session-checkpoint-sync \
    > "$WORKC/fleet.log" 2>&1 &
FLEET_PID=$!
reh_track_fleet "$FLEET_PID" "$WORKC"
if ! reh_wait_fleet "$ROUTER_URL_C" 3 "$BASE_PORT_C" 3 600 warmed; then
    echo "FAIL: fleet C never reached 3 warmed replicas; log tail:"
    tail -30 "$WORKC/fleet.log"
    exit 1
fi

python tools/loadgen.py --url "$ROUTER_URL_C" \
    --stream \
    --rate 20 --duration 25 --vehicles 24 --points 64 --window 16 --grid 8 \
    --seed 13 --concurrency 32 --timeout-s 8 \
    --slo-availability 0.90 --slo-p99-ms 8000 \
    --dump-samples "$WORK/stream_samples.jsonl" \
    --out "$WORK/loadgen_stream.json" &
LOADGEN_PID=$!

sleep 8
VICTIM_PID=$(python -c "
import json; s = json.load(open('$WORKC/fleet.json'))
print(s['replicas'][0]['pid'])")
kill -9 "$VICTIM_PID"
echo "SIGKILLed replica rep-0 (pid $VICTIM_PID) holding live sessions"

sleep 8
# the drain leg: gracefully drain another live replica while its
# beam-handoff export is stalled by the armed slow_drain point
read -r DRAIN_PID DRAIN_URL <<< "$(python -c "
import json; s = json.load(open('$WORKC/fleet.json'))
reps = [r for r in s['replicas'] if r.get('pid')]
print(reps[-1]['pid'], reps[-1]['url'])")"
kill -TERM "$DRAIN_PID"
echo "SIGTERMed replica pid $DRAIN_PID (graceful drain, slow_drain armed)"
# catch the stall evidence LIVE off the drainer's own /metrics before
# its listener closes (the respawn's fresh registry would replace its
# federated snapshot, so post-hoc scrapes can't prove the stall)
python - "$DRAIN_URL" "$WORK/slow_drain_observed" <<'EOF'
import sys, time, urllib.request

sys.path.insert(0, ".")
from reporter_tpu.obs.quantile import parse_metrics

url, marker = sys.argv[1], sys.argv[2]
deadline = time.monotonic() + 15.0
while time.monotonic() < deadline:
    try:
        with urllib.request.urlopen(url + "/metrics", timeout=2) as f:
            m = parse_metrics(f.read().decode())
        fired = sum(v for lv, v in
                    m.get("reporter_faults_injected_total", {}).items()
                    if dict(lv).get("point") == "slow_drain")
        if fired >= 1:
            open(marker, "w").write(str(fired))
            print("slow_drain stall observed on the drainer (%d fired)"
                  % int(fired))
            sys.exit(0)
    except Exception:
        pass  # draining out / listener closing
    time.sleep(0.2)
sys.exit(0)  # judged by the marker file in the final assertion block
EOF

set +e
wait "$LOADGEN_PID"
LOADGEN_RC=$?
set -e
if [ "$LOADGEN_RC" != 0 ]; then
    echo "FAIL: loadgen rc $LOADGEN_RC — the streaming SLO did not survive"
    echo "      a SIGKILL + crawling drain (artifact: loadgen_stream.json)"
    python -c "
import json; a = json.load(open('$WORK/loadgen_stream.json'))
print(json.dumps({k: a[k] for k in ('status', 'quantiles', 'slo')}, indent=1))" \
        2>/dev/null || true
    tail -20 "$WORKC/router.log"
    exit 1
fi
python - "$WORK" "$ROUTER_URL_C" "$WORKC" <<'EOF'
import json, sys, time, urllib.request

work, router = sys.argv[1], sys.argv[2]
workc = sys.argv[3]
sys.path.insert(0, ".")
from reporter_tpu.obs.quantile import parse_metrics

def get(url):
    with urllib.request.urlopen(url, timeout=15) as f:
        return json.loads(f.read().decode())

rows = [json.loads(l) for l in open(work + "/stream_samples.jsonl")]
bad = [r for r in rows if r["code"] not in (200, 429, 503)]
assert not bad, "non-shed client errors under preemption: %r" % bad[:5]
n200 = sum(1 for r in rows if r["code"] == 200)

# THE acceptance gate: the fleet points ledger is EXACT — every
# 200-answered point lives in exactly one live session store, across a
# SIGKILL (checkpoint re-home), a crawling drain (handoff) and the
# recovery rebalances.  Zero lost, zero duplicated.  The read POLLS
# through the settling window: respawned replicas are still booting and
# a rebalance's atomic pop+import means a mid-move read legitimately
# undercounts for a moment — the ledger must CONVERGE to exact, and
# anything else it converges to is a real loss or duplication.
deadline = time.monotonic() + 60.0
fleet = None
while time.monotonic() < deadline:
    try:
        fleet = get(router + "/sessions")
        if fleet["points_total"] == n200:
            break
    except Exception:
        pass  # router mid-churn
    time.sleep(1.0)
assert fleet is not None and fleet["points_total"] == n200, (
    "session points ledger %d != %d answered points across SIGKILL + "
    "drain (%r)" % (fleet["points_total"], n200,
                    fleet and fleet["replicas"]))

# the machinery demonstrably fired: a checkpoint re-home (supervisor ->
# router POST /sessions) and a drain/rebalance handoff moved beams, and
# the slow_drain stall actually hit an export
events = [json.loads(l) for l in open(workc + "/scale_events.jsonl")]
rehomes = [e for e in events if e.get("event") == "rehome"]
assert rehomes and any(e.get("rehomed", 0) > 0 for e in rehomes), (
    "the SIGKILL'd replica's checkpoint was never re-homed: %r" % events)
with urllib.request.urlopen(router + "/metrics?pull=1", timeout=15) as f:
    m = parse_metrics(f.read().decode())
ho = {dict(lv).get("outcome"): v
      for lv, v in m.get("reporter_router_session_handoffs_total",
                         {}).items()}
assert int(ho.get("rehomed", 0)) > 0, ho
assert int(ho.get("moved", 0)) + int(ho.get("rebalanced", 0)) > 0, ho
# the stall was observed LIVE on the drainer's /metrics (the marker is
# written by the in-drain watcher above; the drained process's federated
# snapshot is replaced by its respawn, so it cannot testify post hoc)
import os
assert os.path.exists(work + "/slow_drain_observed"), (
    "the slow_drain stall was never observed on the draining replica")
print("phase C preemption: ledger EXACT (%d == %d answered points), "
      "handoffs %r, slow_drain stall absorbed by the handoff"
      % (fleet["points_total"], n200, ho))
EOF

# the cost-ledger consistency invariant (docs/economics.md leg 1): the
# supervisor's cross-check — Σ per-replica chip-seconds vs supervised
# wall-clock × chips — must hold EXACTLY THROUGH the SIGKILL + respawn
# above: the FleetCostLedger banks a killed incarnation's accrual when
# its counters go backwards, so nothing billed is lost and nothing is
# double-billed.  Poll through federation ticks (5 s cadence) until the
# post-churn report lands.  The fleet-level demand-history ring must
# have recorded the churn window too.
python - "$WORKC" <<'EOF'
import json, os, sys, time

sys.path.insert(0, ".")
from reporter_tpu.obs.economics import read_ring

workc = sys.argv[1]
path = os.path.join(workc, "cost_ledger.json")
deadline = time.monotonic() + 30.0
rep = None
# every replica starts at 1 incarnation, so 3 replicas + the SIGKILL'd
# one's banked respawn means the fleet total must reach >= 4
while time.monotonic() < deadline:
    try:
        rep = json.load(open(path))
        if rep.get("incarnations", 0) >= 4:
            break
    except (OSError, ValueError):
        pass  # federation tick mid-write / not yet written
    time.sleep(1.0)
assert rep is not None, "the supervisor never wrote %s" % path
assert rep.get("incarnations", 0) >= 4, (
    "the SIGKILL'd replica's respawn never registered as a banked "
    "incarnation: %r" % rep)
assert rep["consistent"], (
    "chip-second ledger INCONSISTENT through SIGKILL+respawn: ledger "
    "%.1f chip-s vs supervised %.1f expected (rel_err %.3f > tol %.3f "
    "+ boot slack): %r"
    % (rep["totals"]["chip_seconds"], rep["expected_chip_seconds"],
       rep.get("rel_err", -1), rep.get("tolerance", -1),
       rep.get("replicas")))
assert rep["price_per_chip_hour"] == 3.60, rep["price_per_chip_hour"]
fleet_ticks = read_ring(os.path.join(workc, "history", "fleet.jsonl"))
assert fleet_ticks, "the supervisor's fleet demand-history ring is empty"
assert any(r.get("replicas_live") is not None for r in fleet_ticks)
print("phase C economics: ledger CONSISTENT through SIGKILL+respawn "
      "(%.1f chip-s vs %.1f supervised, %d incarnation(s) banked, "
      "rel_err %.3f); fleet history ring %d ticks"
      % (rep["totals"]["chip_seconds"], rep["expected_chip_seconds"],
         rep["incarnations"], rep.get("rel_err", 0.0), len(fleet_ticks)))
EOF

# ---- graceful fleet drain: exit 0, nothing stranded -----------------------
reh_stop_fleet
echo "overload rehearsal OK (artifacts in $WORK)"
