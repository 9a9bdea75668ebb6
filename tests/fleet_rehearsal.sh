#!/usr/bin/env bash
# Fleet chaos gating rehearsal (the CI `fleet-rehearsal` leg; runnable
# locally): tools/fleet.py boots 3 serve replicas behind the session-
# affine router (serve/router.py), tools/loadgen.py replays an open-loop
# synth fleet against the ROUTER, and mid-replay the fleet is abused the
# way production abuses it:
#
#   t+8s   one replica is SIGKILLed (no drain, no warning) — the
#          supervisor respawns it, the router fails its traffic over
#   t+16s  SIGUSR1 triggers a rolling restart (each replica gracefully
#          drained, respawned, waited healthy, one at a time)
#
# and the verdict must still hold:
#
#   1. loadgen's SLO verdict passes (rc 0): availability + p99 met over
#      the WHOLE run, kill and restarts included — and with --server-slo
#      the ROUTER's client-truth fleet verdict (GET /debug/slo) must
#      AGREE with loadgen's client-side one
#   2. zero non-shed client errors after the failover window: every
#      sample outside [kill, kill+2s) is 200/429/503 — a lost replica
#      may shed, it may NOT surface 5xx/resets/timeouts to clients
#   3. the affinity remap is confined: between the kill and the rolling
#      restart, the ONLY vehicles that changed replica are the ones the
#      dead replica owned (rendezvous hashing's promise, measured from
#      the X-Reporter-Replica echoes in the per-sample dump)
#
# plus the fleet observability plane (docs/observability.md "Fleet
# observability"):
#
#   4. federation consistency (chaos-free phase 0): the sum over
#      replicas of the federated replica-labeled reporter_requests_total
#      plus router sheds equals loadgen's client-observed request count,
#      and the per-replica split matches the --dump-samples distribution
#      exactly
#   5. the SIGKILLed replica's final snapshot stays visible on the
#      router's federated /metrics with a RISING staleness gauge while
#      the replica is down
#   6. at least one failover-masked request shows up as fleet-good /
#      replica-bad in the reporter_fleet_slo_masking_debt gauge
#   7. one stitched GET /debug/traces?id= for a failed-over request
#      returns ≥2 dispatch-attempt hop spans with the serving replica's
#      span tree spliced under them
#
# Usage: tests/fleet_rehearsal.sh [workdir]
set -euo pipefail

# shared spawn/trap/cleanup/wait helpers (tests/rehearsal_lib.sh)
. "$(dirname "$0")/rehearsal_lib.sh"
# snappy failover in the router's retry loop (the default backoff base is
# tuned for WAN egress, not a localhost rehearsal)
export REPORTER_RETRY_BASE_S="${REPORTER_RETRY_BASE_S:-0.05}"
# snappy federation so the SIGKILL staleness window is observable (the
# supervisor respawns a killed replica in under a second, so the stale
# bound must be tighter than the respawn)
export REPORTER_FEDERATION_PULL_S="${REPORTER_FEDERATION_PULL_S:-0.25}"
export REPORTER_FEDERATION_STALE_S="${REPORTER_FEDERATION_STALE_S:-0.75}"
# the router's client-truth fleet SLO states the SAME objectives loadgen
# asserts, so the --server-slo agreement check compares like with like
export REPORTER_SLO_AVAILABILITY=0.95
export REPORTER_SLO_P99_MS=8000
export REPORTER_SLO_P999_MS=0
export REPORTER_SLO_DEGRADED_FRAC=0
# ONE injected router->replica connect refusal: the first phase-0 request
# deterministically fails over, giving the stitched-trace assertion a
# failed-over trace whose winning replica is still alive (the chaos
# phase's own failovers race the rolling restart, which wipes replica
# flight recorders — a live-only assertion would be flaky)
export REPORTER_FAULT_ROUTER_CONNECT="refused:1"
# ...and ONE injected admission shed per replica: each replica 429s its
# first /report (burning ITS availability budget), the router rotates
# onward, the client sees 200 — the deterministic fleet-good/replica-bad
# requests the masking-debt assertion bills (a clean rolling restart can
# rotate traffic off so fast that no organic drain refusal ever occurs)
export REPORTER_FAULT_REPLICA_SHED="1"
# replicas 2..N replay replica 1's XLA compiles instead of redoing them
reh_init "${1:-}" reporter-fleet
export JAX_COMPILATION_CACHE_DIR="$WORK/xla-cache"
ROUTER_PORT=18071
BASE_PORT=18072
echo "fleet rehearsal workdir: $WORK"

# ---- config (grid must match loadgen --grid; one length bucket keeps the
# --warmup grid small enough for CI) ----------------------------------------
cat > "$WORK/config.json" <<EOF
{
  "network": {"type": "grid", "rows": 8, "cols": 8, "spacing_m": 200},
  "matcher": {"sigma_z": 4.07, "beta": 3.0, "search_radius": 50.0,
              "length_buckets": [16],
              "warmup_batch_sizes": [1, 4, 16, 64]},
  "backend": "jax",
  "batch": {"max_batch": 64, "max_wait_ms": 5}
}
EOF

# ---- boot the fleet -------------------------------------------------------
python tools/fleet.py --config "$WORK/config.json" --replicas 3 \
    --base-port "$BASE_PORT" --router-port "$ROUTER_PORT" \
    --workdir "$WORK" --warmup --cpu-default --drain-grace 20 \
    > "$WORK/fleet.log" 2>&1 &
FLEET_PID=$!
reh_track_fleet "$FLEET_PID" "$WORK"

# deferred boot answers 200 while the engine is still attaching:
# readiness for the LOAD run is an attached backend, else the replay's
# head just measures "service initialising" 503s
if ! reh_wait_fleet "http://127.0.0.1:$ROUTER_PORT" 3 "$BASE_PORT" 3 600; then
    echo "FAIL: fleet never reached 3 available replicas; fleet log tail:"
    tail -30 "$WORK/fleet.log"
    for f in "$WORK"/replica-*.log "$WORK"/router.log; do
        echo "--- $f"; tail -10 "$f" 2>/dev/null || true
    done
    exit 1
fi
echo "fleet up: 3 replicas behind the router"

# ---- phase 0: federation consistency, chaos-free --------------------------
# a short clean replay, then the invariant: every client-observed request
# is accounted for EXACTLY ONCE across the federated replica-labeled
# counters (+ router sheds), and the per-replica split matches the
# X-Reporter-Replica echoes in the sample dump
python tools/loadgen.py --url "http://127.0.0.1:$ROUTER_PORT" \
    --rate 10 --duration 6 --vehicles 24 --points 48 --window 16 --grid 8 \
    --seed 7 --concurrency 16 --timeout-s 8 \
    --slo-availability 0.95 --slo-p99-ms 8000 \
    --dump-samples "$WORK/phase0_samples.jsonl" \
    --out "$WORK/loadgen_phase0.json"
python - "$WORK" "http://127.0.0.1:$ROUTER_PORT" <<'EOF'
import json, sys, urllib.request

work, router = sys.argv[1], sys.argv[2]
sys.path.insert(0, ".")
from reporter_tpu.obs.quantile import parse_metrics

rows = [json.loads(l) for l in open(work + "/phase0_samples.jsonl")]
observed = {}
for r in rows:
    if r["replica"] and r["code"] == 200:
        observed[r["replica"]] = observed.get(r["replica"], 0) + 1
n200 = sum(1 for r in rows if r["code"] == 200)
with urllib.request.urlopen(router + "/metrics?pull=1", timeout=10) as f:
    m = parse_metrics(f.read().decode())
federated = {}
resheds = 0
for lv, v in m.get("reporter_requests_total", {}).items():
    d = dict(lv)
    if "replica" not in d or d.get("endpoint") != "report":
        continue
    if d.get("outcome") == "shed":
        # a replica-side shed is RE-DISPATCHED by the router: the client
        # observes one request, the fleet counts the shed AND the
        # winner's ok — so sheds are accounted separately, not summed
        # into the per-request ledger (the injected REPLICA_SHED=1 per
        # replica makes this leg exercise the distinction)
        resheds += int(v)
        continue
    federated[d["replica"]] = federated.get(d["replica"], 0) + int(v)
shed = int(m.get("reporter_router_shed_total", {}).get((), 0))
# the invariant, exact on successes: every client-observed 200 is
# counted by EXACTLY ONE replica on the federated scrape — nothing
# double-counted across failovers, nothing lost
assert sum(federated.values()) == n200, (
    "federation consistency broken: %d federated non-shed counts != "
    "%d client-observed 200s (%r)" % (sum(federated.values()), n200,
                                      federated))
assert federated == observed, (
    "per-replica split mismatch: federated %r vs client-observed %r"
    % (federated, observed))
# ...and exhaustive on the rest: every non-200 client row is a shed of
# some kind, all visible on the same scrape (the router's own gate or a
# replica-side shed leg) — the ledger balances
assert len(rows) - n200 <= shed + resheds, (
    "%d client non-200s but only %d router + %d replica sheds visible"
    % (len(rows) - n200, shed, resheds))
assert resheds >= 3, (
    "the injected per-replica admission sheds never fired (%d)" % resheds)
print("phase 0 consistency OK: %d requests (%d ok), split %s, %d router "
      "sheds, %d replica sheds re-dispatched"
      % (len(rows), n200, dict(sorted(federated.items())), shed, resheds))

# 7. the stitched trace: the injected connect refusal made the first
# phase-0 request fail over; its router span must carry >= 2
# dispatch-attempt hops with the serving replica's span tree spliced
# under them (the winning leg's X-Reporter-Flight-Keep pinned it)
def get(url):
    with urllib.request.urlopen(url, timeout=10) as f:
        return json.loads(f.read().decode())

traces = get(router + "/debug/traces?n=200")["traces"]
candidates = [t for t in traces if t.get("attempts", 1) >= 2
              and t.get("status") == "ok"]
assert candidates, ("the injected connect refusal produced no retained "
                    "failed-over router span")
stitched = None
for t in candidates:
    out = get(router + "/debug/traces?id=%s" % t["trace_id"])
    s = out["stitched"]
    hops = [h for h in s.get("hops", []) if h.get("span") == "dispatch"]
    if len(hops) >= 2 and s.get("children"):
        stitched = out
        break
assert stitched is not None, (
    "no stitched router+replica span tree among %d failed-over traces"
    % len(candidates))
s = stitched["stitched"]
# the losing hop is visible: a transport error or a shed/5xx code
assert any(h.get("outcome") != "200" for h in s["hops"]
           if h.get("span") == "dispatch"), s["hops"]
assert any(e.get("endpoint") == "report" for e in s["children"])
assert all(e.get("trace_id") == stitched["trace_id"]
           for e in s["children"])
print("stitched trace %s: %d dispatch hops, %d replica spans spliced"
      % (stitched["trace_id"],
         len([h for h in s["hops"] if h.get("span") == "dispatch"]),
         len(s["children"])))
EOF

# ---- the fleet-plane watcher: samples the router's federated surfaces
# through the chaos window (staleness + masking debt are TRANSIENT — the
# respawn refreshes the snapshot, so they must be observed live) --------
python - "$WORK" "http://127.0.0.1:$ROUTER_PORT" <<'EOF' &
import json, os, re, sys, time, urllib.request

work, router = sys.argv[1], sys.argv[2]
obs = {"stale_seen": False, "stale_age_max": 0.0,
       "stale_snapshot_present": False, "masking_debt_max": 0.0}
path = work + "/plane_watch.json"
stale_re = re.compile(
    r'reporter_federation_snapshot_stale\{replica="rep-1"\} 1\b')
age_re = re.compile(
    r'reporter_federation_snapshot_age_seconds\{replica="rep-1"\} ([\d.]+)')
debt_re = re.compile(
    r'reporter_fleet_slo_masking_debt\{objective="[^"]+"\} ([\d.eE+-]+)')
while True:
    try:
        with urllib.request.urlopen(router + "/metrics", timeout=3) as f:
            text = f.read().decode()
        age = age_re.search(text)
        if stale_re.search(text) and age:
            obs["stale_seen"] = True
            obs["stale_age_max"] = max(obs["stale_age_max"],
                                       float(age.group(1)))
            # the dead replica's LAST snapshot must still be rendered
            if re.search(r'reporter_requests_total\{replica="rep-1"',
                         text):
                obs["stale_snapshot_present"] = True
        for m in debt_re.finditer(text):
            obs["masking_debt_max"] = max(obs["masking_debt_max"],
                                          float(m.group(1)))
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(obs, f)
        os.replace(tmp, path)
    except Exception:
        pass  # router mid-churn: keep sampling
    time.sleep(0.05)
EOF
WATCHER_PID=$!
reh_track_watcher "$WATCHER_PID"

# ---- open-loop replay against the ROUTER, chaos mid-load ------------------
python tools/loadgen.py --url "http://127.0.0.1:$ROUTER_PORT" \
    --rate 15 --duration 30 --vehicles 24 --points 48 --window 16 --grid 8 \
    --seed 11 --concurrency 32 --timeout-s 8 \
    --slo-availability 0.95 --slo-p99-ms 8000 --server-slo \
    --dump-samples "$WORK/samples.jsonl" \
    --out "$WORK/loadgen_fleet.json" &
LOADGEN_PID=$!

sleep 8
VICTIM_PID=$(python -c "
import json; s = json.load(open('$WORK/fleet.json'))
print(s['replicas'][1]['pid'])")
KILL_EPOCH=$(python -c "import time; print(time.time())")
# freeze-then-kill: the SIGSTOP holds the replica wedged (not yet dead)
# for 2 s, so the federation's staleness window is wide enough to
# observe deterministically — the supervisor respawns a SIGKILLed
# replica in under a second, faster than any sane stale bound.  The
# router sees exactly what a wedged process looks like: probes time
# out, live legs hang until the kill resets them, pulls go stale.
kill -STOP "$VICTIM_PID"
echo "SIGSTOPped replica rep-1 (pid $VICTIM_PID) at $KILL_EPOCH"
sleep 2
kill -9 "$VICTIM_PID"
echo "SIGKILLed replica rep-1 (pid $VICTIM_PID)"

sleep 6
RESTART_EPOCH=$(python -c "import time; print(time.time())")
kill -USR1 "$FLEET_PID"
echo "rolling restart requested at $RESTART_EPOCH"

set +e
wait "$LOADGEN_PID"
LOADGEN_RC=$?
set -e
if [ "$LOADGEN_RC" != 0 ]; then
    echo "FAIL: loadgen rc $LOADGEN_RC — the fleet violated its SLO under"
    echo "      a SIGKILL + rolling restart (artifact: loadgen_fleet.json)"
    python -c "
import json; a = json.load(open('$WORK/loadgen_fleet.json'))
print(json.dumps({k: a[k] for k in ('status', 'quantiles', 'slo')}, indent=1))" \
        2>/dev/null || true
    exit 1
fi
echo "loadgen SLO verdict: PASS (rc 0) under kill + rolling restart"
echo "  (incl. --server-slo: the router's client-truth fleet verdict agrees)"

# ---- fleet plane: staleness, masking debt, stitched failover trace --------
reh_untrack_watchers
python - "$WORK" "http://127.0.0.1:$ROUTER_PORT" <<'EOF'
import json, sys, urllib.request

work, router = sys.argv[1], sys.argv[2]

# 5. the SIGKILLed replica's final snapshot stayed visible with a rising
# staleness gauge (observed LIVE by the watcher: the respawn refreshes
# the snapshot, so the window is transient by design)
w = json.load(open(work + "/plane_watch.json"))
assert w["stale_seen"], (
    "the dead replica never showed a stale federated snapshot: %r" % w)
assert w["stale_snapshot_present"], (
    "the dead replica's last snapshot vanished from the federated "
    "render while stale: %r" % w)
assert w["stale_age_max"] > 0, w

# 6. at least one failover-masked request: replica-level burn the fleet
# verdict never saw, billed by the masking-debt gauge
assert w["masking_debt_max"] > 0, (
    "no masking debt observed across a SIGKILL + rolling restart — "
    "failover-masked replica burn is not being billed: %r" % w)
print("staleness observed (age max %.1fs, snapshot retained); "
      "masking debt max %.3f" % (w["stale_age_max"], w["masking_debt_max"]))
EOF

# the supervisor's own federation artifact exists and carries the herd
python - "$WORK" <<'EOF'
import json, sys

fed = json.load(open(sys.argv[1] + "/federation.json"))
assert set(fed["replicas"]) >= {"rep-0", "rep-2"}, fed["replicas"].keys()
assert fed["merged"], "supervisor federation dump carries no merged snapshot"
print("supervisor federation.json OK: %d replicas, %d merged families"
      % (len(fed["replicas"]), len(fed["merged"])))
EOF

# ---- failover-window errors + affinity confinement ------------------------
python - "$WORK" "$KILL_EPOCH" "$RESTART_EPOCH" <<'EOF'
import json, sys

work, kill_epoch, restart_epoch = sys.argv[1], float(sys.argv[2]), float(sys.argv[3])
FAILOVER_WINDOW_S = 2.0
rows = [json.loads(l) for l in open(work + "/samples.jsonl")]
assert rows, "empty sample dump"

# 1. zero non-shed client errors outside the failover window: a request
# is allowed to be shed (429) or to see a drain/unavailable 503 (the
# router retries those; a residue is shed-class), NEVER a 5xx/timeout
allowed = {200, 429, 503}
bad = [r for r in rows if r["code"] not in allowed
       and not (kill_epoch <= r["sched_epoch"] < kill_epoch + FAILOVER_WINDOW_S)]
assert not bad, (
    "non-shed client errors outside the failover window: %r" % bad[:5])

# 2. affinity remap confined to the SIGKILLed replica's vehicles,
# measured between the kill (+failover window) and the rolling restart:
# a vehicle "moved" if ANY of its phase-2 responses came from a replica
# other than its pre-kill primary (the supervisor respawns the victim
# fast, so a last-assignment view would under-measure the remap)
phase1 = {}
for r in sorted((r for r in rows if r["done_epoch"] < kill_epoch),
                key=lambda r: r["done_epoch"]):
    if r["replica"] and r["code"] == 200:
        phase1[r["uuid"]] = r["replica"]
phase2_rows = [r for r in rows
               if kill_epoch + FAILOVER_WINDOW_S <= r["sched_epoch"]
               and r["done_epoch"] < restart_epoch
               and r["replica"] and r["code"] == 200]
assert phase2_rows, "no samples between kill and rolling restart"
dead = "rep-1"
dead_vehicles = {u for u, rid in phase1.items() if rid == dead}
assert dead_vehicles, "the killed replica owned no vehicles pre-kill?"
moved = {r["uuid"] for r in phase2_rows
         if r["uuid"] in phase1 and r["replica"] != phase1[r["uuid"]]}
stray = moved - dead_vehicles
assert not stray, (
    "vehicles moved that the dead replica never owned: %r "
    "(affinity remap not confined)" % sorted(stray)[:10])
assert moved, ("the dead replica's vehicles never landed elsewhere "
               "during its downtime — remap not measured")

dist = {}
for r in rows:
    if r["replica"]:
        dist[r["replica"]] = dist.get(r["replica"], 0) + 1
print("failover window clean; %d/%d of the dead replica's vehicles "
      "remapped, 0 stray moves; per-replica distribution: %s"
      % (len(moved), len(dead_vehicles), dict(sorted(dist.items()))))
EOF

# ---- graceful fleet drain: exit 0, nothing stranded -----------------------
reh_stop_fleet
echo "fleet rehearsal OK (artifacts in $WORK)"
