#!/usr/bin/env bash
# Mesh gating rehearsal (the CI `mesh-rehearsal` leg; runnable locally):
# ONE replica is forced onto an 8-virtual-device dp mesh
# (XLA_FLAGS=--xla_force_host_platform_device_count=8 plus the
# REPORTER_DEVICES override — a stock config, the topology arrives by
# env exactly as a pod supervisor would deliver it) and serves the
# per-point streaming scenario with the FULL serving composition ON:
# the device-resident session arena, the tiered UBODT hot-bucket
# arena, and the sparse-gap matching model — the whole program family
# of docs/performance.md "One logical matcher per pod" dispatching
# through the partition-rule table at once.  The verdict:
#
#   1. loadgen streaming SLO verdict green (rc 0): the mesh-sharded
#      replica serves real per-point traffic inside its objectives
#   2. the topology is really advertised: /health capacity.devices == 8
#      with mesh {dp: 8, gp: 1}, admission caps scaled 8x over the
#      per-chip config, and the ROUTER's /statusz fleet row carries
#      devices == 8 — the weighted ranking consumed the capacity block
#   3. the arena is really SHARDED across the mesh: /statusz
#      session_arena shows devices == 8, hot_slots a multiple of 8, and
#      the per-chip views exactly 1/8 of the pod totals; ubodt_tier is
#      live (tiering composes with the mesh instead of disabling)
#   4. readbacks stay FLAT through a steady mid-stream window: the
#      dp-sharded slab still performs zero per-step host readbacks —
#      sharding the slot axis did not sneak a host gather into the
#      donated in-place session step
#
# Usage: tests/mesh_rehearsal.sh [workdir]
set -euo pipefail

. "$(dirname "$0")/rehearsal_lib.sh"
export REPORTER_RETRY_BASE_S="${REPORTER_RETRY_BASE_S:-0.05}"
export REPORTER_ROUTER_PROBE_S="${REPORTER_ROUTER_PROBE_S:-0.25}"
# the mesh under test: 8 virtual CPU devices, the replica spans all of
# them as a dp-8 mesh (docs/serving-fleet.md Knobs)
export XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}"
export REPORTER_DEVICES=8
# the full serving composition, pinned explicitly so this gate keeps
# meaning it even if a serving default moves
export REPORTER_SESSION_ARENA=1
export REPORTER_SPARSE=1
export REPORTER_UBODT_HOT_BYTES="${REPORTER_UBODT_HOT_BYTES:-16384}"
# serving objectives (loose: 8 virtual devices SHARE the runner's host
# cores, so per-dispatch latency is the oversubscription's, not the
# mesh's — correctness of the sharded data plane is the gate)
export REPORTER_SLO_AVAILABILITY=0.95
export REPORTER_SLO_P99_MS=12000
export REPORTER_SLO_P999_MS=0
export REPORTER_SLO_DEGRADED_FRAC=0
export REPORTER_SLO_STREAM_P99_MS=4000
reh_init "${1:-}" reporter-mesh
export JAX_COMPILATION_CACHE_DIR="$WORK/xla-cache"
ROUTER_PORT=18281
BASE_PORT=18282
echo "mesh rehearsal workdir: $WORK (dp-8 replica, arena+tiering+sparse ON)"

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

# ---- boot the one-replica, eight-chip fleet -------------------------------
python tools/fleet.py --config "$WORK/config.json" --replicas 1 \
    --base-port "$BASE_PORT" --router-port "$ROUTER_PORT" \
    --workdir "$WORK" --warmup --cpu-default --drain-grace 20 \
    > "$WORK/fleet.log" 2>&1 &
FLEET_PID=$!
reh_track_fleet "$FLEET_PID" "$WORK"

if ! reh_wait_fleet "http://127.0.0.1:$ROUTER_PORT" 1 "$BASE_PORT" 1 600 warmed; then
    echo "FAIL: the mesh replica never warmed; fleet log tail:"
    tail -30 "$WORK/fleet.log"
    for f in "$WORK"/replica-*.log "$WORK"/router.log; do
        echo "--- $f"; tail -10 "$f" 2>/dev/null || true
    done
    exit 1
fi
echo "fleet up: 1 warmed replica spanning 8 virtual devices"

# 2 + 3. the advertised topology and the sharded planes, BEFORE load
python - "$BASE_PORT" "http://127.0.0.1:$ROUTER_PORT" <<'EOF'
import json, sys, urllib.request

base, router = int(sys.argv[1]), sys.argv[2]

def get(url):
    with urllib.request.urlopen(url, timeout=15) as f:
        return json.loads(f.read().decode())

h = get("http://127.0.0.1:%d/health" % base)
cap = h.get("capacity")
assert cap, "no capacity block on /health: %r" % h
assert cap["devices"] == 8, cap
assert cap.get("mesh") == {"dp": 8, "gp": 1}, cap
assert cap["max_device_batch"] % 8 == 0 and cap["max_device_batch"] >= 8, cap
print("capacity advertised: devices=8 mesh=%r max_device_batch=%d "
      "max_device_points=%d" % (cap["mesh"], cap["max_device_batch"],
                                cap["max_device_points"]))

sz = get("http://127.0.0.1:%d/statusz" % base)
a = sz.get("session_arena")
assert a is not None, "replica serves without a session arena"
assert a["devices"] == 8, a
assert a["hot_slots"] % 8 == 0, a
assert a["hot_slots_per_chip"] * 8 == a["hot_slots"], a
assert a["hot_bytes_per_chip"] * 8 == a["hot_bytes"], a
print("session arena sharded: %d slots over 8 chips (%d/chip, %dB/chip)"
      % (a["hot_slots"], a["hot_slots_per_chip"], a["hot_bytes_per_chip"]))

tier = sz.get("ubodt_tier")
assert tier is not None, "tiering disabled itself under the mesh"
assert tier["hot_bytes"] > 0 and tier["hot_rows"] > 0, tier
print("ubodt tiering live under the mesh: hot_bytes=%d hot_rows=%d"
      % (tier["hot_bytes"], tier["hot_rows"]))

sp = sz.get("sparse")
assert sp and sp.get("enabled"), "sparse model not enabled: %r" % sp
print("sparse-gap model enabled")

fleet = get(router + "/statusz")
row = fleet["fleet"][0]
assert row.get("devices") == 8, (
    "router never learned the replica's mesh size: %r" % row)
print("router fleet row advertises devices=8 (capacity-weighted ranking fed)")
EOF

# ---- the loadgen stream scenario against the mesh replica ------------------
python tools/loadgen.py --url "http://127.0.0.1:$ROUTER_PORT" \
    --stream \
    --rate 15 --duration 25 --vehicles 16 --points 48 --window 16 --grid 8 \
    --seed 13 --concurrency 24 --timeout-s 10 \
    --slo-availability 0.95 --slo-p99-ms 12000 \
    --out "$WORK/loadgen_stream.json" &
LOADGEN_PID=$!

# 4. steady-state readback window: two mid-stream scrapes of the arena's
# readback counter must not move (zero per-step host transfers even with
# the slab dp-sharded over 8 devices)
_scrape_readbacks() {
    python - "$BASE_PORT" <<'EOF'
import sys, urllib.request

sys.path.insert(0, ".")
from reporter_tpu.obs.quantile import parse_metrics

base = int(sys.argv[1])
with urllib.request.urlopen(
        "http://127.0.0.1:%d/metrics" % base, timeout=15) as f:
    m = parse_metrics(f.read().decode())
tot = 0
for _lv, v in m.get("reporter_session_arena_readbacks_total", {}).items():
    tot += int(v)
print(tot)
EOF
}
sleep 5
RB_0=$(_scrape_readbacks)
sleep 5
RB_1=$(_scrape_readbacks)
if [ "$RB_0" != "$RB_1" ]; then
    echo "FAIL: arena readbacks grew $RB_0 -> $RB_1 during steady-state"
    echo "      streaming on the dp-8 mesh — sharding the slab leaked a"
    echo "      per-step host transfer"
    exit 1
fi
echo "steady-state readbacks flat on the mesh: $RB_0 across both scrapes"

set +e
wait "$LOADGEN_PID"
LOADGEN_RC=$?
set -e
if [ "$LOADGEN_RC" != 0 ]; then
    echo "FAIL: loadgen rc $LOADGEN_RC — the streaming SLO did not hold on"
    echo "      the mesh replica (artifact: loadgen_stream.json)"
    python -c "
import json; a = json.load(open('$WORK/loadgen_stream.json'))
print(json.dumps({k: a.get(k) for k in ('status', 'quantiles', 'slo')}, indent=1))" \
        2>/dev/null || true
    tail -20 "$WORK/router.log"
    exit 1
fi
echo "loadgen streaming SLO verdict: PASS (rc 0) against the dp-8 replica"

# resident sessions actually landed in the sharded slab under load
python - "$BASE_PORT" <<'EOF'
import json, sys, urllib.request

base = int(sys.argv[1])
with urllib.request.urlopen(
        "http://127.0.0.1:%d/statusz" % base, timeout=15) as f:
    sz = json.loads(f.read().decode())
a = sz["session_arena"]
assert a["hot_used"] > 0, (
    "no session ever went device-resident on the mesh: %r" % a)
print("mesh slab occupancy after load: %d/%d hot slots used, "
      "%d promotions" % (a["hot_used"], a["hot_slots"], a["promotions"]))
EOF

reh_stop_fleet
echo "mesh rehearsal: PASS"
