#!/usr/bin/env bash
# Continent-scale data-plane gating rehearsal (the CI `shard-rehearsal`
# leg; runnable locally): tools/fleet.py boots 3 warmed replicas, each
# holding one UBODT shard assignment (REPORTER_UBODT_SHARD=i/3) and a
# hot-bucket arena budget (REPORTER_UBODT_HOT_BYTES) a small fraction of
# the table — the fleet as a whole serves a table >4x ANY single
# replica's hot budget, host-paging the cold rows — behind the router
# with the flag-gated geo-aware ranking term ON.  The verdict:
#
#   1. loadgen SLO verdict green (rc 0) over the whole run: the tiered,
#      sharded fleet serves real traffic inside its objectives
#   2. the table really exceeded the budget: /statusz ubodt_tier shows
#      table_bytes >= 4 * hot_bytes on every replica
#   3. the tiers actually worked: federated /metrics counts
#      reporter_ubodt_tier_hits_total > 0 AND _misses_total > 0, and
#      every replica's residency gauge is > 0 (arena seeded + admitting)
#   4. the geo term really ranked: reporter_router_geo_requests_total
#      counted every proxied report, and bodies without coordinates did
#      not break routing
#
# Usage: tests/shard_rehearsal.sh [workdir]
set -euo pipefail

. "$(dirname "$0")/rehearsal_lib.sh"
export REPORTER_RETRY_BASE_S="${REPORTER_RETRY_BASE_S:-0.05}"
export REPORTER_ROUTER_PROBE_S="${REPORTER_ROUTER_PROBE_S:-0.25}"
# the continent-scale knobs under test
export REPORTER_UBODT_HOT_BYTES="${REPORTER_UBODT_HOT_BYTES:-16384}"
export REPORTER_ROUTER_GEO=1
# ~220 m cells over the synthetic city so the geo term sees several cells
export REPORTER_ROUTER_GEO_CELL_DEG=0.002
# serving objectives (loose: correctness of the data plane is the gate,
# not CPU latency)
export REPORTER_SLO_AVAILABILITY=0.95
export REPORTER_SLO_P99_MS=8000
export REPORTER_SLO_P999_MS=0
export REPORTER_SLO_DEGRADED_FRAC=0
reh_init "${1:-}" reporter-shard
export JAX_COMPILATION_CACHE_DIR="$WORK/xla-cache"
ROUTER_PORT=18181
BASE_PORT=18182
echo "shard rehearsal workdir: $WORK (hot budget $REPORTER_UBODT_HOT_BYTES B)"

cat > "$WORK/config.json" <<EOF
{
  "network": {"type": "grid", "rows": 8, "cols": 8, "spacing_m": 200},
  "matcher": {"sigma_z": 4.07, "beta": 3.0, "search_radius": 50.0,
              "length_buckets": [16],
              "warmup_batch_sizes": [1, 4, 16]},
  "backend": "jax",
  "batch": {"max_batch": 64, "max_wait_ms": 5, "session_wait_ms": 2}
}
EOF

# ---- boot the sharded fleet ----------------------------------------------
python tools/fleet.py --config "$WORK/config.json" --replicas 3 \
    --base-port "$BASE_PORT" --router-port "$ROUTER_PORT" \
    --ubodt-shards 3 \
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
echo "fleet up: 3 warmed replicas, one table shard + hot arena each"

# ---- drive real traffic through the router --------------------------------
python tools/loadgen.py --url "http://127.0.0.1:$ROUTER_PORT" \
    --rate 12 --duration 20 --vehicles 24 --points 48 --window 12 --grid 8 \
    --seed 5 --concurrency 16 --timeout-s 8 \
    --slo-availability 0.95 --slo-p99-ms 8000 \
    --out "$WORK/loadgen.json"
echo "loadgen SLO verdict: PASS (rc 0) against the tiered sharded fleet"

# ---- assertions -----------------------------------------------------------
python - "$WORK" "http://127.0.0.1:$ROUTER_PORT" "$BASE_PORT" <<'EOF'
import json, os, sys, urllib.request

work, router, base = sys.argv[1], sys.argv[2], int(sys.argv[3])
sys.path.insert(0, ".")
from reporter_tpu.obs.quantile import parse_metrics

def get(url):
    with urllib.request.urlopen(url, timeout=15) as f:
        return json.loads(f.read().decode())

hot_budget = int(os.environ["REPORTER_UBODT_HOT_BYTES"])

# 2. every replica's table really exceeds 4x its hot budget, the arena
# is seeded with its own shard, and the shard assignments tile 0..2
shards = set()
for i in range(3):
    sz = get("http://127.0.0.1:%d/statusz" % (base + i))
    tier = sz.get("ubodt_tier")
    assert tier, "replica %d serves untiered (ubodt_tier missing)" % i
    assert tier["hot_bytes"] == hot_budget, tier
    assert tier["table_bytes"] >= 4 * hot_budget, (
        "table %dB < 4x hot budget %dB on replica %d"
        % (tier["table_bytes"], hot_budget, i))
    assert tier["hot_rows"] > 0, tier
    assert tier["shard"] and tier["shard"].endswith("/3"), tier
    shards.add(tier["shard"])
print("tiered tables: %s, table >= 4x hot budget on all 3" % sorted(shards))
assert shards == {"0/3", "1/3", "2/3"}, shards

# 3. the tiers worked: federated hit AND miss counters counted, and the
# residency gauge is > 0 on every replica
with urllib.request.urlopen(router + "/metrics?pull=1", timeout=15) as f:
    m = parse_metrics(f.read().decode())

def fleet_sum(name):
    return sum(v for lv, v in m.get(name, {}).items()
               if "replica" in dict(lv))

hits = fleet_sum("reporter_ubodt_tier_hits_total")
misses = fleet_sum("reporter_ubodt_tier_misses_total")
assert hits > 0, "no hot-arena hits counted fleet-wide"
assert misses > 0, "no cold misses counted — the table never paged"
res = {dict(lv)["replica"]: v
       for lv, v in m.get("reporter_ubodt_tier_resident_rows", {}).items()
       if "replica" in dict(lv)}
assert len(res) == 3 and all(v > 0 for v in res.values()), res
print("tier counters: %d hits / %d misses fleet-wide, residency %r"
      % (hits, misses, res))

# 4. the geo-aware term ranked real requests
geo = {dict(lv).get("outcome"): v
       for lv, v in m.get("reporter_router_geo_requests_total", {}).items()}
assert sum(geo.values()) > 0, "geo ranking never engaged: %r" % geo
print("geo ranking engaged on %d requests (%r)"
      % (int(sum(geo.values())), geo))

art = json.load(open(work + "/loadgen.json"))
q = art.get("quantiles") or {}
p99 = q.get("p99") or q.get("0.99")
print("shard rehearsal PASS: %d requests%s"
      % (art.get("requests", 0),
         (", p99 %.0f ms" % (p99 * 1000.0)) if p99 else ""))
EOF

reh_stop_fleet
echo "shard rehearsal: PASS"
