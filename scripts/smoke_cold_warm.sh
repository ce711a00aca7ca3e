#!/usr/bin/env bash
# chip_smoke.py twice in one chip call, cold cache then warm: both wall
# times, and that the warm run repeats the cold run's token ids and
# train losses (step programs donate kv_pages / state; a warm
# persistent cache must not change what they return).
#
#   chiprun --timeout 2400 -- bash scripts/smoke_cold_warm.sh
#
# Logs and records land in chiprun_out/.
set -u
cd "$(dirname "$0")/.."
out=chiprun_out
mkdir -p "$out"
rc=0
for pass in cold warm; do
  t0=$(date +%s)
  python chip_smoke.py --record "$out/smoke_$pass.json" "$@" \
    > "$out/smoke_$pass.log" 2> "$out/smoke_$pass.err" || rc=$?
  echo "{\"pass\": \"$pass\", \"rc\": $rc, \"wall_s\": $(( $(date +%s) - t0 ))}"
  tail -n 1 "$out/smoke_$pass.log"
  [ "$rc" -eq 0 ] || { tail -n 40 "$out/smoke_$pass.err"; grep '"phase"' "$out/smoke_$pass.log" | cut -c1-600; exit "$rc"; }
done
python - "$out" <<'PY'
import json, sys
out = sys.argv[1]
cold, warm = (json.load(open(f"{out}/smoke_{p}.json")) for p in ("cold", "warm"))
same = cold == warm
print(json.dumps({"cold_equals_warm": same, "record_keys": sorted(cold)}))
sys.exit(0 if same else 1)
PY
rc=$?
for pass in cold warm; do grep '"phase"' "$out/smoke_$pass.log" | cut -c1-1500; done
ls "${JAX_COMPILATION_CACHE_DIR:-.jax_cache}" | wc -l
exit $rc
