#!/usr/bin/env bash
# graftguard + graftheal chaos gate — the fault-injection subset of tier-1
# on CPU: injected UNAVAILABLE outages, SIGTERM preemption + kill->resume
# parity, hung-bench deadline isolation, the checkpoint crash window
# (tests/test_resilience.py), and the graftheal matrix — mid-run device
# loss with heal-and-continue bit-exact parity (tree AND flat), double
# loss inside one heal window, elastic 8->4 shrink with loss-trajectory
# agreement, and cross-topology resume via the checkpoint meta sidecar
# (tests/test_heal.py). Runbook: OUTAGES.md. Every failure mode exercised
# on demand instead of by the next real outage. Same invocation locally
# and in any future CI.
set -euo pipefail
cd "$(dirname "$0")/.."
JAX_PLATFORMS=cpu python -m pytest -m chaos "$@"

# grafttower fleet-report smoke: a real 2-sim-host run (shared FileKVStore
# quorum, fast heartbeats), then the --fleet fold over its per-host
# events_p<k>.jsonl streams must exit 0 and print the straggler table the
# OUTAGES "which host is the problem?" runbook starts from.
FLEET_DIR="$(mktemp -d)"
FEED_DIR="$(mktemp -d)"
trap 'rm -rf "$FLEET_DIR" "$FEED_DIR"' EXIT
for i in 0 1; do
  JAX_PLATFORMS=cpu \
    python tests/_resilience_driver.py --fit "$FLEET_DIR/run" \
      --sim-host "$i" --sim-hosts 2 \
      --quorum-dir "$FLEET_DIR/kv" --quorum-timeout 15 \
      --obs-dir "$FLEET_DIR/obs" \
      --set obs.heartbeat_every_s=0.2 &
done
wait
JAX_PLATFORMS=cpu python -m mx_rcnn_tpu.obs.report --fleet "$FLEET_DIR/obs" \
  | tee "$FLEET_DIR/report.txt"
grep -q "straggler table" "$FLEET_DIR/report.txt"
echo "fleet-report smoke: OK"

# graftfeed data-chaos smoke: (1) a corrupt record on a tiny CPU fit must
# quarantine + complete, and the report must fold the `data` events into
# the line the OUTAGES "data plane broke" runbook starts from; (2) a
# hung batch must crash with DataStallError inside the data-wait
# deadline, not wedge the smoke.
JAX_PLATFORMS=cpu MX_RCNN_CHAOS="data_corrupt_at=0:1" \
  python tests/_resilience_driver.py --fit "$FEED_DIR/run" \
    --obs-dir "$FEED_DIR/obs_corrupt" \
    --set data.quarantine_max_fraction=0.5
test -s "$FEED_DIR/obs_corrupt/quarantine.jsonl"
JAX_PLATFORMS=cpu python -m mx_rcnn_tpu.obs.report "$FEED_DIR/obs_corrupt" \
  | tee "$FEED_DIR/report_corrupt.txt"
grep -q "record(s) quarantined" "$FEED_DIR/report_corrupt.txt"
if JAX_PLATFORMS=cpu MX_RCNN_CHAOS="data_hang_at=0:2 hang_s=600" \
  timeout -k 10 300 \
  python tests/_resilience_driver.py --fit "$FEED_DIR/hang" \
    --end-epoch 1 --obs-dir "$FEED_DIR/obs_hang" \
    --set data.wait_deadline_s=4.0 --set obs.stall_min_s=0.3 \
    --set obs.stall_factor=0.01 --set obs.watchdog_poll_s=0.1; then
  echo "data-hang smoke: expected DataStallError crash, run completed" >&2
  exit 1
fi
grep -q "DataStallError" "$FEED_DIR/obs_hang"/events*.jsonl
test -e "$FEED_DIR/obs_hang/flight_crash.json"
echo "data-chaos smoke: OK"
