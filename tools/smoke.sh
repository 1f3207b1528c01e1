#!/usr/bin/env bash
# Shared smoke-gate runner: ONE timeout/reporting path for every timed
# gate.
#
#   tools/smoke.sh chaos [scenario ...]   chaos harness (default lossy-net)
#   tools/smoke.sh escrow                 TPC-C escrow floor gate
#   tools/smoke.sh overlap                host path: workers == inline, byte for byte
#   tools/smoke.sh elastic                membership gate: elastic-grow /
#                                         elastic-drain / elastic-kill-reassign
#                                         (liveness + exactly-once invariants)
#   tools/smoke.sh geo                    geo-replication gate: region-loss /
#                                         asymmetric-WAN / replica-lag
#                                         (quorum commit, follower snapshot
#                                         reads, promote-on-region-loss)
#   tools/smoke.sh overload               overload-robustness gate:
#                                         flash-crowd / aggressor-tenant /
#                                         diurnal (bounded admission queue,
#                                         shed + recovery, tenant fairness,
#                                         exactly-once under NACK+resend)
#   tools/smoke.sh partition              partition-tolerance gate:
#                                         symmetric split / asymmetric
#                                         split / gray-slow node /
#                                         flapping link (fencing=true:
#                                         quorum reassignment, minority
#                                         self-fence exit 18, single-
#                                         writer-per-slot + digest-vs-
#                                         replay invariants)
#   tools/smoke.sh trace                  flight-recorder gate:
#                                         telemetry-off wire pin test
#                                         (bit-identity contract) + the
#                                         trace-kill chaos scenario
#                                         (telemetry=true across a
#                                         crash/recovery: every sampled
#                                         committed txn has a gap-free
#                                         client->admit->batch->verdict
#                                         ->quorum->ack span chain, the
#                                         merger renders one flow-linked
#                                         Chrome trace)
#   tools/smoke.sh monitor                metrics-bus gate:
#                                         metrics-off wire pin test
#                                         (bit-identity contract) + the
#                                         monitor-grayslow chaos
#                                         scenario (metrics=true with a
#                                         gray-slow peer + an aggregator
#                                         fault_kill: straggler watchdog
#                                         names the stalled node, the
#                                         recovered aggregator resumes
#                                         the metrics_bus stream)
#   tools/smoke.sh audit                  isolation-audit gate:
#                                         audit-off bit-identity tests
#                                         (no sidecar, pre-audit group
#                                         arity, armed==off row state)
#                                         + the audit-clean /
#                                         audit-mutation chaos pair
#                                         (contended OCC certifies
#                                         serializable; the seeded
#                                         occ-read-skip mutation is
#                                         REJECTED with a cycle witness
#                                         naming the mutated epoch)
#   tools/smoke.sh ctrl                   control-plane gate:
#                                         ctrl-off bit-identity tests
#                                         (no controller object, static
#                                         knobs ≡ legacy path) + the
#                                         ctrl-shift-degrade chaos
#                                         scenario (zipf 0→0.9 mid-run
#                                         shift + flash crowd + an
#                                         aggregator fault_kill: armed
#                                         decisions adapt the backend
#                                         map, the governor falls back
#                                         to static on signal loss and
#                                         re-engages after heal, every
#                                         decision stream replays
#                                         bit-for-bit, exactly-once +
#                                         digest-vs-replay + audit
#                                         certificate all green)
#   tools/smoke.sh repair                 transaction-repair gate:
#                                         repair-contention (zipf-0.9
#                                         write-heavy OCC with repair on +
#                                         crash/recovery: exactly-once with
#                                         salvaged txns acked as commits,
#                                         bit-identical replay through the
#                                         repair sub-rounds, salvage > 0)
#   tools/smoke.sh mesh                   pod-scale measured-path gate:
#                                         the dp=8-vs-dp=1 bit-identity
#                                         oracle (cluster verdict planes,
#                                         logs, acks and replay digests
#                                         identical across the mesh axis,
#                                         YCSB + TPC-C) + the 8-virtual-
#                                         device multichip dry run
#                                         (sharded compile + measured-path
#                                         run_simulation over every
#                                         backend family)
#   tools/smoke.sh dgcc                   wavefront-backend gate:
#                                         dgcc-off pin tests (router/
#                                         map/counter/wire bit-identity
#                                         with the backend unarmed) +
#                                         the zipf-0.9 write-heavy
#                                         anti-inert window (waves
#                                         chain: wave_max > 1,
#                                         waves > epochs, commits > 0,
#                                         aborts == 0)
#   tools/smoke.sh lint                   static-analysis gate: graftlint v2
#                                         (trace/det/wire/own/imports + the
#                                         gate/life/jit families on the
#                                         CFG core) + ruff (pyflakes slice,
#                                         when installed) over deneva_tpu/ +
#                                         tools/.  `lint --changed` = the
#                                         git-diff-scoped incremental mode
#                                         (fast pre-commit signal; the
#                                         full-tree run stays the gate)
#
# Timeout: SMOKE_TIMEOUT_SECS overrides for any scenario; the legacy
# per-gate envs (CHAOS_TIMEOUT_SECS, ESCROW_TIMEOUT_SECS,
# OVERLAP_TIMEOUT_SECS, ELASTIC_TIMEOUT_SECS) still win when set.
# Exits nonzero on an invariant violation, a node error, or the timeout.
set -euo pipefail
cd "$(dirname "$0")/.."

SCEN="${1:-}"
[ $# -gt 0 ] && shift

run() {
    local t="$1"; shift
    timeout -k 10 "$t" env JAX_PLATFORMS=cpu "$@"
}

case "$SCEN" in
  chaos)
    T="${SMOKE_TIMEOUT_SECS:-${CHAOS_TIMEOUT_SECS:-300}}"
    run "$T" python -m deneva_tpu.harness.chaos "${@:-lossy-net}" --quick
    ;;
  escrow)
    T="${SMOKE_TIMEOUT_SECS:-${ESCROW_TIMEOUT_SECS:-600}}"
    run "$T" python -m pytest \
        tests/test_escrow.py::test_tpcc_escrow_smoke_above_floor \
        -q -p no:cacheprovider
    ;;
  overlap)
    T="${SMOKE_TIMEOUT_SECS:-${OVERLAP_TIMEOUT_SECS:-600}}"
    run "$T" python -m pytest tests/test_wire_zero_copy.py \
        "tests/test_runtime.py::test_host_overlap_bit_identical" \
        -q -p no:cacheprovider
    ;;
  elastic)
    T="${SMOKE_TIMEOUT_SECS:-${ELASTIC_TIMEOUT_SECS:-600}}"
    run "$T" python -m deneva_tpu.harness.chaos elastic --quick
    ;;
  geo)
    T="${SMOKE_TIMEOUT_SECS:-${GEO_TIMEOUT_SECS:-900}}"
    run "$T" python -m deneva_tpu.harness.chaos geo --quick
    ;;
  overload)
    T="${SMOKE_TIMEOUT_SECS:-${OVERLOAD_TIMEOUT_SECS:-900}}"
    run "$T" python -m deneva_tpu.harness.chaos overload --quick
    ;;
  partition)
    # full done-windows even under --quick (the PR 4 clamped-window
    # lesson): the fault fires ~3 s in, suspicion needs its silence
    # floor, and the takeover replay-jit stall runs 4-5 s on the CI box
    T="${SMOKE_TIMEOUT_SECS:-${PARTITION_TIMEOUT_SECS:-900}}"
    run "$T" python -m deneva_tpu.harness.chaos partition --quick
    ;;
  repair)
    T="${SMOKE_TIMEOUT_SECS:-${REPAIR_TIMEOUT_SECS:-600}}"
    run "$T" python -m deneva_tpu.harness.chaos repair-contention --quick
    ;;
  ctrl)
    # off-pin first (fast, in-process engine); then the shift/flash/
    # kill scenario — it reuses the kill-one-server recovery machinery
    # plus a governor trip + heal window, so partition-family budget
    T="${SMOKE_TIMEOUT_SECS:-${CTRL_TIMEOUT_SECS:-900}}"
    run "$T" python -m pytest \
        "tests/test_ctrl.py::test_ctrl_off_wire_pin" \
        "tests/test_ctrl.py::test_ctrl_off_knobs_value_identity" \
        -q -p no:cacheprovider
    run "$T" python -m deneva_tpu.harness.chaos ctrl --quick
    ;;
  audit)
    # off-pin first (fast, loopback + in-process engine), then the
    # certify-clean / catch-the-mutation chaos pair
    T="${SMOKE_TIMEOUT_SECS:-${AUDIT_TIMEOUT_SECS:-600}}"
    run "$T" python -m pytest \
        "tests/test_audit.py::test_audit_off_group_outputs" \
        "tests/test_audit.py::test_audit_observation_only_row_state" \
        -q -p no:cacheprovider
    run "$T" python -m deneva_tpu.harness.chaos audit --quick
    ;;
  monitor)
    # off-pin first (fast, loopback); then the gray-slow + aggregator-
    # kill scenario — the kill-one-server recovery machinery plus the
    # stall, so it gets the partition-family budget
    T="${SMOKE_TIMEOUT_SECS:-${MONITOR_TIMEOUT_SECS:-900}}"
    run "$T" python -m pytest \
        "tests/test_metricsbus.py::test_metrics_off_wire_pin" \
        "tests/test_metricsbus.py::test_metrics_off_group_outputs" \
        -q -p no:cacheprovider
    run "$T" python -m deneva_tpu.harness.chaos monitor-grayslow --quick
    ;;
  trace)
    # the off-pin half is fast (loopback ServerNode + ClientNode, no
    # cluster); the chaos half reuses the kill-one-server recovery
    # machinery, so it gets the same budget as the repair gate
    T="${SMOKE_TIMEOUT_SECS:-${TRACE_TIMEOUT_SECS:-600}}"
    run "$T" python -m pytest \
        "tests/test_telemetry.py::test_telemetry_off_wire_pin" \
        "tests/test_telemetry.py::test_telemetry_off_client_pin" \
        -q -p no:cacheprovider
    run "$T" python -m deneva_tpu.harness.chaos trace-kill --quick
    ;;
  mesh)
    # oracle first: the dp=8 cluster reproduces dp=1 bit-for-bit
    # (verdict planes, logs, acks, replay digests; YCSB + TPC-C), then
    # the multichip dry run — sharded compile over every backend family
    # plus the measured-path run_simulation window.  Both need the 8
    # forced host devices BEFORE jax initializes.
    T="${SMOKE_TIMEOUT_SECS:-${MESH_TIMEOUT_SECS:-900}}"
    run "$T" env XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_mesh_cluster.py -q -p no:cacheprovider
    run "$T" env XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
    ;;
  dgcc)
    # off-pin first (router candidates / backend map / device counters /
    # wire bytes all pre-DGCC with the backend unarmed), then the
    # anti-inert half through the REAL measured path: a zipf-0.9
    # write-heavy window where the wavefront must actually chain
    # (wave_max > 1, waves > epochs) while committing with ZERO aborts —
    # the near-zero-abort claim, pinned (a run that silently stopped
    # validating would fail the commit floor, one that stopped chaining
    # would fail wave_max)
    T="${SMOKE_TIMEOUT_SECS:-${DGCC_TIMEOUT_SECS:-600}}"
    run "$T" python -m pytest \
        "tests/test_dgcc.py::test_dgcc_off_pin" \
        "tests/test_dgcc.py::test_engine_hot_zipf_waves_chain_zero_aborts" \
        -q -p no:cacheprovider
    run "$T" python - <<'EOF'
from deneva_tpu.config import CCAlg, Config
from deneva_tpu.engine.driver import run_simulation

cfg = Config(cc_alg=CCAlg.DGCC, zipf_theta=0.9,
             read_perc=0.1, write_perc=0.9,
             synth_table_size=1 << 14, req_per_query=8, max_accesses=8,
             epoch_batch=512, conflict_buckets=2048,
             max_txn_in_flight=2048,
             warmup_secs=0.5, done_secs=2.0).validate()
st = run_simulation(cfg)
c = st.counters
epochs, commits = c["epoch_cnt"], c["total_txn_commit_cnt"]
aborts, waves = c["total_txn_abort_cnt"], c["dgcc_wave_cnt"]
wave_max = c["dgcc_wave_max"]
print(f"[dgcc-smoke] epochs={epochs:.0f} commits={commits:.0f} "
      f"aborts={aborts:.0f} waves={waves:.0f} wave_max={wave_max:.0f} "
      f"fallback={c['dgcc_fallback_cnt']:.0f} "
      f"edges={c['dgcc_edge_cnt']:.0f}")
assert commits > 0, "inert: nothing committed"
assert aborts == 0, f"DGCC aborted {aborts:.0f} txns"
assert wave_max > 1, "inert: wavefront never chained"
assert waves > epochs, "inert: ~1 wave per epoch at zipf 0.9"
print("[dgcc-smoke] PASS")
EOF
    ;;
  lint)
    # static gate; budget 30 s total on the 2-core CI box (graftlint v2
    # measures ~6.5 s full-tree over the 8 families / 78 files, ruff
    # sub-second).  `tools/smoke.sh lint --changed` runs the git-diff-
    # scoped incremental mode instead (~2 s, pre-commit feedback);
    # cross-file families see only the subset there, so the FULL-tree
    # run stays the gate CI must pass.
    T="${SMOKE_TIMEOUT_SECS:-${LINT_TIMEOUT_SECS:-30}}"
    if [ "${1:-}" = "--changed" ]; then
        run "$T" python -m tools.graftlint --changed deneva_tpu/ tools/
    else
        run "$T" python -m tools.graftlint deneva_tpu/ tools/
    fi
    if command -v ruff >/dev/null 2>&1; then
        # generic pyflakes + import-hygiene baseline (ruff.toml); boxes
        # without ruff still get graftlint's imports family
        run "$T" ruff check deneva_tpu tools tests
    else
        echo "[lint] ruff not installed; graftlint imports family stands in"
    fi
    ;;
  *)
    echo "usage: tools/smoke.sh <chaos|escrow|overlap|elastic|geo|overload|partition|repair|ctrl|monitor|trace|mesh|dgcc|lint> [args...]" >&2
    exit 2
    ;;
esac
