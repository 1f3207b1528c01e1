"""Chaos scenario runner: compose transport fault specs into named
scenarios and assert liveness + safety invariants over a real cluster.

The reference has no failure story at all (SURVEY §5.3: a dead peer
hangs its 1 s recv timeouts forever); this harness drives the fault
subsystem end to end —

* **lossy-net**    seeded CL_QRY_BATCH/CL_RSP drops; the client resend
                   path plus server idempotent admission must converge
                   (throughput degrades, nothing wedges or double-acks);
* **dup-storm**    seeded duplication; the server's in-system dedup and
                   the client's first-ack filter keep exactly-once
                   accounting;
* **jittery-net**  uniform extra delay on the open-loop traffic; the
                   deterministic epoch exchange must be order-insensitive;
* **kill-one-server**  fault_kill crashes a server at an epoch boundary
                   (no teardown); the launcher restarts it in recovery
                   mode, it replays its command log, rejoins the mesh,
                   and the run COMPLETES — plus the replayed state is
                   bit-identical to an independent replay of the same
                   log prefix, and each replica log stays a byte prefix
                   of its primary's.

Elastic membership scenarios (runtime/membership.py; `elastic` expands
to all three):

* **elastic-grow**    N=2 active -> 3: a slotless warm spare absorbs an
                   even share of slots mid-run (MIGRATE_BEGIN/ROWS
                   cutover at a group boundary); every server must agree
                   on commits across the cutover and the spare must end
                   up owning slots with migrated rows.
* **elastic-drain**   N=3 -> 2: a node's slots deal onto the survivors;
                   it ends slotless (ready to retire) with zero lost or
                   duplicated txns.
* **elastic-kill-reassign**  a killed server's slots move to the
                   SURVIVORS (log-replay row rebuild) instead of waiting
                   for its restart; liveness + exactly-once across the
                   takeover.

Geo-replication scenarios (runtime/replication.py; `geo` expands to all
three — the tools/smoke.sh ``geo`` gate):

* **geo-region-loss**  3 regions x 1 server, a replica per primary
                   homed one region over; fault_kill under geo kills
                   region 2's WHOLE process set (server 2 + the replica
                   homed there).  Survivors must promote (slot takeover
                   by log replay), commits must continue, exactly-once
                   must hold, and follower snapshot reads must keep
                   serving consistent epoch-boundary snapshots across
                   the loss (per-response version-stamp check + an
                   independent replay of a surviving follower's log
                   reproducing its state digest bit for bit).
* **geo-asymmetric-wan**  2 regions with asymmetric per-link WAN delays
                   (dt_set_peer_delay_us); the epoch exchange and the
                   quorum ack stream must stay live and exactly-once,
                   follower reads keep their consistency contract.
* **geo-replica-lag**  a symmetric 40 ms WAN between the primary's and
                   the follower's regions: quorum acks lag (visible as
                   quorum_stall_ms > 0) and the follower trails, but
                   the shutdown catch-up must converge the follower to
                   the full logged stream (applied == last epoch) with
                   its digest again bit-identical to independent
                   replay.

Partition & gray-failure scenarios (runtime/faildet.py, fencing=true;
`partition` expands to all four — the tools/smoke.sh ``partition``
gate).  All four audit the same safety core: exactly-once accounting,
the SINGLE-WRITER-PER-SLOT bound (the fenced primary's last released
ack strictly precedes the survivors' takeover boundary — the
epoch-boundary ack lease makes a later ack causally impossible), and
the digest-vs-independent-replay oracle (every surviving server's final
state is bit-identical to a replay of its own log under its FINAL map):

* **partition-split**  symmetric blackhole isolates node 2 from both
                   peers (sockets stay open — peer_alive never trips).
                   The majority side {0,1} suspects, reassigns node 2's
                   slots by log replay and continues; node 2 detects it
                   is the minority and self-fences with exit 18
                   (reported as "fenced", not a crash).
* **partition-asym**   one-way blackhole: node 2's frames vanish but it
                   hears everything — the purest gray failure.  The
                   majority fences it with FENCE_NACK (deliverable on
                   the open half-link); its acks were already frozen by
                   the ack lease, so nothing it served conflicts.
* **partition-grayslow**  node 1 turns gray-SLOW (4 s outbound stall on
                   every link; frames arrive, eventually).  Suspicion —
                   not socket death — retires it; the late stragglers
                   of its old incarnation are rejected as stale.
* **partition-flap**   the link to node 2 flaps (1.2 s on/off) below
                   the fencing hysteresis: suspicion rises and HEALS
                   (suspect_cnt/heal_cnt > 0), missed blobs re-ship
                   through the REJOIN catch-up path, nobody is fenced
                   (map_version stays 0) and commits stay identical on
                   all three servers.

Isolation audit (cc/base.audit_observe + runtime/audit.py +
harness/auditgraph.py; `audit` expands to the pair — the tools/smoke.sh
``audit`` gate).  The serializability CERTIFICATE is additionally armed
as a STANDING ORACLE on every kill/partition/repair/geo scenario above
(audit=true in their configs; `_check_audit` joins the per-node
audit_node*.jsonl sidecars into the cluster-wide Direct Serialization
Graph and requires zero dependency cycles and zero cross-node
observation divergence over the surviving servers):

* **audit-clean**     contended OCC (zipf 0.9) with the certifier
                   armed; the run must certify serializable with > 0
                   audited epochs (liveness of the instrument).
* **audit-mutation**  the same run with the seeded ``audit_mutate``
                   fault: OCC's read-set-vs-winner-write-set check is
                   dropped on a chosen epoch window, so stale-read
                   losers commit — the certifier must REJECT the run
                   with a concrete cycle witness (txn tags, edges,
                   owning nodes) naming an epoch inside the mutated
                   window and an rw-classified anomaly (G-single/G2).

Every scenario runs from a fixed fault_seed, so failures reproduce.

CLI:  python -m deneva_tpu.harness.chaos
          [scenario ...|all|elastic|geo|overload|partition|audit]
          [--quick]
"""

from __future__ import annotations

import json
import os
import sys
import time

from deneva_tpu.config import CCAlg, Config, WorkloadKind
from deneva_tpu.engine.epoch import make_dist_step
from deneva_tpu.stats import parse_summary


def chaos_cfg(**kw) -> Config:
    """Small, CI-sized 2-server + 1-client cluster config (the same
    shape tests/test_runtime.py boots), chaos knobs layered on top."""
    base = dict(
        workload=WorkloadKind.YCSB, cc_alg=CCAlg.CALVIN,
        node_cnt=2, client_node_cnt=1,
        epoch_batch=128, conflict_buckets=512, synth_table_size=4096,
        max_txn_in_flight=1024, req_per_query=4, max_accesses=4,
        zipf_theta=0.6, warmup_secs=0.5, done_secs=2.0,
        # full-coverage certification wherever a scenario arms audit:
        # the standing oracles and the mutation catch must see EVERY
        # epoch (the default cadence is the overhead-gate sampling rate)
        audit_cadence=1,
        fault_seed=1234)
    base.update(kw)
    return Config(**base)


# scenario name -> config overrides (composable: overrides win).
# audit=True arms the serializability certificate as a standing oracle
# (the isolation audit plane observes, never decides — every other
# invariant of these scenarios is unchanged by it).
SCENARIOS: dict[str, dict] = {
    "lossy-net": dict(fault_drop_prob=0.05, fault_resend_us=150_000.0,
                      audit=True),
    "dup-storm": dict(fault_dup_prob=0.30, audit=True),
    "jittery-net": dict(fault_delay_jitter_us=20_000.0, audit=True),
    "kill-one-server": dict(
        fault_kill="1:64", logging=True, replica_cnt=1, done_secs=4.0,
        fault_recovery_timeout_s=300.0, audit=True),
    # elastic membership (log dirs on /dev/shm: /tmp is 9p on the CI
    # box and the per-epoch fsync would throttle the timed gate)
    "elastic-grow": dict(
        node_cnt=3, epoch_batch=256, elastic=True, elastic_spare_cnt=1,
        elastic_plan="grow:2:16", done_secs=3.0),
    "elastic-drain": dict(
        node_cnt=3, epoch_batch=256, elastic=True,
        elastic_plan="drain:2:16", done_secs=3.0),
    # done_secs=8: the survivors' replay-jit takeover stall measured
    # 4.4-4.7 s on the CI box — a 4 s window was intermittently
    # swallowed whole (zero commits in the measured window)
    "elastic-kill-reassign": dict(
        node_cnt=3, epoch_batch=256, elastic=True, fault_kill="2:64",
        logging=True, done_secs=8.0, log_dir="/dev/shm/deneva_logs",
        fault_recovery_timeout_s=300.0),
    # geo-replication tier (log dirs on /dev/shm: replicas fsync every
    # record).  Windows stay FULL under --quick like the elastic family:
    # the region-loss promote/replay stall measured 4-5 s on the 2-core
    # CI box and a WAN-stretched epoch cadence needs its whole window —
    # clamping either reports zero commits (the PR 4 flake class).
    # two clients so region 1 has a HOME client targeting primary 1 —
    # the primary whose only follower dies with region 2.  Its held
    # acks must keep releasing across the loss (the durable_quorum
    # live-set degradation; a frozen horizon wedges exactly this
    # client's inflight credit and the scenario reports zero commits)
    "geo-region-loss": dict(
        audit=True,
        node_cnt=3, client_node_cnt=2, epoch_batch=256, elastic=True,
        geo=True, geo_region_cnt=3, geo_quorum=1, geo_read_perc=0.1,
        replica_cnt=1, logging=True, fault_kill="2:64", done_secs=10.0,
        log_dir="/dev/shm/deneva_logs", fault_recovery_timeout_s=300.0),
    "geo-asymmetric-wan": dict(
        audit=True,
        node_cnt=2, epoch_batch=256, elastic=True, geo=True,
        geo_region_cnt=2, geo_quorum=1, geo_read_perc=0.15,
        geo_wan_us="0>1:8000,1>0:30000", replica_cnt=1, logging=True,
        done_secs=4.0, log_dir="/dev/shm/deneva_logs"),
    "geo-replica-lag": dict(
        audit=True,
        node_cnt=2, epoch_batch=256, elastic=True, geo=True,
        geo_region_cnt=2, geo_quorum=1, geo_read_perc=0.15,
        geo_wan_us="0-1:40000", replica_cnt=1, logging=True,
        done_secs=5.0, log_dir="/dev/shm/deneva_logs"),
    # transaction repair under contention + crash (engine/repair.py):
    # zipf-0.9 write-heavy YCSB on OCC (merged protocol — the repair
    # sub-rounds are part of the replicated deterministic verdict) with
    # repair ON, plus the kill-one-server crash/recovery shape.  The
    # invariants this buys: exactly-once accounting holds with salvaged
    # txns acked as commits (a salvage double-ack would trip the
    # unique-acks <= unique-sends check), AND bit-identical replay — the
    # recovered node's state digest must match an independent replay of
    # the same log prefix THROUGH THE REPAIR SUB-ROUNDS (the repair-
    # armed epoch body is the replay body).  rep_salvaged_cnt > 0 is
    # asserted so the scenario can never silently pass with repair
    # inert.
    "repair-contention": dict(
        audit=True,
        cc_alg=CCAlg.OCC, dist_protocol="merged", repair=True,
        zipf_theta=0.9, write_perc=0.9, read_perc=0.1,
        synth_table_size=1024, fault_kill="1:64", logging=True,
        replica_cnt=1, done_secs=4.0, log_dir="/dev/shm/deneva_logs",
        fault_recovery_timeout_s=300.0),
    # transaction flight recorder under crash/recovery (runtime/
    # telemetry.py + harness/txntrace.py): the kill-one-server shape
    # with telemetry armed at a dense sampling rate.  The invariants
    # this buys: the TRACE-COMPLETENESS oracle — every sampled txn that
    # earned a commit verdict has a gap-free send <= admit <= batch <=
    # verdict [<= release] <= ack chain with zero ordering inversions,
    # at least one chain carries the full quorum hold->release hop, and
    # the merger renders the whole run as one flow-linked Chrome trace
    # — all across a crash (the killed node flushes its ring at the
    # boundary, the recovered incarnation appends; events intact to the
    # boundary survive exactly like the command log).
    "trace-kill": dict(
        fault_kill="1:64", logging=True, replica_cnt=1, done_secs=4.0,
        fault_recovery_timeout_s=300.0, telemetry=True,
        telemetry_sample=8, log_dir="/dev/shm/deneva_logs"),
    # overload robustness tier (runtime/loadgen.py + runtime/
    # admission.py): open-loop arrival processes against per-tenant
    # admission control.  Windows stay FULL under --quick like the
    # elastic/geo families (the PR 4 zero-commit flake class): the
    # flash burst + post-burst recovery and the backoff re-entry
    # cadence must all fit INSIDE the measured window on the 2-core CI
    # box, and a clamped window would report zero post-burst acks.
    #
    # flash: x10 open-loop burst at t=2.5s for 1.5s with a small seeded
    # drop rate layered on (exactly-once must hold under NACK + backoff
    # re-entry + loss resend + idempotent admission all at once);
    # admission bounds the queue, NACKs the overflow, and goodput must
    # recover after the burst (post_flash_ack_cnt).
    # max_txn_in_flight is raised in all three: the open-loop generator
    # must be able to flood PAST the server's queue bound (with the
    # default 1024-cap the client throttle binds first and admission
    # never sheds — measured on the CI box: depth pinned at the client
    # cap, zero NACKs)
    # queue bound 1024 against ~5k/s per-server service (measured on
    # the CI box): the x10 burst (50k/s offered for 1.5s) outruns the
    # drain decisively, so the shed path fires thousands of NACKs even
    # on a fast day — a 2048 bound at 4k/s base shed only ~20 (one slow
    # epoch group from zero), too close to a variance flake
    "overload-flash": dict(
        epoch_batch=256, max_txn_in_flight=16384, admission=True,
        admission_queue_max=1024, arrival_process="flash",
        arrival_rate=5000.0, arrival_flash_at_s=2.5,
        arrival_flash_secs=1.5, arrival_flash_factor=10.0,
        fault_drop_prob=0.02, fault_resend_us=500_000.0, done_secs=8.0),
    # aggressor: tenant 1 offers 6x tenant 0's load against equal
    # per-tenant quotas + the queue-delay SLO; the aggressor must be
    # throttled (NACK/shed) while the quota-respecting tenant keeps its
    # service rate and latency
    "overload-aggressor": dict(
        epoch_batch=256, max_txn_in_flight=16384, admission=True,
        admission_queue_max=4096, arrival_process="poisson",
        arrival_rate=3500.0, tenant_cnt=2, tenant_weights="1,6",
        tenant_quota=400.0, tenant_burst_s=0.25,
        admission_slo_ms=200.0, done_secs=6.0),
    # diurnal: sinusoid wave whose peak crests over steady capacity;
    # admission keeps the queue bounded through the crest and the
    # trough drains it — liveness + exactly-once across the wave
    "overload-diurnal": dict(
        epoch_batch=256, max_txn_in_flight=16384, admission=True,
        admission_queue_max=1024, arrival_process="diurnal",
        arrival_rate=5000.0, arrival_period_s=2.0, arrival_amp=0.8,
        done_secs=6.0),
    # live metrics bus under gray failure + aggregator crash (runtime/
    # metricsbus.py): metrics armed on a 3-server cluster; node 1 turns
    # gray-SLOW (1.5 s additive outbound stall from t=3 s — frames
    # arrive, late) while node 0 — the BOOT AGGREGATOR — is fault_killed
    # at an epoch boundary and restarted in recovery mode (the
    # kill-one-server shape).  The invariants this buys: the bus stream
    # carries frames from every node kind, the STRAGGLER watchdog names
    # exactly the stalled node (transit-lag skew vs the cluster median —
    # never the killed-and-recovered aggregator, whose own frames are
    # local), and the aggregator SURVIVES its crash: the recovered
    # incarnation appends to the same metrics_bus stream and post-
    # recovery frames appear (epochs past the resume boundary).  No
    # fencing: a gray-slow peer without the detector is just a slow
    # cluster — exactly the situation a live monitor must surface.
    "monitor-grayslow": dict(
        node_cnt=3, epoch_batch=256, synth_table_size=6144,
        metrics=True, logging=True, replica_cnt=1, fault_kill="0:64",
        fault_peer_stall="1:1500:3.0", done_secs=10.0,
        log_dir="/dev/shm/deneva_logs", fault_recovery_timeout_s=300.0),
    # partition & gray-failure tolerance (runtime/faildet.py): fencing
    # armed on a 3-server elastic cluster, the native partition/stall
    # blackholes driving it.  Windows stay FULL under --quick like the
    # elastic/geo/overload families (the PR 4 clamped-window lesson):
    # the fault fires ~3 s in (past warmup, leaving a healthy commit
    # prefix inside the measured window), suspicion needs its 2 s
    # silence floor, and the survivors' replay-jit takeover stall
    # measured 4-5 s on the 2-core CI box — a clamped window would
    # swallow all of it and report zero commits.
    "partition-split": dict(
        audit=True,
        node_cnt=3, epoch_batch=256, elastic=True, fencing=True,
        logging=True, fault_partition="2-0:3.0,2-1:3.0", done_secs=10.0,
        log_dir="/dev/shm/deneva_logs", fault_recovery_timeout_s=300.0),
    "partition-asym": dict(
        audit=True,
        node_cnt=3, epoch_batch=256, elastic=True, fencing=True,
        logging=True, fault_partition="2>0:3.0,2>1:3.0", done_secs=10.0,
        log_dir="/dev/shm/deneva_logs", fault_recovery_timeout_s=300.0),
    # stall 4 s against the 2 s suspicion floor: the initial bubble is
    # what the detector sees (a constant delay pipelines afterwards —
    # only the first gap is silence), so it must clear the floor with
    # margin on a loaded box
    "partition-grayslow": dict(
        audit=True,
        node_cnt=3, epoch_batch=256, elastic=True, fencing=True,
        logging=True, fault_peer_stall="1:4000:3.0", done_secs=10.0,
        log_dir="/dev/shm/deneva_logs", fault_recovery_timeout_s=300.0),
    # flap 1.2 s on/off under a LOWERED phi threshold (suspicion crosses
    # ~0.9 s into each outage) but a RAISED 3 s fencing floor (no outage
    # ever clears it): suspicion must rise and heal repeatedly with
    # nobody fenced — the hysteresis contract, plus the REJOIN blob
    # catch-up that makes a healed link's dropped epochs recoverable
    "partition-flap": dict(
        audit=True,
        node_cnt=3, epoch_batch=256, elastic=True, fencing=True,
        logging=True, fault_partition="2-0:2.0,2-1:2.0",
        fault_partition_flap_s=1.2, fencing_phi=4.0,
        fencing_suspect_s=3.0, done_secs=8.0,
        log_dir="/dev/shm/deneva_logs", fault_recovery_timeout_s=300.0),
    # isolation audit plane (cc/base.audit_observe + runtime/audit.py +
    # harness/auditgraph.py): contended OCC under the merged protocol
    # (the certifier needs the replicated deterministic verdict) on a
    # small hot table.  audit-clean must CERTIFY serializable with the
    # instrument demonstrably live; audit-mutation drops OCC's
    # read-set-vs-winner-write-set check on epochs [48, 56) — stale-
    # read losers commit and execute, so reciprocal read/write overlaps
    # at zipf 0.9 form real rw cycles — and the certifier must REJECT
    # with a cycle witness naming an epoch inside exactly that window
    # (the anti-inert contract: a certifier that cannot catch a seeded
    # isolation bug proves nothing as an oracle).
    # self-driving control plane under load shift + signal loss
    # (runtime/controller.py): ctrl armed on a merged-OCC cluster with
    # admission + metrics + the audit certificate standing, driven by
    # the three stimuli of the tentpole contract at once — a mid-run
    # zipf hotness shift (0 -> 0.9 at t=2.5 s, the client's staged
    # second ring), an open-loop flash crowd cresting over the
    # admission bound, and a fault_kill of node 0 (the metrics
    # aggregator AND a merged-protocol voter: group progress stalls
    # cluster-wide while it replays, which is exactly the stale-signal
    # shape the governor must catch).  The invariants this buys: the
    # controller DECIDED (armed rows on every surviving server), the
    # governor TRIPPED to static on the stall and RE-ENGAGED after the
    # heal streak, every node's decision stream replays bit-for-bit
    # from its recorded signals (replay_decisions == []), and the
    # standing oracles hold across all of it — exactly-once accounting,
    # digest-vs-replay recovery, serializability certificate green.
    "ctrl-shift-degrade": dict(
        audit=True,
        cc_alg=CCAlg.OCC, dist_protocol="merged",
        ctrl=True, escrow_order_free=False, metrics=True,
        admission=True, max_txn_in_flight=16384,
        admission_queue_max=1024, admission_slo_ms=200.0,
        tenant_quota=2500.0, tenant_burst_s=0.25,
        arrival_process="flash", arrival_rate=3000.0,
        arrival_flash_at_s=2.5, arrival_flash_secs=1.5,
        arrival_flash_factor=6.0,
        zipf_theta=0.0, zipf_shift="0.9:2.5",
        synth_table_size=1024,
        fault_kill="0:64", logging=True, replica_cnt=1,
        done_secs=10.0, log_dir="/dev/shm/deneva_logs",
        fault_recovery_timeout_s=300.0),
    "audit-clean": dict(
        cc_alg=CCAlg.OCC, dist_protocol="merged", audit=True,
        zipf_theta=0.9, synth_table_size=1024, done_secs=2.0),
    "audit-mutation": dict(
        cc_alg=CCAlg.OCC, dist_protocol="merged", audit=True,
        audit_mutate="occ-read-skip:48:8",
        zipf_theta=0.9, synth_table_size=1024, done_secs=2.0),
}

# `elastic` on the CLI expands to the three membership scenarios (the
# tools/smoke.sh elastic gate); `geo` to the geo-replication trio;
# `overload` to the admission-control trio; `audit` to the
# isolation-audit pair
ELASTIC_SCENARIOS = ("elastic-grow", "elastic-drain",
                     "elastic-kill-reassign")
GEO_SCENARIOS = ("geo-region-loss", "geo-asymmetric-wan",
                 "geo-replica-lag")
OVERLOAD_SCENARIOS = ("overload-flash", "overload-aggressor",
                      "overload-diurnal")
PARTITION_SCENARIOS = ("partition-split", "partition-asym",
                       "partition-grayslow", "partition-flap")
AUDIT_SCENARIOS = ("audit-clean", "audit-mutation")
CTRL_SCENARIOS = ("ctrl-shift-degrade",)


class ChaosViolation(AssertionError):
    """A liveness or safety invariant failed under fault injection."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise ChaosViolation(what)


def run_scenario(name: str, quick: bool = False,
                 quiet: bool = False, **overrides) -> dict:
    """Run one named scenario; returns a report dict (raises
    ChaosViolation on an invariant failure, anything else on a crash
    of the harness itself)."""
    from deneva_tpu.runtime.launch import run_cluster

    if name not in SCENARIOS:
        raise KeyError(f"unknown scenario {name!r} "
                       f"(have {sorted(SCENARIOS)})")
    spec = dict(SCENARIOS[name])
    if quick and not name.startswith(("elastic-", "geo-", "overload-",
                                      "partition-", "monitor-",
                                      "audit-", "ctrl-")):
        # elastic scenarios keep their full window: the cutover stall
        # (row stream + boundary sync, 1.4-2.2 s measured on the CI box;
        # ~5 s replay-jit for kill-reassign) would otherwise swallow a
        # clamped measured window and report zero commits
        spec["done_secs"] = min(spec.get("done_secs", 2.0), 1.5)
    spec.update(overrides)
    cfg = chaos_cfg(**spec)
    run_id = f"chaos_{name.replace('-', '_')}_{os.getpid()}"
    t0 = time.monotonic()
    out = run_cluster(cfg, platform="cpu", run_id=run_id)
    wall = time.monotonic() - t0
    report = {"scenario": name, "wall_secs": round(wall, 1),
              "nodes": {nid: kind for nid, (kind, _) in out.items()}}
    _check_invariants(name, cfg, out, run_id, report)
    if not quiet:
        print(f"[chaos] {name}: OK in {wall:.1f}s  "
              + " ".join(f"{k}={v}" for k, v in report.items()
                         if k not in ("scenario", "nodes")), flush=True)
    return report


def _check_invariants(name: str, cfg: Config, out: dict, run_id: str,
                      report: dict) -> None:
    n_srv, n_cl = cfg.node_cnt, cfg.client_node_cnt
    n_all = n_srv + n_cl + cfg.replica_cnt * n_srv
    # liveness: every node reported a summary (run_cluster raises on a
    # node error; a wedged node would have tripped its timeout)
    _require(set(out) == set(range(n_all)),
             f"{name}: nodes {sorted(set(range(n_all)) - set(out))} "
             "never reported")
    # an elastic-reassigned server reports as kind "killed" with no
    # summary (it was retired in place, never restarted)
    srv_ids = [s for s in range(n_srv) if out[s][0] == "server"]
    srv = [parse_summary(out[s][1]) for s in srv_ids]
    cls = [parse_summary(out[n_srv + c][1]) for c in range(n_cl)]
    commits = [s["total_txn_commit_cnt"] for s in srv]
    report["commits"] = commits
    report["client_acked"] = [c["txn_cnt"] for c in cls]
    report["resends"] = [c.get("resend_cnt", 0.0) for c in cls]
    report["dup_acks"] = [c.get("dup_ack_cnt", 0.0) for c in cls]
    for c in cls:
        # exactly-once accounting: unique acks can never exceed unique
        # sends (txn_cnt counts first acks only; resends don't add to
        # sent_cnt) — a double-commit or double-count breaks this
        _require(c["txn_cnt"] > 0, f"{name}: a client was starved")
        _require(c["txn_cnt"] <= c["sent_cnt"],
                 f"{name}: more unique acks ({c['txn_cnt']}) than unique "
                 f"sends ({c['sent_cnt']}) — a tag was acked twice")
    if name not in ("kill-one-server", "repair-contention",
                    "trace-kill", "monitor-grayslow",
                    "ctrl-shift-degrade"):
        # deterministic replicated validation must survive the faults
        # (and any membership cutover): identical [summary] commit
        # counts on every reporting server — except where a server was
        # killed and restarted (its measured window differs)
        _require(len(set(commits)) == 1 and commits[0] > 0,
                 f"{name}: server commit counts diverged: {commits}")
    if name == "lossy-net":
        _require(sum(report["resends"]) > 0,
                 "lossy-net: drops injected but the resend path never "
                 "fired (is fault injection live?)")
    if name == "dup-storm":
        dup_seen = (sum(report["dup_acks"])
                    + sum(s.get("dup_admit_cnt", 0.0) for s in srv)
                    + sum(s.get("net_msg_dup", 0.0) for s in srv)
                    + sum(c.get("net_msg_dup", 0.0) for c in cls))
        _require(dup_seen > 0, "dup-storm: no duplicate was ever seen")
    if name == "kill-one-server":
        _check_recovery(cfg, out, run_id, report)
    if name == "trace-kill":
        # the full crash/recovery oracle first (same machinery as
        # kill-one-server), then the trace-completeness audit on top
        _check_recovery(cfg, out, run_id, report)
        _check_trace(cfg, srv, cls, run_id, report)
    if name == "repair-contention":
        # repair must actually have fired (a scenario that passes with
        # repair inert proves nothing) and every salvaged txn is a
        # commit, never an abort: rep_salvaged_cnt is disjoint from
        # total_txn_abort_cnt by the run_repair contract, so the
        # exactly-once check above already covered salvage acks.  Then
        # the full crash/recovery oracle: bit-identical replay THROUGH
        # the repair sub-rounds (the repair-armed epoch body is the
        # replay body).
        salv = [s.get("rep_salvaged_cnt", 0.0) for s in srv]
        report["rep_salvaged"] = salv
        _require(sum(salv) > 0,
                 "repair-contention: zipf-0.9 write-heavy ran but no "
                 "txn was ever salvaged (is repair live?)")
        for s in srv:
            _require("rep_salvaged_cnt" in s and "rep_fallback_cnt" in s
                     and "rep_frontier_cnt" in s,
                     "repair-contention: a server summary lacks repair "
                     "accounting")
        _check_recovery(cfg, out, run_id, report)
    if name == "monitor-grayslow":
        # the crash/recovery oracle first (node 0 = the aggregator is
        # the killed node), then the bus/watchdog audit on top
        _check_recovery(cfg, out, run_id, report)
        _check_monitor(cfg, srv, cls, run_id, report)
    if name.startswith("ctrl-"):
        # the crash/recovery oracle first (node 0 = the aggregator is
        # the killed node), then the controller's own invariants
        _check_recovery(cfg, out, run_id, report)
        _check_ctrl(name, cfg, out, run_id, report)
    if name.startswith("elastic-"):
        _check_elastic(name, cfg, out, report)
    if name.startswith("geo-"):
        _check_geo(name, cfg, out, run_id, report)
    if name.startswith("overload-"):
        _check_overload(name, cfg, srv, cls, report)
    if name.startswith("partition-"):
        _check_partition(name, cfg, out, run_id, report)
    if cfg.audit:
        # the standing serializability oracle (and, under audit_mutate,
        # its anti-inert inversion) — last, so the violation report
        # lands on an otherwise-validated run
        _check_audit(name, cfg, out, run_id, report)


def _check_elastic(name: str, cfg: Config, out: dict, report: dict) -> None:
    """Membership invariants: exactly one cutover, full slot coverage
    after it, rows actually moved, and the subject node's role change
    (spare -> owner for grow, owner -> slotless for drain, dead ->
    reassigned for kill)."""
    from deneva_tpu.runtime.membership import initial_map

    n_slots = initial_map(cfg).n_slots
    srv = {s: parse_summary(out[s][1]) for s in range(cfg.node_cnt)
           if out[s][0] == "server"}
    report["map_version"] = sorted(v.get("map_version", -1)
                                   for v in srv.values())
    _require(all(v.get("map_version", -1) == 1 for v in srv.values()),
             f"{name}: map versions diverged: {report['map_version']}")
    _require(all(v.get("rebalance_cnt", 0) == 1 for v in srv.values()),
             f"{name}: expected exactly one rebalance everywhere")
    owned = {s: v.get("owned_slots", -1) for s, v in srv.items()}
    report["owned_slots"] = owned
    report["rows_migrated"] = {s: v.get("rows_migrated", 0)
                               for s, v in srv.items()}
    if name == "elastic-grow":
        node = cfg.elastic_plan_spec()[1]
        _require(sum(owned.values()) == n_slots,
                 f"{name}: slot coverage broken: {owned} != {n_slots}")
        _require(owned[node] > 0,
                 f"{name}: the spare never absorbed slots: {owned}")
        _require(srv[node].get("rows_migrated_in", 0) > 0,
                 f"{name}: no rows streamed onto the grown node")
        _require(all(srv[s].get("rows_migrated_out", 0) > 0
                     for s in srv if s != node),
                 f"{name}: a donor streamed no rows")
    elif name == "elastic-drain":
        node = cfg.elastic_plan_spec()[1]
        _require(sum(owned.values()) == n_slots,
                 f"{name}: slot coverage broken: {owned} != {n_slots}")
        _require(owned[node] == 0,
                 f"{name}: the drained node still owns slots: {owned}")
        _require(srv[node].get("rows_migrated_out", 0) > 0,
                 f"{name}: the drained node streamed no rows")
        _require(all(srv[s].get("rows_migrated_in", 0) > 0
                     for s in srv if s != node),
                 f"{name}: a survivor received no rows")
    elif name == "elastic-kill-reassign":
        kill_node, _ = cfg.fault_kill_spec()
        _require(out[kill_node][0] == "killed",
                 f"{name}: the killed node was restarted instead of "
                 "reassigned")
        _require(kill_node not in srv and len(srv) == cfg.node_cnt - 1,
                 f"{name}: unexpected server reports: {sorted(srv)}")
        _require(sum(owned.values()) == n_slots,
                 f"{name}: survivors do not cover the slot space: "
                 f"{owned} != {n_slots}")
        _require(all(v.get("rows_migrated_in", 0) > 0
                     for v in srv.values()),
                 f"{name}: a survivor rebuilt no rows by replay")


def _check_geo(name: str, cfg: Config, out: dict, run_id: str,
               report: dict) -> None:
    """Geo-tier invariants: follower snapshot reads really served with
    their consistency contract intact (per-response version-stamp and
    boundary-monotonicity checks report zero violations), a surviving
    follower's state is BIT-IDENTICAL to an independent replay of its
    own log (snapshot-consistency oracle), quorum accounting is present
    on every primary, and the per-scenario shape (promotion after a
    region loss, convergent catch-up under replica lag) holds."""
    from deneva_tpu.runtime import replication as georepl

    n_srv, n_cl = cfg.node_cnt, cfg.client_node_cnt
    base = n_srv + n_cl
    srv = {s: parse_summary(out[s][1]) for s in range(n_srv)
           if out[s][0] == "server"}
    cls = [parse_summary(out[n_srv + c][1]) for c in range(n_cl)]
    repl = {r: parse_summary(out[base + r][1])
            for r in range(cfg.replica_cnt * n_srv)
            if out[base + r][0] == "replica"}
    # follower reads: issued, answered, and clean on both client-side
    # consistency checks
    reads = sum(c.get("follower_read_cnt", 0.0) for c in cls)
    report["follower_reads"] = reads
    _require(reads > 0, f"{name}: no follower snapshot read was served")
    _require(sum(f.get("follower_read_cnt", 0.0)
                 for f in repl.values()) > 0,
             f"{name}: no follower reports serving reads")
    for c in cls:
        _require(c.get("follower_read_ver_viol", 0.0) == 0,
                 f"{name}: a follower served a row version newer than "
                 "its snapshot boundary")
        _require(c.get("follower_read_mono_viol", 0.0) == 0,
                 f"{name}: a follower's served boundary regressed")
    # every reporting primary carries the quorum ledger
    for s, v in srv.items():
        _require("quorum_stall_ms" in v and "quorum_acked_epoch" in v,
                 f"{name}: server {s} summary lacks quorum accounting")
    # snapshot consistency: an independent full-ownership replay of a
    # surviving follower's own log must reproduce its state digest bit
    # for bit at the same applied epoch
    log_dir = os.path.join(cfg.log_dir, run_id)
    rid_rel = sorted(repl)[0]
    side_path = os.path.join(log_dir,
                             f"replica{base + rid_rel}.follower.json")
    _require(os.path.exists(side_path),
             f"{name}: follower sidecar missing at {side_path}")
    with open(side_path) as f:
        side = json.load(f)
    report["follower_applied"] = side["applied_epoch"]
    from deneva_tpu.runtime.logger import replay_into, state_digest
    node_cfg = cfg.replace(node_id=side["primary"], part_cnt=n_srv,
                           recover=False, fault_kill="")
    _, wl, step, db, cc0, stats0 = georepl.follower_boot(
        node_cfg, side["primary"])
    db, _, _, last = replay_into(
        os.path.join(log_dir, f"replica{base + rid_rel}.log.bin"),
        node_cfg, wl, step, db, cc0, stats0,
        stop_epoch=side["applied_epoch"] + 1)
    _require(last == side["applied_epoch"],
             f"{name}: follower log replay ended at {last}, follower "
             f"applied {side['applied_epoch']}")
    digest = state_digest(db)
    report["follower_digest_match"] = digest == side["state_digest"]
    _require(report["follower_digest_match"],
             f"{name}: follower snapshot state diverged from independent "
             f"replay ({digest[:16]} != {side['state_digest'][:16]})")
    if name == "geo-region-loss":
        kill_node, _ = cfg.fault_kill_spec()
        _require(out[kill_node][0] == "killed",
                 f"{name}: the killed primary was restarted instead of "
                 "promoted around")
        dead_repl = [r for r in range(cfg.replica_cnt * n_srv)
                     if georepl.region_of(cfg, base + r)
                     == georepl.region_of(cfg, kill_node)]
        for r in dead_repl:
            _require(out[base + r][0] == "killed",
                     f"{name}: replica {base + r} homed in the lost "
                     "region survived it")
        _require(all(v.get("promote_cnt", 0.0) == 1 for v in srv.values()),
                 f"{name}: expected exactly one promotion on every "
                 f"survivor: { {s: v.get('promote_cnt') for s, v in srv.items()} }")
        report["promotes"] = {s: v.get("promote_cnt") for s, v in srv.items()}
    if name == "geo-replica-lag":
        _require(any(v.get("quorum_stall_ms", 0.0) > 0
                     for v in srv.values()),
                 f"{name}: 40 ms WAN acks but no quorum stall was ever "
                 "measured")
        # catch-up convergence: the follower applied the whole stream
        epochs = {s: v["epoch_cnt"] for s, v in srv.items()}
        for r, v in repl.items():
            p = r % n_srv
            _require(v.get("applied_epoch", -1) == epochs[p] - 1,
                     f"{name}: follower of {p} applied "
                     f"{v.get('applied_epoch')} of {epochs[p] - 1}")
        report["stale_max"] = max(v.get("stale_read_max_epochs", 0)
                                  for v in repl.values())


def _check_overload(name: str, cfg: Config, srv: list[dict],
                    cls: list[dict], report: dict) -> None:
    """Overload-tier invariants: the admission queue stayed BOUNDED
    (depth never exceeded the configured cap), shedding actually fired
    where the scenario oversubscribes, goodput recovered after a flash
    burst, and per-tenant fairness held under an aggressor — all on top
    of the global exactly-once check (unique acks <= unique sends, which
    the NACK + backoff re-entry path must preserve)."""
    depth_max = max(s.get("adm_queue_depth_max", 0.0) for s in srv)
    nacks = sum(s.get("adm_nack_cnt", 0.0) + s.get("adm_shed_cnt", 0.0)
                for s in srv)
    report["adm_queue_depth_max"] = depth_max
    report["adm_nacked_total"] = nacks
    for s in srv:
        _require("adm_admit_cnt" in s and "adm_queue_depth_max" in s,
                 f"{name}: a server summary lacks admission accounting")
        _require(s.get("adm_queue_depth_max", 0.0)
                 <= cfg.admission_queue_max,
                 f"{name}: admission queue depth "
                 f"{s.get('adm_queue_depth_max')} exceeded the bound "
                 f"{cfg.admission_queue_max}")
    client_nacks = sum(c.get("nack_cnt", 0.0) for c in cls)
    report["client_nacks"] = client_nacks
    report["nack_resends"] = sum(c.get("nack_resend_cnt", 0.0)
                                 for c in cls)
    if name == "overload-flash":
        _require(nacks > 0 and client_nacks > 0,
                 f"{name}: a x{cfg.arrival_flash_factor} flash crowd "
                 "was never shed (is admission live?)")
        post = sum(c.get("post_flash_ack_cnt", 0.0) for c in cls)
        report["post_flash_acks"] = post
        _require(post > 0,
                 f"{name}: no ack after the burst window — goodput "
                 "never recovered to steady state")
    if name == "overload-aggressor":
        # per-tenant fairness: the aggressor (tenant 1, offering 6x) is
        # throttled; the quota-respecting tenant keeps its service rate
        # and its latency tail stays BELOW the aggressor's (NACKed-then-
        # re-entered txns measure from first send, so throttling shows
        # up exactly there)
        _require(nacks > 0, f"{name}: the aggressor was never throttled")
        ratio = []
        for t in (0, 1):
            sent = sum(c.get(f"tenant{t}_sent_cnt", 0.0) for c in cls)
            acked = sum(c.get(f"tenant{t}_acked_cnt", 0.0) for c in cls)
            _require(sent > 0 and acked > 0,
                     f"{name}: tenant {t} starved (sent={sent}, "
                     f"acked={acked})")
            ratio.append(acked / sent)
        report["tenant_ack_ratio"] = [round(r, 3) for r in ratio]
        _require(ratio[0] > ratio[1] + 0.1,
                 f"{name}: quota tenant's ack ratio {ratio[0]:.2f} not "
                 f"clearly above the aggressor's {ratio[1]:.2f}")
        p99 = [max(c.get(f"tenant{t}_latency_p99", 0.0) for c in cls)
               for t in (0, 1)]
        report["tenant_p99_s"] = [round(p, 3) for p in p99]
        _require(p99[0] < p99[1],
                 f"{name}: quota tenant's p99 {p99[0]:.3f}s not below "
                 f"the throttled aggressor's {p99[1]:.3f}s")
    if name == "overload-diurnal":
        # the wave's crest oversubscribes; the bounded queue + NACKs
        # must keep every server live through it (commits already
        # checked identical and > 0 above)
        _require(all(s.get("adm_admit_cnt", 0.0) > 0 for s in srv),
                 f"{name}: a server admitted nothing across the wave")


def _check_partition(name: str, cfg: Config, out: dict, run_id: str,
                     report: dict) -> None:
    """Fencing invariants.  The safety core every scenario audits:

    * **single-writer-per-slot** — the fenced primary's last RELEASED
      ack (its ``fenced.json`` sidecar records it) strictly precedes
      the survivors' takeover boundary, so no slot was ever acked by
      two primaries at overlapping epochs.  The epoch-boundary ack
      lease is what makes this causal (an epoch's CL_RSPs release only
      after a majority confirmed its blob), and this check is its
      end-to-end teeth.
    * **digest-vs-independent-replay** — every surviving server's final
      state is bit-identical to a full replay of its OWN log under its
      FINAL map (for a survivor that absorbed slots, replaying the
      whole stream under the post-reassignment ownership reproduces
      both its original rows and the adopted ones — the same argument
      `_adopt_by_replay` rests on).
    * per-scenario shape: who got fenced, how (minority vs FENCE_NACK),
      slot coverage after the takeover, heal counting for the flap.
    """
    import numpy as np

    from deneva_tpu.cc import get_backend
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.runtime.logger import (iter_record_spans, replay_into,
                                           state_digest)
    from deneva_tpu.runtime.membership import MEMBER_KEY, initial_map
    from deneva_tpu.workloads import get_workload

    n_srv = cfg.node_cnt
    log_dir = os.path.join(cfg.log_dir, run_id)
    srv = {s: parse_summary(out[s][1]) for s in range(n_srv)
           if out[s][0] == "server"}
    for s, v in srv.items():
        _require(all(k in v for k in ("fence_nack_cnt", "suspect_cnt",
                                      "heal_cnt", "phi_peak")),
                 f"{name}: server {s} summary lacks fencing accounting")
    fenced = {"partition-split": 2, "partition-asym": 2,
              "partition-grayslow": 1}.get(name)
    report["fenced_node"] = fenced
    if fenced is None:
        # flap: suspicion must rise AND heal, with nobody fenced and
        # the map untouched — the hysteresis half of the contract
        _require(len(srv) == n_srv,
                 f"{name}: a server was fenced under a sub-floor flap: "
                 f"{ {s: out[s][0] for s in range(n_srv)} }")
        _require(all(v.get("map_version", -1) == 0 for v in srv.values()),
                 f"{name}: the map moved under a flap that should heal")
        report["suspects"] = sum(v.get("suspect_cnt", 0)
                                 for v in srv.values())
        report["heals"] = sum(v.get("heal_cnt", 0) for v in srv.values())
        _require(report["suspects"] > 0,
                 f"{name}: the flap never crossed the (lowered) phi "
                 "threshold — is the detector live?")
        _require(report["heals"] > 0,
                 f"{name}: suspicions rose but never healed")
    else:
        _require(out[fenced][0] == "fenced",
                 f"{name}: node {fenced} reported "
                 f"{out[fenced][0]!r}, expected the exit-18 'fenced' "
                 "outcome")
        _require(fenced not in srv and len(srv) == n_srv - 1,
                 f"{name}: unexpected server reports: {sorted(srv)}")
        n_slots = initial_map(cfg).n_slots
        owned = {s: v.get("owned_slots", -1) for s, v in srv.items()}
        report["owned_slots"] = owned
        _require(sum(owned.values()) == n_slots,
                 f"{name}: survivors do not cover the slot space: "
                 f"{owned} != {n_slots}")
        _require(all(v.get("map_version", -1) == 1 for v in srv.values()),
                 f"{name}: survivor map versions diverged")
        _require(all(v.get("rows_migrated_in", 0) > 0
                     for v in srv.values()),
                 f"{name}: a survivor rebuilt no rows by replay")
        # every survivor derived the same takeover boundary with no
        # negotiation (group-aligned TX-side silence)
        re_eps = {int(v.get("fence_reassign_epoch", -2))
                  for v in srv.values()}
        _require(len(re_eps) == 1 and min(re_eps) >= 0,
                 f"{name}: survivors disagree on the takeover boundary: "
                 f"{sorted(re_eps)}")
        boundary = re_eps.pop()
        report["reassign_epoch"] = boundary
        side_path = os.path.join(log_dir, f"node{fenced}.fenced.json")
        _require(os.path.exists(side_path),
                 f"{name}: fenced sidecar missing at {side_path}")
        with open(side_path) as f:
            fside = json.load(f)
        report["fence_reason"] = fside["reason"]
        report["fenced_last_ack"] = fside["last_acked_epoch"]
        _require(fside["map_version"] == 0,
                 f"{name}: the fenced node installed a map of its own "
                 f"(version {fside['map_version']}) — dual-map merge")
        # SINGLE-WRITER-PER-SLOT: the fenced primary's last released
        # ack strictly precedes the survivors' takeover of its slots
        _require(fside["last_acked_epoch"] < boundary,
                 f"{name}: the fenced node acked epoch "
                 f"{fside['last_acked_epoch']} at/after the takeover "
                 f"boundary {boundary} — split-brain ack")
        # and its pipeline could not have logged meaningfully past the
        # boundary (bounded by the in-flight window)
        with open(os.path.join(log_dir, f"node{fenced}.log.bin"),
                  "rb") as f:
            buf = f.read()
        last = max((e for e, _, _ in iter_record_spans(buf)), default=-1)
        window = (cfg.pipeline_groups + 1) * cfg.pipeline_epochs
        _require(last <= boundary + window,
                 f"{name}: the fenced node logged epoch {last}, far "
                 f"past the takeover boundary {boundary}")
        if name == "partition-split":
            _require(fside["reason"] == "minority",
                     f"{name}: expected the minority self-fence, got "
                     f"{fside['reason']!r}")
        else:
            # asym/grayslow: the fenced node could still HEAR — the
            # targeted FENCE_NACK (or the healed-out map) retired it
            _require(sum(v.get("fence_nack_cnt", 0)
                         for v in srv.values()) > 0,
                     f"{name}: no survivor ever sent a FENCE_NACK")
            _require(fside["reason"] in ("fence_nack", "healed_out"),
                     f"{name}: unexpected fence reason "
                     f"{fside['reason']!r}")
    # digest-vs-independent-replay under each survivor's FINAL map
    for s in sorted(srv):
        with open(os.path.join(log_dir, f"node{s}.fencing.json")) as f:
            side = json.load(f)
        node_cfg = cfg.replace(node_id=s, part_cnt=n_srv,
                               fault_partition="",
                               fault_partition_flap_s=0.0,
                               fault_peer_stall="")
        wl = get_workload(node_cfg)
        be = get_backend(node_cfg.cc_alg)
        step = make_dist_step(node_cfg, wl, be)
        db0 = wl.load()
        db0[MEMBER_KEY] = np.asarray(side["owners"], np.int32)
        stats0 = init_device_stats(
            len(getattr(wl, "txn_type_names", ("txn",))))
        db0, _, _, last = replay_into(
            os.path.join(log_dir, f"node{s}.log.bin"), node_cfg, wl,
            step, db0, be.init_state(node_cfg), stats0,
            stop_epoch=side["epochs_run"])
        _require(last == side["epochs_run"] - 1,
                 f"{name}: node {s} log replay ended at {last}, ran "
                 f"{side['epochs_run']} epochs")
        digest = state_digest(db0)
        _require(digest == side["state_digest"],
                 f"{name}: node {s} state diverged from independent "
                 f"replay under its final map ({digest[:16]} != "
                 f"{side['state_digest'][:16]})")
    report["digest_match"] = True


def _check_trace(cfg: Config, srv: list[dict], cls: list[dict],
                 run_id: str, report: dict) -> None:
    """Trace-completeness oracle (the tools/smoke.sh ``trace`` gate):

    * the recorder was LIVE on servers and clients (anti-inert:
      tel_sampled_cnt > 0 in every reporting summary) and never dropped
      an event (the ring auto-flush keeps headroom);
    * every sampled txn that earned a commit verdict has a GAP-FREE
      send <= admit <= batch <= verdict [<= release] <= ack chain —
      zero completeness violations across the crash;
    * at least one chain carries the full quorum hold->release hop
      (the logging path's group-commit gate is visible per txn);
    * the merger renders the run as one flow-linked Chrome trace whose
      arrows actually cross node tracks (client pid != server pid).
    """
    from deneva_tpu.harness import txntrace

    for s in srv + cls:
        _require(s.get("tel_sampled_cnt", 0.0) > 0,
                 "trace-kill: a node's summary shows zero sampled "
                 "events (is telemetry live?)")
        _require(s.get("tel_dropped_cnt", 0.0) == 0,
                 "trace-kill: the recorder dropped events (ring too "
                 "small for the flush cadence)")
    tdir = os.path.join(cfg.log_dir, run_id)
    recs, roles = txntrace.load_dir(tdir)
    _require(len(recs) > 0,
             f"trace-kill: no telemetry records under {tdir}")
    chains = [txntrace.build_chain(ev)
              for ev in txntrace.index_txns(recs).values()]
    committed, full, viol = txntrace.completeness(chains)
    report["trace_txns"] = len(chains)
    report["trace_committed"] = committed
    report["trace_full_chains"] = full
    _require(committed > 0,
             "trace-kill: no sampled txn ever committed in-trace")
    _require(not viol,
             "trace-kill: span-chain gaps/inversions: "
             + "; ".join(viol[:5]))
    _require(full > 0,
             "trace-kill: no chain carries the quorum hold->release "
             "hop (logging is on — held acks must trace)")
    # per-epoch metrics stream: every reporting server wrote lines
    for s in range(cfg.node_cnt):
        mpath = os.path.join(tdir, f"metrics_node{s}.jsonl")
        _require(os.path.exists(mpath) and os.path.getsize(mpath) > 0,
                 f"trace-kill: metrics stream missing/empty at {mpath}")
    trace = txntrace.chrome_trace(recs, roles)
    flows = [e for e in trace["traceEvents"] if e["ph"] in ("s", "f")]
    _require(len(flows) >= 2,
             "trace-kill: flow arrows missing from the Chrome export")
    _require(any(e["pid"] >= cfg.node_cnt for e in flows),
             "trace-kill: flow arrows never touch a client track")
    report["trace_flow_events"] = len(flows)


def _check_monitor(cfg: Config, srv: list[dict], cls: list[dict],
                   run_id: str, report: dict) -> None:
    """Metrics-bus oracle (the tools/smoke.sh ``monitor`` gate):

    * the bus was LIVE everywhere (anti-inert: mb_frames_sent > 0 in
      every reporting summary) and the aggregator actually aggregated
      (the metrics_bus stream holds frames from every server AND the
      client);
    * the STRAGGLER watchdog fired and named EXACTLY the gray-slow node
      — never the killed-and-recovered aggregator or the healthy peer
      (transit-lag skew is the criterion, so a locally-fed aggregator
      and a merely-restarted node stay clean);
    * the aggregator SURVIVED its fault_kill: the recovered incarnation
      appended to the same stream, visible as frames with epochs past
      the recovery resume boundary;
    * per-epoch conflict density rode the frames (the router item's
      input signal exists end to end).
    """
    from deneva_tpu.runtime.metricschema import read_metrics

    for s in srv + cls:
        _require(s.get("mb_frames_sent", 0.0) > 0,
                 "monitor-grayslow: a node's summary shows zero bus "
                 "frames (is the metrics bus live?)")
    stall_node = cfg.fault_peer_stall_spec()[0]
    kill_node, _ = cfg.fault_kill_spec()
    tdir = os.path.join(cfg.log_dir, run_id)
    rows = read_metrics(os.path.join(
        tdir, f"metrics_bus_node{kill_node}.jsonl"))
    _require(len(rows) > 0,
             "monitor-grayslow: the aggregator's bus stream is empty")
    frames = [r for r in rows if "kind" not in r and "commit" in r]
    by_node = {int(r.get("node", -1)) for r in frames}
    report["bus_nodes"] = sorted(by_node)
    _require(set(range(cfg.node_cnt)) <= by_node,
             f"monitor-grayslow: bus stream missing server frames "
             f"(saw nodes {sorted(by_node)})")
    _require(any(n >= cfg.node_cnt for n in by_node),
             "monitor-grayslow: no client frame ever reached the bus")
    # aggregator survival: post-recovery frames past the resume boundary
    resume = report["resume_epoch"]
    post = [r for r in frames
            if r.get("role") == "server" and int(r["epoch"]) >= resume]
    report["bus_frames"] = len(frames)
    report["bus_post_recovery"] = len(post)
    _require(len(post) > 0,
             f"monitor-grayslow: no frame past the resume boundary "
             f"{resume} — the recovered aggregator never resumed the "
             "stream")
    # straggler watchdog: fired, and ONLY on the stalled node
    watches = [r for r in rows if r.get("kind") == "straggler"]
    subjects = {int(w.get("subject", -1)) for w in watches}
    report["straggler_subjects"] = sorted(subjects)
    _require(len(watches) > 0,
             "monitor-grayslow: the gray-slow node was never flagged "
             "(is the straggler watchdog live?)")
    _require(subjects == {stall_node},
             f"monitor-grayslow: straggler watchdog named "
             f"{sorted(subjects)}, expected exactly node {stall_node}")
    # the contention signal rode the frames end to end
    dens = [r for r in frames if r.get("density")]
    report["bus_density_frames"] = len(dens)
    _require(len(dens) > 0,
             "monitor-grayslow: no frame carried a conflict-density "
             "vector (the router item's input signal is missing)")


def _check_audit(name: str, cfg: Config, out: dict, run_id: str,
                 report: dict) -> None:
    """Serializability-certificate oracle (the tools/smoke.sh ``audit``
    gate, and a STANDING oracle on every kill/partition/repair/geo
    scenario that arms ``audit=true``):

    * the instrument was LIVE: > 0 epochs audited across the surviving
      servers' sidecars, and the export never overflowed its edge cap
      (an incomplete certificate proves nothing);
    * ZERO cross-node observation divergence (merged-mode servers must
      derive identical edge lists and version-stamp digests — the
      split-brain cross-check);
    * without ``audit_mutate``: the cluster-wide Direct Serialization
      Graph is CYCLE-FREE — the run is certified serializable;
    * with ``audit_mutate``: the certifier must REJECT the run with a
      concrete cycle witness naming an epoch INSIDE the mutated window,
      carrying txn tags + owning nodes, classified as an rw anomaly
      (G-single/G2-item — the dropped read check admits exactly
      anti-dependency cycles).

    Only nodes that finished as live servers join the certificate: a
    fenced/killed-in-place node's trailing observations describe
    epochs the survivors re-decided after reassignment (its acks were
    already frozen by the lease), so they are not part of the
    authoritative history."""
    from deneva_tpu.harness import auditgraph

    tdir = os.path.join(cfg.log_dir, run_id)
    live = [s for s in range(cfg.node_cnt) if out[s][0] == "server"]
    cert = auditgraph.certify(tdir, nodes=live)
    report["audit_epochs"] = cert["epochs"]
    report["audit_edges"] = cert["edges_deduped"]
    report["audit_ok"] = cert["ok"]
    _require(cert["epochs"] > 0,
             f"{name}: no epoch was ever audited (is the audit plane "
             "live?)")
    _require(cert["complete"],
             f"{name}: {cert['dropped_epochs']} epoch(s) overflowed "
             "audit_edges_max — the certificate is incomplete")
    _require(not cert["divergences"],
             f"{name}: cross-node audit observations diverged "
             f"(split-brain signature): {cert['divergences'][:3]}")
    spec = cfg.audit_mutate_spec()
    if spec is None:
        _require(cert["ok"],
                 f"{name}: serializability certificate REJECTED:\n"
                 + auditgraph.render(cert))
        return
    # anti-inert inversion: the seeded mutation MUST be caught, and
    # the witness must localize it to the mutated window
    _, start, count = spec
    _require(not cert["ok"],
             f"{name}: mutated epochs [{start}, {start + count}) ran "
             "but the certifier found no cycle — certifier inert or "
             "mutation dead")
    eps = sorted({w["epoch"] for w in cert["cycles"]})
    report["audit_witness_epochs"] = eps
    _require(all(start <= e < start + count for e in eps),
             f"{name}: witness epochs {eps} fall outside the mutated "
             f"window [{start}, {start + count})")
    w = cert["cycles"][0]
    report["audit_anomaly"] = w["anomaly"]
    _require(w["anomaly"] in ("G-single", "G2-item"),
             f"{name}: expected an rw-anomaly class from the dropped "
             f"read check, got {w['anomaly']}")
    _require(all(t["tag"] is not None and t["node"] is not None
                 for t in w["txns"]),
             f"{name}: witness txns missing tag/owner joins: "
             f"{w['txns']}")


def _check_ctrl(name: str, cfg: Config, out: dict, run_id: str,
                report: dict) -> None:
    """Control-plane oracle (the tools/smoke.sh ``ctrl`` gate):

    * the controller was LIVE: > 0 recorded decisions on every
      surviving server's ``ctrl_node*.log`` sidecar, with armed rows
      (anti-inert — a scenario that passes with the plane idle proves
      nothing);
    * the fail-safe governor TRIPPED on the signal stall (node 0's
      kill/replay freezes merged group progress past ``ctrl_stale_s``,
      so the survivor's next boundary tick reads stale) and RE-ENGAGED:
      an armed row follows a static row in the same node's stream;
    * decision determinism: every incarnation's decision stream replays
      BIT-FOR-BIT from its own recorded signals (`replay_decisions`
      over the parse_ctrl rows — a killed node's recovered process
      starts a fresh controller, so its stream splits at seq=1 exactly
      like the command log's resume boundary).
    """
    from deneva_tpu.harness.parse import parse_ctrl
    from deneva_tpu.runtime.controller import replay_decisions

    tdir = os.path.join(cfg.log_dir, run_id)
    live = [s for s in range(cfg.node_cnt) if out[s][0] == "server"]
    armed = 0
    trips = 0
    reengaged = False
    decisions = []
    for s in live:
        path = os.path.join(tdir, f"ctrl_node{s}.log")
        _require(os.path.exists(path),
                 f"{name}: ctrl decision sidecar missing at {path}")
        with open(path) as f:
            rows = parse_ctrl(f)
        _require(len(rows) > 0,
                 f"{name}: node {s} never recorded a decision (is the "
                 "controller live?)")
        decisions.append(len(rows))
        node_cfg = cfg.replace(node_id=s, part_cnt=cfg.node_cnt)
        # split at seq resets: each process incarnation runs its own
        # fresh deterministic controller over its own signal stream
        segs: list[list[dict]] = []
        for r in rows:
            if int(r.get("seq", 0)) == 1 or not segs:
                segs.append([])
            segs[-1].append(r)
        for seg in segs:
            bad = replay_decisions(node_cfg, seg)
            _require(not bad,
                     f"{name}: node {s} decision stream is not "
                     f"replay-reproducible: " + "; ".join(bad[:5]))
        armed += sum(1 for r in rows if r.get("gov") == "armed")
        trips = max(trips, max(int(r.get("trips", 0)) for r in rows))
        seen_static = False
        for r in rows:
            if r.get("gov") == "static":
                seen_static = True
            elif seen_static and r.get("gov") == "armed":
                reengaged = True
    report["ctrl_decisions"] = decisions
    report["ctrl_armed_rows"] = armed
    report["ctrl_trips"] = trips
    report["ctrl_reengaged"] = reengaged
    _require(armed > 0,
             f"{name}: no armed decision was ever recorded — the "
             "adaptive plane never engaged")
    _require(trips > 0,
             f"{name}: the governor never tripped to static — the "
             "signal-loss fallback is unproven (did the stall clear "
             "ctrl_stale_s?)")
    _require(reengaged,
             f"{name}: the governor never re-engaged after its trip "
             "(heal streak never cleared inside the window)")


def _check_recovery(cfg: Config, out: dict, run_id: str,
                    report: dict) -> None:
    """Safety of the failover path: the killed server recovered by log
    replay (bit-for-bit vs an independent replay of the same prefix),
    its log is epoch-contiguous across the crash, and each replica log
    is a byte prefix of its primary's."""
    from deneva_tpu.runtime.logger import (
        iter_record_spans, replay_into, state_digest)

    kill_node, _ = cfg.fault_kill_spec()
    log_dir = os.path.join(cfg.log_dir, run_id)
    killed = parse_summary(out[kill_node][1])
    _require(killed.get("recovered", 0.0) == 1.0,
             "kill-one-server: the killed node's summary did not come "
             "from a recovered process")
    side_path = os.path.join(log_dir, f"node{kill_node}.recovery.json")
    _require(os.path.exists(side_path),
             "kill-one-server: recovery sidecar missing")
    with open(side_path) as f:
        side = json.load(f)
    report["resume_epoch"] = side["resume_epoch"]
    # independent replay of the SAME log prefix must reproduce the
    # recovered node's state digest bit for bit
    node_cfg = cfg.replace(node_id=kill_node, part_cnt=cfg.node_cnt,
                           recover=False, fault_kill="")
    from deneva_tpu.cc import get_backend
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.workloads import get_workload
    wl = get_workload(node_cfg)
    be = get_backend(node_cfg.cc_alg)
    step = make_dist_step(node_cfg, wl, be)
    stats0 = init_device_stats(
        len(getattr(wl, "txn_type_names", ("txn",))))
    log_path = os.path.join(log_dir, f"node{kill_node}.log.bin")
    db, _, _, last = replay_into(
        log_path, node_cfg, wl, step, wl.load(), be.init_state(node_cfg),
        stats0, stop_epoch=side["resume_epoch"])
    _require(last == side["resume_epoch"] - 1,
             f"kill-one-server: log prefix ends at {last}, expected "
             f"{side['resume_epoch'] - 1}")
    digest = state_digest(db)
    report["digest_match"] = digest == side["state_digest"]
    _require(report["digest_match"],
             "kill-one-server: replayed state diverged from the "
             f"recovered node's ({digest[:16]} != "
             f"{side['state_digest'][:16]})")
    # log epoch contiguity across the crash (truncate-then-append must
    # leave no gap and no duplicate)
    for s in range(cfg.node_cnt):
        with open(os.path.join(log_dir, f"node{s}.log.bin"), "rb") as f:
            buf = f.read()
        epochs = [e for e, _, _ in iter_record_spans(buf)]
        _require(epochs == list(range(len(epochs))),
                 f"kill-one-server: node {s} log epochs not contiguous "
                 f"(len={len(epochs)}, tail={epochs[-5:]})")
    # replica logs: byte prefix of the primary's (group commit +
    # rejoin-resync keep them aligned modulo trailing in-flight records)
    n_front = cfg.node_cnt + cfg.client_node_cnt
    for s in range(cfg.node_cnt):
        for k in range(cfg.replica_cnt):
            rid = n_front + s + k * cfg.node_cnt
            with open(os.path.join(log_dir, f"node{s}.log.bin"),
                      "rb") as f:
                p = f.read()
            with open(os.path.join(log_dir, f"replica{rid}.log.bin"),
                      "rb") as f:
                r = f.read()
            _require(len(p) > 0, f"kill-one-server: node {s} log empty")
            _require(p.startswith(r) or r.startswith(p),
                     f"kill-one-server: replica {rid} log diverged from "
                     f"primary {s} (not a byte prefix)")
    report["replica_prefix_ok"] = True


def main(argv: list[str]) -> int:
    quick = "--quick" in argv
    names = [a for a in argv if not a.startswith("--")]
    if not names or names == ["all"]:
        names = list(SCENARIOS)
    names = [x for n in names
             for x in (ELASTIC_SCENARIOS if n == "elastic"
                       else GEO_SCENARIOS if n == "geo"
                       else OVERLOAD_SCENARIOS if n == "overload"
                       else PARTITION_SCENARIOS if n == "partition"
                       else AUDIT_SCENARIOS if n == "audit"
                       else CTRL_SCENARIOS if n == "ctrl"
                       else (n,))]
    rc = 0
    for name in names:
        try:
            run_scenario(name, quick=quick)
        except ChaosViolation as e:
            print(f"[chaos] {name}: VIOLATION: {e}", flush=True)
            rc = 1
        except Exception as e:  # noqa: BLE001 — harness-level failure
            print(f"[chaos] {name}: ERROR: {e!r}", flush=True)
            rc = 2
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
