"""Runtime configuration system.

The reference spreads configuration over three tiers: compile-time
``#define``s in ``config.h`` (CC_ALG, WORKLOAD, MODE, every protocol
constant), a hand-rolled CLI parser for a runtime subset
(``system/parser.cpp:77``), and an experiment layer that rewrites
``config.h`` and recompiles per data point (``scripts/run_experiments.py:83-96``).

Here everything is a runtime field on one frozen dataclass.  Algorithm
selection is runtime dispatch behind the `deneva_tpu.cc` interface — the
``#if CC_ALG`` forest in the reference's ``storage/row.cpp:197-310`` is the
thing this design explicitly does not reproduce.  JAX re-jits per config
anyway (config fields are Python-level constants under trace), so we lose
nothing to the reference's recompile-per-config scheme.

Field names keep the reference's vocabulary (``g_node_cnt``,
``g_inflight_max``, ``zipf_theta`` … see ``system/global.h:130-234``) minus
the ``g_`` prefix so experiment configs read the same as the paper's.
"""

from __future__ import annotations

import dataclasses
import enum
from dataclasses import dataclass
from typing import Any


class CCAlg(str, enum.Enum):
    """Concurrency-control algorithm (reference `config.h:101` + README:24-35).

    All are implemented as batched epoch-validation backends; see
    `deneva_tpu.cc` for per-algorithm semantics.
    """

    NO_WAIT = "NO_WAIT"        # 2PL, abort on conflict
    WAIT_DIE = "WAIT_DIE"      # 2PL, older waits / younger dies
    TIMESTAMP = "TIMESTAMP"    # basic T/O
    MVCC = "MVCC"              # multi-version T/O
    OCC = "OCC"                # Kung-Robinson backward validation
    MAAT = "MAAT"              # dynamic timestamp ranges
    CALVIN = "CALVIN"          # deterministic (sequencer + ordered locks)
    TPU_BATCH = "TPU_BATCH"    # headline backend: MXU conflict matrix + greedy serialization
    DGCC = "DGCC"              # dependency-graph wavefront (exact-key lane graph -> chained waves)
    NOCC = "NOCC"              # oracle mode: no concurrency control (reference MODE=NOCC_MODE)


class WorkloadKind(str, enum.Enum):
    """Benchmark selection (reference `config.h` WORKLOAD)."""

    YCSB = "YCSB"
    TPCC = "TPCC"
    PPS = "PPS"
    TEST = "TEST"


class Mode(str, enum.Enum):
    """Degraded oracle modes used as layer-isolation tests (reference
    `config.h:276-281`, SURVEY §4.2)."""

    NORMAL = "NORMAL"
    SIMPLE = "SIMPLE"      # ack immediately, no execution (client+transport only)
    NOCC = "NOCC"          # execute without CC
    QRY_ONLY = "QRY_ONLY"  # execute queries but skip commit protocol


@dataclass(frozen=True)
class Config:
    """One flat, frozen config record.

    Defaults follow the reference's defaults (`config.h`, with the paper's
    experiment defaults from `scripts/experiments.py:346-420`) except where
    a TPU-shaped knob replaces a CPU-shaped one (noted inline).
    """

    # ---- topology (reference config.h:16-23) ----
    node_id: int = 0
    node_cnt: int = 1              # server nodes
    client_node_cnt: int = 1
    part_cnt: int = 1              # keyspace partitions (== node_cnt in reference)
    core_cnt: int = 8
    rem_thread_cnt: int = 1        # native receiver IO threads (reference
    #                                REM_THREAD_CNT): peers shard src % n
    send_thread_cnt: int = 1       # native sender IO threads (reference
    #                                SEND_THREAD_CNT): dests shard dest % n
    #                                (per-dest FIFO preserved)
    client_thread_cnt: int = 4

    # ---- replication (reference config.h:24-27) ----
    replica_cnt: int = 0
    repl_type: str = "AP"          # active-passive

    # ---- multi-chip (single process, jax.sharding.Mesh) ----
    device_parts: int = 1          # keyspace partitions ACROSS CHIPS: tables
    #                                shard owner-major over the mesh and the
    #                                forwarding executor runs partition-
    #                                parallel under shard_map (parallel/)

    # ---- workload ----
    workload: WorkloadKind = WorkloadKind.YCSB
    cc_alg: CCAlg = CCAlg.TPU_BATCH
    mode: Mode = Mode.NORMAL
    isolation_level: str = "SERIALIZABLE"  # SERIALIZABLE | READ_COMMITTED | READ_UNCOMMITTED | NOLOCK

    # ---- YCSB (reference config.h:150-176) ----
    synth_table_size: int = 2097152 * 8   # 16M rows/node, paper default
    req_per_query: int = 10
    zipf_theta: float = 0.6
    read_perc: float = 0.5
    write_perc: float = 0.5        # per-tuple write prob (TUP_WRITE_PERC)
    txn_write_perc: float = 1.0    # P(txn may write at all); with prob 1-p the
    #                                whole txn is read-only (TXN_WRITE_PERC,
    #                                ycsb_query.cpp:313,331: r_twr drawn once per txn)
    skew_method: str = "ZIPF"      # ZIPF | HOT (config.h:162-167)
    data_perc: int = 100           # HOT: hot-set size in KEYS (g_data_perc is cast
    #                                to an absolute key count, ycsb_query.cpp:218)
    access_perc: float = 0.03      # HOT: fraction of accesses hitting the hot set
    key_order: bool = False        # sort request keys ascending (KEY_ORDER config.h:106)
    tup_size: int = 100            # bytes per field payload (SIM_FULL_ROW analogue)
    field_per_tuple: int = 10
    sim_full_row: bool = False     # SIM_FULL_ROW (storage/row.cpp:30): tables
    #                                materialize real payload bytes
    #                                (uint8[tup_size] per field); reads
    #                                checksum real bytes, writes store real
    #                                bytes.  Off = fingerprint mode (the
    #                                reference's SIM_FULL_ROW=false default).
    first_part_local: bool = True
    part_per_txn: int = 2
    mpr: float = 0.01              # multi-partition txn rate
    strict_ppt: bool = False
    ycsb_abort_mode: bool = False  # sentinel forced-abort consistency check (config.h:103)

    # ---- TPCC (reference config.h:178-209) ----
    num_wh: int = 4
    perc_payment: float = 0.5
    wh_update: bool = True
    mpr_neworder: float = 0.01     # remote-warehouse item probability
    tpcc_full_schema: bool = False
    cust_per_dist: int = 3000      # CUST_PER_DIST_NORM (config.h:188)
    tpcc_by_last_index: bool = True  # resolve payment-by-lastname through
    #                                  the CUSTOMER_LAST nonunique index
    #                                  (hash probe + postings walk, like
    #                                  index_hash.cpp:68-100); False =
    #                                  closed-form arithmetic bypass
    max_items: int = 100000        # MAX_ITEMS_NORM (config.h:187)
    max_items_per_txn: int = 15    # MAX_ITEMS_PER_TXN (config.h:189)
    insert_table_cap: int = 1 << 17  # ring capacity of HISTORY/ORDER/... tables
    #                                  (ORDER-LINE gets cap*max_items_per_txn)

    # ---- PPS (reference config.h:226-242) ----
    pps_table_size: int = 100000
    pps_parts_cnt: int = 10000       # MAX_PPS_PART_KEY
    pps_products_cnt: int = 1000     # MAX_PPS_PRODUCT_KEY
    pps_suppliers_cnt: int = 1000    # MAX_PPS_SUPPLIER_KEY
    pps_parts_per: int = 10          # MAX_PPS_PART_PER_PRODUCT
    perc_getparts: float = 0.0
    perc_getproducts: float = 0.0
    perc_getsuppliers: float = 0.0
    perc_getpartbyproduct: float = 0.34
    perc_getpartbysupplier: float = 0.0
    perc_orderproduct: float = 0.33
    perc_updateproductpart: float = 0.33
    perc_updatepart: float = 0.0

    # ---- txn / client driving (reference config.h:21-22, 84-90) ----
    max_txn_in_flight: int = 10000
    load_rate: int = 0             # 0 = LOAD_MAX (saturate), else fixed txn/s
    client_batch_size: int = 1024  # txns per CL_QRY_BATCH message: the
    #                                Python client's per-message overhead
    #                                (~3 ms: tag ring + codec + send) is
    #                                the cluster-mode supply ceiling, so
    #                                it must amortize over large batches
    #                                (reference clients batch too,
    #                                message.h:243-340)
    abort_penalty_us: float = 25.0      # base restart backoff (config.h:113)
    abort_penalty_max_us: float = 5000.0
    backoff: bool = True

    # ---- simulation lifecycle (reference config.h:346-350) ----
    warmup_secs: float = 2.0       # reference: 60s; scaled for CI-speed runs
    done_secs: float = 5.0         # measured window; reference: 60s
    prog_timer_secs: float = 10.0
    chunk_target_secs: float = 1.0  # driver aims each device scan at this
    #                                 much work: the per-chunk pacing fetch
    #                                 amortizes over it, while one bounded
    #                                 device call keeps the wall-clock
    #                                 windows and [prog] ticks responsive
    #                                 (keep <= ~3)

    # ---- logging (reference config.h:145-149) ----
    logging: bool = False
    log_buf_timeout_us: float = 10.0
    log_dir: str = "/tmp/deneva_logs"

    # ---- epoch engine (TPU-shaped; replaces thread/latch knobs) ----
    epoch_batch: int = 2048        # txns validated per epoch (Calvin SEQ_BATCH analogue)
    conflict_buckets: int = 8192   # hashed key-bucket width of incidence matrices
    conflict_exact: bool = True    # dual-hash AND to squeeze out false conflicts
    watermark_buckets: int = 1 << 20  # hashed width of the T/O family's
    #                                   cross-epoch rts/wts tables.  These
    #                                   are O(K) memory (not O(B*K) like
    #                                   incidence matrices), so they can be
    #                                   wide enough that false bucket
    #                                   sharing stops inflating abort
    #                                   rates (the reference tracks
    #                                   per-ROW ts state; 1M buckets at
    #                                   4 B each is 4 MB)
    max_accesses: int = 16         # padded RW-set width per txn (covers req_per_query)
    defer_rounds_max: int = 8      # WAIT_DIE-style defer budget before forced abort
    sweep_rounds: int = 24         # serialization-sweep fixpoint iterations (chain depth cap)
    maat_peel_rounds: int = 16     # MAAT cycle-peel iterations per epoch (leftovers defer)
    mc_plan_capacity: float = 2.0  # sharded multi-chip plan: per-chip buffer
    #                                = factor * N/D lanes (0 = replicate
    #                                  the full plan per chip, round-3 mode)
    tpcc_order_index: bool = False  # maintain the dynamic ordered ORDER
    #                                 index (index_btree insert analogue;
    #                                 one merge sort per epoch)
    exec_subrounds: int = 4        # chained-execution levels per epoch (CALVIN/TPU_BATCH)
    dgcc_levels: int = 32          # DGCC wave budget: level-relaxation
    #                                round cap AND max wavefront depth per
    #                                epoch (cc/dgcc.py).  Deeper dependency
    #                                closures DEFER to the next epoch's
    #                                retry queue (repair's cyclic-fallback
    #                                analogue) — never abort.  Far above
    #                                exec_subrounds because DGCC's exact-
    #                                key lane graph has no hashed-bucket
    #                                false conflicts inflating chain depth.
    mvcc_his_len: int = 4          # in-state version history depth (HIS_RECYCLE_LEN analogue)
    escrow_order_free: bool = True  # honor workload order_free (escrow/
    #                                 commutative) declarations in the
    #                                 backends' conflict graphs; False =
    #                                 ablation: every backend sees the
    #                                 full RW-sets (separates the
    #                                 algorithm win from the annotation win
    #                                 in TPC-C/PPS numbers)
    escrow_sweep: bool = True      # extend the escrow exemption to the six
    #                                SWEEP backends (NO_WAIT/WAIT_DIE/OCC/
    #                                TIMESTAMP/MVCC/MAAT): conflict edges
    #                                come from the ordered incidence views
    #                                (escrow add-add pairs carry no edge;
    #                                accumulator READS still order against
    #                                every add) and the T/O watermarks
    #                                apply the escrow check/record rules
    #                                (cc/timestamp.py).  False = the
    #                                reference-faithful baseline: row-level
    #                                conflicts, ~1 hot-row winner per epoch
    #                                (the TPC-C 4-warehouse Payment floor).
    #                                Chained backends ignore this flag
    #                                (their exemption is escrow_order_free
    #                                alone, as before).
    repair: bool = False           # transaction repair (engine/repair.py):
    #                                salvage sweep-backend ABORTS by
    #                                re-executing only the invalidated
    #                                slice as chained sub-rounds within
    #                                the SAME epoch — losers whose
    #                                re-validation passes against the
    #                                post-winner state commit instead of
    #                                re-entering the retry queue (PAPERS:
    #                                *Transaction Repair: Full
    #                                Serializability Without Locks*;
    #                                DGCC's dependency-graph batching).
    #                                Default off: losers take the retry
    #                                queue exactly as before — every
    #                                code path, log byte and verdict
    #                                plane is bit-identical to pre-repair.
    repair_rounds: int = 2         # repair sub-rounds per epoch before
    #                                leftovers (cyclic re-invalidation:
    #                                each pass's winners re-invalidate
    #                                the rest) fall back to the retry
    #                                queue; 0 = arm the machinery but
    #                                salvage nothing (ablation floor)
    seq_batch_timer_us: float = 5000.0  # Calvin epoch cadence (config.h:348)

    # ---- device mesh ----
    mesh_shape: tuple = ()         # () = single device; e.g. (8,) shards keyspace
    mesh_axis: str = "key"

    # ---- storage ----
    index_struct: str = "IDX_HASH"  # IDX_HASH | IDX_BTREE (global.h:320-324)
    bucket_cnt_per_slot: float = 2.0  # hash index load factor headroom

    # ---- transport (reference config.h:94, 334-335) ----
    tport_type: str = "ipc"        # ipc | tcp
    tport_port: int = 17000
    msg_size_max: int = 4096
    msg_time_limit_us: float = 0.0
    net_delay_us: float = 0.0      # NETWORK_DELAY_TEST (msg_queue.cpp:104-125)

    # ---- deployment (harness): in-process engine vs multi-process cluster
    deploy: str = "inproc"         # inproc | cluster
    pipeline_epochs: int = 8       # cluster merged mode: epochs fused into ONE
    #                                device dispatch (lax.scan group).  The
    #                                host<->device round trips (merged-batch
    #                                feed up, commit masks down) amortize over
    #                                the whole group instead of being paid per
    #                                epoch (fewer host<->device transfers per
    #                                committed txn).  1 = the round-1
    #                                synchronous loop.
    pipeline_groups: int = 2       # cluster merged mode: dispatch groups kept
    #                                in flight before blocking on the oldest
    #                                group's commit masks (double buffering:
    #                                epoch e+1's admission/exchange/codec work
    #                                overlaps epoch e's device step — the
    #                                reference's sequencer-vs-worker thread
    #                                decoupling, system/calvin_thread.cpp:102).
    #                                1 = retire synchronously.
    host_overlap: str = "auto"     # which thread runs the served loop's
    #                                pure host bodies (the loop, its feed
    #                                buffers and its bytes are the same
    #                                either way — tested bit-identical).
    #                                "on": one ordered wire worker carries
    #                                each epoch's blob broadcast and the
    #                                group's log records + replica LOG_MSG
    #                                sends (per-link FIFO preserved — one
    #                                worker, program order), and a retire
    #                                worker turns each group's verdict
    #                                planes into ack payloads (d2h wait +
    #                                unpackbits + CL_RSP splits) so
    #                                retirement K groups later finds them
    #                                ready.  "off": the dispatch thread
    #                                calls the same bodies inline at the
    #                                same loop positions.  "auto"
    #                                (default) = on unless this box's
    #                                process count (servers + clients +
    #                                replicas, the single-box launcher
    #                                rig) oversubscribes its cores by
    #                                more than one: worker threads can
    #                                only overlap DEVICE time if a spare
    #                                cycle exists — measured on the
    #                                2-core box, on wins at N<=2 procs+1
    #                                and loses 29% at 5 procs (BASELINE
    #                                round-7).  Multi-host fleets set
    #                                on/off explicitly.  Vote mode runs
    #                                inline whatever this says (its epoch
    #                                is a synchronous host round trip).
    dist_protocol: str = "auto"    # cluster coordination for non-deterministic
    #                                backends (reference 2PC,
    #                                system/txn.cpp:498-606):
    #                                auto   — deterministic backends use the
    #                                         merged-batch sequencer exchange;
    #                                         lock/ts/occ backends use VOTE
    #                                vote   — batched 2PC: each server
    #                                         validates its partition's
    #                                         accesses locally and the epoch
    #                                         vote exchange is the prepare
    #                                         round (commit = every owner
    #                                         voted yes)
    #                                merged — every server validates the full
    #                                         merged batch with global state
    #                                         (round-1 behavior)

    # ---- fault injection + failover (chaos harness; no reference
    # analogue — SURVEY §5.3: a dead peer hangs the reference forever).
    # All defaults OFF: with every knob at its default the runtime takes
    # exactly the pre-chaos code paths. ----
    fault_drop_prob: float = 0.0   # P(drop) per fault-eligible message
    #                                (client<->server open-loop traffic;
    #                                 see native.FAULT_RTYPE_MASK)
    fault_dup_prob: float = 0.0    # P(duplicate) per eligible message
    fault_delay_jitter_us: float = 0.0  # uniform [0, jitter) extra delay
    fault_kill: str = ""           # "node:epoch" — server `node` calls
    #                                _exit at the first group boundary
    #                                >= `epoch` (crash, no teardown);
    #                                requires logging (recovery replays).
    #                                Killing node 0 (the coordinator) is
    #                                best-effort: peers echo the
    #                                measure/stop epochs on REJOIN, but
    #                                a restart racing the warmup edge
    #                                can still re-announce a later
    #                                window — prefer killing node >= 1
    fault_seed: int = 0            # fault-stream seed; mixed with the
    #                                node id so each node draws its own
    #                                deterministic splitmix64 stream
    fault_resend_us: float = 250_000.0  # client resend timeout for
    #                                unacked batches (fault mode only)
    fault_recovery_timeout_s: float = 120.0  # how long peers wait for a
    #                                dead server to rejoin before raising
    #                                (fault mode only; otherwise the
    #                                pre-chaos dead-peer raise fires)
    recover: bool = False          # start this server in recovery mode:
    #                                replay the command log, rejoin the
    #                                mesh at the next group boundary
    failover_timeout_s: float = 60.0  # the failover wall family: the
    #                                REJOIN replica-handshake wait, the
    #                                MIGRATE_ROWS donor-stream wait and
    #                                the reassignment-replay flush wait
    #                                all read this single knob (they were
    #                                hidden 30/60 s constants — the PR 4
    #                                clamped-window lesson: hidden walls
    #                                flake slow CI boxes; raise it there)
    fault_partition: str = ""      # network partition injection (native
    #                                dt_set_partition blackholes):
    #                                comma-separated "A-B:START"
    #                                (bidirectional) or "A>B:START"
    #                                (one-way: A's frames to B are
    #                                dropped) entries; A/B are SERVER
    #                                ids, START is seconds after the run
    #                                barrier.  Each endpoint applies its
    #                                own TX-side drops at its group
    #                                boundaries, so the first silenced
    #                                epoch is identical on every
    #                                receiver.  "" = off.
    fault_partition_flap_s: float = 0.0  # flapping link: every armed
    #                                partition toggles on/off with this
    #                                period from its START (on for
    #                                flap_s, off for flap_s, ...).  0 =
    #                                partitions are permanent.
    fault_peer_stall: str = ""     # gray-slow peer (native
    #                                dt_set_peer_stall_us): "NODE:MS:
    #                                START_S" — server NODE delays ALL
    #                                its outbound frames by MS
    #                                milliseconds from START_S seconds
    #                                after the barrier.  Models a
    #                                stalled-but-alive process: sockets
    #                                never close, peer_alive stays true,
    #                                only the suspicion score sees it.

    # ---- elastic membership (slot-map routing + live rebalance;
    # runtime/membership.py).  All defaults OFF: with elastic=False every
    # path takes the static modulo-striping code exactly. ----
    elastic: bool = False          # slot-map ownership: S hash slots ->
    #                                owner node replace implicit
    #                                key % node_cnt everywhere.  The boot
    #                                map degenerates to EXACT modulo
    #                                striping (S is rounded to a multiple
    #                                of the boot active count), so with no
    #                                rebalance triggered all routing,
    #                                logs, replica streams and acks are
    #                                bit-identical to elastic=False.
    #                                Tables hold the FULL keyspace
    #                                (ownership is the mask, local slot ==
    #                                key) so acquired slots always have a
    #                                resident row to install into.
    elastic_slots: int = 256       # base slot count S (rounded up to a
    #                                multiple of node_cnt-elastic_spare_cnt)
    elastic_spare_cnt: int = 0     # trailing servers that boot slotless
    #                                (warm spares for mid-run scale-out);
    #                                they join the epoch exchange with
    #                                empty contributions until a grow
    #                                rebalance moves slots onto them
    elastic_plan: str = ""         # controller-driven rebalance:
    #                                "grow:NODE:EPOCH" | "drain:NODE:EPOCH"
    #                                — server 0 announces MIGRATE_BEGIN at
    #                                the first group boundary >= EPOCH,
    #                                cutover lands 3 groups later (same
    #                                margin discipline as the measurement
    #                                window announcement)

    # ---- geo-replication tier (region-aware slot map, quorum group-
    # commit, follower snapshot reads; runtime/replication.py).  All
    # defaults OFF: with geo=False every path takes the pre-geo code
    # exactly (same wire bytes, logs, replica stream, acks). ----
    geo: bool = False              # arm the geo tier.  Requires elastic
    #                                (full-residency tables are what let a
    #                                follower materialize every row from
    #                                the merged log stream) + logging +
    #                                replica_cnt >= 1.  Replicas become
    #                                FOLLOWERS: they replay the merged
    #                                command stream group-by-group and
    #                                serve REGION_READ snapshot reads at
    #                                the last applied group boundary; the
    #                                primary's group commit gates on a
    #                                QUORUM of LOG_ACKs instead of all
    #                                replicas.  In geo mode fault_kill
    #                                "n:e" means REGION LOSS: server n
    #                                dies at epoch e AND every replica
    #                                homed in n's region dies at its own
    #                                first record >= e.
    geo_region_cnt: int = 1        # regions; servers map block-wise
    #                                (s * R // node_cnt), clients likewise,
    #                                and replica k of primary p lands in
    #                                region (region(p) + 1 + k) % R — a
    #                                primary's replicas always live in
    #                                OTHER regions, so region loss never
    #                                takes a primary and all its replicas
    #                                together (runtime/replication.py
    #                                region_of).
    geo_quorum: int = 0            # replica acks a group boundary needs
    #                                before its CL_RSPs release.  0 = all
    #                                replica_cnt (the pre-geo gate); q <
    #                                replica_cnt tolerates slow/dead
    #                                replicas at the cost of a thinner
    #                                durability margin.
    geo_wan_us: str = ""           # WAN latency profile: "0-1:20000"
    #                                (symmetric) and/or "0>1:5000"
    #                                (directed) comma-separated region-
    #                                pair one-way delays in us, applied
    #                                per-link via dt_set_peer_delay_us at
    #                                node start.
    geo_read_perc: float = 0.0     # target fraction of client traffic
    #                                issued as follower snapshot reads
    #                                (REGION_READ to the nearest live
    #                                follower); 0 disables the read path.

    # ---- partition & gray-failure tolerance (heartbeat failure
    # detector, fenced slot ownership, quorum reassignment;
    # runtime/faildet.py).  All defaults OFF: with fencing=False no
    # heartbeat is ever sent, no frame grows a fence header, and every
    # log byte / replica stream / digest / wire byte is bit-identical
    # to the pre-fencing runtime. ----
    fencing: bool = False          # arm the membership fencing layer:
    #                                HEARTBEAT frames feed a phi-accrual
    #                                per-peer suspicion score (gray
    #                                failures that never close a socket);
    #                                EPOCH_BLOB/LOG_MSG carry the
    #                                sender's map_version and receivers
    #                                reject stale incarnations with
    #                                FENCE_NACK; a fenced-out primary
    #                                self-halts with exit 18 instead of
    #                                serving split-brain writes; dead-
    #                                peer reassignment only fires on the
    #                                majority side of the live set
    #                                (minority partitions self-fence,
    #                                ties resolve to the side holding
    #                                the lowest id); and CL_RSPs gate on
    #                                a majority having CONFIRMED receipt
    #                                of the acked epoch's blob (the
    #                                epoch-boundary ack lease that makes
    #                                a partitioned primary's acks
    #                                causally impossible, not just
    #                                unlikely).  Requires elastic +
    #                                logging (reassignment rebuilds rows
    #                                by log replay).
    fencing_phi: float = 8.0       # phi-accrual suspicion threshold: a
    #                                peer is SUSPECTED once
    #                                phi = log10(e) * elapsed/mean_gap
    #                                crosses this (8.0 at the 100 ms
    #                                heartbeat cadence ~= 1.8 s silent)
    fencing_heartbeat_ms: float = 100.0  # standalone HEARTBEAT cadence
    #                                per live peer link (any received
    #                                frame also counts as a heartbeat —
    #                                the epoch exchange piggybacks)
    fencing_suspect_s: float = 2.0  # wall-clock silence floor a
    #                                suspicion must ALSO clear before it
    #                                may drive reassignment / self-
    #                                fencing — hysteresis so a flapping
    #                                link heals instead of fencing

    # ---- overload robustness tier (open-loop load generation +
    # per-tenant admission control + SLO backpressure; runtime/loadgen.py
    # and runtime/admission.py).  All defaults OFF: with every knob at
    # its default the client drives the pre-overload closed loop and the
    # server admits unconditionally — bit-identical wire bytes. ----
    arrival_process: str = ""      # open-loop arrival process replacing
    #                                closed-loop driving: "" (off) |
    #                                "poisson" (steady seeded Poisson) |
    #                                "diurnal" (sinusoid-modulated rate) |
    #                                "bursty" (on/off duty cycle) |
    #                                "flash" (rate step x factor during a
    #                                window — the flash-crowd scenario).
    #                                The client sends whenever its seeded
    #                                cumulative-arrival target runs ahead
    #                                of sent_total, independent of
    #                                responses (open loop) — backlog, not
    #                                acks, drives the send schedule.
    arrival_rate: float = 0.0      # mean arrival rate, txn/s across ALL
    #                                clients (split per client like
    #                                load_rate); required > 0 when a
    #                                process is armed
    arrival_period_s: float = 1.0  # diurnal sinusoid period / bursty
    #                                on-off cycle length (seconds)
    arrival_amp: float = 0.5       # diurnal amplitude fraction in [0, 1):
    #                                rate(t) = rate * (1 + amp sin wt)
    arrival_duty: float = 0.5      # bursty: fraction of each period spent
    #                                ON at rate/duty (mean rate preserved)
    arrival_flash_at_s: float = 0.0    # flash: burst start, seconds after
    #                                    the client's run start
    arrival_flash_secs: float = 0.0    # flash: burst duration (required
    #                                    > 0 for the flash process)
    arrival_flash_factor: float = 10.0  # flash: rate multiplier inside
    #                                     the burst window
    loadgen_procs: int = 1         # open-loop generator FLEET: each client
    #                                process spawns this many seeded
    #                                generator workers (runtime/loadgen
    #                                LoadFleet), each owning a disjoint
    #                                lane-tag range and a disjoint tenant
    #                                sub-range, their arrival schedules
    #                                merged deterministically — offered
    #                                load scales past one process's
    #                                query-gen rate (the pod-scale
    #                                driving side).  1 (default) keeps
    #                                the single in-process generator and
    #                                bit-identical wire bytes.
    zipf_shift: str = ""           # mid-run contention shift "THETA:AT_S":
    #                                the client pre-generates a SECOND
    #                                seeded query ring at zipf theta=THETA
    #                                and swaps to it AT_S seconds after its
    #                                run start — the load-shift stimulus
    #                                the ctrl chaos scenario drives (zipf
    #                                0 -> 0.9 mid-run).  "" (default) =
    #                                off: no second ring is ever built and
    #                                the send path is untouched.  YCSB
    #                                only (theta is a YCSB knob).
    tenant_cnt: int = 1            # tenants sharing the cluster; each
    #                                query carries its tenant id in tag
    #                                bits 24..31 (<= 256 tenants), so the
    #                                wire format is unchanged and
    #                                tenant_cnt=1 leaves every tag byte
    #                                exactly as before
    tenant_weights: str = ""       # comma-separated arrival weights per
    #                                tenant ("1,8" = tenant 1 offers 8x
    #                                tenant 0's load — the aggressor
    #                                shape); "" = uniform
    admission: bool = False        # server-side admission control: token-
    #                                bucket tenant quotas feed a bounded
    #                                queue ahead of epoch-batch formation;
    #                                over-quota / over-capacity queries
    #                                are NACKed (ADMIT_NACK + retry-after
    #                                hint) instead of held forever.  Off
    #                                (default): every decoded CL_QRY_BATCH
    #                                goes straight to pending, no NACK is
    #                                ever sent, no controller exists.
    admission_queue_max: int = 8192    # admission queue bound (txns
    #                                    pending epoch formation); arrivals
    #                                    past it NACK with a retry hint
    tenant_quota: float = 0.0      # per-tenant token-bucket rate, txn/s
    #                                per SERVER (each server meters its own
    #                                arrivals); 0 = no quota (capacity
    #                                shedding only)
    tenant_burst_s: float = 0.5    # bucket depth in seconds of quota
    #                                (burst tolerance = quota * burst_s)
    admission_slo_ms: float = 0.0  # admission-queue-delay SLO (p99 per
    #                                epoch group).  When breached, the
    #                                controller sheds over-quota tenants
    #                                FIRST: a tenant whose bucket drained
    #                                below half depth (it arrives at >=
    #                                quota) loses its whole batch while
    #                                quota-respecting tenants keep
    #                                admitting.  0 = no SLO backpressure.
    admission_retry_us: float = 50_000.0  # base retry-after hint on a
    #                                       capacity NACK (quota NACKs
    #                                       hint the bucket refill time)
    nack_backoff_base_us: float = 20_000.0  # client backoff ledger: first
    #                                retry delay; doubles per consecutive
    #                                NACK of the same tag, jittered
    #                                +/-50%, floored at the server's
    #                                retry-after hint
    nack_backoff_max_us: float = 2_000_000.0  # backoff growth cap

    # ---- transaction flight recorder (cross-node txn lifecycle tracing
    # + structured telemetry stream; runtime/telemetry.py).  All defaults
    # OFF: with telemetry=False no recorder is ever constructed, no
    # sidecar file is written, no [telemetry] line prints, and every
    # wire byte / log byte / verdict is bit-identical to the
    # pre-telemetry runtime (the same contract as chaos/elastic/geo/
    # overload/repair/fencing). ----
    telemetry: bool = False        # arm the flight recorder: every node
    #                                (client, server, replica) records
    #                                per-hop lifecycle events for the
    #                                DETERMINISTICALLY SAMPLED txn subset
    #                                (lane % telemetry_sample == 0 on the
    #                                tag's ring-lane bits, so client and
    #                                every server pick the SAME txns with
    #                                zero coordination) into a
    #                                preallocated numpy record ring,
    #                                flushed as telemetry_*.bin sidecars;
    #                                servers additionally stream
    #                                per-epoch counters to
    #                                metrics_node*.jsonl.  Join + render
    #                                with harness/txntrace.py.
    telemetry_sample: int = 1024   # sampling modulus (depth knob, live
    #                                default like repair_rounds): 1 =
    #                                record every txn (tests/debug);
    #                                1024 = the default production rate
    #                                the <= 2% overhead gate pins
    #                                (tools/regression_gate.py,
    #                                results/telemetry)
    telemetry_ring: int = 1 << 16  # record-ring capacity per node;
    #                                events past a full ring DROP (and
    #                                count) rather than stall the hot
    #                                loop — the ring auto-flushes at
    #                                half full from the epoch loop
    telemetry_dir: str = ""        # sidecar directory; "" = log_dir
    #                                (the launcher namespaces it per run
    #                                exactly like the command logs)

    # ---- live metrics bus (cluster observability plane; runtime/
    # metricsbus.py).  Default OFF: with metrics=False no frame is ever
    # built, no METRICS rtype crosses the wire, no aggregator exists,
    # no [crit]/[watch] line prints, no metrics_bus_*.jsonl is written,
    # and every broadcast/log byte is bit-identical to the pre-bus
    # runtime (the same contract as chaos/elastic/geo/overload/repair/
    # fencing/telemetry). ----
    metrics: bool = False          # arm the bus: every node samples a
    #                                per-epoch metrics frame (host-side
    #                                counters + stage timings + the
    #                                per-partition conflict density the
    #                                incidence matmuls yield for free)
    #                                and ships it as METRICS (rtype 25)
    #                                to the aggregator on the lowest-id
    #                                live server, which writes the
    #                                metrics_bus_*.jsonl stream, emits
    #                                [crit] critical-path attribution +
    #                                [watch] anomaly events, and feeds
    #                                tools/monitor.py (live TUI +
    #                                --prom exposition)
    metrics_cadence: int = 1       # epochs between frames (depth knob,
    #                                live default like telemetry_sample:
    #                                1 = every retired epoch — the rate
    #                                the <=2% overhead gate pins,
    #                                tools/regression_gate.py +
    #                                results/metricsbus); raise it on
    #                                fast chips where per-epoch frames
    #                                would flood the aggregator

    # ---- isolation audit plane (online serializability certifier with
    # cycle-witness forensics; cc/base.audit_observe + runtime/audit.py
    # + harness/auditgraph.py).  Default OFF: with audit=False no
    # observation is ever derived, no audit_*.jsonl sidecar is written,
    # no [audit] line prints, the group jit's outputs are exactly the
    # pre-audit ones and every wire/log byte is bit-identical to the
    # pre-audit runtime (the same contract as chaos/elastic/geo/
    # overload/repair/fencing/telemetry/metrics). ----
    audit: bool = False            # arm the certifier: each epoch derives
    #                                committed-txn dependency observations
    #                                ON DEVICE (ww/wr/rw edge lists between
    #                                committed txns off the planned access
    #                                sets under the backend's visibility
    #                                rule, plus per-bucket version stamps
    #                                — the audit twin of the VersionRing)
    #                                and exports them beside the verdict
    #                                planes into audit_node*.jsonl;
    #                                harness/auditgraph.py joins the
    #                                sidecars across nodes/epochs into the
    #                                cluster-wide Direct Serialization
    #                                Graph and either certifies the run
    #                                serializable or renders a minimal
    #                                cycle witness (Adya G0/G1c/G-single/
    #                                G2 classification)
    audit_cadence: int = 8         # epochs between audited epochs (depth
    #                                knob with a live default, like
    #                                telemetry_sample: the whole device
    #                                derivation skips off-cadence epochs
    #                                via lax.cond, so coverage trades
    #                                against cost — the <=2% overhead
    #                                gate pins THIS default rate
    #                                (tools/audit_bench.py; the exact-key
    #                                lane sort is ~4 ms/epoch at B=1024
    #                                on the CPU rig, so always-on costs
    #                                ~12% there).  1 = certify every
    #                                epoch — what every chaos scenario
    #                                pins (harness/chaos.py chaos_cfg),
    #                                so the standing oracles and the
    #                                mutation catch run at FULL coverage.
    #                                Every node skips the same epochs,
    #                                keeping sidecars consensus-
    #                                comparable.
    audit_edges_max: int = 4096    # per-epoch exported-edge cap (static
    #                                d2h shape); overflow counts as
    #                                audit_drop_cnt and degrades the
    #                                certificate to "incomplete", never
    #                                silently
    audit_buckets: int = 1 << 16   # hashed width of the audit version-
    #                                stamp tables (the cross-epoch
    #                                observation space; O(K) memory like
    #                                the T/O watermarks, so it can be much
    #                                wider than conflict_buckets)
    audit_mutate: str = ""         # seeded edge-derivation fault (the
    #                                anti-inert knob): "occ-read-skip:
    #                                START[:COUNT]" drops OCC's read-set-
    #                                vs-winner-write-set check on epochs
    #                                [START, START+COUNT) — losers whose
    #                                writes miss every winner-written
    #                                bucket commit anyway, a REAL isolation
    #                                violation the certifier must reject
    #                                with a cycle witness naming an epoch
    #                                in the window.  Test/chaos use only.

    # ---- self-driving control plane (contention-adaptive CC router +
    # closed-loop degradation governors; runtime/controller.py +
    # cc/router.py).  Default OFF: with ctrl=False no controller is ever
    # constructed, the engine compiles the exact pre-router epoch
    # program, no [ctrl] line prints, and every log/wire/digest byte is
    # bit-identical to the pre-ctrl runtime (the same contract as
    # chaos/elastic/geo/overload/repair/fencing/telemetry/metrics/
    # audit). ----
    ctrl: bool = False             # arm the control plane: a
    #                                deterministic feedback controller
    #                                consumes epoch e-1's per-partition
    #                                conflict density (cc/base.
    #                                conflict_density via the metrics
    #                                plane) plus the repair/admission/
    #                                audit counters and sets, at epoch
    #                                boundaries: per-partition CC backend
    #                                (NO_WAIT/OCC/TPU_BATCH) + conflict-
    #                                bucket granularity (in-process
    #                                engine), repair-round budget, audit
    #                                cadence, and admission quota scale
    #                                (cluster servers).  Every decision
    #                                is recorded as a [ctrl] line so
    #                                replay reproduces the sequence
    #                                bit-for-bit, and a fail-safe
    #                                governor reverts every knob to the
    #                                static config when signals go stale
    #                                (aggregator death / partition /
    #                                fenced node) and re-engages on heal.
    ctrl_lo: float = 0.02          # hysteresis band floor: per-epoch
    #                                contended access lanes per batch row
    #                                below which a partition classes as
    #                                SPARSE (depth knob, live default)
    ctrl_hi: float = 0.20          # band ceiling: lanes per row above
    #                                which a partition classes as HOT;
    #                                between lo and hi the class HOLDS
    #                                (the hysteresis dead band)
    ctrl_confirm: int = 2          # consecutive boundary ticks a new
    #                                class must persist before any knob
    #                                moves (oscillation damper #1)
    ctrl_cooldown: int = 4         # boundary ticks a knob stays put
    #                                after it moved (oscillation damper
    #                                #2; per knob, not global)
    ctrl_stale_s: float = 2.0      # governor staleness bound: a
    #                                boundary gap (or density silence)
    #                                beyond this wall-clock budget trips
    #                                the fail-safe revert to the static
    #                                config
    ctrl_heal: int = 3             # consecutive healthy ticks before a
    #                                tripped governor re-engages the
    #                                adaptive knobs
    ctrl_gshift: int = 2           # conflict-granularity coarsening for
    #                                SPARSE partitions: incidence keys
    #                                shift right this many bits (merging
    #                                keys only ADDS conflicts — a sound
    #                                over-approximation that shrinks the
    #                                false-sharing surface the OCC-
    #                                granularity paper prices); 0 =
    #                                granularity knob inert
    ctrl_scale_max: int = 4        # max admission quota-scale steps the
    #                                cluster governor may shed (effective
    #                                quota = tenant_quota * 0.8^step)
    ctrl_dgcc: bool = False        # arm the controller's FOURTH router
    #                                class: HOT partitions route to the
    #                                DGCC wavefront backend (cc/dgcc.py)
    #                                instead of TPU_BATCH — conflicting
    #                                txns serialize into chained waves
    #                                rather than abort.  Default off:
    #                                the candidate list, the compiled
    #                                4-way routed program and every
    #                                [ctrl] replay stay exactly the
    #                                3-class plane (bit-identical off).

    # ---- checkpoint / resume (no reference analogue: SURVEY §5.4 notes
    # the reference cannot recover; we can) ----
    checkpoint_path: str = ""      # "" = checkpointing off
    checkpoint_every_epochs: int = 0   # 0 = only at end of run
    resume: bool = False           # load checkpoint_path before running

    # ---- misc ----
    seed: int = 0
    debug_timeline: bool = False
    owner_check: bool = False      # debug mode: wrap the dispatch-owned
    #                                host collections (runtime/
    #                                ownercheck.GUARDED) in subclasses
    #                                whose mutators assert the calling
    #                                thread is the dispatch thread — the
    #                                runtime half of the graftlint
    #                                thread-ownership checker (our
    #                                substitute for TSAN, broken on this
    #                                box).  Default off: nothing is
    #                                wrapped and no code path changes.

    # ------------------------------------------------------------------
    @property
    def faults_enabled(self) -> bool:
        """True iff any chaos knob is armed.  Every fault/failover code
        path in client, server and launcher is gated on this, so the
        default config runs byte-identical to the pre-chaos runtime."""
        return (self.fault_drop_prob > 0 or self.fault_dup_prob > 0
                or self.fault_delay_jitter_us > 0 or bool(self.fault_kill)
                or bool(self.fault_partition) or bool(self.fault_peer_stall)
                or self.recover)

    def fault_kill_spec(self) -> tuple[int, int] | None:
        """Parse fault_kill 'node:epoch' (None when unset)."""
        if not self.fault_kill:
            return None
        node, epoch = self.fault_kill.split(":")
        return int(node), int(epoch)

    def fault_partition_spec(self) -> list[tuple[int, int, bool, float]]:
        """Parse fault_partition into [(a, b, bidirectional, start_s)].
        "A-B:S" blackholes both directions from S seconds after the
        barrier; "A>B:S" only frames A sends to B.  [] when unset."""
        out: list[tuple[int, int, bool, float]] = []
        if not self.fault_partition:
            return out
        for ent in self.fault_partition.split(","):
            ent = ent.strip()
            sep = ">" if ">" in ent else "-"
            try:
                pair, start = ent.split(":")
                a, b = (int(x) for x in pair.split(sep))
                start = float(start)
            except ValueError:
                raise ValueError(
                    f"config: fault_partition entry {ent!r} must be "
                    "'A-B:START_S' (bidirectional) or 'A>B:START_S' "
                    "(one-way)")
            _check(0 <= a < self.node_cnt and 0 <= b < self.node_cnt
                   and a != b and start >= 0,
                   f"fault_partition entry {ent!r}: A/B must name "
                   "distinct server nodes and START_S must be >= 0")
            out.append((a, b, sep == "-", start))
        return out

    def fault_peer_stall_spec(self) -> tuple[int, float, float] | None:
        """Parse fault_peer_stall 'NODE:MS:START_S' (None when unset)."""
        if not self.fault_peer_stall:
            return None
        try:
            node, ms, start = self.fault_peer_stall.split(":")
            node, ms, start = int(node), float(ms), float(start)
        except ValueError:
            raise ValueError(
                f"config: fault_peer_stall {self.fault_peer_stall!r} "
                "must be 'NODE:MS:START_S'")
        _check(0 <= node < self.node_cnt and ms > 0 and start >= 0,
               "fault_peer_stall: NODE must name a server, MS > 0, "
               "START_S >= 0")
        return node, ms, start

    def geo_wan_spec(self) -> dict[tuple[int, int], int]:
        """Parse geo_wan_us into a directed {(region_a, region_b): us}
        matrix.  "A-B:us" sets both directions, "A>B:us" one; later
        entries override earlier ones."""
        out: dict[tuple[int, int], int] = {}
        if not self.geo_wan_us:
            return out
        for ent in self.geo_wan_us.split(","):
            ent = ent.strip()
            sep = ">" if ">" in ent else "-"
            try:
                pair, us = ent.split(":")
                a, b = (int(x) for x in pair.split(sep))
                us = int(us)
            except ValueError:
                raise ValueError(
                    f"config: geo_wan_us entry {ent!r} must be "
                    "'A-B:us' (symmetric) or 'A>B:us' (directed)")
            _check(0 <= a < self.geo_region_cnt
                   and 0 <= b < self.geo_region_cnt and us >= 0,
                   f"geo_wan_us entry {ent!r}: regions must be in "
                   f"[0, {self.geo_region_cnt}) and delay >= 0")
            out[(a, b)] = us
            if sep == "-":
                out[(b, a)] = us
        return out

    def tenant_weights_spec(self) -> list[float]:
        """Per-tenant arrival weights (normalized); uniform when unset."""
        if not self.tenant_weights:
            return [1.0 / self.tenant_cnt] * self.tenant_cnt
        try:
            ws = [float(x) for x in self.tenant_weights.split(",")]
        except ValueError:
            raise ValueError(
                f"config: tenant_weights {self.tenant_weights!r} must be "
                "comma-separated numbers")
        _check(len(ws) == self.tenant_cnt,
               f"tenant_weights has {len(ws)} entries for "
               f"{self.tenant_cnt} tenants")
        _check(all(w > 0 for w in ws), "tenant_weights must be positive")
        s = sum(ws)
        return [w / s for w in ws]

    def audit_mutate_spec(self) -> tuple[str, int, int] | None:
        """Parse audit_mutate 'KIND:START[:COUNT]' into (kind, start,
        count); None when unset.  COUNT defaults to 1."""
        if not self.audit_mutate:
            return None
        parts = self.audit_mutate.split(":")
        if len(parts) not in (2, 3) or parts[0] != "occ-read-skip":
            raise ValueError(
                f"config: audit_mutate {self.audit_mutate!r} must be "
                "'occ-read-skip:START_EPOCH[:COUNT]'")
        try:
            start = int(parts[1])
            count = int(parts[2]) if len(parts) == 3 else 1
        except ValueError:
            raise ValueError(
                f"config: audit_mutate {self.audit_mutate!r}: START/"
                "COUNT must be integers")
        _check(start >= 0 and count >= 1,
               "audit_mutate needs START >= 0 and COUNT >= 1")
        return parts[0], start, count

    def zipf_shift_spec(self) -> tuple[float, float] | None:
        """Parse zipf_shift 'THETA:AT_S' into (theta, at_s); None when
        unset."""
        if not self.zipf_shift:
            return None
        parts = self.zipf_shift.split(":")
        if len(parts) != 2:
            raise ValueError(
                f"config: zipf_shift {self.zipf_shift!r} must be "
                "'THETA:AT_S' (target zipf theta, shift time in seconds "
                "after run start)")
        try:
            theta, at_s = float(parts[0]), float(parts[1])
        except ValueError:
            raise ValueError(
                f"config: zipf_shift {self.zipf_shift!r}: THETA/AT_S "
                "must be numbers")
        _check(0.0 <= theta < 2.0 and at_s > 0,
               "zipf_shift needs THETA in [0, 2) and AT_S > 0")
        return theta, at_s

    def elastic_plan_spec(self) -> tuple[str, int, int] | None:
        """Parse elastic_plan 'grow|drain:node:epoch' (None when unset)."""
        if not self.elastic_plan:
            return None
        kind, node, epoch = self.elastic_plan.split(":")
        return kind, int(node), int(epoch)

    def replace(self, **kw: Any) -> "Config":
        return dataclasses.replace(self, **kw).validate()

    def validate(self) -> "Config":
        # real raises, not asserts: must hold under `python -O` too
        _check(self.node_cnt >= 1 and self.part_cnt >= 1,
               "node_cnt/part_cnt must be >= 1")
        _check(self.device_parts >= 1, "device_parts must be >= 1")
        if self.device_parts > 1:
            _check(self.part_cnt == 1,
                   "device_parts (multi-chip) and part_cnt (multi-process) "
                   "partitioning do not compose yet")
            if self.mc_plan_capacity > 0:
                _check(self.max_accesses <= 128,
                       "sharded multi-chip planning needs max_accesses "
                       "<= 128: a txn's own lanes must fit one capacity "
                       "block (the 128-lane tile floor of mc_pair_cap) "
                       "or it could defer forever — raise "
                       "mc_plan_capacity=0 to use the replicated plan")
            # ownership anchors must deal evenly over the mesh blocks
            # (storage.table.to_mc_layout); each workload's anchor is the
            # reference's node-partition unit across chips
            D = self.device_parts
            if self.workload == WorkloadKind.YCSB:
                _check(self.synth_table_size % D == 0,
                       "synth_table_size must divide over device_parts")
            elif self.workload == WorkloadKind.TPCC:
                _check(self.num_wh % D == 0,
                       "num_wh must divide over device_parts "
                       "(warehouses are the ownership anchor)")
                _check(self.insert_table_cap % D == 0,
                       "insert_table_cap must divide over device_parts")
            elif self.workload == WorkloadKind.PPS:
                for nm, n in (("pps_parts_cnt", self.pps_parts_cnt),
                              ("pps_products_cnt", self.pps_products_cnt),
                              ("pps_suppliers_cnt", self.pps_suppliers_cnt)):
                    _check(n % D == 0,
                           f"{nm} must divide over device_parts")
        _check(self.epoch_batch > 0
               and (self.epoch_batch & (self.epoch_batch - 1)) == 0,
               "epoch_batch must be a power of two (tiling discipline)")
        if self.cc_alg == CCAlg.MAAT:
            _check(self.epoch_batch <= 32768,
                   "MAAT needs epoch_batch <= 32768: its ancestor-count "
                   "order keys span epoch_batch^2 and must fit int32 "
                   "(cc/maat.py closure branch)")
        if self.sim_full_row:
            _check(self.workload != WorkloadKind.TPCC
                   or self.tpcc_full_schema,
                   "sim_full_row materializes string bytes: YCSB's "
                   "payload fields, PPS's ten strings a row, or TPCC's "
                   "full schema (tpcc_full_schema=true: the short "
                   "schema has numeric columns only)")
            _check(self.workload == WorkloadKind.YCSB
                   or self.device_parts == 1,
                   "TPCC and PPS full-width rows are loaded on one "
                   "device (their loaders build no mesh layout of a "
                   "string column)")
        if self.workload == WorkloadKind.YCSB:
            _check(self.max_accesses >= self.req_per_query,
                   "max_accesses must cover req_per_query")
            _check(abs(self.read_perc + self.write_perc - 1.0) < 1e-6,
                   "read_perc + write_perc must sum to 1")
            _check(self.skew_method in ("ZIPF", "HOT"),
                   f"bad skew_method {self.skew_method!r}")
            _check(0.0 <= self.txn_write_perc <= 1.0,
                   "txn_write_perc must be in [0, 1]")
            if self.skew_method == "HOT":
                _check(1 <= self.data_perc < self.synth_table_size,
                       "HOT skew: data_perc (hot-set key count) must be in "
                       "[1, synth_table_size)")
                _check(0.0 <= self.access_perc <= 1.0,
                       "access_perc must be in [0, 1]")
        else:
            _check(not self.ycsb_abort_mode,
                   "ycsb_abort_mode is YCSB-only (the sentinel key would "
                   "force-abort hot TPCC/PPS rows)")
        if self.workload == WorkloadKind.TPCC:
            _check(self.max_accesses >= 3 + self.max_items_per_txn,
                   "TPCC max_accesses must cover wh+dist+cust+items "
                   f"(>= {3 + self.max_items_per_txn})")
        if self.tpcc_order_index:
            _check(self.workload == WorkloadKind.TPCC,
                   "tpcc_order_index is TPC-C only")
            _check(self.device_parts == 1,
                   "tpcc_order_index does not compose with multi-chip "
                   "execution yet")
            _check(self.node_cnt == 1,
                   "tpcc_order_index is single-node only: the cluster "
                   "server path maintains ORDER_IDX but has no "
                   "overflow surfacing (the index's contract requires "
                   "the host to check DynamicSortedIndex.overflowed(); "
                   "only engine/driver.run_simulation does)")
            _check(self.num_wh * 10 < 1024
                   and self.insert_table_cap + 3001 < (1 << 21),
                   "order_index_key packs district * 2^21 + o_id into "
                   "int32: needs num_wh <= 102 and insert_table_cap + "
                   "3001 < 2^21")
        _check(self.isolation_level in (
            "SERIALIZABLE", "READ_COMMITTED", "READ_UNCOMMITTED", "NOLOCK"),
            f"bad isolation_level {self.isolation_level!r}")
        _check(self.index_struct in ("IDX_HASH", "IDX_BTREE"),
               f"bad index_struct {self.index_struct!r}")
        _check(self.tport_type in ("ipc", "tcp"),
               f"bad tport_type {self.tport_type!r}")
        _check(self.deploy in ("inproc", "cluster"),
               f"bad deploy {self.deploy!r}")
        _check(self.pipeline_epochs >= 1 and self.pipeline_groups >= 1,
               "pipeline_epochs/pipeline_groups must be >= 1")
        _check(self.send_thread_cnt >= 1 and self.rem_thread_cnt >= 1,
               "send/rem thread counts must be >= 1")
        _check(self.client_batch_size >= 64,
               "client_batch_size must be >= 64 (the client skips sends "
               "smaller than one minimal message, client.py)")
        _check(self.dist_protocol in ("auto", "vote", "merged"),
               f"bad dist_protocol {self.dist_protocol!r}")
        _check(self.host_overlap in ("auto", "on", "off"),
               f"bad host_overlap {self.host_overlap!r}")
        if (self.logging or self.replica_cnt) and self.node_cnt > 1 \
                and self.cc_alg not in (CCAlg.CALVIN, CCAlg.TPU_BATCH):
            _check(self.dist_protocol == "merged",
                   "deterministic replay (logging/replication) requires "
                   "deterministic decisions: the VOTE protocol's "
                   "partitioned local validation cannot be replayed from "
                   "the command log alone — set --dist_protocol=merged "
                   "or use a deterministic backend")
        if self.dist_protocol == "vote":
            _check(self.cc_alg not in (CCAlg.CALVIN, CCAlg.TPU_BATCH),
                   "deterministic backends coordinate via the merged-batch "
                   "sequencer exchange, not 2PC votes")
            _check(self.device_parts == 1,
                   "the VOTE protocol's per-epoch host round trip "
                   "(prepare -> vote -> decide) does not compose with "
                   "mesh-sharded epoch programs — use the merged "
                   "sequencer exchange with device_parts > 1")
            _check(not self.ycsb_abort_mode,
                   "forced-abort sentinel is a merged-mode debug oracle")
        _check(self.repl_type in ("AP", "AA"),
               f"bad repl_type {self.repl_type!r}")
        _check(0.0 <= self.fault_drop_prob < 1.0
               and 0.0 <= self.fault_dup_prob < 1.0,
               "fault probabilities must be in [0, 1)")
        _check(self.fault_delay_jitter_us >= 0,
               "fault_delay_jitter_us must be >= 0")
        if self.fault_kill:
            parts = self.fault_kill.split(":")
            _check(len(parts) == 2 and parts[0].lstrip("-").isdigit()
                   and parts[1].lstrip("-").isdigit(),
                   f"fault_kill must be 'node:epoch', got "
                   f"{self.fault_kill!r}")
            _check(0 <= int(parts[0]) < self.node_cnt,
                   "fault_kill node must name a server node")
            _check(int(parts[1]) >= 0, "fault_kill epoch must be >= 0")
        if self.fault_kill or self.recover:
            _check(self.logging,
                   "fault_kill/recover need --logging: recovery rebuilds "
                   "state by replaying the command log")
        _check(self.failover_timeout_s > 0,
               "failover_timeout_s must be > 0")
        self.fault_partition_spec()     # raises on a malformed spec
        self.fault_peer_stall_spec()
        _check(self.fault_partition_flap_s >= 0,
               "fault_partition_flap_s must be >= 0")
        if self.fault_partition_flap_s > 0:
            _check(bool(self.fault_partition),
                   "fault_partition_flap_s needs fault_partition entries "
                   "to flap")
        # ---- fencing gating (same discipline as elastic/geo/overload/
        # repair: defaults take the pre-fencing paths exactly) ----
        _check(self.fencing_phi > 0 and self.fencing_heartbeat_ms > 0
               and self.fencing_suspect_s > 0,
               "fencing_phi/fencing_heartbeat_ms/fencing_suspect_s must "
               "be > 0")
        if self.fencing:
            _check(self.elastic and self.logging,
                   "fencing needs --elastic=true and --logging: quorum "
                   "reassignment retires a fenced peer in place and "
                   "rebuilds its rows by log replay")
        if self.elastic:
            _check(self.workload == WorkloadKind.YCSB,
                   "elastic membership currently supports YCSB only (the "
                   "dense keyspace makes slot->rows enumeration and "
                   "full-residency tables exact); TPCC/PPS keep static "
                   "striping")
            _check(self.cc_alg in (CCAlg.CALVIN, CCAlg.TPU_BATCH),
                   "elastic membership requires a deterministic backend "
                   "(CALVIN/TPU_BATCH): cutover at a group boundary and "
                   "failover-by-replay both rely on deterministic merged "
                   "verdicts")
            _check(self.dist_protocol != "vote",
                   "elastic membership runs the merged sequencer "
                   "exchange; the VOTE protocol's static owner map does "
                   "not rebalance")
            _check(self.device_parts == 1,
                   "elastic (process-level) and device_parts (chip-level) "
                   "repartitioning do not compose yet")
            _check(0 <= self.elastic_spare_cnt < self.node_cnt,
                   "elastic_spare_cnt must leave >= 1 active server")
            _check(self.elastic_slots >= 1, "elastic_slots must be >= 1")
        else:
            _check(self.elastic_spare_cnt == 0 and not self.elastic_plan,
                   "elastic_spare_cnt/elastic_plan need --elastic=true")
        if self.elastic_plan:
            parts = self.elastic_plan.split(":")
            _check(len(parts) == 3 and parts[0] in ("grow", "drain")
                   and parts[1].lstrip("-").isdigit()
                   and parts[2].lstrip("-").isdigit(),
                   f"elastic_plan must be 'grow|drain:NODE:EPOCH', got "
                   f"{self.elastic_plan!r}")
            _check(0 <= int(parts[1]) < self.node_cnt,
                   "elastic_plan node must name a server node")
            _check(int(parts[2]) >= 0, "elastic_plan epoch must be >= 0")
        if self.geo:
            _check(self.elastic,
                   "geo needs --elastic=true: followers materialize every "
                   "row from the merged log stream, which requires the "
                   "full-residency elastic tables")
            _check(self.logging and self.replica_cnt >= 1,
                   "geo needs --logging and replica_cnt >= 1 (quorum "
                   "group-commit and follower reads ride the replica "
                   "LOG_MSG stream)")
            _check(1 <= self.geo_region_cnt <= self.node_cnt,
                   "geo_region_cnt must be in [1, node_cnt]")
            _check(0 <= self.geo_quorum <= self.replica_cnt,
                   "geo_quorum must be in [0, replica_cnt] (0 = all)")
            _check(0.0 <= self.geo_read_perc < 1.0,
                   "geo_read_perc must be in [0, 1)")
            _check(not self.sim_full_row,
                   "geo follower reads serve fingerprint values; "
                   "sim_full_row payload serving is not wired yet")
            _check(self.workload == WorkloadKind.YCSB,
                   "geo is YCSB-scoped for now (the follower replay "
                   "state machine and snapshot serving are built over "
                   "the YCSB full-residency table)")
            self.geo_wan_spec()   # raises on a malformed profile
        else:
            _check(self.geo_region_cnt == 1 and self.geo_quorum == 0
                   and not self.geo_wan_us and self.geo_read_perc == 0.0,
                   "geo_region_cnt/geo_quorum/geo_wan_us/geo_read_perc "
                   "need --geo=true")
        # ---- overload tier gating (same discipline as elastic/geo:
        # defaults take the pre-overload paths exactly) ----
        _check(self.arrival_process in
               ("", "poisson", "diurnal", "bursty", "flash"),
               f"bad arrival_process {self.arrival_process!r}")
        if self.arrival_process:
            _check(self.arrival_rate > 0,
                   "an arrival process needs arrival_rate > 0")
            _check(self.load_rate == 0,
                   "arrival_process replaces load_rate (open loop vs "
                   "fixed-budget closed loop); set only one")
            _check(self.arrival_period_s > 0,
                   "arrival_period_s must be > 0")
            _check(0.0 <= self.arrival_amp < 1.0,
                   "arrival_amp must be in [0, 1)")
            _check(0.0 < self.arrival_duty <= 1.0,
                   "arrival_duty must be in (0, 1]")
            if self.arrival_process == "flash":
                _check(self.arrival_flash_secs > 0
                       and self.arrival_flash_at_s >= 0
                       and self.arrival_flash_factor >= 1.0,
                       "flash arrivals need arrival_flash_secs > 0, "
                       "arrival_flash_at_s >= 0 and factor >= 1")
        else:
            _check(self.arrival_rate == 0.0,
                   "arrival_rate needs an arrival_process")
        _check(self.loadgen_procs >= 1, "loadgen_procs must be >= 1")
        if self.loadgen_procs > 1:
            _check(self.arrival_process != "",
                   "a loadgen fleet (loadgen_procs > 1) drives the "
                   "open loop — arm an arrival_process")
            _check(self.loadgen_procs <= 64,
                   "loadgen_procs > 64 exceeds the per-client lane-tag "
                   "budget (tag bits reserve 6 bits of generator lane)")
            if self.tenant_cnt > 1:
                _check(self.tenant_cnt >= self.loadgen_procs,
                       "a loadgen fleet splits [0, tenant_cnt) into "
                       "disjoint per-generator sub-ranges — tenant_cnt "
                       "must be >= loadgen_procs so no generator's "
                       "range is empty")
        if self.zipf_shift:
            self.zipf_shift_spec()      # raises on a malformed spec
            _check(self.workload == WorkloadKind.YCSB,
                   "zipf_shift shifts the YCSB zipf theta mid-run; other "
                   "workloads have no theta to shift")
        _check(1 <= self.tenant_cnt <= 256,
               "tenant_cnt must be in [1, 256] (tenant ids ride tag "
               "bits 24..31)")
        if self.tenant_cnt > 1 or self.tenant_weights:
            self.tenant_weights_spec()   # raises on a malformed spec
        if self.admission:
            _check(self.admission_queue_max >= 64,
                   "admission_queue_max must be >= 64 (one minimal "
                   "client message)")
            _check(self.tenant_quota >= 0 and self.tenant_burst_s > 0,
                   "tenant_quota must be >= 0 and tenant_burst_s > 0")
            _check(self.admission_slo_ms >= 0,
                   "admission_slo_ms must be >= 0")
            _check(self.admission_retry_us > 0
                   and self.nack_backoff_base_us > 0
                   and self.nack_backoff_max_us
                   >= self.nack_backoff_base_us,
                   "admission retry/backoff knobs must be positive and "
                   "nack_backoff_max_us >= nack_backoff_base_us")
            if self.admission_slo_ms > 0:
                _check(self.tenant_quota > 0,
                       "SLO backpressure sheds over-QUOTA tenants first: "
                       "admission_slo_ms needs tenant_quota > 0")
        else:
            _check(self.tenant_quota == 0.0
                   and self.admission_slo_ms == 0.0,
                   "tenant_quota/admission_slo_ms need --admission=true")
        # ---- telemetry gating (same discipline as elastic/geo/overload/
        # repair/fencing: defaults take the pre-telemetry paths exactly;
        # sample/ring/dir are depth knobs with live defaults) ----
        _check(self.telemetry_sample >= 1,
               "telemetry_sample must be >= 1 (1 records every txn)")
        _check(self.telemetry_ring >= 1024,
               "telemetry_ring must be >= 1024 (one client batch of "
               "events must fit between flush points)")
        # ---- metrics bus gating (same discipline: the default takes
        # the pre-bus paths exactly; cadence is a depth knob with a
        # live default) ----
        _check(self.metrics_cadence >= 1,
               "metrics_cadence must be >= 1 (1 frames every epoch)")
        if self.metrics:
            _check(self.device_parts == 1,
                   "the metrics bus's conflict-density fold does not "
                   "compose with multi-chip execution yet (sharded "
                   "tables have no single bucket space to fold)")
        # ---- isolation audit gating (same discipline: the default
        # takes the pre-audit paths exactly; cadence/edges/buckets are
        # depth knobs with live defaults) ----
        _check(self.audit_cadence >= 1,
               "audit_cadence must be >= 1 (1 exports every epoch)")
        _check(self.audit_edges_max >= 64,
               "audit_edges_max must be >= 64")
        _check(self.audit_buckets >= 1024
               and (self.audit_buckets & (self.audit_buckets - 1)) == 0,
               "audit_buckets must be a power of two >= 1024")
        if self.audit:
            _check(self.mode == Mode.NORMAL,
                   "audit certifies executed state; degraded modes "
                   "(SIMPLE/NOCC/QRY_ONLY) execute nothing to certify")
            _check(self.device_parts == 1,
                   "audit observations do not compose with multi-chip "
                   "execution yet (the edge derivation is single-device)")
            # (MVCC is modeled since the depgraph refactor: audit_init
            # carries per-bucket version-boundary rings and reads select
            # their observed version by timestamp —
            # cc/depgraph.version_select)
            _check(self.workload in (WorkloadKind.YCSB, WorkloadKind.TPCC),
                   "audit is wired for YCSB and TPCC (the workload load "
                   "path installs the audit stamp tables)")
            _check(self.epoch_batch <= 16384,
                   "audit needs epoch_batch <= 16384: exported edges "
                   "pack (kind, src, dst) merged-batch ranks into 14-bit "
                   "fields of one int32")
            _check(self.dist_protocol != "vote",
                   "audit needs the merged epoch body (the VOTE "
                   "dispatch path derives no observation, so the "
                   "certifier would be armed but provably inert)")
            if self.node_cnt > 1:
                _check(self.dist_protocol == "merged"
                       or self.cc_alg in (CCAlg.CALVIN, CCAlg.TPU_BATCH,
                                          CCAlg.DGCC),
                       "cluster audit needs the replicated deterministic "
                       "verdict (--dist_protocol=merged or a "
                       "deterministic backend): the VOTE protocol's "
                       "partitioned local validation exports no "
                       "cluster-consistent observation")
        else:
            _check(not self.audit_mutate,
                   "audit_mutate needs --audit=true (the certifier must "
                   "be armed to catch the mutation)")
        if self.audit_mutate:
            self.audit_mutate_spec()    # raises on a malformed spec
            _check(self.cc_alg == CCAlg.OCC,
                   "audit_mutate 'occ-read-skip' weakens OCC's "
                   "read-set-vs-winner-write-set check; set cc_alg=OCC")
        # ---- transaction repair gating (same discipline as elastic/geo/
        # overload: defaults take the pre-repair paths exactly) ----
        _check(self.repair_rounds >= 0 and self.repair_rounds <= 8,
               "repair_rounds must be in [0, 8] (each round is a fused "
               "re-validation + re-execution pass inside the epoch jit)")
        if self.repair:
            _check(self.cc_alg in (CCAlg.NO_WAIT, CCAlg.WAIT_DIE,
                                   CCAlg.OCC, CCAlg.TIMESTAMP, CCAlg.MVCC,
                                   CCAlg.MAAT),
                   "repair applies to the six sweep backends only "
                   "(CALVIN/TPU_BATCH never abort and DGCC defers its "
                   "over-deep closures — there is nothing to salvage; "
                   "NOCC has no conflicts)")
            _check(self.mode == Mode.NORMAL,
                   "repair re-executes committed state; degraded modes "
                   "(SIMPLE/NOCC/QRY_ONLY) have no abort path to salvage")
            _check(self.device_parts == 1,
                   "repair sub-rounds do not compose with multi-chip "
                   "execution yet (the frontier matvec and the chained "
                   "re-execution are single-device)")
            _check(self.workload in (WorkloadKind.YCSB, WorkloadKind.TPCC),
                   "repair re-execution closures are wired for YCSB and "
                   "TPCC (workloads declare re_execute); PPS keeps "
                   "retry-only semantics")
            if self.node_cnt > 1:
                _check(self.dist_protocol == "merged",
                       "cluster repair needs --dist_protocol=merged: the "
                       "repair sub-rounds are part of the replicated "
                       "deterministic verdict, which the VOTE protocol's "
                       "partitioned local validation cannot express")
        # ---- DGCC wavefront gating (cc/dgcc.py) ----
        _check(1 <= self.dgcc_levels <= 256,
               "dgcc_levels must be in [1, 256] (wave budget per epoch; "
               "each relaxation round is two segmented scans inside the "
               "epoch jit)")
        if self.cc_alg == CCAlg.DGCC:
            _check(self.workload in (WorkloadKind.YCSB, WorkloadKind.TPCC),
                   "DGCC's wave re-execution closures are wired for YCSB "
                   "and TPCC (workloads declare chained execution; PPS "
                   "keeps the sweep backends)")
            _check(self.dist_protocol != "vote",
                   "DGCC's verdict is a pure replicated function of the "
                   "merged batch — use the merged sequencer exchange, "
                   "not 2PC votes")
        # ---- control plane gating (same discipline: the default takes
        # the pre-ctrl paths exactly; lo/hi/confirm/cooldown/stale/heal/
        # gshift/scale_max are depth knobs with live defaults) ----
        _check(0.0 <= self.ctrl_lo < self.ctrl_hi,
               "ctrl hysteresis band needs 0 <= ctrl_lo < ctrl_hi")
        _check(self.ctrl_confirm >= 1 and self.ctrl_cooldown >= 0
               and self.ctrl_heal >= 1,
               "ctrl_confirm/ctrl_heal must be >= 1, ctrl_cooldown >= 0")
        _check(self.ctrl_stale_s > 0, "ctrl_stale_s must be > 0")
        _check(0 <= self.ctrl_gshift <= 16,
               "ctrl_gshift must be in [0, 16] (key bits to coarsen)")
        _check(0 <= self.ctrl_scale_max <= 16,
               "ctrl_scale_max must be in [0, 16] quota-scale steps")
        if self.ctrl:
            _check(self.metrics,
                   "ctrl consumes the conflict-density signal: needs "
                   "--metrics=true (the PR 14 observability plane)")
            _check(self.mode == Mode.NORMAL,
                   "ctrl adapts executed-state knobs; degraded modes "
                   "(SIMPLE/NOCC/QRY_ONLY) have nothing to adapt")
            cands = (CCAlg.NO_WAIT, CCAlg.OCC, CCAlg.TPU_BATCH) \
                + ((CCAlg.DGCC,) if self.ctrl_dgcc else ())
            _check(self.cc_alg in cands,
                   "ctrl routes between NO_WAIT/OCC/TPU_BATCH (plus "
                   "DGCC when --ctrl_dgcc=true); the static cc_alg must "
                   "be one of the candidates (it is the governor's "
                   "fail-safe assignment)")
            _check(self.device_parts == 1,
                   "the ctrl router's branched epoch program is "
                   "single-device (multi-chip plans are built per-shard "
                   "inside shard_map)")
            _check(not self.ycsb_abort_mode,
                   "ctrl does not compose with the ycsb_abort_mode "
                   "sentinel (the forced-abort mask is backend-path "
                   "specific)")
            _check(not self.audit_mutate,
                   "ctrl does not compose with audit_mutate (the "
                   "seeded fault targets the static OCC path)")
            _check(self.workload != WorkloadKind.PPS,
                   "ctrl does not compose with PPS: the router's mixed "
                   "branch hands each backend its own sub-batch, and "
                   "the stale-reconnaissance rule (cc/base.stale_recon) "
                   "needs every mapping write of the epoch in the batch "
                   "it reads")
            _check(not self.escrow_order_free,
                   "ctrl does not compose with escrow ordering "
                   "exemptions yet (the router's cross-backend batch "
                   "carries one shared conflict derivation)")
            if self.node_cnt > 1:
                _check(self.admission,
                       "cluster ctrl actuates admission quota scaling: "
                       "needs --admission=true")
        if self.ctrl_dgcc:
            _check(self.ctrl,
                   "ctrl_dgcc arms the router's fourth (DGCC) class: "
                   "needs --ctrl=true")
        if self.fencing and self.fault_peer_stall:
            # the gray-slow node ends up fenced and retired in place —
            # same coordinator constraint as the elastic kill below
            _check(int(self.fault_peer_stall.split(":")[0]) != 0,
                   "fencing cannot retire node 0 (the measure/stop "
                   "coordinator); stall a node >= 1")
        if self.fencing and self.fault_partition:
            # node 0's partition side must win the quorum decision
            # (majority, or the lowest-id tiebreak — which node 0 holds
            # by construction): a spec that isolates the measure/stop
            # coordinator into a minority would fence it and strand the
            # survivors on multi-minute recovery timeouts instead of
            # failing fast here.  Approximate the sides by connected
            # components over the UNDIRECTED uncut link graph (any
            # entry, either direction, severs its pair).
            cut = {frozenset((a, b))
                   for a, b, _bi, _s in self.fault_partition_spec()}
            comp, frontier = {0}, [0]
            while frontier:
                u = frontier.pop()
                for v in range(self.node_cnt):
                    if v != u and v not in comp \
                            and frozenset((u, v)) not in cut:
                        comp.add(v)
                        frontier.append(v)
            _check(2 * len(comp) >= self.node_cnt,
                   "fencing cannot fence node 0 (the measure/stop "
                   "coordinator): this fault_partition isolates it on "
                   "a minority side — cut around a node >= 1")
        if self.elastic and self.fault_kill:
            # failover-with-reassignment: survivors absorb the dead
            # node's slots by log replay — never restart it
            _check(int(self.fault_kill.split(":")[0]) != 0,
                   "elastic reassignment cannot lose node 0 (the "
                   "measure/stop coordinator); kill node >= 1")
        if self.workload == WorkloadKind.PPS:
            mix = (self.perc_getparts + self.perc_getproducts + self.perc_getsuppliers
                   + self.perc_getpartbyproduct + self.perc_getpartbysupplier
                   + self.perc_orderproduct + self.perc_updateproductpart + self.perc_updatepart)
            _check(abs(mix - 1.0) < 1e-6, "PPS txn mix must sum to 1")
            _check(self.max_accesses >= 1 + 2 * self.pps_parts_per,
                   "PPS max_accesses must cover anchor + mapping + parts "
                   f"(>= {1 + 2 * self.pps_parts_per})")
        return self

    # -- CLI bridge -----------------------------------------------------
    @classmethod
    def from_args(cls, argv: list[str]) -> "Config":
        """Parse ``--field=value`` / ``--field value`` pairs.

        Replaces the reference's hand-rolled ``-nidN -tN -zipfF`` parser
        (`system/parser.cpp:20-262`); any dataclass field is settable.
        """
        kw: dict[str, Any] = {}
        i = 0
        fields = {f.name: f for f in dataclasses.fields(cls)}
        while i < len(argv):
            arg = argv[i]
            if not arg.startswith("--"):
                raise ValueError(f"unrecognized argument {arg!r}")
            if "=" in arg:
                name, val = arg[2:].split("=", 1)
            else:
                if i + 1 >= len(argv):
                    raise ValueError(f"flag {arg!r} is missing a value")
                name, val = arg[2:], argv[i + 1]
                i += 1
            name = name.replace("-", "_")
            if name not in fields:
                raise ValueError(f"unknown config field {name!r}")
            kw[name] = _coerce(fields[name].type, val)
            i += 1
        return cls(**kw).validate()


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise ValueError(f"config: {msg}")


def _coerce(typ: Any, val: str) -> Any:
    t = str(typ)
    if "CCAlg" in t:
        return CCAlg(val)
    if "WorkloadKind" in t:
        return WorkloadKind(val)
    if "Mode" in t:
        return Mode(val)
    if "bool" in t:
        low = val.lower()
        if low in ("1", "true", "yes", "on"):
            return True
        if low in ("0", "false", "no", "off"):
            return False
        raise ValueError(f"invalid boolean value {val!r}")
    if "int" in t:
        return int(val)
    if "float" in t:
        return float(val)
    if "tuple" in t:
        return tuple(int(x) for x in val.strip("()").split(",") if x)
    return val
