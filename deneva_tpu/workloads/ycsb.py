"""YCSB (reference `benchmarks/ycsb_wl.cpp`, `ycsb_query.cpp`, `ycsb_txn.cpp`).

One table of ``synth_table_size`` rows with 10 string fields
(`benchmarks/YCSB_schema.txt`); queries are ``req_per_query`` accesses with
zipfian keys and a per-request write probability
(`ycsb_query.cpp:303-376`).  A request reads field F0 or blindly
overwrites it (`ycsb_txn.cpp:177-209` does `get_value/set_value` on one
field per request).

TPU shape: the table is a `DeviceTable` (SoA, fingerprint strings), the
primary index is the identity `DenseIndex` (YCSB keys are dense,
`ycsb_wl.cpp:70-74`), queries are generated on device per epoch, and
execute is one gather (reads, checksummed into stats so XLA cannot
dead-code them) plus one last-writer scatter (writes); with full rows
(`sim_full_row`) only the final writers' lanes reach that scatter,
compacted first (`ops.scatter.scatter_winner_rows`), and of the
forwarding executor's lanes only the reads that nothing forwards to
reach the gather, compacted likewise (`ops.gather.checksum_needed_rows`).

Multi-partition control (`FIRST_PART_LOCAL`, `PART_PER_TXN`, MPR
`ycsb_query.cpp:303-376`) maps to the mesh build: keys are striped
``slot % n_parts`` across devices, so a zipfian batch is naturally
multi-partition; `deneva_tpu.parallel` documents the layout.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from deneva_tpu.config import CCAlg, Config
from deneva_tpu.ops import (HotSet, Zipfian, checksum_needed_rows,
                            forward_plan, last_writer, scatter_winner_rows)
from deneva_tpu.storage.catalog import parse_schema
from deneva_tpu.storage.index import DenseIndex, SortedIndex
from deneva_tpu.storage.table import DeviceTable, VersionRing, create_mc

# benchmarks/YCSB_schema.txt: MAIN_TABLE, 10 x 100-byte string fields
YCSB_SCHEMA = "TABLE=MAIN_TABLE\n" + "".join(
    f"\t100,string,F{i}\n" for i in range(10)) + "INDEX=MAIN_INDEX\n\tMAIN_TABLE,0\n"

TABLE = "MAIN_TABLE"
TABLE_ID = 0
VER_TABLE = "MAIN_TABLE.F0.ver"   # MVCC per-row version-value ring


@dataclass
class YCSBQuery:
    """One epoch's queries; pytree with leading dim n."""

    keys: jax.Array      # int32[n, R]
    is_write: jax.Array  # bool[n, R]


jax.tree_util.register_dataclass(YCSBQuery, data_fields=["keys", "is_write"],
                                 meta_fields=[])


def _field_fingerprint(key: jax.Array | np.ndarray, version):
    """Deterministic field value = f(key, version): lets consistency tests
    recompute expected content without storing 100-byte payloads."""
    k = jnp.asarray(key).astype(jnp.uint32)
    v = jnp.asarray(version).astype(jnp.uint32)
    return (k * jnp.uint32(2654435761)) ^ (v * jnp.uint32(0x9E3779B9)) | jnp.uint32(1)


def _field_bytes(key, version, nbytes: int) -> jax.Array:
    """SIM_FULL_ROW payload: uint8[..., nbytes] real field bytes, still a
    pure function of (key, version) so consistency tests can recompute
    expected content (reference `storage/row.cpp:30`; the reference fills
    'hello' + garbage, `ycsb_wl.cpp` init — ours must be
    version-dependent so forwarded reads are checkable)."""
    fp = _field_fingerprint(key, version)
    i = jnp.arange(nbytes, dtype=jnp.uint32)
    mixed = fp[..., None] * (i * jnp.uint32(2654435761)
                             + jnp.uint32(0x9E3779B9))
    return ((mixed >> jnp.uint32(13)) & jnp.uint32(0xFF)).astype(jnp.uint8)


def _mono_winner_lanes(p, slots: jax.Array, n_rows: int):
    """(slot, key, rank) each lane of the monotone write scatter carries:
    its nearest preceding winner's, so duplicate lanes rewrite one value
    idempotently.  Lanes BEFORE the first winner have no winner to
    repeat, so they repeat the FIRST winner's write (its slot is <= every
    later one: still monotone); an epoch with no winner at all sends
    every lane to ``n_rows``, out of range, which ``mode='drop'`` drops.

    The result is non-decreasing and within [0, n_rows] — the PROMISE
    ``indices_are_sorted=True`` makes and the TPU's scatter relies on.
    The former -1 sentinel for the leading lanes wraps to the last row
    BEFORE the drop mode applies, i.e. the index vector began with its
    largest value: the CPU ignores the promise and wrote every winner,
    the chip (first run of `chip_smoke.py`, PR 22) wrote none of them."""
    from deneva_tpu.ops.forward import seg_first
    # nearest-preceding-winner slot: cummax works because slots ascend
    # (a Kogge-Stone scan here measures slower end-to-end — XLA fuses
    # its concatenate chains into the gather fusion)
    wslot = jax.lax.cummax(jnp.where(p.win, slots, jnp.int32(-1)))
    lead = wslot < 0
    first = jnp.argmax(p.win)               # 0 when nothing wins
    wslot = jnp.where(lead, jnp.where(p.win[first], slots[first],
                                      jnp.int32(n_rows)), wslot)
    wkey = jnp.where(lead, p.keys[first], seg_first(p.win, p.keys))
    wrank = jnp.where(lead, p.rank[first], seg_first(p.win, p.rank))
    return wslot, wkey, wrank


def _forward_execute_f0(f0: jax.Array, p, slots: jax.Array, trash,
                        mono: bool = False):
    """THE forwarding-executor data path, shared verbatim by the
    single-chip `execute` and each shard of `execute_mc` so their
    semantics cannot diverge: reads take F0 (forwarded lanes take
    f(key, writer rank) instead), the checksum folds over reads, and
    only final writers scatter.  Returns (f0', checksum, write_cnt,
    scatter lanes, gather lanes) — the caller decides whether the
    scalars need a psum.

    ``f0`` is uint32[N] in fingerprint mode or uint8[N, S] under
    SIM_FULL_ROW — the full-row branch moves the real payload bytes, so
    benchmark numbers measure reference-width HBM traffic.

    The read half.  Full rows: only the reads that nothing forwards to
    (``is_read & (fwd < 0)``) reach the row gather, compacted first and
    then gathered in a loop of short calls — write lanes, forwarded
    reads and a shard's padding lanes never do, and a lane costs the
    gather by the lane count of its call, whatever it reads
    (`ops.gather.checksum_needed_rows`); the forwarded reads add the
    bytes of f(key, writer rank).  The checksum is the per-lane
    gather's, to the bit.  Fingerprints (4.9 ns a uint32 lane): every
    lane gathers.  The lanes handed to the gather come back as the
    fifth value (`stats["read_gather_lanes"]`).

    The write half has three forms.  Full rows under ``mono`` (callers
    with key-monotone slot maps, i.e. every current caller: slot order
    follows the plan's sorted key order and masked lanes steer to a
    trash at/above the top): `ops.scatter.scatter_winner_rows` — the
    final writers are compacted to the front and only they reach the
    row write, in chunks whose number follows the epoch's winner
    count: a chunk's rows go through ONE Pallas kernel that moves
    32-row tile groups between HBM and VMEM, 256 in flight, on the TPU
    where a call holds 2,560 lanes (the hot cell), and through XLA's
    scatter elsewhere (on v5e XLA writes a row lane in 71 ns one by
    one, or the whole column in a 4 ms pass with the sorted promise,
    whatever the lanes; the kernel writes a lane in 40-45 ns in calls
    of 2,560, whatever the skew, in 66 in calls of 1,280 and in 90 or
    more in calls of 160: PERF.md section 6, PRs 26 and 48);
    the trash row is never written.  Fingerprints
    under ``mono``: every lane is issued with MONOTONE, pre-sorted
    indices — ``cummax`` carries the latest winner's slot into following
    lanes and two head-propagation scans carry its (key, rank), so the
    duplicate lanes rewrite the same value idempotently
    (`_mono_winner_lanes`); that skips the sort XLA otherwise inserts
    inside a scatter's lowering (~0.6 ms at 655k lanes on v5e), and a
    uint32 lane costs the same 4.9 ns either way (BASELINE.md).  The
    legacy trash-steered scatter remains for non-monotone slot maps
    (mono=False).  Returns the lanes handed to the row write as a fourth
    value (`stats["write_scatter_lanes"]`) and the tile groups its
    kernel wrote back for them as a sixth (`stats["write_row_groups"]`,
    where the server asked for it; 0 on the two other forms).

    The two halves carry the epoch's `ep.read` / `ep.write` scopes
    (metadata: `engine/epoch.make_epoch_body`)."""
    with jax.named_scope("ep.read"):
        if f0.ndim == 2:
            nbytes = f0.shape[1]
            tbl, rlanes = checksum_needed_rows(
                f0, slots, p.is_read & (p.fwd < 0))
            cks = tbl + jnp.sum(
                jnp.where((p.is_read & (p.fwd >= 0))[:, None],
                          _field_bytes(p.keys, p.fwd, nbytes), 0),
                dtype=jnp.uint32)
        else:
            rlanes = jnp.uint32(slots.shape[0])
            vals = jnp.take(f0, jnp.where(p.is_read, slots, trash), axis=0)
            vals = jnp.where(p.fwd >= 0,
                             _field_fingerprint(p.keys, p.fwd), vals)
            cks = jnp.sum(jnp.where(p.is_read, vals, 0), dtype=jnp.uint32)
    with jax.named_scope("ep.write"):
        lanes, groups = jnp.uint32(slots.shape[0]), jnp.uint32(0)
        if mono and f0.ndim == 2:
            f0, lanes, groups, cks = scatter_winner_rows(
                f0, slots, p.win, (p.keys, p.rank),
                lambda k, r: _field_bytes(k, r, nbytes), n_rows=trash,
                after=cks)
        elif mono:
            wslot, wkey, wrank = _mono_winner_lanes(p, slots, f0.shape[0])
            f0 = f0.at[wslot].set(
                _field_fingerprint(wkey, wrank).astype(f0.dtype),
                mode="drop", indices_are_sorted=True)
        else:
            wvals = _field_bytes(p.keys, p.rank, f0.shape[1]) \
                if f0.ndim == 2 \
                else _field_fingerprint(p.keys, p.rank).astype(f0.dtype)
            f0 = f0.at[jnp.where(p.win, slots, trash)].set(wvals)
        wcnt = p.is_write.sum(dtype=jnp.uint32)
    return f0, cks, wcnt, lanes, rlanes, groups


def _count_row_groups(stats: dict, groups) -> None:
    """The tile groups the row write's kernel wrote back, where the
    server asked for the counter
    (`engine/step.init_device_stats(row_groups=True)`)."""
    if "write_row_groups" in stats:     # workloads/base.ROW_GROUP_COUNTER
        stats["write_row_groups"] = stats["write_row_groups"] + groups


def _count(stats: dict, **add) -> None:
    """One epoch's execution into the device counters
    (`engine/step.init_device_stats`); a caller's hand-built dict may
    hold only the counters it reads."""
    for k, v in add.items():
        stats[k] = stats.get(k, 0) + v


class YCSBWorkload:
    # writes overwrite a field with f(key, order) — independent of any
    # read — so the single-pass forwarding executor applies (ops/forward)
    blind_writes = True
    # per-type statistics (reference Stats_thd per-txn-kind counters)
    txn_type_names = ("ycsb_ro", "ycsb_rw")

    def txn_type_of(self, q: "YCSBQuery") -> jax.Array:
        return q.is_write.any(axis=1).astype(jnp.int32)

    def __init__(self, cfg: Config):
        self.cfg = cfg
        # schema at configured width (TUP_SIZE × FIELD_PER_TUPLE,
        # config.h:150-152); the module-level YCSB_SCHEMA is the
        # reference default (10 × 100B)
        self.catalog = parse_schema(
            "TABLE=MAIN_TABLE\n"
            + "".join(f"\t{cfg.tup_size},string,F{i}\n"
                      for i in range(cfg.field_per_tuple))
            + "INDEX=MAIN_INDEX\n\tMAIN_TABLE,0\n")
        self.n_rows = cfg.synth_table_size
        # full rows are written through `ops.scatter.scatter_winner_rows`:
        # a server counts the tile groups its kernel writes back
        # (`workloads/base.ROW_GROUP_COUNTER`)
        self.writes_row_groups = bool(cfg.sim_full_row)
        # partitioned deployment (reference `key % g_part_cnt` node
        # ownership, ycsb_wl.cpp:70-74 / global.h:294): this node stores
        # only keys ≡ node_id (mod part_cnt); the strided index steers
        # remote keys to the trash slot so execution is local-only.
        self.n_parts = max(cfg.part_cnt, 1)
        self.elastic = cfg.elastic
        if self.elastic:
            # elastic membership (runtime/membership.py): ownership is
            # the slot-map MASK, not the storage layout.  Every node
            # holds the FULL keyspace (local slot == key, identity
            # index) so a slot acquired mid-run always has a resident
            # row to install the migrated value into; non-owned lanes
            # steer to the trash slot via `slot_map_owned` at access
            # time (`_local_slots`).  The boot map degenerates to exact
            # modulo striping, so the mask — and therefore every verdict
            # and every ack — is bit-identical to the striped layout
            # until a rebalance moves a slot.
            from deneva_tpu.runtime.membership import initial_map
            self.n_local = self.n_rows
            self.index = DenseIndex(base=0, stride=1, size=self.n_rows,
                                    miss_slot=self.n_rows)
            self._boot_map = initial_map(cfg)
            self.n_slots = self._boot_map.n_slots
        elif self.n_parts > 1:
            assert self.n_rows % self.n_parts == 0, \
                "synth_table_size must divide evenly over part_cnt"
            self.n_local = self.n_rows // self.n_parts
            self.index = DenseIndex(base=cfg.node_id, stride=self.n_parts,
                                    size=self.n_local, miss_slot=self.n_local)
        else:
            self.n_local = self.n_rows
            self.index = DenseIndex(base=0, stride=1, size=self.n_rows,
                                    miss_slot=self.n_rows)
        if cfg.index_struct == "IDX_BTREE":
            # INDEX_STRUCT=IDX_BTREE (global.h:320-324): probe an ordered
            # index (binary-search ladder) instead of the affine perfect
            # hash that dense YCSB keys otherwise admit.  Same key->slot
            # map, so results are identical; this exercises the
            # `index_btree` analogue on the primary path.
            self.index = SortedIndex.build(
                self._owned_keys(),
                np.arange(self.n_local, dtype=np.int32),
                miss_slot=self.n_local)
        # key sampler: Gray zipfian or HOT two-tier uniform
        # (SKEW_METHOD, config.h:162-167)
        if cfg.skew_method == "HOT":
            self.zipf = HotSet(self.n_rows, int(cfg.data_perc),
                               cfg.access_perc)
        else:
            self.zipf = Zipfian(self.n_rows, cfg.zipf_theta)
        self.n_req = cfg.req_per_query

    def _owned_keys(self) -> np.ndarray:
        """Global keys owned by this node, in slot order — the single
        definition of the `key % part_cnt` partition layout
        (ycsb_wl.cpp:70-74); shared by both index kinds and the loader.
        Elastic mode is full-residency: every key has a local row (the
        ownership mask lives in the slot map, not the layout)."""
        base, stride = self._key_law()
        return (base + np.arange(self.n_local, dtype=np.int64)
                * stride).astype(np.int32)

    def _key_law(self) -> tuple[int, int]:
        """(base, stride): local slot s holds global key base + s * stride."""
        if self.elastic or self.n_parts <= 1:
            return 0, 1
        return self.cfg.node_id, self.n_parts

    # -- loader (ycsb_wl.cpp:125-203) ----------------------------------
    def _load_mc(self) -> DeviceTable:
        """The table over ``device_parts`` chips, each shard built on the
        chip that holds it (`storage.table.create_mc`): mesh block d
        holds exactly this node's slots ≡ d (mod D) — the reference's
        strided node partition (ycsb_wl.cpp:70-74) across CHIPS — as
        ``to_mc_layout`` of the single-device load would, without the
        single-device table (25 M full rows fit no one chip).  The mesh
        is the configuration's (`parallel.mesh.make_mesh`,
        deterministic), so every caller of ``load()`` — the server, log
        replay, the benchmark's verdict replay — gets the same placement
        and `ServerNode`'s later ``device_put`` moves nothing."""
        from deneva_tpu.parallel.mesh import make_mesh
        cfg = self.cfg
        base, stride = self._key_law()

        def key(slot):
            return slot * jnp.int32(stride) + jnp.int32(base)
        if cfg.sim_full_row:
            def init(slot):
                return _field_bytes(key(slot), 0, cfg.tup_size)
            fns = {c.name: init for c in self.catalog.table(TABLE).columns}
        else:
            fns = {"F0": lambda slot: _field_fingerprint(key(slot), 0)}
        return create_mc(self.catalog.table(TABLE), self.n_local,
                         make_mesh(cfg.device_parts), fns,
                         full_row=cfg.sim_full_row)

    def _load_one(self) -> DeviceTable:
        full = self.cfg.sim_full_row
        tab = DeviceTable.create(self.catalog.table(TABLE), self.n_local,
                                 full_row=full)
        keys = self._owned_keys()
        if full:
            # SIM_FULL_ROW: every field materializes real payload bytes —
            # rows are reference-width resident data (10 × 100B default)
            init = _field_bytes(jnp.asarray(keys), 0, self.cfg.tup_size)
            for name in tab.columns:
                tab.columns[name] = tab.columns[name].at[
                    : self.n_local].set(init)
        else:
            # remaining fields share the same fingerprint law; only F0 is
            # touched by queries (ycsb_txn.cpp reads/writes one field).
            # Computed and stored on the device: no host round trip, and
            # the whole loader traces (tests/test_chip_compile.py compiles
            # it for the chip at the served size)
            tab.columns["F0"] = tab.columns["F0"].at[: self.n_local].set(
                _field_fingerprint(keys, 0))
        return tab

    def load(self):
        # several chips: each owner-major block is built on the chip that
        # holds it; one chip: the table, eagerly, on the default device
        tab = self._load_mc() if self.cfg.device_parts > 1 \
            else self._load_one()
        db = {TABLE: tab}
        if self.elastic:
            # device-resident owner array: ownership changes are a data
            # update between group dispatches, never a re-jit.  Excluded
            # from state_digest (control plane, not row state).
            from deneva_tpu.runtime.membership import MEMBER_KEY
            db[MEMBER_KEY] = jnp.asarray(self._boot_map.owners)
        if self.cfg.cc_alg == CCAlg.MVCC and self.cfg.device_parts == 1:
            # per-row overwrite-ts ring (row_mvcc.cpp:172-196): stale
            # reads of read-write txns return HISTORICAL bytes of the
            # queried field — reconstructed from the version law
            # f(key, v*) with v* from the ring (VersionRing docstring).
            # Paired with the bucket boundary ring in
            # cc/timestamp.MVCCState, which makes the retention DECISION
            # and bounds this ring's needed depth.
            f0 = tab.columns["F0"]
            # depth must be the FULL mvcc_his_len: a servable read at t
            # may have mvcc_his_len-1 overwrites postdating t (the
            # decision ring's commit rule allows exactly that many), and
            # the ts-only reconstruction needs ONE more retained entry —
            # the newest <= t, which IS v* (the value ring of rounds 3-4
            # stored displaced bytes, so it only needed the >t entries;
            # this one reads v* directly)
            db[VER_TABLE] = VersionRing.create(
                f0.shape[0], self.cfg.mvcc_his_len)
        if self.cfg.audit:
            # isolation audit stamp tables (cc/base.audit_observe):
            # installed by the loader so EVERY db-construction path —
            # engine init, server boot, log replay, follower boot —
            # threads the identical pytree.  Control plane like
            # MEMBER_KEY: excluded from state_digest.
            from deneva_tpu.cc.base import AUDIT_KEY, audit_init
            db[AUDIT_KEY] = audit_init(self.cfg)
        return db

    # -- query generation (ycsb_query.cpp:303-376) ---------------------
    def generate(self, rng: jax.Array, n: int) -> YCSBQuery:
        k1, k2, k3 = jax.random.split(rng, 3)
        keys = self.zipf.sample(k1, (n, self.n_req))
        if self.cfg.key_order:
            # KEY_ORDER (config.h:106): requests sorted ascending by key.
            # acctype is iid per slot so sorting keys alone is
            # distribution-identical to the reference's paired sort.
            keys = jnp.sort(keys, axis=1)
        is_write = jax.random.bernoulli(k2, self.cfg.write_perc,
                                        (n, self.n_req))
        if self.cfg.txn_write_perc < 1.0:
            # TXN_WRITE_PERC: one per-txn draw gates all writes — with prob
            # 1-p the whole txn is read-only (ycsb_query.cpp:313,331)
            may_write = jax.random.bernoulli(
                k3, self.cfg.txn_write_perc, (n, 1))
            is_write = is_write & may_write
        return YCSBQuery(keys=keys, is_write=is_write)

    # -- wire adapters (distributed runtime, CL_QRY/EPOCH_BLOB bodies) --
    def to_wire(self, q: YCSBQuery):
        """(keys int32[n,W], types int8[n,W], scalars int32[n,S]) columnar
        form fed to the native qrybatch codec."""
        keys = np.asarray(q.keys, np.int32)
        types = np.where(np.asarray(q.is_write), 2, 1).astype(np.int8)
        return keys, types, np.zeros((len(keys), 0), np.int32)

    def from_wire(self, keys: np.ndarray, types: np.ndarray,
                  scalars: np.ndarray) -> YCSBQuery:
        return YCSBQuery(keys=jnp.asarray(keys, jnp.int32),
                         is_write=jnp.asarray(types == 2))

    def from_wire_dev(self, keys, types, scalars) -> YCSBQuery:
        """Traceable from_wire: runs INSIDE the cluster dispatch jit so
        the wire columns cross h2d flat (layout-padding-free) and
        decode on device."""
        return YCSBQuery(keys=keys.astype(jnp.int32),
                         is_write=types == jnp.int8(2))

    # -- RW-set planning ------------------------------------------------
    def plan(self, db, q: YCSBQuery) -> dict:
        shape = q.keys.shape
        return dict(
            table_ids=jnp.full(shape, TABLE_ID, jnp.int32),
            keys=q.keys,
            is_read=~q.is_write,
            is_write=q.is_write,
            valid=jnp.ones(shape, bool),
            # access owner under modulo striping (GET_NODE_ID,
            # system/global.h:294) — the VOTE protocol's participant map
            owner=q.keys % jnp.int32(max(self.n_parts, 1)),
        )

    # -- multi-chip execution (partition-parallel forwarding) ----------
    def execute_mc(self, db, batch, stats: dict):
        """Calvin-shaped multi-chip epoch: the batch is replicated (every
        chip sees the full deterministic sequence, like the reference
        sequencer's broadcast, `system/sequencer.cpp:283-326`) and each
        chip plans + executes ONLY its keyspace partition — reads gather
        and writes scatter against the local table shard, the read
        checksum reduces with one psum over ICI.

        SHARDED PLANNING (round-4, VERDICT missing #2 — the distributed
        (key, rank) sort over ICI): each chip takes a BALANCED N/D slice
        of the replicated flat lanes (input-partitioned, so zipf skew
        cannot overload a sorter), sorts it by owner (key % D, stable),
        extracts one fixed pair_cap-sized block per destination chip,
        and a single ``all_to_all`` over the mesh delivers every chip
        exactly the lanes it owns — at most factor * N/D of them.  The
        local (key, rank) plan sort, the segmented scans and the
        random-access table passes then all run at N/D scale instead of
        N: the whole epoch divides by ~D/factor rather than only its
        table-access half (the round-3 replicated-plan asymptote was
        ~2.8x).  Skew safety: a txn with a lane past its (slice, owner)
        block capacity DEFERS (the MoE capacity pattern with deferral
        instead of dropping) — computed HERE, shard-locally at O(N/D)
        against `ops.mc_plan_defer`'s replicated spec: each chip sorts
        only its own slice, reduces per-txn overflow bits, and one
        all_gather replicates the identical defer mask to every chip
        (and to the caller, who builds the epoch verdict from it).  A
        chip counts its slice's lanes per owner ONCE per use with
        compares (no `jnp.bincount`: a scatter-add on the chip) and
        RUNS that pass only where a real owner's count is over pair_cap
        — a lane is over iff its position in its owner's run is >=
        pair_cap, so elsewhere the mask is all False and the pass's two
        sorts are skipped by a per-shard ``lax.cond`` (no collective in
        either branch; ``mc_defer_pass_cnt`` counts the shard-epochs
        that ran it, where the server's stats carry it).  The block
        starts come from the same compare-and-sum of the survivors.  Set
        ``mc_plan_capacity=0`` for the round-3 replicated-plan mode
        (zero capacity factors, zero defers, full-batch sort per chip).

        Returns ``(db, defer_mask)``.  The table is in the owner-major
        stacked layout and lives SHARDED over the mesh, one block a chip:
        `_load_mc` builds each block on the chip that holds it
        (`storage.table.create_mc`; equal to ``to_mc_layout`` of the
        one-chip table, which no one chip could hold at the served
        size); each local block's last row is its trash.

        Scopes (metadata only, as `engine/epoch.make_epoch_body`'s):
        everything the mesh ADDS to an epoch — the slice cuts, the owner
        counts, the defer pass and the conditional around it, the owner
        sort, the block cuts, the three ``all_to_all``s, the
        ``all_gather`` of the defer bits and the ``psum``s — is
        `ep.exchange`; the per-shard plan and slot map are `ep.plan`,
        as on one chip; `ep.read` / `ep.write` are the shared executor's.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deneva_tpu.ops import forward_plan_flat, mc_pair_cap
        from deneva_tpu.parallel import AXIS, current_mesh

        d_parts = self.cfg.device_parts
        mesh = current_mesh()
        assert mesh is not None and mesh.size == d_parts, \
            f"execute_mc needs a use_mesh({d_parts}) context"
        tab: DeviceTable = db[TABLE]
        valid = batch.valid & batch.active[:, None]
        big = jnp.int32(jnp.iinfo(jnp.int32).max)
        b, a = batch.keys.shape
        pair_cap = mc_pair_cap(b, a, d_parts, self.cfg.mc_plan_capacity)
        bD = b // d_parts if pair_cap else b
        sl = bD * a

        def body(f0, keys, rank, ts, is_write, valid):
            me = jax.lax.axis_index(AXIS)
            if pair_cap:
                # my balanced slice of WHOLE txns (row-aligned, so the
                # per-txn defer bits reduce without leaving the shard)
                k2 = jax.lax.dynamic_slice_in_dim(keys, me * bD, bD)
                r2 = jax.lax.dynamic_slice_in_dim(rank, me * bD, bD)
                t2 = jax.lax.dynamic_slice_in_dim(ts, me * bD, bD)
                w2 = jax.lax.dynamic_slice_in_dim(is_write & valid,
                                                  me * bD, bD)
                v2 = jax.lax.dynamic_slice_in_dim(valid, me * bD, bD)
                # invalid lanes carry the big sentinel so the
                # post-exchange ownership mask can never admit them
                ks = jnp.where(v2, k2, big).reshape(-1)
                rs = jnp.broadcast_to(r2[:, None], (bD, a)).reshape(-1)
                tss = jnp.broadcast_to(t2[:, None], (bD, a)).reshape(-1)
                ws = w2.reshape(-1)
                vs = v2.reshape(-1)
                lane = jnp.arange(sl, dtype=jnp.int32)
                owner = jnp.where(vs, ks % d_parts, d_parts)
                # lanes of my slice per owner, by compare-and-sum
                # (`jnp.bincount` is a scatter-add on the chip: it was
                # half of this scope's device time)
                owners = jnp.arange(d_parts + 1, dtype=jnp.int32)

                def count(o):
                    return (o[:, None] == owners).sum(0, dtype=jnp.int32)

                def defer_pass(owner, tss):
                    # (O(N/D) analogue of ops.mc_plan_defer):
                    # age-priority positions per (slice, owner) block;
                    # overflow bits reduce per txn via the sort-by-txn
                    # reshape trick
                    so, _, stx = jax.lax.sort((owner, tss, lane // a),
                                              num_keys=2, is_stable=True)
                    head = jnp.concatenate([jnp.ones((1,), bool),
                                            so[1:] != so[:-1]])
                    start = jax.lax.cummax(jnp.where(head, lane, 0))
                    over = (lane - start >= pair_cap) & (so != d_parts)
                    _, sov = jax.lax.sort((stx, over), num_keys=1,
                                          is_stable=True)
                    return sov.reshape(bD, a).any(axis=1)

                # a lane is over iff its position in its owner's run is
                # >= pair_cap, so where every real owner's count fits
                # its block the pass's mask is all False and its two
                # sorts are not run: the predicate is this shard's own
                # (no collective in either branch; the zeros come from a
                # shard-varying operand, which `cond` wants of both
                # sides); one all_gather replicates the bits below
                fits = (count(owner)[:d_parts] <= pair_cap).all()
                dfr = jax.lax.cond(
                    fits, lambda owner, tss: jnp.zeros_like(t2, dtype=bool),
                    defer_pass, owner, tss)
                ran = jax.lax.psum((~fits).astype(jnp.uint32), AXIS)
                # each sender excludes ITS deferred txns' lanes before
                # cutting blocks, so no chip ever receives one — the
                # global mask is just the shards concatenated
                # (out_specs P(AXIS)); survivors always fit, their
                # positions only move earlier
                dfr_lane = jnp.broadcast_to(dfr[:, None],
                                            (bD, a)).reshape(-1)
                vs2 = vs & ~dfr_lane
                ks2 = jnp.where(vs2, ks, big)
                ws2 = ws & ~dfr_lane
                # stable (owner, ts) sort: each destination's lanes
                # become one contiguous run, OLDEST txns first (the
                # defer rule's age priority, starvation-free)
                owner2 = jnp.where(vs2, ks2 % d_parts, d_parts)
                _, _, ck, cr, cw = jax.lax.sort(
                    (owner2, tss, ks2, rs, ws2), num_keys=2,
                    is_stable=True)
                cnt = count(owner2)
                starts = jnp.cumsum(cnt) - cnt
                # fixed-size block per destination (dynamic start is
                # clamped near the tail — stray lanes are masked after
                # the exchange by the owner check)
                blk = [jnp.stack([jax.lax.dynamic_slice_in_dim(
                    x, starts[d], pair_cap) for d in range(d_parts)])
                    for x in (ck, cr, cw)]
                bk, br, bw = [jax.lax.all_to_all(
                    x, AXIS, split_axis=0, concat_axis=0) for x in blk]
                bk, br, bw = (bk.reshape(-1), br.reshape(-1),
                              bw.reshape(-1))
                mine = (bk % d_parts == me) & (bk != big)
                bk = jnp.where(mine, bk, big)
                bw = bw & mine
            else:
                dfr = jnp.zeros((b,), bool)
                ran = jnp.uint32(0)
            with jax.named_scope("ep.plan"):
                if pair_cap:
                    p = forward_plan_flat(bk, br, bw)
                else:
                    owned = valid & (keys % d_parts == me)
                    p = forward_plan(keys, rank, is_write, owned)
                # f0 here is one owner-major block (`create_mc`): its
                # last padded row is the block-local trash
                trash = jnp.int32(f0.shape[0] - 1)
                slots = jnp.where(p.keys != big, p.keys // d_parts, trash)
            # mono holds per shard: plan keys are sorted with non-owned
            # lanes already masked to the big sentinel, so slots ascend
            # toward the block-local trash at the top
            f0, cks, wcnt, lanes, rlanes, groups = _forward_execute_f0(
                f0, p, slots, trash, mono=True)
            return (f0, jax.lax.psum(cks, AXIS), jax.lax.psum(wcnt, AXIS),
                    jax.lax.psum(lanes, AXIS), jax.lax.psum(rlanes, AXIS),
                    jax.lax.psum(groups, AXIS), ran, dfr)

        with jax.named_scope("ep.exchange"):
            (f0, cks, wcnt, lanes, rlanes, groups, passes,
             dfr) = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(AXIS), P(), P(), P(), P(), P()),
                out_specs=(P(AXIS), P(), P(), P(), P(), P(), P(),
                           P(AXIS) if pair_cap else P()))(
                    tab.columns["F0"], batch.keys, batch.rank, batch.ts,
                    batch.is_write, valid)
            if pair_cap:
                # the all_gather: every chip (and the caller's verdict)
                # needs the whole mask; asked for here so that it
                # carries this scope
                dfr = jax.lax.with_sharding_constraint(
                    dfr, NamedSharding(mesh, P()))
            # (in the scope: the chip's compiler merges the psums
            # into one all-reduce that keeps no op_name; a trace reads
            # its scope from these consumers)
            _count(stats, read_checksum=cks, write_cnt=wcnt,
                   write_scatter_lanes=lanes, read_gather_lanes=rlanes)
            _count_row_groups(stats, groups)
            if "mc_defer_pass_cnt" in stats:
                # shard-epochs that RAN the defer pass (asked for by the
                # server of a mesh: `engine/step.init_device_stats`)
                _count(stats, mc_defer_pass_cnt=passes)
        db = dict(db)
        db[TABLE] = tab._replace(columns={**tab.columns, "F0": f0})
        return db, dfr

    def _local_slots(self, db, keys: jax.Array) -> jax.Array:
        """key -> local slot with ownership applied.  Static striping
        resolves ownership inside the index arithmetic (non-owned keys
        miss); elastic mode indexes the full keyspace and masks by the
        slot map carried in ``db`` instead."""
        slots = self.index.lookup(keys)
        if self.elastic:
            from deneva_tpu.runtime.membership import MEMBER_KEY
            from deneva_tpu.workloads.base import slot_map_owned
            owned = slot_map_owned(keys, db[MEMBER_KEY],
                                   self.cfg.node_id)
            slots = jnp.where(owned, slots, jnp.int32(self.n_local))
        return slots

    # -- repair re-execution (engine/repair.py, Config.repair) ---------
    def re_execute(self, db, q: YCSBQuery, mask: jax.Array,
                   order: jax.Array, stats: dict):
        """Pure re-execution closure, keyed by txn slot: the query
        pytree row IS the captured plan, so re-running a repaired txn is
        ``execute`` on the same row against CURRENT state.  Reads
        re-gather F0 — the masked re-read of the invalidated keys, bit
        for bit: every lane OUTSIDE the frontier re-reads a value no
        committed txn overwrote (the frontier is a bucket-space
        superset of true overwrites, cc/base.committed_write_frontier)
        — and blind writes recompute from ``(key, order)`` exactly as
        any wave's writes do.  One gather + one scatter per sub-round,
        same as the main wave."""
        return self.execute(db, q, mask, order, stats)

    # -- execution (ycsb_txn.cpp:177-209 collapsed to one batch) -------
    def execute(self, db, q: YCSBQuery, mask: jax.Array, order: jax.Array,
                stats: dict, fwd_rank=None, level_exec: bool = False):
        tab: DeviceTable = db[TABLE]
        if fwd_rank is not None:
            assert self.cfg.device_parts == 1, \
                "device_parts > 1 forwarding executes via execute_mc " \
                "under a mesh (the masked path runs through McTableView)"
            # single-pass forwarding executor, in the plan's sorted
            # coordinates (ops/forward.ForwardPlan): a read whose key has
            # an earlier in-batch writer takes that writer's value —
            # f(key, writer rank), computable without the writer having
            # executed (blind writes; RFWD as arithmetic) — and only the
            # final writer of each key touches the table.  Exactly one
            # gather and one scatter against table storage per epoch;
            # checksum and table state are order-independent, so no
            # unsort is needed.  The commit set is BAKED INTO the plan
            # (forward_verdict builds it from batch.valid & batch.active)
            # — a caller with a narrower per-txn mask must rebuild the
            # plan, so we demand mask=None rather than silently ignoring
            # a mask the plan does not reflect.
            assert mask is None, \
                "ForwardPlan embodies the commit set; pass mask=None"
            p = fwd_rank
            slots = self._local_slots(db, p.keys)              # [N]
            # mono: with one partition every valid key is owned, so the
            # slot map follows sorted-key order (DenseIndex identity /
            # SortedIndex rank) and misses steer to capacity at the top;
            # under part_cnt striping (or an elastic mask at n_parts>1)
            # non-owned keys hit miss_slot INTERLEAVED between owned
            # slots — not monotone
            f0, cks, wcnt, lanes, rlanes, groups = _forward_execute_f0(
                tab.columns["F0"], p, slots, tab.capacity,
                mono=self.n_parts == 1)
            _count(stats, read_checksum=cks, write_cnt=wcnt,
                   write_scatter_lanes=lanes, read_gather_lanes=rlanes)
            _count_row_groups(stats, groups)
            db = dict(db)
            db[TABLE] = tab._replace(columns={**tab.columns, "F0": f0})
            return db
        full = self.cfg.sim_full_row
        # the epoch's `ep.read` / `ep.write` scopes (metadata:
        # `engine/epoch.make_epoch_body`) live here, where the row
        # gather and the row scatter are
        with jax.named_scope("ep.read"):
            slots = self._local_slots(db, q.keys)                  # [n, R]
            act = mask[:, None] & jnp.ones_like(q.is_write)
            # reads: gather F0, fold into checksum (keeps the load alive);
            # through .gather so the multi-chip McTableView can interpose
            rmask = act & ~q.is_write
            rslots = jnp.where(rmask, slots, tab.capacity)
            vals = tab.gather(rslots, ("F0",))["F0"]
            ver: VersionRing | None = db.get(VER_TABLE)
            if ver is not None:
                # MVCC stale reads serve HISTORICAL bytes (row_mvcc.cpp:
                # 172-196), reconstructed from the version law f(key, v*)
                # (VersionRing.select_version).  Verdict.order is the
                # serialization ts, with read-only txns forced to 0 (they
                # serialize AT the epoch snapshot, so the live gather already
                # gave them the right version — exclude them by reading "at
                # +inf").  Safe because real txn ts are >= 1 by construction
                # — pool.next_seq starts at 1 and server._contribution raises
                # on a sub-1 stamp.
                big = jnp.int32(jnp.iinfo(jnp.int32).max)
                ver_ts = jnp.where(order > 0, order, big)
                # ONE row gather serves both the version select here and the
                # push below (VersionRing.rows: a lane's whole history is one
                # row).  Raw slots: write-lane rows are garbage for select
                # (masked by rmask downstream) and exactly what push needs.
                # `ep.version` (the innermost scope is an op's own): the
                # ring's gather, the select and the old bytes' law
                with jax.named_scope("ep.version"):
                    ver_rows = ver.rows(slots)
                    vstar, has = ver.version_from(
                        ver_rows,
                        jnp.broadcast_to(ver_ts[:, None], slots.shape))
                    if full:
                        vals = jnp.where(has[..., None],
                                         _field_bytes(q.keys, vstar,
                                                      self.cfg.tup_size),
                                         vals)
                    else:
                        vals = jnp.where(
                            has, _field_fingerprint(q.keys, vstar), vals)
                    if "mvcc_old_version_reads" in stats:
                        # reads served a version other than the live one
                        # (the served MVCC program counts them:
                        # `workloads/base.MVCC_COUNTERS`)
                        _count(stats, mvcc_old_version_reads=(
                            rmask & has).sum(dtype=jnp.uint32))
            rm = rmask[..., None] if full else rmask
            stats["read_checksum"] = stats["read_checksum"] + jnp.sum(
                jnp.where(rm, vals, 0), dtype=jnp.uint32)
        with jax.named_scope("ep.write"):
            # writes: new payload versioned by serialization order
            wmask = (act & q.is_write).reshape(-1)
            wslots = jnp.where(act & q.is_write, slots, tab.capacity).reshape(-1)
            worder = jnp.broadcast_to(order[:, None], slots.shape).reshape(-1)
            if level_exec:
                # caller guarantees the committed set is write-conflict-free
                # (chained sub-round): cross-txn duplicates cannot exist and
                # a txn's own duplicate lanes write identical values, so the
                # scatter-max tournament is redundant
                win = wmask
            else:
                win = last_writer(wslots, worder, wmask, tab.capacity)
            db = dict(db)
            if ver is not None:
                # record each winning overwrite's commit ts (one winner per
                # row per epoch, so each row advances at most one ring slot);
                # no value bytes — reads reconstruct via f(key, v*).  The
                # winners alone, compacted, as rows, in place behind the
                # ring's gather — as F0's below
                with jax.named_scope("ep.version"):
                    db[VER_TABLE] = ver.push_rows(
                        ver_rows.reshape(-1, ver.depth), wslots, worder,
                        win, stats)
            wkeys = q.keys.reshape(-1)
            if full:
                # the winners alone reach the row scatter, compacted, and
                # the trash row is never written; the gather above (all
                # of it is in the checksum) comes first (ops/scatter)
                db[TABLE], lanes, groups, stats["read_checksum"] = \
                    tab.scatter_winners(
                        "F0", wslots, win, (wkeys, worder),
                        lambda k, o: _field_bytes(k, o, self.cfg.tup_size),
                        after=stats["read_checksum"])
                _count_row_groups(stats, groups)
            else:
                db[TABLE] = tab.scatter(
                    wslots, {"F0": _field_fingerprint(wkeys, worder)},
                    mask=win)
                lanes = jnp.uint32(wslots.shape[0])
            # (the gather above was handed every lane)
            _count(stats, write_cnt=wmask.sum(dtype=jnp.uint32),
                   write_scatter_lanes=lanes,
                   read_gather_lanes=jnp.uint32(rslots.size))
        return db
