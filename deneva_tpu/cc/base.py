"""CC backend interface: batched epoch validation.

The reference's concurrency control is a per-row state machine reached via
`row_t::get_row` / `return_row` (`storage/row.cpp:197-310,351-420`), with a
`#if CC_ALG` branch per algorithm.  Here an algorithm is a *pure function
over one epoch*:

    validate(cfg, state, batch) -> (Verdict, state')

``batch`` carries the epoch's planned accesses (padded RW-sets), ``state``
is whatever survives across epochs (per-bucket timestamp tables for the
T/O family; most algorithms are stateless), and the ``Verdict`` partitions
the batch into commit / abort / defer plus a serialization order and an
execution wavefront level:

* ``order`` — total serialization order among committed txns; duplicate
  committed VALUE writes to one slot are resolved to the max-order writer
  (`deneva_tpu.ops.scatter.last_writer`), the batch analogue of the
  reference applying writes serially under latches.  Escrow (order_free)
  writes are DELTAS, not values: the executors accumulate them over ALL
  committed winners (`DeviceTable.scatter_add`), which is order-invariant
  — the multi-winner commit path that lets many escrow writers of one hot
  row commit in a single epoch.
* ``level`` — sub-round index for algorithms that *chain* intra-epoch
  read-after-write dataflow (Calvin, TPU_BATCH): level-l reads observe
  writes of levels < l.  Algorithms whose committed sets are
  RW-conflict-free always report level 0.
* ``defer`` — retry next epoch without an abort penalty: the batch
  analogue of parking a txn on a row's waiter list and resuming it via
  `txn_table.restart_txn` (`system/txn_table.cpp:151-176`) — the
  reference's subtlest machinery (SURVEY §7 hard-part #1) reduced to a
  mask.  Calvin's stale-reconnaissance restart
  (`system/sequencer.cpp:88-115`) is a defer too: `stale_recon`.

Verdict invariants (asserted in tests): commit/abort/defer are disjoint,
cover ``active``, and the committed set is serializable — for level-0
algorithms it is RW/WR/(RMW)WW-conflict-free under ``order`` over its
ORDERED accesses; for chained algorithms each level is conflict-free and
edges only point to lower levels.  Escrow (``order_free``) accesses are
exempt from the conflict-freedom claim by design: their writes are
commutative deltas whose accumulated sum is order-invariant, so
serializability holds modulo commutativity (oracle: accumulator sums vs
serial, `tests/test_escrow.py`).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax

from deneva_tpu.ops import (access_incidence, bucket_hash, combine_key,
                            earlier_edges, key_overlap)


@dataclass(frozen=True)
class Recon:
    """A workload's mark on its plan: the access columns whose READ rows
    a lane's other keys were derived from (PPS: the mapping rows of a
    walk, whose part keys fill the lane's remaining accesses), and the
    columns in which a WRITE of such a row can sit (None: any).  Static,
    so `stale_recon` compares those columns alone."""

    reads: tuple[int, ...]
    writes: tuple[int, ...] | None = None


@dataclass
class AccessBatch:
    """One epoch's planned accesses.  Pytree of static shape [B, A] / [B]."""

    table_ids: jax.Array   # int32[B, A]
    keys: jax.Array        # int32[B, A] primary keys (pre-index lookup)
    is_read: jax.Array     # bool[B, A]
    is_write: jax.Array    # bool[B, A]  (read & write = RMW)
    valid: jax.Array       # bool[B, A]
    ts: jax.Array          # int32[B] timestamp (T/O priority; WAIT_DIE age)
    rank: jax.Array        # int32[B] arrival/sequence rank (lock/queue order)
    active: jax.Array      # bool[B]
    # bool[B] | None: txn is GLOBALLY read-only.  None (default) = derive
    # from valid & is_write.  The distributed VOTE protocol masks valid
    # down to locally-owned accesses, which would make a cross-partition
    # rw-txn look read-only to a node owning only its reads and skip
    # read validation (MVCC's ro fast path) — the unmasked plan's mask
    # rides here so every node classifies identically.
    ro_hint: jax.Array | None = None
    # bool[B, A] | None: escrow/commutative accesses (workload
    # ``order_free`` declarations, PRE-GATED by ``gate_order_free`` —
    # None whenever the backend or config declines the exemption, so a
    # None here reproduces the pre-escrow semantics bit for bit).  The
    # T/O family consumes it directly for its cross-epoch watermark
    # rules; the incidence builder consumes it for the ordered views.
    order_free: jax.Array | None = None
    # the plan's reconnaissance mark (None: no key of the plan came out
    # of a row the batch can write, and nothing below looks)
    recon: Recon | None = None

    @property
    def shape(self) -> tuple[int, int]:
        return self.keys.shape


jax.tree_util.register_dataclass(
    AccessBatch,
    data_fields=["table_ids", "keys", "is_read", "is_write", "valid",
                 "ts", "rank", "active", "ro_hint", "order_free"],
    meta_fields=["recon"],
)


@dataclass
class Verdict:
    commit: jax.Array      # bool[B]
    abort: jax.Array       # bool[B]  -> backoff + restart (abort_queue analogue)
    defer: jax.Array       # bool[B]  -> retry next epoch, no penalty (waiter analogue)
    order: jax.Array       # int32[B] serialization order among committed
    level: jax.Array       # int32[B] execution sub-round (0 = snapshot reads)


jax.tree_util.register_dataclass(
    Verdict, data_fields=["commit", "abort", "defer", "order", "level"],
    meta_fields=[])


@dataclass
class Incidence:
    """One epoch's accesses as the conflict readers want them.

    In ACCESS space, for the pairwise conflict matrices (`overlap`): the
    combined identity of every padded slot, the read and write slots of
    the active txns, and the slots that need ordering.  In BUCKET space,
    bfloat16[B, K] counts for the readers that want per-bucket column
    sums and never pairs (`committed_write_frontier`, `conflict_density`,
    `audit_mutate_verdict`, `cc.router.cross_group_defer`): the write
    and the union incidence of hash family 0, and the write incidence of
    family 1 where ``Config.conflict_exact`` asks for it (None
    otherwise).  XLA drops the scatter-add of an incidence nobody reads,
    so a program with those gates off builds none of them.
    """

    ident: jax.Array       # uint32[B, A]
    rmask: jax.Array       # bool[B, A]
    wmask: jax.Array       # bool[B, A]
    # accesses NOT marked order_free (None: all of them).  The backends
    # that honor the escrow exemption draw their edges from the ORDERED
    # views — overlap("uo", "w"): a pair conflicts iff it overlaps AND
    # at least one side needs ordering, so escrow add-add pairs carry no
    # edge while reads of the same accumulators still order against
    # every write; T/O reader-wait edges from ("ro", "w"), the relaxed-
    # isolation WW lock edges from ("wo", "w"), READ_COMMITTED's
    # residual read locks from ("pro", "w").
    ordered: jax.Array | None
    w1: jax.Array
    u1: jax.Array
    w2: jax.Array | None
    # per-access bucket ids in family 0 (for ts-table gathers/scatters)
    bucket1: jax.Array     # int32[B, A]

    def mask(self, view: str) -> jax.Array:
        """bool[B, A]: the access slots of a named view — ``r`` reads,
        ``w`` writes, ``u`` their union, ``pr`` pure reads (RMW reads
        are ``r & ~pr``); with a trailing ``o`` the ordered ones."""
        r, w = self.rmask, self.wmask
        m = {"r": r, "w": w, "u": r | w, "pr": r & ~w}[view.removesuffix("o")]
        if view.endswith("o") and self.ordered is not None:
            m = m & self.ordered
        return m

    def overlap(self, a: str, b: str) -> jax.Array:
        """bool[B, B]: txn i's ``a`` view holds a key of txn j's ``b``
        view — the one place every sweep backend draws its conflict
        matrix from, a pairwise compare of the exact combined keys
        (`ops.conflict.key_overlap`)."""
        return key_overlap(self.ident, self.mask(a), self.mask(b))


def stale_recon(batch: AccessBatch) -> jax.Array:
    """bool[B]: the active lanes whose reconnaissance is STALE in this
    epoch — an earlier-serialised active lane of the epoch writes a row
    that the lane's other keys were derived from (`Recon`).

    The plan resolved those keys against the epoch's snapshot.  A backend
    that executes the reader after the writer inside the epoch (chained
    levels: CALVIN, TPU_BATCH, DGCC) would run it on keys the writer has
    made obsolete, so `engine/epoch.epoch_core` — the one place every
    verdict passes — takes such a lane out of the batch BEFORE
    validation: it defers whole (no abort), contributes no edge to the
    epoch's levels, keeps its rank, and is planned again from the
    snapshot of the epoch that readmits it, where that writer's row is
    as written.  This is Calvin's restart of a transaction whose
    reconnaissance an UpdateProductPart overtook
    (`system/sequencer.cpp:88-115`), as a deterministic rule of the
    batch alone.  A backend that executes every committed lane on the
    snapshot (the sweeps) already loses such a reader to its own
    U-vs-W test and is left alone.  A writer that itself waits this
    epoch still counts: the rule reads the admitted batch, not the
    verdict, so a reference can restate it from the command log."""
    r = batch.recon
    ident = combine_key(batch.table_ids, batch.keys)
    hit = key_overlap(ident, batch.valid & batch.is_read,
                      batch.valid & batch.is_write, r.reads, r.writes)
    return earlier_edges(hit, batch.rank, batch.active).any(axis=1)


def gate_order_free(cfg, be, order_free: jax.Array | None
                    ) -> jax.Array | None:
    """The ONE escrow gate: returns the workload's ``order_free`` mask iff
    this backend may consume it, else None (pre-escrow semantics, bit for
    bit).  Chained/deterministic backends gate on ``escrow_order_free``
    alone (their exemption shipped rounds ago); the sweep backends
    additionally require ``escrow_sweep`` so the reference-faithful
    baseline (per-row conflicts, the TPC-C hot-row floor) stays one flag
    away."""
    if order_free is None or not be.exempt_order_free \
            or not cfg.escrow_order_free:
        return None
    if not be.chained and not cfg.escrow_sweep:
        return None
    return order_free


def build_conflict_incidence(cfg, be, batch: AccessBatch,
                             order_free: jax.Array | None):
    """`build_incidence` honoring the backend's ``order_free`` exemption
    (escrow/commutative accesses order only against ordered accesses,
    never against each other).  Shared by the single-node engine and the
    distributed server step so their conflict semantics cannot diverge."""
    if not be.needs_incidence:
        return None
    order_free = gate_order_free(cfg, be, order_free)
    return build_incidence(batch, cfg.conflict_buckets, cfg.conflict_exact,
                           order_free=order_free)


def committed_write_frontier(cfg, batch: AccessBatch, inc: Incidence,
                             committed, losers):
    """Invalidated-read frontier: bool[B, A] marking each LOSER's ordered
    read lanes whose bucket some txn in ``committed`` wrote — the reads
    that observed a value the winners overwrote, i.e. exactly the slice
    transaction repair must re-execute (PAPERS: *Transaction Repair*;
    the conflict incidence the sweep already materialized answers it
    with one [B]x[B,K] matvec per hash family).

    Bucket-space over-approximation, stated the same way as every sweep
    input: a collision can only ADD frontier lanes, never hide one — and
    an added lane is harmless because a re-read of a key nobody
    overwrote returns the identical value (which is also why the
    executors' full re-gather IS the masked re-read, bit for bit).
    Escrow (``order_free``) reads are excluded: they are declared-
    immutable columns, so repair of an escrow access is a no-op by
    contract (cc/timestamp.py escrow rules; documented in README)."""
    import jax.numpy as jnp

    wrote = jnp.matmul(committed.astype(inc.w1.dtype)[None, :], inc.w1,
                       preferred_element_type=jnp.float32)[0] > 0
    hit = jnp.take(wrote, inc.bucket1)
    if inc.w2 is not None:
        ident = combine_key(batch.table_ids, batch.keys)
        b2 = bucket_hash(ident, inc.w2.shape[1], family=1)
        wrote2 = jnp.matmul(committed.astype(inc.w2.dtype)[None, :],
                            inc.w2, preferred_element_type=jnp.float32
                            )[0] > 0
        hit = hit & jnp.take(wrote2, b2)
    rmask = batch.valid & losers[:, None] & batch.is_read
    if batch.order_free is not None:
        rmask = rmask & ~batch.order_free
    return rmask & hit


def conflict_density(cfg, batch: AccessBatch, owner,
                     inc: Incidence | None = None):
    """Per-partition observed-conflict density: int32[P] counting this
    epoch's access lanes that CONTEND — their bucket is written by some
    other txn, or they write a bucket some other txn touches — folded
    by the owning partition (the plan's ``owner`` map, the same
    ``key % part_cnt`` striping the VOTE protocol routes on).

    This is the metrics bus's per-epoch contention signal
    (runtime/metricsbus.py) and the input the contention-adaptive CC
    router item needs (PAPERS: *DGCC* builds its whole protocol on the
    dependency-graph signal; *Timestamp Granularity in OCC* argues the
    protocol/granularity choice should follow observed contention).
    When the sweep already materialized an ``Incidence`` the per-bucket
    counts are two column sums over it — effectively free; forwarding
    backends (no incidence) pay two bucket scatter-adds instead.  Like
    every sweep input it is a bucket-space over-approximation: a hash
    collision can only ADD density, never hide it."""
    import jax.numpy as jnp

    p = max(cfg.part_cnt, 1)
    v = batch.valid & batch.active[:, None]
    w = v & batch.is_write
    if inc is not None:
        bucket = inc.bucket1
        # column sums over the already-materialized incidence: one
        # reduction each, no new [B, K] buffer
        wcol = jnp.sum(inc.w1, axis=0, dtype=jnp.float32)
        ucol = jnp.sum(inc.u1, axis=0, dtype=jnp.float32)
    else:
        # forwarding backends carry no incidence: per-bucket counts via
        # two flat scatter-adds (O(B*A) lanes into [K]; never a [B, K]
        # materialization — measured 22% tput off the armed CALVIN pair
        # when a first cut built full incidence here)
        k = cfg.conflict_buckets
        ident = combine_key(batch.table_ids, batch.keys)
        bucket = bucket_hash(ident, k, family=0)
        cols = jnp.where(v, bucket, 0).ravel()
        wcol = jnp.zeros(k, jnp.float32).at[cols].add(
            w.ravel().astype(jnp.float32))
        ucol = jnp.zeros(k, jnp.float32).at[cols].add(
            v.ravel().astype(jnp.float32))
    # per access: how many of its bucket's touches are SOMEONE ELSE'S —
    # the txn's own same-bucket lanes subtract out (pairwise compare
    # within the row, O(B*A^2) with the small padded A), so a txn
    # revisiting its own bucket never reads as contention
    same = bucket[:, :, None] == bucket[:, None, :]
    own_w = jnp.sum(same & w[:, None, :], axis=-1).astype(jnp.float32)
    own_u = jnp.sum(same & v[:, None, :], axis=-1).astype(jnp.float32)
    w_oth = jnp.take(wcol, bucket) - own_w
    u_oth = jnp.take(ucol, bucket) - own_u
    # a lane contends iff some OTHER txn wrote its bucket, or it writes
    # and some OTHER txn touched it (0.5 threshold absorbs bf16 noise)
    conf = v & ((w_oth > 0.5) | (w & (u_oth > 0.5)))
    onehot = (owner[:, :, None] == jnp.arange(p, dtype=jnp.int32)) \
        & conf[:, :, None]
    return onehot.sum(axis=(0, 1), dtype=jnp.int32)


# ---- isolation audit plane: on-device dependency observations ----------
# (Config.audit; the export half lives in runtime/audit.py, the graph/
# certifier half in harness/auditgraph.py.)

AUDIT_KEY = "__audit__"     # db dict key of the audit stamp tables
#                             (control plane like __membership__:
#                             excluded from logger.state_digest)

# exported edge kinds (packed as kind<<28 | src<<14 | dst over
# merged-batch ranks; decode in runtime/audit.py)
AUDIT_WW, AUDIT_WR, AUDIT_RW = 0, 1, 2


def audit_init(cfg):
    """Fresh audit state: per-bucket version-stamp tables (the audit
    twin of the `storage.table.VersionRing` — last committed writer's
    epoch + merged rank per hashed bucket; -1 = never written).  Lives
    in ``db[AUDIT_KEY]`` so every db-construction path (engine init,
    server boot, log replay, follower boot) threads it identically and
    checkpointing carries it (engine/checkpoint schema v8).

    Under MVCC the state additionally carries per-bucket version-
    boundary RINGS (depth ``mvcc_his_len``, mirroring the backend's own
    in-ring retention): the last H committed writers' boundary
    timestamps plus their (epoch, writer) stamps, so a read's observed
    version can be SELECTED BY ITS TIMESTAMP
    (`cc.depgraph.version_select`) instead of assumed to be the last
    writer — the audit plane's MVCC headroom item.  Gated on the
    algorithm so every non-MVCC artifact (checkpoint schema v8,
    sidecars, replay digests) keeps its exact pre-existing shape;
    MVCC+audit was a `config.validate` error before the rings existed,
    so no prior artifact carries the extended shape."""
    import jax.numpy as jnp

    from deneva_tpu.config import CCAlg

    k = cfg.audit_buckets
    aud = {"epoch": jnp.full((k,), -1, jnp.int32),
           "writer": jnp.full((k,), -1, jnp.int32)}
    if cfg.cc_alg == CCAlg.MVCC:
        h = max(1, cfg.mvcc_his_len)
        aud.update(
            vts=jnp.full((k, h), -1, jnp.int32),
            vepoch=jnp.full((k, h), -1, jnp.int32),
            vwriter=jnp.full((k, h), -1, jnp.int32),
            vpos=jnp.zeros((k,), jnp.int32))
    return aud


def audit_observe(cfg, batch: AccessBatch, committed, order, lvl,
                  order_vis: bool, stamps, epoch, cadence=None):
    """Per-epoch committed-txn dependency observations, derived ON
    DEVICE from the planned access sets under the backend's visibility
    rule — the isolation audit plane's measurement half.  Epochs off
    the ``audit_cadence`` grid skip the whole derivation via
    ``lax.cond`` (every node skips the same epochs, so the sidecar
    streams stay consensus-comparable; the overhead gate pins the
    default cadence, chaos scenarios pin cadence=1 for full-coverage
    certification).

    Model: the executors are mechanical (applies by serialization
    order/level, reads at their visibility point), so the data flow a
    committed set ACTUALLY produced is determined by (committed, order,
    lvl) plus the access sets — and any committed conflicting pair the
    backend's edge derivation wrongly admitted shows up here as
    dependency edges the claimed serial order cannot explain (the
    harness's cycle check).  Visibility per backend class:

    * ``order_vis=True`` (forwarding executor): a read observes the
      latest committed writer of its key with strictly LOWER
      serialization order (`ops.forward` serial-in-rank semantics).
    * ``order_vis=False``: a read observes the latest committed writer
      with strictly lower ``lvl`` (chained levels / repair salvage
      rounds); with every txn at lvl 0 this is the level-0 sweep rule —
      reads observe the epoch-start snapshot only.

    Edges emitted over EXACT combined keys (`ops.combine_key` — no
    bucket-collision false edges): wr (observed writer -> reader), rw
    (reader -> first writer past its observed version), ww (version
    chain).  Escrow (``order_free``) lanes are excluded: commutative
    deltas carry no ordering claim (same exemption as
    `committed_write_frontier`).  Self-edges are dropped (a txn's own
    RMW dataflow is program order, and its ww edge covers the chain).

    Honest level-0 sweep epochs emit ZERO edges (their committed sets
    are conflict-free by the Verdict invariant), so the export is
    empty exactly when the backend kept its claim.

    Returns ``(aud', edges, ebkt, cnt, dropped, vdig, rdig)``:
    updated stamp state, int32[audit_edges_max] packed edges (-1 pad)
    with their audit-bucket forensics column, the total edge-lane count
    (pre-cap, pre-dedup), the overflow count, and two uint32 digests —
    the post-epoch stamp tables (``vdig``) and this epoch's epoch-start
    read observations (``rdig``) — which every node of a merged cluster
    must reproduce bit-identically (harness/auditgraph.py's split-brain
    cross-check)."""
    import jax.numpy as jnp

    if cadence is None:
        # static cadence from config (the pre-ctrl path, bit-exact)
        cad_static = max(1, cfg.audit_cadence)
        if cad_static == 1:
            return _audit_observe_impl(cfg, batch, committed, order, lvl,
                                       order_vis, stamps, epoch)
        due = jnp.asarray(epoch, jnp.int32) % cad_static == 0
    else:
        # traced cadence (the ctrl plane's audit-density knob): the
        # due predicate is data, so the lax.cond is always compiled —
        # value cadence==1 makes every epoch due, same observations as
        # the direct call above
        cad = jnp.maximum(jnp.asarray(cadence, jnp.int32), 1)
        due = jnp.asarray(epoch, jnp.int32) % cad == 0
    e_max = cfg.audit_edges_max

    def live(_):
        return _audit_observe_impl(cfg, batch, committed, order, lvl,
                                   order_vis, stamps, epoch)

    def skip(_):
        z = jnp.zeros((), jnp.int32)
        return (stamps, jnp.full((e_max,), -1, jnp.int32),
                jnp.full((e_max,), -1, jnp.int32), z, z,
                jnp.zeros((), jnp.uint32), jnp.zeros((), jnp.uint32))

    return jax.lax.cond(due, live, skip, None)


def _audit_observe_impl(cfg, batch: AccessBatch, committed, order, lvl,
                        order_vis: bool, stamps, epoch):
    import jax.numpy as jnp

    from deneva_tpu.cc import depgraph

    b, a = batch.shape
    cm = batch.valid & committed[:, None]
    if batch.order_free is not None:
        cm = cm & ~batch.order_free
    rm = cm & batch.is_read
    wm = cm & batch.is_write
    ident = combine_key(batch.table_ids, batch.keys)
    big = jnp.uint32(0xFFFFFFFF)

    # dense serialization positions: opos ranks `order` over committed
    # txns (stable iota tiebreak), banded by lvl so writer positions
    # order lexicographically by (lvl, order) and reader visibility
    # points sit below their band (order_vis) or at its floor (level
    # visibility).  Doubling keeps read and write positions disjoint.
    okey = jnp.where(committed, order, jnp.int32(2**31 - 1))
    perm = jnp.argsort(okey, stable=True)
    opos = jnp.zeros((b,), jnp.int32).at[perm].set(
        jnp.arange(b, dtype=jnp.int32))
    band = lvl * jnp.int32(b + 2)
    wpos = (band + 1 + opos) * 2 + 1
    rpos = (band + 1 + opos) * 2 if order_vis else band * 2

    # flat double-lane view: each access contributes a read lane and/or
    # a write lane (an RMW access is both), sorted by (key, position).
    # Lean operand count: write-ness is the position's PARITY (wpos odd,
    # rpos even) and the audit bucket rehashes from the sorted ident,
    # so only the txn id rides as payload — CPU XLA's comparator sort
    # charges per operand (measured ~35% of the armed cost back)
    n = b * a
    tid = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None],
                           (b, a))
    keys2 = jnp.concatenate([jnp.where(rm, ident, big).reshape(-1),
                             jnp.where(wm, ident, big).reshape(-1)])
    pos2 = jnp.concatenate([
        jnp.broadcast_to(rpos[:, None], (b, a)).reshape(-1),
        jnp.broadcast_to(wpos[:, None], (b, a)).reshape(-1)])
    tid2 = jnp.concatenate([tid.reshape(-1), tid.reshape(-1)])
    sk, sp, sid = depgraph.lane_sort(keys2, pos2, tid2)
    sw = (sp & 1) == 1
    sbk = bucket_hash(sk, cfg.audit_buckets, family=0)
    live = sk != big
    head, tail = depgraph.segment_bounds(sk)
    cand = jnp.where(sw & live, sid, jnp.int32(-1))
    # nearest preceding / following writer within the key segment (sort
    # order IS position order; write positions are unique per txn and
    # never tie a read position, so "preceding" is "strictly lower pos")
    prev = depgraph.prev_writer(head, cand)
    nxt = depgraph.next_writer(tail, cand)

    # per sorted lane: a read's preceding writer is its wr source, its
    # following writer the rw target (next version past the observed);
    # a write's preceding writer is its ww predecessor
    f_prev = live & (prev >= 0) & (prev != sid)
    e_prev = jnp.where(
        f_prev,
        depgraph.pack_edge(jnp.where(sw, AUDIT_WW, AUDIT_WR), prev, sid),
        jnp.int32(-1))
    f_next = live & ~sw & (nxt >= 0) & (nxt != sid)
    e_next = jnp.where(f_next, depgraph.pack_edge(AUDIT_RW, sid, nxt),
                       jnp.int32(-1))
    flags = jnp.concatenate([f_prev, f_next])
    allp = jnp.concatenate([e_prev, e_next])
    allb = jnp.concatenate([sbk, sbk])
    (edges, ebkt), cnt, dropped = depgraph.compact_lanes(
        flags, (allp, allb), cfg.audit_edges_max)

    # epoch-start read observations (reads with no in-epoch visible
    # writer) gather the PRE-update stamps: their digest is the
    # cross-epoch fingerprint every node must reproduce.  With MVCC's
    # version-boundary rings present, the observed stamp is instead
    # SELECTED BY THE READER'S TIMESTAMP from the bucket ring — a read
    # at ts t observes the newest retained version bounded by t, which
    # may be older than the last writer (`depgraph.version_select`).
    m1, m2, m3, m4 = (jnp.uint32(0x9E3779B9), jnp.uint32(0x85EBCA6B),
                      jnp.uint32(0xC2B2AE35), jnp.uint32(0x27D4EB2F))
    obs = live & ~sw & (prev < 0)
    if "vts" in stamps:
        sts = jnp.take(batch.ts, sid)
        ring = lambda f: jnp.take(stamps[f], sbk, axis=0)  # noqa: E731
        sel = depgraph.version_select(ring("vts"), sts)
        pick = lambda f: jnp.take_along_axis(  # noqa: E731
            ring(f), jnp.maximum(sel, 0)[:, None], axis=-1)[:, 0]
        oe = jnp.where(sel >= 0, pick("vepoch"), jnp.int32(-1))
        ow = jnp.where(sel >= 0, pick("vwriter"), jnp.int32(-1))
    else:
        oe = jnp.take(stamps["epoch"], sbk)
        ow = jnp.take(stamps["writer"], sbk)
    mix = ((sid.astype(jnp.uint32) * m1) ^ (sbk.astype(jnp.uint32) * m2)
           ^ (oe.astype(jnp.uint32) * m3) ^ (ow.astype(jnp.uint32) * m4))
    rdig = jnp.where(obs, mix, jnp.uint32(0)).sum(dtype=jnp.uint32)

    # advance the stamp tables: last committed writer per audit bucket
    # by (lvl, order) position — argmax via two scatter-max passes
    k = cfg.audit_buckets
    wl_mask = live & sw
    sbk_safe = jnp.where(wl_mask, sbk, 0)
    top = jnp.zeros((k,), jnp.int32).at[sbk_safe].max(
        jnp.where(wl_mask, sp + 1, 0))
    upd = top > 0
    match = wl_mask & (sp + 1 == jnp.take(top, sbk))
    wid = jnp.zeros((k,), jnp.int32).at[jnp.where(match, sbk, 0)].max(
        jnp.where(match, sid + 1, 0))
    new_e = jnp.where(upd, jnp.asarray(epoch, jnp.int32), stamps["epoch"])
    new_w = jnp.where(upd, wid - 1, stamps["writer"])
    vdig = ((new_e.astype(jnp.uint32) * m1)
            ^ (new_w.astype(jnp.uint32) * m2)).sum(dtype=jnp.uint32)
    nstamps = {"epoch": new_e, "writer": new_w}
    if "vts" in stamps:
        # push this epoch's final writer per updated bucket into the
        # version-boundary ring: boundary ts = the winning writer's own
        # timestamp (MVCC stamps versions with the writer's ts)
        hlen = stamps["vts"].shape[1]
        slot = stamps["vpos"] % hlen
        rows = jnp.arange(k, dtype=jnp.int32)
        wts = jnp.take(batch.ts, jnp.maximum(wid - 1, 0))

        def push(ring_arr, val):
            cur = ring_arr[rows, slot]
            return ring_arr.at[rows, slot].set(jnp.where(upd, val, cur))

        nstamps.update(
            vts=push(stamps["vts"], wts),
            vepoch=push(stamps["vepoch"], jnp.asarray(epoch, jnp.int32)),
            vwriter=push(stamps["vwriter"], wid - 1),
            vpos=stamps["vpos"] + upd.astype(jnp.int32))
    return (nstamps, edges, ebkt, cnt, dropped, vdig, rdig)


def audit_mutate_verdict(cfg, batch: AccessBatch, inc: Incidence,
                         verdict, epoch):
    """Seeded edge-derivation fault (``Config.audit_mutate``, the
    certifier's anti-inert knob): emulate dropping OCC's read-set-vs-
    winner-write-set check on the chosen epoch window.  A Kung-Robinson
    loser whose WRITE lanes miss every winner-written bucket was
    aborted purely for its stale reads — with the check gone it commits
    (and executes, and acks), a real isolation violation: reciprocal
    read/write overlaps among the flipped losers and the winners form
    rw cycles (write skew) that harness/auditgraph.py must reject with
    a witness naming an epoch in the window."""
    import dataclasses

    import jax.numpy as jnp

    _, start, count = cfg.audit_mutate_spec()
    committed = verdict.commit & batch.active
    wrote = jnp.matmul(committed.astype(inc.w1.dtype)[None, :], inc.w1,
                       preferred_element_type=jnp.float32)[0] > 0
    hit = jnp.take(wrote, inc.bucket1)
    if inc.w2 is not None:
        ident = combine_key(batch.table_ids, batch.keys)
        b2 = bucket_hash(ident, inc.w2.shape[1], family=1)
        wrote2 = jnp.matmul(committed.astype(inc.w2.dtype)[None, :],
                            inc.w2, preferred_element_type=jnp.float32
                            )[0] > 0
        hit = hit & jnp.take(wrote2, b2)
    wmask = batch.valid & batch.is_write
    if batch.order_free is not None:
        wmask = wmask & ~batch.order_free
    dirty_writes = (wmask & hit).any(axis=1)
    e = jnp.asarray(epoch, jnp.int32)
    in_window = (e >= start) & (e < start + count)
    flip = verdict.abort & batch.active & ~dirty_writes & in_window
    return dataclasses.replace(
        verdict, commit=verdict.commit | flip,
        abort=verdict.abort & ~flip)


def build_incidence(batch: AccessBatch, n_buckets: int, exact: bool,
                    order_free: jax.Array | None = None) -> Incidence:
    # `shard_buckets` is a no-op single-device; under a parallel.use_mesh
    # context it shards the bucket dim so the readers' matvecs contract
    # over partitions and XLA inserts the cross-device reduction.
    from deneva_tpu.parallel.mesh import shard_buckets
    ident = combine_key(batch.table_ids, batch.keys)
    v = batch.valid & batch.active[:, None]
    rmask = v & batch.is_read
    wmask = v & batch.is_write
    b1 = bucket_hash(ident, n_buckets, family=0)

    def inc(b, m):
        return shard_buckets(access_incidence(b, m, n_buckets))

    return Incidence(
        ident=ident, rmask=rmask, wmask=wmask,
        ordered=None if order_free is None else ~order_free,
        w1=inc(b1, wmask), u1=inc(b1, rmask | wmask),
        w2=(inc(bucket_hash(ident, n_buckets, family=1), wmask)
            if exact else None),
        bucket1=b1)
