"""Sort-based in-batch read forwarding — Calvin's RFWD as one segmented scan.

The reference forwards dirty reads between Calvin participants with RFWD
messages (`system/txn.cpp:957-974`): a reader parked on a row waits for
the earlier-sequenced writer's value to arrive.  The chained-subround
executor reproduces that by executing conflict-wavefront levels against
table state — but its level budget caps the commit rate at (levels/epoch)
per hot key, which collapses under zipf-0.9 contention.

``ForwardPlan`` removes the level budget for **blind-write** workloads
(every write's value is independent of what the txn read — YCSB exactly,
`ycsb_txn.cpp:177-209` overwrites a field): when write values are a pure
function of (key, writer order), a reader does not need the writer to
have *executed* — it needs only the writer's identity.  One lexicographic
sort of the epoch's accesses by (key, rank) and segmented scans give
every read the rank of the latest earlier writer of its key AND every
write whether it is the final writer of its key.  Reads with an in-batch
predecessor take the forwarded value (recomputed from (key, rank)); the
rest read the epoch-start snapshot; only final writers touch the table.
Execution equals serial execution in rank order, so the whole batch
commits in ONE pass: no conflict matrix, no levels, no aborts.

The plan stays in **sorted coordinates**: executors (`ycsb.execute`)
gather/scatter the table through the sorted arrays directly, because on
TPU the expensive resource is random-access passes while sorts and scans
are cheap (a 3-operand sort of 163,840 lanes: 0.19 ms on v5e; a 100 B
row gathered costs 6 to 10 ns a lane, whatever the row; a row written
one by one 71 ns a lane, or the whole 6M-row column passed over in 4 ms
whatever the lanes — PERF.md sections 5 and 6).  Keeping sorted
coordinates deletes the unsort scatter and the whole `last_writer`
scatter-max tournament from the hot path; of an epoch's lanes only the
final writers (``win``) and the reads that nothing forwards to
(``is_read & (fwd < 0)``) touch the table, each kind compacted first
(`ops.scatter.scatter_winner_rows`, `ops.gather.checksum_needed_rows`).

Contract: ``rank`` must be unique per txn and >= 0; accesses must be
read-xor-write (an RMW access would be handed its own rank).  Collisions
are exact — real keys, not hash buckets.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


def forwarding_applies(backend, workload) -> bool:
    """Eligibility: backend opts in AND every write in the workload is
    blind (value independent of the txn's reads)."""
    return bool(getattr(backend, "forward", False)
                and getattr(workload, "blind_writes", False))


@dataclass
class ForwardPlan:
    """Flat [B*A] epoch access plan in (key, rank)-sorted order.

    keys     — access keys; invalid/inactive lanes hold INT32_MAX and
               sort to the tail (index lookups send them to the trash
               slot, so executors need no special casing).
    rank     — owning txn's serialization rank.
    is_read / is_write — valid & active read/write lanes.
    fwd      — rank of the latest STRICTLY-earlier in-batch writer of
               this key, or -1 (read the epoch-start snapshot).  A txn
               never sees its own writes (serial semantics: reads
               execute before writes), including duplicate write lanes.
    win      — this lane is the final (max-rank) writer of its key: the
               only lane that must reach the table.
    perm     — flat index into the original [B, A] layout (row-major),
               for callers that need unsorted coordinates; None unless
               requested (the hot path never unsorts, so it skips
               carrying the extra sort payload).
    """

    keys: jax.Array      # int32[N]
    rank: jax.Array      # int32[N]
    is_read: jax.Array   # bool[N]
    is_write: jax.Array  # bool[N]
    fwd: jax.Array       # int32[N]
    win: jax.Array       # bool[N]
    perm: jax.Array | None  # int32[N] | None


jax.tree_util.register_dataclass(
    ForwardPlan,
    data_fields=["keys", "rank", "is_read", "is_write", "fwd", "win",
                 "perm"],
    meta_fields=[])


def mc_pair_cap(b: int, a: int, d_parts: int, factor: float) -> int:
    """Static per-(source slice, owner) lane capacity for the sharded
    multi-chip plan's all_to_all exchange: ``factor`` x the even share
    N/D^2, rounded up to the 128-lane tile.  Returns 0 when sharded
    planning is off (factor <= 0, one chip, or txn-unaligned slices —
    slices hold whole txns so per-txn defer bits reduce shard-locally)
    — callers fall back to the replicated full-batch plan.

    The floor of one 128-lane tile also guarantees a single txn's lanes
    (<= max_accesses <= 128, checked in Config.validate) always fit one
    block, so the age-priority liveness argument holds: the oldest txn
    of a block can never overflow on its own lanes."""
    if factor <= 0 or d_parts <= 1 or b % d_parts:
        return 0
    import math
    sl = (b // d_parts) * a
    cap = (math.ceil(factor * sl / d_parts) + 127) // 128 * 128
    cap = max(cap, 128)
    return 0 if cap >= sl else cap


def mc_plan_defer(keys: jax.Array, ts: jax.Array, valid: jax.Array,
                  d_parts: int, pair_cap: int) -> jax.Array:
    """bool[B]: txns with a lane past the per-(slice, owner) capacity.

    REFERENCE implementation of the capacity rule (replicated, O(N log
    N)) — the production path computes the identical rule shard-locally
    inside `ycsb.execute_mc` (each chip sorts only its N/D slice and an
    all_gather shares the per-txn bits), keeping every per-epoch term
    O(N/D), and runs that pass only on a shard whose slice holds more
    than pair_cap lanes of one owner: elsewhere this mask is all False
    over the slice's txns, which the shard knows from its owner counts
    before any sort (`tests/test_exchange_counts.py` holds both sides
    to this function).  This form is kept as the executable spec and
    for the unit tests.

    The sharded plan gives source chip s a balanced N/D input slice and
    routes lanes to their owner (key % D) in fixed pair_cap-sized
    all_to_all blocks, so a skewed epoch can overflow a (slice, owner)
    block.  Overflowing txns DEFER — deterministically, computed from
    the replicated batch so every chip excludes the identical set (no
    drops, no ragged routing; the MoE token-capacity pattern with
    deferral instead of dropping).

    Block priority is txn AGE (birth ts, smallest first), NOT slot
    order: a deferred txn keeps its ts while every new arrival stamps
    higher, so a txn that overflowed strictly rises in priority each
    epoch until it is kept — starvation-free even in full-pool mode,
    where deferred txns sit in fixed slots and slot-order priority
    would let fresh hot-key arrivals in earlier slots starve them
    forever.  The executor's per-slice (owner, ts) stable sort
    (`ycsb.execute_mc`) keeps exactly the same lanes: removing deferred
    txns only moves surviving lanes earlier, so every survivor fits.
    """
    b, a = keys.shape
    n = b * a
    sl = n // d_parts
    lane = jnp.arange(n, dtype=jnp.int32)
    vf = valid.reshape(-1)
    owner = jnp.where(vf, keys.reshape(-1) % d_parts, d_parts)
    seg = (lane // sl) * (d_parts + 1) + owner
    tsl = jnp.broadcast_to(ts[:, None], (b, a)).reshape(-1)
    txn = lane // a
    sseg, _, stxn = jax.lax.sort((seg, tsl, txn), num_keys=2,
                                 is_stable=True)
    head = jnp.concatenate([jnp.ones((1,), bool), sseg[1:] != sseg[:-1]])
    start = jax.lax.cummax(jnp.where(head, lane, 0))
    pos = lane - start
    over = (pos >= pair_cap) & (sseg % (d_parts + 1) != d_parts)
    # lanes -> txns without a scatter: sort by txn id; every txn has
    # exactly `a` (padded) lanes, so the sorted lanes reshape to [b, a]
    _, sov = jax.lax.sort((stxn, over), num_keys=1, is_stable=True)
    return sov.reshape(b, a).any(axis=1)


def mc_defer_verdict(batch, dfr):
    """Multi-chip forwarding verdict from the capacity defer mask
    `ycsb.execute_mc` computed shard-locally: commit everything active
    except the deferred txns."""
    from deneva_tpu.cc.base import Verdict

    z = jnp.zeros_like(batch.active)
    dfr = dfr & batch.active
    return Verdict(commit=batch.active & ~dfr, abort=z, defer=dfr,
                   order=batch.rank, level=jnp.zeros_like(batch.rank))


def commit_all_verdict(batch):
    """Commit-everything Verdict in rank order — the forwarding
    executor's invariant (also used standalone by the multi-chip path,
    whose plans are built per-shard inside shard_map)."""
    from deneva_tpu.cc.base import Verdict

    z = jnp.zeros_like(batch.active)
    return Verdict(commit=batch.active, abort=z, defer=z,
                   order=batch.rank, level=jnp.zeros_like(batch.rank))


def forward_verdict(batch):
    """Commit-everything Verdict + sorted ForwardPlan for the single-pass
    executor.  Shared by the single-node engine and the distributed
    server step so their semantics cannot diverge."""
    plan = forward_plan(batch.keys, batch.rank, batch.is_write,
                        batch.valid & batch.active[:, None])
    return commit_all_verdict(batch), plan


def _seg_scan(flags: jax.Array, vals: jax.Array, combine) -> jax.Array:
    """Inclusive segmented scan; ``flags`` marks segment heads.

    Kogge-Stone formulation: log2(n) rounds of shift-and-combine, where
    every shift is a contiguous copy.  On v5e this runs ~20x faster
    than `lax.associative_scan`'s generic lowering (3.9 ms -> ~0.2 ms
    for the three scans at 655k lanes).  Exact for any ASSOCIATIVE
    combine (the segmented pair operator is associative); lanes shifted
    in past the array start are masked out rather than filled, so no
    combine identity is needed and ``flags[0]`` may be False."""
    n = flags.shape[0]
    f, v = flags, vals
    d = 1
    while d < n:
        fa = jnp.concatenate([jnp.ones((d,), bool), f[:-d]])
        va = jnp.concatenate([jnp.zeros((d,), v.dtype), v[:-d]])
        # lanes i < d have no left neighbor at distance d: keep v
        in_range = jnp.concatenate([jnp.zeros((d,), bool),
                                    jnp.ones((n - d,), bool)])
        v = jnp.where(f | ~in_range, v, combine(va, v))
        f = f | fa
        d *= 2
    return v


def _shift1(x: jax.Array, fill) -> jax.Array:
    return jnp.concatenate([jnp.full((1,), fill, x.dtype), x[:-1]])


def seg_first(flags: jax.Array, vals: jax.Array) -> jax.Array:
    """Head-value propagation: every lane takes the value at the nearest
    preceding flagged lane (its own if flagged; its initial value if no
    flag precedes it).  The copy-head combine used by `forward_plan_flat`
    and the executors' monotone-scatter winner propagation."""
    return _seg_scan(flags, vals, lambda v1, v2: v1)


def forward_plan(keys: jax.Array, rank: jax.Array,
                 is_write: jax.Array, valid: jax.Array,
                 with_perm: bool = False) -> ForwardPlan:
    """Build the sorted forwarding plan for one epoch.

    keys: int32[B, A]; rank: int32[B] unique, >= 0; is_write/valid: bool[B, A].
    """
    b, a = keys.shape
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    k = jnp.where(valid, keys, big).reshape(-1)     # invalid sorts last
    r = jnp.broadcast_to(rank[:, None], (b, a)).reshape(-1)
    w = (is_write & valid).reshape(-1)
    return forward_plan_flat(k, r, w, with_perm=with_perm)


def forward_plan_flat(k: jax.Array, r: jax.Array, w: jax.Array,
                      with_perm: bool = False) -> ForwardPlan:
    """Flat-lane core of `forward_plan`: k int32[N] with invalid lanes
    already set to INT32_MAX, r int32[N] owning-txn ranks, w bool[N]
    valid write lanes.  The sharded multi-chip path calls this directly
    on its compacted owned-lane buffer (`workloads/ycsb.execute_mc`)."""
    n = k.shape[0]

    # one fused sort carries the payload with the keys — materially
    # faster on TPU than argsort + permutation gathers.  is_stable=False:
    # jax's default stable sort appends an iota tiebreaker operand (a 4th
    # sorted array, ~12% of the sort's time on v5e); ties are (key, rank)
    # duplicates — one txn's repeated accesses to one key — whose relative
    # order is immaterial to fwd/win/checksum (group-head propagation and
    # the suffix-max winner treat equal-(k,r) lanes identically).
    perm = None
    if with_perm:
        lanes = jnp.arange(n, dtype=jnp.int32)
        sk, sr, sw, perm = jax.lax.sort((k, r, w, lanes), num_keys=2,
                                        is_stable=False)
    else:
        sk, sr, sw = jax.lax.sort((k, r, w), num_keys=2, is_stable=False)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    srd = (sk != big) & ~sw                         # valid reads
    cand = jnp.where(sw, sr, jnp.int32(-1))

    key_head = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    # inclusive max over the key segment, shifted: max over entries sorted
    # strictly before me (-1 at key heads)
    excl = _shift1(_seg_scan(key_head, cand, jnp.maximum), jnp.int32(-1))
    excl = jnp.where(key_head, jnp.int32(-1), excl)
    # entries of one (key, rank) group — one txn's accesses to one key —
    # must all see the value at their group head (no self-visibility):
    # propagate the head's exclusive max through the group
    grp_head = key_head | (sr != _shift1(sr, jnp.int32(-1)))
    head_val = jnp.where(grp_head, excl, jnp.int32(-1))
    fwd = seg_first(grp_head, head_val)

    # final writer per key = the max-index write lane of the key segment
    # (reverse segmented max; segment heads in reverse order are the
    # original segment tails)
    idx = jnp.arange(n, dtype=jnp.int32)
    key_tail = jnp.concatenate([sk[1:] != sk[:-1], jnp.ones((1,), bool)])
    widx = jnp.where(sw, idx, jnp.int32(-1))
    suffmax = _seg_scan(key_tail[::-1], widx[::-1], jnp.maximum)[::-1]
    win = sw & (suffmax == idx)

    return ForwardPlan(keys=sk, rank=sr, is_read=srd, is_write=sw,
                       fwd=fwd, win=win, perm=perm)


def last_earlier_writer(keys: jax.Array, rank: jax.Array,
                        is_write: jax.Array, valid: jax.Array) -> jax.Array:
    """int32[B, A]: ``ForwardPlan.fwd`` unsorted back to the [B, A]
    layout (testing/compatibility entry; the hot path stays sorted)."""
    p = forward_plan(keys, rank, is_write, valid, with_perm=True)
    out = jnp.zeros_like(p.fwd).at[p.perm].set(p.fwd)
    return out.reshape(keys.shape)
