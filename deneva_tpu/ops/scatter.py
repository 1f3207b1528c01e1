"""Duplicate-scatter resolution — the VALUE half of the delta-vs-value
write split.

Committed writes apply in one of two ways:

* **Value writes** (ordered): when several committed transactions in one
  epoch write the same slot (allowed under the ts-ordered algorithms —
  T/O's Thomas-rule writes, MVCC, MAAT, Calvin), the batch must apply
  exactly the write of the *latest* transaction in serialization order.
  The reference gets this for free by executing serially under latches
  (`storage/row.cpp:351-420`); here it is the `last_writer` scatter-max
  tournament below.
* **Delta writes** (escrow / ``order_free``): commutative accumulator
  updates are shipped as DELTAS and applied with a segmented scatter-add
  over ALL committed winners (`storage.table.DeviceTable.scatter_add` —
  `.at[slots].add`, XLA's sorted-segment sum), never through the
  tournament: the sum is order-invariant, so N escrow writers of one hot
  row all commit in the same epoch with serializable results.  This is
  what un-floors TPC-C Payment for the sweep backends once their
  validation stops drawing add-add edges (`cc/base.build_incidence`
  ordered views).  A workload must never mix value writes into an
  escrow column — the executors apply deltas unconditionally, so a
  same-epoch value write would not see them (TPC-C/PPS keep the split
  column-disjoint by construction).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def last_writer(slots: jax.Array, order: jax.Array, mask: jax.Array,
                capacity: int) -> jax.Array:
    """Boolean mask selecting, per duplicated slot, the single entry with the
    highest ``order`` (ties broken by position).

    slots: int32[N] target slots in [0, capacity] (capacity = trash slot).
    order: serialization order (commit timestamp / sequence rank), any
        integer dtype; only comparisons are used.
    mask: bool[N]; masked-out entries never win.

    Entries aimed at the trash slot still "win" their tournament among
    themselves but write only to the trash row, so callers need no special
    casing.
    """
    n = slots.shape[0]
    slots = jnp.where(mask, slots, capacity).astype(jnp.int32)
    if n < capacity:
        # SORT-BASED tournament (round-5): scatter/gather over a
        # [capacity+1] arena costs a full arena copy per call on TPU
        # (XLA lowers batched scatters as copy + apply), which at small
        # epochs over big tables (16M-row YCSB, eb<=2048 — every sweep
        # backend's operating point) dominated the epoch (~0.66 ms/call).
        # Sorting the N lanes by (slot, order, lane) makes each slot's
        # winner its segment tail; a second sort by lane restores the
        # original order.  Lane ids break ties exactly like the
        # arena form (highest lane among equal order) and make the keys
        # unique, so the unstable sorts are deterministic.  O(N log^2 N)
        # independent of table size; the arena form remains for
        # N >= capacity, where one arena pass beats two sorts.
        lane = jnp.arange(n, dtype=jnp.int32)
        neg_o = jnp.iinfo(order.dtype).min
        eff_ord = jnp.where(mask, order, neg_o)
        eff_lane = jnp.where(mask, lane, jnp.int32(-1))
        ssl, _, _, slane = jax.lax.sort(
            (slots, eff_ord, eff_lane, lane), num_keys=3,
            is_stable=False)
        tail = jnp.concatenate([ssl[1:] != ssl[:-1],
                                jnp.ones((1,), bool)])
        _, win = jax.lax.sort((slane, tail), num_keys=1, is_stable=False)
        return win & mask
    neg = jnp.iinfo(order.dtype).min
    eff = jnp.where(mask, order, neg)
    best = jnp.full((capacity + 1,), neg, order.dtype).at[slots].max(eff)
    is_best = mask & (eff == jnp.take(best, slots))
    # tie-break: highest lane index among the best
    lane = jnp.arange(n, dtype=jnp.int32)
    eff_lane = jnp.where(is_best, lane, jnp.int32(-1))
    best_lane = jnp.full((capacity + 1,), -1, jnp.int32).at[slots].max(eff_lane)
    return is_best & (eff_lane == jnp.take(best_lane, slots))


# The row scatter's two costs on one v5e, `u8[6291520, 100]` donated
# inside the epoch scan (PERF.md section 6, PR 26): with
# ``indices_are_sorted=True`` XLA passes over the whole column, 0.63 ns
# a ROW of the table plus 3.8 ns a lane; without it, it sorts the
# payload rows and writes them one by one, 71 ns a LANE and nothing
# else, one call or many.  Chunks win while
# lanes * 71 < rows * 0.63 + n * 3.8, i.e. lanes * 112 < rows + 6 n.
_ROWS_PER_LANE = 112
_CHUNKS = 64


def compact_winners(slots: jax.Array, win: jax.Array, carry: tuple,
                    n_rows: int, drop: int):
    """Winning lanes to the front, in slot order: ``(idx, carry', cnt)``.

    ``idx`` int32[N] holds the ``cnt`` winners' slots ascending, then
    ``drop`` (the caller's out-of-range row) on every other lane — so it
    is non-decreasing and within [0, drop] for any count from 0 to N,
    the promise ``indices_are_sorted=True`` makes.  ``carry`` (the
    integers the caller computes its values from) rides the same sort.
    Lanes aimed outside [0, n_rows) — the trash row, a miss — never win.
    One `lax.sort` on the slot alone: ties are winners of one slot, which
    the caller guarantees carry identical values (`last_writer`, the
    forwarding plan's ``win``, or one transaction's duplicate lanes), and
    losers, which are all dropped."""
    n = slots.shape[0]
    slots = slots.astype(jnp.int32)
    win = win & (slots >= 0) & (slots < n_rows)
    cnt = win.sum(dtype=jnp.int32)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    idx, *carry = jax.lax.sort((jnp.where(win, slots, big), *carry),
                               num_keys=1, is_stable=False)
    idx = jnp.where(jnp.arange(n, dtype=jnp.int32) < cnt, idx,
                    jnp.int32(drop))
    return idx, tuple(carry), cnt


def scatter_winner_rows(col: jax.Array, slots: jax.Array, win: jax.Array,
                        carry: tuple, value_fn, n_rows: int, after):
    """``col`` with ``value_fn(*carry)`` written at ``slots`` for the
    ``win`` lanes only; returns ``(col', lanes, after)``, lanes = how
    many the row scatter was handed (uint32).

    ``after`` is what the epoch has READ out of ``col`` (its gather's
    result, or anything computed from it) and comes back unchanged.  The
    loop below writes the column in place, so every read has to come
    first, and only a data dependence says so to XLA: left unordered, its
    copy insertion keeps the column alive for the reader and copies all
    of it twice an epoch (5 ms for 629 MB on v5e: PERF.md section 6,
    PR 26).  The barrier makes the column wait for ``after``.

    The winners are compacted first (`compact_winners`) and the values
    computed AFTER, from the carried integers, so payload rows are never
    sorted or carried.  How many lanes are then issued follows the
    epoch's winner count: ``ceil(cnt / chunk)`` chunks of ``N / 64``
    lanes in a loop (no winner: none at all), or — where that many
    one-by-one writes would cost more than a pass over the column — one
    scatter of all N compacted lanes with the sorted promise.  Rows at
    and above ``n_rows`` (trash, padding) are never written."""
    n, rows = slots.shape[0], col.shape[0]
    after, col = jax.lax.optimization_barrier((after, col))
    idx, carry, cnt = compact_winners(slots, win, carry, n_rows, rows)
    chunk = -(-n // _CHUNKS)
    pad = -n % chunk
    idx_p = jnp.concatenate([idx, jnp.full((pad,), rows, jnp.int32)])
    carry_p = [jnp.concatenate([c, jnp.zeros((pad,), c.dtype)])
               for c in carry]
    trips = (cnt + (chunk - 1)) // chunk

    def by_chunks(c):
        def body(i, c):
            cut = lambda x: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                x, i * chunk, chunk)
            vals = value_fn(*(cut(x) for x in carry_p))
            return c.at[cut(idx_p)].set(vals.astype(c.dtype), mode="drop")
        return jax.lax.fori_loop(0, trips, body, c)

    def whole(c):
        return c.at[idx].set(value_fn(*carry).astype(c.dtype),
                             mode="drop", indices_are_sorted=True)

    few = trips * (chunk * _ROWS_PER_LANE) < rows + 6 * n
    col = jax.lax.cond(few, by_chunks, whole, col)
    return col, jnp.where(few, trips * chunk, n).astype(jnp.uint32), after
