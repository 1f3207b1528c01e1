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


# The row write's costs on one v5e, `u8[6291520, 100]` donated inside
# the epoch scan (PERF.md section 6, PRs 26 and 48; `tools/
# scatter_calls.py`): with ``indices_are_sorted=True`` XLA passes over
# the whole column, 0.63 ns a ROW of the table plus 3.8 ns a lane;
# without it, it sorts the payload rows and writes them one by one,
# 70.6 ns a LANE and nothing else, one call or many (131-141 ns for the
# version ring's 40 B rows); `write_rows_by_group` writes a lane in 40 ns
# in the hot cell's calls of 2,560.  Chunks win while
# lanes * price < rows * 0.63 + n * 3.8, i.e. lanes * 112 < rows + 6 n
# through XLA's loop and lanes * 64 < rows + 6 n through the kernel: the
# price is the one of the path the epoch takes.
_ROWS_PER_LANE = 112
_KERNEL_ROWS_PER_LANE = 64
_CHUNKS = 64
# The kernel's unit: 32 rows of a row-major `u8` column are one packed
# vector tile — four rows share each 32-bit word — and four of the
# chip's `T(8,128)(4,1)` HBM tiles of 8 rows, 4 KB end to end.  (One
# tile a group, 1 KB, costs more: 78 ns a lane against 47 at 128 in
# flight, with a quarter more groups: `tools/scatter_calls.py`, my chip
# run, PR 48.)
_GROUP = 32
# Tile groups the kernel keeps between HBM and VMEM at once.  With few
# the walk waits for DMAs; at 256 its own 34 VLIW bundles a lane are the
# price, about a nanosecond each (36 / 40 / 52 bundles read 40.4 / 45.4
# / 56.6 ns): at the hot cell's winners a lane costs 242 / 155 / 121 /
# 96 / 75 / 58 / 45 / 40 ns with 2 / 4 / 8 / 16 / 32 / 64 / 128 / 256
# groups in flight (XLA's scatter: 70.5).  256 is what the chip's 2 KB
# of DMA flags hold at one flag a buffer; 512 does not compile (my chip
# runs, PR 48; PERF.md section 6).
_IN_FLIGHT = 256
# The kernel is compiled in where a call holds this many lanes: the hot
# cell's 2,560, the one length the chip has served it through.  Its
# price follows the call, not the winners: 30,000 winners in calls of
# 2,560 cost 44-45 ns a lane at theta 0 / 0.6 / 0.8 / 0.9 alike (0.93 to
# 0.69 groups a winner; XLA's loop 70.5), a shard of four's calls of
# 1,280 cost 65.5 (XLA 71.0) and the medium cells' of 160 cost 90-102
# (XLA 70.6; MVCC served 3% less through it): my chip runs, PR 48.  A
# shard of four and the medium cells keep the parent's program.
_MIN_CALL_LANES = 2560
# Mosaic's per-DMA bounds check: 12 bundles a lane on the walk's 36, and
# at 256 in flight the bundles ARE the price: 56.6 ns a lane with it for
# 45.4 without (at 40 bundles: my chip run, PR 48).  The kernel needs
# none: `write_rows_by_group` clamps the slots and the count it hands
# it, so no slot, sorted or not, moves a byte outside the column.
_BOUNDS_CHECKS = False


def write_rows_by_group(col: jax.Array, idx: jax.Array, vals: jax.Array,
                        cnt, *, in_flight: int,
                        interpret=False) -> jax.Array:
    """``col.at[idx[:cnt]].set(vals[:cnt])`` as ONE Pallas TPU kernel
    that moves tile groups, several in flight.

    col: ``u8[rows, W]``, row-major, written in place (it stays in HBM
    and is the call's aliased output).  idx: int32[L], ASCENDING, its
    first ``cnt`` entries distinct rows of ``col`` (`compact_winners`);
    lanes from ``cnt`` on do nothing.  vals: ``u8[L, W]``.  Held to the
    bytes of ``col.at[idx[:cnt]].set(vals[:cnt])`` under that promise;
    where it is broken (a slot outside the column, a count beyond the
    lanes) it writes a wrong row OF THE COLUMN, never outside it: the
    slots and the count are clamped on the way in, which is what lets
    `_BOUNDS_CHECKS` be off.

    Four rows share each 32-bit word of the column's tiles, so a single
    row is no DMA.  The kernel walks the lanes once: a lane that opens a
    new group starts that group's DMA into one of ``in_flight`` VMEM
    buffers, ``in_flight - 1`` lanes ahead of the lane being merged; a
    merge puts the row's bytes into byte ``pos % 4`` of word row
    ``pos // 4`` of its buffer with 32-bit mask-and-shift (v5e's VPU has
    no 8-bit ops); a group is written back once, when the next one
    opens.  Ascending slots make a group's winners neighbours, so no
    group is ever in flight twice, and a buffer is taken again only
    after its write-back has been waited for.

    The window is 128 bytes wide whatever ``W``: the chip pads a row to
    a whole tile's lanes, Mosaic slices no narrower, and the padding
    bytes go back as they came.  Pallas' interpreter has no padding:
    ``interpret`` keeps the window to the column's own bytes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, w = col.shape
    lanes_n = idx.shape[0]
    group = _GROUP
    assert rows % group == 0 and rows >= group and col.dtype == jnp.uint8, (
        col.shape, col.dtype)
    wide = w if interpret else -(-w // 128) * 128
    # the buffers as 32-bit words, the DMAs through a byte view of them:
    # no pack / unpack around the merge (Pallas' interpreter has no such
    # view: there they are bytes, bitcast around the merge)
    as_words = not interpret
    k, ahead = in_flight, in_flight - 1
    assert k & (k - 1) == 0 and k >= 2, k

    def kernel(idx_ref, meta_ref, vals_ref, _, col_ref, bufs, sem):
        n = jnp.clip(meta_ref[0], 0, lanes_n)
        # (a traced zero: the window's 128 lanes overhang the logical
        # width, which only a static start is checked against)
        lane0 = pl.multiple_of(meta_ref[1], 128)
        sub = jax.lax.broadcasted_iota(jnp.int32, (group // 4, wide), 0)
        live = jax.lax.broadcasted_iota(jnp.int32, (group // 4, wide),
                                        1) < w

        def first_row(slot):
            return slot & ~(group - 1)

        def window(start):
            return col_ref.at[pl.ds(pl.multiple_of(start, group), group),
                              pl.ds(lane0, wide)]

        bytes_of = bufs.bitcast(jnp.uint8) if as_words else bufs

        # (ONE flag a buffer: it is read into and written back from in
        # strict alternation, each copy waited for before the next starts)
        def read(start, b):
            return pltpu.make_async_copy(window(start), bytes_of.at[b],
                                         sem.at[b])

        def write(start, b):
            return pltpu.make_async_copy(bytes_of.at[b], window(start),
                                         sem.at[b])

        def words(b):
            return bufs[b] if as_words else pltpu.bitcast(bufs[b], jnp.int32)

        def put_words(b, x):
            bufs[b] = x if as_words else pltpu.bitcast(x, jnp.uint8)

        def fetch(r, carry):
            opened, prev = carry
            start = first_row(idx_ref[jnp.minimum(r, lanes_n - 1)])
            new = (r < n) & (start != prev)

            @pl.when(new)
            def _():
                b = opened & (k - 1)

                @pl.when(opened >= k)
                def _():
                    write(0, b).wait()
                read(start, b).start()
            return opened + new.astype(jnp.int32), start

        def merge(t, carry):
            merged, prev = carry
            slot = idx_ref[t]
            start = first_row(slot)
            new = start != prev

            @pl.when(new)
            def _():
                @pl.when(merged > 0)
                def _():
                    write(prev, (merged - 1) & (k - 1)).start()
                read(0, merged & (k - 1)).wait()
            merged = merged + new.astype(jnp.int32)
            b = (merged - 1) & (k - 1)
            pos = slot - start
            shift = (pos & 3) * 8
            row = vals_ref[pl.ds(t, 1), :] << shift
            old = words(b)
            hit = (sub == (pos >> 2)) & live
            put_words(b, jnp.where(hit, (old & ~(0xFF << shift)) | row, old))
            return merged, start

        none = jnp.int32(-1)
        opened = jax.lax.fori_loop(0, ahead, fetch, (jnp.int32(0), none))

        def body(t, carry):
            merged = merge(t, carry[0])
            return merged, fetch(t + ahead, carry[1])
        (merged, last), _ = jax.lax.fori_loop(
            0, n, body, ((jnp.int32(0), none), opened))

        @pl.when(merged > 0)
        def _():
            write(last, (merged - 1) & (k - 1)).start()
        for b in range(k):
            @pl.when(b < merged)
            def _():
                write(0, b).wait()

    vals = vals.astype(jnp.int32)
    if wide != w:
        vals = jnp.pad(vals, ((0, 0), (0, wide - w)))
    meta = jnp.stack([jnp.asarray(cnt, jnp.int32), jnp.int32(0)])
    return pl.pallas_call(
        kernel,
        # (vma: under a `shard_map` the result varies as the shard does)
        out_shape=jax.ShapeDtypeStruct(col.shape, col.dtype,
                                       vma=jax.typeof(col).vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((k, group // 4, wide), jnp.int32)
                            if as_words else
                            pltpu.VMEM((k, group, wide), jnp.uint8),
                            pltpu.SemaphoreType.DMA((k,))]),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            disable_bounds_checks=not _BOUNDS_CHECKS),
        interpret=interpret,
        # (the clamp: every window the walk opens is a group of the column)
    )(jnp.clip(idx.astype(jnp.int32), 0, rows - 1), meta, vals, col)


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


def _on_tpu() -> bool:
    """(asked when the program is traced, as `engine/epoch.
    make_dist_group` asks for donation)"""
    return jax.default_backend() == "tpu"


def _by_group(shape: tuple, dtype, chunk: int) -> bool:
    """Whether a column is one `write_rows_by_group` may write, ``chunk``
    lanes a call: bytes, whole groups, `_MIN_CALL_LANES` lanes a call or
    more, and rows that fill at least half of a tile's 128 lanes — the
    kernel's operand is row-major, and the chip lays a narrower column
    (MVCC's ring, 40 B a row) rows-minor rather than pad it 3.2x (PERF.md
    section 6, PR 48).  Any other column keeps XLA's scatter."""
    rows, w = shape
    return (dtype == jnp.uint8 and rows % _GROUP == 0
            and chunk >= _MIN_CALL_LANES and 2 * w >= -(-w // 128) * 128)


def scatter_winner_rows(col: jax.Array, slots: jax.Array, win: jax.Array,
                        carry: tuple, value_fn, n_rows: int, after):
    """``col`` with ``value_fn(*carry)`` written at ``slots`` for the
    ``win`` lanes only; returns ``(col', lanes, groups, after)``: lanes =
    how many the row write was handed, groups = how many tile groups the
    kernel wrote back for them (both uint32; 0 groups where the kernel
    does not write this column).

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
    scatter of all N compacted lanes with the sorted promise.  A chunk's
    rows are written by `write_rows_by_group` on the TPU (the backend
    `engine/epoch.make_dist_group` asks, for donation) where the column
    and the chunk are `_by_group`'s, and by XLA's scatter elsewhere; the
    loop is priced against the pass by the path it takes.  Rows at and
    above ``n_rows`` (trash, padding) are never written."""
    n, rows = slots.shape[0], col.shape[0]
    after, col = jax.lax.optimization_barrier((after, col))
    idx, carry, cnt = compact_winners(slots, win, carry, n_rows, rows)
    chunk = -(-n // _CHUNKS)
    pad = -n % chunk
    trips = (cnt + (chunk - 1)) // chunk
    idx_p = jnp.concatenate([idx, jnp.full((pad,), rows, jnp.int32)])
    carry_p = [jnp.concatenate([c, jnp.zeros((pad,), c.dtype)])
               for c in carry]

    grouped = _by_group(col.shape, col.dtype, chunk)
    kernel = grouped and _on_tpu()

    def by_chunks(c):
        def body(i, c):
            cut = lambda x: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                x, i * chunk, chunk)
            vals = value_fn(*(cut(x) for x in carry_p)).astype(c.dtype)
            if kernel:
                return write_rows_by_group(
                    c, cut(idx_p), vals,
                    jnp.minimum(cnt - i * chunk, chunk),
                    in_flight=_IN_FLIGHT)
            return c.at[cut(idx_p)].set(vals, mode="drop")
        return jax.lax.fori_loop(0, trips, body, c)

    def whole(c):
        return c.at[idx].set(value_fn(*carry).astype(c.dtype),
                             mode="drop", indices_are_sorted=True)

    few = trips * (chunk * (_KERNEL_ROWS_PER_LANE if kernel
                            else _ROWS_PER_LANE)) < rows + 6 * n
    col = jax.lax.cond(few, by_chunks, whole, col)
    groups = jnp.zeros_like(cnt, jnp.uint32)
    if grouped:
        # a group is written back once a call: where a winner's group is
        # not its left neighbour's, and at a chunk's first lane (counted
        # here, outside the kernel: the CPU reports the chip's number)
        lane = jnp.arange(n + pad, dtype=jnp.int32)
        opens = (lane % chunk == 0) | (
            idx_p // _GROUP != jnp.roll(idx_p, 1) // _GROUP)
        groups = jnp.where(few, jnp.sum(opens & (lane < cnt),
                                        dtype=jnp.uint32), groups)
    return (col, jnp.where(few, trips * chunk, n).astype(jnp.uint32),
            groups, after)
