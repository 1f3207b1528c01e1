"""Plain serial reference for served TPC-C (Payment + NewOrder): numpy only,
nothing of the program.

The system under test logs the stream it admitted (one record per epoch:
the merged block of transactions and the mask of lanes that carry one).
This module reads that file with its own decoder, builds the nine tables
from the loader's arithmetic, executes the committed transactions ONE
AFTER ANOTHER, and compares every column with the digest the server
printed for it (`column_digests` of its `[device]` line): exact, limit 0,
naming the first table and column that differ.

Semantics held, as the configuration file states them:

* Payment: W_YTD += h_amount (WH_UPDATE), D_YTD += h_amount, the
  customer's C_BALANCE -= h_amount, C_YTD_PAYMENT += h_amount,
  C_PAYMENT_CNT += 1, one HISTORY row;
* NewOrder: O_ID = D_NEXT_O_ID++, one ORDER and one NEW-ORDER row, and per
  valid line the stock row's quantity rule (``q - ol_q`` if that is MORE
  than 10, else ``q - ol_q + 91``: the source's `new_order_9` compares
  strictly, where the TPC-C text has "at least"), S_YTD += ol_q,
  S_ORDER_CNT += 1, S_REMOTE_CNT += 1 for a remote supply warehouse, and
  one ORDER-LINE row with OL_AMOUNT = ol_q x I_PRICE and the stock row's
  S_DIST_<district> as OL_DIST_INFO;
* the deterministic order: an epoch after another, an epoch's
  transactions by (level, rank).  A transaction's level is the longest
  chain of earlier-ranked transactions of its epoch that it conflicts
  with; two transactions conflict when one's STOCK row is a row the other
  writes.  WAREHOUSE, DISTRICT and CUSTOMER rows are escrow accumulators
  (adds commute, D_NEXT_O_ID is dealt in serial order) and order nothing
  among themselves.  A chain deeper than ``exec_subrounds - 1`` waits:
  the transaction is NOT committed in that epoch and comes back in a
  later record.  Rows are compared by the program's 32-bit identity
  (`row_ident`), so two rows of different tables that share one are a
  conflict too: a legal over-approximation that moves the serial order,
  hence restated here;
* every district's O_IDs run 3001, 3002, ... without a gap or a repeat
  (`order_id_gaps`).

float32: the program holds the source's doubles as float32 and adds them
in an order that is not the serial one.  `h_amount` is a whole number
(`float_headroom_violations` counts those that are not) and whole numbers
add exactly in any order while every sum stays under 2^24: the reference
adds in float64, counts the accumulators that reach 2^24
(`float_headroom_violations`, limit 0) and then holds the float columns to
BIT equality.  OL_AMOUNT is a product of two whole numbers under 2^24:
exact in float32, bit-equal (a bfloat16 product of 7 x 93 would not be).

What is the program's FORMAT, not its semantics, and is restated here:
the log framing, the wire layout of a transaction, the loader's value
laws (`rand01`, `mulmod`, `field_bytes`), the row identity, the tables'
row padding, the ring tables' slot rule and the names of the leaves.
"""

from __future__ import annotations

import hashlib
import os
import struct
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# ---- the command log, as the server writes it (little-endian) ---------
#   record: magic u32 | epoch i64 | blob_len u32 | active_len u32
#           | blob | active bits (np.packbits order)
#   blob:   epoch i64 | n u32 | ts i64[n]
#           | N u32 | W u32 | S u32 | tags i64[N] | keys i32[N,W]
#           | types i8[N,W] | scalars i32[N,S]
#   a transaction: keys = [items | supply_w | quantity] (I lanes each),
#   types[:I] = the line is valid, scalars = txn_type (0 Payment, 1
#   NewOrder), w_id, d_id, c_id, c_w_id, c_d_id, h_amount (float32
#   bits), ol_cnt
_FRAME = struct.Struct("<IqII")
_MAGIC = 0xDE7E7A10
_TS_HDR = struct.Struct("<qI")
_Q_HDR = struct.Struct("<III")

WRITE = 1               # a valid line's type: it writes its stock row
PAYMENT, NEW_ORDER = 0, 1
DIST_PER_WARE = 10
FIRST_O_ID = 3001
DIST_INFO_BYTES = 24
F32_EXACT = float(1 << 24)
# table ids of the row identity, in the schema's order
TID = {"WAREHOUSE": 0, "DISTRICT": 1, "CUSTOMER": 2, "STOCK": 8}


def read_records(buf: bytes):
    """Yield (epoch, keys int32[n,W], types int8[n,W], scalars
    int32[n,S], active bool[n]) per complete record; stops at a torn
    tail."""
    off = 0
    while off + _FRAME.size <= len(buf):
        magic, epoch, blen, alen = _FRAME.unpack_from(buf, off)
        end = off + _FRAME.size + blen + alen
        if magic != _MAGIC or end > len(buf):
            return
        b0 = off + _FRAME.size
        _, n_ts = _TS_HDR.unpack_from(buf, b0)
        q0 = b0 + _TS_HDR.size + 8 * n_ts
        n, w, s = _Q_HDR.unpack_from(buf, q0)
        if n != n_ts:
            raise ValueError(f"log record of epoch {epoch}: {n_ts} "
                             f"timestamps for {n} transactions")
        k0 = q0 + _Q_HDR.size + 8 * n
        keys = np.frombuffer(buf, np.int32, n * w, k0).reshape(n, w)
        t0 = k0 + 4 * n * w
        types = np.frombuffer(buf, np.int8, n * w, t0).reshape(n, w)
        scal = np.frombuffer(buf, np.int32, n * s, t0 + n * w).reshape(n, s)
        bits = np.frombuffer(buf, np.uint8, alen, b0 + blen)
        yield (epoch, keys, types, scal,
               np.unpackbits(bits)[:n].astype(bool))
        off = end


def read_log(buf: bytes):
    """(epoch, keys, types, active) per record, as `ycsb_serial.read_log`
    yields them: what `benchmark/control.py` walks to name a committed
    write (a lane of type `WRITE` is a valid line; its key the item)."""
    for epoch, keys, types, _scal, active in read_records(buf):
        yield epoch, keys, types, active


# ---- the loader's value laws --------------------------------------------

def field_bytes(key, salt, nbytes: int) -> np.ndarray:
    """uint8[..., nbytes]: the bytes of string column ``salt`` of row
    ``key`` (the program's byte law)."""
    k = np.asarray(key).astype(np.uint32)
    v = np.asarray(salt).astype(np.uint32)
    with np.errstate(over="ignore"):
        fp = ((k * np.uint32(2654435761)) ^ (v * np.uint32(0x9E3779B9))) \
            | np.uint32(1)
        i = np.arange(nbytes, dtype=np.uint32)
        mixed = fp[..., None] * (i * np.uint32(2654435761)
                                 + np.uint32(0x9E3779B9))
    return ((mixed >> np.uint32(13)) & np.uint32(0xFF)).astype(np.uint8)


def word(ids: np.ndarray, j: int) -> np.ndarray:
    """int32: the loader's filler of numeric extra column ``j`` (the low
    32 bits of ids x 2654435761 + 0x9E3779B9 x (j + 1))."""
    return ((ids.astype(np.uint64) * 2654435761 + 0x9E3779B9 * (j + 1))
            & 0xFFFFFFFF).astype(np.uint32).view(np.int32)


def rand01(ids: np.ndarray, salt: int) -> np.ndarray:
    """float32 in [0, 1): h x 2^-32 of h = the low 32 bits of ids x
    0x7F4A7C15 + salt, rounded to float32 ONCE."""
    h = (ids.astype(np.uint64) * 0x7F4A7C15 + salt) & 0xFFFFFFFF
    hi = (h >> 8).astype(np.float32) * np.float32(2.0 ** -24)
    lo = (h & 0xFF).astype(np.float32) * np.float32(2.0 ** -32)
    return hi + lo


def mulmod(ids: np.ndarray, mul: int, mod: int) -> np.ndarray:
    return ((ids.astype(np.int64) * mul) % mod).astype(np.int32)


def row_ident(table: str, key) -> np.ndarray:
    """uint32: the identity the program orders rows by."""
    with np.errstate(over="ignore"):
        return (np.asarray(key).astype(np.uint32) * np.uint32(2654435761)) \
            ^ np.uint32((TID[table] * 0x9E3779B9) & 0xFFFFFFFF)


def padded_rows(n_rows: int) -> int:
    """Rows the server allocates: one trash row past the last, rounded up
    to a multiple of 64 (all zero: a masked lane writes zeros there)."""
    return -(-(n_rows + 1) // 64) * 64


# the full schema's extra columns, in the loader's order (the index is
# the filler's j): (name, bytes of a string | None for a number)
_EXTRA = {
    "WAREHOUSE": [("W_NAME", 10), ("W_STREET_1", 20), ("W_STREET_2", 20),
                  ("W_CITY", 20), ("W_STATE", 2), ("W_ZIP", 9)],
    "DISTRICT": [("D_NAME", 10), ("D_STREET_1", 20), ("D_STREET_2", 20),
                 ("D_CITY", 20), ("D_STATE", 2), ("D_ZIP", 9)],
    "CUSTOMER": [("C_FIRST", 16), ("C_MIDDLE", 2), ("C_STREET_1", 20),
                 ("C_STREET_2", 20), ("C_CITY", 20), ("C_STATE", 2),
                 ("C_ZIP", 9), ("C_PHONE", 16), ("C_SINCE", None),
                 ("C_CREDIT", 2), ("C_CREDIT_LIM", None),
                 ("C_DELIVERY_CNT", None), ("C_DATA", 500)],
    "ITEM": [("I_NAME", 24), ("I_DATA", 50)],
    "STOCK": [(f"S_DIST_{i:02d}", DIST_INFO_BYTES) for i in range(1, 11)]
             + [("S_YTD", None), ("S_ORDER_CNT", None), ("S_DATA", 50)],
}
_COUNTERS = {"S_YTD", "S_ORDER_CNT", "C_DELIVERY_CNT"}    # zero at load
CHUNK_BYTES = 1 << 22       # of a column, made and hashed at a time


class Sizes:
    def __init__(self, fields: dict):
        self.n_wh = int(fields["num_wh"])
        self.cpd = int(fields["cust_per_dist"])
        self.n_items = int(fields["max_items"])
        self.ipt = int(fields["max_items_per_txn"])
        self.ring_cap = int(fields["insert_table_cap"])
        self.wh_update = str(fields.get("wh_update", True)).lower() \
            in ("1", "true", "yes", "on")
        self.max_level = int(fields.get("exec_subrounds", 4)) - 1
        self.n_dist = self.n_wh * DIST_PER_WARE
        self.n_cust = self.n_dist * self.cpd
        self.n_stock = self.n_wh * self.n_items
        self.names = min(1000, self.cpd)


# ---- an epoch's order ----------------------------------------------------

def epoch_levels(sz: Sizes, keys, types, scal, active) -> np.ndarray:
    """int32[n]: each active transaction's level in its epoch (the
    module's docstring has the rule); more than ``sz.max_level`` means it
    waits for a later epoch."""
    n, ipt = len(active), sz.ipt
    pay = scal[:, 0] == PAYMENT
    w, d = scal[:, 1].astype(np.int64), scal[:, 2].astype(np.int64)
    valid = (types[:, :ipt] != 0) & (~pay & active)[:, None]
    stock = row_ident("STOCK", keys[:, ipt:2 * ipt].astype(np.int64)
                      * sz.n_items + keys[:, :ipt])
    txn = np.arange(n)
    # every row a transaction writes; STOCK rows alone need ordering
    ident = [stock[valid], row_ident("DISTRICT", (w * 10 + d)[active]),
             row_ident("CUSTOMER", ((scal[:, 4].astype(np.int64) * 10
                                     + scal[:, 5]) * sz.cpd
                                    + scal[:, 3])[pay & active])]
    who = [np.broadcast_to(txn[:, None], valid.shape)[valid], txn[active],
           txn[pay & active]]
    if sz.wh_update:
        ident.append(row_ident("WAREHOUSE", w[pay & active]))
        who.append(txn[pay & active])
    ordered = np.concatenate([np.ones(len(ident[0]), bool)]
                             + [np.zeros(len(i), bool) for i in ident[1:]])
    ident, who = np.concatenate(ident), np.concatenate(who)
    level = np.zeros(n, np.int32)
    if not len(ident):
        return level
    by = np.argsort(ident, kind="stable")
    ident, who, ordered = ident[by], who[by], ordered[by]
    start = np.flatnonzero(np.concatenate(
        [[True], ident[1:] != ident[:-1]]))
    size = np.diff(np.concatenate([start, [len(ident)]]))
    hot = (size > 1) & np.logical_or.reduceat(ordered, start)
    before: dict[int, set] = {}
    for s0, k in zip(start[hot], size[hot]):
        t, o = who[s0:s0 + k], ordered[s0:s0 + k]
        for a in range(k):
            for b in range(k):
                if t[a] < t[b] and (o[a] or o[b]):
                    before.setdefault(int(t[b]), set()).add(int(t[a]))
    for j in sorted(before):            # rank order: earlier ones are final
        level[j] = 1 + max(level[i] for i in before[j])
    return level


# ---- the serial execution --------------------------------------------------

class SerialTables:
    """What the transactions change: the accumulators (float64 here:
    exact), the stock counters, D_NEXT_O_ID, and the inserted rows in the
    order they were inserted.  Everything else is the loader's."""

    def __init__(self, sz: Sizes):
        self.sz = sz
        self.w_ytd = np.full(sz.n_wh, 300000.0)
        self.d_ytd = np.full(sz.n_dist, 30000.0)
        self.d_next = np.full(sz.n_dist, FIRST_O_ID, np.int64)
        self.c_balance = np.full(sz.n_cust, -10.0)
        self.c_ytd = np.full(sz.n_cust, 10.0)
        self.c_cnt = np.ones(sz.n_cust, np.int32)
        s = np.arange(sz.n_stock, dtype=np.int64)
        self.s_qty = 10 + mulmod(s, 69621, 91)
        self.s_remote = np.zeros(sz.n_stock, np.int32)
        self.s_ytd = np.zeros(sz.n_stock, np.int32)
        self.s_order_cnt = np.zeros(sz.n_stock, np.int32)
        self.history: list[tuple] = []      # (c, c_d, c_w, d, w, amount)
        self.orders: list[tuple] = []       # (o_id, c, d, w, ol_cnt, local)
        self.lines: list[np.ndarray] = []   # int64[k, 7] per order
        self.commits = 0
        self.deferred = 0
        self.not_whole = 0
        self.stock_writes = 0

    def payment(self, w, d, c, c_w, c_d, amount: float) -> None:
        sz = self.sz
        if amount != int(amount):
            self.not_whole += 1
        if sz.wh_update:
            self.w_ytd[w] += amount
        self.d_ytd[w * 10 + d] += amount
        ck = (c_w * 10 + c_d) * sz.cpd + c
        self.c_balance[ck] -= amount
        self.c_ytd[ck] += amount
        self.c_cnt[ck] += 1
        self.history.append((c, c_d, c_w, d, w, amount))

    def new_order(self, w, d, c, ol_cnt, items, supply, qty, lane,
                  lost_write: int | None = None) -> None:
        """``items/supply/qty/lane``: the valid lines, in lane order.
        ``lost_write`` (the control): that line's quantity never reaches
        its stock row."""
        sz, dk = self.sz, w * 10 + d
        o_id = int(self.d_next[dk])
        self.d_next[dk] += 1
        for n, (i, sw, q) in enumerate(zip(items, supply, qty)):
            s = sw * sz.n_items + i
            left = int(self.s_qty[s]) - q
            if n != lost_write:
                self.s_qty[s] = left if left > 10 else left + 91
            self.s_ytd[s] += q
            self.s_order_cnt[s] += 1
            if sw != w:
                self.s_remote[s] += 1
            self.stock_writes += 1
        self.orders.append((o_id, c, d, w, ol_cnt,
                            int(all(sw == w for sw in supply))))
        k = len(items)
        self.lines.append(np.stack([
            np.full(k, o_id), np.full(k, d), np.full(k, w),
            np.asarray(lane), np.asarray(items), np.asarray(qty),
            np.asarray(supply)], axis=1).astype(np.int64).reshape(k, 7))


def replay(log: bytes, sz: Sizes, fault: dict | None = None
           ) -> tuple[SerialTables, dict]:
    """Execute the whole log serially.  ``fault`` breaks ONE guarantee on
    the reference's side, so that the comparison must fail (the control
    and the tests): {"lost_stock_write": item} — the last committed
    write to a stock row of that item never lands; {"skipped_history":
    True} — the last Payment inserts no HISTORY row;
    {"swapped_neworders": True} — the last two NewOrders of one district
    in one level swap their places; {"defers_commit": True} — a
    transaction that must wait commits in its first epoch anyway."""
    fault = fault or {}
    tab = SerialTables(sz)
    ipt = sz.ipt
    plan = []           # [(epoch, [(txn fields...)] in serial order)]
    epochs = 0
    for epoch, keys, types, scal, active in read_records(log):
        epochs += 1
        level = epoch_levels(sz, keys, types, scal, active)
        commit = active & (level <= sz.max_level)
        if fault.get("defers_commit"):
            commit = active
        tab.deferred += int((active & ~commit).sum())
        order = np.lexsort((np.arange(len(active)), level))
        plan.append((keys, types, scal, [int(t) for t in order if commit[t]],
                     level))
    # the faults name the LAST place they apply to
    lost = skip_hist = swap = None
    if "lost_stock_write" in fault:
        for e in range(len(plan) - 1, -1, -1):
            keys, types, scal, order, _ = plan[e]
            hit = [(t, j) for t in order if scal[t, 0] == NEW_ORDER
                   for j in range(ipt) if types[t, j]
                   and keys[t, j] == fault["lost_stock_write"]]
            if hit:
                lost = (e, *hit[-1])
                break
    if fault.get("skipped_history"):
        for e in range(len(plan) - 1, -1, -1):
            pays = [t for t in plan[e][3] if plan[e][2][t, 0] == PAYMENT]
            if pays:
                skip_hist = (e, pays[-1])
                break
    if fault.get("swapped_neworders"):
        for e in range(len(plan) - 1, -1, -1):
            keys, types, scal, order, level = plan[e]
            seen: dict = {}
            for pos, t in enumerate(order):
                if scal[t, 0] == NEW_ORDER:
                    k = (int(level[t]), int(scal[t, 1]), int(scal[t, 2]))
                    if k in seen:
                        swap = (e, seen[k], pos)
                    seen[k] = pos
            if swap:
                break
    for e, (keys, types, scal, order, _) in enumerate(plan):
        if swap and swap[0] == e:
            order = list(order)
            order[swap[1]], order[swap[2]] = order[swap[2]], order[swap[1]]
        for t in order:
            kind, w, d, c, c_w, c_d, amt, ol_cnt = (int(x) for x in scal[t])
            tab.commits += 1
            if kind == PAYMENT:
                amount = float(np.int32(amt).view(np.float32))
                tab.payment(w, d, c, c_w, c_d, amount)
                if skip_hist == (e, t):
                    tab.history.pop()
            else:
                lanes = np.flatnonzero(types[t, :ipt])
                lw = None
                if lost and lost[:2] == (e, t):
                    lw = int(np.flatnonzero(lanes == lost[2])[0])
                tab.new_order(w, d, c, ol_cnt, keys[t, lanes].tolist(),
                              keys[t, ipt + lanes].tolist(),
                              keys[t, 2 * ipt + lanes].tolist(),
                              lanes.tolist(), lost_write=lw)
    return tab, dict(epochs=epochs, commits=tab.commits,
                     deferred=tab.deferred)


# ---- the tables as the server lays them out, a column at a time ----------

def _fixed(n: int, make, width: int = 0):
    """A loaded column's chunks: ``make(lo, hi)`` for rows [lo, hi) of the
    ``n`` loaded ones, then the zero rows up to `padded_rows`."""
    step = CHUNK_BYTES // (width or 4)

    def chunks():
        for lo in range(0, n, step):
            yield np.ascontiguousarray(make(lo, min(lo + step, n)))
        pad = padded_rows(n) - n
        yield np.zeros((pad, width) if width else pad,
                       np.uint8 if width else np.int32)
    return chunks


def _ring(cap: int, rows: np.ndarray):
    """A ring column: the inserted ``rows`` at slots cursor % cap in the
    order of insertion (a later row overwrites an earlier one), zeros
    elsewhere, `padded_rows(cap)` rows."""
    def chunks():
        col = np.zeros((padded_rows(cap),) + rows.shape[1:], rows.dtype)
        keep = rows[-cap:]
        first = len(rows) - len(keep)
        col[(first + np.arange(len(keep))) % cap] = keep
        yield col
    return chunks


def columns(sz: Sizes, tab: SerialTables) -> dict:
    """{leaf path as the server names it: () -> its chunks}, every leaf
    of the nine tables."""
    out: dict = {}
    ar = lambda lo, hi: np.arange(lo, hi, dtype=np.int64)   # noqa: E731
    i32 = lambda a: np.asarray(a).astype(np.int32)          # noqa: E731
    f32 = lambda a: np.asarray(a).astype(np.float32)        # noqa: E731
    names = sz.names
    cpd, n_items = sz.cpd, sz.n_items
    counts = dict(WAREHOUSE=sz.n_wh, DISTRICT=sz.n_dist, CUSTOMER=sz.n_cust,
                  ITEM=n_items, STOCK=sz.n_stock)

    def put(table, col, chunks):
        out[f"{table}.columns.{col}"] = chunks

    def fixed(table, col, make, width=0):
        put(table, col, _fixed(counts[table], make, width))

    fixed("WAREHOUSE", "W_ID", lambda a, b: i32(ar(a, b)))
    fixed("WAREHOUSE", "W_TAX",
          lambda a, b: rand01(ar(a, b), 7) * np.float32(0.2))
    fixed("WAREHOUSE", "W_YTD", lambda a, b: f32(tab.w_ytd[a:b]))
    fixed("DISTRICT", "D_ID", lambda a, b: i32(ar(a, b) % 10))
    fixed("DISTRICT", "D_W_ID", lambda a, b: i32(ar(a, b) // 10))
    fixed("DISTRICT", "D_TAX",
          lambda a, b: rand01(ar(a, b), 11) * np.float32(0.2))
    fixed("DISTRICT", "D_YTD", lambda a, b: f32(tab.d_ytd[a:b]))
    fixed("DISTRICT", "D_NEXT_O_ID", lambda a, b: i32(tab.d_next[a:b]))
    fixed("CUSTOMER", "C_ID", lambda a, b: i32(ar(a, b) % cpd))
    fixed("CUSTOMER", "C_D_ID", lambda a, b: i32(ar(a, b) // cpd % 10))
    fixed("CUSTOMER", "C_W_ID", lambda a, b: i32(ar(a, b) // (cpd * 10)))
    fixed("CUSTOMER", "C_LAST", lambda a, b: i32(ar(a, b) % cpd % names))
    fixed("CUSTOMER", "C_DISCOUNT",
          lambda a, b: rand01(ar(a, b), 13) * np.float32(0.5))
    fixed("CUSTOMER", "C_BALANCE", lambda a, b: f32(tab.c_balance[a:b]))
    fixed("CUSTOMER", "C_YTD_PAYMENT", lambda a, b: f32(tab.c_ytd[a:b]))
    fixed("CUSTOMER", "C_PAYMENT_CNT", lambda a, b: tab.c_cnt[a:b])
    fixed("ITEM", "I_ID", lambda a, b: i32(ar(a, b)))
    fixed("ITEM", "I_IM_ID", lambda a, b: mulmod(ar(a, b), 2654435761, 10000))
    fixed("ITEM", "I_PRICE", lambda a, b: 1 + mulmod(ar(a, b), 48271, 100))
    fixed("STOCK", "S_I_ID", lambda a, b: i32(ar(a, b) % n_items))
    fixed("STOCK", "S_W_ID", lambda a, b: i32(ar(a, b) // n_items))
    fixed("STOCK", "S_QUANTITY", lambda a, b: i32(tab.s_qty[a:b]))
    fixed("STOCK", "S_REMOTE_CNT", lambda a, b: tab.s_remote[a:b])
    fixed("STOCK", "S_YTD", lambda a, b: tab.s_ytd[a:b])
    fixed("STOCK", "S_ORDER_CNT", lambda a, b: tab.s_order_cnt[a:b])
    fixed("CUSTOMER", "C_DELIVERY_CNT",
          lambda a, b: np.zeros(b - a, np.int32))
    for table, extras in _EXTRA.items():
        for j, (col, width) in enumerate(extras):
            if col in _COUNTERS or col.startswith("S_DIST_"):
                continue        # above; the one S_DIST array, below
            if width is None:
                fixed(table, col, lambda a, b, j=j: word(ar(a, b), j))
            else:
                fixed(table, col, lambda a, b, j=j, width=width:
                      field_bytes(ar(a, b), j + 1, width), width)
    # the ten S_DIST_xx of a stock row: one array of (row, district)
    # cells, cell row x 10 + d = what column S_DIST_<d + 1> holds
    n_cells = sz.n_stock * 10

    def cells():
        step = CHUNK_BYTES // DIST_INFO_BYTES
        for lo in range(0, n_cells, step):
            r = np.arange(lo, min(lo + step, n_cells), dtype=np.int64)
            yield field_bytes(r // 10, 1 + r % 10, DIST_INFO_BYTES)
        yield np.zeros(((padded_rows(sz.n_stock) - sz.n_stock) * 10,
                        DIST_INFO_BYTES), np.uint8)
    put("STOCK", "S_DIST", cells)
    for table, n in counts.items():
        out[f"{table}.row_cnt"] = lambda n=n: iter([np.int32(n)])

    # the inserted rows, in the order of insertion
    cap = sz.ring_cap
    hist = np.array(tab.history, np.float64).reshape(-1, 6)
    for k, col in enumerate(("H_C_ID", "H_C_D_ID", "H_C_W_ID", "H_D_ID",
                             "H_W_ID")):
        put("HISTORY", col, _ring(cap, i32(hist[:, k])))
    put("HISTORY", "H_AMOUNT", _ring(cap, f32(hist[:, 5])))
    put("HISTORY", "H_DATE", _ring(cap, np.full(len(hist), 2013, np.int32)))
    put("HISTORY", "H_DATA", _ring(cap, field_bytes(
        hist[:, 0].astype(np.int64), hist[:, 4].astype(np.int64),
        DIST_INFO_BYTES)))
    out["HISTORY.row_cnt"] = lambda: iter([np.int32(len(hist))])
    orders = np.array(tab.orders, np.int64).reshape(-1, 6)
    for k, col in enumerate(("O_ID", "O_C_ID", "O_D_ID", "O_W_ID",
                             "O_OL_CNT", "O_ALL_LOCAL")):
        put("ORDER", col, _ring(cap, i32(orders[:, k])))
    put("ORDER", "O_ENTRY_D", _ring(cap, np.full(len(orders), 2013,
                                                 np.int32)))
    put("ORDER", "O_CARRIER_ID", _ring(cap, np.zeros(len(orders), np.int32)))
    for k, col in ((0, "NO_O_ID"), (2, "NO_D_ID"), (3, "NO_W_ID")):
        put("NEW-ORDER", col, _ring(cap, i32(orders[:, k])))
    for t in ("ORDER", "NEW-ORDER"):
        out[f"{t}.row_cnt"] = lambda: iter([np.int32(len(orders))])
    lines = np.concatenate(tab.lines) if tab.lines \
        else np.zeros((0, 7), np.int64)
    lcap = cap * sz.ipt
    for k, col in enumerate(("OL_O_ID", "OL_D_ID", "OL_W_ID", "OL_NUMBER",
                             "OL_I_ID", "OL_QUANTITY", "OL_SUPPLY_W_ID")):
        put("ORDER-LINE", col, _ring(lcap, i32(lines[:, k])))
    put("ORDER-LINE", "OL_DELIVERY_D", _ring(lcap, np.zeros(len(lines),
                                                            np.int32)))
    price = 1 + mulmod(lines[:, 4], 48271, 100)
    put("ORDER-LINE", "OL_AMOUNT", _ring(
        lcap, lines[:, 5].astype(np.float32) * price.astype(np.float32)))
    put("ORDER-LINE", "OL_DIST_INFO", _ring(lcap, field_bytes(
        lines[:, 6] * n_items + lines[:, 4], 1 + lines[:, 1],
        DIST_INFO_BYTES)))
    out["ORDER-LINE.row_cnt"] = lambda: iter([np.int32(len(lines))])
    return out


def digests(cols: dict) -> dict[str, str]:
    """sha256 of every leaf, each hashed as its chunks arrive (no leaf is
    ever whole in memory beside another); the widest first, a few at a
    time (numpy and hashlib release the interpreter lock)."""
    def one(name):
        h = hashlib.sha256()
        for chunk in cols[name]():
            h.update(np.ascontiguousarray(chunk).reshape(-1).view(np.uint8))
        return h.hexdigest()
    wide = ("S_DIST", "C_DATA", "OL_DIST_INFO", "S_DATA")
    names = sorted(cols, key=lambda n: (not n.endswith(wide), n))
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        return dict(zip(names, pool.map(one, names)))


def order_id_gaps(sz: Sizes, tab: SerialTables) -> int:
    """Districts whose O_IDs are not exactly 3001 .. D_NEXT_O_ID - 1,
    each once."""
    orders = np.array(tab.orders, np.int64).reshape(-1, 6)
    dk = orders[:, 3] * 10 + orders[:, 2]
    cnt = np.bincount(dk, minlength=sz.n_dist)
    bad = cnt != tab.d_next - FIRST_O_ID
    by = np.lexsort((orders[:, 0], dk))
    o, g = orders[by, 0], dk[by]
    start = np.concatenate([[0], np.cumsum(cnt)[:-1]])
    want = FIRST_O_ID + np.arange(len(o)) - start[g]
    bad[np.unique(g[o != want])] = True
    return int(bad.sum())


def verify(log: bytes, fields: dict, server_info: dict,
           verdicts=None, drop_key: int | None = None,
           fault: dict | None = None
           ) -> tuple[list[tuple[str, float, float]], dict]:
    """The comparison that decides `correct` for a TPC-C configuration:
    ([(what, value, limit)], notes), each an exact comparison (limit 0).

    * ``digest_mismatch``: leaves of the nine tables whose sha256 on the
      chip (`column_digests`) differs from this module's serial
      execution of the logged stream, or is missing on either side;
      the notes name the first;
    * ``commit_count_gap``: the server's whole-run commit count against
      the commits the reference executed;
    * ``order_id_gaps``: districts whose O_IDs are not 3001.. without a
      gap or a repeat;
    * ``float_headroom_violations``: float accumulators that reached
      2^24, plus amounts that are no whole number;
    * ``logged_epochs_missing``: 1 when the log holds no epoch.
    ``drop_key`` is `benchmark/control.py`'s fault (the item of the last
    logged valid line: its last committed stock write is lost); ``fault``
    one of `replay`'s."""
    if verdicts is not None:
        raise ValueError("tpcc_serial derives the committed set itself")
    sz = Sizes(fields)
    if drop_key is not None:
        fault = {**(fault or {}), "lost_stock_write": int(drop_key)}
    tab, res = replay(log, sz, fault)
    ours = digests(columns(sz, tab))
    chip = server_info.get("column_digests") or {}
    differ = sorted(n for n in set(ours) | set(chip)
                    if ours.get(n) != chip.get(n))
    headroom = tab.not_whole + sum(
        int((np.abs(a) >= F32_EXACT).sum())
        for a in (tab.w_ytd, tab.d_ytd, tab.c_balance, tab.c_ytd))
    out = [("digest_mismatch", float(len(differ)), 0.0),
           ("commit_count_gap",
            float(abs(res["commits"] - int(server_info["run_commit_cnt"]))),
            0.0),
           ("order_id_gaps", float(order_id_gaps(sz, tab)), 0.0),
           ("float_headroom_violations", float(headroom), 0.0),
           ("logged_epochs_missing", 0.0 if res["epochs"] else 1.0, 0.0)]
    return out, dict(epochs=res["epochs"], commits=res["commits"],
                     deferred=res["deferred"], leaves=len(ours),
                     first_differing=differ[:3],
                     max_accumulator=float(max(
                         np.abs(tab.w_ytd).max(), np.abs(tab.d_ytd).max())))
