"""TPC-C (reference `benchmarks/tpcc_wl.cpp`, `tpcc_query.cpp`, `tpcc_txn.cpp`).

Payment + NewOrder only, like the reference (`tpcc_query.cpp:122-141`).
Nine tables per `benchmarks/TPCC_short_schema.txt`; composite keys follow
`benchmarks/tpcc_helper.h:24-30` (distKey/custKey/stockKey) flattened to
dense int32 slot spaces so every primary index is a free `DenseIndex`.

TPU shape — the reference's request-at-a-time state machines
(PAYMENT0-5 / NEWORDER0-9, `tpcc_txn.cpp:247-470`) become:

* ``generate`` — whole-epoch device sampling of query structs with the
  reference's exact distributions (`tpcc_query.cpp:150-260`): payment
  remote-customer prob 0.15, by-last-name prob 60 %, NURand(1023) customer
  and NURand(8191) item selection, ol_cnt ~ URand(5,15), remote supply
  warehouse prob 0.01 gated by MPR.
* ``plan`` — the full RW-set declared up front: warehouse/district/
  customer rows + up to 15 stock rows.  ITEM reads are *excluded* from
  the CC access list: the ITEM table is never written after load (the
  reference still routes item reads through `row_t::get_row`, but they
  can never conflict), so dropping them shrinks the conflict problem by
  ~45 % with identical serializability.
* ``execute`` — one batched pass per epoch (or per chained level):
  commutative balance/YTD updates via ``scatter_add`` (exact under
  duplicates), the non-commutative stock-quantity rule via gather/
  last-writer scatter, and O_ID allocation as a *per-district segmented
  prefix sum* over the committed batch — the epoch analogue of
  D_NEXT_O_ID++ under the district row lock (`tpcc_txn.cpp` new_order_2).
  ORDER / NEW-ORDER / ORDER-LINE / HISTORY inserts append into
  ring-retention tables (`table_t::get_new_row` without the latch).

By-last-name lookup (CUSTOMER_LAST_IDX, a nonunique hash index in the
reference): the loader assigns customer ``c`` the lastname id ``c % 1000``
(the reference's loader uses `Lastname(c_id % 1000)` for the first 1000 and
random beyond, `tpcc_wl.cpp` init_cust).  With ``tpcc_by_last_index``
(default) the lookup resolves through a REAL nonunique HashIndex — bucket
probe + postings walk to the middle matching customer
(`_build_lastname_index`, the analogue of `index_hash.cpp:68-100`); the
closed-form arithmetic bypass (``c_id = L + 1000*(cust_per_dist // 1000
// 2)``) remains as the ablation path and the oracle the index probe is
tested against.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from deneva_tpu.config import Config
from deneva_tpu.ops import last_writer
from deneva_tpu.storage.catalog import parse_schema
from deneva_tpu.workloads.base import partition_owned, partition_slot
from deneva_tpu.storage.table import (DeviceTable, fill_columns, padded_rows,
                                      to_mc_layout)
from deneva_tpu.workloads.ycsb import _field_bytes

# ---------------------------------------------------------------------------
# schema (column set of benchmarks/TPCC_short_schema.txt)

_SCHEMA_COLS = {
    "WAREHOUSE": [("W_ID", "int64_t"), ("W_TAX", "double"),
                  ("W_YTD", "double")],
    "DISTRICT": [("D_ID", "int64_t"), ("D_W_ID", "int64_t"),
                 ("D_TAX", "double"), ("D_YTD", "double"),
                 ("D_NEXT_O_ID", "int64_t")],
    "CUSTOMER": [("C_ID", "int64_t"), ("C_D_ID", "int64_t"),
                 ("C_W_ID", "int64_t"), ("C_LAST", "int64_t"),
                 ("C_DISCOUNT", "double"), ("C_BALANCE", "double"),
                 ("C_YTD_PAYMENT", "double"), ("C_PAYMENT_CNT", "int64_t")],
    "HISTORY": [("H_C_ID", "int64_t"), ("H_C_D_ID", "int64_t"),
                ("H_C_W_ID", "int64_t"), ("H_D_ID", "int64_t"),
                ("H_W_ID", "int64_t"), ("H_AMOUNT", "double")],
    "NEW-ORDER": [("NO_O_ID", "int64_t"), ("NO_D_ID", "int64_t"),
                  ("NO_W_ID", "int64_t")],
    "ORDER": [("O_ID", "int64_t"), ("O_C_ID", "int64_t"),
              ("O_D_ID", "int64_t"), ("O_W_ID", "int64_t"),
              ("O_ENTRY_D", "int64_t"), ("O_OL_CNT", "int64_t"),
              ("O_ALL_LOCAL", "int64_t")],
    "ORDER-LINE": [("OL_O_ID", "int64_t"), ("OL_D_ID", "int64_t"),
                   ("OL_W_ID", "int64_t"), ("OL_NUMBER", "int64_t"),
                   ("OL_I_ID", "int64_t"), ("OL_QUANTITY", "int64_t")],
    "ITEM": [("I_ID", "int64_t"), ("I_IM_ID", "int64_t"),
             ("I_PRICE", "int64_t")],
    "STOCK": [("S_I_ID", "int64_t"), ("S_W_ID", "int64_t"),
              ("S_QUANTITY", "int64_t"), ("S_REMOTE_CNT", "int64_t")],
}

TPCC_SCHEMA = "".join(
    f"TABLE={t}\n" + "".join(f"\t8,{ct},{cn}\n" for cn, ct in cols)
    for t, cols in _SCHEMA_COLS.items())

# TPCC_FULL_SCHEMA extras (reference `benchmarks/TPCC_full_schema.txt`):
# the columns the short schema drops.  Strings materialize as fingerprint
# words (storage/table.py) or, with ``sim_full_row``, as their bytes at
# the schema's widths (``uint8[rows, size]``: a STOCK row holds 290 B of
# strings and a CUSTOMER row 607 B, 314 and 651 B with their 4 B
# numbers); the loader fills them
# deterministically from the row id, and the full-schema execution deltas
# below keep S_YTD/S_ORDER_CNT/OL_* live.
_FULL_EXTRA = {
    "WAREHOUSE": [("W_NAME", "string", 10), ("W_STREET_1", "string", 20),
                  ("W_STREET_2", "string", 20), ("W_CITY", "string", 20),
                  ("W_STATE", "string", 2), ("W_ZIP", "string", 9)],
    "DISTRICT": [("D_NAME", "string", 10), ("D_STREET_1", "string", 20),
                 ("D_STREET_2", "string", 20), ("D_CITY", "string", 20),
                 ("D_STATE", "string", 2), ("D_ZIP", "string", 9)],
    "CUSTOMER": [("C_FIRST", "string", 16), ("C_MIDDLE", "string", 2),
                 ("C_STREET_1", "string", 20), ("C_STREET_2", "string", 20),
                 ("C_CITY", "string", 20), ("C_STATE", "string", 2),
                 ("C_ZIP", "string", 9), ("C_PHONE", "string", 16),
                 ("C_SINCE", "int64_t", 8), ("C_CREDIT", "string", 2),
                 ("C_CREDIT_LIM", "int64_t", 8),
                 ("C_DELIVERY_CNT", "uint64_t", 8),
                 ("C_DATA", "string", 500)],
    "HISTORY": [("H_DATE", "int64_t", 8), ("H_DATA", "string", 24)],
    "ORDER": [("O_CARRIER_ID", "int64_t", 8)],
    "ORDER-LINE": [("OL_SUPPLY_W_ID", "int64_t", 8),
                   ("OL_DELIVERY_D", "int64_t", 8),
                   ("OL_AMOUNT", "double", 8),
                   ("OL_DIST_INFO", "string", 24)],
    "ITEM": [("I_NAME", "string", 24), ("I_DATA", "string", 50)],
    "STOCK": [(f"S_DIST_{i:02d}", "string", 24) for i in range(1, 11)]
             + [("S_YTD", "int64_t", 8), ("S_ORDER_CNT", "int64_t", 8),
                ("S_DATA", "string", 50)],
}


def tpcc_schema(full: bool) -> str:
    if not full:
        return TPCC_SCHEMA
    out = []
    for t, cols in _SCHEMA_COLS.items():
        out.append(f"TABLE={t}\n")
        out.extend(f"\t8,{ct},{cn}\n" for cn, ct in cols)
        out.extend(f"\t{sz},{ct},{cn}\n"
                   for cn, ct, sz in _FULL_EXTRA.get(t, ()))
    return "".join(out)

# table ids for CC access identity (order matters: stable across runs)
TID = {name: i for i, name in enumerate(_SCHEMA_COLS)}

TPCC_PAYMENT = 0
TPCC_NEW_ORDER = 1

# full-width rows keep the ten S_DIST_xx of a stock row as ONE array of
# (row, district) cells, ``S_DIST: uint8[rows * 10, 24]`` with cell
# ``row * 10 + d``: NewOrder copies exactly one of the ten into each
# order line, picked by the order's district, and one gather of the
# lines' cells reads it where ten columns would take ten gathers of
# every line.  The layout is the builder's; the bytes are the schema's.
S_DIST = "S_DIST"
_S_DIST_COLS = tuple(f"S_DIST_{i:02d}" for i in range(1, 11))
_DIST_INFO_BYTES = 24

_LASTNAMES = 1000          # Lastname(NURand(255,0,999)), tpcc_helper.cpp


@dataclass
class TPCCQuery:
    """One epoch of TPC-C queries; pytree with leading dim n.

    Mirrors `TPCCQuery` / `Item_no` (`benchmarks/tpcc_query.h`) with the
    item list padded to ``max_items_per_txn``.
    """

    txn_type: jax.Array     # int32[n]  TPCC_PAYMENT | TPCC_NEW_ORDER
    w_id: jax.Array         # int32[n]  home warehouse (0-based)
    d_id: jax.Array         # int32[n]
    c_id: jax.Array         # int32[n]  resolved customer (by-lastname folded in)
    c_w_id: jax.Array       # int32[n]  payment customer warehouse
    c_d_id: jax.Array       # int32[n]
    h_amount: jax.Array     # float32[n]
    ol_cnt: jax.Array       # int32[n]
    items: jax.Array        # int32[n, I] item ids; duplicates invalidated
    item_valid: jax.Array   # bool[n, I]
    supply_w: jax.Array     # int32[n, I]
    quantity: jax.Array     # int32[n, I]


jax.tree_util.register_dataclass(
    TPCCQuery,
    data_fields=["txn_type", "w_id", "d_id", "c_id", "c_w_id", "c_d_id",
                 "h_amount", "ol_cnt", "items", "item_valid", "supply_w",
                 "quantity"],
    meta_fields=[])


def _nurand(key: jax.Array, A: int, n: int, shape) -> jax.Array:
    """TPC-C NURand(A, 0, n-1) with C=0 (`tpcc_helper.cpp` NURand; the
    reference draws C once per run — a constant offset mod n)."""
    k1, k2 = jax.random.split(key)
    a = jax.random.randint(k1, shape, 0, A + 1)
    b = jax.random.randint(k2, shape, 0, n)
    return (a | b) % n


class TPCCWorkload:
    """Payment + NewOrder over 9 device tables."""

    txn_type_names = ("tpcc_payment", "tpcc_new_order")

    def txn_type_of(self, q: "TPCCQuery") -> jax.Array:
        return q.txn_type

    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.full_schema = cfg.tpcc_full_schema
        # strings as bytes at the schema's widths (config.validate: only
        # with the full schema, on one device)
        self.full_row = cfg.sim_full_row
        self.catalog = parse_schema(tpcc_schema(self.full_schema))
        self.n_wh = cfg.num_wh
        self.n_dist = 10                     # DIST_PER_WARE (tpcc_const.h)
        self.cust_per_dist = cfg.cust_per_dist
        self.max_items = cfg.max_items
        self.ipt = cfg.max_items_per_txn     # MAX_ITEMS_PER_TXN=15 (config.h:189)
        # partitioned deployment: warehouse -> node (reference wh_to_part,
        # `benchmarks/tpcc_helper.cpp`); this node stores warehouses
        # ≡ node_id (mod part_cnt).  ITEM is read-only and replicated
        # everywhere, exactly like the reference.
        self.n_parts = max(cfg.part_cnt, 1)
        self.me = cfg.node_id if self.n_parts > 1 else 0
        if self.n_wh % self.n_parts != 0:
            raise ValueError("num_wh must divide evenly over part_cnt")
        self.n_wh_loc = self.n_wh // self.n_parts
        # effective lastname population: every district must contain at
        # least one customer per lastname for the closed-form lookup
        self.lastnames = min(_LASTNAMES, self.cust_per_dist)
        need = 3 + self.ipt                  # wh + dist + cust + stock rows
        if cfg.max_accesses < need:
            raise ValueError(
                f"TPCC needs max_accesses >= {need}, got {cfg.max_accesses}")
        self.n_districts = self.n_wh * self.n_dist
        self.n_cust = self.n_districts * self.cust_per_dist
        self.n_stock = self.n_wh * self.max_items
        # local (stored) row counts — global counts / n_parts
        self.n_districts_loc = self.n_wh_loc * self.n_dist
        self.n_cust_loc = self.n_districts_loc * self.cust_per_dist
        self.n_stock_loc = self.n_wh_loc * self.max_items
        # flattened composite keys and the per-district sort key must fit
        # int32 (storage/table.py's stated key contract)
        lim = 2**31 - 1
        if max(self.n_stock, self.n_cust) > lim:
            raise ValueError("TPCC key space exceeds int32: shrink "
                             "num_wh/max_items/cust_per_dist")
        if (self.n_districts + 1) * 2 * cfg.epoch_batch > lim:
            raise ValueError("num_wh*10*2*epoch_batch must fit int32")
        if cfg.tpcc_by_last_index:
            self._build_lastname_index()

    def _build_lastname_index(self):
        """CUSTOMER_LAST nonunique secondary index (reference
        `tpcc_wl.cpp` index_insert on custNPKey, probed
        `index_hash.cpp:68-100`): hash probe on (w, d, lastname) ->
        packed (postings start, count); the postings array lists the
        matching customers' c_ids in ascending order, and payment picks
        the middle one (`tpcc_txn.cpp` run_payment by-last-name).  Global
        (every node resolves remote customers — queries are generated
        before planning, like the reference client)."""
        from deneva_tpu.storage.index import HashIndex

        cpd, names = self.cust_per_dist, self.lastnames
        c = np.arange(self.n_cust, dtype=np.int64)
        c_local = (c % cpd).astype(np.int32)
        dist = (c // cpd).astype(np.int64)
        lastkey = dist * names + c_local % names        # (w,d,L) composite
        order = np.lexsort((c_local, lastkey))
        postings = c_local[order]                       # grouped by lastkey
        sorted_keys = lastkey[order]
        uniq, starts, counts = np.unique(sorted_keys, return_index=True,
                                         return_counts=True)
        if counts.max() >= 256 or len(postings) >= (1 << 23):
            raise ValueError("CUSTOMER_LAST packing overflow: shrink "
                             "cust_per_dist or num_wh")
        packed = (starts.astype(np.int64) << 8 | counts).astype(np.int32)
        self.last_idx = HashIndex.build(uniq.astype(np.int32), packed,
                                        miss_slot=0)
        self.last_postings = jnp.asarray(postings)

    def _lastname_middle(self, c_w, c_d, lastname):
        """Middle same-lastname customer via the real index probe."""
        names = self.lastnames
        key = (c_w * self.n_dist + c_d) * names + lastname
        packed = self.last_idx.lookup(key)
        start, cnt = packed >> 8, packed & 0xFF
        return jnp.take(self.last_postings,
                        jnp.clip(start + cnt // 2, 0,
                                 self.last_postings.shape[0] - 1))

    # -- composite keys (tpcc_helper.h:24-30, flattened dense) ----------
    # global keys: CC identity (plan / conflict detection) — same on
    # every node so the merged-epoch validation agrees cluster-wide
    def dist_key(self, w, d):
        return w * self.n_dist + d

    def cust_key(self, w, d, c):
        return self.dist_key(w, d) * self.cust_per_dist + c

    def order_index_key(self, w, d, o_id):
        """Dynamic ORDER-index key, district-major so one district's
        orders are a contiguous ascending o_id run (range scans = the
        B+-tree leaf walk).  o_id stays < 2^21 and districts < 2^10 by
        the tpcc_order_index config guard, so the composite fits int32."""
        return (self.dist_key(w, d) * jnp.int32(1 << 21)
                + o_id.astype(jnp.int32))

    def stock_key(self, w, i):
        return w * self.max_items + i

    # local slots: storage addressing on THIS node — warehouses not owned
    # here resolve to each table's trash slot.  NOTE: the trash row is a
    # spill target, not guaranteed zeros — masked scatters land IN it, so
    # trash-row gathers of scatter-written columns return garbage; every
    # consumer of a remote-lane gather below must stay masked by
    # ownership (they do: o_id/inserts use m & owned, stock writes
    # resolve back into trash)
    def wh_owned(self, w):
        return partition_owned(w, self.n_parts, self.me)

    def _wloc(self, w):
        return w // self.n_parts if self.n_parts > 1 else w

    def wh_slot(self, w):
        return partition_slot(w, self.n_parts, self.me, self.n_wh_loc)

    def dist_slot(self, w, d):
        return jnp.where(self.wh_owned(w),
                         self._wloc(w) * self.n_dist + d,
                         jnp.int32(self.n_districts_loc))

    def cust_slot(self, w, d, c):
        return jnp.where(self.wh_owned(w),
                         (self._wloc(w) * self.n_dist + d)
                         * self.cust_per_dist + c,
                         jnp.int32(self.n_cust_loc))

    def stock_slot(self, w, i):
        return jnp.where(self.wh_owned(w),
                         self._wloc(w) * self.max_items + i,
                         jnp.int32(self.n_stock_loc))

    # -- loader (tpcc_wl.cpp:89-152 parallel loaders) -------------------
    def load(self):
        """Build the initial database ON DEVICE as one jitted program.

        The reference's loaders are parallel host threads writing rows
        (`tpcc_wl.cpp:89-152`); the first cut here mirrored that with
        numpy columns copied to the device — which meant shipping
        hundreds of MB over the host link at num_wh=64.  Every initial
        value is arithmetic on the row
        index, so the whole load is a single XLA program: zero
        host->device bytes, compile + run in seconds at any scale."""
        db = jax.jit(self._build_db)()
        if self.full_row:
            db = self._fill_strings(db)
        if self.cfg.audit:
            # isolation audit stamp tables (cc/base.audit_observe):
            # loader-installed so every db-construction path threads the
            # identical pytree; excluded from state_digest (control
            # plane, like the elastic MEMBER_KEY)
            from deneva_tpu.cc.base import AUDIT_KEY, audit_init
            db[AUDIT_KEY] = audit_init(self.cfg)
        return db

    def _build_db(self):
        cfg = self.cfg
        db = {}

        def tab(name, cap, ring=False):
            t = DeviceTable.create(self.catalog.table(name), cap,
                                   full_row=self.full_row, ring=ring)
            db[name] = t
            return t

        # local slot ℓ stores global warehouse me + n_parts * (ℓ // ...):
        # loader values derive from GLOBAL ids so any node's copy of a row
        # matches what a single-node load would have produced
        p, me = self.n_parts, self.me

        wh = tab("WAREHOUSE", self.n_wh_loc)
        w_glob = me + p * jnp.arange(self.n_wh_loc, dtype=jnp.int32)
        db["WAREHOUSE"] = fill_columns(wh, self.n_wh_loc, {
            "W_ID": w_glob,
            "W_TAX": _rand01(w_glob, 7) * 0.2,      # URand(0,.2) (init_wh)
            "W_YTD": jnp.full(self.n_wh_loc, 300000.0, jnp.float32)})

        dist = tab("DISTRICT", self.n_districts_loc)
        dl = jnp.arange(self.n_districts_loc, dtype=jnp.int32)
        d_w = me + p * (dl // self.n_dist)
        d_id = dl % self.n_dist
        d_glob = d_w * self.n_dist + d_id
        db["DISTRICT"] = fill_columns(dist, self.n_districts_loc, {
            "D_ID": d_id,
            "D_W_ID": d_w,
            "D_TAX": _rand01(d_glob, 11) * 0.2,
            "D_YTD": jnp.full(self.n_districts_loc, 30000.0, jnp.float32),
            "D_NEXT_O_ID": jnp.full(self.n_districts_loc, 3001, jnp.int32)})

        cust = tab("CUSTOMER", self.n_cust_loc)
        cl = jnp.arange(self.n_cust_loc, dtype=jnp.int32)
        c_local = cl % self.cust_per_dist
        c_d = (cl // self.cust_per_dist) % self.n_dist
        c_w = me + p * (cl // (self.cust_per_dist * self.n_dist))
        c_glob = (c_w * self.n_dist + c_d) * self.cust_per_dist + c_local
        db["CUSTOMER"] = fill_columns(cust, self.n_cust_loc, {
            "C_ID": c_local,
            "C_D_ID": c_d,
            "C_W_ID": c_w,
            "C_LAST": c_local % self.lastnames,
            "C_DISCOUNT": _rand01(c_glob, 13) * 0.5,
            "C_BALANCE": jnp.full(self.n_cust_loc, -10.0, jnp.float32),
            "C_YTD_PAYMENT": jnp.full(self.n_cust_loc, 10.0, jnp.float32),
            "C_PAYMENT_CNT": jnp.ones(self.n_cust_loc, jnp.int32)})

        item = tab("ITEM", self.max_items)
        i_ids = jnp.arange(self.max_items, dtype=jnp.int32)
        db["ITEM"] = fill_columns(item, self.max_items, {
            "I_ID": i_ids,
            "I_IM_ID": _mulmod(i_ids, 2654435761, 10000),
            "I_PRICE": 1 + _mulmod(i_ids, 48271, 100)})

        stock = tab("STOCK", self.n_stock_loc)
        sl = jnp.arange(self.n_stock_loc, dtype=jnp.int32)
        s_i = sl % self.max_items
        s_w = me + p * (sl // self.max_items)
        s_glob = s_w * self.max_items + s_i
        db["STOCK"] = fill_columns(stock, self.n_stock_loc, {
            "S_I_ID": s_i,
            "S_W_ID": s_w,
            "S_QUANTITY": 10 + _mulmod(s_glob, 69621, 91),
            "S_REMOTE_CNT": jnp.zeros(self.n_stock_loc, jnp.int32)})

        cap = cfg.insert_table_cap
        tab("HISTORY", cap, ring=True)
        tab("ORDER", cap, ring=True)
        tab("NEW-ORDER", cap, ring=True)
        # lines wrap no earlier than their orders (<= ipt lines per order)
        tab("ORDER-LINE", cap * self.ipt, ring=True)

        if self.full_schema:
            # TPCC_FULL_SCHEMA: fill the extra columns of the fixed
            # tables with deterministic per-row hashes (the reference
            # loader draws random strings, tpcc_wl.cpp init_*; ours must
            # be recomputable for consistency checks)
            counts = {"WAREHOUSE": self.n_wh_loc,
                      "DISTRICT": self.n_districts_loc,
                      "CUSTOMER": self.n_cust_loc, "ITEM": self.max_items,
                      "STOCK": self.n_stock_loc}
            for t, extras in _FULL_EXTRA.items():
                n = counts.get(t)
                if n is None:          # ring tables fill at insert time
                    continue
                cols = dict(db[t].columns)
                ids = jnp.arange(n, dtype=jnp.int32).astype(jnp.uint32)
                for j, (cn, ct, _sz) in enumerate(extras):
                    if cn in ("S_YTD", "S_ORDER_CNT", "C_DELIVERY_CNT"):
                        continue       # spec-initialized counters: zero
                    if self.full_row and ct == "string":
                        continue       # bytes: `_fill_strings`
                    v = ids * jnp.uint32(2654435761) \
                        + jnp.uint32(0x9E3779B9) * jnp.uint32(j + 1)
                    cols[cn] = cols[cn].at[:n].set(
                        v.astype(cols[cn].dtype))
                if self.full_row and t == "STOCK":
                    for cn in _S_DIST_COLS:
                        del cols[cn]
                    cols[S_DIST] = jnp.zeros(
                        (padded_rows(n) * 10, _DIST_INFO_BYTES), jnp.uint8)
                db[t] = db[t]._replace(columns=cols)

        D = cfg.device_parts
        if D > 1:
            # owner-major stacked layout across chips: warehouses are the
            # ownership anchor (reference wh_to_part node partition,
            # `benchmarks/tpcc_helper.cpp`); read-only ITEM replicates
            # like the reference's per-node copy
            db["ITEM"] = db["ITEM"]._replace(mc_replicated=True)
            for name, anchor_rows in (
                    ("WAREHOUSE", 1), ("DISTRICT", self.n_dist),
                    ("CUSTOMER", self.n_dist * self.cust_per_dist),
                    ("STOCK", self.max_items), ("HISTORY", 1),
                    ("ORDER", 1), ("NEW-ORDER", 1), ("ORDER-LINE", 1)):
                db[name] = to_mc_layout(db[name], D, anchor_rows)
        if self.cfg.tpcc_order_index:
            # dynamic ordered ORDER index (reference index_btree over
            # inserted orders, `index_btree.cpp:252-420`): key =
            # district * 2^21 + o_id, merged per epoch as NewOrders
            # commit (`_exec_neworder`), probed by key or district range
            from deneva_tpu.storage.index import DynamicSortedIndex
            db["ORDER_IDX"] = DynamicSortedIndex.build(
                np.zeros(0, np.int32), np.zeros(0, np.int32),
                miss_slot=db["ORDER"].capacity,
                cap=self.cfg.insert_table_cap)
        return db

    def _fill_strings(self, db):
        """Full-width rows: the fixed tables' string columns get their
        bytes — column j of a table holds ``_field_bytes(row, j + 1)`` at
        the schema's width, cell ``(row, d)`` of `S_DIST` what column
        S_DIST_<d+1> would — ONE COLUMN AT A TIME, each in place (the
        zeros `_build_db` made are donated): one program over all of
        them holds 6.6 GB of temporaries beside 8.8 GB of tables at 128
        warehouses (the chip's compiler, no chip attached)."""
        # (donation off the CPU backend only, as `make_dist_group`)
        fill = _string_filler(jax.default_backend() != "cpu")
        db = dict(db)
        for t, extras in _FULL_EXTRA.items():
            if db[t].ring:             # ring tables fill at insert time
                continue
            n = db[t].capacity         # a fixed table is loaded full
            cols = dict(db[t].columns)
            for j, (cn, ct, _sz) in enumerate(extras):
                if ct == "string" and cn in cols:
                    cols[cn] = fill(cols[cn], jnp.uint32(j + 1), n, 1)
            if t == "STOCK":
                cols[S_DIST] = fill(cols[S_DIST], jnp.uint32(1), n, 10)
            db[t] = db[t]._replace(columns=cols)
        return db

    # -- generation (tpcc_query.cpp:144-260) ----------------------------
    def generate(self, rng: jax.Array, n: int) -> TPCCQuery:
        cfg = self.cfg
        ks = jax.random.split(rng, 12)
        is_pay = jax.random.bernoulli(ks[0], cfg.perc_payment, (n,))
        w_id = jax.random.randint(ks[1], (n,), 0, self.n_wh)
        d_id = jax.random.randint(ks[2], (n,), 0, self.n_dist)

        # payment customer: remote (w', d') with prob 0.15 (tpcc_query.cpp:168-186)
        remote = jax.random.bernoulli(ks[3], 0.15, (n,)) & (self.n_wh > 1)
        rw = jax.random.randint(ks[4], (n,), 0, max(self.n_wh - 1, 1))
        rw = jnp.where(rw >= w_id, rw + 1, rw)          # != w_id
        c_w_id = jnp.where(remote, rw, w_id)
        c_d_id = jnp.where(remote,
                           jax.random.randint(ks[5], (n,), 0, self.n_dist),
                           d_id)

        # by-last-name 60% resolves to the middle same-lastname customer
        # (customers with lastname L are {L, L+names, L+2*names, ...}) —
        # through the CUSTOMER_LAST index probe (hash + postings walk) on
        # the generation hot path, or the closed form when disabled
        by_last = jax.random.bernoulli(ks[6], 0.6, (n,))
        names = self.lastnames
        lastname = _nurand(ks[7], 255, names, (n,))
        if cfg.tpcc_by_last_index:
            mid = self._lastname_middle(c_w_id, c_d_id, lastname)
        else:
            per_name = self.cust_per_dist // names
            mid = lastname + names * (per_name // 2)
        c_direct = _nurand(ks[8], 1023, self.cust_per_dist, (n,))
        c_id = jnp.where(by_last & is_pay, mid, c_direct)

        h_amount = jax.random.uniform(ks[9], (n,), jnp.float32, 1.0, 5000.0)

        # new-order item list (tpcc_query.cpp:221-256)
        I = self.ipt
        ol_cnt = jax.random.randint(ks[10], (n,), 5, I + 1)
        ki, kq, kr, kw = jax.random.split(ks[11], 4)
        items = _nurand(ki, 8191, self.max_items, (n, I))
        lane = jnp.arange(I)
        in_cnt = lane[None, :] < ol_cnt[:, None]
        # reference rejects duplicate item ids (tpcc_query.cpp:237); here
        # duplicates beyond the first are invalidated (collision odds
        # ~I^2/2/max_items per txn)
        first = jnp.argmax(items[:, :, None] == items[:, None, :], axis=1)
        item_valid = in_cnt & (first == lane[None, :])
        quantity = jax.random.randint(kq, (n, I), 1, 11)
        kr1, kr2 = jax.random.split(kr)
        rem_item = (jax.random.bernoulli(kr1, 0.01, (n, I))
                    & jax.random.bernoulli(kr2, cfg.mpr_neworder, (n, 1))
                    & (self.n_wh > 1))
        rsup = jax.random.randint(kw, (n, I), 0, max(self.n_wh - 1, 1))
        rsup = jnp.where(rsup >= w_id[:, None], rsup + 1, rsup)
        supply_w = jnp.where(rem_item, rsup, w_id[:, None])

        return TPCCQuery(
            txn_type=jnp.where(is_pay, TPCC_PAYMENT, TPCC_NEW_ORDER
                               ).astype(jnp.int32),
            w_id=w_id, d_id=d_id, c_id=c_id, c_w_id=c_w_id, c_d_id=c_d_id,
            h_amount=h_amount, ol_cnt=ol_cnt,
            items=items, item_valid=item_valid, supply_w=supply_w,
            quantity=quantity)

    # -- wire adapters (distributed runtime: CL_QRY / EPOCH_BLOB bodies) --
    # keys[n, 3I] = [items | supply_w | quantity]; types[n, 3I] marks item
    # validity in the first I lanes; scalars[n, 8] carries the per-txn
    # fields (h_amount as raw float32 bits).
    def to_wire(self, q: TPCCQuery):
        k = np.concatenate([np.asarray(q.items, np.int32),
                            np.asarray(q.supply_w, np.int32),
                            np.asarray(q.quantity, np.int32)], axis=1)
        t = np.zeros_like(k, np.int8)
        t[:, : self.ipt] = np.asarray(q.item_valid, np.int8)
        s = np.stack([
            np.asarray(q.txn_type, np.int32), np.asarray(q.w_id, np.int32),
            np.asarray(q.d_id, np.int32), np.asarray(q.c_id, np.int32),
            np.asarray(q.c_w_id, np.int32), np.asarray(q.c_d_id, np.int32),
            np.asarray(q.h_amount, np.float32).view(np.int32),
            np.asarray(q.ol_cnt, np.int32)], axis=1)
        return k, t, s

    def from_wire(self, keys: np.ndarray, types: np.ndarray,
                  scalars: np.ndarray) -> TPCCQuery:
        I = self.ipt
        keys = np.asarray(keys, np.int32)
        scalars = np.ascontiguousarray(scalars, np.int32)
        return TPCCQuery(
            txn_type=jnp.asarray(scalars[:, 0]),
            w_id=jnp.asarray(scalars[:, 1]), d_id=jnp.asarray(scalars[:, 2]),
            c_id=jnp.asarray(scalars[:, 3]),
            c_w_id=jnp.asarray(scalars[:, 4]),
            c_d_id=jnp.asarray(scalars[:, 5]),
            h_amount=jnp.asarray(
                np.ascontiguousarray(scalars[:, 6]).view(np.float32)),
            ol_cnt=jnp.asarray(scalars[:, 7]),
            items=jnp.asarray(keys[:, :I]),
            item_valid=jnp.asarray(types[:, :I] != 0),
            supply_w=jnp.asarray(keys[:, I:2 * I]),
            quantity=jnp.asarray(keys[:, 2 * I:3 * I]))

    def from_wire_dev(self, keys, types, scalars) -> TPCCQuery:
        """Traceable from_wire (cluster dispatch jit): the float32
        h_amount rides the wire as raw int32 bits, so the host's
        ``.view(np.float32)`` becomes a device bitcast."""
        import jax
        I = self.ipt
        return TPCCQuery(
            txn_type=scalars[:, 0], w_id=scalars[:, 1], d_id=scalars[:, 2],
            c_id=scalars[:, 3], c_w_id=scalars[:, 4], c_d_id=scalars[:, 5],
            h_amount=jax.lax.bitcast_convert_type(scalars[:, 6],
                                                  jnp.float32),
            ol_cnt=scalars[:, 7],
            items=keys[:, :I], item_valid=types[:, :I] != 0,
            supply_w=keys[:, I:2 * I], quantity=keys[:, 2 * I:3 * I])

    # -- RW-set planning (tpcc_txn.cpp state machines, declared up front)
    def plan(self, db, q: TPCCQuery) -> dict:
        cfg = self.cfg
        n = q.w_id.shape[0]
        A = cfg.max_accesses
        is_pay = q.txn_type == TPCC_PAYMENT

        tables = jnp.zeros((n, A), jnp.int32)
        keys = jnp.zeros((n, A), jnp.int32)
        is_read = jnp.zeros((n, A), bool)
        is_write = jnp.zeros((n, A), bool)
        valid = jnp.zeros((n, A), bool)
        order_free = jnp.zeros((n, A), bool)
        owner = jnp.zeros((n, A), jnp.int32)

        def put(a, tid, key, r, w, v, of=False, wh=None):
            nonlocal tables, keys, is_read, is_write, valid, order_free, owner
            tables = tables.at[:, a].set(tid)
            keys = keys.at[:, a].set(key)
            is_read = is_read.at[:, a].set(r)
            is_write = is_write.at[:, a].set(w)
            valid = valid.at[:, a].set(v)
            if of is not False:
                order_free = order_free.at[:, a].set(of)
            if wh is not None:
                # access owner = the row's warehouse's node (wh_to_part,
                # benchmarks/tpcc_helper.cpp) — the VOTE participant map
                owner = owner.at[:, a].set(wh % jnp.int32(self.n_parts))

        # The warehouse/district/customer accesses are ``order_free``
        # (escrow/commutative semantics): every write on them is a
        # scatter-add (W_YTD/D_YTD/C_BALANCE/C_YTD_PAYMENT/
        # C_PAYMENT_CNT += ...) or the D_NEXT_O_ID prefix sum
        # (rank-ordered within each chained sub-round, level-major
        # across sub-rounds — serializable as (level, rank) order),
        # and every read is of an immutable column (W_TAX, D_TAX,
        # C_DISCOUNT) — so the batched executor applies them
        # order-exactly with no conflict edges.  The reference's
        # row-level lock managers serialize payments on the warehouse
        # row (`row_lock.cpp`), which is exactly the scaling cliff this
        # column-aware declaration removes for the deterministic
        # backends (lock/ts baselines still see the full RW-sets).
        # Stock is a genuine RMW (quantity rule) and stays ordered.
        one = jnp.ones((n,), bool)
        # 0: warehouse — payment updates W_YTD (run_payment_0), neworder
        #    reads W_TAX (new_order_0)
        wh_write = is_pay & cfg.wh_update
        put(0, TID["WAREHOUSE"], q.w_id, one, wh_write, one, of=one,
            wh=q.w_id)
        # 1: district — payment D_YTD += (run_payment_2/3); neworder
        #    D_NEXT_O_ID++ (new_order_2)
        put(1, TID["DISTRICT"], self.dist_key(q.w_id, q.d_id), one, one, one,
            of=one, wh=q.w_id)
        # 2: customer — payment balance update at (c_w,c_d); neworder
        #    reads C_DISCOUNT at home (new_order_4)
        ck = jnp.where(is_pay, self.cust_key(q.c_w_id, q.c_d_id, q.c_id),
                       self.cust_key(q.w_id, q.d_id, q.c_id))
        put(2, TID["CUSTOMER"], ck, one, is_pay, one, of=one,
            wh=jnp.where(is_pay, q.c_w_id, q.w_id))
        # 3..3+I: stock rows (new_order_8); ITEM reads excluded (immutable)
        sk = self.stock_key(q.supply_w, q.items)
        iv = q.item_valid & ~is_pay[:, None]
        for j in range(self.ipt):
            put(3 + j, TID["STOCK"], sk[:, j], iv[:, j], iv[:, j], iv[:, j],
                wh=q.supply_w[:, j])
        return dict(table_ids=tables, keys=keys, is_read=is_read,
                    is_write=is_write, valid=valid, order_free=order_free,
                    owner=owner)

    # -- repair re-execution (engine/repair.py, Config.repair) ---------
    def re_execute(self, db, q: TPCCQuery, mask: jax.Array,
                   order: jax.Array, stats: dict):
        """Pure re-execution closure, keyed by txn slot: re-running a
        repaired txn is ``execute`` on the same query row against
        CURRENT state.  NewOrder re-reads D_NEXT_O_ID, stock quantities
        and the immutable price columns post-winners — the masked
        re-read (non-frontier gathers return values nothing overwrote)
        — recomputes its RMW writes and appends its ORDER/NEW-ORDER/
        ORDER-LINE rows in the sub-round wave, so per-district o_ids
        stay dense across waves (oracle: tests/test_repair.py audit).
        Escrow contract, documented and tested: repair of an escrow
        (order_free) delta is a NO-OP semantically — the delta
        recomputes identically from the query row (pure function,
        independent of any read) and scatter-adds once, exactly the
        write the main wave would have applied; escrow reads are
        declared-immutable columns and never enter the frontier."""
        return self.execute(db, q, mask, order, stats)

    # -- execution ------------------------------------------------------
    # NewOrder's stock update is a true RMW (the new quantity depends on
    # the read), so the single-pass forwarding executor does not apply
    blind_writes = False

    def execute(self, db, q: TPCCQuery, mask: jax.Array, order: jax.Array,
                stats: dict, fwd_rank=None, level_exec: bool = False):
        # NOTE: payments usually land at wavefront level 0 (all their
        # accesses are order_free), but hash-collision FALSE edges can
        # legitimately assign one a higher level, so every sub-round
        # must execute its payment mask — skipping "provably empty"
        # levels here would silently drop those payments' writes.
        db = dict(db)
        is_pay = q.txn_type == TPCC_PAYMENT
        pay = mask & is_pay
        neworder = mask & ~is_pay
        db = self._exec_payment(db, q, pay, stats)
        db = self._exec_neworder(db, q, neworder, order, stats, level_exec)
        if "write_scatter_lanes" in stats:
            # lanes handed to a scatter, a scatter_add or an append, by
            # call: a pass hands each all its lanes whatever its mask
            # — Payment's three accumulator rows and its HISTORY row,
            # NewOrder's D_NEXT_O_ID, ORDER and NEW-ORDER rows, and per
            # item the stock scatter, the stock adds and the ORDER-LINE
            # (an append scatters nothing: its own counters are
            # `workloads/base.APPEND_COUNTERS`; the formula is the one
            # `exec.write_lanes_per_epoch` was accepted with)
            n = q.w_id.shape[0]
            stats["write_scatter_lanes"] = stats["write_scatter_lanes"] + \
                jnp.uint32((6 + bool(self.cfg.wh_update)) * n
                           + 3 * n * self.ipt)
        return db

    def _exec_payment(self, db, q, m, stats):
        """run_payment_0..5 (`tpcc_txn.cpp:472-`): YTD/balance updates are
        commutative -> exact batched scatter_add.  Partitioned: each row
        component lands only on its owner (remote slots resolve to trash),
        so a cross-warehouse payment splits naturally across nodes."""
        amt = jnp.where(m, q.h_amount, 0.0)
        hist_row = {"H_C_ID": q.c_id, "H_C_D_ID": q.c_d_id,
                    "H_C_W_ID": q.c_w_id, "H_D_ID": q.d_id,
                    "H_W_ID": q.w_id, "H_AMOUNT": q.h_amount}
        if self.full_schema:
            n = q.w_id.shape[0]
            hist_row["H_DATE"] = jnp.full((n,), 2013, jnp.int32)
            hist_row["H_DATA"] = (
                _field_bytes(q.c_id, q.w_id, _DIST_INFO_BYTES)
                if self.full_row else
                q.c_id.astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
        with jax.named_scope("ep.write"):
            if self.cfg.wh_update:
                db["WAREHOUSE"] = db["WAREHOUSE"].scatter_add(
                    self.wh_slot(q.w_id), {"W_YTD": amt}, mask=m)
            db["DISTRICT"] = db["DISTRICT"].scatter_add(
                self.dist_slot(q.w_id, q.d_id), {"D_YTD": amt}, mask=m)
            ck = self.cust_slot(q.c_w_id, q.c_d_id, q.c_id)
            db["CUSTOMER"] = db["CUSTOMER"].scatter_add(
                ck, {"C_BALANCE": -amt, "C_YTD_PAYMENT": amt,
                     "C_PAYMENT_CNT": m.astype(jnp.int32)}, mask=m)
            m_h = m & self.wh_owned(q.w_id)
            db["HISTORY"], _ = db["HISTORY"].append(
                hist_row, m_h, anchor=q.w_id, stats=stats)
        # W_YTD + D_YTD + 3 customer cols + HISTORY row per payment
        stats["write_cnt"] = stats["write_cnt"] + \
            (m.sum() * 6).astype(jnp.uint32)
        return db

    def _exec_neworder(self, db, q, m, order, stats,
                       level_exec: bool = False):
        """new_order_0..9 (`tpcc_txn.cpp:`): O_ID allocation is a
        per-district segmented prefix sum over the committed batch in
        serialization order — D_NEXT_O_ID++ under the row latch, batched."""
        n = q.w_id.shape[0]
        I = self.ipt
        dist = db["DISTRICT"]
        dk = self.dist_key(q.w_id, q.d_id)          # global (segment id)
        dslot = self.dist_slot(q.w_id, q.d_id)      # local (storage)
        owned = self.wh_owned(q.w_id)
        bcast = lambda x: jnp.broadcast_to(x[:, None], (n, I)).reshape(-1)  # noqa: E731
        iv = (q.item_valid & m[:, None]).reshape(-1)
        sk = self.stock_slot(q.supply_w, q.items).reshape(-1)
        qty = q.quantity.reshape(-1)
        stock = db["STOCK"]

        with jax.named_scope("ep.read"):
            # taxes / discount reads feed the checksum (keeps gathers
            # alive)
            w_tax = db["WAREHOUSE"].gather(self.wh_slot(q.w_id),
                                           ("W_TAX",))["W_TAX"]
            d = dist.gather(dslot, ("D_TAX", "D_NEXT_O_ID"))
            c_disc = db["CUSTOMER"].gather(
                self.cust_slot(q.w_id, q.d_id, q.c_id),
                ("C_DISCOUNT",))["C_DISCOUNT"]
            # per-lane integer conversion BEFORE the sum: uint32 addition
            # is associative, so the multi-chip psum of per-chip partial
            # sums is bit-identical to the single-chip value (mc.py
            # contract) — a float sum would round differently per
            # reduction order
            stats["read_checksum"] = stats["read_checksum"] + jnp.sum(
                jnp.where(m, (w_tax + d["D_TAX"] + c_disc) * 1000, 0)
                .astype(jnp.uint32), dtype=jnp.uint32)
            s_q = stock.gather(sk, ("S_QUANTITY",))["S_QUANTITY"]
            if self.full_schema:
                price = jnp.take(db["ITEM"].columns["I_PRICE"],
                                 jnp.clip(q.items, 0, self.max_items - 1),
                                 axis=0).reshape(-1)
            if self.full_row:
                # the line's OL_DIST_INFO: the stock row's S_DIST_<d_id>
                # (new_order_8 copies it), one 24 B cell a line
                cell = jnp.where((sk < 0) | (sk > stock.capacity),
                                 stock.capacity, sk) * 10 + bcast(q.d_id)
                dist_info = jnp.take(stock.columns[S_DIST], cell, axis=0)

        with jax.named_scope("ep.oid"):
            # o_id = snapshot next_o_id + rank among committed
            # same-district neworders ordered by serialization order
            big = jnp.int32(jnp.iinfo(jnp.int32).max)
            # bounded segment id (masked rows share one trailing segment)
            # so the composite sort key stays within int32
            seg = jnp.where(m, dk, jnp.int32(self.n_districts))
            order_rank = jnp.argsort(jnp.argsort(jnp.where(m, order, big)))
            sort_key = seg * (2 * n) + order_rank.astype(jnp.int32)
            perm = jnp.argsort(sort_key)
            sorted_seg = jnp.take(seg, perm)
            new_segment = jnp.concatenate(
                [jnp.ones((1,), bool), sorted_seg[1:] != sorted_seg[:-1]])
            pos = jnp.arange(n) - jax.lax.cummax(
                jnp.where(new_segment, jnp.arange(n), 0))
            rank = jnp.zeros((n,), jnp.int32).at[perm].set(
                pos.astype(jnp.int32))
            o_id = d["D_NEXT_O_ID"] + rank

        # stock update (new_order_8): non-commutative quantity rule ->
        # gather/modify/last-writer scatter; S_REMOTE_CNT is scatter_add
        # strict: replenish at s_q - qty <= 10 (tpcc_txn.cpp new_order_8/9)
        new_q = jnp.where(s_q - qty > 10, s_q - qty, s_q - qty + 91)
        remote = (q.supply_w != q.w_id[:, None]).reshape(-1)
        adds = {"S_REMOTE_CNT": (iv & remote).astype(jnp.int32)}
        if self.full_schema:
            # full-spec stock bookkeeping (TPC-C §2.4.2.2: s_ytd +=
            # quantity, s_order_cnt++) — commutative scatter-adds
            adds["S_YTD"] = jnp.where(iv, qty, 0)
            adds["S_ORDER_CNT"] = iv.astype(jnp.int32)
        # inserts: ORDER, NEW-ORDER, ORDER-LINE (new_order_1 / _3 / _9) —
        # at the home warehouse's owner node only
        m_ins = m & owned
        all_local = jnp.all(~q.item_valid | (q.supply_w == q.w_id[:, None]),
                            axis=1)
        order_row = {"O_ID": o_id, "O_C_ID": q.c_id, "O_D_ID": q.d_id,
                     "O_W_ID": q.w_id, "O_ENTRY_D": jnp.full((n,), 2013),
                     "O_OL_CNT": q.ol_cnt,
                     "O_ALL_LOCAL": all_local.astype(jnp.int32)}
        if self.full_schema:
            order_row["O_CARRIER_ID"] = jnp.zeros((n,), jnp.int32)
        ol_m = (q.item_valid & m_ins[:, None]).reshape(-1)
        ol_row = {"OL_O_ID": bcast(o_id), "OL_D_ID": bcast(q.d_id),
                  "OL_W_ID": bcast(q.w_id),
                  "OL_NUMBER": jnp.broadcast_to(jnp.arange(I)[None], (n, I)
                                                ).reshape(-1),
                  "OL_I_ID": q.items.reshape(-1),
                  "OL_QUANTITY": qty}
        if self.full_schema:
            ol_row["OL_SUPPLY_W_ID"] = q.supply_w.reshape(-1)
            ol_row["OL_DELIVERY_D"] = jnp.zeros((n * I,), jnp.int32)
            ol_row["OL_AMOUNT"] = (qty * price).astype(jnp.float32)
            ol_row["OL_DIST_INFO"] = dist_info if self.full_row else (
                q.items.reshape(-1).astype(jnp.uint32)
                * jnp.uint32(2654435761))

        with jax.named_scope("ep.write"):
            db["DISTRICT"] = dist.scatter_add(
                dslot, {"D_NEXT_O_ID": m.astype(jnp.int32)}, mask=m)
            if level_exec:
                # chained sub-round: the level's committed set is stock-
                # conflict-free and item_valid dedups in-txn items, so
                # every valid lane IS the final writer — the scatter-max
                # tournament (4 full-table passes) is redundant
                win = iv
            else:
                worder = bcast(order)
                win = last_writer(jnp.where(iv, sk, stock.capacity), worder,
                                  iv, stock.capacity)
            stock = stock.scatter(
                sk, _live_rows({"S_QUANTITY": new_q}, win), mask=win)
            db["STOCK"] = stock.scatter_add(sk, adds, mask=iv)
            db["ORDER"], oslots = db["ORDER"].append(
                order_row, m_ins, anchor=q.w_id, stats=stats)
            if "ORDER_IDX" in db:
                # between-epoch batched merge into the dynamic ordered
                # index (one fused sort per epoch instead of per-key tree
                # descents)
                db["ORDER_IDX"] = db["ORDER_IDX"].insert(
                    self.order_index_key(q.w_id, q.d_id, o_id), oslots,
                    m_ins)
            db["NEW-ORDER"], _ = db["NEW-ORDER"].append(
                {"NO_O_ID": o_id, "NO_D_ID": q.d_id, "NO_W_ID": q.w_id},
                m_ins, anchor=q.w_id, stats=stats)
            db["ORDER-LINE"], _ = db["ORDER-LINE"].append(
                ol_row, ol_m, anchor=bcast(q.w_id), stats=stats)

        stats["write_cnt"] = stats["write_cnt"] + \
            (iv.sum() + m.sum() * 2).astype(jnp.uint32)
        return db


@functools.lru_cache(maxsize=None)
def _string_filler(donate: bool):
    """Jitted ``(col, salt, n, cells) -> col`` with rows ``[0, n * cells)``
    of a ``uint8[rows, size]`` column set to the bytes of (row //
    ``cells``, ``salt`` + row % ``cells``), in place where ``donate``."""
    @functools.partial(jax.jit, static_argnums=(2, 3),
                       donate_argnums=(0,) if donate else ())
    def fill(col, salt, n, cells):
        r = jnp.arange(col.shape[0], dtype=jnp.uint32)
        v = _field_bytes(r // jnp.uint32(cells),
                         salt + r % jnp.uint32(cells), col.shape[1])
        # (the trash and pad rows past the loaded ones stay zero)
        return jnp.where((r < n * cells)[:, None], v, col)
    return fill


def _live_rows(rows: dict, mask: jax.Array) -> dict:
    """``rows`` with the lanes outside ``mask`` zeroed.  A masked lane of
    a scatter lands in the table's trash slot (an append writes no
    masked lane: `storage/table.DeviceTable.append`), where which
    of the lanes is left standing is the compiler's choice: writing
    zeros there keeps the trash row at its load value whichever wins, so
    a table's bytes are a function of the committed stream alone (the
    per-column digests of `runtime/logger.state_digests` are compared
    with a serial reference's)."""
    return {n: jnp.where(mask.reshape(mask.shape + (1,) * (v.ndim - 1)),
                         v, jnp.zeros((), v.dtype))
            for n, v in rows.items()}


def _rand01(ids: jax.Array, salt: int) -> jax.Array:
    """Deterministic per-row uniform [0,1) for loader columns (device
    arithmetic; uint32 product keeps the low 32 bits, which is all the
    64-bit golden-ratio multiply contributed)."""
    h = (ids.astype(jnp.uint32) * jnp.uint32(0x7F4A7C15)
         + jnp.uint32(salt))
    # split so each half converts to f32 exactly; one rounding at the add
    hi = (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    lo = (h & 0xFF).astype(jnp.float32) * jnp.float32(2.0 ** -32)
    return hi + lo


def _mulmod(ids: jax.Array, mul: int, mod: int) -> jax.Array:
    """(ids * mul) % mod, bit-exact to the old int64 host loader without
    64-bit device math: (x*y) mod m == ((x mod m) * (y mod m)) mod m,
    and both reduced factors fit comfortably in 32 bits."""
    return ((ids.astype(jnp.uint32) % jnp.uint32(mod))
            * jnp.uint32(mul % mod) % jnp.uint32(mod)).astype(jnp.int32)
