"""PPS — Product-Parts-Suppliers (reference `benchmarks/pps_wl.cpp`,
`pps_query.cpp`, `pps_txn.cpp`).

Five tables (`benchmarks/PPS_schema.txt`): PARTS (10k), PRODUCTS (1k),
SUPPLIERS (1k), USES (product -> 10 parts), SUPPLIES (supplier -> 10
parts).  Eight transaction types mixed by ``perc_*`` config
(`config.h:235-242`):

  GETPART / GETPRODUCT / GETSUPPLIER    — one-row reads
  GETPARTBYPRODUCT / GETPARTBYSUPPLIER — secondary-index walks: read the
      anchor row, the 10 USES/SUPPLIES mapping rows, then the referenced
      part rows (`pps_txn.cpp:729-808,893-960`)
  ORDERPRODUCT    — the mapping walk, then PART_AMOUNT -= 1 on each used
      part (`pps_txn.cpp:962-973` run_orderproduct_5)
  UPDATEPRODUCTPART — rewrite one product-to-part mapping: USES.PART_KEY
      of the product's first mapping row (`pps_txn.cpp:975-982`
      set_value(1, part_key): field 1 of a USES row is PART_KEY)
  UPDATEPART      — PART_AMOUNT += 100 (`pps_txn.cpp:997-1006`)

**The recon path** (SURVEY §7: the most exotic reference machinery): under
Calvin the part keys behind a product are unknown until USES is read, so
the sequencer pre-runs a reconnaissance txn and restarts the real txn with
the keys filled in (`system/sequencer.cpp:88-115`, `:239-257`).  Here every
transaction's RW-set is planned against the epoch snapshot: ``plan``
*gathers* the USES/SUPPLIES mapping rows on device and declares the
resolved part rows in the same RW-set — reconnaissance is one gather.
The mapping IS written (UPDATEPRODUCTPART), so reconnaissance can go
STALE: a walk planned from the snapshot whose product an earlier
transaction of the same epoch rewrites holds part keys that are no longer
the product's.  ``plan`` marks the mapping reads as the accesses its
other keys came out of (`cc.Recon`), and `cc/base.stale_recon` defers
such a lane whole under the backends that would run it after the writer
(CALVIN / TPU_BATCH); it is planned again from the snapshot of the epoch
that readmits it — the sequencer's restart, as a rule of the batch.  The
mapping reads and the mapping write are declared CC accesses (exactly the
rows the reference locks), so a validating backend loses the stale reader
to its own read-write test, and a LATER-ranked writer is ordered after
the walk by the levels.

TPU shape: all primary keys are dense -> free `DenseIndex`; the nonunique
USES/SUPPLIES indexes (count-suffixed probes `pps_txn.cpp:755-768`) are
dense [anchor*10 + j] layouts — the index walk is an affine gather.  With
``sim_full_row`` the three anchor tables hold their rows at the schema's
widths: a row's ten 10-byte strings are ONE ``uint8[rows, 100]`` leaf
(`FIELDS`; bytes 10 j .. 10 j + 9 = FIELD<j+1> by the byte law of (row,
column), `workloads/ycsb._field_bytes`), so that a read of a row is one
row gather and not ten, and a part row read by a walk or a GETPART folds
its bytes into ``read_checksum`` beside PART_AMOUNT.

Scopes: ``ep.recon`` (the mapping gather of ``plan``), ``ep.read`` (the
walk at execution: mapping rows, part rows), ``ep.write`` (the part adds,
the mapping scatter).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from deneva_tpu.cc.base import Recon
from deneva_tpu.config import Config
from deneva_tpu.ops import last_writer
from deneva_tpu.storage.catalog import parse_schema
from deneva_tpu.workloads.base import partition_owned, partition_slot
from deneva_tpu.storage.table import (DeviceTable, fill_columns, padded_rows,
                                      to_mc_layout)
from deneva_tpu.workloads.ycsb import _field_bytes

_FIELDS = "".join(f"\t10,string,FIELD{i}\n" for i in range(1, 11))
PPS_SCHEMA = (
    "TABLE=PARTS\n\t8,int64_t,PART_KEY\n\t8,int64_t,PART_AMOUNT\n" + _FIELDS
    + "TABLE=PRODUCTS\n\t8,int64_t,PRODUCT_KEY\n" + _FIELDS
    + "TABLE=SUPPLIERS\n\t8,int64_t,SUPPLIER_KEY\n" + _FIELDS
    + "TABLE=USES\n\t8,int64_t,PRODUCT_KEY\n\t8,int64_t,PART_KEY\n"
    + "TABLE=SUPPLIES\n\t8,int64_t,SUPPLIER_KEY\n\t8,int64_t,PART_KEY\n")

TID = {"PARTS": 20, "PRODUCTS": 21, "SUPPLIERS": 22, "USES": 23,
       "SUPPLIES": 24}
# full-width rows: a row's ten strings, one leaf (the module docstring)
FIELDS, N_FIELDS, FIELD_BYTES = "FIELDS", 10, 10

(GETPART, GETPRODUCT, GETSUPPLIER, GETPARTBYPRODUCT, GETPARTBYSUPPLIER,
 ORDERPRODUCT, UPDATEPRODUCTPART, UPDATEPART) = range(8)


@dataclass
class PPSQuery:
    """One epoch of PPS queries (reference `PPSQuery`,
    `benchmarks/pps_query.cpp:40-120`); part_keys recon happens in plan."""

    txn_type: jax.Array      # int32[n] 0..7
    part_key: jax.Array      # int32[n]
    product_key: jax.Array   # int32[n]
    supplier_key: jax.Array  # int32[n]


jax.tree_util.register_dataclass(
    PPSQuery,
    data_fields=["txn_type", "part_key", "product_key", "supplier_key"],
    meta_fields=[])


class PPSWorkload:
    txn_type_names = ("pps_getpart", "pps_getproduct", "pps_getsupplier",
                      "pps_getpartbyproduct", "pps_getpartbysupplier",
                      "pps_orderproduct", "pps_updateproductpart",
                      "pps_updatepart")

    # `[summary]` sums over the types, as the server prints them
    # (`<name>_commit_cnt`): the walks that read, the walk that orders,
    # the mapping writer — what `pps_epoch_bytes` counts bytes for
    commit_groups = {
        "pps_lookup": (GETPARTBYPRODUCT, GETPARTBYSUPPLIER),
        "pps_order": (ORDERPRODUCT,),
        "pps_update": (UPDATEPRODUCTPART,)}

    def txn_type_of(self, q: PPSQuery) -> jax.Array:
        return q.txn_type

    def __init__(self, cfg: Config):
        self.cfg = cfg
        self.catalog = parse_schema(PPS_SCHEMA)
        self.full_row = cfg.sim_full_row
        self.n_parts = cfg.pps_parts_cnt
        self.n_products = cfg.pps_products_cnt
        self.n_suppliers = cfg.pps_suppliers_cnt
        self.per = cfg.pps_parts_per        # MAX_PPS_PART_PER_PRODUCT (config.h:230)
        # the mapping rows of a walk are where its part keys come from,
        # and UPDATEPRODUCTPART's write of one sits in the first of
        # those columns (`plan`)
        self.recon = Recon(reads=tuple(range(1, 1 + self.per)), writes=(1,))
        # partitioned deployment: PARTS/PRODUCTS/SUPPLIERS stripe by
        # key % part_cnt; the USES/SUPPLIES mapping tables are
        # replicated on every node, which is what lets on-device recon
        # (`plan`) stay local — the reference instead ships recon
        # results through the sequencer (`system/sequencer.cpp:88-115`).
        # Every holder of a copy sees the whole merged batch and applies
        # each committed mapping write to its own (`execute`)
        self.n_pt = max(cfg.part_cnt, 1)
        self.me = cfg.node_id if self.n_pt > 1 else 0
        for nm, n in (("pps_parts_cnt", self.n_parts),
                      ("pps_products_cnt", self.n_products),
                      ("pps_suppliers_cnt", self.n_suppliers)):
            if n % self.n_pt != 0:
                raise ValueError(f"{nm} must divide evenly over part_cnt")
        self.n_parts_loc = self.n_parts // self.n_pt
        self.n_products_loc = self.n_products // self.n_pt
        self.n_suppliers_loc = self.n_suppliers // self.n_pt
        need = 1 + 2 * self.per
        if cfg.max_accesses < need:
            raise ValueError(f"PPS needs max_accesses >= {need}")
        # txn-type mix (config.h:235-242); order matches the enum
        self.mix = np.array([
            cfg.perc_getparts, cfg.perc_getproducts, cfg.perc_getsuppliers,
            cfg.perc_getpartbyproduct, cfg.perc_getpartbysupplier,
            cfg.perc_orderproduct, cfg.perc_updateproductpart,
            cfg.perc_updatepart], np.float64)
        assert abs(self.mix.sum() - 1.0) < 1e-6

    # -- local slots (partitioned storage addressing) --------------------
    def _owned(self, key):
        return partition_owned(key, self.n_pt, self.me)

    def _slot(self, key, n_local):
        return partition_slot(key, self.n_pt, self.me, n_local)

    def part_slot(self, key):
        return self._slot(key, self.n_parts_loc)

    # -- loader (pps_wl.cpp:71-111 threadInit*) -------------------------
    def load(self):
        db = {}
        p, me = self.n_pt, self.me

        def fill(name, cap, cols, ids=None):
            t = DeviceTable.create(self.catalog.table(name), cap,
                                   full_row=self.full_row)
            t = fill_columns(t, cap, cols)
            if self.full_row and ids is not None:
                # the ten string columns as ONE leaf of the row's bytes
                tcols = {n: v for n, v in t.columns.items()
                         if not n.startswith("FIELD")}
                tcols[FIELDS] = jnp.zeros(
                    (padded_rows(cap), N_FIELDS * FIELD_BYTES), jnp.uint8
                ).at[:cap].set(row_bytes(jnp.asarray(ids)))
                t = t._replace(columns=tcols)
            db[name] = t

        p_ids = me + p * np.arange(self.n_parts_loc, dtype=np.int32)
        fill("PARTS", self.n_parts_loc,
             {"PART_KEY": p_ids,
              "PART_AMOUNT": np.full(self.n_parts_loc, 10000, np.int32)},
             p_ids)
        pr_ids = me + p * np.arange(self.n_products_loc, dtype=np.int32)
        fill("PRODUCTS", self.n_products_loc, {"PRODUCT_KEY": pr_ids},
             pr_ids)
        s_ids = me + p * np.arange(self.n_suppliers_loc, dtype=np.int32)
        fill("SUPPLIERS", self.n_suppliers_loc, {"SUPPLIER_KEY": s_ids},
             s_ids)

        # mapping tables: row (anchor*per + j) -> part (pps_wl.cpp uses
        # URand parts per anchor; here a deterministic hash map)
        u = np.arange(self.n_products * self.per, dtype=np.int32)
        fill("USES", len(u),
             {"PRODUCT_KEY": u // self.per,
              "PART_KEY": _map_part(u // self.per, u % self.per, 1,
                                    self.n_parts)})
        s = np.arange(self.n_suppliers * self.per, dtype=np.int32)
        fill("SUPPLIES", len(s),
             {"SUPPLIER_KEY": s // self.per,
              "PART_KEY": _map_part(s // self.per, s % self.per, 2,
                                    self.n_parts)})
        D = self.cfg.device_parts
        if D > 1:
            # anchor keys stripe across chips; the USES/SUPPLIES mapping
            # tables replicate (what keeps recon local, see above): every
            # chip writes its copy alike, exactly like the multi-process
            # deployment
            for name in ("PARTS", "PRODUCTS", "SUPPLIERS"):
                db[name] = to_mc_layout(db[name], D)
            for name in ("USES", "SUPPLIES"):
                db[name] = db[name]._replace(mc_replicated=True)
        return db

    # -- generation (pps_query.cpp:40-120) ------------------------------
    def generate(self, rng: jax.Array, n: int) -> PPSQuery:
        k0, k1, k2, k3 = jax.random.split(rng, 4)
        cum = jnp.asarray(np.cumsum(self.mix), jnp.float32)
        r = jax.random.uniform(k0, (n,))
        txn_type = jnp.sum(r[:, None] >= cum[None, :], axis=1
                           ).astype(jnp.int32)
        return PPSQuery(
            txn_type=jnp.clip(txn_type, 0, 7),
            part_key=jax.random.randint(k1, (n,), 0, self.n_parts),
            product_key=jax.random.randint(k2, (n,), 0, self.n_products),
            supplier_key=jax.random.randint(k3, (n,), 0, self.n_suppliers))

    # -- wire adapters (distributed runtime) -----------------------------
    # all four query fields are per-txn scalars; no per-access columns
    def to_wire(self, q: PPSQuery):
        n = int(q.txn_type.shape[0])
        s = np.stack([np.asarray(q.txn_type, np.int32),
                      np.asarray(q.part_key, np.int32),
                      np.asarray(q.product_key, np.int32),
                      np.asarray(q.supplier_key, np.int32)], axis=1)
        return (np.zeros((n, 1), np.int32), np.zeros((n, 1), np.int8), s)

    def from_wire(self, keys: np.ndarray, types: np.ndarray,
                  scalars: np.ndarray) -> PPSQuery:
        scalars = np.ascontiguousarray(scalars, np.int32)
        return PPSQuery(txn_type=jnp.asarray(scalars[:, 0]),
                        part_key=jnp.asarray(scalars[:, 1]),
                        product_key=jnp.asarray(scalars[:, 2]),
                        supplier_key=jnp.asarray(scalars[:, 3]))

    def from_wire_dev(self, keys, types, scalars) -> PPSQuery:
        """Traceable from_wire (cluster dispatch jit)."""
        return PPSQuery(txn_type=scalars[:, 0], part_key=scalars[:, 1],
                        product_key=scalars[:, 2],
                        supplier_key=scalars[:, 3])

    def _walk_parts(self, db, q: PPSQuery, by_prod) -> jax.Array:
        """int32[n, per]: the part keys a walk of each lane's product (or
        supplier) resolves to in ``db``.  A mapping table is dense,
        [anchor * per + j], so an anchor's keys are ONE row of ``per``
        numbers: a row gather of n lanes a table (n x per single-key
        gathers cost the chip 7 ns a lane, PERF.md section 6, PR 38)."""
        def rows(name, anchor):
            col = db[name].columns["PART_KEY"]
            n_anchor = db[name].capacity // self.per
            return jnp.take(col[:n_anchor * self.per].reshape(
                n_anchor, self.per), anchor, axis=0)
        return jnp.where(by_prod[:, None], rows("USES", q.product_key),
                         rows("SUPPLIES", q.supplier_key))

    # -- RW-set planning with on-device recon ---------------------------
    def plan(self, db, q: PPSQuery) -> dict:
        n = q.txn_type.shape[0]
        A = self.cfg.max_accesses
        t = q.txn_type
        per = self.per

        anchor_is_part = (t == GETPART) | (t == UPDATEPART)
        anchor_is_supp = (t == GETSUPPLIER) | (t == GETPARTBYSUPPLIER)
        by_prod = ((t == GETPARTBYPRODUCT) | (t == ORDERPRODUCT))
        walks = by_prod | (t == GETPARTBYSUPPLIER)
        remaps = t == UPDATEPRODUCTPART

        tables = jnp.zeros((n, A), jnp.int32)
        keys = jnp.zeros((n, A), jnp.int32)
        is_read = jnp.zeros((n, A), bool)
        is_write = jnp.zeros((n, A), bool)
        valid = jnp.zeros((n, A), bool)
        order_free = jnp.zeros((n, A), bool)
        owner = jnp.zeros((n, A), jnp.int32)
        p_nodes = jnp.int32(self.n_pt)

        # access 0: anchor row
        a_tid = jnp.where(anchor_is_part, TID["PARTS"],
                          jnp.where(anchor_is_supp, TID["SUPPLIERS"],
                                    TID["PRODUCTS"]))
        a_key = jnp.where(anchor_is_part, q.part_key,
                          jnp.where(anchor_is_supp, q.supplier_key,
                                    q.product_key))
        tables = tables.at[:, 0].set(a_tid)
        keys = keys.at[:, 0].set(a_key)
        is_read = is_read.at[:, 0].set(True)
        is_write = is_write.at[:, 0].set(t == UPDATEPART)
        valid = valid.at[:, 0].set(True)
        owner = owner.at[:, 0].set(a_key % p_nodes)
        # UPDATEPART is a pure escrow add (PART_AMOUNT += 100, no read
        # used): order_free — adds commute, while GETPART's accumulator
        # READ stays ordered against every add (base.build_incidence)
        order_free = order_free.at[:, 0].set(t == UPDATEPART)

        # accesses 1..per: USES/SUPPLIES mapping rows — a walk reads all
        # of its anchor's, UPDATEPRODUCTPART writes its product's first
        # (blind: it reads nothing of the row).  recon: gather the
        # referenced part keys from the snapshot
        lane = jnp.arange(per)
        uses = by_prod | remaps
        map_key = jnp.where(uses[:, None], q.product_key[:, None],
                            q.supplier_key[:, None]) * per + lane[None, :]
        map_tid = jnp.where(uses, TID["USES"], TID["SUPPLIES"])
        with jax.named_scope("ep.recon"):
            part_keys = self._walk_parts(db, q, by_prod)
        wmask = walks[:, None] & jnp.ones((n, per), bool)
        mw = remaps[:, None] & (lane == 0)[None, :]
        tables = tables.at[:, 1:1 + per].set(map_tid[:, None])
        keys = keys.at[:, 1:1 + per].set(map_key)
        is_read = is_read.at[:, 1:1 + per].set(wmask)
        is_write = is_write.at[:, 1:1 + per].set(mw)
        valid = valid.at[:, 1:1 + per].set(wmask | mw)
        # USES/SUPPLIES replicate; their accesses are validated at the
        # anchor's owner — the writer of a product's mapping row and
        # every walk of that product meet at one participant
        anchor = jnp.where(uses, q.product_key, q.supplier_key)
        owner = owner.at[:, 1:1 + per].set((anchor % p_nodes)[:, None])

        # accesses 1+per..1+2*per: resolved part rows
        pw = (t == ORDERPRODUCT)[:, None] & wmask
        tables = tables.at[:, 1 + per:1 + 2 * per].set(TID["PARTS"])
        keys = keys.at[:, 1 + per:1 + 2 * per].set(part_keys)
        is_read = is_read.at[:, 1 + per:1 + 2 * per].set(wmask)
        is_write = is_write.at[:, 1 + per:1 + 2 * per].set(pw)
        valid = valid.at[:, 1 + per:1 + 2 * per].set(wmask)
        # ORDERPRODUCT's part lanes are pure escrow decrements
        # (PART_AMOUNT -= 1; the declared read is vestigial): add-add
        # pairs need no ordering, GETPARTBY* reads of the same parts do
        order_free = order_free.at[:, 1 + per:1 + 2 * per].set(pw)
        owner = owner.at[:, 1 + per:1 + 2 * per].set(part_keys % p_nodes)

        return dict(table_ids=tables, keys=keys, is_read=is_read,
                    is_write=is_write, valid=valid, order_free=order_free,
                    owner=owner, recon=self.recon)

    # -- execution ------------------------------------------------------
    # a walk's part keys come out of a row that UPDATEPRODUCTPART
    # rewrites, so the single-pass forwarding executor does not apply
    blind_writes = False

    def execute(self, db, q: PPSQuery, mask: jax.Array, order: jax.Array,
                stats: dict, fwd_rank=None, level_exec: bool = False):
        db = dict(db)
        t = q.txn_type
        per = self.per
        n = t.shape[0]
        by_prod = (t == GETPARTBYPRODUCT) | (t == ORDERPRODUCT)
        lookup = mask & ((t == GETPARTBYPRODUCT) | (t == GETPARTBYSUPPLIER))

        with jax.named_scope("ep.read"):
            # the walk: the anchor's mapping rows as they stand now (a
            # lane that runs here is no stale one: the rows are what
            # `plan` read), then the part rows they name.  One gather
            # of PARTS serves GETPART's anchor (lane 0 of a row of
            # per + 1) and the walks' parts; the whole row is read
            parts = self._walk_parts(db, q, by_prod)
            pk = jnp.concatenate([q.part_key[:, None], parts], axis=1)
            rows = db["PARTS"].gather(
                self.part_slot(pk).reshape(-1),
                ("PART_AMOUNT",) + ((FIELDS,) if self.full_row else ()))
            val = rows["PART_AMOUNT"].astype(jnp.uint32)
            if self.full_row:
                val = val + rows[FIELDS].sum(axis=1, dtype=jnp.uint32)
            # reads feed the checksum (committed lanes only); remote
            # parts read the trash row and stay out of this node's stat
            use = jnp.concatenate(
                [(mask & (t == GETPART))[:, None],
                 lookup[:, None] & jnp.ones((n, per), bool)], axis=1)
            stats["read_checksum"] = stats["read_checksum"] + jnp.sum(
                jnp.where(use & self._owned(pk), val.reshape(n, per + 1), 0),
                dtype=jnp.uint32)

        with jax.named_scope("ep.write"):
            # ORDERPRODUCT: PART_AMOUNT -= 1 on each part of the product;
            # UPDATEPART: PART_AMOUNT += 100 (run_updatepart_1) — escrow
            # adds, one call (each node applies those of the rows it owns)
            om = mask & (t == ORDERPRODUCT)
            um = mask & (t == UPDATEPART)
            live = jnp.concatenate(
                [um[:, None], om[:, None] & jnp.ones((n, per), bool)],
                axis=1)
            delta = jnp.where(live, jnp.where(jnp.arange(per + 1) == 0,
                                              100, -1), 0)
            db["PARTS"] = db["PARTS"].scatter_add(
                self.part_slot(pk).reshape(-1),
                {"PART_AMOUNT": delta.reshape(-1)}, mask=live.reshape(-1))

            # UPDATEPRODUCTPART: the product's first mapping row names
            # another part (run_updateproductpart_1 set_value(1,
            # part_key)).  USES is replicated: every holder of a copy
            # applies the batch's writes to its own, whoever owns the
            # product.  A masked lane writes zeros: it lands in the
            # trash row, where which lane is left standing is the
            # compiler's choice (`workloads/tpcc._live_rows`)
            pm = mask & (t == UPDATEPRODUCTPART)
            urow = q.product_key * per
            cap = db["USES"].capacity
            if level_exec:
                # chained sub-round: committed set is write-conflict-free,
                # so each mapping row has at most one writer in this call
                win = pm
            else:
                win = last_writer(jnp.where(pm, urow, cap), order, pm, cap)
            db["USES"] = db["USES"].scatter(
                urow, {"PART_KEY": jnp.where(win, q.part_key, 0)}, mask=win)

        stats["write_cnt"] = stats["write_cnt"] + (
            (om.sum() * per) + um.sum() + pm.sum()).astype(jnp.uint32)
        # lanes handed to a gather / to a scatter or a scatter_add, by
        # call: a pass hands each all its lanes whatever its mask
        stats["read_gather_lanes"] = stats["read_gather_lanes"] + \
            jnp.uint32(n * (per + 3))
        stats["write_scatter_lanes"] = stats["write_scatter_lanes"] + \
            jnp.uint32(n * (per + 2))
        return db


def row_bytes(ids: jax.Array) -> jax.Array:
    """uint8[len(ids), 100]: the ten strings of rows ``ids`` — string
    column j + 1 of a row holds ``_field_bytes(row, j + 1, 10)``."""
    col = jnp.arange(1, N_FIELDS + 1, dtype=jnp.uint32)
    return _field_bytes(ids.astype(jnp.uint32)[:, None], col[None, :],
                        FIELD_BYTES).reshape(ids.shape[0], -1)


def _map_part(anchor, j, salt, n_parts) -> np.ndarray:
    """Deterministic anchor->part mapping for USES/SUPPLIES (the
    reference loader draws URand parts, pps_wl.cpp threadInitUses)."""
    h = (np.asarray(anchor).astype(np.int64) * 1000003 + np.asarray(j) * 7919
         + salt * 104729) % 2654435761
    return (h % n_parts).astype(np.int32)
