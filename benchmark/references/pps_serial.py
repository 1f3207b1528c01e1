"""Plain serial reference for served PPS (Deneva's Product-Parts-Suppliers):
numpy only, nothing of the program.

The system under test logs the stream it admitted (one record per epoch:
the merged block of transactions and the mask of lanes that carry one).
This module reads that file with its own decoder, builds the five tables
from the loader's arithmetic, executes the committed transactions ONE
AFTER ANOTHER, and compares with what the server printed on its
`[device]` line: every leaf's digest (`column_digests`), the commit
count, the count of lanes that waited, and — because a third of the
transactions change no table — the checksum of what the committed reads
returned (`read_checksum`).  Each comparison is exact, limit 0.

Semantics held, as the configuration file states them:

* GETPARTBYPRODUCT / GETPARTBYSUPPLIER: read the anchor's ten mapping
  rows, then the ten part rows they name, at the transaction's own place
  in the serial order; each part row read adds its PART_AMOUNT (and, at
  full row width, the sum of its hundred string bytes) to the checksum;
  GETPART likewise for its one row;
* ORDERPRODUCT: the same walk over USES, then PART_AMOUNT -= 1 on each
  part; UPDATEPART: PART_AMOUNT += 100;
* UPDATEPRODUCTPART: USES.PART_KEY of the product's FIRST mapping row
  (row product x 10) := its part key;
* the deterministic order: an epoch after another, an epoch's
  transactions by (level, rank).
  - STALE RECONNAISSANCE: the server resolves a walk's part keys from
    the mapping as it stands when the epoch starts.  A walk over USES
    with an earlier-ranked active UPDATEPRODUCTPART of the same mapping
    row's product in its epoch holds keys that writer makes obsolete: it
    is NOT committed in that epoch, draws no edge, and comes back in a
    later record (Calvin's restart of a transaction whose reconnaissance
    went stale, as a rule of the batch).  The writer counts whether or
    not it commits itself.
  - LEVELS: of the rest, a transaction's level is the longest chain of
    earlier-ranked transactions of its epoch that it conflicts with.
    Two conflict when a row that one WRITES is a row the other reads, or
    writes other than by an escrow add: a look-up's part against an
    order's or UPDATEPART's add of it, a walk's mapping row against
    UPDATEPRODUCTPART's write of it, two writers of one mapping row;
    adds of one part commute and order nothing among themselves.  A
    chain deeper than ``exec_subrounds - 1`` waits for a later epoch.
    Rows are compared by the program's 32-bit identity (`row_ident`), so
    two rows of different tables that share one conflict too: a legal
    over-approximation that moves the serial order, hence restated;
* `stale_recon_commits`: every committed walk's part set, as the server
  resolved it from the epoch's snapshot, is the mapping as it stands at
  the walk's own place in the serial order — what the two rules above
  exist to guarantee, counted here rather than assumed.

What is the program's FORMAT, not its semantics, and is restated here:
the log framing, the wire layout of a transaction, the loader's value
laws (`map_part`, `field_bytes`), the row identity, the tables' row
padding, the one-leaf layout of a row's ten strings and the leaves'
names.
"""

from __future__ import annotations

import hashlib
import struct

import numpy as np

# ---- the command log, as the server writes it (little-endian) ---------
#   record: magic u32 | epoch i64 | blob_len u32 | active_len u32
#           | blob | active bits (np.packbits order)
#   blob:   epoch i64 | n u32 | ts i64[n]
#           | N u32 | W u32 | S u32 | tags i64[N] | keys i32[N,W]
#           | types i8[N,W] | scalars i32[N,S]
#   a transaction: scalars = txn_type, part_key, product_key,
#   supplier_key (keys and types are one column of zeros)
_FRAME = struct.Struct("<IqII")
_MAGIC = 0xDE7E7A10
_TS_HDR = struct.Struct("<qI")
_Q_HDR = struct.Struct("<III")

(GETPART, GETPRODUCT, GETSUPPLIER, GETPARTBYPRODUCT, GETPARTBYSUPPLIER,
 ORDERPRODUCT, UPDATEPRODUCTPART, UPDATEPART) = range(8)
WRITE = 1               # `read_log`'s mark of a lane that decrements parts
TID = {"PARTS": 20, "PRODUCTS": 21, "SUPPLIERS": 22, "USES": 23,
       "SUPPLIES": 24}
N_FIELDS, FIELD_BYTES = 10, 10
START_AMOUNT = 10000


def read_records(buf: bytes):
    """Yield (epoch, scalars int32[n, 4], active bool[n]) per complete
    record; stops at a torn tail."""
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
        s0 = q0 + _Q_HDR.size + 8 * n + 4 * n * w + n * w
        scal = np.frombuffer(buf, np.int32, n * s, s0).reshape(n, s)
        bits = np.frombuffer(buf, np.uint8, alen, b0 + blen)
        yield epoch, scal, np.unpackbits(bits)[:n].astype(bool)
        off = end


def read_log(buf: bytes):
    """(epoch, keys, types, active) per record, as `ycsb_serial.read_log`
    yields them: what `benchmark/control.py` walks to name a committed
    write — here a lane of type `WRITE` is an ORDERPRODUCT and its key
    the product whose parts it decrements."""
    for epoch, scal, active in read_records(buf):
        yield (epoch, scal[:, 2:3],
               (scal[:, 0:1] == ORDERPRODUCT).astype(np.int8) * WRITE,
               active)


# ---- the loader's value laws --------------------------------------------

def map_part(anchor, j, salt: int, n_parts: int) -> np.ndarray:
    """The part that mapping row (anchor, j) names at load (salt 1:
    USES, 2: SUPPLIES)."""
    h = (np.asarray(anchor).astype(np.int64) * 1000003
         + np.asarray(j).astype(np.int64) * 7919 + salt * 104729) % 2654435761
    return (h % n_parts).astype(np.int32)


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


def row_strings(n: int) -> np.ndarray:
    """uint8[n, 100]: the ten strings of rows 0 .. n - 1, side by side
    (string column j + 1 is bytes 10 j .. 10 j + 9)."""
    rows = np.arange(n, dtype=np.int64)[:, None]
    cols = np.arange(1, N_FIELDS + 1, dtype=np.int64)[None, :]
    return field_bytes(rows, cols, FIELD_BYTES).reshape(n, -1)


def row_ident(table, key) -> np.ndarray:
    """uint32: the identity the program orders rows by (``table``: a
    name, or an array of table ids beside ``key``)."""
    tid = TID[table] if isinstance(table, str) else table
    with np.errstate(over="ignore"):
        return (np.asarray(key).astype(np.uint32) * np.uint32(2654435761)) \
            ^ (np.asarray(tid).astype(np.uint32) * np.uint32(0x9E3779B9))


def padded_rows(n_rows: int) -> int:
    """Rows the server allocates: one trash row past the last, rounded up
    to a multiple of 64 (all zero: a masked lane writes zeros there)."""
    return -(-(n_rows + 1) // 64) * 64


def _true(v) -> bool:
    return str(v).lower() in ("1", "true", "yes", "on")


class Sizes:
    def __init__(self, fields: dict):
        self.n_parts = int(fields.get("pps_parts_cnt", 10000))
        self.n_products = int(fields.get("pps_products_cnt", 1000))
        self.n_suppliers = int(fields.get("pps_suppliers_cnt", 1000))
        self.per = int(fields.get("pps_parts_per", 10))
        self.max_level = int(fields.get("exec_subrounds", 4)) - 1
        self.full_row = _true(fields.get("sim_full_row", False))
        self.escrow = _true(fields.get("escrow_order_free", True))


class SerialTables:
    """What the transactions change (PART_AMOUNT, the two mappings'
    PART_KEY — SUPPLIES' never, but a walk reads it) and what they
    count."""

    def __init__(self, sz: Sizes):
        self.sz = sz
        self.amount = np.full(sz.n_parts, START_AMOUNT, np.int64)
        u = np.arange(sz.n_products * sz.per)
        self.uses = map_part(u // sz.per, u % sz.per, 1, sz.n_parts)
        s = np.arange(sz.n_suppliers * sz.per)
        self.supplies = map_part(s // sz.per, s % sz.per, 2, sz.n_parts)
        # what a read of a part row returns beside its PART_AMOUNT
        self.row_extra = row_strings(sz.n_parts).sum(axis=1, dtype=np.int64) \
            if sz.full_row else np.zeros(sz.n_parts, np.int64)
        self.checksum = 0
        self.commits = self.deferred = self.recon_deferred = 0
        self.stale_commits = 0

    def read(self, parts) -> None:
        self.checksum += int((self.amount[parts] + self.row_extra[parts]
                              ).sum())


# ---- an epoch's order ----------------------------------------------------

def epoch_plan(sz: Sizes, tab: SerialTables, scal, active,
               stale_rule: bool = True):
    """(stale bool[n], level int32[n], planned {txn: int array of the
    part keys the server resolved for its walk}) of one record, from the
    mapping as it stands when the epoch starts (the module's docstring
    has both rules).  ``stale_rule`` off (the `stale_commits` fault): the
    stale lanes are named all the same, but stay in the batch and draw
    their edges."""
    n, per = len(active), sz.per
    kind, part, product, supplier = (scal[:, i].astype(np.int64)
                                     for i in range(4))
    lane = np.arange(per)
    by_prod = (kind == GETPARTBYPRODUCT) | (kind == ORDERPRODUCT)
    walks = (by_prod | (kind == GETPARTBYSUPPLIER)) & active
    remaps = (kind == UPDATEPRODUCTPART) & active
    urow = product[:, None] * per + lane[None, :]
    srow = supplier[:, None] * per + lane[None, :]
    map_id = np.where(by_prod[:, None], row_ident("USES", urow),
                      row_ident("SUPPLIES", srow))
    parts = np.where(by_prod[:, None], tab.uses[urow], tab.supplies[srow])
    planned = {int(t): parts[t] for t in np.flatnonzero(walks)}
    wrow_id = row_ident("USES", product * per)      # an update's one write

    # stale: an earlier active writer of one of my mapping rows
    stale = np.zeros(n, bool)
    first_writer: dict[int, int] = {}
    for t in np.flatnonzero(remaps):
        first_writer.setdefault(int(wrow_id[t]), int(t))
    if first_writer:
        for t in np.flatnonzero(walks):
            stale[t] = any(first_writer.get(int(x), n) < t
                           for x in map_id[t])
    live = active & ~stale if stale_rule else active

    # every access of the live lanes: (identity, txn, writes, ordered)
    adds = kind == UPDATEPART
    on_part = (kind == GETPART) | adds
    on_supp = (kind == GETSUPPLIER) | (kind == GETPARTBYSUPPLIER)
    anchor_id = row_ident(
        np.where(on_part, TID["PARTS"],
                 np.where(on_supp, TID["SUPPLIERS"], TID["PRODUCTS"])),
        np.where(on_part, part, np.where(on_supp, supplier, product)))
    txn = np.arange(n)
    orders = kind == ORDERPRODUCT
    w_live, r_live = walks & live, remaps & live
    wide = lambda m: np.broadcast_to(m[:, None], (n, per))   # noqa: E731
    ident = np.concatenate([
        anchor_id[live], map_id[w_live].ravel(),
        row_ident("PARTS", parts[w_live]).ravel(), wrow_id[r_live]])
    who = np.concatenate([
        txn[live], wide(txn)[w_live].ravel(), wide(txn)[w_live].ravel(),
        txn[r_live]])
    writes = np.concatenate([
        adds[live], np.zeros(w_live.sum() * per, bool),
        wide(orders)[w_live].ravel(), np.ones(r_live.sum(), bool)])
    # an escrow add orders nothing against another add
    escrow = np.concatenate([
        adds[live], np.zeros(w_live.sum() * per, bool),
        wide(orders)[w_live].ravel(), np.zeros(r_live.sum(), bool)])
    ordered = ~escrow if sz.escrow else np.ones(len(ident), bool)

    level = np.zeros(n, np.int32)
    if not len(ident):
        return stale, level, planned
    by = np.argsort(ident, kind="stable")
    ident, who, writes, ordered = ident[by], who[by], writes[by], ordered[by]
    start = np.flatnonzero(np.concatenate([[True], ident[1:] != ident[:-1]]))
    size = np.diff(np.concatenate([start, [len(ident)]]))
    hot = (size > 1) & np.logical_or.reduceat(writes, start)
    before: dict[int, set] = {}
    for s0, k in zip(start[hot], size[hot]):
        t, w, o = who[s0:s0 + k], writes[s0:s0 + k], ordered[s0:s0 + k]
        for a in range(k):
            for b in range(k):
                # a's ordered access meets b's write, or the other way
                if t[a] < t[b] and ((o[a] and w[b]) or (o[b] and w[a])):
                    before.setdefault(int(t[b]), set()).add(int(t[a]))
    for j in sorted(before):            # rank order: earlier ones are final
        level[j] = 1 + max(level[i] for i in before[j])
    return stale, level, planned


# ---- the serial execution --------------------------------------------------

def replay(log: bytes, sz: Sizes, fault: dict | None = None
           ) -> tuple[SerialTables, dict]:
    """Execute the whole log serially.  ``fault`` breaks ONE guarantee on
    the reference's side, so that the comparison must fail (the control
    and the tests): {"stale_commits": True} — a walk whose
    reconnaissance is stale commits in its epoch anyway, on the part set
    of the snapshot; {"swapped_lookup_order": True} — the last look-up
    that reads a part which a later-levelled ORDERPRODUCT of its epoch
    decrements runs after that order instead of before it;
    {"lost_mapping_write": True} — the last UPDATEPRODUCTPART that
    changes its row never lands; {"lost_part_write": product} — the
    first decrement of the last committed ORDERPRODUCT of that product
    never lands."""
    fault = fault or {}
    tab = SerialTables(sz)
    per = sz.per
    records = list(read_records(log))
    # the faults name the LAST place they apply to: found on a sound
    # pass over the log first
    target = None
    if fault.keys() & {"swapped_lookup_order", "lost_mapping_write",
                       "lost_part_write"}:
        target = _fault_target(records, sz, fault)
    for e, (_epoch, scal, active) in enumerate(records):
        stale, level, planned = epoch_plan(
            sz, tab, scal, active, not fault.get("stale_commits"))
        # (the fault: as a program without the rule, the stale walks
        # stay in the batch and run on the snapshot's keys)
        on_snapshot = stale & bool(fault.get("stale_commits"))
        stale = stale & ~on_snapshot
        commit = active & ~stale & (level <= sz.max_level)
        tab.recon_deferred += int(stale.sum())
        tab.deferred += int((active & ~commit).sum())
        order = [int(t) for t in np.lexsort((np.arange(len(active)), level))
                 if commit[t]]
        if target and target[0] == "swap" and target[1] == e:
            a, b = order.index(target[2]), order.index(target[3])
            order[a], order[b] = order[b], order[a]
        for t in order:
            kind, part, product, supplier = (int(x) for x in scal[t])
            tab.commits += 1
            if kind in (GETPARTBYPRODUCT, ORDERPRODUCT, GETPARTBYSUPPLIER):
                now = tab.uses[product * per:(product + 1) * per] \
                    if kind != GETPARTBYSUPPLIER \
                    else tab.supplies[supplier * per:(supplier + 1) * per]
                if (now != planned[t]).any():
                    tab.stale_commits += 1
                parts = planned[t] if on_snapshot[t] else now
                if kind == ORDERPRODUCT:
                    lost = target == ("part", e, t)
                    np.subtract.at(tab.amount, parts[1:] if lost else parts,
                                   1)
                else:
                    tab.read(parts)
            elif kind == UPDATEPRODUCTPART:
                if target != ("mapping", e, t):
                    tab.uses[product * per] = part
            elif kind == GETPART:
                tab.read(np.array([part]))
            elif kind == UPDATEPART:
                tab.amount[part] += 100
    return tab, dict(epochs=len(records), commits=tab.commits,
                     deferred=tab.deferred,
                     recon_deferred=tab.recon_deferred)


def _fault_target(records, sz: Sizes, fault: dict):
    """The last place of the log the fault applies to, found on a sound
    replay: ("swap", record, look-up, order) | ("mapping", record, txn)
    | ("part", record, txn)."""
    tab = SerialTables(sz)
    per = sz.per
    found = None
    for e, (_epoch, scal, active) in enumerate(records):
        stale, level, planned = epoch_plan(sz, tab, scal, active)
        commit = active & ~stale & (level <= sz.max_level)
        order = [int(t) for t in np.lexsort((np.arange(len(active)), level))
                 if commit[t]]
        if fault.get("swapped_lookup_order"):
            at = {t: i for i, t in enumerate(order)}
            looks = [t for t in order if scal[t, 0] == GETPARTBYPRODUCT]
            orders = [t for t in order if scal[t, 0] == ORDERPRODUCT]
            # (not across a rewrite of the look-up's own product: the
            # fault is a misplaced READ, nothing else)
            found = next((
                ("swap", e, lk, o) for o in reversed(orders)
                for lk in reversed(looks)
                if level[lk] < level[o]
                and np.intersect1d(planned[lk], planned[o]).size
                and not any(scal[t, 0] == UPDATEPRODUCTPART
                            and scal[t, 2] == scal[lk, 2]
                            for t in order[at[lk]:at[o]])), found)
        for t in order:
            kind, part, product, _s = (int(x) for x in scal[t])
            if kind == UPDATEPRODUCTPART:
                if fault.get("lost_mapping_write") \
                        and tab.uses[product * per] != part:
                    found = ("mapping", e, t)
                tab.uses[product * per] = part
            elif kind == ORDERPRODUCT \
                    and fault.get("lost_part_write", -1) == product:
                found = ("part", e, t)
    return found


# ---- the tables as the server lays them out --------------------------------

def columns(sz: Sizes, tab: SerialTables) -> dict[str, np.ndarray]:
    """{leaf path as the server names it: the leaf}, every leaf of the
    five tables."""
    out: dict[str, np.ndarray] = {}

    def put(table, n, cols, strings):
        rows = padded_rows(n)
        for name, v in cols.items():
            col = np.zeros(rows, np.int32)
            col[:n] = v
            out[f"{table}.columns.{name}"] = col
        if strings and sz.full_row:
            col = np.zeros((rows, N_FIELDS * FIELD_BYTES), np.uint8)
            col[:n] = row_strings(n)
            out[f"{table}.columns.FIELDS"] = col
        elif strings:       # one fingerprint word a string, never filled
            for j in range(1, N_FIELDS + 1):
                out[f"{table}.columns.FIELD{j}"] = np.zeros(rows, np.uint32)
        out[f"{table}.row_cnt"] = np.int32(n)

    put("PARTS", sz.n_parts, dict(PART_KEY=np.arange(sz.n_parts),
                                  PART_AMOUNT=tab.amount), True)
    put("PRODUCTS", sz.n_products,
        dict(PRODUCT_KEY=np.arange(sz.n_products)), True)
    put("SUPPLIERS", sz.n_suppliers,
        dict(SUPPLIER_KEY=np.arange(sz.n_suppliers)), True)
    n_u, n_s = sz.n_products * sz.per, sz.n_suppliers * sz.per
    put("USES", n_u, dict(PRODUCT_KEY=np.arange(n_u) // sz.per,
                          PART_KEY=tab.uses), False)
    put("SUPPLIES", n_s, dict(SUPPLIER_KEY=np.arange(n_s) // sz.per,
                              PART_KEY=tab.supplies), False)
    return out


def digests(cols: dict) -> dict[str, str]:
    return {name: hashlib.sha256(
        np.ascontiguousarray(v).reshape(-1).view(np.uint8)).hexdigest()
        for name, v in cols.items()}


def verify(log: bytes, fields: dict, server_info: dict,
           verdicts=None, drop_key: int | None = None,
           fault: dict | None = None
           ) -> tuple[list[tuple[str, float, float]], dict]:
    """The comparison that decides `correct` for a PPS configuration:
    ([(what, value, limit)], notes), each an exact comparison (limit 0).

    * ``digest_mismatch``: leaves of the five tables whose sha256 on the
      chip (`column_digests`) differs from this module's serial
      execution of the logged stream, or is missing on either side; the
      notes name the first;
    * ``commit_count_gap`` / ``defer_count_gap``: the server's whole-run
      commit count and count of lanes sent back (`run_defer_cnt`)
      against the reference's, by the two rules;
    * ``read_checksum_mismatch``: 1 when the server's `read_checksum`
      (uint32) is not the sum over every part row a committed look-up or
      GETPART read, in serial order;
    * ``stale_recon_commits``: committed walks whose part set from the
      epoch's snapshot is not the mapping at their serial position;
    * ``logged_epochs_missing``: 1 when the log holds no epoch.
    ``drop_key`` is `benchmark/control.py`'s fault (the product of the
    last logged ORDERPRODUCT: one decrement of its last committed order
    is lost); ``fault`` one of `replay`'s."""
    if verdicts is not None:
        raise ValueError("pps_serial derives the committed set itself")
    sz = Sizes(fields)
    if drop_key is not None:
        fault = {**(fault or {}), "lost_part_write": int(drop_key)}
    tab, res = replay(log, sz, fault)
    ours = digests(columns(sz, tab))
    chip = server_info.get("column_digests") or {}
    differ = sorted(n for n in set(ours) | set(chip)
                    if ours.get(n) != chip.get(n))
    out = [("digest_mismatch", float(len(differ)), 0.0),
           ("commit_count_gap",
            float(abs(res["commits"] - int(server_info["run_commit_cnt"]))),
            0.0),
           ("defer_count_gap",
            float(abs(res["deferred"]
                      - int(server_info.get("run_defer_cnt", -1)))), 0.0),
           ("read_checksum_mismatch",
            0.0 if tab.checksum & 0xFFFFFFFF
            == server_info.get("read_checksum") else 1.0, 0.0),
           ("stale_recon_commits", float(tab.stale_commits), 0.0),
           ("logged_epochs_missing", 0.0 if res["epochs"] else 1.0, 0.0)]
    return out, dict(epochs=res["epochs"], commits=res["commits"],
                     deferred=res["deferred"],
                     recon_deferred=res["recon_deferred"],
                     leaves=len(ours), first_differing=differ[:3],
                     read_checksum=tab.checksum & 0xFFFFFFFF)
