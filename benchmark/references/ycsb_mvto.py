"""Plain reference for served YCSB under multi-version timestamp ordering
(Deneva's MVCC, `concurrency_control/row_mvcc.{h,cpp}`): numpy only,
nothing of the program.

The system under test logs the stream it admitted: one record per epoch
with the merged block of transactions, the TIMESTAMP each carries and the
mask of lanes that hold one.  Who committed is replayed from that log
(`benchmark/verdicts_child.py`) and is not trusted: this module reads the
log with its own decoder (keys, types, active AND timestamps), takes the
committed masks, builds for every key its committed versions in
timestamp order, and holds the masks, the table, the version rings and —
because under this backend a READ is the mechanism — the bytes every
committed read returned to the rule below.  Each comparison is exact,
limit 0.

Semantics held, as the configuration file states them:

* a committed WRITE of a read-write transaction with timestamp t makes
  version t of its key (bytes ``field_bytes(key, t)``); after the log
  each key holds its committed version of the GREATEST timestamp;
* a committed READ of a read-write transaction with timestamp t returns
  the committed version of its key with the greatest timestamp below t
  (the load's version 0 where there is none) — never a write of its own
  transaction: a transaction's reads are of the state before its writes;
* a READ-ONLY transaction reads at the snapshot its epoch began with:
  every key's greatest committed version of the EARLIER epochs;
* `mvto_rule_violations` counts what would make a history other than the
  serial one in timestamp order:
  - a committed lane of a record's inactive slots;
  - a committed read (read-write transaction, timestamp t, epoch e) with
    a committed write of its key at a timestamp in (its version, t) in
    its OWN epoch — the reader should have waited for that writer — or
    in a LATER epoch — that write changes what a committed reader of a
    later timestamp has read and should not have committed;
  - a committed read whose version its row did not RETAIN when its
    epoch began — a row retains, of each epoch that wrote it, the
    version of the greatest timestamp, for the ``mvcc_his_len`` newest
    such epochs: it was served from beyond the retained history and
    should have aborted.

What is the program's FORMAT, not its semantics, and is restated here:
the log framing, the value law ``field_bytes`` (a pure function of key
and writer timestamp — the program's stand-in for a payload, and what
it recomputes an old version's bytes from: it holds no old bytes), the
table's row padding, the leaves' names, and the layout of the version
ring (``mvcc_his_len`` int32 timestamps a row, flat; an epoch's entry
takes the slot of the row's least entry, empty ones first; the trash row
past the table stays empty).
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
#           | types i8[N,W] (1 read, 2 write) | scalars i32[N,S]
_FRAME = struct.Struct("<IqII")
_MAGIC = 0xDE7E7A10
_TS_HDR = struct.Struct("<qI")
_Q_HDR = struct.Struct("<III")

FIELDS = 10             # F0..F9; requests touch F0 only
READ, WRITE = 1, 2
TABLE, RING = "MAIN_TABLE", "MAIN_TABLE.F0.ver.wts"


def read_records(buf: bytes):
    """Yield (epoch, ts int64[n], keys int32[n,W], types int8[n,W],
    active bool[n]) per complete record; stops at a torn tail."""
    off = 0
    while off + _FRAME.size <= len(buf):
        magic, epoch, blen, alen = _FRAME.unpack_from(buf, off)
        end = off + _FRAME.size + blen + alen
        if magic != _MAGIC or end > len(buf):
            return
        b0 = off + _FRAME.size
        _, n_ts = _TS_HDR.unpack_from(buf, b0)
        ts = np.frombuffer(buf, np.int64, n_ts, b0 + _TS_HDR.size)
        q0 = b0 + _TS_HDR.size + 8 * n_ts
        n, w, _s = _Q_HDR.unpack_from(buf, q0)
        if n != n_ts:
            raise ValueError(f"log record of epoch {epoch}: {n_ts} "
                             f"timestamps for {n} transactions")
        k0 = q0 + _Q_HDR.size + 8 * n
        keys = np.frombuffer(buf, np.int32, n * w, k0).reshape(n, w)
        types = np.frombuffer(buf, np.int8, n * w,
                              k0 + 4 * n * w).reshape(n, w)
        bits = np.frombuffer(buf, np.uint8, alen, b0 + blen)
        yield epoch, ts, keys, types, np.unpackbits(bits)[:n].astype(bool)
        off = end


def read_log(buf: bytes):
    """(epoch, keys, types, active) per record, as `ycsb_serial.read_log`
    yields them: what `benchmark/control.py` walks to name a committed
    write."""
    for epoch, _ts, keys, types, active in read_records(buf):
        yield epoch, keys, types, active


# ---- the value law and the leaves ---------------------------------------

def field_bytes(key, version, nbytes: int) -> np.ndarray:
    """uint8[..., nbytes]: the bytes a field holds after the writer with
    timestamp ``version`` wrote it (version 0 at load)."""
    k = np.asarray(key).astype(np.uint32)
    v = np.asarray(version).astype(np.uint32)
    with np.errstate(over="ignore"):
        fp = ((k * np.uint32(2654435761)) ^ (v * np.uint32(0x9E3779B9))) \
            | np.uint32(1)
        i = np.arange(nbytes, dtype=np.uint32)
        mixed = fp[..., None] * (i * np.uint32(2654435761)
                                 + np.uint32(0x9E3779B9))
    return ((mixed >> np.uint32(13)) & np.uint32(0xFF)).astype(np.uint8)


def padded_rows(n_rows: int) -> int:
    """Rows the server allocates: one trash row past the last, rounded up
    to a multiple of 64."""
    return -(-(n_rows + 1) // 64) * 64


def _row_sums(key: np.ndarray, version: np.ndarray, nbytes: int
              ) -> np.ndarray:
    """uint64[n]: the sum of the bytes of ``field_bytes(key, version)``,
    a chunk at a time."""
    out = np.empty(len(key), np.uint64)
    step = 1 << 16
    for lo in range(0, len(key), step):
        out[lo:lo + step] = field_bytes(
            key[lo:lo + step], version[lo:lo + step], nbytes
        ).sum(axis=-1, dtype=np.uint64)
    return out


def _sha(a: np.ndarray) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(a).reshape(-1).view(np.uint8)).hexdigest()


# ---- the history ----------------------------------------------------------

class History:
    """The log's committed accesses as flat arrays, and per key its
    committed versions in timestamp order.

    ``wk, wt, we``: key, timestamp and epoch of every committed version,
    sorted by (key, timestamp), one entry a (key, transaction).
    ``rk, rt, re, rro``: key, timestamp, epoch of every committed READ
    lane and whether its transaction is read-only."""

    def __init__(self, log: bytes, n_rows: int,
                 verdicts: dict[int, np.ndarray]):
        wk, wt, we, rk, rt, re_, rro = ([] for _ in range(7))
        self.epochs = self.commits = self.ro_commits = 0
        self.inactive_commits = self.waited = 0
        seen: set[int] = set()
        for epoch, ts, keys, types, active in read_records(log):
            if keys.size and (keys.min() < 0 or keys.max() >= n_rows):
                raise ValueError(f"epoch {epoch}: key outside [0, {n_rows})")
            if len(ts) and ts.max() >= 1 << 31:
                raise ValueError(f"epoch {epoch}: timestamp past 2^31")
            commit = np.asarray(verdicts[epoch], bool)
            self.inactive_commits += int((commit & ~active).sum())
            commit = commit & active
            # a transaction that waited comes back with the timestamp it
            # was born with; an aborted one with a fresh one
            live_ts = ts[active].tolist()
            self.waited += sum(1 for t in live_ts if t in seen)
            seen.update(live_ts)
            ro = ~(types == WRITE).any(axis=1)
            self.epochs += 1
            self.commits += int(commit.sum())
            self.ro_commits += int((commit & ro).sum())
            w = keys.shape[1]
            lane_ts = np.repeat(ts, w)
            wl = np.flatnonzero((commit[:, None] & (types == WRITE)).ravel())
            rl = np.flatnonzero((commit[:, None] & (types == READ)).ravel())
            wk.append(keys.ravel()[wl])
            wt.append(lane_ts[wl])
            we.append(np.full(len(wl), epoch, np.int64))
            rk.append(keys.ravel()[rl])
            rt.append(lane_ts[rl])
            re_.append(np.full(len(rl), epoch, np.int64))
            rro.append(np.repeat(ro, w)[rl])

        def cat(parts, dtype):
            return np.concatenate(parts).astype(dtype) if parts \
                else np.zeros(0, dtype)
        wk, wt, we = cat(wk, np.int64), cat(wt, np.int64), cat(we, np.int64)
        # one version a (key, transaction): two write lanes of one
        # transaction on one key store the same bytes
        comp, first = np.unique((wk << 32) | wt, return_index=True)
        self.wk, self.wt, self.we = wk[first], wt[first], we[first]
        self.w_comp = comp                      # sorted (key, timestamp)
        self.rk, self.rt = cat(rk, np.int64), cat(rt, np.int64)
        self.re, self.rro = cat(re_, np.int64), cat(rro, bool)
        # by (key, epoch, timestamp): the greatest timestamp a key has
        # over its versions of the earlier epochs is its LIVE version
        by_e = np.lexsort((self.wt, self.we, self.wk))
        self._e_comp = (self.wk[by_e] << 32) | self.we[by_e]
        self._e_key = self.wk[by_e]
        self._e_max = np.maximum.accumulate(
            (self.wk[by_e] << 32) | self.wt[by_e]) & 0xFFFFFFFF \
            if len(by_e) else np.zeros(0, np.int64)

    def live_version(self, key: np.ndarray, epoch: np.ndarray) -> np.ndarray:
        """int64[n]: the greatest committed timestamp of ``key`` among
        the versions of epochs before ``epoch`` (0: the load's)."""
        if not len(self._e_comp):
            return np.zeros(len(key), np.int64)
        pos = np.searchsorted(self._e_comp, (key << 32) | epoch, "left") - 1
        ok = (pos >= 0) & (self._e_key[np.maximum(pos, 0)] == key)
        return np.where(ok, self._e_max[np.maximum(pos, 0)], 0)

    def final_version(self) -> np.ndarray:
        """(keys, timestamps) of each written key's greatest version."""
        last = np.ones(len(self.wk), bool)
        last[:-1] = self.wk[1:] != self.wk[:-1]
        return self.wk[last], self.wt[last]


def select_versions(h: History, his_len: int) -> dict:
    """The version every committed read lane is owed, and what breaks
    the rule.  Returns {"version" int64[reads], "old" bool[reads] (a
    version other than the live one), "unwaited", "late_writes",
    "beyond_history"}."""
    n = len(h.rk)
    live = h.live_version(h.rk, h.re)
    version = live.copy()
    unwaited = late = beyond = 0
    rw = np.flatnonzero(~h.rro)
    version[rw] = 0
    if len(rw) and len(h.w_comp):
        k, t, e = h.rk[rw], h.rt[rw], h.re[rw]
        # the greatest version below the reader's timestamp, any epoch
        j = np.searchsorted(h.w_comp, (k << 32) | t, "left") - 1
        has = (j >= 0) & (h.wk[np.maximum(j, 0)] == k)
        jj = np.maximum(j, 0)
        ok = has & (h.we[jj] < e)
        version[rw] = np.where(ok, h.wt[jj], 0)
        # a version of the reader's own epoch or of a later one under
        # its timestamp: the rule is broken, and the reader is owed the
        # next one down that was there when its epoch began
        for i in np.flatnonzero(has & ~ok):
            p = int(j[i])
            while p >= 0 and h.wk[p] == k[i] and h.we[p] >= e[i]:
                if h.we[p] == e[i]:
                    unwaited += 1
                else:
                    late += 1
                p -= 1
            version[rw[i]] = h.wt[p] if p >= 0 and h.wk[p] == k[i] else 0
    old = version != live
    # an old version has to be one the row RETAINED when the reader's
    # epoch began: the greatest of an earlier epoch's versions of the
    # row, among the his_len newest such (the load's version while
    # fewer than his_len epochs have written the row)
    for i in np.flatnonzero(old):
        lo = np.searchsorted(h.w_comp, h.rk[i] << 32, "left")
        hi = np.searchsorted(h.w_comp, (h.rk[i] + 1) << 32, "left")
        before = h.we[lo:hi] < h.re[i]
        kept = retained(h.wt[lo:hi][before], h.we[lo:hi][before], his_len)
        if version[i] not in kept:
            beyond += 1
    return dict(version=version, old=old, unwaited=unwaited,
                late_writes=late, beyond_history=beyond, reads=n)


def retained(wt: np.ndarray, we: np.ndarray, his_len: int) -> list[int]:
    """The versions one row retains of its committed ones (timestamps
    ``wt``, epochs ``we``): the greatest of each epoch that wrote it, of
    the ``his_len`` newest such epochs, oldest first; before them the
    load's version 0 while the ring has an empty slot."""
    tops: dict[int, int] = {}
    for t, e in zip(wt.tolist(), we.tolist()):
        tops[e] = max(tops.get(e, 0), t)
    kept = [tops[e] for e in sorted(tops)][-his_len:]
    return ([0] if len(tops) < his_len else []) + kept


def ring_leaf(h: History, n_rows: int, his_len: int) -> np.ndarray:
    """int32[padded_rows x his_len]: the version ring as the program
    lays it out after the log — a row takes ONE entry an epoch that
    wrote it, its greatest committed timestamp of that epoch, in the
    slot of the row's least entry (empty ones first)."""
    ring = np.zeros((padded_rows(n_rows), his_len), np.int32)
    if not len(h.wk):
        return ring.reshape(-1)
    order = np.lexsort((h.wt, h.we, h.wk))      # commit order a key
    k, t, e = h.wk[order], h.wt[order], h.we[order]
    top = np.ones(len(k), bool)                 # last of its (key, epoch)
    top[:-1] = (k[1:] != k[:-1]) | (e[1:] != e[:-1])
    k, t = k[top], t[top]
    start = np.flatnonzero(np.concatenate([[True], k[1:] != k[:-1]]))
    cnt = np.diff(np.append(start, len(k)))
    nth = np.arange(len(k)) - np.repeat(start, cnt)
    few = np.repeat(cnt <= his_len, cnt)        # no slot is taken twice
    ring[k[few], nth[few]] = t[few]
    for s, c in zip(start[cnt > his_len], cnt[cnt > his_len]):
        row = ring[k[s]]
        for x in t[s:s + c]:
            row[int(np.argmin(row))] = x
    return ring.reshape(-1)


def leaves(h: History, n_rows: int, row_bytes: int, his_len: int,
           drop_key: int | None = None) -> dict[str, np.ndarray]:
    """{leaf path as the server names it: the leaf}: F0 with each key's
    greatest committed version, the nine columns nothing writes, the row
    cursor, the version ring.  ``drop_key`` (the control): that key's
    last write is lost — its F0 row keeps the load's bytes."""
    ver = np.zeros(n_rows, np.int64)
    wk, wt = h.final_version()
    ver[wk] = wt
    if drop_key is not None:
        ver[drop_key] = 0
    rows = padded_rows(n_rows)
    keys = np.arange(n_rows, dtype=np.int64)

    def column(v):
        col = np.zeros((rows, row_bytes), np.uint8)
        step = 1 << 17
        for lo in range(0, n_rows, step):
            hi = min(lo + step, n_rows)
            col[lo:hi] = field_bytes(keys[lo:hi], v[lo:hi], row_bytes)
        return col
    untouched = column(np.zeros(n_rows, np.int64))
    out = {f"{TABLE}.columns.F0": column(ver)}
    for i in range(1, FIELDS):
        out[f"{TABLE}.columns.F{i}"] = untouched
    out[f"{TABLE}.row_cnt"] = np.zeros((), np.int32)
    out[RING] = ring_leaf(h, n_rows, his_len)
    return out


def verify(log: bytes, fields: dict, server_info: dict,
           verdicts: dict[int, np.ndarray] | None = None,
           drop_key: int | None = None, fault: dict | None = None
           ) -> tuple[list[tuple[str, float, float]], dict]:
    """The comparison that decides `correct` for a YCSB configuration
    under MVCC: ([(what, value, limit)], notes), each exact (limit 0).

    * ``digest_mismatch``: leaves whose sha256 on the chip
      (`column_digests`) differs from this module's — F0 with each key's
      committed version of the greatest timestamp, F1..F9, the cursor,
      the version ring — or is missing on either side, and 1 more where
      the chip's `state_digest` is not the hash of these leaves in order;
    * ``commit_count_gap``: the server's whole-run commit count against
      the committed lanes of the replayed masks;
    * ``read_checksum_mismatch``: 1 when the server's `read_checksum`
      (uint32) is not the sum, over every committed read lane, of the
      bytes of the version the rule selects for it: the check that an
      old version was SERVED, not only decided;
    * ``mvto_rule_violations``: see the module's head;
    * ``logged_epochs_missing``: 1 when the log holds no epoch.
    ``drop_key`` is `benchmark/control.py`'s fault; ``fault`` the
    tests': ``{"stale_reads_live": True}`` has every read of an old
    version served the live bytes instead."""
    if verdicts is None:
        raise ValueError("ycsb_mvto needs the replayed commit masks")
    if str(fields.get("sim_full_row", "false")).lower() != "true":
        raise ValueError("ycsb_mvto restates the full-row value law only")
    n_rows = int(fields["synth_table_size"])
    row_bytes = int(fields.get("tup_size", 100))
    his_len = int(fields["mvcc_his_len"])
    h = History(log, n_rows, verdicts)
    sel = select_versions(h, his_len)
    served = sel["version"]
    if fault and fault.get("stale_reads_live"):
        served = h.live_version(h.rk, h.re)
    # one sum a distinct (key, version): the hot keys are read often
    pair, cnt = np.unique((h.rk << 32) | served, return_counts=True)
    with np.errstate(over="ignore"):
        checksum = int((_row_sums(pair >> 32, pair & 0xFFFFFFFF, row_bytes)
                        * cnt.astype(np.uint64)).sum(dtype=np.uint64)
                       & np.uint64(0xFFFFFFFF))
    ours = leaves(h, n_rows, row_bytes, his_len, drop_key)
    shas: dict[int, str] = {}       # F1..F9 are one array: hashed once
    dig = {name: shas.setdefault(id(v), _sha(v)) for name, v in ours.items()}
    chip = server_info.get("column_digests") or {}
    differ = sorted(n for n in set(dig) | set(chip)
                    if dig.get(n) != chip.get(n))
    whole = hashlib.sha256()
    for name in sorted(ours, key=lambda n: (n == RING, n)):
        whole.update(np.ascontiguousarray(ours[name]).reshape(-1)
                     .view(np.uint8))
    whole_differs = whole.hexdigest() != server_info.get("state_digest")
    violations = (h.inactive_commits + sel["unwaited"] + sel["late_writes"]
                  + sel["beyond_history"])
    out = [("digest_mismatch", float(len(differ) + whole_differs), 0.0),
           ("commit_count_gap",
            float(abs(h.commits - int(server_info["run_commit_cnt"]))), 0.0),
           ("read_checksum_mismatch",
            0.0 if checksum == server_info.get("read_checksum") else 1.0,
            0.0),
           ("mvto_rule_violations", float(violations), 0.0),
           ("logged_epochs_missing", 0.0 if h.epochs else 1.0, 0.0)]
    return out, dict(
        epochs=h.epochs, commits=h.commits, read_only_commits=h.ro_commits,
        committed_reads=sel["reads"], old_version_reads=int(sel["old"].sum()),
        waited=h.waited, versions=len(h.wk),
        inactive_commits=h.inactive_commits, unwaited_readers=sel["unwaited"],
        late_writes=sel["late_writes"],
        beyond_history=sel["beyond_history"], first_differing=differ[:3],
        read_checksum=checksum)
