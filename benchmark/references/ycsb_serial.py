"""Plain serial reference for served YCSB: numpy only, nothing of the program.

The system under test logs the stream it admitted (one record per epoch:
the merged block of transactions and the mask of lanes that carry one).
This module reads that file with its own decoder, executes the committed
transactions ONE AFTER ANOTHER in rank order on a table built from the
key formula, and hashes the table the way the server hashes its own.

Semantics held, as the configuration files state them:

* serial execution in (epoch, rank) order of the committed transactions:
  a read sees the latest earlier committed write to its key, else the
  table; a write stores ``field_bytes(key, rank)``; after an epoch each
  key holds its LAST committed writer's bytes;
* TPU_BATCH (deterministic batch order): every admitted transaction
  commits;
* OCC: the committed set of an epoch must pass Kung-Robinson backward
  validation on EXACT keys (`occ_rule_violations`): no committed txn i
  may have a committed j of lower rank with W_j ∩ (R_i ∪ W_i) ≠ ∅.

What is the program's FORMAT, not its semantics, and is restated here:
the log framing, the value law ``field_bytes`` (a pure function of key
and writer rank — the program's stand-in for a payload), the table's
row padding and the order the digest walks the columns in.
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
#           | types i8[N,W] (1 read, 2 write) | scalars i32[N,S]
_FRAME = struct.Struct("<IqII")
_MAGIC = 0xDE7E7A10
_TS_HDR = struct.Struct("<qI")
_Q_HDR = struct.Struct("<III")

FIELDS = 10             # F0..F9; requests touch F0 only
READ, WRITE = 1, 2


def read_log(buf: bytes):
    """Yield (epoch, keys int32[n,W], types int8[n,W], active bool[n]) per
    complete record; stops at a torn tail."""
    off = 0
    while off + _FRAME.size <= len(buf):
        magic, epoch, blen, alen = _FRAME.unpack_from(buf, off)
        end = off + _FRAME.size + blen + alen
        if magic != _MAGIC or end > len(buf):
            return
        b0 = off + _FRAME.size
        _, n_ts = _TS_HDR.unpack_from(buf, b0)
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
        yield epoch, keys, types, np.unpackbits(bits)[:n].astype(bool)
        off = end


# ---- the value law and the table ---------------------------------------

def field_bytes(key, version, nbytes: int) -> np.ndarray:
    """uint8[..., nbytes]: the bytes a field holds after the writer of
    rank ``version`` wrote it (version 0 at load)."""
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


def final_writer_lanes(keys: np.ndarray, types: np.ndarray,
                       commit: np.ndarray) -> np.ndarray:
    """Flat lane indices (rank-major) of each key's LAST committed
    write in an epoch: lanes are in rank order, so the last occurrence
    of a key among the committed write lanes is its final writer."""
    lanes = np.flatnonzero((commit[:, None] & (types == WRITE)).ravel())
    _, first_rev = np.unique(keys.ravel()[lanes][::-1], return_index=True)
    return lanes[::-1][first_rev]


class SerialTable:
    """F0 as (last committed writer's rank, written?) per key — the bytes
    are a pure function of both, made when the digest walks the rows."""

    def __init__(self, n_rows: int, row_bytes: int = 100):
        self.n_rows, self.row_bytes = n_rows, row_bytes
        self.version = np.zeros(n_rows, np.uint32)
        self.commit_cnt = 0
        self.last_epoch = None      # (keys, types, commit) of the newest
        self._f0_hash = None        # (drop_key, sha256 over F0's rows)
        self._untouched = None      # bytes of a never-written column

    def apply_epoch(self, keys: np.ndarray, types: np.ndarray,
                    commit: np.ndarray) -> None:
        """One epoch, serially in rank order: only each key's last
        committed writer is left standing."""
        self.commit_cnt += int(commit.sum())
        self.last_epoch = (keys, types, commit)
        self._f0_hash = None
        lanes = final_writer_lanes(keys, types, commit)
        self.version[keys.ravel()[lanes]] = (
            lanes // keys.shape[1]).astype(np.uint32)

    def _column_bytes(self, versions: np.ndarray | None) -> bytes:
        """One column's rows [0, n_rows) as bytes: F0 with the written
        versions, or (``None``) a never-written column."""
        def rows(lo):
            hi = min(lo + step, self.n_rows)
            v = 0 if versions is None else versions[lo:hi]
            return field_bytes(np.arange(lo, hi, dtype=np.uint32), v,
                               self.row_bytes).tobytes()
        step = 1 << 17
        # numpy releases the interpreter lock inside its loops
        with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)
                                ) as pool:
            return b"".join(pool.map(rows, range(0, self.n_rows, step)))

    def digest(self, trash_row: np.ndarray | None = None,
               drop_key: int | None = None) -> str:
        """sha256 of the table as the server's ``state_digest`` walks it:
        columns F0..F9 in name order, each ``padded_rows`` x row_bytes
        (rows past ``n_rows`` zero unless ``trash_row`` says what the
        trash row holds), then the int32 row cursor (0).  ``drop_key``
        (the control): that key's last write is lost — its F0 row keeps
        the load value."""
        pad = np.zeros((padded_rows(self.n_rows) - self.n_rows,
                        self.row_bytes), np.uint8)
        key = drop_key
        if self._f0_hash is None or self._f0_hash[0] != key:
            ver = self.version
            if drop_key is not None:
                ver = ver.copy()
                ver[drop_key] = 0
            self._f0_hash = (key, hashlib.sha256(self._column_bytes(ver)))
            if self._untouched is None:
                self._untouched = self._column_bytes(None)
        h = self._f0_hash[1].copy()
        if trash_row is not None:
            p = pad.copy()
            p[0] = trash_row
            h.update(p.tobytes())
        else:
            h.update(pad.tobytes())
        for _ in range(1, FIELDS):
            h.update(self._untouched)
            h.update(pad.tobytes())
        h.update(np.zeros((), np.int32).tobytes())
        return h.hexdigest()

    def trash_candidates(self) -> list[tuple[str, np.ndarray | None]]:
        """What the trash row of F0 may hold.  The deterministic executor
        (TPU_BATCH) drops masked lanes and never writes it.  The masked
        scatter of the validating backends steers every lane that is not
        a final writer to the trash row with its ``field_bytes(key,
        rank)``; which of those duplicates is left standing is the
        compiler's choice, so the reference names the plain ones: never
        written, the last such lane of the newest epoch, or the first."""
        out: list[tuple[str, np.ndarray | None]] = [("never_written", None)]
        if self.last_epoch is None:
            return out
        keys, types, commit = self.last_epoch
        w, k = keys.shape[1], keys.ravel()
        final = np.zeros(k.size, bool)
        final[final_writer_lanes(keys, types, commit)] = True
        losers = np.flatnonzero(~final)
        for name, pick in (("last_masked_lane", -1), ("first_masked_lane", 0)):
            if len(losers):
                ln = int(losers[pick])
                out.append((name, field_bytes(np.uint32(k[ln]),
                                              np.uint32(ln // w),
                                              self.row_bytes)))
        return out


def occ_rule_violations(keys: np.ndarray, types: np.ndarray,
                        commit: np.ndarray) -> int:
    """Committed access lanes of an epoch that break Kung-Robinson
    backward validation on exact keys: a committed txn i touching (read
    or write) a key that a committed txn of LOWER rank writes."""
    n, w = keys.shape
    rank = np.repeat(np.arange(n, dtype=np.int64), w)
    k = keys.ravel().astype(np.int64)
    acc = np.repeat(commit, w) & (types.ravel() != 0)
    wr = acc & (types.ravel() == WRITE)
    if not wr.any():
        return 0
    order = np.lexsort((rank[wr], k[wr]))
    wk, wrk = k[wr][order], rank[wr][order]
    first = np.concatenate([[True], wk[1:] != wk[:-1]])
    uk, min_rank = wk[first], wrk[first]       # lowest writer per key
    pos = np.searchsorted(uk, k[acc])
    pos = np.minimum(pos, len(uk) - 1)
    hit = uk[pos] == k[acc]
    return int((hit & (min_rank[pos] < rank[acc])).sum())


def replay(log: bytes, n_rows: int, row_bytes: int = 100,
           verdicts: dict[int, np.ndarray] | None = None) -> dict:
    """Execute the whole log serially.  ``verdicts`` (validating
    backends): {epoch: bool[n] committed}; absent, every active lane
    commits (the deterministic backends).  Returns the table, the number
    of epochs and commits, and the OCC rule's violation count."""
    tab = SerialTable(n_rows, row_bytes)
    epochs = violations = 0
    for epoch, keys, types, active in read_log(log):
        if keys.size and (keys.min() < 0 or keys.max() >= n_rows):
            raise ValueError(f"epoch {epoch}: key outside [0, {n_rows})")
        commit = active
        if verdicts is not None:
            commit = verdicts[epoch]
            if (commit & ~active).any():
                violations += int((commit & ~active).sum())
            violations += occ_rule_violations(keys, types, commit)
        tab.apply_epoch(keys, types, commit)
        epochs += 1
    return dict(table=tab, epochs=epochs, commits=tab.commit_cnt,
                violations=violations)


def verify(log: bytes, fields: dict, server_info: dict,
           verdicts: dict[int, np.ndarray] | None = None,
           drop_key: int | None = None
           ) -> tuple[list[tuple[str, float, float]], dict]:
    """The comparison that decides `correct` for a YCSB configuration:
    ([(what, value, limit)], notes), each an exact comparison (limit 0);
    the notes say what was replayed and which trash row matched.

    * ``digest_mismatch``: the chip's ``state_digest`` against this
      module's serial execution of the logged stream (1 = differs);
    * ``commit_count_gap``: the server's whole-run commit count against
      the commits the reference executed;
    * ``occ_rule_violations`` (with ``verdicts``): committed lanes that
      break exact-key backward validation, or commit an inactive lane;
    * ``logged_epochs_missing``: 1 when the log holds no epoch.
    ``drop_key`` is the control's fault (see `SerialTable.digest`)."""
    res = replay(log, int(fields["synth_table_size"]),
                 int(fields.get("tup_size", 100)), verdicts)
    tab: SerialTable = res["table"]
    chip = server_info.get("state_digest")
    matched = None
    for name, trash in tab.trash_candidates():
        if tab.digest(trash, drop_key) == chip:
            matched = name
            break
    out = [("digest_mismatch", 0.0 if matched else 1.0, 0.0),
           ("commit_count_gap",
            float(abs(res["commits"] - int(server_info["run_commit_cnt"]))),
            0.0),
           ("logged_epochs_missing", 0.0 if res["epochs"] else 1.0, 0.0)]
    if verdicts is not None:
        out.append(("occ_rule_violations", float(res["violations"]), 0.0))
    return out, dict(epochs=res["epochs"], commits=res["commits"],
                     trash_row=matched)
