"""Plain reference for served YCSB under two-phase locking (Deneva's
NO_WAIT and WAIT_DIE, `concurrency_control/row_lock.{h,cpp}`): numpy and
plain Python, nothing of the program.

The system under test logs the stream it admitted: one record per epoch
with the merged block of transactions, the tag and the birth TIMESTAMP
each carries and the mask of lanes that hold one.  Who committed is
replayed from that log (`benchmark/verdicts_child.py`) and is not
trusted: this module reads the log with its own decoder, runs a textbook
lock table over every epoch serially, decides every lane's fate ITSELF
(commit / wait / die) and holds the replayed masks, the table, the
program's own counts of deaths, waits and leftovers, the timestamps and
the bytes every committed read returned to the rule below.  Each
comparison is exact, limit 0.

The rule (the configuration file's DEPARTURE 1):

    The lock table is empty when an epoch begins (every earlier epoch's
    winners have released).  The epoch's lanes ask in RANK order — a
    lane's rank is its position in the logged block, which is the
    ``rank`` the program's epoch step hands its access batch
    (``jnp.arange`` over the merged block): retries first, in the retry
    queue's order, then fresh arrivals.  A lane asks for all its locks
    at once: exclusive on each key it writes, shared on each key it
    only reads; its own repeated keys do not conflict with itself.  It
    is granted them iff no earlier-ranked WINNER of this epoch holds a
    conflicting one; then it is a winner, commits, and holds them to
    the epoch's end.  Otherwise its owners are the earlier-ranked
    winners it conflicts with: under WAIT_DIE, if its birth timestamp
    is below every owner's it WAITS (deferred, not aborted), else it
    DIES (aborted, restarted later with the same timestamp); under
    NO_WAIT every loser dies.

The sweep budget (the configuration's `sweep_rounds`, the program's and
not the source's) is restated as what it is, a bound on how long a chain
of verdicts an epoch follows: a lane with no earlier conflicting lane is
decided in round 1; a grant is known one round after the LAST of the
refusals it rests on (every earlier conflicting lane), a refusal one
round after the FIRST earlier conflicting grant.  A lane whose round is
past the budget is a LEFTOVER: deferred, neither granted nor refused,
and no owner of anybody; it may never be decided wrongly.  With a budget
no chain reaches, this is the textbook table and nothing else.

Semantics held, as the configuration file states them:

* a committed WRITE stores ``field_bytes(key, rank)``; within an epoch no
  two winners write one key, so after the log each key holds the bytes of
  its last committed writer by (epoch, rank);
* a committed READ returns the bytes its key held when its epoch BEGAN
  (no winner of its epoch writes a key it reads, but for the reader
  itself: a transaction's reads are of the state before its writes).
  The program's `read_checksum` folds exactly these lanes — every read
  lane of a committed transaction, a repeated key once a lane — and no
  other;
* a transaction keeps the timestamp it was born with through every wait
  and every death.

What is the program's FORMAT, not its semantics, and is restated here:
the log framing, the value law ``field_bytes`` (a pure function of key
and writer rank — the program's stand-in for a payload), the table's row
padding and the leaves' names.
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
TABLE = "MAIN_TABLE"
# a lane's fate in its epoch (0: the slot holds no transaction)
COMMIT, WAIT, DIE, LEFTOVER = 1, 2, 3, 4


def read_records(buf: bytes):
    """Yield (epoch, ts int64[n], tags int64[n], keys int32[n,W], types
    int8[n,W], active bool[n]) per complete record; stops at a torn
    tail."""
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
        tags = np.frombuffer(buf, np.int64, n, q0 + _Q_HDR.size)
        k0 = q0 + _Q_HDR.size + 8 * n
        keys = np.frombuffer(buf, np.int32, n * w, k0).reshape(n, w)
        types = np.frombuffer(buf, np.int8, n * w,
                              k0 + 4 * n * w).reshape(n, w)
        bits = np.frombuffer(buf, np.uint8, alen, b0 + blen)
        yield (epoch, ts, tags, keys, types,
               np.unpackbits(bits)[:n].astype(bool))
        off = end


def read_log(buf: bytes):
    """(epoch, keys, types, active) per record, as `ycsb_serial.read_log`
    yields them: what `benchmark/control.py` walks to name a committed
    write."""
    for epoch, _ts, _tags, keys, types, active in read_records(buf):
        yield epoch, keys, types, active


# ---- the value law and the leaves ---------------------------------------

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


def column_chunks(version: np.ndarray | None, n_rows: int,
                  row_bytes: int) -> list[bytes]:
    """One column as the server holds it, in pieces: rows [0, n_rows) in
    key order — F0 with each key's ``version``, or (None) a column
    nothing writes — then the padding, zero (the full-row executor writes
    its winners alone and never the trash row)."""
    step = 1 << 17

    def rows(lo):
        k = np.arange(lo, min(lo + step, n_rows), dtype=np.uint32)
        v = 0 if version is None else version[lo:lo + len(k)]
        return field_bytes(k, v, row_bytes).tobytes()
    # numpy releases the interpreter lock inside its loops
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        chunks = list(pool.map(rows, range(0, n_rows, step)))
    return chunks + [bytes((padded_rows(n_rows) - n_rows) * row_bytes)]


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


# ---- the lock table ---------------------------------------------------------

_PAIRS: dict[int, tuple[np.ndarray, np.ndarray]] = {}


def conflicts(keys: np.ndarray, types: np.ndarray, active: np.ndarray
              ) -> dict[int, list[int]]:
    """{lane: its earlier-ranked lanes in conflict with it}, on exact
    keys: two lanes conflict iff they ask for one key and at least one
    of them asks exclusively (it writes the key in some access).  Lanes
    with no earlier conflicting lane are left out."""
    n, w = keys.shape
    live = np.flatnonzero((np.repeat(active, w) & (types.ravel() != 0)))
    # one request a (key, lane): exclusive if any access of it writes
    comp = (keys.ravel()[live].astype(np.int64) << 24) | (live // w)
    order = np.argsort(comp, kind="stable")
    comp, wr = comp[order], (types.ravel()[live] == WRITE)[order]
    if not len(comp):
        return {}
    idx = np.flatnonzero(np.concatenate([[True], comp[1:] != comp[:-1]]))
    excl = np.logical_or.reduceat(wr, idx)
    key, lane = comp[idx] >> 24, comp[idx] & ((1 << 24) - 1)
    start = np.flatnonzero(np.concatenate([[True], key[1:] != key[:-1]]))
    size = np.diff(np.append(start, len(key)))
    later, earlier = [], []
    for s, g in zip(start[size > 1].tolist(), size[size > 1].tolist()):
        if g not in _PAIRS:
            _PAIRS[g] = np.triu_indices(g, 1)
        a, b = _PAIRS[g]        # positions a < b: lanes ascend in a key
        hit = excl[s + a] | excl[s + b]
        earlier.append(lane[s + a][hit])
        later.append(lane[s + b][hit])
    nb: dict[int, list[int]] = {}
    if later:
        for i, j in zip(np.concatenate(later).tolist(),
                        np.concatenate(earlier).tolist()):
            nb.setdefault(i, []).append(j)
    return nb


def lock_table(ts: np.ndarray, keys: np.ndarray, types: np.ndarray,
               active: np.ndarray, wait_die: bool = True,
               rounds: int | None = None, younger_waits: bool = False
               ) -> np.ndarray:
    """int8[n]: every lane's fate in one epoch (0 where the slot holds no
    transaction), by the module head's rule, the lanes asking one after
    another in rank order.  ``rounds``: the sweep budget (None: no
    bound).  ``younger_waits`` (the tests' fault): the age test
    inverted."""
    n = len(active)
    fate = np.where(active, COMMIT, 0).astype(np.int8)
    rnd = [1] * n                       # the round a lane is decided in
    won = active.tolist()               # granted, by the textbook table
    budget = rounds if rounds is not None else n + 1
    stamp = ts.tolist()
    nb = conflicts(keys, types, active)
    for i in sorted(nb):
        owners = [j for j in nb[i] if won[j]]
        if not owners:
            # every earlier conflicting lane was refused: granted, once
            # the last of those refusals is known
            rnd[i] = 1 + max(rnd[j] for j in nb[i])
        else:
            won[i] = False
            rnd[i] = 1 + min(rnd[j] for j in owners)
        if rnd[i] > budget:
            fate[i] = LEFTOVER
        elif owners:
            # the owners a decided loser sees: the grants the budget knew
            known = [stamp[j] for j in owners if rnd[j] <= budget]
            older = stamp[i] < min(known)
            fate[i] = WAIT if wait_die and older != younger_waits else DIE
    return fate


# ---- the comparison --------------------------------------------------------

def verify(log: bytes, fields: dict, server_info: dict,
           verdicts: dict[int, np.ndarray] | None = None,
           drop_key: int | None = None, fault: dict | None = None
           ) -> tuple[list[tuple[str, float, float]], dict]:
    """The comparison that decides `correct` for a YCSB configuration
    under NO_WAIT / WAIT_DIE: ([(what, value, limit)], notes), each exact
    (limit 0).

    * ``digest_mismatch``: leaves whose sha256 on the chip
      (`column_digests`) differs from this module's — F0 with each key's
      last committed writer's bytes, F1..F9, the cursor — or is missing
      on either side, and 1 more where the chip's `state_digest` is not
      the hash of these leaves in order;
    * ``commit_count_gap``: the server's whole-run commit count against
      the committed lanes of the replayed masks;
    * ``lock_rule_violations``: committed lanes that the lock table did
      not grant (two committed lanes of one epoch in conflict on exact
      keys; a commit of an inactive slot);
    * ``ungranted_winners_gap``: lanes the lock table grants that the
      program did not commit and the sweep budget does not explain, lane
      for lane, plus the gap between the budget's leftovers as restated
      here and the program's own count (`run_lock_leftover_cnt`);
    * ``wait_count_gap``, ``die_count_gap``: this module's waits and
      deaths over the logged epochs against the program's counters
      (`run_lock_wait_cnt`, `run_lock_die_cnt`; a program that prints
      none reads a gap);
    * ``birth_ts_changed``: transactions (tags) that carry more than one
      timestamp over the logged epochs they appear in (WAIT_DIE's
      guarantee; under NO_WAIT a timestamp decides nothing, a restart is
      stamped anew and nothing is counted);
    * ``read_checksum_mismatch``: 1 when the server's `read_checksum`
      (uint32) is not the sum, over every read lane of a committed
      transaction, of the bytes its key held when its epoch began;
    * ``logged_epochs_missing``: 1 when the log holds no epoch.
    ``drop_key`` is `benchmark/control.py`'s fault; ``fault`` the
    tests': ``{"younger_waits": True}`` inverts the age test."""
    if verdicts is None:
        raise ValueError("ycsb_2pl needs the replayed commit masks")
    if str(fields.get("sim_full_row", "false")).lower() != "true":
        raise ValueError("ycsb_2pl restates the full-row value law only")
    if str(fields.get("isolation_level", "SERIALIZABLE")) != "SERIALIZABLE":
        raise ValueError("ycsb_2pl holds strict two-phase locking only")
    n_rows = int(fields["synth_table_size"])
    row_bytes = int(fields.get("tup_size", 100))
    wait_die = str(fields["cc_alg"]) == "WAIT_DIE"
    rounds = int(fields["sweep_rounds"])
    younger = bool(fault and fault.get("younger_waits"))
    version = np.zeros(n_rows, np.uint32)       # each key's last writer
    count = {COMMIT: 0, WAIT: 0, DIE: 0, LEFTOVER: 0}
    epochs = commits = violations = unexplained = 0
    epochs_with_waits = epochs_with_deaths = 0
    born: dict[int, int] = {}
    ts_changed: set[int] = set()
    returns = txns = 0              # lanes of a tag seen in an earlier epoch
    read_pairs = []
    for epoch, ts, tags, keys, types, active in read_records(log):
        if keys.size and (keys.min() < 0 or keys.max() >= n_rows):
            raise ValueError(f"epoch {epoch}: key outside [0, {n_rows})")
        commit = np.asarray(verdicts[epoch], bool)
        fate = lock_table(ts, keys, types, active, wait_die, rounds, younger)
        for f, c in enumerate(np.bincount(fate, minlength=5).tolist()):
            if f:
                count[f] += c
        epochs_with_waits += bool((fate == WAIT).any())
        epochs_with_deaths += bool((fate == DIE).any())
        # a commit the table did not grant (a leftover's too: the budget
        # may leave a lane undecided, never commit it)
        violations += int((commit & (fate != COMMIT)).sum())
        unexplained += int((~commit & (fate == COMMIT)).sum())
        commit = commit & active
        epochs += 1
        commits += int(commit.sum())
        # a tag names a transaction from its first lane to its commit
        # (the clients draw tags from a ring and use one again later)
        for tag, t, done in zip(tags[active].tolist(), ts[active].tolist(),
                                commit[active].tolist()):
            if tag in born:
                returns += 1
                if wait_die and born[tag] != t:
                    ts_changed.add(tag)
            else:
                born[tag] = t
                txns += 1
            if done:
                del born[tag]
        # reads first, of the state the epoch began with; then the
        # winners' writes (no two winners write one key: a key's writer
        # lanes are one transaction's and store the same bytes)
        rk = keys[commit[:, None] & (types == READ)].astype(np.int64)
        read_pairs.append((rk << 32) | version[rk])
        w = keys.shape[1]
        wl = np.flatnonzero((commit[:, None] & (types == WRITE)).ravel())
        version[keys.ravel()[wl]] = (wl // w).astype(np.uint32)
    # one sum a distinct (key, version): the hot keys are read often
    pair, cnt = np.unique(np.concatenate(read_pairs) if read_pairs
                          else np.zeros(0, np.int64), return_counts=True)
    with np.errstate(over="ignore"):
        checksum = int((_row_sums(pair >> 32, pair & 0xFFFFFFFF, row_bytes)
                        * cnt.astype(np.uint64)).sum(dtype=np.uint64)
                       & np.uint64(0xFFFFFFFF))
    if drop_key is not None:
        version[drop_key] = 0
    # every leaf's own hash, and the whole's over the leaves in order
    whole = hashlib.sha256()
    dig = {}
    for name, chunks in (
            ("F0", column_chunks(version, n_rows, row_bytes)),
            ("F1", column_chunks(None, n_rows, row_bytes))):
        leaf = hashlib.sha256()
        for c in chunks:
            leaf.update(c)
            whole.update(c)
        dig[f"{TABLE}.columns.{name}"] = leaf.hexdigest()
    for i in range(2, FIELDS):          # F2..F9 hold F1's bytes
        dig[f"{TABLE}.columns.F{i}"] = dig[f"{TABLE}.columns.F1"]
        for c in chunks:
            whole.update(c)
    cursor = np.zeros((), np.int32).tobytes()
    dig[f"{TABLE}.row_cnt"] = hashlib.sha256(cursor).hexdigest()
    whole.update(cursor)
    chip = server_info.get("column_digests") or {}
    differ = sorted(n for n in set(dig) | set(chip)
                    if dig.get(n) != chip.get(n))
    whole_differs = whole.hexdigest() != server_info.get("state_digest")

    def gap(ours: int, key: str) -> float:
        theirs = server_info.get(key)
        return float(ours + 1 if theirs is None else abs(ours - int(theirs)))
    out = [("digest_mismatch", float(len(differ) + whole_differs), 0.0),
           ("commit_count_gap",
            float(abs(commits - int(server_info["run_commit_cnt"]))), 0.0),
           ("lock_rule_violations", float(violations), 0.0),
           ("ungranted_winners_gap", unexplained + gap(
               count[LEFTOVER], "run_lock_leftover_cnt"), 0.0),
           ("wait_count_gap", gap(count[WAIT], "run_lock_wait_cnt"), 0.0),
           ("die_count_gap", gap(count[DIE], "run_lock_die_cnt"), 0.0),
           ("birth_ts_changed", float(len(ts_changed)), 0.0),
           ("read_checksum_mismatch",
            0.0 if checksum == server_info.get("read_checksum") else 1.0,
            0.0),
           ("logged_epochs_missing", 0.0 if epochs else 1.0, 0.0)]
    return out, dict(
        epochs=epochs, commits=commits, granted=count[COMMIT],
        waits=count[WAIT], deaths=count[DIE], leftovers=count[LEFTOVER],
        epochs_with_waits=epochs_with_waits,
        epochs_with_deaths=epochs_with_deaths,
        returning_lanes=returns,
        committed_reads=int(cnt.sum()), transactions=txns,
        first_differing=differ[:3], read_checksum=checksum)
