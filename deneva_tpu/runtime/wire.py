"""Message bodies for the distributed runtime (reference `transport/message.cpp`).

The reference defines 20+ typed messages with hand-rolled binary
serialization per type (`Message::create_message` factory,
`transport/message.cpp:112-194`, `COPY_VAL/COPY_BUF` `:196-270`).  Here the
wire vocabulary collapses to four columnar bodies — batch thinking removes
most of the zoo (RQRY/RPREPARE/RFIN/RACK_* all vanish into the
deterministic epoch exchange, SURVEY §3.B step 4 → matmul):

* CL_QRY_BATCH  client→server: columnar query block + per-txn tag
  (reference ClientQueryMessage batches, `message.h:243-340`).
* CL_RSP        server→client: per-txn ack with latency echo
  (ClientResponseMessage, `message.h`).
* EPOCH_BLOB    server→server: one node's contribution to a global epoch
  (the Calvin sequencer batch, `system/sequencer.cpp:283-326`; doubles as
  the RDONE epoch barrier — exactly one blob per (server, epoch)).
* SHUTDOWN      coordinator→all: stop-epoch announcement.

All bodies ride the native framed transport; the query columns use the
C codec (`dt_qrybatch_encode/decode`) so the server can hand them straight
to the device without Python-level row loops.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass

import numpy as np

from deneva_tpu.runtime.native import (_QB_HDR, decode_qrybatch,
                                       decode_qrybatch_into,
                                       encode_qrybatch)

_HDR = struct.Struct("<q")          # epoch (blob) / stop_epoch (shutdown)
_RSP = struct.Struct("<II")         # n, pad
_QHDR = _QB_HDR                     # qrybatch header (n, width, n_scalars):
#                                     single definition, native.py owns it


@dataclass
class QueryBlock:
    """Columnar query batch + per-txn metadata."""

    keys: np.ndarray      # int32[n, W]
    types: np.ndarray     # int8[n, W]  1=read 2=write 3=rmw 0=pad
    scalars: np.ndarray   # int32[n, S] workload-specific params
    tags: np.ndarray      # int64[n]    client-assigned txn tag / startts

    def __len__(self) -> int:
        return len(self.keys)

    @classmethod
    def empty(cls, width: int, n_scalars: int = 0) -> "QueryBlock":
        return cls(keys=np.zeros((0, width), np.int32),
                   types=np.zeros((0, width), np.int8),
                   scalars=np.zeros((0, n_scalars), np.int32),
                   tags=np.zeros(0, np.int64))

    @classmethod
    def concat(cls, blocks: list["QueryBlock"]) -> "QueryBlock":
        return cls(keys=np.concatenate([b.keys for b in blocks]),
                   types=np.concatenate([b.types for b in blocks]),
                   scalars=np.concatenate([b.scalars for b in blocks]),
                   tags=np.concatenate([b.tags for b in blocks]))

    def slice(self, lo: int, hi: int) -> "QueryBlock":
        return QueryBlock(self.keys[lo:hi], self.types[lo:hi],
                          self.scalars[lo:hi], self.tags[lo:hi])

    def take(self, idx: np.ndarray) -> "QueryBlock":
        return QueryBlock(self.keys[idx], self.types[idx],
                          self.scalars[idx], self.tags[idx])


def encode_qry_block(b: QueryBlock) -> bytes:
    return encode_qrybatch(b.tags, b.keys, b.types, b.scalars)


def decode_qry_block(buf: bytes) -> QueryBlock:
    tags, keys, types, scalars = decode_qrybatch(buf)
    return QueryBlock(keys=keys, types=types, scalars=scalars, tags=tags)


# ---- EPOCH_BLOB: header(epoch) + birth timestamps + query block --------
# Birth ts ride the blob explicitly so every node's merged batch carries
# identical ages: WAIT_DIE's wound-wait rule needs timestamps preserved
# across restarts (reference keeps them, `worker_thread.cpp:492-508`),
# which epoch-derived ts cannot do.

_TS_HDR = struct.Struct("<qI")      # epoch, n


def encode_epoch_blob(epoch: int, b: QueryBlock,
                      ts: np.ndarray | None = None) -> bytes:
    if ts is None:
        ts = np.zeros(len(b), np.int64)
    ts = np.ascontiguousarray(ts, np.int64)
    return _TS_HDR.pack(epoch, len(ts)) + ts.tobytes() \
        + encode_qry_block(b)


def decode_epoch_blob(buf: bytes) -> tuple[int, QueryBlock, np.ndarray]:
    epoch, n = _TS_HDR.unpack_from(buf)
    ts = np.frombuffer(buf, np.int64, count=n, offset=_TS_HDR.size)
    return epoch, decode_qry_block(buf[_TS_HDR.size + 8 * n:]), ts


# ---- zero-copy wire fast paths (host-path pipeline PR) -----------------
# The bytes codecs above build each message through 2-3 intermediate
# copies (column .tobytes() + joins).  The cluster steady loop instead
# ships messages as SCATTER-SEND PARTS (NativeTransport.sendv /
# dt_sendv): the column arrays themselves plus two tiny packed headers —
# the native layer frames everything in one pass, so the Python side
# performs zero payload copies.  The parts concatenation is
# byte-identical to the corresponding encode_* output (fuzz-tested in
# tests/test_wire_zero_copy.py), which is what keeps log records and
# replica streams unchanged whichever path produced them.

def epoch_blob_parts(epoch: int, ts: np.ndarray, tags: np.ndarray,
                     keys: np.ndarray, types: np.ndarray,
                     scalars: np.ndarray) -> list:
    """EPOCH_BLOB as sendv parts; concatenated == encode_epoch_blob of
    the same columns.  All arrays must be C-contiguous row views."""
    n = len(tags)
    return [_TS_HDR.pack(epoch, len(ts)), ts,
            _QHDR.pack(n, keys.shape[1],
                       scalars.shape[1] if scalars.ndim == 2 else 0),
            tags, keys, types, scalars]


def qry_block_parts(tags: np.ndarray, keys: np.ndarray, types: np.ndarray,
                    scalars: np.ndarray) -> list:
    """CL_QRY_BATCH as sendv parts; concatenated == encode_qry_block of
    the same columns.  The client's hot loop ships its pre-generated
    ring columns directly — no per-send codec pass."""
    return [_QHDR.pack(len(tags), keys.shape[1], scalars.shape[1]),
            np.ascontiguousarray(tags, np.int64), keys, types, scalars]


def cl_rsp_parts(tags: np.ndarray) -> list:
    """CL_RSP as sendv parts; concatenated == encode_cl_rsp(tags)."""
    tags = np.ascontiguousarray(tags, np.int64)
    return [_RSP.pack(len(tags), 0), tags]


def peek_blob_epoch(buf: bytes) -> int:
    """Epoch of an EPOCH_BLOB without decoding the body (the overlap
    path buffers raw payloads and decodes straight into the feed)."""
    return _TS_HDR.unpack_from(buf)[0]


def decode_epoch_blob_into(buf: bytes, tags: np.ndarray, ts: np.ndarray,
                           keys: np.ndarray, types: np.ndarray,
                           scalars: np.ndarray) -> tuple[int, int]:
    """Decode an EPOCH_BLOB straight into feed-slice row views (the
    assembly path that replaces per-group ``np.concatenate``): birth ts
    and the query columns land in the caller's arrays; rows past the
    decoded count are untouched.  Returns (epoch, n)."""
    epoch, n_ts = _TS_HDR.unpack_from(buf)
    if len(ts) < n_ts:
        raise ValueError(f"ts view too small ({len(ts)} < {n_ts})")
    ts[:n_ts] = np.frombuffer(buf, np.int64, count=n_ts,
                              offset=_TS_HDR.size)
    n = decode_qrybatch_into(buf, _TS_HDR.size + 8 * n_ts, tags, keys,
                             types, scalars)
    if n != n_ts:
        raise ValueError(
            f"corrupt epoch blob: {n_ts} timestamps for {n} txns")
    return epoch, n


# ---- CL_RSP: tags + commit latency echo --------------------------------

def encode_cl_rsp(tags: np.ndarray) -> bytes:
    tags = np.ascontiguousarray(tags, np.int64)
    return _RSP.pack(len(tags), 0) + tags.tobytes()


def decode_cl_rsp(buf: bytes) -> np.ndarray:
    n, _ = _RSP.unpack_from(buf)
    return np.frombuffer(buf, np.int64, count=n, offset=_RSP.size)


# ---- VOTE (batched 2PC prepare, reference RPREPARE/RACK_PREP,
# `system/txn.cpp:498-606`): one server's per-txn verdict over the merged
# epoch batch for the accesses it owns.  Three packed bitsets; commit =
# every owner voted commit, abort = any owner voted abort.  MAAT votes
# additionally piggyback per-txn LOWER BOUNDS on the serialization
# position — the batch analogue of the reference shipping `[lower,upper)`
# timestamp ranges on RACK_PREP and intersecting at the coordinator
# (`concurrency_control/maat.cpp:176-190`,
# `transport/message.cpp:1057-1137`); intersection of lower bounds =
# elementwise max, see server._vote_epoch. -----------------------------

_VOTE = struct.Struct("<qIB")       # epoch, n_txns, has_bounds


def encode_vote(epoch: int, commit: np.ndarray, abort: np.ndarray,
                bounds: np.ndarray | None = None) -> bytes:
    """Two bitsets suffice: the global wait (defer) set is the complement
    ``active & ~commit & ~abort`` — a local defer vote is exactly a
    not-commit-not-abort vote, so shipping it would be redundant."""
    n = len(commit)
    body = (_VOTE.pack(epoch, n, 0 if bounds is None else 1)
            + np.packbits(commit.astype(bool)).tobytes()
            + np.packbits(abort.astype(bool)).tobytes())
    if bounds is not None:
        body += np.ascontiguousarray(bounds, np.int32).tobytes()
    return body


def decode_vote(buf: bytes
                ) -> tuple[int, np.ndarray, np.ndarray, np.ndarray | None]:
    epoch, n, has_bounds = _VOTE.unpack_from(buf)
    nb = (n + 7) // 8
    off = _VOTE.size
    out = []
    for _ in range(2):
        bits = np.unpackbits(np.frombuffer(buf, np.uint8, count=nb,
                                           offset=off))[:n].astype(bool)
        out.append(bits)
        off += nb
    bounds = np.frombuffer(buf, np.int32, count=n, offset=off) \
        if has_bounds else None
    return epoch, out[0], out[1], bounds


# ---- SHUTDOWN ----------------------------------------------------------

def encode_shutdown(stop_epoch: int) -> bytes:
    return _HDR.pack(stop_epoch)


def decode_shutdown(buf: bytes) -> int:
    return _HDR.unpack_from(buf)[0]


# ---- INIT_DONE barrier (reference sim_manager setup counting,
# `system/sim_manager.cpp:95-100`) ---------------------------------------

# Set-up waits (peer dial + INIT_DONE barrier) of a node nobody
# supervises — a test posing as the cluster.  Under the launcher every
# node waits as long as the launcher itself does (`launch.run_cluster`
# passes its own limit and ends the run when a peer dies), because a
# peer's cold compile of the epoch-group program sits inside both waits.
SETUP_WAIT_S = 120.0


def run_barrier(tp, me: int, n_all: int, on_other, who: str,
                timeout_s: float = SETUP_WAIT_S) -> None:
    """Send INIT_DONE to every peer, then drain until all peers' INIT_DONEs
    arrive.  Non-barrier messages that race in early are handed to
    ``on_other(src, rtype, payload)`` so no protocol traffic is lost."""
    import time as _time

    seen = {me}
    for p in range(n_all):
        if p != me:
            tp.send(p, "INIT_DONE")
    tp.flush()
    t0 = _time.monotonic()
    while len(seen) < n_all:
        if _time.monotonic() - t0 > timeout_s:
            raise TimeoutError(
                f"{who}: INIT_DONE barrier timed out ({sorted(seen)})")
        m = tp.recv(10_000)
        if m is None:
            continue
        if m[1] == "INIT_DONE":
            seen.add(m[0])
        else:
            on_other(*m)
