"""Logging + active-passive replication (reference `system/logger.{h,cpp}`,
`system/log_thread.cpp`, REPLICA flow in SURVEY §5.4).

The reference writes per-write command records `LogRecord{lsn,iud,txn_id,
table_id,key}` (`logger.cpp:8-60`); a commit enqueues L_NOTIFY and parks
until the LogThread flushes (`txn.cpp:434-441`,
`worker_thread.cpp:543-554`), and with replication also ships records as
LOG_MSG to a replica and waits for the ack (`worker_thread.cpp:527-541`).
It has **no replay path** — recovery is unimplemented there.

Here the unit of durability is the *epoch*: one length-framed record holds
the merged epoch block (the full command stream) + the active mask.
Because epoch validation/execution is a deterministic pure function,
replay is literal re-execution — command logging finally pays for itself.
Group commit falls out naturally: CL_RSPs for epoch e are held until the
log record of e is on disk (and acked by the replica when configured),
which is exactly the reference's commit-parks-until-flush semantics
amortized over a batch.

Wire/disk framing (little-endian):
    magic u32 | epoch i64 | blob_len u32 | active_len u32
    | blob bytes (wire.encode_epoch_blob payload) | active bitmask bytes
"""

from __future__ import annotations

import os
import struct
import threading
import queue as _queue

import numpy as np

from deneva_tpu.engine.epoch import make_dist_step

_FRAME = struct.Struct("<IqII")
_MAGIC = 0xDE7E7A10


def pack_record(epoch: int, blob: bytes, active: np.ndarray) -> bytes:
    bits = np.packbits(active.astype(np.uint8))
    return _FRAME.pack(_MAGIC, epoch, len(blob), len(bits)) + blob \
        + bits.tobytes()


def pack_record_views(epoch: int, ts: np.ndarray, tags: np.ndarray,
                      keys: np.ndarray, types: np.ndarray,
                      scalars: np.ndarray, active: np.ndarray) -> np.ndarray:
    """Assemble a framed record in ONE pass straight from merged-feed
    row views (the host-pipeline log path): byte-identical to
    ``pack_record(epoch, encode_epoch_blob(epoch, block, ts), active)``
    but with a single allocation and one copy per column instead of the
    2-3 full-record copies of the bytes codecs.  Returns uint8[total]
    (file-writable and zero-copy sendable)."""
    from deneva_tpu.runtime import wire

    parts = wire.epoch_blob_parts(epoch, ts, tags, keys, types, scalars)
    flat = [np.frombuffer(p, np.uint8) if isinstance(p, bytes)
            else np.ascontiguousarray(p).reshape(-1).view(np.uint8)
            for p in parts]
    bits = np.packbits(active.astype(np.uint8))
    blob_len = sum(p.size for p in flat)
    out = np.empty(_FRAME.size + blob_len + bits.size, np.uint8)
    _FRAME.pack_into(out, 0, _MAGIC, epoch, blob_len, bits.size)
    off = _FRAME.size
    for p in flat:
        out[off:off + p.size] = p
        off += p.size
    out[off:] = bits
    return out


def unpack_records(buf: bytes):
    """Yield (epoch, blob_bytes, active_bits) from a log byte stream;
    stops cleanly at a torn tail (crash mid-write)."""
    for epoch, lo, hi in iter_record_spans(buf):
        magic, _, blen, alen = _FRAME.unpack_from(buf, lo)
        del magic
        blob = buf[lo + _FRAME.size: lo + _FRAME.size + blen]
        bits = np.frombuffer(buf, np.uint8, count=alen,
                             offset=lo + _FRAME.size + blen)
        yield epoch, blob, bits


def iter_record_spans(buf: bytes):
    """Yield (epoch, start_off, end_off) for every complete framed record
    (the raw-byte view of unpack_records; recovery re-ships and truncates
    by span).  Stops cleanly at a torn tail."""
    off = 0
    while off + _FRAME.size <= len(buf):
        magic, epoch, blen, alen = _FRAME.unpack_from(buf, off)
        end = off + _FRAME.size + blen + alen
        if magic != _MAGIC or end > len(buf):
            return
        yield epoch, off, end
        off = end


def truncate_log_to_epoch(path: str, resume_epoch: int) -> int:
    """Physically truncate the log at ``path`` to records with
    epoch < resume_epoch (recovery discards the partial tail group the
    crash may have torn — group-commit acks gate on whole-group
    durability in fault mode, so no acked txn is lost).  Any torn tail
    bytes go with it.  Returns the last epoch kept (-1 if none)."""
    with open(path, "rb") as f:
        buf = f.read()
    keep_end = 0
    last = -1
    for epoch, _lo, hi in iter_record_spans(buf):
        if epoch >= resume_epoch:
            break
        keep_end = hi
        last = epoch
    if keep_end != len(buf):
        os.truncate(path, keep_end)
    return last


class EpochLogger:
    """Background log writer (the reference's LogThread).

    ``append`` enqueues; the writer thread writes + flushes and advances
    ``flushed_epoch``.  ``wait_flushed`` is the L_NOTIFY/park analogue —
    but callers poll it per epoch instead of parking per txn.
    """

    def __init__(self, path: str, append: bool = False,
                 flushed_epoch: int = -1):
        """``append`` (recovery): keep the existing prefix and write
        after it; ``flushed_epoch`` seeds the durability watermark with
        the last epoch of that prefix."""
        self.path = path
        self._q: _queue.Queue = _queue.Queue()
        self._flushed = flushed_epoch
        self._cv = threading.Condition()
        self._stop = False
        self._error: BaseException | None = None
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._f = open(path, "ab" if append else "wb")
        self._thr = threading.Thread(target=self._run, daemon=True)
        self._thr.start()
        self.records = 0
        self.bytes = 0

    def _raise_if_failed(self) -> None:
        # a dead writer thread means durability is gone: surface it loudly
        # instead of holding client acks forever
        if self._error is not None:
            raise RuntimeError(
                f"log writer failed for {self.path}") from self._error

    def append(self, epoch: int, blob: bytes, active: np.ndarray,
               framed: bytes | None = None) -> None:
        """Queue one epoch record; ``framed`` lets callers that already
        built the packed record (replica shipping) avoid packing twice."""
        self._raise_if_failed()
        self._q.put((epoch, framed if framed is not None
                     else pack_record(epoch, blob, active)))

    @property
    def flushed_epoch(self) -> int:
        self._raise_if_failed()
        with self._cv:
            return self._flushed

    def wait_flushed(self, epoch: int, timeout: float = 10.0) -> bool:
        self._raise_if_failed()
        with self._cv:
            return self._cv.wait_for(
                lambda: self._flushed >= epoch or self._error is not None,
                timeout)

    def _run(self) -> None:
        while True:
            try:
                item = self._q.get(timeout=0.05)
            except _queue.Empty:
                if self._stop:
                    return
                continue
            if item is None:
                return
            epoch, rec = item
            try:
                self._f.write(rec)
                self._f.flush()
                os.fsync(self._f.fileno())
            except OSError as e:
                with self._cv:
                    self._error = e
                    self._cv.notify_all()
                return
            self.records += 1
            self.bytes += len(rec)
            with self._cv:
                self._flushed = max(self._flushed, epoch)
                self._cv.notify_all()

    def close(self) -> None:
        self._stop = True
        self._q.put(None)
        self._thr.join(timeout=5)
        self._f.close()


def replay_into(path: str, cfg, wl, step, db, cc_state, stats,
                stop_epoch: int | None = None, on_epoch=None
                ) -> tuple[dict, object, dict, int]:
    """Re-execute the logged command stream into EXISTING engine state
    through the per-epoch jit ``step`` (``make_dist_step`` — kept
    precisely for this path).  Stops before ``stop_epoch`` when given.
    ``on_epoch(epoch, block, active, done)`` is called per replayed
    record (recovery seeds its committed-tag dedup set from the done
    masks).  Returns (db, cc_state, stats, last_replayed_epoch[-1])."""
    import jax
    import jax.numpy as jnp

    from deneva_tpu.runtime import wire

    with open(path, "rb") as f:
        buf = f.read()
    last = -1
    for epoch, blob, bits in unpack_records(buf):
        if stop_epoch is not None and epoch >= stop_epoch:
            break
        _, block, ts = wire.decode_epoch_blob(blob)
        active = np.unpackbits(bits)[: len(block.keys)].astype(bool)
        # logged ts length always equals the merged block length (the
        # server logs ts_np of exactly b_merged entries)
        if len(ts) != len(block.keys):
            raise ValueError(
                f"corrupt log record at epoch {epoch}: {len(ts)} ts for "
                f"{len(block.keys)} txns")
        query = wl.from_wire(block.keys, block.types, block.scalars)
        db, cc_state, stats, done, *_ = step(db, cc_state, stats,
                                             jnp.int32(epoch),
                                             jnp.asarray(active),
                                             jnp.asarray(ts.astype(np.int32)),
                                             query)
        if on_epoch is not None:
            on_epoch(epoch, block, active, np.asarray(done))
        last = epoch
    jax.block_until_ready(stats["total_txn_commit_cnt"])
    return db, cc_state, stats, last


def replay_log(path: str, cfg) -> dict:
    """Rebuild table state by re-executing the logged command stream
    (deterministic replay; the reference has no equivalent —
    `system/logger.cpp` writes records it never reads back).

    Returns the reconstructed ``db`` dict for this node's partition.
    """
    from deneva_tpu.cc import get_backend
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.workloads import get_workload

    wl = get_workload(cfg)
    be = get_backend(cfg.cc_alg)
    step = make_dist_step(cfg, wl, be)
    stats = init_device_stats(len(getattr(wl, "txn_type_names", ("txn",))))
    db, *_ = replay_into(path, cfg, wl, step, wl.load(),
                         be.init_state(cfg), stats)
    return db


def state_digest(db) -> str:
    """Order-stable sha256 over every pytree leaf of the engine state
    (the bit-for-bit recovery check: a replayed partition must hash
    identically to the state it reconstructs; pytree flattening order is
    deterministic for a fixed structure).  Leaves under ``__*__`` dict
    keys (control-plane state: the elastic membership owner array) are
    excluded — the digest covers ROW state, so an elastic run with no
    rebalance hashes identically to the same tables under static
    membership."""
    return state_digests(db)[0]


def state_digests(db) -> tuple[str, dict[str, str]]:
    """(`state_digest`, {leaf path: sha256 of that leaf alone}): the whole
    digest says THAT a replay differs, a leaf's own says WHERE — a path
    reads ``STOCK.columns.S_QUANTITY`` / ``ORDER.row_cnt``.  One pull of
    each leaf; its two hashes run side by side (hashlib releases the
    interpreter lock), so the pair costs the wall time of one."""
    import hashlib
    from concurrent.futures import ThreadPoolExecutor

    import jax

    h = hashlib.sha256()
    per_leaf: dict[str, str] = {}
    with ThreadPoolExecutor(max_workers=1) as pool:
        for path, leaf in jax.tree_util.tree_flatten_with_path(db)[0]:
            if any(str(getattr(p, "key", "")).startswith("__")
                   for p in path):
                continue
            buf = np.ascontiguousarray(np.asarray(leaf)).reshape(-1).view(
                np.uint8)
            own = pool.submit(hashlib.sha256, buf)
            try:
                h.update(buf)
            finally:        # (a future is drained on every path)
                per_leaf[".".join(
                    str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)] = own.result().hexdigest()
    return h.hexdigest(), per_leaf
