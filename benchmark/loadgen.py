"""The benchmark's one traffic generator: a YCSB client process on the CPU.

    python benchmark/loadgen.py <spec.json> <client index>

Reads its parameters from the run's spec (the cell's traffic file merged
with the configuration file and ``--seed``), pre-generates a ring of
query blocks with numpy (`make_ring`: every key and request type drawn
from ``--seed`` and the client's index), then drives one server over the
program's native transport and times every acknowledgement on ITS OWN
clock.  No JAX, nothing of the program but the transport (the system
under test's own wire) — so a change to the program's client cannot move
what the benchmark measures.  (Generator and closed loop follow
`deneva_tpu/runtime/client.py`'s LOAD_MAX mode: PERF.md, Open questions.)

Traffic parameters (``benchmark/traffic/<name>.json``):

    zipf_theta       key skew over [0, rows): Gray's zipfian, 0 = uniform
    read_share       probability that a request of a transaction that may
                     write reads (the source's 1 - TUP_WRITE_PERC)
    txn_write_share  probability that a transaction may write at all;
                     else every request of it reads (TXN_WRITE_PERC)
    arrival          "closed": send a block whenever fewer than the cap
                     are outstanding (saturating); nothing else yet
    clients          client processes
    ring_txns        transactions a client draws from the seed before the
                     start and then sends round and round
    warmup_secs      served before the measured window opens

The measured window is the client's: it opens ``warmup_secs`` after the
start barrier and lasts ``seconds``; the server is kept serving past its
end.  Reported per client (JSON on the last stdout line): transactions
sent and acknowledged over the whole run and inside the window, the
acknowledgements of each second since the barrier, and the window's
acknowledgement latencies (a float32 ``.npy`` beside the spec).
"""

from __future__ import annotations

import json
import os
import struct
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TAG_RING = 1 << 22          # outstanding-tag ring; must exceed the cap
RING_CHUNK = 1 << 18        # drawn this many at a time (bounds temporaries)
_Q_HDR = struct.Struct("<III")
_RSP = struct.Struct("<II")


def zeta(n: int, theta: float) -> float:
    return float(np.sum(1.0 / np.arange(1, n + 1, dtype=np.float64)
                        ** theta))


def zipf_keys(rng: np.random.Generator, shape, n: int, theta: float,
              zetan: float) -> np.ndarray:
    """Gray et al.'s zipfian over [0, n) as YCSB and Deneva draw it
    (`ycsb_query.cpp` zipf()): key 0 is the hottest; theta 0 is uniform.
    ``zetan`` = ``zeta(n, theta)``, computed once by the caller."""
    u = rng.random(shape)
    if theta == 0.0:
        return np.minimum((u * n).astype(np.int64), n - 1).astype(np.int32)
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    spread = (n * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    v = np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, spread))
    return np.clip(v, 0, n - 1).astype(np.int32)


def make_ring(seed: int, client: int, ring_txns: int, batch: int, width: int,
              n_rows: int, theta: float, read_share: float,
              txn_write_share: float):
    """[(keys int32[batch,width], types int8[batch,width])], ``ring_txns``
    transactions in all, every one drawn from ``seed`` and the client's
    index: keys by the zipfian, and request types as `ycsb_query.cpp`
    draws them — one draw per transaction decides whether it may write
    at all (``txn_write_share``), one per request whether it reads
    (``read_share``).  The ring is the sample of the workload that a run
    sends, again and again, so it is large: with 65,536 transactions a
    ring's few hottest ones differed enough between seeds to move OCC's
    throughput by +-5% and its p99 2.5x (my chip runs, PR 24)."""
    zetan = zeta(n_rows, theta) if theta else 0.0
    keys = np.empty((ring_txns, width), np.int32)
    types = np.empty((ring_txns, width), np.int8)
    for c, i in enumerate(range(0, ring_txns, RING_CHUNK)):
        rng = np.random.default_rng([int(seed), int(client), c])
        n = min(RING_CHUNK, ring_txns - i)
        keys[i:i + n] = zipf_keys(rng, (n, width), n_rows, theta, zetan)
        reads = rng.random((n, width)) < read_share
        reads |= rng.random((n, 1)) >= txn_write_share
        types[i:i + n] = np.where(reads, 1, 2)
    return [(keys[i:i + batch], types[i:i + batch])
            for i in range(0, ring_txns - batch + 1, batch)]


def block_parts(tags: np.ndarray, keys: np.ndarray, types: np.ndarray):
    """CL_QRY_BATCH body as scatter-send parts (header, tags, key and
    type columns; YCSB carries no scalars)."""
    n, w = keys.shape
    return [_Q_HDR.pack(n, w, 0), tags, keys, types,
            np.zeros((n, 0), np.int32)]


def run_client(spec: dict, idx: int) -> dict:
    sys.path.insert(0, ROOT)
    from deneva_tpu.runtime.native import NativeTransport

    tr, tp_cfg = spec["traffic"], spec["transport"]
    n_srv, n_cl = 1, int(tr["clients"])
    me, n_all = n_srv + idx, n_srv + n_cl
    batch = int(spec["fields"]["client_batch_size"])
    width = int(spec["fields"]["req_per_query"])
    cap = max(64, int(spec["fields"]["max_txn_in_flight"]) // n_cl)
    if cap >= TAG_RING:
        raise ValueError(f"in-flight cap {cap} must stay under the tag "
                         f"ring ({TAG_RING})")
    warm, seconds = float(tr["warmup_secs"]), float(spec["seconds"])
    if tr["arrival"] != "closed":
        raise ValueError(f"arrival {tr['arrival']!r}: this generator "
                         "sends a closed loop only")
    ring = make_ring(spec["seed"], idx, int(tr["ring_txns"]), batch, width,
                     int(spec["fields"]["synth_table_size"]),
                     float(tr["zipf_theta"]), float(tr["read_share"]),
                     float(tr["txn_write_share"]))

    tp = NativeTransport(me, spec["endpoints"], n_all,
                         msg_size_max=tp_cfg["msg_size_max"],
                         send_threads=tp_cfg["send_threads"],
                         recv_threads=tp_cfg["recv_threads"])
    send_ns = np.zeros(TAG_RING, np.int64)
    lat_chunks: list[np.ndarray] = []
    st = dict(sent=0, acked=0, win_sent=0, win_acked=0, inflight=0,
              stop=False)
    win = [0, 0, 0]                     # window edges and the barrier, ns
    acks_by_s: dict[int, int] = {}      # acks per second since the barrier

    def on_msg(src, rtype, payload):
        if rtype == "CL_RSP":
            n, _ = _RSP.unpack_from(payload)
            tags = np.frombuffer(payload, np.int64, n, _RSP.size)
            now = time.monotonic_ns()
            st["acked"] += n
            st["inflight"] -= n
            sec = (now - win[2]) // 1_000_000_000
            acks_by_s[sec] = acks_by_s.get(sec, 0) + n
            if win[0] <= now < win[1]:
                st["win_acked"] += n
                lat_chunks.append(
                    ((now - send_ns[tags % TAG_RING]) * 1e-6
                     ).astype(np.float32))
        elif rtype == "SHUTDOWN":
            st["stop"] = True

    try:
        tp.start(int(spec["setup_wait_s"] * 1000))
        # start barrier: INIT_DONE to every peer, then wait for theirs
        for p in range(n_all):
            if p != me:
                tp.send(p, "INIT_DONE")
        tp.flush()
        seen, t_wait = {me}, time.monotonic()
        while len(seen) < n_all:
            if time.monotonic() - t_wait > spec["setup_wait_s"]:
                raise TimeoutError(f"client {me}: start barrier timed out "
                                   f"(saw {sorted(seen)})")
            m = tp.recv(10_000)
            if m is None:
                continue
            if m[1] == "INIT_DONE":
                seen.add(m[0])
            else:
                on_msg(*m)
        t0 = win[2] = time.monotonic_ns()
        win[0] = t0 + int(warm * 1e9)
        win[1] = win[0] + int(seconds * 1e9)
        if idx == 0 and spec.get("barrier_file"):
            # the traced server opens its profiler window from this
            tmp = spec["barrier_file"] + ".tmp"
            with open(tmp, "w") as f:
                f.write(str(t0))
            os.replace(tmp, spec["barrier_file"])
        iota = np.arange(batch, dtype=np.int64)
        next_tag = pos = 0
        while not st["stop"]:
            progressed = False
            n = min(batch, cap - st["inflight"])
            now = time.monotonic_ns()
            if n >= 64:
                keys, types = ring[pos]
                pos = (pos + 1) % len(ring)
                tags = (iota[:n] + next_tag) % TAG_RING
                next_tag = int(tags[-1]) + 1
                send_ns[tags] = now
                tp.sendv(0, "CL_QRY_BATCH",
                         block_parts(tags, keys[:n], types[:n]))
                st["sent"] += n
                st["inflight"] += n
                if win[0] <= now < win[1]:
                    st["win_sent"] += n
                progressed = True
            timeout = 0 if progressed else 2_000
            for _ in range(4096):
                m = tp.recv(timeout)
                if m is None:
                    break
                on_msg(*m)
                timeout = 0
        t_end = time.monotonic() + 0.3      # trailing acknowledgements
        while time.monotonic() < t_end:
            m = tp.recv(20_000)
            if m is not None:
                on_msg(*m)
        run_s = (time.monotonic_ns() - t0) * 1e-9
        net = {k: int(v) for k, v in tp.stats().items()}
    finally:
        tp.close()
    lat = np.concatenate(lat_chunks) if lat_chunks else np.zeros(0, np.float32)
    lat_path = os.path.join(os.path.dirname(spec["spec_path"]),
                            f"client{idx}_lat.npy")
    np.save(lat_path, lat)
    return dict(client=idx, sent=st["sent"], acked=st["acked"],
                win_sent=st["win_sent"], win_acked=st["win_acked"],
                window_s=seconds, run_s=run_s, cap=cap,
                window_closed=bool(time.monotonic_ns() >= win[1]),
                lat_path=lat_path, net=net,
                acks_by_s=[acks_by_s.get(i, 0)
                           for i in range(max(acks_by_s, default=-1) + 1)])


def main(argv: list[str]) -> int:
    with open(argv[0]) as f:
        spec = json.load(f)
    spec["spec_path"] = os.path.abspath(argv[0])
    print("[client] " + json.dumps(run_client(spec, int(argv[1]))),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
