"""TPC-C's traffic (Payment + NewOrder, as Deneva's client draws them):
what is workload-specific of a client, and nothing else.

The four functions `generators/ycsb.py` documents (`check`,
`server_fields`, `make_ring`, `block_parts`); `benchmark/loadgen.py` owns
the process, the transport, the arrival loop, the window and the
latencies.  Numpy only, nothing of the program: the draws are the
source's (`tpcc_query.cpp` gen_payment / gen_new_order), restated —

* warehouse and district uniform;
* Payment: the customer's warehouse and district are the home ones for
  85% and another warehouse (uniform among the others) and a uniform
  district for 15%; 60% pick the customer by last name,
  Lastname(NURand(255, 0, 999)), else by id NURand(1023); `h_amount` =
  URand(1, 5000), a WHOLE number (the program adds it into float32
  accumulators with a scatter-add, whose order is not the serial one:
  whole numbers add exactly in any order while a sum stays under 2^24);
* NewOrder: customer NURand(1023), `ol_cnt` = URand(5, 15), items
  NURand(8191) with repeats drawn again, quantity URand(1, 10), the
  supply warehouse another one for 1% of the lines.

NURand(A, 0, n-1) = ((URand(0, A) | URand(0, n-1)) + C) % n with C drawn
once per run (here: from the seed, the same for every client).  By last
name resolves AT THE CLIENT to the middle customer of that name by the
closed form (the loader names customer c ``c % names``, names = min(1000,
cust_per_dist)): the wire carries `c_id`; the source probes
CUSTOMER_LAST on the server (the configuration's `assumed`).

The wire layout is the program's (`TPCCWorkload.to_wire`): keys
int32[n, 3 I] = [items | supply_w | quantity] (I = `max_items_per_txn`),
types int8[n, 3 I] with item validity in the first I lanes, scalars
int32[n, 8] = txn_type (0 Payment, 1 NewOrder), w_id, d_id, c_id, c_w_id,
c_d_id, h_amount as float32 bits, ol_cnt.

Traffic parameters (``benchmark/traffic/<name>.json``):

    perc_payment     share of Payments (PERC_PAYMENT); the rest NewOrder
    arrival          "closed": send a block whenever fewer than the cap
                     are outstanding (saturating); nothing else yet
    clients          client processes
    ring_txns        transactions a client draws from the seed before the
                     start and then sends round and round
    warmup_secs      served before the measured window opens
"""

from __future__ import annotations

import struct

import numpy as np

RING_CHUNK = 1 << 18        # drawn this many at a time (bounds temporaries)
_Q_HDR = struct.Struct("<III")
KEYS = {"perc_payment", "arrival", "clients", "ring_txns", "warmup_secs"}
DIST_PER_WARE = 10
PAYMENT, NEW_ORDER = 0, 1
N_SCALARS = 8
# the source's constants (TPC-C 2.4.1 / 2.5.1, `tpcc_query.cpp`)
REMOTE_CUSTOMER_PCT, BY_LAST_NAME_PCT, REMOTE_LINE_PCT = 15, 60, 1


def check(traffic: dict) -> None:
    """The keys this generator knows, their ranges, an arrival law it
    has."""
    if traffic.get("arrival") != "closed":
        raise ValueError(f"arrival {traffic.get('arrival')!r}: this "
                         "generator sends a closed loop only")
    if set(traffic) != KEYS:
        raise ValueError(f"tpcc traffic has the keys {sorted(KEYS)}, not "
                         f"{sorted(traffic)}")
    if not 0.0 <= traffic["perc_payment"] <= 1.0:
        raise ValueError("perc_payment is a probability")
    if traffic["clients"] < 1 or traffic["ring_txns"] < 1 \
            or traffic["warmup_secs"] < 0:
        raise ValueError("clients and ring_txns are at least 1, "
                         "warmup_secs at least 0")


def server_fields(traffic: dict) -> dict:
    """The mix as the program names it (the server builds its workload
    object from the fields; the queries come from the clients)."""
    return dict(perc_payment=traffic["perc_payment"])


def nurand_consts(seed: int) -> dict[int, int]:
    """C of NURand(A, ...) for A = 255, 1023, 8191: drawn once per run,
    the same for every client."""
    rng = np.random.default_rng([int(seed), 0xC0])
    return {a: int(rng.integers(0, a + 1)) for a in (255, 1023, 8191)}


def nurand(rng: np.random.Generator, a: int, n: int, c: int, shape
           ) -> np.ndarray:
    """NURand(a, 0, n - 1)."""
    return ((rng.integers(0, a + 1, shape) | rng.integers(0, n, shape))
            + c) % n


def draw(rng: np.random.Generator, n: int, fields: dict, perc_payment: float,
         consts: dict[int, int]):
    """``n`` transactions as (keys, types, scalars) in the wire layout."""
    n_wh, cpd = int(fields["num_wh"]), int(fields["cust_per_dist"])
    n_items, ipt = int(fields["max_items"]), int(fields["max_items_per_txn"])
    pay = rng.random(n) < perc_payment
    w = rng.integers(0, n_wh, n)
    d = rng.integers(0, DIST_PER_WARE, n)
    # Payment's customer: home, or another warehouse's
    remote = (rng.integers(1, 101, n) > 100 - REMOTE_CUSTOMER_PCT) \
        & (n_wh > 1)
    other = rng.integers(0, max(n_wh - 1, 1), n)
    other += other >= w
    c_w = np.where(pay & remote, other, w)
    c_d = np.where(pay & remote, rng.integers(0, DIST_PER_WARE, n), d)
    names = min(1000, cpd)
    by_last = rng.integers(1, 101, n) <= BY_LAST_NAME_PCT
    middle = nurand(rng, 255, names, consts[255], n) \
        + names * (cpd // names // 2)
    by_id = nurand(rng, 1023, cpd, consts[1023], n)
    c_id = np.where(pay & by_last, middle, by_id)
    h_amount = np.where(pay, rng.integers(1, 5001, n), 0).astype(np.float32)
    # NewOrder's lines: ol_cnt of them, items without repeats
    ol_cnt = np.where(pay, 0, rng.integers(5, ipt + 1, n))
    lane = np.arange(ipt)
    valid = lane[None, :] < ol_cnt[:, None]
    items = nurand(rng, 8191, n_items, consts[8191], (n, ipt))
    while True:
        srt = np.sort(np.where(valid, items, -1 - lane[None, :]), axis=1)
        rows = np.flatnonzero((srt[:, 1:] == srt[:, :-1]).any(axis=1))
        if not len(rows):
            break
        # the source draws a repeated item again: here the later lanes
        for r in rows:
            _, first = np.unique(items[r, :ol_cnt[r]], return_index=True)
            again = np.setdiff1d(np.arange(ol_cnt[r]), first)
            items[r, again] = nurand(rng, 8191, n_items, consts[8191],
                                     len(again))
    quantity = rng.integers(1, 11, (n, ipt))
    far = (rng.integers(1, 101, (n, ipt)) <= REMOTE_LINE_PCT) & (n_wh > 1)
    other = rng.integers(0, max(n_wh - 1, 1), (n, ipt))
    other += other >= w[:, None]
    supply = np.where(far, other, w[:, None])
    keys = np.concatenate([np.where(valid, items, 0),
                           np.where(valid, supply, w[:, None]),
                           np.where(valid, quantity, 0)],
                          axis=1).astype(np.int32)
    types = np.zeros((n, 3 * ipt), np.int8)
    types[:, :ipt] = valid
    scalars = np.stack([
        np.where(pay, PAYMENT, NEW_ORDER), w, d, c_id, c_w, c_d,
        h_amount.view(np.int32), ol_cnt], axis=1).astype(np.int32)
    return keys, types, scalars


def make_ring(spec: dict, client: int):
    """[(keys int32[batch, 3 I], types int8[batch, 3 I], scalars
    int32[batch, 8])] for a run's spec (``seed``, ``traffic``, the
    launch's ``fields``): the traffic's ``ring_txns`` transactions in
    all, every one drawn from the seed and the client's index."""
    tr, f = spec["traffic"], spec["fields"]
    ring_txns, batch = int(tr["ring_txns"]), int(f["client_batch_size"])
    width = 3 * int(f["max_items_per_txn"])
    consts = nurand_consts(spec["seed"])
    keys = np.empty((ring_txns, width), np.int32)
    types = np.empty((ring_txns, width), np.int8)
    scalars = np.empty((ring_txns, N_SCALARS), np.int32)
    for c, i in enumerate(range(0, ring_txns, RING_CHUNK)):
        rng = np.random.default_rng([int(spec["seed"]), int(client), c])
        n = min(RING_CHUNK, ring_txns - i)
        keys[i:i + n], types[i:i + n], scalars[i:i + n] = draw(
            rng, n, f, float(tr["perc_payment"]), consts)
    return [(keys[i:i + batch], types[i:i + batch], scalars[i:i + batch])
            for i in range(0, ring_txns - batch + 1, batch)]


def block_parts(tags: np.ndarray, keys: np.ndarray, types: np.ndarray,
                scalars: np.ndarray):
    """CL_QRY_BATCH body as scatter-send parts (header, tags, key, type
    and scalar columns)."""
    n, w = keys.shape
    return [_Q_HDR.pack(n, w, scalars.shape[1]), tags, keys, types, scalars]
