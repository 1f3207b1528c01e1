"""PPS's traffic (Deneva's Product-Parts-Suppliers, as its client draws
it): what is workload-specific of a client, and nothing else.

The four functions `generators/ycsb.py` documents (`check`,
`server_fields`, `make_ring`, `block_parts`); `benchmark/loadgen.py` owns
the process, the transport, the arrival loop, the window and the
latencies.  Numpy only, nothing of the program: the draws are the
source's (`pps_query.cpp` gen_requests_* — recalled, the configuration's
`assumed` says so), restated —

* the transaction type from the `PERC_PPS_*` mix, one uniform draw
  against the cumulative shares in the source's order (GETPART,
  GETPRODUCT, GETSUPPLIER, GETPARTBYPRODUCT, GETPARTBYSUPPLIER,
  ORDERPRODUCT, UPDATEPRODUCTPART, UPDATEPART);
* `part_key`, `product_key`, `supplier_key` uniform over their tables,
  each drawn for every transaction whatever its type (a type reads the
  ones it needs: UPDATEPRODUCTPART its product and the part it maps the
  product's first mapping row to).

No part key of a walk is on the wire: the server resolves a product's or
a supplier's parts from its own mapping tables, which UPDATEPRODUCTPART
rewrites.

The wire layout is the program's (`PPSWorkload.to_wire`): keys
int32[n, 1] and types int8[n, 1] of zeros (no per-access column), scalars
int32[n, 4] = txn_type, part_key, product_key, supplier_key.

Traffic parameters (``benchmark/traffic/<name>.json``):

    perc_<type>      the eight shares, by the program's names
                     (`perc_getparts` ... `perc_updatepart`); they sum
                     to 1
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
# the mix, in the order of the transaction type's number
MIX = ("perc_getparts", "perc_getproducts", "perc_getsuppliers",
       "perc_getpartbyproduct", "perc_getpartbysupplier",
       "perc_orderproduct", "perc_updateproductpart", "perc_updatepart")
KEYS = set(MIX) | {"arrival", "clients", "ring_txns", "warmup_secs"}
N_SCALARS = 4


def check(traffic: dict) -> None:
    """The keys this generator knows, their ranges, an arrival law it
    has."""
    if traffic.get("arrival") != "closed":
        raise ValueError(f"arrival {traffic.get('arrival')!r}: this "
                         "generator sends a closed loop only")
    if set(traffic) != KEYS:
        raise ValueError(f"pps traffic has the keys {sorted(KEYS)}, not "
                         f"{sorted(traffic)}")
    shares = [traffic[k] for k in MIX]
    if any(not 0.0 <= s <= 1.0 for s in shares) \
            or abs(sum(shares) - 1.0) > 1e-9:
        raise ValueError("the eight perc_* are probabilities that sum "
                         "to 1")
    if traffic["clients"] < 1 or traffic["ring_txns"] < 1 \
            or traffic["warmup_secs"] < 0:
        raise ValueError("clients and ring_txns are at least 1, "
                         "warmup_secs at least 0")


def server_fields(traffic: dict) -> dict:
    """The mix as the program names it (the server builds its workload
    object from the fields; the queries come from the clients)."""
    return {k: traffic[k] for k in MIX}


def draw(rng: np.random.Generator, n: int, fields: dict, shares) -> np.ndarray:
    """``n`` transactions as the wire's scalars int32[n, 4]."""
    cum = np.cumsum(np.asarray(shares, np.float64))
    cum[-1] = 1.0               # the last type takes the rounding
    kind = np.searchsorted(cum, rng.random(n), side="right")
    return np.stack([
        kind,
        rng.integers(0, int(fields["pps_parts_cnt"]), n),
        rng.integers(0, int(fields["pps_products_cnt"]), n),
        rng.integers(0, int(fields["pps_suppliers_cnt"]), n)],
        axis=1).astype(np.int32)


def make_ring(spec: dict, client: int):
    """[(keys int32[batch, 1], types int8[batch, 1], scalars
    int32[batch, 4])] for a run's spec (``seed``, ``traffic``, the
    launch's ``fields``): the traffic's ``ring_txns`` transactions in
    all, every one drawn from the seed and the client's index."""
    tr, f = spec["traffic"], spec["fields"]
    ring_txns, batch = int(tr["ring_txns"]), int(f["client_batch_size"])
    shares = [float(tr[k]) for k in MIX]
    scalars = np.empty((ring_txns, N_SCALARS), np.int32)
    for c, i in enumerate(range(0, ring_txns, RING_CHUNK)):
        rng = np.random.default_rng([int(spec["seed"]), int(client), c])
        n = min(RING_CHUNK, ring_txns - i)
        scalars[i:i + n] = draw(rng, n, f, shares)
    keys = np.zeros((batch, 1), np.int32)
    types = np.zeros((batch, 1), np.int8)
    return [(keys, types, scalars[i:i + batch])
            for i in range(0, ring_txns - batch + 1, batch)]


def block_parts(tags: np.ndarray, keys: np.ndarray, types: np.ndarray,
                scalars: np.ndarray):
    """CL_QRY_BATCH body as scatter-send parts (header, tags, key, type
    and scalar columns)."""
    n, w = keys.shape
    return [_Q_HDR.pack(n, w, scalars.shape[1]), tags, keys, types, scalars]
