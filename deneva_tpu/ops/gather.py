"""Row gather of the lanes that need the table, and no others — the READ
half of what `ops.scatter.scatter_winner_rows` is for the write.

XLA's row gather on one v5e prices a LANE of `u8[rows, 100]` by the
lane count of the call it rides in, in two tiers and not by size: 7.3-
8.7 ns with its fold at most counts (ONE call of 92,161 as a call of
2,560), 10.7-14.2 at every multiple of 1,024 tried and at a few others
(PR 30 tried only multiples beside 2,560 and read a price by size); no
fixed cost, no discount for a lane parked on the trash row or for a row
another lane has just read (PERF.md sections 5 and 6).  An epoch's
forwarding plan hands it write lanes, forwarded reads and a mesh
shard's padding; all the epoch needs from the table are the reads that
nothing forwards to — brought to the front by one sort, then gathered
in a loop of short calls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# The needed lanes are gathered in calls of N / 64 lanes: 2,560 in the
# hot cell, 1,280 a shard of four.  On one v5e, `u8[6291520, 100]`
# (`tools/gather_calls.py`, my chip runs, PR 47; PERF.md section 6): a
# lane costs the gather 5.9 ns in a call of 2,560 and 9.7 in ONE call
# of 92,160; with the fold and the loop's four device ops a trip, 8.1
# ns at 2,560 lanes a call and 8.7 at 1,280, but 11.3-12.9 at 1,024,
# 2,048, 4,096, 5,120 and 10,240 against 11.0 for the one call: a plan
# whose N / 64 is a multiple of 1,024 wants another divisor.  The hot
# cell served 1.1% more with calls of 2,560 than of 1,280.
_CHUNKS = 64
# A `lax.sort` of n lanes costs what the gather of about n / 8 row lanes
# costs (hot cell, 163,840 lanes: 0.124 ms for a 2-operand sort against
# 5.9-9.75 ns a lane — PERF.md section 6, my chip runs, PR 30):
# compaction pays while it saves more lanes than that.
_SORT_PER_LANES = 8


def checksum_needed_rows(col: jax.Array, slots: jax.Array, need: jax.Array):
    """``(sum, lanes)``: the uint32 sum of the bytes of row ``col[slot]``
    over the ``need`` lanes, mod 2^32, and how many lanes the row gather
    was handed (uint32).

    The needed lanes' slots are brought to the front, ascending, by one
    `lax.sort`; the front is then gathered in ``ceil(cnt / chunk)``
    calls of ``chunk = ceil(N / 64)`` lanes, each folded into the scalar
    the loop carries — no ``[N, row]`` value array outlives a trip
    (nothing needed: no trip at all).  Where that saves fewer lanes than
    the sort costs (a read-only epoch) the lanes are gathered as they
    stand, all of them, and the lanes not needed masked out of the sum.
    Every ``slots`` entry must index ``col`` (a trash row counts: a
    needed lane parked there reads it, as the per-lane gather did)."""
    n = slots.shape[0]
    slots = slots.astype(jnp.int32)
    cnt = need.sum(dtype=jnp.int32)
    chunk = -(-n // _CHUNKS)
    pad = -n % chunk
    trips = (cnt + (chunk - 1)) // chunk
    compact = (trips * chunk + n // _SORT_PER_LANES) < n

    # `big`: a lane left out of the sum (the gather clips it to a row)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    idx = jax.lax.cond(compact, jnp.sort, lambda x: x,
                       jnp.where(need, slots, big))
    trips = jnp.where(compact, trips, (n + pad) // chunk)
    idx = jnp.concatenate([idx, jnp.full((pad,), big)])

    def body(i, total):
        cut = jax.lax.dynamic_slice_in_dim(idx, i * chunk, chunk)
        # (clip: every index is a row of col; no fill mask to compute)
        rows = jnp.take(col, cut, axis=0, mode="clip")
        return total + jnp.sum(
            jnp.where((cut < big)[:, None], rows.reshape(chunk, -1), 0),
            dtype=jnp.uint32)

    # (zeros_like: under a shard_map the carry varies over the mesh)
    total = jax.lax.fori_loop(0, trips, body,
                              jnp.zeros_like(cnt, jnp.uint32))
    return total, (trips * chunk).astype(jnp.uint32)
