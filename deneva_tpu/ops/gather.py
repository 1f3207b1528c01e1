"""Row gather of the lanes that need the table, and no others — the READ
half of what `ops.scatter.scatter_winner_rows` is for the write.

XLA's row gather on one v5e costs a price a LANE of `u8[rows, 100]`
that depends on how many lanes the call holds (5.9 ns at 2,560, 9.4 at
25,600, 9.75 at 92,160, 10.4 at 163,840) and on nothing else: no fixed
cost, no discount for a lane parked on the trash row or for a row
another lane has just read (PERF.md sections 5 and 6).  An epoch's
forwarding plan hands it write lanes, forwarded reads and a mesh
shard's padding; all the epoch needs from the table are the reads that
nothing forwards to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# The gather takes a whole number of sixteenths of the plan's lanes, in
# ONE call: the last sixteenth's empty half costs ~50 us an epoch (hot
# cell), and each size is one gather and one reduction on the device.  A
# loop over chunks of N / 64 gathers a lane for 5.9 ns and not 9.75, and
# spends five device ops a trip — and the ops an epoch issues are what a
# traced window of four chips has to store inside its harness's wait
# (PERF.md section 7: the loop form timed it out).
_RUNGS = 16
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
    `lax.sort`; the first ``ceil(cnt / (N / 16))`` sixteenths of the
    lanes are then gathered in one call and folded into the scalar at
    once — no ``[N, row]`` value array outlives the fold (nothing
    needed: no gather at all).  Where that saves fewer lanes than the
    sort costs (a read-only epoch) the lanes are gathered as they stand,
    all of them, and the lanes not needed masked out of the sum.  Every
    ``slots`` entry must index ``col`` (a trash row counts: a needed
    lane parked there reads it, as the per-lane gather did)."""
    n = slots.shape[0]
    slots = slots.astype(jnp.int32)
    cnt = need.sum(dtype=jnp.int32)
    rung = -(-n // _RUNGS)
    pad = rung * _RUNGS - n
    rungs = (cnt + (rung - 1)) // rung
    compact = (rungs * rung + n // _SORT_PER_LANES) < n

    def front(_):
        big = jnp.int32(jnp.iinfo(jnp.int32).max)
        first = jnp.arange(n, dtype=jnp.int32) < cnt
        # past the needed lanes: any row, left out of the sum
        return jnp.where(first, jnp.sort(jnp.where(need, slots, big)), 0), \
            first

    idx, take = jax.lax.cond(compact, front, lambda _: (slots, need), None)
    rungs = jnp.where(compact, rungs, _RUNGS)
    idx = jnp.concatenate([idx, jnp.zeros((pad,), jnp.int32)])
    take = jnp.concatenate([take, jnp.zeros((pad,), bool)])

    def first_rungs(k):
        def fold():
            # (clip: every index is a row of col; no fill mask to compute)
            rows = jnp.take(col, idx[:k * rung], axis=0, mode="clip")
            return jnp.sum(jnp.where(take[:k * rung, None],
                                     rows.reshape(k * rung, -1), 0),
                           dtype=jnp.uint32)
        # (zeros_like: under a shard_map every branch varies over the mesh)
        return fold if k else lambda: jnp.zeros_like(cnt, jnp.uint32)

    total = jax.lax.switch(rungs,
                           [first_rungs(k) for k in range(_RUNGS + 1)])
    return total, (rungs * rung).astype(jnp.uint32)
