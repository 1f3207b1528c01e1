"""Conflict-matrix and serialization-sweep kernels — the heart of the build.

The reference detects conflicts one row at a time: every ``row_t`` owns a
per-algorithm manager with latched owner/waiter lists
(`concurrency_control/row_lock.cpp`, `row_ts.cpp`, ...), reached through
`row_t::get_row` (`storage/row.cpp:197-310`).  The TPU-native replacement
detects *all* conflicts of an epoch at once:

1. Pairwise overlap — "does txn i's A-set meet txn j's B-set", a boolean
   [B, B] matrix; read-write / write-write decompositions are just
   different choices of A and B — is a compare of the exact keys
   (`key_overlap`): the combined identities of the two padded RW-sets
   compared pair by pair on the VPU, A x A compares ORed into the
   [B, B] matrix in one fusion.  No false conflict, no memory beside the
   result.  The CC backends reach it through `cc.base.Incidence.overlap`.
2. The readers that want per-bucket column sums and never pairs
   (`cc.base.committed_write_frontier`, `conflict_density`, the router's
   cross-group defers) hash each RW-set into a bucket space of width K
   (`deneva_tpu.ops.hashing`) and scatter it into incidence matrices
   ``W, U ∈ {0,1,...}^{B×K}`` (`access_incidence`).  The pairwise
   overlap is NOT drawn from these: ``(A @ B.T) > 0`` on the MXU with
   two hash families ANDed is linear in A where the compare is
   quadratic, but slower than it up to A ~55 at B 1024, K 8192 (PERF.md
   section 6, PR 32); the cells plan 10 accesses a txn, the default
   `max_accesses` is 16, TPC-C's NewOrder needs 18.
3. A *serialization sweep* turns the boolean conflict matrix plus a
   priority order into per-transaction verdicts:

   * `greedy_first_fit` — lexicographically-first maximal independent set
     in priority order: the batch analogue of "first to the lock wins"
     (NO_WAIT/WAIT_DIE owners, OCC serial validation order).  Computed as
     a matvec fixpoint: a txn wins once all earlier conflicting txns have
     lost, loses once any earlier conflicting txn has won.  Each round
     decides at least the earliest undecided txn, so ``rounds`` bounds the
     resolved conflict-chain depth; leftovers are reported undecided and
     the caller defers them to the next epoch (never unsafe).
   * `wavefront_levels` — longest-conflict-chain depth per txn; Calvin's
     deterministic execution uses it to chain intra-epoch read-after-write
     dataflow (level l reads see levels < l), replacing the reference's
     per-row FIFO lock queues (`row_lock.cpp:152-170`).
   * `precedence_levels` — longest-path levels in a *directed*
     must-precede graph with cycle over-approximation, used by MAAT's
     dynamic-ordering validation (`concurrency_control/maat.cpp:44-162`).

Safety argument used throughout: bucket collisions and undecided leftovers
only ever *add* conflicts/deferrals, never hide one, so every sweep output
is serializable even at tiny K or rounds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def access_incidence(bucket_ids: jax.Array, valid: jax.Array,
                     n_buckets: int) -> jax.Array:
    """Build the B×K incidence matrix of an epoch's accesses.

    bucket_ids: int32[B, A] hashed bucket per padded access slot.
    valid: bool[B, A] (padding / inactive accesses excluded).
    Returns bfloat16[B, K] counts (exact for A ≤ 256) ready for the MXU.
    """
    b, a = bucket_ids.shape
    rows = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None], (b, a))
    cols = jnp.where(valid, bucket_ids, 0)
    vals = valid.astype(jnp.bfloat16)
    inc = jnp.zeros((b, n_buckets), jnp.bfloat16)
    return inc.at[rows, cols].add(vals)


# `key_overlap`'s padding: what a masked-out lane of either side compares
# as.  Two DISTINCT values, so padding never meets padding; both are
# `combine_key` images of table 0's keys -1 and -2, which no non-negative
# key of table 0 maps to (the multiplier is odd, so `combine_key` is a
# bijection of the key within a table).  In another table some key's
# identity may equal one of them (one in 2^32): that key then reads as
# conflicting with padded lanes — an added conflict, never a hidden one.
# (numpy scalars: a `jnp` constant here would start a backend at import)
_PAD_A = np.uint32((-2654435761) % (1 << 32))
_PAD_B = np.uint32((-2 * 2654435761) % (1 << 32))


def key_overlap(ident: jax.Array, mask_a: jax.Array, mask_b: jax.Array,
                cols_a=None, cols_b=None) -> jax.Array:
    """bool[B, B]: does txn i's A-set hold a key of txn j's B-set?

    ident: uint32[B, A] combined identities (`combine_key`) of the padded
    access slots; mask_a / mask_b: bool[B, A], the slots of each side;
    ``cols_a`` / ``cols_b``: the access columns either side can sit in,
    where the caller knows them (None: all A) — the compare is unrolled
    over their product, so a side that lives in one column costs a
    twenty-first of one that may live anywhere among 21.
    One compare and one OR per key pair on the VPU, A x A of them
    unrolled over a [B, B] accumulator — the chip's compiler makes ONE
    loop fusion of it with the caller's `earlier_edges` and cast folded
    in; no arena, no scatter, no matmul.  0.65-0.75 ps a key pair and
    matrix element on one v5e: 0.069 ms at B 1024 x A 10, 0.175 at A 16,
    0.81 at A 32, 0.27 at B 2048 x A 10 (my chip run, PR 32).
    """
    ka = jnp.where(mask_a, ident, _PAD_A)
    kb = jnp.where(mask_b, ident, _PAD_B).T        # [A, B]: rows of lanes
    b, a = ident.shape
    m = jnp.zeros((b, b), bool)
    for i in range(a) if cols_a is None else cols_a:
        col = ka[:, i, None]
        for j in range(a) if cols_b is None else cols_b:
            m |= col == kb[j]
    return m


def earlier_edges(conflict: jax.Array, rank: jax.Array,
                  active: jax.Array) -> jax.Array:
    """Directed edges E[i, j] = "active j precedes active i and conflicts".

    ``rank`` is the serialization priority (lower = earlier); ties are
    broken by lane index so the order is always total — the analogue of the
    reference's FIFO arrival order at each row latch.
    """
    b = conflict.shape[0]
    lane = jnp.arange(b, dtype=jnp.int32)
    # lexicographic (rank, lane) compare — no widening, no overflow
    lt = rank[None, :] < rank[:, None]
    eq = rank[None, :] == rank[:, None]
    before = lt | (eq & (lane[None, :] < lane[:, None]))
    act = active[:, None] & active[None, :]
    return conflict & before & act


def greedy_first_fit(edges: jax.Array, active: jax.Array,
                     rounds: int = 24
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Lex-first maximal-independent-set sweep.

    edges: bool[B, B], E[i, j] = earlier txn j blocks txn i on conflict.
    Returns (win, lose, undecided) boolean masks partitioning ``active``.
    """
    e = edges.astype(jnp.float32)
    win = jnp.zeros(active.shape, bool)
    lose = jnp.zeros(active.shape, bool)

    def body(_, carry):
        win, lose = carry
        pending = active & ~win & ~lose
        not_out = (~lose).astype(jnp.float32)
        blocked = (e @ not_out) > 0          # some earlier nbr not yet OUT
        hit = (e @ win.astype(jnp.float32)) > 0  # some earlier nbr IN
        new_win = pending & ~blocked
        new_lose = pending & hit
        return win | new_win, lose | (new_lose & ~new_win)

    win, lose = jax.lax.fori_loop(0, rounds, body, (win, lose))
    undecided = active & ~win & ~lose
    return win, lose, undecided


def wavefront_levels(edges: jax.Array, max_level: int
                     ) -> tuple[jax.Array, jax.Array]:
    """Longest-chain depth per txn in the (DAG) earlier-edges graph.

    Returns (levels int32[B], overflow bool[B]); overflow marks txns whose
    chain exceeds ``max_level`` — callers defer those to the next epoch.
    """
    b = edges.shape[0]
    lv = jnp.zeros((b,), jnp.int32)

    def body(_, lv):
        cand = jnp.where(edges, lv[None, :] + 1, 0)
        return jnp.maximum(lv, cand.max(axis=1))

    lv = jax.lax.fori_loop(0, max_level + 1, body, lv)
    return jnp.minimum(lv, max_level), lv > max_level


def precedence_levels(prec: jax.Array, active: jax.Array, rounds: int
                      ) -> tuple[jax.Array, jax.Array]:
    """Longest-path levels of a *possibly cyclic* must-precede digraph.

    prec: bool[B, B], P[i, j] = "i must serialize before j".
    Iterates ``l_j = 1 + max_{i: P[i,j]} l_i`` ``rounds`` times.  A node is
    flagged unstable if its level still changes on a probe round OR its
    level reached ``rounds`` — after r all-weight-1 sweeps a node's level
    is min(true longest-path depth, r), so ``lv >= rounds`` exactly marks
    "depth not resolved within budget", which covers cycle members, their
    downstream, and over-deep DAG chains; nodes below the bound have exact
    depths.  Over-approximation: flagged txns abort/defer, never commit.
    """
    p = prec & active[:, None] & active[None, :]
    lv = jnp.zeros(active.shape, jnp.int32)

    def body(_, lv):
        cand = jnp.where(p, lv[:, None] + 1, 0)
        return jnp.maximum(lv, cand.max(axis=0))

    lv = jax.lax.fori_loop(0, rounds, body, lv)
    lv2 = body(0, lv)
    unstable = ((lv2 != lv) | (lv >= rounds)) & active
    return lv, unstable
