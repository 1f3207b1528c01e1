"""Workload interface (reference `system/wl.{h,cpp}`, `benchmarks/*_wl.*`).

The reference couples workloads to threads: `Workload::get_txn_man` hands a
per-thread txn-manager subclass whose ``run_txn`` advances a request-at-a-
time state machine (`benchmarks/ycsb_txn.cpp:91-209`).  Here a workload is
four pure functions over whole epochs:

* ``load()``      — build device tables (the parallel loaders,
                    `benchmarks/ycsb_wl.cpp:125-203`, become host numpy
                    passes + one device_put).
* ``generate()``  — a fresh batch of queries on device (the client query
                    generators, `benchmarks/*_query.cpp`).
* ``plan()``      — queries -> padded RW-set arrays (keys/tables/modes):
                    what the reference discovers incrementally through its
                    state machines is declared up front so the whole epoch
                    can be validated at once.  Workloads whose keys depend
                    on reads (PPS recon) resolve them here with gathers
                    against the current snapshot.
* ``execute()``   — apply committed txns: gather reads, compute, scatter
                    writes (with last-writer resolution), append inserts.
                    Called once per chained sub-round for deterministic
                    backends.

``DB`` is the carried table state; indexes with static contents live on
the workload object itself (device arrays inside them still ride along as
jit constants).
"""

from __future__ import annotations

from typing import Any, Protocol

import jax
import jax.numpy as jnp

DB = dict  # table name -> DeviceTable; a pytree

# The executors' device counters: what an ``execute`` body adds to the
# ``stats`` dict it is handed.  ONE list: `engine/step.init_device_stats`
# makes its zeros from it, `workloads/mc.mc_execute` its per-chip dict,
# its reductions and its out-specs (in this order), and the server's
# `[summary]` reads each ``*_lanes`` counter as ``*_lane_cnt``.  A new
# counter is a line here plus `engine/checkpoint.SCHEMA_VERSION`.
EXEC_COUNTERS = (
    "read_checksum",
    "write_cnt",
    # lanes handed to YCSB's F0 scatter (ops/scatter.
    # scatter_winner_rows): against write_cnt and the epoch's lane
    # count it says how far the winner compaction engages
    "write_scatter_lanes",
    # lanes handed to YCSB's F0 gather: under a forwarding plan with
    # full rows the unforwarded reads, in whole calls of the loop
    # (ops/gather.checksum_needed_rows), else every lane
    "read_gather_lanes",
)


# The tile groups the row write's kernel writes back
# (`ops/scatter.scatter_winner_rows`: 32 rows of a byte column are one
# group; a group goes back once a call, whatever its winners) — against
# ``write_scatter_lanes`` the share of lanes that merged into a
# neighbour's group, times a group's bytes the kernel's traffic.  NOT
# part of ``EXEC_COUNTERS``: the server of a workload whose executor
# writes full rows through it asks for it
# (`engine/step.init_device_stats(row_groups=True)`), so every other
# program's stats pytree is what it was.  `[summary]` reads it as
# ``write_row_group_cnt``.
ROW_GROUP_COUNTER = "write_row_groups"


# What `storage/table.DeviceTable.append` counts where the ``stats`` dict
# it is handed carries them: live lanes written through a window, and
# lanes of calls that fell back to the scatter (more lanes than the table
# has rows).  NOT part of ``EXEC_COUNTERS``: the server of a workload
# with ring tables on one device asks for them
# (`engine/step.init_device_stats(append_lanes=True)`), so every other
# program's stats pytree is what it was.
APPEND_COUNTERS = ("append_window_lanes", "append_scatter_lanes")


# What served YCSB under MVCC decides, counted where the ``stats`` dict
# carries them: reads served a version other than the live one
# (`workloads/ycsb.YCSBWorkload.execute`), and — in
# `cc/timestamp.validate_mvcc` — transactions sent back for a read whose
# version is out of reach, transactions that wait behind a writer of
# their epoch, read-only commits — and the lanes handed to the version
# ring's row write (`storage/table.VersionRing.push_rows`: the epoch's
# winners, in whole chunks, as ``write_scatter_lanes`` is for the table's
# column).  NOT part of ``EXEC_COUNTERS``: an MVCC
# server on one device asks for them
# (`engine/step.init_device_stats(mvcc_counters=True)`), so every other
# program's stats pytree is what it was.  `[summary]` reads ``<x>s`` as
# ``<x>_cnt``.
MVCC_COUNTERS = ("mvcc_old_version_reads", "mvcc_history_aborts",
                 "mvcc_waits", "mvcc_ro_commits", "ring_push_lanes")


# What the lock family decides (`cc/twopl.validate_no_wait` /
# `validate_wait_die`), counted where the ``stats`` dict carries them:
# losers that DIE (aborted: refused a lock and, under WAIT_DIE, not older
# than every owner), losers that WAIT (WAIT_DIE: older than every owner,
# deferred with their timestamp) and the sweep budget's LEFTOVERS (lanes
# `sweep_rounds` left undecided, deferred: neither granted nor refused).
# The host sees deaths as aborts and both other kinds as one defer, so
# the split is counted where it is decided.  NOT part of
# ``EXEC_COUNTERS``: a 2PL server on one device asks for them
# (`engine/step.init_device_stats(lock_counters=True)`), so every other
# program's stats pytree is what it was.  `[summary]` reads ``<x>`` as
# ``<x>_cnt``.
LOCK_COUNTERS = ("lock_die", "lock_wait", "lock_leftover")


def partition_owned(key: jax.Array, n_parts: int, me: int) -> jax.Array:
    """bool mask: does this node own ``key`` under modulo striping
    (reference GET_NODE_ID, `system/global.h:294`)?"""
    if n_parts == 1:
        return jnp.ones(jnp.shape(key), bool)
    return key % n_parts == me


def slot_map_owned(key: jax.Array, owners: jax.Array, me: int) -> jax.Array:
    """bool mask: does this node own ``key`` under the elastic slot map
    (`runtime/membership.py`)?  ``owners`` is the device-resident
    int32[S] owner array carried in the db pytree (MEMBER_KEY), so a
    rebalance is a data update, never a re-jit.  With the boot map this
    is EXACTLY ``partition_owned`` (S is a multiple of the active count;
    the degeneracy contract)."""
    slot = key.astype(jnp.int32) % jnp.int32(owners.shape[0])
    return jnp.take(owners, slot, axis=0) == jnp.int32(me)


def partition_slot(key: jax.Array, n_parts: int, me: int,
                   n_local: int) -> jax.Array:
    """Local storage slot for a striped global key; keys this node does
    not own resolve to ``n_local`` — the table's TRASH slot.  NOTE the
    trash-row contract (see `storage/table.py`): masked scatters land IN
    the trash row, so gathers of scatter-written columns through it
    return garbage — consumers must stay masked by `partition_owned`."""
    loc = key // n_parts if n_parts > 1 else key
    return jnp.where(partition_owned(key, n_parts, me), loc,
                     jnp.int32(n_local))


class Workload(Protocol):
    def load(self) -> DB: ...

    def generate(self, rng: jax.Array, n: int) -> Any:
        """Return a query pytree with leading dim n."""
        ...

    def plan(self, db: DB, queries: Any) -> dict:
        """Return dict(table_ids, keys, is_read, is_write, valid) [n, A]."""
        ...

    def execute(self, db: DB, queries: Any, mask: jax.Array,
                order: jax.Array, stats: dict, fwd_rank=None,
                level_exec: bool = False) -> DB:
        """Apply txns selected by ``mask`` to ``db``; update device stats
        dict in place (read checksums keep gathers alive under XLA).

        ``fwd_rank`` — a `deneva_tpu.ops.ForwardPlan` when the single-pass
        forwarding executor applies (``mask`` must then be None: the plan
        embodies the commit set).  ``level_exec`` — the caller guarantees
        this committed set is write-conflict-free (a chained sub-round),
        so duplicate-writer resolution may be skipped."""
        ...
