"""Generic partition-parallel execution over a device mesh.

The reference partitions EVERY benchmark across server nodes — warehouses
map to nodes for TPC-C (`benchmarks/tpcc_helper.cpp` wh_to_part, remote
hops `tpcc_txn.cpp:332-368`), keys stripe for YCSB (`ycsb_wl.cpp:70-74`),
PPS anchors stripe (`pps_wl.cpp`) — and a transaction's per-node work
executes on the owner.  This module is that deployment model across
CHIPS, for any workload and any CC backend:

* The epoch batch is **replicated** (Calvin-sequencer shape: every chip
  sees the full deterministic sequence, `system/sequencer.cpp:283-326`)
  and validation runs on the replicated batch (conflict matmuls contract
  over the bucket dim, which `parallel.mesh.shard_buckets` shards).
* Tables live in the **owner-major stacked layout**
  (`storage.table.to_mc_layout`): block ``d`` of every column holds the
  rows whose ownership anchor ≡ d (mod D), so sharding dim 0 over the
  mesh hands each chip exactly its partition; the tables every chip
  needs whole (TPC-C's read-only ITEM; PPS's USES / SUPPLIES mappings,
  which every chip WRITES alike from the replicated batch) are
  replicated like the reference's per-node copies.
* Execution runs the workload's **unmodified** ``execute`` body under
  `shard_map`: each chip passes global slots through a `McTableView`
  that translates them to block-local rows — non-owned lanes read 0 and
  scatter to the block trash — so per-chip work is exactly the owned
  partition and the psum of per-chip read checksums reconstructs the
  single-chip value bit-exactly.

Executor contract (held by ycsb/tpcc/pps, asserted by the bit-identity
tests in `tests/test_parallel.py`):

* every gather-derived statistic folds into ``read_checksum`` with
  per-lane integer conversion (integer sums are associative, so the
  cross-chip psum is exact);
* all other statistics derive from replicated inputs (masks/queries)
  only, so every chip computes the same value and no psum is needed;
* ring appends pass the row's ownership ``anchor`` so inserts land on
  the owner's block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deneva_tpu.parallel.mesh import AXIS, current_mesh
from deneva_tpu.storage.table import DeviceTable, mc_block_geometry
from deneva_tpu.workloads.base import EXEC_COUNTERS


class McTableView:
    """DeviceTable facade inside a `shard_map` body: global slots in,
    block-local storage ops out.  ``capacity`` stays the GLOBAL trash id
    so caller arithmetic (trash steering, `last_writer` sentinels) is
    unchanged; `_loc` folds global trash, out-of-range and non-owned
    slots into the block-local trash."""

    def __init__(self, tab: DeviceTable, me: jax.Array,
                 local: DeviceTable | None = None):
        self._meta = tab            # shard leaves + global static metadata
        self.d_parts = tab.mc_parts
        self.anchor_rows = tab.anchor_rows
        self.me = me
        if local is None:
            _, lb = mc_block_geometry(tab.capacity, tab.anchor_rows,
                                      tab.mc_parts)
            local_cap = tab.capacity // tab.mc_parts if tab.ring else lb - 1
            local = DeviceTable(
                columns=tab.columns, row_cnt=tab.row_cnt.reshape(()),
                name=tab.name, capacity=local_cap, full_row=tab.full_row,
                ring=tab.ring)
        self.local = local

    @property
    def capacity(self) -> int:
        return self._meta.capacity

    def _with(self, local: DeviceTable) -> "McTableView":
        return McTableView(self._meta, self.me, local=local)

    def _loc(self, slots: jax.Array) -> tuple[jax.Array, jax.Array]:
        slots = slots.astype(jnp.int32)
        a = slots // self.anchor_rows
        owned = ((slots >= 0) & (slots < self.capacity)
                 & (a % self.d_parts == self.me))
        loc = (a // self.d_parts) * self.anchor_rows + slots % self.anchor_rows
        return jnp.where(owned, loc, jnp.int32(self.local.capacity)), owned

    # -- DeviceTable interface -----------------------------------------
    def gather(self, slots: jax.Array, cols: tuple[str, ...] | None = None
               ) -> dict[str, jax.Array]:
        loc, owned = self._loc(slots)
        out = self.local.gather(loc, cols)
        # non-owned lanes read 0 (never block-trash garbage): each row is
        # owned by exactly one chip, so per-chip contributions sum to the
        # single-chip gather and checksums psum exactly
        def zero(v):
            m = owned.reshape(owned.shape + (1,) * (v.ndim - owned.ndim))
            return jnp.where(m, v, 0)
        return {n: zero(v) for n, v in out.items()}

    def scatter(self, slots, updates, mask=None) -> "McTableView":
        loc, _ = self._loc(slots)
        return self._with(self.local.scatter(loc, updates, mask=mask))

    def scatter_winners(self, name, slots, win, carry, value_fn, after):
        loc, _ = self._loc(slots)
        local, lanes, groups, after = self.local.scatter_winners(
            name, loc, win, carry, value_fn, after)
        return self._with(local), lanes, groups, after

    def scatter_add(self, slots, updates, mask=None) -> "McTableView":
        loc, _ = self._loc(slots)
        return self._with(self.local.scatter_add(loc, updates, mask=mask))

    def append(self, rows, mask, anchor=None, stats=None):
        assert anchor is not None, \
            "multi-chip append needs the row ownership anchor"
        m = mask & (anchor.astype(jnp.int32) % self.d_parts == self.me)
        local, slots = self.local.append(rows, m, stats=stats)
        return self._with(local), slots

    def assemble(self) -> DeviceTable:
        """Back to a shard-leaf DeviceTable for the shard_map output."""
        return self._meta._replace(columns=self.local.columns,
                                   row_cnt=self.local.row_cnt.reshape((1,)))


def table_specs(db: dict) -> dict:
    """shard_map spec tree for a DB dict: stacked tables shard dim 0 over
    the mesh axis, replicated tables ride whole."""
    return {name: jax.tree.map(
        lambda _, s=(P() if tab.mc_parts == 1 else P(AXIS)): s, tab)
        for name, tab in db.items()}


def mc_execute(cfg, wl, db: dict, queries, commit: jax.Array,
               order: jax.Array, level: jax.Array, stats: dict,
               chained: bool, level_exec: bool = True,
               n_levels: int | None = None) -> dict:
    """One epoch's execution, partition-parallel across the mesh.

    ``commit``/``order``/``level`` come from the replicated verdict; for
    chained backends each wavefront level executes as a sub-round against
    the chip-local table state, exactly like the single-chip engine loop
    (`engine/epoch.py`).  ``level_exec`` follows `engine/epoch.run_levels`:
    True claims each sub-round's committed set is write-conflict-free
    (CALVIN/TPU_BATCH); False (DGCC) keeps the per-wave ``last_writer``
    order tournament, so same-wave duplicate writers resolve identically
    on every shard (the verdict is replicated, the tournament is a pure
    function of it — dp>1 stays bit-identical to dp=1).  ``n_levels``
    overrides the static sub-round unroll budget (DGCC waves are bounded
    by ``dgcc_levels``, not ``exec_subrounds`` — a committed level past
    the unroll would silently never execute)."""
    mesh = current_mesh()
    assert mesh is not None and mesh.size == cfg.device_parts, \
        f"mc_execute needs a use_mesh({cfg.device_parts}) context"
    db_spec = table_specs(db)

    def body(db, queries, commit, order, level):
        me = jax.lax.axis_index(AXIS)
        dbv = {n: (McTableView(t, me) if t.mc_parts > 1 else t)
               for n, t in db.items()}
        st = {k: jnp.zeros((), jnp.uint32) for k in EXEC_COUNTERS}
        if chained:
            for lvl in range(n_levels if n_levels is not None
                             else cfg.exec_subrounds):
                m = commit & (level == lvl)
                dbv = wl.execute(dbv, queries, m, order, st,
                                 level_exec=level_exec)
        else:
            dbv = wl.execute(dbv, queries, commit, order, st)
        out = {n: (v.assemble() if isinstance(v, McTableView) else v)
               for n, v in dbv.items()}
        # what a chip gathers and scatters is its own (each compacts its
        # own winners, and every chip gathers all of its lanes): summed
        # over the mesh; write_cnt is counted from the replicated commit
        # mask and is every chip's already
        return (out,) + tuple(
            st[k] if k == "write_cnt" else jax.lax.psum(st[k], AXIS)
            for k in EXEC_COUNTERS)

    out_db, *counts = jax.shard_map(
        body, mesh=mesh,
        in_specs=(db_spec, P(), P(), P(), P()),
        out_specs=(db_spec,) + (P(),) * len(EXEC_COUNTERS))(
            db, queries, commit, order, level)
    for k, v in zip(EXEC_COUNTERS, counts):
        stats[k] = stats[k] + v
    return out_db
