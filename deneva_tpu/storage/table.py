"""Device-resident tables (reference `storage/row.{h,cpp}`, `storage/table.{h,cpp}`).

A `DeviceTable` is the TPU-native replacement for the reference's
``table_t`` + per-row ``row_t`` pointers: one JAX array per column, indexed
by *slot id*.  Field access (`row_t::set_value/get_value`,
`storage/row.cpp:95-153`) becomes vectorized gather/scatter over whole
epochs of accesses at once.

Representation choices per declared column type:

* ``int64_t``/``uint64_t`` -> int32.  TPU int64 is emulated and slow; all
  benchmark keys fit 31 bits at the scales the harness drives (asserted at
  load time by the workloads).
* ``double`` -> float32 (MXU/VPU native).
* ``string`` -> by default a uint32 *fingerprint* word per field — the
  analogue of the reference's ``SIM_FULL_ROW=false`` mode
  (`storage/row.cpp:30`), which likewise does not materialize payload
  bytes.  With ``full_row=True`` strings are raw ``uint8[capacity, size]``
  so consistency tests can check real bytes.

Every table allocates one extra **trash slot** at index ``capacity``:
masked-out scatters are steered there instead of branching, keeping all
shapes static under jit.

Appends (`table_t::get_new_row`, `storage/table.cpp:42-53`) are a
prefix-sum slot assignment over the epoch's insert mask; the running
``row_cnt`` is traced state so inserts compose with jit.  The slots of
one call are contiguous, so its live lanes are compacted and written as
windows (`dynamic_update_slice`), never scattered.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from deneva_tpu.ops.scatter import scatter_winner_rows
from deneva_tpu.storage.catalog import TableSchema


def padded_rows(capacity: int) -> int:
    """Allocated row count for a table of ``capacity`` rows: padded to a
    multiple of 64 past the trash slot so the row dimension shards evenly
    over any mesh up to 64 devices (jax NamedSharding requires
    divisibility); pad rows are inert.  Config validation for
    ``device_parts`` checks divisibility against THIS number."""
    return -(-(capacity + 1) // 64) * 64


def _col_spec(ctype: str, size: int, full_row: bool) -> tuple[object, tuple]:
    """(dtype, extra_shape) for one column."""
    if ctype in ("int64_t", "uint64_t", "int32_t", "uint32_t"):
        return jnp.int32, ()
    if ctype in ("double", "float"):
        return jnp.float32, ()
    if ctype == "string":
        if full_row:
            return jnp.uint8, (size,)
        return jnp.uint32, ()
    raise ValueError(f"unknown column type {ctype!r}")


@dataclass
class DeviceTable:
    """One table: dict of column arrays + insert cursor.  Pytree."""

    columns: dict[str, jax.Array]
    row_cnt: jax.Array           # int32 scalar: next free slot
    #                              (int32[mc_parts] in the stacked layout)
    # -- static metadata --
    name: str
    capacity: int
    full_row: bool
    ring: bool = False     # append wraps (windowed retention for insert-only
    #                        tables: HISTORY/ORDER/ORDER-LINE keep the last
    #                        `capacity` rows instead of growing unboundedly)
    # -- multi-chip layout metadata (see to_mc_layout) --
    mc_parts: int = 1      # >1: columns hold mc_parts owner-major blocks
    anchor_rows: int = 1   # rows per ownership anchor (e.g. rows per
    #                        warehouse for TPCC's warehouse-partitioned
    #                        tables); owner(slot) = (slot // anchor_rows)
    #                        % mc_parts
    mc_replicated: bool = False  # multi-chip runs keep a full copy per
    #                              device (read-only tables: ITEM, USES,
    #                              SUPPLIES — same replication choice as
    #                              the reference's per-node copies)

    # ------------------------------------------------------------------
    @classmethod
    def create(cls, schema: TableSchema, capacity: int,
               full_row: bool = False, ring: bool = False) -> "DeviceTable":
        nrows = padded_rows(capacity)
        cols = {}
        for c in schema.columns:
            dtype, extra = _col_spec(c.ctype, c.size, full_row)
            cols[c.name] = jnp.zeros((nrows, *extra), dtype=dtype)
        return cls(columns=cols, row_cnt=jnp.zeros((), jnp.int32),
                   name=schema.name, capacity=capacity, full_row=full_row,
                   ring=ring)

    @property
    def trash_slot(self) -> int:
        return self.capacity

    # -- vectorized field access ---------------------------------------
    def gather(self, slots: jax.Array, cols: tuple[str, ...] | None = None
               ) -> dict[str, jax.Array]:
        """Read fields of many rows at once.  Out-of-range / negative slots
        read the trash slot (zeros)."""
        slots = _sanitize(slots, self.capacity)
        names = cols if cols is not None else tuple(self.columns)
        return {n: jnp.take(self.columns[n], slots, axis=0) for n in names}

    def scatter(self, slots: jax.Array, updates: dict[str, jax.Array],
                mask: jax.Array | None = None) -> "DeviceTable":
        """Masked last-write scatter.  Callers that need a deterministic
        winner among duplicate slots must pre-resolve (see
        `deneva_tpu.ops.scatter.last_writer`); raw duplicates here follow
        XLA's unspecified ordering."""
        slots = _sanitize(slots, self.capacity, mask)
        cols = dict(self.columns)
        for n, v in updates.items():
            cols[n] = cols[n].at[slots].set(v.astype(cols[n].dtype))
        return self._replace(columns=cols)

    def scatter_winners(self, name: str, slots: jax.Array, win: jax.Array,
                        carry: tuple, value_fn, after):
        """Row scatter of the ``win`` lanes alone into column ``name``
        (`deneva_tpu.ops.scatter.scatter_winner_rows`: values computed
        from ``carry`` after compaction; the trash row is never written;
        ``after`` = what was read from the column, ordered first).
        Returns (table, lanes handed to the row write, tile groups its
        kernel wrote back, after)."""
        col, lanes, groups, after = scatter_winner_rows(
            self.columns[name], slots, win, carry, value_fn, self.capacity,
            after)
        return (self._replace(columns={**self.columns, name: col}), lanes,
                groups, after)

    def scatter_add(self, slots: jax.Array, updates: dict[str, jax.Array],
                    mask: jax.Array | None = None) -> "DeviceTable":
        """Commutative read-modify-write (balance += x, stock -= y): the
        batch analogue of the reference's in-place row updates; order-free
        so duplicate slots are exact."""
        slots = _sanitize(slots, self.capacity, mask)
        cols = dict(self.columns)
        for n, v in updates.items():
            cols[n] = cols[n].at[slots].add(v.astype(cols[n].dtype))
        return self._replace(columns=cols)

    def append(self, rows: dict[str, jax.Array], mask: jax.Array,
               anchor: jax.Array | None = None, stats: dict | None = None
               ) -> tuple["DeviceTable", jax.Array]:
        """Insert up to len(mask) rows; returns (table, slot ids).

        Slot assignment is a prefix sum over the insert mask starting at
        ``row_cnt`` (`table_t::get_new_row` without the latch).  Rows past
        capacity fall into the trash slot and are dropped (callers size
        tables for the run length, as the reference pre-sizes pools).

        The live lanes' slots are CONTIGUOUS (``row_cnt`` + rank, mod
        ``capacity`` in a ring), so nothing is scattered: the live lanes
        are moved to the front in lane order (`_compact_live`) and
        each column is written through windows of len(mask) rows
        (`_write_window`: one at the cursor and, in a ring, one at slot 0
        for what wraps).  A masked lane writes nothing, nor does a row
        past ``capacity``: the trash and pad rows keep their zeros.  Only
        a call of more lanes than the table has rows (toy tables) keeps
        the scatter, its masked lanes writing zeros into the trash row.

        ``anchor`` — each row's ownership anchor (e.g. home warehouse):
        ignored here, consumed by the multi-chip `McTableView.append`,
        which keeps rows on their owner's block.  Callers pass it
        unconditionally so single-chip and multi-chip runs share one
        executor body.

        ``stats`` — a device-counter dict: where it carries them
        (`engine/step.init_device_stats(append_lanes=True)`),
        ``append_window_lanes`` counts the live lanes written through a
        window and ``append_scatter_lanes`` the lanes handed to the
        scatter form.
        """
        mask = mask.astype(jnp.int32)
        n, k = mask.shape[0], mask.sum()
        offs = jnp.cumsum(mask) - mask
        slots = self.row_cnt + offs
        if self.ring:
            start = self.row_cnt % self.capacity
            slots = jnp.where(mask > 0, slots % self.capacity, self.capacity)
            new_cnt = self.row_cnt + k   # cursor runs free, mod on use
        else:
            start = self.row_cnt
            slots = jnp.where((mask > 0) & (slots < self.capacity),
                              slots, self.capacity)
            new_cnt = jnp.minimum(self.row_cnt + k, jnp.int32(self.capacity))
        cols = dict(self.columns)
        rows = {c: v.astype(cols[c].dtype) for c, v in rows.items()}
        counted = stats is not None and "append_window_lanes" in stats
        if n <= self.capacity:
            for c, v in _compact_live(rows, mask).items():
                cols[c] = _write_window(cols[c], v, k, start, self.capacity,
                                        self.ring)
            if counted:
                stats["append_window_lanes"] = stats["append_window_lanes"] \
                    + (new_cnt - self.row_cnt).astype(jnp.uint32)
        else:
            dead = slots == self.capacity
            for c, v in rows.items():
                v = jnp.where(dead.reshape((n,) + (1,) * (v.ndim - 1)),
                              jnp.zeros((), v.dtype), v)
                cols[c] = cols[c].at[slots].set(v)
            if counted:
                stats["append_scatter_lanes"] = \
                    stats["append_scatter_lanes"] + jnp.uint32(n)
        return self._replace(columns=cols, row_cnt=new_cnt), slots

    # ------------------------------------------------------------------
    def host_column(self, name: str) -> np.ndarray:
        """Host copy of a column minus the trash slot (tests/loaders)."""
        return np.asarray(self.columns[name])[: self.capacity]

    def _replace(self, **kw) -> "DeviceTable":
        d = dict(columns=self.columns, row_cnt=self.row_cnt, name=self.name,
                 capacity=self.capacity, full_row=self.full_row,
                 ring=self.ring, mc_parts=self.mc_parts,
                 anchor_rows=self.anchor_rows,
                 mc_replicated=self.mc_replicated)
        d.update(kw)
        return DeviceTable(**d)


@dataclass
class VersionRing:
    """Per-row bounded OVERWRITE-TIMESTAMP history for ONE column
    (reference `row_mvcc.{h,cpp}`: HIS_RECYCLE_LEN-deep write history per
    row, `row_mvcc.cpp:172-196,303-321`).

    Entry ``(r, i)`` holds the serialization timestamp of a committed
    overwrite of row r; 0 = empty.  The ring is FIFO without a cursor:
    commit timestamps increase monotonically, so the oldest entry is
    simply the row's MINIMUM and each push overwrites it (argmin —
    empties first, since 0 sorts below every real ts >= 1).

    The ring stores NO value bytes (round-5; round 3-4 stored the
    overwritten payload per entry).  In this framework every committed
    value is the deterministic version law ``f(key, writer_ts)`` — the
    same law the executors use to WRITE (`workloads/ycsb._forward_execute_f0`)
    — so the version a reader at t needs is reconstructed from timestamps
    alone: it was written at ``v* = max(entry ts <= t, default 0)`` (0 =
    the load-time base version), value ``f(key, v*)``.  ``select_version``
    returns (v*, has_newer); the workload turns v* into bytes.

    STORAGE (PR 45): the unit of transfer is a ROW.  ``wts`` is
    ``uint8[rows, 4*H]`` — row r's H int32 timestamps as their 4*H
    little-endian bytes, so the leaf's bytes are those of
    ``int32[rows, H]`` row-major — the shape class of a full-row table
    column (``uint8[rows, width]``, which the chip tiles rows-minor and
    does not pad at a width that is a multiple of 8): a lane reads its
    row's whole history in ONE row gather (`rows`) and the words are
    bit-cast in registers; the push (`push_rows`) hands the epoch's
    winners alone, compacted, to the table's own in-place row write
    (`ops.scatter.scatter_winner_rows`), ordered behind the gather by a
    data dependence, so the compiled epoch holds no copy of the ring and
    writes neither its trash row nor its padding.  (Why not the other
    forms: flat ``int32[rows*H]`` costs H scalar gathers a lane, 14.8 ns
    a word on v5e, and its scatter copies the array each epoch;
    ``int32[rows, H]`` is tiled rows-minor too, its H words padded to a
    multiple of 8 — 1.6x the bytes at H = 10: PERF.md section 6, PR 45.)

    Retention/GC is the bucket boundary ring in `cc/timestamp.MVCCState`:
    a read COMMITS only when ``ts >= min(bucket boundaries)``, and at most
    H-1 distinct epoch boundaries (hence at most H-1 per-row overwrites)
    can exceed such a ts, so every post-t overwrite of the row is still
    retained here and v* is exact.  The decision ring is a hashed
    over-approximation (may abort a servable read, never serves a wrong
    one); this ring is exact per row.
    """

    wts: jax.Array   # uint8[R, 4*H]: row r's H int32 ts, little-endian
    depth: int       # H (static)

    @classmethod
    def create(cls, nrows: int, depth: int) -> "VersionRing":
        return cls(wts=jnp.zeros((nrows, 4 * depth), jnp.uint8), depth=depth)

    def rows(self, slots: jax.Array) -> jax.Array:
        """The H ring entries of many rows at once, int32[..., H]: ONE
        row gather (a lane moves its row's 4*H bytes), the words bit-cast
        from them.  Callers that both read versions and push overwrites
        in one epoch fetch ONE combined row set and feed it to
        `version_from` / `push_rows`."""
        b = jnp.take(self.wts, slots, axis=0, mode="clip")
        return jax.lax.bitcast_convert_type(
            b.reshape(*slots.shape, self.depth, 4), jnp.int32)

    @staticmethod
    def version_from(vw: jax.Array, ts: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
        """(v*, has_newer) per access from pre-gathered rows ``vw``
        (int32[..., H]): ``v*`` is the timestamp that wrote the version
        current at ``ts`` (0 = load base) and ``has_newer`` whether any
        retained overwrite postdates ``ts`` (if not, the live table value
        is already correct and callers skip reconstruction)."""
        newer = vw > ts[..., None]
        vstar = jnp.max(jnp.where(newer, 0, vw), axis=-1)
        return vstar, newer.any(axis=-1)

    def select_version(self, slots: jax.Array, ts: jax.Array
                       ) -> tuple[jax.Array, jax.Array]:
        """`rows` + `version_from` for callers without a shared gather."""
        return self.version_from(self.rows(slots), ts)

    def push_rows(self, vw: jax.Array, slots: jax.Array, wts: jax.Array,
                  mask: jax.Array, stats: dict | None = None
                  ) -> "VersionRing":
        """Record committed overwrites (flat lanes) given pre-gathered
        rows ``vw`` (int32[N, H], from `rows(slots)`).  Callers
        pre-resolve duplicate slots (``mask`` holds one winner per row
        per epoch), so each row advances at most one ring slot: FIFO slot
        = argmin of the row (0-empties first; real ts are monotone), the
        new row computed from the row already gathered.  Only the masked
        lanes reach the write, compacted, as whole rows and in place
        (`ops.scatter.scatter_winner_rows`, which orders the write behind
        ``vw``'s gather); the last row (the ring's trash) is never
        written, so trash and padding stay 0 as loaded.

        ``stats`` — a device-counter dict: where it carries
        ``ring_push_lanes`` (`workloads/base.MVCC_COUNTERS`) the lanes
        handed to the row write are counted there."""
        h = self.depth

        def new_rows(lane):
            # (the lanes that won, by their carried index: no [N, H]
            # array is sorted)
            row = jnp.take(vw, lane, axis=0, mode="clip")
            ts = jnp.take(wts.astype(jnp.int32), lane, mode="clip")
            fifo = jnp.arange(h) == jnp.argmin(row, axis=-1)[:, None]
            return jax.lax.bitcast_convert_type(
                jnp.where(fifo, ts[:, None], row), jnp.uint8
            ).reshape(lane.shape[0], 4 * h)

        lane = jnp.arange(slots.shape[0], dtype=jnp.int32)
        ring, lanes, _, _ = scatter_winner_rows(
            self.wts, slots, mask, (lane,), new_rows,
            self.wts.shape[0] - 1, after=vw)
        if stats is not None and "ring_push_lanes" in stats:
            stats["ring_push_lanes"] = stats["ring_push_lanes"] + lanes
        return VersionRing(wts=ring, depth=h)

    def push(self, slots: jax.Array, wts: jax.Array, mask: jax.Array
             ) -> "VersionRing":
        return self.push_rows(self.rows(slots), slots, wts, mask)


jax.tree_util.register_dataclass(
    VersionRing, data_fields=["wts"], meta_fields=["depth"])


def mc_block_geometry(capacity: int, anchor_rows: int, d_parts: int
                      ) -> tuple[int, int]:
    """(data rows per block, padded rows per block) of the stacked layout.

    ``capacity`` global data rows group into ``capacity // anchor_rows``
    ownership anchors dealt round-robin over ``d_parts`` blocks (the
    reference's ``key % g_part_cnt`` node striping, `system/global.h:294`,
    across CHIPS); each block is padded like a standalone table so its
    tail rows serve as the block-local trash."""
    R = anchor_rows
    if capacity % R != 0:
        raise ValueError(f"capacity {capacity} not a multiple of "
                         f"anchor_rows {R}")
    anchors = capacity // R
    if anchors % d_parts != 0:
        raise ValueError(f"{anchors} ownership anchors do not divide over "
                         f"{d_parts} device partitions")
    local_rows = (anchors // d_parts) * R
    return local_rows, padded_rows(local_rows)


def to_mc_layout(tab: DeviceTable, d_parts: int, anchor_rows: int = 1
                 ) -> DeviceTable:
    """Permute a single-device table into the owner-major stacked layout.

    Block ``d`` (rows ``[d*Lb, (d+1)*Lb)`` of every column) holds the rows
    whose ownership anchor ≡ d (mod d_parts), in anchor order; sharding
    dim 0 of the result over a ``d_parts`` mesh gives each device exactly
    its partition, and `workloads.mc.McTableView` translates global slots
    inside `shard_map` bodies.  Ring tables (empty at load) keep per-block
    append cursors: ``row_cnt`` becomes int32[d_parts]."""
    R = anchor_rows
    local_rows, lb = mc_block_geometry(tab.capacity, R, d_parts)
    if tab.ring:
        cols = {n: jnp.zeros((d_parts * lb, *v.shape[1:]), v.dtype)
                for n, v in tab.columns.items()}
        row_cnt = jnp.zeros((d_parts,), jnp.int32)
    else:
        pos = jnp.arange(d_parts * lb, dtype=jnp.int32)
        d, j = pos // lb, pos % lb
        src = ((j // R) * d_parts + d) * R + j % R
        # block pad rows read the (zero, never-yet-scattered) trash slot
        src = jnp.where(j < local_rows, src, jnp.int32(tab.capacity))
        cols = {n: jnp.take(v, src, axis=0) for n, v in tab.columns.items()}
        row_cnt = jnp.full((d_parts,), local_rows, jnp.int32)
    return tab._replace(columns=cols, row_cnt=row_cnt, mc_parts=d_parts,
                        anchor_rows=R)


def mc_column_builder(mesh, capacity: int, row_fn, dtype, extra: tuple = ()):
    """Jitted ``() -> column`` that BUILDS one column of a ``capacity``-row
    table directly in the owner-major stacked layout, sharded over
    ``mesh`` (one block a device, dim 0): device ``d`` computes its own
    block and nothing else — rows ``j < local_rows`` hold
    ``row_fn(j * D + d)`` (the single-device slot the row would have
    had), the block's pad rows and its trash row stay zero, exactly
    `mc_block_geometry`'s.  ``row_fn`` maps int32 slots to
    ``dtype[len(slots), *extra]``, or is None for a column of zeros.  The
    result equals that column of ``to_mc_layout(<single-device table>)``
    bit for bit, but no array of the whole table's rows ever exists on
    one device: a table no single device can hold is loaded this way."""
    from jax.sharding import PartitionSpec as P

    (axis,) = mesh.axis_names
    d_parts = mesh.size
    local_rows, lb = mc_block_geometry(capacity, 1, d_parts)

    def block():
        if row_fn is None:
            return jnp.zeros((lb, *extra), dtype)
        j = jnp.arange(lb, dtype=jnp.int32)
        live = j < local_rows
        # pad rows compute a (discarded) value of slot 0: in range
        slot = jnp.where(live, j * d_parts + jax.lax.axis_index(axis), 0)
        live = live.reshape((lb,) + (1,) * len(extra))
        return jnp.where(live, row_fn(slot).astype(dtype), 0)

    return jax.jit(jax.shard_map(
        block, mesh=mesh, in_specs=(),
        out_specs=P(axis, *([None] * len(extra)))))


def create_mc(schema: TableSchema, capacity: int, mesh, row_fns: dict,
              full_row: bool = False) -> DeviceTable:
    """A loaded table in the owner-major stacked layout, each block built
    on the device of ``mesh`` that holds it (`mc_column_builder`), one
    column at a time.  ``row_fns``: column name -> slots -> values for the
    columns the loader fills; the others are zeros.  Equals
    ``to_mc_layout`` of `DeviceTable.create` + those columns written,
    placed as `parallel.mesh.state_shardings` places a table — a later
    ``device_put`` over the same mesh moves nothing."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    d_parts = mesh.size
    local_rows, _ = mc_block_geometry(capacity, 1, d_parts)
    cols, built = {}, {}
    for c in schema.columns:
        dtype, extra = _col_spec(c.ctype, c.size, full_row)
        fn = row_fns.get(c.name)
        # one program per distinct (law, shape): ten YCSB fields share one
        key = (fn, jnp.dtype(dtype).name, extra)
        if key not in built:
            built[key] = mc_column_builder(mesh, capacity, fn, dtype, extra)
        cols[c.name] = built[key]()
    row_cnt = jax.device_put(
        jnp.full((d_parts,), local_rows, jnp.int32),
        NamedSharding(mesh, P(*mesh.axis_names)))
    return DeviceTable(columns=cols, row_cnt=row_cnt, name=schema.name,
                       capacity=capacity, full_row=full_row,
                       mc_parts=d_parts)


def fill_columns(tab: DeviceTable, n: int, cols: dict) -> DeviceTable:
    """Loader helper: set the first ``n`` rows of the named columns and
    advance ``row_cnt`` (the parallel loaders of SURVEY §2.5 reduced to
    one sliced device write per column)."""
    out = dict(tab.columns)
    for name, v in cols.items():
        out[name] = out[name].at[:n].set(jnp.asarray(v, out[name].dtype))
    return tab._replace(columns=out, row_cnt=jnp.int32(n))


def _compact_live(rows: dict[str, jax.Array], mask: jax.Array
                  ) -> dict[str, jax.Array]:
    """``rows`` with the lanes of ``mask`` moved to the front, in lane
    order (what follows them is unspecified).  A live lane moves left by
    the masked lanes before it, one bit of that count a round, lowest
    bit first: log2(N) rounds of a shift and a select over every column
    at once (the columns ride as rows of 4-byte words), and no two live
    lanes ever meet — lane order is kept, so a lane's remaining move
    never exceeds that of a lane to its right.  On a v5e the 15
    words x 15,360 lanes of an ORDER-LINE call take 0.030 ms and compile
    in a second; one stable 16-operand `lax.sort` keyed on the mask took
    0.070 ms and 43 s to compile, a stable `argsort` and one gather of
    the stacked words 0.14 ms (my chip runs, PR 37)."""
    n = mask.shape[0]
    words = [_as_words(v) for v in rows.values()]
    x = jnp.concatenate(words, axis=0)
    live = mask > 0
    move = jnp.arange(n, dtype=jnp.int32) - (jnp.cumsum(mask) - mask)
    for bit in range((n - 1).bit_length()):
        def ahead(a):   # a[..., j] <- a[..., j + 2^bit], zeros at the end
            return jnp.pad(a[..., 1 << bit:],
                           [(0, 0)] * (a.ndim - 1) + [(0, 1 << bit)])
        leaves = live & ((move >> bit) & 1 == 1)
        arrives = ahead(leaves)
        x = jnp.where(arrives, ahead(x), x)
        move = jnp.where(arrives, ahead(move), move)
        live = arrives | (live & ~leaves)
    done, at = {}, 0
    for (c, v), w in zip(rows.items(), words):
        done[c] = _from_words(x[at:at + w.shape[0]], v)
        at += w.shape[0]
    return done


def _as_words(v: jax.Array) -> jax.Array:
    """``v[N, ...]`` as ``uint32[W, N]``: the bytes of a row in words of
    four, the last one padded with zeros."""
    if v.ndim == 1 and v.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(v, jnp.uint32)[None]
    n = v.shape[0]
    b = jax.lax.bitcast_convert_type(v, jnp.uint8).reshape(n, -1)
    b = jnp.pad(b, ((0, 0), (0, -b.shape[1] % 4)))
    return jax.lax.bitcast_convert_type(b.reshape(n, -1, 4), jnp.uint32).T


def _from_words(w: jax.Array, like: jax.Array) -> jax.Array:
    """`_as_words` undone: ``uint32[W, N]`` as ``like``'s shape and dtype."""
    if like.ndim == 1 and like.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(w[0], like.dtype)
    n, size = like.shape[0], like.dtype.itemsize
    b = jax.lax.bitcast_convert_type(w.T, jnp.uint8).reshape(n, -1)
    b = b[:, :like.size // n * size]
    return jax.lax.bitcast_convert_type(
        b.reshape(like.shape + ((size,) if size > 1 else ())), like.dtype)


def _write_window(col: jax.Array, live: jax.Array, k: jax.Array,
                  start: jax.Array, capacity: int, ring: bool) -> jax.Array:
    """``col`` with ``live[i]`` at row ``start + i`` for every ``i < k``
    (``live``: N <= ``capacity`` rows, ``start`` <= ``capacity``), as
    masked windows of N rows — read, select, `dynamic_update_slice`, in
    place: no scatter.  `dynamic_update_slice` clamps a start that does
    not fit, so the window sits at ``min(start, capacity - N)`` with the
    rows shifted to match; what passes ``capacity`` wraps to a second
    window at row 0 in a ring and is dropped otherwise.  Rows the mask
    leaves out keep what the column held."""
    n = live.shape[0]
    j = jnp.arange(n, dtype=jnp.int32)
    twice = jnp.concatenate([live, live])

    def put(col, at, first, take):
        # window row j <- live[first + j] where ``take``
        new = jax.lax.dynamic_slice_in_dim(twice, first, n)
        old = jax.lax.dynamic_slice_in_dim(col, at, n)
        take = take.reshape((n,) + (1,) * (col.ndim - 1))
        return jax.lax.dynamic_update_slice_in_dim(
            col, jnp.where(take, new, old), at, 0)

    at = jnp.minimum(start, jnp.int32(capacity - n))
    shift = start - at
    col = put(col, at, n - shift, (j >= shift) & (j - shift < k))
    if ring:
        ahead = jnp.minimum(jnp.int32(capacity) - start, n)
        col = put(col, jnp.int32(0), ahead, j + ahead < k)
    return col


def _sanitize(slots: jax.Array, capacity: int,
              mask: jax.Array | None = None) -> jax.Array:
    slots = slots.astype(jnp.int32)
    bad = (slots < 0) | (slots > capacity)
    if mask is not None:
        bad = bad | ~mask.astype(bool)
    return jnp.where(bad, jnp.int32(capacity), slots)


jax.tree_util.register_dataclass(
    DeviceTable,
    data_fields=["columns", "row_cnt"],
    meta_fields=["name", "capacity", "full_row", "ring", "mc_parts",
                 "anchor_rows", "mc_replicated"],
)
