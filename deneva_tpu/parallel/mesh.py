"""Mesh construction + sharding specs for the epoch engine.

Design (scaling-book recipe: pick a mesh, annotate, let XLA insert
collectives):

* 1-D mesh over axis ``part`` = keyspace partition = the reference's
  server node (`GET_NODE_ID`, `system/global.h:294`).
* `state_shardings` annotates an `EngineState`: DeviceTable columns and
  per-bucket CC watermark tables shard dim 0 over ``part``; pool, rng and
  stats replicate.
* `shard_buckets` is a `with_sharding_constraint` hook applied to the
  B×K incidence matrices inside `cc.base.build_incidence`: with K sharded,
  the B×K @ K×B conflict matmul contracts over the sharded dimension, so
  each device multiplies its bucket slice and XLA reduces the partial
  conflict matrices across ICI — the batched equivalent of every
  participant voting in 2PC prepare (`system/txn.cpp:498-530`).

The hook is a context (not a config field) because it must be active
during jit *tracing*; `make_sharded_run` wires it up.
"""

from __future__ import annotations

import contextlib
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "part"


_current: dict = {"mesh": None}


def make_mesh(n_devices: int | None = None) -> Mesh:
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if len(devs) < n:
        raise RuntimeError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (AXIS,))


def current_mesh() -> Mesh | None:
    """Mesh of the enclosing `use_mesh` context (None outside one).
    Read at jit *trace* time by the engine to pick sharded code paths."""
    return _current["mesh"]


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    prev = _current["mesh"]
    _current["mesh"] = mesh
    try:
        yield mesh
    finally:
        _current["mesh"] = prev


def shard_buckets(x: jax.Array) -> jax.Array:
    """Constrain the trailing (bucket) dim of an incidence matrix to be
    sharded over ``part``.  No-op outside a `use_mesh` context."""
    mesh = _current["mesh"]
    if mesh is None:
        return x
    spec = P(*([None] * (x.ndim - 1) + [AXIS]))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def state_shardings(mesh: Mesh, state: Any):
    """Pytree of NamedSharding for an EngineState: db tables + CC watermark
    tables shard dim 0 (keyspace slices per 'node'); the rest replicates.
    Tables marked ``mc_replicated`` (read-only ITEM/USES/SUPPLIES) keep a
    full copy per device, like the reference's per-node copies."""
    repl_tables = set()
    db = state.get("db") if isinstance(state, dict) \
        else getattr(state, "db", None)
    if isinstance(db, dict):
        repl_tables = {name for name, t in db.items()
                       if getattr(t, "mc_replicated", False)}

    def spec(path, leaf) -> NamedSharding:
        keys = [getattr(p, "name", getattr(p, "key", None)) for p in path]
        if "db" in keys and repl_tables.intersection(keys):
            return NamedSharding(mesh, P())
        shard0 = ("db" in keys or "cc_state" in keys) and hasattr(leaf, "ndim") \
            and leaf.ndim >= 1 and leaf.shape[0] >= mesh.size \
            and leaf.shape[0] % mesh.size == 0
        if shard0:
            return NamedSharding(mesh, P(AXIS, *([None] * (leaf.ndim - 1))))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec, state)


def a2a_bytes_per_epoch(cfg, b: int, width: int) -> int:
    """Bytes the sharded owner exchange of `YCSBWorkload.execute_mc`
    moves BETWEEN chips in one epoch of ``b`` transactions whose batch is
    ``width`` accesses wide (the wire's, not ``max_accesses``: the block
    shapes `execute_mc` really cuts): each of D shards ships one
    ``pair_cap``-lane block of key/rank/write lanes (int32 + int32 + bool
    = 9 B a lane) to each of its D - 1 peers; the block a shard keeps for
    itself crosses no wire.  Static (a function of shapes).  0 when
    capacity planning is off: the replicated plan exchanges only psum
    partials, not lanes."""
    from ..ops.forward import mc_pair_cap
    d = cfg.device_parts
    cap = mc_pair_cap(b, width, d, cfg.mc_plan_capacity)
    return d * (d - 1) * cap * 9


def mesh_line(node: int, fields: dict) -> str:
    """One `[mesh]` summary satellite line (harness.parse.parse_mesh)."""
    kv = " ".join(f"{k}={v}" for k, v in fields.items())
    return f"[mesh] node={node} {kv}"


def make_sharded_run(engine, mesh: Mesh):
    """Return (place, run): ``place(state)`` lays EngineState out over the
    mesh; ``run(state, n)`` scans n epochs with partition-parallel
    validation and sharded table updates."""
    import functools

    def place(state):
        return jax.device_put(state, state_shardings(mesh, state))

    @functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
    def _run(state, n):
        return jax.lax.scan(lambda s, _: (engine.step(s), None), state,
                            None, length=n)[0]

    def run(state, n: int):
        with use_mesh(mesh):
            return _run(state, n)

    return place, run
