"""Checkpoint / resume of engine state.

The reference has **no checkpointing** (SURVEY §5.4: logging+replication
are the closest thing; recovery is unimplemented).  Here the whole
`EngineState` is one pytree — tables, CC watermarks, txn pool, RNG, epoch
counter, stats — so a checkpoint is a flat dump of its leaves and resume
is bit-exact: a resumed run continues the *identical* epoch stream the
uninterrupted run would have produced (the RNG key is state, not ambient).

Format: one ``.npz`` with leaves in flatten order plus their key-paths for
a structure sanity check.  The config is not serialized — the caller
recreates the engine from the same `Config` (the reference pins config at
compile time; we pin it at restore time and verify leaf shapes agree).
"""

from __future__ import annotations

import io
import os

import jax
import numpy as np

# Bump whenever the EngineState pytree LAYOUT changes (new/renamed state
# fields, cc_state reshapes, db companion tables) so a stale checkpoint
# fails with a clear message instead of an opaque tree/shape error.
# History: 1 = round-2 (TOState->MVCCState, watermark_buckets split);
#          2 = round-3 (MVCC per-row VersionRing joins the db pytree);
#          3 = round-4 (PoolState.defer_cnt for the defer budget);
#          4 = round-4 (per-type latency_hist + retry/wait hist leaves);
#          5 = round-5 (VersionRing flattened to [R*H] storage);
#          6 = round-13 (rep_* transaction-repair counters in
#              device stats);
#          7 = round-16 (conflict_density per-partition counter in
#              device stats — the metrics bus's contention signal);
#          8 = round-17 (isolation audit plane: audit_edge_cnt/
#              audit_drop_cnt device counters, and with audit armed the
#              db pytree gains the __audit__ version-stamp tables);
#          9 = PR 26 (write_scatter_lanes device counter);
#         10 = PR 30 (read_gather_lanes device counter);
#         11 = PR 41 (mc_defer_pass_cnt device counter, where a mesh's
#              server asks for it);
#         12 = PR 43 (MVCCState.lossy; the MVCC_COUNTERS device counters,
#              where an MVCC server asks for them);
#         13 = PR 45 (VersionRing stored by rows, uint8[R, 4*H], and the
#              ring_push_lanes device counter among MVCC_COUNTERS);
#         14 = PR 46 (the LOCK_COUNTERS device counters, where a 2PL
#              server asks for them);
#         15 = PR 48 (the write_row_groups device counter, where a
#              server that writes full rows asks for it).
SCHEMA_VERSION = 15


def save_state(path: str, state) -> None:
    """Dump a state pytree (EngineState or any pytree of arrays)."""
    leaves_p = jax.tree_util.tree_flatten_with_path(state)[0]
    payload = {f"leaf_{i:04d}": np.asarray(jax.device_get(v))
               for i, (_, v) in enumerate(leaves_p)}
    payload["__schema__"] = np.int64(SCHEMA_VERSION)
    payload["__paths__"] = np.array(
        [jax.tree_util.keystr(p) for p, _ in leaves_p])
    buf = io.BytesIO()
    np.savez(buf, **payload)
    tmp = path + ".tmp"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(tmp, "wb") as f:
        f.write(buf.getvalue())
    os.replace(tmp, path)          # atomic: no torn checkpoints


def load_state(path: str, template):
    """Rebuild a state pytree from ``path`` using ``template`` (a freshly
    initialized state of the same config) for structure and placement."""
    with np.load(path, allow_pickle=False) as z:
        saved_schema = int(z["__schema__"]) if "__schema__" in z else 0
        if saved_schema != SCHEMA_VERSION:
            raise ValueError(
                f"incompatible checkpoint: schema v{saved_schema} "
                f"(this build writes v{SCHEMA_VERSION}) — the engine "
                "state layout changed between builds; re-run from "
                "scratch (checkpoints are not migrated)")
        paths = list(z["__paths__"])
        leaves_t, treedef = jax.tree_util.tree_flatten_with_path(template)
        if len(paths) != len(leaves_t):
            raise ValueError(
                f"checkpoint has {len(paths)} leaves, template has "
                f"{len(leaves_t)} — config mismatch?")
        leaves = []
        for i, ((p, t), saved_path) in enumerate(zip(leaves_t, paths)):
            if jax.tree_util.keystr(p) != str(saved_path):
                raise ValueError(
                    f"leaf {i} path mismatch: checkpoint "
                    f"{saved_path!r} vs template {jax.tree_util.keystr(p)!r}")
            v = z[f"leaf_{i:04d}"]
            if hasattr(t, "shape") and tuple(t.shape) != v.shape:
                raise ValueError(
                    f"leaf {jax.tree_util.keystr(p)}: shape {v.shape} != "
                    f"template {tuple(t.shape)} — config mismatch?")
            leaves.append(jax.numpy.asarray(v, dtype=getattr(t, "dtype",
                                                             None)))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(template), leaves)
