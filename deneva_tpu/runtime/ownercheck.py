"""Thread-ownership declarations + debug-mode runtime asserts for the
server's shared state (our substitute for the broken TSAN on this box).

Up to three thread roles run inside a server process — the DISPATCH
thread (the epoch loop: admission, feed build, device dispatch,
retirement, all state mutation) and, where ``host_overlap`` gives the
loop's pure bodies threads of their own, ONE ordered WIRE worker
(``_bcast_views`` / ``_log_group_views`` / ``tp.flush``: blob broadcast,
log pack/append, replica sends) and ONE RETIRE worker
(``_prefetch_retire``: verdict d2h wait + pure unpacking and ack
splits).  Without workers the dispatch thread calls the same bodies
inline.  The bit-identity contract is that these bodies are PURE and
every state mutation stays at the dispatch thread's loop positions.

This module is the single source of truth for who owns what:

* ``OWNER`` maps every ServerNode attribute to its owning role.  The
  graftlint ownership checker (tools/graftlint/ownership.py) walks each
  worker's call graph and reports writes to state the worker does not
  own; an attribute missing from this map is itself a finding, so the
  map cannot silently rot.
* ``install(server)`` — the ``owner_check=true`` runtime mode — stamps
  the dispatch thread on the mutable collections in ``GUARDED`` by
  wrapping them in subclasses whose mutators assert the calling thread.
  With ``owner_check=false`` (default) nothing is wrapped and no code
  path changes: the flag is checked once at ``ServerNode.run()`` entry,
  after recovery/replay has populated the collections.

Kept import-light (stdlib only): the linter imports these declarations
without pulling in jax or the runtime.
"""

from __future__ import annotations

import threading
from collections import deque

DISPATCH = "dispatch"   # the epoch loop thread (owns all state mutation)
WIRE = "wire"           # ordered wire worker (host_overlap)
RETIRE = "retire"       # verdict prefetch worker (host_overlap)
SHARED = "shared"       # internally synchronized (lock / thread-safe impl)

# ---- ServerNode attribute -> owning role ------------------------------
# Workers may READ anything (staged work is pure given its inputs); a
# WRITE from a non-owning role is the bug class this map exists to catch.
OWNER: dict[str, str] = {
    # static shape/config (written once in __init__, read-only after)
    "cfg": DISPATCH, "me": DISPATCH, "n_srv": DISPATCH, "n_cl": DISPATCH,
    "n_repl": DISPATCH, "b_loc": DISPATCH, "b_merged": DISPATCH,
    "wl": DISPATCH, "be": DISPATCH, "vote_mode": DISPATCH,
    "defer_budget": DISPATCH, "C": DISPATCH, "K": DISPATCH,
    "_width": DISPATCH, "_n_scalars": DISPATCH, "_counts_levels": DISPATCH,
    "vote_step": DISPATCH, "check_step": DISPATCH, "apply_step": DISPATCH,
    "maat_vote": DISPATCH, "group_step": DISPATCH,
    "_elastic": DISPATCH, "_M": DISPATCH, "_full_planes": DISPATCH,
    "_plane_lo": DISPATCH, "_plane_n": DISPATCH,
    "_failover": DISPATCH, "_dedup_on": DISPATCH, "_kill_at": DISPATCH,
    "_committed_cap": DISPATCH, "log_path": DISPATCH,
    "repl_ids": DISPATCH, "_overlap": DISPATCH, "_own_installed": DISPATCH,
    "setup_wait_s": DISPATCH,
    # device / set-up / compile record the launcher carries back
    # (runtime/jaxenv.py; filled in __init__ and at run()'s closing)
    "info": DISPATCH, "_compiles": SHARED, "_compiles_meas": DISPATCH,
    # engine state + counters (dispatch-loop positions only)
    "db": DISPATCH, "cc_state": DISPATCH, "dev_stats": DISPATCH,
    "stats": DISPATCH, "_retry_hist": DISPATCH,
    "_wait_hist": DISPATCH, "_uniq_aborts": DISPATCH,
    "_dup_admits": DISPATCH, "_reacks": DISPATCH,
    "stop_epoch": DISPATCH, "measure_epoch": DISPATCH,
    "_resume_epoch": DISPATCH, "_inflight": DISPATCH,
    "_t_meas": DISPATCH, "_uniq_meas": DISPATCH, "_retry_meas": DISPATCH,
    "_wait_meas": DISPATCH,
    # the lock family's host counter (waiters the defer budget restarts)
    "_counts_locks": DISPATCH, "_lock_forced": DISPATCH,
    "_lock_forced_meas": DISPATCH,
    # the dispatch loop's stage clock (runtime/stages.py): every
    # boundary call, its window snapshot and the queue's running count
    # are dispatch-thread positions (the retire WORKER opens only a
    # `srv.prefetch` trace span, which keeps no state)
    "clk": DISPATCH, "_stage_meas": DISPATCH, "_queue_txns": DISPATCH,
    # admission / retirement queues and dedup state (adm = the overload
    # tier's AdmissionController: admits in _route, pops in the
    # contribution paths, ticks at group boundaries — all dispatch)
    "adm": DISPATCH,
    "pending": DISPATCH, "retry": DISPATCH,
    "blob_buf": DISPATCH, "vote_buf": DISPATCH, "vote2_buf": DISPATCH,
    "_in_system": DISPATCH, "_committed_set": DISPATCH,
    "_committed_recent": DISPATCH, "_held_rsp": DISPATCH,
    "_held_commit": DISPATCH, "repl_acked": DISPATCH,
    "_rejoin_pending": DISPATCH, "_feed_free": DISPATCH,
    # geo-replication tier (quorum ledger + promote accounting; acks
    # arrive through _route on the dispatch thread, holds/releases at
    # the retire positions)
    "_geo": DISPATCH, "_geo_region": DISPATCH, "repl_applied": DISPATCH,
    "_promote_cnt": DISPATCH, "_quorum_hold_t": DISPATCH,
    "_quorum_stall_s": DISPATCH, "_quorum_release_cnt": DISPATCH,
    "_geo_spans": DISPATCH,
    # transaction repair (engine/repair.py): the rep-plane accounting
    # happens only at the dispatch thread's retire positions (the
    # retire worker PREFETCH returns the plane; _retire consumes it)
    "_repair": DISPATCH, "_rep_salvaged": DISPATCH,
    "_rep_meas": DISPATCH, "_rep_span": DISPATCH,
    # transaction flight recorder (runtime/telemetry.py): every hook
    # point — _route admit, the contribution call sites, _retire's
    # verdict/hold pass, _flush_held_rsp's release — runs on the
    # dispatch thread; workers never touch the ring or the stream
    "tel": DISPATCH, "_metrics": DISPATCH,
    # live metrics bus (runtime/metricsbus.py): frames assemble at the
    # retire positions, the aggregator feeds from _route and ticks at
    # group boundaries — all dispatch; workers never touch the bus
    "mbus": DISPATCH, "magg": DISPATCH, "_MB": DISPATCH,
    # isolation audit plane (runtime/audit.py): exports happen at the
    # _retire positions and the summary path — all dispatch; workers
    # never touch the exporter or its stream
    "aud": DISPATCH, "_AUD": DISPATCH,
    # feedback control plane (runtime/controller.py): signal
    # accumulation at the _retire positions, the decide/actuate tick at
    # the group boundary in run() — all dispatch; workers never touch
    # the controller or its accumulators
    "ctl": DISPATCH, "_ctrl_ep": DISPATCH, "_ctrl_dens": DISPATCH,
    "_ctrl_sv": DISPATCH, "_ctrl_wit0": DISPATCH, "_ctrl_t": DISPATCH,
    "_ctrl_breach0": DISPATCH, "_ctrl_span": DISPATCH,
    "_ctrl_log": DISPATCH, "_ctrl_primed": DISPATCH,
    # fencing layer (runtime/faildet.py): detector, heartbeat ledgers
    # and fence counters all live on the dispatch thread (_route runs
    # there; workers only READ smap/_FD for the envelope header)
    "_fencing": DISPATCH, "_fd": DISPATCH, "_FD": DISPATCH,
    "_hb_next_s": DISPATCH, "_epoch_cur": DISPATCH,
    "_blob_seen_from": DISPATCH, "_hb_peer_seen": DISPATCH,
    "_fence_nacks": DISPATCH, "_fence_nack_rx": DISPATCH,
    "_fence_last_ack": DISPATCH, "_fence_reassign_epoch": DISPATCH,
    "_fence_spans": DISPATCH,
    # partition/stall fault surface (wall-clock ticks at dispatch-loop
    # positions only)
    "_partitions": DISPATCH, "_part_links": DISPATCH,
    "_part_on": DISPATCH, "_stall": DISPATCH, "_stall_on": DISPATCH,
    "_t_run0": DISPATCH,
    # elastic membership control plane (cutovers at group boundaries,
    # always applied on the dispatch thread)
    "smap": DISPATCH, "_mig_pending": DISPATCH, "_mig_rows": DISPATCH,
    "_contrib_gone": DISPATCH, "_reassigned": DISPATCH,
    "_plan_sent": DISPATCH, "_rebalance_cnt": DISPATCH,
    "_rows_in": DISPATCH, "_rows_out": DISPATCH,
    "_cutover_stall_ms": DISPATCH, "_redirects": DISPATCH,
    # pod-scale mesh path (parallel/mesh.py): the mesh handle, lazily
    # imported module and feed sharding are stamped in __init__ and only
    # read afterwards; the prefetch-overlap counters and wait ledger are
    # bumped in _retire, which runs on the dispatch thread (the retire
    # WORKER's body is _prefetch_retire, which never touches them)
    "mesh": DISPATCH, "_mesh_mod": DISPATCH, "_feed_sharding": DISPATCH,
    "_prefetch_polls": DISPATCH, "_prefetch_hits": DISPATCH,
    "_prefetch_wait_s": DISPATCH,
    # internally synchronized / thread-safe objects
    "tp": SHARED,            # native transport: MPMC queues
    "logger": SHARED,        # EpochLogger: queue + writer thread
    "_sent_blobs": SHARED,   # deque guarded by _sent_lock (REJOIN resend)
    "_sent_lock": SHARED,
    "wire_pool": SHARED, "retire_pool": SHARED,
}

# worker role -> function names whose call graphs run on that role
# (`_wire` submits _bcast_views/_log_group_views to wire_pool; run()
# submits _prefetch_retire to retire_pool)
WORKER_ENTRY: dict[str, tuple[str, ...]] = {
    WIRE: ("_bcast_views", "_log_group_views"),
    RETIRE: ("_prefetch_retire",),
}

# method names that mutate their receiver (the static checker flags
# `self.X.<mutator>(...)` from a non-owning worker; the runtime guard
# intercepts the same set)
MUTATORS = frozenset((
    "append", "appendleft", "extend", "extendleft", "insert", "pop",
    "popleft", "popitem", "remove", "discard", "clear", "add", "update",
    "setdefault", "sort", "reverse",
    "__setitem__", "__delitem__",
    # augmented in-place operators: `buf = self._in_system; buf |= ...`
    # from a worker is exactly the aliased mutation only the runtime
    # guard can see, so the wrappers must intercept these too
    "__ior__", "__iand__", "__ixor__", "__isub__", "__iadd__", "__imul__",
))

# dispatch-owned attrs wrapped by install(): plain host collections only.
# db/cc_state/dev_stats are jax pytrees (a dict subclass would turn them
# into opaque leaves) and numpy buffers are mutated via views — both are
# covered by the static checker instead.
GUARDED = (
    "pending", "blob_buf", "vote_buf", "vote2_buf", "_in_system",
    "_committed_set", "_committed_recent", "_held_rsp", "_held_commit",
    "_feed_free", "_mig_rows", "_reassigned", "_rejoin_pending",
    "_contrib_gone", "repl_acked", "repl_applied", "_quorum_hold_t",
    "_geo_spans", "_blob_seen_from", "_hb_peer_seen", "_fence_spans",
)


class OwnershipViolation(AssertionError):
    """A thread mutated state owned by a different thread role."""


_guard_cache: dict[type, type] = {}


def _guarded_class(base: type) -> type:
    """Subclass of ``base`` whose mutators assert the stamped owner."""
    cls = _guard_cache.get(base)
    if cls is not None:
        return cls

    def _check(self):
        t = threading.current_thread()
        if t is not self._own_thread:
            raise OwnershipViolation(
                f"{self._own_name}: mutated from thread {t.name!r}; "
                f"owner is {self._own_thread.name!r} (dispatch). "
                f"Staged worker code must stay pure — see "
                f"runtime/ownercheck.py")

    ns = {"_check_owner": _check, "_own_thread": None, "_own_name": "?"}

    def _make(m, base_m):
        def f(self, *a, **kw):
            self._check_owner()
            return base_m(self, *a, **kw)
        f.__name__ = m
        return f

    for m in MUTATORS:
        base_m = getattr(base, m, None)
        if base_m is not None:
            ns[m] = _make(m, base_m)
    cls = type(f"Guarded{base.__name__}", (base,), ns)
    _guard_cache[base] = cls
    return cls


def _guard_value(val, owner: threading.Thread, name: str):
    """Wrapped copy of a plain collection (None when not wrappable)."""
    for base in (deque, dict, set, list):
        if type(val) is base:            # exact type: never re-wrap
            cls = _guarded_class(base)
            if base is deque and val.maxlen is not None:
                g = cls(val, val.maxlen)
            else:
                g = cls(val)
            g._own_thread = owner
            g._own_name = name
            return g
    return None


def install(server) -> int:
    """Stamp the calling thread (the dispatch thread — ServerNode is
    constructed and run on it) as owner of the GUARDED collections and
    wrap them with asserting subclasses.  Returns the number wrapped.
    Called only under ``owner_check=true``; the default config never
    reaches this function."""
    owner = threading.current_thread()
    wrapped = 0
    for attr in GUARDED:
        val = getattr(server, attr, None)
        if val is None:
            continue
        g = _guard_value(val, owner,
                         f"srv{getattr(server, 'me', '?')}.{attr}")
        if g is not None:
            setattr(server, attr, g)
            wrapped += 1
    server._own_installed = wrapped
    return wrapped
