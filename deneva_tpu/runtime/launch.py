"""Multi-process cluster launcher (reference `scripts/run_experiments.py`
local mode: all nodes as processes on one box over IPC sockets,
`transport/transport.cpp:132-133` — the de-facto integration rig,
SURVEY §4.4; TCP endpoints for real clusters).

Node ids: servers 0..node_cnt-1, clients node_cnt..node_cnt+client_cnt-1
(the reference numbers the same way, `system/global.h:298-306`).

A chip belongs to one process: the launcher's parent never imports
JAX, servers run on ``platform`` and clients and replicas on the CPU, so
on a one-chip host exactly one server process asks for the chip.  Each
child pins its own ``JAX_PLATFORMS`` (`runtime/jaxenv.py`) and fails if
JAX answers with another platform than the one it was asked for.

CLI:  python -m deneva_tpu.runtime.launch --node_cnt=2 --client_node_cnt=1 \
          --cc_alg=CALVIN --done_secs=3
      python -m deneva_tpu.runtime.launch --platform=tpu --node_cnt=1 ...
prints one [device] and one [summary] line per node (parse the latter
with `deneva_tpu.stats`); ``--client_platform=`` moves the clients.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import sys
import traceback

import itertools

from deneva_tpu.config import Config

_tcp_seq = itertools.count()
# node kind == its module under deneva_tpu.runtime -> its class
_NODE_CLASS = {"server": "ServerNode", "client": "ClientNode",
               "replica": "ReplicaNode"}


def _node_main(kind: str, cfg: Config, endpoints: str, platform: str,
               setup_wait_s: float, q) -> None:
    """One node process.  Reports ``(node_id, "info", dict)`` — the
    device JAX ran on and what set-up cost — then ``(node_id, kind,
    summary_line)``, or ``(node_id, "error", traceback)``."""
    try:
        # before jax is imported, and never setdefault: an inherited
        # JAX_PLATFORMS must not move this node to another device
        from deneva_tpu.runtime.jaxenv import pin_platform
        pin_platform(platform)
        import importlib
        module = importlib.import_module(f"deneva_tpu.runtime.{kind}")
        node = getattr(module, _NODE_CLASS[kind])(
            cfg, endpoints, platform, setup_wait_s)
        try:
            st = node.run()
            q.put((cfg.node_id, "info", node.info))
            q.put((cfg.node_id, kind, st.summary_line()))
        finally:
            # a run() that raises must still release the transport: the
            # error report below races peer teardown otherwise
            node.close()
    except Exception:
        q.put((cfg.node_id, "error", traceback.format_exc()))


def run_cluster(cfg: Config, platform: str = "cpu",
                run_id: str | None = None,
                timeout_s: float | None = None,
                client_platform: str = "cpu",
                node_info: dict[int, dict] | None = None
                ) -> dict[int, tuple[str, str]]:
    """Spawn node_cnt servers + client_node_cnt clients; returns
    {node_id: (kind, summary_line)}.  Raises on any node error.

    ``platform`` selects the servers' JAX platform, ``client_platform``
    the clients'.  The accelerated shape on a one-chip host is ONE
    server on the chip with clients on the CPU (node_cnt=1,
    platform="tpu") — the deployment ``chip_smoke.py`` runs.  Replicas
    replay the command stream through the per-epoch jit; on a one-chip
    host they belong on the CPU, so that is where they always run.

    ``node_info``, when given, is filled with each node's ``info`` dict
    (device platform/kind/count as that process's JAX reported them,
    load/compile seconds, compiles inside the measured window, and with
    ``logging`` the server's final state digest).  IPC sockets live in
    the system temp directory (``TMPDIR``)."""
    import tempfile

    from deneva_tpu.config import WorkloadKind
    from deneva_tpu.runtime.native import ensure_built, ipc_endpoints

    if cfg.workload not in (WorkloadKind.YCSB, WorkloadKind.TPCC,
                            WorkloadKind.PPS):
        raise NotImplementedError(
            f"distributed runtime: workload {cfg.workload} has no wire "
            "adapters (to_wire/from_wire) or partitioned loader")
    n_srv, n_cl = cfg.node_cnt, cfg.client_node_cnt
    n_repl = cfg.replica_cnt * n_srv
    n_all = n_srv + n_cl + n_repl
    run_id = run_id or f"{os.getpid()}_{abs(hash(cfg)) % 99999}"
    if cfg.tport_type == "tcp":
        # loopback TCP (the reference's cluster mode, TPORT_TYPE TCP,
        # config.h:335).  Ports stay below Linux's ephemeral range
        # (default starts at 32768) and vary by pid + a per-process
        # counter so concurrent launches (even same-process) coexist.
        # Best-effort only: no bind-availability probe — a range clash
        # with a resident service fails the cluster at dt_start (the
        # reference's static ifconfig.txt has the same property); rerun
        # or set tport_port explicitly.  IPC mode is the collision-free
        # default for single-box rigs.
        from deneva_tpu.runtime.native import tcp_endpoints
        base = 10000 + (os.getpid() * 131 + next(_tcp_seq) * 997) % 22000
        endpoints = tcp_endpoints(n_all, base_port=base)
    else:
        endpoints = ipc_endpoints(n_all, run_id, tempfile.gettempdir())
    if cfg.logging or cfg.telemetry or cfg.metrics or cfg.audit or cfg.ctrl:
        # namespace log files per run like the IPC endpoints, or two
        # concurrent clusters would truncate each other's logs; the
        # telemetry sidecars, the metrics-bus stream, the audit
        # sidecars and the ctrl decision records live in the same
        # per-run directory
        cfg = cfg.replace(log_dir=os.path.join(cfg.log_dir, run_id))
    if timeout_s is None:
        # generous: every node jit-compiles its epoch step before the
        # barrier, and on a loaded box (parallel test runs) a TPCC
        # compile alone can take minutes
        timeout_s = cfg.warmup_secs + cfg.done_secs + 420
    # build the native library ONCE, here, before any node exists: the
    # nodes are spawned together and would otherwise each run `make`
    # into the same output file (native/build/ is not in a checkout)
    ensure_built()

    ctx = mp.get_context("spawn")
    q: mp.Queue = ctx.Queue()
    procs = []

    def node_proc(kind, node_id, plat, daemon=True, **over):
        # setup_wait_s = this launcher's own limit: a node's dial and
        # INIT_DONE waits never expire while a peer is alive and
        # compiling — the loop below notices a dead peer and ends the
        # run, so no fixed deadline sits across a cold compile
        return ctx.Process(
            target=_node_main,
            args=(kind, cfg.replace(node_id=node_id, part_cnt=n_srv,
                                    **over),
                  endpoints, plat, timeout_s, q),
            daemon=daemon)

    for s in range(n_srv):
        procs.append(node_proc("server", s, platform))
    for c in range(n_cl):
        # a fleet-armed client must parent the loadgen worker processes,
        # and daemonic processes cannot have children; the finally block
        # below terminates it explicitly either way
        procs.append(node_proc("client", n_srv + c, client_platform,
                               daemon=cfg.loadgen_procs <= 1))
    for r in range(n_repl):
        procs.append(node_proc("replica", n_srv + n_cl + r, "cpu"))
    for p in procs:
        p.start()
    # supervision (chaos mode): map each server's node id to its process
    # so a crash can be detected and the node restarted in recovery mode
    srv_proc: dict[int, mp.process.BaseProcess] = {
        s: procs[s] for s in range(n_srv)}
    supervise = cfg.faults_enabled and cfg.logging
    restarted: set[int] = set()
    out: dict[int, tuple[str, str]] = {}
    try:
        import queue as _queue
        import time as _time
        deadline = _time.monotonic() + timeout_s
        while len(out) < n_all:    # one report per node id (a restarted
            #                        server reports under its old id)
            try:
                nid, kind, line = q.get(timeout=1.0)
            except _queue.Empty:
                if supervise and cfg.geo:
                    # geo region loss also takes the region's replicas:
                    # only the planned kill sentinel (exit 17) retires a
                    # follower in place; anything else is a real crash
                    for r in range(n_repl):
                        rid = n_srv + n_cl + r
                        p = procs[rid]
                        if (rid not in out and not p.is_alive()
                                and p.exitcode not in (0, None)):
                            if p.exitcode != 17:
                                raise RuntimeError(
                                    f"replica {rid} crashed (exitcode "
                                    f"{p.exitcode}) in geo mode")
                            out[rid] = ("killed", "")
                if supervise:
                    # a dead, unreported server with logging enabled is
                    # recoverable: restart it once in recovery mode (it
                    # replays its command log and rejoins the mesh) —
                    # the failover the reference never had (SURVEY §5.3)
                    for s, p in srv_proc.items():
                        if (s not in out and s not in restarted
                                and not p.is_alive()
                                and p.exitcode not in (0, None)):
                            restarted.add(s)
                            if cfg.elastic:
                                # failover-with-reassignment: the
                                # survivors absorb the dead node's slots
                                # by log replay — never restart it; its
                                # report slot closes as "killed".  Two
                                # planned exits only: the deliberate
                                # fault_kill sentinel (os._exit(17))
                                # and the fencing self-halt sentinel
                                # (os._exit(18) — a minority/fenced-out
                                # primary retiring itself instead of
                                # serving split-brain writes, reported
                                # as "fenced").  Any other code is a
                                # genuine crash and still fails loudly.
                                if p.exitcode not in (17, 18):
                                    raise RuntimeError(
                                        f"server {s} crashed (exitcode "
                                        f"{p.exitcode}) in elastic mode")
                                out[s] = ("fenced" if p.exitcode == 18
                                          else "killed", "")
                                continue
                            rp = node_proc("server", s, platform,
                                           recover=True)
                            rp.start()
                            procs.append(rp)
                            srv_proc[s] = rp
                dead = [i for i, p in enumerate(procs)
                        if not p.is_alive() and p.exitcode not in (0, None)]
                if not supervise and any(i not in out for i in dead):
                    # a node that died without reporting (killed, or
                    # crashed below Python) ends the run now: its peers
                    # wait for it as long as this launcher does.  (No
                    # restarts off supervision: proc index == node id.)
                    raise RuntimeError(
                        "node process died before reporting; reported="
                        f"{sorted(out)}, dead procs (index, exitcode)="
                        f"{[(i, procs[i].exitcode) for i in dead]}")
                if _time.monotonic() < deadline:
                    continue
                raise RuntimeError(
                    f"cluster timed out after {timeout_s:.0f}s; reported="
                    f"{sorted(out)}, crashed procs (index, exitcode)="
                    f"{[(i, procs[i].exitcode) for i in dead]}") from None
            if kind == "error":
                raise RuntimeError(f"node {nid} failed:\n{line}")
            if kind == "info":
                if node_info is not None:
                    node_info[nid] = line
                continue
            out[nid] = (kind, line)
    finally:
        for p in procs:
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
    return out


def main(argv: list[str]) -> None:
    from deneva_tpu.runtime.jaxenv import device_line
    plat = {"platform": "cpu", "client_platform": "cpu"}
    rest = []
    for a in argv:
        k, _, v = a[2:].partition("=")
        if a.startswith("--") and k in plat:
            if not v:
                raise SystemExit(f"--{k}= needs a JAX platform name")
            plat[k] = v
        else:
            rest.append(a)
    cfg = Config.from_args(rest)
    info: dict[int, dict] = {}
    out = run_cluster(cfg, plat["platform"],
                      client_platform=plat["client_platform"],
                      node_info=info)
    for nid, (kind, line) in sorted(out.items()):
        if nid in info:
            print(device_line(nid, info[nid]))
        print(f"node {nid} ({kind}): {line}")


if __name__ == "__main__":
    main(sys.argv[1:])
