#!/usr/bin/env python3
"""chip_smoke.py — the served path on one TPU v5e, from a clean checkout.

    python chip_smoke.py              one chip, the driver's check
    python chip_smoke.py --four-chip  four chips, run by a builder

Drives the product path once through the normal launcher
(`python -m deneva_tpu.runtime.launch`: one server process on the chip,
client processes on the CPU) and checks ANSWERS, never speed:

  served_8m      YCSB 8M rows x 10 fields, theta 0.9, 50% writes,
                 TPU_BATCH, epoch_batch 16384, C=32 epochs/dispatch, K=2
                 groups in flight, 2 clients, command log on.  The chip's
                 final table digest must equal a replay of that log on
                 the CPU backend, in a separate process.
  served_fullrow the same with real 10 x 100 B rows at 2M rows
  served_occ     one non-deterministic backend (OCC, epoch_batch 1024):
                 the B x B validation program, compiled and run once

Every phase: all nodes report, commits > 0, every client is acked, acks
<= commits, no compilation inside the measured window, and the server
process itself reports platform "tpu".  A server asked for the chip that
finds none raises before it loads a table: no CPU fallback, no retry.

This parent process never imports JAX (a chip belongs to one process):
every phase is a child, one at a time, and the device on the last line
is what the chip-holding server process reported about itself.  Each
phase prints a line when it starts and one with its wall and compile
seconds when it ends; a failure names the phase and shows the end of the
failing child's stderr.  The last stdout line is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# the deployment BASELINE round 5 measured on the chip (a `Config` as
# --field=value strings: this module must import without the package)
SERVED = dict(
    workload="YCSB", cc_alg="TPU_BATCH", node_cnt=1, client_node_cnt=2,
    synth_table_size=1 << 23, req_per_query=10, max_accesses=16,
    zipf_theta=0.9, read_perc=0.5, write_perc=0.5, epoch_batch=16384,
    pipeline_epochs=32, pipeline_groups=2, conflict_buckets=8192,
    max_txn_in_flight=1 << 21, client_batch_size=16384,
    warmup_secs=2.0, done_secs=3.0)

# (phase, overrides of SERVED, command log + CPU replay?)
# The logged phase keeps its windows short: the chip runs ~400 epochs of
# 16384 txns a second and the CPU replays ~3-5 of them a second, so the
# replay — not the run — is what the epoch count must fit (measured, my
# chip run, PR 22: 2048 epochs of a 2 s + 3 s run were not replayed in
# 600 s).
PHASES = (
    ("served_8m", dict(logging="true", warmup_secs=0.25, done_secs=0.5),
     True),
    ("served_fullrow", dict(sim_full_row="true",
                            synth_table_size=1 << 21), False),
    ("served_occ", dict(cc_alg="OCC", epoch_batch=1024,
                        synth_table_size=1 << 21,
                        max_txn_in_flight=1 << 17,
                        client_batch_size=1024), False),
)
PHASE_TIMEOUT_S = 600           # per child; the whole script has 1200 s
# what the launcher is asked to put the server on.  The gate in
# `check_served` wants "tpu" whatever this says: there is no option that
# lets a CPU run pass (tests/test_chip_smoke.py rehearses the phases by
# setting this to "cpu" and sees exactly the gate fail).
SERVER_PLATFORM = "tpu"


class PhaseFailed(Exception):
    pass


def cfg_args(fields: dict) -> list[str]:
    return [f"--{k}={v}" for k, v in fields.items()]


def served_cfg(**over):
    """`SERVED` (+ overrides) as a validated `Config`."""
    from deneva_tpu.config import Config
    return Config.from_args(cfg_args({**SERVED, **over}))


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def run_child(phase: str, argv: list[str], timeout_s: float) -> str:
    """Run one child to its end; return its stdout.  Whatever it started
    dies with it (own process group).  On a non-zero exit or a timeout:
    the phase name and the last 60 lines of the child's stderr."""
    proc = subprocess.Popen(argv, cwd=ROOT, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout_s)
        why = f"exit code {proc.returncode}"
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, err = proc.communicate()
        why = f"no end after {timeout_s:.0f} s (killed)"
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)     # stragglers, if any
        except ProcessLookupError:
            pass
    if proc.returncode != 0:
        tail = "\n".join((err or "").splitlines()[-60:])
        out_tail = "\n".join((out or "").splitlines()[-15:])
        raise PhaseFailed(
            f"phase {phase}: child {why}\n--- last stdout lines ---\n"
            f"{out_tail}\n--- last 60 stderr lines ---\n{tail}")
    return out


def parse_launch(out: str) -> dict[int, dict]:
    """{node: {"kind", "summary": {k: float}, "info": {...}}} from the
    launcher CLI's `[device]` and `[summary]` lines."""
    from deneva_tpu.stats import parse_summary      # numpy only, no JAX
    nodes: dict[int, dict] = {}
    for line in out.splitlines():
        if line.startswith("[device] node="):
            head, _, js = line.partition(" {")
            nid = int(head.split("node=", 1)[1])
            nodes.setdefault(nid, {})["info"] = json.loads("{" + js)
        elif line.startswith("node ") and "[summary]" in line:
            nid = int(line.split()[1])
            kind = line.split("(", 1)[1].split(")", 1)[0]
            nodes.setdefault(nid, {}).update(kind=kind,
                                             summary=parse_summary(line))
    return nodes


def check_served(phase: str, fields: dict,
                 nodes: dict[int, dict]) -> list[str]:
    """The answer checks every served phase must pass; returns failures."""
    bad: list[str] = []
    n_all = int(fields["node_cnt"]) + int(fields["client_node_cnt"])
    if sorted(nodes) != list(range(n_all)) or any(
            "summary" not in n or "info" not in n for n in nodes.values()):
        return [f"{phase}: expected [device]+[summary] of nodes "
                f"0..{n_all - 1}, got {sorted(nodes)}"]
    srv, info = nodes[0]["summary"], nodes[0]["info"]
    if info.get("platform") != "tpu":
        bad.append(f"server ran on platform {info.get('platform')!r}, "
                   "not 'tpu' — no TPU was found")
    if srv["total_txn_commit_cnt"] <= 0:
        bad.append("server committed nothing in the measured window")
    if fields["cc_alg"] == "TPU_BATCH" and (
            srv["total_txn_abort_cnt"] or info["run_abort_cnt"]):
        bad.append(f"TPU_BATCH aborted ({info['run_abort_cnt']} in the run)")
    acks = 0
    for nid in range(1, n_all):
        got = nodes[nid]["summary"]["txn_cnt"]
        acks += got
        if got <= 0:
            bad.append(f"client {nid} was never acked")
    if acks > info["run_commit_cnt"]:
        bad.append(f"clients count {acks:.0f} acks, the server only "
                   f"{info['run_commit_cnt']} commits")
    if info["window_compile_cnt"] != 0:
        bad.append(f"{info['window_compile_cnt']} compilation(s) inside "
                   "the measured window")
    return bad


def serve_and_check(phase: str, over: dict, replay: bool,
                    scratch: str) -> tuple[dict, list[str]]:
    """One served run through the launcher CLI (+ the CPU replay of its
    command log): (the server's `info`, the checks that failed).  Raises
    PhaseFailed when a child does not run to its end."""
    fields = {**SERVED, **over}
    log_dir = os.path.join(scratch, phase)
    out = run_child(phase, [
        sys.executable, "-m", "deneva_tpu.runtime.launch",
        f"--platform={SERVER_PLATFORM}", "--client_platform=cpu",
        f"--log_dir={log_dir}"] + cfg_args(fields), PHASE_TIMEOUT_S)
    nodes = parse_launch(out)
    bad = check_served(phase, fields, nodes)
    info = nodes.get(0, {}).get("info", {})
    srv = nodes.get(0, {}).get("summary", {})
    say(f"phase {phase}: device={info.get('platform')}/"
        f"{info.get('kind')}/x{info.get('count')} "
        f"load_s={info.get('load_s')} warm_s={info.get('warm_s')} "
        f"compile_s={info.get('compile_s')} "
        f"compiles={info.get('compile_cnt')} "
        f"cache_hits={info.get('cache_hits')} "
        f"window_compiles={info.get('window_compile_cnt')} "
        f"commits={srv.get('total_txn_commit_cnt')} "
        f"aborts={srv.get('total_txn_abort_cnt')} "
        f"run_commits={info.get('run_commit_cnt')} "
        f"epochs={srv.get('epoch_cnt')}")
    if replay and "info" in nodes.get(0, {}):
        logs = glob.glob(os.path.join(log_dir, "*", "node0.log.bin"))
        if len(logs) != 1:
            bad.append(f"expected one command log under {log_dir}, "
                       f"found {logs}")
        else:
            t1 = time.monotonic()
            rout = run_child(phase + ".cpu_replay", [
                sys.executable, os.path.abspath(__file__), "--replay-child",
                logs[0]] + cfg_args({**fields, "log_dir": log_dir}),
                PHASE_TIMEOUT_S)
            digest = rout.strip().splitlines()[-1]
            say(f"phase {phase}: cpu_replay_s={time.monotonic() - t1:.1f} "
                f"log_bytes={os.path.getsize(logs[0])} "
                f"chip_digest={info.get('state_digest', '')[:16]} "
                f"replay_digest={digest[:16]}")
            if digest != info.get("state_digest"):
                bad.append("the chip's state_digest differs from the CPU "
                           "replay of its own command log")
    shutil.rmtree(log_dir, ignore_errors=True)
    return info, bad


def run_served_phase(phase: str, over: dict, replay: bool,
                     scratch: str) -> dict:
    """`serve_and_check` between its start and end lines; returns the
    server's `info`, raises PhaseFailed on any failed check."""
    say(f"phase {phase}: start")
    t0 = time.monotonic()
    info, bad = serve_and_check(phase, over, replay, scratch)
    say(f"phase {phase}: end wall_s={time.monotonic() - t0:.1f} "
        f"compile_s={info.get('compile_s')} "
        + ("ok" if not bad else "FAILED"))
    if bad:
        raise PhaseFailed(f"phase {phase}: " + "; ".join(bad))
    return info


def replay_child(log_path: str, argv: list[str]) -> None:
    """Separate process, pinned to the CPU backend: re-execute the
    command log and print the digest of the table it rebuilds."""
    sys.path.insert(0, ROOT)
    from deneva_tpu.runtime.jaxenv import init_jax
    init_jax("cpu")
    from deneva_tpu.config import Config
    from deneva_tpu.runtime.logger import replay_log, state_digest
    cfg = Config.from_args(argv).replace(node_id=0, part_cnt=1)
    print(state_digest(replay_log(log_path, cfg)), flush=True)


# ---- four chips (behind --four-chip; the driver never passes it) -------

def four_chip_child(scratch: str) -> None:
    """ONE process driving all four chips: the served YCSB/TPU_BATCH run
    with device_parts=4 and again with device_parts=1, on the
    deterministic rig of tests/test_mesh_cluster.py (every batch
    delivered before the barrier, zero-length windows), at the served
    table size.  Prints one JSON line per run, then the comparison."""
    sys.path.insert(0, ROOT)
    import hashlib
    import threading

    import numpy as np

    from deneva_tpu.runtime.jaxenv import init_jax
    dev = init_jax(SERVER_PLATFORM)
    if dev["count"] < 4:
        raise SystemExit(f"--four-chip needs 4 chips, JAX found {dev}")
    import jax

    from deneva_tpu.runtime import wire
    from deneva_tpu.runtime.native import NativeTransport, ipc_endpoints
    from deneva_tpu.runtime.server import ServerNode
    from deneva_tpu.storage.table import mc_block_geometry
    from deneva_tpu.workloads import get_workload

    n_batches, bsz = 16, int(SERVED["epoch_batch"])

    def one_run(parts: int) -> dict:
        cfg = served_cfg(
            client_node_cnt=1, device_parts=parts, logging="true",
            log_dir=os.path.join(scratch, f"dp{parts}"), warmup_secs=0.0,
            done_secs=0.0).replace(node_id=0, part_cnt=1)
        eps = ipc_endpoints(2, f"fc{os.getpid()}_{parts}", scratch)
        wl = get_workload(cfg)
        batches = []
        for s in range(n_batches):
            k, t, sc = wl.to_wire(wl.generate(jax.random.PRNGKey(100 + s),
                                              bsz))
            batches.append((np.arange(bsz, dtype=np.int64) + bsz * s,
                            np.asarray(k), np.asarray(t), np.asarray(sc)))
        out: dict = {"parts": parts}

        def run_server():
            node = None
            try:
                node = ServerNode(cfg, eps, SERVER_PLATFORM, 600.0)
                out["shards"] = shard_report(node)
                node.run()
                out["info"] = node.info
                out["rows"] = rows_digest(node.db)
            except BaseException as e:      # surfaced by the main thread
                out["err"] = repr(e)
                raise
            finally:
                if node is not None:
                    node.close()

        def shard_report(node) -> list[str]:
            lines, bad = [], []
            state = {"db": node.db, "cc_state": node.cc_state}
            for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
                if not hasattr(leaf, "addressable_shards"):
                    continue
                name = jax.tree_util.keystr(path)
                sh = [(s.device.id, tuple(s.data.shape))
                      for s in leaf.addressable_shards]
                repl = leaf.sharding.is_fully_replicated
                lines.append(f"{name} {tuple(leaf.shape)} "
                             f"{'replicated' if repl else 'sharded'} {sh}")
                if parts > 1 and not repl and (
                        len({d for d, _ in sh}) != parts
                        or any(s[0] * parts != leaf.shape[0]
                               for _, s in sh)):
                    bad.append(name)
            tab = node.db["MAIN_TABLE"].columns["F0"]
            if parts > 1 and tab.sharding.is_fully_replicated:
                bad.append("MAIN_TABLE.F0 is not sharded")
            if bad:
                raise RuntimeError(f"sharded leaves not spread over "
                                   f"{parts} devices: {bad}")
            return lines

        def rows_digest(db) -> str:
            """sha256 of every column's rows IN KEY ORDER (the mesh run
            keeps them owner-major: key k in block k % D at k // D)."""
            tab = db["MAIN_TABLE"]
            n = tab.capacity
            k = np.arange(n)
            if tab.mc_parts > 1:
                _, lb = mc_block_geometry(n, tab.anchor_rows, tab.mc_parts)
                k = (k % tab.mc_parts) * lb + k // tab.mc_parts
            h = hashlib.sha256()
            for name in sorted(tab.columns):
                h.update(np.ascontiguousarray(
                    np.asarray(tab.columns[name])[k]).tobytes())
            return h.hexdigest()

        th = threading.Thread(target=run_server, daemon=True)
        th.start()
        cl = NativeTransport(1, eps, 2)
        cl.start(600_000)
        acked: list = []

        def on_other(src, rtype, payload):
            if rtype == "CL_RSP":
                acked.append(wire.decode_cl_rsp(payload))

        try:
            for tags, k, t, sc in batches:
                cl.sendv(0, "CL_QRY_BATCH",
                         wire.qry_block_parts(tags, k, t, sc))
            cl.flush()
            wire.run_barrier(cl, 1, 2, on_other, "four-chip client", 600.0)
            t_end = time.monotonic() + 600
            while time.monotonic() < t_end and th.is_alive():
                m = cl.recv(50_000)
                if m is None:
                    continue
                if m[1] == "SHUTDOWN":
                    break
                on_other(*m)
        finally:
            th.join(timeout=600)
            cl.close()
        if "err" in out or "info" not in out:
            raise RuntimeError(f"device_parts={parts} run failed: "
                               f"{out.get('err', 'server never finished')}")
        tags = np.sort(np.concatenate(acked)) if acked else np.zeros(0)
        log = glob.glob(os.path.join(scratch, f"dp{parts}",
                                     "node0.log.bin"))[0]
        with open(log, "rb") as f:
            out["log_sha"] = hashlib.sha256(f.read()).hexdigest()
        out["acked"] = len(tags)
        out["acked_sha"] = hashlib.sha256(tags.tobytes()).hexdigest()
        return out

    runs = {}
    for parts in (4, 1):
        t0 = time.monotonic()
        r = runs[parts] = one_run(parts)
        for line in r.pop("shards"):
            print(f"[shards dp={parts}] {line}", flush=True)
        r["wall_s"] = round(time.monotonic() - t0, 1)
        print(f"[four-chip run] {json.dumps(r)}", flush=True)
    a, b = runs[4], runs[1]
    same = {k: a[k] == b[k] for k in ("log_sha", "acked_sha", "acked",
                                      "rows")}
    same["commits"] = (a["info"]["run_commit_cnt"]
                       == b["info"]["run_commit_cnt"] > 0)
    same["aborts"] = a["info"]["run_abort_cnt"] == b["info"]["run_abort_cnt"]
    print(f"[four-chip compare] {json.dumps(same)}", flush=True)
    if not all(same.values()) or a["acked"] <= 0:
        raise SystemExit("device_parts=4 and device_parts=1 differ: "
                         f"{same}")
    print(json.dumps({"device": dev}), flush=True)


def main(argv: list[str]) -> int:
    if argv[:1] == ["--replay-child"]:
        replay_child(argv[1], argv[2:])
        return 0
    if argv[:1] == ["--four-chip-child"]:
        four_chip_child(argv[1])
        return 0
    if argv not in ([], ["--four-chip"]):
        print(__doc__)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "deneva_tpu")):
        print("chip_smoke: the deneva_tpu package is not beside this "
              "script — nothing to run", file=sys.stderr)
        return 2
    scratch = tempfile.mkdtemp(prefix="dts_")
    t0 = time.monotonic()
    try:
        if argv:
            say("phase four_chip: start")
            out = run_child("four_chip", [
                sys.executable, os.path.abspath(__file__),
                "--four-chip-child", scratch], 1100)
            for line in out.splitlines()[:-1]:
                print(line)
            device = json.loads(out.splitlines()[-1])["device"]
            say(f"phase four_chip: end wall_s={time.monotonic() - t0:.1f} ok")
        else:
            device = None
            for phase, over, replay in PHASES:
                info = run_served_phase(phase, over, replay, scratch)
                device = device or {k: info[k] for k in
                                    ("platform", "kind", "count")}
    except PhaseFailed as e:
        print(f"[chip_smoke] FAILED after {time.monotonic() - t0:.1f} s\n{e}",
              flush=True)
        return 1
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    say(f"all phases ok in {time.monotonic() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
