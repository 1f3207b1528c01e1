#!/usr/bin/env python3
"""One run of one benchmark cell: served YCSB on the chip, timed from the client's side.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This parent never imports JAX (a chip belongs to one process).  A cell of
`BENCHMARK.json` names a configuration (`benchmark/configs/<config>.json`:
the deployment as the program's `Config` fields, its source, cuts and
guarantees) and a traffic mix (`benchmark/traffic/<traffic>.json`, read
by the workload's generator, `benchmark/generators/<fields.workload, lower
case>.py`, inside the one client process, `benchmark/loadgen.py`).  A
configuration, a traffic mix, a generator, a reference, a cell and a
metric reader are files found by name: adding one edits no file here.  A
run is two launches of the same deployment, one server process on the chip
(`benchmark/server_child.py`) and the traffic's client processes on the
CPU:

1. verify — command log on, short windows.  The configuration's plain
   reference (`benchmark/references/<name>.py`, numpy, nothing of the
   program) executes the logged stream serially and must reproduce the
   chip's table digest and commit count; a validating backend's verdicts
   (replayed from the log by `verdicts_child.py`) are held to its rule
   on exact keys.  The answer checks (`check_served`)
   hold in both launches.  Every number compared is printed beside its
   limit (`[check] ...`).  The check's time is not part of `setup_s`.
2. timed — log off, the traffic's warm-up, then a window of `--seconds`
   on the CLIENTS' clocks.  End-to-end metrics (`--trace 0`) come from
   the clients' own counts and clocks; per-layer metrics (`--trace 1`: a
   device trace of a steady stretch of the window of the SAME programs)
   from the server's closing lines and the reduced trace, each through a
   reader of its own, `benchmark/metrics/<metric name>.py`.

Last stdout line: {"correct", "attempted", "failed", "metrics", "device",
"timing" [, "breakdown"], "checks"}; the checks are stderr's last lines
too.  No chip, or fewer than the cell asks for: non-zero exit, no result
line — there is no option that lets a CPU run pass.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# what the server is asked to run on.  `check_served` wants "tpu"
# whatever this says (tests/benchmark rehearses a run with "cpu" and sees
# exactly that gate fail).
SERVER_PLATFORM = "tpu"
SERVER_CHILD = os.path.join(HERE, "server_child.py")
CHILD_TIMEOUT_S = 1100          # a cold compile fits; a run has 1200 s
SERVE_PAST_WINDOW_S = 1.0       # the server outlasts the clients' window
# the logged launch: the server's windows as `chip_smoke.py`'s logged
# phase has them (the reference must fit every epoch they hold); the
# clients' own window closes inside them
VERIFY_WINDOWS = dict(warmup_secs=0.25, done_secs=0.5, client_window=0.25)
TRACE_START_S = 1.0                     # after the window opens
# seconds traced, by the cell's chips: what `stop_trace` has to store
# grows with chips x epochs x ops an epoch (0.11 ms an op event: 140 s
# for 2.5 s of the four-chip cell, 58 s for 1.0 s = 384 epochs a chip)
TRACE_LEN_S = {1: 2.5, 4: 1.0}


class RunFailed(Exception):
    pass


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_by_name(kind: str, name: str):
    """`benchmark/<kind>/<name>.py` as a module (metric readers,
    references, generators): found by name, so adding one edits no
    file."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(fields: dict):
    """The workload's generator, `generators/<fields.workload in lower
    case>.py`: the parent asks it for a launch's fields, every client
    (`loadgen.py`) for its ring and its messages."""
    return load_by_name("generators", str(fields["workload"]).lower())


def load_cell(name: str) -> dict:
    """The cell's entry of BENCHMARK.json with its configuration and
    traffic files read in, the traffic held to its generator's `check`."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = dict(cells[name])
    cfgs = {c["name"]: c for c in bench["configs"]}
    cell["config_file"] = load_json(
        os.path.join(ROOT, cfgs[cell["config"]]["file"]))
    cell["traffic_file"] = load_json(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    cell["bench"] = bench
    generator(cell["config_file"]["fields"]).check(cell["traffic_file"])
    return cell


def server_fields(cell: dict, seed: int, over: dict) -> dict:
    """The program's `Config` fields for one launch: the configuration
    file's, what the generator makes of the traffic file (the server
    builds its workload object from them; the queries come from the
    clients), the clients, the chips, the seed."""
    f = dict(cell["config_file"]["fields"])
    f.update(generator(f).server_fields(cell["traffic_file"]))
    f.update(client_node_cnt=cell["traffic_file"]["clients"],
             device_parts=cell["chips"], seed=seed % (1 << 31))
    f.update(over)
    return f


# ---- children ----------------------------------------------------------

def _kill(procs) -> None:
    """End every process group and wait until each child has ended."""
    for p in procs:
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass


def run_child(what: str, argv: list[str], timeout_s: float) -> str:
    """One child to its end; returns its stdout.  Its whole process
    group dies with it."""
    p = subprocess.Popen(argv, cwd=ROOT, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True,
                         start_new_session=True)
    try:
        out, err = p.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        raise RunFailed(f"{what}: no end after {timeout_s:.0f} s (killed)")
    finally:
        _kill([p])
    if p.returncode != 0:
        raise RunFailed(f"{what}: exit code {p.returncode}\n"
                        + "\n".join((err or "").splitlines()[-40:]))
    return out


def launch(what: str, cell: dict, fields: dict, seed: int, seconds: float,
           warmup: float, run_dir: str, trace: bool) -> dict:
    """One server on `SERVER_PLATFORM` + the traffic's clients, to their
    end.  Returns {"server": parsed lines, "clients": [reports],
    "wall_s"}."""
    sys.path.insert(0, ROOT)
    from deneva_tpu.config import Config        # no JAX in these two
    from deneva_tpu.runtime.native import ensure_built, ipc_endpoints
    cfg = Config.from_args([f"--{k}={v}" for k, v in fields.items()])
    ensure_built()
    n_cl = int(cell["traffic_file"]["clients"])
    d = os.path.join(run_dir, what)
    os.makedirs(d, exist_ok=True)
    spec = dict(
        platform=SERVER_PLATFORM, fields=fields,
        traffic={**cell["traffic_file"], "warmup_secs": warmup},
        seed=seed, seconds=seconds, setup_wait_s=float(CHILD_TIMEOUT_S),
        endpoints=ipc_endpoints(1 + n_cl, f"b{os.getpid()}{what[0]}",
                                run_dir),
        barrier_file=os.path.join(d, "barrier_ns"),
        transport=dict(msg_size_max=cfg.msg_size_max,
                       send_threads=cfg.send_thread_cnt,
                       recv_threads=cfg.rem_thread_cnt),
        trace=dict(dir=os.path.join(d, "trace"),
                   start_s=warmup + TRACE_START_S,
                   len_s=TRACE_LEN_S[cell["chips"]]) if trace else None)
    spec_path = os.path.join(d, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    say(f"{what}: launching {fields['cc_alg']} rows="
        f"{fields.get('synth_table_size')} logging={fields['logging']} "
        f"window={seconds} s trace={int(trace)}")
    t0 = time.monotonic()
    argvs = [[sys.executable, SERVER_CHILD, spec_path]] + [
        [sys.executable, os.path.join(HERE, "loadgen.py"), spec_path, str(i)]
        for i in range(n_cl)]
    names = ["server"] + [f"client{i}" for i in range(n_cl)]
    files = [(open(os.path.join(d, n + ".out"), "w+"),
              open(os.path.join(d, n + ".err"), "w+")) for n in names]
    procs = []
    try:
        for a, (fo, fe) in zip(argvs, files):
            procs.append(subprocess.Popen(a, cwd=ROOT, stdout=fo, stderr=fe,
                                          start_new_session=True))
        while any(p.poll() is None for p in procs):
            bad = [i for i, p in enumerate(procs)
                   if p.poll() not in (None, 0)]
            if bad or time.monotonic() - t0 > CHILD_TIMEOUT_S:
                break
            time.sleep(0.02)
        wall = time.monotonic() - t0
        for i, p in enumerate(procs):
            if p.poll() != 0:
                files[i][1].seek(0)
                tail = "\n".join(files[i][1].read().splitlines()[-40:])
                why = (f"exit code {p.returncode}" if p.poll() is not None
                       else "still running when another node ended or "
                       f"{CHILD_TIMEOUT_S} s passed (killed)")
                raise RunFailed(f"{what}: {names[i]} {why}\n{tail}")
        outs = []
        for fo, _ in files:
            fo.seek(0)
            outs.append(fo.read())
    finally:
        _kill(procs)
        for fo, fe in files:
            fo.close()
            fe.close()
    server = parse_server(outs[0])
    clients = [parse_client(o) for o in outs[1:]]
    say(f"{what}: ended after {wall:.1f} s, epochs="
        f"{server.get('summary', {}).get('epoch_cnt')}")
    return dict(server=server, clients=clients, wall_s=wall, dir=d,
                fields=fields, seconds=seconds)


def parse_summary(line: str) -> dict[str, float]:
    body = line.split("[summary]", 1)[1].strip()
    return {k: float(v) for k, _, v in
            (kv.partition("=") for kv in body.split(",") if kv)}


def parse_server(out: str) -> dict:
    """The server child's closing lines: {"info", "summary", "memory",
    "trace"}."""
    srv: dict = {}
    for line in out.splitlines():
        if line.startswith("[device] node=0 "):
            srv["info"] = json.loads(line.split(" ", 2)[2])
        elif line.startswith("node 0 (server): [summary]"):
            srv["summary"] = parse_summary(line)
        elif line.startswith("[memory] "):
            srv["memory"] = json.loads(line[9:])
        elif line.startswith("[trace] "):
            srv["trace"] = json.loads(line[8:])
    return srv


def parse_client(out: str) -> dict:
    for line in reversed(out.splitlines()):
        if line.startswith("[client] "):
            return json.loads(line[9:])
    return {}


# ---- the checks --------------------------------------------------------

def check_served(what: str, conf: dict, res: dict
                 ) -> list[tuple[str, float, float]]:
    """The answer checks every launch must pass, as (what, value,
    limit) with value <= limit meaning sound.  ``conf`` is the
    configuration file: where its stated guarantee on aborts is "none
    ...", one abort is a wrong answer, whatever the backend's name."""
    srv, cl = res["server"], res["clients"]
    if "info" not in srv or "summary" not in srv or not all(cl):
        return [(f"{what}.nodes_not_reporting", 1.0, 0.0)]
    info, summ = srv["info"], srv["summary"]
    acks = sum(c["acked"] for c in cl)
    out = [
        (f"{what}.server_not_on_tpu",
         0.0 if info.get("platform") == "tpu" else 1.0, 0.0),
        (f"{what}.window_commits_missing",
         0.0 if summ["total_txn_commit_cnt"] > 0 else 1.0, 0.0),
        (f"{what}.clients_never_acked",
         float(sum(1 for c in cl if c["acked"] <= 0)), 0.0),
        (f"{what}.acks_beyond_commits",
         float(max(0, acks - info["run_commit_cnt"])), 0.0),
        (f"{what}.window_compiles", float(info["window_compile_cnt"]), 0.0),
        (f"{what}.client_window_cut_short",
         float(sum(1 for c in cl if not c["window_closed"])), 0.0),
    ]
    if conf["guarantees"]["aborts"].startswith("none"):
        out.append((f"{what}.deterministic_aborts",
                    float(info["run_abort_cnt"]
                          + summ["total_txn_abort_cnt"]), 0.0))
    return out


def read_log(res: dict) -> bytes:
    """The verify launch's command log (one file, node 0's)."""
    path = os.path.join(res["fields"]["log_dir"], "node0.log.bin")
    with open(path, "rb") as f:
        return f.read()


def logged_launch(cell: dict, seed: int, run_dir: str):
    """The verify launch and what the reference needs from it: (launch,
    its fields, the log's bytes or None, the verdicts or None)."""
    conf = cell["config_file"]
    over = dict(conf.get("verify", {}))
    over.update(logging="true", log_dir=os.path.join(run_dir, "log"),
                warmup_secs=VERIFY_WINDOWS["warmup_secs"],
                done_secs=VERIFY_WINDOWS["done_secs"])
    fields = server_fields(cell, seed, over)
    res = launch("verify", cell, fields, seed,
                 VERIFY_WINDOWS["client_window"],
                 VERIFY_WINDOWS["warmup_secs"], run_dir, trace=False)
    if "info" not in res["server"]:
        return res, fields, None, None
    log = read_log(res)
    verdicts = None
    if conf.get("verdicts") == "replay":
        # a validating backend's log carries no verdicts: replay it
        # through the program's per-epoch step, on the device the server
        # has just left (on the CPU backend the B x B validation costs
        # 0.1 s an epoch), and take each epoch's committed mask — held
        # to the rule on exact keys by the reference, not trusted
        import numpy as np
        t0 = time.monotonic()
        vpath = os.path.join(run_dir, "verdicts.npz")
        vspec = os.path.join(run_dir, "verdicts_spec.json")
        with open(vspec, "w") as f:
            json.dump(dict(fields=fields, out=vpath,
                           platform=SERVER_PLATFORM, log=os.path.join(
                               fields["log_dir"], "node0.log.bin")), f)
        run_child("verdicts", [sys.executable,
                               os.path.join(HERE, "verdicts_child.py"),
                               vspec], CHILD_TIMEOUT_S)
        say(f"verdicts: replayed in {time.monotonic() - t0:.1f} s")
        with np.load(vpath) as z:
            verdicts = {int(e): np.unpackbits(b)[:int(n)].astype(bool)
                        for e, b, n in zip(z["epochs"], z["bits"], z["n"])}
    return res, fields, log, verdicts


def verify_phase(cell: dict, seed: int, run_dir: str
                 ) -> tuple[list[tuple[str, float, float]], dict]:
    """Launch with the command log on, then hold the chip's answers to
    the configuration's plain reference.  Returns (checks, launch)."""
    res, fields, log, verdicts = logged_launch(cell, seed, run_dir)
    checks = check_served("verify", cell["config_file"], res)
    if log is None:
        return checks, res
    t0 = time.monotonic()
    name = cell["config_file"]["reference"]
    ref = load_by_name("references", name)
    ref_checks, notes = ref.verify(log, fields, res["server"]["info"],
                                   verdicts)
    checks += [(f"reference.{n}", v, lim) for n, v, lim in ref_checks]
    say(f"reference {name}: {notes} log_bytes={len(log)} "
        f"reference_s={time.monotonic() - t0:.1f}")
    return checks, res


def timed_phase(cell: dict, seed: int, seconds: float, run_dir: str,
                trace: bool) -> tuple[list[tuple[str, float, float]], dict]:
    tr = cell["traffic_file"]
    over = dict(logging="false", warmup_secs=tr["warmup_secs"],
                done_secs=seconds + SERVE_PAST_WINDOW_S,
                log_dir=os.path.join(run_dir, "tlog"))
    fields = server_fields(cell, seed, over)
    res = launch("timed", cell, fields, seed, seconds, tr["warmup_secs"],
                 run_dir, trace)
    return check_served("timed", cell["config_file"], res), res


# ---- metrics -----------------------------------------------------------

def metric_context(cell: dict, res: dict, trace: dict | None) -> dict:
    """What a metric reader may read: the timed launch's parsed lines,
    the clients' reports and merged window latencies, the reduced trace
    (or None), the merged fields, and the table of peaks."""
    import numpy as np
    lats = [np.load(c["lat_path"]) for c in res["clients"]]
    return dict(server=res["server"], clients=res["clients"],
                lat_ms=np.concatenate(lats) if lats else np.zeros(0),
                seconds=res["seconds"],
                setup_s=res["wall_s"] - res["seconds"],
                fields=res["fields"], trace=trace,
                peaks=load_by_name(".", "peaks"))


def compute_metrics(cell: dict, ctx: dict, traced: bool) -> dict:
    """{name: {"value", "unit"}} for the cell's end-to-end (untraced) or
    per-layer (traced) metrics; a reader that finds nothing to read
    returns None and its metric is left out."""
    group = "per_layer" if traced else "end_to_end"
    out = {}
    for m in cell["bench"][group]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        v = load_by_name("metrics", m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def reduce_trace(res: dict) -> dict:
    """The traced launch's `.xplane.pb`, reduced in a child pinned to the
    CPU backend (reading it needs JAX's own reader).  The server child
    prints `[trace]` with its window or fails the launch by name."""
    tr = res["server"]["trace"]
    out = run_child("trace_reduce", [
        sys.executable, os.path.join(HERE, "trace_reduce.py"),
        os.path.join(res["dir"], "trace"), str(tr["window_s"]),
        str(res["fields"]["pipeline_epochs"])], 300)
    return json.loads(out.strip().splitlines()[-1])


# ---- one run -----------------------------------------------------------

def run_cell(cell: dict, seed: int, seconds: float, trace: bool) -> dict:
    """Verify, then time; returns the result object (or raises
    RunFailed / SystemExit where no result may be printed)."""
    run_dir = tempfile.mkdtemp(prefix="db")
    try:
        t0 = time.monotonic()
        checks, vres = verify_phase(cell, seed, run_dir)
        check_s = time.monotonic() - t0
        shutil.rmtree(os.path.join(run_dir, "log"), ignore_errors=True)
        tchecks, res = timed_phase(cell, seed, seconds, run_dir, trace)
        checks += tchecks
        for name, v, lim in checks:
            print(f"[check] {name} value={v:g} limit={lim:g} "
                  + ("ok" if v <= lim else "FAILED"), flush=True)
        gate = [n for n, v, lim in checks
                if n.endswith("server_not_on_tpu") and v > lim]
        srv = res["server"]
        if gate or "info" not in srv:
            raise RunFailed("no TPU was found: the server ran on "
                            f"{srv.get('info', {}).get('platform')!r}")
        info = srv["info"]
        if info["count"] < cell["chips"]:
            raise RunFailed(f"cell needs {cell['chips']} chips, JAX found "
                            f"{info['count']}")
        reduced = reduce_trace(res) if trace else None
        ctx = metric_context(cell, res, reduced)
        metrics = compute_metrics(cell, ctx, trace)
        sent = sum(c["sent"] for c in res["clients"])
        acked = sum(c["acked"] for c in res["clients"])
        cap = sum(c["cap"] for c in res["clients"])
        mem = srv.get("memory") or {}
        # the peak is the loader's; in use = what serving held (read
        # when the serve loop ended, before the node was closed)
        device = dict(platform=info["platform"], kind=info["kind"],
                      count=info["count"],
                      memory_peak_bytes=mem.get("memory_peak_bytes"),
                      memory_in_use_bytes=mem.get("memory_in_use_bytes"))
        result = dict(correct=all(v <= lim for _, v, lim in checks),
                      attempted=int(sent),
                      failed=int(max(0, sent - acked - cap)),
                      metrics=metrics, device=device)
        # how near the run is to its limits (360 s a run; the children
        # `CHILD_TIMEOUT_S`): the check, the timed launch, and what
        # `stop_trace` took past the serve loop
        result["timing"] = dict(check_s=check_s, timed_wall_s=res["wall_s"])
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = reduced["breakdown"]
            result["timing"]["stop_cost_s"] = srv["trace"]["stop_cost_s"]
        # every number compared beside its limit: the result's last key
        result["checks"] = {n: {"value": v, "limit": lim}
                            for n, v, lim in checks}
        say(f"check_s={check_s:.1f} timed_wall_s={res['wall_s']:.1f} "
            f"load_s={info.get('load_s')} warm_s={info.get('warm_s')} "
            f"compile_s={info.get('compile_s')} "
            f"cache_hits={info.get('cache_hits')} "
            f"epochs={srv['summary'].get('epoch_cnt')} "
            f"run_commits={info.get('run_commit_cnt')} "
            f"acks_in_window={sum(c['win_acked'] for c in res['clients'])} "
            f"latency_samples={len(ctx['lat_ms'])}")
        # how deep in retries the compared launch got, beside the timed
        # window (the check compares the first 0.75 s of a cold start)
        for what, r in (("verify", vres), ("timed", res)):
            summ = r["server"].get("summary", {})
            say(f"{what} window: abort_rate={summ.get('abort_rate')} "
                f"retries_per_txn={summ.get('txn_retries_mean')}")
        say("acks per second since the barrier, in thousands: " + " ".join(
            str(sum(v) // 1000) for v in zip(*(
                c["acks_by_s"] for c in res["clients"]))))
        return result
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "deneva_tpu")):
        print("benchmark: the deneva_tpu package is not beside "
              "benchmark/ — nothing to run", file=sys.stderr)
        return 2
    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except RunFailed as e:
        print(f"[bench] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():    # stderr's last lines
        print(f"[check] {name} value={c['value']:g} limit={c['limit']:g}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
