#!/usr/bin/env python3
"""The dispatch thread's stage clock over UNTRACED runs of a benchmark cell.

    python tools/stage_record.py --workload <cell> --seed <n> --seconds <s>
                                 [--launches <k>]

One whole run through `benchmark/run.py`'s own functions (imported as a
module: verify, then the timed launch, no profiler anywhere) and, with
``--launches k``, k - 1 more TIMED launches on the seeds n + 1 ... (a
stall hunt wants many windows and one verification: the check is two to
six times the window).  `run_cell` throws the timed launch's parsed
server lines away with its run directory; this keeps them and prints,
for every launch:

* the result line (the first launch; `correct` is the whole run's) or
  the end-to-end metrics of a further timed launch;
* the window's wall and CPU milliseconds an epoch by stage
  (`stage_<stage>_time` / `stage_<stage>_cpu_time` of `[summary]`, by
  `benchmark/stage_cpu.py`'s rule), their sums, `host.busy_share` and
  `host.offcpu_share`;
* `process_cpu_time` over the window's wall: the cores the server burns,
  of the `cpus` it may use;
* `stage_record` of the `[device]` line (`deneva_tpu/runtime/stages.py`):
  the window's pass walls and the longest intervals of the run — over
  every stage (`*` marks the two waits by design) and over the working
  stages — each with the second since the clients' start barrier it began
  in, its wall, its CPU, the other threads' CPU over the span between the
  two readings of the process clock that enclose it (a tenth of a second
  or a pass), and the clients' acks (in thousands) in the second before,
  its own and the next: a stall that the clients felt dips there;
* one `[stage_record] {json}` line with all of it, for a table.

The parent process never imports JAX.  On the chip: `chiprun -- python
tools/stage_record.py ...`; without one the run fails its chip gate as
`benchmark/run.py` does, after printing what the launches read.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
# a working-stage interval this long is a stall worth a line of its own
STALL_S = 0.05


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def keep(res: dict) -> dict:
    """What a timed launch leaves behind that the report needs, taken
    while its run directory still exists."""
    with open(os.path.join(res["dir"], "barrier_ns")) as f:
        barrier_s = int(f.read()) * 1e-9        # CLOCK_MONOTONIC, shared
    acks = [sum(v) for v in zip(*(c["acks_by_s"] for c in res["clients"]))]
    return dict(server=res["server"], barrier_s=barrier_s, acks_by_s=acks)


def report(seed: int, e2e: dict, kept: dict, run, stage_cpu) -> dict:
    """One launch's numbers as a dict (the `[stage_record]` line)."""
    summ = kept["server"]["summary"]
    rec = kept["server"].get("info", {}).get("stage_record")
    ctx = dict(server=kept["server"])
    epochs = summ.get("stage_epoch_cnt") or 0.0
    wall = summ.get("stage_wall_time") or 0.0
    per = 1e3 / epochs if epochs else 0.0
    by_stage = {s: dict(wall_ms=per * stage_cpu.wall_s(summ, s),
                        cpu_ms=per * stage_cpu.cpu_s(summ, s))
                for s in stage_cpu.STAGES}
    out = dict(
        seed=seed, end_to_end=e2e, epochs=epochs, window_s=wall,
        ms_per_epoch=by_stage,
        wall_ms_per_epoch=sum(v["wall_ms"] for v in by_stage.values()),
        work_wall_ms_per_epoch=sum(by_stage[s]["wall_ms"]
                                   for s in stage_cpu.WORKING),
        cpu_ms_per_epoch=stage_cpu.cpu_ms_per_epoch(ctx),
        busy_share=run.load_by_name("metrics", "host.busy_share").read(ctx),
        offcpu_share=stage_cpu.offcpu_share(ctx),
        process_cores=(summ["process_cpu_time"] / wall
                       if wall and "process_cpu_time" in summ else None),
        acks_by_s=kept["acks_by_s"], stage_record=rec)
    if rec is not None:
        # seconds since the clients' barrier, and what they acked around
        # each of the longest intervals
        lead = rec["t_start"] - kept["barrier_s"]
        out["window_at_s"] = rec["t_meas"] - kept["barrier_s"]
        acks = kept["acks_by_s"]
        for r in rec["longest"] + rec.get("longest_work", []):
            r["barrier_s"] = round(r["at_s"] + lead, 3)
            sec = int(r["barrier_s"])
            r["acks_k"] = [acks[i] // 1000 if 0 <= i < len(acks) else None
                           for i in (sec - 1, sec, sec + 1)]
    return out


def show(rep: dict, stage_cpu) -> None:
    def line(msg):
        print(f"[stage] {msg}", flush=True)
    e2e = " ".join(f"{k}={v['value']:.6g}" for k, v in
                   rep["end_to_end"].items())
    line(f"seed={rep['seed']} {e2e}")
    line(f"window: {rep['epochs']:.0f} epochs in {rep['window_s']:.3f} s; "
         "ms an epoch   wall     cpu")
    for s, v in rep["ms_per_epoch"].items():
        mark = "*" if s in stage_cpu.WAITS else " "
        line(f"  {s + mark:<13} {v['wall_ms']:8.4f} {v['cpu_ms']:8.4f}")
    line(f"  {'all':<13} {rep['wall_ms_per_epoch']:8.4f} "
         f"{rep['cpu_ms_per_epoch'] or 0.0:8.4f}   working stages' wall "
         f"{rep['work_wall_ms_per_epoch']:.4f}")
    cores = rep["process_cores"]
    rec = rep["stage_record"]
    line(f"host.busy_share={rep['busy_share'] or 0.0:.3f}% "
         f"host.offcpu_share={rep['offcpu_share'] or 0.0:.3f}% "
         f"process_cpu/wall="
         + ("not printed" if cores is None else f"{cores:.3f}")
         + (f" of {rec['cpus']} cpus" if rec else ""))
    if rec is None:
        line("no stage_record on the [device] line (a program before "
             "PR 40)")
        return
    pw = rec["pass_wall_s"]
    line(f"record: {rec['intervals']} intervals, {rec['dropped']} dropped; "
         f"the window opened {rep['window_at_s']:.3f} s after the barrier; "
         f"pass wall ms p50={1e3 * pw['p50']:.3f} p99={1e3 * pw['p99']:.3f} "
         f"max={1e3 * pw['max']:.3f}")
    for key in ("longest", "longest_work"):
        line(f"{key} (s): stage group barrier+ wall cpu "
             "others_cpu/over_span acks_k[before,at,after]")
        for r in rec.get(key, []):
            stall = "  <-- STALL" if not r["wait"] and \
                r["wall_s"] >= STALL_S else ""
            others = "        -/-" if r["others_cpu_s"] is None else \
                f"{r['others_cpu_s']:9.6f}/{r['span_s']:.3f}"
            line(f"  {r['stage'] + ('*' if r['wait'] else ''):<13}"
                 f"{r['group']:>9} {r['barrier_s']:9.3f} {r['wall_s']:9.6f} "
                 f"{r['cpu_s']:9.6f} {others} {r['acks_k']}{stall}")
    line("acks per second since the barrier, in thousands: "
         + " ".join(str(a // 1000) for a in rep["acks_by_s"]))


def main(argv: list[str], run=None, cell=None) -> int:
    """``run`` / ``cell``: a test's handles (run.py loaded with the
    server steered to the CPU, a toy-sized cell)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--launches", type=int, default=1)
    args = ap.parse_args(argv)
    run = run or _load("bench_run", os.path.join(BENCH, "run.py"))
    stage_cpu = _load("bench_stage_cpu", os.path.join(BENCH, "stage_cpu.py"))
    cell = cell or run.load_cell(args.workload)
    kept: list[dict] = []
    timed_phase = run.timed_phase

    def keeping(*a, **kw):
        checks, res = timed_phase(*a, **kw)
        if "summary" in res["server"]:
            kept.append(keep(res))
        return checks, res

    def emit(seed, e2e):
        rep = report(seed, e2e, kept.pop(), run, stage_cpu)
        show(rep, stage_cpu)
        print("[stage_record] " + json.dumps(rep), flush=True)

    rc = 0
    run.timed_phase = keeping       # `run_cell` calls it by this name
    try:
        result = run.run_cell(cell, args.seed, args.seconds, False)
        print(json.dumps(result), flush=True)
        e2e = result["metrics"]
    except run.RunFailed as e:
        print(f"[bench] FAILED: {e}", file=sys.stderr, flush=True)
        rc, e2e = 1, {}
    finally:
        run.timed_phase = timed_phase
    if not kept:
        return rc
    emit(args.seed, e2e)
    for seed in range(args.seed + 1, args.seed + args.launches):
        run_dir = tempfile.mkdtemp(prefix="dbs")
        try:
            checks, res = keeping(cell, seed, args.seconds, run_dir, False)
            for name, v, lim in checks:
                if v > lim:
                    print(f"[check] {name} value={v:g} limit={lim:g} FAILED",
                          flush=True)
                    rc = 1
            if kept:
                emit(seed, run.compute_metrics(
                    cell, run.metric_context(cell, res, None), traced=False))
        except run.RunFailed as e:
            print(f"[bench] FAILED: {e}", file=sys.stderr, flush=True)
            return 1
        finally:
            shutil.rmtree(run_dir, ignore_errors=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
