"""Experiment runner (reference `scripts/run_experiments.py`).

The reference rewrites `config.h`, recompiles, launches rundb/runcl under
`timeout` watchdogs and collects per-node output files.  Here every point
is a `run_simulation` call in-process (configs are runtime values); each
point writes ``results/<exp>/<stem>.out`` containing a config echo and the
``[summary]`` line, so `deneva_tpu.harness.parse` (and the reference's own
regex parsers) can consume them.

CLI:  ``python -m deneva_tpu.harness.run <experiment> [--quick] [--out DIR]``
"""

from __future__ import annotations

import os
import sys
import time
import traceback

from deneva_tpu.config import Config
from deneva_tpu.harness.experiments import get_experiment
from deneva_tpu.harness.parse import cfg_header, load_results, outfile_name


def run_point(cfg: Config, out_dir: str, quiet: bool = True) -> str:
    """Run one config, write its output file, return the path.

    ``deploy=inproc`` runs the single-process engine; ``deploy=cluster``
    boots real server/client processes over IPC (the reference's local
    multi-node mode, `scripts/run_experiments.py:67`) and reports server
    0's summary, with every other node's line as a comment."""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, outfile_name(cfg))
    t0 = time.monotonic()
    try:
        if cfg.deploy == "cluster":
            from deneva_tpu.runtime.launch import run_cluster
            out = run_cluster(cfg, platform="cpu")
            body = "".join(f"# node {nid} ({kind}): {line}\n"
                           for nid, (kind, line) in sorted(out.items())
                           if nid != 0)
            body += out[0][1] + "\n"
        else:
            from deneva_tpu.engine.driver import run_simulation
            stats = run_simulation(cfg, quiet=True)
            body = stats.summary_line() + "\n"
        ok = True
    except Exception:
        body = "# run failed\n" + "".join(
            "# " + ln + "\n" for ln in traceback.format_exc().splitlines())
        ok = False
    with open(path, "w") as f:
        f.write(cfg_header(cfg))
        f.write(f"# wall_secs={time.monotonic() - t0:.1f}\n")
        f.write(body)
    if not quiet:
        mark = "ok" if ok else "FAILED"
        print(f"  {outfile_name(cfg)}: {mark} "
              f"({time.monotonic() - t0:.1f}s)", flush=True)
    return path


RESULT_DIRS = {
    # experiment -> canonical results/ leaf when they differ (the
    # repair_ablation sweep IS the "results/repair" record)
    "repair_ablation": "repair",
    "dgcc_contention": "dgcc",
}


def run_experiment(name: str, quick: bool = False,
                   out_root: str = "results", quiet: bool = False,
                   bench: bool = False) -> list[dict]:
    """Run every point of a named experiment; returns parsed result rows.

    ``bench``: full problem sizes with short measurement windows
    (1.5 s warmup + 4 s measured) — the single-chip tier; the
    reference's 60+60 s windows exist to amortize its thread-level noise,
    which the chunked device scan does not have."""
    cfgs = get_experiment(name, quick=quick)
    if bench:
        cfgs = [c.replace(warmup_secs=1.5, done_secs=4.0) for c in cfgs]
    out_dir = os.path.join(out_root, RESULT_DIRS.get(name, name))
    if not quiet:
        print(f"[{name}] {len(cfgs)} points -> {out_dir}", flush=True)
    written = [os.path.basename(run_point(cfg, out_dir, quiet=quiet))
               for cfg in cfgs]
    # only the files this sweep wrote: stale points from earlier runs in
    # the same directory must not leak into the returned table
    return load_results(out_dir, only=written)


def main(argv: list[str]) -> int:
    if not argv or argv[0].startswith("-"):
        from deneva_tpu.harness.experiments import experiment_map
        print("usage: python -m deneva_tpu.harness.run <experiment> "
              "[--quick] [--out DIR]")
        print("experiments:", ", ".join(sorted(experiment_map)))
        return 2
    name = argv[0]
    quick = "--quick" in argv
    bench = "--bench" in argv
    out_root = "results"
    if "--out" in argv:
        i = argv.index("--out")
        if i + 1 >= len(argv):
            print("error: --out needs a directory argument")
            return 2
        out_root = argv[i + 1]
    rows = run_experiment(name, quick=quick, out_root=out_root, bench=bench)
    for row in rows:
        tput = row.get("tput", float("nan"))
        print(f"{row['file']}: tput={tput:.1f} "
              f"abort_rate={row.get('abort_rate', 0.0):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
