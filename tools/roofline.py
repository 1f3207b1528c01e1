"""Single-chip roofline/utilization ledger for the full-pool epoch.

VERDICT r4 next #1: the headline (YCSB theta=0.9 full-pool TPU_BATCH) had
been ~6.05M txn/s for three rounds with no accounting of where the
epoch's milliseconds go or how close they run to what the chip can do.
This tool produces that ledger from an `xprof` trace of the real jitted
scan, summed per HLO op (phase microbenchmarks time each phase's own
dispatch and sync beside its work and mislead; see git history of this
file).

Output: per-op device ms/epoch for the top ops, tagged with what each op
is (gather / scatter-apply / plan sort / cummax / bookkeeping), plus the
roofline summary BASELINE.md quotes:

* the epoch is RANDOM-ACCESS bound: the read gather and the winner
  scatter-apply are per-index limited (~7.1 / ~4.9 ns per lane on v5e —
  XLA's TPU gather/scatter primitive rate, invariant across 9 tested
  formulations: 1D/2D-row layouts, sorted/unique hints, OOB-drop
  steering, one-hot-matmul hot paths, compaction via second sorts), and
* the sum of the irreducible primitives (gather + scatter + plan sort)
  is reported as a fraction of the epoch — the "% of primitive roofline"
  figure.  The absolute HBM roofline (two 655k-lane passes at 32 B
  transaction granularity = ~42 MB = ~51 us at 819 GB/s) is ~150x away
  and unreachable without per-lane dynamic addressing, which neither XLA
  nor Mosaic/Pallas exposes on v5e.

Usage:
    python tools/roofline.py [--full-row] [--eb 65536] [--epochs 20]
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# op-name prefix -> phase attribution at the headline shape (v5e HLO);
# anything unmatched lands in "bookkeeping/other"
def classify(name: str, big: dict) -> str:
    if name.startswith("sort."):
        return "plan sort (key,rank,w)"
    if name.startswith("reduce-window"):
        return "mono-scatter cummax"
    if name.startswith("fusion."):
        # the two dominant fusions are the RA passes: larger = gather
        # (it also folds the forwarded-value where + checksum), smaller =
        # scatter apply.  Identified by rank among fusions, checked
        # against metadata when present.
        return big.get(name, "bookkeeping/other")
    return "bookkeeping/other"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full-row", action="store_true")
    ap.add_argument("--eb", type=int, default=65536)
    ap.add_argument("--epochs", type=int, default=20)
    args = ap.parse_args()

    import jax

    from deneva_tpu.config import Config
    from deneva_tpu.engine.step import Engine
    from deneva_tpu.workloads import get_workload

    eb = args.eb
    table = (1 << 21) if args.full_row else (1 << 23)
    over = ["--sim_full_row=true"] if args.full_row else []
    cfg = Config.from_args([
        "--workload=YCSB", "--cc_alg=TPU_BATCH", "--zipf_theta=0.9",
        "--read_perc=0.5", "--write_perc=0.5", "--req_per_query=10",
        "--max_accesses=16", f"--synth_table_size={table}",
        f"--epoch_batch={eb}", f"--max_txn_in_flight={eb}",
    ] + over)
    wl = get_workload(cfg)
    eng = Engine(cfg, wl)
    state = eng.init_state()
    n = args.epochs
    run = eng.jit_run
    state = run(state, n)
    jax.block_until_ready(state.stats["total_txn_commit_cnt"])

    tmp = tempfile.mkdtemp(prefix="roofline_")
    with jax.profiler.trace(tmp):
        state = run(state, n)
        jax.block_until_ready(state.stats["total_txn_commit_cnt"])

    path = sorted(glob.glob(os.path.join(
        tmp, "plugins/profile/*/*.trace.json.gz")))[-1]
    with gzip.open(path) as f:
        tr = json.load(f)
    pids = {}
    for e in tr["traceEvents"]:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pids[e["pid"]] = e["args"].get("name", "")
    by = collections.Counter()
    for e in tr["traceEvents"]:
        if e.get("ph") == "X" and "TPU" in pids.get(e["pid"], ""):
            nm = e["name"]
            if nm.startswith(("jit_", "while")):
                by["__total__"] = max(by["__total__"], e.get("dur", 0))
                continue
            by[nm] += e.get("dur", 0)

    if "__total__" not in by or by["__total__"] == 0:
        sys.exit("roofline: no top-level jit_/while event found on the "
                 "TPU trace track — profiler naming changed? inspect "
                 f"{path} by hand")
    total = by.pop("__total__") / n / 1000
    # label the two biggest fusions as the RA passes
    fus = sorted(((d, nm) for nm, d in by.items()
                  if nm.startswith("fusion.")), reverse=True)
    big = {}
    if len(fus) >= 2 and fus[1][0] / n / 1000 > 0.2 * total:
        big[fus[0][1]] = "exec read gather (+fwd where +checksum)"
        big[fus[1][1]] = "exec winner scatter apply"
    else:
        print("WARNING: fusion-labeling heuristic failed at this shape "
              "(the two RA passes were not the two dominant fusions); "
              "per-index rates below are NOT computed", file=sys.stderr)

    mode = "full-row" if args.full_row else "fingerprint"
    print(f"# roofline ledger: eb={eb} x {cfg.req_per_query} req = "
          f"{eb * cfg.req_per_query} lanes, table {table} rows, {mode}")
    print(f"device epoch: {total:.3f} ms -> "
          f"{eb / total * 1000 / 1e6:.2f}M txn/s (device-bound)\n")
    phases = collections.Counter()
    for nm, d in by.items():
        phases[classify(nm, big)] += d
    print(f"{'phase':<42}{'ms/epoch':>9}{'% epoch':>9}")
    for ph, d in phases.most_common():
        ms = d / n / 1000
        print(f"{ph:<42}{ms:>9.3f}{100 * ms / total:>8.1f}%")
    if not big:
        return
    lanes = eb * cfg.req_per_query
    g = next((d for nm, d in by.items()
              if big.get(nm, "").startswith("exec read")), 0) / n / 1000
    s = next((d for nm, d in by.items()
              if big.get(nm, "").startswith("exec winner")), 0) / n / 1000
    srt = sum(d for nm, d in by.items()
              if nm.startswith("sort.")) / n / 1000
    prim = g + s + srt
    print(f"\nper-index rates: gather {g * 1e6 / lanes:.1f} ns/lane, "
          f"scatter {s * 1e6 / lanes:.1f} ns/lane "
          f"({lanes} lanes)")
    print(f"irreducible primitives (gather+scatter+sort): {prim:.3f} ms "
          f"= {100 * prim / total:.0f}% of epoch "
          f"(residue {total - prim:.3f} ms bookkeeping)")


if __name__ == "__main__":
    main()
