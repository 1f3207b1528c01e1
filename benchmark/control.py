#!/usr/bin/env python3
"""The control of `correct`: the comparison must FAIL when a guarantee breaks.

    python benchmark/control.py --workload <cell> --seeds <n,n,...>

Not part of a benchmark run.  Per seed: the cell's verify launch at its
own size on the chip, then the reference's comparison three ways —

* sound: as a run makes it; every number within its limit;
* lost write: the reference is told that the last committed write of the
  log never reached the table (its row keeps the load value) — the
  guarantee "state equals the serial execution of the admitted stream"
  is broken by one row, and `digest_mismatch` must say so;
* illegal verdict (validating backends): in every epoch that aborted
  anything, every admitted transaction is taken as committed;
  `occ_rule_violations` or `digest_mismatch` must say so.

Prints one JSON line per seed and exits non-zero unless every sound
comparison passed and every control failed.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv: list[str], run=None, cell=None) -> int:
    """``run`` / ``cell``: the test's handles (run.py loaded with the
    server steered to the CPU, a toy-sized cell)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    if run is None:
        spec = importlib.util.spec_from_file_location(
            "bench_run", os.path.join(HERE, "run.py"))
        run = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(run)
    import numpy as np
    cell = cell or run.load_cell(args.workload)
    ref = run.load_by_name("references", cell["config_file"]["reference"])
    all_ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        run_dir = tempfile.mkdtemp(prefix="dbc")
        try:
            res, fields, log, verdicts = run.logged_launch(cell, seed,
                                                           run_dir)
            info = res["server"]["info"]

            def failed(**kw):
                checks, notes = ref.verify(log, fields, info, **kw)
                return sorted(n for n, v, lim in checks if v > lim), notes
            sound, notes = failed(verdicts=verdicts)
            # the last committed write lane of the log
            last_key = None
            for epoch, keys, types, active in ref.read_log(log):
                commit = active if verdicts is None else verdicts[epoch]
                lanes = np.flatnonzero(
                    (commit[:, None] & (types == ref.WRITE)).ravel())
                if len(lanes):
                    last_key = int(keys.ravel()[lanes[-1]])
            out = dict(seed=seed, platform=info["platform"],
                       epochs=notes["epochs"], sound_failed=sound,
                       lost_write_failed=failed(verdicts=verdicts,
                                                drop_key=last_key)[0])
            ok = not sound and "digest_mismatch" in out["lost_write_failed"]
            if verdicts is not None:
                forced = dict(verdicts)
                for epoch, _k, _t, active in ref.read_log(log):
                    if (active & ~verdicts[epoch]).any():
                        forced[epoch] = active
                out["illegal_verdict_failed"] = failed(verdicts=forced)[0] \
                    if any(forced[e] is not verdicts[e] for e in forced) \
                    else ["no epoch aborted anything"]
                ok = ok and out["illegal_verdict_failed"] != []
            out["control_ok"] = ok
            all_ok = all_ok and ok
            print(json.dumps(out), flush=True)
        finally:
            shutil.rmtree(run_dir, ignore_errors=True)
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
