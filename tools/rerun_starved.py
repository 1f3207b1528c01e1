"""Re-measure sweep points whose first pass was starved by host-side CPU
contention (epochs/sec collapsed; flagged by the epoch_cnt/total_runtime
scan).  Must run on a quiet machine — the measurement is sensitive to
host pacing."""

from __future__ import annotations

import sys

sys.path.insert(0, "/root/repo")

from deneva_tpu.config import CCAlg  # noqa: E402
from deneva_tpu.harness.experiments import (ALL_ALGS, get_experiment,  # noqa: E402
                                            paper_base)
from deneva_tpu.harness.run import run_point  # noqa: E402


def bench(cfgs):
    return [c.replace(warmup_secs=1.5, done_secs=4.0) for c in cfgs]


def main() -> int:
    base = paper_base(False)
    jobs = []
    # ycsb_skew: every alg at theta 0.6 and 0.9, plus TPU_BATCH at 0.3
    skew = [base.replace(zipf_theta=t, cc_alg=CCAlg(a))
            for t in (0.6, 0.9) for a in ALL_ALGS]
    skew.append(base.replace(zipf_theta=0.3, cc_alg=CCAlg.TPU_BATCH))
    jobs.append(("ycsb_skew", bench(skew)))
    op = base.replace(zipf_theta=0.9)
    jobs.append(("operating_points", bench(
        [op.replace(cc_alg=CCAlg.MAAT, epoch_batch=8192),
         op.replace(cc_alg=CCAlg.MVCC, epoch_batch=8192)])))
    jobs.append(("isolation_levels", bench(
        [c for c in get_experiment("isolation_levels", quick=False)
         if c.isolation_level == "SERIALIZABLE"])))
    for name, cfgs in jobs:
        print(f"[{name}] rerun {len(cfgs)} points", flush=True)
        for cfg in cfgs:
            run_point(cfg, f"results/{name}", quiet=False)
    print("RERUN_STARVED_DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
