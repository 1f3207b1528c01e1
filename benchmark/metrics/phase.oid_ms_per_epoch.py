"""Device milliseconds per epoch allocating O_IDs — NewOrder's
per-district segmented prefix sum (two `argsort`s, a sort and a scan an
execute pass, `workloads/tpcc._exec_neworder`): self time of the ops
under `ep.oid` inside the group programs that ran whole in the traced
window, over their epochs (`benchmark/phase_reduce.py`, `scope_s`).  No
phase of `phase_reduce.PHASES`: a PART of `phase.other_ms_per_epoch`.  A
program without the scope: None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from phase_reduce import cached  # noqa: E402


def read(ctx):
    r = cached(ctx)
    secs = (r.get("scope_s") or {}).get("ep.oid")
    if secs is None or not r.get("epochs"):
        return None
    return 1e3 * secs / r["epochs"]
