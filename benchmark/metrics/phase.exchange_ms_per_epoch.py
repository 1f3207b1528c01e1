"""Device milliseconds per epoch in what a mesh ADDS to the epoch — the
slice cuts, the capacity-defer pass, the owner sort, the block cuts, the
three `all_to_all`s, the `all_gather` of the defer bits and the `psum`s
of `YCSBWorkload.execute_mc`: self time of the ops under `ep.exchange`
inside the group programs that ran whole in the traced window, a chip's
mean, over their epochs (`benchmark/phase_reduce.py`, `scope_s`).

`ep.exchange` is no phase of `phase_reduce.PHASES`, so this time is also
a PART of `phase.other_ms_per_epoch` in a cell that has it.  A program
without the scope (one chip, or a tree from before it): None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from phase_reduce import cached  # noqa: E402


def read(ctx):
    r = cached(ctx)
    secs = (r.get("scope_s") or {}).get("ep.exchange")
    if secs is None or not r.get("epochs"):
        return None
    return 1e3 * secs / r["epochs"]
