"""Device milliseconds per epoch in the chained levels' own remainder — the
`lax.while_loop` of `engine/epoch.run_levels`, its condition and each
pass's masks: self time of the ops whose INNERMOST scope is `ep.levels`
(the gathers, scatters and the O_ID prefix sum inside a pass carry
`ep.read` / `ep.write` / `ep.oid`) inside the group programs that ran
whole in the traced window, over their epochs
(`benchmark/phase_reduce.py`, `scope_s`).  No phase of
`phase_reduce.PHASES`: a PART of `phase.other_ms_per_epoch`.  A program
without the scope: None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from phase_reduce import cached  # noqa: E402


def read(ctx):
    r = cached(ctx)
    secs = (r.get("scope_s") or {}).get("ep.levels")
    if secs is None or not r.get("epochs"):
        return None
    return 1e3 * secs / r["epochs"]
