"""Device milliseconds per epoch in reconnaissance — PPS's mapping gather
in `PPSWorkload.plan` (the part keys of every walk, read from the epoch's
snapshot of USES / SUPPLIES) and the stale test of `cc/base.stale_recon`
(one [B, B] compare of ten mapping-row columns against the one column a
mapping write sits in, `earlier_edges`, a row reduction): self time of
the ops whose INNERMOST scope is `ep.recon` inside the group programs
that ran whole in the traced window, over their epochs
(`benchmark/phase_reduce.py`, `scope_s`).  No phase of
`phase_reduce.PHASES`: the gather sits under `ep.plan/ep.recon` and the
test outside any phase, and an op belongs to its innermost scope, so
both are a PART of `phase.other_ms_per_epoch` and none of
`phase.plan_ms_per_epoch`.  A program without the scope: None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from phase_reduce import cached  # noqa: E402


def read(ctx):
    r = cached(ctx)
    secs = (r.get("scope_s") or {}).get("ep.recon")
    if secs is None or not r.get("epochs"):
        return None
    return 1e3 * secs / r["epochs"]
