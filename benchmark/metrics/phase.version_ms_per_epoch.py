"""Device milliseconds per epoch in the version ring — MVCC's gather of
the `mvcc_his_len` overwrite timestamps of every lane's row
(`storage/table.VersionRing.rows`), the select of the version a read's
timestamp is owed with the old bytes' value law (`version_from`), and
the push of the epoch's committed overwrites (`push_rows`: a scatter
into `int32[rows x H]`, which on the chip copies the array): self time
of the ops whose INNERMOST scope is `ep.version` inside the group
programs that ran whole in the traced window, over their epochs
(`benchmark/phase_reduce.py`, `scope_s`).  The scope sits inside
`ep.read` and `ep.write` of `workloads/ycsb.py`, and an op belongs to
its innermost scope: in this cell `phase.read_ms_per_epoch` and
`phase.write_ms_per_epoch` read WITHOUT the ring, and this metric is no
phase of `phase_reduce.PHASES` but a part of `phase.other_ms_per_epoch`.
A program without the scope (the parent): None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from phase_reduce import cached  # noqa: E402


def read(ctx):
    r = cached(ctx)
    secs = (r.get("scope_s") or {}).get("ep.version")
    if secs is None or not r.get("epochs"):
        return None
    return 1e3 * secs / r["epochs"]
