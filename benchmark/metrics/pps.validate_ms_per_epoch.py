"""Device milliseconds per epoch ordering an epoch of PPS — the [B, B]
matrix of `ops.conflict.key_overlap` over 21 accesses a transaction (the
anchor, ten mapping rows, ten part rows: TPC-C's compare runs over 18,
the OCC cell's over 10), `earlier_edges` and `wavefront_levels` of
`cc/calvin.validate_calvin`, whose rounds are as many as
`exec_subrounds`: self time of the ops under `ep.validate` inside the
group programs that ran whole in the traced window, over their epochs
(`benchmark/phase_reduce.py`; the same number
`phase.validate_ms_per_epoch` reads in the cell it lists).  A phase of
`phase_reduce.PHASES`: beside plan, read, write and other it adds up to
`group.device_ms_per_epoch`."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from phase_reduce import phase_ms_per_epoch  # noqa: E402


def read(ctx):
    return phase_ms_per_epoch(ctx, "validate")
