"""Device milliseconds per epoch deciding an epoch under MVCC
(`cc/timestamp.validate_mvcc`): the gathers of the per-bucket watermarks
and of the retention floor (the least retained boundary, the newest
lossy one) for every lane, the [B, B] compare of the exact keys
(`ops.conflict.key_overlap`, readers against writers), `earlier_edges`
by timestamp, `greedy_first_fit`'s rounds, and the commit's scatters
into the watermark tables and the boundary ring (`int32[K x H]`, a dense
pass): self time of the ops under `ep.validate` inside the group
programs that ran whole in the traced window, over their epochs
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
