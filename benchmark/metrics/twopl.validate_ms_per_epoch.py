"""Device milliseconds per epoch deciding an epoch under two-phase
locking (`cc/twopl.validate_no_wait` / `validate_wait_die`): the [B, B]
compare of the exact keys (`ops.conflict.key_overlap`: every access
against the writes, symmetrized — read-write in both directions and
write-write), `earlier_edges` by rank, `greedy_first_fit`'s
`sweep_rounds` rounds of two matvecs (the lock table, lane after lane in
rank order), and under WAIT_DIE the age test (the least birth timestamp
over a loser's owners, a masked [B, B] min): self time of the ops under
`ep.validate` inside the group programs that ran whole in the traced
window, over their epochs (`benchmark/phase_reduce.py`; the same number
`phase.validate_ms_per_epoch` reads in the cell it lists).  A phase of
`phase_reduce.PHASES`: beside plan, read, write and other it adds up to
`group.device_ms_per_epoch`."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from phase_reduce import phase_ms_per_epoch  # noqa: E402


def read(ctx):
    return phase_ms_per_epoch(ctx, "validate")
