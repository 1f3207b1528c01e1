"""What the server's dispatch thread COSTS an epoch: its CPU milliseconds
over the measured window — the sum of `stage_<stage>_cpu_time` of the
eight stages of its stage clock (`deneva_tpu/runtime/stages.py`, which
reads `time.thread_time()` at every boundary) — over the window's
epochs.  Unlike the wall the clock charges (`host.busy_share`,
`host.admit_ms_per_epoch`), it holds only what the thread itself ran:
what another thread holding the interpreter, the scheduler or a blocked
call take from the thread is wall, not CPU.  If an epoch's device time
is under it, the host binds.

Where a program prints no CPU reading, its CPU is taken as its wall
(CPU <= wall always): the metric then reads the dispatch thread's wall
an epoch, an upper bound.  No measured window (`stage_epoch_cnt` absent
or 0): None.  (`benchmark/stage_cpu.py` is that rule.)

The benchmark reads it in TRACED runs, where it is about twice an
untraced run's: `benchmark/stage_cpu.py` says what it means there."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from stage_cpu import cpu_ms_per_epoch  # noqa: E402


def read(ctx):
    return cpu_ms_per_epoch(ctx)
