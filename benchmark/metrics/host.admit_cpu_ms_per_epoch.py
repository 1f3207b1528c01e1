"""CPU milliseconds per epoch of the dispatch thread assembling
contributions (admission, its inner drains included): the window's
`stage_admit_cpu_time` of the server's stage clock
(`deneva_tpu/runtime/stages.py`) over the window's epochs — the CPU
twin of `host.admit_ms_per_epoch`, which charges wall and so books as
admission whatever kept the thread off the CPU meanwhile.  What taking
admission off the dispatch thread can save is THIS number, UNTRACED
(ROADMAP S7).

Where a program prints no CPU reading, its CPU is taken as its wall
(CPU <= wall always): the metric then reads `host.admit_ms_per_epoch`,
an upper bound.  No measured window (`stage_epoch_cnt` absent or 0):
None.  (`benchmark/stage_cpu.py` is that rule.)

The benchmark reads it in TRACED runs, where it is about twice an
untraced run's: `benchmark/stage_cpu.py` says what it means there."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from stage_cpu import cpu_ms_per_epoch  # noqa: E402


def read(ctx):
    return cpu_ms_per_epoch(ctx, ("admit",))
