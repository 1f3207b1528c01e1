"""Per cent of the measured window in which the server's dispatch thread
had WORK open and was not running: the sum over the six working stages
of its stage clock (`deneva_tpu/runtime/stages.py`: all but
`retire_wait` and `collect`, which block by design) of wall less CPU,
over `stage_wall_time`.  Untraced it is stalls, the interpreter lock
held by the server's other threads (the retire pool, the transport), the
scheduler.  Traced it is, besides, what `stop_trace` beside the serve
loop takes from the thread — little, on the chip: what the number
means in a traced run is in `benchmark/stage_cpu.py`.

Where a program prints no CPU reading, its CPU is taken as its wall
(CPU <= wall always), so the share reads 0.0: nothing is known to be
off the CPU.  No measured window (`stage_epoch_cnt` or
`stage_wall_time` absent or 0): None.  (`benchmark/stage_cpu.py` is
that rule.)"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from stage_cpu import offcpu_share  # noqa: E402


def read(ctx):
    return offcpu_share(ctx)
