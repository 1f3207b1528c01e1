"""How far the host runs behind the device: from the end of a group
program on the device to the start of that group's `srv.retire` on the
host, MEDIAN over the whole group executions of the traced window
(`benchmark/phase_reduce.py`; its JSON keeps mean and max too).

Its floor is the offset between the two planes' clocks: in the chip
traces of PR 25 a program starts on the device plane 0.8-2.5 ms BEFORE
its `DoEnqueueProgram` on the host plane, so the device's stamps are
that early and every lag reads that much long: a host that retired the
instant the device finished would still read 0.8-2.5 ms."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from phase_reduce import cached  # noqa: E402


def read(ctx):
    lag = cached(ctx).get("lag")
    return 1e3 * lag["median_s"] if lag else None
