"""Device milliseconds per epoch in the executor's row gather: self time
of the ops under `ep.read` inside the group programs that ran whole in
the traced window, over their epochs (`benchmark/phase_reduce.py`)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from phase_reduce import phase_ms_per_epoch  # noqa: E402


def read(ctx):
    return phase_ms_per_epoch(ctx, "read")
