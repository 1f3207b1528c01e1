"""99th percentile of first send -> acknowledgement over every
transaction acked inside the window, all clients merged."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx["lat_ms"], 99)) if len(ctx["lat_ms"]) \
        else None
