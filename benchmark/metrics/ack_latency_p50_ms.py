"""Median of first send -> acknowledgement over every transaction acked
inside the window, all clients merged (millions of samples a run)."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx["lat_ms"], 50)) if len(ctx["lat_ms"]) \
        else None
