"""The owner exchange against the chip's interconnect: the bytes ONE
chip sends to its peers in an epoch (`mesh_a2a_bytes` over the mesh's
chips) over what its ICI links could carry in the device time the
exchange took (`ep.exchange` seconds an epoch, a chip's mean:
`phase.exchange_ms_per_epoch`).  The scope also holds the exchange's
sorts, slice cuts and block cuts, so a share of a few percent says the
exchange pays for its sorts, not for its wires.

No mesh, no scope, or no lanes exchanged (the replicated plan): None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from phase_reduce import cached  # noqa: E402

# Google Cloud documentation, "TPU v5e" (system architecture): 1,600
# Gbit/s of inter-chip interconnect (ICI) bandwidth per chip.  Keyed by
# JAX's `device_kind`; a kind that is not here is an error, as in
# `benchmark/peaks.py` (which holds HBM's and is not this PR's to edit).
ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}


def read(ctx):
    s = ctx["server"]["summary"]
    chips, sent = s.get("mesh_shards"), s.get("mesh_a2a_bytes")
    if not chips or not sent:
        return None
    r = cached(ctx)
    secs = (r.get("scope_s") or {}).get("ep.exchange")
    if not secs or not r.get("epochs"):
        return None
    kind = ctx["server"]["info"]["kind"]
    if kind not in ICI_BYTES_PER_S:
        raise KeyError(f"device kind {kind!r} has no ICI peak in "
                       "benchmark/metrics/exchange_ici_roofline.py; add "
                       "its published peak with its source")
    return 100.0 * (sent / chips) / (
        secs / r["epochs"] * ICI_BYTES_PER_S[kind])
