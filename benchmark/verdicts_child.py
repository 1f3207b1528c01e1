"""A validating backend's per-epoch verdicts, replayed from the command log.

    python benchmark/verdicts_child.py <spec.json>

The log of OCC (and of any backend that may abort) carries the admitted
stream but not who committed.  This child, on the platform the server
ran on (it starts once the server has left the chip; on the CPU backend
the B x B validation costs 0.1 s an epoch), re-executes the log through
the program's per-epoch step
(`runtime/logger.replay_into`) and writes each epoch's committed mask to
``spec["out"]`` (npz: epochs, packed bits, lane counts).  The masks are
NOT trusted: the plain reference holds them to the backend's rule on
exact keys, and a chip that decided otherwise than its own log replays
fails the digest.  ("Log the verdict mask" is listed in PERF.md for the
tracing issue: the reference then needs no program code at all.)
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list[str]) -> int:
    with open(argv[0]) as f:
        spec = json.load(f)
    sys.path.insert(0, ROOT)
    from deneva_tpu.runtime.jaxenv import init_jax
    init_jax(spec["platform"])
    import numpy as np

    from deneva_tpu.cc import get_backend
    from deneva_tpu.config import Config
    from deneva_tpu.engine.epoch import make_dist_step
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.runtime.logger import replay_into
    from deneva_tpu.workloads import get_workload

    cfg = Config.from_args([f"--{k}={v}" for k, v in spec["fields"].items()]
                           ).replace(node_id=0, part_cnt=1)
    wl, be = get_workload(cfg), get_backend(cfg.cc_alg)
    stats = init_device_stats(len(getattr(wl, "txn_type_names", ("txn",))))
    epochs, bits, lanes = [], [], []

    def on_epoch(epoch, block, active, done):
        epochs.append(epoch)
        bits.append(np.packbits(done.astype(bool)))
        lanes.append(len(done))

    replay_into(spec["log"], cfg, wl, make_dist_step(cfg, wl, be), wl.load(),
                be.init_state(cfg), stats, on_epoch=on_epoch)
    np.savez(spec["out"], epochs=np.asarray(epochs, np.int64),
             bits=np.stack(bits) if bits else np.zeros((0, 0), np.uint8),
             n=np.asarray(lanes, np.int64))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
