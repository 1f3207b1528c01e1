"""The one process that holds the chip: the program's server node.

    python benchmark/server_child.py <spec.json>

Does what `deneva_tpu.runtime.launch._node_main("server", ...)` does —
pin the platform, build `ServerNode`, run it, close it — in a process
the benchmark starts itself, and adds the two readings that only the
chip-holding process can take and the launcher does not print:

* the device's memory (`memory_stats()` of the fullest local device)
  after the run: the peak, which is the loader's, and what is still in
  use when the serve loop has ended (table and group buffers), and
* with ``spec["trace"]``, a device trace of a steady stretch of the
  measured window: a timer thread calls `jax.profiler.start_trace` /
  `stop_trace` (there is no profiler hook inside the program).  The
  thread takes the start barrier's time from the file client 0 writes.

Prints the launcher CLI's own closing lines — ``[device] node=0 {json}``
and ``node 0 (server): [summary] ...`` — plus ``[memory] {json}`` and,
traced, ``[trace] {json}``.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def trace_window(tr: dict, barrier_file: str, out: dict,
                 stop: threading.Event) -> None:
    """Timer thread: wait for the barrier time, sleep to ``start_s``
    after it, trace ``len_s`` seconds into ``tr["dir"]``."""
    import jax
    while not os.path.exists(barrier_file):
        if stop.wait(0.01):
            return
    with open(barrier_file) as f:
        t0 = int(f.read()) * 1e-9           # CLOCK_MONOTONIC, shared
    if stop.wait(max(0.0, t0 + tr["start_s"] - time.monotonic())):
        return
    jax.profiler.start_trace(tr["dir"])
    t_a = time.monotonic()
    stop.wait(tr["len_s"])
    t_b = time.monotonic()
    jax.profiler.stop_trace()
    out.update(window_s=t_b - t_a, started_at_s=t_a - t0,
                stop_cost_s=time.monotonic() - t_b)


def main(argv: list[str]) -> int:
    with open(argv[0]) as f:
        spec = json.load(f)
    sys.path.insert(0, ROOT)
    platform = spec["platform"]
    # before jax is imported, never setdefault (launch._node_main)
    from deneva_tpu.runtime.jaxenv import device_line, pin_platform
    pin_platform(platform)
    from deneva_tpu.config import Config
    from deneva_tpu.runtime.server import ServerNode
    cfg = Config.from_args([f"--{k}={v}" for k, v in
                            spec["fields"].items()]
                           ).replace(node_id=0, part_cnt=1)
    node = ServerNode(cfg, spec["endpoints"], platform,
                      spec["setup_wait_s"])
    traced: dict = {}
    stop = threading.Event()
    th = None
    try:
        if spec.get("trace"):
            th = threading.Thread(
                target=trace_window, daemon=True,
                args=(spec["trace"], spec["barrier_file"], traced, stop))
            th.start()
        st = node.run()
        stop.set()
        if th is not None:
            th.join(timeout=120)
        import jax
        peak = in_use = limit = None
        for d in jax.local_devices():
            ms = d.memory_stats() or {}
            if ms.get("peak_bytes_in_use") is not None:
                peak = max(peak or 0, int(ms["peak_bytes_in_use"]))
                in_use = max(in_use or 0, int(ms.get("bytes_in_use", 0)))
                limit = int(ms.get("bytes_limit", 0)) or limit
        print(device_line(0, node.info), flush=True)
        print(f"node 0 (server): {st.summary_line()}", flush=True)
        print("[memory] " + json.dumps(
            {"memory_peak_bytes": peak, "memory_in_use_bytes": in_use,
             "bytes_limit": limit}), flush=True)
        if spec.get("trace"):
            print("[trace] " + json.dumps(traced), flush=True)
    finally:
        stop.set()
        node.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
