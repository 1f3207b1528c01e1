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
  The Python tracer is off: no metric reads its events, and while it
  ran a host-bound server fed its device half as often; the program's
  `srv.<stage>` spans are `TraceAnnotation`s, which the HOST tracer
  records.  `stop_trace` has the rest of the run's own limit
  (``spec["setup_wait_s"]``, the parent's kill time), not a budget of
  its own: it takes ~0.11 ms a device op event, so a faster program
  needs longer, and a traced run that cannot return its window fails
  by name ("stop_trace: no end after N s"), never silently.

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
# the child gives up this long before the parent would kill it, so that
# the failure it reports is its own, named one
STOP_TRACE_MARGIN_S = 10.0


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
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tr["dir"], profiler_options=opts)
    t_a = time.monotonic()
    stop.wait(tr["len_s"])
    t_b = time.monotonic()
    jax.profiler.stop_trace()
    out.update(window_s=t_b - t_a, started_at_s=t_a - t0,
                stop_cost_s=time.monotonic() - t_b)


def join_trace(th: threading.Thread, traced: dict, limit_s: float) -> None:
    """Wait for the trace thread for what is left of the run's own
    limit; a thread that has not handed back its window by then fails
    the run by name."""
    limit_s = max(0.0, limit_s)
    th.join(timeout=limit_s)
    if th.is_alive():
        raise RuntimeError(f"stop_trace: no end after {limit_s:.0f} s past "
                           "the serve loop")
    if "window_s" not in traced:
        raise RuntimeError("the serve loop ended before the traced window "
                           "opened: nothing was traced")


def main(argv: list[str]) -> int:
    t_start = time.monotonic()
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
            join_trace(th, traced, t_start + spec["setup_wait_s"]
                       - STOP_TRACE_MARGIN_S - time.monotonic())
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
