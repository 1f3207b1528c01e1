"""The benchmark's server child with a profiler whose `stop_trace` never
returns, around a node that serves nothing: `server_child.main` runs as
it is, waits for the trace thread as long as the run's limit allows and
fails by name.  Used by test_bench_rehearsal.py to see a traced launch
fail loudly instead of returning without its device metrics."""

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

if __name__ == "__main__":
    import jax
    import server_child
    from deneva_tpu.runtime import server

    with open(sys.argv[1]) as f:
        spec = json.load(f)
    barrier_file = spec["barrier_file"]
    # give up 5 s after this child started, not 10 s before a limit of
    # many minutes (the test keeps the parent's kill well beyond that)
    server_child.STOP_TRACE_MARGIN_S = spec["setup_wait_s"] - 5.0
    opened = threading.Event()

    class Node:
        """No client ever passes a barrier with it: it writes the
        barrier's time itself, long enough ago for the traced window to
        open at once, and its serve loop ends when the trace is open."""
        info: dict = {}

        def __init__(self, *args):
            pass

        def run(self):
            with open(barrier_file, "w") as f:
                f.write(str(time.monotonic_ns() - 3_600_000_000_000))
            opened.wait(30)

        def close(self):
            pass

    server.ServerNode = Node
    jax.profiler.start_trace = lambda *a, **kw: opened.set()
    jax.profiler.stop_trace = threading.Event().wait
    sys.exit(server_child.main(sys.argv[1:]))
