"""The benchmark's server child with the timed path broken underneath:
the YCSB executor returns the table it was given, so every committed
write is lost while commits are still counted and acked.  Used by
test_bench_rehearsal.py to see `correct` come out false."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

if __name__ == "__main__":
    import server_child
    from deneva_tpu.workloads import ycsb

    def lost_writes(self, db, *args, **kwargs):
        return db

    ycsb.YCSBWorkload.execute = lost_writes
    sys.exit(server_child.main(sys.argv[1:]))
