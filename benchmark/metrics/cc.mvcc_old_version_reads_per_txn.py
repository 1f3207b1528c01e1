"""Reads served a version other than the live one, per committed
transaction of the server's window: `mvcc_old_version_read_cnt` (read
lanes of committed read-write transactions whose row's ring held an
overwrite newer than their timestamp, so their bytes came from the
value law at an older one, `workloads/ycsb.YCSBWorkload.execute`) over
`total_txn_commit_cnt`.  The mechanism's own rate: 0 would mean the ring
is carried and never read from.  A program that counts none (the
parent): None."""


def read(ctx):
    s = ctx["server"]["summary"]
    if "mvcc_old_version_read_cnt" not in s \
            or not s.get("total_txn_commit_cnt"):
        return None
    return s["mvcc_old_version_read_cnt"] / s["total_txn_commit_cnt"]
