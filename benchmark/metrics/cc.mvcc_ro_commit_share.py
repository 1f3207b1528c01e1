"""The share of the window's commits that were read-only transactions
taking MVCC's fast path — serialized at the snapshot their epoch began
with, never aborted, never waiting (`mvcc_ro_commit_cnt` of
`cc/timestamp.validate_mvcc` over `total_txn_commit_cnt`, in percent).
Over the traffic's share of read-only transactions (50%) it says how
much of the throughput the read-write half costs.  A program that counts
none (the parent): None."""


def read(ctx):
    s = ctx["server"]["summary"]
    if "mvcc_ro_commit_cnt" not in s or not s.get("total_txn_commit_cnt"):
        return None
    return 100.0 * s["mvcc_ro_commit_cnt"] / s["total_txn_commit_cnt"]
