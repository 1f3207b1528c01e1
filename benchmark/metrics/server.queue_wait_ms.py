"""How long a transaction waits on the server before it is given an
epoch, by Little's law: the mean number waiting in the admission and
retry queues (`queue_txn_mean`, sampled at every group boundary) over
the window's commit rate."""


def read(ctx):
    s = ctx["server"]["summary"]
    if "queue_txn_mean" not in s or not s.get("total_runtime") \
            or not s.get("total_txn_commit_cnt"):
        return None
    return 1e3 * s["queue_txn_mean"] * s["total_runtime"] \
        / s["total_txn_commit_cnt"]
