"""Bytes the server's transport sent and received per committed
transaction (both counters cover the whole run)."""


def read(ctx):
    s, info = ctx["server"]["summary"], ctx["server"]["info"]
    if "net_bytes_sent" not in s or not info.get("run_commit_cnt"):
        return None
    return (s["net_bytes_sent"] + s["net_bytes_rcvd"]) / info["run_commit_cnt"]
