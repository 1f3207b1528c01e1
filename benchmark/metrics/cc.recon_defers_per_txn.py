"""Stale-reconnaissance defers per committed transaction in the server's
window: `recon_defer_cnt` (lanes whose part keys came out of a mapping
row that an earlier-ranked lane of the same epoch rewrites,
`cc/base.stale_recon`: deferred whole, planned again from a later
epoch's snapshot — Calvin's restart) over `total_txn_commit_cnt`.  Near
0.10 with a third of the lanes rewriting one of 1,000 products and two
thirds walking one.  A program that counts none (no plan marks
reconnaissance; the parent): None."""


def read(ctx):
    s = ctx["server"]["summary"]
    if "recon_defer_cnt" not in s or not s.get("total_txn_commit_cnt"):
        return None
    return s["recon_defer_cnt"] / s["total_txn_commit_cnt"]
