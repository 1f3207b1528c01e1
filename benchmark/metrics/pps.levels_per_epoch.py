"""Passes of the chained levels' loop per epoch of the measured window in
a PPS cell: the server's `level_pass_cnt` (a device counter: one a pass
of `engine/epoch.run_levels`' `lax.while_loop`, each a whole-batch
execute — the walk's two mapping row gathers, its gather of 11,264 part
rows and the adds of 11,264 lanes) over the window's epochs
(`stage_epoch_cnt`).  What chains here: a look-up's read of a part against an order's decrement of
it, a walk against a later-ranked rewrite of its product's mapping row.
1 = no transaction of an epoch waited for another; the deepest is
`exec_subrounds`.  A program that counts no passes: None."""


def read(ctx):
    s = ctx["server"]["summary"]
    if not s.get("stage_epoch_cnt") or "level_pass_cnt" not in s:
        return None
    return s["level_pass_cnt"] / s["stage_epoch_cnt"]
