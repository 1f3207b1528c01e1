"""Passes of the chained levels' loop per epoch of the measured window:
the server's `level_pass_cnt` (a device counter: one a pass of
`engine/epoch.run_levels`' `lax.while_loop`, each a whole-batch execute)
over the window's epochs (`stage_epoch_cnt`).  1 = no transaction of an
epoch waited for another; the deepest is `exec_subrounds`.  A program
that counts no passes (the parent; a forwarding or sweep backend):
None."""


def read(ctx):
    s = ctx["server"]["summary"]
    if not s.get("stage_epoch_cnt") or "level_pass_cnt" not in s:
        return None
    return s["level_pass_cnt"] / s["stage_epoch_cnt"]
