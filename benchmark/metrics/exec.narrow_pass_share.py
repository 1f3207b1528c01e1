"""Share of the chained levels' passes that ran under the batch's width,
over the measured window: the server's `narrow_pass_cnt` (a device
counter: a pass of `engine/epoch.run_levels` that executed its level on
the narrowest static width holding it, its transactions moved to the
front in lane order) over `level_pass_cnt` (one a pass, whatever its
width).  0 = every pass handed its executor the whole batch; the nearer
1, the more of an epoch's passes cost their live transactions and not
the batch.  A program that counts its passes and none of them narrow
(the parent: every pass ran whole there) reads 0.0, and so does one
that runs no level pass at all (a forwarding or sweep backend: no pass
ran narrow) — the entry lists no cells, so every served cell reports
it.  No measured window (no `stage_epoch_cnt`): None."""


def read(ctx):
    s = ctx["server"]["summary"]
    if not s.get("stage_epoch_cnt"):
        return None
    passes = s.get("level_pass_cnt", 0.0)
    return s.get("narrow_pass_cnt", 0.0) / passes if passes else 0.0
