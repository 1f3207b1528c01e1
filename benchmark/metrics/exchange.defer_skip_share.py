"""Share of the window's shard-epochs in which the owner exchange did
NOT run its capacity-defer pass: 100 x (1 - `mc_defer_pass_cnt` /
(`stage_epoch_cnt` x `mesh_shards`)).  `mc_defer_pass_cnt` is a device
counter of `YCSBWorkload.execute_mc`: a shard counts the lanes of its
slice per owner with compares and runs the pass (two sorts of the
slice's lanes and a `cummax`) only where a real owner's count is over
its `pair_cap` block — elsewhere the pass's mask is all False and is
not computed.  100 = no shard's slice could overflow in any epoch of
the window (then `cc.defers_per_txn` reads 0.0 too); 0 = every shard
ran the pass in every epoch, which is what the parent's program does
without counting it.  A server that armed no mesh prints no
`mesh_shards`, the parent no `mc_defer_pass_cnt`, and a run without a
measured window no `stage_epoch_cnt`: None."""


def read(ctx):
    s = ctx["server"]["summary"]
    shard_epochs = s.get("stage_epoch_cnt", 0.0) * s.get("mesh_shards", 0.0)
    if "mc_defer_pass_cnt" not in s or not shard_epochs:
        return None
    return 100.0 * (1.0 - s["mc_defer_pass_cnt"] / shard_epochs)
