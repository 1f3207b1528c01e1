"""The epoch group program against the chip's memory roofline: bytes the
algorithm needs per epoch (`peaks.ycsb_epoch_bytes`: committed accesses x
row bytes) over the bytes the chip could move in the device time an epoch
took.  The program is memory-bound (gathers and scatters of 100 B rows);
empty lanes, plans and sorts are overhead and lower the share.

A chip's bytes over a chip's time over a chip's peak: the commits are
the CLUSTER's and the device time a chip's mean, so over a mesh the
needed bytes are divided by the chips that moved them — `mesh_shards` of
the server's summary, as in `exchange_ici_roofline`; a server that
prints none ran on one.  The bytes are YCSB's (`req_per_query` x
`tup_size` of the configuration's `fields`), so the entry in
`BENCHMARK.json` lists the cells whose algorithm that describes; another
schema brings a roofline reader and a bytes function of its own."""


def read(ctx):
    t, s, info = ctx["trace"], ctx["server"]["summary"], ctx["server"]["info"]
    if not t or not t.get("epochs") or not s.get("epoch_cnt"):
        return None
    peak = ctx["peaks"].peak_for(info["kind"])
    f = ctx["fields"]
    need = ctx["peaks"].ycsb_epoch_bytes(
        info["run_commit_cnt"] / s["epoch_cnt"], int(f["req_per_query"]),
        int(f["tup_size"])) / (s.get("mesh_shards") or 1)
    return 100.0 * need / (t["group_busy_s"] / t["epochs"]
                           * peak["hbm_bytes_per_s"])
