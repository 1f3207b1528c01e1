"""The MVCC epoch against the chip's memory roofline: the bytes the
algorithm needs for the window's committed reads and writes
(`mvcc_epoch_bytes`) over the bytes the chip could move in the device
time their epochs took.  One chip: the server's window counters
(`total_txn_commit_cnt` x `req_per_query` lanes, `write_cnt` of them
writes) over `stage_epoch_cnt`, against a traced epoch's device time.
The program is latency-bound — gathers and scatters of 40-104 B a lane
against 6.5 GB, a [B, B] compare, a dense pass over the boundary ring —
so the share reads small: it is the yardstick, not a target.  Nothing
without a trace or the counters."""

TS = 4              # a timestamp of the ring: int32


def mvcc_epoch_bytes(reads: float, writes: float, row_bytes: int = 100,
                     his_len: int = 10) -> float:
    """Bytes of table traffic the algorithm NEEDS:

    * a committed read moves its field (``row_bytes`` of F0) and its
      row's ring of ``his_len`` timestamps, which says WHICH version the
      field's bytes are owed from;
    * a committed write moves its field, reads the row's ring (to find
      the oldest entry) and writes one timestamp into it.

    The whole-array copy a scatter into the ring or the table costs on
    the chip, the watermark tables, the boundary ring's dense pass, the
    conflict matrix and the lanes of transactions that waited or aborted
    are overhead, not needed traffic — they lower the share."""
    return (reads * (row_bytes + TS * his_len)
            + writes * (row_bytes + TS * his_len + TS))


def read(ctx):
    t, s, info = ctx["trace"], ctx["server"]["summary"], ctx["server"]["info"]
    f = ctx["fields"]
    if not t or not t.get("epochs") or not s.get("stage_epoch_cnt") \
            or "write_cnt" not in s or "mvcc_his_len" not in f:
        return None
    lanes = s["total_txn_commit_cnt"] * int(f["req_per_query"])
    need = mvcc_epoch_bytes(lanes - s["write_cnt"], s["write_cnt"],
                            int(f.get("tup_size", 100)),
                            int(f["mvcc_his_len"])) / s["stage_epoch_cnt"]
    peak = ctx["peaks"].peak_for(info["kind"])
    return 100.0 * need / (t["group_busy_s"] / t["epochs"]
                           * peak["hbm_bytes_per_s"])
