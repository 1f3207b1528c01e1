"""The PPS epoch against the chip's memory roofline: the bytes the
algorithm needs for the window's committed look-ups, orders and mapping
updates (`pps_epoch_bytes`) over the bytes the chip could move in the
device time their epochs took.  The program is latency-bound, not
memory-bound — 1.5 MB of tables, gathers and adds of 4-108 B a lane, a
[B, B] compare, and level passes that run the whole batch again — so the
share reads small: it is the yardstick, not a target.  One chip: the
server's window counters (`pps_lookup_commit_cnt`, `pps_order_commit_cnt`,
`pps_update_commit_cnt`) over `stage_epoch_cnt`, against a traced
epoch's device time.  Nothing without a trace or the counters."""

NUMBER = 4          # the program's int32 for the schema's int64_t
STRINGS = 100       # ten strings of 10 B
MAPPING_ROW = 2 * NUMBER            # (anchor key, PART_KEY)
PART_ROW = 2 * NUMBER + STRINGS     # PART_KEY, PART_AMOUNT, the strings


def pps_epoch_bytes(lookups: float, orders: float, updates: float,
                    per: int = 10) -> float:
    """Bytes of table traffic the algorithm NEEDS (numbers are the
    program's 4 B, strings their schema widths), with ``per`` parts an
    anchor:

    * a look-up (GETPARTBYPRODUCT / GETPARTBYSUPPLIER) reads its
      anchor's ``per`` mapping rows of 8 B and the ``per`` part rows they
      name, whole: 108 B each;
    * an order (ORDERPRODUCT) reads the same mapping rows and reads and
      writes PART_AMOUNT of each part: 2 x 4 B;
    * an update (UPDATEPRODUCTPART) writes PART_KEY of one mapping row.

    The anchor row of a walk is declared to concurrency control and not
    read by the program: not counted.  Plans, the second gather of the
    mapping at execution, the conflict matrix and the level passes are
    overhead, not needed traffic — they lower the share."""
    return (lookups * per * (MAPPING_ROW + PART_ROW)
            + orders * per * (MAPPING_ROW + 2 * NUMBER)
            + updates * NUMBER)


def read(ctx):
    t, s, info = ctx["trace"], ctx["server"]["summary"], ctx["server"]["info"]
    keys = ("pps_lookup_commit_cnt", "pps_order_commit_cnt",
            "pps_update_commit_cnt")
    if not t or not t.get("epochs") or not s.get("stage_epoch_cnt") \
            or any(k not in s for k in keys):
        return None
    need = pps_epoch_bytes(*(s[k] for k in keys),
                           per=int(ctx["fields"].get("pps_parts_per", 10))) \
        / s["stage_epoch_cnt"]
    peak = ctx["peaks"].peak_for(info["kind"])
    return 100.0 * need / (t["group_busy_s"] / t["epochs"]
                           * peak["hbm_bytes_per_s"])
