"""The TPC-C epoch against the chip's memory roofline: the bytes the
algorithm needs for the window's committed Payments and NewOrders
(`tpcc_epoch_bytes`) over the bytes the chip could move in the device
time their epochs took.  The program is memory-bound (gathers, scatter
adds and appends of 4-60 B); the conflict matrix, the level passes that
run the whole batch again, sorts and plans are overhead and lower the
share.  One chip: the server's window counters (`tpcc_payment_commit_cnt`,
`tpcc_new_order_commit_cnt`, and the valid lines out of `write_cnt` = 6 a
Payment + 2 a NewOrder + 1 a line) over `stage_epoch_cnt`, against a
traced epoch's device time.  Nothing without a trace or the counters."""

PAYMENT_BYTES = 5 * 2 * 4 + 52
NEW_ORDER_BYTES = 3 * 4 + 2 * 4 + 32 + 12
LINE_BYTES = 4 + 4 * 2 * 4 + 24 + 60


def tpcc_epoch_bytes(payments: float, new_orders: float, lines: float
                     ) -> float:
    """Bytes of table traffic the algorithm NEEDS (numbers are the
    program's 4 B, strings their schema widths):

    * a Payment reads and writes five accumulators (W_YTD, D_YTD,
      C_BALANCE, C_YTD_PAYMENT, C_PAYMENT_CNT) and inserts one HISTORY
      row of 52 B;
    * a NewOrder reads W_TAX, D_TAX and C_DISCOUNT, reads and writes
      D_NEXT_O_ID, and inserts one ORDER row of 32 B and one NEW-ORDER
      row of 12 B;
    * a valid line reads I_PRICE, reads and writes the stock row's four
      counters (S_QUANTITY, S_YTD, S_ORDER_CNT, S_REMOTE_CNT), reads the
      24 B of its S_DIST_xx and inserts one ORDER-LINE row of 60 B."""
    return (payments * PAYMENT_BYTES + new_orders * NEW_ORDER_BYTES
            + lines * LINE_BYTES)


def read(ctx):
    t, s, info = ctx["trace"], ctx["server"]["summary"], ctx["server"]["info"]
    keys = ("tpcc_payment_commit_cnt", "tpcc_new_order_commit_cnt",
            "write_cnt")
    if not t or not t.get("epochs") or not s.get("stage_epoch_cnt") \
            or any(k not in s for k in keys):
        return None
    pay, new = s[keys[0]], s[keys[1]]
    need = tpcc_epoch_bytes(pay, new, s["write_cnt"] - 6 * pay - 2 * new) \
        / s["stage_epoch_cnt"]
    peak = ctx["peaks"].peak_for(info["kind"])
    return 100.0 * need / (t["group_busy_s"] / t["epochs"]
                           * peak["hbm_bytes_per_s"])
