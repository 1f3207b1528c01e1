"""The two-phase-locking epoch against the chip's memory roofline: the
bytes the epoch's WORK needs (`twopl_epoch_bytes`) over the bytes the
chip could move in the device time an epoch took.  One chip: the
server's window counters (`total_txn_commit_cnt` x `req_per_query`
committed lanes, `write_cnt` of them writes) over `stage_epoch_cnt`,
against a traced epoch's device time.  The bytes count the work, not the
implementation, so a later kernel cannot move the share by moving fewer
bytes for the same answer.  The program is latency-bound — a gather and
a scatter of 100 B rows against 6.3 GB, a [B, B] compare and a sweep of
matvecs — so the share reads small: it is the yardstick, not a target.
Nothing without a trace or the window's counters."""

KEY = 4             # a request's key: int32
FLAG = 1            # its type (read / write): int8


def twopl_epoch_bytes(read_lanes: float, written_rows: float,
                      batch_lanes: float, row_bytes: int = 100) -> float:
    """Bytes an epoch NEEDS:

    * a committed read lane moves its field (``row_bytes`` of F0);
    * a winner's written row is read and written (the row's old bytes
      leave the table and its new ones enter it): ``2 x row_bytes``;
    * every lane of the admitted batch, granted or not, brings its key
      and its flag to the lock table.

    The conflict matrix, the sweep, the lanes of transactions that died
    or waited and the whole-column copy a scatter may cost on the chip
    are overhead, not needed traffic — they lower the share."""
    return (read_lanes * row_bytes + written_rows * 2 * row_bytes
            + batch_lanes * (KEY + FLAG))


def read(ctx):
    t, s, info = ctx["trace"], ctx["server"]["summary"], ctx["server"]["info"]
    f = ctx["fields"]
    if not t or not t.get("epochs") or not s.get("stage_epoch_cnt") \
            or "write_cnt" not in s or "epoch_batch" not in f:
        return None
    req = int(f["req_per_query"])
    lanes = s["total_txn_commit_cnt"] * req
    need = twopl_epoch_bytes(
        (lanes - s["write_cnt"]) / s["stage_epoch_cnt"],
        s["write_cnt"] / s["stage_epoch_cnt"],
        int(f["epoch_batch"]) * req, int(f.get("tup_size", 100)))
    peak = ctx["peaks"].peak_for(info["kind"])
    return 100.0 * need / (t["group_busy_s"] / t["epochs"]
                           * peak["hbm_bytes_per_s"])
