"""The plain reference against streams worked out by hand, against a
loop that is obviously serial, and against the faults it is there to
catch: a winner's write dropped, an illegal OCC verdict."""

import struct

import numpy as np
import pytest

R, W = 1, 2


def pack_epoch(epoch, keys, types, active):
    """One log record, framed as the server frames it (the format the
    reference restates; `test_decoder_reads_the_programs_own_records`
    holds it to the program's encoder)."""
    keys = np.asarray(keys, np.int32)
    types = np.asarray(types, np.int8)
    n, w = keys.shape
    blob = (struct.pack("<qI", epoch, n) + np.zeros(n, np.int64).tobytes()
            + struct.pack("<III", n, w, 0)
            + np.arange(n, dtype=np.int64).tobytes() + keys.tobytes()
            + types.tobytes())
    bits = np.packbits(np.asarray(active, np.uint8)).tobytes()
    return struct.pack("<IqII", 0xDE7E7A10, epoch, len(blob),
                       len(bits)) + blob + bits


def plain_serial(n_rows, epochs, serial):
    """One transaction after another, one request after another: the
    table as {key: bytes} plus every value a read saw."""
    tab = {k: serial.field_bytes(np.uint32(k), 0, 100).tobytes()
           for k in range(n_rows)}
    reads = []
    for keys, types, commit in epochs:
        for rank in range(len(keys)):
            if not commit[rank]:
                continue
            for k, t in zip(keys[rank], types[rank]):
                if t == R:
                    reads.append((rank, int(k), tab[int(k)]))
                elif t == W:
                    tab[int(k)] = serial.field_bytes(
                        np.uint32(k), np.uint32(rank), 100).tobytes()
    return tab, reads


# three epochs over 8 rows, 4 txns x 2 requests:
#  epoch 0: duplicate keys — txns 0, 1 and 3 all write key 5 (3 wins);
#           txn 2 reads key 5 AFTER txn 1 wrote it (read-after-write)
#  epoch 1: no writer at all
#  epoch 2: txn 1 inactive; txn 0 writes key 2 twice; txn 3 writes key 7
HAND = [
    ([[5, 1], [5, 2], [5, 3], [5, 0]],
     [[W, R], [W, W], [R, R], [W, R]], [1, 1, 1, 1]),
    ([[0, 1], [2, 3], [4, 5], [6, 7]],
     [[R, R], [R, R], [R, R], [R, R]], [1, 1, 1, 1]),
    ([[2, 2], [6, 6], [1, 1], [7, 0]],
     [[W, W], [W, W], [R, R], [W, R]], [1, 0, 1, 1]),
]


def hand_log():
    return b"".join(pack_epoch(e, k, t, a)
                    for e, (k, t, a) in enumerate(HAND))


def test_hand_worked_stream(serial):
    res = serial.replay(hand_log(), 8)
    tab = res["table"]
    assert res["epochs"] == 3 and res["commits"] == 4 + 4 + 3
    # key 5: last writer of epoch 0 is rank 3; key 2: rank 1 in epoch 0,
    # then rank 0 in epoch 2; key 7: rank 3; key 6 untouched (inactive)
    assert list(tab.version) == [0, 0, 0, 0, 0, 3, 0, 3]
    want, reads = plain_serial(8, [(np.array(k), np.array(t), np.array(a, bool))
                                   for k, t, a in HAND], serial)
    got = np.frombuffer(tab._column_bytes(tab.version), np.uint8
                        ).reshape(8, 100)
    for k in range(8):
        assert got[k].tobytes() == want[k], k
    # the read of key 5 by txn 2 in epoch 0 saw txn 1's write
    assert (2, 5, serial.field_bytes(np.uint32(5), np.uint32(1), 100
                                     ).tobytes()) in reads


@pytest.mark.parametrize("seed", range(4))
def test_vectorised_executor_equals_the_plain_loop(seed, serial):
    rng = np.random.default_rng(seed)
    n_rows, eps = 64, []
    for _ in range(5):
        eps.append((rng.integers(0, n_rows, (32, 4)).astype(np.int32),
                    rng.integers(1, 3, (32, 4)).astype(np.int8),
                    rng.random(32) < 0.8))
    tab = serial.SerialTable(n_rows)
    for k, t, c in eps:
        tab.apply_epoch(k, t, c)
    want, _ = plain_serial(n_rows, eps, serial)
    got = np.frombuffer(tab._column_bytes(tab.version), np.uint8
                        ).reshape(n_rows, 100)
    assert all(got[k].tobytes() == want[k] for k in range(n_rows))


def test_a_dropped_winner_write_changes_the_digest(serial):
    res = serial.replay(hand_log(), 8)
    tab = res["table"]
    sound = tab.digest()
    assert tab.digest(drop_key=5) != sound      # key 5's winner lost
    assert tab.digest(drop_key=0) == sound      # key 0 was never written
    info = dict(state_digest=sound, run_commit_cnt=11)
    fields = dict(synth_table_size=8)
    ok, notes = serial.verify(hand_log(), fields, info)
    assert all(v <= lim for _, v, lim in ok), ok
    assert notes == dict(epochs=3, commits=11, trash_row="never_written")
    bad = dict((n, v) for n, v, _ in
               serial.verify(hand_log(), fields, info, drop_key=5)[0])
    assert bad["digest_mismatch"] == 1.0 and bad["commit_count_gap"] == 0.0
    short = dict((n, v) for n, v, _ in serial.verify(
        hand_log(), fields, dict(info, run_commit_cnt=12))[0])
    assert short["commit_count_gap"] == 1.0


def test_occ_rule_refuses_an_illegal_verdict(serial):
    keys = np.array([[1, 2], [2, 3], [4, 5], [5, 6]], np.int32)
    types = np.array([[W, R], [R, W], [W, W], [R, R]], np.int8)
    viol = serial.occ_rule_violations
    # nothing shared with a lower-ranked committed writer
    assert viol(keys, types, np.array([1, 0, 1, 0], bool)) == 0
    # txn 1 writes 3 only; txn 0 writes 1: no conflict either way
    assert viol(keys, types, np.array([1, 1, 0, 0], bool)) == 0
    # txn 3 READS key 5 that lower-ranked committed txn 2 writes: illegal
    assert viol(keys, types, np.array([0, 0, 1, 1], bool)) == 1
    # a later writer of a key an earlier txn only read is legal (rw)
    k2 = np.array([[7, 7], [7, 0]], np.int32)
    t2 = np.array([[R, R], [W, R]], np.int8)
    assert viol(k2, t2, np.array([1, 1], bool)) == 0
    # write-write on one key by two committed txns: illegal
    t3 = np.array([[W, R], [W, R]], np.int8)
    assert viol(k2, t3, np.array([1, 1], bool)) == 1
    # through `verify`: the illegal mask fails, and so does committing
    # a lane that carried no transaction
    log = pack_epoch(0, keys, types, [1, 1, 1, 0])
    tab = serial.replay(log, 8, verdicts={0: np.array([1, 0, 1, 0], bool)})
    info = dict(state_digest=tab["table"].digest(), run_commit_cnt=2)
    res = dict((n, v) for n, v, _ in serial.verify(
        log, dict(synth_table_size=8), info,
        verdicts={0: np.array([1, 0, 1, 0], bool)})[0])
    assert res["occ_rule_violations"] == 0 and res["digest_mismatch"] == 0
    res = dict((n, v) for n, v, _ in serial.verify(
        log, dict(synth_table_size=8), info,
        verdicts={0: np.array([0, 0, 1, 1], bool)})[0])
    assert res["occ_rule_violations"] >= 1


def test_decoder_reads_the_programs_own_records(serial):
    from deneva_tpu.runtime import wire
    from deneva_tpu.runtime.logger import pack_record
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 100, (16, 10)).astype(np.int32)
    types = rng.integers(1, 3, (16, 10)).astype(np.int8)
    active = rng.random(16) < 0.7
    blk = wire.QueryBlock(keys, types, np.zeros((16, 0), np.int32),
                          np.arange(16, dtype=np.int64))
    rec = pack_record(9, wire.encode_epoch_blob(9, blk, np.arange(16)),
                      active)
    got = list(serial.read_log(rec + b"torn-tail"))
    assert len(got) == 1
    e, k, t, a = got[0]
    assert e == 9 and (k == keys).all() and (t == types).all() \
        and (a == active).all()


def test_value_law_and_digest_equal_the_programs_on_a_loaded_table(serial):
    from deneva_tpu.config import Config
    from deneva_tpu.runtime.logger import state_digest
    from deneva_tpu.workloads import get_workload
    cfg = Config.from_args(["--workload=YCSB", "--sim_full_row=true",
                            "--synth_table_size=1000"])
    assert state_digest(get_workload(cfg).load()) == \
        serial.SerialTable(1000).digest()
