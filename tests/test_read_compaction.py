"""The read half of the forwarding executor with full rows (PR 30): only
the reads that nothing forwards to reach the row gather, compacted to
the front first (`ops.gather.checksum_needed_rows`).  Held here to the
per-lane gather it replaced — every lane gathers, forwarded lanes are
overwritten with f(key, writer rank), the checksum folds over the read
lanes — bit for bit, on one device and under `execute_mc` on a CPU mesh
of four.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.generators.ycsb import zeta, zipf_keys
from deneva_tpu.ops import checksum_needed_rows, forward_plan_flat
from deneva_tpu.ops import gather as G
from deneva_tpu.workloads.ycsb import (_field_bytes, _field_fingerprint,
                                       _forward_execute_f0)

BIG = np.iinfo(np.int32).max
WIDTH = 24


def _per_lane(f0, p, slots, trash):
    """The read half as it was before PR 30 (`_forward_execute_f0` of
    PR 29, verbatim): one gather lane per plan lane."""
    vals = jnp.take(f0, jnp.where(p.is_read, slots, trash), axis=0)
    vals = jnp.where((p.fwd >= 0)[:, None],
                     _field_bytes(p.keys, p.fwd, f0.shape[1]), vals)
    return jnp.sum(jnp.where(p.is_read[:, None], vals, 0), dtype=jnp.uint32)


def _epoch(keys_of: str, mix: str, n: int, tab: int, rng, per_txn: int = 4,
           invalid: float = 0.0, pad: int = 0):
    """(keys, rank, is_write) flat lanes of one epoch: ``per_txn`` lanes
    a transaction, ``invalid`` of them masked and ``pad`` more lanes of
    the `big` sentinel at the end (a mesh shard's padding)."""
    if keys_of == "one_key":
        keys = np.full(n, 7, np.int32)
    else:
        theta = float(keys_of)
        keys = zipf_keys(rng, (n,), tab, theta,
                         zeta(tab, theta) if theta else 0.0)
    if mix == "all_reads":
        w = np.zeros(n, bool)
    elif mix == "no_reads":
        w = np.ones(n, bool)
    else:                       # the `hot` mix: half the txns read only
        w = rng.random(n) >= 0.5
        w &= np.repeat(rng.random(n // per_txn) < 0.5, per_txn)
    rank = np.repeat(np.arange(n // per_txn, dtype=np.int32), per_txn)
    dead = rng.random(n) < invalid
    keys = np.where(dead, BIG, keys).astype(np.int32)
    w &= ~dead
    keys = np.concatenate([keys, np.full(pad, BIG, np.int32)])
    rank = np.concatenate([rank, np.zeros(pad, np.int32)])
    w = np.concatenate([w, np.zeros(pad, bool)])
    return keys, rank, w


def _plan(keys, rank, w, tab):
    p = forward_plan_flat(jnp.asarray(keys), jnp.asarray(rank),
                          jnp.asarray(w))
    return p, jnp.where(p.keys != BIG, p.keys, tab)


def _column(rows: int, rng):
    return jnp.asarray(rng.integers(0, 256, (rows, WIDTH), dtype=np.uint8))


def _lanes(cnt: int, n: int) -> tuple[int, int]:
    """What the gather is handed for ``cnt`` needed lanes of ``n``: whole
    chunks of ``ceil(n / 64)`` lanes covering them while the sort pays,
    else every lane (the chunks rounded up); and whether it compacted."""
    chunk = -(-n // G._CHUNKS)
    need = chunk * -(-cnt // chunk)
    if need + n // G._SORT_PER_LANES < n:
        return need, True
    return chunk * -(-n // chunk), False


_EPOCHS = [(k, m) for k in ("0.0", "0.6", "0.9", "one_key")
           for m in ("hot", "all_reads", "no_reads")]


@pytest.mark.parametrize("lanes", ["whole", "invalid_and_padded"])
@pytest.mark.parametrize("keys_of,mix", _EPOCHS,
                         ids=[f"theta_{k}-{m}" for k, m in _EPOCHS])
def test_compacted_read_is_the_per_lane_checksum_and_table(keys_of, mix,
                                                           lanes):
    """`_forward_execute_f0` with full rows against the per-lane gather:
    the same `read_checksum` to the bit and the same table, at every
    skew, with all reads / no reads / the `hot` mix, with masked lanes
    and a shard's `big` padding; the gather is handed whole chunks of
    the plan covering the unforwarded reads, or every lane where the
    sort cannot pay."""
    rng = np.random.default_rng(list(f"{keys_of}/{mix}/{lanes}".encode()))
    n, tab = 2048, 3000
    rows = tab + 9                       # trash row + padding rows
    keys, rank, w = _epoch(
        keys_of, mix, n, tab, rng,
        **(dict(invalid=0.05, pad=512) if lanes != "whole" else {}))
    p, slots = _plan(keys, rank, w, tab)
    f0 = _column(rows, rng)
    got_f0, cks, wcnt, _, rlanes, _ = jax.jit(
        lambda f0, p, slots: _forward_execute_f0(f0, p, slots, tab,
                                                 mono=True))(f0, p, slots)
    assert int(cks) == int(_per_lane(f0, p, slots, tab))
    # the write half is PR 26's, untouched: the legacy scatter's table
    want_f0 = f0.at[jnp.where(p.win, slots, tab)].set(
        _field_bytes(p.keys, p.rank, WIDTH))
    np.testing.assert_array_equal(np.asarray(got_f0)[:tab],
                                  np.asarray(want_f0)[:tab])
    assert int(wcnt) == int(w.sum())
    need = int((np.asarray(p.is_read) & (np.asarray(p.fwd) < 0)).sum())
    assert int(rlanes) == _lanes(need, len(keys))[0]
    if mix == "no_reads":
        assert need == 0 and int(rlanes) == 0 and int(cks) == 0
    if mix == "all_reads":              # nothing to forward: every read
        assert need == int((keys != BIG).sum())


def test_a_txn_that_reads_and_writes_one_key_reads_the_epochs_row():
    """The plan's tie order decides where such a read stands among the
    key's lanes: it is an unforwarded read in every order (a txn never
    sees its own write), and a later txn's read of the key is forwarded
    and never reaches the gather."""
    keys = np.array([5, 5, 5, 5, 9, 9], np.int32)
    rank = np.array([0, 0, 0, 1, 0, 0], np.int32)
    w = np.array([True, False, True, False, False, True])
    for order in ([0, 1, 2, 3, 4, 5], [1, 0, 2, 3, 5, 4],
                  [2, 0, 1, 3, 4, 5]):
        o = np.array(order)
        p, slots = _plan(keys[o], rank[o], w[o], 16)
        need = p.is_read & (p.fwd < 0)
        assert int(need.sum()) == 2
        f0 = _column(24, np.random.default_rng(3))
        cks, _ = checksum_needed_rows(f0, slots, need)
        want = int(np.asarray(f0)[5].sum()) + int(np.asarray(f0)[9].sum())
        assert int(cks) == want


_COUNTS = ["none", "one", "a_chunk", "a_chunk_and_one", "edge",
           "edge_and_one", "all"]


def _count_case(count: str, n: int, rows: int, rng):
    """(cnt, slots, need, col) of one count case over ``n`` lanes."""
    chunk = -(-n // G._CHUNKS)
    edge = (n - n // G._SORT_PER_LANES - 1) // chunk
    assert 1 < edge < G._CHUNKS
    cnt = {"none": 0, "one": 1, "a_chunk": chunk,
           "a_chunk_and_one": chunk + 1, "edge": edge * chunk,
           "edge_and_one": edge * chunk + 1, "all": n}[count]
    slots = rng.integers(0, rows, n).astype(np.int32)   # rows repeat
    need = np.zeros(n, bool)
    need[rng.choice(n, cnt, replace=False)] = True
    return cnt, slots, need, _column(rows, rng)


def _per_row_sum(col, slots, need) -> int:
    per_row = np.asarray(col).astype(np.uint64).sum(axis=1)
    return int(per_row[slots[need]].sum() % (1 << 32))


@pytest.mark.parametrize("n", [1024, 1000], ids=["whole_chunks",
                                                 "ragged_last_chunk"])
@pytest.mark.parametrize("count", _COUNTS)
def test_checksum_needed_rows_follows_the_count(count, n):
    """`checksum_needed_rows` alone, under `jax.jit`: the sum of the
    needed lanes' rows (a row needed twice counts twice) whatever their
    number — none, one, exactly a chunk, a chunk and one, just below and
    just above where the `lax.cond` stops compacting, every lane — and
    the lanes it reports are what its loop issued: whole chunks covering
    the needed lanes (at most a chunk over them), or every lane of the
    plan."""
    rng = np.random.default_rng(list(f"{count}/{n}".encode()))
    cnt, slots, need, col = _count_case(count, n, 5000, rng)
    got, lanes = jax.jit(checksum_needed_rows)(col, jnp.asarray(slots),
                                               jnp.asarray(need))
    assert int(got) == _per_row_sum(col, slots, need)
    want_lanes, compacted = _lanes(cnt, n)
    assert compacted == (count not in ("edge_and_one", "all"))
    chunk = -(-n // G._CHUNKS)
    assert int(lanes) == want_lanes and want_lanes % chunk == 0
    assert not compacted or cnt <= want_lanes < cnt + chunk


@pytest.mark.parametrize("n", [1024, 1000], ids=["whole_chunks",
                                                 "ragged_last_chunk"])
@pytest.mark.parametrize("count", _COUNTS)
def test_four_shards_each_loop_over_their_own_count(count, n):
    """The same cases on a CPU mesh of four against one device: every
    shard gathers from its own block of the column with its own count —
    shard 0 the case's, the others none, every lane and the case's
    neighbour — so the four loops run different numbers of trips side by
    side; sum and lanes of each are what one device gives for that
    shard's lanes alone."""
    from jax.sharding import PartitionSpec as P
    from deneva_tpu.parallel import make_mesh
    from deneva_tpu.parallel.mesh import AXIS
    rng = np.random.default_rng(list(f"mesh/{count}/{n}".encode()))
    rows = 1250
    i = _COUNTS.index(count)
    shards = [_count_case(c, n, rows, rng) for c in (
        count, "none", "all", _COUNTS[(i + 1) % len(_COUNTS)])]
    col = jnp.concatenate([s[3] for s in shards])
    slots = jnp.asarray(np.stack([s[1] for s in shards]))
    need = jnp.asarray(np.stack([s[2] for s in shards]))

    def shard(col, slots, need):
        got, lanes = checksum_needed_rows(col, slots[0], need[0])
        return got[None], lanes[None]

    got, lanes = jax.jit(jax.shard_map(
        shard, mesh=make_mesh(4), in_specs=(P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS))))(col, slots, need)
    for d, (cnt, s, nd, c) in enumerate(shards):
        one, one_lanes = checksum_needed_rows(c, jnp.asarray(s),
                                              jnp.asarray(nd))
        assert int(got[d]) == int(one) == _per_row_sum(c, s, nd)
        assert int(lanes[d]) == int(one_lanes) == _lanes(cnt, n)[0]


def test_the_sum_wraps_like_the_per_lane_sum():
    """Mod 2^32, as the per-lane checksum wrapped: 200,000 lanes of one
    all-255 row of 100 bytes pass 2^32."""
    col = jnp.full((8, 100), 255, jnp.uint8)
    n = 200_000
    got, _ = checksum_needed_rows(col, jnp.full((n,), 3, jnp.int32),
                                  jnp.ones((n,), bool))
    assert int(got) == (n * 25_500) % (1 << 32) != n * 25_500


def test_fingerprint_columns_keep_the_per_lane_gather():
    """A uint32 column (no cell runs it) is gathered lane by lane as it
    was, chosen by the column's rank: every lane is handed to it."""
    rng = np.random.default_rng(30)
    keys, rank, w = _epoch("0.9", "hot", 1024, 300, rng)
    p, slots = _plan(keys, rank, w, 300)
    f0 = jnp.asarray(rng.integers(0, 2**32, 309, dtype=np.uint32))
    _, cks, _, _, rlanes, _ = _forward_execute_f0(f0, p, slots, 300,
                                                  mono=True)
    assert int(rlanes) == 1024
    vals = np.where(np.asarray(p.fwd) >= 0,
                    np.asarray(_field_fingerprint(p.keys, p.fwd)),
                    np.asarray(f0)[np.asarray(slots)])
    assert int(cks) == int(vals[np.asarray(p.is_read)].astype(np.uint64)
                           .sum() % (1 << 32))


# ---- four shards: `execute_mc` on a CPU mesh ------------------------------

@pytest.mark.parametrize("theta", [0.0, 0.9], ids=["uniform", "hot"])
def test_execute_mc_on_a_mesh_of_four_reads_what_one_device_reads(theta):
    """Each shard of `execute_mc` compacts its own unforwarded reads (its
    padding lanes hold the `big` sentinel and are no reads); the psum of
    the four checksums is the one-device `read_checksum`, the tables
    agree row for row, and the four shards together are handed fewer
    gather lanes than their plans hold."""
    from deneva_tpu.config import Config
    from deneva_tpu.engine import Engine
    from deneva_tpu.parallel import make_mesh, make_sharded_run
    from deneva_tpu.workloads import get_workload
    from deneva_tpu.workloads.ycsb import TABLE

    cfg = Config(cc_alg="TPU_BATCH", epoch_batch=256, conflict_buckets=1024,
                 max_accesses=4, req_per_query=4, synth_table_size=4096,
                 zipf_theta=theta, max_txn_in_flight=1024,
                 sim_full_row=True, tup_size=WIDTH, field_per_tuple=2)
    eng = Engine(cfg, get_workload(cfg))
    one = eng.jit_run(eng.init_state(seed=5), 6)
    cfg4 = cfg.replace(device_parts=4)
    eng4 = Engine(cfg4, get_workload(cfg4))
    place, run = make_sharded_run(eng4, make_mesh(4))
    four = run(place(eng4.init_state(seed=5)), 6)
    s1, s4 = (jax.device_get(s.stats) for s in (one, four))
    assert int(s1["read_checksum"]) == int(s4["read_checksum"]) != 0
    assert int(s1["write_cnt"]) == int(s4["write_cnt"]) > 0
    assert int(s1["total_txn_commit_cnt"]) == int(s4["total_txn_commit_cnt"])
    # mesh block d holds the rows of the keys = d (mod 4), in key order
    f1 = np.asarray(one.db[TABLE].columns["F0"])
    f4 = np.asarray(four.db[TABLE].columns["F0"])
    f4 = f4.reshape(4, f4.shape[0] // 4, WIDTH)
    k = np.arange(cfg.synth_table_size)
    np.testing.assert_array_equal(f4[k % 4, k // 4], f1[k])
    lanes = 6 * 256 * 4
    assert 0 < int(s4["read_gather_lanes"]) < 4 * lanes // 2
    assert 0 < int(s1["read_gather_lanes"]) < lanes


def test_the_chip_tool_rehearses_on_the_cpu():
    """`tools/gather_calls.py` (the micro-run behind `_CHUNKS`) in a
    process of its own, on the CPU at a toy column: every form it times
    passed its own check against the per-lane sum, and the loop was
    handed whole calls covering the needed lanes at both plans."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "gather_calls.py"),
         "--platform", "cpu", "--rows", "20000", "--reps", "1",
         "--lanes", "1280", "5120"],
        capture_output=True, text=True, timeout=300, check=True).stdout
    plans = [json.loads(ln.split(" ", 1)[1]) for ln in out.splitlines()
             if ln.startswith("[gather_calls] ")]
    assert [(p["plan_lanes"], p["needed"]) for p in plans] == [
        (163_840, 90_000), (81_920, 24_000)]
    for p in plans:
        assert p["device"]["platform"] == "cpu"
        assert len(p["one_call"]) == 2
        for lanes, r in p["loop"].items():
            assert p["needed"] <= r["handed"] < p["needed"] + int(lanes)
            assert r["handed"] % int(lanes) == 0
