"""`storage.table.VersionRing` stored by ROWS (PR 45), against a plain
numpy model: a lane gathers its row's whole history, the push writes the
epoch's winners alone, as rows, in place.

* random pushes: FIFO by argmin with the empties first, more than H
  overwrites of one row wrap, losers and masked lanes leave the ring as
  it was, and the trash and padding rows stay 0 as loaded;
* `rows` -> `version_from` returns the (v*, has_newer) of the words the
  flat ring held (max entry <= ts with 0 the load's version; any entry
  > ts);
* the leaf `wts`, viewed as bytes, is `int32[padded_rows x H]` row-major
  and little-endian — what `benchmark/references/ycsb_mvto.ring_leaf`
  builds and `runtime/logger.state_digests` hashes;
* the push counts the lanes it hands the row write where the stats carry
  `ring_push_lanes`, and nothing where they do not.
"""

import hashlib
import importlib.util
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deneva_tpu.ops.scatter import last_writer
from deneva_tpu.storage.table import VersionRing, padded_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H = 4


def _words(ring: VersionRing) -> np.ndarray:
    """The leaf as the reference reads it: int32[rows, H] of its bytes."""
    return np.asarray(ring.wts).reshape(-1).view("<i4").reshape(-1, ring.depth)


def _model_push(ring: np.ndarray, slots, ts, win) -> None:
    for s, t, w in zip(slots, ts, win):
        if w:
            ring[s, int(np.argmin(ring[s]))] = t


def _epoch(ring, slots, ts, mask, cap):
    """One epoch as `YCSBWorkload.execute` runs the ring: one gather,
    the tournament, the push of its winners."""
    vw = ring.rows(slots)
    sl = jnp.where(mask, slots, cap)
    win = last_writer(sl, ts, mask, cap)
    return ring.push_rows(vw, sl, ts, win), win


@pytest.mark.parametrize("seed,n_rows,n", [(0, 37, 24), (1, 37, 200),
                                           (2, 500, 64), (3, 6000, 64)],
                         ids=["few_lanes", "more_lanes_than_rows", "one_pass",
                              "chunks_of_the_winners"])
def test_random_pushes_equal_a_numpy_fifo(seed, n_rows, n):
    """Forty epochs of ``n`` lanes over ``n_rows`` rows, a few of them
    hot: duplicates (one winner a row, its greatest timestamp), masked
    lanes, both forms of the row write (chunks of the winners; one pass
    where that is cheaper), and hot rows that wrap many times over."""
    rng = np.random.default_rng(seed)
    rows = padded_rows(n_rows)
    ring = VersionRing.create(rows, H)
    assert ring.wts.shape == (rows, 4 * H) and ring.wts.dtype == jnp.uint8
    model = np.zeros((rows, H), np.int32)
    step = jax.jit(lambda r, s, t, m: _epoch(r, s, t, m, n_rows))
    pushed = np.zeros(rows, np.int64)
    for e in range(40):
        slots = np.where(rng.random(n) < 0.3, rng.integers(0, 3, n),
                         rng.integers(0, n_rows, n)).astype(np.int32)
        ts = (1 + e * n + rng.permutation(n)).astype(np.int32)
        mask = rng.random(n) < 0.6
        ring, win = step(ring, jnp.asarray(slots), jnp.asarray(ts),
                         jnp.asarray(mask))
        win = np.asarray(win)
        # one winner a written row: the greatest timestamp of the epoch
        want = np.zeros(n, bool)
        for s in np.unique(slots[mask]):
            lanes = np.flatnonzero(mask & (slots == s))
            want[lanes[np.argmax(ts[lanes])]] = True
        assert (win == want).all()
        _model_push(model, slots, ts, win)
        pushed[slots[win]] += 1
        assert (_words(ring) == model).all(), e
    assert (model[n_rows:] == 0).all()          # trash and padding
    assert pushed.max() > 2 * H                 # a row wrapped, twice over
    assert ((model > 0).sum(axis=1) == np.minimum(pushed, H)).all()
    # FIFO: a row holds its newest min(pushes, H) timestamps
    assert (model[pushed > H].min(axis=1) > 0).all()


def test_losers_and_masked_lanes_write_nothing():
    """Three lanes on one row, one masked lane, one lane parked on the
    trash row with its mask set by mistake: the winner's timestamp lands
    in the row's first empty slot and nothing else changes."""
    ring = VersionRing.create(padded_rows(10), H)
    trash = ring.wts.shape[0] - 1
    slots = jnp.asarray([3, 3, 3, 5, trash], jnp.int32)
    ts = jnp.asarray([7, 9, 8, 4, 11], jnp.int32)
    win = jnp.asarray([False, True, False, False, True])
    ring = ring.push_rows(ring.rows(slots), slots, ts, win)
    want = np.zeros((padded_rows(10), H), np.int32)
    want[3, 0] = 9
    assert (_words(ring) == want).all()
    # `push` is `rows` + `push_rows`
    ring = ring.push(slots, ts + 10, win)
    want[3, 1] = 19
    assert (_words(ring) == want).all()


@pytest.mark.parametrize("seed", [0, 1])
def test_rows_and_version_from_read_what_the_words_say(seed):
    rng = np.random.default_rng(seed)
    rows = padded_rows(50)
    words = np.zeros((rows, H), np.int32)
    for r in range(50):
        k = rng.integers(0, H + 1)
        words[r, rng.permutation(H)[:k]] = rng.integers(1, 1 << 30, k)
    ring = VersionRing(wts=jnp.asarray(words.view(np.uint8).reshape(rows, -1)),
                       depth=H)
    slots = rng.integers(0, 51, (16, 3)).astype(np.int32)   # 50: the trash
    ts = rng.integers(0, 1 << 30, (16, 3)).astype(np.int32)
    vw = np.asarray(ring.rows(jnp.asarray(slots)))
    assert vw.shape == (16, 3, H) and vw.dtype == np.int32
    assert (vw == words[slots]).all()
    vstar, has = ring.select_version(jnp.asarray(slots), jnp.asarray(ts))
    w = words[slots]
    newer = w > ts[..., None]
    assert (np.asarray(has) == newer.any(-1)).all()
    assert (np.asarray(vstar) == np.where(newer, 0, w).max(-1)).all()
    # the greatest timestamp a word can hold survives the bytes
    top = VersionRing.create(8, H).push(
        jnp.asarray([2], jnp.int32),
        jnp.asarray([np.iinfo(np.int32).max], jnp.int32),
        jnp.asarray([True]))
    assert _words(top)[2, 0] == np.iinfo(np.int32).max


def _mvto():
    spec = importlib.util.spec_from_file_location(
        "ring_ref_ycsb_mvto",
        os.path.join(ROOT, "benchmark", "references", "ycsb_mvto.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_leafs_bytes_are_the_references_ring():
    """A toy history — keys written once, twice in one epoch, and more
    than H times over the epochs — pushed epoch by epoch: the leaf hashes
    to `ycsb_mvto.ring_leaf`'s `int32[padded_rows x H]`, as
    `runtime/logger.state_digests` hashes it."""
    from deneva_tpu.runtime.logger import state_digests
    from deneva_tpu.workloads.ycsb import VER_TABLE
    mvto = _mvto()
    n_rows = 20
    rng = np.random.default_rng(7)
    wk, wt, we = [], [], []
    ring = VersionRing.create(padded_rows(n_rows), H)
    t = 1
    for e in range(12):
        keys = np.concatenate([[0, 0, 1], rng.integers(2, n_rows, 5)])
        ts = t + rng.permutation(len(keys))
        t += len(keys)
        wk += keys.tolist()
        wt += ts.tolist()
        we += [e] * len(keys)
        ring, _ = _epoch(ring, jnp.asarray(keys, jnp.int32),
                         jnp.asarray(ts, jnp.int32),
                         jnp.ones(len(keys), bool), n_rows)
    order = np.lexsort((wt, wk))
    h = SimpleNamespace(wk=np.asarray(wk)[order], wt=np.asarray(wt)[order],
                        we=np.asarray(we)[order])
    leaf = mvto.ring_leaf(h, n_rows, H)
    assert leaf.dtype == np.int32 and leaf.shape == (padded_rows(n_rows) * H,)
    assert (leaf.reshape(-1, H)[0] > 0).all()       # key 0 wrapped
    got = np.asarray(ring.wts)
    assert got.tobytes() == leaf.astype("<i4").tobytes()
    _, per_leaf = state_digests({VER_TABLE: ring})
    assert per_leaf == {mvto.RING: hashlib.sha256(
        leaf.astype("<i4").tobytes()).hexdigest()}


def test_the_push_counts_its_lanes_where_the_stats_carry_them():
    n, n_rows = 128, 1000
    ring = VersionRing.create(padded_rows(n_rows), H)
    slots = jnp.arange(n, dtype=jnp.int32)
    ts = slots + 1
    win = slots < 5                       # five winners: chunks of 2 lanes
    stats = {"ring_push_lanes": jnp.uint32(10), "other": jnp.uint32(0)}
    ring = ring.push_rows(ring.rows(slots), slots, ts, win, stats)
    assert int(stats["ring_push_lanes"]) == 10 + 6 and int(stats["other"]) == 0
    bare = {"other": jnp.uint32(0)}
    ring.push_rows(ring.rows(slots), slots, ts, win, bare)
    assert list(bare) == ["other"]
    assert (_words(ring)[:5, 0] == np.arange(1, 6)).all()
    assert (_words(ring)[5:] == 0).all()


def test_the_replicas_push_is_one_program_a_power_of_two():
    """`runtime/replication.GeoFollower._push_ring` pushes a group's
    written rows through the same class, padded to a power of two with
    the padding masked off: the stamps are those of the unpadded push,
    row 0 (where the padding points) is written only when it is among
    the rows, and lengths that share a power of two share a program."""
    from deneva_tpu.runtime import replication
    n_rows = 300
    rep = SimpleNamespace(_ring=VersionRing.create(n_rows + 1, H))
    plain = VersionRing.create(n_rows + 1, H)
    rng = np.random.default_rng(5)
    before = replication._ring_push._cache_size()
    for boundary, n in enumerate([5, 6, 7, 8, 33, 40, 64, 1], start=1):
        rows = np.unique(rng.integers(1, n_rows, n))
        replication.GeoFollower._push_ring(rep, rows, boundary)
        slots = jnp.asarray(rows, jnp.int32)
        plain = plain.push(slots, jnp.full(len(rows), boundary, jnp.int32),
                           jnp.ones(len(rows), bool))
        assert (_words(rep._ring) == _words(plain)).all()
    assert (_words(rep._ring)[0] == 0).all()
    replication.GeoFollower._push_ring(rep, np.zeros(0, np.int64), 9)
    assert (_words(rep._ring) == _words(plain)).all()
    # 5-8 -> 8, 33-64 -> 64, 1 -> 1 (np.unique may shorten a draw)
    assert replication._ring_push._cache_size() - before <= 4
