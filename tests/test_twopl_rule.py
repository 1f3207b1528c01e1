"""The lock family's rule against a textbook lock table (PR 46).

`cc.twopl.validate_wait_die` / `validate_no_wait` — the [B, B] conflict
matrix and `ops.greedy_first_fit`'s sweep — against the serial lock table
of the benchmark's plain reference (`benchmark/references/ycsb_2pl.py`,
nothing of the program: lanes ask one after another in rank order) on
seeded random batches: 64 lanes over 32 keys, mixed reads and writes,
repeated keys inside a lane, empty slots, birth timestamps in another
order than the ranks.

* with a sweep budget no chain reaches: identical commit / wait / die
  masks, lane for lane, and the device counters (`LOCK_COUNTERS`) equal
  to the masks' sums;
* with `sweep_rounds` 1: the committed set is a SUBSET of the table's
  winners and every other lane is a leftover — deferred and counted as
  such, never a death and never a wait — as the reference restates the
  budget.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deneva_tpu.cc import AccessBatch, build_incidence, get_backend
from deneva_tpu.config import Config
from deneva_tpu.workloads.base import LOCK_COUNTERS

B, A, KEYS = 64, 6, 32
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "ref_ycsb_2pl", os.path.join(ROOT, "benchmark", "references",
                                     "ycsb_2pl.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _draw(seed):
    """(ts, keys, types, active) as a record of the command log has
    them: types 0 (no access) / 1 read / 2 write."""
    rng = np.random.default_rng([46, seed])
    keys = rng.integers(0, KEYS, (B, A)).astype(np.int32)
    types = rng.choice([0, 1, 1, 2], (B, A)).astype(np.int8)
    ro = rng.random(B) < 0.3                # read-only transactions
    types[ro] = np.minimum(types[ro], 1)
    active = rng.random(B) < 0.9
    ts = (1 + rng.permutation(B)).astype(np.int64)
    return ts, keys, types, active


@functools.lru_cache(maxsize=8)
def _validate(alg, rounds, b=B, a=A):
    cfg = Config(epoch_batch=b, conflict_buckets=4096, max_accesses=a,
                 req_per_query=a, synth_table_size=1 << 16, cc_alg=alg,
                 sweep_rounds=rounds)
    be = get_backend(alg)

    @jax.jit
    def go(batch):
        stats = {k: jnp.zeros((), jnp.uint32) for k in LOCK_COUNTERS}
        inc = build_incidence(batch, cfg.conflict_buckets,
                              cfg.conflict_exact)
        v, _ = be.validate(cfg, be.init_state(cfg), batch, inc, stats=stats)
        return v.commit, v.abort, v.defer, stats
    return go


def _batch(ts, keys, types, active):
    """The record as the epoch step's access batch: rank = position."""
    return AccessBatch(
        table_ids=jnp.zeros(keys.shape, jnp.int32), keys=jnp.asarray(keys),
        is_read=jnp.asarray(types == 1), is_write=jnp.asarray(types == 2),
        valid=jnp.asarray(types != 0), ts=jnp.asarray(ts, jnp.int32),
        rank=jnp.arange(len(keys), dtype=jnp.int32),
        active=jnp.asarray(active))


def _program(alg, rounds, ts, keys, types, active):
    commit, abort, defer, stats = _validate(alg, rounds)(
        _batch(ts, keys, types, active))
    return (np.asarray(commit), np.asarray(abort), np.asarray(defer),
            {k: int(v) for k, v in stats.items()})


@pytest.mark.parametrize("seed", range(100))
@pytest.mark.parametrize("alg", ["WAIT_DIE", "NO_WAIT"])
def test_the_sweep_is_the_textbook_lock_table(alg, seed, ref):
    ts, keys, types, active = _draw(seed)
    commit, abort, defer, stats = _program(alg, B, ts, keys, types, active)
    fate = ref.lock_table(ts, keys, types, active, alg == "WAIT_DIE")
    assert (commit == (fate == ref.COMMIT)).all()
    assert (abort == (fate == ref.DIE)).all()
    assert (defer == (fate == ref.WAIT)).all()
    assert not (fate == ref.LEFTOVER).any()
    assert ((fate != 0) == active).all()
    assert stats == dict(lock_die=int(abort.sum()),
                         lock_wait=int(defer.sum()), lock_leftover=0)
    if alg == "NO_WAIT":
        assert not defer.any()
    # the batches are hot enough to show every kind of verdict
    assert commit.any() and abort.any()


@pytest.mark.parametrize("seed", range(25))
@pytest.mark.parametrize("alg", ["WAIT_DIE", "NO_WAIT"])
def test_a_budget_of_one_round_leaves_lanes_over_and_decides_none_wrongly(
        alg, seed, ref):
    ts, keys, types, active = _draw(seed)
    commit, abort, defer, stats = _program(alg, 1, ts, keys, types, active)
    table = ref.lock_table(ts, keys, types, active, alg == "WAIT_DIE")
    winners = table == ref.COMMIT
    assert not (commit & ~winners).any() and (winners & ~commit).any()
    # one round grants the lanes nothing earlier conflicts with and
    # refuses nobody: every other lane is a leftover, never a death
    assert not abort.any()
    assert (defer == (active & ~commit)).all()
    assert stats == dict(lock_die=0, lock_wait=0,
                         lock_leftover=int(defer.sum()))
    # ... which is how the reference restates the budget
    fate = ref.lock_table(ts, keys, types, active, alg == "WAIT_DIE",
                          rounds=1)
    assert (commit == (fate == ref.COMMIT)).all()
    assert (defer == (fate == ref.LEFTOVER)).all()


@pytest.mark.parametrize("rounds", [2, 3, 5])
@pytest.mark.parametrize("alg", ["WAIT_DIE", "NO_WAIT"])
def test_a_short_budget_is_restated_lane_for_lane(alg, rounds, ref):
    """Between one round and enough: the decided lanes are decided as
    the table decides them, a decided loser's owners are the grants the
    budget knew, and the rest are leftovers — commit, wait, die and
    leftover lane for lane over 20 batches."""
    seen = dict(wait=0, die=0, leftover=0)
    for seed in range(200, 220):
        ts, keys, types, active = _draw(seed)
        commit, abort, defer, stats = _program(alg, rounds, ts, keys, types,
                                               active)
        fate = ref.lock_table(ts, keys, types, active, alg == "WAIT_DIE",
                              rounds=rounds)
        assert (commit == (fate == ref.COMMIT)).all()
        assert (abort == (fate == ref.DIE)).all()
        assert (defer == ((fate == ref.WAIT) | (fate == ref.LEFTOVER))).all()
        assert stats == dict(lock_die=int((fate == ref.DIE).sum()),
                             lock_wait=int((fate == ref.WAIT).sum()),
                             lock_leftover=int((fate == ref.LEFTOVER).sum()))
        for k in seen:
            seen[k] += stats["lock_" + k]
    assert seen["die"] and seen["leftover"]
    assert bool(seen["wait"]) == (alg == "WAIT_DIE")


@pytest.mark.parametrize("rounds", [24, 4])
@pytest.mark.parametrize("hot_keys", [300, 5000])
def test_the_cells_width_and_budget(hot_keys, rounds, ref):
    """Epochs of 1,024 lanes of ten requests, as the WAIT_DIE cell serves
    them, on keys far hotter than its traffic: under the cell's budget of
    24 rounds no lane is left over even there, under 4 some are, and
    both ways every lane's verdict is the table's."""
    b, a = 1024, 10
    rng = np.random.default_rng([46, hot_keys])
    keys = np.minimum((rng.random((b, a)) ** 2.5 * hot_keys).astype(np.int32),
                      hot_keys - 1)
    types = np.where(rng.random((b, a)) < 0.5, 2, 1).astype(np.int8)
    types[rng.random(b) < 0.5] = 1
    ts = (1 + rng.permutation(b)).astype(np.int64)
    active = np.ones(b, bool)
    commit, abort, defer, stats = _validate("WAIT_DIE", rounds, b, a)(
        _batch(ts, keys, types, active))
    fate = ref.lock_table(ts, keys, types, active, True, rounds)
    assert (np.asarray(commit) == (fate == ref.COMMIT)).all()
    assert (np.asarray(abort) == (fate == ref.DIE)).all()
    assert (np.asarray(defer)
            == ((fate == ref.WAIT) | (fate == ref.LEFTOVER))).all()
    left = int((fate == ref.LEFTOVER).sum())
    assert int(stats["lock_leftover"]) == left
    assert int(stats["lock_wait"]) == int((fate == ref.WAIT).sum()) > 50
    assert (left == 0) == (rounds == 24)


def test_without_the_counters_the_verdict_is_the_same_and_nothing_counts():
    cfg = Config(epoch_batch=B, conflict_buckets=4096, max_accesses=A,
                 req_per_query=A, synth_table_size=1024, cc_alg="WAIT_DIE")
    be = get_backend("WAIT_DIE")
    batch = _batch(*_draw(7))
    inc = build_incidence(batch, cfg.conflict_buckets, cfg.conflict_exact)
    plain, _ = be.validate(cfg, (), batch, inc)
    other = {"defer_cnt": jnp.zeros((), jnp.uint32)}
    handed, _ = be.validate(cfg, (), batch, inc, stats=other)
    for a, b in zip((plain.commit, plain.abort, plain.defer),
                    (handed.commit, handed.abort, handed.defer)):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert list(other) == ["defer_cnt"] and int(other["defer_cnt"]) == 0
