"""The row write's Pallas kernel (`ops.scatter.write_rows_by_group`)
under Pallas' TPU interpreter on the CPU, held to XLA's scatter to the
bit — and `scatter_winner_rows` with the kernel in its loop, as the chip
runs it, with the tile groups it reports held to a numpy count.

The interpreter runs the kernel's own DMAs, semaphores and 32-bit
merges; what it cannot show — Mosaic's layouts, the 128-lane window, the
column's copies — `tests/test_chip_compile.py` holds on the chip's own
compiler.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from deneva_tpu.ops import scatter as S

CHUNK = 20          # lanes a trip at N = 1,280


def _kernel(col, idx, vals, cnt, *, k=4, mode="on_wait"):
    return S.write_rows_by_group(
        jnp.asarray(col), jnp.asarray(idx), jnp.asarray(vals), cnt,
        in_flight=k,
        interpret=pltpu.InterpretParams(dma_execution_mode=mode))


def _lanes(rows, slots, lanes):
    """(idx padded with ``rows`` to ``lanes``, the count)"""
    idx = np.full(lanes, rows, np.int32)
    idx[:len(slots)] = np.sort(slots)
    return idx, len(slots)


SLOT_CASES = {
    "no_winner": lambda rows: [],
    "one": lambda rows: [77],
    "all_in_one_group": lambda rows: list(range(64, 96)),
    "one_a_group": lambda rows: list(range(5, rows, 32))[:40],
    "across_a_groups_edge": lambda rows: [30, 31, 32, 33, 63, 64],
    "the_columns_last_group": lambda rows: [rows - 32, rows - 2, rows - 1],
    "first_and_last_row": lambda rows: [0, rows - 1],
}


@pytest.mark.parametrize("mode", ["on_wait", "eager"])
@pytest.mark.parametrize("width", [100, 40])
@pytest.mark.parametrize("case", sorted(SLOT_CASES))
def test_kernel_writes_what_xlas_scatter_writes(case, width, mode):
    """One call of the kernel against ``col.at[idx].set(vals,
    mode="drop")``: every byte of the column, at the two row widths the
    cells store, with the DMAs run when issued and when waited for (a
    buffer touched between a copy's start and its wait shows in one of
    the two)."""
    rows, lanes = 1280, 48
    rng = np.random.default_rng(list(f"{case}/{width}".encode()))
    col = rng.integers(0, 256, (rows, width), dtype=np.uint8)
    vals = rng.integers(0, 256, (lanes, width), dtype=np.uint8)
    idx, cnt = _lanes(rows, SLOT_CASES[case](rows), lanes)
    want = jnp.asarray(col).at[jnp.asarray(idx)].set(jnp.asarray(vals),
                                                     mode="drop")
    for k in (2, 8, 64):
        got = _kernel(col, idx, vals, cnt, k=k, mode=mode)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lanes_past_the_count_do_nothing():
    """The count, not the slots, ends the walk: real rows parked behind
    it (and the padding's ``rows``) are never written."""
    rows, width, lanes = 256, 100, 16
    rng = np.random.default_rng(5)
    col = rng.integers(0, 256, (rows, width), dtype=np.uint8)
    vals = rng.integers(0, 256, (lanes, width), dtype=np.uint8)
    idx = np.array([3, 40, 41, 200, 201, 202] + [rows] * 10, np.int32)
    got = np.asarray(_kernel(col, idx, vals, 3))
    want = col.copy()
    want[[3, 40, 41]] = vals[:3]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("broken", ["a_slot_at_the_rows", "a_slot_below_0",
                                    "a_slot_far_above", "a_count_past_the_"
                                    "lanes", "a_count_below_0"])
def test_a_broken_promise_stays_inside_the_column(broken):
    """What lets Mosaic's bounds check be off (`_BOUNDS_CHECKS`): the
    slots and the count are clamped on the way into the kernel, so one
    that `compact_winners` would never hand it moves no byte outside the
    column — the bad lane writes the column's first or last row, the
    good ones their rows."""
    rows, width, lanes = 256, 100, 8
    rng = np.random.default_rng(list(broken.encode()))
    col = rng.integers(0, 256, (rows, width), dtype=np.uint8)
    vals = rng.integers(0, 256, (lanes, width), dtype=np.uint8)
    idx, cnt = np.array([3, 40, 41, 200] + [rows] * 4, np.int32), 4
    bad = {"a_slot_at_the_rows": (3, rows), "a_slot_below_0": (0, -7),
           "a_slot_far_above": (3, 2**31 - 1)}.get(broken)
    want = col.copy()
    want[idx[:4]] = vals[:4]
    if bad:
        idx[bad[0]] = bad[1]
        want[[3, 200][bad[0] > 0]] = col[[3, 200][bad[0] > 0]]
        want[[0, rows - 1][bad[0] > 0]] = vals[bad[0]]
    elif broken == "a_count_past_the_lanes":
        cnt = lanes + 5     # the padding lanes, `rows`, land on the last
        want[rows - 1] = vals[lanes - 1]
    else:
        cnt, want = -3, col
    np.testing.assert_array_equal(np.asarray(_kernel(col, idx, vals, cnt)),
                                  want)


def test_kernel_under_jit_and_in_a_loop_with_a_traced_trip_count():
    """As the executor's loop holds it: jitted, the column carried
    through a `fori_loop` whose trip count is data."""
    rows, width, lanes = 512, 100, 8
    rng = np.random.default_rng(6)
    col = rng.integers(0, 256, (rows, width), dtype=np.uint8)
    slots = np.sort(rng.choice(rows, 3 * lanes, replace=False)
                    ).astype(np.int32)
    vals = rng.integers(0, 256, (3 * lanes, width), dtype=np.uint8)

    @jax.jit
    def run(col, idx, vals, trips):
        def body(i, c):
            cut = lambda x: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                x, i * lanes, lanes)
            return S.write_rows_by_group(
                c, cut(idx), cut(vals), lanes, in_flight=4,
                interpret=pltpu.InterpretParams())
        return jax.lax.fori_loop(0, trips, body, col)

    for trips in (0, 2, 3):
        got = run(jnp.asarray(col), jnp.asarray(slots), jnp.asarray(vals),
                  trips)
        want = col.copy()
        want[slots[:trips * lanes]] = vals[:trips * lanes]
        np.testing.assert_array_equal(np.asarray(got), want)


def test_kernel_as_four_shards_of_a_cpu_mesh():
    """Under `shard_map`, as the four-chip cell runs it: every shard
    writes its own block of the column with its own lanes."""
    from jax.sharding import Mesh, PartitionSpec as P
    rows, width, lanes = 128, 100, 8            # a shard's
    rng = np.random.default_rng(7)
    col = rng.integers(0, 256, (4 * rows, width), dtype=np.uint8)
    vals = rng.integers(0, 256, (4 * lanes, width), dtype=np.uint8)
    idx = np.stack([_lanes(rows, rng.choice(rows, 3 + d, replace=False),
                           lanes)[0] for d in range(4)])
    cnt = np.arange(3, 7, dtype=np.int32)
    mesh = Mesh(np.array(jax.devices()[:4]), ("d",))

    def shard(c, i, v, n):
        return S.write_rows_by_group(
            c, i[0], v, n[0], in_flight=2,
            interpret=pltpu.InterpretParams())
    got = jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(P("d"), P("d"), P("d"), P("d")),
        out_specs=P("d")))(
            jnp.asarray(col), jnp.asarray(idx), jnp.asarray(vals),
            jnp.asarray(cnt))
    want = col.copy()
    for d in range(4):
        want[d * rows + idx[d, :cnt[d]]] = vals[d * lanes:][:cnt[d]]
    np.testing.assert_array_equal(np.asarray(got), want)


# ---- `scatter_winner_rows` with the kernel in its loop --------------------

@pytest.fixture
def as_on_the_chip(monkeypatch):
    """`scatter_winner_rows` takes its TPU side, the kernel interpreted,
    at this file's toy chunks."""
    monkeypatch.setattr(S, "_on_tpu", lambda: True)
    monkeypatch.setattr(S, "_MIN_CALL_LANES", 1)
    monkeypatch.setattr(S, "write_rows_by_group", functools.partial(
        S.write_rows_by_group, interpret=pltpu.InterpretParams()))


def _winner_slots(case: str, cap: int, rng) -> np.ndarray:
    if case in SLOT_CASES:
        return np.asarray(SLOT_CASES[case](cap), np.int64)
    count = {"a_chunk": CHUNK, "a_chunk_and_one": CHUNK + 1,
             "three_chunks_less_one": 3 * CHUNK - 1}[case]
    return rng.choice(cap, count, replace=False)


def _numpy_groups(slots: np.ndarray, chunk: int) -> int:
    """Write-backs of the kernel for ascending ``slots`` in calls of
    ``chunk`` lanes: a group once a call."""
    slots = np.sort(slots)
    return sum(len(set(slots[i:i + chunk] // 32))
               for i in range(0, len(slots), chunk))


@pytest.mark.parametrize("case", sorted(SLOT_CASES) + [
    "a_chunk", "a_chunk_and_one", "three_chunks_less_one"])
def test_scatter_winner_rows_through_the_kernel(case, as_on_the_chip):
    """The whole function as the chip runs it — compaction, a loop of
    ``ceil(cnt / chunk)`` kernel calls, values computed after the sort —
    against the legacy scatter of every lane: the column to the bit, the
    trash row and the padding rows untouched, whole chunks handed, and
    `write_row_groups` = a numpy count of the groups each call touches."""
    from deneva_tpu.workloads.ycsb import _field_bytes
    rng = np.random.default_rng(list(case.encode()))
    n, width, cap = 64 * CHUNK, 100, 1280 - 64
    rows = cap + 64                          # trash row + 63 padding rows
    winners = _winner_slots(case, cap, rng)
    # losers, twins of winners, lanes aimed at the trash, misses
    slots = rng.integers(0, cap, n).astype(np.int32)
    win = np.zeros(n, bool)
    at = rng.choice(n, len(winners), replace=False)
    slots[at], win[at] = winners, True
    slots[~win & (rng.random(n) < 0.1)] = cap
    slots[~win & (rng.random(n) < 0.05)] = -1
    keys = slots * 3 + 1
    order = rng.integers(0, 1000, n).astype(np.int32)
    col = jnp.asarray(rng.integers(0, 256, (rows, width), dtype=np.uint8))
    value = lambda k, o: _field_bytes(k, o, width)      # noqa: E731
    got, lanes, groups, read = jax.jit(
        S.scatter_winner_rows, static_argnums=(4, 5))(
            col, jnp.asarray(slots), jnp.asarray(win),
            (jnp.asarray(keys), jnp.asarray(order)), value, cap,
            jnp.uint32(9))
    assert int(read) == 9
    steer = np.where(win, slots, cap)
    want = col.at[jnp.asarray(steer)].set(value(jnp.asarray(keys),
                                                jnp.asarray(order)))
    np.testing.assert_array_equal(np.asarray(got)[:cap],
                                  np.asarray(want)[:cap])
    np.testing.assert_array_equal(np.asarray(got)[cap:],
                                  np.asarray(col)[cap:])
    assert int(lanes) == -(-len(winners) // CHUNK) * CHUNK
    assert int(groups) == _numpy_groups(winners, CHUNK)
    assert int(groups) <= len(winners)


def test_the_groups_are_counted_off_the_chip_too():
    """The counter is computed from the slots, outside the kernel: the
    CPU's scatter reports the groups the chip's kernel writes back, the
    flagged whole pass reports none, and a write the kernel does not
    take — a ragged column, one narrower than half a tile's lanes (the
    version ring), calls shorter than `_MIN_CALL_LANES` (the medium
    cells', a shard of four's) — reports none and keeps XLA's scatter."""
    rng = np.random.default_rng(11)
    n, cap = 64 * CHUNK, 1280 - 64
    winners = rng.choice(cap, 50, replace=False)
    slots = rng.integers(0, cap, n).astype(np.int32)
    win = np.zeros(n, bool)
    at = rng.choice(n, 50, replace=False)
    slots[at], win[at] = winners, True
    value = lambda k: jnp.zeros((k.shape[0], 1), jnp.uint8) + \
        k[:, None].astype(jnp.uint8)                    # noqa: E731

    def groups_of(rows, width, per_lane=None, min_call=1):
        col = jnp.zeros((rows, width), jnp.uint8)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(S, "_MIN_CALL_LANES", min_call)
            if per_lane is not None:
                mp.setattr(S, "_ROWS_PER_LANE", per_lane)
            out = S.scatter_winner_rows(
                col, jnp.asarray(slots), jnp.asarray(win),
                (jnp.asarray(slots),),
                lambda k: jnp.broadcast_to(value(k), (k.shape[0], width)),
                cap, jnp.uint32(0))
        return int(out[1]), int(out[2])

    assert groups_of(cap + 64, 100) == (3 * CHUNK,
                                        _numpy_groups(winners, CHUNK))
    assert groups_of(cap + 64, 100, per_lane=1 << 12) == (n, 0)     # whole
    assert groups_of(cap + 64, 100, min_call=CHUNK + 1) == (3 * CHUNK, 0)
    assert S._by_group((1280, 100), jnp.uint8, 2560)
    assert S._by_group((1280, 64), jnp.uint8, 2560)
    # the hot cell's calls; not a shard of four's, not the medium cells'
    for lanes in (1280, 160):
        assert not S._by_group((6_291_520, 100), jnp.uint8, lanes)
    for rows, width in ((cap + 64 + 9, 100), (cap + 64, 40)):
        assert not S._by_group((rows, width), jnp.uint8, 2560)
        assert groups_of(rows, width) == (3 * CHUNK, 0)
    assert not S._by_group((1280, 100), jnp.int32, 2560)


def test_the_chip_tool_rehearses_on_the_cpu():
    """`tools/scatter_calls.py` (the micro-run behind `_IN_FLIGHT`,
    `_MIN_CALL_LANES` and `_KERNEL_ROWS_PER_LANE`) in a process of its own, on the CPU at
    a toy column, the kernel under Pallas' interpreter: every form wrote
    the bytes of XLA's loop, the loops were handed whole chunks covering
    the winners and the whole pass every lane, and only the kernel's
    forms report groups."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "scatter_calls.py"),
         "--platform", "cpu", "--rows", "4096", "--reps", "1",
         "--plans", "toy", "--widths", "100", "--in-flight", "4",
         "--chunks", "64", "16", "--bounds-checks", "0", "1"],
        capture_output=True, text=True, timeout=600, check=True).stdout
    (run,) = [json.loads(ln.split(" ", 1)[1]) for ln in out.splitlines()
              if ln.startswith("[scatter_calls] ")]
    assert run["device"]["platform"] == "cpu"
    assert (run["width"], run["plan_lanes"], run["winners"]) == (100, 1280,
                                                                 300)
    forms = run["forms"]
    assert sorted(forms) == ["kernel_k4_c16", "kernel_k4_c16_checked",
                             "kernel_k4_c64", "kernel_k4_c64_checked",
                             "whole", "xla_loop"]
    assert all(f["same_bytes"] for f in forms.values())
    assert forms["whole"]["handed"] == 1280 and forms["whole"]["groups"] == 0
    assert forms["xla_loop"]["handed"] == 300 == \
        forms["kernel_k4_c64"]["handed"]            # 15 chunks of 20
    assert forms["kernel_k4_c16"]["handed"] == 320  # 4 chunks of 80
    assert forms["xla_loop"]["groups"] == 0
    assert 0 < forms["kernel_k4_c16"]["groups"] \
        <= forms["kernel_k4_c64"]["groups"] <= 300
