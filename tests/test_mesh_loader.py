"""The sharded loader and what the mesh adds to the epoch program
(PR 29): with ``device_parts > 1`` `YCSBWorkload.load()` builds each
owner-major block on the device that holds it, bit for bit what
``to_mc_layout`` makes of the single-device table; `execute_mc` carries
`ep.exchange` / `ep.plan`; `mesh_a2a_bytes` is what the program
exchanges between chips.  Four of conftest's eight virtual devices."""

import re

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from chip_smoke import served_cfg
from deneva_tpu.parallel import mesh as M
from deneva_tpu.storage.table import (mc_block_geometry, padded_rows,
                                      to_mc_layout)
from deneva_tpu.workloads import get_workload
from deneva_tpu.workloads.ycsb import TABLE

ROWS = 4096


@pytest.mark.parametrize("full_row", ["true", "false"],
                         ids=["full_row", "fingerprint"])
@pytest.mark.parametrize("d_parts", [2, 4])
def test_sharded_loader_equals_the_permuted_single_device_load(
        d_parts, full_row):
    cfg = served_cfg(device_parts=d_parts, sim_full_row=full_row,
                     synth_table_size=ROWS, epoch_batch=512,
                     max_txn_in_flight=4096, client_batch_size=512)
    wl = get_workload(cfg)
    got = wl.load()[TABLE]
    want = to_mc_layout(wl._load_one(), d_parts)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert (got.mc_parts, got.capacity, got.full_row) == (d_parts, ROWS,
                                                          full_row == "true")
    local, lb = mc_block_geometry(ROWS, 1, d_parts)
    assert (local, lb) == (ROWS // d_parts, padded_rows(ROWS // d_parts))
    np.testing.assert_array_equal(np.asarray(got.row_cnt),
                                  np.asarray(want.row_cnt))
    mesh = M.make_mesh(d_parts)
    placed = M.state_shardings(mesh, {"db": {TABLE: got}})["db"][TABLE]
    assert sorted(got.columns) == [f"F{i}" for i in range(10)]
    for name, col in got.columns.items():
        w = np.asarray(want.columns[name])
        g = np.asarray(col)
        assert (g.dtype, g.shape) == (w.dtype, w.shape), name
        np.testing.assert_array_equal(g, w, err_msg=name)
        # pad and trash rows of every block are zero
        blocks = g.reshape(d_parts, lb, *g.shape[1:])
        assert not blocks[:, local:].any(), name
        # where `state_shardings` would put it: ServerNode's later
        # device_put moves nothing
        assert col.sharding.is_equivalent_to(placed.columns[name], col.ndim)
        assert col.sharding.is_equivalent_to(
            NamedSharding(mesh, P(M.AXIS, *[None] * (col.ndim - 1))),
            col.ndim)
        assert {s.data.shape[0] for s in col.addressable_shards} == {lb}
        assert len(col.addressable_shards) == d_parts
    f0 = np.asarray(got.columns["F0"]).reshape(d_parts, lb, -1)
    assert f0[:, :local].any(axis=-1).all()          # every live row is set


def test_block_d_row_j_is_key_j_times_d_plus_d():
    """The layout said once more, from the value law alone: no
    single-device table, no permutation."""
    from deneva_tpu.workloads.ycsb import _field_fingerprint
    cfg = served_cfg(device_parts=4, synth_table_size=ROWS, epoch_batch=512,
                     max_txn_in_flight=4096, client_batch_size=512)
    f0 = np.asarray(get_workload(cfg).load()[TABLE].columns["F0"])
    local, lb = mc_block_geometry(ROWS, 1, 4)
    for d in range(4):
        keys = np.arange(local) * 4 + d
        np.testing.assert_array_equal(
            f0[d * lb:d * lb + local],
            np.asarray(_field_fingerprint(keys, 0)))


def _group_hlo(cfg) -> str:
    """Compiled HLO text of the served C-epoch group, as `ServerNode`
    builds it, on the CPU devices (metadata is the same on any backend)."""
    from deneva_tpu.cc import get_backend
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.engine.epoch import make_dist_group
    wl, be = get_workload(cfg), get_backend(cfg.cc_alg)
    k, _t, s = wl.to_wire(wl.generate(jax.random.PRNGKey(0), 1))
    width, n_scal = k.shape[1], s.shape[1]
    group = make_dist_group(cfg, wl, be, width, n_scal)
    state = (jax.eval_shape(wl.load),
             jax.eval_shape(lambda: be.init_state(cfg)),
             jax.eval_shape(lambda: init_device_stats(2)))
    n = cfg.pipeline_epochs * cfg.epoch_batch
    feed = [jax.ShapeDtypeStruct((n * m,), dt) for m, dt in (
        (1, np.bool_), (1, np.int32), (width, np.int32), (width, np.int8),
        (n_scal, np.int32))]
    if cfg.device_parts > 1:
        with M.use_mesh(M.make_mesh(cfg.device_parts)):
            return group.lower(*state, *feed).compile().as_text()
    return group.lower(*state, *feed).compile().as_text()


_TOY = dict(sim_full_row="true", synth_table_size=1 << 14, epoch_batch=1024,
            pipeline_epochs=2, max_txn_in_flight=8192,
            client_batch_size=1024)
_COLLECTIVE = re.compile(
    r" (all-to-all|all-gather|all-reduce|collective-permute)[a-z\-]*\(")


def _scopes(hlo: str) -> set[str]:
    return {part for name in re.findall(r'op_name="([^"]*)"', hlo)
            for part in name.split("/") if part.startswith(("ep.", "grp."))}


def test_mesh_group_carries_exchange_and_plan_scopes():
    hlo = _group_hlo(served_cfg(device_parts=4, **_TOY))
    assert {"ep.exchange", "ep.plan", "ep.read", "ep.write"} <= _scopes(hlo)
    seen = set()
    for ln in hlo.splitlines():
        m = _COLLECTIVE.search(ln)
        if not m:
            continue
        seen.add(m.group(1))
        name = re.search(r'op_name="([^"]*)"', ln)
        # a merged all-reduce may keep no op_name: its consumers carry
        # the scope (benchmark/phase_reduce.hlo_scopes reads it there)
        assert name is None or "ep.exchange" in name.group(1).split("/"), ln
        if m.group(1) in ("all-to-all", "all-gather"):
            assert name is not None, ln
    assert {"all-to-all", "all-gather", "all-reduce"} <= seen
    # the per-shard plan sort sits under ep.plan, the exchange's own
    # sorts under ep.exchange alone
    sorts = [re.search(r'op_name="([^"]*)"', ln).group(1).split("/")
             for ln in hlo.splitlines() if re.search(r" sort\(", ln)]
    inner = [next(p for p in reversed(s) if p.startswith("ep."))
             for s in sorts]
    assert inner.count("ep.plan") == 1 and inner.count("ep.exchange") == 3
    assert all("ep.exchange" in s for s in sorts)


def test_no_mesh_scope_or_collective_leaks_into_the_one_chip_program():
    hlo = _group_hlo(served_cfg(device_parts=1, **_TOY))
    assert _scopes(hlo) == {"ep.decode", "ep.plan", "ep.read", "ep.write",
                            "ep.stats", "grp.pack"}
    assert not _COLLECTIVE.search(hlo)


@pytest.mark.parametrize("width,pair_cap", [(10, 20_480), (16, 32_768)])
def test_a2a_bytes_are_the_cross_chip_blocks_at_the_batchs_width(
        width, pair_cap):
    from deneva_tpu.ops import mc_pair_cap
    cfg = served_cfg(device_parts=4, epoch_batch=16384)
    assert mc_pair_cap(16384, width, 4, cfg.mc_plan_capacity) == pair_cap
    assert M.a2a_bytes_per_epoch(cfg, 16384, width) == 4 * 3 * pair_cap * 9
    # no sharded plan (capacity off, or slices that cut a txn): no lanes
    assert M.a2a_bytes_per_epoch(
        served_cfg(device_parts=4, mc_plan_capacity=0.0), 16384, width) == 0
