"""The four-chip deployment `ycsb-fullrow-tpubatch-dp4` (PR 29): its
cell through the harness's own functions, and the four per-layer readers
it brings on hand-made contexts — a value where the program prints the
counter or the trace holds the scope, None where it does not (a one-chip
run, a tree from before them)."""

import json
import os

import pytest

from bench_contract import ACCEPTED_ENTRIES, check_accepted
from conftest import ROOT

CELL = "ycsb_fullrow_tpubatch_dp4.hot"
# D x (D-1) blocks of pair_cap lanes x 9 B at the cell's shapes: slices of
# 4,096 txns x 10 lanes, blocks of twice the even share
A2A = 4 * 3 * 20_480 * 9
MESH = dict(mesh_shards=4.0, mesh_a2a_bytes=float(A2A), defer_cnt=0.0,
            total_txn_commit_cnt=136_314_880.0)
ONE_CHIP = dict(defer_cnt=0.0, total_txn_commit_cnt=136_314_880.0)
INFO = dict(kind="TPU v5 lite", count=4)


def _ctx(tmp_path, summary, reduced):
    """A traced run's context as `run.metric_context` makes it, with the
    phase reduction already beside the trace (`phase_reduce.cached`
    reads it from `<run>/timed/phase_reduce.json`)."""
    if reduced is not None:
        os.makedirs(tmp_path / "timed", exist_ok=True)
        with open(tmp_path / "timed" / "phase_reduce.json", "w") as f:
            json.dump(reduced, f)
    return dict(server=dict(summary=summary, info=INFO),
                trace={"epochs": 480} if reduced is not None else None,
                fields=dict(log_dir=str(tmp_path / "tlog"),
                            pipeline_epochs=32))


REDUCED = dict(groups=15, epochs=480, group_s=1.2,
               scope_s={"ep.exchange": 0.24, "ep.read": 0.5},
               phase_s=dict(plan=0.1, validate=0.0, read=0.5, write=0.3,
                            other=0.3))
NO_SCOPE = dict(REDUCED, scope_s={"ep.read": 0.5})


@pytest.mark.parametrize("name,summary,reduced,want", [
    ("phase.exchange_ms_per_epoch", MESH, REDUCED, 0.5),
    ("phase.exchange_ms_per_epoch", ONE_CHIP, NO_SCOPE, None),
    ("phase.exchange_ms_per_epoch", MESH, {}, None),
    ("phase.exchange_ms_per_epoch", MESH, None, None),
    ("mesh.a2a_bytes_per_epoch", MESH, None, float(A2A)),
    ("mesh.a2a_bytes_per_epoch", ONE_CHIP, None, None),
    ("cc.defers_per_txn", MESH, None, 0.0),
    ("cc.defers_per_txn", dict(MESH, defer_cnt=13_631_488.0), None, 0.1),
    ("cc.defers_per_txn", dict(total_txn_commit_cnt=5.0), None, None),
    ("cc.defers_per_txn", dict(defer_cnt=3.0, total_txn_commit_cnt=0.0),
     None, None),
    # one chip sends A2A / 4 = 552,960 B in 0.5 ms against 200 GB/s
    ("exchange_ici_roofline", MESH, REDUCED,
     100.0 * (A2A / 4) / (0.5e-3 * 200e9)),
    ("exchange_ici_roofline", ONE_CHIP, REDUCED, None),
    ("exchange_ici_roofline", dict(MESH, mesh_a2a_bytes=0.0), REDUCED, None),
    ("exchange_ici_roofline", MESH, NO_SCOPE, None),
    ("exchange_ici_roofline", MESH, None, None),
], ids=["exchange", "exchange_one_chip", "exchange_old_trace",
        "exchange_untraced", "a2a", "a2a_one_chip", "defers_none",
        "defers_some", "defers_no_counter", "defers_empty_window",
        "ici", "ici_one_chip", "ici_replicated_plan", "ici_no_scope",
        "ici_untraced"])
def test_the_four_readers(bench_run, tmp_path, name, summary, reduced, want):
    read = bench_run.load_by_name("metrics", name).read
    got = read(_ctx(tmp_path, summary, reduced))
    assert got == (want if want is None else pytest.approx(want))


def test_the_ici_share_stays_far_under_the_limit_a_check_refuses(
        bench_run, tmp_path):
    """Even an exchange that took no longer than the wire alone would
    reads 100%, not more: the bytes are one chip's, the peak one chip's."""
    wire_s = (A2A / 4) / 200e9
    r = dict(REDUCED, scope_s={"ep.exchange": wire_s * 480})
    read = bench_run.load_by_name("metrics", "exchange_ici_roofline").read
    assert read(_ctx(tmp_path, MESH, r)) == pytest.approx(100.0)


def test_an_unknown_chip_has_no_ici_peak_to_divide_by(bench_run, tmp_path):
    ctx = _ctx(tmp_path, MESH, REDUCED)
    ctx["server"]["info"] = dict(kind="TPU v9", count=4)
    read = bench_run.load_by_name("metrics", "exchange_ici_roofline").read
    with pytest.raises(KeyError, match="no ICI peak"):
        read(ctx)


def test_the_cell_is_the_four_chip_deployment_and_its_config_validates(
        bench_run):
    """`load_cell` / `server_fields` on the REAL cell: four partitions,
    25,165,824 rows of the source's width, the accepted traffic, and
    launch arguments the program's `Config` accepts."""
    from deneva_tpu.config import Config
    from deneva_tpu.ops import mc_pair_cap
    from deneva_tpu.parallel.mesh import a2a_bytes_per_epoch
    cell = bench_run.load_cell(CELL)
    assert (cell["chips"], cell["traffic"]) == (4, "hot")
    conf = cell["config_file"]
    one = bench_run.load_cell("ycsb_fullrow_tpubatch.hot")["config_file"]
    assert conf["fields"] == dict(one["fields"], synth_table_size=25_165_824)
    assert conf["shapes"] == one["shapes"]
    assert conf["guarantees"]["aborts"].startswith("none")
    assert "partitions" in conf["guarantees"]
    assert conf["reference"] == "ycsb_serial" and "verdicts" not in conf
    f = bench_run.server_fields(cell, 3_000_000_019, {})
    assert f["device_parts"] == 4 and f["synth_table_size"] == 25_165_824
    assert f["zipf_theta"] == 0.9 and f["client_node_cnt"] == 2
    cfg = Config.from_args([f"--{k}={v}" for k, v in f.items()]
                           ).replace(node_id=0, part_cnt=1)
    assert cfg.device_parts == 4 and cfg.mc_plan_capacity == 2.0
    # what the server will print as `mesh_a2a_bytes` in this cell
    assert mc_pair_cap(cfg.epoch_batch, cfg.req_per_query, 4, 2.0) == 20_480
    assert a2a_bytes_per_epoch(cfg, cfg.epoch_batch, cfg.req_per_query) \
        == A2A


def test_the_new_metrics_list_only_the_new_cell():
    """PR 29's four entries, looked up BY NAME: each lists exactly this
    cell, moves the rate and belongs to the kernels' layer.  The pins
    are `bench_contract.ACCEPTED_ENTRIES`'s, which `check_accepted`
    holds on any tree; how many entries or four-chip cells there are is
    nobody's pin (`check_benchmark` bounds the four-chip share)."""
    want = dict(workloads=[CELL], moves="served_txn_per_s",
                layer="CC and executor kernels")
    for name in ("cc.defers_per_txn", "exchange_ici_roofline",
                 "mesh.a2a_bytes_per_epoch", "phase.exchange_ms_per_epoch"):
        assert ACCEPTED_ENTRIES[name] == want, name
    check_accepted(ROOT)
