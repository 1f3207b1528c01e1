"""A deployment is new files: a pretended later PR adds, to a copy of
`BENCHMARK.json` and `benchmark/`, a configuration of ANOTHER SCHEMA
(its `fields` have none of YCSB's `req_per_query`, `tup_size`,
`field_per_tuple`), a generator with another message layout, a traffic
file with another mix, a reference, a cell and two per-layer metrics,
one of them its own `*_roofline` with its own bytes function — new
files and new entries only — and the contract's three functions,
`load_cell`, `server_fields` and `compute_metrics` take them as they
are: the traced metrics of the new cell raise nothing and every metric
that lists no cells has a value there (a check refuses a new cell in
which one reads nothing).  The same copy with a shape cut, an accepted
metric dropped, an accepted mix changed or an accepted entry edited is
refused."""

import hashlib
import json
import os
import shutil

import numpy as np
import pytest

from bench_contract import (check_accepted, check_benchmark,
                            check_per_layer, load_module)
from conftest import BENCH, ROOT
from test_bench_phases import PHASE
from test_bench_phases import SUMMARY as STAGE_KEYS
from test_bench_run import TRACE, _res

CELL = "toy_narrow_calvin.allwrite"
CONFIG = dict(
    name="toy-narrow-calvin",
    source="a made-up benchmark, section 2: 64 B records, 4 keys a transaction",
    deployment="one partition on one chip, 3 clients",
    fields=dict(workload="TOY", cc_alg="CALVIN", node_cnt=1,
                synth_table_size=1 << 20, toy_record_bytes=64,
                toy_keys_per_txn=4, epoch_batch=4096, pipeline_epochs=8,
                max_txn_in_flight=1 << 16, client_batch_size=512),
    shapes=dict(toy_record_bytes=64, toy_keys_per_txn=4),
    reduced=dict(synth_table_size="16M rows in the source -> 1M"),
    assumed=["epoch_batch: this repo's default"],
    guarantees=dict(isolation="serializable", aborts="none (Calvin)",
                    acknowledgement="committed only", durability="none"),
    reference="toy_serial")
TRAFFIC = dict(write_share=1.0, burst=3, arrival="closed", clients=3,
               ring_txns=4096, warmup_secs=1.5)
GENERATOR = '''
"""A toy workload's traffic: three columns, a 16-byte header."""
import struct
import numpy as np
_HDR = struct.Struct("<IIII")


def check(traffic):
    if set(traffic) != {"write_share", "burst", "arrival", "clients",
                        "ring_txns", "warmup_secs"}:
        raise ValueError("toy traffic: unknown or missing keys")
    if traffic["arrival"] != "closed" or not 0 <= traffic["write_share"] <= 1:
        raise ValueError("toy traffic: closed loop, a share in [0, 1]")


def server_fields(traffic):
    return dict(zipf_theta=0.0, write_perc=traffic["write_share"],
                read_perc=round(1.0 - traffic["write_share"], 6))


def make_ring(spec, client):
    tr, f = spec["traffic"], spec["fields"]
    rng = np.random.default_rng([int(spec["seed"]), int(client)])
    n, b, w = int(tr["ring_txns"]), int(f["client_batch_size"]), \\
        int(f["toy_keys_per_txn"])
    keys = rng.integers(0, int(f["synth_table_size"]), (n, w), np.int64)
    writes = rng.random((n, w)) < tr["write_share"]
    amount = rng.integers(1, 100, n).astype(np.int16)
    return [(keys[i:i + b], writes[i:i + b], amount[i:i + b])
            for i in range(0, n - b + 1, b)]


def block_parts(tags, keys, writes, amount):
    n, w = keys.shape
    return [_HDR.pack(0x70, n, w, 1), tags, amount, keys,
            np.packbits(writes, axis=1)]
'''
REFERENCE = '''
"""The toy workload's plain reference."""


def verify(log, fields, info, verdicts=None):
    return [("digest_mismatch", 0.0, 0.0)], dict(epochs=0)
'''
READERS = {
    "client.acks_per_client": '''
"""Transactions acked to a client, whole run, the clients' mean: it reads
what every cell's clients report, so it lists no cells."""


def read(ctx):
    cl = ctx["clients"]
    return sum(c["acked"] for c in cl) / len(cl) if cl else None
''',
    "toy_group_hbm_roofline": '''
"""The toy schema's epoch against the chip's memory roofline, with the
toy schema's own bytes; nothing without a trace."""


def toy_epoch_bytes(committed_txns, keys_per_txn, record_bytes):
    """Every committed key reads or writes one whole record once."""
    return committed_txns * keys_per_txn * record_bytes


def read(ctx):
    t, s, info = ctx["trace"], ctx["server"]["summary"], ctx["server"]["info"]
    if not t or not t.get("epochs") or not s.get("epoch_cnt"):
        return None
    f = ctx["fields"]
    need = toy_epoch_bytes(info["run_commit_cnt"] / s["epoch_cnt"],
                           int(f["toy_keys_per_txn"]),
                           int(f["toy_record_bytes"]))
    return 100.0 * need / (
        t["group_busy_s"] / t["epochs"]
        * ctx["peaks"].peak_for(info["kind"])["hbm_bytes_per_s"])
''',
}
METRICS = [
    dict(name="client.acks_per_client", unit="txn", better="higher",
         source="program_counter", layer="client",
         moves="served_txn_per_s"),
    dict(name="toy_group_hbm_roofline", unit="%", better="higher",
         source="device_trace", layer="toy kernels",
         moves="served_txn_per_s", workloads=[CELL]),
]
# what a new deployment's PROGRAM prints in its `[summary]` so that every
# per-layer metric that lists no cells has a value in its cell (PERF.md
# section 4): the stage clock's keys and the row scatter's lane counter
PRINTED = dict(STAGE_KEYS, write_scatter_lane_cnt=150_000_000.0)


def _tree_digest(root):
    """Every file of the tree but BENCHMARK.json and PERF.md, by path."""
    h = {}
    for d, _, fs in os.walk(root):
        for f in fs:
            p = os.path.join(d, f)
            rel = os.path.relpath(p, root)
            if rel not in ("BENCHMARK.json", "PERF.md"):
                with open(p, "rb") as fh:
                    h[rel] = hashlib.sha256(fh.read()).hexdigest()
    return h


def _write(root, rel, text):
    path = os.path.join(root, rel)
    assert not os.path.exists(path), rel        # new files only
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text if isinstance(text, str) else json.dumps(text, indent=2))


def _later_pr(tmp_path, break_it):
    """The copy with the deployment added; returns its root."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copy(os.path.join(ROOT, "PERF.md"), root)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = _tree_digest(root)
    config, entry_reduced = dict(CONFIG), ["synth_table_size"]
    if break_it == "shape_cut":
        config["reduced"] = dict(CONFIG["reduced"],
                                 toy_record_bytes="100 -> 64")
        entry_reduced = ["synth_table_size", "toy_record_bytes"]
    _write(root, "benchmark/configs/toy-narrow-calvin.json", config)
    _write(root, "benchmark/traffic/allwrite.json", TRAFFIC)
    _write(root, "benchmark/generators/toy.py", GENERATOR)
    _write(root, "benchmark/references/toy_serial.py", REFERENCE)
    for name, text in READERS.items():
        _write(root, f"benchmark/metrics/{name}.py", text)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(dict(
        name=CONFIG["name"], source=CONFIG["source"],
        file="benchmark/configs/toy-narrow-calvin.json",
        reduced=entry_reduced, why="another schema, another wire layout"))
    bench["workloads"].append(dict(
        name=CELL, config=CONFIG["name"], traffic="allwrite", chips=1,
        why="1M x 64 B records, closed loop, 3 clients, every key written"))
    bench["per_layer"] += METRICS
    if break_it == "accepted_entry_edited":
        for m in bench["per_layer"]:
            if m["name"] == "exchange_ici_roofline":
                m["workloads"] = m["workloads"] + [CELL]
    if break_it == "metric_dropped":
        bench["per_layer"] = [m for m in bench["per_layer"]
                              if m["name"] != "host.idle_share"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=2)
    # PERF.md section 3 is the PR's to keep: it names the new layer
    with open(os.path.join(root, "PERF.md")) as f:
        perf = f.read()
    with open(os.path.join(root, "PERF.md"), "w") as f:
        f.write(perf.replace("\n## 4.", "\n| toy kernels | `workloads/` | "
                             "`toy_group_hbm_roofline` |\n\n## 4.", 1))
    assert _tree_digest(root).items() >= before.items()     # none edited
    if break_it == "hot_mix_changed":
        path = os.path.join(root, "benchmark", "traffic", "hot.json")
        with open(path) as f:
            hot = json.load(f)
        with open(path, "w") as f:
            json.dump(dict(hot, txn_write_share=1.0), f)
    return root


def _drive(root, tmp_path):
    """The copy's own `run.py` on the added cell, as a run drives it."""
    run = load_module(os.path.join(root, "benchmark", "run.py"))
    assert run.ROOT == root
    cell = run.load_cell(CELL)
    fields = run.server_fields(cell, 2**31 + 5, dict(logging="false"))
    assert list(fields)[-6:] == ["write_perc", "read_perc", "client_node_cnt",
                                 "device_parts", "seed", "logging"]
    assert (fields["client_node_cnt"], fields["write_perc"],
            fields["toy_record_bytes"], fields["seed"]) == (3, 1.0, 64, 5)
    # YCSB's, not every launch's
    assert not {"txn_write_perc", "req_per_query", "tup_size",
                "field_per_tuple"} & set(fields)
    gen = run.generator(fields)
    spec = dict(seed=9, fields=fields, traffic=cell["traffic_file"])
    ring = gen.make_ring(spec, 1)
    assert len(ring) == 8 and [a.shape for a in ring[0]] == [
        (512, 4), (512, 4), (512,)]
    # the client's own expression (`loadgen.run_client`), 200 of a block
    parts = gen.block_parts(np.arange(200), *(a[:200] for a in ring[3]))
    assert len(parts[0]) == 16 and parts[3].shape == (200, 4)
    # a traced run's context of the new cell: the clients' reports, the
    # closing lines of a program that prints `PRINTED`, the reduced
    # trace, and the phase reduction beside it (`phase_reduce.cached`)
    res = _res(run, tmp_path)
    res["fields"] = dict(fields, pipeline_epochs=8,
                         log_dir=str(tmp_path / "tlog"))
    res["server"]["summary"].update(PRINTED)
    os.makedirs(tmp_path / "timed")
    with open(tmp_path / "timed" / "phase_reduce.json", "w") as f:
        json.dump(PHASE, f)
    lay = run.compute_metrics(cell, run.metric_context(cell, res, TRACE),
                              traced=True)
    assert lay["client.acks_per_client"] == {"value": 7_500_000.0,
                                             "unit": "txn"}
    # 1875 txns x 4 keys x 64 B needed in 2 ms at 819 GB/s: the toy's
    # own bytes, and YCSB's roofline is not asked (it lists its cells)
    assert lay["toy_group_hbm_roofline"]["value"] == pytest.approx(
        100 * 1875 * 4 * 64 / (2e-3 * 819e9))
    assert "epoch_group_hbm_roofline" not in lay
    listless = [m["name"] for m in cell["bench"]["per_layer"]
                if "workloads" not in m]
    assert len(listless) > 15 and not set(listless) - set(lay)
    assert "cc.abort_rate" not in lay and "exec.read_lanes_per_epoch" \
        not in lay                              # not this cell's: left out
    e2e = run.compute_metrics(cell, run.metric_context(cell, res, None),
                              traced=False)
    assert set(e2e) == {m["name"] for m in cell["bench"]["end_to_end"]}
    # an accepted cell of the copy reports the appended metric that lists
    # no cells, and not the one that lists the new cell
    hot = run.load_cell("ycsb_fullrow_tpubatch.hot")
    res["fields"].update(req_per_query=10, tup_size=100)
    lay = run.compute_metrics(hot, run.metric_context(hot, res, TRACE),
                              traced=True)
    assert "client.acks_per_client" in lay
    assert "toy_group_hbm_roofline" not in lay
    assert "epoch_group_hbm_roofline" in lay
    # the stated guarantee, not the backend's name, arms the abort check
    res["server"]["info"]["run_abort_cnt"] = 1
    assert "t.deterministic_aborts" in [
        n for n, v, lim in run.check_served("t", cell["config_file"], res)
        if v > lim]


CHECKS = (check_benchmark, check_per_layer, check_accepted)


@pytest.mark.parametrize("break_it,refused_by", [
    (None, None),
    ("shape_cut", check_benchmark),
    ("metric_dropped", check_per_layer),
    ("hot_mix_changed", check_accepted),
    ("accepted_entry_edited", check_accepted),
], ids=["sound", "shape_cut", "metric_dropped", "hot_mix_changed",
        "accepted_entry_edited"])
def test_a_later_pr_adds_a_deployment_as_new_files_only(
        tmp_path, break_it, refused_by):
    (tmp_path / "tree").mkdir()
    root = _later_pr(tmp_path / "tree", break_it)
    if refused_by is None:
        for check in CHECKS:
            check(root)
        _drive(root, tmp_path)
        return
    with pytest.raises(AssertionError):
        refused_by(root)
    # and only by that: the rest of the contract still holds
    for check in CHECKS:
        if check is not refused_by:
            check(root)
