"""`benchmark/run.py` without a chip: parsing and metric arithmetic from
canned closing lines (window and whole-run counters kept apart), the
answer checks, the traffic generator's seeding, BENCHMARK.json against
the contract's limits, and the no-JAX / no-chip / no-package exits."""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT

DEVICE = ('[device] node=0 {"platform": "tpu", "kind": "TPU v5 lite", '
          '"count": 1, "load_s": 9.5, "warm_s": 1.6, "compile_cnt": 12, '
          '"compile_s": 3.6, "cache_hits": 12, "window_compile_cnt": 0, '
          '"run_commit_cnt": 18000000, "run_abort_cnt": 0}')
SUMMARY = ("node 0 (server): [summary] total_runtime=21,tput=700000,"
           "txn_cnt=14700000,total_txn_commit_cnt=14700000,"
           "total_txn_abort_cnt=0,unique_txn_abort_cnt=0,abort_rate=0.25,"
           "epoch_cnt=9600,net_bytes_rcvd=1100000000,net_bytes_sent=160000000,"
           "txn_retries_mean=0.4,worker_idle_time=6,worker_process_time=18")
OUT = "\n".join(["noise", DEVICE, SUMMARY,
                 '[memory] {"memory_peak_bytes": 11000000000, '
                 '"memory_in_use_bytes": 8000000000, '
                 '"bytes_limit": 16000000000}',
                 '[trace] {"window_s": 2.5, "started_at_s": 3.0}'])


def _client(i, tmp_path, **over):
    lat = tmp_path / f"lat{i}.npy"
    np.save(lat, np.arange(1, 101, dtype=np.float32) + 100 * i)
    c = dict(client=i, sent=8_000_000, acked=7_500_000, win_sent=7_000_000,
             win_acked=7_000_000, window_s=20.0, run_s=23.4, cap=1 << 20,
             window_closed=True, lat_path=str(lat), net={})
    c.update(over)
    return c


def _res(bench_run, tmp_path, **over):
    return dict(server=bench_run.parse_server(OUT),
                clients=[_client(0, tmp_path, **over), _client(1, tmp_path)],
                wall_s=61.0, seconds=20.0, dir=str(tmp_path),
                fields=dict(req_per_query=10, tup_size=100,
                            pipeline_epochs=32))


def test_closing_lines_parse(bench_run):
    srv = bench_run.parse_server(OUT)
    assert srv["info"]["kind"] == "TPU v5 lite"
    assert srv["summary"]["epoch_cnt"] == 9600
    assert srv["memory"]["memory_peak_bytes"] == 11_000_000_000
    assert srv["trace"]["window_s"] == 2.5
    assert bench_run.parse_client('x\n[client] {"sent": 3}\n') == {"sent": 3}


def test_metric_arithmetic_keeps_window_and_whole_run_apart(
        bench_run, tmp_path):
    cell = bench_run.load_cell("ycsb_fullrow_occ.medium")
    res = _res(bench_run, tmp_path)
    ctx = bench_run.metric_context(cell, res, None)
    e2e = {k: v["value"] for k, v in
           bench_run.compute_metrics(cell, ctx, traced=False).items()}
    # the clients' window counts over the clients' window, not the
    # server's 14.7M window commits nor the 18M whole-run commits
    assert e2e["served_txn_per_s"] == 14_000_000 / 20.0
    assert e2e["setup_s"] == 41.0
    assert e2e["ack_latency_p50_ms"] == pytest.approx(100.5)
    assert e2e["ack_latency_p99_ms"] == pytest.approx(198.01, abs=0.01)
    trace = dict(busy_s=2.0, window_s=2.5, epochs=960.0, groups=30.0,
                 group_busy_s=1.92, breakdown={})
    ctx = bench_run.metric_context(cell, res, trace)
    lay = {k: v["value"] for k, v in
           bench_run.compute_metrics(cell, ctx, traced=True).items()}
    assert lay["client.sent_txn_per_s"] == 700_000
    # whole-run bytes over whole-run commits
    assert lay["transport.bytes_per_txn"] == 1_260_000_000 / 18_000_000
    assert lay["host.idle_share"] == 25.0
    assert lay["group.txn_per_epoch"] == 18_000_000 / 9600
    assert lay["group.device_ms_per_epoch"] == 2.0
    assert lay["device.idle_share"] == pytest.approx(20.0)
    assert lay["cc.retries_per_txn"] == 0.4 and lay["cc.abort_rate"] == 25.0
    # 1875 txn x 10 accesses x 100 B = 1.875 MB needed in 2 ms at 819 GB/s
    assert lay["epoch_group_hbm_roofline"] == pytest.approx(
        100 * 1.875e6 / (2e-3 * 819e9))
    # an OCC-only metric is left out of a TPU_BATCH cell, and a reader
    # with nothing to read (no trace) returns nothing
    hot = bench_run.load_cell("ycsb_fullrow_tpubatch.hot")
    ctx = bench_run.metric_context(hot, res, None)
    lay = bench_run.compute_metrics(hot, ctx, traced=True)
    assert "cc.abort_rate" not in lay and "device.idle_share" not in lay
    assert "host.idle_share" in lay


def test_check_served_catches_each_wrong_answer(bench_run, tmp_path):
    def failed(res, alg="TPU_BATCH"):
        return [n for n, v, lim in bench_run.check_served("t", alg, res)
                if v > lim]
    assert failed(_res(bench_run, tmp_path)) == []
    res = _res(bench_run, tmp_path)
    res["server"]["info"]["platform"] = "cpu"
    assert failed(res) == ["t.server_not_on_tpu"]
    res = _res(bench_run, tmp_path)
    res["server"]["summary"]["total_txn_commit_cnt"] = 0.0
    assert failed(res) == ["t.window_commits_missing"]
    assert failed(_res(bench_run, tmp_path, acked=0)) == [
        "t.clients_never_acked"]
    assert failed(_res(bench_run, tmp_path, acked=11_000_000)) == [
        "t.acks_beyond_commits"]
    assert failed(_res(bench_run, tmp_path, window_closed=False)) == [
        "t.client_window_cut_short"]
    res = _res(bench_run, tmp_path)
    res["server"]["info"]["window_compile_cnt"] = 2
    assert failed(res) == ["t.window_compiles"]
    res = _res(bench_run, tmp_path)
    res["server"]["info"]["run_abort_cnt"] = 3
    assert failed(res) == ["t.deterministic_aborts"]
    assert failed(res, "OCC") == []         # OCC may abort
    res = _res(bench_run, tmp_path)
    del res["server"]["info"]
    assert failed(res) == ["t.nodes_not_reporting"]


def _ring_rows(loadgen, seed, client, theta=0.9, n=4096, txn_write=0.5):
    r = loadgen.make_ring(seed, client, n, 256, 10, 1 << 16, theta, 0.5,
                          txn_write)
    k = np.concatenate([k for k, _ in r])
    t = np.concatenate([t for _, t in r])
    return r, np.concatenate([k, t.astype(np.int32)], axis=1)


def test_generator_is_seeded_and_keeps_the_sources_shapes(loadgen):
    big = 2**31 + 5
    (a, ra), (_, rb) = _ring_rows(loadgen, big, 0), _ring_rows(loadgen, big, 0)
    assert len(a) == 16 and a[0][0].shape == (256, 10)
    assert a[0][0].flags["C_CONTIGUOUS"] and a[0][1].dtype == np.int8
    assert (ra == rb).all()                     # same seed, same inputs
    assert not (ra == _ring_rows(loadgen, big, 1)[1]).all()   # clients differ
    keys = ra[:, :10].ravel()
    assert keys.min() >= 0 and keys.max() < 1 << 16
    # skewed: key 0 is the hottest; uniform: it is not
    assert np.bincount(keys).argmax() == 0
    u = _ring_rows(loadgen, 1, 0, theta=0.0)[1][:, :10].ravel()
    assert np.bincount(u).max() < 12


def test_another_seed_draws_other_transactions(loadgen):
    """The seed draws WHICH transactions a run sends, not only their
    order: two seeds' rings are different multisets."""
    ra, rd = _ring_rows(loadgen, 5, 0)[1], _ring_rows(loadgen, 77, 0)[1]
    def as_set(r):
        return {tuple(x) for x in r.tolist()}
    assert len(as_set(ra) & as_set(rd)) < len(ra) // 2
    # a ring over several chunks is one stream: no chunk repeats another
    small = loadgen.RING_CHUNK
    try:
        loadgen.RING_CHUNK = 1024
        rc = _ring_rows(loadgen, 5, 0)[1]
    finally:
        loadgen.RING_CHUNK = small
    assert len(as_set(rc[:1024]) & as_set(rc[1024:2048])) < 512


@pytest.mark.parametrize("txn_write", [0.5, 1.0])
def test_request_types_follow_the_sources_two_draws(loadgen, txn_write):
    """TXN_WRITE_PERC gates a whole transaction, TUP_WRITE_PERC each
    request of one that may write (`ycsb_query.cpp` r_twr)."""
    types = _ring_rows(loadgen, 9, 0, n=1 << 14, txn_write=txn_write
                       )[1][:, 10:]
    assert set(np.unique(types)) == {1, 2}
    read_only = (types == 1).all(axis=1).mean()
    # a transaction that may write is all reads with probability 2**-10
    assert abs(read_only - (1 - txn_write + txn_write / 1024)) < 0.02
    writers = types[(types == 2).any(axis=1)]
    assert abs((writers == 2).mean() - 0.5) < 0.02
    assert abs((types == 2).mean() - 0.5 * txn_write) < 0.02


def test_an_arrival_law_the_generator_does_not_have_is_refused(
        loadgen, tmp_path):
    spec = dict(traffic=dict(arrival="paced", clients=2, warmup_secs=1.0,
                             ring_txns=256),
                transport={}, seconds=1.0,
                fields=dict(client_batch_size=128, req_per_query=10,
                            max_txn_in_flight=4096))
    with pytest.raises(ValueError, match="closed loop only"):
        loadgen.run_client(spec, 0)


# ---- BENCHMARK.json against the contract's limits ----------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_benchmark_json_keeps_to_the_contract():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    b = json.load(open(path))
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert all(_line(w) for w in b["command"]) and len(b["command"]) <= 32
    assert b["command"][1].startswith(b["paths"][0] + "/")
    cfgs = {c["name"] for c in b["configs"]}
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files) == len(cfgs)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        conf = json.load(open(os.path.join(ROOT, c["file"])))
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert {"fields", "assumed", "guarantees", "reference"} <= set(conf)
        # the source's shapes are kept: row width, requests, no width cut
        f = conf["fields"]
        assert (f["tup_size"], f["field_per_tuple"], f["req_per_query"],
                f["sim_full_row"]) == (100, 10, 10, "true")
        assert os.path.exists(os.path.join(
            BENCH, "references", conf["reference"] + ".py"))
    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) \
        == len(cells)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4) and _line(w["why"])
        tr = json.load(open(os.path.join(BENCH, "traffic",
                                         w["traffic"] + ".json")))
        # the source's operation mix (ycsb_skew), a closed loop
        assert (tr["read_share"], tr["txn_write_share"], tr["arrival"]) \
            == (0.5, 0.5, "closed")
    assert {c["config"] for c in b["workloads"]} == cfgs
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = list(e2e) + [m["name"] for m in b["per_layer"]]
    assert len(set(names)) == len(names)
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert _line(m["layer"])
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_file_of_the_benchmark_has_a_contract_name():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in ("benchmark", "tests/benchmark"):
        for d, _, fs in os.walk(os.path.join(ROOT, base)):
            if "__pycache__" in d:
                continue
            for f in fs:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel


# ---- exits -------------------------------------------------------------

def test_parent_imports_no_jax():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, runpy; sys.argv=['run.py']; "
         "m = runpy.run_path('benchmark/run.py', run_name='bench_run'); "
         "m['load_cell']('ycsb_fullrow_tpubatch.hot'); "
         "import deneva_tpu.config, deneva_tpu.runtime.native; "
         "print('jax' in sys.modules)"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.stdout.strip() == "False", proc.stderr[-2000:]


def test_without_a_chip_the_command_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "ycsb_fullrow_occ.medium", "--seed", "3000000000", "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU was found" in proc.stderr


def test_alone_with_benchmark_json_it_exits_nonzero_and_prints_no_result(
        tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "ycsb_fullrow_tpubatch.hot", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "not beside" in proc.stderr
