"""`benchmark/run.py` without a chip: parsing and metric arithmetic from
canned closing lines (window and whole-run counters kept apart), the
answer checks, the traffic generator's seeding, BENCHMARK.json against
the contract's limits, and the no-JAX / no-chip / no-package exits."""

import hashlib
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench_contract import check_accepted, check_benchmark
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
    ctx = bench_run.metric_context(cell, res, TRACE)
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
    assert lay["epoch_group_hbm_roofline"] == pytest.approx(ONE_CHIP_SHARE)
    # an OCC-only metric is left out of a TPU_BATCH cell, and a reader
    # with nothing to read (no trace) returns nothing
    hot = bench_run.load_cell("ycsb_fullrow_tpubatch.hot")
    ctx = bench_run.metric_context(hot, res, None)
    lay = bench_run.compute_metrics(hot, ctx, traced=True)
    assert "cc.abort_rate" not in lay and "device.idle_share" not in lay
    assert "host.idle_share" in lay


TRACE = dict(busy_s=2.0, window_s=2.5, epochs=960.0, groups=30.0,
             group_busy_s=1.92, breakdown={})
# 1875 txn x 10 accesses x 100 B = 1.875 MB an epoch, 2 ms of device time
# an epoch (a chip's mean), 819 GB/s a chip
ONE_CHIP_SHARE = 100 * 1.875e6 / (2e-3 * 819e9)


@pytest.mark.parametrize("shards,want", [
    (None, ONE_CHIP_SHARE),             # a server that prints no mesh
    (1.0, ONE_CHIP_SHARE),
    (4.0, ONE_CHIP_SHARE / 4),          # the cluster's bytes, four chips
], ids=["no_mesh", "one_shard", "four_shards"])
def test_the_roofline_is_a_chips_bytes_over_a_chips_time_and_peak(
        bench_run, tmp_path, shards, want):
    """`run_commit_cnt` is the cluster's, `group_busy_s` a chip's mean:
    the needed bytes are divided by the chips that moved them."""
    cell = bench_run.load_cell("ycsb_fullrow_tpubatch_dp4.hot")
    res = _res(bench_run, tmp_path)
    if shards is not None:
        res["server"]["summary"]["mesh_shards"] = shards
    read = bench_run.load_by_name("metrics", "epoch_group_hbm_roofline").read
    assert read(bench_run.metric_context(cell, res, TRACE)) \
        == pytest.approx(want)
    assert read(bench_run.metric_context(cell, res, None)) is None


def test_the_roofline_is_not_asked_in_a_cell_it_does_not_list(
        bench_run, tmp_path):
    """Its bytes are YCSB's: a cell of another schema (its `fields` have
    no `req_per_query`, no `tup_size`) is not in its list, the reader is
    never called there, and nothing raises."""
    hot = bench_run.load_cell("ycsb_fullrow_tpubatch.hot")
    other = dict(hot, name="tpcc_payment_neworder.mixed")
    res = _res(bench_run, tmp_path)
    res["fields"] = dict(pipeline_epochs=32, num_wh=64)
    lay = bench_run.compute_metrics(
        other, bench_run.metric_context(other, res, TRACE), traced=True)
    assert "epoch_group_hbm_roofline" not in lay
    assert "group.device_ms_per_epoch" in lay       # list-less: asked
    with pytest.raises(KeyError):                   # what the list keeps out
        bench_run.compute_metrics(
            hot, bench_run.metric_context(hot, res, TRACE), traced=True)


def test_check_served_catches_each_wrong_answer(bench_run, tmp_path):
    def failed(res, cell="ycsb_fullrow_tpubatch.hot"):
        conf = bench_run.load_cell(cell)["config_file"]
        return [n for n, v, lim in bench_run.check_served("t", conf, res)
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
    assert failed(res, "ycsb_fullrow_occ.medium") == []    # OCC may abort
    # the configuration's stated guarantee decides, not the backend's
    # name: a CALVIN or DGCC file that states "none" is held to it too
    conf = dict(guarantees=dict(aborts="none (deterministic batch order)"),
                fields=dict(cc_alg="CALVIN"))
    assert "t.deterministic_aborts" in [
        n for n, v, lim in bench_run.check_served("t", conf, res) if v > lim]
    res = _res(bench_run, tmp_path)
    del res["server"]["info"]
    assert failed(res) == ["t.nodes_not_reporting"]


def _spec(seed, theta=0.9, n=4096, txn_write=0.5, **fields):
    return dict(seed=seed, traffic=dict(
        zipf_theta=theta, read_share=0.5, txn_write_share=txn_write,
        arrival="closed", clients=2, ring_txns=n, warmup_secs=1.0),
        fields=dict(dict(client_batch_size=256, req_per_query=10,
                         synth_table_size=1 << 16), **fields))


def _ring_rows(gen, seed, client, **kw):
    r = gen.make_ring(_spec(seed, **kw), client)
    k = np.concatenate([k for k, _ in r])
    t = np.concatenate([t for _, t in r])
    return r, np.concatenate([k, t.astype(np.int32)], axis=1)


def test_generator_is_seeded_and_keeps_the_sources_shapes(ycsb_gen):
    big = 2**31 + 5
    (a, ra), (_, rb) = _ring_rows(ycsb_gen, big, 0), _ring_rows(ycsb_gen, big, 0)
    assert len(a) == 16 and a[0][0].shape == (256, 10)
    assert a[0][0].flags["C_CONTIGUOUS"] and a[0][1].dtype == np.int8
    assert (ra == rb).all()                     # same seed, same inputs
    assert not (ra == _ring_rows(ycsb_gen, big, 1)[1]).all()   # clients differ
    keys = ra[:, :10].ravel()
    assert keys.min() >= 0 and keys.max() < 1 << 16
    # skewed: key 0 is the hottest; uniform: it is not
    assert np.bincount(keys).argmax() == 0
    u = _ring_rows(ycsb_gen, 1, 0, theta=0.0)[1][:, :10].ravel()
    assert np.bincount(u).max() < 12


def test_another_seed_draws_other_transactions(ycsb_gen):
    """The seed draws WHICH transactions a run sends, not only their
    order: two seeds' rings are different multisets."""
    ra, rd = _ring_rows(ycsb_gen, 5, 0)[1], _ring_rows(ycsb_gen, 77, 0)[1]
    def as_set(r):
        return {tuple(x) for x in r.tolist()}
    assert len(as_set(ra) & as_set(rd)) < len(ra) // 2
    # a ring over several chunks is one stream: no chunk repeats another
    small = ycsb_gen.RING_CHUNK
    try:
        ycsb_gen.RING_CHUNK = 1024
        rc = _ring_rows(ycsb_gen, 5, 0)[1]
    finally:
        ycsb_gen.RING_CHUNK = small
    assert len(as_set(rc[:1024]) & as_set(rc[1024:2048])) < 512


@pytest.mark.parametrize("txn_write", [0.5, 1.0])
def test_request_types_follow_the_sources_two_draws(ycsb_gen, txn_write):
    """TXN_WRITE_PERC gates a whole transaction, TUP_WRITE_PERC each
    request of one that may write (`ycsb_query.cpp` r_twr)."""
    types = _ring_rows(ycsb_gen, 9, 0, n=1 << 14, txn_write=txn_write
                       )[1][:, 10:]
    assert set(np.unique(types)) == {1, 2}
    read_only = (types == 1).all(axis=1).mean()
    # a transaction that may write is all reads with probability 2**-10
    assert abs(read_only - (1 - txn_write + txn_write / 1024)) < 0.02
    writers = types[(types == 2).any(axis=1)]
    assert abs((writers == 2).mean() - 0.5) < 0.02
    assert abs((types == 2).mean() - 0.5 * txn_write) < 0.02


def test_an_arrival_law_the_generator_does_not_have_is_refused(
        loadgen, ycsb_gen, tmp_path):
    spec = dict(traffic=dict(arrival="paced", clients=2, warmup_secs=1.0,
                             ring_txns=256),
                transport={}, seconds=1.0,
                fields=dict(workload="YCSB", client_batch_size=128,
                            req_per_query=10, max_txn_in_flight=4096))
    with pytest.raises(ValueError, match="closed loop only"):
        loadgen.run_client(spec, 0)
    # the refusal is the generator's `check`, and `load_cell` asks it too
    with pytest.raises(ValueError, match="closed loop only"):
        ycsb_gen.check(dict(_spec(1)["traffic"], arrival="paced"))
    ycsb_gen.check(_spec(1)["traffic"])
    for bad in (dict(zipf_theta=1.0), dict(read_share=1.5), dict(burst=3),
                dict(clients=0)):
        with pytest.raises(ValueError):
            ycsb_gen.check(dict(_spec(1)["traffic"], **bad))


# ---- what must not move: the measurement's input -----------------------
# Taken from the PARENT commit's functions (ef56ba3: `loadgen.make_ring`,
# `loadgen.block_parts`, `run.server_fields` of a `git archive` of it,
# the cell's own parameters but a ring of 32,768 transactions): the ring
# a client draws, one message's bytes, and a launch's `Config` arguments.

PIN_RING_TXNS = 32768
PARENT_RINGS = {
    ("ycsb_fullrow_tpubatch.hot", 3_000_000_019, 0):
        "bd7900851e85908fa6f7bf935cbb1385e004fb17daad60d256f432d0c49f4e58",
    ("ycsb_fullrow_tpubatch.hot", 3_000_000_019, 1):
        "619e435912ea36ec96632c61bc1edd2e2a0b9eda564bfd1fe514a828c9f3cd8a",
    ("ycsb_fullrow_tpubatch.hot", 7, 0):
        "460fbef86c5c0789588ccb7d335ac57bcfdea31248867bb347dc7b383a45d2e8",
    ("ycsb_fullrow_tpubatch.hot", 7, 1):
        "642f633f3548ce1d825bbe08e89235cdd98dc3893a36febf183e54843b7435ce",
    ("ycsb_fullrow_occ.medium", 3_000_000_019, 0):
        "e7a1e3d1397fca7a37c5401194ea79ed7cc21ee46cf6b86db5ba793f5aab7915",
    ("ycsb_fullrow_occ.medium", 3_000_000_019, 1):
        "6b034979b7d81b459a90716ffa3aadb4f994ecc69fe9de65d0892a19290d931f",
    ("ycsb_fullrow_occ.medium", 7, 0):
        "1e92bb3d5a23da86b55408fee4ee71cb5cee5be0fc9782dc13d81c66498369e8",
    ("ycsb_fullrow_occ.medium", 7, 1):
        "9a204263b254d05ace8912d7b294abcdbe06984a9eee6694a88fe7692110647a",
}
# the second block of seed 7, client 1: its first 100 transactions under
# the tags 12345.. as one CL_QRY_BATCH body
PARENT_MESSAGE = {
    "ycsb_fullrow_tpubatch.hot":
        (5812,
         "2774b6b8ef619f3b732696988acbc3568bd1ab19c360c4276eaa986c343cf9a7"),
    "ycsb_fullrow_occ.medium":
        (5812,
         "38b6e286f369b142d6ff2b554e23a47cd22dabf80bf61527b87c9824b30df42c"),
}
PARENT_ARGS = {
    "ycsb_fullrow_tpubatch.hot": (
        "--workload=YCSB --cc_alg=TPU_BATCH --node_cnt=1 "
        "--sim_full_row=true --synth_table_size=6291456 --tup_size=100 "
        "--field_per_tuple=10 --req_per_query=10 --max_accesses=16 "
        "--epoch_batch=16384 --pipeline_epochs=32 --pipeline_groups=2 "
        "--conflict_buckets=8192 --max_txn_in_flight=2097152 "
        "--client_batch_size=16384 --zipf_theta=0.9 --read_perc=0.5 "
        "--write_perc=0.5 --txn_write_perc=0.5 --client_node_cnt=2 "
        "--device_parts=1 --seed=852516371"),
    "ycsb_fullrow_occ.medium": (
        "--workload=YCSB --cc_alg=OCC --node_cnt=1 --sim_full_row=true "
        "--synth_table_size=6291456 --tup_size=100 --field_per_tuple=10 "
        "--req_per_query=10 --max_accesses=16 --epoch_batch=1024 "
        "--pipeline_epochs=32 --pipeline_groups=2 --conflict_buckets=8192 "
        "--max_txn_in_flight=131072 --client_batch_size=1024 "
        "--zipf_theta=0.6 --read_perc=0.5 --write_perc=0.5 "
        "--txn_write_perc=0.5 --client_node_cnt=2 --device_parts=1 "
        "--seed=852516371"),
}


def _cell_ring(bench_run, name, seed, client):
    cell = bench_run.load_cell(name)
    spec = dict(seed=seed, fields=bench_run.server_fields(cell, seed, {}),
                traffic=dict(cell["traffic_file"], ring_txns=PIN_RING_TXNS))
    gen = bench_run.generator(spec["fields"])
    return gen, gen.make_ring(spec, client)


@pytest.mark.parametrize("name,seed,client", sorted(PARENT_RINGS))
def test_the_ring_a_client_draws_is_the_parents(bench_run, name, seed,
                                                client):
    _, ring = _cell_ring(bench_run, name, seed, client)
    h = hashlib.sha256()
    for keys, types in ring:
        h.update(np.ascontiguousarray(keys).tobytes())
        h.update(np.ascontiguousarray(types).tobytes())
    assert h.hexdigest() == PARENT_RINGS[name, seed, client]


@pytest.mark.parametrize("name", sorted(PARENT_MESSAGE))
def test_one_messages_bytes_are_the_parents(bench_run, loadgen, name):
    gen, ring = _cell_ring(bench_run, name, 7, 1)
    tags = (np.arange(100, dtype=np.int64) + 12345) % loadgen.TAG_RING
    parts = gen.block_parts(tags, *(a[:100] for a in ring[1]))
    msg = b"".join(p if isinstance(p, bytes)
                   else np.ascontiguousarray(p).tobytes() for p in parts)
    assert (len(msg), hashlib.sha256(msg).hexdigest()) == PARENT_MESSAGE[name]


@pytest.mark.parametrize("name", sorted(PARENT_ARGS))
def test_a_launchs_config_arguments_are_the_parents(bench_run, name):
    """Names, values and order (a later flag overrides an earlier one)."""
    fields = bench_run.server_fields(bench_run.load_cell(name),
                                     3_000_000_019, {})
    assert " ".join(f"--{k}={v}" for k, v in fields.items()) \
        == PARENT_ARGS[name]
    # what an override of a launch changes still lands last
    over = bench_run.server_fields(bench_run.load_cell(name), 1,
                                   dict(logging="true", zipf_theta=0.1))
    assert list(over)[-1] == "logging" and over["zipf_theta"] == 0.1


# ---- BENCHMARK.json against the contract's limits ----------------------

def test_benchmark_json_keeps_to_the_contract():
    check_benchmark(ROOT)
    check_accepted(ROOT)


def test_every_file_of_the_benchmark_has_a_contract_name():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in ("benchmark", "tests/benchmark"):
        for d, _, fs in os.walk(os.path.join(ROOT, base)):
            if "__pycache__" in d:
                continue
            for f in fs:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel


def test_no_test_pins_an_entry_by_its_position_or_a_lists_length():
    """A later PR appends cells and per-layer entries: a test here that
    subscripts `per_layer` or `workloads` by a number or a slice, or
    holds their length to a value, fails that PR for adding them (PR 29
    put such a pin in; PR 28 had taken four out).  What is accepted is
    looked up by name: `bench_contract.check_accepted`."""
    lists = r"(?:per_layer|workloads)"
    by_position = re.compile(
        lists + r"""["']?\]?\[\s*[-+\d:a-z]|"""         # a subscript
        r"len\([^()]*" + lists + r"[^()]*\)\s*[=!]=")    # a length held
    here = os.path.join(ROOT, "tests", "benchmark")
    hits = []
    for name in sorted(os.listdir(here)):
        if name.endswith(".py"):
            with open(os.path.join(here, name)) as f:
                hits += [f"{name}:{n}: {line.strip()}"
                         for n, line in enumerate(f, 1)
                         if by_position.search(line)]
    assert hits == []
    # the pattern does catch the forms it is there for (@: a list's
    # name, kept out of this file's own lines)
    for bad in ('new = bench["@"][22:]', "@[:n]", 'cell = b["@"][0]',
                "@[-1]", 'assert len(bench["@"]) == 3'):
        for name in ("per_layer", "workloads"):
            assert by_position.search(bad.replace("@", name)), bad
    for fine in ('m["workloads"] == [CELL]', 'bench["per_layer"] += METRICS',
                 'for m in bench["per_layer"]:', 'by_name["x"]["workloads"]',
                 'm["workloads"] = m["workloads"] + [CELL]'):
        assert not by_position.search(fine), fine


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
