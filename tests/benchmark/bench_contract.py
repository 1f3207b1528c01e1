"""What every tree's `BENCHMARK.json` and `benchmark/` are held to, as
functions of the tree's root: the real tree's tests call them with the
repo, `test_bench_extend.py` with a copy that a pretended later PR has
added a deployment to.  One rule: a later PR adds a configuration, a
traffic mix, a generator, a reference, a cell or a per-layer metric as
NEW files and NEW entries, and nothing here needs an edit.  What is
accepted is pinned BY NAME, so nothing is loosened for it."""

import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

# the source's shapes of the accepted configurations (ycsb_skew's row and
# transaction), and the accepted traffic files' operation mix
YCSB_ROW = dict(tup_size=100, field_per_tuple=10, req_per_query=10,
                sim_full_row="true")
ACCEPTED_SHAPES = {"ycsb-fullrow-tpubatch": YCSB_ROW,
                   "ycsb-fullrow-occ": YCSB_ROW,
                   "ycsb-fullrow-tpubatch-dp4": YCSB_ROW}
ACCEPTED_MIX = {"hot": (0.5, 0.5, "closed"), "medium": (0.5, 0.5, "closed")}
# the accepted cells: (configuration, traffic, chips)
HOT, OCC, DP4 = ("ycsb_fullrow_tpubatch.hot", "ycsb_fullrow_occ.medium",
                 "ycsb_fullrow_tpubatch_dp4.hot")
ACCEPTED_CELLS = {HOT: ("ycsb-fullrow-tpubatch", "hot", 1),
                  OCC: ("ycsb-fullrow-occ", "medium", 1),
                  DP4: ("ycsb-fullrow-tpubatch-dp4", "hot", 4)}
# the per-layer metrics of PRs 24, 25, 28, 29 and 35, in their order
ACCEPTED_PER_LAYER = [
    "client.sent_txn_per_s", "transport.bytes_per_txn", "host.idle_share",
    "group.txn_per_epoch", "group.device_ms_per_epoch",
    "epoch_group_hbm_roofline", "cc.retries_per_txn", "cc.abort_rate",
    "device.idle_share",
    "host.busy_share", "host.device_wait_share", "host.admit_ms_per_epoch",
    "host.retire_ms_per_epoch", "server.queue_wait_ms", "server.pipeline_ms",
    "group.verdict_lag_ms", "phase.plan_ms_per_epoch",
    "phase.read_ms_per_epoch", "phase.write_ms_per_epoch",
    "phase.other_ms_per_epoch", "phase.validate_ms_per_epoch",
    "exec.write_lanes_per_epoch",
    "phase.exchange_ms_per_epoch", "mesh.a2a_bytes_per_epoch",
    "cc.defers_per_txn", "exchange_ici_roofline",
    "exec.read_lanes_per_epoch"]
# what an accepted entry is held to beyond its place: the cells it
# lists (None: it lists none and every cell reports it) and, where a PR
# pinned them, what it moves and its layer
KERNELS = "CC and executor kernels"
_DP4_ONLY = dict(workloads=[DP4], moves="served_txn_per_s", layer=KERNELS)
ACCEPTED_ENTRIES = {
    "phase.validate_ms_per_epoch": dict(workloads=[OCC]),
    "exec.write_lanes_per_epoch": dict(workloads=None),
    "phase.exchange_ms_per_epoch": _DP4_ONLY,
    "mesh.a2a_bytes_per_epoch": _DP4_ONLY,
    "cc.defers_per_txn": _DP4_ONLY,
    "exchange_ici_roofline": _DP4_ONLY,
    "exec.read_lanes_per_epoch": dict(
        workloads=[HOT, DP4], moves="served_txn_per_s", layer=KERNELS,
        unit="lanes/epoch", better="lower", source="program_counter"),
}
# `epoch_group_hbm_roofline` counts YCSB's bytes (`peaks.ycsb_epoch_bytes`
# of these `fields`): it lists its cells, and each runs such a schema
ROOFLINE_FIELDS = ("req_per_query", "tup_size")


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def load_json(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def load_module(path):
    spec = importlib.util.spec_from_file_location(
        "contract_" + re.sub(r"\W", "_", os.path.basename(path)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_benchmark(root):
    """BENCHMARK.json against the contract's limits, every configuration
    against its own `shapes`, every traffic file against its generator."""
    bench_dir = os.path.join(root, "benchmark")
    path = os.path.join(root, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    b = load_json(path)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert all(_line(w) for w in b["command"]) and len(b["command"]) <= 32
    assert b["command"][1].startswith(b["paths"][0] + "/")
    cfgs = {c["name"] for c in b["configs"]}
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files) == len(cfgs)
    confs = {}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        conf = confs[c["name"]] = load_json(root, c["file"])
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert {"fields", "shapes", "assumed", "guarantees", "reference"} \
            <= set(conf)
        # the source's shapes are stated, run as stated, and never cut
        f, shapes = conf["fields"], conf["shapes"]
        assert shapes, c["name"]
        assert {k: f.get(k) for k in shapes} == shapes, c["name"]
        assert not set(shapes) & set(c["reduced"]), c["name"]
        assert "aborts" in conf["guarantees"]
        assert os.path.exists(os.path.join(
            bench_dir, "references", conf["reference"] + ".py"))
        assert os.path.exists(os.path.join(
            bench_dir, "generators", f["workload"].lower() + ".py"))
    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) \
        == len(cells)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(cells) // 2)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4) and _line(w["why"])
        tr = load_json(bench_dir, "traffic", w["traffic"] + ".json")
        # the mix is the traffic file's; its generator says whether it
        # can draw it
        gen = load_module(os.path.join(
            bench_dir, "generators",
            confs[w["config"]]["fields"]["workload"].lower() + ".py"))
        gen.check(tr)
        assert int(tr["clients"]) >= 1 and float(tr["warmup_secs"]) >= 0
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
        assert os.path.exists(os.path.join(bench_dir, "metrics",
                                           m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def check_per_layer(root):
    """Order and presence, not equality: the accepted per-layer metrics
    are all there, in their order among themselves; what a later PR
    declares has a reader, a layer that an accepted entry has or that
    PERF.md section 3 names, and only cells that exist."""
    bench = load_json(root, "BENCHMARK.json")
    accepted = [m for m in bench["per_layer"]
                if m["name"] in ACCEPTED_PER_LAYER]
    assert [m["name"] for m in accepted] == ACCEPTED_PER_LAYER
    layers = {m["layer"] for m in accepted}
    with open(os.path.join(root, "PERF.md")) as f:
        perf = f.read()
    section3 = perf[perf.index("\n## 3."):perf.index("\n## 4.")]
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if m["name"] in ACCEPTED_PER_LAYER:
            continue
        assert os.path.exists(os.path.join(root, "benchmark", "metrics",
                                           m["name"] + ".py")), m["name"]
        assert m["layer"] in layers or m["layer"] in section3, m["name"]
        assert m["moves"] in e2e, m["name"]
        assert set(m.get("workloads", ())) <= cells, m["name"]


def check_accepted(root):
    """Every by-name pin of an accepted cell, configuration and
    per-layer entry, on any tree: the real one, and the pretended later
    PR's, which must pass it with its own deployment added."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    confs = {c["name"]: load_json(root, c["file"])
             for c in bench["configs"]}
    for name, pin in ACCEPTED_CELLS.items():
        w = cells.get(name, {})
        assert (w.get("config"), w.get("traffic"), w.get("chips")) == pin, \
            name
    for name, shapes in ACCEPTED_SHAPES.items():
        assert name in confs and confs[name]["shapes"] == shapes, name
    # the accepted traffic files keep the source's mix (ycsb_skew)
    for name, mix in ACCEPTED_MIX.items():
        tr = load_json(root, "benchmark", "traffic", name + ".json")
        assert (tr["read_share"], tr["txn_write_share"], tr["arrival"]) \
            == mix, name
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, pins in ACCEPTED_ENTRIES.items():
        for key, want in pins.items():
            assert by_name[name].get(key) == want, (name, key)
    listed = by_name["epoch_group_hbm_roofline"]["workloads"]
    assert set(listed) >= {HOT, OCC, DP4}
    for name in listed:
        fields = confs[cells[name]["config"]]["fields"]
        assert all(k in fields for k in ROOFLINE_FIELDS), name
