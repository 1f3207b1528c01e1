"""CPU rehearsal of a whole benchmark run at a toy table.

The same functions a chip run uses, with the server pinned to the CPU by
the test (`SERVER_PLATFORM`, not an option of the command).  Every check
must pass — both launches' answer checks, and the plain reference's
digest, commit count and (OCC) rule check on the logged stream — and
exactly one must fail: the chip gate.  `run_cell` then refuses to print
a result.  With the executor broken underneath (a step that returns its
state unchanged), the reference's digest check fails too; with a
profiler whose `stop_trace` never returns, a traced launch fails by
name."""

import os

import pytest

_TOY = dict(synth_table_size=4096, epoch_batch=128, pipeline_epochs=4,
            max_txn_in_flight=4096, client_batch_size=128,
            conflict_buckets=512, req_per_query=4, max_accesses=4)
CELLS = ("ycsb_fullrow_tpubatch.hot", "ycsb_fullrow_occ.medium")


@pytest.fixture(autouse=True)
def _cpu_server_with_slack(bench_run, monkeypatch):
    """The server on the CPU, and serving 3 s past the clients' window:
    under six test workers a client may leave its barrier late."""
    monkeypatch.setattr(bench_run, "SERVER_PLATFORM", "cpu")
    monkeypatch.setattr(bench_run, "SERVE_PAST_WINDOW_S", 3.0)


def _toy_cell(bench_run, name):
    cell = bench_run.load_cell(name)
    cell["config_file"]["fields"].update(_TOY)
    cell["config_file"].pop("verify", None)
    if cell["config_file"]["fields"]["cc_alg"] == "OCC":
        cell["config_file"]["fields"].update(epoch_batch=64,
                                             client_batch_size=64)
    cell["traffic_file"].update(warmup_secs=0.5, ring_txns=1 << 14)
    return cell


def _failed(out: str) -> list[str]:
    return [ln.split()[1] for ln in out.splitlines()
            if ln.startswith("[check] ") and ln.endswith("FAILED")]


@pytest.mark.parametrize("name", CELLS)
def test_only_the_chip_gate_fails_on_cpu_and_the_digests_agree(
        name, bench_run, monkeypatch, capfd):
    with pytest.raises(bench_run.RunFailed, match="no TPU was found"):
        bench_run.run_cell(_toy_cell(bench_run, name), 3_000_000_019, 1.0,
                           trace=False)
    out = capfd.readouterr().out
    assert "[check] reference.digest_mismatch value=0 limit=0 ok" in out
    assert "[check] reference.commit_count_gap value=0 limit=0 ok" in out
    if "occ" in name:
        assert "[check] reference.occ_rule_violations value=0 limit=0 ok" \
            in out
    assert sorted(_failed(out)) == ["timed.server_not_on_tpu",
                                    "verify.server_not_on_tpu"]
    assert '"correct"' not in out           # no result line without a chip


@pytest.mark.parametrize("capacity", [None, 1.0],
                         ids=["default_capacity", "forced_defers"])
def test_a_four_chip_cell_rehearses_on_four_cpu_devices(
        capacity, bench_run, monkeypatch, tmp_path):
    """`chips: 4` -> `device_parts=4`: the server child shards the toy
    table over four (virtual CPU) devices and runs the mesh path at a
    batch where its sharded plan engages (`mesh_a2a_bytes` > 0: at 128
    lanes `mc_pair_cap` is 0 and the replicated plan runs), under the
    SAME checks.  The reference restates the owner-major layout and
    reproduces digest and commit count; only the chip gates fail.

    With the per-owner capacity forced down to the even share
    (`mc_plan_capacity=1.0`; the default 2.0 defers nothing here)
    transactions DEFER: no abort shows, so the zero-abort check holds —
    and the reference says not correct, because the command log does not
    say which admitted lanes an epoch deferred and `ycsb_serial` commits
    them all (PERF.md section 7, row 1)."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    cell = _toy_cell(bench_run, CELLS[0])
    cell["chips"] = 4
    cell["config_file"]["fields"].update(
        epoch_batch=512, client_batch_size=512, max_txn_in_flight=8192)
    if capacity is not None:
        cell["config_file"]["fields"]["mc_plan_capacity"] = capacity
    checks, vres = bench_run.verify_phase(cell, 2_500_000_033, str(tmp_path))
    tchecks, res = bench_run.timed_phase(cell, 2_500_000_033, 1.0,
                                         str(tmp_path), trace=False)
    for r in (vres, res):
        assert r["fields"]["device_parts"] == 4
        assert r["server"]["info"]["count"] == 4
        assert r["server"]["summary"]["mesh_shards"] == 4.0
        assert r["server"]["summary"]["mesh_a2a_bytes"] > 0
    got = {n: (v, lim) for n, v, lim in checks + tchecks}
    assert got["verify.deterministic_aborts"] == (0.0, 0.0)
    assert got["timed.deterministic_aborts"] == (0.0, 0.0)
    assert res["server"]["summary"]["total_txn_commit_cnt"] > 0
    failed = sorted(n for n, (v, lim) in got.items() if v > lim)
    gates = ["timed.server_not_on_tpu", "verify.server_not_on_tpu"]
    if capacity is None:
        assert res["server"]["summary"]["defer_cnt"] == 0
        assert got["reference.digest_mismatch"] == (0.0, 0.0)
        assert got["reference.commit_count_gap"] == (0.0, 0.0)
        assert failed == gates
    else:
        assert res["server"]["summary"]["defer_cnt"] > 0
        # the digest differs too unless every deferred writer came back
        # before the log ended
        assert "reference.commit_count_gap" in failed
        assert set(failed) <= {"reference.commit_count_gap",
                               "reference.digest_mismatch", *gates}


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
        bench_run, monkeypatch, capfd):
    monkeypatch.setattr(bench_run, "SERVER_CHILD", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "broken_server.py"))
    with pytest.raises(bench_run.RunFailed):
        bench_run.run_cell(_toy_cell(bench_run, CELLS[0]), 7, 1.0,
                           trace=False)
    out = capfd.readouterr().out
    assert "reference.digest_mismatch" in _failed(out)
    assert "reference.commit_count_gap" not in _failed(out)


def test_the_control_fails_the_comparison_on_the_toy_occ_cell(
        bench_run, monkeypatch, capfd):
    """benchmark/control.py at a size a test run holds: the sound
    comparison passes, a lost write and an illegal verdict each fail."""
    import json

    from conftest import load_script
    control = load_script("control.py")
    rc = control.main(["--workload", CELLS[1], "--seeds", "11"],
                      run=bench_run, cell=_toy_cell(bench_run, CELLS[1]))
    out = json.loads([ln for ln in capfd.readouterr().out.splitlines()
                      if ln.startswith("{")][-1])
    assert rc == 0 and out["control_ok"] and out["sound_failed"] == [], out
    assert "digest_mismatch" in out["lost_write_failed"]
    assert out["illegal_verdict_failed"]


def test_a_traced_launch_whose_stop_trace_never_returns_fails_by_name(
        bench_run, monkeypatch, tmp_path):
    """The server child waits for `stop_trace` until shortly before its
    own limit (`CHILD_TIMEOUT_S`; `hung_trace_server.py` makes that 5 s
    after its start), then fails: the launch raises `RunFailed` with the
    reason, it does not come back without its `[trace]` window."""
    monkeypatch.setattr(bench_run, "SERVER_CHILD", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "hung_trace_server.py"))
    monkeypatch.setattr(bench_run, "CHILD_TIMEOUT_S", 90)
    cell = _toy_cell(bench_run, CELLS[0])
    fields = bench_run.server_fields(cell, 7, dict(
        logging="false", warmup_secs=0.5, done_secs=2.0))
    with pytest.raises(bench_run.RunFailed,
                       match="stop_trace: no end after [0-5] s"):
        bench_run.launch("timed", cell, fields, 7, 1.0, 0.5, str(tmp_path),
                         True)
