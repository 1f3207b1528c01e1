"""CPU rehearsal of a whole benchmark run at a toy table.

The same functions a chip run uses, with the server pinned to the CPU by
the test (`SERVER_PLATFORM`, not an option of the command).  Every check
must pass — both launches' answer checks, and the plain reference's
digest, commit count and (OCC) rule check on the logged stream — and
exactly one must fail: the chip gate.  `run_cell` then refuses to print
a result.  With the executor broken underneath (a step that returns its
state unchanged), the reference's digest check fails too."""

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
    assert rc == 0 and out["control_ok"] and out["sound_failed"] == []
    assert "digest_mismatch" in out["lost_write_failed"]
    assert out["illegal_verdict_failed"]
