"""CPU rehearsal of `chip_smoke.py` at toy size.

The same phase functions the chip run uses, with the server pinned to
the CPU *by the test* (`SERVER_PLATFORM`, not an option of the script):
every answer check must pass — all nodes report, commits > 0, every
client acked, acks <= commits, the served table's digest equals the CPU
replay of its own command log, zero compilations inside the measured
window — and exactly one check must fail: the chip gate, because the
server reports platform "cpu".  `main()` must then exit non-zero, name
the phase, and print no result line."""

import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke

_TOY = dict(synth_table_size=4096, epoch_batch=128, pipeline_epochs=4,
            max_txn_in_flight=4096, client_batch_size=128,
            conflict_buckets=512, req_per_query=4, max_accesses=4,
            warmup_secs=0.5, done_secs=1.0)
_GATE = "not 'tpu' — no TPU was found"


def _toy(over: dict) -> dict:
    toy = {**over, **_TOY}
    if over.get("cc_alg") == "OCC":
        toy.update(epoch_batch=64, client_batch_size=64)
    return toy


@pytest.mark.parametrize("phase,over,replay", chip_smoke.PHASES,
                         ids=[p[0] for p in chip_smoke.PHASES])
def test_phase_answers_hold_on_cpu_and_only_the_chip_gate_fails(
        phase, over, replay, tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "SERVER_PLATFORM", "cpu")
    info, bad = chip_smoke.serve_and_check(phase, _toy(over), replay,
                                           str(tmp_path))
    assert info["platform"] == "cpu" and info["count"] >= 1
    assert info["window_compile_cnt"] == 0 and info["compile_cnt"] > 0
    assert info["run_commit_cnt"] > 0
    # every answer check passed; the one failure is the chip gate
    assert len(bad) == 1 and _GATE in bad[0], bad
    lines = capsys.readouterr().out
    assert f"phase {phase}: device=cpu/" in lines
    if replay:
        # the digest check ran (and agreed, or `bad` would say so)
        assert len(info["state_digest"]) == 64
        assert "cpu_replay_s=" in lines and "replay_digest=" in lines


def test_main_exits_nonzero_naming_the_phase_without_a_chip(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "SERVER_PLATFORM", "cpu")
    phase, over, _ = chip_smoke.PHASES[2]
    monkeypatch.setattr(chip_smoke, "PHASES", ((phase, _toy(over), False),))
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert f"phase {phase}: start" in out
    assert f"phase {phase}: end wall_s=" in out and "FAILED" in out
    assert _GATE in out
    assert '"ok"' not in out            # no result line without a chip


def test_check_served_catches_each_wrong_answer():
    fields = {**chip_smoke.SERVED}

    def nodes(**over):
        info = dict(platform="tpu", kind="TPU v5 lite", count=1,
                    window_compile_cnt=0, run_commit_cnt=100,
                    run_abort_cnt=0)
        srv = dict(total_txn_commit_cnt=60.0, total_txn_abort_cnt=0.0)
        acks = {1: 50.0, 2: 50.0}
        for k, v in over.items():
            if k in info:
                info[k] = v
            elif k in srv:
                srv[k] = v
            else:
                acks[int(k[-1])] = v
        return {0: dict(kind="server", summary=srv, info=info),
                **{n: dict(kind="client", summary=dict(txn_cnt=a), info={})
                   for n, a in acks.items()}}

    check = chip_smoke.check_served
    assert check("p", fields, nodes()) == []
    assert "no TPU" in check("p", fields, nodes(platform="cpu"))[0]
    assert "committed nothing" in check(
        "p", fields, nodes(total_txn_commit_cnt=0.0))[0]
    assert "aborted" in check("p", fields, nodes(run_abort_cnt=3))[0]
    assert "never acked" in check("p", fields, nodes(ack1=0.0))[0]
    assert "acks" in check("p", fields, nodes(ack2=51.0))[0]
    assert "compilation" in check(
        "p", fields, nodes(window_compile_cnt=2))[0]
    assert "expected" in check("p", fields, {0: nodes()[0]})[0]
    # OCC may abort; TPU_BATCH may not
    assert check("p", {**fields, "cc_alg": "OCC"},
                 nodes(run_abort_cnt=3, total_txn_abort_cnt=3.0)) == []


def test_script_alone_exits_nonzero_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(chip_smoke.ROOT, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not beside this script" in proc.stderr


def test_parent_module_imports_no_jax():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; chip_smoke.served_cfg(); "
         "print('jax' in sys.modules)"],
        cwd=chip_smoke.ROOT, capture_output=True, text=True, timeout=60)
    assert proc.stdout.strip() == "False", proc.stderr[-2000:]
