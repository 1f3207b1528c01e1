"""The launcher's process discipline (`runtime/launch.py`,
`runtime/jaxenv.py`): the parent of a cluster never imports JAX (a chip
belongs to one process), the native library is built once in the parent
before any node is spawned, every node reports the device its own JAX
found, and a server asked for a platform that is not there fails with an
error that names the platform instead of running elsewhere."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TOY = dict(node_cnt=1, client_node_cnt=1, cc_alg="TPU_BATCH",
            epoch_batch=64, synth_table_size=1024, req_per_query=4,
            max_accesses=4, conflict_buckets=512, warmup_secs=0.2,
            done_secs=0.5)

_DRIVER = textwrap.dedent("""
    import json, multiprocessing as mp, sys

    from deneva_tpu.config import Config
    from deneva_tpu.runtime import launch, native

    if __name__ == "__main__":
        toy, platform = json.loads(sys.argv[1]), sys.argv[2]
        events = []
        built = native.ensure_built

        def ensure_built(*a, **kw):
            events.append("build")
            return built(*a, **kw)

        native.ensure_built = ensure_built
        Process = mp.get_context("spawn").Process
        start = Process.start

        def traced_start(self):
            events.append("spawn")
            return start(self)

        Process.start = traced_start
        info, err = {}, None
        cfg = Config.from_args([f"--{k}={v}" for k, v in toy.items()])
        try:
            out = launch.run_cluster(cfg, platform, node_info=info,
                                     timeout_s=240)
        except RuntimeError as e:
            out, err = {}, str(e)
        print(json.dumps({
            "jax_in_parent": "jax" in sys.modules, "events": events,
            "kinds": {k: v[0] for k, v in out.items()},
            "info": info, "err": err}))
""")


def _drive(tmp_path, platform: str, shell_platform: str) -> dict:
    script = tmp_path / "drive_cluster.py"
    script.write_text(_DRIVER)
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS=shell_platform)
    proc = subprocess.run(
        [sys.executable, str(script), json.dumps(_TOY), platform],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_parent_off_jax_native_built_first_nodes_report_device(tmp_path):
    # the shell says "cuda": every node pins its OWN platform, so the
    # inherited value must neither reach the server nor the client
    r = _drive(tmp_path, "cpu", shell_platform="cuda")
    assert r["err"] is None, r["err"]
    assert r["jax_in_parent"] is False
    assert r["events"][0] == "build" and r["events"].count("build") == 1
    assert r["events"][1:] == ["spawn", "spawn"]
    assert r["kinds"] == {"0": "server", "1": "client"}
    for nid in ("0", "1"):
        assert r["info"][nid]["platform"] == "cpu"
        assert r["info"][nid]["count"] >= 1
    srv = r["info"]["0"]
    assert srv["window_compile_cnt"] == 0
    assert srv["compile_cnt"] > 0 and srv["compile_s"] > 0
    assert srv["run_commit_cnt"] > 0


def test_unavailable_platform_is_a_named_error_not_a_fallback(tmp_path):
    r = _drive(tmp_path, "cuda", shell_platform="cpu")
    assert r["jax_in_parent"] is False
    assert r["kinds"] == {} and r["info"] == {}
    assert "requested JAX platform 'cuda' is unavailable" in r["err"]
    assert "no CUDA was found" in r["err"]


def test_launcher_cli_splits_server_and_client_platforms(capsys):
    from deneva_tpu.runtime import launch
    with pytest.raises(SystemExit, match="needs a JAX platform name"):
        launch.main(["--platform="])


def test_parent_side_modules_import_without_jax():
    """What a launcher parent or a numpy-only fleet worker imports must
    not pull JAX in on the way (it would ask for the chip)."""
    code = ("import sys; import deneva_tpu.runtime.launch, "
            "deneva_tpu.runtime.native, deneva_tpu.runtime.jaxenv, "
            "deneva_tpu.runtime.loadgen; print('jax' in sys.modules)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=60)
    assert proc.stdout.strip() == "False", proc.stderr[-2000:]
