"""graftlint self-tests (PR 6; v2 families PR 9).

Fixture trees under tests/graftlint_fixtures/ carry one seeded violation
per `EXPECT[rule]` marker; each rule must fire exactly at its marker
lines and nowhere else, stay silent on the clean tree, and the real repo
tree must be lint-clean.  The runtime half (ownercheck.install guards)
is unit-tested at the bottom; the CFG core has its own tests in
test_graftlint_cfg.py.
"""

import os
import re
import subprocess
import sys
import threading
from collections import Counter, deque

from tools.graftlint import gateconsistency, wireproto
from tools.graftlint.core import FAMILIES, Tree, run_checkers
from tools.graftlint.wiremodel import RtypeSpec, WIRE_MODEL

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(REPO, "tests", "graftlint_fixtures")

_EXPECT = re.compile(r"EXPECT\[([a-z-]+)\]")


def _expected(root):
    """Multiset of (rel path, line, rule) from EXPECT[...] markers."""
    out = Counter()
    for dirpath, _dirs, files in os.walk(root):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root)
            with open(path) as f:
                for i, ln in enumerate(f, 1):
                    for rule in _EXPECT.findall(ln):
                        out[(rel, i, rule)] += 1
    return out


def _got(findings):
    return Counter((f.path, f.line, f.rule) for f in findings)


# ---- each rule fires exactly at its seeded marker ----------------------

def test_bad_fixture_rules_fire_exactly():
    """trace / det / own / imports / life / jit: the bad tree produces
    exactly the marked findings (right rule, right file, right line —
    no extras)."""
    root = os.path.join(FIX, "bad")
    tree = Tree(root, ["."])
    findings = run_checkers(tree, {"trace", "det", "own", "imports",
                                   "life", "jit"})
    assert _got(findings) == _expected(root), \
        "\n".join(f.render() for f in findings)


# the wire fixture is checked against its own miniature model (the real
# WIRE_MODEL describes the real runtime, not the fixture registry)
_MINI = {s.name: s for s in (
    RtypeSpec("PING", False),
    RtypeSpec("DATA", True, ("encode_data",),
              ("decode_data", "decode_data_gone"), ("handler",)),
    RtypeSpec("GHOST", False),
)}


def test_wire_fixture_rules_fire_exactly():
    root = os.path.join(FIX, "wire_bad")
    tree = Tree(root, ["."])
    findings = tree.filter(wireproto.check(
        tree, model=_MINI,
        codec_modules=("deneva_tpu/runtime/codec_fx.py",),
        route_funcs={"handler": ("deneva_tpu/runtime/codec_fx.py",
                                 "route")}))
    assert _got(findings) == _expected(root), \
        "\n".join(f.render() for f in findings)


def test_clean_fixture_is_silent():
    root = os.path.join(FIX, "clean")
    tree = Tree(root, ["."])
    findings = run_checkers(tree, set(FAMILIES))
    assert findings == [], "\n".join(f.render() for f in findings)


def test_repo_tree_is_lint_clean():
    """The acceptance gate: the real tree ends the PR clean under ALL
    families — v2 included — with zero suppressions (every true finding
    fixed)."""
    tree = Tree(REPO, ["deneva_tpu", "tools"])
    findings = run_checkers(tree, set(FAMILIES))
    assert findings == [], "\n".join(f.render() for f in findings)


# ---- gate-consistency fixture (its own registry, like the wire one) ----

def _gate_specs():
    from deneva_tpu.runtime.gates import GateSpec
    return {s.name: s for s in (
        GateSpec("fx", flags=("fx_flag",), guards=("fx_flag", "_fx"),
                 home=("deneva_tpu/runtime/fxsub.py",),
                 use_attrs=("fxo",)),
        # drift seeds: one flag that is not a Config field, one whose
        # default is ON
        GateSpec("fxbad", flags=("bad_flag", "missing_flag")),
    )}


_GFX_MODEL = {s.name: s for s in (
    RtypeSpec("FXMSG", False, gate="fx"),
    RtypeSpec("FXBAD", True, gate="fx"),     # gated AND fault-eligible
)}


def test_gate_fixture_rules_fire_exactly():
    root = os.path.join(FIX, "gate_bad")
    tree = Tree(root, ["."])
    findings = tree.filter(gateconsistency.check(
        tree, gates=_gate_specs(), exempt=(),
        escrow_funcs=("fx_gate",), escrow_home=(),
        config_module="deneva_tpu/config.py",
        guarded=("pending",), model=_GFX_MODEL))
    assert _got(findings) == _expected(root), \
        "\n".join(f.render() for f in findings)


def test_repair_gate_fires_on_unguarded_use():
    """The REAL ``repair`` GateSpec (runtime/gates.py, not a fixture
    registry) catches an unguarded call into engine/repair.py and
    accepts the two guarded idioms the runtime uses (``cfg.repair`` at
    the engine/server call sites, the server's cached ``self._repair``)
    — the CI teeth behind the default-off bit-identity contract."""
    from deneva_tpu.runtime.gates import GATES

    root = os.path.join(FIX, "gate_bad_repair")
    tree = Tree(root, ["."])
    findings = tree.filter(gateconsistency.check(
        tree, gates={"repair": GATES["repair"]}, exempt=(),
        escrow_funcs=(), escrow_home=(),
        config_module="deneva_tpu/config.py", guarded=(), model={}))
    assert _got(findings) == _expected(root), \
        "\n".join(f.render() for f in findings)


def test_fencing_gate_fires_on_unguarded_use():
    """The REAL ``fencing`` GateSpec (runtime/gates.py) catches an
    unguarded call into runtime/faildet.py and accepts the guarded
    idioms the runtime uses (``cfg.fencing`` at construction, the
    cached ``self._fencing``, the detector's ``is not None`` check) —
    the CI teeth behind the fencing default-off bit-identity
    contract."""
    from deneva_tpu.runtime.gates import GATES

    root = os.path.join(FIX, "gate_bad_fencing")
    tree = Tree(root, ["."])
    findings = tree.filter(gateconsistency.check(
        tree, gates={"fencing": GATES["fencing"]}, exempt=(),
        escrow_funcs=(), escrow_home=(),
        config_module="deneva_tpu/config.py", guarded=(), model={}))
    assert _got(findings) == _expected(root), \
        "\n".join(f.render() for f in findings)


def test_telemetry_gate_fires_on_unguarded_use():
    """The REAL ``telemetry`` GateSpec (runtime/gates.py) catches an
    unguarded call into runtime/telemetry.py and accepts the guarded
    idioms the runtime uses (``cfg.telemetry`` at construction, the
    recorder handle's ``is not None`` check) — the CI teeth behind the
    flight recorder's default-off bit-identity contract."""
    from deneva_tpu.runtime.gates import GATES

    root = os.path.join(FIX, "gate_bad_telemetry")
    tree = Tree(root, ["."])
    findings = tree.filter(gateconsistency.check(
        tree, gates={"telemetry": GATES["telemetry"]}, exempt=(),
        escrow_funcs=(), escrow_home=(),
        config_module="deneva_tpu/config.py", guarded=(), model={}))
    assert _got(findings) == _expected(root), \
        "\n".join(f.render() for f in findings)


def test_metrics_gate_fires_on_unguarded_use():
    """The REAL ``metrics`` GateSpec (runtime/gates.py) catches an
    unguarded call into runtime/metricsbus.py and accepts the guarded
    idioms the runtime uses (``cfg.metrics`` at construction, the
    sender/aggregator handles' ``is not None`` checks, and the
    ``rtype == "METRICS"`` route branch — a gated rtype only exists
    once the subsystem armed it) — the CI teeth behind the metrics
    bus's default-off bit-identity contract."""
    from deneva_tpu.runtime.gates import GATES

    root = os.path.join(FIX, "gate_bad_metrics")
    tree = Tree(root, ["."])
    findings = tree.filter(gateconsistency.check(
        tree, gates={"metrics": GATES["metrics"]}, exempt=(),
        escrow_funcs=(), escrow_home=(),
        config_module="deneva_tpu/config.py", guarded=(),
        model={"METRICS": WIRE_MODEL["METRICS"]}))
    assert _got(findings) == _expected(root), \
        "\n".join(f.render() for f in findings)


def test_audit_gate_fires_on_unguarded_use():
    """The REAL ``audit`` GateSpec (runtime/gates.py) catches an
    unguarded call into runtime/audit.py AND an unguarded call to the
    declared device-derivation use_calls (cc/base's audit_observe
    family), while accepting the guarded idioms the runtime uses
    (``cfg.audit`` at construction, the exporter handle's ``is not
    None`` check, ``cfg.audit_mutate`` around the seeded fault) — the
    CI teeth behind the audit plane's default-off bit-identity
    contract."""
    from deneva_tpu.runtime.gates import GATES

    root = os.path.join(FIX, "gate_bad_audit")
    tree = Tree(root, ["."])
    findings = tree.filter(gateconsistency.check(
        tree, gates={"audit": GATES["audit"]}, exempt=(),
        escrow_funcs=(), escrow_home=(),
        config_module="deneva_tpu/config.py", guarded=(), model={}))
    assert _got(findings) == _expected(root), \
        "\n".join(f.render() for f in findings)


def test_ctrl_gate_fires_on_unguarded_use():
    """The REAL ``ctrl`` GateSpec (runtime/gates.py) catches an
    unguarded call into either ctrl home module (runtime/controller.py,
    cc/router.py) and an unguarded deep use of the controller handle,
    while accepting the guarded idioms the runtime uses (``cfg.ctrl``
    at construction, ``self.ctl is not None``, the engine's ``knobs is
    not None`` routing test, ``cfg.zipf_shift`` around the client's
    staged ring) — the CI teeth behind the control plane's default-off
    bit-identity contract."""
    from deneva_tpu.runtime.gates import GATES

    root = os.path.join(FIX, "gate_bad_ctrl")
    tree = Tree(root, ["."])
    findings = tree.filter(gateconsistency.check(
        tree, gates={"ctrl": GATES["ctrl"]}, exempt=(),
        escrow_funcs=(), escrow_home=(),
        config_module="deneva_tpu/config.py", guarded=(), model={}))
    assert _got(findings) == _expected(root), \
        "\n".join(f.render() for f in findings)


def test_dgcc_gate_fires_on_unguarded_use():
    """The REAL ``dgcc`` GateSpec (runtime/gates.py) catches an
    unguarded call into the wavefront home module (cc/dgcc.py) and an
    unguarded wave-assignment use_call, while accepting the guarded
    idioms the runtime uses (``cfg.ctrl_dgcc`` dominating the call, a
    local alias of the flag) — the CI teeth behind the fourth router
    class's default-off bit-identity contract (CC_ALG=DGCC itself is
    registry dispatch, not a gate bypass)."""
    from deneva_tpu.runtime.gates import GATES

    root = os.path.join(FIX, "gate_bad_dgcc")
    tree = Tree(root, ["."])
    findings = tree.filter(gateconsistency.check(
        tree, gates={"dgcc": GATES["dgcc"]}, exempt=(),
        escrow_funcs=(), escrow_home=(),
        config_module="deneva_tpu/config.py", guarded=(), model={}))
    assert _got(findings) == _expected(root), \
        "\n".join(f.render() for f in findings)


def test_device_pin_gate_fires_on_silent_pin():
    """gate-device-pin: conjoining the REAL ``audit`` gate's guard with
    a ``device_parts`` comparison fires — the silent single-device pin
    that drops a subsystem on the mesh-sharded measured path — while
    the legal shapes stay silent (a bare device_parts route branch, a
    non-gate workload-layout conjunction) and config.py itself is
    exempt (validate() is the sanctioned home for multi-chip pins)."""
    from deneva_tpu.runtime.gates import GATES

    root = os.path.join(FIX, "gate_bad_devpin")
    tree = Tree(root, ["."])
    findings = tree.filter(gateconsistency.check(
        tree, gates={"audit": GATES["audit"]}, exempt=(),
        escrow_funcs=(), escrow_home=(),
        config_module="deneva_tpu/config.py", guarded=(), model={}))
    assert _got(findings) == _expected(root), \
        "\n".join(f.render() for f in findings)


def test_gate_registry_matches_config():
    """Executable half of gate-registry-drift: every registered flag is
    a real Config field defaulting OFF, every wiremodel gate names a
    registered subsystem, and every gated rtype sits outside the fault
    mask (the lint checks the ASTs; this pins the live objects)."""
    import dataclasses

    from deneva_tpu.config import Config
    from deneva_tpu.runtime.gates import GATES

    fields = {f.name: f for f in dataclasses.fields(Config)}
    for name, spec in GATES.items():
        for flag in spec.flags:
            assert flag in fields, (name, flag)
            assert not fields[flag].default, (name, flag)
        assert spec.all_guards(), name
        for req in spec.requires:
            assert req in GATES, (name, req)
    for s in WIRE_MODEL.values():
        if s.gate:
            assert s.gate in GATES, s.name
            assert not s.fault_mask, \
                f"gated rtype {s.name} must stay outside FAULT_RTYPE_MASK"


# ---- CLI exit codes (the smoke-gate contract) --------------------------

def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.graftlint", *args],
        cwd=REPO, capture_output=True, text=True).returncode


def test_cli_exit_codes():
    assert _cli(f"--root={os.path.join(FIX, 'bad')}", ".") == 1
    assert _cli(f"--root={os.path.join(FIX, 'wire_bad')}", ".") == 1
    assert _cli(f"--root={os.path.join(FIX, 'clean')}", ".") == 0
    assert _cli("deneva_tpu/") == 0
    # the gate fails CLOSED on a typo'd path (never "clean, 0 files")
    assert _cli("deneva_tpuu/") == 2


def test_changed_mode(tmp_path):
    """--changed lints exactly the git-diff-scoped subset: clean exit
    when nothing changed, findings when a changed file carries one, and
    exit 2 on a bad ref (never a silent pass)."""
    def git(*a):
        subprocess.run(["git", "-c", "user.email=ci@fx",
                        "-c", "user.name=ci", *a],
                       cwd=tmp_path, capture_output=True, check=True)

    def cli(*args):
        return subprocess.run(
            [sys.executable, "-m", "tools.graftlint",
             f"--root={tmp_path}", *args],
            cwd=REPO, capture_output=True, text=True)

    git("init", "-q")
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    clean_src = "import json\n\n\ndef f():\n    return json.dumps({})\n"
    (pkg / "mod.py").write_text(clean_src)
    git("add", "-A")
    git("commit", "-qm", "seed")
    r = cli("--changed", "pkg")
    assert r.returncode == 0 and "no python files changed" in r.stderr
    (pkg / "mod.py").write_text("import os\n" + clean_src)
    r = cli("--changed", "pkg")
    assert r.returncode == 1 and "imp-unused" in r.stdout
    r = cli("--changed=not-a-ref", "pkg")
    assert r.returncode == 2


def test_zero_suppressions_in_repo():
    """The acceptance statement: the tree is clean with ZERO
    suppression markers — nothing is waved through."""
    for top in ("deneva_tpu", "tools"):
        for dirpath, dirnames, files in os.walk(os.path.join(REPO, top)):
            # the linter package's own docs DEFINE the marker syntax
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", "graftlint")]
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                with open(os.path.join(dirpath, fn)) as f:
                    src = f.read()
                assert "graftlint: ignore" not in src \
                    and "graftlint: skip-file" not in src, \
                    os.path.join(dirpath, fn)


# ---- suppression syntax ------------------------------------------------

_SUPPRESSED = """import jax


@jax.jit
def f(x):
    # device-side decision is deliberate here (fixture reason)
    if x > 0:  # graftlint: ignore[trace-branch]
        x = x + 1
    return x
"""


def test_suppression_marker(tmp_path):
    d = tmp_path / "pkg"
    d.mkdir()
    (d / "sup_fx.py").write_text(_SUPPRESSED)
    tree = Tree(str(tmp_path), ["."])
    assert run_checkers(tree, {"trace"}) == []
    # control: without the marker the same code fires
    (d / "sup_fx.py").write_text(_SUPPRESSED.replace(
        "  # graftlint: ignore[trace-branch]", ""))
    tree = Tree(str(tmp_path), ["."])
    assert [f.rule for f in run_checkers(tree, {"trace"})] \
        == ["trace-branch"]


def test_skip_file_marker(tmp_path):
    d = tmp_path / "pkg"
    d.mkdir()
    (d / "skip_fx.py").write_text(
        "# graftlint: skip-file (generated fixture)\n"
        + _SUPPRESSED.replace("  # graftlint: ignore[trace-branch]", ""))
    tree = Tree(str(tmp_path), ["."])
    assert run_checkers(tree, {"trace"}) == []


# ---- runtime half: ownercheck.install guards ---------------------------

class _Srv:
    pass


def _guarded_server():
    from deneva_tpu.runtime import ownercheck

    s = _Srv()
    s.me = 0
    s.pending = deque([("c", "blk")])
    s._in_system = {11}
    s.repl_acked = {3: -1}
    s._feed_free = [{}]
    n = ownercheck.install(s)
    assert n == 4        # exactly the wrappable GUARDED attrs present
    return ownercheck, s


def test_ownercheck_owner_thread_mutates_freely():
    _oc, s = _guarded_server()
    s.pending.append(("c", "blk2"))
    s._in_system.add(12)
    s.repl_acked[3] = 5
    s._feed_free.pop()
    assert len(s.pending) == 2 and s.repl_acked[3] == 5


def test_ownercheck_cross_thread_mutation_raises():
    oc, s = _guarded_server()
    def _ior():
        buf = s._in_system           # aliased in-place mutation: the
        buf |= {97, 98}              # case only the runtime half sees

    ops = [lambda: s.pending.append(("x", "y")),
           lambda: s._in_system.discard(11),
           lambda: s.repl_acked.update({3: 9}),
           lambda: s.repl_acked.__setitem__(3, 9),
           lambda: s._feed_free.pop(),
           _ior]
    caught = []

    def hostile():
        for op in ops:
            try:
                op()
            except oc.OwnershipViolation as e:
                caught.append(str(e))

    t = threading.Thread(target=hostile, name="wire-worker-fx")
    t.start()
    t.join()
    assert len(caught) == len(ops)
    assert "wire-worker-fx" in caught[0]
    # the guard rejects BEFORE mutating: state is untouched
    assert len(s.pending) == 1 and s.repl_acked[3] == -1
    assert s._in_system == {11} and len(s._feed_free) == 1


def test_ownercheck_cross_thread_reads_are_free():
    _oc, s = _guarded_server()
    got = []

    def reader():
        got.append((len(s.pending), 3 in s.repl_acked,
                    sorted(s._in_system), list(s.pending)))

    t = threading.Thread(target=reader)
    t.start()
    t.join()
    assert got == [(1, True, [11], [("c", "blk")])]


def test_ownercheck_preserves_deque_maxlen():
    from deneva_tpu.runtime import ownercheck

    s = _Srv()
    s.me = 1
    s._committed_recent = deque([1, 2], maxlen=2)
    assert ownercheck.install(s) == 1
    s._committed_recent.append(3)
    assert list(s._committed_recent) == [2, 3]
    assert s._committed_recent.maxlen == 2


def test_ownercheck_owner_map_covers_guarded():
    """Every GUARDED attr must have a declared owner (the static checker
    enforces the server side; this pins the declarations file itself)."""
    from deneva_tpu.runtime import ownercheck as oc

    assert set(oc.GUARDED) <= set(oc.OWNER)
    assert all(oc.OWNER[a] == oc.DISPATCH for a in oc.GUARDED)
    for role in oc.WORKER_ENTRY:
        assert role in (oc.WIRE, oc.RETIRE)
