"""`benchmark/trace_reduce.py`: the interval arithmetic on hand-made
events, and the whole reduction on a small trace recorded on the chip
(`benchmark/testdata/tiny.xplane.pb`: a toy-sized cell, 0.05 s of a
served TPU_BATCH run on one v5e, my chip run, PR 24).  And the traced
window of `benchmark/server_child.py` under a fake profiler: the Python
tracer off, `stop_trace` waited for as long as the run's own limit
allows, a window that never comes back a failure by name."""

import json
import os
import threading
import time

import pytest

from conftest import BENCH, load_script

TINY = os.path.join(BENCH, "testdata", "tiny.xplane.pb")
SCOPED = os.path.join(BENCH, "testdata", "tiny_scoped.xplane.pb")


def test_union_and_gaps(trace_reduce):
    ev = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (32, 35, "d")]
    busy, gaps = trace_reduce.union_s(ev)
    assert busy == pytest.approx(30e-9) and gaps == [(20, 30)]
    assert trace_reduce.union_s([]) == (0.0, [])


def test_self_time_charges_a_loop_only_what_its_body_leaves(trace_reduce):
    # a while of 100 ns around two fusions of 30 ns and one nested pair
    ev = [(0, 100, "while"), (10, 40, "fusion.1"), (50, 80, "fusion.2"),
          (55, 60, "inner"), (200, 210, "fusion.1")]
    st = trace_reduce.self_times(ev)
    assert st["while"] == pytest.approx(40e-9)
    assert st["fusion.1"] == pytest.approx(40e-9)
    assert st["fusion.2"] == pytest.approx(25e-9)
    assert st["inner"] == pytest.approx(5e-9)
    assert sum(st.values()) == pytest.approx(110e-9)    # == the union


def test_gaps_are_named_by_the_host_event_that_covers_them(trace_reduce):
    host = [(0, 50, "np.asarray(jax.Array)"), (40, 45, "Execute")]
    out = trace_reduce.name_gaps([(10, 30), (41, 44), (100, 101)], host)
    assert out[0] == ["np.asarray(jax.Array)", pytest.approx(20e-9)]
    assert out[2] == ["no host event", pytest.approx(1e-9)]


def test_a_trace_without_a_device_plane_is_refused(trace_reduce):
    class Plane:
        name, lines = "/host:CPU", []

    class Prof:
        planes = [Plane()]
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce(Prof(), 1.0, 4)


def _profile(ops):
    """A one-chip profile whose "XLA Ops" line holds ``ops``."""
    class Ev:
        def __init__(self, a, b, n):
            self.start_ns, self.duration_ns, self.name = a, b - a, n

    class Line:
        name = "XLA Ops"
        events = [Ev(*o) for o in ops]

    class Plane:
        name, lines = "/device:TPU:0", [Line()]

    class Prof:
        planes = [Plane()]
    return Prof()


@pytest.mark.parametrize("host_window_s, want_window_s", [
    (2.0, 2.0),         # the host's reading encloses the operations
    (0.9, 1.0),         # the profiler recorded from before the reading
])
def test_busy_time_never_passes_the_window(trace_reduce, host_window_s,
                                           want_window_s):
    ops = [(0, 600_000_000, "a"), (600_000_000, 1_000_000_000, "b")]
    got = trace_reduce.reduce(_profile(ops), host_window_s, 4)
    assert got["busy_s"] == pytest.approx(1.0)
    assert got["window_s"] == pytest.approx(want_window_s)
    assert got["busy_s"] <= got["window_s"]


def test_the_recorded_chip_trace_reduces_to_its_known_numbers(trace_reduce):
    want = json.load(open(os.path.join(BENCH, "testdata",
                                       "tiny_expected.json")))
    got = trace_reduce.reduce(trace_reduce.load(TINY), want["window_s"],
                              want["epochs_per_group"])
    assert got["chips"] == 1 and got["window_s"] == want["window_s"]
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert got["groups"] == want["groups"] > 0
    assert got["epochs"] == want["groups"] * want["epochs_per_group"]
    assert got["group_busy_s"] == pytest.approx(want["group_busy_s"])
    ops = got["breakdown"]["device_ops"]
    # no scopes handed in (this trace's HLO has none): "unscoped:"
    assert 1 <= len(ops) <= 10 and ops[0][0] == want["top_op"]
    assert all(n.startswith("unscoped:") for n, _ in ops)
    assert ops == sorted(ops, key=lambda r: -r[1])
    # self times never add up to more than the device was busy
    assert sum(s for _, s in ops) <= got["busy_s"] * (1 + 1e-9)
    assert len(got["breakdown"]["idle_gaps"]) <= 10


@pytest.mark.parametrize("event,scopes,want", [
    ("%fusion.62 = u8[6291520,100]{1,0:T(8,128)(4,1)} fusion(u8[6291520,100]"
     "{1,0:T(8,128)(4,1)} %get-tuple-element.715, s32[30720]{0} %x), "
     "kind=kLoop", {"fusion.62": "ep.write"},
     "ep.write:fusion u8[6291520,100]"),
    # another PR's number for the same op: the same key
    ("%fusion.104 = u8[6291520,100]{1,0:T(8,128)(4,1)} fusion(...)",
     {"fusion.104": "ep.write"}, "ep.write:fusion u8[6291520,100]"),
    ("%multiply_reduce_fusion.3 = (f32[1024]{0:T(1024)S(1)}, f32[1024]{0}) "
     "fusion(f32[1024,1024]{1,0} %a)", {"multiply_reduce_fusion.3":
                                         "ep.validate"},
     "ep.validate:multiply_reduce_fusion (f32[1024],..)"),
    # the table's two relayout copies differ in layout only: one entry
    ("%copy.28 = u8[6291520,100]{0,1:T(8,128)(4,1)} copy(u8[6291520,100]"
     "{1,0:T(8,128)(4,1)} %p)", {}, "unscoped:copy u8[6291520,100]"),
    ("%convert_reduce_fusion.14 = u32[]{:T(128)} fusion(pred[1024,10]{1,0} "
     "%c)", {"convert_reduce_fusion.14": "ep.stats"},
     "ep.stats:convert_reduce_fusion u32[]"),
    ("while.4", {}, "unscoped:while"),          # a name with no HLO text
], ids=["fusion", "renumbered", "tuple", "relayout_copy", "scalar", "bare"])
def test_a_device_op_is_keyed_by_scope_op_and_shape_not_by_number(
        trace_reduce, event, scopes, want):
    assert trace_reduce.op_key(event, scopes) == want


def test_the_breakdown_of_a_scoped_trace_names_ops_by_their_scope(
        trace_reduce):
    """With the scopes `phase_reduce.hlo_scopes` reads from the trace's
    own HLO: ten entries at most, largest first, each `<scope>:<op>
    <shape>`; the four bucket scatter-adds of that tree's validation,
    `fusion.84`-`fusion.87`, are ONE entry; the idle gaps stay."""
    pr = load_script("phase_reduce.py")
    with open(SCOPED, "rb") as f:
        scopes = pr.hlo_scopes(f.read())
    got = trace_reduce.reduce(trace_reduce.load(SCOPED), 0.05, 4, scopes)
    ops = got["breakdown"]["device_ops"]
    assert len(ops) == 10 and ops == sorted(ops, key=lambda r: -r[1])
    assert ops[0][0] == "ep.validate:fusion bf16[64,512]"
    assert all(len(n) <= 64 and ":" in n and "%" not in n for n, _ in ops)
    assert {"ep.write:fusion u8[4160,100]", "ep.read:fusion u8[256,100]"} \
        <= {n for n, _ in ops}
    assert sum(s for _, s in ops) <= got["busy_s"] * (1 + 1e-9)
    bare = trace_reduce.reduce(trace_reduce.load(SCOPED), 0.05, 4)
    assert bare["busy_s"] == got["busy_s"]
    assert bare["breakdown"]["idle_gaps"] == got["breakdown"]["idle_gaps"]
    assert all(n.startswith("srv.") for n, _ in
               got["breakdown"]["idle_gaps"])


# ---- the traced window of the server child -------------------------------

class FakeProfiler:
    """`jax.profiler.start_trace` / `stop_trace` for `trace_window`:
    records how the trace was opened; `stop_trace` takes ``stop_s``, or
    never returns (until the test lets it go) with ``stop_s`` None."""

    def __init__(self, stop_s):
        self.stop_s, self.opened, self.let_go = stop_s, [], threading.Event()

    def start_trace(self, log_dir, **kw):
        self.opened.append((log_dir, kw))

    def stop_trace(self):
        if self.stop_s is None:
            self.let_go.wait(60)
        else:
            time.sleep(self.stop_s)


def _traced_window(monkeypatch, tmp_path, fake, serve_s, start_s=0.0):
    """What `server_child.main` does around `node.run()`, with the fake
    profiler: returns (server_child, thread, what it handed back)."""
    import jax
    sc = load_script("server_child.py")
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    barrier = tmp_path / "barrier_ns"
    barrier.write_text(str(time.monotonic_ns()))
    traced, stop = {}, threading.Event()
    th = threading.Thread(target=sc.trace_window, daemon=True, args=(
        dict(dir=str(tmp_path / "trace"), start_s=start_s, len_s=0.05),
        str(barrier), traced, stop))
    th.start()
    time.sleep(serve_s)                 # the serve loop
    stop.set()
    return sc, th, traced


def test_a_stop_trace_that_outlives_the_old_budget_still_yields_its_window(
        monkeypatch, tmp_path):
    """Time scaled by 100: the old fixed wait was 120 s (1.2 s here), a
    child's limit is 1100 s (11 s).  `stop_trace` takes 1.5 s: the old
    join gave up and the run lost every device metric; now it waits."""
    fake = FakeProfiler(stop_s=1.5)
    sc, th, traced = _traced_window(monkeypatch, tmp_path, fake, 0.2)
    sc.join_trace(th, traced, 11.0)
    assert not th.is_alive()
    assert traced["window_s"] == pytest.approx(0.05, abs=0.04)
    assert traced["stop_cost_s"] >= 1.5 > 1.2
    # the Python tracer is off, the host tracer (the `srv.*` spans) is not
    (log_dir, kw), = fake.opened
    assert log_dir == str(tmp_path / "trace")
    assert kw["profiler_options"].python_tracer_level == 0
    assert kw["profiler_options"].host_tracer_level > 0


def test_a_stop_trace_that_never_returns_fails_the_run_by_name(
        monkeypatch, tmp_path):
    fake = FakeProfiler(stop_s=None)
    sc, th, traced = _traced_window(monkeypatch, tmp_path, fake, 0.2)
    try:
        with pytest.raises(RuntimeError, match="stop_trace: no end after"):
            sc.join_trace(th, traced, 0.5)
        assert "window_s" not in traced
        with pytest.raises(RuntimeError, match="stop_trace: no end after 0 s"):
            sc.join_trace(th, traced, -3.0)     # the limit already passed
    finally:
        fake.let_go.set()
        th.join(5)


def test_a_serve_loop_that_ends_before_the_window_opens_fails_too(
        monkeypatch, tmp_path):
    fake = FakeProfiler(stop_s=0.0)
    sc, th, traced = _traced_window(monkeypatch, tmp_path, fake, 0.1,
                                    start_s=30.0)
    with pytest.raises(RuntimeError, match="before the traced window"):
        sc.join_trace(th, traced, 5.0)
    assert fake.opened == []
