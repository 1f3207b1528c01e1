"""The dispatch loop's stage clock (`runtime/stages.py`) and the epoch
program's named phases: one recorder call per loop boundary, window
sums that add up to the wall, the thread's CPU beside the wall and every
interval kept (PR 40), the `[timeline]` / `[crit]` lines as they were,
`srv.*` spans in a live profiler trace, and `jax.named_scope`s that
change no verdict and no byte of the table."""

import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from deneva_tpu.runtime import stages
from deneva_tpu.runtime.stages import STAGES, StageClock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def clock(monkeypatch):
    """Hand-driven clocks for the recorder: t[0] is now on the wall,
    t[1] on the dispatch thread's CPU clock, t[2] on the process's."""
    t = [100.0, 5.0, 9.0]
    monkeypatch.setattr(stages.time, "monotonic", lambda: t[0])
    monkeypatch.setattr(stages.time, "thread_time", lambda: t[1])
    monkeypatch.setattr(stages.time, "process_time", lambda: t[2])
    return t


def _pass(clk, t, epoch0, secs, cpu=None):
    """One dispatch pass spending ``secs[stage]`` in each stage, of it
    ``cpu[stage]`` = (on this thread's CPU, on the other threads')."""
    def spend(s):
        t[0] += secs.get(s, 0.0)
        if cpu is not None:
            mine, others = cpu.get(s, (0.0, 0.0))
            t[1] += mine
            t[2] += mine + others

    clk.begin_pass(epoch0, 4, queue_txns=10)
    for s in ("drain", "admit", "collect", "feed", "dispatch"):
        spend(s)
        nxt = {"drain": "admit", "admit": "collect", "collect": "feed",
               "feed": "dispatch", "dispatch": "other"}[s]
        at = clk.enter(nxt)
        if nxt == "dispatch":
            t_disp = at
    clk.enter("retire_wait", epoch0)
    spend("retire_wait")
    clk.enter("retire", epoch0)
    spend("retire")
    clk.enter("other")
    clk.retired(t_disp)
    spend("other")


def test_every_second_of_the_loop_belongs_to_one_stage(clock):
    clk = StageClock()
    secs = dict(drain=0.01, admit=0.2, collect=0.03, feed=0.004,
                dispatch=0.05, retire_wait=0.6, retire=0.1, other=0.006)
    t0 = clock[0]
    for g in range(3):
        _pass(clk, clock, 4 * g, secs)
    clk.end()
    got = clk.since(None)
    for s in STAGES:
        assert got[f"stage_{s}_time"] == pytest.approx(3 * secs[s])
    assert sum(got[f"stage_{s}_time"] for s in STAGES) == \
        pytest.approx(clock[0] - t0)
    assert got["stage_epoch_cnt"] == 12
    # every key of the line has a reader: no per-stage or group counts
    assert not [k for k in got if k.endswith("_cnt")
                and k != "stage_epoch_cnt"]
    assert got["queue_txn_mean"] == 10
    # dispatch start -> end of the group's retire
    assert got["pipeline_time_mean"] == pytest.approx(0.05 + 0.6 + 0.1)


def test_the_window_snapshot_leaves_the_warm_up_out(clock):
    clk = StageClock()
    _pass(clk, clock, 0, dict(collect=0.5, admit=0.1))   # warm-up only
    clock[0] += 0.25                  # the snapshot falls inside `other`
    snap = clk.snapshot()
    t_edge = clock[0]
    _pass(clk, clock, 4, dict(admit=0.2, retire=0.3, other=0.05))
    clk.end()
    win = clk.since(snap)
    assert win["stage_collect_time"] == 0.0       # ran only in warm-up
    assert win["stage_admit_time"] == pytest.approx(0.2)
    assert win["stage_other_time"] == pytest.approx(0.05)
    assert win["stage_epoch_cnt"] == 4
    assert sum(win[f"stage_{s}_time"] for s in STAGES) == \
        pytest.approx(clock[0] - t_edge)
    # the whole run still holds both
    assert clk.since(None)["stage_collect_time"] == pytest.approx(0.5)


def test_an_empty_window_reads_zero_and_divides_by_nothing(clock):
    clk = StageClock()
    _pass(clk, clock, 0, dict(admit=0.1))
    clk.end()
    win = clk.since(clk.snapshot())
    assert all(v == 0 for v in win.values())


def test_a_wait_inside_a_working_stage_is_recharged(clock):
    clk = StageClock()
    _pass(clk, clock, 0, dict(dispatch=1.0, collect=0.5))
    clk.shift("dispatch", "collect", 0.4)     # a vote wait inside dispatch
    clk.shift("collect", "feed", 0.1)         # blob decode inside collect
    clk.end()
    got = clk.since(None)
    assert got["stage_dispatch_time"] == pytest.approx(0.6)
    assert got["stage_collect_time"] == pytest.approx(0.8)
    assert got["stage_feed_time"] == pytest.approx(0.1)


def test_one_boundary_call_feeds_the_timeline_and_the_crit_ledger(
        clock, capsys):
    """The recorder's reading is the older ledgers' reading: the
    `[timeline]` line keeps its span names and order, the `[crit]`
    stages sum to its wall, both lines parse as before."""
    from deneva_tpu.harness.parse import parse_metrics
    from deneva_tpu.harness.timeline import parse_timeline
    from deneva_tpu.runtime import metricsbus as MB
    from deneva_tpu.runtime.server import _Timeline

    tl, crit = _Timeline(), MB.CritLedger(0)
    crit._time = stages.time.monotonic
    crit.reset()
    clk = StageClock(tl, crit)
    secs = dict(drain=0.001, admit=0.010, collect=0.040, feed=0.002,
                dispatch=0.018, retire_wait=0.300, retire=0.020,
                other=0.700)
    _pass(clk, clock, 8, secs)
    clk.begin_pass(12, 4, 0)          # closes the pass: `loop` on the line
    tl.emit(0, 12)
    assert crit.end_pass(12) is not None          # 1 s passed: emits
    out = capsys.readouterr().out.splitlines()
    line = [ln for ln in out if ln.startswith("[timeline] ")][0]
    assert re.findall(r"(\w+)=[0-9.]+ms", line) == [
        "loop", "admit", "collect", "dispatch", "retire", "loop"]
    ph = parse_timeline(out)[0]["phases"]
    assert ph["admit"] == pytest.approx(11.0)     # drain + admit
    assert ph["collect"] == pytest.approx(40.0)
    assert ph["dispatch"] == pytest.approx(20.0)  # feed + dispatch
    assert ph["retire"] == pytest.approx(320.0)   # the wait + the work
    c = [r for r in parse_metrics(out) if r["family"] == "crit"][0]
    assert c["admit_ms"] == pytest.approx(11.0)
    assert c["wire_ms"] == pytest.approx(40.0)
    assert c["device_ms"] == pytest.approx(20.0)
    assert c["retire_ms"] == pytest.approx(320.0)
    assert sum(c[s + "_ms"] for s in MB.CRIT_STAGES) == \
        pytest.approx(c["wall_ms"])
    assert c["gate"] == "other"


def test_the_spans_land_in_a_live_profiler_trace(tmp_path):
    """With a profiler session open, every stage is a `srv.<stage>` span
    on the host plane tagged with its group; with none, nothing is
    written and nothing fails."""
    import glob

    import jax
    clk = StageClock()
    _pass(clk, [0.0], 0, {})                      # no session: no-ops
    jax.profiler.start_trace(str(tmp_path))
    try:
        clk.begin_pass(40, 4, 0)
        clk.enter("admit")
        with stages.span("drain", 40):
            pass
        clk.enter("dispatch")
        clk.enter("retire_wait", 36)
        clk.enter("retire", 36)
        clk.end()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    prof = jax.profiler.ProfileData.from_file(path)
    got = [(e.name, dict(e.stats).get("group"))
           for p in prof.planes if p.name.startswith("/host:")
           for ln in p.lines for e in ln.events
           if e.name.startswith("srv.")]
    assert sorted(got) == sorted([
        ("srv.drain", 40), ("srv.admit", 40), ("srv.drain", 40),
        ("srv.dispatch", 40), ("srv.retire_wait", 36), ("srv.retire", 36)])


# ---- the thread's CPU beside the wall, and every interval kept (PR 40) ---

SECS = dict(drain=0.01, admit=0.2, collect=0.03, feed=0.004, dispatch=0.05,
            retire_wait=0.6, retire=0.1, other=0.006)
# (this thread's CPU, the other threads') inside each stage: admit works,
# retire is held up by another thread, the wait burns nothing
CPU = dict(drain=(0.01, 0.0), admit=(0.19, 0.02), feed=(0.004, 0.0),
           dispatch=(0.03, 0.01), retire=(0.02, 0.07), other=(0.006, 0.0),
           retire_wait=(0.001, 0.3))


def test_cpu_seconds_ride_the_same_boundaries_as_the_wall(clock):
    clk = StageClock()
    _pass(clk, clock, 0, SECS, CPU)               # warm-up
    snap = clk.snapshot()
    for g in (1, 2):
        _pass(clk, clock, 4 * g, SECS, CPU)
    clk.shift("dispatch", "collect", 0.02)        # moves wall only
    clk.end()
    win, whole = clk.since(snap), clk.since(None)
    for s in STAGES:
        mine = CPU.get(s, (0.0, 0.0))[0]
        assert win[f"stage_{s}_cpu_time"] == pytest.approx(2 * mine)
        assert whole[f"stage_{s}_cpu_time"] == pytest.approx(3 * mine)
        assert win[f"stage_{s}_cpu_time"] <= 2 * SECS[s] + 1e-9
    assert win["stage_dispatch_time"] == pytest.approx(2 * 0.05 - 0.02)
    assert win["process_cpu_time"] == pytest.approx(
        2 * sum(a + b for a, b in CPU.values()))
    assert whole["process_cpu_time"] == pytest.approx(
        3 * sum(a + b for a, b in CPU.values()))
    # the accepted keys are all there, beside the nine new ones
    assert {k for k in win if "cpu" in k} == {
        f"stage_{s}_cpu_time" for s in STAGES} | {"process_cpu_time"}
    assert set(win) - {k for k in win if "cpu" in k} == {
        f"stage_{s}_time" for s in STAGES} | {
        "stage_epoch_cnt", "queue_txn_mean", "pipeline_time_mean"}


def test_a_busy_stage_reads_cpu_near_its_wall_and_a_sleeping_one_none():
    """On the real clocks: a stage that spins is charged the CPU it
    burnt, one that sleeps next to nothing, and no stage more CPU than
    wall (the two clocks are read a fraction of a microsecond apart)."""
    clk = StageClock()
    clk.begin_pass(0, 4, 0)
    clk.enter("admit")
    c0 = time.thread_time()
    while time.thread_time() - c0 < 0.05:
        pass
    clk.enter("retire_wait")
    time.sleep(0.05)
    clk.end()
    got = clk.since(None)
    assert got["stage_admit_cpu_time"] == pytest.approx(0.05, abs=0.005)
    assert got["stage_retire_wait_time"] >= 0.05
    assert got["stage_retire_wait_cpu_time"] < 0.01
    for s in STAGES:
        assert got[f"stage_{s}_cpu_time"] <= got[f"stage_{s}_time"] + 1e-4
    assert got["process_cpu_time"] >= sum(
        got[f"stage_{s}_cpu_time"] for s in STAGES) - 1e-4
    by_stage = {r["stage"]: r for r in clk.record(None)["longest"]}
    busy, wait = by_stage["admit"], by_stage["retire_wait"]
    assert not busy["wait"] and busy["cpu_s"] == pytest.approx(0.05, abs=0.005)
    assert wait["wait"] and wait["cpu_s"] < 0.01 <= 0.05 <= wait["wall_s"]


def test_the_records_walls_add_up_to_the_loops_wall(clock):
    clk = StageClock()
    t0 = clock[0]
    clock[0] += 0.5                   # set-up before the first pass: `other`
    for g in range(3):
        _pass(clk, clock, 4 * g, SECS, CPU)
    clk.end()
    rows = clk.rows()
    assert len(rows) == clk.intervals == 1 + 3 * 9    # 9 boundaries a pass
    assert rows[:, 3].sum() == pytest.approx(clock[0] - t0)
    # back to back: an interval starts where the one before it ended
    assert rows[0, 2] == t0
    np.testing.assert_allclose(rows[1:, 2], rows[:-1, 2] + rows[:-1, 3])
    got = clk.since(None)
    for i, s in enumerate(STAGES):
        mine = rows[rows[:, 0] == i]
        assert mine[:, 3].sum() == pytest.approx(got[f"stage_{s}_time"])
        assert mine[:, 4].sum() == pytest.approx(got[f"stage_{s}_cpu_time"])
    # the sixth field is the process clock's last READING, taken at a
    # pass's start (each pass here outlasts `PROCESS_EVERY_S`): it never
    # falls, and a pass's intervals all carry its start's reading
    assert (np.diff(rows[:, 5]) >= 0).all()
    assert len(set(rows[1:10, 5])) == 1 and rows[10, 5] > rows[9, 5]
    assert rows[10, 5] - rows[1, 5] == pytest.approx(
        sum(a + b for a, b in CPU.values()))
    rec = clk.record(None)
    assert (rec["intervals"], rec["dropped"]) == (28, 0)
    assert rec["t_end"] - rec["t_start"] == pytest.approx(clock[0] - t0)


def test_the_process_clock_is_read_at_a_pass_start_ten_times_a_second(
        clock, monkeypatch):
    """It sums over the process's threads (tens of microseconds in a
    server of hundreds): not at every boundary."""
    reads = []
    monkeypatch.setattr(stages.time, "process_time",
                        lambda: reads.append(clock[0]) or clock[2])
    clk = StageClock()
    assert len(reads) == 1                        # the clock's first
    for g in range(30):                           # passes of 0.03 s
        _pass(clk, clock, 4 * g, dict(admit=0.01, retire=0.02),
              dict(admit=(0.01, 0.0), retire=(0.01, 0.02)))
    assert len(reads) == 1 + 7                    # every fourth pass
    assert np.diff(reads)[1:] == pytest.approx(0.12)
    clk.end()
    rec = clk.record(None)
    # an interval's others' CPU: over the four passes between readings
    r = rec["longest"][5]
    assert (r["stage"], r["span_s"], r["others_cpu_s"]) == (
        "retire", pytest.approx(0.12), pytest.approx(4 * 0.02))
    n = len(reads)
    snap = clk.snapshot()
    win = clk.since(snap)
    assert len(reads) == n + 2 and win["process_cpu_time"] == 0


def test_the_ring_keeps_the_newest_intervals_and_counts_the_dropped(
        clock, monkeypatch):
    assert stages.RING == 1 << 17
    assert len(StageClock()._ring) == 6 * 8 * stages.RING   # preallocated
    monkeypatch.setattr(stages, "RING", 64)
    clk = StageClock()
    for g in range(30):
        _pass(clk, clock, 4 * g, dict(admit=0.001 * (g + 1)))
    clk.end()
    rows = clk.rows()
    assert clk.intervals == 30 * 9 + 1 and len(rows) == 64
    rec = clk.record(None)
    assert (rec["intervals"], rec["dropped"]) == (271, 271 - 64)
    # the newest: oldest first, back to back, up to the loop's end
    np.testing.assert_allclose(rows[1:, 2], rows[:-1, 2] + rows[:-1, 3])
    assert rows[-1, 2] + rows[-1, 3] == pytest.approx(clock[0])
    # the longest admit of all is the last pass's, and it was kept
    assert (rec["longest"][0]["stage"], rec["longest"][0]["group"]) == (
        "admit", 4 * 29)
    assert rec["longest"][0]["wall_s"] == pytest.approx(0.030)


def test_longest_is_sorted_carries_the_spans_group_and_marks_the_waits(
        clock):
    clk = StageClock()
    t0 = clock[0]
    _pass(clk, clock, 0, SECS, CPU)               # warm-up
    snap = clk.snapshot()
    for g, admit in ((1, 0.2), (2, 0.9), (3, 0.3)):
        clk.begin_pass(4 * g, 4, 0)
        clk.enter("admit")
        clock[0] += admit
        clock[1] += 0.1                           # 0.1 s of it on the CPU
        clock[2] += 0.1 + 0.75 * admit            # the rest: other threads
        clk.enter("dispatch")
        clock[0] += 0.01
        # a retirement belongs to the group it retires, as its span does
        clk.enter("retire_wait", 4 * (g - 1))
        clock[0] += 0.5
        clk.enter("retire", 4 * (g - 1))
        clock[0] += 0.05
        clk.enter("other")
    clk.end()
    rec = clk.record(snap)
    assert rec["clock"] == "CLOCK_MONOTONIC" and rec["cpus"] >= 1
    assert rec["t_start"] == t0 and rec["t_meas"] == snap["now"]
    assert rec["t_end"] == clock[0]
    for key in ("longest", "longest_work"):
        walls = [r["wall_s"] for r in rec[key]]
        assert walls == sorted(walls, reverse=True) and len(walls) <= 16
        assert set(rec[key][0]) == {"stage", "group", "at_s", "wall_s",
                                    "cpu_s", "others_cpu_s", "span_s",
                                    "wait"}
    # the other threads' CPU is read over the span between two readings
    # of the process clock that enclose the interval: here its pass
    top = rec["longest"][0]
    assert top == dict(stage="admit", group=8, at_s=pytest.approx(
        top["at_s"]), wall_s=0.9, cpu_s=0.1, others_cpu_s=0.675,
        span_s=1.46, wait=False)
    # the last pass is closed by no reading: nothing is claimed for it
    last = [r for r in rec["longest"] if r["stage"] == "admit"
            and r["group"] == 12][0]
    assert last["others_cpu_s"] is None and last["span_s"] is None
    assert clock[0] - t0 > top["at_s"] > 1.0      # since the clock's start
    assert [(r["stage"], r["wait"]) for r in rec["longest"][1:5]] == [
        ("retire_wait", True)] * 4
    # the wait of the pass of group 12 carries the group it retired
    assert {r["group"] for r in rec["longest"][1:5]} == {0, 4, 8}
    assert not [r for r in rec["longest_work"] if r["wait"]]
    assert [r["stage"] for r in rec["longest_work"][:3]] == ["admit"] * 3
    assert len(rec["longest"]) == 16
    # the window's passes, begin_pass to begin_pass: two whole ones
    assert rec["pass_wall_s"] == dict(
        p50=pytest.approx((0.76 + 1.46) / 2), p99=pytest.approx(
            0.76 + 0.99 * 0.7), max=pytest.approx(1.46))


# ---- a short served run --------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One server + one client through the launcher, `[timeline]` and
    `[crit]` armed (OCC on a hot toy table: aborts and retries too).
    Four seconds, a quarter of them warm-up: beside five other xdist
    workers one pass of the loop has stalled for over a second, which a
    two-second run (PR 31's whole run: a window of 0.35 s, 8 passes)
    does not outlast."""
    d = tmp_path_factory.mktemp("served")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "deneva_tpu.runtime.launch", "--node_cnt=1",
         "--client_node_cnt=1", "--cc_alg=OCC", "--epoch_batch=128",
         "--synth_table_size=4096", "--req_per_query=4", "--max_accesses=4",
         "--zipf_theta=0.9", "--warmup_secs=1.0", "--done_secs=3.0",
         "--debug_timeline=true", "--metrics=true", f"--log_dir={d}"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout.splitlines()


def test_window_stage_seconds_add_up_to_the_window_wall(served):
    from deneva_tpu.stats import parse_summary
    line = [ln for ln in served if ln.startswith("node 0 (server): ")][0]
    s = parse_summary(line.split(": ", 1)[1])
    total = sum(s[f"stage_{st}_time"] for st in STAGES)
    assert total == pytest.approx(s["stage_wall_time"], rel=0.01)
    # the window, not the run: warm-up is a quarter of the run
    # (both clocks start at the window's first pass, a few ms apart)
    assert s["stage_wall_time"] == pytest.approx(s["total_runtime"],
                                                 rel=0.02, abs=0.03)
    assert s["stage_epoch_cnt"] < s["epoch_cnt"]
    assert s["pipeline_time_mean"] > 0
    assert s["stage_retire_wait_time"] > 0 and s["stage_admit_time"] > 0
    assert s["queue_txn_mean"] >= 0
    # the reference's two worker times are read off the same clock
    assert s["worker_process_time"] >= s["stage_retire_wait_time"]
    assert s["worker_idle_time"] >= 0


def test_the_closing_lines_carry_the_cpu_keys_and_the_stage_record(served):
    from deneva_tpu.stats import parse_summary
    line = [ln for ln in served if ln.startswith("node 0 (server): ")][0]
    s = parse_summary(line.split(": ", 1)[1])
    # every accepted key of the clock, as it was
    for k in [f"stage_{st}_time" for st in STAGES] + [
            "stage_wall_time", "stage_epoch_cnt", "queue_txn_mean",
            "pipeline_time_mean"]:
        assert k in s
    # the nine new ones: no stage is charged more CPU than wall, the
    # thread's CPU is a part of the process's, the loop does work
    for st in STAGES:
        assert 0 <= s[f"stage_{st}_cpu_time"] <= s[f"stage_{st}_time"] + 1e-3
    cpu = sum(s[f"stage_{st}_cpu_time"] for st in STAGES)
    assert 0 < cpu <= s["process_cpu_time"] + 1e-3
    assert s["stage_admit_cpu_time"] > 0
    dev = [ln for ln in served if ln.startswith("[device] node=0 ")][0]
    rec = json.loads(dev.split(" ", 2)[2])["stage_record"]
    assert rec["clock"] == "CLOCK_MONOTONIC" and rec["cpus"] >= 1
    assert rec["t_start"] < rec["t_meas"] < rec["t_end"]
    # the window the `[summary]` keys cover is the record's
    assert rec["t_end"] - rec["t_meas"] == pytest.approx(
        s["stage_wall_time"], abs=0.05)
    assert rec["intervals"] > 9 * 10 and rec["dropped"] == 0
    pw = rec["pass_wall_s"]
    assert 0 < pw["p50"] <= pw["p99"] <= pw["max"] < s["stage_wall_time"]
    for key in ("longest", "longest_work"):
        walls = [r["wall_s"] for r in rec[key]]
        assert len(walls) == 16 and walls == sorted(walls, reverse=True)
        assert all(r["wait"] == (r["stage"] in ("retire_wait", "collect"))
                   and r["cpu_s"] <= r["wall_s"] + 1e-3 for r in rec[key])
        assert all(r["span_s"] is None or r["span_s"] >= r["wall_s"] - 1e-6
                   for r in rec[key])
    assert not [r for r in rec["longest_work"] if r["wait"]]


def test_summary_carries_the_lanes_handed_to_the_write_scatter(served):
    """`write_scatter_lane_cnt` rides next to `write_cnt` in `[summary]`
    (`exec.write_lanes_per_epoch` reads it): a window count like its
    neighbours, so it divides by the WINDOW's epochs (`stage_epoch_cnt`;
    `epoch_cnt` is the whole run's).  This run stores fingerprints, not
    rows, and that scatter is handed every lane of every epoch (128
    txns x 4)."""
    from deneva_tpu.stats import parse_summary
    line = [ln for ln in served if ln.startswith("node 0 (server): ")][0]
    s = parse_summary(line.split(": ", 1)[1])
    assert s["write_scatter_lane_cnt"] == 128 * 4 * s["stage_epoch_cnt"]
    assert 0 < s["write_cnt"] < s["write_scatter_lane_cnt"]


def test_timeline_and_crit_lines_are_what_they_were(served):
    from deneva_tpu.harness.parse import parse_metrics
    from deneva_tpu.harness.timeline import parse_timeline
    rows = parse_timeline(served)
    assert len(rows) > 10
    steady = [ln for ln in served
              if ln.startswith("[timeline] ") and " retire=" in ln]
    loop = ["loop", "admit", "collect", "dispatch", "retire"]
    # (a `crit_<gate>` ledger span may ride in front of a pass's marks)
    assert steady and all(
        [n for n in re.findall(r"(\w+)=[0-9.]+ms", ln) if n in loop][:5]
        == loop for ln in steady)
    crit = [r for r in parse_metrics(served) if r["family"] == "crit"]
    assert crit
    for r in crit:
        stages_ms = sum(r[k + "_ms"] for k in
                        ("admit", "wire", "device", "retire", "other"))
        assert stages_ms == pytest.approx(r["wall_ms"], rel=0.05, abs=0.1)


# ---- the named phases ----------------------------------------------------

# recorded from the parent tree (commit 3393394, before any scope
# existed) by this same seeded feed: scopes are metadata, so the commit
# and abort counts, the verdict planes and the table's digest are these.
# OCC's digest is the parent's table with F0's TRASH ROW ZEROED (parent
# tree at 3d631a6, the same feed; its own digest d2a3765f... held some
# losing lane's bytes there): since PR 26 only the final writers reach
# the row scatter and nothing is steered to the trash row any more —
# every row below `capacity` is the parent's, bit for bit.  Since PR 32
# OCC's conflict matrix comes from the exact keys: the same feed commits
# the FOUR transactions that only a shared bucket (of 512, one family)
# had aborted (488 -> 492 of the same 1,392 decisions; with the hashed
# matmul put back the program gave dc1af2a8... again, bit for bit)
PARENT = {
    "TPU_BATCH": dict(
        commits=1392, aborts=0, writes=2827, planes=15064,
        digest="5ed0474b85d6e6053fe5bec7509574bb3eac139bde94c3712cd83aa7"
               "c9209fed"),
    "OCC": dict(
        commits=492, aborts=900, writes=995, planes=15064,
        digest="9b7233d6de29f2b518493f4b44439c55432297f5c27484480e266b422d13e2"
               "c5"),
}
SCOPES = {
    "TPU_BATCH": {"ep.decode", "ep.plan", "ep.read", "ep.write", "ep.stats",
                  "grp.pack"},
    "OCC": {"ep.decode", "ep.plan", "ep.validate", "ep.read", "ep.write",
            "ep.stats", "grp.pack"},
}


@pytest.mark.parametrize("cc_alg", ["TPU_BATCH", "OCC"])
def test_group_program_carries_its_scopes_and_the_parents_answers(cc_alg):
    import jax

    from deneva_tpu.cc import get_backend
    from deneva_tpu.config import Config
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.runtime.logger import state_digest
    from deneva_tpu.engine.epoch import make_dist_group
    from deneva_tpu.workloads import get_workload

    cfg = Config.from_args([f"--{k}={v}" for k, v in dict(
        workload="YCSB", cc_alg=cc_alg, node_cnt=1, sim_full_row="true",
        synth_table_size=4096, tup_size=100, epoch_batch=128,
        pipeline_epochs=4, conflict_buckets=512, req_per_query=4,
        max_accesses=4, zipf_theta=0.9).items()]
        ).replace(node_id=0, part_cnt=1)
    wl, be = get_workload(cfg), get_backend(cfg.cc_alg)
    C, b = 4, 128
    k, t, s = (np.asarray(x) for x in wl.to_wire(
        wl.generate(jax.random.PRNGKey(7), C * b * 3)))
    group = make_dist_group(cfg, wl, be, k.shape[1], s.shape[1])
    db, cc = wl.load(), be.init_state(cfg)
    st = init_device_stats(len(wl.txn_type_names))
    rng = np.random.default_rng(11)
    text = None
    for g in range(3):
        sl = slice(g * C * b, (g + 1) * C * b)
        active = rng.random(C * b) < 0.9
        ts = (np.arange(C * b) + 1 + g * C * b).astype(np.int32)
        feed = (active, ts, k[sl].reshape(-1), t[sl].reshape(-1),
                s[sl].reshape(-1))
        if text is None:
            text = group.lower(db, cc, st, *feed).compile().as_text()
        db, cc, st, planes = group(db, cc, st, *feed)
    got = dict(commits=int(st["total_txn_commit_cnt"]),
               aborts=int(st["total_txn_abort_cnt"]),
               writes=int(st["write_cnt"]),
               planes=int(np.asarray(planes).astype(np.int64).sum()),
               digest=state_digest(db))
    assert got == PARENT[cc_alg]
    names = set(re.findall(r'op_name="([^"]*)"', text))
    found = {part for n in names for part in n.split("/")
             if part.startswith(("ep.", "grp."))}
    assert found == SCOPES[cc_alg]
    # the scopes sit where the work is: the row gather under ep.read,
    # the row scatter under ep.write, inside the scanned body
    assert any("while/body" in n and "ep.read" in n and "gather" in n
               for n in names)
    assert any("while/body" in n and "ep.write" in n and "scatter" in n
               for n in names)


@pytest.mark.parametrize("cc_alg", ["TPU_BATCH", "OCC"])
def test_engine_step_carries_the_group_programs_scopes(cc_alg):
    """`Engine.step` runs the middle the group program runs
    (`engine/epoch.epoch_core`), so its ops carry the same phase names —
    all but the two the served feed adds."""
    import jax

    from deneva_tpu.config import Config
    from deneva_tpu.engine import Engine
    from deneva_tpu.workloads import get_workload

    cfg = Config.from_args([f"--{k}={v}" for k, v in dict(
        workload="YCSB", cc_alg=cc_alg, sim_full_row="true",
        synth_table_size=4096, tup_size=100, epoch_batch=128,
        max_txn_in_flight=256, conflict_buckets=512, req_per_query=4,
        max_accesses=4, zipf_theta=0.9).items()])
    eng = Engine(cfg, get_workload(cfg))
    state = jax.eval_shape(eng.init_state)
    text = eng.jit_step.lower(state).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    found = {part for n in names for part in n.split("/")
             if part.startswith(("ep.", "grp."))}
    assert found == SCOPES[cc_alg] - {"ep.decode", "grp.pack"}
    assert any("ep.read" in n and "gather" in n for n in names)
    assert any("ep.write" in n and "scatter" in n for n in names)


def test_the_thread_registry_knows_the_recorder():
    from deneva_tpu.runtime.ownercheck import DISPATCH, OWNER
    assert "_ph" not in OWNER                 # the inline sums are gone
    for attr in ("clk", "_stage_meas", "_queue_txns", "_prefetch_wait_s"):
        assert OWNER[attr] == DISPATCH
    # the retire worker keeps no ledger: its `srv.prefetch` span is all
    assert "_prefetch_s" not in OWNER and "_prefetch_meas" not in OWNER


def test_scope_reading_survives_in_the_compile_cache_key():
    """Op metadata is what the device trace is read by, so it has to be
    part of the persistent cache's key (JAX leaves it out by default: a
    hit would hand back the first compiler's scopes)."""
    src = open(os.path.join(ROOT, "deneva_tpu", "runtime",
                            "jaxenv.py")).read()
    assert '"jax_compilation_cache_include_metadata_in_key", True' in src
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, sys; sys.path.insert(0, sys.argv[1]);"
         "import jax;"
         "from deneva_tpu.runtime import jaxenv;"
         "jax.config.update('jax_platforms', 'tpu');"
         "jaxenv.place_compile_cache();"
         "print(json.dumps(jax.config."
         "jax_compilation_cache_include_metadata_in_key))", ROOT],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-1500:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) is True
