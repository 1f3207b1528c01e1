"""One stage clock for the server's dispatch loop.

`ServerNode.run()` passes the same few boundaries every dispatch group:
drain -> admit -> collect -> feed -> dispatch -> (retire_wait -> retire)
-> other.  `StageClock.enter(stage)` is the ONE call at each of them.
It reads the clock once and, with that reading,

* closes the stage that was running: seconds per stage, always on.
  Every interval of the dispatch thread belongs to exactly one stage,
  so the stage seconds of a window SUM TO ITS WALL;
* feeds the two older ledgers where they are armed, at the boundaries
  they always marked: the `[timeline]` spans (`debug_timeline`) and the
  `[crit]` stages (`metrics`) keep their names and positions;
* opens `jax.profiler.TraceAnnotation("srv.<stage>", group=<first epoch
  of the group>)`: nothing unless a profiler session is live, and then a
  span in the `/host:CPU` plane of the same `.xplane.pb` as the device's
  operations, on the same clock.  The tag is what groups a pass's
  spans: a `srv.group` span AROUND them would take the name of every
  idle gap in `benchmark/trace_reduce.name_gaps` (the host event that
  covers most of a gap names it), so there is none.

`snapshot()` at the measured window's edge and `since(snapshot)` at the
end give the `[summary]` line WINDOW values, as `_retry_meas` does for
the retry histogram.

**One more reading at the same call (PR 40).**  Beside the wall
(`time.monotonic`) the boundary reads the dispatch thread's own CPU
clock (`time.thread_time`) and charges the running stage its CPU
seconds as it charges its wall: `[summary]` gains the WINDOW values
`stage_<stage>_cpu_time`, and `process_cpu_time` from the process's CPU
clock (`time.process_time`).  That clock sums over the process's
threads, is a system call of ~6 us on the chip's host like the
thread's own, and ticks at 10 ms there, so a reading at every boundary
would double the call's cost for nothing an interval could use.  It is
read at a pass's start once `PROCESS_EVERY_S` has passed since its last
reading, and at the window's edges.  A stage's wall less its CPU is time in
which the thread had the stage open and was not running: another thread
holding the interpreter, the scheduler, a blocked call.  What the CPU
reading is NOT is immune to a profiler beside the loop: on the chip a
traced run's CPU an epoch reads about twice the untraced one with only
6-12% of the window off the CPU — `stop_trace` makes the thread's own
work dearer, it does not merely take the core (PERF.md section 6, PR
40) — so the program's own numbers are an UNTRACED run's.  On the chip's
host the CPU clocks tick at 10 ms: a stage's CPU over a 40 s window is a
count of ticks, good to a few per cent (a stage can read a little over
its wall), and an interval's CPU says something from ~50 ms up.
`benchmark/metrics/host.cpu_ms_per_epoch.py`, its two
`*_cpu_ms_per_epoch` siblings and `host.offcpu_share.py` read the keys;
`process_cpu_time` over the wall is the cores the server burns, printed
by `tools/stage_record.py`.

**The record: every interval is kept.**  `_close()` stores `(stage,
group, start, wall, thread CPU, the process clock's last reading)` of
the interval it ends in a preallocated ring of `RING` intervals (6.3 MB;
the newest stay, `dropped` counts the rest).  `record()` reduces it
once, when the loop has ended, to what `ServerNode.run()` puts on the
`[device]` line as `stage_record`: the clock's name and three readings
of it (`t_start`, `t_meas`, `t_end`: CLOCK_MONOTONIC is shared by the
processes of a machine, so the clients' start barrier and a trace's
window place on it), the cores the process may use, the window's pass
walls (p50, p99, max) and the longest intervals of the run, each with
its CPU and the CPU the process's OTHER threads burnt over the span
between the two readings of the process clock that enclose it
(`span_s`: a tenth of a second, or the pass where passes are longer).
That triple tells a stall's kind without a profiler: CPU ~ wall, the
thread was working (a long admit or retire: Python's to mend); CPU <<
wall and the others' CPU ~ the span, another thread of the server held
the interpreter or the core (the retire pool, the transport's threads,
a profiler); both small, the thread was blocked outside the process
(the runtime's queue, the kernel, a client).  `tools/stage_record.py`
prints it for an untraced run of a benchmark cell.  What it costs a
boundary: one more clock read and one packed store, +1.1 us on a call
of ~1 us where the CPU clocks are cheap; on the chip's host, where a CPU
clock is a system call, +7 us in a micro-run and **~40-50 us from the
busy server** — nine boundaries a pass: ~0.5 ms of a PPS pass of 11 ms
(the device sets the pace there), +1.8% of the dispatch thread's work
and about 1% of the rate in the host-bound OCC cell (PERF.md section 6,
PR 40, has the runs; section 7 what would cut it).
"""

from __future__ import annotations

import os
import struct
import time

# the dispatch thread's stages; `retire_wait` (blocked on the device's
# verdicts) and `collect` (blocked on the peers' blobs) are waits, the
# rest is host work
STAGES = ("drain", "admit", "collect", "feed", "dispatch", "retire_wait",
          "retire", "other")
# a closing stage ends this `[timeline]` span / this `[crit]` stage
# (both ledgers charge everything since their last mark)
_TIMELINE = frozenset(("admit", "collect", "dispatch", "retire"))
_CRIT = {"admit": "admit", "collect": "wire", "dispatch": "device",
         "retire": "retire"}
WAITS = frozenset(("retire_wait", "collect"))
# what `begin_pass()` and `retired()` count
COUNTERS = ("group_cnt", "epoch_cnt", "queue_txn_sum", "pipeline_cnt",
            "pipeline_time_sum")
# the record: a ring of this many intervals (OCC closes ~34k in a run,
# the hot cell 3k), six doubles each, and how many of the longest are
# written out
RING = 1 << 17
# stage, group, start, wall, thread CPU, the process clock's last reading
_INTERVAL = struct.Struct("6d")
LONGEST = 16
# the process's CPU clock is a second system call (~6 us on the chip's
# host, as the thread's own) whose 10 ms tick no interval can use: it is
# read at a pass's start, and only once this long has passed since its
# last reading
PROCESS_EVERY_S = 0.1
_ID = {s: float(i) for i, s in enumerate(STAGES)}


def span(stage: str, group: int):
    """A `srv.<stage>` trace span (a context manager) for code off the
    dispatch thread; a no-op unless a profiler session is live."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation("srv." + stage, group=group)


class StageClock:
    """Owned by the dispatch thread, like every host counter."""

    def __init__(self, timeline=None, crit=None):
        self.tl, self.crit = timeline, crit
        self.sec = dict.fromkeys(STAGES, 0.0)
        self.cpu = dict.fromkeys(STAGES, 0.0)
        self.cnt = dict.fromkeys(COUNTERS, 0)
        self.now = self.t_start = time.monotonic()
        self.cpu_now = time.thread_time()
        self._read_process()
        self.group = 0
        self._stage = "other"
        self._ann = None
        # the open interval: its group and the two readings it began at
        self._open_group = 0
        self._open = (self.now, self.cpu_now)
        self._ring = bytearray(_INTERVAL.size * RING)
        self.intervals = 0      # closed so far; the ring keeps the last RING
        # the window of `since(None)` / `record(None)`: the whole run
        self._zero = {"sec": dict(self.sec), "cpu": dict(self.cpu),
                      "cnt": dict(self.cnt), "proc": self.proc_now,
                      "now": self.now}

    def _read_process(self) -> None:
        self.proc_now, self._proc_at = time.process_time(), self.now

    def _tick(self) -> float:
        """Charge the wall and the thread's CPU since the last reading
        to the running stage."""
        now, cpu = time.monotonic(), time.thread_time()
        self.sec[self._stage] += now - self.now
        self.cpu[self._stage] += cpu - self.cpu_now
        self.now, self.cpu_now = now, cpu
        return now

    def _close(self) -> float:
        """End the running stage with one reading of the clocks: its
        seconds, its interval in the record, its trace span, and the
        older ledgers' marks."""
        now = self._tick()
        old = self._stage
        t0, cpu0 = self._open
        cpu = self.cpu_now
        _INTERVAL.pack_into(
            self._ring, _INTERVAL.size * (self.intervals % RING), _ID[old],
            self._open_group, t0, now - t0, cpu - cpu0, self.proc_now)
        self.intervals += 1
        self._open = (now, cpu)
        if self.tl is not None and old in _TIMELINE:
            self.tl.mark(old, now)
        if self.crit is not None and old in _CRIT:
            self.crit.lap(_CRIT[old], now)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        return now

    def enter(self, stage: str, group: int | None = None) -> float:
        """The boundary call: close the running stage, open ``stage``
        (its span tagged with the pass's group, or with ``group``: a
        retirement belongs to the group it retires).  Returns the one
        clock reading it took."""
        # a TraceAnnotation starts when it is built: before the running
        # span ends, so the two overlap by this call's few microseconds
        # and leave no hole (built after, the hole was 4-10 us under the
        # Python tracer: as long as the device idle gaps the spans name)
        if group is None:
            group = self.group
        ann = span(stage, group)
        now = self._close()
        ann.__enter__()
        self._stage, self._ann, self._open_group = stage, ann, group
        return now

    def begin_pass(self, epoch0: int, epochs: int, queue_txns: int) -> None:
        """Top of a dispatch pass: the previous pass ends (its rest is
        `other`, and `loop` on the timeline), the queue is sampled, and
        the pass's first stage, `drain`, opens."""
        self.group = epoch0
        self.enter("drain")
        if self.now - self._proc_at >= PROCESS_EVERY_S:
            # (the `drain` interval just opened will carry the reading)
            self._read_process()
        if self.tl is not None:
            self.tl.mark("loop", self.now)
        self.cnt["group_cnt"] += 1
        self.cnt["epoch_cnt"] += epochs
        self.cnt["queue_txn_sum"] += queue_txns

    def end(self) -> None:
        """Close the running stage; what follows is `other`."""
        self._close()
        self._stage = "other"

    def shift(self, src: str, dst: str, seconds: float) -> None:
        """Recharge seconds measured inside ``src`` to ``dst`` (a wait
        for a peer inside a working stage, work inside a wait).  WALL
        only: the seconds are a measured wait or a measured piece of
        work, whose CPU nobody read, so the CPU sums and the record's
        intervals stay as the boundaries cut them."""
        self.sec[src] -= seconds
        self.sec[dst] += seconds

    def retired(self, t_dispatch: float) -> None:
        """A group's `srv.retire` has just ended (`self.now`): it spent
        dispatch start -> now in the device pipeline."""
        self.cnt["pipeline_cnt"] += 1
        self.cnt["pipeline_time_sum"] += self.now - t_dispatch

    def snapshot(self) -> dict:
        now = self._tick()
        return {"sec": dict(self.sec), "cpu": dict(self.cpu),
                "cnt": dict(self.cnt), "proc": time.process_time(),
                "now": now}

    def since(self, snap: dict | None) -> dict[str, float]:
        """`[summary]` keys over the window that began at ``snap`` (the
        whole run when None)."""
        self._tick()
        snap = snap or self._zero
        sec0, cpu0, cnt0 = snap["sec"], snap["cpu"], snap["cnt"]
        c = {k: self.cnt[k] - cnt0[k] for k in self.cnt}
        out = {f"stage_{s}_time": self.sec[s] - sec0[s] for s in STAGES}
        out.update((f"stage_{s}_cpu_time", self.cpu[s] - cpu0[s])
                   for s in STAGES)
        out["process_cpu_time"] = time.process_time() - snap["proc"]
        out["stage_epoch_cnt"] = float(c["epoch_cnt"])
        out["queue_txn_mean"] = c["queue_txn_sum"] / max(c["group_cnt"], 1)
        out["pipeline_time_mean"] = (c["pipeline_time_sum"]
                                     / max(c["pipeline_cnt"], 1))
        return out

    def rows(self):
        """The kept intervals, oldest first: float64[n, 6] of (index
        into `STAGES`, group, start, wall, thread CPU, the process CPU
        clock's last reading when the interval closed)."""
        import numpy as np
        kept = min(self.intervals, RING)
        head = self.intervals % RING if self.intervals > RING else 0
        ring = np.frombuffer(self._ring).reshape(-1, 6)
        return np.concatenate((ring[head:kept], ring[:head]))

    def record(self, snap: dict | None) -> dict:
        """The run's intervals, reduced once for the `[device]` line
        (the module docstring says what each field is for).  ``snap`` is
        the measured window's snapshot: the pass walls are the
        window's, begin_pass to begin_pass; the longest intervals are
        the whole run's, `longest` over every stage with the two waits
        marked, `longest_work` over the six working stages (a device-
        bound cell's longest sixteen are all `retire_wait`)."""
        import numpy as np
        rows = self.rows()
        t_meas = (snap or self._zero)["now"]
        # a pass opens with `drain`, and only `begin_pass()` enters it
        drains = np.flatnonzero(rows[:, 0] == _ID["drain"])
        starts = rows[drains, 2]
        passes = np.diff(starts[starts >= t_meas])
        p50, p99, longest_pass = (
            (float(np.percentile(passes, 50)),
             float(np.percentile(passes, 99)), float(passes.max()))
            if len(passes) else (0.0, 0.0, 0.0))
        # the passes whose `drain` carries a fresh reading of the process
        # clock cut the run into spans; over a span the other threads'
        # CPU is the process's less this thread's
        proc = rows[drains, 5]
        marks = drains[proc != np.r_[self._zero["proc"], proc[:-1]]]
        mark_t, mark_proc = rows[marks, 2], rows[marks, 5]
        mark_cpu = np.r_[0.0, np.cumsum(rows[:, 4])][marks]

        def longest(idx):
            out = []
            for i in idx[np.argsort(-rows[idx, 3], kind="stable")[:LONGEST]]:
                stage, group, t0, wall, cpu, _ = rows[i]
                k = int(np.searchsorted(mark_t, t0, side="right")) - 1
                span = others = None
                if 0 <= k < len(marks) - 1:
                    span = round(mark_t[k + 1] - mark_t[k], 6)
                    others = round((mark_proc[k + 1] - mark_proc[k])
                                   - (mark_cpu[k + 1] - mark_cpu[k]), 6)
                out.append(dict(
                    stage=STAGES[int(stage)], group=int(group),
                    at_s=round(t0 - self.t_start, 6), wall_s=round(wall, 6),
                    cpu_s=round(cpu, 6), others_cpu_s=others, span_s=span,
                    wait=STAGES[int(stage)] in WAITS))
            return out

        every = np.arange(len(rows))
        work = every[~np.isin(rows[:, 0], [_ID[s] for s in WAITS])]
        return dict(
            clock="CLOCK_MONOTONIC", t_start=self.t_start, t_meas=t_meas,
            t_end=self.now, cpus=len(os.sched_getaffinity(0)),
            intervals=self.intervals, dropped=self.intervals - len(rows),
            pass_wall_s=dict(p50=round(p50, 6), p99=round(p99, 6),
                             max=round(longest_pass, 6)),
            longest=longest(every), longest_work=longest(work))
