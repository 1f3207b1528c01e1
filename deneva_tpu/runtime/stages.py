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
"""

from __future__ import annotations

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
# what `begin_pass()` and `retired()` count
COUNTERS = ("group_cnt", "epoch_cnt", "queue_txn_sum", "pipeline_cnt",
            "pipeline_time_sum")


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
        self.cnt = dict.fromkeys(COUNTERS, 0)
        self.now = time.monotonic()
        self.group = 0
        self._stage = "other"
        self._ann = None

    def _tick(self) -> float:
        """Charge the time since the last reading to the running stage."""
        now = time.monotonic()
        self.sec[self._stage] += now - self.now
        self.now = now
        return now

    def _close(self) -> float:
        """End the running stage with one clock reading: its seconds,
        its trace span, and the older ledgers' marks."""
        now = self._tick()
        old = self._stage
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
        ann = span(stage, self.group if group is None else group)
        now = self._close()
        ann.__enter__()
        self._stage, self._ann = stage, ann
        return now

    def begin_pass(self, epoch0: int, epochs: int, queue_txns: int) -> None:
        """Top of a dispatch pass: the previous pass ends (its rest is
        `other`, and `loop` on the timeline), the queue is sampled, and
        the pass's first stage, `drain`, opens."""
        self.group = epoch0
        self.enter("drain")
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
        for a peer inside a working stage, work inside a wait)."""
        self.sec[src] -= seconds
        self.sec[dst] += seconds

    def retired(self, t_dispatch: float) -> None:
        """A group's `srv.retire` has just ended (`self.now`): it spent
        dispatch start -> now in the device pipeline."""
        self.cnt["pipeline_cnt"] += 1
        self.cnt["pipeline_time_sum"] += self.now - t_dispatch

    def snapshot(self) -> dict:
        self._tick()
        return {"sec": dict(self.sec), "cnt": dict(self.cnt)}

    def since(self, snap: dict | None) -> dict[str, float]:
        """`[summary]` keys over the window that began at ``snap`` (the
        whole run when None)."""
        self._tick()
        sec0 = snap["sec"] if snap else dict.fromkeys(STAGES, 0.0)
        cnt0 = snap["cnt"] if snap else dict.fromkeys(self.cnt, 0)
        c = {k: self.cnt[k] - cnt0[k] for k in self.cnt}
        out = {f"stage_{s}_time": self.sec[s] - sec0[s] for s in STAGES}
        out["stage_epoch_cnt"] = float(c["epoch_cnt"])
        out["queue_txn_mean"] = c["queue_txn_sum"] / max(c["group_cnt"], 1)
        out["pipeline_time_mean"] = (c["pipeline_time_sum"]
                                     / max(c["pipeline_cnt"], 1))
        return out
