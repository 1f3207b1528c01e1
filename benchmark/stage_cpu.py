"""The stage clock's CPU seconds, as the four `host.*` CPU readers
(`metrics/host.cpu_ms_per_epoch.py`, `host.admit_cpu_ms_per_epoch.py`,
`host.retire_cpu_ms_per_epoch.py`, `host.offcpu_share.py`) and
`tools/stage_record.py` take them from a server's `[summary]`.

Since PR 40 the server's stage clock (`deneva_tpu/runtime/stages.py`)
reads the dispatch thread's CPU clock beside the wall at every boundary
and prints WINDOW values `stage_<stage>_cpu_time` beside
`stage_<stage>_time`.  ONE rule for a program that prints no CPU
reading (the parent of PR 40; another deployment's server): **its CPU is
taken as its wall**.  CPU <= wall always, so a per-epoch CPU metric then
reads the wall value as an upper bound, and the off-CPU share reads 0.0.
No measured window (`stage_epoch_cnt` absent or 0): None.

**What the four read in a TRACED run** — the only run the benchmark
reads per-layer metrics in (PERF.md section 6, PR 40, my chip runs):
the thread's CPU an epoch is about TWICE an untraced run's in the
host-bound cells (OCC 1.17 for 0.61 ms, PPS 1.59 for 0.77; admission
4x) while `host.offcpu_share` is 6-12% for 2-6% untraced.  `stop_trace`
beside the serve loop makes the thread's own work dearer; it does not
merely take the core.  So the three `*_cpu_ms_per_epoch` read what the
thread costs WITH the profiler beside it: comparable parent against
change, an upper bound on the program's own, whose untraced value
`tools/stage_record.py` prints.  The chip host's CPU clocks tick at
10 ms: over a 40 s window a stage's CPU is good to a few per cent, a
stage can read a little more CPU than wall, and the share is good to
about a point.
"""

STAGES = ("drain", "admit", "collect", "feed", "dispatch", "retire_wait",
          "retire", "other")
# blocked by design: on the device's verdicts, on the peers' blobs
WAITS = ("retire_wait", "collect")
WORKING = tuple(s for s in STAGES if s not in WAITS)


def wall_s(summary: dict, stage: str) -> float:
    return summary.get(f"stage_{stage}_time", 0.0)


def cpu_s(summary: dict, stage: str) -> float:
    """The dispatch thread's CPU seconds inside ``stage`` over the
    window; the stage's wall where no CPU reading is printed."""
    return summary.get(f"stage_{stage}_cpu_time", wall_s(summary, stage))


def cpu_ms_per_epoch(ctx: dict, stages=STAGES):
    """CPU milliseconds of the dispatch thread an epoch, over ``stages``."""
    s = ctx["server"]["summary"]
    if not s.get("stage_epoch_cnt"):
        return None
    return 1e3 * sum(cpu_s(s, st) for st in stages) / s["stage_epoch_cnt"]


def offcpu_share(ctx: dict):
    """Per cent of the window's wall in which a WORKING stage was open
    and the thread was not on a CPU."""
    s = ctx["server"]["summary"]
    if not s.get("stage_epoch_cnt") or not s.get("stage_wall_time"):
        return None
    off = sum(wall_s(s, st) - cpu_s(s, st) for st in WORKING)
    return 100.0 * off / s["stage_wall_time"]
