"""From a profiler trace (`.xplane.pb`) to device busy time and a breakdown.

    python benchmark/trace_reduce.py <trace dir or .xplane.pb> <window_s> [<epochs per group>]
    python benchmark/trace_reduce.py --describe <trace dir or .xplane.pb>

Run as a child pinned to the CPU backend: the reader is JAX's own
(`jax.profiler.ProfileData`), and the benchmark's parent stays off JAX.
Prints one JSON object on the last line:

    {"busy_s", "window_s", "groups", "epochs", "group_busy_s", "chips",
     "breakdown": {"device_ops": [[name, s], ...10],
                   "idle_gaps": [[host activity, s], ...10]}}

* a device plane is one whose name starts with ``/device:TPU:`` (a
  trace of another platform has none, and the reduction says so);
* ``busy_s``: the union of the intervals of the plane's "XLA Ops" line —
  seconds in which an operation ran on the device — averaged over the
  device planes; ``window_s`` is the traced window's length: the
  caller's host-clock reading (taken between `start_trace` returning
  and `stop_trace` being called), or the extent of the device planes'
  operations on the trace's own clock where that is longer — the
  profiler records from inside `start_trace`, a few milliseconds before
  the host's reading begins, so busy time can never pass the window;
* ``groups``: WHOLE executions on the "XLA Modules" line whose name
  holds ``group`` (the program's `make_dist_group` jit) — the first and
  the last execution of a plane are cut by the window's edges and are
  left out; ``group_busy_s`` is the device time of those whole
  executions and ``epochs`` = groups x the epochs one group scans (the
  configuration's `pipeline_epochs`);
* ``device_ops``: SELF time per op (an op that encloses others, a
  `while` around its body, is charged only what its children leave), the
  ten largest, each keyed ``<innermost scope>:<HLO op> <result shape>``
  (`op_key`: ``ep.write:fusion u8[6291520,100]``) and not by the
  compiler's instruction number, which every change to the program
  renumbers — two PRs' lines name one op alike, and ops that differ
  only in number or layout (the table's two relayout copies) are one
  entry; the scopes are `phase_reduce.hlo_scopes`'s, read from the HLO
  the profiler stores in the trace;
* ``idle_gaps``: the ten longest gaps between program
  executions ("XLA Modules"; between ops where that line is missing),
  each named by the runtime's host-thread event that covers most of it
  (the Python tracer's ``$...`` events belong to the thread that holds
  the profiler open, not to the server's loop, and are left out).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
GROUP_MARK = "group"
INSTR = re.compile(r"%?([\w.\-]+)")        # an HLO instruction's name
_SHAPE = re.compile(r" = (\(?)([a-z]+\d*\[[\d,]*\])")


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(find_xplane(path))


def events_of(line) -> list[tuple[float, float, str]]:
    """[(start_ns, end_ns, name)] sorted by start."""
    ev = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
          for e in line.events]
    ev.sort(key=lambda t: (t[0], -t[1]))
    return ev


def union_s(ev) -> tuple[float, list[tuple[float, float]]]:
    """Seconds covered by the intervals, and the gaps between them."""
    busy, gaps = 0.0, []
    cur_a = cur_b = None
    for a, b, _ in ev:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                busy += cur_b - cur_a
                gaps.append((cur_b, a))
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        busy += cur_b - cur_a
    return busy * 1e-9, gaps


def self_times(ev) -> dict[str, float]:
    """Self seconds per name on one line: an event's duration less what
    the events nested inside it cover."""
    out: dict[str, float] = {}
    stack: list[list] = []          # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, ns = stack.pop()
            out[name] = out.get(name, 0.0) + ns * 1e-9
    for a, b, name in ev:
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, name, b - a])
    close(float("inf"))
    return out


def op_key(name: str, scopes: dict[str, str]) -> str:
    """``<scope>:<op> <result shape>`` of an "XLA Ops" event, whose name
    is its HLO instruction's text (``%fusion.62 = u8[6291520,100]{1,0:T(8,
    128)(4,1)} fusion(...)``): the instruction's name up to its first
    dot, the first array of its result without the layout (``(f32[64],..)``
    for a tuple), under the scope ``scopes`` gives the instruction."""
    ins = INSTR.match(name).group(1)
    m = _SHAPE.search(name)
    shape = "" if m is None else \
        " " + (m.group(2) if not m.group(1) else f"({m.group(2)},..)")
    return f"{scopes.get(ins, 'unscoped')}:{ins.split('.')[0]}{shape}"


def name_gaps(gaps, host_ev, top: int = 10) -> list[list]:
    """The longest gaps, each with the host event covering most of it."""
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "no host event", 0.0
        for ha, hb, hn in host_ev:
            if hb <= a or ha >= b:
                continue
            c = min(b, hb) - max(a, ha)
            if c > cover:
                best, cover = hn, c
        out.append([best, (b - a) * 1e-9])
    return out


def reduce(prof, window_s: float, epochs_per_group: int,
           scopes: dict[str, str] | None = None) -> dict:
    scopes = scopes or {}
    devs = [p for p in prof.planes if p.name.startswith(DEVICE_PREFIX)]
    if not devs:
        raise ValueError("the trace holds no device plane "
                         f"({[p.name for p in prof.planes]})")
    busy, groups, group_busy, ops, gaps_all = [], [], [], {}, []
    extent_s = 0.0
    for p in devs:
        lines = {ln.name: ln for ln in p.lines}
        if OPS_LINE not in lines:
            continue
        ev = events_of(lines[OPS_LINE])
        b, gaps = union_s(ev)
        busy.append(b)
        if ev:
            extent_s = max(extent_s, (max(e[1] for e in ev) - ev[0][0])
                           * 1e-9)
        for n, s in self_times(ev).items():
            k = op_key(n, scopes)
            ops[k] = ops.get(k, 0.0) + s
        mods = events_of(lines[MODULES_LINE]) if MODULES_LINE in lines \
            else []
        gaps_all += union_s(mods)[1] if mods else gaps
        whole = [(a, b) for a, b, n in mods if GROUP_MARK in n][1:-1]
        groups.append(len(whole))
        group_busy.append(sum(b - a for a, b in whole) * 1e-9)
    if not busy:
        raise ValueError(f"no '{OPS_LINE}' line on a device plane")
    host_ev = []
    for p in prof.planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                host_ev += [e for e in events_of(ln)
                            if not e[2].startswith("$")]
    chips = len(busy)
    n_groups = sum(groups) / chips
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return dict(busy_s=sum(busy) / chips, window_s=max(window_s, extent_s),
                groups=n_groups, epochs=n_groups * epochs_per_group,
                group_busy_s=sum(group_busy) / chips, chips=chips,
                breakdown=dict(
                    device_ops=[[n, s / chips] for n, s in top],
                    idle_gaps=name_gaps(gaps_all, host_ev)))


def describe(prof) -> None:
    for p in prof.planes:
        print(f"plane {p.name!r}")
        for ln in p.lines:
            ev = events_of(ln)
            names: dict[str, list] = {}
            for a, b, n in ev:
                r = names.setdefault(n, [0, 0.0])
                r[0] += 1
                r[1] += (b - a) * 1e-9
            span = (ev[-1][1] - ev[0][0]) * 1e-9 if ev else 0.0
            print(f"  line {ln.name!r}: {len(ev)} events over {span:.4f} s")
            for n, (c, s) in sorted(names.items(),
                                    key=lambda kv: -kv[1][1])[:12]:
                print(f"    {s:10.6f} s  x{c:<7d} {n[:110]}")


def main(argv: list[str]) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    if argv[0] == "--describe":
        describe(load(argv[1]))
        return 0
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from phase_reduce import hlo_scopes     # it imports this module
    path = find_xplane(argv[0])
    with open(path, "rb") as f:
        scopes = hlo_scopes(f.read())
    out = reduce(load(path), float(argv[1]),
                 int(argv[2]) if len(argv) > 2 else 1, scopes)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
