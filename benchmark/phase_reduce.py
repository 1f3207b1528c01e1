"""From a profiler trace to device time per named phase of the epoch
program, and the host loop's lag behind the device.

    python benchmark/phase_reduce.py <trace dir or .xplane.pb> <epochs per group>

Run as a child pinned to the CPU backend, like `trace_reduce.py` (whose
interval arithmetic it shares); the benchmark's parent stays off JAX.
Reads what the program writes into the profiler's own trace and nothing
else:

* the program's `jax.named_scope`s (`ep.plan`, `ep.validate`, `ep.read`,
  `ep.write`, ... : `deneva_tpu/engine/epoch.make_epoch_body`).  An
  "XLA Ops" event carries only its HLO instruction's text, so the scope
  of an op comes from the HLO module the profiler stores in the trace's
  `/host:metadata` plane ("Hlo Proto"): {instruction name -> op_name},
  read here with a few lines of protobuf wire format (the trace reader
  JAX ships does not expose that plane's metadata).  An op belongs to
  the INNERMOST scope of its op_name (a gather under
  `ep.levels/ep.read` is a read); a fusion carries its root's; an op
  the compiler made with no op_name at all takes its consumer's
  (`hlo_scopes`);
* the dispatch loop's `srv.<stage>` spans on the `/host:CPU` plane
  (`deneva_tpu/runtime/stages.py`), each tagged `group=<first epoch>`.

Prints one JSON object on the last line (or `{}` where the trace holds
no scopes and no spans: a program from before they existed):

    {"groups", "epochs", "group_s",
     "phase_s": {"plan", "validate", "read", "write", "other"},
     "scope_s": {scope or "unscoped" or "no_op": s}, "other_ops": [[name, s]..10],
     "lag": {"pairs", "with_dispatch", "with_retire", "median_s", "mean_s",
             "max_s"}}

`groups`/`group_s` are `trace_reduce.py`'s: the WHOLE executions of the
group program inside the window and their device time.  `phase_s` is
the SELF time of the ops of each scope inside those executions; `other`
is the rest of `group_s` (scopes that are no phase of their own —
decode, stats, pack —, unscoped ops such as relayout copies, and time in
which no op ran), so the phases sum to `group_s` by construction.
`lag`: per whole execution, from its end on the device to the start of
that group's `srv.retire` on the host (the end of its `srv.retire_wait`
where the retire span was cut by the window's end).  The metric is the
MEDIAN over the whole executions: one stall as the trace opens (70 ms in
a chip run of PR 25) moves a mean of ten groups by half; mean and max
stay in the JSON for the operator.  An execution's
group is that of the `srv.dispatch` span before its `DoEnqueueProgram`
event (same `run_id`); group programs run back to back in one queue, so
an execution dispatched before the window takes its group from its
neighbours' (consecutive `run_id`s, consecutive groups).
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import trace_reduce as tr  # noqa: E402

PHASES = {"ep.plan": "plan", "ep.validate": "validate", "ep.read": "read",
          "ep.write": "write"}
SCOPE_PREFIXES = ("ep.", "grp.")
BARRIERS = ("while", "call", "conditional")


# ---- protobuf wire format: just enough for XSpace and HloProto ----------

def fields(buf: memoryview):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        no, wt = key >> 3, key & 7
        if wt == 0:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield no, v
        elif wt == 2:
            ln = shift = 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield no, buf[i:i + ln]
            i += ln
        elif wt == 1:
            i += 8
        elif wt == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wt} in the trace")


def first(buf: memoryview, no: int):
    return next((v for k, v in fields(buf) if k == no), None)


def varints(buf) -> list[int]:
    """A repeated integer field: one varint, or a packed run of them."""
    if isinstance(buf, int):
        return [buf]
    out, v, shift = [], 0, 0
    for b in bytes(buf):
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            out.append(v)
            v = shift = 0
    return out


def group_modules(xspace: bytes, mark: str = tr.GROUP_MARK):
    """The HloModuleProto of every module named ``mark`` that the
    trace's `/host:metadata` plane holds.  XSpace.planes=1;
    XPlane.name=2, .event_metadata=4 (map value=2); XEventMetadata
    .name=2, .stats=5; XStat.bytes_value=6 (the HloProto);
    HloProto.hlo_module=1."""
    for k, plane in fields(memoryview(xspace)):
        if k != 1 or bytes(first(plane, 2) or b"") != b"/host:metadata":
            continue
        for k2, entry in fields(plane):
            em = first(entry, 2) if k2 == 4 else None
            if em is None or mark not in bytes(first(em, 2) or b"").decode():
                continue
            for k3, stat in fields(em):
                proto = first(stat, 6) if k3 == 5 else None
                module = first(proto, 1) if proto is not None else None
                if module is not None:
                    yield module


def scope_of(op_name: str) -> str:
    """The innermost named scope of an op_name path, or "unscoped"."""
    for part in reversed(op_name.split("/")):
        if part.startswith(SCOPE_PREFIXES):
            return part
    return "unscoped"


def hlo_scopes(xspace: bytes, mark: str = tr.GROUP_MARK) -> dict[str, str]:
    """{HLO instruction name: scope} over the group program's modules.
    HloModuleProto.computations=3; HloComputationProto.instructions=2;
    HloInstructionProto.name=1, .opcode=2, .metadata=7, .id=35,
    .operand_ids=36; OpMetadata.op_name=2.

    An instruction with an op_name of the program's (`jit(group)/...`)
    has the innermost scope of that path.  One WITHOUT — the compiler
    made it while expanding another op: on the chip a scatter-add
    becomes a sort, tuple reads and a kernel, all bare — takes the scope
    of the nearest instruction that consumes its result, through other
    bare ones, inside its own computation and never through a loop, a
    call or a conditional."""
    out: dict[str, str] = {}
    for module in group_modules(xspace, mark):
        for k, comp in fields(module):
            if k != 3:
                continue
            ins = []            # (id, name, opcode, op_name, operand ids)
            for k2, i in fields(comp):
                if k2 != 2:
                    continue
                f = {}
                for no, v in fields(i):
                    if no in (1, 2, 7, 35):
                        f[no] = v
                    elif no == 36:
                        f.setdefault(36, []).extend(varints(v))
                meta = f.get(7)
                op = first(meta, 2) if meta is not None else None
                ins.append((f.get(35, 0), bytes(f[1]).decode(),
                            bytes(f.get(2, b"")).decode(),
                            bytes(op).decode() if op is not None else "",
                            f.get(36, [])))
            users: dict[int, list[int]] = {}
            for n, (_, _, _, _, operands) in enumerate(ins):
                for o in operands:
                    users.setdefault(o, []).append(n)
            for iid, name, opcode, op_name, _ in ins:
                sc, seen, frontier = scope_of(op_name), {iid}, [iid]
                bare = not op_name.startswith("jit(") \
                    and opcode not in BARRIERS
                while bare and sc == "unscoped" and frontier:
                    nxt = []
                    for n in (n for i in frontier for n in users.get(i, ())):
                        uid, _, u_opcode, u_op, _ = ins[n]
                        if uid in seen or u_opcode in BARRIERS:
                            continue
                        seen.add(uid)
                        if u_op.startswith("jit("):
                            sc = scope_of(u_op)
                            break
                        nxt.append(uid)
                    frontier = nxt
                out[name] = sc
    return out


# ---- the reduction -------------------------------------------------------

def whole_groups(plane) -> tuple[list, list]:
    """(whole group executions [(start, end, run_id)], their op events)
    of one device plane: `trace_reduce.py`'s rule — the first and the
    last execution are cut by the window's edges."""
    lines = {ln.name: ln for ln in plane.lines}
    if tr.MODULES_LINE not in lines or tr.OPS_LINE not in lines:
        return [], []
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                   dict(e.stats).get("run_id"))
                  for e in lines[tr.MODULES_LINE].events
                  if tr.GROUP_MARK in e.name)[1:-1]
    ops, i = [], 0
    for a, b, name in tr.events_of(lines[tr.OPS_LINE]):
        while i < len(mods) and mods[i][1] <= a:
            i += 1
        if i < len(mods) and mods[i][0] <= a:
            ops.append((a, min(b, mods[i][1]), name))
    return mods, ops


def host_spans(prof) -> tuple[dict, list]:
    """({"dispatch"|"retire"|"retire_wait": {group: (start, end)}},
    [(time, run_id)] of the host's `DoEnqueueProgram` events)."""
    spans: dict = {"dispatch": {}, "retire": {}, "retire_wait": {}}
    enq = []
    for p in prof.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith("srv.") and e.name[4:] in spans:
                    g = dict(e.stats).get("group")
                    if g is not None:
                        spans[e.name[4:]][int(g)] = (
                            e.start_ns, e.start_ns + e.duration_ns)
                elif e.name == "DoEnqueueProgram":
                    r = dict(e.stats).get("run_id")
                    if r is not None:
                        enq.append((e.start_ns, int(r)))
    return spans, enq


def group_of_run(mods, spans, enq, epochs_per_group: int) -> dict[int, int]:
    """{run_id: the group's first epoch} for the whole executions."""
    disp = sorted((a, g) for g, (a, _) in spans["dispatch"].items())
    direct: dict[int, int] = {}
    runs = {r for _, _, r in mods if r is not None}
    for t, r in enq:
        before = [g for a, g in disp if a <= t]
        if r in runs and before:
            direct[r] = before[-1]
    out = dict(direct)
    if direct:
        # one queue, in order: consecutive run_ids are consecutive groups
        r0, g0 = min(direct.items())
        if all(g == g0 + (r - r0) * epochs_per_group
               for r, g in direct.items()):
            for r in runs:
                out.setdefault(r, g0 + (r - r0) * epochs_per_group)
    return out


def reduce(prof, scopes: dict[str, str], epochs_per_group: int) -> dict:
    spans, enq = host_spans(prof)
    scoped = any(v != "unscoped" for v in scopes.values())
    if not scoped and not any(spans.values()):
        return {}
    devs = [p for p in prof.planes if p.name.startswith(tr.DEVICE_PREFIX)]
    n_groups, group_s, chips = 0, 0.0, 0
    scope_s: dict[str, float] = {}
    unclaimed: dict[str, float] = {}
    lags, with_disp, with_ret = [], 0, 0
    for p in devs:
        mods, ops = whole_groups(p)
        if not mods:
            continue
        chips += 1
        n_groups += len(mods)
        group_s += sum(b - a for a, b, _ in mods) * 1e-9
        for name, s in tr.self_times(ops).items():
            ins = tr.INSTR.match(name).group(1)
            sc = scopes.get(ins, "unscoped")
            scope_s[sc] = scope_s.get(sc, 0.0) + s
            if sc not in PHASES:
                unclaimed[ins] = unclaimed.get(ins, 0.0) + s
        if chips > 1:
            continue            # host spans pair with one device's queue
        grp = group_of_run(mods, spans, enq, epochs_per_group)
        for _, end, r in mods:
            g = grp.get(r)
            if g is None:
                continue
            with_disp += g in spans["dispatch"]
            with_ret += g in spans["retire"]
            at = spans["retire"][g][0] if g in spans["retire"] else \
                spans["retire_wait"].get(g, (None, None))[1]
            if at is not None:
                lags.append((at - end) * 1e-9)
    if not chips:
        return {}
    n_groups, group_s = n_groups / chips, group_s / chips
    scope_s = {k: v / chips for k, v in scope_s.items()}
    scope_s["no_op"] = group_s - sum(scope_s.values())
    phase_s = {ph: scope_s.get(sc, 0.0) for sc, ph in PHASES.items()}
    phase_s["other"] = group_s - sum(phase_s.values())
    out = dict(groups=n_groups, epochs=n_groups * epochs_per_group,
               group_s=group_s, phase_s=phase_s if scoped else None,
               scope_s=scope_s,
               other_ops=[[n, s / chips] for n, s in sorted(
                   unclaimed.items(), key=lambda kv: -kv[1])[:10]],
               lag=None)
    if lags:
        out["lag"] = dict(pairs=len(lags), with_dispatch=with_disp,
                          with_retire=with_ret,
                          median_s=statistics.median(lags),
                          mean_s=sum(lags) / len(lags), max_s=max(lags))
    return out


def cached(ctx: dict) -> dict:
    """For the metric readers: the reduction of the traced launch that
    ``ctx`` describes (its trace lies beside its log directory:
    `<run>/tlog` -> `<run>/timed/trace`), made ONCE per run in a child
    and kept beside the trace.  `{}` where there is nothing to read."""
    log_dir = (ctx.get("fields") or {}).get("log_dir")
    if not ctx.get("trace") or not log_dir:
        return {}
    timed = os.path.join(os.path.dirname(log_dir), "timed")
    out = os.path.join(timed, "phase_reduce.json")
    if not os.path.exists(out):
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             os.path.join(timed, "trace"),
             str(ctx["fields"]["pipeline_epochs"])],
            capture_output=True, text=True, timeout=300)
        lines = p.stdout.strip().splitlines()
        if p.returncode != 0 or not lines:
            print(f"[bench] phase_reduce: exit code {p.returncode}\n"
                  + "\n".join(p.stderr.splitlines()[-20:]),
                  file=sys.stderr, flush=True)
        with open(out, "w") as f:
            f.write(lines[-1] if p.returncode == 0 and lines else "{}")
    with open(out) as f:
        return json.load(f)


def phase_ms_per_epoch(ctx: dict, phase: str) -> float | None:
    """What every `phase.<phase>_ms_per_epoch` reader returns."""
    r = cached(ctx)
    if not r.get("phase_s") or not r.get("epochs"):
        return None
    return 1e3 * r["phase_s"][phase] / r["epochs"]


def main(argv: list[str]) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    path = tr.find_xplane(argv[0])
    with open(path, "rb") as f:
        scopes = hlo_scopes(f.read())
    print(json.dumps(reduce(tr.load(path), scopes, int(argv[1]))),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
