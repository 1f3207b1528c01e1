"""Result parsing (reference `scripts/parse_results.py`, `latency_stats.py`,
`scripts/helper.py` output-file naming).

The reference regexes `[summary] k=v,...` lines out of per-run output
files whose names encode the config via SHORTNAMES (`helper.py:59+`).
Same contract here: `outfile_name` encodes the sweep-relevant fields,
`parse_file` recovers the summary dict, `results_table` joins a directory
of results into rows for plotting / regression checks.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import re
from typing import Any

from deneva_tpu.config import Config
from deneva_tpu.stats import parse_summary

# config field -> short name in output files (reference SHORTNAMES)
SHORTNAMES = {
    "workload": "WL", "cc_alg": "CC", "mode": "MODE",
    "node_cnt": "N", "part_cnt": "P", "zipf_theta": "SKEW",
    "write_perc": "WR", "txn_write_perc": "TWR", "part_per_txn": "PPT",
    "access_perc": "A", "data_perc": "D", "skew_method": "SK",
    "max_txn_in_flight": "TIF", "num_wh": "WH",
    "perc_payment": "PAY", "isolation_level": "ISO",
    "epoch_batch": "EB", "load_rate": "LR", "device_parts": "DP",
}

_DEFAULT = Config()


def outfile_name(cfg: Config) -> str:
    """Encode the non-default sweep fields into a filename stem.  Fields
    outside SHORTNAMES that differ from the default fold into a short
    hash suffix so two distinct configs never share a filename."""
    parts = []
    extra = []
    for f in dataclasses.fields(cfg):
        v = getattr(cfg, f.name)
        if v == getattr(_DEFAULT, f.name):
            continue
        sv = v.value if hasattr(v, "value") else v
        if f.name in SHORTNAMES:
            if f.name not in ("workload", "cc_alg"):
                parts.append(f"{SHORTNAMES[f.name]}-{sv}")
        else:
            extra.append(f"{f.name}={sv}")
    if extra:
        h = hashlib.sha1(";".join(extra).encode()).hexdigest()[:6]
        parts.append(f"H-{h}")
    wl = getattr(cfg.workload, "value", cfg.workload)
    alg = getattr(cfg.cc_alg, "value", cfg.cc_alg)
    return "_".join([wl, alg] + parts) + ".out"


def _parse_lines(path: str) -> tuple[dict[str, Any], str | None]:
    """One pass over an output file: (`# cfg` echo dict, last summary line)."""
    cfg: dict[str, Any] = {}
    summary = None
    with open(path) as f:
        for line in f:
            if line.startswith("# cfg "):
                k, v = line[len("# cfg "):].strip().split("=", 1)
                cfg[k] = _auto(v)
            elif "[summary]" in line:
                summary = line
    return cfg, summary


def parse_file(path: str) -> dict[str, float] | None:
    """Last `[summary]` line of one output file -> field dict (reference
    `parse_results.py:19-38` takes the server summary the same way)."""
    _, summary = _parse_lines(path)
    return parse_summary(summary) if summary else None


def load_results(out_dir: str, only: list[str] | None = None
                 ) -> list[dict[str, Any]]:
    """All parsed rows of a result directory, one dict per output file,
    with the config echo (`# cfg key=value` header lines) merged in.
    ``only`` restricts to a set of filenames (the runner passes the files
    it just wrote, keeping stale points of earlier sweeps out)."""
    rows = []
    names = sorted(os.listdir(out_dir)) if only is None else sorted(only)
    for name in names:
        if not name.endswith(".out"):
            continue
        path = os.path.join(out_dir, name)
        row: dict[str, Any] = {"file": name}
        cfg, summary = _parse_lines(path)
        row.update(cfg)
        if summary:
            row.update(parse_summary(summary))
        rows.append(row)
    return rows


def results_table(out_dir: str, x: str, y: str = "tput",
                  series: str = "cc_alg") -> dict[Any, list[tuple]]:
    """Pivot rows into {series_value: [(x, y), ...]} — the shape
    `scripts/plot.py` consumes."""
    table: dict[Any, list[tuple]] = {}
    for row in load_results(out_dir):
        if x not in row or y not in row:
            continue
        table.setdefault(row.get(series), []).append((row[x], row[y]))
    for pts in table.values():
        pts.sort()
    return table


def _parse_tagged(lines, pattern: re.Pattern) -> list[dict[str, Any]]:
    """One tagged-line family -> [{k: v}] (the shared body of every
    ``parse_<family>`` below: regex match, split on spaces, k=v with
    auto-typed values).  Each family keeps its own thin wrapper so the
    per-family contract stays documented in one obvious place."""
    out = []
    for line in lines:
        m = pattern.search(line)
        if not m:
            continue
        d: dict[str, Any] = {}
        for kv in m.group(1).split():
            if "=" not in kv:
                continue
            k, v = kv.split("=", 1)
            d[k] = _auto(v)
        out.append(d)
    return out


_MEMBER = re.compile(r"\[membership\] (.*)")


def parse_membership(lines) -> list[dict[str, Any]]:
    """Per-cutover ``[membership]`` lines (runtime/membership.py) ->
    [{node, version, epoch, reason, subject, slots_moved, owned,
    rows_in, rows_out, stall_ms}].  Logs predating the membership
    subsystem simply yield [] — and every other parser here ignores
    ``[membership]`` lines, so old tooling keeps working on new logs
    (forward/backward compat, tested in tests/test_harness.py)."""
    return _parse_tagged(lines, _MEMBER)


_REPL = re.compile(r"\[replication\] (.*)")


def parse_replication(lines) -> list[dict[str, Any]]:
    """Per-node ``[replication]`` summary lines (runtime/replication.py)
    -> [{node, role, region, ...}] — primaries carry quorum fields
    (quorum, quorum_acked, quorum_stall_ms, promote_cnt), followers the
    read-side ones (follower_read_cnt, stale_read_max_epochs,
    applied_epoch).  Logs predating the geo tier yield [], and every
    other parser ignores ``[replication]`` lines — the same
    forward/backward-compat contract as ``parse_membership`` (tested in
    tests/test_harness.py)."""
    return _parse_tagged(lines, _REPL)


_ADMIT = re.compile(r"\[admission\] (.*)")


def parse_admission(lines) -> list[dict[str, Any]]:
    """Per-tenant ``[admission]`` lines (runtime/admission.py) ->
    [{node, tenant, admitted, nacked, shed, ...}].  ``tenant=-1`` rows
    are node aggregates and additionally carry the queue-delay
    quantiles (qdelay_p50/p95/p99_ms), depth_max and breach_groups.
    Logs predating the overload tier yield [] — and every other parser
    here ignores ``[admission]`` lines — the same forward/backward-
    compat contract as ``parse_membership``/``parse_replication``
    (tested in tests/test_harness.py)."""
    return _parse_tagged(lines, _ADMIT)


_REPAIR = re.compile(r"\[repair\] (.*)")


def parse_repair(lines) -> list[dict[str, Any]]:
    """Per-node ``[repair]`` summary lines (engine/repair.py via
    runtime/server.py) -> [{node, salvaged, frontier, fallback, rounds,
    plane_cnt}].  ``salvaged`` counts txns that committed via in-epoch
    repair — by contract they are NOT in ``total_txn_abort_cnt``, so
    abort-rate parsing keeps its pre-repair semantics (the
    ``rep_salvaged_cnt`` [summary] field carries the same number).
    Logs predating the repair tier yield [] — and every other parser
    here ignores ``[repair]`` lines — the same forward/backward-compat
    contract as ``parse_membership``/``parse_replication``/
    ``parse_admission`` (tested in tests/test_harness.py)."""
    return _parse_tagged(lines, _REPAIR)


_FENCING = re.compile(r"\[fencing\] (.*)")


def parse_fencing(lines) -> list[dict[str, Any]]:
    """Per-node ``[fencing]`` lines (runtime/faildet.py via
    runtime/server.py) -> [{node, phi_peak, suspect_cnt,
    fence_nack_cnt, self_halt, heal_cnt, ...}].  Servers emit one at
    summary time (``self_halt=0``); a fenced-out primary emits one just
    before its exit-18 self-halt (``self_halt=1`` plus the reason and
    epoch).  Logs predating the fencing tier yield [] — and every
    other parser here ignores ``[fencing]`` lines — the same
    forward/backward-compat contract as ``parse_membership``/
    ``parse_replication``/``parse_admission``/``parse_repair`` (tested
    in tests/test_harness.py)."""
    return _parse_tagged(lines, _FENCING)


_TELEMETRY = re.compile(r"\[telemetry\] (.*)")


def parse_telemetry(lines) -> list[dict[str, Any]]:
    """Per-node ``[telemetry]`` lines (runtime/telemetry.py via every
    node kind's summary path) -> [{node, sampled_cnt, dropped_cnt,
    ring_highwater, flush_ms, sample}].  The flight recorder's health
    ledger: sampled_cnt proves the instrument was live (the regression
    gate's anti-inert check reads the [summary] twin of this field),
    dropped_cnt/ring_highwater size the ring, flush_ms bounds the
    sidecar-write cost.  Logs predating the telemetry tier yield [] —
    and every other parser here ignores ``[telemetry]`` lines — the
    same forward/backward-compat contract as ``parse_membership``/
    ``parse_replication``/``parse_admission``/``parse_repair``/
    ``parse_fencing`` (tested in tests/test_harness.py)."""
    return _parse_tagged(lines, _TELEMETRY)


_CRIT = re.compile(r"\[crit\] (.*)")
_WATCH = re.compile(r"\[watch\] (.*)")


def parse_metrics(lines) -> list[dict[str, Any]]:
    """Metrics-bus tagged lines (runtime/metricsbus.py) — BOTH
    families, each row stamped with its ``family``:

    * ``[crit]`` critical-path attribution (one per emit window):
      {family: "crit", node, epoch, gate, wall_ms, admit_ms, wire_ms,
      device_ms, retire_ms, other_ms, quorum_ms} — the wall stages sum
      to wall_ms by construction (CritLedger), quorum_ms is the
      overlapped hold->release ledger competing for ``gate``.
    * ``[watch]`` anomaly watchdog events: {family: "watch", node,
      kind, subject, ...} with kind in epoch_stall / straggler /
      jit_recompile (per-kind extra fields ride along; the structured
      twin of each event also lands in metrics_bus_*.jsonl).

    Logs predating the metrics bus yield [] — and every other parser
    here ignores ``[crit]``/``[watch]`` lines — the same forward/
    backward-compat contract as ``parse_membership`` through
    ``parse_telemetry`` (tested in tests/test_harness.py)."""
    lines = list(lines)
    rows = [dict(family="crit", **d)
            for d in _parse_tagged(lines, _CRIT)]
    rows += [dict(family="watch", **d)
             for d in _parse_tagged(lines, _WATCH)]
    return rows


_AUDIT = re.compile(r"\[audit\] (.*)")


def parse_audit(lines) -> list[dict[str, Any]]:
    """Per-node ``[audit]`` lines (runtime/audit.py via the server
    summary path) -> [{node, epochs, edges, edge_lanes, dropped,
    cadence, export_ms}].  The isolation audit plane's health ledger:
    ``epochs`` proves the certifier's instrument was live (the
    regression gate's anti-inert check reads the [summary]
    ``audit_edges_exported`` twin), ``edges``/``edge_lanes`` size the
    observation stream, ``dropped`` > 0 flags an export-cap overflow
    (certificate incomplete — raise audit_edges_max).  The CERTIFICATE
    itself is harness-side (``harness.auditgraph.certify`` over the
    audit_node*.jsonl sidecars); this line is the per-node export
    accounting.  Logs predating the audit plane yield [] — and every
    other parser here ignores ``[audit]`` lines — the same forward/
    backward-compat contract as ``parse_membership`` through
    ``parse_metrics`` (tested in tests/test_harness.py)."""
    return _parse_tagged(lines, _AUDIT)


_CTRL = re.compile(r"\[ctrl\] (.*)")


def parse_ctrl(lines) -> list[dict[str, Any]]:
    """Per-node ``[ctrl]`` decision lines (runtime/controller.ctrl_line)
    -> [{node, seq, epoch, epochs, dens, fb, sv, wit, slo, gap_us, gov,
    heal, trips, assign, gshift, cap, cad, qidx}].  One row per
    controller boundary tick, carrying BOTH the recorded signals
    (``dens``/``assign``/``gshift`` are colon-joined per-partition int
    strings — `_auto` keeps them as strings, split on ':' to consume)
    and the decision, which is the decision-replay contract's whole
    input: `runtime.controller.replay_decisions` re-derives the
    decision stream from these rows and diffs it field-for-field.
    Rows come back in emit order (seq order per node).  Logs predating
    the control plane yield [] — and every other parser here ignores
    ``[ctrl]`` lines — the same forward/backward-compat contract as
    ``parse_membership`` through ``parse_audit`` (tested in
    tests/test_harness.py)."""
    return _parse_tagged(lines, _CTRL)


_MESH = re.compile(r"\[mesh\] (.*)")


def parse_mesh(lines) -> list[dict[str, Any]]:
    """Per-node ``[mesh]`` lines (parallel/mesh.mesh_line via the server
    summary path, emitted only when ``device_parts > 1``) -> [{node,
    shards, a2a_bytes, prefetch_overlap, groups}].  The pod-scale
    measured path's health ledger: ``shards`` is the mesh width the
    epoch program actually ran at, ``a2a_bytes`` the bytes the owner
    exchange's ``all_to_all`` blocks move BETWEEN chips an epoch
    (static, from the block shapes the program cuts at the batch's real
    width; 0 = the replicated fallback plan, or a path that exchanges
    no lanes), ``prefetch_overlap`` the fraction of
    verdict-plane d2h prefetches already complete when the retire
    worker asked (1.0 = fully overlapped with device execution),
    ``groups`` the retired-group count behind that ratio.  Logs
    predating the mesh path — and every single-device run — yield []
    — and every other parser here ignores ``[mesh]`` lines — the same
    forward/backward-compat contract as ``parse_membership`` through
    ``parse_ctrl`` (tested in tests/test_harness.py)."""
    return _parse_tagged(lines, _MESH)


_DGCC = re.compile(r"\[dgcc\] (.*)")


def parse_dgcc(lines) -> list[dict[str, Any]]:
    """Per-node ``[dgcc]`` lines (engine/driver.py and runtime/server.py
    when the DGCC wavefront backend can validate) -> [{node, waves,
    wave_max, fallback, edges}].  The dependency-graph backend's health
    ledger: ``waves`` sums the executed wavefront depths over the
    measured window (>#epochs proves the backend actually chained —
    the smoke gate's anti-inert signal), ``wave_max`` is the deepest
    single-epoch wavefront of the run, ``fallback`` counts over-deep
    closures deferred to the retry queue (the cyclic fallback), and
    ``edges`` the pre-commit dependency-graph census (cross-checked
    against the audit plane's post-commit DSG by the dgcc oracle).
    Logs predating the DGCC backend — and every non-DGCC run — yield
    [] — and every other parser here ignores ``[dgcc]`` lines — the
    same forward/backward-compat contract as ``parse_membership``
    through ``parse_mesh`` (tested in tests/test_harness.py)."""
    return _parse_tagged(lines, _DGCC)


def cfg_header(cfg: Config) -> str:
    """`# cfg key=value` echo lines the runner prepends to each output file
    so parsing never has to re-derive the config from the filename."""
    lines = []
    for f in dataclasses.fields(cfg):
        v = getattr(cfg, f.name)
        sv = v.value if hasattr(v, "value") else v
        lines.append(f"# cfg {f.name}={sv}")
    return "\n".join(lines) + "\n"


def _auto(v: str) -> Any:
    for conv in (int, float):
        try:
            return conv(v)
        except ValueError:
            pass
    return v
