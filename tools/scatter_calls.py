#!/usr/bin/env python3
"""What a lane of the row WRITE costs, by the form that writes it.

    chiprun --timeout 2400 -- python tools/scatter_calls.py   (one v5e)

`ops.scatter.scatter_winner_rows` itself, on a column of the served
shape (`uint8[6291520, 100]`, and the version ring's `[6291520, 40]`) at
the winners the cells hand it — the hot cell's (163,840 lanes a plan,
zipf 0.9, ~30k final writers), a shard of four's (`--plans shard`:
81,920 lanes, ~7,600) and the medium cells' (10,240 lanes, zipf 0.6,
~2,000); `--plans flat hot06 hot08` are the hot cell's lanes and winners
at zipf 0 / 0.6 / 0.8, where ever fewer winners share a group — in
XLA's form and every other asked for (`--forms`):

* ``xla_loop``: a trip of the loop is XLA's scatter (PRs 26-47);
* ``whole``: the one pass over the column with the sorted promise;
* ``kernel``: a trip is `ops.scatter.write_rows_by_group`, at every
  ``--in-flight`` x ``--chunks`` x ``--bounds-checks`` (its module
  constants `_IN_FLIGHT`, `_CHUNKS`, `_BOUNDS_CHECKS`; `--chunks 16` is
  calls of four times the lanes), whatever `_MIN_CALL_LANES` says.

Each form writes one epoch into a fresh column and is held to the bytes
of XLA's loop; then the epochs run back to back INSIDE one program (a
`fori_loop` over a pool of four plans, the column donated, as the served
scan has it — a program's entry relayouts fall out of the difference of
two trip counts).  "ns a lane" is the time over the time with no winner
(the compaction sort alone, no trip), a lane HANDED to the row write.
One `[scatter_calls] {json}` line a (width, plan).  `--platform cpu
--rows 4096 --reps 1 --plans toy` rehearses it without a chip (the
kernel under Pallas' interpreter); its times mean nothing.
`JAX_PLATFORMS=cpu python tools/scatter_calls.py --compile-only` compiles
every form at the real shapes for a described v5e instead of running it.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name -> (lanes a plan, zipf theta, final writers)
PLANS = {"hot": (163_840, 0.9, 30_000), "shard": (81_920, 0.9, 7_600),
         "medium": (10_240, 0.6, 2_000), "toy": (1_280, 0.6, 300),
         "flat": (163_840, 0.0, 30_000), "hot06": (163_840, 0.6, 30_000),
         "hot08": (163_840, 0.8, 30_000)}
POOL = 4


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--rows", type=int, default=6_291_520)
    ap.add_argument("--widths", type=int, nargs="+", default=[100, 40])
    ap.add_argument("--plans", nargs="+", default=["hot", "medium"],
                    choices=sorted(PLANS))
    ap.add_argument("--in-flight", type=int, nargs="+",
                    default=[2, 8, 32, 128, 256])
    ap.add_argument("--chunks", type=int, nargs="+", default=[64])
    ap.add_argument("--bounds-checks", type=int, nargs="+", default=[0],
                    choices=[0, 1])
    ap.add_argument("--forms", nargs="+", default=["whole", "kernel"],
                    choices=["whole", "kernel"],
                    help="beside xla_loop, which every other is held to")
    ap.add_argument("--reps", type=int, default=100)
    ap.add_argument("--compile-only", action="store_true",
                    help="compile every form for a DESCRIBED v5e (no chip: "
                    "run it with JAX_PLATFORMS=cpu) and say what the "
                    "chip's compiler made of each")
    args = ap.parse_args(argv)

    from deneva_tpu.runtime.jaxenv import init_jax
    dev = init_jax("cpu" if args.compile_only else args.platform)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.generators.ycsb import zeta, zipf_keys
    from deneva_tpu.ops import scatter as S
    from deneva_tpu.workloads.ycsb import _field_bytes

    rows = args.rows
    n_rows = rows - 64                      # the table's trash and padding
    kernel_fn = S.write_rows_by_group
    if args.platform != "tpu" and not args.compile_only:
        from jax.experimental.pallas import tpu as pltpu
        kernel_fn = functools.partial(kernel_fn,
                                      interpret=pltpu.InterpretParams())
    defaults = (S._IN_FLIGHT, S._CHUNKS)

    def form(width, n, kind, in_flight=None, chunks=None, checked=0):
        """(jitted epochs(col, slots, win, keys, reps), its name)."""
        k, c = defaults
        S._IN_FLIGHT, S._CHUNKS = in_flight or k, chunks or c
        S._BOUNDS_CHECKS = bool(checked)
        # the choice's side, flagged: a loop form loops whatever the
        # counts, the whole pass passes wherever anything won
        S._ROWS_PER_LANE = S._KERNEL_ROWS_PER_LANE = \
            (rows + 6 * n) // -(-n // S._CHUNKS) + 1 \
            if kind == "whole" else 0
        S._on_tpu = lambda: kind == "kernel"
        S._by_group = lambda shape, dtype, chunk: kind == "kernel"
        S.write_rows_by_group = kernel_fn

        def epochs(col, slots, win, keys, reps):
            """(col', lanes and groups the last epoch handed)"""
            def body(i, c):
                j = i % POOL
                col, lanes, groups, _ = S.scatter_winner_rows(
                    c[0], slots[j], win[j], (keys[j], keys[j] + i),
                    lambda k, r: _field_bytes(k, r, width), n_rows,
                    jnp.uint32(0))
                return col, lanes, groups
            return jax.lax.fori_loop(
                0, reps, body, (col, jnp.uint32(0), jnp.uint32(0)))
        f = jax.jit(epochs, donate_argnums=0)
        # (traced at its first call — the caller's next line — under
        # THIS form's constants)
        name = kind if kind != "kernel" else \
            f"kernel_k{S._IN_FLIGHT}_c{S._CHUNKS}" + "_checked" * checked
        return f, name

    def compiled_for_v5e(f, width, n) -> dict:
        """What the chip's compiler makes of a form: its kernels, its
        scatters into the column, and the column's copies by where."""
        import re
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=one)
        hlo = f.lower(sds((rows, width), jnp.uint8),
                      sds((POOL, n), jnp.int32), sds((POOL, n), jnp.bool_),
                      sds((POOL, n), jnp.int32),
                      sds((), jnp.int32)).compile().as_text()
        shape, where, copies = f"u8[{rows},{width}]", None, []
        for ln in hlo.splitlines():
            head = re.match(r"^(ENTRY )?%[\w.\-]+ \(", ln)
            if head:
                where = "entry" if head.group(1) else "inner"
            if re.search(r"= " + re.escape(shape) + r"\S* copy\(", ln):
                copies.append(where)
        return dict(kernels=hlo.count('custom_call_target="tpu_custom_call"'),
                    scatters=len(re.findall(
                        r"= " + re.escape(shape) + r"\S* scatter\(", hlo)),
                    column_copies=copies,
                    layouts=sorted(set(re.findall(
                        re.escape(shape) + r"(\{[^}]*\})", hlo))))

    def timed(f, col, *a) -> tuple[float, object]:
        """(ms an epoch, col'): the difference of two trip counts."""
        lo, hi = 2, 2 + args.reps
        col = f(col, *a, lo)[0]
        col.block_until_ready()
        t = []
        for reps in (lo, hi):
            t0 = time.perf_counter()
            col = f(col, *a, reps)[0]
            col.block_until_ready()
            t.append(time.perf_counter() - t0)
        return (t[1] - t[0]) / (hi - lo) * 1e3, col

    for width in args.widths:
        fresh = jax.jit(lambda: jax.random.bits(
            jax.random.PRNGKey(48), (rows, width), jnp.uint8))
        for plan in args.plans:
            n, theta, cnt = PLANS[plan]
            zetan = zeta(n_rows, theta)
            slots, win = [], []
            for j in range(POOL):
                rng = np.random.default_rng([48, n, j])
                # slot order follows the plan's key order; the final
                # writer of a key is its last lane
                s = np.sort(zipf_keys(rng, (n,), n_rows, theta, zetan))
                last = np.flatnonzero(np.append(s[1:] != s[:-1], True))
                w = np.zeros(n, bool)
                w[rng.choice(last, min(cnt, last.size), replace=False)] = True
                slots.append(s)
                win.append(w)
            slots, win = jnp.asarray(np.stack(slots)), np.stack(win)
            keys = slots * 3 + 1
            none, win = jnp.zeros_like(win), jnp.asarray(win)
            out = dict(width=width, plan=plan, plan_lanes=n,
                       winners=int(win[0].sum()), rows=rows, device=dev,
                       reps=args.reps, forms={})
            kinds = [("xla_loop", {}), ("whole", {})] + [
                ("kernel", dict(in_flight=k, chunks=c, checked=b))
                for c in args.chunks for k in args.in_flight
                for b in args.bounds_checks]
            kinds = [kd for kd in kinds
                     if kd[0] in ["xla_loop"] + args.forms]
            want = None
            for kind, kw in kinds:
                f, name = form(width, n, kind, **kw)
                if args.compile_only:
                    try:
                        out["forms"][name] = compiled_for_v5e(f, width, n)
                    except Exception as e:
                        out["forms"][name] = dict(error=str(e)[:400])
                    print(f"[scatter_calls.form] {width} {plan} {name} "
                          + json.dumps(out["forms"][name]), flush=True)
                    continue
                try:
                    one, lanes, groups = f(fresh(), slots, win, keys, 1)
                    one.block_until_ready()
                except Exception as e:      # a form the chip refuses
                    out["forms"][name] = dict(error=repr(e)[:300])
                    continue
                if want is None:            # (`one` is donated below)
                    want = jnp.copy(one)
                same = bool(jnp.array_equal(one, want))
                lanes, groups = int(lanes), int(groups)
                base, one = timed(f, one, slots, none, keys)
                ms, one = timed(f, one, slots, win, keys)
                del one
                out["forms"][name] = dict(
                    same_bytes=same, handed=lanes, groups=groups, ms=ms,
                    sort_only_ms=base,
                    ns_a_lane=(ms - base) * 1e6 / max(lanes, 1))
                print(f"[scatter_calls.form] {width} {plan} {name} "
                      + json.dumps(out["forms"][name]), flush=True)
            del want
            print("[scatter_calls] " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
