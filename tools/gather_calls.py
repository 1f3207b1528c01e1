#!/usr/bin/env python3
"""What a lane of the row gather costs by the lane count of its call.

    chiprun -- python tools/gather_calls.py        (one v5e; ~2 min)

`ops.gather.checksum_needed_rows` itself, on a column of the served
shape (`uint8[6291520, 100]`) at the two plans the cells run — 163,840
lanes of which ~90k are needed (the hot cell), 81,920 of which ~24k (a
shard of four) — once for every chunk size asked for (its `_CHUNKS`
set to ceil(N / lanes)), and beside it ONE `jnp.take` of the front at
each `--one-call` size (default: the covering sixteenths and every
lane, the form of PRs 30-46).  Each form is held to the
per-lane gather's sum, then timed as back-to-back calls whose last is
waited for.  "ns a lane" is the time over the time with nothing needed
(the sort alone, no trip), a lane HANDED to the gather.  One
`[gather_calls] {json}` line a plan.  `--platform cpu --rows 50000
--reps 3` rehearses it without a chip; its times mean nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PLANS = ((163_840, 90_000), (81_920, 24_000))       # (lanes, needed)


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--rows", type=int, default=6_291_520)
    ap.add_argument("--lanes", type=int, nargs="+",
                    default=[1024, 1280, 2048, 2560, 4096, 5120, 10240])
    ap.add_argument("--one-call", type=int, nargs="*", default=[])
    ap.add_argument("--reps", type=int, default=200)
    args = ap.parse_args(argv)

    from deneva_tpu.runtime.jaxenv import init_jax
    dev = init_jax(args.platform)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.generators.ycsb import zeta, zipf_keys
    from deneva_tpu.ops import gather as G

    rows = args.rows
    col = jax.random.bits(jax.random.PRNGKey(47), (rows, 100), jnp.uint8)
    zetan = zeta(rows - 64, 0.9)

    def timed(f, *a) -> float:
        f(*a)[0].block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = f(*a)
        out[0].block_until_ready()
        return (time.perf_counter() - t0) / args.reps * 1e3

    def one_call(k):
        """The needed lanes at the front, then ONE gather of k lanes."""
        def f(col, slots, need):
            big = jnp.int32(jnp.iinfo(jnp.int32).max)
            idx = jnp.sort(jnp.where(need, slots, big))[:k]
            vals = jnp.take(col, idx, axis=0, mode="clip")
            return jnp.sum(jnp.where((idx < big)[:, None], vals, 0),
                           dtype=jnp.uint32), k
        return jax.jit(f)

    for n, cnt in PLANS:
        rng = np.random.default_rng([47, n])
        # slot order follows the plan's key order; a hot row repeats
        slots = jnp.asarray(np.sort(zipf_keys(rng, (n,), rows - 64, 0.9,
                                              zetan)))
        mask = np.zeros(n, bool)
        mask[rng.choice(n, cnt, replace=False)] = True
        need, none = jnp.asarray(mask), jnp.zeros((n,), bool)
        want = int(jnp.sum(jnp.where(
            need[:, None], jnp.take(col, slots, axis=0), 0),
            dtype=jnp.uint32))
        out = dict(plan_lanes=n, needed=cnt, device=dev, rows=rows,
                   reps=args.reps, loop={}, one_call={})
        for lanes in args.lanes:
            G._CHUNKS = -(-n // lanes)
            f = jax.jit(lambda *a: G.checksum_needed_rows(*a))
            got, handed = f(col, slots, need)
            assert int(got) == want, (lanes, int(got), want)
            base, ms = timed(f, col, slots, none), timed(f, col, slots, need)
            out["loop"][-(-n // G._CHUNKS)] = dict(
                handed=int(handed), ms=ms, sort_only_ms=base,
                ns_a_lane=(ms - base) * 1e6 / int(handed))
        base = min(r["sort_only_ms"] for r in out["loop"].values())
        for k in sorted({k for k in args.one_call if cnt <= k <= n}
                        or {-(-cnt // (n // 16)) * (n // 16), n}):
            f = one_call(k)
            assert int(f(col, slots, need)[0]) == want, k
            ms = timed(f, col, slots, need)
            out["one_call"][k] = dict(ms=ms,
                                      ns_a_lane=(ms - base) * 1e6 / k)
        print("[gather_calls] " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
