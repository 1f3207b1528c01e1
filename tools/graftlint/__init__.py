"""graftlint v2: repo-specific static analysis for the jax_graft
runtime.

Seven invariant checker families plus generic import hygiene protect
the invariants the headline results rest on (README "Invariants &
lint", COVERAGE §2.12/§2.15).  The flow-sensitive families share one
intraprocedural CFG/dataflow core (`cfg.py`: basic blocks with
exception edges, labeled branch edges, dominance, reaching
definitions):

* **trace**  — trace-safety inside jit/shard_map-reachable code: no
  Python branching on tracer values, no `np.*` on traced arrays, no
  `.item()`/`float()` host syncs, no hash-unstable static args that
  re-trace per epoch (taint fixpoint over CFG blocks in RPO).
* **det**    — determinism in replay-relevant modules: no unseeded RNG
  or wall-clock feeding state/digests, no set/dict iteration ORDER
  escaping into wire encoders, log records or digests — directly or
  through locals/accumulators (flow-sensitive; `sorted(...)` rebinds
  kill the taint, commutative folds carry none).
* **wire**   — the rtype registry, the wire codecs, the route branches
  and the fault-mask classification must agree with one declared model
  (`wiremodel.py`).
* **own**    — thread-ownership of ServerNode state (dispatch / wire
  worker / retire worker): no worker writes state it does
  not own (`deneva_tpu/runtime/ownercheck.py` is the declarations
  file; the same decls drive the `owner_check=true` runtime asserts).
* **gate**   — default-off subsystems (geo/elastic/admission/fault)
  used only under their registered config-flag checks (dominating-
  condition analysis; registry `deneva_tpu/runtime/gates.py`, gated
  rtypes on `wiremodel.py` rows), no guard-shedding rebinds of
  owner-checked collections, raw escrow masks confined to the ONE
  escrow gate.
* **life**   — threads joined, futures drained, transports/files
  closed on every path out, exception edges included (the try/finally
  discipline, checked instead of remembered).
* **jit**    — recompile-storm hazards inside jit entry graphs:
  value-dependent shapes, unhashable static defaults, captured mutable
  globals, weak-dtype scalar call sites.
* **imports** — generic import hygiene (unused/duplicate imports), the
  in-repo stand-in for the ruff pyflakes baseline on boxes without ruff.

Run:      python -m tools.graftlint deneva_tpu/
          python -m tools.graftlint --changed   (git-diff-scoped subset)
Suppress: trailing `# graftlint: ignore[rule-id]` (same or previous
line), with a comment explaining why; `# graftlint: skip-file` in the
first five lines skips a file (fixtures only).
"""

from tools.graftlint.core import Finding, Tree, run_checkers  # noqa: F401
