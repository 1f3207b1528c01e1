"""The one place a process picks its JAX device and compile cache.

Every process that traces (server, client, replica, `run_simulation`,
`bench.py`) calls ``init_jax(platform)`` before its first trace.  It
asks for exactly one platform, checks that JAX found it — a process
asked for the chip never continues on the CPU — and places the
persistent compile cache.  Launcher parents stay off JAX entirely (a
chip belongs to one process) and only call ``pin_platform`` in the
child before ``jax`` is imported.
"""

from __future__ import annotations

import json
import os
import threading

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# fixed path inside the checkout: the directory is part of the cache
# key, so a path made from a pid, a run id or a temp name never hits
REPO_CACHE_DIR = os.path.join(_ROOT, ".jax_cache")


def pin_platform(platform: str) -> None:
    """Child-process entry, BEFORE jax is imported: set (never
    ``setdefault``) ``JAX_PLATFORMS``, so a value inherited from the
    parent's shell can neither put a chip run on the CPU nor a client
    on the chip."""
    os.environ["JAX_PLATFORMS"] = platform


def place_compile_cache() -> None:
    """Persistent compile cache for the process's platform.

    On an accelerator: where ``JAX_COMPILATION_CACHE_DIR`` says if it is
    set (JAX reads it; no directory is set in code), else the fixed
    in-checkout ``REPO_CACHE_DIR``; the minimum compile time is lowered
    so the table loaders are kept beside the epoch programs.

    On the CPU the cache is turned OFF: CPU programs compile in a second
    or two, while XLA's CPU loader logs a page of machine-feature
    warnings per cached executable it reads back (and warns of SIGILL
    when a cache directory placed from outside outlives the machine
    that filled it).  So clients, replicas, the CPU replay child and the
    tests never fill the directory the chip's programs live in.
    ``JAX_ENABLE_COMPILATION_CACHE=false`` (JAX's own switch) turns it
    off everywhere."""
    import jax
    if jax.config.jax_platforms == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    # the epoch programs' `jax.named_scope`s are what a device trace is
    # read by (benchmark/phase_reduce.py), and JAX's cache key leaves
    # op metadata out by default: a hit would then hand back an
    # executable with the scopes of whoever compiled it first — a
    # parent commit's, none at all — and the profiler would report
    # those.  With the metadata in the key a program whose scopes (or
    # source lines) changed compiles anew instead.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def init_jax(platform: str) -> dict:
    """Select ``platform``, place the compile cache, start the compile
    ledger and return the device description JAX reports.  Raises when
    the platform is unavailable or JAX answers with another one."""
    pin_platform(platform)
    import jax
    jax.config.update("jax_platforms", platform)
    place_compile_cache()
    compile_ledger()
    try:
        devs = jax.devices()
    except Exception as e:      # RuntimeError, or a bare assert in JAX
        raise RuntimeError(
            f"requested JAX platform {platform!r} is unavailable — no "
            f"{platform.upper()} was found: {e!r}") from e
    if devs[0].platform != platform:
        raise RuntimeError(
            f"requested JAX platform {platform!r} but JAX runs on "
            f"{devs[0].platform!r} — no {platform.upper()} was found")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class _CompileLedger:
    """Process-wide count of XLA compile requests (persistent-cache
    hits included: each is still a stall of the calling thread), their
    trace + lower + compile seconds, and the cache hits among them —
    fed by JAX's own monitoring events."""

    _DUR = ("/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/jaxpr_to_mlir_module_duration",
            "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring
        self._lock = threading.Lock()
        self.count = 0
        self.secs = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_secs)
        monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **_kw) -> None:
        if event in self._DUR:
            with self._lock:
                self.secs += secs
                if event == self._DUR[2]:       # one per compile request
                    self.count += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        with self._lock:
            return self.count, self.secs, self.cache_hits


_ledger: _CompileLedger | None = None


def compile_ledger() -> _CompileLedger:
    """The process's compile ledger (registered on first use)."""
    global _ledger
    if _ledger is None:
        _ledger = _CompileLedger()
    return _ledger


def device_line(node: int, fields: dict) -> str:
    """One ``[device]`` closing line per node, printed by the launcher's
    CLI from the node's ``info`` (as JSON: ``device_kind`` has spaces):
    what JAX ran on, what set-up cost."""
    return f"[device] node={node} {json.dumps(fields)}"
