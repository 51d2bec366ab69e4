"""Backend-derived execution defaults for the Pallas kernel layer.

Every kernel entry point takes ``interpret: bool | None = None``.  ``None``
resolves from the JAX backend at trace time: off-TPU (CPU/GPU) the kernel
body runs under the Pallas interpreter — bit-exact dataflow validation on
any host — while on TPU it compiles for the MXU/VPU.  Passing an explicit
bool still pins the mode (the kernel tests pin ``interpret=True`` shapes).

This module also hosts the **retrace ledger** (DESIGN.md §12): every jitted
entry point of the serving stack calls ``count_retrace(name)`` from inside
its traced Python body.  A jit body only executes when JAX traces a new
(shape, static-arg) signature, so the counter is an exact census of
compilations — the serving loops diff it across a run and publish the delta
as ``stats["retraces"]``, turning "the shape buckets held" from a hope into
an assertable number.  ``enable_persistent_cache`` additionally wires JAX's
on-disk compilation cache (``JAX_COMPILATION_CACHE_DIR``, else a fixed
directory in the checkout) so re-traced signatures at least skip XLA
compilation across processes.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

_RETRACES: dict = {"total": 0, "by_fn": {}}


def count_retrace(name: str) -> None:
    """Record one trace of jitted entry point ``name``.

    Call this from *inside* the function handed to ``jax.jit`` — the body
    runs once per cache-missing signature, never on a cache hit — guarded
    so an eager (un-jitted) call of the same body does not count.
    """
    _RETRACES["total"] += 1
    _RETRACES["by_fn"][name] = _RETRACES["by_fn"].get(name, 0) + 1


def retrace_count() -> int:
    """Monotone total of jit traces so far; diff two reads to attribute
    traces to one run (the ``stats["retraces"]`` mechanism)."""
    return _RETRACES["total"]


def retrace_counts() -> dict:
    """Per-entry-point trace totals (diagnostic view of the same ledger)."""
    return dict(_RETRACES["by_fn"])


# Fixed, checkout-relative default for JAX's persistent compilation cache:
# the directory is part of the cache key, so it must not move between runs.
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")
_CACHE_DIR: str | None = None


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and no other
    directory is used.  Otherwise the cache lives at ``DEFAULT_CACHE_DIR``
    (``<checkout>/.jax_cache``).  Call it before the process's first
    compilation: JAX fixes the cache when it first compiles.  Idempotent:
    later calls return the directory the first call chose.  Retrace
    *avoidance* comes from the pow2 shape buckets; the cache only
    de-duplicates XLA compilation across processes.
    """
    global _CACHE_DIR
    if _CACHE_DIR is not None:
        return _CACHE_DIR
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _CACHE_DIR = path
    return path


def resolve_interpret(flag: bool | None = None) -> bool:
    if flag is None:
        return jax.default_backend() != "tpu"
    return bool(flag)


def ceil_to(x: int, mult: int) -> int:
    """Round ``x`` up to a multiple of ``mult`` (block/lane alignment)."""
    return ((x + mult - 1) // mult) * mult


def pow2_bucket(x: int, floor: int) -> int:
    """Round ``x`` up to a power of two, never below ``floor``.

    Shape bucketing for the serving loop (DESIGN.md §5): padding every
    dynamic dimension to a power of two above its hardware alignment bounds
    the set of compiled executor variants to O(log) per dimension instead of
    one per distinct workload size.
    """
    v = max(int(x), 1, floor)
    return 1 << (v - 1).bit_length()
