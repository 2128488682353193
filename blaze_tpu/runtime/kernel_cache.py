"""Process-wide operator-kernel cache.

≙ SURVEY.md §7: kernels are "compiled per (operator, schema,
batch-shape-bucket) and cached".  Exec nodes are rebuilt per task (the
gateway decodes a fresh plan from TaskDefinition bytes, exactly like
the reference's from_proto per task), so jitted kernels must NOT live
on exec instances — a per-instance ``@jax.jit`` closure means a full
XLA recompile for every task.  Builders register here under a
structural key (operator name + schema signature + expression keys);
the shape-bucket dimension is jax's own jit cache on the shared
function object.

Builders must close over NOTHING reachable from an exec node's
children (that would pin scanned data for the process lifetime) —
only schemas, expression IR, and static parameters.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, Tuple

from ..analysis.locks import make_lock
from ..schema import Schema
from . import lockset

_CACHE: Dict[tuple, Any] = {}
_LOCK = make_lock("kernel_cache.registry")
_REG = lockset.module_guard(__name__)

#: guarded-by declaration (analysis/guarded.py): concurrent map tasks
#: cold-hit the same kernels (exchange fan-out) — registry growth must
#: hold the lock
GUARDED_BY = {"_CACHE": "kernel_cache.registry"}
GUARDED_REFS = ("_CACHE",)


def schema_key(schema: Schema) -> Tuple:
    return tuple((f.name, f.dtype) for f in schema.fields)


def key_cacheable(key) -> bool:
    """False when the key embeds an opaque (identity-keyed) expression
    — e.g. a PythonUdf — which would grow the cache per instance."""
    if isinstance(key, tuple):
        return all(key_cacheable(k) for k in key)
    return key != "opaque"


def _kernel_label(key) -> str:
    """Operator attribution label for trace spans: the structural head
    of the kernel-cache key ("agg", "filter", "fused_stage", ...)."""
    if isinstance(key, tuple) and key and isinstance(key[0], str):
        return key[0]
    return "kernel"


def _instrumented(built: Any, label: str = "kernel") -> Any:
    """Wrap the builder's kernel(s) with dispatch/compile counting and
    trace attribution under ``label`` (runtime.dispatch): builders
    return one callable or a tuple of them.  Composition sites that
    inline a kernel inside another trace unwrap via ``dispatch.raw``."""
    from .dispatch import instrument

    if isinstance(built, tuple):
        return tuple(instrument(f, label) if callable(f) else f for f in built)
    return instrument(built, label) if callable(built) else built


def cached_kernel(key: tuple, builder: Callable[[], Any]) -> Any:
    """Return the kernel(s) registered under ``key``, building once.
    Keys containing opaque expressions bypass the cache."""
    if not key_cacheable(key):
        return _instrumented(builder(), _kernel_label(key))
    with _LOCK:
        lockset.check(_REG, "_CACHE")
        hit = _CACHE.get(key)
        if hit is not None:
            return hit
    built = _instrumented(builder(), _kernel_label(key))
    with _LOCK:
        lockset.check(_REG, "_CACHE")
        return _CACHE.setdefault(key, built)


#: the variable JAX itself reads for ``jax_compilation_cache_dir``
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def checkout_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — the fixed fallback.  The directory
    is part of the cache key, so it is never built from a temp name,
    pid or time, and it stays inside the checkout (gitignored)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)), ".jax_cache")


def enable_persistent_cache() -> str:
    """Decide where JAX's persistent compilation cache lives, for every
    launcher (the CLI, pool workers, ``chip_smoke.py``), and return the
    directory in force:

    1. ``JAX_COMPILATION_CACHE_DIR`` set: whoever launched the process
       placed the cache.  JAX reads the variable itself, so the
       directory is not touched here at all;
    2. else ``spark.blaze.xla.cacheDir`` when set;
    3. else ``<checkout>/.jax_cache``.

    Thresholds drop to zero either way: every program is worth keeping
    when a new process starts with no compiled code (≙ the reference
    shipping precompiled native code in its .so).  Shape bucketing
    (batch.py power-of-two capacities) keeps the entry count bounded."""
    import jax

    from .. import conf

    path = os.environ.get(CACHE_DIR_ENV, "")
    if not path:
        path = str(conf.XLA_CACHE_DIR.get() or "") or checkout_cache_dir()
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cache_stats() -> Dict[str, int]:
    with _LOCK:
        return {"entries": len(_CACHE)}


def clear_kernel_cache() -> None:
    with _LOCK:
        _CACHE.clear()
