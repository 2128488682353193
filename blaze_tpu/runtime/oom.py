"""Graceful degradation under device memory pressure.

The reference engine survives production because its memory manager
degrades to SPILL instead of dying (PAPER.md: "memory management with
spill"); on TPU the analogous cliff is XLA's ``RESOURCE_EXHAUSTED`` —
a program whose buffers don't fit HBM kills the task, the attempt
budget burns retrying the same too-big program, and the query dies.
This module is the recovery ladder between the allocator failure and
the attempt failure:

1. **Spill** (:func:`recover_spill`, applied at the dispatch choke
   point ``runtime/dispatch.py`` every instrumented kernel crosses):
   force every memmgr-tracked consumer to spill its host-staging
   state — shrinking the arrays the next transfer ships — and re-run
   the failing program once.
2. **Batch downshift** (``FusedStageExec``, ``ops/fusion.py``): a
   fused program that still OOMs halves its batch and re-runs the same
   program on each half, recursively up to
   ``spark.blaze.oom.maxDownshifts`` times — shape bucketing means the
   halves hit smaller, cheaper capacity buckets.
3. **Eager fallback**: at max depth the fused chain decomposes into
   its per-operator programs (one dispatch each — the pre-fusion
   path), trading the dispatch collapse for peak-memory headroom; the
   tier-5 fused shuffle write likewise falls back to its per-kernel
   path.

Only when the eager path ITSELF exhausts the device does the attempt
fail (:class:`DeviceOomError`, retryable) — and by then the failure is
genuine pressure, not a fusion artifact.

Async-dispatch caveat: the ladder catches an exhaustion surfaced at
the launch OR at the fused stage's own count sync (resolved inside the
guard).  A backend that defers the failure past both — async dispatch
with no in-ladder sync point, e.g. a non-compacting chain whose OOM
only appears at the next host transfer — degrades to the pre-ladder
behavior: the attempt fails retryably and the retry may land after
pressure subsided.  Forcing a block-until-ready per dispatch would
close that window at the cost of serializing the device per program —
the exact dispatch-overhead cliff tiers 1-5 exist to avoid.  Every rung records a counter
(``oom_recoveries`` / ``batch_downshifts`` / ``eager_fallbacks``,
runtime.dispatch -> stage MetricNode -> /metrics) and emits an
``oom_recovery`` trace event so ``--report`` shows what degraded and
why; the faults grammar's ``@oom`` modifier (``kernel.dispatch@N@oom``)
makes the whole ladder deterministically testable.
"""

from __future__ import annotations

from typing import List, Optional


class DeviceOomError(RuntimeError):
    """The degradation ladder is exhausted: even the smallest piece on
    the eager path exhausted the device.  Retryable (pressure may have
    subsided by the retry), unlike host MemoryError which stays
    FATAL."""

    def __init__(self, label: str, cause: Optional[BaseException] = None):
        self.label = label
        super().__init__(
            f"device OOM in {label!r} survived the degradation ladder "
            f"(spill, batch downshift, eager fallback)"
            + (f": {cause}" if cause is not None else ""))


def is_resource_exhausted(exc: BaseException) -> bool:
    """Is this exception a device-memory exhaustion the ladder should
    absorb?  True for XLA's RESOURCE_EXHAUSTED status (surfaced as
    ``XlaRuntimeError`` — matched by message, the only stable contract
    across jaxlib versions) and for the fault injector's
    :class:`runtime.faults.InjectedOom` stand-in.  Host-side
    ``MemoryError`` stays out: retry.classify treats it as FATAL.  So
    does a COMPILER refusal: Mosaic/XLA report a kernel whose scoped
    on-chip memory (``vmem``/``smem``) does not fit under the same
    status, and no spill or smaller batch repairs a kernel that cannot
    be built — absorbing it would hide a broken kernel behind the
    eager rung."""
    if isinstance(exc, MemoryError):
        return False
    if isinstance(exc, DeviceOomError):
        # the ladder's own terminal verdict: the message embeds the
        # cause's RESOURCE_EXHAUSTED text, but re-absorbing it would
        # climb the ladder again for a batch that already exhausted it
        return False
    s = str(exc)
    if "RESOURCE_EXHAUSTED" not in s and "Resource exhausted" not in s:
        return False
    low = s.lower()
    return "vmem" not in low and "smem" not in low


def max_downshifts() -> int:
    from .. import conf

    return max(0, int(conf.OOM_MAX_DOWNSHIFTS.get()))


def recover_spill(label: str) -> int:
    """Ladder rung 1: shed host-staging pressure (memmgr force-spill —
    every tracked consumer spills regardless of watermark), count the
    recovery, and leave an ``oom_recovery`` event on the record.
    Returns bytes freed (0 when nothing was buffered — the retry still
    happens: the failed allocation itself was freed with the failed
    program)."""
    from . import dispatch, trace
    from .memmgr import MemManager

    freed = MemManager.get().force_spill()
    dispatch.record("oom_recoveries")
    trace.emit("oom_recovery", label=label, action="spill",
               freed_bytes=freed)
    return freed


def record_downshift(label: str, rows: int, depth: int) -> None:
    """Ladder rung 2 bookkeeping: one batch split into halves."""
    from . import dispatch, trace

    dispatch.record("batch_downshifts")
    trace.emit("oom_recovery", label=label, action="downshift",
               rows=rows, depth=depth)


def record_eager_fallback(label: str) -> None:
    """Ladder rung 3 bookkeeping: fused program decomposed to the
    eager per-operator path."""
    from . import dispatch, trace

    dispatch.record("eager_fallbacks")
    trace.emit("oom_recovery", label=label, action="eager")


def build_eager_kernels(keys_and_fns) -> List:
    """Rung 3's per-operator programs, ONE place: each trace transform
    becomes its own cached jitted kernel under a ``fused_stage_eager``
    key — shared by ``FusedStageExec._eager_run`` and the tier-5 fused
    shuffle write's degraded chain, so the eager-rung contract (key
    shape, caching, instrumentation) cannot drift between the two."""
    from .kernel_cache import cached_kernel

    kernels = []
    for key, fn in keys_and_fns:
        def build(fn=fn):
            import jax

            @jax.jit
            def kernel(cols, num_rows):
                return fn(cols, num_rows)

            return kernel

        kernels.append(cached_kernel(("fused_stage_eager", key), build))
    return kernels


def split_batch(batch) -> List:
    """Halve a batch by rows (host-side — the degraded path trades a
    transfer for fitting the device at all); each half re-enters the
    kernel under its own (smaller) capacity bucket.  Batches of one
    row don't split."""
    import numpy as np

    from ..batch import Column, RecordBatch, bucket_capacity

    n = batch.num_rows
    if n <= 1:
        return [batch]
    host = batch.to_host()

    def slice_col(c: Column, lo: int, hi: int) -> Column:
        s = lambda a: None if a is None else np.asarray(a)[lo:hi]  # noqa: E731
        return Column(
            c.dtype, s(c.data), s(c.validity), s(c.lengths),
            None if c.children is None
            else tuple(slice_col(k, lo, hi) for k in c.children),
        )

    mid = n // 2
    out = []
    for lo, hi in ((0, mid), (mid, n)):
        cols = [slice_col(c, lo, hi) for c in host.columns]
        piece = RecordBatch(host.schema, cols, hi - lo)
        out.append(piece.with_capacity(bucket_capacity(hi - lo)))
    return out
