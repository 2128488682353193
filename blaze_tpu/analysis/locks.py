"""Concurrency checker: a declared lock hierarchy, enforced two ways.

The engine runs four background-thread subsystems (the monitor HTTP
server, the async shuffle stager, the memory manager spilling one
consumer from another task's thread, and the exchange map fan-out),
and the PR 3 deadlock class — an event emission or nested acquisition
made while holding an unrelated lock — was caught by review, not by a
checker.  This module makes the ordering mechanical:

- :data:`HIERARCHY` declares every NAMED lock in the process, outermost
  first.  Modules create their locks through :func:`make_lock`, which
  refuses undeclared names — adding a lock WITHOUT placing it in the
  hierarchy fails at import time, not in review.
- **Runtime assertion** (conf ``spark.blaze.verify.locks``, armed in
  ``--chaos`` and the monitor/fault test suites): while armed, every
  acquire checks a thread-local stack of held locks and raises
  :class:`LockOrderError` when the new lock's rank is not strictly
  inward of everything already held — the would-be deadlock surfaces
  deterministically at the first inverted acquisition, not as a rare
  hang.  Disarmed (the default), an acquire costs one module-global
  bool read on top of the plain ``threading.Lock``.
- **Static pass** (:func:`lint_lock_order`): an AST walk over the
  package flags lexically visible nested ``with <lock>:`` acquisitions
  whose ranks are inverted (or tied), resolving lock variables through
  their ``make_lock("<name>")`` assignments.

The async shuffle stager itself synchronizes through a bounded
``queue.Queue`` (its own internal lock is invisible here); the lock it
shares with producers and the memory manager is the repartitioner's —
``shuffle.repartitioner`` in the hierarchy.  The heartbeat TLS
(monitor ``_tls``) is not a lock, but the runtime checker's held-stack
rides the same thread-local mechanism, so a beat callback that fires
inside an operator drive is checked against whatever that operator
holds.
"""

from __future__ import annotations

import ast
import os
import threading
from typing import Dict, List, Optional, Tuple

#: The declared lock hierarchy, OUTERMOST first: a thread may only
#: acquire locks at strictly increasing rank.  Every named lock in the
#: process appears here; make_lock refuses names that don't.
#:
#: Ordering rationale (the nestings that exist today):
#: - ``shuffle.repartitioner`` is held while staging spills into the
#:   memory manager (``memmgr.manager``) and bumping operator metrics
#:   (``metrics.set``), so it ranks outside both.
#: - ``memmgr.manager`` is held while reading trace arming, which can
#:   lazily load conf (``conf.store``) — conf is innermost of all.
#: - ``dispatch.kernel_state`` (the per-kernel compile-detection lock)
#:   records into the process tally (``dispatch.counters``) while held.
#: - ``trace.log`` (event-file IO) can lazily load conf; the kernel
#:   sinks (``trace.sink``) are the one lock events may be recorded
#:   under — the lint rule in analysis/lint.py pins that.
HIERARCHY: Tuple[str, ...] = (
    "monitor.server",        # server lifecycle (ensure/shutdown)
    "service.state",         # query-service admission queue + registry
                             # (held for queue/dict mutation only;
                             # query spans, cancels, and emission all
                             # happen after release)
    "service.gate",          # fair-share device-lease DRR state (held
                             # for grant bookkeeping; waiters block on
                             # their Events OUTSIDE it)
    "context.cancel",        # query CancelScope registry + fan-out set
                             # (held only for set/dict mutation; the
                             # trace emission a cancel produces happens
                             # after release)
    "hostpool.state",        # worker-host pool slot table: liveness,
                             # blacklist tallies, map-output ownership
                             # (held for dict/slot mutation only —
                             # spawn/kill syscalls, frame IO waits, and
                             # all trace emission happen after release;
                             # ranks inside context.cancel so a cancel
                             # checkpoint may consult pool state, and
                             # outside monitor.registry/ledger.state
                             # whose accounting hooks it calls)
    "querycache.state",      # result-cache LRU map + byte accounting
                             # (held for dict/LRU mutation, entry
                             # spill/promote serde — spill streams are
                             # one-shot cursors, so readers must never
                             # interleave — and set_mem_used_no_trigger
                             # [memmgr.manager, diskmgr.state and
                             # ledger.state all rank inside]; trace
                             # emission happens outside)
    "shuffle.repartitioner", # per-map-task staged partition buffers
    "monitor.registry",      # live query registry
    "monitor.workers",       # per-worker telemetry registry folded by
                             # hostpool reader threads + pool aggregate
                             # (held for dict arithmetic only; hostpool
                             # calls in AFTER releasing hostpool.state,
                             # and emission happens outside)
    "monitor.progress",      # per-stage progress counters (leaf: held
                             # only for arithmetic, emission is outside)
    "stats.registry",        # runtime-stats live plan registry +
                             # per-exchange histograms + HLL merges
                             # (held for dict/array arithmetic only;
                             # flush drains under it, then all trace
                             # emission, metric bumps, and store IO
                             # happen strictly after release)
    "otel.state",            # OTLP export queue + pusher lifecycle
                             # (held for list/slot mutation only; the
                             # HTTP POST and file IO happen outside)
    "monitor.hist",          # latency histograms + statsd timer queue
                             # (held for bucket arithmetic only)
    "slo.state",             # per-pool SLO sample rings + alert table
                             # (held for ring/dict arithmetic and the
                             # conf.store objective reads ranked
                             # inside; alert trace emission and the
                             # dispatch counter bumps happen strictly
                             # after release)
    "memmgr.manager",        # host-staging budget accounting
    "metrics.node",          # MetricNode tree growth
    "metrics.set",           # per-operator counters
    "dispatch.kernel_state", # per-kernel compile high-water mark
    "dispatch.counters",     # process dispatch tally + captures
    "integrity.state",       # per-path corruption tallies (held for
                             # dict arithmetic only; quarantine renames
                             # and emission happen outside)
    "diskmgr.state",         # registered shuffle roots + reclaim
                             # bookkeeping (held for set mutation and
                             # the age-gated unlink walk; emission is
                             # always outside)
    "kernel_cache.registry", # process-wide kernel cache
    "trace.log",             # event-log file IO
    "trace.sink",            # kernel-attribution sinks
    "trace.sample",          # sampling counter
    "conf.store",            # conf key/value store
    "errors.state",          # error-escape audit record (held for list
                             # append only; absorbed() is called from
                             # handler threads holding none of the
                             # locks above)
    "ledger.state",          # resource-ledger live table (innermost of
                             # the audit pair: acquire/release fire
                             # inside spill/shuffle critical sections,
                             # so every operator lock ranks outside it)
    "lockset.state",         # dynamic lockset-checker table (innermost:
                             # guarded accesses record while holding
                             # ANY of the locks above)
)

RANK: Dict[str, int] = {name: i for i, name in enumerate(HIERARCHY)}

_ARMED = False
#: held-stack tracking WITHOUT order assertions — armed by the dynamic
#: lockset checker (runtime/lockset.py), which needs to read the
#: per-thread held lockset at each guarded access even when the
#: lock-order assertion itself is off
_TRACK = False
_tls = threading.local()


class LockOrderError(AssertionError):
    """A named lock was acquired against the declared hierarchy."""

    def __init__(self, acquiring: str, held: List[str]):
        self.acquiring = acquiring
        self.held = list(held)
        super().__init__(
            f"lock-order violation: acquiring {acquiring!r} "
            f"(rank {RANK[acquiring]}) while holding "
            f"{[f'{h} (rank {RANK[h]})' for h in held]} — the declared "
            f"hierarchy (analysis/locks.py) only permits strictly "
            f"inward acquisition")


class OrderedLock:
    """A ``threading.Lock`` with a declared place in :data:`HIERARCHY`.

    Disarmed, acquire/release add one module-global bool read.  Armed
    (``spark.blaze.verify.locks``), each acquire asserts the new rank
    is strictly greater than every rank this thread already holds."""

    __slots__ = ("name", "rank", "_inner")

    def __init__(self, name: str):
        rank = RANK.get(name)
        if rank is None:
            raise ValueError(
                f"lock {name!r} is not declared in the hierarchy "
                f"(analysis/locks.py HIERARCHY) — place it before use")
        self.name = name
        self.rank = rank
        self._inner = threading.Lock()

    def _held_stack(self) -> List["OrderedLock"]:
        stack = getattr(_tls, "held", None)
        if stack is None:
            stack = _tls.held = []
        return stack

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if _ARMED or _TRACK:
            stack = self._held_stack()
            if _ARMED and stack and any(h.rank >= self.rank for h in stack):
                raise LockOrderError(self.name, [h.name for h in stack])
            got = self._inner.acquire(blocking, timeout)
            if got:
                stack.append(self)
            return got
        return self._inner.acquire(blocking, timeout)

    def release(self) -> None:
        # pop UNCONDITIONALLY (not gated on _ARMED): a thread that
        # acquired armed may release after a concurrent disarm (chaos
        # finally, suite teardown) — skipping the pop would leave a
        # stale entry that fires a spurious LockOrderError once a
        # later suite re-arms.  Disarmed acquires never push, so the
        # stack is empty/absent and this costs one TLS read.
        # Identity removal (the PR 3 bug class): two OrderedLocks
        # never compare equal, but the stack discipline is the same
        # as the capture lists runtime.metrics _remove_by_identity
        # guards — never evict a lookalike.
        stack = getattr(_tls, "held", None)
        if stack:
            for i in range(len(stack) - 1, -1, -1):
                if stack[i] is self:
                    del stack[i]
                    break
        self._inner.release()

    def locked(self) -> bool:
        return self._inner.locked()

    def __enter__(self) -> "OrderedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()


def make_lock(name: str) -> OrderedLock:
    """THE factory every module-level/instance lock in the checked
    subsystems goes through — the hierarchy stays complete because an
    undeclared name refuses to construct."""
    return OrderedLock(name)


def armed() -> bool:
    return _ARMED


def arm(on: bool) -> None:
    """Directly flip the runtime assertion (tests); :func:`refresh`
    reads it from conf instead.  Flip only at quiescent points: locks
    acquired disarmed are not tracked, so arming mid-critical-section
    would start from an empty held-stack.  The calling thread's stack
    is reset here; other threads' stacks drain as their scopes exit."""
    global _ARMED
    _ARMED = on
    _tls.held = []


def refresh() -> None:
    """(Re)load arming from conf ``spark.blaze.verify.locks`` — called
    by the chaos CLI and the monitor/fault suites after setting it.
    Lazy import: conf itself creates its lock through this module."""
    from .. import conf

    arm(bool(conf.VERIFY_LOCKS.get()))


def set_tracking(on: bool) -> None:
    """Flip held-stack tracking WITHOUT the order assertion — the
    dynamic lockset checker (runtime/lockset.py) arms this so
    :func:`held_names` is populated even when ``verify.locks`` is off.
    Same quiescent-point caveat as :func:`arm`; the calling thread's
    stack is reset, other threads' stacks drain as their scopes exit.
    Release pops unconditionally either way, so flipping tracking off
    can never strand an entry."""
    global _TRACK
    _TRACK = on
    _tls.held = []


def held_names() -> List[str]:
    """Names of ordered locks the calling thread holds right now
    (armed runs only — disarmed acquires don't track)."""
    stack = getattr(_tls, "held", None)
    return [h.name for h in stack] if stack else []


# ------------------------------------------------------ static AST pass

def _lock_name_bindings(tree: ast.AST) -> Dict[str, str]:
    """Map variable/attribute tails assigned from ``make_lock("x")``
    (or ``locks.make_lock``) to their hierarchy names within one
    module, e.g. ``{"_lock": "monitor.registry"}``.  A tail bound to
    TWO different hierarchy names in one module (two classes both
    using ``self._lock``) is ambiguous and dropped — checking it at an
    arbitrary rank would report false passes/failures; cross-function
    nesting is the runtime assertion's job anyway."""
    out: Dict[str, str] = {}
    ambiguous: set = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        call = node.value
        if not (isinstance(call, ast.Call) and call.args
                and isinstance(call.args[0], ast.Constant)
                and isinstance(call.args[0].value, str)):
            continue
        fn = call.func
        fn_name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else "")
        if fn_name != "make_lock":
            continue
        lock_name = call.args[0].value
        if lock_name not in RANK:
            continue
        for tgt in node.targets:
            tail = tgt.id if isinstance(tgt, ast.Name) else (
                tgt.attr if isinstance(tgt, ast.Attribute) else None)
            if tail is None:
                continue
            if tail in out and out[tail] != lock_name:
                ambiguous.add(tail)
            out[tail] = lock_name
    for tail in ambiguous:
        del out[tail]
    return out


def _with_lock_name(item: ast.withitem, bindings: Dict[str, str]) -> Optional[str]:
    e = item.context_expr
    if isinstance(e, ast.Name):
        return bindings.get(e.id)
    if isinstance(e, ast.Attribute):
        return bindings.get(e.attr)
    return None


def lint_lock_order(root: Optional[str] = None, parsed=None) -> List:
    """Static half of the concurrency checker: flag lexically nested
    ``with <lock>:`` acquisitions of hierarchy locks whose ranks are
    not strictly increasing.  Cross-function nesting is the runtime
    assertion's job; this pass catches the statically visible class
    before any test runs."""
    from .lint import Finding, package_root, parse_package

    root = root or package_root()
    findings: List[Finding] = []
    for path, _, tree in (parsed if parsed is not None
                          else parse_package(root)):
        bindings = _lock_name_bindings(tree)
        if not bindings:
            continue
        rel = os.path.relpath(path, os.path.dirname(root))

        def walk(node: ast.AST, held: List[Tuple[str, int]]) -> None:
            for child in ast.iter_child_nodes(node):
                entered = 0
                if isinstance(child, ast.With):
                    for item in child.items:
                        name = _with_lock_name(item, bindings)
                        if name is None:
                            continue
                        rank = RANK[name]
                        for held_name, held_rank in held:
                            if held_rank >= rank:
                                findings.append(Finding(
                                    rule="lock.static-order",
                                    path=rel, line=child.lineno,
                                    symbol=name,
                                    message=(
                                        f"acquires {name!r} (rank {rank}) "
                                        f"inside a region holding "
                                        f"{held_name!r} (rank {held_rank})"
                                    )))
                        held.append((name, rank))
                        entered += 1
                # nested function bodies run later, on an unknown
                # stack: reset the lexically-held set for them
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda)):
                    walk(child, [])
                else:
                    walk(child, held)
                for _ in range(entered):
                    held.pop()

        walk(tree, [])
    return findings
