"""Whole-stage program fusion: collapse a stage's operator chain into
single XLA programs.

≙ SURVEY.md §7 "hard parts": "ours depends on keeping a stage's
operator chain fused on-device".  The reference gets per-operator
streams fused by its CPU pipeline; on TPU every operator boundary is a
dispatch + a materialized intermediate — q01's hash-agg -> final-merge
-> sort chain issued on the order of a hundred programs per batch
before fusion (VERDICT r5).  Four tiers, all gated on
``spark.blaze.fusion.enabled``:

1. **Agg absorption** (:func:`fuse_stages`): a PARTIAL AggExec over
   pure device Filter/Project chains absorbs them — the predicate
   becomes the kernel's liveness mask (``pre_filter``) and projections
   substitute into the aggregate expressions, so q06 collapses to
   scan->partial-agg.
2. **Trivial-exchange elimination** (:func:`fuse_stages`): a shuffle
   into ONE partition whose child already has one partition is a
   pass-through; dropping it removes the partition/concat programs
   between the final agg and its consumer in single-chip plans.
3. **Final-sort folding** (:func:`fuse_stages`): ``Limit?(Sort(FINAL
   agg))`` folds the key sort (+ fetch clamp) into the agg's finalize
   program — FINAL emits one blocking batch per partition, so the
   in-program sort is exact (``AggExec.post_sort``/``post_fetch``).
4. **Traceable-chain collapse** (:func:`fuse_traceable_chains`, run
   AFTER column pruning so scan narrowing still sees the original
   operators): consecutive unary operators exposing the
   ``ExecNode.trace_fn`` contract compose into one
   :class:`FusedStageExec` program per batch.  Operators whose traced
   transform needs the whole partition in one batch
   (``trace_requires_buffer`` — WindowExec) get a
   :class:`BufferPartitionExec` planted below the fused program.
5. **Fused shuffle write** (:func:`fuse_shuffle_write`, run last): when
   a traceable chain (or nothing) feeds a ``ShuffleWriterExec`` with
   hash or round-robin partitioning, the chain's transform, the
   partition-id computation, the pid sort, and the per-partition
   counts compose into ONE program per batch
   (``ShuffleWriterExec.absorb_traceable_chain``) — a shuffle map
   stage costs ~1 dispatch/batch instead of chain+hash+sort, mirroring
   the reference's native shuffle writer where map-side compute and
   partitioning live in one pipeline.

The per-batch agg-update program (reduce + accumulator merge in one
dispatch) lives in ``ops/agg.py`` (``AggExec._update_kernels``); the
``fused_stage_len`` observability counter feeds the scheduler's
MetricNode through ``runtime.dispatch``.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from .. import conf
from ..batch import RecordBatch
from .base import BatchStream, ExecNode

from ..exprs.ir import (
    Alias,
    BinOp,
    Case,
    Cast,
    Col,
    Expr,
    GetIndexedField,
    GetMapValue,
    GetStructField,
    InList,
    IsNotNull,
    IsNull,
    Like,
    NamedStruct,
    Not,
    ScalarFunc,
)


def substitute(e: Expr, mapping: Dict[str, Expr]) -> Expr:
    """Replace column references per ``mapping``, rebuilding the tree."""
    if isinstance(e, Col):
        return mapping.get(e.name, e)
    if isinstance(e, Alias):
        return Alias(substitute(e.child, mapping), e.name)
    if isinstance(e, BinOp):
        return BinOp(e.op, substitute(e.left, mapping), substitute(e.right, mapping))
    if isinstance(e, Not):
        return Not(substitute(e.child, mapping))
    if isinstance(e, IsNull):
        return IsNull(substitute(e.child, mapping))
    if isinstance(e, IsNotNull):
        return IsNotNull(substitute(e.child, mapping))
    if isinstance(e, Cast):
        return Cast(substitute(e.child, mapping), e.to)
    if isinstance(e, Case):
        return Case(
            [(substitute(c, mapping), substitute(v, mapping)) for c, v in e.branches],
            None if e.else_ is None else substitute(e.else_, mapping),
        )
    if isinstance(e, InList):
        return InList(
            substitute(e.child, mapping), [substitute(v, mapping) for v in e.values],
            e.negated,
        )
    if isinstance(e, Like):
        return Like(substitute(e.child, mapping), e.pattern, e.negated)
    if isinstance(e, ScalarFunc):
        return ScalarFunc(e.name, [substitute(a, mapping) for a in e.args])
    if isinstance(e, GetIndexedField):
        return GetIndexedField(substitute(e.child, mapping), e.index)
    if isinstance(e, GetMapValue):
        return GetMapValue(substitute(e.child, mapping), e.key)
    if isinstance(e, GetStructField):
        return GetStructField(substitute(e.child, mapping), e.name)
    if isinstance(e, NamedStruct):
        return NamedStruct(list(e.names), [substitute(x, mapping) for x in e.exprs])
    return e  # literals, opaque nodes


def projection_mapping(names, exprs) -> Dict[str, Expr]:
    """name -> (Alias-stripped) expr for inlining a projection."""
    return {n: (e.child if isinstance(e, Alias) else e) for n, e in zip(names, exprs)}


def _apply_mapping(groupings, aggs, pre, mapping):
    from .agg import AggFunction, GroupingExpr

    groupings = [GroupingExpr(substitute(g.expr, mapping), g.name) for g in groupings]
    aggs = [
        AggFunction(a.fn, None if a.expr is None else substitute(a.expr, mapping), a.name)
        for a in aggs
    ]
    if pre is not None:
        pre = substitute(pre, mapping)
    return groupings, aggs, pre


def fuse_stages(plan):
    """Rewrite (in place below the root): agg absorption, trivial
    single-partition exchange elimination, and final-sort folding (see
    module docstring tiers 1-3).  Returns the root.  A no-op under
    ``spark.blaze.fusion.enabled=false`` — the per-operator fallback
    the fused-vs-unfused differential tests pin."""
    from .agg import AggExec, AggFunction, AggMode, GroupingExpr
    from .filter import FilterExec
    from .project import ProjectExec

    if not bool(conf.FUSION_ENABLE.get()):
        return plan

    plan = _drop_noop_exchanges(plan)

    def try_fuse(agg: "AggExec"):
        if agg.mode != AggMode.PARTIAL:
            return agg
        groupings = list(agg.groupings)
        aggs = list(agg.aggs)
        pre = agg.pre_filter
        child = agg.children[0]
        changed = False
        absorbed = 0
        while True:
            if isinstance(child, ProjectExec) and not child._host_parts:
                mapping = projection_mapping(child.names, child.exprs)
                groupings, aggs, pre = _apply_mapping(groupings, aggs, pre, mapping)
                child = child.children[0]
                changed = True
                absorbed += 1
                continue
            if isinstance(child, FilterExec) and not child._host_parts:
                if child.project is not None:
                    # a filter already fused with a projection: inline
                    # the projection first (pre/groupings/aggs reference
                    # its OUTPUT names), then AND the predicate (which
                    # references the filter's INPUT schema)
                    proj_exprs, proj_names = child.project
                    mapping = projection_mapping(proj_names, proj_exprs)
                    groupings, aggs, pre = _apply_mapping(groupings, aggs, pre, mapping)
                pred = child.predicate
                pre = pred if pre is None else BinOp("and", pred, pre)
                child = child.children[0]
                changed = True
                absorbed += 1
                continue
            break
        if not changed:
            return agg
        from ..runtime import dispatch

        dispatch.record_max("fused_stage_len", absorbed + 1)
        return AggExec(
            child, AggMode.PARTIAL, groupings, aggs,
            supports_partial_skipping=agg.supports_partial_skipping,
            pre_filter=pre,
        )

    def try_fuse_fp(node):
        """Project(Filter(x)) / Filter(Project(x)) -> one FilterExec
        with a fused projection (single kernel, compacts only the
        projected columns)."""
        if (
            isinstance(node, ProjectExec)
            and not node._host_parts
            and node._select_names is None
            and isinstance(node.children[0], FilterExec)
            and not node.children[0]._host_parts
            and node.children[0].project is None
        ):
            f = node.children[0]
            return FilterExec(f.children[0], f.predicate,
                              project=(list(node.exprs), list(node.names)))
        if (
            isinstance(node, FilterExec)
            and node.project is None
            and not node._host_parts
            and isinstance(node.children[0], ProjectExec)
            and not node.children[0]._host_parts
        ):
            proj = node.children[0]
            mapping = projection_mapping(proj.names, proj.exprs)
            return FilterExec(
                proj.children[0], substitute(node.predicate, mapping),
                project=(list(proj.exprs), list(proj.names)),
            )
        return node

    def walk(node):
        for i, c in enumerate(list(node.children)):
            walk(c)
            if isinstance(c, AggExec):
                node.children[i] = try_fuse(c)
            else:
                node.children[i] = try_fuse_fp(node.children[i])

    from .agg import AggExec

    walk(plan)
    if isinstance(plan, AggExec):
        plan = try_fuse(plan)
    else:
        plan = try_fuse_fp(plan)
    return _fuse_final_sort(plan)


# ------------------------------------------------- tier 2: exchanges

def _drop_noop_exchanges(plan):
    """Remove shuffle exchanges that provably move nothing: ONE output
    partition fed by ONE input partition is a pass-through (any
    partitioning function maps every row to partition 0).  In
    single-chip plans this deletes the partition-kernel + concat
    programs between the two agg stages and before the result sort —
    the adjacency tiers 3/4 then fuse across."""
    from ..parallel.exchange import NativeShuffleExchangeExec

    def rewrite(node):
        while (
            isinstance(node, NativeShuffleExchangeExec)
            and node.partitioning.num_partitions == 1
            and node.children[0].num_partitions() == 1
        ):
            node = node.children[0]
        return node

    def walk(node):
        for i, c in enumerate(list(node.children)):
            node.children[i] = rewrite(c)
            walk(node.children[i])

    plan = rewrite(plan)
    walk(plan)
    return plan


# ------------------------------------------- tier 3: final-agg sort

def _fuse_final_sort(plan):
    """Fold ``Limit?(Sort(FINAL agg))`` into the agg's finalize
    program (``post_sort``/``post_fetch``): the FINAL agg emits one
    blocking batch per partition, so sorting inside finalize is exact
    and saves the sort's own dispatch + host round trip."""
    from ..exprs.compile import device_only, infer_dtype
    from .agg import AggExec, AggMode
    from .limit import LimitExec
    from .pruning import expr_columns
    from .sort import SortExec

    def rewrite(node):
        limit = None
        sort = node
        if isinstance(node, LimitExec) and isinstance(node.children[0], SortExec):
            limit = node.limit
            sort = node.children[0]
        if not isinstance(sort, SortExec):
            return node
        agg = sort.children[0]
        if not (
            isinstance(agg, AggExec)
            and agg.mode == AggMode.FINAL
            and agg.post_sort is None
            and device_only([f.expr for f in sort.fields])
        ):
            return node
        out_names = set(agg.schema.names)
        for f in sort.fields:
            if not expr_columns(f.expr) <= out_names:
                return node
            if infer_dtype(f.expr, agg.schema).is_nested:
                return node  # no order words for nested keys
        fetch = sort.fetch
        if limit is not None:
            fetch = limit if fetch is None else min(fetch, limit)
        from ..runtime import dispatch

        dispatch.record_max("fused_stage_len", 2 if limit is None else 3)
        return AggExec(
            agg.children[0], agg.mode, agg.groupings, agg.aggs,
            supports_partial_skipping=agg.supports_partial_skipping,
            pre_filter=agg.pre_filter,
            post_sort=list(sort.fields), post_fetch=fetch,
        )

    def walk(node):
        for i, c in enumerate(list(node.children)):
            node.children[i] = rewrite(c)
            walk(node.children[i])

    plan = rewrite(plan)
    walk(plan)
    return plan


# -------------------------------------- tier 4: traceable chains

class BufferPartitionExec(ExecNode):
    """Buffer the child partition's batches and emit them as ONE
    concatenated batch — the blocking prelude a ``trace_requires_buffer``
    operator (WindowExec) needs before its traced transform can join a
    fused program.  Identical semantics to WindowExec's own
    buffer-then-concat execute, just factored below the fused kernel."""

    def __init__(self, child: ExecNode):
        super().__init__([child])

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def preserves_ordering(self) -> bool:
        return True  # concat of the ordered stream, in order

    def execute(self, partition: int, ctx) -> BatchStream:
        child_stream = self.children[0].execute(partition, ctx)

        def stream():
            from ..batch import concat_batches

            buffered = [b.to_host() for b in child_stream]
            if not buffered:
                return
            merged = concat_batches(buffered).to_device()
            self._record_batch(merged)
            yield merged

        return stream()


class FusedStageExec(ExecNode):
    """One jitted program per batch for a chain of traceable unary
    operators (``ExecNode.trace_fn`` contract), bottom-up.  All
    intermediates stay on device; the single count scalar syncs only
    when some fused operator compacts rows.

    Itself implements the trace contract (the composition of its ops'
    transforms), so tier 5 can absorb an already-collapsed chain into
    a fused shuffle-write program without re-walking the originals."""

    def __init__(self, child, ops: List):
        super().__init__([child])
        self.ops = list(ops)  # bottom -> top
        self._schema = self.ops[-1].schema
        self._changes_count = any(op.trace_changes_count for op in self.ops)
        fns = [op.trace_fn() for op in self.ops]
        assert all(fn is not None for fn in fns)
        self._fns = fns
        self._keys = tuple(op.trace_key() for op in self.ops)
        keys = self._keys
        # slots-as-cols-tail contract (ops/base.py trace_slots): the
        # fused program takes the CONCATENATION of every op's slot
        # values appended after the input columns and deals each op its
        # own group; the per-op counts are static (part of the chain's
        # structure), only the VALUES are traced, so parameter-shifted
        # chains reuse this one compiled program.
        self._slot_counts = tuple(len(op.trace_slots()) for op in self.ops)
        self._slot_args = tuple(
            v for op in self.ops for v in op.trace_slots())
        slot_counts = self._slot_counts
        n_slots = len(self._slot_args)

        def build():
            import jax

            @jax.jit
            def kernel(cols, num_rows):
                cols = tuple(cols)
                slots = cols[len(cols) - n_slots:] if n_slots else ()
                cols = cols[:len(cols) - n_slots] if n_slots else cols
                n = num_rows
                i = 0
                for fn, cnt in zip(fns, slot_counts):
                    cols, n = fn(tuple(cols) + slots[i:i + cnt], n)
                    i += cnt
                return cols, n

            return kernel

        from ..runtime.kernel_cache import cached_kernel

        self._kernel = cached_kernel(("fused_stage", keys), build)
        self.metrics.set("fused_stage_len", len(self.ops))
        #: OOM degradation (runtime/oom.py): halving a batch is only
        #: sound for per-row streaming transforms — a whole-partition
        #: op (trace_requires_buffer, e.g. window) must see its batch
        #: intact, so such chains skip rung 2 and go straight to eager
        self._downshift_ok = not any(
            getattr(op, "trace_requires_buffer", False) for op in self.ops)
        self._eager_kernels = None  # built lazily, only if rung 3 fires

    @property
    def schema(self):
        return self._schema

    # ------------------------------------------- tracing contract

    def trace_fn(self):
        fns = self._fns
        slot_counts = self._slot_counts
        n_slots = len(self._slot_args)

        def fn(cols, num_rows):
            cols = tuple(cols)
            slots = cols[len(cols) - n_slots:] if n_slots else ()
            cols = cols[:len(cols) - n_slots] if n_slots else cols
            n = num_rows
            i = 0
            for f, cnt in zip(fns, slot_counts):
                cols, n = f(tuple(cols) + slots[i:i + cnt], n)
                i += cnt
            return cols, n

        return fn

    def trace_key(self):
        return ("fused_stage", self._keys)

    def trace_slots(self) -> tuple:
        # the chain's flattened slot vector, in op order — an enclosing
        # consumer (the fused shuffle write) appends these exactly like
        # any single op's slots
        return self._slot_args

    @property
    def trace_changes_count(self) -> bool:
        return self._changes_count

    @property
    def preserves_ordering(self) -> bool:
        # every traceable op is a per-row/in-order transform; columns
        # may be renamed by fused projections, so the verifier
        # downgrades key matching past a fused chain
        return True

    def name(self) -> str:
        inner = "+".join(type(op).__name__ for op in self.ops)
        return f"FusedStageExec[{inner}]"

    def _eager_run(self, batch):
        """Rung 3 of the OOM ladder: the chain's per-operator programs,
        one dispatch each (the pre-fusion path) — every intermediate is
        materialized separately, so peak program memory drops to the
        single-op footprint.  Kernels are cached under the op's own
        trace key and built only the first time the rung fires."""
        if self._eager_kernels is None:
            from ..runtime.oom import build_eager_kernels

            self._eager_kernels = build_eager_kernels(
                [(op.trace_key(), fn)
                 for op, fn in zip(self.ops, self._fns)])
        cols, n = tuple(batch.columns), batch.num_rows
        for kernel, op in zip(self._eager_kernels, self.ops):
            cols, n = kernel(tuple(cols) + op.trace_slots(), n)
        return cols, n

    def _degradable_results(self, batch, depth: int):
        """Run one batch through the fused program, walking rungs 2-3
        of the OOM degradation ladder (rung 1 — force-spill + one
        retry — already ran inside the instrumented kernel,
        runtime/dispatch._oom_call).  Yields ``(cols, n)`` per
        surviving piece with the live count already RESOLVED: the
        one-scalar sync (when a fused op compacts) happens inside the
        try, so a RESOURCE_EXHAUSTED that async dispatch only surfaces
        at the first consumption point is still caught by the ladder —
        and inside the caller's ``elapsed_compute`` timer, so the
        device bill stays attributed.  A non-compacting chain's OOM
        can still surface further downstream (the next host transfer);
        that path fails the attempt and retries, the pre-ladder
        behavior."""
        from ..runtime import oom as _oom

        try:
            cols, n_dev = self._kernel(
                tuple(batch.columns) + self._slot_args, batch.num_rows)
            n = int(n_dev) if self._changes_count else batch.num_rows
        except Exception as exc:  # noqa: BLE001 — classified below
            if not _oom.is_resource_exhausted(exc):
                raise
            if (self._downshift_ok and depth < _oom.max_downshifts()
                    and batch.num_rows > 1):
                _oom.record_downshift("fused_stage", batch.num_rows,
                                      depth + 1)
                for piece in _oom.split_batch(batch):
                    yield from self._degradable_results(piece, depth + 1)
                return
            _oom.record_eager_fallback("fused_stage")
            try:
                cols, n_dev = self._eager_run(batch)
                n = int(n_dev) if self._changes_count else batch.num_rows
            except Exception as exc2:  # noqa: BLE001
                if _oom.is_resource_exhausted(exc2):
                    # ladder exhausted: genuine pressure, retryable
                    raise _oom.DeviceOomError(self.name(), exc2) from exc2
                raise
        yield cols, n

    def execute(self, partition: int, ctx) -> BatchStream:
        child_stream = self.children[0].execute(partition, ctx)

        def stream():
            from ..batch import bucket_capacity

            for batch in child_stream:
                with self.metrics.timer("elapsed_compute"):
                    pieces = list(self._degradable_results(batch, 0))
                for cols, n in pieces:
                    if n == 0:
                        continue
                    out = RecordBatch(self._schema, list(cols), n)
                    # expanding ops (generate cap*M, expand cap*P)
                    # leave a non-power-of-two capacity: renormalize so
                    # downstream kernels keep the shape-bucketing
                    # invariant (mirrors GenerateExec's unfused stream)
                    cap = out.capacity
                    if cap != bucket_capacity(cap):
                        out = out.with_capacity(bucket_capacity(n))
                    self._record_batch(out)
                    yield out

        return stream()


def optimize_plan(plan):
    """THE canonical task-plan optimizer composition:
    ``fuse_stages -> prune_columns -> fuse_traceable_chains ->
    fuse_shuffle_write`` (order matters: pruning rebuilds known
    operator types and treats FusedStageExec conservatively, so chain
    collapse must come after it, and the shuffle-write absorption eats
    the collapsed chain, so it must come last).  Every entry point —
    run_task, bench.py, ``--warmup``, the budget tests — MUST go
    through this helper: the persistent compile cache pre-warm is only
    worth anything if warmup compiles exactly the programs production
    tasks execute.

    With conf ``spark.blaze.verify.plan`` armed (forced on in tests
    and ``--chaos``), the OPTIMIZED plan runs through the structural
    plan verifier (analysis/plan_verify.py) before execution — this is
    THE choke point every execution path crosses, so a rewrite tier
    that breaks a schema/distribution/ordering/fusion invariant fails
    loudly here instead of producing wrong answers downstream."""
    from .pruning import prune_columns

    plan = fuse_shuffle_write(
        fuse_traceable_chains(prune_columns(fuse_stages(plan)))
    )
    if bool(conf.VERIFY_PLAN.get()):
        from ..analysis.plan_verify import verify_or_raise

        verify_or_raise(plan)
    # Level-1 plan-cache bookkeeping (runtime/querycache.py): every
    # execution path crosses this choke point, so the fingerprint tally
    # here is THE ground truth for compiled-program reuse — a hit means
    # this plan structure's programs (parameter shifts included, via
    # literal slots) are already in the kernel cache
    from ..runtime.querycache import record_plan

    fp = record_plan(plan)
    # Runtime-stats estimator (runtime/stats.py): stamp est_rows /
    # est_bytes onto the optimized plan (persisted actuals for this
    # fingerprint replace the cold estimates) and register the
    # instance for actuals collection at query-span flush.  Disarmed
    # cost is the one enabled() bool read.
    from ..runtime import stats as _stats

    if _stats.enabled():
        _stats.annotate(plan, fp)
    return plan


def traceable_chain_from(node):
    """THE chain-discovery rule every fusion consumer shares (tier 4's
    collapse and tier 5's shuffle-write absorption must agree on what a
    chain is): walk down through consecutive unary operators exposing
    ``trace_fn``, stopping after a ``trace_requires_buffer`` op (a
    whole-partition transform like window becomes the chain's BOTTOM,
    fed by a partition-buffering node; anything below it streams per
    batch and is collapsed separately by the recursive walks).
    Returns (ops top-down, the node below the chain, buffered?)."""
    ops_top_down = []
    cur = node
    buffered = False
    while len(cur.children) == 1 and cur.trace_fn() is not None:
        ops_top_down.append(cur)
        if cur.trace_requires_buffer:
            buffered = True
            cur = cur.children[0]
            break
        cur = cur.children[0]
    return ops_top_down, cur, buffered


def fuse_traceable_chains(plan):
    """Collapse maximal runs (length >= 2, with >= 2 real kernels) of
    consecutive traceable unary operators into FusedStageExec nodes.
    Run AFTER ``prune_columns`` — pruning rebuilds known operator
    types and treats FusedStageExec conservatively, so fusing first
    would block scan narrowing."""
    if not bool(conf.FUSION_ENABLE.get()):
        return plan

    chain_from = traceable_chain_from

    def rewrite(node):
        ops, bottom, buffered = chain_from(node)
        kernels = sum(1 for o in ops if o.has_kernel)
        if len(ops) >= 2 and kernels >= 2:
            from ..runtime import dispatch

            dispatch.record_max("fused_stage_len", len(ops))
            if buffered:
                bottom = BufferPartitionExec(bottom)
            return FusedStageExec(bottom, list(reversed(ops)))
        return node

    def walk(node):
        for i, c in enumerate(list(node.children)):
            node.children[i] = rewrite(c)
            walk(node.children[i])

    plan = rewrite(plan)
    walk(plan)
    return plan


# -------------------------------------- tier 5: fused shuffle write

def fuse_shuffle_write(plan):
    """Absorb the traceable chain feeding each hash/round-robin
    ``ShuffleWriterExec`` into the writer's per-batch program: chain
    transform + partition-id computation + pid sort + per-partition
    counts compile into ONE dispatch (see
    ``ShuffleWriterExec.absorb_traceable_chain``).  Applies after
    :func:`fuse_traceable_chains`, so the common shape is absorbing a
    single FusedStageExec (whose trace contract composes its ops)."""
    if not bool(conf.FUSION_ENABLE.get()):
        return plan
    from ..parallel.shuffle import ShuffleWriterExec

    def rewrite(node):
        if isinstance(node, ShuffleWriterExec):
            node.absorb_traceable_chain()
        return node

    def walk(node):
        for i, c in enumerate(list(node.children)):
            walk(rewrite(c))

    walk(rewrite(plan))
    return plan
