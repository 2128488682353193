"""Filter: predicate -> mask -> compact.

≙ reference FilterExec (filter_exec.rs:45).  Dynamic output size under
XLA's static shapes uses the two-phase pattern (SURVEY.md §7): the
kernel computes keep-mask, compacts survivors to the front of the same
capacity buffer, and returns the survivor count as a device scalar; the
host syncs only that one scalar to set ``num_rows``.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..batch import Column, RecordBatch
from ..exprs.compile import host_eval, infer_dtype, lower, split_host_exprs
from ..exprs.ir import Expr
from ..runtime import trace
from ..runtime.context import TaskContext
from ..schema import DataType, Field, Schema
from .base import BatchStream, ExecNode


def kept_indices(keep):
    """Ascending indices of the True rows of ``keep``, padded to its
    capacity with 0; returns (idx int32[cap], count int32).

    One single-operand int32 sort of ``where(keep, row, cap)``: the
    kept rows' indices are distinct, so no stability is needed and their
    order holds.  Not ``jnp.nonzero(size=cap)``: its bincount is a
    scatter-add of every row whatever ``keep`` holds — int64 under x64 —
    which the TPU runs nearly serially (as ``build_sorted_segs`` in
    ops/agg.py already avoids)."""
    cap = keep.shape[0]
    count = jnp.sum(keep, dtype=jnp.int32)
    pos = jax.lax.sort(jnp.where(keep, jax.lax.iota(jnp.int32, cap), jnp.int32(cap)))
    idx = jnp.where(pos < cap, pos, jnp.int32(0))
    return idx, count


def compact_columns(cols, keep):
    """Move rows where ``keep`` to the front; invalidate the rest.
    Returns (new_cols, count)."""
    cap = keep.shape[0]
    idx, count = kept_indices(keep)
    live = jax.lax.iota(jnp.int32, cap) < count
    out = []
    for c in cols:
        taken = c.take(idx)
        out.append(
            Column(
                c.dtype,
                taken.data,
                taken.validity & live,
                None if taken.lengths is None else jnp.where(live, taken.lengths, 0),
                taken.children,  # nested columns keep their gathered children
            )
        )
    return tuple(out), count


class FilterExec(ExecNode):
    """Filter, optionally FUSED with a following projection (stage
    fusion rewrites Project(Filter(x)) into one kernel: predicate mask,
    projection over the raw batch, one compact of only the projected
    columns — masked-out rows compute garbage that compaction drops)."""

    def __init__(self, child: ExecNode, predicate: Expr,
                 project: Optional[Tuple[List[Expr], List[str]]] = None):
        from ..exprs.compile import fold_literals, infer_dtype

        super().__init__([child])
        self.predicate = fold_literals(predicate)
        self.project = project
        in_schema = child.schema
        (self._device_pred,), self._host_parts = split_host_exprs([self.predicate])
        self._in_schema_aug = Schema(
            list(in_schema.fields)
            + [Field(name, DataType.bool_()) for name, _ in self._host_parts]
        )
        schema_aug = self._in_schema_aug
        pred = self._device_pred
        n_in_fields = len(in_schema.fields)
        n_fields = len(schema_aug.fields)
        if project is not None:
            proj_exprs, proj_names = project
            self._schema = Schema(
                [Field(n, infer_dtype(e, in_schema)) for e, n in zip(proj_exprs, proj_names)]
            )
        else:
            proj_exprs = None
            self._schema = in_schema

        # plan-fingerprint program reuse (runtime/querycache.py):
        # canonicalize literal leaves into Slot nodes so parameter-
        # shifted variants of this predicate share one kernel-cache key
        # and one compiled program; the values travel as traced scalars
        # appended to the cols tail (trace_slots contract, ops/base.py).
        # `self.predicate` keeps the ORIGINAL literals — plan rewrites,
        # pruning and scan pushdown read it, not the kernel form.
        from .. import conf
        from ..exprs.compile import slotify_literals

        if bool(conf.CACHE_PLAN_ENABLED.get()):
            slotified, self._slot_args = slotify_literals(
                [pred] + (proj_exprs if proj_exprs is not None else []))
            pred = slotified[0]
            if proj_exprs is not None:
                proj_exprs = slotified[1:]
        else:
            self._slot_args = ()

        def body(cols: Tuple[Column, ...], num_rows):
            slots = tuple(cols[n_fields:])
            cols = tuple(cols[:n_fields])
            n = cols[0].validity.shape[0]
            env = {f.name: c for f, c in zip(schema_aug.fields, cols)}
            if slots:
                env["__slots__"] = slots
            memo: dict = {}
            p = lower(pred, schema_aug, env, n, memo)
            # the live mask is load-bearing: IsNull turns padding-row
            # invalidity into data=True, so validity alone cannot be
            # trusted to exclude padding
            live = jnp.arange(n) < num_rows
            keep = p.validity & p.data.astype(jnp.bool_) & live
            if proj_exprs is not None:
                out = tuple(lower(e, schema_aug, env, n, memo) for e in proj_exprs)
            else:
                out = cols[:n_in_fields]
            return compact_columns(out, keep)

        self._body = body

        def build():
            return jax.jit(body)

        from ..exprs.compile import expr_key
        from ..runtime.kernel_cache import cached_kernel, schema_key

        self._key = (
            "filter", schema_key(schema_aug), expr_key(pred),
            None if proj_exprs is None else tuple(expr_key(e) for e in proj_exprs),
        )
        self._kernel = cached_kernel(self._key, build)

    # ---------------------------------------------- tracing contract

    def trace_fn(self):
        # host-fallback predicate subtrees evaluate per batch OUTSIDE
        # jit; such a filter cannot join a fused program
        return None if self._host_parts else self._body

    def trace_key(self):
        return None if self._host_parts else self._key

    def trace_slots(self) -> tuple:
        return self._slot_args

    @property
    def trace_changes_count(self) -> bool:
        return True

    @property
    def preserves_ordering(self) -> bool:
        return True  # compaction keeps relative row order

    @property
    def schema(self) -> Schema:
        return self._schema

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        child_stream = self.children[0].execute(partition, ctx)

        def stream():
            for batch in child_stream:
                with self.metrics.timer("elapsed_compute"):
                    cols = list(batch.columns)
                    for _, sub in self._host_parts:
                        cols.append(host_eval(sub, batch))
                    out_cols, count = self._kernel(
                        tuple(cols) + self._slot_args, batch.num_rows)
                    n = trace.read_scalar(count)  # one-scalar device->host sync
                if n == 0:
                    continue
                out = RecordBatch(self.schema, list(out_cols), n)
                self._record_batch(out)
                yield out

        return stream()
