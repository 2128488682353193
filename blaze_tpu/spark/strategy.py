"""Convert strategy: tagging, trial conversion, fallback boundaries.

≙ reference ``BlazeConvertStrategy.scala:46-250``:

- bottom-up **trial conversion** decides convertibility per subtree
  (``convertibleTag``, ``:62-80``);
- unconvertible nodes fall back for their whole subtree through the
  session's ``host_fallback`` (the ``ConvertToNative`` seam,
  ``BlazeConverters.scala:850``);
- **removeInefficientConverts** (``:182-243``): a cheap native op
  (Filter/Project) sandwiched between non-native parent and non-native
  child wastes two boundary crossings, so it is re-tagged NeverConvert
  to a fixpoint.
"""

from __future__ import annotations

import enum
import logging
from typing import Dict, Optional, Set

from ..ops import ExecNode, RenameColumnsExec
from .converters import (
    ConversionContext, UnsupportedSparkExec, convert_exec, output_attrs,
)
from .expr_converter import UnsupportedSparkExpr
from .plan_json import SparkNode

logger = logging.getLogger(__name__)


class ConvertTag(enum.Enum):
    """≙ convertStrategyTag values in BlazeConvertStrategy.scala:46."""

    DEFAULT = "default"
    ALWAYS = "always_convert"
    NEVER = "never_convert"


# ops cheap enough that converting them under a non-native neighbor
# costs more in boundary crossings than it saves
# (≙ BlazeConvertStrategy.isInefficientConvert)
_CHEAP_OPS = {"FilterExec", "ProjectExec", "LocalLimitExec", "GlobalLimitExec"}


class _StrategyContext(ConversionContext):
    """ConversionContext whose child dispatch consults strategy tags and
    absorbs unsupported subtrees into fallback boundaries."""

    def __init__(self, base: ConversionContext, forced_never: Set[int]):
        super().__init__(base.catalog, base.default_parallelism, base.host_fallback)
        self.forced_never = forced_never
        self.tags: Dict[int, ConvertTag] = {}
        # share the subquery memo across fixpoint iterations: each
        # rebuild (and each trial conversion that later falls back)
        # must not re-execute subquery plans
        self._subquery_memo = getattr(base, "_subquery_memo", {})
        base._subquery_memo = self._subquery_memo

    def convert(self, node: SparkNode) -> ExecNode:
        if id(node) in self.forced_never:
            self.tags[id(node)] = ConvertTag.NEVER
            return self._fallback(node)
        try:
            out = convert_exec(node, self)
            self.tags[id(node)] = ConvertTag.ALWAYS
            return out
        except (UnsupportedSparkExec, UnsupportedSparkExpr) as e:
            self.tags[id(node)] = ConvertTag.NEVER
            logger.info("falling back for %s: %s", node.name, e)
            return self._fallback(node)

    def _resolve_subquery(self, sub_plan: SparkNode, dtype):
        """Eagerly run a scalar subquery's plan and inject the value as
        a typed literal (≙ SparkScalarSubqueryWrapperExpr: the JVM
        evaluates, the engine sees a literal).  Memoized per subquery
        node across fixpoint rebuilds."""
        hit = self._subquery_memo.get(id(sub_plan))
        # the entry pins the node object, so an id() can never be
        # recycled while its memo entry lives; the identity check
        # guards the cross-query case regardless
        if hit is not None and hit[0] is sub_plan:
            return hit[1]
        from ..batch import batch_to_pydict
        from ..exprs.ir import Lit
        from ..runtime.context import TaskContext

        plan = _StrategyContext(self, set()).convert(sub_plan)
        value = None
        for p in range(plan.num_partitions()):
            for b in plan.execute(p, TaskContext(p, plan.num_partitions())):
                d = batch_to_pydict(b)
                col = next(iter(d.values()))
                if col:
                    value = col[0]
                    break
            if value is not None:
                break
        t = dtype or plan.schema.fields[0].dtype
        out = Lit(value, t)
        if t.is_decimal and value is not None:
            # batch_to_pydict returns decimals UNSCALED; Lit is logical
            # (same contract as tpch.queries.scalar_subquery_row) — a
            # raw int here would inflate the literal by 10^scale
            from ..exprs.compile import RawUnscaled

            out = Lit(RawUnscaled(value), t)
        self._subquery_memo[id(sub_plan)] = (sub_plan, out)
        return out

    def _fallback(self, node: SparkNode) -> ExecNode:
        if self.host_fallback is None:
            raise UnsupportedSparkExec(
                f"{node.name} is unconvertible and no host_fallback is "
                f"registered (≙ running without the JVM side)"
            )
        return self.host_fallback(node)


def apply_strategy(
    root: SparkNode, ctx: ConversionContext
) -> Dict[int, ConvertTag]:
    """Tag-only pass (diagnostics / tests): run a trial conversion and
    return the per-node tags, without keeping the converted plan."""
    from .expr_converter import SUBQUERY_RESOLVER

    sctx = _StrategyContext(ctx, set())
    token = SUBQUERY_RESOLVER.set(sctx._resolve_subquery)
    try:
        sctx.convert(root)
    except UnsupportedSparkExec:
        pass
    finally:
        SUBQUERY_RESOLVER.reset(token)
    return sctx.tags


def convert_spark_plan(
    root: SparkNode, ctx: ConversionContext, rename_root: bool = True
) -> ExecNode:
    """Full conversion: trial-convert with fallback boundaries, then
    remove inefficient converts to a fixpoint and rebuild.  The
    subquery resolver installs ONCE around the whole conversion (not
    per node) and memoizes per subquery plan."""
    from .expr_converter import SUBQUERY_RESOLVER

    from .plan_json import CatalystParseError

    forced: Set[int] = set()
    for _ in range(16):  # fixpoint ≙ removeInefficientConverts loop
        sctx = _StrategyContext(ctx, forced)
        token = SUBQUERY_RESOLVER.set(sctx._resolve_subquery)
        try:
            plan = sctx.convert(root)
        except (KeyError, TypeError, AttributeError, IndexError) as e:
            # a converter tripping over a gutted/degraded dump field is
            # a PARSE failure of the ingested JSON, not an engine
            # crash: surface it typed so callers at the Spark seam can
            # reject the dump (the fuzz suite pins this contract)
            raise CatalystParseError(
                f"catalyst dump rejected during conversion: "
                f"{type(e).__name__}: {e}") from e
        finally:
            SUBQUERY_RESOLVER.reset(token)
        added = _inefficient_converts(root, sctx.tags, forced)
        if not added:
            break
        forced |= added
    if rename_root:
        attrs = output_attrs(root)
        if attrs and len(attrs) == len(plan.schema.fields):
            internal = [a for a, _ in attrs]
            if internal == plan.schema.names:
                plan = RenameColumnsExec(plan, [u for _, u in attrs])
    return plan


def _inefficient_converts(
    root: SparkNode, tags: Dict[int, ConvertTag], already: Set[int]
) -> Set[int]:
    """Find cheap native ops sandwiched by non-native parent AND child:
    converting them buys nothing but two extra boundary crossings."""
    out: Set[int] = set()

    def walk(node: SparkNode, parent_tag: Optional[ConvertTag]):
        tag = tags.get(id(node), ConvertTag.NEVER)
        if (
            tag == ConvertTag.ALWAYS
            and id(node) not in already
            and node.name in _CHEAP_OPS
            and parent_tag == ConvertTag.NEVER
            and node.children
            and all(
                tags.get(id(c), ConvertTag.NEVER) == ConvertTag.NEVER
                for c in node.children
            )
        ):
            out.add(id(node))
        for c in node.children:
            walk(c, tag)

    walk(root, None)
    return out
