"""Session entry point for Spark plan interception.

≙ reference ``BlazeSparkSessionExtension`` + ``NativeRDD`` +
``NativeHelper.executeNativePlan``
(``BlazeSparkSessionExtension.scala:29-95``, ``NativeRDD.scala:27-52``,
``NativeHelper.scala:77-90``): the user-facing seam that accepts a
Spark physical plan (catalyst ``toJSON`` dump), converts it through the
strategy + converters, and executes it on the TPU engine — either
in-process, or by emitting per-partition ``TaskDefinition`` protobuf
bytes for the gateway (the NativeRDD contract: one TaskDefinition per
partition per stage).
"""

from __future__ import annotations

import itertools
import logging
from typing import Any, Callable, Dict, List, Optional, Union

from ..batch import batch_from_pydict, batch_to_pydict
from ..ops import ExecNode, MemoryScanExec
from ..schema import Schema
from .converters import ConversionContext
from .plan_json import SparkNode, parse_plan_json
from .strategy import convert_spark_plan

_log = logging.getLogger("blaze_tpu.spark")

#: process-wide sequence for generated query ids (span/registry labels)
_QUERY_SEQ = itertools.count(1)


class BlazeSparkSession:
    """Catalog + conversion + execution front door.

    Usage::

        sess = BlazeSparkSession()
        sess.register_table("lineitem", pydict, schema, partitions=4)
        rows = sess.execute(spark_plan_json)   # dict of columns
    """

    def __init__(
        self,
        default_parallelism: int = 4,
        host_fallback: Optional[Callable[[SparkNode], ExecNode]] = None,
    ):
        self.catalog: Dict[str, ExecNode] = {}
        self.default_parallelism = default_parallelism
        self.host_fallback = host_fallback

    # ----------------------------------------------------------- catalog

    def register_table(
        self,
        name: str,
        data: Union[ExecNode, Dict[str, List[Any]]],
        schema: Optional[Schema] = None,
        partitions: int = 1,
    ) -> None:
        """Register a table as an ExecNode (any scan) or as staged
        in-memory columns (the FFIReader/ConvertToNative analogue)."""
        if isinstance(data, ExecNode):
            self.catalog[name] = data
            return
        assert schema is not None, "schema required for pydict tables"
        n = len(next(iter(data.values()))) if data else 0
        per = max(1, (n + partitions - 1) // partitions)
        parts = []
        for p in range(partitions):
            sl = {k: v[p * per : (p + 1) * per] for k, v in data.items()}
            parts.append([batch_from_pydict(sl, schema)])
        self.catalog[name] = MemoryScanExec(parts, schema)

    # -------------------------------------------------------- conversion

    def plan(self, plan_json: Union[str, list, SparkNode]) -> ExecNode:
        """Spark physical plan (toJSON) -> executable ExecNode tree.
        Parse, strategy and conversion lie under the ``plan_convert``
        span: what a query pays for arriving as a catalyst dump."""
        from ..runtime import trace

        with trace.span("plan_convert"):
            node = (
                plan_json
                if isinstance(plan_json, SparkNode)
                else parse_plan_json(plan_json)
            )
            ctx = ConversionContext(
                catalog=self.catalog,
                default_parallelism=self.default_parallelism,
                host_fallback=self.host_fallback,
            )
            converted = convert_spark_plan(node, ctx)
        if _log.isEnabledFor(logging.DEBUG):
            # ≙ the reference's plan dump at conversion
            # (BlazeSparkSessionExtension.scala:52-61,80-88)
            _log.debug("converted plan:\n%s", converted.tree_string())
        return converted

    # --------------------------------------------------------- execution

    def execute(
        self,
        plan_json: Union[str, list, SparkNode],
        query_id: Optional[str] = None,
        traceparent: Optional[str] = None,
    ) -> Dict[str, List[Any]]:
        """Convert and run to completion, collecting all partitions
        (driver-side collect; ≙ executeNativePlan + row iterator).

        The non-scheduler path opens the SAME query -> stage -> kernel
        spans the scheduler path produces (one ``result`` stage over
        all partitions): with tracing armed the run leaves an event log
        ``--report`` renders identically to a scheduler run, and with
        the live monitor armed it is observable mid-flight via
        ``/queries`` — both structural no-ops when disarmed.

        ``traceparent`` (a W3C header value) continues the caller's
        distributed trace — the embedding JVM gateway forwards the
        Spark job's trace context through here."""
        from ..runtime import monitor, trace

        plan = self.plan(plan_json)
        query_id = query_id or f"session_execute_{next(_QUERY_SEQ)}"
        out: Dict[str, List[Any]] = {f.name: [] for f in plan.schema.fields}

        def collect(b) -> None:
            d = batch_to_pydict(b)
            for k in out:
                out[k].extend(d[k])

        ctx = trace.parse_traceparent(traceparent) if traceparent else None
        with monitor.query_span(query_id, mode="in-process",
                                trace_id=ctx[0] if ctx else None,
                                parent_span=ctx[1] if ctx else None):
            monitor.drive_result_stage(plan, collect)
        return out

    def cancel(self, query_id: str) -> bool:
        """Cancel a live query by the id :meth:`execute` /
        :meth:`execute_distributed` was given (or generated) — ≙ the
        Spark UI kill link / ``SparkContext.cancelJobGroup``.  The
        cancelled call raises :class:`runtime.context.
        QueryCancelledError` to ITS caller; this returns whether a
        live query accepted the request."""
        from ..runtime.context import cancel_query

        return cancel_query(query_id)

    def task_definitions(
        self, plan_json: Union[str, list, SparkNode]
    ) -> List[List[bytes]]:
        """Serialized TaskDefinitions, one list per stage in dependency
        order — what a real deployment ships to gateway workers
        (≙ NativeRDD.compute building TaskDefinition bytes per
        partition, BlazeCallNativeWrapper.scala:142-156; stage
        splitting at exchanges ≙ Spark's DAGScheduler)."""
        from ..runtime.scheduler import split_stages, stage_task_definitions

        plan = self.plan(plan_json)
        stages, manager = split_stages(plan)
        return [stage_task_definitions(s, manager) for s in stages]

    def execute_distributed(
        self,
        plan_json: Union[str, list, SparkNode],
        query_id: Optional[str] = None,
        traceparent: Optional[str] = None,
    ) -> Dict[str, List[Any]]:
        """Run through the stage scheduler: every task crosses the
        TaskDefinition protobuf boundary and every exchange goes
        through shuffle files — the full multi-process data path,
        driven in one process (≙ dev/testenv pseudo-distributed).
        Wrapped in the same query span as :meth:`execute`; per-stage
        spans come from the scheduler itself."""
        from ..runtime import monitor, trace
        from ..runtime.scheduler import run_stages, split_stages

        plan = self.plan(plan_json)
        query_id = query_id or f"session_distributed_{next(_QUERY_SEQ)}"
        stages, manager = split_stages(plan)
        schema = stages[-1].plan.schema
        out: Dict[str, List[Any]] = {f.name: [] for f in schema.fields}
        ctx = trace.parse_traceparent(traceparent) if traceparent else None
        with monitor.query_span(query_id, mode="scheduler",
                                trace_id=ctx[0] if ctx else None,
                                parent_span=ctx[1] if ctx else None):
            for b in run_stages(stages, manager):
                d = batch_to_pydict(b)
                for k in out:
                    out[k].extend(d[k])
        return out
