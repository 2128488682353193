"""Standalone stage scheduler: split a multi-stage plan at its
exchanges and run it as TaskDefinition-per-task stages.

≙ the Spark-side plumbing the reference delegates to Spark itself:
stage splitting at ``ShuffleExchange`` boundaries (DAGScheduler), map
tasks running ``ShuffleWriterExec`` plans with per-task output files
(``BlazeShuffleWriterBase.nativeShuffleWrite:52-110`` — clone proto,
set ``.data``/``.index`` paths, execute, commit), and reduce tasks
whose plans read ``IpcReaderExec`` blocks registered in the resources
map (``BlazeBlockStoreShuffleReaderBase.readIpc:47``,
``NativeShuffleExchangeBase.doExecuteNative:100-156``).

Every task crosses the protobuf boundary: the scheduler serializes one
``TaskDefinition`` per task and drives them through
``serde.from_proto.run_task`` — the same bytes a multi-host deployment
would ship to gateway workers.
"""

from __future__ import annotations

import contextlib
import itertools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .. import conf
from ..ops import ExecNode
from ..parallel.exchange import NativeShuffleExchangeExec
from ..parallel.shuffle import IpcReaderExec, LocalShuffleManager, ShuffleWriterExec
from . import monitor, trace
from .context import (
    RESOURCES, QueryCancelledError, ScopedResources, TaskContext,
    current_cancel_scope,
)
from .metrics import MetricNode
from .speculation import SpeculationPolicy, StageTaskRunner

#: scheduler-level MetricNode of the most recent :func:`run_stages`
#: call (attempt/retry/fetch-failure counters) — read by the chaos CLI
#: and tests; pass ``metrics=`` to run_stages to own the node instead.
LAST_RUN_METRICS: Optional[MetricNode] = None

#: process-global broadcast-id allocator: broadcast resources live in
#: the process-wide RESOURCES map under ``broadcast_<bid>`` keys, so
#: ids minted per-plan (the pre-service behavior: every split started
#: at 0) would collide the moment two queries run concurrently through
#: the multi-tenant service — one query's reduce tasks would consume a
#: neighbor's blobs.  itertools.count is GIL-atomic.
_broadcast_ids = itertools.count()


def next_broadcast_id() -> int:
    """A process-unique broadcast id (split_stages + adaptive joins)."""
    return next(_broadcast_ids)


@dataclass
class Stage:
    """One stage = one plan template + task count.  Map stages write a
    shuffle; broadcast stages collect IPC blobs every downstream task
    re-reads replicated; the result stage yields batches to the
    caller."""

    stage_id: int
    kind: str                      # "map" | "broadcast" | "result"
    plan: ExecNode                 # stage-local plan (no exchanges)
    n_tasks: int
    shuffle_id: Optional[int] = None   # map stages
    n_out: int = 1                     # map stages: reduce partition count
    broadcast_id: Optional[int] = None  # broadcast stages
    depends_on: List[int] = field(default_factory=list)


class _StageRoot(ExecNode):
    """Mutable wrapper so the root exchange (if any) can be swapped."""

    def __init__(self, child: ExecNode):
        super().__init__([child])

    @property
    def schema(self):
        return self.children[0].schema


def split_stages(
    root: ExecNode, manager: Optional[LocalShuffleManager] = None
) -> Tuple[List[Stage], LocalShuffleManager]:
    """Replace every NativeShuffleExchangeExec with an IpcReaderExec and
    emit a map Stage for its child.  Returns stages in dependency order
    (result stage last)."""
    from ..parallel.broadcast import BroadcastExchangeExec, IpcWriterExec

    manager = manager or LocalShuffleManager()
    stages: List[Stage] = []
    wrapper = _StageRoot(root)

    def walk(node: ExecNode) -> List[int]:
        deps: List[int] = []
        for i, c in enumerate(list(node.children)):
            if isinstance(c, BroadcastExchangeExec):
                # broadcast = its own collect stage: child partitions
                # drain into IPC blobs (IpcWriterExec ≙ the reference's
                # collectNative, NativeBroadcastExchangeBase.scala:138),
                # and the consumer re-reads them replicated through an
                # IpcReaderExec the scheduler re-registers per task
                child_deps = walk(c.children[0])
                bid = next_broadcast_id()
                src = c.children[0]
                st = Stage(
                    stage_id=len(stages),
                    kind="broadcast",
                    plan=IpcWriterExec(src, f"broadcast_{bid}"),
                    n_tasks=src.num_partitions(),
                    broadcast_id=bid,
                    depends_on=child_deps,
                )
                stages.append(st)
                node.children[i] = IpcReaderExec(c.schema, f"broadcast_{bid}", 1)
                # build the join hash map ONCE per executor across this
                # stage's tasks (≙ the reference's per-executor cached
                # build, join_hash_map.rs:43): key by manager identity
                # so concurrent schedulers never share maps
                from ..ops.joins import BroadcastJoinExec

                if isinstance(node, BroadcastJoinExec) and node.cached_build_id is None:
                    node.cached_build_id = f"sched_bcast_{id(manager)}_{bid}"
                deps.append(st.stage_id)
            elif isinstance(c, NativeShuffleExchangeExec):
                child_deps = walk(c.children[0])
                sid = c.shuffle_id
                st = Stage(
                    stage_id=len(stages),
                    kind="map",
                    plan=c.children[0],
                    n_tasks=c.children[0].num_partitions(),
                    shuffle_id=sid,
                    n_out=c.partitioning.num_partitions,
                    depends_on=child_deps,
                )
                stages.append(st)
                node.children[i] = IpcReaderExec(
                    c.schema, f"shuffle_{sid}", c.partitioning.num_partitions
                )
                # keep the partitioning object reachable for the map
                # task builder
                st._partitioning = c.partitioning  # type: ignore[attr-defined]
                deps.append(st.stage_id)
            else:
                deps.extend(walk(c))
        return deps

    result_deps = walk(wrapper)
    stages.append(
        Stage(
            stage_id=len(stages),
            kind="result",
            plan=wrapper.children[0],
            n_tasks=wrapper.children[0].num_partitions(),
            depends_on=result_deps,
        )
    )
    return stages, manager


def build_task(
    stage: Stage, manager: LocalShuffleManager, t: int, attempt: int = 0
) -> Tuple[ExecNode, bytes]:
    """Per-task plan + TaskDefinition bytes.  Map-stage tasks wrap the
    plan in a ShuffleWriterExec with this task's output paths (≙ the
    per-task proto clone in BlazeShuffleWriterBase:66-75); serializing
    stages fresh one-shot resources, so every attempt builds anew."""
    from ..serde.to_proto import task_definition

    if stage.kind == "map":
        data, index = manager.map_output_paths(stage.shuffle_id, t)
        plan: ExecNode = ShuffleWriterExec(
            stage.plan, stage._partitioning, data, index  # type: ignore[attr-defined]
        )
    else:
        plan = stage.plan
    suffix = f"_a{attempt}" if attempt else ""
    td = task_definition(
        plan, f"task_{stage.stage_id}_{t}{suffix}", stage.stage_id, t
    )
    return plan, td


def stage_task_definitions(
    stage: Stage, manager: LocalShuffleManager
) -> List[bytes]:
    """One TaskDefinition per task (see :func:`build_task`)."""
    return [build_task(stage, manager, t)[1] for t in range(stage.n_tasks)]


def worker_task_spec(
    stage: Stage,
    manager: LocalShuffleManager,
    t: int,
    attempt: int = 0,
    n_maps: Optional[Dict[int, int]] = None,
    output: Optional[str] = None,
) -> Dict[str, object]:
    """The ``runtime/worker.py`` job spec for ONE task of a stage —
    the driver half of the multi-process path (testenv suites,
    :func:`worker.run_worker_with_retry`): TaskDefinition bytes, the
    shared shuffle root, this partition's reduce-block readers
    (``n_maps`` = committed map counts per upstream shuffle id), the
    result-frame output path for non-map stages, and — when a traced
    query span is open — the driver's W3C ``traceparent``, so every
    event the worker subprocess emits into its OWN log carries the
    driver's trace id and ``--report`` / the OTLP export reconcile the
    segments into one trace."""
    import base64

    _, td = build_task(stage, manager, t, attempt)
    readers = [
        {"resource_id": f"shuffle_{sid}", "shuffle_id": sid, "n_maps": nm}
        for sid, nm in sorted((n_maps or {}).items())
    ]
    spec: Dict[str, object] = {
        "task_def": base64.b64encode(td).decode(),
        "partition": t,
        "attempt": attempt,
        "shuffle_root": manager.root,
        "readers": readers,
        "output": output,
    }
    tp = trace.current_traceparent()
    if tp:
        spec["traceparent"] = tp
    return spec


def _compute_range_boundaries(stage: Stage, register_readers,
                              max_rows: int = 1 << 16, scope=None):
    """Driver-side boundary pass for a range-partitioned map stage
    (≙ Spark's RangePartitioner sample job): run the stage's plan once,
    extract sort-key ORDER WORDS, and pick the (n_out-1) lexicographic
    split points.  Any consistent split preserves global sort order, so
    stride-subsampling above ``max_rows`` only affects balance."""
    import numpy as np

    from ..parallel.exchange import _build_range_kernels

    part = stage._partitioning  # type: ignore[attr-defined]
    key_words, _, _ = _build_range_kernels(
        stage.plan.schema, part.fields, part.num_partitions
    )
    # bounded accumulation: sample per batch and re-stride the pool
    # whenever it doubles past the target, so driver memory stays
    # O(max_rows) regardless of input size (split points only affect
    # balance, never sort correctness).  Each task's stream is
    # abandoned once its per-task quota is met (Spark's
    # RangePartitioner likewise runs a CHEAP sample job, not the full
    # map stage): any consistent boundary set preserves order, so
    # sampling only stream prefixes costs balance, not correctness
    per_word: List[List] = []
    pool_rows = 0
    stride = 1
    task_quota = max(1024, max_rows // max(1, stage.n_tasks))
    for t in range(stage.n_tasks):
        register_readers(t)
        ctx = TaskContext(t, stage.n_tasks,
                          cancel_event=scope.event if scope else None)
        task_rows = 0
        for b in stage.plan.execute(t, ctx):
            if scope is not None:
                scope.check(stage.stage_id, t)
            words = key_words(tuple(b.columns), b.num_rows)
            for i, w in enumerate(words):
                if len(per_word) <= i:
                    per_word.append([])
                per_word[i].append(np.asarray(w)[: b.num_rows : stride])
            got = len(per_word[0][-1])
            pool_rows += got
            task_rows += got * stride
            if pool_rows > 2 * max_rows:
                per_word = [[np.concatenate(chunks)[::2]] for chunks in per_word]
                pool_rows = len(per_word[0][0])
                stride *= 2
            if task_rows >= task_quota:
                break
    if not per_word or not per_word[0]:
        # empty input: no batch will ever reach the pid kernel, any
        # consistent boundary set satisfies the contract
        return (np.zeros(part.num_partitions - 1, np.uint64),)
    cat = [np.concatenate(chunks) for chunks in per_word]
    n = cat[0].shape[0]
    if n == 0:
        # batches existed but every one was zero-row: same empty case
        return tuple(
            np.zeros(part.num_partitions - 1, np.uint64) for _ in cat
        )
    if n > max_rows:
        s = (n + max_rows - 1) // max_rows
        cat = [c[::s] for c in cat]
        n = cat[0].shape[0]
    order = np.lexsort(tuple(cat[::-1]))  # first word = primary key
    n_out = part.num_partitions
    positions = [min(n - 1, (i * n) // n_out) for i in range(1, n_out)]
    idx = order[positions]
    return tuple(c[idx] for c in cat)


def run_stages(
    stages: List[Stage],
    manager: LocalShuffleManager,
    max_task_attempts: Optional[int] = None,
    metrics: Optional[MetricNode] = None,
    pool=None,
):
    """Execute all stages in order over the serde boundary; yields the
    result stage's batches.  Before each stage that reads a shuffle,
    register its reduce blocks in the resources map (the
    shuffle-reader half: readIpc -> resourcesMap.put).

    Fault tolerance (≙ the Spark recovery tiers the reference inherits,
    SURVEY §1/§5), driven by :class:`runtime.retry.RetryPolicy` (conf
    ``spark.blaze.task.*`` knobs; ``max_task_attempts`` overrides the
    attempt budget for this call):

    - **Task retry.**  A failed attempt discards its staged resources,
      backs off deterministically, and re-runs from a fresh
      TaskDefinition decode with a new attempt id.  Shuffle outputs
      commit by atomic rename (ShuffleRepartitioner.write_output) and
      reduce blocks re-register per attempt, so retries are idempotent
      and a failed map attempt never counts toward the reduce barrier.
      Result stages always STREAM (no buffering); their retry window
      covers failures before the first output batch — after that the
      attempt is not replayable and the failure propagates.
    - **Fetch-failure recovery.**  A ``FetchFailedError`` from a
      shuffle read names its producing shuffle; the scheduler
      invalidates that shuffle's map outputs, re-runs just the
      producing map stage, and then re-runs the fetching task — without
      consuming its plain-retry budget (bounded by
      ``spark.blaze.stage.maxAttempts``).
    - **Terminal errors.**  Exhausted budgets raise
      :class:`TaskRetriesExhausted` naming the stage/task/attempts with
      the last cause chained; non-retryable failures (cancellation,
      assertion/engine bugs) propagate immediately.

    - **Partial map re-runs.**  When the fetch failure names the exact
      missing producers (``FetchFailedError.map_ids``, parsed from the
      block path), only THOSE map tasks regenerate —
      ``map_tasks_rerun`` counts them, strictly less than ``n_tasks``
      on a partial recovery.
    - **Speculation / wedge detection** (runtime/speculation.py, conf
      ``spark.blaze.speculation.*`` / ``spark.blaze.task.wedgeMs``):
      non-result stages run under a concurrent attempt runner that
      races a backup attempt against stragglers (first commit wins
      through the attempt-id seams; the loser is cancelled and rolled
      back) and retries heartbeat-wedged tasks the cooperative drain
      deadline can never see.

    - **Pooled placement / lost-worker recovery** (``pool``, a
      :class:`runtime.hostpool.HostPool`): eligible map tasks bind to
      persistent worker processes round-robin; a worker death
      (heartbeat silence, nonzero exit, SIGKILL) raises
      :class:`WorkerLostError` carrying the dead worker's committed
      map outputs, which regenerate through the SAME partial-rerun
      path before the interrupted task retries on a survivor — and
      with every worker dead or blacklisted the stage degrades to
      in-process execution instead of failing.

    Attempt/retry/fetch-failure counters accumulate on ``metrics``
    (default: a fresh node published as ``LAST_RUN_METRICS``):
    ``task_attempts``, ``task_retries``, ``task_timeouts``,
    ``fetch_failures``, ``map_stage_reruns``, ``map_tasks_rerun``,
    ``worker_lost``, ``speculative_attempts``, ``speculative_won``,
    ``speculative_lost``."""
    from ..serde import from_proto
    from ..serde.to_proto import STAGED_RIDS
    from .retry import (
        FETCH_FAILED, RETRY, RetryPolicy, TaskRetriesExhausted,
        TaskTimeoutError, classify,
    )

    policy = RetryPolicy.from_conf()
    if max_task_attempts is not None:
        policy = policy.with_max_attempts(max_task_attempts)
    metrics = metrics or MetricNode()
    global LAST_RUN_METRICS
    LAST_RUN_METRICS = metrics
    sched_m = metrics.metrics
    # query-level cancellation + deadline (context.CancelScope, opened
    # by monitor.query_span): every cooperative checkpoint below calls
    # scope.check, serial attempts share the scope event as their
    # cancel_event, and concurrent attempts attach their own events —
    # a cancel mid-stage reaches ALL live attempts
    scope = current_cancel_scope()
    # multi-tenant fair-share lease (runtime/service.py): under the
    # query service every stage executes inside a deficit-round-robin
    # turn on the one device lease, so concurrent queries interleave
    # stage-by-stage instead of racing the device; outside the service
    # this is one ContextVar read and every turn is a no-op
    from .service import current_lease

    lease = current_lease()

    n_maps: Dict[int, int] = {}
    bcast_blobs: Dict[int, List[bytes]] = {}
    map_stage_by_shuffle: Dict[int, Stage] = {
        s.shuffle_id: s for s in stages if s.kind == "map"
    }
    bcast_stage_by_id: Dict[int, Stage] = {
        s.broadcast_id: s for s in stages if s.kind == "broadcast"
    }

    def ipc_readers(plan: ExecNode, prefix: str) -> List[IpcReaderExec]:
        out: List[IpcReaderExec] = []
        seen: set = set()

        def walk(node: ExecNode):
            for c in node.children:
                walk(c)
            if (
                isinstance(node, IpcReaderExec)
                and node.resource_id.startswith(prefix)
                and id(node) not in seen
            ):
                seen.add(id(node))
                out.append(node)

        walk(plan)
        return out

    def make_registrar(stage: Stage):
        readers = ipc_readers(stage.plan, "shuffle_")
        breaders = ipc_readers(stage.plan, "broadcast_")

        def register_stage_readers(t: int, scope: Optional[str] = None):
            """Stage this task's reduce blocks / broadcast blobs.
            Returns ``(stored_keys, remap)``: with a ``scope`` the
            resources land under scope-suffixed keys and ``remap``
            translates the plan's key to them (via ScopedResources),
            so CONCURRENT attempts of one task never pop each other's
            one-shot registrations."""
            keys: List[str] = []
            remap: Dict[str, str] = {}

            def stage_key(key: str, value) -> None:
                stored = key + scope if scope else key
                RESOURCES.put(stored, value)
                keys.append(stored)
                if scope:
                    remap[key] = stored

            for node in readers:
                sid = int(node.resource_id.split("_")[1])
                stage_key(f"{node.resource_id}.{t}",
                          manager.reduce_blocks(sid, n_maps[sid], t))
            for node in breaders:
                bid = int(node.resource_id.split("_")[1])
                stage_key(f"{node.resource_id}.0", list(bcast_blobs[bid]))
            return keys, remap

        return register_stage_readers

    def build_attempt_td(stage: Stage, t: int, attempt: int):
        """Fresh TaskDefinition per attempt (serialization stages fresh
        one-shot resources); returns (td bytes, staged resource ids) so
        a failed attempt doesn't leak them."""
        staged: List[str] = []
        token = STAGED_RIDS.set(staged)
        try:
            _, td = build_task(stage, manager, t, attempt)
        finally:
            STAGED_RIDS.reset(token)
        return td, staged

    def drain(stage: Stage, t: int, it, out: List, progress) -> None:
        """Collect a task's output, enforcing the cooperative per-task
        timeout between batches; driver-observed batches feed the
        heartbeat-gated stage progress.  Every pulled batch is also a
        query-cancellation/deadline checkpoint."""
        deadline = policy.deadline()
        for b in it:
            if scope is not None:
                scope.check(stage.stage_id, t)
            out.append(b)
            progress.add_batch(b)
            if deadline is not None and time.monotonic() > deadline:
                raise TaskTimeoutError(
                    f"task {t} of stage {stage.stage_id} exceeded "
                    f"{policy.task_timeout}s"
                )

    def regenerate_map_stage(mstage: Stage,
                             map_ids: Optional[List[int]] = None) -> None:
        """Fetch-failure recovery: drop the shuffle's lost map outputs
        and re-run the producing map stage (≙ DAGScheduler resubmitting
        the parent stage on FetchFailed).  When the failure names the
        exact missing producers (``map_ids``), only THOSE map tasks
        re-run — a partial re-run that leaves the surviving outputs
        committed (``map_tasks_rerun`` counts the re-run tasks, so a
        partial recovery is visibly cheaper than ``n_tasks``)."""
        tasks = None
        if map_ids:
            tasks = sorted(m for m in set(map_ids)
                           if 0 <= m < mstage.n_tasks)
            if len(tasks) >= mstage.n_tasks or not tasks:
                tasks = None  # degenerate subset: full rerun
        sched_m.add("map_stage_reruns", 1)
        sched_m.add("map_tasks_rerun",
                    len(tasks) if tasks is not None else mstage.n_tasks)
        trace.emit("map_stage_rerun", stage_id=mstage.stage_id,
                   shuffle_id=mstage.shuffle_id, map_ids=tasks)
        manager.invalidate(mstage.shuffle_id, map_ids=tasks)
        run_stage_tasks(mstage, tasks=tasks)
        n_maps[mstage.shuffle_id] = mstage.n_tasks

    def regenerate_broadcast_stage(bstage: Stage) -> None:
        """Fetch-failure recovery for a CORRUPT broadcast blob: re-run
        the producing broadcast stage and re-collect its blobs.  The
        driver's cached copy is the corrupt artifact itself, so —
        unlike the pre-integrity fallback that re-registered the same
        bytes and burned the retry budget on identical failures — the
        producer must regenerate."""
        sched_m.add("map_stage_reruns", 1)
        sched_m.add("map_tasks_rerun", bstage.n_tasks)
        trace.emit("map_stage_rerun", stage_id=bstage.stage_id,
                   shuffle_id=-1, broadcast_id=bstage.broadcast_id,
                   map_ids=None)
        run_stage_tasks(bstage)
        bcast_blobs[bstage.broadcast_id] = [
            RESOURCES.get(f"broadcast_{bstage.broadcast_id}.{p}")
            for p in range(bstage.n_tasks)
        ]

    def handle_failure(stage: Stage, t: int, exc: BaseException,
                       attempt: int, regens: int, sleep: bool = True):
        """Classify a failed attempt and perform the recovery
        bookkeeping; returns the (attempt, regens) counters for the
        next try, or raises when the failure is terminal.  With
        ``sleep=False`` (the concurrent runner) the backoff is NOT
        slept here — the return grows to (attempt, regens, delay_s)
        and the caller schedules the relaunch, so one flaky task's
        backoff never stalls the whole stage's polling loop."""
        from .hostpool import WorkerLostError

        if isinstance(exc, WorkerLostError) and exc.lost_outputs:
            # a pooled worker died owning committed map outputs: they
            # must regenerate NOW through the partial-rerun path —
            # reduce_blocks silently SKIPS missing index files, so
            # deferring the invalidation to an eventual fetch would
            # silently drop the dead worker's rows from every
            # downstream reduce.  The interrupted task itself then
            # falls through to its registered RETRY disposition and
            # re-runs on a survivor (or in-process once the pool
            # degrades).
            trace.emit("worker_lost", worker=exc.worker,
                       reason=exc.reason, stage_id=stage.stage_id,
                       task=max(t, 0),
                       lost_maps=sum(len(m)
                                     for m in exc.lost_outputs.values()))
            sched_m.add("worker_lost", 1)
            for sid in sorted(exc.lost_outputs):
                mstage = map_stage_by_shuffle.get(sid)
                if mstage is None:
                    continue
                regens += 1
                if regens > policy.max_stage_regens:
                    raise TaskRetriesExhausted(
                        stage.stage_id, t, attempt + 1, exc
                    ) from exc
                regenerate_map_stage(mstage,
                                     map_ids=exc.lost_outputs[sid])
        elif isinstance(exc, WorkerLostError):
            trace.emit("worker_lost", worker=exc.worker,
                       reason=exc.reason, stage_id=stage.stage_id,
                       task=max(t, 0), lost_maps=0)
            sched_m.add("worker_lost", 1)
        action = classify(exc)
        if action == FETCH_FAILED:
            sched_m.add("fetch_failures", 1)
            trace.emit("fetch_failure", stage_id=stage.stage_id, task=t,
                       shuffle_id=exc.shuffle_id)
            sid = exc.shuffle_id
            mstage = map_stage_by_shuffle.get(sid) if sid is not None else None
            if mstage is not None:
                regens += 1
                if regens > policy.max_stage_regens:
                    raise TaskRetriesExhausted(
                        stage.stage_id, t, attempt + 1, exc
                    ) from exc
                regenerate_map_stage(mstage, map_ids=exc.map_ids)
                # doesn't consume the retry budget
                return (attempt, regens) if sleep else (attempt, regens, 0.0)
            bid = getattr(exc, "broadcast_id", None)
            bstage = bcast_stage_by_id.get(bid) if bid is not None else None
            if bstage is not None:
                # a corrupt broadcast blob: re-registering the driver's
                # cached copy would re-read the same bad bytes — the
                # producing broadcast stage regenerates instead (same
                # regen budget as map-stage recovery)
                regens += 1
                if regens > policy.max_stage_regens:
                    raise TaskRetriesExhausted(
                        stage.stage_id, t, attempt + 1, exc
                    ) from exc
                regenerate_broadcast_stage(bstage)
                return (attempt, regens) if sleep else (attempt, regens, 0.0)
            # producer unresolvable (an in-process broadcast read with
            # no owning stage): a plain re-run can still succeed, so
            # fall through to RETRY
            action = RETRY
        if action == RETRY:
            attempt += 1
            if attempt >= policy.max_attempts:
                raise TaskRetriesExhausted(
                    stage.stage_id, t, attempt, exc
                ) from exc
            sched_m.add("task_retries", 1)
            trace.emit("task_retry", stage_id=stage.stage_id, task=t,
                       attempt=attempt, reason=type(exc).__name__)
            if isinstance(exc, TaskTimeoutError):
                sched_m.add("task_timeouts", 1)
                trace.emit("task_timeout", stage_id=stage.stage_id, task=t,
                           attempt=attempt - 1)
            if sleep:
                policy.sleep_before_retry(stage.stage_id, t, attempt - 1)
                return attempt, regens
            return attempt, regens, policy.backoff(stage.stage_id, t,
                                                   attempt - 1)
        raise exc  # FATAL

    def attempt_once(stage: Stage, t: int, attempt: int, register,
                     progress, res_scope: Optional[str] = None,
                     cancel_event=None, on_beat=None) -> List:
        """ONE attempt of a non-result task, end to end: (re)register
        this attempt's reduce blocks (pops on read, so every attempt
        stages afresh; broadcast blobs re-register too), decode a fresh
        TaskDefinition, drive it, and on failure roll back everything
        the attempt touched (progress delta, registry heartbeat, staged
        resources) before re-raising — shared verbatim by the serial
        retry loop and the concurrent/speculative runner, which passes
        a ``res_scope`` so racing attempts read through attempt-scoped
        resource keys, plus the cancel event and wedge-clock beat."""
        if cancel_event is None and scope is not None:
            # serial attempts share the query CancelScope's event
            # directly, so a cancel reaches the in-flight plan drive
            # (the shuffle/RSS/broadcast writers' cooperative seams)
            cancel_event = scope.event
        block_keys, remap = register(t, res_scope)
        td, staged = build_attempt_td(stage, t, attempt)
        sched_m.add("task_attempts", 1)
        trace.emit("task_attempt_start", stage_id=stage.stage_id,
                   task=t, attempt=attempt)
        # progress is cumulative across the stage: a failed attempt's
        # partial batches must be rolled back or the retry re-counts
        # them — tracked as a per-attempt DELTA so concurrent sibling
        # attempts' progress survives the rollback
        delta = monitor.AttemptProgress(progress)
        resources = ScopedResources(RESOURCES, remap) if remap else None
        try:
            batches: List = []
            with trace.annotation("task", stage=stage.stage_id, partition=t,
                                  attempt=attempt):
                drain(stage, t,
                      from_proto.run_task(td, task_attempt_id=attempt,
                                          resources=resources,
                                          cancel_event=cancel_event,
                                          on_beat=on_beat),
                      batches, delta)
            if cancel_event is not None and cancel_event.is_set():
                # a cancelled LOSER exits cleanly without consuming
                # its one-shot registrations — drop them (pop-if-
                # present, so partially-consumed sets are fine) or a
                # long-lived speculating process accumulates dead
                # block/blob entries in the resources map forever
                for key in staged + block_keys:
                    RESOURCES.discard(key)
                if scope is not None and scope.cancelled:
                    # a QUERY cancel (not a speculation race): the
                    # attempt resolves as cancelled through the
                    # rollback path below, never as "ok"
                    scope.raise_cancelled(stage.stage_id, t)
            trace.emit("task_attempt_end", stage_id=stage.stage_id,
                       task=t, attempt=attempt, status="ok")
            return batches
        except BaseException as exc:
            delta.discard()
            # the failed attempt's registry heartbeat goes with it:
            # a fast retry may never beat again, and a stale
            # entry's rows would inflate task_rows forever (attempt-
            # keyed so a concurrent winner's beat is never erased)
            monitor.task_discard(stage.stage_id, t, attempt=attempt)
            trace.emit("task_attempt_end", stage_id=stage.stage_id,
                       task=t, attempt=attempt, status="failed",
                       error=f"{type(exc).__name__}: {exc}"[:300])
            for key in staged + block_keys:
                RESOURCES.discard(key)
            if stage.kind == "map":
                # rollback path reclaims the attempt's .inprogress
                # staging temps NOW (they were previously reclaimed
                # only at process exit — the cancellation leak); the
                # commit-by-rename contract means a committed winner's
                # final files are untouched
                manager.sweep_inprogress(stage.shuffle_id, t, attempt)
            raise

    def pool_eligible(stage: Stage) -> bool:
        """A stage the worker pool may host: map stages whose plans
        read only the SHARED shuffle root (no broadcast-blob readers —
        those live in the driver's resources map; driver-staged
        serialization resources are caught per-build below)."""
        return (pool is not None and stage.kind == "map"
                and not ipc_readers(stage.plan, "broadcast_"))

    def pooled_attempt_once(stage: Stage, t: int, attempt: int,
                            worker: str) -> bool:
        """ONE attempt of a map task on a POOLED worker.  Returns
        False when the TaskDefinition cannot ship — building it staged
        driver-process resources (e.g. a memory-scan plan), which a
        worker in ANOTHER process can never read — so the caller falls
        back to the local path.  On success the worker has committed
        the map output into the shared shuffle root through the same
        atomic-rename seam as a local attempt, and the pool records
        the worker's ownership for lost-worker recovery."""
        staged: List[str] = []
        token = STAGED_RIDS.set(staged)
        try:
            plan_sids = sorted(
                int(node.resource_id.split("_")[1])
                for node in ipc_readers(stage.plan, "shuffle_"))
            spec = worker_task_spec(
                stage, manager, t, attempt,
                n_maps={sid: n_maps[sid] for sid in plan_sids})
        finally:
            STAGED_RIDS.reset(token)
        if staged:
            for key in staged:
                RESOURCES.discard(key)
            return False
        sched_m.add("task_attempts", 1)
        trace.emit("task_attempt_start", stage_id=stage.stage_id,
                   task=t, attempt=attempt)
        try:
            pool.run_task(spec, worker)
        except BaseException as exc:
            trace.emit("task_attempt_end", stage_id=stage.stage_id,
                       task=t, attempt=attempt, status="failed",
                       error=f"{type(exc).__name__}: {exc}"[:300])
            # the dead/failed attempt's staging temps are reclaimed
            # NOW, exactly like the local rollback path
            manager.sweep_inprogress(stage.shuffle_id, t, attempt)
            raise
        pool.note_map_output(worker, stage.shuffle_id, t)
        trace.emit("task_attempt_end", stage_id=stage.stage_id,
                   task=t, attempt=attempt, status="ok")
        return True

    def run_task_attempts(stage: Stage, t: int, register, progress) -> List:
        """One non-result task under the retry policy (the serial
        path); returns its (side-effect-only, usually empty) batch
        list.  With a worker pool attached, eligible map tasks bind to
        a pooled worker first (placement-aware binding); a degraded
        pool (placement None) or an unshippable plan falls back to the
        in-process path — the query never fails for lack of
        workers."""
        attempt = 0
        regens = 0
        can_pool = pool_eligible(stage)
        while True:
            if scope is not None:
                scope.check(stage.stage_id, t)
            try:
                if can_pool:
                    worker = pool.placement(stage.stage_id, t)
                    if worker is not None:
                        if pooled_attempt_once(stage, t, attempt, worker):
                            return []
                        # unshippable plan: local from here on, same
                        # attempt id (nothing ran yet)
                        can_pool = False
                        continue
                return attempt_once(stage, t, attempt, register, progress)
            except BaseException as exc:
                attempt, regens = handle_failure(stage, t, exc, attempt, regens)

    def run_result_task(stage: Stage, t: int, register, progress):
        """Result task: stream batches straight through (buffering
        would pin the whole partition).  The retry window covers every
        failure BEFORE the first output batch — which is where fetch
        failures, decode errors, and (for blocking plans like aggs and
        sorts) compute failures surface; once a batch has been yielded
        to the caller the attempt is not replayable and the failure is
        terminal."""
        attempt = 0
        regens = 0
        while True:
            if scope is not None:
                scope.check(stage.stage_id, t)
            block_keys, _ = register(t)
            td, staged = build_attempt_td(stage, t, attempt)
            sched_m.add("task_attempts", 1)
            trace.emit("task_attempt_start", stage_id=stage.stage_id,
                       task=t, attempt=attempt)
            yielded = False
            try:
                deadline = policy.deadline()
                # the task annotation closes before every yield and
                # re-opens for the next pull
                ids = dict(stage=stage.stage_id, partition=t, attempt=attempt)
                done = object()
                with trace.annotation("task", **ids):
                    it = iter(from_proto.run_task(
                        td, task_attempt_id=attempt,
                        cancel_event=scope.event if scope else None))
                    b = next(it, done)
                while b is not done:
                    # the pulled batch is a cancellation checkpoint
                    # BEFORE it is surfaced to the caller
                    if scope is not None:
                        scope.check(stage.stage_id, t)
                    # deadline checked on the PULLED batch before it is
                    # surfaced, so a timed-out attempt stays replayable
                    if deadline is not None and time.monotonic() > deadline:
                        raise TaskTimeoutError(
                            f"task {t} of stage {stage.stage_id} exceeded "
                            f"{policy.task_timeout}s"
                        )
                    yielded = True
                    progress.add_batch(b)
                    yield b
                    with trace.annotation("task", **ids):
                        b = next(it, done)
                if scope is not None:
                    # a cancelled operator STOPS yielding instead of
                    # raising (the cooperative seams), so a cancel that
                    # lands during the final drain would otherwise end
                    # the loop quietly and return a silently TRUNCATED
                    # result as "ok" — the post-loop checkpoint turns
                    # it into the typed terminal error
                    scope.check(stage.stage_id, t)
                trace.emit("task_attempt_end", stage_id=stage.stage_id,
                           task=t, attempt=attempt, status="ok")
                return
            except BaseException as exc:
                trace.emit("task_attempt_end", stage_id=stage.stage_id,
                           task=t, attempt=attempt, status="failed",
                           error=f"{type(exc).__name__}: {exc}"[:300])
                for key in staged + block_keys:
                    RESOURCES.discard(key)
                if yielded:
                    raise  # mid-stream: output already delivered
                # pre-first-batch failure: replayable, so the failed
                # attempt's heartbeat entry must not outlive it
                monitor.task_discard(stage.stage_id, t, attempt=attempt)
                attempt, regens = handle_failure(stage, t, exc, attempt, regens)

    def run_stage_tasks(stage: Stage, progress=None,
                        tasks: Optional[List[int]] = None) -> None:
        """Run tasks of a non-result stage (also the fetch-recovery
        re-run path for map stages; ``tasks`` restricts a partial
        re-run to the missing map ids).  With speculation, wedge
        detection, or ``spark.blaze.stage.taskConcurrency`` > 1 armed,
        the tasks run under the concurrent attempt runner
        (runtime/speculation.py); otherwise strictly serially — the
        deterministic default the fault-injection hit ordering relies
        on."""
        own_progress = progress is None
        if own_progress:
            # fetch-recovery rerun: runs INSIDE the fetching stage's
            # scope, so the re-run map stage gets its own progress and
            # its heartbeats land under its own stage id
            progress = monitor.StageProgress(
                stage.stage_id, stage.kind, stage.n_tasks, attempts=sched_m)
        register = make_registrar(stage)
        from ..parallel.shuffle import RangePartitioning

        part = getattr(stage, "_partitioning", None)
        if (
            stage.kind == "map"
            and isinstance(part, RangePartitioning)
            and part.boundaries is None
        ):
            # the driver-side sampling pass reads the stage's upstream
            # shuffles too, so it gets the same retry/fetch-recovery
            # treatment as a task (t = -1 marks the boundary pass in
            # terminal errors)
            attempt = 0
            regens = 0
            while True:
                try:
                    part.boundaries = _compute_range_boundaries(
                        stage, register, scope=scope)
                    break
                except BaseException as exc:
                    attempt, regens = handle_failure(stage, -1, exc,
                                                     attempt, regens)
        task_list = list(tasks) if tasks is not None \
            else list(range(stage.n_tasks))
        pol = SpeculationPolicy.from_conf()
        if pol.runner_needed():
            runner = StageTaskRunner(
                stage.stage_id, stage.kind, task_list, pol,
                attempt_fn=lambda t, a, rscope, cancel, beat: attempt_once(
                    stage, t, a, register, progress,
                    res_scope=rscope, cancel_event=cancel, on_beat=beat),
                # sleep=False: the runner schedules the backoff itself
                # so its polling loop keeps resolving sibling tasks
                on_failure=lambda t, exc, a, r: handle_failure(
                    stage, t, exc, a, r, sleep=False),
                progress=progress, metrics=sched_m)
            runner.run()
        else:
            for t in task_list:
                run_task_attempts(stage, t, register, progress)
                progress.task_done()
        if own_progress:
            progress.flush(force=True)

    # AQE-style dynamic join selection (runtime/adaptive.py, opt-in):
    # adaptive broadcast ids come from the same process-global
    # allocator as split_stages, so concurrent service queries can
    # never mint colliding broadcast resource keys
    adaptive_on = bool(conf.ADAPTIVE_JOIN_ENABLE.get())
    if adaptive_on:
        from .adaptive import maybe_rewrite_stage

    from . import dispatch

    def publish_dispatch(stage: Stage, cap: Dict[str, int]) -> None:
        """Mirror the stage's XLA dispatch observability
        (xla_dispatches / xla_compiles / compile_ms / fused_stage_len,
        runtime.dispatch) into its MetricNode child AND the scheduler
        totals — the q01 collapse must be measurable in-repo, not only
        on the leased chip."""
        snode = metrics.child(stage.stage_id).metrics
        for k, v in cap.items():
            if k in dispatch.MAX_GAUGES:
                snode.set(k, max(snode.get(k), v))
                sched_m.set(k, max(sched_m.get(k), v))
            else:
                snode.add(k, v)
                sched_m.add(k, v)

    def stage_scope(stage: Stage):
        """Per-stage observability (monitor.stage_span): the dispatch
        capture every run gets, plus — when tracing is armed — a trace
        kernel capture (block-until-ready attribution) bracketed by
        stage_submit/stage_complete events carrying the
        device/dispatch/compile split and the dispatch counters, plus —
        when the live monitor is armed — the registry stage lifecycle.
        Yields a StageProgress that heartbeats driver-observed batches
        (stage_progress events + /queries live state)."""
        return monitor.stage_span(stage.stage_id, stage.kind, stage.n_tasks,
                                  shuffle_id=stage.shuffle_id,
                                  attempts=sched_m,
                                  # the MetricNode publishes dispatch
                                  # counters even with observability off
                                  capture_dispatch=True)

    try:
        for stage in stages:
            if scope is not None:
                # between-stage checkpoint: a cancel that landed while
                # no task was draining still stops the query here
                scope.check(stage.stage_id)
            if adaptive_on:
                maybe_rewrite_stage(stage, manager, n_maps, bcast_blobs,
                                    next_broadcast_id)
            if stage.kind == "result":
                register = make_registrar(stage)
                # the lease turn covers COMPUTE only: it is paused
                # around every yield to the consumer, so a slow
                # consumer backpressures its own producer while the
                # device lease serves other tenants — never held
                # across a wait the consumer controls
                turn = lease.acquire_turn() if lease is not None else None
                try:
                    with stage_scope(stage) as progress:
                        for t in range(stage.n_tasks):
                            for b in run_result_task(stage, t, register,
                                                     progress):
                                if turn is not None:
                                    lease.pause(turn)
                                yield b
                                if turn is not None:
                                    lease.resume(turn)
                            progress.task_done()
                finally:
                    if turn is not None:
                        lease.release(turn)
                publish_dispatch(stage, progress.counters)
                continue
            with (lease.stage_turn() if lease is not None
                  else contextlib.nullcontext()):
                with stage_scope(stage) as progress:
                    run_stage_tasks(stage, progress)
            publish_dispatch(stage, progress.counters)
            if stage.kind == "map":
                n_maps[stage.shuffle_id] = stage.n_tasks
            elif stage.kind == "broadcast":
                # collect the per-partition blobs the IpcWriterExec tasks
                # registered; downstream tasks get them re-registered each
                bcast_blobs[stage.broadcast_id] = [
                    RESOURCES.get(f"broadcast_{stage.broadcast_id}.{p}")
                    for p in range(stage.n_tasks)
                ]
    except QueryCancelledError:
        # query-level rollback: every live attempt has already been
        # cancelled/joined on the way out (the runner's terminal path,
        # the serial attempt's own rollback); what remains is the
        # on-disk debris no attempt-level handler owns — abandoned
        # attempts' .inprogress staging temps.  Committed shuffle
        # outputs are left for the manager's normal lifecycle (they
        # are shared, possibly by a concurrent re-run).
        manager.sweep_inprogress()
        raise
