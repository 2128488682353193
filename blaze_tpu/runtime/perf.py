"""Performance introspection: EXPLAIN ANALYZE, roofline/MFU
attribution, and the perf-baseline regression gate.

The reference blaze plumbs per-operator native metrics back to the
Spark UI so an operator can see *where* a query spends its time; PR 3
and PR 12 recorded the raw material here (per-kernel
``device_ns``/``dispatch_ns``/``compile_ns`` splits, per-node
MetricsSet trees in ``task_plan`` events) but nothing turned it into a
judgment.  This module is that judgment layer, three surfaces over the
same data:

1. **EXPLAIN ANALYZE** (:func:`explain_doc` / :func:`render_explain`,
   CLI ``python -m blaze_tpu tpch q1 --explain``, monitor
   ``/queries/<id>/explain``): the optimized plan tree annotated per
   node with rows/bytes/batches, fused-chain membership, own-time and
   % of query wall — the metric-annotated plan the Spark UI would
   show, derived purely from the ``task_plan`` + kernel-sink events an
   armed trace already records.

2. **Roofline / MFU attribution** (:func:`classify` /
   :func:`query_perf`): per-kernel bytes-moved and flops estimates
   (recorded at the ``dispatch.instrument`` choke point while a kernel
   capture is active) divided by the per-device-kind peak table
   (``device_peaks.json``) yield ``hbm_util`` / ``mfu_est`` and a
   bound classification — dispatch-bound (the q06 "5.43x at ~2% of
   HBM" pathology, VERDICT r5), memory-bound, or compute-bound.
   Utilization is computed over the ATTRIBUTED wall
   (device + dispatch), so a chip idling between programs reads as
   low utilization + dispatch-bound rather than flattering itself
   with a device-seconds-only denominator.

3. **Perf-baseline gate** (:func:`run_perfcheck`, CLI ``--perfcheck``,
   tier-1 via tests/test_perf.py): a golden registry
   (``perf_baselines.json``) pins warm dispatches, programs, zero
   warm recompiles, and the bound class per TPC-H-slice query;
   ``--perfcheck`` exits nonzero on drift outside
   ``spark.blaze.perf.tolerance`` and ``--perfcheck --update`` re-pins
   with provenance — the dispatch-budget protection generalized from
   q01 to the whole slice.

Estimator cost contract (the ``trace.enabled()`` pattern): bytes/flops
estimation runs ONLY while a trace kernel capture is active (the scope
that already pays block-until-ready timing), gated on the module bool
``_ARMED`` that ``dispatch.instrument`` reads directly — disarmed
(``spark.blaze.perf.estimates=false``) the traced path pays one bool
read and the estimator is never entered (poisoned-estimator gate in
``--chaos`` and tests/test_perf.py), and the untraced hot path never
sees any of it.

Estimates are deliberately coarse and documented as such: bytes-moved
is the sum of input+output array bytes of each program (each operand
read once, each result written once — no cache modeling), flops is one
op per element touched (an elementwise lower bound; the engine's
kernels are filter/project/segment-reduce shaped, not matmuls).  They
exist to place kernels on the right DECADE of the roofline — 2% vs
80% of HBM — which is the judgment ROADMAP items 3-4 need, not a
cycle-accurate model.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

from .. import conf
from .errors import reraise_control

PEAKS_PATH = os.path.join(os.path.dirname(__file__), "device_peaks.json")
BASELINES_PATH = os.path.join(
    os.path.dirname(__file__), "perf_baselines.json")

#: plan-node timer metrics that are DISJOINT phases of a node's own
#: work (each wraps its own with-block; none nests another from this
#: list) — their sum is the node's attributable own-time, and the sum
#: over all nodes is the explain tree's "attributed" share of the
#: query wall
NODE_TIMERS = (
    "elapsed_compute", "input_io_time", "output_io_time", "sort_time",
    "probe_time", "build_time", "build_hash_map_time", "exchange_time",
    "shuffle_read_total_time", "shuffle_host_stage_time",
)

#: the bound classes :func:`classify` may return (API for dashboards,
#: the bench line, and the baseline registry)
BOUND_CLASSES = ("dispatch-bound", "memory-bound", "compute-bound",
                 "unknown")

# ------------------------------------------------------- the estimator

#: read DIRECTLY by dispatch.instrument's traced branch — one module
#: bool read when disarmed, the spark.blaze.trace.enabled cost contract
_ARMED = True
_loaded = False


def _load() -> None:
    global _ARMED, _loaded
    _ARMED = bool(conf.PERF_ESTIMATES.get())
    _loaded = True


def enabled() -> bool:
    """Estimator arming (conf ``spark.blaze.perf.estimates``).  Lazily
    loads conf once; call :func:`reset` after flipping it."""
    if not _loaded:
        _load()
    return _ARMED


def reset() -> None:
    """(Re)load arming from conf — call after changing
    ``spark.blaze.perf.*`` keys."""
    _load()


def force(armed: bool) -> None:
    """Directly arm/disarm the estimator for a measurement scope,
    overriding conf AND the ``BLAZE_PERF_ESTIMATES`` env (which wins
    over ``conf.set`` by ConfEntry design): the surfaces whose whole
    point is JUDGING the estimates (``--perfcheck``, ``--explain``)
    force it on around their runs.  :func:`reset` returns control to
    conf/env."""
    global _ARMED, _loaded
    _ARMED = bool(armed)
    _loaded = True


def _walk_leaves(x, out: List[Any]) -> None:
    """Plain-container fallback walk (dict/tuple/list) for when jax is
    unimportable — the engine's Column batches are registered pytrees,
    so the jax path is the one that sees their buffers."""
    if isinstance(x, dict):
        for v in x.values():
            _walk_leaves(v, out)
    elif isinstance(x, (tuple, list)):
        for v in x:
            _walk_leaves(v, out)
    else:
        out.append(x)


def _estimate(args: tuple, kwargs: dict, out: Any) -> Tuple[int, int]:
    """``(bytes_moved, flops)`` estimate for one program launch from
    its host-visible operands and results: every array operand read
    once + every result written once; one flop per element touched.
    Operands are flattened with ``jax.tree_util`` so registered
    pytrees (``batch.Column`` — data/validity/lengths buffers) count
    their real arrays, not an opaque container.  This is the function
    the poisoned-estimator gate replaces — it must only ever be
    entered through the ``_ARMED`` bool in ``dispatch.instrument``."""
    try:
        from jax import tree_util

        leaves = tree_util.tree_leaves((args, kwargs, out))
    except Exception as e:  # noqa: BLE001 — estimation must never kill
        # a run (but a control-flow error is not the estimator's to eat)
        reraise_control(e)
        leaves = []
        _walk_leaves(args, leaves)
        _walk_leaves(kwargs, leaves)
        _walk_leaves(out, leaves)
    nbytes = 0
    elems = 0
    for leaf in leaves:
        nb = getattr(leaf, "nbytes", None)
        if nb is None:
            continue
        nbytes += int(nb)
        elems += int(getattr(leaf, "size", 0))
    return nbytes, elems


# ------------------------------------------------------ the peak table

_peaks_cache: Dict[str, Dict[str, Any]] = {}


def peaks_path() -> str:
    return str(conf.PERF_PEAKS.get() or "") or PEAKS_PATH


def load_peaks(path: Optional[str] = None) -> Dict[str, Any]:
    """The per-device-kind peak table (``device_peaks.json`` or the
    ``spark.blaze.perf.peaks`` override)."""
    path = path or peaks_path()
    cached = _peaks_cache.get(path)
    if cached is not None:
        return cached
    with open(path) as f:
        doc = json.load(f)
    _peaks_cache[path] = doc
    return doc


def peaks_for(device_kind: str,
              table: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Peak numbers for a device kind (``jax.devices()[0].device_kind``
    or an event log's ``device_kind`` stamp): exact, case-insensitive
    match over the table's keys.  A kind that is not in the table
    raises ``KeyError`` — there is no default row, so a chip the table
    does not know is never judged against an invented roof.  The
    returned dict carries the matched key as ``device`` so consumers
    can stamp which roof they judged against."""
    table = table or load_peaks()
    kind = (device_kind or "").strip().lower()
    for key, row in table.get("devices", {}).items():
        if key.lower() == kind:
            return dict(row, device=key)
    raise KeyError(
        f"device kind {device_kind!r} is not in the peak table "
        f"({peaks_path()}): add its row with the source of its peaks")


_device_kind_cache: List[str] = []


def current_device_kind() -> str:
    """``jax.devices()[0].device_kind`` cached — what this process's
    programs actually ran on (the query span stamps it into the event
    log, and the peak table is keyed on it)."""
    if not _device_kind_cache:
        import jax

        _device_kind_cache.append(jax.devices()[0].device_kind)
    return _device_kind_cache[0]


# ------------------------------------------------------- classification

def classify(device_ns: int, dispatch_ns: int, bytes_est: int,
             flops_est: int, peaks: Dict[str, Any]) -> Dict[str, Any]:
    """Roofline judgment for one kernel or one whole query.

    Utilization denominators are the ATTRIBUTED wall (device +
    dispatch): a query whose chip idles between programs must read as
    2% HBM utilization, not as the flattering device-seconds-only
    number (compile time is excluded — warm steady state is the thing
    being judged, and a cold compile would mask it).

    Bound classes:

    - ``dispatch-bound`` — launch overhead exceeds device time: the
      per-program floor, not the hardware, is the limit (fuse more);
    - ``memory-bound`` / ``compute-bound`` — device time dominates;
      the operational intensity (flops/byte) against the device's
      ridge point says which wall the kernel is climbing;
    - ``unknown`` — nothing attributed (no timed program)."""
    busy_ns = int(device_ns) + int(dispatch_ns)
    bw_peak = float(peaks.get("hbm_gbps", 50.0)) * 1e9
    flops_peak = float(peaks.get("tflops", 0.5)) * 1e12
    out: Dict[str, Any] = {
        "hbm_bytes_est": int(bytes_est),
        "flops_est": int(flops_est),
    }
    if busy_ns <= 0:
        out.update(hbm_util=0.0, mfu_est=0.0, intensity=0.0,
                   bound="unknown")
        return out
    busy_s = busy_ns / 1e9
    out["hbm_util"] = round(bytes_est / busy_s / bw_peak, 6)
    out["mfu_est"] = round(flops_est / busy_s / flops_peak, 8)
    out["intensity"] = round(flops_est / bytes_est, 4) if bytes_est else 0.0
    ridge = flops_peak / bw_peak
    if dispatch_ns > device_ns:
        out["bound"] = "dispatch-bound"
    elif bytes_est and out["intensity"] < ridge:
        out["bound"] = "memory-bound"
    elif flops_est:
        out["bound"] = "compute-bound"
    else:
        out["bound"] = "unknown"
    return out


#: bound-class flips are only judged when the larger side of the
#: device/dispatch split exceeds this — below it the whole
#: measurement sits inside CPU-host scheduling noise (warm q06 at
#: perfcheck scale: device 0.14-6.6 ms depending on host load, a 47x
#: swing), while the guarded pathology (dispatch-floor
#: re-fragmentation) lands dispatch in the hundreds of ms
BORDERLINE_FLOOR_NS = 50_000_000


def borderline(device_ns: int, dispatch_ns: int) -> bool:
    """True when the dispatch/device split is too close to call —
    within 10x either way, or too SMALL to trust (neither side past
    :data:`BORDERLINE_FLOOR_NS`) — so the perfcheck bound-class
    comparison treats a flip across it as measurement noise, not
    drift.  The band is wide on purpose: on a loaded CI host the CPU
    backend's device drain legitimately swings 4-8x run to run (and
    collapses under load far below its idle reading), while the
    regression this guards (the per-program dispatch floor
    re-fragmenting — VERDICT r5's 100-programs-per-batch pathology)
    moves the ratio by over an order of magnitude AND the absolute
    dispatch wall into the hundreds of ms.  A re-fragmentation also
    always moves the warm_dispatches/programs pins, which have no
    noise band to hide in."""
    if max(int(device_ns), int(dispatch_ns)) < BORDERLINE_FLOOR_NS:
        return True
    d = max(1, int(device_ns))
    return 0.1 <= (int(dispatch_ns) / d) <= 10.0


def kernel_perf(entry: Dict[str, int],
                peaks: Dict[str, Any]) -> Dict[str, Any]:
    """Roofline fields for one kernel-sink entry (a ``kernels`` dict
    value from a ``stage_complete``/``task_kernels`` event), device
    time scaled by the sampling factor."""
    from . import trace

    return classify(trace.scaled_device_ns(entry),
                    entry.get("dispatch_ns", 0),
                    entry.get("bytes_est", 0),
                    entry.get("flops_est", 0), peaks)


def sum_kernel_rows(kernels: Dict[str, Dict[str, int]]) -> Dict[str, int]:
    """Query-level totals over a per-label kernel table (sampling-aware
    device time)."""
    from . import trace

    return {
        "programs": sum(v.get("programs", 0) for v in kernels.values()),
        # programs that paid the block-until-ready: 0 under
        # spark.blaze.trace.sampleRate=0, where device time is not
        # sampled (which is not the same as 0)
        "timed": sum(v.get("timed", v.get("programs", 0))
                     for v in kernels.values()),
        "device_ns": sum(trace.scaled_device_ns(v)
                         for v in kernels.values()),
        "dispatch_ns": sum(v.get("dispatch_ns", 0)
                           for v in kernels.values()),
        "compile_ns": sum(v.get("compile_ns", 0)
                          for v in kernels.values()),
        "bytes_est": sum(v.get("bytes_est", 0) for v in kernels.values()),
        "flops_est": sum(v.get("flops_est", 0) for v in kernels.values()),
    }


def device_kind_from_events(events: List[Dict[str, Any]]) -> Optional[str]:
    """The ``device_kind`` stamp the query span recorded at
    ``query_start`` — the hardware that RAN the log's programs.  An
    offline analysis (another machine) must judge against that roof,
    not the analyzer's; None for pre-stamp logs."""
    for e in events:
        if e.get("type") == "query_start" and e.get("device_kind"):
            return e["device_kind"]
    return None


def query_perf(events: List[Dict[str, Any]],
               device_kind: Optional[str] = None,
               kernels: Optional[Dict[str, Dict[str, int]]] = None,
               ) -> Dict[str, Any]:
    """Whole-query roofline judgment from an event list: per-kernel
    totals aggregated over every ``stage_complete``, classified against
    the peak table for ``device_kind`` (default: the log's own
    ``query_start`` stamp, falling back to this process's device for
    pre-stamp logs).  Pass ``kernels`` (a ``_kernel_rows`` result) to
    avoid re-aggregating an event list the caller already walked."""
    from . import trace_report

    if kernels is None:
        kernels = trace_report._kernel_rows(events)
    totals = sum_kernel_rows(kernels)
    device_kind = (device_kind or device_kind_from_events(events)
                   or current_device_kind())
    peaks = peaks_for(device_kind)
    doc = classify(totals["device_ns"], totals["dispatch_ns"],
                   totals["bytes_est"], totals["flops_est"], peaks)
    doc.update(
        programs=totals["programs"],
        timed=totals["timed"],
        device_ns=totals["device_ns"],
        dispatch_ns=totals["dispatch_ns"],
        compile_ns=totals["compile_ns"],
        device_kind=device_kind,
        peak=peaks,
    )
    return doc


# ------------------------------------------------------ EXPLAIN ANALYZE

#: golden-pinned top-level keys of :func:`explain_doc` (the ``--explain
#: --json`` shape — add keys freely, never rename; tests/test_perf.py
#: gates it like the ``--report --json`` pins)
EXPLAIN_JSON_KEYS = ("query_id", "status", "wall_ns", "attributed_ns",
                     "attributed_pct", "stages", "kernels", "perf",
                     "cache", "autotune", "stats")


def _node_own_ns(metrics: Dict[str, Any]) -> int:
    return sum(int(metrics.get(t, 0)) for t in NODE_TIMERS)


def _annotate_node(node: Dict[str, Any], wall_ns: int) -> Dict[str, Any]:
    m = node.get("metrics", {})
    own = _node_own_ns(m)
    op = node.get("op", "?")
    fused = op.startswith("FusedStage") or "Fused" in op
    out = {
        "op": op,
        "rows": int(m.get("output_rows", 0)),
        "bytes": int(m.get("output_bytes", 0) or m.get("data_size", 0)),
        "batches": int(m.get("output_batches", 0)),
        "own_ns": own,
        "pct_of_query": round(100.0 * own / wall_ns, 1) if wall_ns else 0.0,
        "fused": fused,
        "children": [_annotate_node(c, wall_ns)
                     for c in node.get("children", [])],
    }
    if fused and "[" in op:
        out["fused_ops"] = op.count("+") + 1
    # cardinality-estimator stamps (runtime/stats.py at optimize_plan):
    # estimate vs the actual above, Q-error = max(est/act, act/est) —
    # absent on nodes the estimator could not reach (IpcReader inputs)
    est = m.get("est_rows")
    if est is not None:
        est = int(est)
        out["est_rows"] = est
        out["est_bytes"] = int(m.get("est_bytes", 0))
        if est > 0 and out["rows"] > 0:
            out["q_error"] = round(max(est / out["rows"],
                                       out["rows"] / est), 3)
    return out


def _tree_sum_own(node: Dict[str, Any]) -> int:
    return node["own_ns"] + sum(_tree_sum_own(c)
                                for c in node.get("children", []))


def terminal_status(events: List[Dict[str, Any]]) -> str:
    """The query's terminal status from its ``query_end`` event(s):
    ``done`` / ``failed`` / ``cancelled`` / ``deadline_exceeded``, or
    ``incomplete`` when the log has no terminal event at all (a crash
    mid-run / a live query's log read early)."""
    ends = [e for e in events if e.get("type") == "query_end"]
    if not ends:
        return "incomplete"
    statuses = [e.get("status", "ok") for e in ends]
    for bad in ("failed", "deadline_exceeded", "cancelled"):
        if bad in statuses:
            return bad
    return "done"


def explain_doc(events: List[Dict[str, Any]],
                device_kind: Optional[str] = None) -> Dict[str, Any]:
    """The EXPLAIN ANALYZE document for one traced query run: the
    merged plan tree per stage annotated with rows/bytes/batches,
    per-node own-time and % of query wall, fused-chain markers, the
    per-kernel roofline table, and the whole-query bound judgment.
    Top-level keys are golden-pinned (:data:`EXPLAIN_JSON_KEYS`)."""
    from . import trace_report

    t = trace_report.by_type(events)
    qids = [e.get("query_id", "?") for e in t.get("query_start", [])]
    wall_ns = sum(e.get("wall_ns", 0) for e in t.get("query_end", []))
    if not wall_ns:
        # incomplete log: the stage walls are the best denominator left
        wall_ns = sum(e.get("wall_ns", 0)
                      for e in t.get("stage_complete", []))

    plans: Dict[int, Dict[str, Any]] = {}
    for e in t.get("task_plan", []):
        sid = e.get("stage_id", 0)
        plans[sid] = (trace_report._merge_plan(plans[sid], e["plan"])
                      if sid in plans else e["plan"])

    completes = {e.get("stage_id"): e for e in t.get("stage_complete", [])}
    stages = []
    attributed = 0
    for sid in sorted(set(plans) | set(completes)):
        ce = completes.get(sid, {})
        stage_doc: Dict[str, Any] = {
            "stage_id": sid,
            "kind": ce.get("kind"),
            "status": ce.get("status", "incomplete"),
            "wall_ns": ce.get("wall_ns", 0),
            "pct_of_query": round(100.0 * ce.get("wall_ns", 0) / wall_ns, 1)
            if wall_ns else 0.0,
            "plan": None,
        }
        if sid in plans:
            annotated = _annotate_node(plans[sid], wall_ns)
            stage_doc["plan"] = annotated
            attributed += _tree_sum_own(annotated)
        stages.append(stage_doc)

    peaks_kind = (device_kind or device_kind_from_events(events)
                  or current_device_kind())
    peaks = peaks_for(peaks_kind)
    rows = trace_report._kernel_rows(events)
    kernels = {label: dict(v, **kernel_perf(v, peaks))
               for label, v in rows.items()}

    return {
        "query_id": qids[0] if qids else "?",
        "status": terminal_status(events),
        "wall_ns": wall_ns,
        "attributed_ns": attributed,
        "attributed_pct": round(100.0 * attributed / wall_ns, 1)
        if wall_ns else 0.0,
        "stages": stages,
        "kernels": kernels,
        "perf": query_perf(events, device_kind=peaks_kind, kernels=rows),
        "cache": _cache_doc(t),
        "autotune": _autotune_doc(t),
        "stats": _stats_doc(t, stages),
    }


def _stats_doc(t: Dict[str, List[Dict[str, Any]]],
               stages: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The runtime-statistics story for one traced run: worst per-node
    Q-error over the annotated plans, this run's skew findings
    (``stats_skew_detected`` events), and the stats-store traffic
    (``stats_reused`` / ``stats_persisted``)."""
    qerrs: List[float] = []

    def walk(n: Dict[str, Any]) -> None:
        if n.get("q_error") is not None:
            qerrs.append(n["q_error"])
        for c in n.get("children", []):
            walk(c)

    for st in stages:
        if st.get("plan") is not None:
            walk(st["plan"])
    skew = [{k: e.get(k) for k in ("exchange", "op", "partition",
                                   "rows", "ratio", "partitions")}
            for e in t.get("stats_skew_detected", [])]
    return {
        "qerror_max": max(qerrs) if qerrs else None,
        "nodes_estimated": len(qerrs),
        "skew": skew,
        "reused": len(t.get("stats_reused", [])),
        "persisted": len(t.get("stats_persisted", [])),
    }


def _autotune_doc(t: Dict[str, List[Dict[str, Any]]]) -> Dict[str, int]:
    """The batch-autotune story from this run's ``autotune`` trace
    events (runtime/dispatch.py controller): how often the coalescing
    bucket grew / was pushed back, and where it ended up."""
    evs = t.get("autotune", [])
    return {
        "grows": sum(1 for e in evs if e.get("action") == "grow"),
        "pushbacks": sum(1 for e in evs if e.get("action") == "pushback"),
        "target_rows": int(evs[-1].get("target_rows", 0)) if evs else 0,
    }


def _cache_doc(t: Dict[str, List[Dict[str, Any]]]) -> Dict[str, int]:
    """The query-cache story from this run's plan_cache/result_cache
    trace events (runtime/querycache.py): program-reuse hits at the
    optimize_plan choke point and result-cache traffic, including the
    bytes a hit served off-device."""

    def count(evs, action):
        return sum(1 for e in evs if e.get("action") == action)

    pc = t.get("plan_cache", [])
    rc = t.get("result_cache", [])
    return {
        "plan_hits": count(pc, "hit"),
        "plan_misses": count(pc, "miss"),
        "result_hits": count(rc, "hit"),
        "result_misses": count(rc, "miss"),
        "result_stores": count(rc, "store"),
        "result_invalidations": count(rc, "invalidate"),
        "result_hit_bytes": sum(e.get("bytes", 0) for e in rc
                                if e.get("action") == "hit"),
    }


def _fmt_ns(ns: float) -> str:
    return f"{ns / 1e9:.3f}s" if ns >= 1e6 else f"{ns / 1e3:.0f}us"


def _render_node(node: Dict[str, Any], indent: int,
                 out: List[str]) -> None:
    marks = []
    if node.get("fused"):
        n = node.get("fused_ops")
        marks.append(f"[fused x{n}]" if n else "[fused]")
    ann = (f"rows={node['rows']:,} bytes={node['bytes']:,} "
           f"batches={node['batches']}")
    if node.get("est_rows") is not None:
        ann += f" est={node['est_rows']:,}"
        if node.get("q_error") is not None:
            ann += f" Q-err={node['q_error']:.2f}"
    if node["own_ns"]:
        ann += (f" own={_fmt_ns(node['own_ns'])}"
                f" ({node['pct_of_query']:.1f}% of query)")
    out.append("  " * indent + node["op"]
               + ("  " + " ".join(marks) if marks else "")
               + f"  [{ann}]")
    for c in node.get("children", []):
        _render_node(c, indent + 1, out)


def render_explain(events: List[Dict[str, Any]],
                   device_kind: Optional[str] = None,
                   doc: Optional[Dict[str, Any]] = None) -> str:
    """The EXPLAIN ANALYZE text rendering (CLI ``--explain``, monitor
    ``/queries/<id>/explain``).  Pass ``doc`` (a prebuilt
    :func:`explain_doc`) to avoid re-walking the event list a caller
    already analyzed."""
    doc = doc or explain_doc(events, device_kind=device_kind)
    lines: List[str] = []
    status = doc["status"]
    lines.append(
        f"EXPLAIN ANALYZE {doc['query_id']}"
        f"  status={status.upper()}"
        f"  wall={_fmt_ns(doc['wall_ns'])}"
        f"  plan-attributed={doc['attributed_pct']:.0f}%")
    if status not in ("done",):
        lines.append(
            f"  !! query ended {status.upper()} — metrics below cover "
            f"only what ran before the terminal event")
    p = doc["perf"]
    # programs ran and none paid the block (sampleRate=0): device time
    # is not a 0, and what is reckoned from it is not known either
    if p["timed"] or not p["programs"]:
        bound, device = p["bound"], _fmt_ns(p["device_ns"])
        hbm, mfu = f"{100 * p['hbm_util']:.2f}%", f"{100 * p['mfu_est']:.4f}%"
    else:
        bound, device, hbm, mfu = "n/a", "not sampled", "n/a", "n/a"
    lines.append(
        f"perf: {bound}  programs={p['programs']}  "
        f"device={device}  "
        f"dispatch={_fmt_ns(p['dispatch_ns'])}  "
        f"hbm_util={hbm}  "
        f"mfu_est={mfu}  "
        f"(peaks: {p['peak']['device']}, "
        f"{p['peak']['hbm_gbps']:g} GB/s, {p['peak']['tflops']:g} TF)")
    at = doc.get("autotune") or {}
    if at.get("grows") or at.get("pushbacks"):
        lines.append(
            f"autotune: target_rows={at['target_rows']:,}  "
            f"({at['grows']} grow, {at['pushbacks']} pushback)")
    cd = doc.get("cache") or {}
    if any(cd.values()):
        line = (f"cache: plan {cd['plan_hits']} hit"
                f"/{cd['plan_misses']} miss  "
                f"result {cd['result_hits']} hit"
                f"/{cd['result_misses']} miss"
                f"/{cd['result_invalidations']} inval")
        if cd["result_hit_bytes"]:
            line += f"  served {cd['result_hit_bytes']:,}B off-device"
        lines.append(line)
    sd = doc.get("stats") or {}
    if sd.get("qerror_max") is not None or sd.get("skew"):
        if sd.get("qerror_max") is not None:
            line = (f"stats: Q-err max {sd['qerror_max']:.2f} over "
                    f"{sd['nodes_estimated']} estimated node"
                    f"{'s' if sd['nodes_estimated'] != 1 else ''}")
            if sd.get("reused"):
                line += f"  (warm: reused {sd['reused']} stored plan)"
            if sd.get("persisted"):
                line += f"  (persisted {sd['persisted']})"
            lines.append(line)
        for f in sd.get("skew", []):
            lines.append(
                f"  !! skew {f['exchange']} p{f['partition']}: "
                f"{f['rows']:,} rows {f['ratio']:.1f}x median of "
                f"{f['partitions']} partitions ({f['op']})")
    for st in doc["stages"]:
        lines.append("")
        lines.append(
            f"stage {st['stage_id']} {st['kind'] or '?'}"
            f"  wall={_fmt_ns(st['wall_ns'])}"
            f" ({st['pct_of_query']:.1f}% of query)"
            + ("" if st["status"] in ("ok", "incomplete")
               else f"  <-- {st['status'].upper()}"))
        if st["plan"] is not None:
            sub: List[str] = []
            _render_node(st["plan"], 1, sub)
            lines.extend(sub)
        else:
            lines.append("  (no task_plan event recorded for this stage)")
    if doc["kernels"]:
        lines.append("")
        lines.append("operator kernels (roofline):")
        for label, v in sorted(doc["kernels"].items(),
                               key=lambda kv: -(kv[1].get("dispatch_ns", 0)
                                                + kv[1].get("device_ns", 0))):
            lines.append(
                f"  {label:24s} programs {v.get('programs', 0):>5d}  "
                f"bytes~{v.get('hbm_bytes_est', 0):,}  "
                f"hbm {100 * v.get('hbm_util', 0.0):.2f}%  "
                f"mfu {100 * v.get('mfu_est', 0.0):.4f}%  "
                f"{v.get('bound', 'unknown')}")
    return "\n".join(lines)


# ------------------------------------------------- perf-baseline gate

#: golden-pinned top-level keys of the ``--perfcheck --json`` document
PERFCHECK_JSON_KEYS = ("baselines", "tolerance", "device_kind",
                       "queries", "problems", "ok")


def baselines_path() -> str:
    return str(conf.PERF_BASELINES.get() or "") or BASELINES_PATH


def load_baselines(path: Optional[str] = None) -> Dict[str, Any]:
    """The golden perf-baseline registry (``perf_baselines.json`` or
    the ``spark.blaze.perf.baselines`` override)."""
    with open(path or baselines_path()) as f:
        return json.load(f)


def measure_query(name: str, scans: Dict[str, Any], n_parts: int,
                  n_batches: int, build_query=None) -> Dict[str, Any]:
    """One query's warm perf measurement, the way ``run_task`` runs it
    (fused + pruned, in-process): one cold pass (compiles allowed),
    then one warm pass under a dispatch capture + kernel capture with
    the estimator armed.  ``n_batches`` normalizes dispatches per input
    batch (the scale-robust number the baseline pins)."""
    from ..ops.fusion import optimize_plan
    from .context import TaskContext
    from . import dispatch, trace

    if build_query is None:
        from ..tpch import build_query

    def run_once():
        plan = optimize_plan(build_query(name, scans, n_parts))
        rows = 0
        for p in range(plan.num_partitions()):
            for b in plan.execute(p, TaskContext(p, plan.num_partitions())):
                rows += b.num_rows
        return rows

    if dispatch.autotune_enabled():
        # pin the batch-autotune controller at its dispatch-bound
        # fixed point (min(maxRows, pushback ceiling)) instead of
        # racing timing-driven convergence: near deviceShareTarget the
        # CPU backend's per-window device share is a coin flip, and a
        # different converged target means a different coalesced batch
        # count — flapping the pinned dispatch/program counts run to
        # run.  Saturating BEFORE the cold pass makes that one pass
        # compile the final bucket shapes, so the measured pass stays
        # zero-warm-recompile; at the cap, further observations cannot
        # move the target (growth is capped, pushback needs an OOM),
        # so the measurement is stable.
        dispatch.autotune_reset()
        dispatch.autotune_saturate(name)
    run_once()  # cold: compiles allowed
    with dispatch.capture() as warm:
        with trace.profile_kernels() as prof:
            rows = run_once()
    totals = sum_kernel_rows(trace.snapshot_kernels(prof))
    peaks = peaks_for(current_device_kind())
    cls = classify(totals["device_ns"], totals["dispatch_ns"],
                   totals["bytes_est"], totals["flops_est"], peaks)
    return {
        "rows": rows,
        "warm_dispatches": int(warm.get("xla_dispatches", 0)),
        "dispatches_per_batch": round(
            warm.get("xla_dispatches", 0) / max(1, n_batches), 2),
        "programs": int(totals["programs"]),
        "warm_compiles": int(warm.get("xla_compiles", 0)),
        "device_ns": totals["device_ns"],
        "dispatch_ns": totals["dispatch_ns"],
        "hbm_bytes_est": cls["hbm_bytes_est"],
        "flops_est": cls["flops_est"],
        "hbm_util": cls["hbm_util"],
        "mfu_est": cls["mfu_est"],
        "bound": cls["bound"],
    }


def check_query(name: str, measured: Dict[str, Any],
                base: Dict[str, Any], tolerance: float) -> List[str]:
    """Drift findings for one query against its pinned baseline.
    Drift in EITHER direction outside tolerance fails — an improvement
    is re-pinned deliberately (``--perfcheck --update``), never
    absorbed silently, so the registry keeps meaning something."""
    problems: List[str] = []
    for key in ("warm_dispatches", "programs"):
        b = base.get(key)
        m = measured.get(key, 0)
        if b is None:
            continue
        lo, hi = b * (1 - tolerance), b * (1 + tolerance)
        if not (lo <= m <= hi):
            direction = "regressed" if m > hi else "improved"
            problems.append(
                f"{name}: {key} {m} outside [{lo:.1f}, {hi:.1f}] "
                f"(baseline {b}, {direction} — "
                f"{'fix the fragmentation' if m > hi else 're-pin with --perfcheck --update'})")
    if measured.get("warm_compiles", 0) > base.get("warm_compiles", 0):
        problems.append(
            f"{name}: warm run recompiled "
            f"{measured['warm_compiles']}x (baseline "
            f"{base.get('warm_compiles', 0)}) — the kernel-cache / "
            f"shape-bucketing contract broke")
    base_bound = base.get("bound")
    if (base_bound and measured.get("bound") != base_bound
            and not borderline(measured.get("device_ns", 0),
                               measured.get("dispatch_ns", 0))):
        problems.append(
            f"{name}: bound class flipped {base_bound} -> "
            f"{measured.get('bound')} decisively "
            f"(device {measured.get('device_ns', 0)}ns vs dispatch "
            f"{measured.get('dispatch_ns', 0)}ns)")
    return problems


def _tpch_scans(scale: float, n_parts: int, batch_rows: int):
    from ..ops import MemoryScanExec
    from ..tpch import TPCH_SCHEMAS
    from ..tpch.datagen import generate_all, table_to_batches

    data = generate_all(scale)
    scans = {
        name: MemoryScanExec(
            table_to_batches(data[name], TPCH_SCHEMAS[name], n_parts,
                             batch_rows=batch_rows),
            TPCH_SCHEMAS[name])
        for name in TPCH_SCHEMAS
    }
    n_rows = len(data["lineitem"][next(iter(data["lineitem"]))][0])
    per_part = (n_rows + n_parts - 1) // n_parts
    n_batches = n_parts * ((per_part + batch_rows - 1) // batch_rows)
    return scans, n_batches


def run_perfcheck(update: bool = False, inflate: float = 1.0,
                  registry_path: Optional[str] = None,
                  out=print) -> Tuple[int, Dict[str, Any]]:
    """The CLI ``--perfcheck`` body: measure every query pinned in the
    baseline registry at the registry's pinned scale, diff against the
    pins (nonzero on drift outside ``spark.blaze.perf.tolerance``), or
    — with ``update`` — re-pin the registry with fresh measurements +
    provenance.  ``inflate`` multiplies the measured dispatch/program
    counts (the gate's own self-test hook: ``--perfcheck-inflate 2``
    must fail, proving drift detection actually fires).  Returns
    ``(rc, json_doc)`` with the golden-pinned
    :data:`PERFCHECK_JSON_KEYS` shape."""
    from . import dispatch

    if update and inflate != 1.0:
        # the self-test hook must never be able to pin falsified
        # counts as the golden baselines (the CLI rejects this too)
        raise ValueError("inflate is a drift-detection self-test hook "
                         "and cannot be combined with update")
    registry_path = registry_path or baselines_path()
    registry = load_baselines(registry_path)
    prov = registry.get("provenance", {})
    scale = float(prov.get("scale", 0.01))
    n_parts = int(prov.get("parts", 1))
    batch_rows = int(prov.get("batch_rows", 4096))
    # the registry's pinned tolerance is the default; the conf knob
    # overrides when set nonzero (0 = defer to the registry, so the
    # field in perf_baselines.json is live, not decorative)
    tolerance = (float(conf.PERF_TOLERANCE.get())
                 or float(registry.get("tolerance", 0.25)))
    scans, n_batches = _tpch_scans(scale, n_parts, batch_rows)
    device_kind = current_device_kind()
    problems: List[str] = []
    measured_all: Dict[str, Dict[str, Any]] = {}
    # the gate JUDGES the estimator's numbers: force it armed for the
    # measurement even when the operator's conf or env disarmed it
    # (baseline hbm/bound pins would otherwise read as zero drift).
    # The batch autotuner is likewise forced armed: the baselines pin
    # the TUNED warm path (q01/q06 majority-device), and measuring the
    # untuned path would read as a bound-class flip.
    force(True)
    dispatch.autotune_force(True)
    try:
        for name in sorted(registry.get("queries", {})):
            measured_all[name] = measure_query(name, scans, n_parts,
                                               n_batches)
    finally:
        reset()
        dispatch.autotune_force(None)
    for name in sorted(registry.get("queries", {})):
        measured = measured_all[name]
        if inflate != 1.0:
            for key in ("warm_dispatches", "programs"):
                measured[key] = int(round(measured[key] * inflate))
            measured["dispatches_per_batch"] = round(
                measured["dispatches_per_batch"] * inflate, 2)
        measured_all[name] = measured
        base = registry["queries"][name]
        qp = [] if update else check_query(name, measured, base, tolerance)
        problems.extend(qp)
        out(f"perfcheck {name}: dispatches {measured['warm_dispatches']} "
            f"({measured['dispatches_per_batch']}/batch)  "
            f"programs {measured['programs']}  "
            f"compiles {measured['warm_compiles']}  "
            f"{measured['bound']}  hbm {100 * measured['hbm_util']:.2f}%"
            + ("" if not qp else "  <-- DRIFT"))
    if update:
        pinned = {
            name: {k: m[k] for k in (
                "warm_dispatches", "dispatches_per_batch", "programs",
                "warm_compiles", "bound", "hbm_util", "mfu_est")}
            for name, m in measured_all.items()
        }
        doc = {
            "title": registry.get("title", ""),
            "provenance": {
                "pinned_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime()),
                "device_kind": device_kind,
                "scale": scale,
                "parts": n_parts,
                "batch_rows": batch_rows,
                # pins were measured with the batch autotuner armed
                # (the tuned warm path is what the gate protects)
                "autotune": True,
            },
            "tolerance": registry.get("tolerance", 0.25),
            "queries": pinned,
        }
        tmp = f"{registry_path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        os.replace(tmp, registry_path)
        out(f"# perfcheck: re-pinned {len(pinned)} queries to "
            f"{registry_path} (device {device_kind})")
    json_doc = {
        "baselines": registry_path,
        "tolerance": tolerance,
        "device_kind": device_kind,
        "queries": measured_all,
        "problems": problems,
        "ok": not problems,
    }
    return (1 if problems else 0), json_doc
