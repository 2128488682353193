"""Performance introspection: EXPLAIN ANALYZE and roofline/MFU
attribution.

The reference blaze plumbs per-operator native metrics back to the
Spark UI so an operator can see *where* a query spends its time; PR 3
and PR 12 recorded the raw material here (per-kernel
``device_ns``/``dispatch_ns``/``compile_ns`` splits, per-node
MetricsSet trees in ``task_plan`` events) but nothing turned it into a
judgment.  This module is that judgment layer, two surfaces over the
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
from typing import Any, Dict, List, Optional, Tuple

from .. import conf
from .errors import reraise_control

PEAKS_PATH = os.path.join(os.path.dirname(__file__), "device_peaks.json")

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

#: the bound classes :func:`classify` may return (API for dashboards
#: and the bench line)
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
    point is JUDGING the estimates (``--explain``, the ``--chaos``
    gate) force it on around their runs.  :func:`reset` returns control to
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
                     "cache", "stats")


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

