"""Render a per-query profile from a structured event log.

``python -m blaze_tpu --report <eventlog>`` — the standalone analogue
of Spark's history-server SQL tab over an ``EventLoggingListener``
log: per-stage timeline, the dispatch-floor vs on-chip-compute
breakdown VERDICT r5 asked to be judgeable in-repo, the plan-annotated
metrics tree, the shuffle/memory totals, and the retry/fault timeline
a chaos run leaves behind.

Everything here is a pure function over the parsed event list
(runtime.trace.read_events), so tests and the chaos reconciliation
gate consume the same helpers the CLI renders with.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

#: event types that count as RECOVERY for an injected fault: a plain
#: task re-attempt, or a map-stage rerun after a fetch failure
RECOVERY_EVENTS = ("task_retry", "map_stage_rerun")

#: recovery candidates for an injected OOM (``@oom`` faults carry
#: ``kind: "oom"``): the degradation ladder's own event first — an OOM
#: the ladder absorbed never produces a retry — with the retry events
#: still counting for a ladder-exhausted attempt that re-ran
OOM_RECOVERY_EVENTS = ("oom_recovery",) + RECOVERY_EVENTS

#: recovery candidates for an injected CORRUPTION (``@corrupt`` faults
#: carry ``kind: "corrupt"``): the read boundary's typed DETECTION
#: event first (zero silent wrong results means the flip must be
#: SEEN), with the retry/rerun events covering the recovery itself
CORRUPTION_RECOVERY_EVENTS = ("block_corruption",) + RECOVERY_EVENTS

#: recovery candidates for an injected ENOSPC (``kind: "enospc"``):
#: the disk-pressure ladder's own event when a rung absorbed it, the
#: retry events when it escalated to the typed retryable error
DISK_RECOVERY_EVENTS = ("disk_pressure",) + RECOVERY_EVENTS

#: incident event types the recovery timeline shows — ONE definition
#: for the text report and the JSON profile, so a new event type can
#: never appear in one rendering and silently miss the other
TIMELINE_TYPES = frozenset({
    "fault_injected", "straggler_injected",
    "fetch_failure", "task_retry", "task_timeout",
    "map_stage_rerun", "speculative_attempt_start",
    "speculative_attempt_won", "speculative_attempt_lost",
    "oom_recovery", "block_corruption", "disk_pressure",
    "query_cancel_requested", "query_cancelled",
    "slo_alert_firing", "slo_alert_resolved",
})


def _pair_requests(events, is_request, accept):
    """Greedy forward pairing shared by every reconciliation gate:
    each request event matches the FIRST later unconsumed event
    ``accept`` approves.  Returns (pairs, unpaired)."""
    pairs: List[Tuple[Dict[str, Any], Dict[str, Any]]] = []
    unpaired: List[Dict[str, Any]] = []
    used: set = set()
    for i, e in enumerate(events):
        if not is_request(e):
            continue
        match: Optional[int] = None
        for j in range(i + 1, len(events)):
            if j in used:
                continue
            if accept(e, events[j]):
                match = j
                break
        if match is None:
            unpaired.append(e)
        else:
            used.add(match)
            pairs.append((e, events[match]))
    return pairs, unpaired


def _device(text: str, programs: int, timed: int,
            absent: str = "not sampled") -> str:
    """A rendered device time (or what is reckoned from one), or
    ``absent`` where programs ran and none paid the block-until-ready
    (spark.blaze.trace.sampleRate=0): that is not a measured 0."""
    return text if timed or not programs else absent


def _timed(kernels) -> int:
    return sum(v.get("timed", v.get("programs", 0))
               for v in (kernels or {}).values())


def _fmt_s(ns: float) -> str:
    return f"{ns / 1e9:.3f}s"


def _pct(part: float, whole: float) -> str:
    return f"{100.0 * part / whole:.0f}%" if whole else "-"


def by_type(events: List[Dict[str, Any]]) -> Dict[str, List[Dict[str, Any]]]:
    out: Dict[str, List[Dict[str, Any]]] = {}
    for e in events:
        out.setdefault(e.get("type", "?"), []).append(e)
    return out


# ------------------------------------------- cross-process log merging

def event_log_files(directory: str) -> List[str]:
    """The event-log segments under ``directory``: every ``*.jsonl``
    base file, sorted (rotated ``.segN`` pieces ride along through
    ``read_event_log``, so they are NOT listed separately)."""
    import glob
    import os

    return sorted(glob.glob(os.path.join(directory, "*.jsonl")))


def merge_event_logs(paths: List[str],
                     trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
    """Reconcile several processes' event-log segments — the driver's
    per-query log plus each worker subprocess's own default log — into
    ONE time-ordered event list.  The shared W3C ``trace_id`` (minted
    by the driver's query span, threaded into workers via
    ``BLAZE_TRACEPARENT``) is the join key: pass ``trace_id`` to keep
    only that query's events (events WITHOUT a trace id — memory
    watermarks from an untraced helper, pre-context segments — are
    kept only when no filter is given).  Sort is stable, so same-
    timestamp events keep their per-file order."""
    from . import trace as _trace

    events: List[Dict[str, Any]] = []
    for p in paths:
        try:
            events.extend(_trace.read_event_log(p))
        except OSError:
            continue
    if trace_id is not None:
        events = [e for e in events if e.get("trace_id") == trace_id]
    events.sort(key=lambda e: e.get("ts", 0.0))
    return events


# ----------------------------------------------------- flame profiles

def collapsed_stacks(events: List[Dict[str, Any]]) -> List[str]:
    """The query's device-time profile as COLLAPSED-STACK lines
    (``frame;frame;frame <value>``, value = microseconds) — the input
    format of ``flamegraph.pl`` / speedscope / any standard flamegraph
    tooling (``--report --flame <path>`` writes it).

    Two stack families, both rooted at the query id:

    - ``<query>;stage_<id>_<kind>;<label>;device|dispatch|compile`` —
      the PR 3 kernel sinks aggregated per stage and operator-kernel
      label: where the wall went, split hardware-side;
    - ``<query>;stage_<id>;plan;<op path>`` — the plan-node tree
      weighted by each node's own ``elapsed_compute``, so the flame
      also answers WHICH operator in the plan burned the time."""
    t = by_type(events)
    qid = next((e.get("query_id", "?") for e in t.get("query_start", [])),
               "query")
    agg: Dict[str, int] = {}

    def add(stack: str, ns: int) -> None:
        if ns > 0:
            agg[stack] = agg.get(stack, 0) + ns

    from . import trace as _trace

    for e in t.get("stage_complete", []):
        sid = e.get("stage_id", 0)
        kind = e.get("kind", "?")
        for label, v in (e.get("kernels") or {}).items():
            base = f"{qid};stage_{sid}_{kind};{label}"
            add(base + ";device", _trace.scaled_device_ns(v))
            add(base + ";dispatch", v.get("dispatch_ns", 0))
            add(base + ";compile", v.get("compile_ns", 0))

    plans: Dict[int, Dict[str, Any]] = {}
    for e in t.get("task_plan", []):
        sid = e.get("stage_id", 0)
        plans[sid] = (_merge_plan(plans[sid], e["plan"])
                      if sid in plans else e["plan"])

    def walk(node: Dict[str, Any], path: str, sid: int) -> None:
        frame = f"{path};{node.get('op', '?')}"
        add(frame, int(node.get("metrics", {}).get("elapsed_compute", 0)))
        for c in node.get("children", []):
            walk(c, frame, sid)

    for sid, plan in sorted(plans.items()):
        walk(plan, f"{qid};stage_{sid};plan", sid)

    return [f"{stack} {max(1, ns // 1000)}"
            for stack, ns in sorted(agg.items())]


def write_flame(events: List[Dict[str, Any]], path: str) -> int:
    """Write the collapsed-stack profile to ``path`` (``-`` = stdout);
    returns the number of stack lines."""
    import sys

    lines = collapsed_stacks(events)
    text = "\n".join(lines) + ("\n" if lines else "")
    if path == "-":
        sys.stdout.write(text)
    else:
        with open(path, "w") as f:
            f.write(text)
    return len(lines)


def reconcile_faults(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Pair every ``fault_injected`` with the first subsequent recovery
    event (``task_retry`` or ``map_stage_rerun``) in log order — the
    chaos gate's reconciliation contract: a fault the runtime absorbed
    silently (no recovery recorded) or a recovery with no cause both
    break the replayable-recovery story."""
    by_kind = {"oom": OOM_RECOVERY_EVENTS,
               "corrupt": CORRUPTION_RECOVERY_EVENTS,
               "enospc": DISK_RECOVERY_EVENTS}
    pairs, unpaired = _pair_requests(
        events,
        lambda e: e.get("type") == "fault_injected",
        lambda e, f: f.get("type") in by_kind.get(e.get("kind"),
                                                  RECOVERY_EVENTS))
    recovery_types = set(OOM_RECOVERY_EVENTS) | {"block_corruption",
                                                 "disk_pressure"}
    recoveries = sum(1 for e in events
                     if e.get("type") in recovery_types)
    return {
        "injected": len(pairs) + len(unpaired),
        "recoveries": recoveries,
        "pairs": pairs,
        "unpaired": unpaired,
        "reconciled": not unpaired,
    }


def reconcile_speculation(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Pair every ``speculative_attempt_start`` with a subsequent
    ``speculative_attempt_won`` / ``_lost`` for the same (stage, task,
    attempt) — the chaos gate's speculation contract: a backup that
    was launched but never resolved means a leaked race (its thread,
    its progress rollback, or its commit arbitration never finished).
    A log with no speculation events reconciles trivially."""
    outcomes = ("speculative_attempt_won", "speculative_attempt_lost")

    def key(e):
        return (e.get("stage_id"), e.get("task"), e.get("attempt"))

    pairs, unpaired = _pair_requests(
        events,
        lambda e: e.get("type") == "speculative_attempt_start",
        lambda e, f: f.get("type") in outcomes and key(f) == key(e))
    won = sum(1 for e in events
              if e.get("type") == "speculative_attempt_won")
    lost = sum(1 for e in events
               if e.get("type") == "speculative_attempt_lost")
    return {
        "speculated": len(pairs) + len(unpaired),
        "won": won,
        "lost": lost,
        "pairs": pairs,
        "unpaired": unpaired,
        "reconciled": not unpaired,
    }


def reconcile_cancellation(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Pair every ``query_cancel_requested`` with a subsequent
    ``query_cancelled`` for the same query id — the cancel-storm gate's
    contract: a requested cancel whose query never reached a terminal
    ``query_cancelled`` means the scope leaked (attempts still running,
    resources still registered) or the request was silently dropped.
    A log with no cancel events reconciles trivially."""
    pairs, unpaired = _pair_requests(
        events,
        lambda e: e.get("type") == "query_cancel_requested",
        lambda e, f: (f.get("type") == "query_cancelled"
                      and f.get("query_id") == e.get("query_id")))
    cancelled = sum(1 for e in events
                    if e.get("type") == "query_cancelled")
    return {
        "requested": len(pairs) + len(unpaired),
        "cancelled": cancelled,
        "pairs": pairs,
        "unpaired": unpaired,
        "reconciled": not unpaired,
    }


def reconcile_slo_alerts(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Pair every ``slo_alert_firing`` with a subsequent
    ``slo_alert_resolved`` for the same (pool, slo) — the slo-storm
    gate's contract.  A firing with no resolve is a legitimate TERMINAL
    state (the incident outlived the log) but it is reported under
    ``still_firing``, never silently dropped; a resolve with no prior
    firing is a pairing bug and fails reconciliation.  A log with no
    SLO events reconciles trivially."""
    pairs, still_firing = _pair_requests(
        events,
        lambda e: e.get("type") == "slo_alert_firing",
        lambda e, f: (f.get("type") == "slo_alert_resolved"
                      and f.get("pool") == e.get("pool")
                      and f.get("slo") == e.get("slo")))
    resolves = [e for e in events
                if e.get("type") == "slo_alert_resolved"]
    paired = {id(f) for _, f in pairs}
    orphan_resolves = [e for e in resolves if id(e) not in paired]
    return {
        "fired": len(pairs) + len(still_firing),
        "resolved": len(resolves),
        "pairs": pairs,
        "still_firing": still_firing,
        "orphan_resolves": orphan_resolves,
        "reconciled": not orphan_resolves,
    }


def _merge_plan(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    """Sum two task_plan trees node-by-node (same stage => same plan
    shape; a rewritten/retried plan that differs structurally keeps the
    first shape and merges what aligns)."""
    merged = {
        "op": a["op"],
        "metrics": dict(a["metrics"]),
        "children": [dict(c) for c in a["children"]],
    }
    for k, v in b.get("metrics", {}).items():
        if k.startswith("est_"):
            # estimator stamps (runtime/stats.py) are per-PLAN, not
            # per-task: every task of the stage carries the same
            # stamp, so summing would scale the estimate by the task
            # count — take the max instead
            merged["metrics"][k] = max(merged["metrics"].get(k, 0), v)
        else:
            merged["metrics"][k] = merged["metrics"].get(k, 0) + v
    kids = []
    for i, c in enumerate(merged["children"]):
        if i < len(b.get("children", [])):
            kids.append(_merge_plan(c, b["children"][i]))
        else:
            kids.append(c)
    merged["children"] = kids
    return merged


def _stats_section(t: Dict[str, List[Dict[str, Any]]],
                   plans: Dict[Any, Dict[str, Any]]) -> Dict[str, Any]:
    """The runtime-statistics story (runtime/stats.py) for one traced
    run, shared by the text and JSON reports: worst per-node Q-error
    from the estimator stamps riding the merged plan metrics, this
    run's skew findings, and the stats-store traffic."""
    qerrs: List[float] = []

    def walk(n: Dict[str, Any]) -> None:
        m = n.get("metrics", {})
        est, act = m.get("est_rows", 0), m.get("output_rows", 0)
        if est > 0 and act > 0:
            qerrs.append(round(max(est / act, act / est), 3))
        for c in n.get("children", []):
            walk(c)

    for p in plans.values():
        walk(p)
    findings = [{k: e.get(k) for k in ("exchange", "op", "partition",
                                       "rows", "ratio", "partitions")}
                for e in t.get("stats_skew_detected", [])]
    return {
        "qerror_max": max(qerrs) if qerrs else None,
        "nodes_estimated": len(qerrs),
        "skew": findings,
        "reused": len(t.get("stats_reused", [])),
        "persisted": len(t.get("stats_persisted", [])),
    }


def _render_plan(node: Dict[str, Any], indent: int, out: List[str]) -> None:
    metrics = node.get("metrics", {})
    shown = " ".join(
        f"{k}={v}" for k, v in sorted(metrics.items())
        if not k.startswith("_")
    )
    out.append("  " * indent + node["op"] + (f"  [{shown}]" if shown else ""))
    for c in node.get("children", []):
        _render_plan(c, indent + 1, out)


def _stage_rows(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Per-stage timeline entries shared by the text and JSON reports
    (one dict per stage_complete, submit-aligned start offset)."""
    t = by_type(events)
    ts0 = min((e["ts"] for e in events if "ts" in e), default=0.0)
    completes = sorted(t.get("stage_complete", []),
                      key=lambda e: e.get("stage_id", 0))
    submits = {e.get("stage_id"): e for e in t.get("stage_submit", [])}
    out = []
    for e in completes:
        sid = e.get("stage_id")
        sub = submits.get(sid, {})
        out.append({
            "stage_id": sid,
            "kind": e.get("kind"),
            "n_tasks": e.get("n_tasks"),
            "status": e.get("status", "ok"),
            "start_s": round(sub.get("ts", e["ts"]) - ts0, 6),
            "wall_ns": e.get("wall_ns", 0),
            "programs": e.get("programs", 0),
            "device_time_ns": e.get("device_time_ns", 0),
            "dispatch_overhead_ns": e.get("dispatch_overhead_ns", 0),
            "compile_ns": e.get("compile_ns", 0),
            "counters": e.get("counters") or {},
        })
    return out


def _kernel_rows(events: List[Dict[str, Any]]) -> Dict[str, Dict[str, int]]:
    """Per-kernel-label totals across all stage_complete events (the
    operator-kernel table, sampling-aware; ``bytes_est``/``flops_est``
    are the perf estimator's roofline numerators, 0 in pre-estimator
    logs)."""
    kernels: Dict[str, Dict[str, int]] = {}
    for e in by_type(events).get("stage_complete", []):
        for label, v in (e.get("kernels") or {}).items():
            agg = kernels.setdefault(
                label, {"programs": 0, "device_ns": 0,
                        "dispatch_ns": 0, "compile_ns": 0, "timed": 0,
                        "bytes_est": 0, "flops_est": 0})
            for k in agg:
                if k == "timed":
                    agg[k] += v.get("timed", v.get("programs", 0))
                else:
                    agg[k] += v.get(k, 0)
    return kernels


def render_json(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The full profile as one JSON document (``--report --json``) —
    the dashboard-facing mirror of :func:`render`: stage timeline,
    dispatch-floor split, per-kernel table, plan-annotated metrics
    trees, data movement/memory totals, and the fault/recovery
    pairing.  Top-level keys are pinned by a golden-keys tier-1 test;
    add keys freely, never rename or remove."""
    from . import trace as _trace

    from . import perf

    t = by_type(events)
    ts0 = min((e["ts"] for e in events if "ts" in e), default=0.0)
    ends = t.get("query_end", [])
    query = {
        "ids": [e.get("query_id", "?") for e in t.get("query_start", [])],
        "status": [e.get("status", "ok") for e in ends],
        # the one-word verdict consumers branch on: done / failed /
        # cancelled / deadline_exceeded / incomplete (no terminal
        # event at all — crash mid-run or a live log read early)
        "terminal_status": perf.terminal_status(events),
        "wall_ns": sum(e.get("wall_ns", 0) for e in ends),
        # the distributed-trace join key (one per query span; a merged
        # driver+worker log shows each query's segments under ONE id)
        "trace_ids": sorted({e.get("trace_id")
                             for e in t.get("query_start", [])
                             if e.get("trace_id")}),
    }

    stages = _stage_rows(events)
    total = {"wall_ns": sum(s["wall_ns"] for s in stages),
             "device_time_ns": sum(s["device_time_ns"] for s in stages),
             "dispatch_overhead_ns": sum(s["dispatch_overhead_ns"]
                                         for s in stages),
             "compile_ns": sum(s["compile_ns"] for s in stages)}

    rows = _kernel_rows(events)
    # one aggregation pass feeds both the kernel table and the query
    # perf section; the peak table resolves once, against the log's
    # own device_kind stamp (offline analysis judges the hardware
    # that RAN the log, not the analyzer's)
    qperf = perf.query_perf(events, kernels=rows)
    peaks = qperf["peak"]
    kernels = {}
    for label, v in rows.items():
        kernels[label] = dict(
            v,
            device_ns_scaled=_trace.scaled_device_ns(v),
            sampled=v["timed"] < v["programs"],
            # per-kernel roofline judgment (hbm_util / mfu_est / bound)
            **perf.kernel_perf(v, peaks),
        )

    plans: Dict[str, Any] = {}
    for e in t.get("task_plan", []):
        sid = str(e.get("stage_id", 0))
        plans[sid] = (
            _merge_plan(plans[sid], e["plan"]) if sid in plans else e["plan"]
        )

    sw = t.get("shuffle_write", [])
    sf = t.get("shuffle_fetch", [])
    rp = t.get("rss_push", [])
    sp = t.get("spill", [])
    wm = t.get("mem_watermark", [])
    data_movement = {
        "shuffle_write": {"bytes": sum(e["bytes"] for e in sw),
                          "blocks": sum(e["blocks"] for e in sw),
                          "outputs": len(sw)},
        "shuffle_fetch": {"bytes": sum(e["bytes"] for e in sf),
                          "blocks": sum(e["blocks"] for e in sf),
                          "reads": len(sf)},
        "rss_push": {"bytes": sum(e["bytes"] for e in rp),
                     "blocks": sum(e["blocks"] for e in rp)},
        "spills": {"count": len(sp),
                   "bytes": sum(e["bytes"] for e in sp)},
    }
    memory = {
        "peak_bytes": max((e["used"] for e in wm), default=0),
        "budget_bytes": wm[-1].get("total", 0) if wm else 0,
    }

    rec = reconcile_faults(events)
    timeline_types = TIMELINE_TYPES
    incidents = sorted(
        [e for e in events if e.get("type") in timeline_types]
        + [e for e in t.get("task_attempt_end", [])
           if e.get("status") == "failed"],
        key=lambda e: e.get("ts", 0))
    oom_events = t.get("oom_recovery", [])
    cxl = reconcile_cancellation(events)
    slo_rec = reconcile_slo_alerts(events)
    recovery = {
        "injected": rec["injected"],
        "recoveries": rec["recoveries"],
        "reconciled": rec["reconciled"],
        "unpaired": rec["unpaired"],
        "incidents": [dict(e, offset_s=round(e.get("ts", ts0) - ts0, 6))
                      for e in incidents],
        # the degradation ladder's story: what shed pressure and how
        "oom": {
            "recoveries": len(oom_events),
            "by_action": {a: sum(1 for e in oom_events
                                 if e.get("action") == a)
                          for a in ("spill", "downshift", "eager")},
        },
        # cancel-request <-> terminal-cancel pairing (cancel storms)
        "cancellation": {
            "requested": cxl["requested"],
            "cancelled": cxl["cancelled"],
            "reconciled": cxl["reconciled"],
        },
        # SLO firing <-> resolve pairing (burn-rate alert storms)
        "slo_alerts": {
            "fired": slo_rec["fired"],
            "resolved": slo_rec["resolved"],
            "still_firing": len(slo_rec["still_firing"]),
            "reconciled": slo_rec["reconciled"],
        },
        # the data-integrity story: detections, quarantines, and the
        # disk-pressure ladder's rung usage
        "integrity": {
            "corruption_detected": len(t.get("block_corruption", [])),
            "blocks_quarantined": sum(
                1 for e in t.get("block_corruption", [])
                if e.get("quarantined")),
            "disk_pressure_recoveries": len(t.get("disk_pressure", [])),
            "disk_by_action": {
                a: sum(1 for e in t.get("disk_pressure", [])
                       if e.get("action") == a)
                for a in ("victim_reselect", "reclaim", "retry",
                          "host_fallback", "exhausted")
            },
        },
    }

    hb = t.get("task_heartbeat", [])
    prog = t.get("stage_progress", [])
    progress = {
        "stage_progress_events": len(prog),
        "task_heartbeats": len(hb),
        "last_stage_progress": prog[-1] if prog else None,
    }

    # per-worker fleet totals summed from the driver-side
    # worker_telemetry events (emitted per versioned done frame) — the
    # offline mirror of the live /workers document
    workers: Dict[str, Dict[str, int]] = {}
    for e in t.get("worker_telemetry", []):
        w = workers.setdefault(e.get("worker", "?"), {
            "telemetry_events": 0, "rows": 0, "bytes": 0, "jobs_ok": 0,
            "jobs_failed": 0, "device_ns": 0, "dispatch_ns": 0,
            "compile_ns": 0, "mem_peak": 0})
        w["telemetry_events"] += 1
        for k in ("rows", "bytes", "jobs_ok", "jobs_failed",
                  "device_ns", "dispatch_ns", "compile_ns"):
            w[k] += int(e.get(k, 0) or 0)
        w["mem_peak"] = max(w["mem_peak"], int(e.get("mem_peak", 0) or 0))

    return {
        "query": query,
        "events": len(events),
        "stages": stages,
        "totals": total,
        "kernels": kernels,
        "plans": plans,
        "data_movement": data_movement,
        "memory": memory,
        "recovery": recovery,
        "progress": progress,
        "workers": workers,
        # the whole-query roofline judgment (runtime/perf.py): bytes/
        # flops estimates vs the device peak table -> hbm_util /
        # mfu_est / bound classification
        "perf": qperf,
        # the runtime-stats drift story (runtime/stats.py): worst
        # per-node Q-error, skew findings, stats-store traffic
        "stats": _stats_section(t, plans),
    }


def render(events: List[Dict[str, Any]]) -> str:
    """The full profile report (plain text)."""
    from . import perf

    if not events:
        return "empty event log"
    t = by_type(events)
    lines: List[str] = []
    ts0 = min((e["ts"] for e in events if "ts" in e), default=0.0)

    # ---- header
    queries = [e.get("query_id", "?") for e in t.get("query_start", [])]
    ends = t.get("query_end", [])
    wall_ns = sum(e.get("wall_ns", 0) for e in ends)
    tids = sorted({e.get("trace_id") for e in t.get("query_start", [])
                   if e.get("trace_id")})
    status = perf.terminal_status(events)
    lines.append(
        f"query: {', '.join(queries) if queries else '(no query span)'}"
        + f"  status {status.upper()}"
        + (f"  wall {_fmt_s(wall_ns)}" if wall_ns else "")
        + f"  events {len(events)}"
        + (f"  trace {', '.join(tids)}" if tids else "")
    )
    if status != "done":
        # explicit terminal-status banner: a profile over a query that
        # ended failed / cancelled / deadline_exceeded (or whose log
        # has no terminal event at all) must SAY so up front — the
        # numbers below cover only what ran before the terminal event
        lines.append(
            f"*** query terminal status: {status.upper()} — partial "
            f"profile (metrics cover only what ran"
            + (" before the terminal event) ***" if status != "incomplete"
               else "; no query_end event in this log) ***"))

    # ---- per-stage timeline + dispatch-floor split
    completes = sorted(t.get("stage_complete", []),
                       key=lambda e: e.get("stage_id", 0))
    submits = {e.get("stage_id"): e for e in t.get("stage_submit", [])}
    if completes:
        lines.append("")
        lines.append("stage timeline (device vs dispatch-floor vs compile):")
        total = {"wall": 0, "dev": 0, "disp": 0, "comp": 0,
                 "programs": 0, "timed": 0}
        for e in completes:
            sid = e.get("stage_id")
            sub = submits.get(sid, {})
            start = sub.get("ts", e["ts"]) - ts0
            wall = e.get("wall_ns", 0)
            dev = e.get("device_time_ns", 0)
            disp = e.get("dispatch_overhead_ns", 0)
            comp = e.get("compile_ns", 0)
            total["wall"] += wall
            total["dev"] += dev
            total["disp"] += disp
            total["comp"] += comp
            programs, timed = e.get("programs", 0), _timed(e.get("kernels"))
            total["programs"] += programs
            total["timed"] += timed
            lines.append(
                f"  stage {sid} {e.get('kind', '?'):9s} +{start:7.3f}s "
                f"wall {_fmt_s(wall):>9s}  tasks {e.get('n_tasks', '?')}  "
                f"programs {programs:>4d}  "
                f"device {_device(f'{_fmt_s(dev)} ({_pct(dev, wall)})', programs, timed)}  "
                f"dispatch {_fmt_s(disp)} ({_pct(disp, wall)})  "
                f"compile {_fmt_s(comp)}"
                + ("" if e.get("status", "ok") == "ok" else "  <-- FAILED")
            )
        unattr = max(0, total["wall"] - total["dev"] - total["disp"] - total["comp"])
        lines.append(
            f"  total: device {_device(_pct(total['dev'], total['wall']), total['programs'], total['timed'])}  "
            f"dispatch-floor {_pct(total['disp'], total['wall'])}  "
            f"compile {_pct(total['comp'], total['wall'])}  "
            f"host/other {_pct(unattr, total['wall'])} of "
            f"{_fmt_s(total['wall'])} stage wall"
        )

        # the whole-query roofline judgment (runtime/perf.py): are we
        # limited by the per-program launch floor, the HBM roof, or
        # the flops roof — and how far under the hardware we sit.
        # One aggregation pass shared with the kernel table below.
        krows = _kernel_rows(events)
        qp = perf.query_perf(events, kernels=krows)
        if qp["programs"]:
            lines.append(
                f"  perf: {qp['bound']}  "
                f"hbm_util {100 * qp['hbm_util']:.2f}%  "
                f"mfu_est {100 * qp['mfu_est']:.4f}%  "
                f"(bytes~{qp['hbm_bytes_est']:,}, "
                f"flops~{qp['flops_est']:,}; peaks "
                f"{qp['peak']['device']}: {qp['peak']['hbm_gbps']:g} GB/s, "
                f"{qp['peak']['tflops']:g} TF)")

        # per-kernel-label attribution across all stages.  Sampled
        # captures (spark.blaze.trace.sampleRate > 1) timed only every
        # Nth program: device time scales back up by programs/timed
        # (trace.scaled_device_ns), flagged with '~' as an estimate.
        from . import trace as _trace

        kernels = krows
        if kernels:
            lines.append("")
            lines.append("operator kernels (by device time):")
            for label, v in sorted(
                    kernels.items(),
                    key=lambda kv: -_trace.scaled_device_ns(kv[1])):
                sampled = v["timed"] < v["programs"]
                dev = _trace.scaled_device_ns(v)
                kp = perf.kernel_perf(v, qp["peak"])
                hbm = f"{100 * kp['hbm_util']:.2f}%  {kp['bound']}"
                lines.append(
                    f"  {label:24s} programs {v['programs']:>5d}  "
                    f"device {_device(('~' if sampled else '') + _fmt_s(dev), v['programs'], v['timed']):>9s}  "
                    f"dispatch {_fmt_s(v['dispatch_ns']):>9s}  "
                    f"compile {_fmt_s(v['compile_ns'])}  "
                    f"hbm {_device(hbm, v['programs'], v['timed'], 'n/a')}"
                    + (f"  (timed {v['timed']}/{v['programs']})"
                       if sampled else "")
                )

    # ---- plan-annotated metrics tree (merged per stage)
    plans: Dict[int, Dict[str, Any]] = {}
    for e in t.get("task_plan", []):
        sid = e.get("stage_id", 0)
        plans[sid] = (
            _merge_plan(plans[sid], e["plan"]) if sid in plans else e["plan"]
        )
    for sid in sorted(plans):
        lines.append("")
        lines.append(f"plan (stage {sid}, metrics merged over task attempts):")
        sub: List[str] = []
        _render_plan(plans[sid], 1, sub)
        lines.extend(sub)

    # ---- runtime stats / drift (estimator stamps + skew findings)
    sd = _stats_section(t, plans)
    if sd["qerror_max"] is not None or sd["skew"]:
        lines.append("")
        lines.append("runtime stats / drift:")
        if sd["qerror_max"] is not None:
            line = (f"  Q-err max {sd['qerror_max']:.2f} over "
                    f"{sd['nodes_estimated']} estimated node"
                    f"{'s' if sd['nodes_estimated'] != 1 else ''}")
            if sd["reused"]:
                line += f"  (warm: reused {sd['reused']} stored plan)"
            if sd["persisted"]:
                line += f"  (persisted {sd['persisted']})"
            lines.append(line)
        for f in sd["skew"]:
            lines.append(
                f"  !! skew {f['exchange']} p{f['partition']}: "
                f"{f['rows']:,} rows {f['ratio']:.1f}x median of "
                f"{f['partitions']} partitions ({f['op']})")

    # ---- data movement + memory
    sw = t.get("shuffle_write", [])
    sf = t.get("shuffle_fetch", [])
    rp = t.get("rss_push", [])
    sp = t.get("spill", [])
    wm = t.get("mem_watermark", [])
    if sw or sf or rp or sp or wm:
        lines.append("")
        lines.append("data movement / memory:")
        if sw:
            lines.append(f"  shuffle write: {sum(e['bytes'] for e in sw)} B "
                         f"in {sum(e['blocks'] for e in sw)} blocks "
                         f"({len(sw)} map outputs)")
        if sf:
            lines.append(f"  shuffle fetch: {sum(e['bytes'] for e in sf)} B "
                         f"in {sum(e['blocks'] for e in sf)} blocks "
                         f"({len(sf)} reads)")
        if rp:
            lines.append(f"  rss push:      {sum(e['bytes'] for e in rp)} B "
                         f"in {sum(e['blocks'] for e in rp)} blocks")
        if sp:
            lines.append(f"  spills:        {len(sp)} "
                         f"({sum(e['bytes'] for e in sp)} B freed)")
        if wm:
            peak = max(e["used"] for e in wm)
            lines.append(f"  mem watermark: peak {peak} B "
                         f"of {wm[-1].get('total', 0)} B budget")

    # ---- worker fleet (merged driver+worker logs: the offline mirror
    # of the live /workers document, summed from worker_telemetry)
    wt = t.get("worker_telemetry", [])
    if wt:
        fleet: Dict[str, Dict[str, int]] = {}
        for e in wt:
            w = fleet.setdefault(e.get("worker", "?"), {
                "rows": 0, "bytes": 0, "jobs_ok": 0, "jobs_failed": 0,
                "device_ns": 0, "dispatch_ns": 0})
            for k in w:
                w[k] += int(e.get(k, 0) or 0)
        lines.append("")
        lines.append(f"worker fleet ({len(fleet)} workers):")
        for name in sorted(fleet):
            w = fleet[name]
            lines.append(
                f"  {name:>8s}  jobs {w['jobs_ok']}+{w['jobs_failed']}f  "
                f"rows {w['rows']:,d}  {w['bytes']} B  "
                f"dev/disp {w['device_ns'] / 1e6:.0f}"
                f"/{w['dispatch_ns'] / 1e6:.0f}ms")

    # ---- retry / fault timeline
    timeline_types = TIMELINE_TYPES
    incidents = [e for e in events if e.get("type") in timeline_types]
    incidents += [e for e in t.get("task_attempt_end", [])
                  if e.get("status") == "failed"]
    incidents.sort(key=lambda e: e.get("ts", 0))
    if incidents:
        rec = reconcile_faults(events)
        lines.append("")
        lines.append(
            f"recovery timeline ({rec['injected']} faults injected, "
            f"{rec['recoveries']} recovery events, "
            + ("reconciled):" if rec["reconciled"] else "NOT RECONCILED):")
        )
        oom_events = t.get("oom_recovery", [])
        if oom_events:
            by_action = {a: sum(1 for e in oom_events
                                if e.get("action") == a)
                         for a in ("spill", "downshift", "eager")}
            lines.append(
                "  degradation ladder: "
                + ", ".join(f"{v} {k}" for k, v in by_action.items() if v))
        bc = t.get("block_corruption", [])
        dp = t.get("disk_pressure", [])
        if bc or dp:
            q = sum(1 for e in bc if e.get("quarantined"))
            disk = {a: sum(1 for e in dp if e.get("action") == a)
                    for a in ("victim_reselect", "reclaim", "retry",
                              "host_fallback", "exhausted")}
            lines.append(
                f"  integrity: {len(bc)} corruption(s) detected"
                + (f", {q} quarantined" if q else "")
                + (", disk ladder: " + ", ".join(
                    f"{v} {k}" for k, v in disk.items() if v) if dp else ""))
        cxl = reconcile_cancellation(events)
        if cxl["requested"] or cxl["cancelled"]:
            lines.append(
                f"  cancellation: {cxl['requested']} requested / "
                f"{cxl['cancelled']} terminal "
                + ("(reconciled)" if cxl["reconciled"]
                   else "(NOT RECONCILED)"))
        slo_rec = reconcile_slo_alerts(events)
        if slo_rec["fired"] or slo_rec["resolved"]:
            lines.append(
                f"  slo alerts: {slo_rec['fired']} fired / "
                f"{slo_rec['resolved']} resolved"
                + (f", {len(slo_rec['still_firing'])} still firing"
                   if slo_rec["still_firing"] else "")
                + (" (reconciled)" if slo_rec["reconciled"]
                   else " (NOT RECONCILED)"))
        for e in incidents:
            dt = e.get("ts", ts0) - ts0
            detail = {k: v for k, v in e.items() if k not in ("ts", "type")}
            parts = " ".join(f"{k}={v}" for k, v in detail.items())
            lines.append(f"  +{dt:7.3f}s {e['type']:18s} {parts}")
    return "\n".join(lines)
