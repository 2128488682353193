"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports from it.  Needs nothing but JAX's own ``ProfileData`` reader.

What a TPU trace holds (looked at by hand on the recorded fixture): one
plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one
event per device operation and whose line ``XLA Modules`` has one event
per launched program (an operation's event carries its whole HLO line,
reported here as ``<program>/<operation>``); and host planes whose lines are threads, where
``jax.profiler.TraceAnnotation`` spans appear under their own names.
All planes share one clock, in nanoseconds.

* the traced window is from the start of the first ``window_span``
  event to the end of the last (the harness wraps every whole query in
  one); with none, from the first device operation to the last;
* busy is the union of the device-operation intervals inside the
  window, averaged over the chips that ran anything;
* an idle gap is an interval of the window in which no operation ran
  on that chip.  It is named by the span (other than the window's)
  that the host spent most of it in, and by the program that ended
  it: ``blaze:scan_stage>jit_dense_update``.
"""

import bisect
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _merge(intervals):
    """Sorted, non-overlapping union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _program(name):
    """A launched program's stable name: ``jit_fused_stage(123456)`` ->
    ``jit_fused_stage``."""
    return re.sub(r"\(\d+\)$", "", name)


def _op(name):
    """A device operation's short name: the trace gives the whole HLO
    line, ``%fusion.19 = pred[65536]{...} fusion(...)`` -> ``fusion.19``."""
    return name.split(" = ", 1)[0].lstrip("%")


def read_planes(path):
    """The trace as plain data: {plane: {line: [(name, start_ns, end_ns)]}}."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)))
    return planes


def reduce_planes(planes, spans=(), window_span="bench_query"):
    """See the module's docstring.  ``spans``: the host spans' names,
    the window's among them.  Returns None where no device operation ran."""
    device = {p: lines for p, lines in planes.items()
              if DEVICE_PLANE.match(p) and lines.get(OPS_LINE)}
    if not device:
        return None
    host = defaultdict(list)  # span name -> [(start, end)]
    for p, lines in planes.items():
        if not DEVICE_PLANE.match(p):
            for events in lines.values():
                for name, s, e in events:
                    if name in spans:
                        host[name].append((s, e))

    if host.get(window_span):
        w0 = min(s for s, _ in host[window_span])
        w1 = max(e for _, e in host[window_span])
    else:
        w0 = min(s for lines in device.values() for _, s, _ in lines[OPS_LINE])
        w1 = max(e for lines in device.values() for _, _, e in lines[OPS_LINE])

    # the spans inside a query follow one another on one thread
    inner = sorted((s, e, name) for name in spans if name != window_span for s, e in host.get(name, ()))
    inner_starts = [s for s, _, _ in inner]

    def host_span_of(g0, g1):
        share = defaultdict(float)
        i = max(bisect.bisect_right(inner_starts, g0) - 1, 0)
        while i < len(inner) and inner[i][0] < g1:
            s, e, name = inner[i]
            if e > g0:
                share[name] += min(e, g1) - max(s, g0)
            i += 1
        return max(share, key=share.get) if share else "outside_spans"

    busy_ns = []
    op_ns = defaultdict(float)
    gap_ns = defaultdict(float)
    for lines in device.values():
        clipped = [(max(s, w0), min(e, w1)) for _, s, e in lines[OPS_LINE] if e > w0 and s < w1]
        union = _merge(clipped)
        busy_ns.append(sum(e - s for s, e in union))
        modules = sorted((s, _program(name)) for name, s, _ in lines.get(MODULES_LINE, ()))
        module_starts = [s for s, _ in modules]
        for name, s, e in lines[OPS_LINE]:
            if e > w0 and s < w1:
                # an operation belongs to the program launched last before it
                m = bisect.bisect_right(module_starts, s) - 1
                program = modules[m][1] if m >= 0 else "no_program"
                op_ns[f"{program}/{_op(name)}"] += min(e, w1) - max(s, w0)
        edges = [(w0, w0)] + union + [(w1, w1)]
        k = 0
        for (_, gap_start), (gap_end, _) in zip(edges, edges[1:]):
            if gap_end <= gap_start:
                continue
            while k < len(modules) and modules[k][0] < gap_end - 1:
                k += 1
            then = modules[k][1] if k < len(modules) and modules[k][0] < w1 else "end_of_window"
            gap_ns[f"{host_span_of(gap_start, gap_end)}>{then}"] += gap_end - gap_start

    n = len(device)
    top = lambda d: [[k, v / 1e9 / n] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "chips": n,
        "queries": len(host.get(window_span, ())),
        "device_ops": top(op_ns),
        "idle_gaps": top(gap_ns),
    }


def reduce_file(path, spans=(), window_span="bench_query"):
    return reduce_planes(read_planes(path), spans, window_span)
