"""The reduction from a profiler trace to busy/idle seconds, per-op
sums and named idle gaps: on a hand-made trace whose sums are known,
and on a small trace recorded on the chip (``bench/fixtures``)."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import run, trace_reduce

FIXTURES = os.path.join(ROOT, "bench", "fixtures")


def test_hand_made_trace():
    ms = 1e6  # the trace's clock is in nanoseconds
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_scan(11)", 10 * ms, 30 * ms), ("jit_agg(22)", 60 * ms, 80 * ms)],
            "XLA Ops": [
                ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 10 * ms, 20 * ms),
                ("%copy.2 = f32[8]{0} copy(%fusion.1)", 15 * ms, 30 * ms),  # overlap: counted once in the union
                ("%fusion.1 = s64[4]{0} fusion(s64[4]{0} %q)", 60 * ms, 80 * ms),
                ("%fusion.9 = f32[] fusion()", 150 * ms, 160 * ms),  # after the window: not counted
            ],
        },
        "/host:CPU": {
            "python": [
                ("bench_query", 0 * ms, 100 * ms),
                ("plan", 0 * ms, 8 * ms),
                ("run_stages", 8 * ms, 90 * ms),  # the harness's own, not in SPANS: no gap takes its name
                ("d2h", 90 * ms, 100 * ms),
                ("unrelated", 0 * ms, 500 * ms),
            ],
            # the program's leaf spans come from the threads that run its tasks
            "task": [
                ("blaze:task_decode", 8 * ms, 12 * ms),
                ("blaze:scan_stage", 12 * ms, 50 * ms),
                ("blaze:device_read", 55 * ms, 90 * ms),
                ("blaze:exchange_write", 55 * ms, 90 * ms),  # holds device_read; not in SPANS
            ],
        },
    }
    r = trace_reduce.reduce_planes(planes, run.SPANS)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.040)  # [10,30] and [60,80]
    assert r["queries"] == 1 and r["chips"] == 1
    # an operation goes by the program launched last before it; most time first
    assert r["device_ops"] == [["jit_agg/fusion.1", pytest.approx(0.020)],
                               ["jit_scan/copy.2", pytest.approx(0.015)],
                               ["jit_scan/fusion.1", pytest.approx(0.010)]]
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps == pytest.approx({
        "plan>jit_scan": 0.010,                      # [0,10]: 8 ms of it in plan, 2 in task_decode
        "blaze:scan_stage>jit_agg": 0.030,           # [30,60]: 20 ms staging, 5 in no span, 5 reading
        "blaze:device_read>end_of_window": 0.020,    # [80,100]: 10 ms each of device_read and d2h; the first wins
    })
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    # what the device did does not depend on the spans that name its gaps
    bare = trace_reduce.reduce_planes(planes, ("bench_query",))
    assert [bare[k] for k in ("window_s", "busy_s", "device_ops")] == \
        [r[k] for k in ("window_s", "busy_s", "device_ops")]
    assert dict(map(tuple, bare["idle_gaps"])) == pytest.approx({
        "outside_spans>jit_scan": 0.010, "outside_spans>jit_agg": 0.030,
        "outside_spans>end_of_window": 0.020})


def test_no_device_operation_gives_nothing():
    assert trace_reduce.reduce_planes({"/host:CPU": {"python": [("bench_query", 0.0, 5.0)]}},
                                      run.SPANS) is None


#: the harness's spans when that trace was recorded; the program had none yet
SPANS_PR25 = ("bench_query", "plan", "run_stages", "d2h")


def test_recorded_chip_trace():
    """Three q6 at SF0.02 on one TPU v5e (chip run, PR 25), 569 KB."""
    path = os.path.join(FIXTURES, "q06_sf0.02_3queries.xplane.pb")
    r = trace_reduce.reduce_file(path, SPANS_PR25)
    assert (r["chips"], r["queries"]) == (1, 3)
    assert r["window_s"] == pytest.approx(0.214457993, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.000455788, rel=1e-9)
    # no two operations of one chip overlap here, so their sum is the union
    assert sum(s for _, s in r["device_ops"]) == pytest.approx(r["busy_s"])
    assert sum(s for _, s in r["idle_gaps"]) + r["busy_s"] == pytest.approx(r["window_s"])
    assert r["device_ops"][0] == ["jit_scalar_kernel/fusion.19", pytest.approx(2.7712e-05)]
    assert r["idle_gaps"][0] == ["run_stages>jit_convert_element_type", pytest.approx(0.146232518)]
    assert all(len(name) <= 80 for name, _ in r["device_ops"] + r["idle_gaps"])
    json.dumps(r)  # what goes into the result line is plain data
    # read by today's spans: the same device numbers, and what run_stages covered is in no span
    now = trace_reduce.reduce_file(path, run.SPANS)
    assert [now[k] for k in ("window_s", "busy_s", "device_ops")] == \
        [r[k] for k in ("window_s", "busy_s", "device_ops")]
    assert now["idle_gaps"][0][0] == "outside_spans>jit_convert_element_type"


def test_recorded_chip_trace_names_gaps_by_the_programs_spans():
    """Three q6 at SF0.02 on one TPU v5e through the final tree's ``Cell``
    (chip run, PR 29), 572 KB: the program's leaf spans are in it."""
    path = os.path.join(FIXTURES, "q06_sf0.02_3queries_pr29.xplane.pb")
    r = trace_reduce.reduce_file(path, run.SPANS)
    assert (r["chips"], r["queries"]) == (1, 3)
    assert r["window_s"] == pytest.approx(0.21045635, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.000456294, rel=1e-9)
    assert sum(s for _, s in r["idle_gaps"]) + r["busy_s"] == pytest.approx(r["window_s"])
    assert r["idle_gaps"][0] == ["blaze:device_read>jit_convert_element_type", pytest.approx(0.102642602)]
    by_span = {}
    for name, s in r["idle_gaps"]:
        by_span[name.split(">")[0]] = by_span.get(name.split(">")[0], 0.0) + s
    assert set(by_span) == {"blaze:device_read", "blaze:exchange_read", "blaze:scan_stage",
                            "blaze:task_decode", "d2h", "outside_spans"}
    assert by_span["outside_spans"] == pytest.approx(0.020748203)  # 9.9% of idle at this toy size
    # the harness's former span, which held them all, names the same gaps less finely
    old = trace_reduce.reduce_file(path, SPANS_PR25)
    assert [old[k] for k in ("window_s", "busy_s", "device_ops")] == \
        [r[k] for k in ("window_s", "busy_s", "device_ops")]
    assert old["idle_gaps"][0] == ["run_stages>jit_convert_element_type", pytest.approx(0.14411056)]
