"""Static analysis & verification subsystem (blaze_tpu/analysis/).

1. **Lint gate**: the AST rules + conf-registry drift gates run over
   the REAL package and must be clean (``python -m blaze_tpu --lint``
   mirrors this and adds the full 250-plan corpus sweep).
2. **Seeded violations**: each lint rule class catches a deliberately
   broken temp module — trace purity, stray jax.jit, emit-under-lock,
   static lock order, conf drift.
3. **Plan verifier negatives**: hand-corrupted plans (dropped
   exchange, missing buffer bottom, schema-mismatched edge, lost
   writer schema, impure trace key, unsorted SMJ child) each produce
   the right rule id with the offending node path in the message.
4. **Plan verifier acceptance**: real TPC-H/TPC-DS plans verify clean
   fused AND unfused, and FusedStageExec trace keys are deterministic
   across two builds of the same plan.
5. **Lock framework**: hierarchy enforcement at construction, runtime
   inversion assertions, end-to-end scheduler run armed.
6. **Waiver pinning**: the waiver set can only shrink.
7. **_remove_by_identity**: the shared identity-removal helper and its
   duplicate-content regression (the PR 3 bug class).
"""

import json
import os

import pytest

from blaze_tpu import conf
from blaze_tpu.analysis import lint, locks, plan_verify
from blaze_tpu.ops import MemoryScanExec
from blaze_tpu.ops.fusion import FusedStageExec, optimize_plan
from blaze_tpu.runtime.metrics import _remove_by_identity
from blaze_tpu.schema import DataType, Field, Schema
from blaze_tpu.tpch import TPCH_SCHEMAS, build_query

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _empty_scans(schemas):
    return {n: MemoryScanExec([[], []], schemas[n]) for n in schemas}


def _write_pkg(tmp_path, name, source):
    pkg = tmp_path / name
    pkg.mkdir()
    p = pkg / "mod.py"
    p.write_text(source)
    return str(pkg)


# ------------------------------------------------- 1. the lint gate

def test_lint_clean_on_head():
    """THE gate: every AST rule + conf drift over the real package,
    waivers applied, must be clean — exactly what --lint asserts
    (minus the plan-corpus sweep, sampled in this module)."""
    findings = lint.lint_package()
    assert not findings, "\n".join(repr(f) for f in findings)


def test_lint_cli_smoke_subset():
    """The CLI plumbing end to end: the AST half of --lint, through
    the same entry the console uses (the full 250-plan sweep lives in
    the --lint CLI itself; the corpus sample below keeps tier-1
    fast)."""
    assert lint.lint_package(apply_waivers=True) == []
    # waivers actually FILTER something (the pinned exceptions exist)
    raw = lint.lint_package(apply_waivers=False)
    assert any(f.rule in ("purity.host-sync", "jit.uncached",
                          "lock.emit-under-lock") for f in raw)


# ------------------------------------------ 2. seeded rule violations

def test_seeded_trace_purity_violations(tmp_path):
    root = _write_pkg(tmp_path, "pkg_purity", """\
import time
import numpy as np

def _bad_body(cols, num_rows):
    n = int(cols[0].sum())          # device coercion
    host = np.asarray(cols[1])      # host pull
    t = time.perf_counter()         # wall clock baked into the trace
    return cols, n

def fine_host_helper(x):
    return int(x) + len(np.asarray(x))  # not a traced scope
""")
    rules = {f.rule for f in lint.lint_purity(root)}
    assert "purity.host-sync" in rules
    assert "purity.wall-clock" in rules
    # the non-traced helper contributed nothing
    assert all("fine_host_helper" not in f.symbol
               for f in lint.lint_purity(root))


def test_seeded_stray_jit(tmp_path):
    root = _write_pkg(tmp_path, "pkg_jit", """\
import jax

stray = jax.jit(lambda x: x + 1)   # module-level: bypasses the cache

def _build_good_kernel():
    @jax.jit
    def kernel(x):
        return x * 2
    return kernel

def registered():
    from blaze_tpu.runtime.kernel_cache import cached_kernel
    return cached_kernel(("k",), _build_good_kernel)
""")
    findings = lint.lint_uncached_jit(root)
    assert any(f.rule == "jit.uncached" and f.symbol == "<module>"
               for f in findings)
    # the registered builder's jit is NOT flagged
    assert all("_build_good_kernel" not in f.symbol for f in findings)


def test_seeded_emit_under_lock(tmp_path):
    root = _write_pkg(tmp_path, "pkg_emit", """\
import threading
from blaze_tpu.runtime import trace

_lock = threading.Lock()
_sink_lock = threading.Lock()

def bad():
    with _lock:
        trace.emit("spill", consumer="x", bytes=1)

def ok_sink():
    with _sink_lock:
        trace.record_kernel("k", 0, 0, 0)

def ok_outside():
    trace.emit("spill", consumer="x", bytes=1)
""")
    findings = lint.lint_emit_under_lock(root)
    assert any(f.rule == "lock.emit-under-lock" and f.symbol == "bad"
               for f in findings)
    assert all(f.symbol not in ("ok_sink", "ok_outside") for f in findings)


def test_seeded_static_lock_order(tmp_path):
    root = _write_pkg(tmp_path, "pkg_locks", """\
from blaze_tpu.analysis.locks import make_lock

_inner = make_lock("conf.store")
_outer = make_lock("monitor.registry")

def inverted():
    with _inner:
        with _outer:      # conf.store is INNERMOST: this inverts
            pass

def fine():
    with _outer:
        with _inner:
            pass
""")
    findings = locks.lint_lock_order(root)
    assert any(f.rule == "lock.static-order" for f in findings)
    assert all(f.line != 0 for f in findings)
    # only the inverted nesting is flagged
    assert len([f for f in findings if f.rule == "lock.static-order"]) == 1


def test_seeded_conf_drift(tmp_path):
    root = _write_pkg(tmp_path, "pkg_conf", """\
KNOB = "spark.blaze.notAKnob.definitelyUnregistered"
FAMILY_OK = "spark.blaze.enable.myop"
REAL_OK = "spark.blaze.batchSize"
""")
    findings = lint.lint_conf_registry(root)
    bad = [f for f in findings if f.rule == "conf.unregistered"]
    assert len(bad) == 1
    assert "notAKnob" in bad[0].symbol


def test_conf_registry_two_way_and_shape():
    """Registry ⊆ conf.py declarations and vice versa (the live gate
    --lint runs); dynamic prefix present; the new verify knobs are in."""
    reg = conf.load_conf_names()
    keys = set(reg["keys"])
    declared = set(conf.declared_entries())
    assert keys == declared, (keys ^ declared)
    assert "spark.blaze.enable." in reg["dynamic_prefixes"]
    assert {"spark.blaze.verify.plan", "spark.blaze.verify.locks"} <= keys


def test_conf_readme_table_complete():
    with open(os.path.join(REPO, "README.md")) as f:
        text = f.read()
    missing = [k for k in conf.registered_conf_keys()
               if k.startswith("spark.blaze.") and k not in text]
    assert not missing, f"README conf table missing: {missing}"


def test_every_conf_entry_has_a_reader():
    """A declared key that no module of the package reads is a
    setting that does nothing: every ``ConfEntry`` constant is named
    somewhere under ``blaze_tpu/`` outside ``conf.py`` itself."""
    import re

    pkg = os.path.join(REPO, "blaze_tpu")
    text = []
    for d, _, files in os.walk(pkg):
        for name in files:
            path = os.path.join(d, name)
            if name.endswith(".py") and path != os.path.join(pkg, "conf.py"):
                with open(path) as f:
                    text.append(f.read())
    text = "\n".join(text)
    unread = sorted(
        name for name, v in vars(conf).items()
        if isinstance(v, conf.ConfEntry)
        and not re.search(rf"\b{name}\b", text))
    assert unread == []


# --------------------------------- 3. plan-verifier negative tests

def _scan(n_parts=2, fields=("a", "b")):
    schema = Schema([Field(n, DataType.int64()) for n in fields])
    return MemoryScanExec([[] for _ in range(n_parts)], schema)


def test_verifier_catches_dropped_exchange():
    """FINAL grouped agg over a multi-partition child with NO hash
    exchange — the hand-corrupted 'dropped exchange' plan — is caught
    with the rule id and the offending node path."""
    from blaze_tpu.exprs.ir import Col
    from blaze_tpu.ops.agg import AggExec, AggFunction, AggMode, GroupingExpr

    partial = AggExec(_scan(2), AggMode.PARTIAL,
                      [GroupingExpr(Col("a"), "a")],
                      [AggFunction("sum", Col("b"), "s")])
    final = AggExec(partial, AggMode.FINAL,
                    [GroupingExpr(Col("a"), "a")],
                    [AggFunction("sum", Col("b"), "s")])
    findings = plan_verify.verify_plan(final)
    assert any(f.rule == "dist.final-agg" for f in findings), findings
    f = next(f for f in findings if f.rule == "dist.final-agg")
    assert f.path.startswith("root")
    assert "root" in repr(f) and "dist.final-agg" in repr(f)


def test_verifier_catches_ungrouped_final_over_partitions():
    from blaze_tpu.exprs.ir import Col
    from blaze_tpu.ops.agg import AggExec, AggFunction, AggMode

    partial = AggExec(_scan(2), AggMode.PARTIAL, [],
                      [AggFunction("sum", Col("b"), "s")])
    final = AggExec(partial, AggMode.FINAL, [],
                    [AggFunction("sum", Col("b"), "s")])
    findings = plan_verify.verify_plan(final)
    assert any(f.rule == "dist.final-scalar" for f in findings), findings


def test_verifier_accepts_exchange_and_single_partition():
    from blaze_tpu.exprs.ir import Col
    from blaze_tpu.ops.agg import AggExec, AggFunction, AggMode, GroupingExpr
    from blaze_tpu.parallel.exchange import NativeShuffleExchangeExec
    from blaze_tpu.parallel.shuffle import HashPartitioning

    partial = AggExec(_scan(2), AggMode.PARTIAL,
                      [GroupingExpr(Col("a"), "a")],
                      [AggFunction("sum", Col("b"), "s")])
    ex = NativeShuffleExchangeExec(partial, HashPartitioning([Col("a")], 2))
    final = AggExec(ex, AggMode.FINAL,
                    [GroupingExpr(Col("a"), "a")],
                    [AggFunction("sum", Col("b"), "s")])
    assert plan_verify.verify_plan(final) == []
    # single-partition child: any distribution is exact
    partial1 = AggExec(_scan(1), AggMode.PARTIAL,
                       [GroupingExpr(Col("a"), "a")],
                       [AggFunction("sum", Col("b"), "s")])
    final1 = AggExec(partial1, AggMode.FINAL,
                     [GroupingExpr(Col("a"), "a")],
                     [AggFunction("sum", Col("b"), "s")])
    assert plan_verify.verify_plan(final1) == []


def test_verifier_catches_schema_mismatched_edge():
    """A filter re-parented over a child missing its predicate column
    (the 'schema-mismatched edge' corruption) — caught with rule id +
    node path, since it would otherwise fail deep in kernel lowering
    or silently bind a wrong column."""
    from blaze_tpu.exprs.ir import BinOp, Col, Lit
    from blaze_tpu.ops.filter import FilterExec

    good = _scan(1, fields=("a", "b"))
    flt = FilterExec(good, BinOp(">", Col("a"), Lit(0, DataType.int64())))
    assert plan_verify.verify_plan(flt) == []
    flt.children[0] = _scan(1, fields=("x", "y"))  # corrupt the edge
    findings = plan_verify.verify_plan(flt)
    assert any(f.rule == "schema.edge" and "'a'" in f.message
               for f in findings), findings


def test_verifier_catches_missing_buffer_bottom():
    """A fused chain containing a whole-partition (window) op whose
    child is NOT a BufferPartitionExec — the 'missing buffer bottom'
    corruption — is caught; the correct construction passes."""
    from blaze_tpu.exprs.ir import Col
    from blaze_tpu.ops.fusion import BufferPartitionExec
    from blaze_tpu.ops.sort import SortField
    from blaze_tpu.ops.window import WindowExec, WindowFunction

    scan = _scan(1)
    win = WindowExec(scan, [WindowFunction("rank", "r")],
                     [Col("a")], [SortField(Col("b"))])
    fused_bad = FusedStageExec(scan, [win])
    findings = plan_verify.verify_plan(fused_bad)
    assert any(f.rule == "fusion.buffer-bottom" for f in findings), findings
    fused_ok = FusedStageExec(BufferPartitionExec(scan), [win])
    assert not [f for f in plan_verify.verify_plan(fused_ok)
                if f.rule == "fusion.buffer-bottom"]


def test_verifier_catches_lost_writer_schema(tmp_path):
    from blaze_tpu.exprs.ir import Col
    from blaze_tpu.parallel.shuffle import HashPartitioning, ShuffleWriterExec

    w = ShuffleWriterExec(_scan(1), HashPartitioning([Col("a")], 4),
                          str(tmp_path / "s.data"), str(tmp_path / "s.index"))
    w.absorb_traceable_chain()  # bare writer: fuses hash+sort
    assert w._fused_write is not None
    assert plan_verify.verify_plan(w) == []
    w._out_schema = None  # the corruption: schema lost after absorption
    findings = plan_verify.verify_plan(w)
    assert any(f.rule == "fusion.writer-schema" for f in findings), findings


def test_verifier_catches_impure_trace_key():
    class _BadTraceOp(MemoryScanExec):
        def trace_fn(self):
            return lambda cols, n: (cols, n)

        def trace_key(self):
            return ("bad", object())  # identity-bearing: ' at 0x...'

    schema = Schema([Field("a", DataType.int64())])
    node = _BadTraceOp([[]], schema)
    findings = plan_verify.verify_plan(node)
    assert any(f.rule == "fusion.trace-key" for f in findings), findings

    class _NoKeyOp(MemoryScanExec):
        def trace_fn(self):
            return lambda cols, n: (cols, n)

    findings = plan_verify.verify_plan(_NoKeyOp([[]], schema))
    assert any(f.rule == "fusion.trace-key" and "None" in f.message
               for f in findings), findings


def test_verifier_catches_unsorted_smj_child():
    """SMJ fed by a hash exchange with the sort DROPPED (the rewrite
    bug class — an exchange provably destroys row order) is caught on
    both sides; re-inserting the sorts passes.  A leaf-source child is
    accepted: its order is the caller's contract."""
    from blaze_tpu.exprs.ir import Col
    from blaze_tpu.ops.joins import JoinType, SortMergeJoinExec
    from blaze_tpu.ops.sort import SortExec, SortField
    from blaze_tpu.parallel.exchange import NativeShuffleExchangeExec
    from blaze_tpu.parallel.shuffle import HashPartitioning

    def exchanged(fields):
        return NativeShuffleExchangeExec(
            _scan(2, fields=fields), HashPartitioning([Col("k")], 2))

    smj = SortMergeJoinExec(exchanged(("k", "v1")), exchanged(("k", "v2")),
                            [Col("k")], [Col("k")], JoinType.INNER)
    findings = plan_verify.verify_plan(smj)
    assert sum(1 for f in findings if f.rule == "order.smj") == 2, findings
    assert any("destroys" in f.message for f in findings)
    sorted_smj = SortMergeJoinExec(
        SortExec(exchanged(("k", "v1")), [SortField(Col("k"))]),
        SortExec(exchanged(("k", "v2")), [SortField(Col("k"))]),
        [Col("k")], [Col("k")], JoinType.INNER)
    assert not [f for f in plan_verify.verify_plan(sorted_smj)
                if f.rule == "order.smj"]
    # leaf-source children: order is the caller's contract, accepted
    leaf_smj = SortMergeJoinExec(_scan(1, fields=("k", "v1")),
                                 _scan(1, fields=("k", "v2")),
                                 [Col("k")], [Col("k")], JoinType.INNER)
    assert not [f for f in plan_verify.verify_plan(leaf_smj)
                if f.rule == "order.smj"]


def test_verifier_catches_wrong_sort_keys_under_smj():
    """A sort IS there but on the wrong key — the prefix check."""
    from blaze_tpu.exprs.ir import Col
    from blaze_tpu.ops.joins import JoinType, SortMergeJoinExec
    from blaze_tpu.ops.sort import SortExec, SortField

    left = SortExec(_scan(1, fields=("k", "v1")), [SortField(Col("v1"))])
    right = SortExec(_scan(1, fields=("k", "v2")), [SortField(Col("k"))])
    smj = SortMergeJoinExec(left, right, [Col("k")], [Col("k")],
                            JoinType.INNER)
    findings = [f for f in plan_verify.verify_plan(smj)
                if f.rule == "order.smj"]
    assert len(findings) == 1 and "child 0" in findings[0].message


def test_verifier_catches_desc_and_reordered_sort_under_smj():
    """Direction and key order are part of what a streaming merge
    relies on: a DESC sort on the join key, or keys sorted (b, a) when
    the join needs (a, b), both break the merge exactly like a dropped
    sort (review finding)."""
    from blaze_tpu.exprs.ir import Col
    from blaze_tpu.ops.joins import JoinType, SortMergeJoinExec
    from blaze_tpu.ops.sort import SortExec, SortField

    def smj_with_left(left_sort_fields):
        left = SortExec(_scan(1, fields=("a", "b")), left_sort_fields)
        right = SortExec(_scan(1, fields=("a", "c")),
                         [SortField(Col("a"))])
        return SortMergeJoinExec(left, right, [Col("a")], [Col("a")],
                                 JoinType.INNER)

    desc = smj_with_left([SortField(Col("a"), ascending=False)])
    findings = [f for f in plan_verify.verify_plan(desc)
                if f.rule == "order.smj"]
    assert len(findings) == 1 and "child 0" in findings[0].message

    # two-key join sorted in the WRONG key order
    left = SortExec(_scan(1, fields=("a", "b")),
                    [SortField(Col("b")), SortField(Col("a"))])
    right = SortExec(_scan(1, fields=("a", "b")),
                     [SortField(Col("a")), SortField(Col("b"))])
    from blaze_tpu.ops.joins import SortMergeJoinExec as SMJ
    smj = SMJ(left, right, [Col("a"), Col("b")], [Col("a"), Col("b")],
              JoinType.INNER)
    findings = [f for f in plan_verify.verify_plan(smj)
                if f.rule == "order.smj"]
    assert len(findings) == 1 and "child 0" in findings[0].message


def test_ambiguous_lock_binding_dropped_not_misranked(tmp_path):
    """Two classes in one module both naming their lock ``self._lock``
    at DIFFERENT ranks: the static pass drops the ambiguous tail
    instead of checking it at an arbitrary rank (review finding) —
    the runtime assertion still covers those nestings."""
    root = _write_pkg(tmp_path, "pkg_ambig", """\
from blaze_tpu.analysis.locks import make_lock

class A:
    def __init__(self):
        self._lock = make_lock("metrics.set")

class B:
    def __init__(self):
        self._lock = make_lock("metrics.node")

    def nested(self, other):
        with self._lock:
            with other._lock:   # tail is ambiguous: must NOT be flagged
                pass

_outer = make_lock("monitor.registry")
_inner = make_lock("conf.store")

def still_checked():
    with _inner:
        with _outer:            # unambiguous names: still flagged
            pass
""")
    findings = [f for f in locks.lint_lock_order(root)
                if f.rule == "lock.static-order"]
    assert len(findings) == 1
    assert findings[0].symbol == "monitor.registry"


def test_verify_or_raise_is_the_execution_hook():
    """optimize_plan with spark.blaze.verify.plan armed (as the whole
    test suite runs, via conftest) raises PlanVerificationError on a
    corrupted plan — the execution hookpoint, not just a library."""
    from blaze_tpu.exprs.ir import Col
    from blaze_tpu.ops.agg import AggExec, AggFunction, AggMode, GroupingExpr

    partial = AggExec(_scan(2), AggMode.PARTIAL,
                      [GroupingExpr(Col("a"), "a")],
                      [AggFunction("sum", Col("b"), "s")])
    final = AggExec(partial, AggMode.FINAL,
                    [GroupingExpr(Col("a"), "a")],
                    [AggFunction("sum", Col("b"), "s")])
    assert bool(conf.VERIFY_PLAN.get()), "conftest must force this on"
    with pytest.raises(plan_verify.PlanVerificationError) as ei:
        optimize_plan(final)
    assert "dist.final-agg" in str(ei.value)


# ------------------------------------ 4. acceptance over real plans

@pytest.mark.parametrize("fused", [True, False])
def test_real_tpch_plans_verify_clean(fused):
    scans = _empty_scans(TPCH_SCHEMAS)
    prev = bool(conf.FUSION_ENABLE.get())
    conf.FUSION_ENABLE.set(fused)
    try:
        for name in ("q1", "q3", "q6"):
            plan = optimize_plan(build_query(name, scans, 2))
            assert plan_verify.verify_plan(plan) == [], name
    finally:
        conf.FUSION_ENABLE.set(prev)


@pytest.mark.parametrize("fused", [True, False])
def test_real_tpcds_plans_verify_clean(fused):
    from blaze_tpu.tpcds import TPCDS_SCHEMAS
    from blaze_tpu.tpcds import build_query as build_ds

    scans = _empty_scans(TPCDS_SCHEMAS)
    prev = bool(conf.FUSION_ENABLE.get())
    conf.FUSION_ENABLE.set(fused)
    try:
        for name in ("q6", "q36", "q47"):  # agg, window, stacked window
            plan = optimize_plan(build_ds(name, scans, 2))
            assert plan_verify.verify_plan(plan) == [], name
    finally:
        conf.FUSION_ENABLE.set(prev)


def test_fused_stage_trace_key_deterministic_across_builds():
    """Two independent builds of the same plan produce IDENTICAL
    FusedStageExec trace keys (the invariant that makes the fused
    program cache process-wide and the persistent compile cache
    reusable across tasks)."""
    scans = _empty_scans(TPCH_SCHEMAS)

    def fused_keys():
        plan = optimize_plan(build_query("q1", scans, 2))
        out = []

        def walk(n):
            if isinstance(n, FusedStageExec):
                out.append(n.trace_key())
            for c in n.children:
                walk(c)

        walk(plan)
        return out

    k1, k2 = fused_keys(), fused_keys()
    assert k1 == k2
    for k in k1:
        assert " at 0x" not in repr(k)
        hash(k)


# -------------------------------------------- 5. the lock framework

def test_make_lock_refuses_undeclared_names():
    with pytest.raises(ValueError, match="not declared in the hierarchy"):
        locks.make_lock("totally.new.lock")


def test_runtime_lock_order_assertion():
    outer = locks.make_lock("monitor.registry")
    inner = locks.make_lock("conf.store")
    locks.arm(True)
    try:
        with outer:
            with inner:  # inward: fine
                assert locks.held_names() == ["monitor.registry",
                                              "conf.store"]
        with inner:
            with pytest.raises(locks.LockOrderError, match="monitor.registry"):
                outer.acquire()
        # same-rank re-entry is an inversion too (self-deadlock /
        # sibling-instance cycles like consumer->consumer spill)
        other = locks.make_lock("conf.store")
        with inner:
            with pytest.raises(locks.LockOrderError):
                other.acquire()
    finally:
        locks.arm(False)
    assert locks.held_names() == []
    # disarmed: inversion passes silently (one bool read per acquire)
    with inner:
        with outer:
            pass


def test_release_while_disarmed_still_pops_held_stack():
    """Disarming mid-critical-section on ANOTHER thread (the chaos
    finally / suite teardown path) must not strand that thread's
    held-stack entry: release() pops unconditionally, so re-arming
    later cannot raise a spurious LockOrderError against a lock the
    thread no longer holds."""
    import threading

    lk = locks.make_lock("trace.log")
    acquired = threading.Event()
    disarmed = threading.Event()
    rearmed = threading.Event()
    errors = []

    def worker():
        try:
            lk.acquire()          # armed: pushed onto this thread's TLS
            acquired.set()
            assert disarmed.wait(5)
            lk.release()          # DISARMED now: must still pop
            assert rearmed.wait(5)
            with lk:              # armed again: stale entry would raise
                pass
        except BaseException as e:  # noqa: BLE001 — surface to the test
            errors.append(e)

    locks.arm(True)
    t = threading.Thread(target=worker)
    try:
        t.start()
        assert acquired.wait(5)
        locks.arm(False)
        disarmed.set()
        t.join(0.2)  # let the release land disarmed
        locks.arm(True)
        rearmed.set()
        t.join(5)
    finally:
        locks.arm(False)
        disarmed.set()
        rearmed.set()
        t.join(5)
    assert not errors, errors


def test_conf_literal_with_sentence_period_resolves():
    """An exact registered key captured with a trailing sentence
    period ('...set spark.blaze.batchSize.') must not produce a
    phantom conf.unregistered finding."""
    reg = conf.load_conf_names()
    keys = set(reg["keys"])
    prefixes = list(reg["dynamic_prefixes"])
    assert lint._literal_resolves("spark.blaze.batchSize.", keys, prefixes)
    assert not lint._literal_resolves("spark.blaze.nope.", keys, prefixes)


def test_lock_order_armed_end_to_end_scheduler_run():
    """A real multi-stage scheduler query (spills, async staging,
    metrics, trace arming off) under the runtime assertion: the
    declared hierarchy holds on every path the run crosses."""
    from blaze_tpu.runtime.scheduler import run_stages, split_stages
    from blaze_tpu.tpch.datagen import generate_all, table_to_batches

    data = generate_all(0.002)
    scans = {
        n: MemoryScanExec(
            table_to_batches(data[n], TPCH_SCHEMAS[n], 2, batch_rows=65536),
            TPCH_SCHEMAS[n])
        for n in TPCH_SCHEMAS
    }
    conf.VERIFY_LOCKS.set(True)
    locks.refresh()
    try:
        stages, mgr = split_stages(build_query("q6", scans, 2))
        rows = sum(b.num_rows for b in run_stages(stages, mgr))
        assert rows > 0
    finally:
        conf.VERIFY_LOCKS.set(False)
        locks.refresh()


def test_hierarchy_covers_every_make_lock_site():
    """Every make_lock("...") literal in the package names a declared
    hierarchy entry (construction would raise anyway — this pins the
    declared set against drift), and the named subsystems are all
    ranked."""
    import re

    names = set()
    pkg = os.path.join(REPO, "blaze_tpu")
    for root, _, files in os.walk(pkg):
        if os.path.basename(root) == "analysis":
            continue  # the checker's own docstrings use placeholders
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(root, fn)) as f:
                    names |= set(re.findall(r'make_lock\("([^"]+)"\)',
                                            f.read()))
    assert names <= set(locks.HIERARCHY), names - set(locks.HIERARCHY)
    # the subsystems the checker exists for are all present
    assert {"monitor.server", "monitor.registry", "shuffle.repartitioner",
            "kernel_cache.registry", "trace.sink", "dispatch.counters",
            "memmgr.manager", "conf.store"} <= names


# ------------------------------------------------ 6. waiver pinning

#: the REVIEWED waiver set — additions fail here by design (fix the
#: violation instead); removals are always allowed.  PR 8 removed the
#: two spill-path emit-under-lock waivers (ShuffleRepartitioner.spill,
#: _Window.spill): the spill.write fault probe moved to the consumer
#: spill() entry points OUTSIDE their state locks, so no emission rides
#: inside those critical sections anymore.
PINNED_WAIVERS = {
    ("purity.host-sync", "ops/window.py", "_window_body.*"),
    ("jit.uncached", "parallel/ici.py", "ici_shuffle*"),
    ("jit.uncached", "parallel/ici.py", "ici_range_shuffle*"),
    ("lock.emit-under-lock", "parallel/ici.py",
     "IciShuffleExchangeExec._materialize"),
    # emit reached ≤3 helper hops deep while holding a materialize-once
    # lock: each span is load-bearing (exactly-once drive) and every
    # reachable emit rides a trace lock ranked strictly inward of the
    # held lock — no cycle
    ("lock.emit-under-lock", "parallel/exchange.py",
     "NativeShuffleExchangeExec.materialize"),
    ("lock.emit-under-lock", "ops/joins/broadcast.py",
     "BroadcastJoinBuildHashMapExec._build_payload"),
    # the unmanaged (manager-None) branches touch a consumer no other
    # thread can reach; the managed branches all lock
    ("guard.unlocked", "runtime/memmgr.py", "MemConsumer.*"),
    # PR 15 (exception-flow passes, analysis/errflow.py): transports
    # that statically look like swallows but deliver the error onward
    # (the speculation attempt record, the async stager's deferred
    # surfacing), per-row value-parse handlers where nothing inside
    # the try can raise a control-flow/integrity error, and the worker
    # subprocess commit (no cancellation concept; attempt-qualified,
    # driver-verified)
    ("except.swallow", "runtime/speculation.py",
     "StageTaskRunner._spawn.body"),
    ("except.swallow", "parallel/shuffle.py", "_AsyncInserter._drain"),
    ("except.swallow", "ops/generate.py", "json_tuple_generator.gen"),
    ("except.swallow", "exprs/functions.py", "_to_date"),
    ("except.swallow", "exprs/json_path.py", "get_json_object"),
    ("except.swallow", "exprs/json_path.py", "parse_json"),
    ("commit.guard", "runtime/worker.py", "main"),
}


def test_waiver_file_can_only_shrink():
    waivers = lint.load_waivers()
    current = {(w["rule"], w["file"], w["symbol"]) for w in waivers}
    new = current - PINNED_WAIVERS
    assert not new, (
        f"new lint waivers {new} — fix the violation instead of waiving "
        f"it (or get the pinned set in tests/test_analysis.py reviewed)")
    for w in waivers:
        assert w.get("reason", "").strip(), f"waiver without reason: {w}"


def test_waiver_file_entries_still_needed():
    """A waiver whose violation no longer exists is stale — the set
    shrinks instead of accumulating dead exceptions."""
    raw = lint.lint_package(apply_waivers=False)
    for w in lint.load_waivers():
        hit = [f for f in raw if f.rule == w["rule"]
               and f.path.endswith(w["file"])]
        assert hit, f"stale waiver (violation gone — delete it): {w}"


# -------------------------------- 7. _remove_by_identity regression

def test_remove_by_identity_with_equal_duplicates():
    """The PR 3 bug class, pinned at the helper: two EQUAL-content
    entries; removal must evict the exact object, not a lookalike."""
    a = {"programs": 0}
    b = {"programs": 0}
    assert a == b and a is not b
    items = [a, b]
    assert _remove_by_identity(items, b)
    assert len(items) == 1 and items[0] is a
    assert not _remove_by_identity(items, b)  # already gone
    assert items[0] is a


def test_capture_scopes_survive_equal_content_siblings():
    """dispatch.capture + trace.kernel_capture both route through the
    shared helper: an inner scope with content EQUAL to the outer must
    not evict the outer on exit (duplicates exist exactly when nothing
    was recorded yet)."""
    from blaze_tpu.runtime import dispatch, trace

    with dispatch.capture() as outer:
        with dispatch.capture() as inner:
            pass  # inner == outer == {}
        dispatch.record("xla_dispatches")  # must still land on outer
    assert outer.get("xla_dispatches") == 1 and inner == {}

    with trace.kernel_capture() as osink:
        with trace.kernel_capture() as isink:
            pass
        trace.record_kernel("k", 1, 2, 3)
    assert "k" in osink and isink == {}
