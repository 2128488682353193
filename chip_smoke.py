#!/usr/bin/env python3
"""The quickest proof that blaze-tpu still starts on the chip.

One process, one chip.  Generates TPC-H at ``--scale`` from ``--seed``,
then drives q06, q01 and q03 through the path the
CLI's ``--scheduler`` mode uses — ``tpch.build_query`` ->
``runtime.scheduler.split_stages`` -> ``run_stages`` (every stage
decoded from TaskDefinition bytes), 4 partitions, 65,536-row batches,
no worker pool — once cold and once warm each, and compares every
result with the numpy oracles in ``blaze_tpu/tpch/oracle.py``, digit
for digit.

The target is SF1 (``--scale 1``), and SF1 passes on the v5e — but
cold, q03 alone compiles for over 20 minutes there (XLA:TPU compiles a
sort in time linear in rows x operands, and the FINAL agg sorts four u64
key words at its partition's capacity bucket; PERF.md, PR 22).  The
default is the largest scale whose COLD run fits this script's 1,200 s:
SF0.5, with the same batch shapes and every program class of SF1.

It fails unless JAX's first device is a TPU: there is no CPU,
interpret or degraded path that still reads as a pass.  ``--rehearse``
runs the same phases on whatever backend there is (the CPU sandbox)
and ALWAYS ends ``"ok": false`` with a non-zero exit.

Earlier lines of stdout are one JSON object per phase: notes of one
run, not metrics.  The last line is the verdict:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

import argparse
import inspect
import json
import sys
import time
import traceback

DEFAULT_SCALE = 0.5  # SF1 is the target; see the module docstring
N_PARTS = 4
BATCH_ROWS = 65536  # the CLI's batch size (__main__._load_suite)
DEGRADED = ("oom_recoveries", "batch_downshifts", "eager_fallbacks")


def note(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check_q6(got, exp):
    assert len(got["revenue"]) == 1, got
    assert got["revenue"][0] == exp, (got["revenue"][0], exp)


def check_q1(got, exp):
    keys = list(zip(got["l_returnflag"], got["l_linestatus"]))
    assert keys == sorted(keys), "q1 must be ordered by returnflag, linestatus"
    assert set(keys) == set(exp), (keys, sorted(exp))
    for i, k in enumerate(keys):
        for m, want in exp[k].items():
            assert got[m][i] == want, (k, m, got[m][i], want)


def check_q3(got, exp):
    rows = list(zip(got["l_orderkey"], got["revenue"],
                    got["o_orderdate"], got["o_shippriority"]))
    assert len(rows) == len(exp), (len(rows), len(exp))
    # ties on equal revenue+date may break differently: compare as sets
    assert {(r[0], r[1]) for r in rows} == {(r[0], r[1]) for r in exp}
    revenue = [r[1] for r in rows]
    assert revenue == sorted(revenue, reverse=True), revenue


# the queries, in the order they run
CHECKS = {"q6": check_q6, "q1": check_q1, "q3": check_q3}


def run_query(name, scans, platform):
    """One pass of one query through split_stages/run_stages.  Returns
    (columns as python values, rows out, dispatch counters of the pass,
    seconds)."""
    from blaze_tpu.batch import batch_to_pydict
    from blaze_tpu.runtime import dispatch
    from blaze_tpu.runtime.scheduler import run_stages, split_stages
    from blaze_tpu.tpch import build_query

    t0 = time.perf_counter()
    # a fresh plan per pass: exchanges memoize their map side per
    # exec instance, so a reused plan would only re-run the reduce side
    plan = build_query(name, scans, N_PARTS)
    got = {f.name: [] for f in plan.schema.fields}
    rows = 0
    with dispatch.capture() as counted:
        stages, manager = split_stages(plan)
        # one attempt per task: a retry would hide a first failure
        for b in run_stages(stages, manager, max_task_attempts=1):
            for c in b.columns:
                where = {d.platform for d in c.data.devices()}
                assert where == {platform}, (name, where, platform)
            rows += b.num_rows
            for k, v in batch_to_pydict(b).items():
                got[k].extend(v)  # D2H: the pass ends with the device drained
    return got, rows, dict(counted), time.perf_counter() - t0


def run(args, device):
    import jax

    from blaze_tpu import native
    from blaze_tpu.kernels import pallas_ops
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.runtime import dispatch
    from blaze_tpu.runtime.kernel_cache import enable_persistent_cache
    from blaze_tpu.tpch import TPCH_SCHEMAS, oracle
    from blaze_tpu.tpch.datagen import generate_all, table_to_batches

    platform = device["platform"]
    if args.seed is None:
        args.seed = inspect.signature(generate_all).parameters["seed"].default
    note("start", device=device, scale=args.scale, seed=args.seed,
         rehearse=args.rehearse, x64=bool(jax.config.jax_enable_x64),
         cache_dir=enable_persistent_cache(),
         native_lib=native.available(),
         pallas_available=pallas_ops.available())
    # the Pallas kernels are part of the chip path: on a TPU they must
    # be on, and nothing but force_interpret may turn them on elsewhere
    assert pallas_ops.available() == (platform == "tpu")

    t0 = time.perf_counter()
    data = generate_all(args.scale, args.seed)
    # host tables -> per-partition host batches, staged to the device
    # by the scan as the query runs, exactly as the CLI builds them
    scans = {
        name: MemoryScanExec(
            table_to_batches(data[name], TPCH_SCHEMAS[name], N_PARTS,
                             batch_rows=BATCH_ROWS),
            TPCH_SCHEMAS[name])
        for name in TPCH_SCHEMAS
    }
    rows_in = {name: int(next(iter(data[name].values()))[0].shape[0])
               for name in ("lineitem", "orders", "customer")}
    note("datagen", seconds=round(time.perf_counter() - t0, 3),
         rows=rows_in)

    before = dispatch.counters()
    for name, check in CHECKS.items():
        t0 = time.perf_counter()
        expected = getattr(oracle, "oracle_" + name)(data)
        note(f"{name}_oracle", seconds=round(time.perf_counter() - t0, 3))
        for temp in ("cold", "warm"):
            got, rows_out, counted, seconds = run_query(name, scans, platform)
            check(got, expected)
            stats = jax.devices()[0].memory_stats() or {}
            note(f"{name}_{temp}", seconds=round(seconds, 3),
                 rows_out=rows_out,
                 compiles=counted.get("xla_compiles", 0),
                 compile_ms=counted.get("compile_ms", 0),
                 programs=counted.get("xla_dispatches", 0),
                 oracle="exact",
                 peak_bytes_in_use=stats.get("peak_bytes_in_use"))
            if temp == "warm":
                assert counted.get("xla_compiles", 0) == 0, (name, counted)

    after = dispatch.counters()
    degraded = {k: after.get(k, 0) - before.get(k, 0) for k in DEGRADED}
    built = pallas_ops._build_murmur3_pids.cache_info().currsize
    note("summary", degraded=degraded, pallas_pid_kernels_built=built,
         q3_exchange_hash=("pallas murmur3_pids" if built else
                           "xla hash inside the tier-5 fused write program; "
                           "no pallas kernel on this path"))
    assert not any(degraded.values()), degraded


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, default=DEFAULT_SCALE,
                    help="TPC-H scale factor (default %(default)s: the "
                         "largest whose cold run fits 1,200 s; 1 is the "
                         "target; smaller only for a rehearsal)")
    ap.add_argument("--seed", type=int, default=None,
                    help="datagen seed (default the datagen's own)")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases on whatever backend there is; "
                         "never passes")
    args = ap.parse_args()

    import jax

    import blaze_tpu  # noqa: F401 — turns x64 on before any array exists

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    verdict = {"ok": False, "device": device}
    if device["platform"] != "tpu" and not args.rehearse:
        verdict["error"] = "no TPU: jax.devices()[0] is " + str(devices[0])
    else:
        try:
            run(args, device)
            if args.rehearse:
                verdict["error"] = "rehearsal: every phase passed, nothing is claimed"
            else:
                verdict["ok"] = True
        except BaseException as e:  # the verdict line is owed on any exit
            traceback.print_exc()
            verdict["error"] = f"{type(e).__name__}: {e}"[:500]
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
