#!/usr/bin/env python3
"""Device time of the forms a compaction can take its kept-row indices
by, and of the forms an exchange writer can count its rows per
partition by, at the capacities the engine's batches come in.  Not a
cell: nothing in ``BENCHMARK.json`` names it and ``bench/run.py`` never runs
it; its readings are ``PERF.md``'s (section 6).

    python3 tests/bench_harness/chip_compact_forms.py [--caps 1024,65536] [--out FILE]

Each form is its own program, launched ``--reps`` times over each of
eight masks inside one profiler trace; a form's ``device_ms`` is the
union of its device operations in that trace (``bench/trace_reduce``)
a launch, and ``top_op`` its largest operation (both null off a TPU).
Every form's result is checked against ``jnp.nonzero``'s, or
``np.bincount``'s, on the same input first.  Forms:

- ``nonzero``: ``jnp.nonzero(keep, size=cap)`` — a cumsum, a bincount
  (a scatter-add of every row; int64 under x64) and a cumsum;
- ``sort``: ``ops/filter.kept_indices`` — one int32 ``lax.sort`` of
  ``where(keep, row, cap)``;
- ``search``, ``search_unrolled``: an int32 inclusive cumsum of
  ``keep`` and ``searchsorted`` of ``j + 1`` in it (``method="scan"``
  / ``"scan_unrolled"``);
- ``compact8_nonzero``, ``compact8_sort``: the whole compaction of 8
  int64 columns with validity, by the first index form and by the
  program's own ``compact_columns``;
- ``counts_segment_sum``, ``counts_sorted``: rows per partition of a
  pid-sorted batch, by ``jax.ops.segment_sum`` of int64 ones (a
  scatter-add) and by ``parallel/shuffle._sorted_counts``.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CAPS = (1024, 8192, 65536, 262144)
N_OUTS = (4, 200)


def _forms():
    import jax
    import jax.numpy as jnp

    from blaze_tpu.batch import Column
    from blaze_tpu.ops.filter import compact_columns, kept_indices
    from blaze_tpu.schema import DataType

    def nonzero(keep):
        return jnp.nonzero(keep, size=keep.shape[0], fill_value=0)[0].astype(jnp.int32)

    def search(method):
        def form(keep):
            cap = keep.shape[0]
            cs = jnp.cumsum(keep.astype(jnp.int32))
            pos = jnp.searchsorted(cs, jax.lax.iota(jnp.int32, cap) + 1, side="left",
                                   method=method).astype(jnp.int32)
            return jnp.where(pos < cap, pos, jnp.int32(0))
        return form

    def compact8(index_of):
        def form(keep):
            cap = keep.shape[0]
            data = jax.lax.iota(jnp.int64, cap)
            cols = tuple(Column(DataType.int64(), data + k, keep) for k in range(8))
            if index_of is None:
                out, _ = compact_columns(cols, keep)
            else:
                idx = index_of(keep)
                live = jnp.arange(cap) < jnp.sum(keep.astype(jnp.int32))
                out = tuple(Column(c.dtype, jnp.take(c.data, idx), jnp.take(c.validity, idx) & live)
                            for c in cols)
            return tuple((c.data, c.validity) for c in out)
        return form

    return {
        "nonzero": nonzero,
        "sort": lambda keep: kept_indices(keep)[0],
        "search": search("scan"),
        "search_unrolled": search("scan_unrolled"),
        "compact8_nonzero": compact8(nonzero),
        "compact8_sort": compact8(None),
    }


def _count_forms(n_out):
    import jax
    import jax.numpy as jnp

    from blaze_tpu.parallel.shuffle import _sorted_counts

    def segment_sum(skey):
        live = skey < n_out
        return jax.ops.segment_sum(live.astype(jnp.int64),
                                   jnp.clip(skey, 0, n_out - 1).astype(jnp.int32),
                                   num_segments=n_out)

    return {"counts_segment_sum": segment_sum,
            "counts_sorted": lambda skey: _sorted_counts(skey, n_out)}


def _device_ms(fn, args, reps):
    """Device milliseconds of one launch of ``fn``, over ``reps`` launches
    on each of ``args``, read from a profiler trace; and its largest
    device operation."""
    import glob
    import shutil
    import tempfile

    import jax

    from bench import trace_reduce

    prog = jax.jit(fn)
    for a in args:  # compiled and warm before the trace opens
        jax.block_until_ready(prog(a))
    trace_dir = tempfile.mkdtemp(prefix="compact_forms_")
    try:
        jax.profiler.start_trace(trace_dir)
        for _ in range(reps):
            for a in args:
                out = prog(a)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        reduced = trace_reduce.reduce_file(found[0])
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if reduced is None:  # no TPU operation in the trace
        return None, None
    return reduced["busy_s"] * 1e3 / (reps * len(args)), reduced["device_ops"][0]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--caps", default=",".join(str(c) for c in CAPS))
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--out", default=None, help="also write the lines here")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import blaze_tpu  # noqa: F401 — x64 on, as in the engine

    device = jax.devices()[0]
    stamp = {"platform": device.platform, "kind": device.device_kind}
    forms = _forms()
    lines = []

    def emit(line):
        line = dict(line, device=stamp)
        lines.append(line)
        print(json.dumps(line), flush=True)

    for cap in (int(c) for c in args.caps.split(",")):
        rng = np.random.default_rng(cap)
        masks = [jnp.asarray(rng.random(cap) < 0.5) for _ in range(8)]
        want = np.asarray(forms["nonzero"](masks[0]))
        for name, fn in forms.items():
            if not name.startswith("compact8"):
                assert np.array_equal(np.asarray(jax.jit(fn)(masks[0])), want), (name, cap)
            ms, top = _device_ms(fn, masks, args.reps)
            emit({"form": name, "cap": cap, "device_ms": ms, "top_op": top})
        for n_out in N_OUTS:
            # pid-sorted keys, ``n_out`` marking a dead row
            pids = [np.sort(rng.integers(0, n_out + 1, cap).astype(np.uint32)) for _ in range(8)]
            keys = [jnp.asarray(p) for p in pids]
            for name, fn in _count_forms(n_out).items():
                want = np.bincount(pids[0], minlength=n_out + 1)[:n_out]
                assert np.array_equal(np.asarray(jax.jit(fn)(keys[0])), want), (name, cap, n_out)
                ms, top = _device_ms(fn, keys, args.reps)
                emit({"form": name, "cap": cap, "n_out": n_out, "device_ms": ms, "top_op": top})
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(x) + "\n" for x in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
