"""The bring-up contract, as far as a CPU can hold it: ``chip_smoke.py``
never passes without a TPU, the compile cache is placed from outside,
and no Pallas failure, compiler refusal or missing backend is turned
into a quieter path."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from blaze_tpu import conf
from blaze_tpu.kernels import pallas_ops
from blaze_tpu.runtime import kernel_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(*args, cwd=REPO, script=SMOKE, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=timeout)
    return out.returncode, out.stdout.strip().splitlines(), out.stderr


# ------------------------------------------------------- chip_smoke.py

def test_smoke_without_a_tpu_fails_at_once():
    rc, lines, _ = _smoke()
    assert rc != 0
    verdict = json.loads(lines[-1])
    assert verdict["ok"] is False
    assert verdict["device"]["platform"] == "cpu"
    assert "no TPU" in verdict["error"]
    # at once: no phase ran, so no note precedes the verdict
    assert len(lines) == 1


def test_smoke_rehearsal_runs_every_phase_and_never_passes():
    rc, lines, err = _smoke("--rehearse", "--scale", "0.002")
    assert rc != 0, err[-2000:]
    verdict = json.loads(lines[-1])
    assert verdict["ok"] is False and "rehearsal" in verdict["error"], err[-2000:]
    notes = {n["phase"]: n for n in map(json.loads, lines[:-1])}
    assert notes["start"]["rehearse"] is True
    assert notes["start"]["pallas_available"] is False  # not forced, not a TPU
    for q in ("q6", "q1", "q3"):
        cold, warm = notes[f"{q}_cold"], notes[f"{q}_warm"]
        assert cold["oracle"] == warm["oracle"] == "exact"
        assert cold["compiles"] > 0 and warm["compiles"] == 0
        assert warm["programs"] > 0 and warm["rows_out"] == cold["rows_out"]
    assert not any(notes["summary"]["degraded"].values())


def test_smoke_alone_in_a_directory_fails_and_prints_no_result(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    rc, lines, err = _smoke(cwd=str(tmp_path), script=str(alone))
    assert rc != 0 and lines == []
    assert "blaze_tpu" in err


# ---------------------------------------------- compile-cache placement

@pytest.fixture
def config_updates(monkeypatch):
    """Record (and do not apply) what our code sets on jax.config."""
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.__setitem__(name, value))
    return seen


def test_cache_dir_from_the_environment_is_not_touched(monkeypatch,
                                                       config_updates):
    monkeypatch.setenv(kernel_cache.CACHE_DIR_ENV, "/placed/from/outside")
    monkeypatch.setattr(conf.XLA_CACHE_DIR, "get", lambda: "/from/conf")
    assert kernel_cache.enable_persistent_cache() == "/placed/from/outside"
    # JAX reads the variable itself: we set the thresholds and nothing else
    assert "jax_compilation_cache_dir" not in config_updates
    assert config_updates == {
        "jax_persistent_cache_min_compile_time_secs": 0,
        "jax_persistent_cache_min_entry_size_bytes": 0}


def test_cache_dir_from_conf_when_the_environment_is_silent(monkeypatch,
                                                            config_updates):
    monkeypatch.delenv(kernel_cache.CACHE_DIR_ENV, raising=False)
    monkeypatch.setattr(conf.XLA_CACHE_DIR, "get", lambda: "/from/conf")
    assert kernel_cache.enable_persistent_cache() == "/from/conf"
    assert config_updates["jax_compilation_cache_dir"] == "/from/conf"


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout(monkeypatch,
                                                            config_updates):
    monkeypatch.delenv(kernel_cache.CACHE_DIR_ENV, raising=False)
    monkeypatch.setattr(conf.XLA_CACHE_DIR, "get", lambda: "")
    fixed = os.path.join(REPO, ".jax_cache")
    assert kernel_cache.enable_persistent_cache() == fixed
    assert config_updates["jax_compilation_cache_dir"] == fixed
    # the path is part of the cache key: twice the same, nothing of a
    # temp name, pid or time in it
    assert kernel_cache.checkout_cache_dir() == fixed
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ------------------------------------------- no fallback hides a fault

def test_interpret_mode_only_when_forced():
    assert jax.default_backend() == "cpu"
    assert not pallas_ops._interpret() and not pallas_ops.available()
    pallas_ops.force_interpret(True)
    try:
        assert pallas_ops._interpret() and pallas_ops.available()
    finally:
        pallas_ops.force_interpret(False)
    assert not pallas_ops._interpret()


def test_x32_scope_is_the_public_context_manager():
    assert jax.config.jax_enable_x64
    with pallas_ops._x32():
        assert not jax.config.jax_enable_x64
    assert jax.config.jax_enable_x64


def _shuffle_once(key_type, n_out):
    from blaze_tpu.batch import batch_from_pydict, batch_to_pydict
    from blaze_tpu.exprs import col
    from blaze_tpu.ops import MemoryScanExec
    from blaze_tpu.parallel import HashPartitioning, NativeShuffleExchangeExec
    from blaze_tpu.runtime.context import TaskContext
    from blaze_tpu.schema import DataType, Field, Schema

    schema = Schema([Field("k", key_type), Field("v", DataType.int32())])
    keys = [f"k{i % 7}" for i in range(50)] if key_type.is_string \
        else list(range(50))
    src = MemoryScanExec(
        [[batch_from_pydict({"k": keys, "v": list(range(50))}, schema)]],
        schema)
    ex = NativeShuffleExchangeExec(src, HashPartitioning([col("k")], n_out))
    rows = []
    for p in range(n_out):
        for b in ex.execute(p, TaskContext(p, n_out)):
            rows.extend(batch_to_pydict(b)["v"])
    return sorted(rows)


@pytest.mark.parametrize("n_out,error", [
    (5, RuntimeError("Mosaic failed to legalize operation")),
    # Mosaic's own NotImplementedError is a failure, not a key dtype
    (6, NotImplementedError("Unimplemented primitive in Pallas TPU lowering")),
])
def test_failing_pallas_pid_kernel_raises_through_the_shuffle_writer(
        interpret, monkeypatch, n_out, error):
    from blaze_tpu.schema import DataType

    def broken(*a, **k):
        raise error

    monkeypatch.setattr(pallas_ops, "murmur3_pids", broken)
    with pytest.raises(type(error), match=str(error)):
        _shuffle_once(DataType.int64(), n_out)


def test_string_keys_still_take_the_xla_hash_quietly(interpret,
                                                     monkeypatch):
    """Dispatch on type, not a failure: strings have no word-plane form
    (``key_type_supported``), so the writer never builds the kernel."""
    from blaze_tpu.schema import DataType

    calls = []
    real = pallas_ops.murmur3_pids
    monkeypatch.setattr(pallas_ops, "murmur3_pids",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    assert not pallas_ops.key_type_supported(DataType.string(8))
    assert pallas_ops.key_type_supported(DataType.float64())
    assert _shuffle_once(DataType.string(8), 3) == list(range(50))
    assert not calls


@pytest.mark.parametrize("message,is_oom", [
    # what the chip's compiler said of a Pallas kernel over scoped VMEM
    ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
     "allocating on stack for %sorted_lookup.1 ... Scoped allocation with "
     "size 26.95M and limit 16.00M exceeded scoped vmem limit", False),
    ("RESOURCE_EXHAUSTED: Ran out of memory in memory space smem", False),
    # device memory at run time: the ladder's business
    ("RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
     "allocate 1.20G. That was not possible. There are 512.00M free.", True),
    ("RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm. Used "
     "17.2G of 15.75G hbm.", True),
    ("INVALID_ARGUMENT: something else", False),
])
def test_a_compiler_refusal_is_not_device_oom(message, is_oom):
    from blaze_tpu.runtime.oom import is_resource_exhausted

    assert is_resource_exhausted(RuntimeError(message)) is is_oom


def test_vmem_refusal_propagates_through_the_dispatch_guard():
    """``dispatch._oom_call`` must not spill-and-retry around a kernel
    the compiler refused: one call, the refusal out, no recovery."""
    from blaze_tpu.runtime import dispatch

    calls = []

    def refused():
        calls.append(1)
        raise RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in "
                           "memory space vmem while allocating on stack")

    with dispatch.capture() as counted:
        with pytest.raises(RuntimeError, match="vmem"):
            dispatch._oom_call(refused, "join_probe")
    assert calls == [1] and not counted.get("oom_recoveries")
