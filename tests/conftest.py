"""Test harness configuration.

Per the build contract, all tests run on a virtual 8-device CPU mesh so
multi-chip sharding is exercised without TPU hardware; the driver
separately dry-runs the multi-chip path and benches on a real chip.

This mirrors the reference's test strategy (SURVEY.md §4): unit tests
run the operators "pure native" with the JVM bridge stubbed by absence;
here kernels run pure-JAX with the gateway absent.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Tests compile for the CPU and carry nothing to a later run: the
# persistent compilation cache stays off in this process and in every
# child it starts (pool workers, CLI children), which otherwise place
# it at <checkout>/.jax_cache (runtime/kernel_cache.py).
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402

# Tests run on the CPU backend whatever the machine holds: the config
# (not only the env var) pins it, so a suite started on a host with a
# chip never claims it.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


def _ensure_native_built() -> None:
    """A fresh checkout has no native/build (gitignored build output);
    several suites (gateway FFI, UDF wire, batch serde differentials)
    hard-require libblaze_tpu_native.so.  Build it once up front with
    the baked-in toolchain instead of failing 40 tests in."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lib = os.path.join(repo, "native", "build", "libblaze_tpu_native.so")
    if os.path.exists(lib) or os.environ.get("BLAZE_TPU_NATIVE_LIB"):
        return
    src = os.path.join(repo, "native")
    try:
        subprocess.run(["cmake", "-B", "build", "-G", "Ninja",
                        "-DCMAKE_BUILD_TYPE=Release"], cwd=src, check=True,
                       capture_output=True, timeout=300)
        subprocess.run(["ninja", "-C", "build"], cwd=src, check=True,
                       capture_output=True, timeout=600)
    except Exception as e:  # noqa: BLE001 — tests that need the lib
        print(f"conftest: native build failed ({e}); FFI tests will fail")


_ensure_native_built()


import pytest

# Per the static-analysis contract (ISSUE 6): the plan verifier runs
# over every optimized plan in EVERY test — any plan a test executes
# through optimize_plan/run_task that breaks a structural invariant
# (schema edge, distribution/ordering prerequisite, fusion invariant)
# fails loudly here instead of producing wrong answers.
from blaze_tpu import conf as _blaze_conf  # noqa: E402

_blaze_conf.VERIFY_PLAN.set(True)


@pytest.fixture
def interpret():
    """Pallas kernels in interpret mode for one test: off a TPU nothing
    else interprets a kernel (kernels/pallas_ops.force_interpret)."""
    from blaze_tpu.kernels import pallas_ops

    pallas_ops.force_interpret(True)
    yield
    pallas_ops.force_interpret(False)


@pytest.fixture(autouse=True, scope="module")
def _clear_compiled_caches_between_modules():
    """Free compiled XLA executables between test MODULES.

    jaxlib's CPU backend segfaults inside backend_compile_and_load
    once enough compiled programs accumulate in one process (~44 slow
    differential tests in; deterministic, single-threaded, independent
    of thread stack size).  Per-module cache clearing keeps the full
    single-process `pytest tests/` run under that ceiling at the cost
    of recompiling shared kernels per module."""
    yield
    import jax

    from blaze_tpu.ops.joins.broadcast import clear_join_map_cache
    from blaze_tpu.runtime.kernel_cache import clear_kernel_cache

    clear_kernel_cache()
    clear_join_map_cache()
    jax.clear_caches()
