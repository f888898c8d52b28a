"""Test config: 8 virtual CPU devices (SURVEY §4 — the XPU op-test harness
pattern: same suite runs on a simulated multi-device backend)."""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

# tests never touch an accelerator, whatever the environment defaults to
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(2024)
    yield


@pytest.fixture(autouse=True, scope="module")
def _reclaim_executables():
    """Every XLA:CPU executable mmaps JIT code pages; a full
    single-process run accumulates mappings toward the kernel's
    vm.max_map_count ceiling (65530 default) and segfaults inside
    backend_compile once mmap fails.  Modules don't share compiled
    programs (each builds fresh model/closure objects), so dropping the
    compile caches at module boundaries reclaims the pages without
    forcing recompiles."""
    yield
    import gc
    jax.clear_caches()
    gc.collect()
