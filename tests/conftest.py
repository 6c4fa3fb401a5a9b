"""Test bootstrap: an 8-device CPU jax, so the whole engine — including
multi-chip sharding — runs without TPU hardware (SURVEY.md §4 takeaway: mock
workers + CPU-backed engine tests mirror the reference's GPU-free CI tiers).
The tests set their own platform and device count, before jax is imported;
what a chip does is ``chip_smoke.py``'s business.
"""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# Persistent XLA compilation cache: the suite boots dozens of engines that
# all compile the SAME tiny-model programs (prefill buckets, decode
# megasteps, verify blocks), and XLA compile time dominates tier-1
# wall-clock (ROADMAP practical note — the full suite stopped fitting the
# harness timeout).  Caching compiled executables across engine boots AND
# across runs cuts that cost to one compile per distinct program.
# Parity-safe: a cache hit returns the identical executable.  Override with
# SMG_TEST_COMPILE_CACHE=0 to disable or =<dir> to relocate.
_cache = os.environ.get("SMG_TEST_COMPILE_CACHE", "")
if _cache != "0":
    jax.config.update(
        "jax_compilation_cache_dir",
        _cache or os.path.join(tempfile.gettempdir(), "smg-test-xla-cache"),
    )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected >=8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def tiny_cfg():
    from smg_tpu.models.config import tiny_test_config

    return tiny_test_config()
