"""Test configuration.

Mirrors the reference's distributed-test philosophy (SURVEY.md §4.2): tests
run on a virtual 8-device CPU mesh via
`--xla_force_host_platform_device_count=8`, the TPU analogue of
DummyTransport / Spark local[n] — multi-chip semantics validated in one
process with no real hardware.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# The persistent compilation cache stays off for the suite so that a run
# never depends on what an earlier run left on disk. It used to be off
# for a worse reason: under the jaxlib of PR 1, cache-served executables
# run with donated buffers corrupted the glibc heap
# (tests/test_attention_elastic.py aborted with "corrupted double-linked
# list"). Re-checked on jaxlib 0.9.0 (PR 21): with
# JAX_COMPILATION_CACHE_DIR set, MIN_ENTRY_SIZE=-1 and
# MIN_COMPILE_TIME=0.2, that file plus test_generation,
# test_resilient_training and test_elastic_training pass on both the
# write and the read path, and on the chip `chip_smoke.py`'s engines
# donate their pools every step out of cache-served executables. The
# crash does not reproduce; entry points may rely on the cache
# (deeplearning4j_tpu/compile_cache.py).

import jax  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture
def np_rng():
    return np.random.RandomState(12345)
