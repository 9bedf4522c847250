"""Test env: force a virtual 8-device CPU backend.

Multi-chip sharding is tested on host CPU with 8 virtual devices (the
standard fake-backend trick, SURVEY §4e); the chip is exercised by
``chip_smoke.py`` through the chip tool instead.

A test must never take the chip, so the CPU is forced twice: in the
environment (every child a test spawns — bench children,
``tools/serve_proc.py`` — inherits ``JAX_PLATFORMS=cpu``) and through
``jax.config`` (this process, whatever the caller exported).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# Entry points the tests call in-process (cli.run, chip_smoke.main) point
# JAX's persistent compile cache at <checkout>/.jax_cache with a zero
# persistence threshold; in THIS process that would serialize and write
# every one of the suite's thousands of toy programs.  Children a test
# spawns still use the cache, as they would outside a test.
jax.config.update("jax_enable_compilation_cache", False)


# Markers (slow/http/chaos/mesh) are registered centrally in the
# repo-root pytest.ini so every invocation — including ones that bypass
# this conftest — knows them.


@pytest.fixture(autouse=True)
def _fresh_kernel_ledger():
    """``support._RUNTIME_DISABLED`` is process-wide BY DESIGN (a kernel
    that faulted at dispatch stays off across supervisor rebuilds), so a
    test that degrades an engine — on purpose, or because a dispatch
    raised for any other reason — would silently turn every later
    ``mixed_step="auto"`` engine in the run into the split tick."""
    yield
    from llm_np_cp_tpu.ops.pallas import support

    support._RUNTIME_DISABLED.clear()
    # ...and so is a probe's verdict (``lru_cache``).  A test that forces
    # probes to fail (``support._FORCE_FAIL``) and builds one more engine
    # before ``monkeypatch`` puts the flag back leaves "forced failure"
    # cached for every later test of its worker; this fixture is torn
    # down after ``monkeypatch``, and off the chip a probe costs nothing
    support._probe.cache_clear()


@pytest.fixture
def rng_np():
    return np.random.default_rng(0)
