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

from llm_np_cp_tpu.utils.runtime import configure_compile_cache  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# The suite is one more entry point: it keeps JAX's persistent compile
# cache where cli.run, chip_smoke.main and every child a test spawns keep
# it (<checkout>/.jax_cache).  Most of a test's time is XLA compiling a
# toy program that another test, in this worker or the next, has already
# compiled: from a cold cache the serve files run a seventh faster than
# with no cache (PR 46, ROADMAP D9).  It also has to be ON for
# ``tools.compile_counter.CompileCounter`` to see anything: the event it
# counts is recorded hit or miss, but not with the cache switched off.
configure_compile_cache()


# Markers (slow/http/chaos/mesh) are registered centrally in the
# repo-root pytest.ini so every invocation — including ones that bypass
# this conftest — knows them.


@pytest.fixture(autouse=True)
def _fresh_kernel_ledger():
    """``support._RUNTIME_DISABLED`` is process-wide BY DESIGN (a kernel
    that faulted at dispatch stays off across supervisor rebuilds), so a
    test that degrades an engine — on purpose, or because a dispatch
    raised for any other reason — would silently take the Pallas kernels
    away from every later engine of the run: they would serve, and be
    tested, on the XLA twins."""
    yield
    from llm_np_cp_tpu.ops.pallas import support

    support._RUNTIME_DISABLED.clear()
    # ...and so is a probe's verdict (``lru_cache``).  A test that forces
    # probes to fail (``support._FORCE_FAIL``) and builds one more engine
    # before ``monkeypatch`` puts the flag back leaves "forced failure"
    # cached for every later test of its worker; this fixture is torn
    # down after ``monkeypatch``, and off the chip a probe costs nothing
    support._probe.cache_clear()
    # ...and the "said once a process" ledger of the fallback warnings
    support._WARNED.clear()


@pytest.fixture
def rng_np():
    return np.random.default_rng(0)
