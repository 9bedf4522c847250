"""Process-level JAX policy shared by every entry point.

Two rules, one place:

- **Platform.**  ``JAX_PLATFORMS`` in the environment decides which
  backend a process uses; no entry point re-pins it in code.  Tests and
  ``tools/compile_counter.py`` force the CPU themselves, because a test
  must never take the chip.
- **Compile cache.**  Every entry point (``cli.run``, ``chip_smoke.py``,
  ``bench.py`` children, ``tools/serve_proc.py``, ``train.py``) calls
  ``configure_compile_cache()`` before its first compile.  If
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
  is set in code; otherwise the cache lives at the FIXED
  ``<checkout>/.jax_cache`` (gitignored).  The directory is part of the
  cache key's neighbourhood — a temp/pid/timestamp path would never hit
  — so no other path is ever chosen.  JAX's default persistence
  threshold (only programs that took >= 1 s to compile) EXCLUDES serve
  steps: on the v5e host the unified tick compiles in 0.5–2 s per
  packed-width bucket (measured, PR 21: 5 of ~12 serve programs
  persisted), so the threshold is lowered to 0 — every program persists.

A chip belongs to ONE process.  ``require_uninitialized_backend`` is the
guard launchers call before starting a child that needs the device.
"""

from __future__ import annotations

import contextlib
import os
from pathlib import Path
from typing import Iterator

REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the one agreed
    directory and return it.  Idempotent; call before the first compile."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir  # JAX reads the variable itself
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


@contextlib.contextmanager
def compile_cache_bypassed() -> Iterator[None]:
    """What compiles inside is neither read from the persistent cache nor
    written to it.  For the ONE kind of program the cache hands back
    wrong (jax 0.9.0, the CPU and a v5e alike, PERF.md section 6, PR 54):
    an executable whose RESULT has another layout than the device's
    default — ``jax.device_put(x, Format(layout))`` is one, a jitted
    identity with that out-layout — comes back from the cache writing the
    same bytes but labelling its result with the default layout, so every
    reader takes a transposed weight for a plain one.  A program whose
    PARAMETERS have such layouts survives the cache (so the step's
    programs stay cached).  The switch is JAX's own, process-wide, and
    read once a process: flipping it means resetting the cache object on
    both sides (a compile on another thread meanwhile skips the cache,
    nothing worse)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def require_uninitialized_backend(what: str) -> None:
    """Raise if this process already holds a JAX backend: a parent that
    has touched JAX owns the chip, and ``what`` (a child that needs it)
    would fail or hang."""
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            f"{what}: this process has already initialised a JAX backend "
            "and therefore holds the device; start device-owning children "
            "from a parent that has not touched JAX"
        )
