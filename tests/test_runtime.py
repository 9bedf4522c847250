"""The process-level JAX policy (utils/runtime.py): where the compile
cache lives, and that no entry point re-pins the platform."""

import re
from pathlib import Path

import jax
import pytest

from llm_np_cp_tpu.utils import runtime

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_dir_from_environment_sets_nothing_in_code(
        monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.configure_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: no directory is set in code
    assert "jax_compilation_cache_dir" not in dict(config_updates)


def test_cache_dir_defaults_to_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert runtime.configure_compile_cache() == want
    assert dict(config_updates) == {
        "jax_compilation_cache_dir": want,
        # the default 1 s threshold would exclude sub-second serve steps
        "jax_persistent_cache_min_compile_time_secs": 0.0,
    }
    # fixed: a second call (another entry point, another process) agrees
    assert runtime.configure_compile_cache() == want


def test_uninitialized_backend_guard():
    jax.devices()  # this process holds a backend now
    with pytest.raises(RuntimeError, match="already initialised"):
        runtime.require_uninitialized_backend("a child that needs the chip")


def _python_sources():
    """The repo's own sources: not the tests, not hidden directories
    (.git, caches, .archive proof checkouts), not tool output."""
    for path in REPO.rglob("*.py"):
        parts = path.relative_to(REPO).parts
        if parts[0] in ("tests", "chiprun_out") \
                or any(part.startswith(".") for part in parts):
            continue
        yield path


def test_no_other_cache_dir_and_no_platform_repin_in_the_sources():
    """One helper sets the cache directory; nothing re-pins the platform
    (JAX_PLATFORMS in the environment decides).  tools/compile_counter.py
    is the one allowed exception: a lint self-check must never take the
    chip, like the tests."""
    cache = re.compile(r"""update\(\s*["']jax_compilation_cache_dir""")
    platform = re.compile(r"""update\(\s*["']jax_platforms?["']""")
    cache_sites, platform_sites = [], []
    for path in _python_sources():
        text = path.read_text()
        rel = str(path.relative_to(REPO))
        if cache.search(text):
            cache_sites.append(rel)
        if platform.search(text):
            platform_sites.append(rel)
    assert cache_sites == ["llm_np_cp_tpu/utils/runtime.py"]
    assert platform_sites == ["tools/compile_counter.py"]
    # ...and the entry points do call the helper
    for entry in ("llm_np_cp_tpu/cli.py", "llm_np_cp_tpu/train.py",
                  "bench.py", "tools/serve_proc.py", "chip_smoke.py"):
        assert "configure_compile_cache()" in (REPO / entry).read_text(), entry
