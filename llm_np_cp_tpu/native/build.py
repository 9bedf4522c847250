"""Build the native library with g++ (no pip/pybind11 — plain C ABI .so).

The binary is keyed on what it was built FROM: its file name carries a
hash of the source and the compiler flags, so a library left on disk by
another checkout state (or copied along with the tree to another
machine) is only ever loaded when it is this source built with these
flags.  The flags name no host CPU (no ``-march=native``): the same
binary runs on any x86-64 machine with this installation.
"""

from __future__ import annotations

import hashlib
import logging
import subprocess
from pathlib import Path

log = logging.getLogger("llm_np_cp_tpu")

SRC = Path(__file__).parent / "safetensors_reader.cc"
FLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17")


def lib_path() -> Path:
    """Where the library for the CURRENT source + flags lives."""
    key = hashlib.sha256(SRC.read_bytes() + " ".join(FLAGS).encode())
    return SRC.parent / f"libllmtpu_native-{key.hexdigest()[:12]}.so"


def build(force: bool = False) -> Path | None:
    """Compile the .so for the current source unless it already exists.
    Returns the path, or None — with a logged reason — if the toolchain
    is unavailable (callers fall back to pure Python)."""
    lib = lib_path()
    if lib.exists() and not force:
        return lib
    cmd = ["g++", *FLAGS, "-o", str(lib), str(SRC), "-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except FileNotFoundError:
        log.warning("native safetensors reader not built: g++ not found; "
                    "checkpoints load through the python safetensors reader")
        return None
    except subprocess.SubprocessError as e:
        detail = getattr(e, "stderr", b"") or b""
        log.warning("native safetensors reader failed to build (%s: %s); "
                    "checkpoints load through the python safetensors reader",
                    type(e).__name__, detail.decode(errors="replace")[-400:])
        return None
    for stale in SRC.parent.glob("libllmtpu_native*.so"):
        if stale != lib:
            stale.unlink(missing_ok=True)
    return lib


if __name__ == "__main__":
    path = build(force=True)
    print(f"built: {path}" if path else "build failed (see the log line)")
