"""The device profile (SURVEY §5 tracing row).

``trace()`` is a ``jax.profiler`` context that dumps a TensorBoard /
Perfetto trace of the XLA timeline (``--jax-profile DIR`` on the serve
subcommands).  The host side of the serving stack is traced by
``serve/tracing.TraceRecorder``; its ``serve.*`` annotations and the
step's named scopes (``models/transformer.STEP_SCOPES``) are what line
the two up.
"""

from __future__ import annotations

import contextlib
from typing import Iterator

import jax


@contextlib.contextmanager
def trace(log_dir: str = "/tmp/llmtpu_trace") -> Iterator[None]:
    """XLA timeline trace → TensorBoard/Perfetto (view with
    ``tensorboard --logdir`` or ui.perfetto.dev)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
