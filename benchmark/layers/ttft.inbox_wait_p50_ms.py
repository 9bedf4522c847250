"""Median over the window's requests of the command's put into the tick
thread's inbox (instant ``enqueued``) -> the tick thread takes it (``queued``
begins): the wait for the running tick to end, which
``http.accept_to_queue_p50_ms`` holds mixed with reading and parsing the
request.  A program without the instant gives nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ttftstages.py lies beside the readers
import ttftstages  # noqa: E402


def read(run: dict) -> float | None:
    return ttftstages.stage_percentile_ms(run, "enqueued", "queued", 50)
