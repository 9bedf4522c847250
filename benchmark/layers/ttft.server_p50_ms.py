"""Median over the window's requests of socket accept (``http`` begins) -> the
first token's SSE frame is written (instant ``first_write``): the eight
stages together, the server's own TTFT.  The client's ``ttft_p50_s`` is this
plus the loopback and the load generator's read (and, in a closed loop,
starts at the send).  Read only where the track carries the stamps between
(instant ``lane``): there ``http`` begins at the request's ``received_time``
and ``decode`` at its emit, and the stages sum to this."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ttftstages.py lies beside the readers
import ttftstages  # noqa: E402


def read(run: dict) -> float | None:
    return ttftstages.stage_percentile_ms(run, "http", "first_write", 50,
                                          needs="lane")
