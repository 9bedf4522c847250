"""The index-score kernel's share of ITS byte bound over the capture's
decode-only ticks (``step.decode_device_ms``'s): the least time the chip could
take to read ONE index key for every position a decode row sees (tick arg
``dsa_visible`` x 256 B at the published widths x the layers:
``costs_glm_dsa.index_bytes``) over the peak HBM rate / the device time those
ticks spend under the ``dsa_score`` scope, in %.  Nothing for fewer than 20 such
ticks, for a configuration without an indexer or a program without the
scope."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # dsatrace.py lies beside the readers
import dsatrace  # noqa: E402


def read(run: dict) -> float | None:
    return dsatrace.decode_roofline(
        run, dsatrace.SCORE, "dsa_visible", dsatrace.costs_glm_dsa.index_bytes)
