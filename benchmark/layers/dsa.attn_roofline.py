"""The sparse attention's share of ITS byte bound over the capture's
decode-only ticks (``step.decode_device_ms``'s): the least time the chip could
take to read the latent rows a decode row ATTENDS (tick arg ``dsa_selected`` =
min(context, index_topk) a row, x 1,152 B x the layers:
``costs_glm_dsa.selected_row_bytes`` - the least the mathematics needs, whatever
form the kernel takes, so a masked walk of every page reads honestly low) over
the peak HBM rate / the device time those ticks spend under the ``dsa_attn``
scope, in %.  Nothing for fewer than 20 such ticks, for a configuration
without an indexer or a program without the scope."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # dsatrace.py lies beside the readers
import dsatrace  # noqa: E402


def read(run: dict) -> float | None:
    return dsatrace.decode_roofline(
        run, dsatrace.ATTN, "dsa_selected",
        dsatrace.costs_glm_dsa.selected_row_bytes)
