"""``step_roofline`` for a ``glm_moe_dsa`` stack: ``dsatrace.step_roofline``
(the tick's bytes and operations from ``costs_glm_dsa.py``: an index key a
position SEEN, a latent row a position ATTENDED).  ``step_roofline`` itself
prices K and V per kv head and every position of the context, and is not
reported in such a cell."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # dsatrace.py lies beside the readers
import dsatrace  # noqa: E402


def read(run: dict) -> float | None:
    if run["config"].get("model_type") != "glm_moe_dsa":
        return None
    return dsatrace.step_roofline(run)
