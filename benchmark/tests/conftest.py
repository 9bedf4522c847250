"""The benchmark's own tests: CPU only, no chip, not part of tests/.

    python -m pytest benchmark/tests -q
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)
