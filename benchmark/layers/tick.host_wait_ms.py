"""Mean over dispatching ticks of (tick - ``host_sync_us`` -
``thread_cpu_us``): time in which the tick thread neither ran on a core nor
waited for the device in ``host_sync`` - the GIL held by the event loop, a
transfer that blocks."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.host_wait_ms(run)
