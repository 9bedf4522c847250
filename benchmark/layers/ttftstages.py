"""A request's first token stage by stage, and the tick by its two kinds
(PR 53): what the ``ttft.*``, ``tick.*_wall_ms`` and ``step.*_device_ms``
readers share.

Not a metric's reader (no metric has this name); like ``tracefile.py`` and
``ticktimeline.py`` it lies beside the readers, which put their directory on
the path and ``import ttftstages``.

**The stages.**  The program stamps a request's way to its first token where
the work happens and puts each stamp on its track in the trace dump: the
``http`` span begins at socket accept, instant ``enqueued`` is the command's
put into the tick thread's inbox, ``queued`` begins where the tick thread
takes it (between two ticks), ``prefill`` at admission, instant ``lane`` at
the plan of the first tick that hands the row more than its fair share of
the prompt lane (or completes its prompt), ``last_chunk`` at the plan of the
tick that carries its last prompt token, ``first_token`` at the accept,
``decode`` begins at the emit (just before the callback) and ``first_write``
is the frame's write on the loop thread.  A stage is the distance between two
of these edges, the FIRST of each name on the track (a preempted request
keeps its first stamps), over the requests whose ``http`` span began in the
window (``tracefile.request_tracks``).

**The kinds.**  Since PR 52 a tick with a prompt aboard is a different program
at a different length from one without, and a mean over whatever mix a 3 s
capture met describes neither.  Tick arg ``lane_rows`` (rows that received
leftover of the prompt lane beyond their fair share) says which a tick was:
``prefill`` where it is > 0, ``decode`` where it is 0 and the tick carried no
prompt token at all; a tick of fair-share chunks alone is neither.  A kind
with fewer than ``MIN_TICKS`` ticks reads nothing, not a mean of three.

Every function returns None on a program without the instants or the tick
arg (the parent of PR 53): the metric is then left out of the line.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # the two lie beside
import ticktimeline  # noqa: E402
import tracefile  # noqa: E402

MIN_TICKS = 20
SPANS = ("http", "queued", "prefill", "decode")  # an edge that is a begin


def edge(track: dict, name: str) -> float | None:
    """Where ``name`` lies on a request's track (us), or None."""
    if name in SPANS:
        return track["begin"].get(name)
    ev = track["instant"].get(name)
    return None if ev is None else ev["ts"]


def stage_percentile_ms(run: dict, a: str, b: str, q: float,
                        needs: str | None = None) -> float | None:
    """``q``-th percentile over the window's requests of edge ``a`` ->
    edge ``b``; a request without either (or without edge ``needs``: both
    ends of the whole way are older than the stamps between them) is left
    out."""
    def value(track: dict) -> float | None:
        t0, t1 = edge(track, a), edge(track, b)
        if needs is not None and edge(track, needs) is None:
            return None
        return None if t0 is None or t1 is None else t1 - t0

    return tracefile.track_percentile_ms(run, value, q)


def kind(args: dict) -> str | None:
    """``prefill`` | ``decode`` | None (fair-share chunks alone, or a
    program that does not say)."""
    if "lane_rows" not in args:
        return None
    if args["lane_rows"] > 0:
        return "prefill"
    return None if args.get("prefill_tokens", 0) else "decode"


def _mean_ms(seconds: list[float]) -> float | None:
    if len(seconds) < MIN_TICKS:
        return None
    return 1e3 * sum(seconds) / len(seconds)


def tick_wall_ms(run: dict, which: str) -> float | None:
    """Mean ``tick`` span over the window's dispatching ticks of one kind."""
    return _mean_ms([t["dur_s"] for t in tracefile.dispatching_ticks(run)
                     if kind(t["args"]) == which])


def program_ms(run: dict, which: str) -> float | None:
    """Mean duration of the device program (the profile's ``XLA Modules``
    execution, joined to the recorder's tick by ``seq``) over the capture's
    ticks of one kind."""
    return _mean_ms([(r["program"][1] - r["program"][0]) / 1e9
                     for r in ticktimeline.rows(run)
                     if kind(r["tick"]["args"]) == which])
