"""Process start -> the window's start: imports, weights, engine build,
probes, warm-up (compile or cache) and the ramp."""


def read(run: dict) -> float | None:
    return run["client"]["window"][0] - run["process_start"]
