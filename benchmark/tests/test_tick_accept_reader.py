"""``tick.accept_ms`` (PR 35): the mean of the recorder's ``accept`` phase,
the part of the old ``deliver`` that stays on the device's critical path.

The chip fixture of PR 24 (fixtures/v5e_tick_account.json) has the uncut
order — ``host_sync``, ``deliver``, ``account`` — so it stands for the
PARENT here: the reader finds nothing and returns None.  The new order is
made from it by hand: each tick's ``deliver`` is cut in two."""

import json
from pathlib import Path

import pytest
import run as harness

BENCH = Path(__file__).resolve().parents[1]
FIXTURE = Path(__file__).parent / "fixtures" / "v5e_tick_account.json"


@pytest.fixture(scope="module")
def record(tmp_path_factory):
    with open(FIXTURE) as f:
        fx = json.load(f)
    path = tmp_path_factory.mktemp("accept") / "host_trace.json"
    path.write_text(json.dumps(fx["dump"]))
    w0, w1 = fx["window"]
    return dict(workload=fx["workload"], seed=fx["seed"], replicas=1,
                client={"window": [w0, w1]},
                host_trace=harness.load_host_trace(path, w0, w1),
                device_trace=None)


def read(name: str, run: dict):
    return harness.load_reader(BENCH / "layers" / name)(run)


def with_accept(run: dict, accept_s: float) -> dict:
    """The new order: every ``deliver`` gives its last ``accept_s``
    seconds to an ``accept`` phase of the same tick."""
    phases = []
    for p in run["host_trace"]["phases"]:
        if p["name"] != "deliver":
            phases.append(p)
            continue
        assert p["dur_s"] > accept_s
        phases.append(dict(p, dur_s=p["dur_s"] - accept_s))
        phases.append(dict(name="accept", start=p["start"] + p["dur_s"] - accept_s,
                           dur_s=accept_s))
    return dict(run, host_trace=dict(run["host_trace"], phases=phases))


def test_the_parent_has_no_accept_phase_and_reads_nothing(record):
    assert len(record["host_trace"]["ticks"]) == 2
    assert read("tick.accept_ms", record) is None
    assert read("tick.accept_ms", dict(record, host_trace=None)) is None
    # an uncut tick (no ``account`` phase either): nothing, and no raise
    uncut = dict(record, host_trace=dict(record["host_trace"], phases=[
        p for p in record["host_trace"]["phases"] if p["name"] != "account"]))
    assert read("tick.accept_ms", uncut) is None
    assert read("tick.deliver_ms", record) > 0.0


def test_accept_is_the_mean_over_dispatching_ticks_and_deliver_keeps_its_name(record):
    cut = with_accept(record, 0.0004)
    assert read("tick.accept_ms", cut) == pytest.approx(0.4, rel=1e-9)
    # the accepted reader still reads the phase named ``deliver``
    assert read("tick.deliver_ms", cut) == pytest.approx(
        read("tick.deliver_ms", record) - 0.4, rel=1e-9)
    # a phase outside every dispatching tick is not counted
    stray = dict(cut, host_trace=dict(cut["host_trace"], phases=cut["host_trace"][
        "phases"] + [dict(name="accept", start=cut["host_trace"]["ticks"][-1]["start"]
                          + 10.0, dur_s=5.0)]))
    assert read("tick.accept_ms", stray) == pytest.approx(0.4, rel=1e-9)


def test_the_entry_is_appended_and_names_a_layer_of_perf_md():
    # (no ``workloads`` list: every cell that reports out_tok_s reports it)
    with open(BENCH.parent / "BENCHMARK.json") as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    # appended behind everything PR 34 had (later PRs append behind it)
    assert names.index("tick.accept_ms") > names.index("falcon-h1.step_roofline")
    entry = bench["per_layer"][names.index("tick.accept_ms")]
    assert entry == dict(name="tick.accept_ms", unit="ms", better="lower",
                         source="program_span", layer="tick (host side)",
                         moves="out_tok_s")
    perf = (BENCH.parent / "PERF.md").read_text()
    assert "| tick (host side) |" in perf and "`tick.accept_ms`" in perf
