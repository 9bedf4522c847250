"""Request table, arrival times and the record's arithmetic."""

import json
import random

import pytest
import stats
import traffic

BENCH = traffic.__file__.rsplit("/", 1)[0]
MIXES = ["decode-closed", "chat-open"]


def load(name):
    return traffic.load_traffic(f"{BENCH}/traffic/{name}.json")


@pytest.mark.parametrize("mix", MIXES)
def test_table_is_a_pure_function_of_seed_and_file(mix):
    t = load(mix)
    a = traffic.request_table(t, 2**31 + 17, 200, 151936)
    b = traffic.request_table(t, 2**31 + 17, 200, 151936)
    c = traffic.request_table(t, 2**31 + 18, 200, 151936)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    # a longer table only adds requests
    assert traffic.request_table(t, 2**31 + 17, 70, 151936) == a[:70]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_sizes_in_another_order(mix):
    t = load(mix)
    block = t["block"]
    a = traffic.request_table(t, 1, 2 * block, 151936)
    b = traffic.request_table(t, 2, 2 * block, 151936)
    for lo in (0, block):
        for key in ("prompt_len", "max_tokens"):
            assert sorted(r[key] for r in a[lo:lo + block]) == \
                sorted(r[key] for r in b[lo:lo + block])
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]
    p_max, m_max = traffic.limits(t)
    assert max(r["prompt_len"] for r in a) <= p_max
    assert max(r["max_tokens"] for r in a) <= m_max
    assert all(len(set(r["prompt"])) > 0.9 * len(r["prompt"]) for r in a)


def test_order_seed_makes_one_periodic_sequence_entered_at_a_seeded_offset():
    t = load("chat-open")
    assert t["order_seed"] is not None and load("decode-closed").get("order_seed") is None
    block, rate = t["block"], 2.8
    tables = {s: traffic.request_table(t, s, 3 * block, 151936) for s in (11, 12)}
    for s, table in tables.items():
        lens = [(r["prompt_len"], r["max_tokens"]) for r in table]
        assert lens[:block] == lens[block:2 * block]            # periodic
    k = (traffic.start_offset(t, 12) - traffic.start_offset(t, 11)) % block
    assert k and [r["prompt_len"] for r in tables[11]][k:k + block] == \
        [r["prompt_len"] for r in tables[12]][:block]           # same order, shifted
    assert tables[11][k]["prompt"] != tables[12][0]["prompt"]   # own token ids
    a, b = (traffic.arrival_times(t, s, rate, 3 * block) for s in (11, 12))
    gaps = lambda d: [round(y - x, 9) for x, y in zip([0.0] + d, d)]  # noqa: E731
    assert gaps(a)[k:k + block] == gaps(b)[:block]
    assert a[block - 1] == pytest.approx(block / rate, rel=0.02)


def test_due_times_span_the_same_time_for_every_seed():
    a = traffic.due_times(5.0, 128, random.Random(1), 64)
    b = traffic.due_times(5.0, 128, random.Random(2), 64)
    assert a != b and a == sorted(a)
    assert a[63] == pytest.approx(b[63]) and a[127] == pytest.approx(b[127])
    assert a[127] == pytest.approx(128 / 5.0, rel=0.02)  # the mean rate holds


def test_bursts_keep_the_mean_rate_and_crowd_the_burst():
    bursts = {"period_s": 10.0, "burst_s": 1.0, "factor": 5.0}
    d = traffic.due_times(10.0, 640, random.Random(3), 64, bursts)
    whole = d[:600]  # six whole periods of 100 arrivals
    assert whole[-1] == pytest.approx(60.0, rel=0.03)
    in_burst = sum(1 for x in whole if x % 10.0 < 1.0)
    assert in_burst == pytest.approx(0.5 * len(whole), rel=0.1)  # 5x for a tenth


def test_prefix_sharing_and_sessions_are_data():
    t = dict(load("chat-open"), block=16)
    t["sharing"] = {"kind": "prefix", "groups": 2,
                    "prefix_tokens": {"dist": "constant", "value": 40}}
    reqs = traffic.request_table(t, 5, 32, 5000)
    by_group = {}
    for r in reqs:
        by_group.setdefault(r["group"], []).append(r["prompt"])
    for prompts in by_group.values():
        long = [p for p in prompts if len(p) > 41]
        assert len({tuple(p[:40]) for p in long}) == 1  # one shared head
    t["sharing"] = {"kind": "sessions", "turns": {"dist": "uniform", "min": 2, "max": 4},
                    "think_s": {"dist": "uniform", "min": 0.5, "max": 1.0}}
    reqs = traffic.request_table(t, 5, 32, 5000)
    assert any(r["turn"] > 0 for r in reqs)
    prev = {}
    for r in reqs:
        if r["turn"]:
            p = prev[r["session"]]
            assert r["prompt_len"] == p["prompt_len"] + p["max_tokens"] + len(r["prompt"])
            assert r["prompt_len"] <= traffic.limits(t)[0]
        prev[r["session"]] = r


def test_unknown_family_and_loop_are_errors(tmp_path):
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "zipf"}, 0.5)
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"loop": "spiral"}))
    with pytest.raises(ValueError):
        traffic.load_traffic(str(bad))


# ----- the record's arithmetic, on a hand-made record ----------------

def req(idx, due, sent, times, max_tokens=None, **kw):
    base = dict(idx=idx, due=due, sent=sent, times=times, tokens=list(range(len(times))),
                max_tokens=len(times) if max_tokens is None else max_tokens,
                status=200, error=None, finish="length", ramp_cut=False,
                end=times[-1] if times else sent, prompt_len=10, turn=0)
    base.update(kw)
    return base


RECORD = dict(window=[100.0, 110.0], requests=[
    req(0, 95.0, 95.0, [96.0, 101.0, 102.0]),             # ramp: tokens count, latency not
    req(1, 100.0, 100.5, [101.0, 101.5, 102.0, 102.5]),   # open loop, sent 0.5 s late
    req(2, 104.0, 104.0, [104.2, 104.4]),
    req(3, 109.0, 109.0, [109.5, 111.0, 113.0]),          # ends after the window
    req(4, 105.0, 105.0, [105.1], max_tokens=2),          # short: failed
    req(5, 106.0, 106.0, [], status=503, error="busy", finish=None),
    req(6, 110.0, 110.0, [110.5]),                        # due at the edge: not measured
    req(7, 103.0, 103.0, [103.4], ramp_cut=True),
])


def test_window_membership_failures_and_tokens():
    m = stats.measured(RECORD)
    assert [r["idx"] for r in m] == [1, 2, 3, 4, 5]
    assert [stats.good(r) for r in m] == [True, True, True, False, False]
    # tokens by ARRIVAL time in [100, 110): r0 2, r1 4, r2 2, r3 1, r4 1, r7 1
    assert stats.tokens_in_window(RECORD) == 11
    assert stats.window_seconds(RECORD) == 10.0


def test_latency_is_taken_from_the_due_time():
    r1 = RECORD["requests"][1]
    assert stats.ttft_s(r1) == pytest.approx(1.0)   # 101.0 - due 100.0, not - sent
    assert stats.late_s(r1) == pytest.approx(0.5)
    assert stats.tpot_s(r1) == pytest.approx(0.5)   # (102.5 - 101.0) / 3
    assert stats.tpot_s(RECORD["requests"][3]) == pytest.approx(1.75)
    assert stats.ttft_s(RECORD["requests"][4]) is None  # a failed request has none
    assert stats.series(RECORD, stats.ttft_s) == pytest.approx([1.0, 0.2, 0.5])


def test_percentile_is_linear_between_ranks():
    assert stats.percentile([], 50) is None
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1.0, 2.0, 3.0, 4.0], 50) == pytest.approx(2.5)
    assert stats.percentile(list(range(101)), 95) == pytest.approx(95.0)
    assert stats.percentile([0.0, 10.0], 95) == pytest.approx(9.5)


def test_in_flight_counts_due_but_not_ended():
    assert stats.in_flight(RECORD, 101.2) == 2      # r0 (ends 102) and r1
    assert stats.in_flight(RECORD, 110.0) == 2      # r3 and r6


def test_scrape_reads_labels_and_sums_replicas():
    text = ('llm_serve_ticks_total{replica="0"} 5\nllm_serve_ticks_total{replica="1"} 7\n'
            'llm_serve_queue_wait_s_quantile{quantile="0.5"} 0.25\n'
            'llm_serve_queue_wait_s_quantile{quantile="0.99"} 2\n')
    assert stats.scrape_sum(text, "ticks_total") == 12
    assert stats.scrape_mean(text, "queue_wait_s_quantile", {"quantile": "0.5"}) == 0.25
    assert stats.scrape_sum(text, "restarts_total") is None
    rec = dict(scrapes=dict(start={"/metrics": {"text": "llm_serve_ticks_total 10\n"}},
                            end={"/metrics": {"text": "llm_serve_ticks_total 25\n"}}))
    assert stats.scrape_delta(rec, "ticks_total") == 15
