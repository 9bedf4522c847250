"""Scheduler policy tests: pure Python/NumPy simulation, no model.

The simulation mirrors the engine's tick exactly (serve/engine.py
``step``): admit → prefill emits the first token → grow blocks
(evict-on-OOM) → one decode token per running request → finish.  That
lets thousands of ticks of scheduling behavior run in milliseconds and
pins the policy invariants: no starvation, pool accounting never
oversubscribes, and continuous batching beats static batching on
makespan.
"""

import numpy as np
import pytest

from llm_np_cp_tpu.serve.block_pool import FreeList
from llm_np_cp_tpu.serve.scheduler import Request, RequestState, Scheduler
from llm_np_cp_tpu.serve.trace import poisson_trace

BLOCK = 8


def _requests(specs):
    """specs: [(prompt_len, max_new_tokens)] → Request list."""
    return [
        Request(req_id=i, prompt=np.zeros(p, np.int32), max_new_tokens=m)
        for i, (p, m) in enumerate(specs)
    ]


def _simulate(sched, arrivals=(), max_ticks=10_000):
    """Drive the scheduler exactly like the engine's tick loop; returns
    (completion order of req_ids, ticks used).  ``arrivals`` is
    [(tick, request)] for requests not pre-queued.  Asserts the pool
    accounting invariants every tick."""
    fl = sched.allocator
    pending = sorted(arrivals, key=lambda a: a[0])
    done: list[int] = []
    for tick in range(1, max_ticks + 1):
        while pending and pending[0][0] <= tick:
            sched.add(pending.pop(0)[1])
        for req in sched.admit():
            req.generated.append(1)  # prefill emits the first token
            if req.done:
                sched.finish(req)
                done.append(req.req_id)
        sched.ensure_decode_blocks()
        for req in list(sched.running):
            if not req.generated:
                continue  # readmission happens via admit() next tick
            req.generated.append(1)
            if req.done:
                sched.finish(req)
                done.append(req.req_id)
        # -- accounting invariants, every tick ------------------------
        assert fl.num_allocated + fl.num_free == fl.capacity
        held = [b for r in sched.running for b in r.block_ids]
        assert len(held) == len(set(held)), "block double-booked"
        assert len(held) == fl.num_allocated
        assert len(sched.running) <= sched.max_slots
        if not sched.has_work and not pending:
            return done, tick
    raise AssertionError(f"did not drain in {max_ticks} ticks")


def _mk(n_blocks=64, slots=4, **kw):
    return Scheduler(FreeList(n_blocks), max_slots=slots, block_size=BLOCK,
                     **kw)


def test_admission_is_fifo_and_slot_bounded():
    sched = _mk(slots=2)
    reqs = _requests([(4, 3)] * 5)
    for r in reqs:
        sched.add(r)
    admitted = sched.admit()
    assert [r.req_id for r in admitted] == [0, 1]
    assert all(r.state is RequestState.RUNNING for r in admitted)
    assert sched.queue_depth == 3
    assert {r.slot for r in admitted} == {0, 1}


def test_admission_blocked_by_free_blocks_not_just_slots():
    # 4 allocatable blocks, reserve 1 → a 2-block prefill fits once
    sched = _mk(n_blocks=5, slots=4)
    reqs = _requests([(16, 2), (16, 2)])  # 2 blocks each
    for r in reqs:
        sched.add(r)
    admitted = sched.admit()
    assert [r.req_id for r in admitted] == [0]  # head only; 2+1 > 2 free
    assert sched.queue_depth == 1


def test_finish_returns_blocks_and_slot():
    sched = _mk(n_blocks=8, slots=1)
    (req,) = _requests([(4, 1)])
    sched.add(req)
    sched.admit()
    held = list(req.block_ids)
    assert held
    req.generated.append(1)
    sched.finish(req)
    assert req.block_ids == [] and req.slot == -1
    assert sched.allocator.num_allocated == 0
    assert req.state is RequestState.FINISHED


def test_eviction_requeues_at_front_with_tokens_kept():
    # 3 allocatable blocks: two 1-block requests admitted, then growth
    # forces an eviction
    sched = _mk(n_blocks=4, slots=2)
    r0, r1 = _requests([(6, 20), (6, 20)])
    sched.add(r0)
    sched.add(r1)
    sched.admit()
    r0.generated = [1] * 3  # cache_len 9 > one block → needs a 2nd
    r1.generated = [1] * 3
    preempted = sched.ensure_decode_blocks()
    assert len(preempted) == 1
    victim = preempted[0]
    assert victim.state is RequestState.QUEUED
    assert sched.queue[0] is victim  # requeued at the FRONT
    assert victim.block_ids == [] and victim.slot == -1
    assert victim.generated == [1, 1, 1]  # progress kept (teacher-forced)
    assert victim.n_preemptions == 1 and sched.n_preemptions == 1
    survivor = r0 if victim is r1 else r1
    assert len(survivor.block_ids) == 2  # the growth that forced it


def test_readmitted_request_prefills_prompt_plus_generated():
    (req,) = _requests([(5, 10)])
    req.generated = [7, 8, 9]
    eff = req.effective_prompt()
    assert eff.shape == (8,)
    assert list(eff[-3:]) == [7, 8, 9]


def test_no_starvation_under_poisson_load():
    """Every request from a Poisson trace finishes, even with a pool
    tight enough to force preemptions."""
    rng = np.random.default_rng(3)
    trace = poisson_trace(
        rng, 40, rate_rps=4.0, prompt_len_range=(2, 20),
        max_new_tokens=(1, 12), vocab_size=100,
    )
    # arrival seconds → ticks (one tick per simulated second at rate*1)
    arrivals = []
    for i, t in enumerate(trace):
        req = Request(req_id=i, prompt=t["prompt"],
                      max_new_tokens=t["max_new_tokens"])
        arrivals.append((int(t["arrival_s"]) + 1, req))
    sched = _mk(n_blocks=8, slots=3)  # tight: forces eviction churn
    done, ticks = _simulate(sched, arrivals)
    assert sorted(done) == list(range(40))  # nobody starves
    assert sched.n_preemptions > 0  # the pool WAS tight enough to evict
    assert sched.allocator.num_allocated == 0
    assert len(sched.finished) == 40


def test_continuous_beats_static_batching_on_makespan():
    """Static batching holds a whole batch until its slowest row; the
    continuous scheduler backfills freed slots.  On a workload with
    high decode-length variance the simulated makespan must be
    strictly smaller."""
    slots = 2
    specs = [(2, 16), (2, 1), (2, 16), (2, 1), (2, 8), (2, 1)]
    sched = _mk(n_blocks=64, slots=slots)
    for r in _requests(specs):
        sched.add(r)
    _, continuous_ticks = _simulate(sched)
    # static: groups of `slots` in arrival order, each group runs for
    # its slowest member (one tick per token, prefill emits the first)
    static_ticks = sum(
        max(m for _, m in specs[i:i + slots])
        for i in range(0, len(specs), slots)
    )
    assert continuous_ticks < static_ticks


def test_single_slot_request_filling_whole_pool_converges():
    """One slot, and the request's full lifetime exactly fills the
    allocatable pool: growth must reach the last block without an
    eviction loop and the request completes."""
    sched = _mk(n_blocks=4, slots=1, decode_reserve=0)
    (req,) = _requests([(4, 20)])  # 24 slots == 3 allocatable blocks
    sched.add(req)
    done, _ = _simulate(sched, max_ticks=200)
    assert done == [0]
    assert sched.n_preemptions == 0


# ---------------------------------------------------------------------------
# Token-budget planner (the unified tick's co-schedule, Scheduler.plan_tick)
# ---------------------------------------------------------------------------

def _simulate_mixed(sched, budget, chunk, shared_done=None,
                    max_ticks=10_000):
    """Drive the scheduler exactly like the unified tick (_step_mixed):
    admit → init prefill progress → grow → plan → apply the plan.
    Returns per-tick plan records; asserts the planner invariants the
    engine relies on every tick.  ``shared_done`` maps req_id → content
    tokens pre-covered by the prefix cache (consume NO budget)."""
    shared_done = shared_done or {}
    records = []
    prefill_budgeted: dict[int, int] = {}
    for _ in range(max_ticks):
        for req in sched.admit():
            req.prefill_target = req.prompt_len + len(req.generated)
            req.prefill_done = shared_done.get(req.req_id, 0)
            req.prefilled = False
        sched.ensure_decode_blocks()
        decode, prefill = sched.plan_tick(budget, chunk)
        # -- invariants, every tick --------------------------------------
        planned = len(decode) + sum(n for _, n in prefill)
        assert planned <= budget, "budget overrun"
        rows = [r.req_id for r, _ in prefill]
        assert len(rows) == len(set(rows)), "one segment a row"
        assert all(1 <= n <= r.prefill_target - r.prefill_done
                   for r, n in prefill), "a segment stays inside its prompt"
        # the fair share: every mid-prefill row gets its chunk (or its
        # tail) while the budget lasts, oldest first ...
        got = {r.req_id: n for r, n in prefill}
        room = budget - len(decode)
        for r in sched.running:
            if not r.prefilled:
                share = min(chunk, r.prefill_target - r.prefill_done, room)
                assert got.get(r.req_id, 0) >= share, "fair share"
                room -= share
        # ... and the lane is work-conserving: budget is left unspent
        # only when no row could use it
        if planned < budget:
            assert all(
                got.get(r.req_id, 0) == r.prefill_target - r.prefill_done
                for r in sched.running if not r.prefilled), "idle lane"
        # decode rows are NEVER starved: every prefilled running request
        # with a token to feed is in the decode batch
        ready = [r for r in sched.running if r.prefilled and r.generated]
        assert decode == ready
        # a mid-prefill row always progresses when budget remains
        waiting = [r for r in sched.running if not r.prefilled]
        if waiting and budget - len(decode) > 0:
            assert prefill, "prefill starved despite remaining budget"
        records.append((len(decode), [(r.req_id, n) for r, n in prefill]))
        # -- apply the plan (what _step_mixed's deliver phase does) ------
        for r, n in prefill:
            prefill_budgeted[r.req_id] = prefill_budgeted.get(r.req_id, 0) + n
            r.prefill_done += n
            if r.prefill_done >= r.prefill_target:
                r.prefilled = True
                r.generated.append(1)  # first token
                if r.done:
                    sched.finish(r)
        for r in decode:
            r.generated.append(1)
            if r.done:
                sched.finish(r)
        if not sched.has_work:
            return records, prefill_budgeted
    raise AssertionError(f"did not drain in {max_ticks} ticks")


def test_planner_budget_exact_and_decode_first():
    """A long prefill arriving mid-decode must not stall the decoding
    rows: every tick they decode first, the long prompt fills only the
    remaining budget, and the total never exceeds it."""
    sched = _mk(n_blocks=64, slots=3)
    short = _requests([(4, 30), (4, 30)])
    for r in short:
        sched.add(r)
    long_req = Request(req_id=9, prompt=np.zeros(120, np.int32),
                       max_new_tokens=2)
    # bootstrap: prefill the two short requests to decoding state
    for req in sched.admit():
        req.prefill_target = req.prompt_len
        req.prefill_done = 0
        req.prefilled = False
    _, prefill = sched.plan_tick(16, 8)
    for r, n in prefill:
        r.prefill_done += n
        if r.prefill_done >= r.prefill_target:
            r.prefilled = True
            r.generated.append(1)
    sched.add(long_req)
    records, budgeted = _simulate_mixed(sched, budget=16, chunk=8)
    # while the long prefill ran, both decoders kept decoding every tick
    long_ticks = [rec for rec in records if any(
        rid == 9 for rid, _ in rec[1])]
    assert long_ticks, "long request never prefilled"
    assert all(rec[0] == 2 for rec in long_ticks[:-1]), (
        "decode rows starved during the long prefill"
    )
    # the long prompt's budgeted tokens exactly cover its content
    assert budgeted[9] == 120
    assert sorted(r.req_id for r in sched.finished) == [0, 1, 9]


def test_planner_prefix_covered_content_consumes_no_budget():
    """Prefix-cache-covered content is pre-marked done at admission, so
    the planner budgets ONLY the uncovered tail (plus the always-
    re-prefilled final chunk) — a full-coverage twin finishes its
    prefill in one tick where the cold run needs several."""
    def run(covered):
        sched = _mk(n_blocks=64, slots=1)
        (req,) = _requests([(40, 1)])
        sched.add(req)
        _, budgeted = _simulate_mixed(
            sched, budget=9, chunk=8, shared_done={0: covered})
        return budgeted[0]

    cold = run(0)
    warm = run(32)  # 4 chunks covered, final chunk re-prefills
    assert cold == 40
    assert warm == 8
    assert cold - warm == 32  # covered chunks consumed zero budget


def test_planner_multiple_prefills_share_budget_oldest_first():
    """Two queued prompts admitted together split the prefill budget in
    admission order — the older one finishes first (FIFO preserved), and
    both make progress when the budget covers more than one chunk."""
    sched = _mk(n_blocks=64, slots=2)
    for r in _requests([(24, 2), (24, 2)]):
        sched.add(r)
    records, budgeted = _simulate_mixed(sched, budget=12, chunk=8)
    first_tick = records[0][1]
    assert [rid for rid, _ in first_tick] == [0, 1]
    assert first_tick[0][1] == 8  # oldest takes a whole chunk
    assert first_tick[1][1] == 4  # younger gets the remainder
    assert budgeted == {0: 24, 1: 24}
    assert [r.req_id for r in sched.finished] == [0, 1]


@pytest.mark.parametrize("budget, ticks", [(9, 16), (33, 4), (121, 2)])
def test_planner_long_prompt_costs_prompt_over_lane_ticks(budget, ticks):
    """A long prompt beside a short one that decodes from the second tick
    on: its first token comes after about prompt / lane ticks, whatever
    the chunk — a lane of one chunk is the one-chunk-a-tick pace, a lane
    of the whole prompt two ticks (the first carries the short prompt's
    4 tokens too)."""
    sched = _mk(n_blocks=64, slots=2)
    for r in _requests([(4, 40), (120, 1)]):
        sched.add(r)
    records, budgeted = _simulate_mixed(sched, budget=budget, chunk=8)
    long_ticks = [rec for rec in records if any(
        rid == 1 for rid, _ in rec[1])]
    assert len(long_ticks) == ticks == 1 + -(
        -(120 - (budget - 4)) // (budget - 1))
    assert budgeted == {0: 4, 1: 120}


def test_planner_respects_tiny_budget_progress_guarantee():
    """budget == max_slots is the liveness floor: even with every other
    slot decoding, a mid-prefill row advances at least one token per
    tick (token granularity — no whole-chunk stall), and everything
    drains."""
    sched = _mk(n_blocks=64, slots=2)
    for r in _requests([(4, 20), (30, 3)]):
        sched.add(r)
    records, budgeted = _simulate_mixed(sched, budget=2, chunk=8)
    assert budgeted == {0: 4, 1: 30}
    assert sorted(r.req_id for r in sched.finished) == [0, 1]
    # single-token prefill slices appeared (the decode row held 1 slot)
    assert any(n == 1 for rec in records for _, n in rec[1])


def _planned(sched, specs):
    """Admit ``specs`` — ``(prompt_len, prefilled, draft_len)`` a request —
    straight into the state a tick would find them in."""
    reqs = _requests([(p, 8) for p, _, _ in specs])
    for r in reqs:
        sched.add(r)
    assert sched.admit() == reqs
    for r, (p, prefilled, draft) in zip(reqs, specs):
        r.prefill_target, r.prefilled = p, prefilled
        r.prefill_done = p if prefilled else 0
        if prefilled:
            r.generated.append(1)
        r.draft_len = draft
    return reqs


# what ``plan_tick`` decides, one policy point a case: (requests as
# ``_planned`` takes them, budget, chunk, prefill order) → (decode rows,
# prefill segments, draft widths left on the decode rows)
PLAN_CASES = {
    # a decode row's base token comes before any prefill, its drafts after
    "drafts-spend-what-prefill-leaves": (
        [(4, True, 3), (20, False, 0)], 12, 8, None,
        [0], [(1, 8)], [3]),
    "drafts-are-trimmed-to-the-slack": (
        [(4, True, 3), (20, False, 0)], 10, 8, None,
        [0], [(1, 8)], [1]),
    "drafts-are-trimmed-to-nothing-never-the-base-token": (
        [(4, True, 3), (4, True, 2), (20, False, 0)], 4, 8, None,
        [0, 1], [(2, 2)], [0, 0]),
    # the fair share is the chunk, the rest of the prompt or the rest of
    # the budget, whichever is least
    "a-chunk-caps-the-fair-share": (
        [(20, False, 0), (20, False, 0)], 12, 8, None,
        [], [(0, 8), (1, 4)], []),
    "the-prompts-tail-caps-a-segment": (
        [(5, False, 0), (3, False, 0)], 64, 8, None,
        [], [(0, 5), (1, 3)], []),
    "a-spent-budget-plans-no-prefill": (
        [(4, True, 0), (4, True, 0), (20, False, 0)], 2, 8, None,
        [0, 1], [], [0, 0]),
    # the fairness hook reorders the candidates, nothing else
    "prefill-order-is-the-hooks": (
        [(20, False, 0), (20, False, 0)], 12, 8,
        lambda running: list(reversed(running)),
        [], [(1, 8), (0, 4)], []),
    # what the fair share leaves goes to the oldest prompt: one segment
    # a row, its two grants summed
    "a-lone-row-takes-its-whole-prompt": (
        [(20, False, 0)], 64, 8, None, [], [(0, 20)], []),
    "the-leftover-goes-to-the-oldest": (
        [(40, False, 0), (40, False, 0)], 30, 8, None,
        [], [(0, 22), (1, 8)], []),
    "the-leftover-cascades-when-the-oldest-finishes": (
        [(12, False, 0), (40, False, 0)], 30, 8, None,
        [], [(0, 12), (1, 18)], []),
    "rows-that-all-finish-leave-budget-unspent": (
        [(10, False, 0), (10, False, 0), (40, False, 0)], 64, 8, None,
        [], [(0, 10), (1, 10), (2, 40)], []),
    "a-short-prompt-behind-a-long-one-keeps-its-chunk": (
        [(100, False, 0), (6, False, 0)], 20, 8, None,
        [], [(0, 14), (1, 6)], []),
    "the-budget-is-spent-to-the-token-beside-decode-rows": (
        [(4, True, 0), (100, False, 0), (100, False, 0)], 20, 8, None,
        [0], [(1, 11), (2, 8)], [0]),
    # drafts are budgeted between the two passes: they spend what the
    # fair share left, the leftover pass what the drafts left
    "drafts-are-budgeted-before-the-leftover": (
        [(4, True, 3), (40, False, 0)], 20, 8, None,
        [0], [(1, 16)], [3]),
    "the-leftover-never-trims-a-draft-that-fits": (
        [(4, True, 3), (4, True, 3), (40, False, 0)], 16, 8, None,
        [0, 1], [(2, 8)], [3, 3]),
    "prefill-order-leads-both-passes": (
        [(40, False, 0), (40, False, 0)], 30, 8,
        lambda running: list(reversed(running)),
        [], [(1, 22), (0, 8)], []),
    "a-decode-only-tick-is-unchanged": (
        [(4, True, 0), (4, True, 2)], 16, 8, None,
        [0, 1], [], [0, 2]),
}


@pytest.mark.parametrize("case", sorted(PLAN_CASES))
def test_plan_tick_policy(case):
    specs, budget, chunk, order, decode, prefill, drafts = PLAN_CASES[case]
    sched = _mk(n_blocks=64, slots=len(specs))
    _planned(sched, specs)
    got_decode, got_prefill = sched.plan_tick(
        budget, chunk, prefill_order=order)
    assert [r.req_id for r in got_decode] == decode
    assert [(r.req_id, n) for r, n in got_prefill] == prefill
    assert [r.draft_len for r in got_decode] == drafts
    spent = (len(got_decode) + sum(n for _, n in got_prefill)
             + sum(r.draft_len for r in got_decode))
    assert spent <= budget, "budgets are exact"
    done = {r.req_id: r.prefill_done + n for r, n in got_prefill}
    if any(done.get(r.req_id, 0) < r.prefill_target
           for r in sched.running if not r.prefilled):
        assert spent == budget, "a row could have used what was left"


def test_no_growth_at_exact_block_boundary():
    """At cache_len == blocks*BLOCK the tick's write slot (cache_len-1)
    still fits the allocation — growing there under pool exhaustion
    would preempt a victim for a block the grower may never use (e.g.
    when its final token lands exactly on the boundary)."""
    fl = FreeList(4)  # 3 allocatable blocks
    sched = Scheduler(fl, max_slots=3, block_size=BLOCK)
    reqs = _requests([(BLOCK - 1, 2), (BLOCK - 1, 2), (BLOCK - 1, 2)])
    for slot, r in enumerate(reqs):
        r.block_ids = fl.alloc(1)
        r.slot = slot
        r.state = RequestState.RUNNING
        r.generated.append(1)  # cache_len == BLOCK exactly
        sched.running.append(r)
    assert fl.num_free == 0
    assert sched.ensure_decode_blocks() == []
    assert all(len(r.block_ids) == 1 for r in reqs)

    # one more token pushes the oldest past the boundary: NOW it needs a
    # block, and with the pool exhausted the youngest gets evicted
    reqs[0].generated.append(1)
    preempted = sched.ensure_decode_blocks()
    assert preempted == [reqs[2]]
    assert len(reqs[0].block_ids) == 2
