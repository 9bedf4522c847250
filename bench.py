"""Decode-throughput benchmark over the BASELINE.md config matrix.

(ROADMAP S1 replaces this matrix with a ``workloads`` table the chip
can finish; until then the harness keeps its shape, with the device
rules of the round applied.)

- The parent never touches JAX: every config runs in its OWN subprocess
  with a hard timeout, so a child owns the chip for exactly as long as
  it runs and a hang cannot take down the whole benchmark.
- ONE cheap probe subprocess runs first.  The device path fails without
  a chip: if the probe dies, or reports a platform other than ``tpu``,
  the run exits non-zero with nothing on stdout — no CPU number is ever
  written under a device metric name.  The only thing a CPU may run is
  an explicit list of ``smoke_*`` configs (``--configs smoke_tiny``):
  a rehearsal of the harness, stamped ``"rehearsal": true`` with a
  headline value of 0.0.
- Each config's result line is printed to stderr AS IT COMPLETES, and the
  full summary JSON line is RE-EMITTED on stdout after every config (last
  line wins) — an outer kill at any moment leaves a parseable artifact
  with everything that finished.
- Configs run in priority order (headline first) against a global
  deadline from ``BENCH_DEADLINE_S`` (default 1500 s); per-config
  timeouts are clipped to the remaining deadline and configs that can't
  fit are skipped, not silently truncated.
- Children print ``bench-phase`` breadcrumbs (params built, prefill
  compiled, decode compiled, each rep) to stderr; on a timeout the
  parent recovers the partial stderr from TimeoutExpired, so a burned
  config still says WHERE it died (compile vs execute).
- Every child stamps its result with the device it ran on
  (``platform``/``device_kind``/``devices``) and shares the one
  persistent compilation cache (utils/runtime.configure_compile_cache).

Matrix (BASELINE.md "Benchmark configurations"):
- llama1b bs=1/8/32 decode, prompt=128, decode=256 (config 1 family;
  bs=8 is the headline)
- int8 weight-only quant at bs=1/8
- gemma2_2b greedy decode bs=1 seq=128 (config 2)
- llama3b sampled decode, seq=2048 prompt, bs=8, KV cache (config 3)
- llama1b prefill TTFT at seq=8192, Pallas flash vs XLA attention
  (config 5 shape, single-chip)

Headline + baseline bookkeeping: the north-star target (BASELINE.json,
1,000 decode tok/s/chip) is unreachable at bs=1 by the HBM roofline
(1.24B bf16 params = 2.47 GB/step ÷ ~819 GB/s ≈ 331 steps/s), so the
headline ``value`` is the aggregate tok/s/chip at bs=8 and the JSON
carries BOTH ratios explicitly: ``vs_baseline`` (= bs8 aggregate / 1000,
the headline) and ``detail.vs_baseline_bs1_per_seq`` (the strict bs=1
per-sequence reading of the same target).  Decode configs also report
``hbm_gb_s`` (achieved weight+KV stream bandwidth) and
``hbm_roofline_frac`` (÷ 819 GB/s, the v5e spec number).

Measurement notes: dispatch is asynchronous, so every timed iteration
ends in a real D2H materialization (``np.asarray``) before the clock is
read, and feeds FRESH inputs chained host-side to the previous
iteration's output, so no iteration can be answered from a cached
result.

Prints ONE JSON line to stdout:
  {"metric": "decode_tokens_per_sec_per_chip", "value": N,
   "unit": "tokens/s/chip", "vs_baseline": N/1000, "detail": {...}}
(The reference publishes no numbers of its own — SURVEY §6; this
artifact IS the baseline.)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HBM_GB_S = 819.0  # TPU v5e HBM bandwidth spec
PEAK_BF16_FLOP_S = 197e12  # TPU v5e bf16 peak (MFU denominator)
NORTH_STAR_TOK_S = 1000.0  # BASELINE.json north_star
REPO = os.path.dirname(os.path.abspath(__file__))

# name -> measurement kwargs (per-config timeouts live in TIMEOUTS below)
DECODE_CONFIGS = {
    "llama1b_bs1": dict(model="llama1b", batch=1, prompt_len=128, decode_tokens=256),
    "llama1b_bs8": dict(model="llama1b", batch=8, prompt_len=128, decode_tokens=256),
    "llama1b_bs32": dict(model="llama1b", batch=32, prompt_len=128, decode_tokens=128),
    "int8_bs1": dict(model="llama1b", batch=1, prompt_len=128, decode_tokens=256, quant=True),
    "int8_bs8": dict(model="llama1b", batch=8, prompt_len=128, decode_tokens=256, quant=True),
    "int4_bs8": dict(model="llama1b", batch=8, prompt_len=128, decode_tokens=256,
                     quant="int4"),
    # W8A8 / W4A8: all-integer MXU einsums (no weight convert in the
    # operand stream) — the candidate fix for int8's 47.5%-of-roofline gap
    "int8a8_bs8": dict(model="llama1b", batch=8, prompt_len=128,
                       decode_tokens=256, quant="int8_a8"),
    "int4a8_bs8": dict(model="llama1b", batch=8, prompt_len=128,
                       decode_tokens=256, quant="int4_a8"),
    "gemma2_2b_bs1": dict(model="gemma2_2b", batch=1, prompt_len=128, decode_tokens=256),
    # Gemma-2 aggregate configs (VERDICT r4 task 3): the north star names
    # BOTH models at >1k tok/s/chip; at bs=1 a 5.23 GB model is
    # roofline-capped at ~157 tok/s, so the Gemma number must come from a
    # batched config exactly like llama's headline does
    "gemma2_2b_bs8": dict(model="gemma2_2b", batch=8, prompt_len=128, decode_tokens=256),
    "gemma2_2b_bs16": dict(model="gemma2_2b", batch=16, prompt_len=128, decode_tokens=256),
    # the fused Pallas decode-attention experiment (keep only if it wins)
    "llama1b_bs8_fdec": dict(model="llama1b", batch=8, prompt_len=128,
                             decode_tokens=256, decode_attn="flash_decode"),
    # flagship combo: Pallas decode kernel streaming the int8 KV cache
    "llama1b_bs8_fdec_kvq8": dict(model="llama1b", batch=8, prompt_len=128,
                                  decode_tokens=256, decode_attn="flash_decode",
                                  cache_dtype="int8"),
    "llama3b_seq2048_bs8": dict(
        model="llama3b", batch=8, prompt_len=2048, decode_tokens=64, sampler="top_p"
    ),
    # int8 KV cache at the long-context shape: cache HBM stream halves
    "llama3b_seq2048_bs8_kvq8": dict(
        model="llama3b", batch=8, prompt_len=2048, decode_tokens=64,
        sampler="top_p", cache_dtype="int8",
    ),
    # headline shape with the layer scan unrolled 2x (weight-stream
    # software pipelining experiment; promoted to default only if it wins)
    "llama1b_bs8_unroll2": dict(model="llama1b", batch=8, prompt_len=128,
                                decode_tokens=256,
                                env={"LLMTPU_SCAN_UNROLL": "2"}),
    # not in the default matrix: offline smoke test of the measurement path
    "smoke_tiny": dict(model="tiny", batch=2, prompt_len=16, decode_tokens=8),
}
PREFILL_CONFIGS = {
    "prefill8k_xla": dict(model="llama1b", prompt_len=8192, attn_impl="xla"),
    "prefill8k_flash": dict(model="llama1b", prompt_len=8192, attn_impl="flash"),
    "prefill8k_chunked": dict(model="llama1b", prompt_len=8192, attn_impl="xla",
                              chunk=1024),
}
# Ragged-batch decode: prompts of very different lengths, LEFT-padded
# (generate.generate_ragged).  The XLA path streams the full [B, S_cap]
# cache slab every step regardless of validity; the Pallas decode kernel
# skips each row's invisible blocks (leading pads + tail), so this is the
# workload where the kernel has a structural edge — the win-case evidence
# VERDICT r4 task 2 asks for, on a shape real serving actually has.
RAGGED_CONFIGS = {
    "ragged_bs8_xla": dict(model="llama1b", attn="xla"),
    "ragged_bs8_fdec": dict(model="llama1b", attn="flash_decode"),
    "smoke_ragged": dict(model="tiny", attn="xla", lens=(24, 16, 9, 4),
                         decode=8),
}
# serving-like length mix: mean visible ≈ 31% of the 4224-slot slab, so
# the XLA path streams ~1.1 GB/step of cache the kernel mostly skips
RAGGED_LENS = (4096, 2048, 1536, 1024, 768, 512, 256, 128)
RAGGED_DECODE = 64

# HTTP front-end loadgen (llm_np_cp_tpu/serve/http/): the SAME Poisson
# trace replayed twice on one engine build — direct ServeEngine calls
# (realtime replay) vs in-process HTTP server + asyncio SSE clients — so
# the HTTP layer's TTFT/throughput overhead is a measured delta, not a
# guess.
SERVE_HTTP_CONFIGS = {
    "serve_http_poisson": dict(model="llama1b", requests=32, rate=16.0,
                               prompt_len=512, max_tokens=64, slots=8,
                               block_size=128),
    "smoke_serve_http": dict(model="tiny", requests=6, rate=50.0,
                             prompt_len=16, max_tokens=4, slots=2,
                             block_size=8),
}

# Chaos leg (llm_np_cp_tpu/serve/faults.py + the EngineRunner
# supervisor): the SAME Poisson trace replayed twice over HTTP — clean,
# then under a seeded fault schedule (a tick-thread crash mid-flight and
# a step dispatch fault, plus transient 429s on the smoke) with
# supervised restarts on.  The observables are what an outage costs:
# recovery latency, p99 TTFT degradation vs the clean leg, and
# token-identical recovery (the teacher-forced replay contract).  The
# clean leg doubles as the "chaos off = unchanged numbers" reference.
SERVE_CHAOS_CONFIGS = {
    "serve_chaos_poisson": dict(model="llama1b", requests=32, rate=16.0,
                                prompt_len=512, max_tokens=64, slots=8,
                                block_size=128,
                                chaos="tick_crash@90;decode@40",
                                tick_deadline=60.0, backoff=0.2),
    "smoke_serve_chaos": dict(model="tiny", requests=8, rate=50.0,
                              prompt_len=16, max_tokens=6, slots=2,
                              block_size=8,
                              chaos="tick_crash@8;decode@4;http_429@2:2=0",
                              tick_deadline=30.0, backoff=0.05),
}

# Tick-tail leg (ServeEngine mixed_step): the SAME long-prefill-heavy
# Poisson trace (mixed chat+completion decode budgets, prompts skewed
# long so admissions land mid-decode) replayed twice on one engine
# geometry — the tick with the fused sampling epilogue, and with the XLA
# logits tail — so the tick-tail fusion's Δhost_sync/Δroofline_util are
# measured deltas on identical arrivals at token parity.
SERVE_MIXED_CONFIGS = {
    "serve_mixed_poisson": dict(model="llama1b", requests=32, rate=16.0,
                                prompt_len=512, max_tokens=64, slots=8,
                                block_size=128),
    "smoke_serve_mixed": dict(model="tiny", requests=8, rate=50.0,
                              prompt_len=28, max_tokens=8, slots=2,
                              block_size=8),
}

# Speculative-serving leg (ServeEngine spec_k + serve/spec.py): the
# SAME Poisson arrival schedule replayed twice on one engine geometry —
# plain unified tick vs spec-enabled (every request opts in) — over a
# REPETITIVE-prompt workload (each prompt is a small random pattern
# tiled to length: the extractive/quoting shape where prompt-lookup
# drafting pays).  The observables are the draft-then-verify claims on
# identical arrivals: acceptance rate, decode tok/s and p99 TTFT vs the
# plain leg, TOKEN PARITY (deterministic verify keys make spec streams
# byte-identical), and dispatches-per-tick staying ~1 on the spec leg
# (drafting is host-side; verify lanes ride the one mixed dispatch).
SERVE_SPEC_CONFIGS = {
    "serve_spec_poisson": dict(model="llama1b", requests=32, rate=16.0,
                               prompt_len=512, max_tokens=64, slots=8,
                               block_size=128, spec_k=4, pattern_len=24),
    "smoke_serve_spec": dict(model="tiny", requests=8, rate=50.0,
                             prompt_len=20, max_tokens=12, slots=2,
                             block_size=8, spec_k=4, pattern_len=5),
}

# Mesh-sharded serving (ServeEngine mesh_plan + serve/replica.py): ONE
# shared-prompt Poisson trace (requests cycled over a few distinct prompts)
# replayed over three topologies on identical arrivals — single chip,
# TP=8 (one engine, kv-head-sharded pool), and DP=4 replicas x TP=2
# behind the prefix-affinity router.  The observables: per-chip tok/s,
# p99 TTFT per topology, token parity across all legs, and the
# router's routed/spilled split (shared-prompt traffic must stay
# block-local).  Legs that need more devices than the backend exposes
# are skipped with a note — the config never manufactures virtual
# devices for itself.
SERVE_SHARDED_CONFIGS = {
    "serve_sharded_poisson": dict(model="llama1b", requests=32, rate=16.0,
                                  prompt_len=512, max_tokens=64, slots=8,
                                  block_size=128, distinct_prompts=8,
                                  prefix_cache=True, extra_blocks=32,
                                  tp=8, dp=(4, 2)),
    "smoke_serve_sharded": dict(model="tiny", requests=8, rate=50.0,
                                prompt_len=24, max_tokens=6, slots=2,
                                block_size=8, distinct_prompts=4,
                                prefix_cache=True, extra_blocks=16,
                                tp=2, dp=(2, 2)),
}

# Durable-journal restart leg (serve/journal.py + tools/serve_proc.py):
# REAL server subprocesses, three legs on identical arrivals — plain
# (no journal), journaled (same trace; the delta IS the journal's
# cost: client tok/s regression + off-thread fsync p99 from the
# scrape), and a kill -9 leg (chaos proc_kill SIGKILLs the server
# mid-decode; the parent respawns it on the same port + journal and
# every client resumes via Last-Event-ID).  Observables: token parity
# across the kill (journal replay is teacher-forced, so streams must
# be byte-identical to the plain leg), restart-to-first-resumed-token
# latency (client-observed: cut → first resumed token, including the
# respawned process's model build), and the journal overhead pair.
SERVE_RESTART_CONFIGS = {
    "serve_restart_poisson": dict(model="llama1b", requests=32, rate=16.0,
                                  prompt_len=512, max_tokens=64, slots=8,
                                  block_size=128, kill_tick=90),
    "smoke_serve_restart": dict(model="tiny", requests=8, rate=50.0,
                                prompt_len=16, max_tokens=8, slots=2,
                                block_size=8, kill_tick=14),
}

# Rolling-upgrade leg (serve/lifecycle.py + ReplicaSet.rolling_upgrade):
# ONE Poisson trace over a direct-mode DP fleet, two legs on identical
# arrivals — steady (no roll) and rolling (a full replica-by-replica
# weight swap triggered mid-trace: each replica drains its in-flight
# streams to peers, rebuilds on the "new" checkpoint via clone_fresh,
# and rejoins routing).  Observables are the zero-downtime claims:
# ZERO dropped streams, token parity across the roll (the drain is
# teacher-forced), p99 TTFT degradation during the roll bounded
# (ttft_p99_degradation — what tools/slo_gate.py
# --max-p99-ttft-degradation consumes in CI), and zero new compiles
# for a same-shaped swap (params are jit call arguments; pinned).
SERVE_ROLLING_CONFIGS = {
    "serve_rolling_upgrade": dict(model="llama1b", requests=32, rate=16.0,
                                  prompt_len=512, max_tokens=64, slots=8,
                                  block_size=128, replicas=3,
                                  roll_after_ticks=8),
    "smoke_serve_rolling": dict(model="tiny", requests=16, rate=50.0,
                                prompt_len=16, max_tokens=8, slots=2,
                                block_size=8, replicas=3,
                                roll_after_ticks=3),
}

# Tiered KV prefix cache (serve/host_tier.py): ONE shared-prompt
# Poisson trace whose prefix WORKING SET is ~4x the pool's block
# capacity (distinct prompts cycled round-robin, so every repeat
# arrives after its prefix blocks were LRU-reclaimed), replayed twice
# on identical arrivals — tier off (reclaim drops, repeats re-prefill)
# vs tier on (reclaim spills to host RAM, repeats restore via async
# device_put above the measured breakeven).  Observables: prefix
# hit-rate (strictly higher tier-on), prefill tokens dispatched
# (strictly fewer tier-on — the restored bytes are prefill the fleet
# did not redo), restore-latency p99, p99 TTFT, tok/s, TOKEN PARITY
# (restored K/V is bit-identical to recompute), and
# compiles_added_by_tier == 0 (restores land as ordinary pool blocks
# through one warmed program).  num_blocks deliberately OVERRIDES the
# worst-case sizing: capacity pressure is the whole point.
SERVE_TIER_CONFIGS = {
    "serve_prefix_tiered": dict(model="llama1b", requests=48, rate=16.0,
                                prompt_len=512, max_tokens=64, slots=8,
                                block_size=128, distinct_prompts=24,
                                num_blocks=14, tier_gb=4.0),
    "smoke_serve_prefix_tiered": dict(model="tiny", requests=16,
                                      rate=50.0, prompt_len=24,
                                      max_tokens=6, slots=2,
                                      block_size=8, distinct_prompts=8,
                                      num_blocks=12, tier_gb=1.0),
}

# Multi-tenant fairness leg (serve/tenants.py + the plan_tick
# fair-share prefill order): ONE merged arrival schedule built from
# three independent per-tenant Poisson processes at skewed rates — a
# chat-like tenant (short prompts, short decodes, high rate), a
# completion tenant (medium), and a prefill-heavy batch tenant (long
# prompts, few tokens, low rate) — replayed twice on one engine
# geometry: fairness off (prefill budget fills in admission order) vs
# fairness on (smallest-accumulated-cost-share tenant first).  The
# observables are the accounting-plane claims on identical arrivals:
# per-tenant attainment / goodput / cost share from the TenantLedger
# (what tools/slo_gate.py --min-tenant-attainment gates), each
# tenant's mean first-token RANK (ordinal, so the fairness reorder is
# visible without trusting CPU wall clocks), TOKEN PARITY between the
# legs (fairness reorders prefill scheduling, never content), and
# compiles_added_by_trace == 0 on both legs (ordering is host-side;
# the ragged buckets don't change).
SERVE_TENANT_CONFIGS = {
    "serve_tenant_poisson": dict(
        model="llama1b", slots=8, block_size=128,
        tenants=dict(
            chat=dict(requests=16, rate=24.0, prompt_len=128,
                      max_tokens=32),
            complete=dict(requests=10, rate=8.0, prompt_len=384,
                          max_tokens=64),
            batch=dict(requests=6, rate=3.0, prompt_len=512,
                       max_tokens=8),
        )),
    "smoke_serve_tenant": dict(
        model="tiny", slots=4, block_size=8,
        tenants=dict(
            chat=dict(requests=6, rate=120.0, prompt_len=16,
                      max_tokens=8),
            complete=dict(requests=3, rate=60.0, prompt_len=24,
                          max_tokens=10),
            batch=dict(requests=3, rate=30.0, prompt_len=48,
                       max_tokens=4),
        )),
}

SPEC_CONFIGS = {
    # batched self-speculation: bf16 target + int8 self-draft, γ=4
    "int8_spec_bs8": dict(model="llama1b", batch=8, prompt_len=128,
                          decode_tokens=256, gamma=4),
    # Configs that can plausibly WIN (VERDICT r4 task 5): bs=1 (where
    # decode is maximally bandwidth-bound and batching can't amortize the
    # weight stream) with drafts much cheaper than the int8 self-draft —
    # an int4 self-draft (¼ the stream) and a layer-skip draft (first 8
    # of 16 layers, int4: ~1/6 the stream).  γ kept small: per-cycle cost
    # is γ·draft + 1 verify, so big γ only pays at high acceptance.
    "spec_int4_bs1_g2": dict(model="llama1b", batch=1, prompt_len=128,
                             decode_tokens=256, gamma=2, draft="int4"),
    "spec_int4_bs1_g4": dict(model="llama1b", batch=1, prompt_len=128,
                             decode_tokens=256, gamma=4, draft="int4"),
    "spec_trunc8_bs1_g4": dict(model="llama1b", batch=1, prompt_len=128,
                               decode_tokens=256, gamma=4, draft="trunc8_int4"),
    # offline smoke for the speculative measurement path
    "smoke_spec": dict(model="tiny", batch=2, prompt_len=16, decode_tokens=8,
                       gamma=2),
}
# Priority order, round 5 (VERDICT r4 tasks 1–5): headline anchor first,
# then everything r4 left UNVERIFIED (fused int4
# einsum, rewritten decode kernel, fdec_kvq8, unroll2), then the
# never-measured BASELINE configs (Gemma aggregate, llama-3B), then the
# experiments.  A burned config only costs its own timeout — the summary
# re-emits after each.
PRIORITY = [
    "llama1b_bs8",        # the headline + the anchor every twin compares to
    "int4_bs8",           # r4 fused-nibble einsum fix — never re-measured
    "llama1b_bs8_fdec_kvq8",  # kernel's best shot (VERDICT task 2) — never measured
    "llama1b_bs8_fdec",   # rewritten decode kernel at the headline shape
    "ragged_bs8_xla",     # ragged decode: the kernel's structural win case
    "ragged_bs8_fdec",
    "serve_prefix_tiered",  # host-RAM KV tier: spill/restore vs drop/recompute
    "serve_mixed_poisson",  # the tick: fused epilogue vs the XLA logits tail
    "serve_spec_poisson",  # draft-then-verify vs plain on identical arrivals
    "serve_http_poisson",  # HTTP front-end overhead vs direct engine calls
    "serve_chaos_poisson",  # supervised recovery under a seeded fault schedule
    "serve_restart_poisson",  # kill -9 + journal replay + client resume
    "serve_rolling_upgrade",  # zero-downtime weight swap over the DP fleet
    "serve_sharded_poisson",  # TP pool sharding + DP replicas vs single chip
    "serve_tenant_poisson",  # fair-share prefill + per-tenant accounting
    "gemma2_2b_bs8",      # Gemma north-star number (VERDICT task 3)
    "int8_bs8",           # roofline-gap anchor (VERDICT task 6)
    "int8a8_bs8",         # W8A8 int8-MXU einsums vs that anchor
    "int4a8_bs8",         # W4A8: ¼ weight stream, all-integer contraction
    "decomp",             # ...and the diagnostic that locates that gap
    "llama3b_seq2048_bs8",  # BASELINE config 3 — no number in 4 rounds (task 4)
    "llama1b_bs8_unroll2",  # layer-scan unroll experiment vs bs8
    "gemma2_2b_bs16",
    "prefill8k_xla",
    "prefill8k_flash",
    "prefill8k_chunked",  # BASELINE config 5 via chunked prefill
    "spec_int4_bs1_g2",   # speculation configs that can win (task 5)
    "spec_int4_bs1_g4",
    "spec_trunc8_bs1_g4",
    "gemma2_2b_bs1",      # re-capture: prior-round coverage, cheap
    "llama1b_bs1",
    "llama1b_bs32",
    "int8_spec_bs8",      # the documented-negative bs=8 self-spec point
    "int8_bs1",
    "llama3b_seq2048_bs8_kvq8",
]
# diagnostic children that run as priority slots but aren't matrix configs
EXTRA_CHILDREN = {"decomp"}
# every non-smoke config must be in PRIORITY — a config added to the dicts
# but not the ordering would otherwise silently never run
assert set(PRIORITY) == {
    n
    for n in list(DECODE_CONFIGS) + list(SPEC_CONFIGS)
    + list(PREFILL_CONFIGS) + list(RAGGED_CONFIGS)
    + list(SERVE_HTTP_CONFIGS) + list(SERVE_CHAOS_CONFIGS)
    + list(SERVE_MIXED_CONFIGS) + list(SERVE_SPEC_CONFIGS)
    + list(SERVE_SHARDED_CONFIGS) + list(SERVE_RESTART_CONFIGS)
    + list(SERVE_ROLLING_CONFIGS) + list(SERVE_TIER_CONFIGS)
    + list(SERVE_TENANT_CONFIGS)
    if not n.startswith("smoke")
} | EXTRA_CHILDREN, "PRIORITY out of sync with config dicts"

TIMEOUTS = {
    "llama1b_bs8": 600,
    "gemma2_2b_bs8": 600,  # 2.6B params: first-touch compile + 3 reps
    "gemma2_2b_bs16": 600,  # same model, 2x tokens per rep
    "decomp": 850,  # 6 decode-loop compiles (full/half × 3 quant modes) + head
    "ragged_bs8_xla": 600,  # 2 prefill + 2 loop compiles + 3 rep pairs
    "ragged_bs8_fdec": 600,
    # two trace replays (tier-off + tier-on) on one param build, under
    # DELIBERATE pool-capacity pressure (admissions serialize on
    # blocks, so the trace span stretches well past the shared config)
    "serve_prefix_tiered": 1100,
    # two realtime replays of the trace (direct + HTTP) at wall-clock
    # arrival pacing (~2s traffic span each) on top of the serve compile
    # budget; the HTTP leg adds event-loop + SSE framing time per token
    "serve_http_poisson": 850,
    # two trace replays (fused + XLA tail) on one param build, each
    # with its own warmup — each leg warms one mixed_step compile per
    # packed-width bucket
    "serve_mixed_poisson": 1100,
    # two unified-tick replays (plain + spec) on one param build; the
    # spec leg's verify lanes widen the sample operands, so its bucket
    # warmup compiles its own mixed_step set
    "serve_spec_poisson": 850,
    # clean + chaos HTTP legs at realtime pacing, plus a supervised
    # restart (backoff + pool rebuild + teacher-forced replay prefills)
    # inside the chaos leg's measured span
    "serve_chaos_poisson": 850,
    # three trace replays (single / TP / DP x TP) on one param build;
    # the sharded legs re-place params + pool per topology and the DP
    # leg warms every replica
    "serve_sharded_poisson": 850,
    # FIVE server subprocesses (plain / journaled / journaled_sync /
    # kill / restart), each paying its own model build + warmup, plus
    # the realtime client traffic spans
    "serve_restart_poisson": 1300,
    # two trace replays over a 3-replica direct-mode fleet on one param
    # build, each replica warmed, plus the roll's three clone_fresh
    # rebuilds + teacher-forced drain re-prefills inside the measured
    # span
    "serve_rolling_upgrade": 850,
    # two trace replays (fairness off/on) on one param build; the
    # merged 32-request trace mixes three prompt-length bands, so the
    # bucket warmup compiles one mixed_step set per leg
    "serve_tenant_poisson": 850,
    # prefill-dominated: the marginal measurement's extra prefill+half
    # decode per rep nearly doubles measured-phase wall time
    "llama3b_seq2048_bs8": 700,
    "llama3b_seq2048_bs8_kvq8": 600,
}
DEFAULT_TIMEOUT = 420
PROBE_TIMEOUT = 180
MIN_CONFIG_BUDGET_S = 120  # don't launch a config with less than this left


def _deadline_s() -> float:
    return float(os.environ.get("BENCH_DEADLINE_S", "1500"))


def _half_len(decode_tokens: int) -> int:
    """Half-length decode dispatch of the marginal-rate measurement —
    ONE definition so run_warm AOT-compiles exactly the length
    _measure_decode dispatches."""
    return max(decode_tokens // 2, 1)


def _phase(config: str, phase: str, t0: float, **extra) -> None:
    """Timestamped breadcrumb on stderr.  These survive a parent-side
    timeout kill (recovered from TimeoutExpired.stderr), so a burned
    config still records whether it died in compile or execute."""
    rec = {"config": config, "phase": phase, "t": round(time.perf_counter() - t0, 1)}
    rec.update(extra)
    print("bench-phase " + json.dumps(rec), file=sys.stderr, flush=True)


# ----------------------------------------------------------------------
# Child-process side
# ----------------------------------------------------------------------

def _child_jax():
    """JAX for a child: the platform is whatever ``JAX_PLATFORMS`` says
    (tests export ``cpu``), the compile cache is the one agreed
    directory."""
    import jax

    sys.path.insert(0, REPO)
    from llm_np_cp_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()
    return jax


def _build_model(name: str, quant=False, tag: str | None = None, t0: float | None = None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.config import GEMMA_2_2B, LLAMA_3_2_1B, LLAMA_3_2_3B, tiny_config
    from llm_np_cp_tpu.models.transformer import init_params

    config = {
        "llama1b": LLAMA_3_2_1B,
        "llama3b": LLAMA_3_2_3B,
        "gemma2_2b": GEMMA_2_2B,
        "tiny": tiny_config("llama"),
    }[name]
    # Breadcrumb BEFORE the first device op (VERDICT r4 weak #6: with no
    # pre-build phases, a dead backend, a slow params materialization and
    # a hung compile were indistinguishable in a timeout diagnosis).
    if tag is not None and t0 is not None:
        _phase(tag, "params_init_start", t0)
    # Random bf16 weights — no checkpoint downloads in this environment;
    # decode throughput is weight-value-independent.  init_params is ONE
    # jitted program: a single dispatch, on-device materialization.
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.bfloat16)
    # fence: make "params_built" mean MATERIALIZED, not just dispatched
    np.asarray(jax.tree.leaves(params)[0][..., :1])
    if quant:  # True/"int8" → 8-bit, "int4" → 4-bit, "*_a8" → act quant
        from llm_np_cp_tpu.quant import quantize_params

        params = quantize_params(
            params, bits=4 if str(quant).startswith("int4") else 8,
            act_quant=str(quant).endswith("_a8"),
        )
    return config, params


def _tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _chained_reps(one, seed_prompt, vocab_size, reps=3):
    """Run ``one(prompt_host, tag)`` reps+1 times (first is compile warmup)
    with FRESH inputs each rep, chained through the previous output, so
    no rep can be answered from a cached (executable, args) result.

    ``one`` returns a result dict that includes ``"chain"``: an int derived
    from a materialized (host) output, proving the execution completed and
    perturbing the next prompt; ``tag`` ("warmup"/"repN") lets it emit
    bench-phase breadcrumbs.  Returns ``(warm_s, results)``: the warmup
    wall-clock (the compile-phase cost, reported separately) and the
    ``reps`` measured dicts.
    """
    carry = seed_prompt
    t0 = time.perf_counter()
    out = one(carry, "warmup")  # compile
    # a measurement fn can report time its warmup spent EXECUTING extra
    # segments (e.g. _measure_decode's half-run) so the compile-phase
    # number stays comparable across rounds
    warm_s = time.perf_counter() - t0 - out.get("extra_s", 0.0)
    results = []
    for i in range(reps):
        carry = (carry + out["chain"] + i + 1) % vocab_size
        out = one(carry, f"rep{i}")
        results.append(out)
    return warm_s, results


def _measure_decode(name, config, params, prefill, loop, batch, prompt_len,
                    decode_tokens, reps=3, t_start=None,
                    cache_dtype=None):
    """Median TTFT + aggregate decode rate over ``reps`` fresh-input runs.

    Warmup is split into two timed phases (prefill compile, decode-loop
    compile) with ``bench-phase`` breadcrumbs, so a timeout kill records
    which compile burned the budget (VERDICT r2 weak #2: the bs=8 600 s
    timeout was undiagnosable from artifacts).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.cache import KVCache, align_capacity

    key = jax.random.PRNGKey(0)
    # the same capacity sizing Generator._init_cache uses in production
    max_seq = align_capacity(prompt_len + decode_tokens + 8)
    rng = np.random.default_rng(batch)
    if t_start is None:
        t_start = time.perf_counter()

    cache_dtype = cache_dtype or jnp.bfloat16

    half = _half_len(decode_tokens)

    def one(prompt_host, tag):
        cache = KVCache.init(config, batch, max_seq, dtype=cache_dtype)
        t0 = time.perf_counter()
        tok0, cache, _ = prefill(params, jnp.asarray(prompt_host, jnp.int32), cache, key)
        np.asarray(tok0)  # real D2H: the clock stops on materialized tokens
        t1 = time.perf_counter()
        _phase(name, f"{tag}:prefill_done", t_start, dt=round(t1 - t0, 1))
        toks, cache, _steps = loop(params, tok0, cache, key, decode_tokens)
        toks_host = np.asarray(toks)
        t2 = time.perf_counter()
        _phase(name, f"{tag}:decode_done", t_start, dt=round(t2 - t1, 1))
        # a HALF-length dispatch of the same loop: the fixed per-dispatch
        # cost (host dispatch + result fetch) cancels in the marginal
        # rate Δtokens/Δtime, isolating the steady-state on-chip rate the
        # e2e number under-reports.  Fresh cache + perturbed prompt — the
        # full run's cache was donated.
        cache_h = KVCache.init(config, batch, max_seq, dtype=cache_dtype)
        tok_h, cache_h, _ = prefill(
            params,
            jnp.asarray((prompt_host + 1) % config.vocab_size, jnp.int32),
            cache_h, key,
        )
        np.asarray(tok_h)  # fence: keep prefill out of the half timing
        t3 = time.perf_counter()
        toks_h, _, _ = loop(params, tok_h, cache_h, key, half)
        np.asarray(toks_h)
        t4 = time.perf_counter()
        _phase(name, f"{tag}:half_done", t_start, dt=round(t4 - t3, 1))
        return {
            "ttft": t1 - t0,
            "rate": batch * decode_tokens / (t2 - t1),
            "t_full": t2 - t1,
            "t_half": t4 - t3,
            "extra_s": t4 - t2,  # the half segment (its prefill included)
            "chain": int(toks_host.sum()),
        }

    compile_s, runs = _chained_reps(
        one, rng.integers(0, config.vocab_size, (batch, prompt_len)),
        config.vocab_size, reps,
    )
    t_full = float(np.median([r["t_full"] for r in runs]))
    t_half = float(np.median([r["t_half"] for r in runs]))
    marginal = None
    if t_full > t_half * 1.1:
        marginal = batch * (decode_tokens - half) / (t_full - t_half)
    return (
        float(np.median([r["ttft"] for r in runs])),
        float(np.median([r["rate"] for r in runs])),
        compile_s,
        marginal,
    )


def run_decode_config(name: str) -> dict:
    import numpy as np

    from llm_np_cp_tpu.generate import make_decode_loop_fn, make_prefill_fn
    from llm_np_cp_tpu.ops.sampling import Sampler

    t0 = time.perf_counter()
    spec = DECODE_CONFIGS[name]
    config, params = _build_model(
        spec["model"], quant=spec.get("quant", False), tag=name, t0=t0
    )
    _phase(name, "params_built", t0)
    sampler = Sampler(kind=spec.get("sampler", "greedy"))
    prefill = make_prefill_fn(config, sampler)
    loop = make_decode_loop_fn(
        config, sampler, attn_impl=spec.get("decode_attn", "xla")
    )
    batch, prompt_len, decode_tokens = spec["batch"], spec["prompt_len"], spec["decode_tokens"]

    import jax.numpy as jnp

    kv_quant = spec.get("cache_dtype") == "int8"
    ttft, rate, compile_s, marginal = _measure_decode(
        name, config, params, prefill, loop, batch, prompt_len, decode_tokens,
        t_start=t0, cache_dtype=jnp.int8 if kv_quant else None,
    )

    # Roofline accounting: each decode step streams the full weight set plus
    # the valid KV prefix for every sequence (mean length over the run).
    param_bytes = _tree_bytes(params)
    mean_len = prompt_len + decode_tokens / 2
    kv_elem_bytes = 1 + 4 / config.head_dim if kv_quant else 2
    kv_bytes_per_tok = int(
        config.num_hidden_layers * 2 * config.num_key_value_heads
        * config.head_dim * kv_elem_bytes
    )
    step_bytes = param_bytes + batch * mean_len * kv_bytes_per_tok
    steps_per_s = rate / batch
    hbm_gb_s = steps_per_s * step_bytes / 1e9
    return {
        "config": name,
        "ok": True,
        "decode_tok_s_chip": round(rate, 1),
        "per_seq_tok_s": round(rate / batch, 1),
        # steady-state rate with the fixed per-dispatch transport cost
        # cancelled (two-length marginal); e2e rate stays the headline
        **({"decode_tok_s_chip_marginal": round(marginal, 1)}
           if marginal is not None else {}),
        "ttft_s_p50": round(ttft, 4),
        "hbm_gb_s": round(hbm_gb_s, 1),
        "hbm_roofline_frac": round(hbm_gb_s / HBM_GB_S, 3),
        "param_gb": round(param_bytes / 1e9, 2),
        "compile_s": round(compile_s, 1),
        "batch": batch,
        "prompt_len": prompt_len,
        "decode_tokens": decode_tokens,
    }


def run_prefill_config(name: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.cache import KVCache, align_capacity
    from llm_np_cp_tpu.generate import make_chunked_prefill_fn, make_prefill_fn
    from llm_np_cp_tpu.ops.sampling import Sampler

    t_start = time.perf_counter()
    spec = PREFILL_CONFIGS[name]
    config, params = _build_model(spec["model"], tag=name, t0=t_start)
    _phase(name, "params_built", t_start)
    prompt_len = spec["prompt_len"]
    chunk = spec.get("chunk")
    if chunk:
        prefill = make_chunked_prefill_fn(
            config, Sampler(kind="greedy"), chunk_size=chunk,
            attn_impl=spec["attn_impl"],
        )
    else:
        prefill = make_prefill_fn(
            config, Sampler(kind="greedy"), attn_impl=spec["attn_impl"]
        )
    key = jax.random.PRNGKey(0)
    rng = np.random.default_rng(0)

    def one(prompt_host, tag):
        cache = KVCache.init(
            config, 1, align_capacity(prompt_len + 8), dtype=jnp.bfloat16
        )
        t0 = time.perf_counter()
        tok0, _, _ = prefill(params, jnp.asarray(prompt_host, jnp.int32), cache, key)
        out = np.asarray(tok0)
        dt = time.perf_counter() - t0
        _phase(name, f"{tag}:prefill_done", t_start, dt=round(dt, 1))
        return {"ttft": dt, "chain": int(out.sum())}

    compile_s, runs = _chained_reps(
        one, rng.integers(0, config.vocab_size, (1, prompt_len)),
        config.vocab_size,
    )
    ttft = float(np.median([r["ttft"] for r in runs]))
    # MFU vs the v5e bf16 peak (VERDICT r3 weak #5): matmul FLOPs are
    # 2·N_params·S (the tied head's vocab matmul counts via N; the embed
    # gather is free) plus causal attention 2·L·S²·H·D per QKᵀ/PV pair.
    n_params = sum(x.size for x in jax.tree.leaves(params))
    flops = 2.0 * n_params * prompt_len + (
        2.0 * config.num_hidden_layers * prompt_len**2
        * config.num_attention_heads * config.head_dim
    )
    return {
        "config": name,
        "ok": True,
        "ttft_s_p50": round(ttft, 4),
        "prefill_tok_s": round(prompt_len / ttft, 1),
        "mfu": round(flops / ttft / PEAK_BF16_FLOP_S, 4),
        "prompt_len": prompt_len,
        "attn_impl": spec["attn_impl"],
        **({"chunk": chunk} if chunk else {}),
        "compile_s": round(compile_s, 1),
    }


def run_ragged_config(name: str) -> dict:
    """Aggregate decode rate over a ragged batch (mixed prompt lengths,
    left-padded).  Rates come from the difference of two matched calls
    (full- vs half-length decode, identical prompt shapes): the prefill
    cost and the fixed per-dispatch transport cancel in
    Δtokens/Δtime, isolating the steady-state decode rate — the number
    where the kernel's per-row block skipping should show up against the
    XLA path's full-slab streaming."""
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.generate import Generator
    from llm_np_cp_tpu.ops.sampling import Sampler

    t0 = time.perf_counter()
    spec = RAGGED_CONFIGS[name]
    lens = spec.get("lens", RAGGED_LENS)
    n_full = spec.get("decode", RAGGED_DECODE)
    n_half = max(n_full // 2, 1)
    b = len(lens)
    config, params = _build_model(spec["model"], tag=name, t0=t0)
    _phase(name, "params_built", t0)
    gen = Generator(
        params, config, sampler=Sampler(kind="greedy"),
        decode_attn=spec["attn"],
    )
    # Generator's Mosaic gate downgrades a rejected kernel to XLA; record
    # the verdict so a downgraded run can't masquerade as a kernel number
    gate_error = None
    if spec["attn"] == "flash_decode":
        from llm_np_cp_tpu.ops.pallas.support import kernel_error

        gate_error = kernel_error("decode_attention")
    rng = np.random.default_rng(11)

    def one(seed_val, tag):
        prompts = [
            (rng.integers(0, config.vocab_size, L) + seed_val)
            % config.vocab_size
            for L in lens
        ]
        t1 = time.perf_counter()
        res_f = gen.generate_ragged(prompts, n_full, seed=int(seed_val) % 97)
        t2 = time.perf_counter()
        res_h = gen.generate_ragged(
            [(p + 1) % config.vocab_size for p in prompts], n_half,
            seed=int(seed_val) % 89,
        )
        t3 = time.perf_counter()
        _phase(name, f"{tag}:pair_done", t0,
               dt_full=round(t2 - t1, 1), dt_half=round(t3 - t2, 1))
        return {
            "t_full": t2 - t1,
            "t_half": t3 - t2,
            "ttft": res_f.ttft_s,
            "extra_s": t3 - t2,
            "chain": int(np.asarray(res_f.tokens).sum() % 10007)
            + int(np.asarray(res_h.tokens).sum() % 101),
        }

    _, runs = _chained_reps(one, 3, 10**9)
    t_full = float(np.median([r["t_full"] for r in runs]))
    t_half = float(np.median([r["t_half"] for r in runs]))
    marginal = (
        b * (n_full - n_half) / (t_full - t_half)
        if t_full > t_half * 1.05 else None
    )
    from llm_np_cp_tpu.cache import align_capacity

    cap = align_capacity(max(lens) + n_full)
    slab_gb = (
        config.num_hidden_layers * 2 * b * cap
        * config.num_key_value_heads * config.head_dim * 2 / 1e9
    )
    return {
        "config": name,
        "ok": True,
        # e2e number includes prefill of the ragged batch; marginal is
        # the steady-state decode rate (prefill+transport cancelled)
        **({"decode_tok_s_chip_marginal": round(marginal, 1)}
           if marginal is not None else {}),
        "decode_tok_s_chip_e2e": round(b * n_full / t_full, 1),
        "ttft_s_p50": round(float(np.median([r["ttft"] for r in runs])), 4),
        "attn": spec["attn"],
        **({"kernel_downgraded_to_xla": gate_error} if gate_error else {}),
        "prompt_lens": list(lens),
        "decode_tokens": n_full,
        "cache_capacity": cap,
        "cache_slab_gb": round(slab_gb, 2),
    }


def run_serve_mixed_config(name: str) -> dict:
    """The tick-tail fusion head-to-head: ONE long-prefill-heavy Poisson
    trace (prompts skewed toward the long end, mixed chat+completion
    decode budgets) replayed through two engines of identical geometry
    — ``mixed`` (one ragged mixed dispatch per tick with the SLO
    token-budget planner; fused sampling epilogue when the probe
    passes) and ``mixed_xla_tail`` (the same tick with
    ``sample_epilogue="off"`` — the XLA final_logits+sampler oracle).
    The observables: p99 TTFT, decode tok/s, token parity between the
    legs, one dispatch per tick, and for the fused-vs-unfused pair on
    identical arrivals: Δhost_sync p99 + share, Δroofline utilization,
    and the one-fetch ceiling (host_fetches <= 1 per tick,
    trace-verified) — what ``tools/slo_gate.py --min-bandwidth-util``
    gates on live captures."""
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.serve import ServeEngine, TraceRecorder, poisson_trace
    from tools.summarize_trace import mixed_utilization

    t0 = time.perf_counter()
    spec = SERVE_MIXED_CONFIGS[name]
    config, params = _build_model(spec["model"], tag=name, t0=t0)
    _phase(name, "params_built", t0)
    from llm_np_cp_tpu.ops.pallas.support import (
        kernel_error,
        ragged_kernel_name,
    )
    from llm_np_cp_tpu.serve.engine import pool_geometry

    bs = spec["block_size"]
    chunk = min(bs * 2, 256)
    _, num_blocks, max_seq_len = pool_geometry(
        spec["prompt_len"], spec["max_tokens"], spec["slots"], bs,
        prefill_chunk=chunk,
    )
    ragged_err = kernel_error(ragged_kernel_name(False))
    from llm_np_cp_tpu.serve.telemetry import TelemetryModel

    # one shared roofline model for both legs (immutable, config+params
    # derived): the legs record achieved GB/s / utilization / MFU so
    # tools/slo_gate.py --min-bandwidth-util can gate live captures
    telemetry = TelemetryModel(config, params)

    # long-prefill-heavy: prompts in the TOP half of the length range,
    # decode budgets mixed chat (short) + completion (long) — the shape
    # where a monolithic prefill visibly stalls the decode batch
    rng = np.random.default_rng(17)
    trace = poisson_trace(
        rng, spec["requests"], rate_rps=spec["rate"],
        prompt_len_range=(max(spec["prompt_len"] // 2, 2),
                          spec["prompt_len"]),
        max_new_tokens=(max(spec["max_tokens"] // 8, 1),
                        spec["max_tokens"]),
        vocab_size=config.vocab_size, seed_base=17,
    )
    _phase(name, "trace_built", t0)

    per_leg: dict = {}
    tokens_by_leg: dict = {}
    for leg, epilogue in (("mixed", "auto"), ("mixed_xla_tail", "off")):
        # the fused-vs-unfused pair reads its host_sync column from the
        # trace plane (per-tick host_sync_us + the one-fetch ceiling)
        tracer = TraceRecorder()
        engine = ServeEngine(
            params, config,
            sampler=Sampler(kind="greedy"),
            max_slots=spec["slots"],
            num_blocks=num_blocks,
            block_size=bs,
            max_seq_len=max_seq_len,
            prefill_chunk=chunk,
            cache_dtype=jnp.bfloat16,
            sample_epilogue=epilogue,
            telemetry=telemetry,
            tracer=tracer,
        )
        engine.warmup([int(t["prompt"].size) for t in trace],
                      max_new_tokens=spec["max_tokens"])
        engine.n_dispatches = 0  # count the measured span only
        _phase(name, f"warmed_{leg}", t0)
        snap = engine.replay_trace(trace)
        _phase(name, f"trace_drained_{leg}", t0, ticks=snap["ticks"])
        tokens_by_leg[leg] = {
            r.req_id: list(r.generated)
            for r in engine.scheduler.finished
        }
        per_leg[leg] = {
            "ok": snap["finished"] == spec["requests"],
            "throughput_tok_s": round(snap["throughput_tok_s"], 1),
            "ttft_s_p50": round(snap.get("ttft_s_p50", float("nan")), 4),
            "ttft_s_p99": round(snap.get("ttft_s_p99", float("nan")), 4),
            "decode_tok_s_p50": round(snap.get("decode_tok_s_p50",
                                               float("nan")), 1),
            "ticks": snap["ticks"],
            "dispatches": engine.n_dispatches,
            "dispatches_per_tick": round(
                engine.n_dispatches / max(snap["ticks"], 1), 3
            ),
            "preemptions": snap["preemptions"],
            "mixed_prefill_tokens": snap["mixed_prefill_tokens"],
            "mixed_decode_tokens": snap["mixed_decode_tokens"],
            # roofline telemetry (CPU: the absolute GB/s is meaningless
            # — no HBM — but the fields prove the plumbing and give
            # slo_gate --min-bandwidth-util its input on live captures)
            "roofline_gbps_mean": round(
                snap.get("roofline_gbps_mean", 0.0), 4),
            "roofline_util_mean": round(
                snap.get("roofline_util_mean", 0.0), 8),
            "mfu_mean": round(snap.get("mfu_mean", 0.0), 8),
            "hbm_gbps": snap.get("hbm_gbps"),
            "compile_counts": engine.compile_counts(),
            "epilogue": engine.epilogue_impl,
            "ragged_attn_impl": engine.ragged_attn_impl,
            "tick_token_budget": engine.tick_token_budget,
            "buckets": list(engine.mixed_buckets),
        }
        util = mixed_utilization(tracer.events()) or {}
        per_leg[leg]["host_sync_us_p99"] = round(
            util.get("host_sync_us_p99", 0.0), 1)
        per_leg[leg]["host_sync_share"] = round(
            util.get("host_sync_share", 0.0), 4)
        per_leg[leg]["host_fetches_max"] = util.get("host_fetches_max", 0)
        del engine

    fused_parity = tokens_by_leg["mixed"] == tokens_by_leg["mixed_xla_tail"]
    m, xt = per_leg["mixed"], per_leg["mixed_xla_tail"]
    return {
        "config": name,
        "ok": all(r["ok"] for r in per_leg.values()) and fused_parity,
        "requests": spec["requests"],
        "rate_rps": spec["rate"],
        "slots": spec["slots"],
        "pool_blocks": num_blocks,
        "block_size": bs,
        # the tick-tail fusion pair: identical arrivals, fused epilogue
        # vs the XLA logits tail — token parity is the non-negotiable
        # bar, the deltas are the win (signs meaningful on live HBM;
        # on CPU the fields prove the plumbing)
        "token_parity_fused_vs_xla_tail": fused_parity,
        "epilogue": m["epilogue"],
        "host_sync_p99_delta_us": round(
            xt["host_sync_us_p99"] - m["host_sync_us_p99"], 1),
        "roofline_util_delta": round(
            m["roofline_util_mean"] - xt["roofline_util_mean"], 8),
        "host_fetches_max": m["host_fetches_max"],
        # headline: the fused leg's numbers
        "ttft_s_p99": m["ttft_s_p99"],
        "decode_tok_s_p50": m["decode_tok_s_p50"],
        "throughput_tok_s": m["throughput_tok_s"],
        "dispatches_per_tick": m["dispatches_per_tick"],
        # headline roofline mirror (the unified leg's — what
        # slo_gate --min-bandwidth-util consumes)
        "roofline_gbps_mean": m["roofline_gbps_mean"],
        "roofline_util_mean": m["roofline_util_mean"],
        "hbm_gbps": m["hbm_gbps"],
        "legs": per_leg,
        "ragged_kernel_probe": ragged_err or "ok",
    }


def run_serve_spec_config(name: str) -> dict:
    """Speculative serving vs plain unified tick: ONE Poisson arrival
    schedule over repetitive prompts (random patterns tiled to length —
    the extractive shape where prompt-lookup drafting pays) replayed
    through two engines of identical geometry — ``spec_k=0`` and
    ``spec_k=K`` with every request opted in.  Observables: acceptance
    rate and mean accept length, decode tok/s and p99 TTFT deltas on
    identical arrivals, TOKEN PARITY between the legs (the deterministic
    (seed, content-pos) verify keys make accepted streams byte-identical
    to plain decode), and dispatches-per-tick staying ~1 on the spec leg
    (drafting is host-side; verify lanes ride the one mixed dispatch).
    Both legs carry SLO trackers so ``tools/slo_gate.py`` can gate on
    the leg summaries (attainment/goodput/burn in the JSON)."""
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.serve import ServeEngine, poisson_trace
    from llm_np_cp_tpu.serve.slo import SLOPolicy, SLOTracker

    t0 = time.perf_counter()
    spec = SERVE_SPEC_CONFIGS[name]
    config, params = _build_model(spec["model"], tag=name, t0=t0)
    _phase(name, "params_built", t0)
    from llm_np_cp_tpu.ops.pallas.support import (
        kernel_error,
        ragged_kernel_name,
    )
    from llm_np_cp_tpu.serve.engine import pool_geometry

    bs = spec["block_size"]
    chunk = min(bs * 2, 256)
    _, num_blocks, max_seq_len = pool_geometry(
        spec["prompt_len"], spec["max_tokens"], spec["slots"], bs,
        prefill_chunk=chunk,
    )
    ragged_err = kernel_error(ragged_kernel_name(False))
    from llm_np_cp_tpu.serve.telemetry import TelemetryModel

    telemetry = TelemetryModel(config, params)

    rng = np.random.default_rng(23)
    trace = poisson_trace(
        rng, spec["requests"], rate_rps=spec["rate"],
        prompt_len_range=(max(spec["prompt_len"] // 4, 2),
                          spec["prompt_len"]),
        max_new_tokens=spec["max_tokens"], vocab_size=config.vocab_size,
        seed_base=23,
    )
    # repetitive prompts: tile a small per-request random pattern to the
    # drawn length, so the suffix n-gram always has a prior occurrence
    # (the prompt-lookup draft's win case: quoting/extractive traffic)
    pat = spec["pattern_len"]
    for item in trace:
        base = rng.integers(1, config.vocab_size, size=pat,
                            dtype=np.int64).astype(np.int32)
        item["prompt"] = np.resize(base, item["prompt"].size)
    _phase(name, "trace_built", t0)

    per_leg: dict = {}
    tokens_by_leg: dict = {}
    for leg, k in (("plain", 0), ("spec", spec["spec_k"])):
        engine = ServeEngine(
            params, config,
            sampler=Sampler(kind="greedy"),
            max_slots=spec["slots"],
            num_blocks=num_blocks,
            block_size=bs,
            max_seq_len=max_seq_len,
            prefill_chunk=chunk,
            cache_dtype=jnp.bfloat16,
            spec_k=k,
            telemetry=telemetry,
        )
        engine.warmup([int(t["prompt"].size) for t in trace],
                      max_new_tokens=spec["max_tokens"])
        engine.metrics.slo = SLOTracker(
            SLOPolicy(ttft_s=5.0, tpot_s=1.0, target=0.99),
            clock=engine.clock,
        )
        engine.n_dispatches = 0  # count the measured span only
        _phase(name, f"warmed_{leg}", t0)
        leg_trace = [
            dict(item, speculative=k > 0) for item in trace
        ]
        snap = engine.replay_trace(leg_trace)
        _phase(name, f"trace_drained_{leg}", t0, ticks=snap["ticks"])
        tokens_by_leg[leg] = {
            r.req_id: list(r.generated)
            for r in engine.scheduler.finished
        }
        per_leg[leg] = {
            "ok": snap["finished"] == spec["requests"],
            "throughput_tok_s": round(snap["throughput_tok_s"], 1),
            "ttft_s_p50": round(snap.get("ttft_s_p50", float("nan")), 4),
            "ttft_s_p99": round(snap.get("ttft_s_p99", float("nan")), 4),
            "decode_tok_s_p50": round(snap.get("decode_tok_s_p50",
                                               float("nan")), 1),
            "ticks": snap["ticks"],
            "dispatches": engine.n_dispatches,
            "dispatches_per_tick": round(
                engine.n_dispatches / max(snap["ticks"], 1), 3
            ),
            "preemptions": snap["preemptions"],
            "goodput_tok_s": round(snap.get("goodput_tok_s", 0.0), 1),
            "slo_attainment": snap.get("slo_attainment"),
            "slo_burn_rate_5m": snap.get("slo_burn_rate_5m", 0.0),
            # roofline telemetry: on the spec leg the verify lanes ride
            # the same HBM sweep, so utilization per emitted token is
            # the whole speculative win made visible
            "roofline_gbps_mean": round(
                snap.get("roofline_gbps_mean", 0.0), 4),
            "roofline_util_mean": round(
                snap.get("roofline_util_mean", 0.0), 8),
            "mfu_mean": round(snap.get("mfu_mean", 0.0), 8),
            "hbm_gbps": snap.get("hbm_gbps"),
            "compile_counts": engine.compile_counts(),
        }
        if k:
            per_leg[leg].update({
                "spec_k": k,
                "spec_drafted_tokens": snap.get("spec_drafted_tokens", 0),
                "spec_accepted_tokens": snap.get("spec_accepted_tokens", 0),
                "acceptance_rate": round(
                    snap.get("spec_accept_rate", 0.0), 4
                ),
                "spec_accept_len_mean": round(
                    snap.get("spec_accept_len_mean", 0.0), 3
                ),
                "ragged_attn_impl": engine.ragged_attn_impl,
            })
        del engine
    parity = tokens_by_leg["plain"] == tokens_by_leg["spec"]
    p, s = per_leg["plain"], per_leg["spec"]
    return {
        "config": name,
        "ok": all(r["ok"] for r in per_leg.values()) and parity
        and s["spec_drafted_tokens"] > 0,
        "requests": spec["requests"],
        "rate_rps": spec["rate"],
        "slots": spec["slots"],
        "spec_k": spec["spec_k"],
        "token_parity_spec_vs_plain": parity,
        # headline: what a verify sweep buys on identical arrivals
        "acceptance_rate": s["acceptance_rate"],
        "spec_accept_len_mean": s["spec_accept_len_mean"],
        "throughput_tok_s": s["throughput_tok_s"],
        "throughput_tok_s_plain": p["throughput_tok_s"],
        "ttft_s_p99": s["ttft_s_p99"],
        "ttft_s_p99_plain": p["ttft_s_p99"],
        "decode_tok_s_p50": s["decode_tok_s_p50"],
        "decode_tok_s_p50_plain": p["decode_tok_s_p50"],
        "dispatches_per_tick": s["dispatches_per_tick"],
        "ticks_spec_vs_plain": [s["ticks"], p["ticks"]],
        # headline roofline mirror (the spec leg's — what
        # slo_gate --min-bandwidth-util consumes)
        "roofline_gbps_mean": s["roofline_gbps_mean"],
        "roofline_util_mean": s["roofline_util_mean"],
        "hbm_gbps": s["hbm_gbps"],
        "legs": per_leg,
        "ragged_kernel_probe": ragged_err or "ok",
    }


def run_serve_tier_config(name: str) -> dict:
    """Tiered KV prefix cache: the SAME capacity-stressed shared-prompt
    trace (prefix working set ~4x pool blocks; distinct prompts cycled
    so every repeat outlives its cached blocks) through two engines of
    identical geometry — ``host_tier=None`` (LRU reclaim drops, every
    repeat re-prefills) vs ``host_tier=HostTier(...)`` (reclaim spills
    to host RAM, repeats restore above the measured breakeven).  The
    observables are the ISSUE's acceptance targets: strictly higher
    prefix hit-rate and strictly fewer prefill tokens dispatched on the
    tier leg, restore-latency p99, p99 TTFT / tok/s deltas, token
    parity, and ``compiles_added_by_tier == 0``.  Both legs carry SLO
    trackers so ``tools/slo_gate.py`` can gate the leg summaries."""
    import math

    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.serve import ServeEngine, poisson_trace
    from llm_np_cp_tpu.serve.host_tier import HostTier
    from llm_np_cp_tpu.serve.slo import SLOPolicy, SLOTracker

    t0 = time.perf_counter()
    spec = SERVE_TIER_CONFIGS[name]
    config, params = _build_model(spec["model"], tag=name, t0=t0)
    _phase(name, "params_built", t0)
    from llm_np_cp_tpu.ops.pallas.support import (
        kernel_error,
        ragged_kernel_name,
    )

    bs = spec["block_size"]
    chunk = min(bs * 2, 256)
    num_blocks = spec["num_blocks"]  # deliberately capacity-starved
    max_seq_len = -(-(spec["prompt_len"] + spec["max_tokens"] + chunk)
                    // bs) * bs
    ragged_err = kernel_error(ragged_kernel_name(False))

    # uniform full-length prompts: every distinct prompt contributes
    # the same shareable block count, so the working-set ratio is exact
    rng = np.random.default_rng(29)
    trace = poisson_trace(
        rng, spec["requests"], rate_rps=spec["rate"],
        prompt_len_range=(spec["prompt_len"], spec["prompt_len"]),
        max_new_tokens=spec["max_tokens"], vocab_size=config.vocab_size,
        seed_base=29, distinct_prompts=spec["distinct_prompts"],
    )
    unit = math.lcm(bs, chunk) // bs
    w = -(-spec["prompt_len"] // chunk) * chunk
    keys_per_prompt = ((w - chunk) // (unit * bs)) * unit
    working_set = spec["distinct_prompts"] * keys_per_prompt
    _phase(name, "trace_built", t0, working_set_blocks=working_set,
           pool_capacity=num_blocks - 1)

    per_leg: dict = {}
    tokens_by_leg: dict = {}
    for leg in ("tier_off", "tier_on"):
        tier = HostTier(int(spec["tier_gb"] * 2**30)) \
            if leg == "tier_on" else None
        engine = ServeEngine(
            params, config,
            sampler=Sampler(kind="greedy"),
            max_slots=spec["slots"],
            num_blocks=num_blocks,
            block_size=bs,
            max_seq_len=max_seq_len,
            prefill_chunk=chunk,
            cache_dtype=jnp.bfloat16,
            enable_prefix_cache=True,
            host_tier=tier,
        )
        engine.warmup([int(t["prompt"].size) for t in trace],
                      max_new_tokens=spec["max_tokens"])
        warm_compiles = dict(engine.compile_counts())
        engine.metrics.slo = SLOTracker(
            SLOPolicy(ttft_s=5.0, tpot_s=1.0, target=0.99),
            clock=engine.clock,
        )
        engine.n_dispatches = 0  # count the measured span only
        _phase(name, f"warmed_{leg}", t0)
        snap = engine.replay_trace(trace)
        if tier is not None:
            tier.drain()
        _phase(name, f"trace_drained_{leg}", t0, ticks=snap["ticks"])
        tokens_by_leg[leg] = {
            r.req_id: list(r.generated)
            for r in engine.scheduler.finished
        }
        counts = engine.compile_counts()
        per_leg[leg] = {
            "ok": snap["finished"] == spec["requests"],
            "throughput_tok_s": round(snap["throughput_tok_s"], 1),
            "ttft_s_p50": round(snap.get("ttft_s_p50", float("nan")), 4),
            "ttft_s_p99": round(snap.get("ttft_s_p99", float("nan")), 4),
            "ticks": snap["ticks"],
            "preemptions": snap["preemptions"],
            "prefix_hit_rate": round(snap.get("prefix_hit_rate", 0.0), 4),
            "prefix_blocks_hit": snap.get("prefix_blocks_hit", 0),
            "prefix_evicted_blocks": snap.get("prefix_evicted_blocks", 0),
            "mixed_prefill_tokens": snap["mixed_prefill_tokens"],
            "goodput_tok_s": round(snap.get("goodput_tok_s", 0.0), 1),
            "slo_attainment": snap.get("slo_attainment"),
            "compile_counts": counts,
            "compiles_added_by_trace": (
                counts.get("mixed_step", 0)
                - warm_compiles.get("mixed_step", 0)
            ),
        }
        if tier is not None:
            st = tier.stats()
            per_leg[leg].update({
                "tier_spilled_blocks": st["spilled_blocks"],
                "tier_restored_blocks": st["restored_blocks"],
                "tier_restored_bytes": st["restored_bytes"],
                "tier_restore_misses": st["restore_misses"],
                "tier_skipped_blocks": st["skipped_blocks"],
                "tier_restore_s_p99": round(
                    snap.get("tier_restore_s_p99", 0.0), 6),
                "tier_breakeven_ratio": round(
                    snap.get("tier_breakeven_ratio", 0.0), 3),
                "tier_restore_gbps": round(st["restore_gbps"], 3),
            })
            tier.close()
        del engine
    parity = tokens_by_leg["tier_off"] == tokens_by_leg["tier_on"]
    off, on = per_leg["tier_off"], per_leg["tier_on"]
    hit_win = on["prefix_hit_rate"] > off["prefix_hit_rate"]
    prefill_win = (on["mixed_prefill_tokens"]
                   < off["mixed_prefill_tokens"])
    return {
        "config": name,
        "ok": (all(r["ok"] for r in per_leg.values()) and parity
               and hit_win and prefill_win
               and on["tier_restored_blocks"] > 0
               and on["compiles_added_by_trace"] == 0),
        "requests": spec["requests"],
        "rate_rps": spec["rate"],
        "slots": spec["slots"],
        "pool_blocks": num_blocks,
        "block_size": bs,
        "distinct_prompts": spec["distinct_prompts"],
        # the capacity stress in one number: shareable prefix blocks
        # the trace's working set needs over the pool's total blocks
        "working_set_over_capacity": round(
            working_set / max(num_blocks - 1, 1), 2),
        "token_parity_tier_vs_off": parity,
        # headline: what the host tier buys on identical arrivals
        "prefix_hit_rate": on["prefix_hit_rate"],
        "prefix_hit_rate_off": off["prefix_hit_rate"],
        "hit_rate_win": hit_win,
        "prefill_tokens": on["mixed_prefill_tokens"],
        "prefill_tokens_off": off["mixed_prefill_tokens"],
        "prefill_tokens_saved": (off["mixed_prefill_tokens"]
                                 - on["mixed_prefill_tokens"]),
        "restored_blocks": on["tier_restored_blocks"],
        "restored_bytes": on["tier_restored_bytes"],
        "restore_s_p99": on["tier_restore_s_p99"],
        "breakeven_ratio": on["tier_breakeven_ratio"],
        "ttft_s_p99": on["ttft_s_p99"],
        "ttft_s_p99_off": off["ttft_s_p99"],
        "throughput_tok_s": on["throughput_tok_s"],
        "throughput_tok_s_off": off["throughput_tok_s"],
        "compiles_added_by_tier": on["compiles_added_by_trace"],
        "legs": per_leg,
        "ragged_kernel_probe": ragged_err or "ok",
    }


def run_serve_tenant_config(name: str) -> dict:
    """Multi-tenant fairness: three per-tenant Poisson processes at
    skewed rates merged into ONE arrival schedule, replayed twice on
    one engine geometry — fairness off vs on — reporting per-tenant
    attainment / goodput / cost share from the TenantLedger, mean
    first-token ranks (the ordinal view of the prefill reorder), token
    parity between the legs, and zero added compiles."""
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.serve import ServeEngine, TenantLedger, poisson_trace
    from llm_np_cp_tpu.serve.engine import pool_geometry
    from llm_np_cp_tpu.serve.slo import SLOPolicy, SLOTracker

    t0 = time.perf_counter()
    spec = SERVE_TENANT_CONFIGS[name]
    config, params = _build_model(spec["model"], tag=name, t0=t0)
    _phase(name, "params_built", t0)

    bs = spec["block_size"]
    chunk = min(bs * 2, 256)
    tenants = spec["tenants"]
    max_prompt = max(t["prompt_len"] for t in tenants.values())
    max_new = max(t["max_tokens"] for t in tenants.values())
    _, num_blocks, max_seq_len = pool_geometry(
        max_prompt, max_new, spec["slots"], bs, prefill_chunk=chunk,
    )

    # one rng per tenant: each tenant is its OWN Poisson process at its
    # own rate (seed offsets keep per-request sampler seeds unique);
    # the merged, arrival-sorted schedule is identical for both legs
    trace: list[dict] = []
    for idx, (tenant, tspec) in enumerate(sorted(tenants.items())):
        rng = np.random.default_rng(31 + idx)
        sub = poisson_trace(
            rng, tspec["requests"], rate_rps=tspec["rate"],
            prompt_len_range=(max(tspec["prompt_len"] // 2, 1),
                              tspec["prompt_len"]),
            max_new_tokens=tspec["max_tokens"],
            vocab_size=config.vocab_size,
            seed_base=31 + 1000 * idx,
        )
        trace.extend(dict(item, tenant=tenant) for item in sub)
    trace.sort(key=lambda item: item["arrival_s"])
    n_requests = len(trace)
    _phase(name, "trace_built", t0, requests=n_requests)

    per_leg: dict = {}
    tokens_by_leg: dict = {}
    for leg in ("fair_off", "fair_on"):
        ledger = TenantLedger(
            fairness=(leg == "fair_on"),
            policy=SLOPolicy(ttft_s=5.0, tpot_s=1.0, target=0.99),
        )
        engine = ServeEngine(
            params, config,
            sampler=Sampler(kind="greedy"),
            max_slots=spec["slots"],
            num_blocks=num_blocks,
            block_size=bs,
            max_seq_len=max_seq_len,
            prefill_chunk=chunk,
            cache_dtype=jnp.bfloat16,
            tenants=ledger,
        )
        ledger.clock = engine.clock
        engine.warmup([int(t["prompt"].size) for t in trace],
                      max_new_tokens=max_new)
        warm_compiles = dict(engine.compile_counts())
        engine.metrics.slo = SLOTracker(ledger.policy, clock=engine.clock)
        _phase(name, f"warmed_{leg}", t0)
        snap = engine.replay_trace(trace)
        _phase(name, f"trace_drained_{leg}", t0, ticks=snap["ticks"])
        finished = list(engine.scheduler.finished)
        tokens_by_leg[leg] = {
            r.req_id: list(r.generated) for r in finished
        }
        # ordinal fairness observable: each tenant's mean rank in
        # first-token order — reorder wins survive CPU clock noise
        ranked = sorted(
            (r for r in finished if r.first_token_time is not None),
            key=lambda r: r.first_token_time,
        )
        ranks: dict[str, list[int]] = {}
        for rank, r in enumerate(ranked):
            ranks.setdefault(r.tenant, []).append(rank)
        ten_detail: dict[str, dict] = {}
        for tenant, ent in ledger.snapshot()["tenants"].items():
            d: dict = {
                "requests": ent["requests"],
                "tokens": ent["tokens"],
                "cost_share": round(ent["cost_share"], 4),
                "throttled": ent["throttled"],
                "first_token_rank_mean": round(
                    sum(ranks.get(tenant, [0]))
                    / max(len(ranks.get(tenant, [])), 1), 2),
            }
            if "slo" in ent:
                d["slo_attainment"] = ent["slo"].get("slo_attainment")
                d["goodput_tok_s"] = round(
                    ent["slo"].get("goodput_tok_s", 0.0), 1)
            ten_detail[tenant] = d
        counts = engine.compile_counts()
        per_leg[leg] = {
            "ok": (snap["finished"] == n_requests
                   and set(ten_detail) == set(tenants)
                   and all(ten_detail[t]["requests"]
                           == tenants[t]["requests"] for t in tenants)),
            "throughput_tok_s": round(snap["throughput_tok_s"], 1),
            "ttft_s_p50": round(snap.get("ttft_s_p50", float("nan")), 4),
            "ttft_s_p99": round(snap.get("ttft_s_p99", float("nan")), 4),
            "ticks": snap["ticks"],
            "goodput_tok_s": round(snap.get("goodput_tok_s", 0.0), 1),
            "slo_attainment": snap.get("slo_attainment"),
            "compiles_added_by_trace": (
                counts.get("mixed_step", 0)
                - warm_compiles.get("mixed_step", 0)
            ),
            "tenants": ten_detail,
        }
        del engine
    parity = tokens_by_leg["fair_off"] == tokens_by_leg["fair_on"]
    off, on = per_leg["fair_off"], per_leg["fair_on"]

    def worst_att(leg: dict) -> float | None:
        atts = [d["slo_attainment"] for d in leg["tenants"].values()
                if d.get("slo_attainment") is not None]
        return min(atts) if atts else None

    return {
        "config": name,
        "ok": (all(r["ok"] for r in per_leg.values()) and parity
               and off["compiles_added_by_trace"] == 0
               and on["compiles_added_by_trace"] == 0),
        "requests": n_requests,
        "slots": spec["slots"],
        "pool_blocks": num_blocks,
        "block_size": bs,
        "tenant_mix": {
            t: dict(requests=ts["requests"], rate_rps=ts["rate"])
            for t, ts in sorted(tenants.items())
        },
        "token_parity_fair_vs_off": parity,
        # headline: worst tenant's attainment with/without fairness —
        # what tools/slo_gate.py --min-tenant-attainment consumes
        "worst_tenant_attainment": worst_att(on),
        "worst_tenant_attainment_off": worst_att(off),
        "throughput_tok_s": on["throughput_tok_s"],
        "throughput_tok_s_off": off["throughput_tok_s"],
        "ttft_s_p99": on["ttft_s_p99"],
        "ttft_s_p99_off": off["ttft_s_p99"],
        "compiles_added_by_fairness": on["compiles_added_by_trace"],
        "legs": per_leg,
    }


def run_serve_sharded_config(name: str) -> dict:
    """Mesh-sharded serving: the SAME shared-prompt Poisson trace over
    three topologies — single chip, TP=N (one engine, kv-head-sharded
    paged pool), DP x TP replicas behind the prefix-affinity router —
    reporting per-chip tok/s, p99 TTFT,
    token parity across every leg, and the router's routed/spilled
    verdicts with the fleet prefix hit rate."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.parallel.sharding import MeshPlan
    from llm_np_cp_tpu.serve import ReplicaSet, ServeEngine, poisson_trace
    from llm_np_cp_tpu.serve.engine import pool_geometry

    t0 = time.perf_counter()
    spec = SERVE_SHARDED_CONFIGS[name]
    config, params = _build_model(spec["model"], tag=name, t0=t0)
    _phase(name, "params_built", t0)

    bs = spec["block_size"]
    chunk = min(bs * 2, 256)
    _, sized_blocks, max_seq_len = pool_geometry(
        spec["prompt_len"], spec["max_tokens"], spec["slots"], bs,
        prefill_chunk=chunk,
    )
    num_blocks = sized_blocks + spec.get("extra_blocks", 0)
    n_dev = jax.device_count()
    tp = spec["tp"]
    dp_replicas, dp_tp = spec["dp"]

    rng = np.random.default_rng(13)
    trace = poisson_trace(
        rng, spec["requests"], rate_rps=spec["rate"],
        prompt_len_range=(max(spec["prompt_len"] // 4, 1),
                          spec["prompt_len"]),
        max_new_tokens=spec["max_tokens"], vocab_size=config.vocab_size,
        seed_base=13, distinct_prompts=spec.get("distinct_prompts"),
    )
    lens = [int(t["prompt"].size) for t in trace]
    _phase(name, "trace_built", t0)

    from llm_np_cp_tpu.serve.slo import SLOPolicy, SLOTracker

    # goodput/attainment/burn recorded per topology leg (fleet legs
    # aggregate across replicas via ReplicaSet.snapshot); ok never
    # depends on the attainment VALUE on a CPU child
    slo_policy = SLOPolicy(ttft_s=spec.get("slo_ttft", 2.5),
                           tpot_s=spec.get("slo_tpot", 1.0))

    def build_engine(plan, devices):
        eng = ServeEngine(
            params, config,
            sampler=Sampler(kind="greedy"),
            max_slots=spec["slots"],
            num_blocks=num_blocks,
            block_size=bs,
            max_seq_len=max_seq_len,
            prefill_chunk=chunk,
            cache_dtype=jnp.bfloat16,
            enable_prefix_cache=spec.get("prefix_cache", False),
            mesh_plan=plan,
            mesh_devices=devices,
        )
        eng.metrics.slo = SLOTracker(slo_policy, clock=eng.clock)
        return eng

    legs = {
        "single": dict(chips=1, replicas=1, tp=1),
        "tp": dict(chips=tp, replicas=1, tp=tp),
        "dp_tp": dict(chips=dp_replicas * dp_tp, replicas=dp_replicas,
                      tp=dp_tp),
    }
    per_leg: dict = {}
    tokens_by_leg: dict = {}
    for leg, shape in legs.items():
        if shape["chips"] > n_dev:
            per_leg[leg] = {
                "ok": True,
                "skipped": f"needs {shape['chips']} devices, "
                           f"have {n_dev}",
            }
            continue
        plan = MeshPlan(model=shape["tp"]) if shape["tp"] > 1 else None
        devices = jax.devices()
        per = shape["tp"]
        engines = [
            build_engine(
                plan,
                devices[i * per:(i + 1) * per] if plan is not None
                else None,
            )
            for i in range(shape["replicas"])
        ]
        for e in engines:
            e.warmup(lens, max_new_tokens=spec["max_tokens"])
        _phase(name, f"warmed_{leg}", t0, chips=shape["chips"])
        if shape["replicas"] > 1:
            fleet = ReplicaSet(engines)
            snap = fleet.replay_trace(trace)
            tokens_by_leg[leg] = {
                r.req_id: list(r.generated) for r in fleet.finished
            }
            router = {
                "router_routed": snap["router_routed"],
                "router_spilled": snap["router_spilled"],
            }
            compile_counts = engines[0].compile_counts()
        else:
            snap = engines[0].replay_trace(trace)
            tokens_by_leg[leg] = {
                r.req_id: list(r.generated)
                for r in engines[0].scheduler.finished
            }
            router = {}
            compile_counts = engines[0].compile_counts()
        _phase(name, f"trace_drained_{leg}", t0, ticks=snap["ticks"])
        tok_s = snap["throughput_tok_s"]
        per_leg[leg] = {
            "ok": snap["finished"] == spec["requests"],
            "chips": shape["chips"],
            "mesh": engines[0].mesh_desc,
            "throughput_tok_s": round(tok_s, 1),
            "tok_s_per_chip": round(tok_s / shape["chips"], 1),
            "ttft_s_p50": round(snap.get("ttft_s_p50", float("nan")), 4),
            "ttft_s_p99": round(snap.get("ttft_s_p99", float("nan")), 4),
            "prefix_hit_rate": round(snap["prefix_hit_rate"], 3)
            if "prefix_hit_rate" in snap else None,
            "slo_attainment": round(
                snap.get("slo_attainment", float("nan")), 4),
            "goodput_tok_s": round(snap.get("goodput_tok_s", 0.0), 1),
            "slo_burn_rate_5m": round(
                snap.get("slo_burn_rate_5m", 0.0), 3),
            "ticks": snap["ticks"],
            "compile_counts": compile_counts,
            **router,
        }
        del engines
    ran = {k: v for k, v in per_leg.items() if "skipped" not in v}
    # ordered per-request parity: request ids are assigned in submission
    # order in every leg (single engine and ReplicaSet both), so keying
    # by id catches a cross-request stream swap that a multiset compare
    # would miss — exactly the routing/recovery bug class this config
    # exists to surface
    streams = {
        leg: tuple(
            tuple(tokens_by_leg[leg][rid])
            for rid in sorted(tokens_by_leg[leg])
        )
        for leg in tokens_by_leg
    }
    parity = len(set(streams.values())) <= 1
    headline = (per_leg.get("dp_tp") if "dp_tp" in ran
                else per_leg.get("tp") if "tp" in ran
                else per_leg["single"])
    return {
        "config": name,
        "ok": all(r["ok"] for r in per_leg.values()) and parity
        and bool(ran),
        "backend": jax.default_backend(),
        "devices": n_dev,
        "requests": spec["requests"],
        "rate_rps": spec["rate"],
        "slots": spec["slots"],
        "pool_blocks": num_blocks,
        "block_size": bs,
        "distinct_prompts": spec.get("distinct_prompts"),
        "token_parity_across_legs": parity,
        "tok_s_per_chip": headline.get("tok_s_per_chip"),
        "ttft_s_p99": headline.get("ttft_s_p99"),
        "slo_ttft_s": slo_policy.ttft_s,
        "slo_tpot_s": slo_policy.tpot_s,
        "slo_attainment": headline.get("slo_attainment"),
        "goodput_tok_s": headline.get("goodput_tok_s"),
        "legs": per_leg,
    }


def _client_pct(vals: list, q: float) -> float:
    """Client-observed-TTFT percentile — the SAME estimator as
    ServeMetrics._pcts (np.percentile linear interpolation), shared by
    the HTTP and chaos legs: a different one would fold estimator
    mismatch into the deltas those configs exist to measure."""
    import numpy as np

    return float(np.percentile(vals, q)) if vals else float("nan")


def _run_http_trace_leg(
    engine, model_id: str, trace: list, *, client_timeout: float,
    retries: int = 3, backoff_s: float = 0.25, scrape: bool = False,
    server_kwargs: dict | None = None,
) -> tuple[list, dict, str | None]:
    """One realtime HTTP replay of ``trace``: in-process HttpServer, one
    SSE client per request sleeping until its arrival time (with
    transient 429/503 retry — a queue blip must not burn the leg, and a
    retried request's TTFT honestly carries the added wait), an optional
    Prometheus scrape before drain, and the runner's supervision stats.
    The ONE leg runner shared by the HTTP-overhead and chaos configs so
    their client machinery cannot drift."""
    import asyncio

    from llm_np_cp_tpu.serve.http.client import astream_completion, http_get
    from llm_np_cp_tpu.serve.http.server import HttpServer

    async def leg():
        server = HttpServer(engine, model_id=model_id, drain_timeout=60.0,
                            **(server_kwargs or {}))
        await server.start("127.0.0.1", 0)

        async def one(item):
            await asyncio.sleep(item["arrival_s"])
            return await astream_completion(
                server.host, server.port,
                {"model": model_id,
                 "prompt": [int(t) for t in item["prompt"]],
                 "max_tokens": item["max_new_tokens"],
                 "seed": item.get("seed", 0)},
                timeout=client_timeout, retries=retries,
                backoff_s=backoff_s,
            )

        results = await asyncio.gather(*(one(item) for item in trace))
        prom = None
        if scrape:
            loop = asyncio.get_running_loop()
            _, raw = await loop.run_in_executor(
                None, http_get, server.host, server.port, "/metrics")
            prom = raw.decode()
        runner = server.runner
        stats = {
            "restarts": runner.restarts,
            "recovery_latency_s": [
                round(v, 4) for v in runner.recovery_latency_s
            ],
            "decode_impl_final": runner.engine.ragged_attn_impl,
            "compile_counts": runner.engine.compile_counts(),
            "buckets": list(runner.engine.mixed_buckets),
        }
        server.begin_drain()
        await server.serve_until_shutdown()
        return list(results), stats, prom

    return asyncio.run(leg())


def run_serve_http_config(name: str) -> dict:
    """HTTP front-end overhead: ONE engine, the SAME Poisson trace, two
    realtime replays — direct ``ServeEngine`` calls, then the in-process
    asyncio HTTP server driven by SSE streaming clients at the same
    arrival times.  The delta between the two legs' TTFT/throughput is
    the HTTP layer's cost (event loop, bridge queues, SSE framing) —
    measured, not guessed.  The HTTP leg's TTFT is CLIENT-observed
    (request sent → first SSE chunk parsed), which is what a user sees.
    """
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.serve import ServeEngine, ServeMetrics, poisson_trace
    from llm_np_cp_tpu.serve.engine import pool_geometry

    t0 = time.perf_counter()
    spec = SERVE_HTTP_CONFIGS[name]
    config, params = _build_model(spec["model"], tag=name, t0=t0)
    _phase(name, "params_built", t0)

    bs = spec["block_size"]
    chunk = min(bs * 2, 256)
    _, num_blocks, max_seq_len = pool_geometry(
        spec["prompt_len"], spec["max_tokens"], spec["slots"], bs,
        prefill_chunk=chunk,
    )
    engine = ServeEngine(
        params, config,
        sampler=Sampler(kind="greedy"),
        max_slots=spec["slots"],
        num_blocks=num_blocks,
        block_size=bs,
        max_seq_len=max_seq_len,
        prefill_chunk=chunk,
        cache_dtype=jnp.bfloat16,
    )
    # SLO goodput accounting rides every leg: generous CPU-scale
    # targets (this records attainment/goodput/burn alongside tok/s —
    # tools/slo_gate.py gates live-TPU runs on them; ok never depends
    # on the attainment VALUE, only on the plumbing)
    from llm_np_cp_tpu.serve.slo import SLOPolicy, SLOTracker

    slo_policy = SLOPolicy(ttft_s=spec.get("slo_ttft", 2.5),
                           tpot_s=spec.get("slo_tpot", 1.0))

    def fresh_metrics():
        m = ServeMetrics(clock=engine.clock)
        m.slo = SLOTracker(slo_policy, clock=engine.clock)
        return m

    engine.metrics = fresh_metrics()
    rng = np.random.default_rng(13)
    trace = poisson_trace(
        rng, spec["requests"], rate_rps=spec["rate"],
        prompt_len_range=(max(spec["prompt_len"] // 4, 1),
                          spec["prompt_len"]),
        max_new_tokens=spec["max_tokens"], vocab_size=config.vocab_size,
        seed_base=13,
    )
    engine.warmup([int(t["prompt"].size) for t in trace],
                  max_new_tokens=spec["max_tokens"])
    _phase(name, "warmed", t0)

    # leg 1: direct engine calls at wall-clock arrival pacing — the
    # no-HTTP baseline every client-observed number compares against
    direct = engine.replay_trace(trace, realtime=True)
    direct_tokens = {
        r.req_id: list(r.generated) for r in engine.scheduler.finished
    }
    _phase(name, "direct_done", t0, ticks=direct["ticks"])

    # leg 2: same trace through the HTTP server, one SSE client per
    # request sleeping until its arrival time
    engine.metrics = fresh_metrics()
    engine.scheduler.finished.clear()
    results, _http_stats, prom = _run_http_trace_leg(
        engine, spec["model"], trace,
        client_timeout=TIMEOUTS.get(name, DEFAULT_TIMEOUT) / 2,
        scrape=True,
    )
    _phase(name, "http_done", t0)

    http_ok = [r for r in results if r["status"] == 200]
    parity = all(
        r["token_ids"] == direct_tokens.get(rid, None)
        for rid, r in zip(sorted(direct_tokens), http_ok)
    ) if len(http_ok) == len(direct_tokens) else False
    ttft_http = [r["ttft_s"] for r in http_ok if r["ttft_s"]]
    http_snap = engine.metrics.snapshot()
    pct = _client_pct
    d_p50 = direct.get("ttft_s_p50", float("nan"))
    d_p99 = direct.get("ttft_s_p99", float("nan"))
    h_p50, h_p99 = pct(ttft_http, 50), pct(ttft_http, 99)

    # leg 3: tracing overhead — the SAME trace, direct realtime replay
    # again but with a TraceRecorder attached (request spans + tick
    # phases + profiler annotations live).  The delta vs the untraced
    # direct leg is what --trace-out costs a production replay; it must
    # stay small or the instrument perturbs what it measures.
    import shutil
    import tempfile

    from llm_np_cp_tpu.serve.request_log import RequestLog, read_request_log
    from llm_np_cp_tpu.serve.tracing import TraceRecorder

    engine.metrics = fresh_metrics()
    engine.scheduler.finished.clear()
    engine.tracer = TraceRecorder(ring=500_000)
    # the canonical request log rides the traced leg: one JSON line per
    # terminal, asserted consistent with the metrics the same leg
    # recorded (request-log ↔ metrics parity)
    rl_dir = tempfile.mkdtemp(prefix="serve_http_rl_")
    rl_path = os.path.join(rl_dir, "requests.jsonl")
    engine.request_log = RequestLog(rl_path)
    traced = engine.replay_trace(trace, realtime=True)
    # ids keep counting across legs — compare token streams in submit
    # order (both legs replay the same arrivals through submit())
    trace_parity = (
        [t for _, t in sorted(
            (r.req_id, r.generated) for r in engine.scheduler.finished)]
        == [direct_tokens[k] for k in sorted(direct_tokens)]
    )
    n_trace_events = len(engine.tracer)
    engine.tracer = None
    # request-log ↔ metrics parity: the wide-event lines and the
    # metrics snapshot were recorded by the SAME leg, so their counts
    # must agree exactly — one line per terminal, reasons matching the
    # finish_reasons counters, token totals matching, every line
    # carrying a trace id and an SLO verdict
    engine.request_log.flush(10.0)
    log_lines = read_request_log(rl_path)
    engine.request_log.close()
    engine.request_log = None
    shutil.rmtree(rl_dir, ignore_errors=True)
    from collections import Counter as _Counter

    traced_snap = traced
    log_reasons = dict(_Counter(ln["reason"] for ln in log_lines))
    request_log_parity = (
        len(log_lines) == traced_snap["finished"] + traced_snap["aborted"]
        and log_reasons == traced_snap["finish_reasons"]
        and sum(ln["new_tokens"] for ln in log_lines)
        == traced_snap["total_generated_tokens"]
        and all(ln.get("trace") for ln in log_lines)
        and all("slo" in ln for ln in log_lines)
    )
    _phase(name, "traced_done", t0, events=n_trace_events,
           log_lines=len(log_lines))
    t_p99 = traced.get("ttft_s_p99", float("nan"))
    trace_tok_delta = round(
        direct["throughput_tok_s"] - traced["throughput_tok_s"], 1)
    trace_p99_delta = round(t_p99 - d_p99, 4)
    # generous bounds — this guards against a broken hot path (tracing
    # turning ticks into seconds), not against scheduler jitter
    trace_overhead_small = (
        traced["throughput_tok_s"] >= 0.7 * direct["throughput_tok_s"]
        and (t_p99 - d_p99) < max(0.25, d_p99)
    )
    return {
        "config": name,
        "ok": (direct["finished"] == spec["requests"]
               and len(http_ok) == spec["requests"] and parity
               and traced["finished"] == spec["requests"]
               and trace_parity and trace_overhead_small
               and request_log_parity),
        "requests": spec["requests"],
        "rate_rps": spec["rate"],
        "slots": spec["slots"],
        "pool_blocks": num_blocks,
        "block_size": bs,
        "token_parity_http_vs_direct": parity,
        "ttft_s_p50_direct": round(d_p50, 4),
        "ttft_s_p99_direct": round(d_p99, 4),
        "ttft_s_p50_http": round(h_p50, 4),
        "ttft_s_p99_http": round(h_p99, 4),
        # the headline: what the HTTP layer costs a request's TTFT
        "http_ttft_overhead_s_p50": round(h_p50 - d_p50, 4),
        "http_ttft_overhead_s_p99": round(h_p99 - d_p99, 4),
        "throughput_tok_s_direct": round(direct["throughput_tok_s"], 1),
        "throughput_tok_s_http": round(http_snap["throughput_tok_s"], 1),
        "metrics_scrape_ok": "llm_serve_requests_finished_total" in prom,
        # the traced leg: what request-lifecycle tracing costs
        "throughput_tok_s_traced": round(traced["throughput_tok_s"], 1),
        "ttft_s_p99_traced": round(t_p99, 4),
        "trace_overhead_tok_s": trace_tok_delta,
        "trace_overhead_ttft_p99_s": trace_p99_delta,
        "trace_overhead_small": trace_overhead_small,
        "trace_events": n_trace_events,
        "trace_token_parity": trace_parity,
        # SLO goodput accounting (the slo_gate.py observables — the
        # HTTP leg is the headline; per-leg values alongside)
        "slo_ttft_s": slo_policy.ttft_s,
        "slo_tpot_s": slo_policy.tpot_s,
        "slo_attainment": round(http_snap.get("slo_attainment",
                                              float("nan")), 4),
        "goodput_tok_s": round(http_snap.get("goodput_tok_s", 0.0), 1),
        "slo_burn_rate_5m": round(
            http_snap.get("slo_burn_rate_5m", 0.0), 3),
        "slo_burn_rate_1h": round(
            http_snap.get("slo_burn_rate_1h", 0.0), 3),
        "slo_attainment_direct": round(direct.get("slo_attainment",
                                                  float("nan")), 4),
        "goodput_tok_s_direct": round(direct.get("goodput_tok_s", 0.0), 1),
        "slo_attainment_traced": round(traced.get("slo_attainment",
                                                  float("nan")), 4),
        "goodput_tok_s_traced": round(traced.get("goodput_tok_s", 0.0), 1),
        # canonical request log (traced leg)
        "request_log_lines": len(log_lines),
        "request_log_parity": request_log_parity,
        "compile_counts": engine.compile_counts(),
        "buckets": list(engine.mixed_buckets),
    }


def run_serve_chaos_config(name: str) -> dict:
    """Supervised recovery under fault injection: the SAME Poisson trace
    through the HTTP server twice — a clean leg, then a chaos leg with a
    seeded fault schedule (tick-thread crash + step dispatch fault) and
    ``max_restarts=3`` supervision.  Reports recovery latency, restart
    count, p99 TTFT degradation vs clean, and token parity (recovered
    streams must be token-identical — the teacher-forced replay
    contract).  The clean leg is also the "chaos disabled = unchanged
    numbers" reference for the injection points' zero-overhead claim."""
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.serve import FaultInjector, ServeEngine, poisson_trace
    from llm_np_cp_tpu.serve.engine import pool_geometry

    t0 = time.perf_counter()
    spec = SERVE_CHAOS_CONFIGS[name]
    config, params = _build_model(spec["model"], tag=name, t0=t0)
    _phase(name, "params_built", t0)

    bs = spec["block_size"]
    chunk = min(bs * 2, 256)
    _, num_blocks, max_seq_len = pool_geometry(
        spec["prompt_len"], spec["max_tokens"], spec["slots"], bs,
        prefill_chunk=chunk,
    )
    # the default engine, i.e. the served tick: the chaos 'decode' fault
    # exercises the runtime Pallas -> XLA degradation where a kernel is
    # live, a second supervised restart where none is — both are
    # recovery paths
    rng = np.random.default_rng(13)
    trace = poisson_trace(
        rng, spec["requests"], rate_rps=spec["rate"],
        prompt_len_range=(max(spec["prompt_len"] // 4, 1),
                          spec["prompt_len"]),
        max_new_tokens=spec["max_tokens"], vocab_size=config.vocab_size,
        seed_base=13,
    )
    _phase(name, "trace_built", t0)

    def build_engine(injector):
        engine = ServeEngine(
            params, config,
            sampler=Sampler(kind="greedy"),
            max_slots=spec["slots"],
            num_blocks=num_blocks,
            block_size=bs,
            max_seq_len=max_seq_len,
            prefill_chunk=chunk,
            cache_dtype=jnp.bfloat16,
            fault_injector=injector,
        )
        engine.warmup([int(t["prompt"].size) for t in trace],
                      max_new_tokens=spec["max_tokens"])
        return engine

    def run_leg(engine, tag):
        results, stats, _ = _run_http_trace_leg(
            engine, spec["model"], trace,
            client_timeout=TIMEOUTS.get(name, DEFAULT_TIMEOUT) / 3,
            retries=4, backoff_s=0.1,
            server_kwargs=dict(
                tick_deadline=spec.get("tick_deadline"),
                max_restarts=3,
                restart_backoff_s=spec.get("backoff", 0.2),
            ),
        )
        _phase(name, f"{tag}_done", t0, restarts=stats["restarts"])
        ok = [r for r in results if r["status"] == 200]
        ttft = [r["ttft_s"] for r in ok if r["ttft_s"]]
        return results, ok, ttft, stats

    clean_results, clean_ok, clean_ttft, clean_stats = run_leg(
        build_engine(None), "clean")
    clean_tokens = [r["token_ids"] for r in clean_results]

    injector = FaultInjector(spec["chaos"], seed=13)
    chaos_results, chaos_ok, chaos_ttft, chaos_stats = run_leg(
        build_engine(injector), "chaos")
    parity = [r["token_ids"] for r in chaos_results] == clean_tokens

    c50, c99 = _client_pct(clean_ttft, 50), _client_pct(clean_ttft, 99)
    x50, x99 = _client_pct(chaos_ttft, 50), _client_pct(chaos_ttft, 99)
    recov = chaos_stats["recovery_latency_s"]
    return {
        "config": name,
        "ok": (len(clean_ok) == spec["requests"]
               and len(chaos_ok) == spec["requests"]
               and parity and chaos_stats["restarts"] >= 1),
        "requests": spec["requests"],
        "rate_rps": spec["rate"],
        "slots": spec["slots"],
        "pool_blocks": num_blocks,
        "block_size": bs,
        "chaos_spec": spec["chaos"],
        # every request completed despite the schedule, token-identically
        "token_parity_chaos_vs_clean": parity,
        "restarts": chaos_stats["restarts"],
        "faults_injected": injector.snapshot(),
        "client_retries_total": sum(
            r.get("retries", 0) for r in chaos_results
        ),
        # the headline pair: what an engine death costs
        "recovery_latency_s": recov,
        "recovery_latency_s_max": max(recov) if recov else None,
        "ttft_s_p50_clean": round(c50, 4),
        "ttft_s_p99_clean": round(c99, 4),
        "ttft_s_p50_chaos": round(x50, 4),
        "ttft_s_p99_chaos": round(x99, 4),
        "chaos_ttft_p99_degradation_s": round(x99 - c99, 4),
        "decode_impl_final": chaos_stats["decode_impl_final"],
        # restart must not recompile: the step stays within one program
        # a packed-width bucket (``buckets``), degraded twin included
        "compile_counts": chaos_stats["compile_counts"],
        "compile_counts_clean": clean_stats["compile_counts"],
        "buckets": chaos_stats["buckets"],
    }


def _spawn_serve_proc(spec, tmp, tag, *, port=0, journal=None,
                      journal_sync=None, chaos=None, timeout=600.0):
    """Spawn tools/serve_proc.py (deterministic random-weight model, so
    a restarted process serves the identical model) and wait for its
    port file → ``(proc, host, port)``.  The server takes the device
    ``JAX_PLATFORMS`` names, so this process must not hold it."""
    from llm_np_cp_tpu.utils.runtime import require_uninitialized_backend

    require_uninitialized_backend("serve_restart server")
    pf = os.path.join(tmp, f"port_{tag}")
    cmd = [
        sys.executable, os.path.join(REPO, "tools", "serve_proc.py"),
        "--model", spec["model"], "--port", str(port), "--port-file", pf,
        "--slots", str(spec["slots"]),
        "--block-size", str(spec["block_size"]),
        "--prompt-len", str(spec["prompt_len"]),
        "--max-tokens", str(spec["max_tokens"]),
    ]
    if journal:
        cmd += ["--journal", journal]
    if journal_sync:
        cmd += ["--journal-sync", journal_sync]
    if chaos:
        cmd += ["--chaos", chaos]
    log_path = os.path.join(tmp, f"log_{tag}")
    proc = subprocess.Popen(cmd, stdout=open(log_path, "w"),
                            stderr=subprocess.STDOUT, cwd=REPO)
    deadline = time.time() + timeout
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"serve_proc {tag} died at startup: "
                + open(log_path).read()[-1500:])
        if os.path.exists(pf):
            host, port_s = open(pf).read().split()
            return proc, host, int(port_s)
        time.sleep(0.1)
    proc.kill()
    raise RuntimeError(f"serve_proc {tag} never wrote its port file")


def run_serve_restart_config(name: str) -> dict:
    """kill -9 durability: REAL server subprocesses, one Poisson trace,
    four legs — plain (no journal), journaled (the overhead leg: the
    client tok/s delta + the writer thread's fsync p99 IS the journal's
    cost), journaled with ``--journal-sync admission`` (the strict
    mode's cost: one synchronous admission fsync before each stream
    starts), and a kill leg (chaos ``proc_kill`` SIGKILLs the server
    mid-decode; the parent respawns it on the same port + journal and
    every client resumes its stream via Last-Event-ID).  Token parity
    across ALL legs is the teacher-forced replay contract applied to
    process death."""
    import asyncio
    import re as _re
    import signal as _signal
    import tempfile

    import numpy as np

    from llm_np_cp_tpu.config import LLAMA_3_2_1B, tiny_config
    from llm_np_cp_tpu.serve import poisson_trace, scan_journal
    from llm_np_cp_tpu.serve.http.client import (
        astream_completion,
        http_get,
    )

    t0 = time.perf_counter()
    spec = SERVE_RESTART_CONFIGS[name]
    config = {"llama1b": LLAMA_3_2_1B,
              "tiny": tiny_config("llama")}[spec["model"]]
    rng = np.random.default_rng(13)
    trace = poisson_trace(
        rng, spec["requests"], rate_rps=spec["rate"],
        prompt_len_range=(max(spec["prompt_len"] // 4, 1),
                          spec["prompt_len"]),
        max_new_tokens=spec["max_tokens"], vocab_size=config.vocab_size,
        seed_base=13,
    )
    client_timeout = TIMEOUTS.get(name, DEFAULT_TIMEOUT) / 4

    def drive(host, port, *, retries, max_backoff_s=2.0, give_up=None):
        """``give_up``: a ``threading.Event`` that ends the leg at once
        (the respawn failed: no retry will ever find a server)."""
        async def leg():
            async def one(item):
                await asyncio.sleep(item["arrival_s"])
                return await astream_completion(
                    host, port,
                    {"model": spec["model"],
                     "prompt": [int(t) for t in item["prompt"]],
                     "max_tokens": item["max_new_tokens"],
                     "seed": item.get("seed", 0)},
                    timeout=client_timeout, retries=retries,
                    backoff_s=0.3, max_backoff_s=max_backoff_s,
                )

            async def watch(tasks):
                while give_up is not None and not all(
                        t.done() for t in tasks):
                    if give_up.is_set():
                        for t in tasks:
                            t.cancel()
                        return
                    await asyncio.sleep(0.1)

            t_leg = time.perf_counter()
            tasks = [asyncio.ensure_future(one(item)) for item in trace]
            watcher_task = asyncio.ensure_future(watch(tasks))
            try:
                results = await asyncio.gather(*tasks)
            except asyncio.CancelledError:
                results = None  # gave up: the caller says why
            await watcher_task
            return results, time.perf_counter() - t_leg
        return asyncio.run(leg())

    def leg_stats(results, wall):
        ok = [r for r in results if r["status"] == 200]
        ttft = [r["ttft_s"] for r in ok if r["ttft_s"]]
        toks = sum(len(r["token_ids"]) for r in ok)
        return {
            "completed": len(ok),
            "client_tok_s": round(toks / wall, 1) if wall > 0 else 0.0,
            "ttft_s_p50": round(_client_pct(ttft, 50), 4),
            "ttft_s_p99": round(_client_pct(ttft, 99), 4),
        }

    tmp = tempfile.mkdtemp(prefix="serve_restart_")

    def scrape(host, port, pattern):
        _, raw = http_get(host, port, "/metrics")
        m = _re.search(pattern, raw.decode(), _re.M)
        return float(m.group(1)) if m else None

    # -- leg 1: plain (no journal) — the baseline every delta reads from
    proc, host, port = _spawn_serve_proc(spec, tmp, "plain")
    try:
        plain_results, plain_wall = drive(host, port, retries=2)
    finally:
        proc.send_signal(_signal.SIGTERM)
        proc.wait(timeout=90)
    plain_tokens = [r["token_ids"] for r in plain_results]
    _phase(name, "plain_done", t0)

    # -- leg 2: journaled — same trace; the delta is the journal's cost
    j_overhead = os.path.join(tmp, "overhead.journal")
    proc, host, port = _spawn_serve_proc(
        spec, tmp, "journaled", journal=j_overhead)
    try:
        jr_results, jr_wall = drive(host, port, retries=2)
        fsync_p99 = scrape(host, port,
                           r"^llm_serve_journal_fsync_p99_s (\S+)")
        records = scrape(host, port,
                         r"^llm_serve_journal_records_total (\S+)")
    finally:
        proc.send_signal(_signal.SIGTERM)
        proc.wait(timeout=90)
    journaled_parity = [r["token_ids"] for r in jr_results] == plain_tokens
    _phase(name, "journaled_done", t0)

    # -- leg 2b: strict-durability journal (--journal-sync admission —
    # every admission record fsyncs BEFORE its stream starts, closing
    # the async-fsync admission-loss window); the delta vs the async
    # journaled leg is what the strict mode costs
    j_sync = os.path.join(tmp, "sync.journal")
    proc, host, port = _spawn_serve_proc(
        spec, tmp, "journaled_sync", journal=j_sync,
        journal_sync="admission")
    try:
        js_results, js_wall = drive(host, port, retries=2)
        sync_fsync_p99 = scrape(host, port,
                                r"^llm_serve_journal_fsync_p99_s (\S+)")
    finally:
        proc.send_signal(_signal.SIGTERM)
        proc.wait(timeout=90)
    sync_parity = [r["token_ids"] for r in js_results] == plain_tokens
    _phase(name, "journaled_sync_done", t0)

    # -- leg 3: kill -9 mid-decode, respawn on the same port + journal,
    # clients resume via Last-Event-ID
    j_kill = os.path.join(tmp, "kill.journal")
    proc1, host, port = _spawn_serve_proc(
        spec, tmp, "kill", journal=j_kill,
        chaos=f"proc_kill@{spec['kill_tick']}")
    import threading

    killed_at: dict = {}
    respawned: dict = {}
    respawn_failed = threading.Event()

    def respawn_when_dead():
        proc1.wait()
        killed_at["t"] = time.perf_counter()
        try:
            # (returns once the new server listens: warm, ready)
            p2, h2, pt2 = _spawn_serve_proc(
                spec, tmp, "restart", port=port, journal=j_kill)
        except Exception as e:  # noqa: BLE001 — reported by the leg
            respawned["error"] = e
            respawn_failed.set()
            return
        respawned["proc"] = p2

    watcher = threading.Thread(target=respawn_when_dead, daemon=True)
    watcher.start()
    try:
        try:
            # the clients wait for the respawned server to LISTEN (it
            # does once it is warm), however long its start takes on
            # this machine: short retries without a count that matters,
            # ended by the respawn's own failure and by nothing else
            kill_results, kill_wall = drive(
                host, port, retries=100_000, max_backoff_s=0.5,
                give_up=respawn_failed)
        finally:
            watcher.join(timeout=client_timeout)
            proc2 = respawned.get("proc")
        if proc2 is None:
            raise RuntimeError(
                f"restart server never came up: {respawned.get('error')}")
        journal_replayed = scrape(
            host, port, r"^llm_serve_journal_replayed_total (\S+)")
        journal_resumed = scrape(
            host, port, r"^llm_serve_journal_resumed_total (\S+)")
        proc2.send_signal(_signal.SIGTERM)
        proc2.wait(timeout=90)
    finally:
        # never leak a warm model server past the child, whatever
        # failed above (proc_kill not firing, client timeouts, ...)
        for p in (proc1, respawned.get("proc")):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=30)
    _phase(name, "kill_done", t0, restarts=1)

    kill_parity = [r["token_ids"] for r in kill_results] == plain_tokens
    resumed = [r for r in kill_results if r.get("resumed")]
    resume_lat = sorted(r["resume_latency_s"] for r in resumed
                        if r.get("resume_latency_s"))
    live, _, epoch = scan_journal(j_kill)
    plain_stats = leg_stats(plain_results, plain_wall)
    jr_stats = leg_stats(jr_results, jr_wall)
    js_stats = leg_stats(js_results, js_wall)
    overhead_tok_s = round(
        plain_stats["client_tok_s"] - jr_stats["client_tok_s"], 1)
    # counts, not rates: on a shared host a leg's tokens/s is the
    # machine's load (the deltas above are reported, and read on the
    # chip).  What a journal must not do is lose or hold back tokens:
    # each journaled leg delivered every token of the plain leg, and
    # the kill leg's resumed streams delivered theirs across the kill
    def n_tokens(results):
        return sum(len(r["token_ids"]) for r in results)

    overhead_ok = bool(records) and n_tokens(jr_results) == n_tokens(
        plain_results)
    sync_overhead_ok = n_tokens(js_results) == n_tokens(plain_results)
    tokens_resumed = n_tokens(resumed)
    n = spec["requests"]
    return {
        "config": name,
        "ok": (plain_stats["completed"] == n
               and jr_stats["completed"] == n
               and js_stats["completed"] == n
               and len([r for r in kill_results if r["status"] == 200]) == n
               and journaled_parity and kill_parity and sync_parity
               and bool(resumed) and overhead_ok and sync_overhead_ok
               and proc1.returncode == -_signal.SIGKILL
               and live == {}),
        "requests": n,
        "rate_rps": spec["rate"],
        "kill_tick": spec["kill_tick"],
        # journal overhead (the journaled-vs-plain pair)
        "token_parity_journaled_vs_plain": journaled_parity,
        "client_tok_s_plain": plain_stats["client_tok_s"],
        "client_tok_s_journaled": jr_stats["client_tok_s"],
        "journal_overhead_tok_s": overhead_tok_s,
        "journal_overhead_ok": overhead_ok,
        "journal_fsync_p99_s": fsync_p99,
        "journal_records": records,
        "ttft_s_p99_plain": plain_stats["ttft_s_p99"],
        "ttft_s_p99_journaled": jr_stats["ttft_s_p99"],
        # strict admission-fsync mode (--journal-sync admission)
        "token_parity_sync_vs_plain": sync_parity,
        "client_tok_s_journaled_sync": js_stats["client_tok_s"],
        "sync_admission_overhead_tok_s": round(
            jr_stats["client_tok_s"] - js_stats["client_tok_s"], 1),
        "sync_admission_overhead_ok": sync_overhead_ok,
        "ttft_s_p99_journaled_sync": js_stats["ttft_s_p99"],
        "journal_fsync_p99_s_sync": sync_fsync_p99,
        # the kill -9 headline
        "token_parity_across_kill": kill_parity,
        "streams_resumed": len(resumed),
        "tokens_resumed": tokens_resumed,
        "restart_to_first_resumed_token_s": (
            round(resume_lat[0], 3) if resume_lat else None),
        "resume_latency_s_max": (
            round(resume_lat[-1], 3) if resume_lat else None),
        "journal_replayed_total": journal_replayed,
        "journal_resumed_total": journal_resumed,
        "journal_epoch_final": epoch,
        "drain_left_unterminated": len(live),
    }


def run_serve_rolling_config(name: str) -> dict:
    """Zero-downtime rolling upgrade: ONE Poisson trace over a
    direct-mode 3-replica fleet, replayed twice on identical arrivals —
    steady (no roll) vs rolling (a full replica-by-replica weight swap
    triggered mid-trace).  The swap drains each replica's in-flight
    streams to peers (teacher-forced — token parity across the roll is
    the drain contract), rebuilds it on the "new" checkpoint via
    clone_fresh (same params object here: the zero-compile same-shape
    case, pinned), and rejoins routing.  ``ttft_p99_degradation`` and
    ``dropped_streams`` are what ``tools/slo_gate.py
    --max-p99-ttft-degradation`` gates in CI."""
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.serve import (
        LifecycleController,
        ReplicaSet,
        ServeEngine,
        SLOPolicy,
        SLOTracker,
        poisson_trace,
    )
    from llm_np_cp_tpu.serve.engine import pool_geometry
    from llm_np_cp_tpu.serve.trace import replay_arrivals

    t0 = time.perf_counter()
    spec = SERVE_ROLLING_CONFIGS[name]
    config, params = _build_model(spec["model"], tag=name, t0=t0)
    _phase(name, "params_built", t0)
    bs = spec["block_size"]
    chunk = min(bs * 2, 256)
    _, num_blocks, max_seq_len = pool_geometry(
        spec["prompt_len"], spec["max_tokens"], spec["slots"], bs,
        prefill_chunk=chunk,
    )
    rng = np.random.default_rng(29)
    trace = poisson_trace(
        rng, spec["requests"], rate_rps=spec["rate"],
        prompt_len_range=(max(spec["prompt_len"] // 4, 1),
                          spec["prompt_len"]),
        max_new_tokens=spec["max_tokens"], vocab_size=config.vocab_size,
        seed_base=29,
    )
    lens = [int(t["prompt"].size) for t in trace]
    _phase(name, "trace_built", t0)

    def build_fleet() -> ReplicaSet:
        engines = []
        for _ in range(spec["replicas"]):
            e = ServeEngine(
                params, config,
                sampler=Sampler(kind="greedy"),
                max_slots=spec["slots"],
                num_blocks=num_blocks,
                block_size=bs,
                max_seq_len=max_seq_len,
                prefill_chunk=chunk,
                cache_dtype=jnp.bfloat16,
            )
            e.warmup(lens, max_new_tokens=spec["max_tokens"])
            e.metrics.slo = SLOTracker(
                SLOPolicy(ttft_s=2.5, tpot_s=2.5), clock=e.clock,
            )
            engines.append(e)
        return ReplicaSet(engines)

    def leg_stats(snap) -> dict:
        return {
            "ok": snap["finished"] == spec["requests"],
            "finished": snap["finished"],
            "throughput_tok_s": round(snap["throughput_tok_s"], 1),
            "ttft_s_p50": round(snap.get("ttft_s_p50", float("nan")), 4),
            "ttft_s_p99": round(snap.get("ttft_s_p99", float("nan")), 4),
            "slo_attainment": snap.get("slo_attainment", float("nan")),
            "goodput_tok_s": round(snap.get("goodput_tok_s", 0.0), 1),
            "slo_burn_rate_5m": snap.get("slo_burn_rate_5m", 0.0),
            "router_routed": snap["router_routed"],
            "router_spilled": snap["router_spilled"],
        }

    # -- leg 1: steady (no roll) — the baseline every delta reads from
    steady_fleet = build_fleet()
    _phase(name, "warmed_steady", t0)
    snap_s = steady_fleet.replay_trace(trace)
    steady_tokens = [list(r.generated) for r in steady_fleet.finished]
    steady = leg_stats(snap_s)
    del steady_fleet  # free its pools before the measured rolling leg
    _phase(name, "steady_done", t0)

    # -- leg 2: rolling — same arrivals, a full fleet roll mid-trace
    fleet = build_fleet()
    controller = LifecycleController(fleet)
    _phase(name, "warmed_rolling", t0)
    rolled: dict = {}

    def on_tick(i: int) -> None:
        if i == spec["roll_after_ticks"] and not rolled:
            rolled.update(controller.rolling_upgrade(
                lambda: params, version=1, steps_between=1,
            ))

    # process-global counter, not engines[0]'s cache sizes: a compile
    # on a not-yet-rolled peer (or on a callable the roll then
    # discards) must count too
    from tools.compile_counter import CompileCounter

    with CompileCounter().watch() as roll_counter:
        snap_r = replay_arrivals(fleet, trace, fleet.snapshot,
                                 on_tick=on_tick)
    _phase(name, "rolling_done", t0, ticks=snap_r["ticks"])
    rolling_tokens = [list(r.generated) for r in fleet.finished]
    rolling = leg_stats(snap_r)
    compiles_added = roll_counter.count
    parity = rolling_tokens == steady_tokens
    dropped = spec["requests"] - snap_r["finished"]
    deg = (
        rolling["ttft_s_p99"] / steady["ttft_s_p99"]
        if steady["ttft_s_p99"] else float("nan")
    )
    lifecycle = {}
    for e in fleet.engines:
        for k, v in e.metrics.snapshot().get(
                "lifecycle_actions", {}).items():
            lifecycle[k] = lifecycle.get(k, 0) + v
    versions = snap_r["weights_versions"]
    return {
        "config": name,
        "ok": (steady["ok"] and rolling["ok"] and parity
               and bool(rolled) and dropped == 0
               and compiles_added == 0
               and all(v == 1 for v in versions)),
        "requests": spec["requests"],
        "rate_rps": spec["rate"],
        "replicas": spec["replicas"],
        "roll_after_ticks": spec["roll_after_ticks"],
        "rolled": rolled.get("rolled"),
        "drained_streams": rolled.get("drained"),
        "dropped_streams": dropped,
        "token_parity_across_roll": parity,
        # the headline pair slo_gate consumes
        "ttft_p99_degradation": round(deg, 3),
        "compiles_added_by_roll": compiles_added,
        "compile_counts": dict(fleet.engines[0].compile_counts()),
        "weights_versions": versions,
        "lifecycle_actions": lifecycle,
        "legs": {"steady": steady, "rolling": rolling},
    }


def run_spec_config(name: str) -> dict:
    import numpy as np

    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.speculative import SpeculativeGenerator

    t_start = time.perf_counter()
    spec = SPEC_CONFIGS[name]
    config, params = _build_model(spec["model"], tag=name, t0=t_start)
    _phase(name, "params_built", t_start)
    # draft selection: default int8 self-draft; "int4" = int4 self-draft
    # (¼ the weight stream); "truncN_int4" = layer-skip draft (first N
    # layers of the target, int4 — speculative.truncated_draft)
    draft = spec.get("draft")
    kwargs = {}
    if draft == "int4":
        from llm_np_cp_tpu.quant import quantize_params

        kwargs["draft_params"] = quantize_params(params, bits=4)
    elif draft and draft.startswith("trunc"):
        from llm_np_cp_tpu.speculative import truncated_draft

        n_layers = int(draft.removeprefix("trunc").split("_")[0])
        bits = 4 if draft.endswith("int4") else None
        dp, dc = truncated_draft(params, config, n_layers, bits=bits)
        kwargs.update(draft_params=dp, draft_config=dc)
    gen = SpeculativeGenerator(
        params, config, gamma=spec["gamma"], sampler=Sampler(kind="greedy"),
        **kwargs,
    )
    batch, prompt_len, decode_tokens = spec["batch"], spec["prompt_len"], spec["decode_tokens"]
    rng = np.random.default_rng(0)

    def one(prompt_host, tag):
        res = gen.generate(prompt_host, decode_tokens)
        _phase(name, f"{tag}:done", t_start)
        return {
            "rate": res.decode_tokens_per_s,
            "acc": res.acceptance_rate,
            "chain": int(res.tokens.sum()),
        }

    _, runs = _chained_reps(
        one, rng.integers(0, config.vocab_size, (batch, prompt_len)),
        config.vocab_size,
    )
    rates = [r["rate"] for r in runs]
    acc = [r["acc"] for r in runs]
    return {
        "config": name,
        "ok": True,
        "decode_tok_s_chip": round(float(np.median(rates)), 1),
        "per_seq_tok_s": round(float(np.median(rates)) / batch, 1),
        "acceptance_rate": round(float(np.median(acc)), 3),
        "gamma": spec["gamma"],
        "draft": spec.get("draft", "int8_self"),
        "batch": batch,
        "prompt_len": prompt_len,
        "decode_tokens": decode_tokens,
    }


def run_warm() -> dict:
    """AOT-compile every decode/prefill config's programs from ABSTRACT
    shapes (jax.eval_shape params — no weight init, no transfer, no
    execution) to populate the persistent compilation cache.  One warm
    pass makes every subsequent measured run (including the driver's)
    hit warm compiles — the r2 evidence says cold compile is what burns
    the per-config budget: the one config with cache entries (bs=1)
    finished, the cold ones (bs=8/32) timed out.
    """
    import jax
    import jax.numpy as jnp

    from llm_np_cp_tpu.cache import KVCache, align_capacity
    from llm_np_cp_tpu.config import GEMMA_2_2B, LLAMA_3_2_1B, LLAMA_3_2_3B, tiny_config
    from llm_np_cp_tpu.generate import make_decode_loop_fn, make_prefill_fn
    from llm_np_cp_tpu.models.transformer import init_params
    from llm_np_cp_tpu.ops.sampling import Sampler

    t0 = time.perf_counter()
    configs = {
        "llama1b": LLAMA_3_2_1B, "llama3b": LLAMA_3_2_3B,
        "gemma2_2b": GEMMA_2_2B, "tiny": tiny_config("llama"),
    }
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    done, failed = [], []
    # PRIORITY order: a partial warm (timeout) still covers the headline.
    # Spec/ragged configs build their programs inside Generator classes
    # and aren't abstractly warmable here; they pay their own compiles.
    # BENCH_WARM_LIMIT=N (parent sets it under a tight deadline) warms
    # only the first N priority configs so measurement starts sooner —
    # later configs pay their own compile out of their own timeout.
    warm_limit = int(os.environ.get("BENCH_WARM_LIMIT", "0")) or None
    warmable = [
        n for n in PRIORITY
        if n not in SPEC_CONFIGS and n not in EXTRA_CHILDREN
        and n not in RAGGED_CONFIGS
        and n not in SERVE_HTTP_CONFIGS and n not in SERVE_CHAOS_CONFIGS
        and n not in SERVE_MIXED_CONFIGS and n not in SERVE_SPEC_CONFIGS
        and n not in SERVE_SHARDED_CONFIGS
        and n not in SERVE_RESTART_CONFIGS
        and n not in SERVE_ROLLING_CONFIGS
        and n not in SERVE_TIER_CONFIGS
        and n not in SERVE_TENANT_CONFIGS
    ]
    for name in warmable[:warm_limit]:
        spec = {**DECODE_CONFIGS, **PREFILL_CONFIGS}[name]
        config = configs[spec["model"]]

        def _abstract_params(cfg=config, quant=spec.get("quant", False)):
            params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
            if quant:
                from llm_np_cp_tpu.quant import quantize_params

                params = quantize_params(
                    params, bits=4 if str(quant).startswith("int4") else 8,
                    act_quant=str(quant).endswith("_a8"),
                )
            return params

        params = jax.eval_shape(_abstract_params)
        sampler = Sampler(kind=spec.get("sampler", "greedy"))
        batch = spec.get("batch", 1)
        prompt_len = spec["prompt_len"]
        decode_tokens = spec.get("decode_tokens")
        # keep in lockstep with _measure_decode's capacity sizing
        max_seq = align_capacity(prompt_len + (decode_tokens or 0) + 8)
        cdt = jnp.int8 if spec.get("cache_dtype") == "int8" else jnp.bfloat16
        cache = jax.eval_shape(
            lambda c=config, b=batch, m=max_seq, dt=cdt: KVCache.init(
                c, b, m, dtype=dt
            )
        )
        ids = jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)
        # per-config env (e.g. LLMTPU_SCAN_UNROLL) is read at TRACE time,
        # so it must be live while lowering or this warms the wrong
        # program and the measured child compiles cold
        saved_env = {
            k: os.environ.get(k) for k in (spec.get("env") or {})
        }
        os.environ.update(spec.get("env") or {})
        try:
            chunk = spec.get("chunk")
            if chunk:
                # chunked prefill = one chunk-wide program; warm the SAME
                # jitted step the measured path dispatches (its exposed
                # chunk_step — logits-only, donated cache), not a
                # make_prefill_fn lowered at the chunk shape, which is a
                # different program and misses the cache (ADVICE r3 #2)
                from llm_np_cp_tpu.generate import make_chunked_prefill_fn

                ids = jax.ShapeDtypeStruct((batch, chunk), jnp.int32)
                chunked = make_chunked_prefill_fn(
                    config, sampler, chunk_size=chunk,
                    attn_impl=spec.get("attn_impl", "xla"),
                )
                chunked.chunk_step.lower(params, ids, cache).compile()
            else:
                prefill = make_prefill_fn(
                    config, sampler, attn_impl=spec.get("attn_impl", "xla")
                )
                prefill.lower(params, ids, cache, key).compile()
            _phase("warm", f"{name}:prefill", t0)
            if decode_tokens:
                loop = make_decode_loop_fn(
                    config, sampler, attn_impl=spec.get("decode_attn", "xla")
                )
                tok = jax.ShapeDtypeStruct((batch,), jnp.int32)
                loop.lower(params, tok, cache, key, decode_tokens).compile()
                # the half-length dispatch of the marginal-rate measurement
                loop.lower(
                    params, tok, cache, key, _half_len(decode_tokens)
                ).compile()
                _phase("warm", f"{name}:decode_loop", t0)
            done.append(name)
        except Exception as e:  # record and keep warming the rest
            failed.append({"config": name, "error": repr(e)[:300]})
            _phase("warm", f"{name}:FAILED", t0)
        finally:
            for k, v in saved_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # Ragged configs dispatch Generator-owned programs: the SAME factories
    # with ragged (attn_mask, pad_offsets) operands and n-1 step loops.
    # Lowering identical HLO here hits the shared XLA compilation cache,
    # so the measured child's 600 s isn't spent on the [8, 4096] prefill
    # compile.  Skipped under BENCH_WARM_LIMIT (tight deadline).
    for name in [] if warm_limit else [n for n in PRIORITY if n in RAGGED_CONFIGS]:
        spec = RAGGED_CONFIGS[name]
        config = configs[spec["model"]]
        lens = spec.get("lens", RAGGED_LENS)
        n_full = spec.get("decode", RAGGED_DECODE)
        b, s = len(lens), max(lens)
        cap = align_capacity(s + n_full)
        try:
            params = jax.eval_shape(
                lambda cfg=config: init_params(
                    jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16
                )
            )
            cache = jax.eval_shape(
                lambda cfg=config, m=cap: KVCache.init(cfg, b, m, dtype=jnp.bfloat16)
            )
            sampler = Sampler(kind="greedy")
            ids = jax.ShapeDtypeStruct((b, s), jnp.int32)
            mask = jax.ShapeDtypeStruct((b, s), jnp.bool_)
            pads = jax.ShapeDtypeStruct((b,), jnp.int32)
            prefill = make_prefill_fn(config, sampler)
            prefill.lower(params, ids, cache, key, mask, pads).compile()
            _phase("warm", f"{name}:prefill", t0)
            loop = make_decode_loop_fn(config, sampler, attn_impl=spec["attn"])
            tok = jax.ShapeDtypeStruct((b,), jnp.int32)
            for n_steps in (n_full - 1, max(n_full // 2, 1) - 1):
                if n_steps > 0:
                    loop.lower(params, tok, cache, key, n_steps, pads).compile()
            _phase("warm", f"{name}:decode_loop", t0)
            done.append(name)
        except Exception as e:
            failed.append({"config": name, "error": repr(e)[:300]})
            _phase("warm", f"{name}:FAILED", t0)

    return {
        "config": "warm",
        "ok": not failed,
        "warmed": done,
        "failed": failed,
        "total_s": round(time.perf_counter() - t0, 1),
    }


def run_decomp() -> dict:
    """Locate the int8 roofline gap (VERDICT r4 weak #4 / task 6).

    int8_bs8 achieved 47.5% of HBM roofline vs bf16's 63% — the absolute
    per-step times imply a fixed ~1.9 ms/step that doesn't shrink with
    the weight stream.  This child separates the two directly: the decode
    step is timed at FULL and HALF layer depth (the truncated model is a
    prefix of the full one — speculative.truncated_draft), so

        per_layer_ms = (t_full − t_half) / (L − L/2)
        fixed_ms     = t_full − per_layer_ms · L

    plus the lm_head matmul timed alone.  If per_layer_ms tracks the
    weight stream at roofline, the gap is the fixed part (head, sampling,
    cache update, dispatch) — that's what to attack; if not, the quant
    einsum itself is the blocker.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.generate import make_decode_loop_fn, make_prefill_fn
    from llm_np_cp_tpu.models.transformer import final_logits
    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.quant import quantize_params
    from llm_np_cp_tpu.speculative import truncated_draft

    t0 = time.perf_counter()
    batch = int(os.environ.get("DECOMP_BATCH", "8"))
    prompt_len, decode_tokens = 128, 128
    model = os.environ.get("DECOMP_MODEL", "llama1b")
    config, params = _build_model(model, tag="decomp", t0=t0)
    sampler = Sampler(kind="greedy")
    out = {"config": "decomp", "ok": True, "model": model, "batch": batch}
    full_l = config.num_hidden_layers
    half_l = max(full_l // 2, 1)

    for mode in ("bf16", "int8", "int8_a8"):
        p = (
            params if mode == "bf16"
            else quantize_params(params, act_quant=mode == "int8_a8")
        )
        rates: dict[int, tuple[float, str]] = {}
        for n_layers in (full_l, half_l):
            pl_, cl = (
                (p, config) if n_layers == full_l
                else truncated_draft(p, config, n_layers)
            )
            prefill = make_prefill_fn(cl, sampler)
            loop = make_decode_loop_fn(cl, sampler)
            _, rate, _, marginal = _measure_decode(
                f"decomp_{mode}_L{n_layers}", cl, pl_, prefill, loop,
                batch, prompt_len, decode_tokens, reps=2, t_start=t0,
            )
            # marginal (dispatch-cost-cancelled) when available:
            # decomposition needs on-chip step time
            rates[n_layers] = (
                (marginal, "marginal") if marginal is not None else (rate, "e2e")
            )
        step_full_ms = 1000.0 * batch / rates[full_l][0]
        step_half_ms = 1000.0 * batch / rates[half_l][0]
        out[mode] = {
            "step_ms": round(step_full_ms, 3),
            "step_half_ms": round(step_half_ms, 3),
            "layers": [full_l, half_l],
            "rate_sources": [rates[full_l][1], rates[half_l][1]],
        }
        # the fixed-vs-per-layer split is only meaningful when BOTH depths
        # are dispatch-cost-cancelled — mixing an on-chip number with an
        # e2e one would put the dispatch cost into fixed_ms, the very
        # thing the decomposition isolates
        if full_l > half_l and rates[full_l][1] == rates[half_l][1] == "marginal":
            per_layer_ms = (step_full_ms - step_half_ms) / (full_l - half_l)
            out[mode].update(
                per_layer_ms=round(per_layer_ms, 4),
                fixed_ms=round(step_full_ms - per_layer_ms * full_l, 3),
            )
        else:
            out[mode]["decomposition"] = (
                "skipped: marginal rate unavailable at one or both depths"
                if full_l > half_l
                else "skipped: single-layer model has no depth contrast"
            )

    # lm_head alone, via the same two-length marginal trick the decode
    # measurement uses (a single small dispatch is dominated by its fixed
    # cost): fused loops of 8 vs 4 head matmuls, serialized by a data
    # dependence so XLA can't hoist the matmul, marginal = Δt/4.
    def _head_loop(n):
        def body(i, carry):
            logits = final_logits(params, carry, config, last_only=True)
            nudge = jnp.tanh(jnp.mean(logits) * 1e-3) * 1e-3
            return carry * (1.0 + nudge).astype(carry.dtype)

        return jax.jit(
            lambda x0: jnp.sum(jax.lax.fori_loop(0, n, body, x0))
        )

    head8, head4 = _head_loop(8), _head_loop(4)

    def one_head(seed, tag):
        x0 = jnp.full(
            (batch, 1, config.hidden_size), 1.0 + (seed % 7) / 7.0, jnp.bfloat16
        )
        t1 = time.perf_counter()
        np.asarray(head8(x0))
        t2 = time.perf_counter()
        np.asarray(head4(x0))
        t3 = time.perf_counter()
        _phase("decomp", f"{tag}:head_done", t0)
        return {"d8": t2 - t1, "d4": t3 - t2, "chain": seed + 1}

    _, runs = _chained_reps(one_head, 1, 10**9)
    out["lm_head_ms"] = round(
        1000.0 * float(np.median([r["d8"] - r["d4"] for r in runs])) / 4, 3
    )
    out["total_s"] = round(time.perf_counter() - t0, 1)
    return out


def run_kernels() -> dict:
    """Mosaic compile probe for EVERY Pallas kernel the gates know
    (``support.KERNELS``) on the live backend: compile+run each at the
    probe shapes, record ok/error.  The same probes back the serve
    engine's and Generator's gates (ops/pallas/support.py); this child
    makes the verdict a bench artifact.  The per-family shape matrix
    with XLA-twin comparison is ``python chip_smoke.py --kernels``."""
    import jax

    from llm_np_cp_tpu.ops.pallas import support

    t0 = time.perf_counter()
    out = {"config": "kernels", "backend": jax.default_backend()}
    failed = []
    for kernel in support.KERNELS:
        err = support.kernel_error(kernel)
        out[kernel] = "ok" if err is None else f"FAIL: {err[:300]}"
        if err is not None:
            failed.append(kernel)
    out["ok"] = not failed
    out["total_s"] = round(time.perf_counter() - t0, 1)
    return out


def run_probe() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.perf_counter()
    x = jnp.ones((256, 256), jnp.bfloat16)
    s = float(np.asarray(x @ x).sum())
    return {
        "ok": True,
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "matmul_sum": s,
        "probe_s": round(time.perf_counter() - t0, 2),
    }


def _device_stamp() -> dict:
    """Which device a child's numbers came from — every result carries it."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "devices": jax.device_count()}


def child_main(mode: str) -> None:
    _child_jax()
    if mode == "probe":
        out = run_probe()
    elif mode == "warm":
        out = run_warm()
    elif mode == "kernels":
        out = run_kernels()
    elif mode == "decomp":
        out = run_decomp()
    elif mode in DECODE_CONFIGS:
        out = run_decode_config(mode)
    elif mode in PREFILL_CONFIGS:
        out = run_prefill_config(mode)
    elif mode in SPEC_CONFIGS:
        out = run_spec_config(mode)
    elif mode in RAGGED_CONFIGS:
        out = run_ragged_config(mode)
    elif mode in SERVE_MIXED_CONFIGS:
        out = run_serve_mixed_config(mode)
    elif mode in SERVE_TIER_CONFIGS:
        out = run_serve_tier_config(mode)
    elif mode in SERVE_SPEC_CONFIGS:
        out = run_serve_spec_config(mode)
    elif mode in SERVE_HTTP_CONFIGS:
        out = run_serve_http_config(mode)
    elif mode in SERVE_CHAOS_CONFIGS:
        out = run_serve_chaos_config(mode)
    elif mode in SERVE_RESTART_CONFIGS:
        out = run_serve_restart_config(mode)
    elif mode in SERVE_ROLLING_CONFIGS:
        out = run_serve_rolling_config(mode)
    elif mode in SERVE_SHARDED_CONFIGS:
        out = run_serve_sharded_config(mode)
    elif mode in SERVE_TENANT_CONFIGS:
        out = run_serve_tenant_config(mode)
    else:
        raise SystemExit(f"unknown config {mode!r}")
    if mode not in SERVE_RESTART_CONFIGS:
        # (the restart config's servers are its children; this process
        # must not initialise a backend of its own to ask)
        out.update(_device_stamp())
    print(json.dumps(out), flush=True)


# ----------------------------------------------------------------------
# Parent-process orchestration
# ----------------------------------------------------------------------

def _spawn(mode: str, timeout: float, env: dict | None = None) -> dict:
    """Run `python bench.py --run mode` with a hard timeout; parse the last
    JSON line of its stdout.  Never raises.  On timeout, the child's
    partial stderr (recovered from TimeoutExpired) yields the last
    ``bench-phase`` breadcrumbs — where the budget actually went."""
    cmd = [sys.executable, os.path.abspath(__file__), "--run", mode]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout, cwd=REPO,
            env={**os.environ, **(env or {})},
        )
    except subprocess.TimeoutExpired as e:
        err = e.stderr or b""
        if isinstance(err, bytes):
            err = err.decode(errors="replace")
        phases = [l for l in err.splitlines() if l.startswith("bench-phase")]
        return {
            "config": mode,
            "ok": False,
            "error": f"timeout after {round(timeout)}s",
            "diagnosis": _diagnose_timeout(phases, timeout),
            "last_phases": phases[-4:],
        }
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-8:]
    return {
        "config": mode,
        "ok": False,
        "error": f"rc={proc.returncode}, no JSON line",
        "tail": "\n".join(tail)[-800:],
    }


def _diagnose_timeout(phases: list[str], timeout: float) -> str:
    """One-line explanation of WHERE a timed-out child spent its budget,
    from its bench-phase breadcrumbs (VERDICT r2 weak #2: the bs=8 burn
    was undiagnosable from artifacts)."""
    if not phases:
        return (
            f"no phase reached in {round(timeout)}s — hung in backend init / "
            "params transfer"
        )
    try:
        last = json.loads(phases[-1].removeprefix("bench-phase "))
    except json.JSONDecodeError:
        return "unparseable phase log"
    name, t = last.get("phase", "?"), last.get("t", "?")
    if name == "params_init_start":
        nxt = "params materialization (device init / transfer, not compile)"
    elif name == "params_built":
        nxt = "prefill compile"
    elif name.startswith("warmup:prefill"):
        nxt = "decode-loop compile"
    elif name.startswith("warmup") or name == "compiled":
        nxt = "first measured rep"
    elif name.startswith("rep"):
        nxt = "a later measured rep (execution, not compile)"
    else:
        nxt = "the next phase"
    return f"reached {name!r} at t={t}s, then burned the rest in {nxt}"


def _emit_summary(detail: dict, probe: dict, error: str | None) -> None:
    on_chip = probe.get("backend") == "tpu"
    bs8 = detail.get("llama1b_bs8", {})
    bs1 = detail.get("llama1b_bs1", {})
    # Headline: bs=8 aggregate; fall back to whatever decode config
    # finished.  Only a chip run has a headline: off-chip (an explicit
    # smoke_* rehearsal of the harness) the value stays 0.0 — a CPU
    # number is never written under the device metric's name.
    value = bs8.get("decode_tok_s_chip") if on_chip else None
    headline = "llama1b_bs8_aggregate"
    if value is None and on_chip:
        for name, r in detail.items():
            if r.get("ok") and "decode_tok_s_chip" in r:
                value, headline = r["decode_tok_s_chip"], f"{name}_aggregate"
                break
    result = {
        "metric": "decode_tokens_per_sec_per_chip",
        "value": value if value is not None else 0.0,
        "unit": "tokens/s/chip",
        "vs_baseline": round((value or 0.0) / NORTH_STAR_TOK_S, 3),
        "platform": probe.get("backend"),
        "rehearsal": not on_chip,
        "detail": {
            "headline_definition": (
                f"{headline}: aggregate decode tokens/s on one chip "
                f"(north star {NORTH_STAR_TOK_S:.0f} tok/s/chip; the strict "
                "bs=1 per-seq reading is vs_baseline_bs1_per_seq)"
            ),
            "vs_baseline_bs1_per_seq": round(
                (bs1.get("per_seq_tok_s", 0.0) if on_chip else 0.0)
                / NORTH_STAR_TOK_S, 3
            ),
            "hbm_roofline_gb_s": HBM_GB_S,
            "probe": probe,
            **detail,
        },
    }
    if error:
        result["error"] = error
    print(json.dumps(result), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run", help="(internal) run one config in this process")
    ap.add_argument("--configs", nargs="*", help="subset of configs to run")
    args = ap.parse_args()
    if args.run:
        child_main(args.run)
        return

    t_start = time.time()
    deadline = _deadline_s()
    detail: dict[str, dict] = {}

    # ONE probe.  The device path fails without a chip: a dead probe or
    # a platform other than tpu ends the run non-zero with nothing on
    # stdout.  Off-chip the only thing that may run is an explicit list
    # of smoke_* configs — a rehearsal of the harness, stamped as such.
    probe = _spawn("probe", min(PROBE_TIMEOUT, deadline))
    if not probe.get("ok"):
        print(f"bench: device probe failed ({probe.get('error')}); "
              f"nothing measured\n{probe.get('tail', '')}", file=sys.stderr)
        sys.exit(3)
    if probe.get("backend") != "tpu" and not (
        args.configs and all(n.startswith("smoke") for n in args.configs)
    ):
        print(f"bench: no TPU — the probe found platform "
              f"{probe.get('backend')!r} ({probe.get('device')}).  Device "
              "cells need a chip; off-chip only explicit smoke_* configs "
              "run (--configs smoke_tiny ...), as a rehearsal.",
              file=sys.stderr)
        sys.exit(3)

    names = args.configs or list(PRIORITY)
    if not args.configs:
        # AOT-warm the compilation cache first (abstract shapes, no
        # execution): one pass amortizes every config's compile.  Capped
        # so slow compiles can't eat the run; a timeout here is recorded
        # but configs still proceed (each re-compiles what warm didn't
        # reach).
        remaining = deadline - (time.time() - t_start)
        # cap covers ~2 programs per decode config (full + half loop);
        # under a tight deadline (e.g. the driver's 1500 s default) warm
        # only the top few priority configs so measurement starts sooner
        warm_env = {"BENCH_WARM_LIMIT": "4"} if remaining < 2400 else None
        warm = _spawn(
            "warm", min(540.0, max(remaining / 3, 60.0)), env=warm_env
        )
        detail["warm"] = warm
        print(json.dumps(warm), file=sys.stderr, flush=True)
        # Mosaic verdict per Pallas kernel at the probe shapes
        detail["kernels"] = _spawn("kernels", 300.0)
        print(json.dumps(detail["kernels"]), file=sys.stderr, flush=True)
        _emit_summary(detail, probe, error=_failed_error(detail))
    for name in names:
        remaining = deadline - (time.time() - t_start)
        if remaining < MIN_CONFIG_BUDGET_S:
            detail[name] = {
                "config": name, "ok": False,
                "error": f"skipped: {round(remaining)}s left of "
                         f"BENCH_DEADLINE_S={round(deadline)}",
            }
            print(json.dumps(detail[name]), file=sys.stderr, flush=True)
            continue
        budget = min(TIMEOUTS.get(name, DEFAULT_TIMEOUT), remaining - 10)
        spec_env = {
            **DECODE_CONFIGS, **PREFILL_CONFIGS, **SPEC_CONFIGS,
            **RAGGED_CONFIGS, **SERVE_MIXED_CONFIGS,
            **SERVE_HTTP_CONFIGS,
            **SERVE_CHAOS_CONFIGS, **SERVE_SHARDED_CONFIGS,
            **SERVE_RESTART_CONFIGS,
        }.get(name, {}).get("env")
        res = _spawn(name, budget, env=spec_env)
        detail[name] = res
        print(json.dumps(res), file=sys.stderr, flush=True)
        # Re-emit the FULL summary after every config (last stdout line
        # wins) so an outer kill at any moment leaves a parseable artifact.
        _emit_summary(detail, probe, error=_failed_error(detail))

    # Final emit covers the nothing-ran / everything-skipped path too.
    _emit_summary(detail, probe, error=_failed_error(detail))


def _failed_error(detail: dict) -> str | None:
    # "warm" is advisory (cache priming): its failure alone doesn't
    # flag the run
    failed = [
        n for n, r in detail.items() if not r.get("ok") and n != "warm"
    ]
    return f"configs failed: {failed}" if failed else None


if __name__ == "__main__":
    main()
