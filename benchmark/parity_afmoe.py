#!/usr/bin/env python3
"""How close is the SERVED path to float32?  The plain float32 reference ON
THE CHIP, at the configuration's widths, against what the unified tick itself
produced on the same seeded weights - the question ``correct`` cannot ask (it
ranks the served tokens under the program's own bf16 ``models.forward``).

    python benchmark/parity_afmoe.py --workload <cell> --seed <n>

makes the weights from the seed as ``run.py`` does, builds a ``ServeEngine``
with the cell's block size, chunking, tick budget and dtypes (a few slots:
the reference needs the room the cell's pool takes), serves ``--samples``
requests with prompts spread over the traffic mix's range (the longest first:
8,192 tokens send a window chain round its ring of 67 blocks nearly twice)
and ``--new`` answer tokens each - prefill in slices of a chunk, then decode,
through BOTH page classes, the Pallas kernel (``--attn xla``: its XLA twin) -
and keeps the LOGITS every served token was drawn from (the XLA tail, wrapped
with a callback).  Per request they are compared with ``reference_afmoe.py``
(float32, ``highest``, attention in query blocks, routing over all 256
experts with the same share held, the post-norm of the partial sum) over
prompt + the served tokens:

- ``off``: the largest difference of any logit at a position, as a share of
  the reference's (max - mean) spread there: mean / p99 / worst;
- ``gap``: how far the served token lies below the float32 maximum
  (``reference.py``'s gap, measured against float32).

The run FAILS (exit 1) when ``off`` passes ``OFF_MEAN_LIMIT`` /
``OFF_P99_LIMIT``.  Each ``--control`` serves the FIRST (longest) request
again with ONE equation of the PROGRAM changed and reports the same numbers,
and beside them the verdict of ``benchmark/reference.py``'s rule (the
comparison that decides ``correct``) on the control's tokens.  Controls, each
of which must come out NOT within the limits: ``window_off_by_one`` (a window
of 4,097), ``recycled_early`` (a window block let go one tick before its last
reader), ``rope_in_global`` (the global layer rotated like the window ones),
``no_gate`` (the output gate left out), ``post_norm_routed_only`` (the
post-norm taken of the routed part alone, the shared expert added beside
it); and one that the chip's comparison does NOT see (it reads inside the
limits; the CPU tests refuse it at float32): ``bf16_router`` (the router's
scores rounded to bfloat16 where the configuration says float32); ``--reference-precision default`` runs the
float32 reference at the default matmul precision instead (bf16 operands on a
TPU: what the served path computes in anyway), ``--reference-precision fp8``
on weights rounded to float8 (e4m3) first: the nearest precision below the
bf16 the configuration states, which has to come out NOT within the limits.

A builder's diagnostic: not a metric, not part of ``correct``; writes
``benchmark/out/<cell>-<seed>.parity.json``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

# Limits on ``off`` (share of the float32 logits' spread), between the two
# readings on the chip at the published widths (PERF.md section 6, PR 50;
# prompts 8,192 and 5,120, 32 answer tokens each, the file's damped experts):
# what the served path read over four seeds (mean 0.0081-0.0094, a request's
# 0.0069-0.0098; p99 0.044-0.059, worst 0.050-0.070: the tail is the handful
# of positions where the two precisions chose another held expert), and what
# the nearest broken programs read: a window of 4,097 mean 0.0125-0.0139 over
# four seeds (ONE more key among 4,096 moves every logit a little and none
# much: its p99 0.018-0.046 lies UNDER the unbroken program's, so the mean is
# the limit that sees it), a window block recycled a tick early 0.0176-0.0208,
# RoPE in the global layer 0.067 / p99 0.084, the post-norm of the routed part
# alone 0.37, no gate 0.49; the reference on weights rounded to float8 0.133 /
# 0.176.  A bf16 program against float32 is rounding: it moves every logit a
# little; a wrong equation moves them by a share of the spread.  NOT seen on
# the chip: the router's scores in bfloat16 (0.0067 / 0.0086, inside both
# limits, as in the two earlier expert stacks' parity runs: its flips are a
# fraction of those the two precisions already differ by, and the file draws
# a routed expert's answer small); tests/test_afmoe.py sees it at float32.
OFF_MEAN_LIMIT = 0.011
OFF_P99_LIMIT = 0.075

CONTROLS = ("window_off_by_one", "recycled_early", "rope_in_global", "no_gate",
            "post_norm_routed_only", "bf16_router")


def summary(x) -> dict:
    import numpy as np

    return dict(mean=float(np.mean(x)), p99=float(np.quantile(x, 0.99)),
                worst=float(np.max(x)))


def broken_config(config, control: str | None):
    """One number of the layer declaration changed."""
    change = {
        "window_off_by_one": dict(sliding_window=config.sliding_window + 1),
        "rope_in_global": dict(global_rope=True),
        "no_gate": dict(attn_output_gate=False),
    }.get(control)
    return dataclasses.replace(config, **change) if change else config


@contextlib.contextmanager
def broken_program(control: str | None):
    """One rule of the program changed while an engine is built and serves:
    a ring that lets a block go while this tick's first query still sees it;
    an expert layer that norms the routed part alone; a router whose scores
    are rounded to bfloat16."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    import llm_np_cp_tpu.serve.engine as engine_mod
    from llm_np_cp_tpu.models import transformer
    from llm_np_cp_tpu.ops import moe
    from llm_np_cp_tpu.ops.norms import rms_norm
    from llm_np_cp_tpu.serve.block_pool import WindowRings

    real = (WindowRings.advance, engine_mod.experts_block,
            moe.route_sigmoid_topk)

    def early(self, slot, start, n):
        got = real[0](self, slot, start, n)
        first = np.maximum(np.asarray(start) + n - self.window + 1, 0) // self.block_size
        self.first[slot] = np.minimum(np.maximum(first, self.first[slot]),
                                      self.end[slot] - 1)
        return got

    def routed_only(w, x, *, config, act, live=None):
        routed, shared, chosen, load = transformer.experts_parts(
            w, x, config=config, act=act, live=live)
        with jax.named_scope(transformer.SCOPE_MOE_EXPERTS):
            x = x + shared() + rms_norm(routed, w["ln_mlp_out"],
                                        eps=config.rms_norm_eps)
        return x, chosen.reshape(*x.shape[:2], -1), load

    if control == "recycled_early":
        WindowRings.advance = early
    elif control == "post_norm_routed_only":
        engine_mod.experts_block = routed_only
    elif control == "bf16_router":
        moe.route_sigmoid_topk = functools.partial(
            real[2], score_dtype=jnp.bfloat16)
        # (``moe_dropless`` is jitted: what it traced for the unbroken
        # program must not answer for this one, nor this one's later)
        moe.moe_dropless.clear_cache()
    try:
        yield
    finally:
        (WindowRings.advance, engine_mod.experts_block,
         moe.route_sigmoid_topk) = real
        if control == "bf16_router":
            moe.moe_dropless.clear_cache()


def serve(params, config, spec, prompts, new: int, attn: str,
          control: str | None) -> list[dict]:
    """The requests through a fresh engine: per request its tokens and the
    logits each was drawn from ``[new, V]`` float32, and the window blocks
    the engine recycled."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import llm_np_cp_tpu.serve.engine as engine_mod
    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.serve import ServeEngine
    from llm_np_cp_tpu.serve.engine import pool_geometry

    serve_cfg = spec["config"].get("serve", {})
    block = serve_cfg.get("block_size", 64)
    chunk = min(block * 2, 256)  # the CLI's chunking
    slots = len(prompts)
    _, blocks, max_seq = pool_geometry(
        max(len(p) for p in prompts), new, slots, block, prefill_chunk=chunk)
    cache_dtype = jnp.bfloat16 if serve_cfg.get(
        "cache_dtype", "bf16") == "bf16" else jnp.float32
    config = broken_config(config, control)
    budget = next((int(v) for f, v in zip(
        spec["params"].get("serve_flags", []),
        spec["params"].get("serve_flags", [])[1:])
        if f == "--tick-token-budget"), None)
    ticks: list[np.ndarray] = []
    real_logits = engine_mod.final_logits

    def probed(p, x, cfg, **kw):
        logits = real_logits(p, x, cfg, **kw)
        jax.debug.callback(lambda a: ticks.append(np.asarray(a)), logits)
        return logits

    engine_mod.final_logits = probed
    try:
        with broken_program(control):
            engine = ServeEngine(
                params, config,
                sampler=Sampler(kind="greedy"), sample_epilogue="off",
                max_slots=slots, num_blocks=blocks, block_size=block,
                max_seq_len=max_seq, prefill_chunk=chunk,
                cache_dtype=cache_dtype, tick_token_budget=budget,
                mixed_step="on" if attn == "xla" else "auto")
            if attn == "xla":
                engine.ragged_attn_impl = "xla"
                engine._mixed_step = engine._make_mixed_step()
            assert engine.mixed and engine.ragged_attn_impl == attn, (
                engine.mixed, engine.ragged_attn_impl)
            reqs = [engine.submit(p, max_new_tokens=new, seed=i)
                    for i, p in enumerate(prompts)]
            got: dict[int, list] = {r.req_id: [] for r in reqs}
            more = True
            while more:
                before = {r.req_id: len(r.generated) for r in reqs}
                more = engine.step()
                jax.effects_barrier()
                for r in reqs:
                    if len(r.generated) > before[r.req_id]:
                        slot = (r.slot if r.slot is not None and r.slot >= 0
                                else r.extra["_slot"])
                        got[r.req_id].append(ticks[-1][slot, 0])
                    if r.slot is not None and r.slot >= 0:
                        r.extra["_slot"] = r.slot
            recycled = engine.pool.stats().get("window_blocks_recycled_total", 0)
    finally:
        engine_mod.final_logits = real_logits
    del engine
    return [dict(prompt=list(map(int, r.prompt)), tokens=list(r.generated),
                 logits=np.stack(got[r.req_id]), recycled=recycled)
            for r in reqs]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--samples", type=int, default=3)
    ap.add_argument("--new", type=int, default=48,
                    help="answer tokens a request (decode ticks)")
    ap.add_argument("--prompt", type=int, nargs="*", default=None,
                    help="prompt lengths (default: spread over the mix's range)")
    ap.add_argument("--attn", choices=("pallas", "xla"), default="pallas")
    ap.add_argument("--control", action="append", default=[],
                    choices=CONTROLS + ("all",))
    ap.add_argument("--reference-precision",
                    choices=("highest", "default", "fp8"), default="highest")
    ap.add_argument("--q-block", type=int, default=512)
    ap.add_argument("--data-root", default=None,
                    help="directory that holds BENCHMARK.json (tests: a copy)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference_afmoe as ref
    import run as harness
    import traffic as traffic_mod
    from llm_np_cp_tpu.config import ModelConfig
    from reference import Reference

    spec = harness.load_spec(Path(args.data_root or harness.ROOT), args.workload)
    cfg_dict = spec["config"]
    config = ModelConfig.from_hf_dict(cfg_dict)
    served = jnp.bfloat16 if cfg_dict.get("serve", {}).get(
        "dtype", "bf16") == "bf16" else jnp.float32
    params = harness.make_weights(config, args.seed, served, False)
    p_max, _ = traffic_mod.limits(spec["traffic"])
    dist = spec["traffic"]["prompt_tokens"]
    p_min = int(dist.get("min", dist.get("value", p_max)))
    lengths = args.prompt or [
        int(round(p_min + (p_max - p_min) * i / max(args.samples - 1, 1)))
        for i in range(args.samples)][::-1]
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, config.vocab_size, n).tolist() for n in lengths]
    controls = list(CONTROLS) if "all" in args.control else args.control
    rule = Reference(params, config, length=max(lengths) + args.new,
                     batch=min(4, len(prompts)))
    def to_float8(a):
        # the nearest precision below the served bf16: a matrix rounded to
        # float8 (e4m3) before the float32 reference reads it, in place of
        # the one it was (two copies of the weights do not fit the chip)
        if a.dtype != served or a.ndim < 2:
            return a
        rounded = a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        a.delete()
        return rounded

    if args.reference_precision == "fp8" and controls:
        raise SystemExit("--reference-precision fp8 rounds the weights in "
                         "place after serving: run it without controls")
    ref_params = params
    rows, ok = [], True
    for control in [None] + controls:
        t = time.time()
        # (a control serves the longest request alone: its reference costs
        # half a minute of the chip a request)
        asked = prompts if control is None else prompts[:1]
        served_reqs = serve(params, config, spec, asked, args.new, args.attn,
                            control)
        t_serve = time.time() - t
        verdicts = rule.check([(r["prompt"], r["tokens"]) for r in served_reqs])
        offs, gaps, same, finite = [], [], [], True
        t = time.time()
        if args.reference_precision == "fp8":
            ref_params = jax.tree.map(to_float8, params)
        for r in served_reqs:
            # every sequence at ONE length (a causal model's logits do not
            # depend on what follows): the reference's plain jax.numpy
            # compiles each operation anew for each length, a minute of the
            # chip a length at these widths
            seq = np.ones(max(lengths) + args.new, np.int32)
            seq[:len(r["prompt"]) + len(r["tokens"])] = r["prompt"] + r["tokens"]
            lo = len(r["prompt"]) - 1
            want = np.asarray(ref.forward(
                ref_params, cfg_dict, seq, q_block=args.q_block, logits_from=lo,
                precision=("highest" if args.reference_precision == "fp8"
                           else args.reference_precision)),
                np.float32)[:len(r["tokens"])]
            got = r["logits"].astype(np.float32)
            finite = finite and bool(np.isfinite(got).all() and np.isfinite(want).all())
            top = want.max(-1)
            spread = np.maximum(top - want.mean(-1), 1e-9)
            toks = np.asarray(r["tokens"])
            offs.append(np.abs(got - want).max(-1) / spread)
            gaps.append((top - want[np.arange(len(toks)), toks]) / spread)
            same.append(want.argmax(-1) == toks)
        off, gap = np.concatenate(offs), np.concatenate(gaps)
        within = bool(finite and off.mean() <= OFF_MEAN_LIMIT
                      and np.quantile(off, 0.99) <= OFF_P99_LIMIT)
        row = dict(
            control=control, attn=args.attn,
            prompts=lengths[:len(served_reqs)], new=args.new,
            reference_precision=args.reference_precision, finite=finite,
            first_token_off=[float(o[0]) for o in offs],
            off=summary(off), gap=summary(gap),
            off_by_request=[summary(o) for o in offs],
            same_argmax=float(np.concatenate(same).mean()),
            within_limits=within,
            limits=dict(off_mean=OFF_MEAN_LIMIT, off_p99=OFF_P99_LIMIT),
            rule_correct=bool(all(v["ok"] for v in verdicts)),
            rule_worst_ratio=max(v["worst_ratio"] for v in verdicts),
            rule_p99=max(v["ratio_quantiles"][2] for v in verdicts),
            window_blocks_recycled=served_reqs[0]["recycled"],
            seconds=dict(serve=round(t_serve, 1), reference=round(time.time() - t, 1)))
        rows.append(row)
        print(json.dumps(row), flush=True)
        if control is None:
            ok = within
    out = spec["dir"] / "out"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.workload}-{args.seed}.parity.json").write_text(json.dumps(rows, indent=1))
    base = rows[0]
    print(f"parity: {'ok' if ok else 'FAIL'}: served logits against float32 "
          f"({args.reference_precision}): off mean {base['off']['mean']:.4f} p99 "
          f"{base['off']['p99']:.4f} of the spread (limits {OFF_MEAN_LIMIT:g} / "
          f"{OFF_P99_LIMIT:g}); controls: " + ", ".join(
              f"{r['control']} within_limits={r['within_limits']} "
              f"rule_correct={r['rule_correct']}" for r in rows[1:]), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
