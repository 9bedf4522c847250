#!/usr/bin/env python3
"""How close is the SERVED path to float32?  The plain float32 reference ON
THE CHIP, at the configuration's widths, against what the unified tick itself
produced on the same seeded weights - the question ``correct`` cannot ask (it
ranks the served tokens under the program's own bf16 ``models.forward``).

    python benchmark/parity_deepseek_v3.py --workload <cell> --seed <n>

makes the weights from the seed as ``run.py`` does, builds a ``ServeEngine``
with the cell's block size, chunking and dtypes (a few slots: the reference
needs the room the cell's pool takes), serves ``--samples`` requests with
prompts spread over the traffic mix's range and ``--new`` answer tokens each
- prefill in chunks, then decode through the latent pool, the Pallas kernel
(``--attn xla``: its XLA twin) - and keeps the LOGITS every served token was
drawn from (the XLA tail, wrapped with a callback).  Per request they are
compared with ``reference_deepseek_v3.py`` (float32, ``highest``, expanded
attention in query blocks, routing over all experts with the same share
held) over prompt + the served tokens:

- ``off``: the largest difference of any logit at a position, as a share of
  the reference's (max - mean) spread there: mean / p99 / worst;
- ``gap``: how far the served token lies below the float32 maximum
  (``reference.py``'s gap, measured against float32).

The run FAILS (exit 1) when ``off`` passes ``OFF_MEAN_LIMIT`` /
``OFF_P99_LIMIT``.  Each ``--control`` serves the same requests with ONE
equation of the PROGRAM changed and reports the same numbers, and beside them
the verdict of ``benchmark/reference.py``'s rule (the comparison that decides
``correct``) on the control's tokens: what each comparison can and cannot see
is PERF.md section 6.  Controls: ``no_bias`` (the router's correction bias
zeroed), ``no_shared`` (the shared experts' output zeroed), ``k_pe_unrotated``,
``halfsplit_rope`` (pairs (i, i + d/2) in place of (2i, 2i+1)),
``no_kv_a_layernorm``, ``bf16_router``; ``--reference-precision default``
runs the float32 reference at the default matmul precision instead.

``--given-experts`` adds, for the unbroken program, what is left of the
difference when no expert differs: the program's plain ``models.forward``
(bf16, expanded attention: the side of ``correct`` the served tokens are
ranked under) is teacher-forced over the same tokens with every expert
layer's choices, and the float32 reference computed again GIVEN those choices.
``off_given`` is the plain forward against that reference (rounding alone),
``flip_share`` the (token, expert layer) pairs whose chosen experts differ
between the bf16 forward and the float32 reference, ``flip_share_held`` those
among them in which an expert this chip HOLDS goes or comes: a random-weight
expert stack is chaotic under routing noise, and the limits on ``off`` are
held against ``off_given`` where ``off`` itself is mostly flips.

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
# readings on the chip at the published widths (PERF.md section 6, PR 41):
# what the served path read over its seeds (mean 0.0113-0.0131, p99
# 0.017-0.030), and what the nearest broken program reads (a router without
# its correction bias: mean 0.0318, p99 0.0535; every other control reads
# 0.3 or more).  A bf16 program against float32 is rounding: it moves every
# logit a little; a wrong equation moves them by a share of the spread.  The
# mean is the steadier of the two: a p99 of 192 tokens is its second largest.
OFF_MEAN_LIMIT = 0.02
OFF_P99_LIMIT = 0.042

CONTROLS = ("no_bias", "no_shared", "k_pe_unrotated", "halfsplit_rope",
            "no_kv_a_layernorm", "bf16_router")


def summary(x) -> dict:
    import numpy as np

    return dict(mean=float(np.mean(x)), p99=float(np.quantile(x, 0.99)),
                worst=float(np.max(x)))


def broken_params(params: dict, control: str | None) -> dict:
    """``params`` with one leaf of every expert layer zeroed (same shapes:
    the compiled programs serve both)."""
    import jax.numpy as jnp

    leaf = {"no_bias": "expert_bias", "no_shared": "shared_down"}.get(control)
    if leaf is None:
        return params
    return dict(params, layers=[
        {k: (jnp.zeros_like(v) if k == leaf else v) for k, v in g.items()}
        for g in params["layers"]])


@contextlib.contextmanager
def broken_program(control: str | None):
    """One equation of the program changed while an engine is built and
    traced (controls that are no change of the parameters)."""
    from llm_np_cp_tpu.models import transformer
    from llm_np_cp_tpu.ops import moe

    undo = []

    def patch(mod, name, new):
        undo.append((mod, name, getattr(mod, name)))
        setattr(mod, name, new)

    if control == "k_pe_unrotated":
        real = transformer.apply_rope
        patch(transformer, "apply_rope", lambda x, cos, sin, **kw: (
            x if x.shape[-2] == 1 else real(x, cos, sin, **kw)))
    elif control == "bf16_router":
        import jax.numpy as jnp

        real_route = moe.route_sigmoid_topk
        patch(moe, "route_sigmoid_topk", lambda *a, **kw: real_route(
            *a, **dict(kw, score_dtype=jnp.bfloat16)))
    try:
        yield
    finally:
        for mod, name, old in reversed(undo):
            setattr(mod, name, old)


def serve(params, config, spec, prompts, new: int, attn: str,
          control: str | None) -> list[dict]:
    """The requests through a fresh engine: per request its tokens and the
    logits each was drawn from ``[new, V]`` float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import llm_np_cp_tpu.serve.engine as engine_mod
    from llm_np_cp_tpu.models import transformer
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
    if control == "halfsplit_rope":
        config = dataclasses.replace(config, rope_interleave=False)
    ticks: list[np.ndarray] = []
    real_logits = engine_mod.final_logits
    real_norm = transformer.rms_norm

    def probed(p, x, cfg, **kw):
        logits = real_logits(p, x, cfg, **kw)
        jax.debug.callback(lambda a: ticks.append(np.asarray(a)), logits)
        return logits

    def unnormed(x, w, **kw):  # kv_a_layernorm alone is rank wide
        if x.shape[-1] == config.kv_lora_rank:
            return x
        return real_norm(x, w, **kw)

    engine_mod.final_logits = probed
    if control == "no_kv_a_layernorm":
        transformer.rms_norm = unnormed
    try:
        with broken_program(control):
            engine = ServeEngine(
                broken_params(params, control), config,
                sampler=Sampler(kind="greedy"), sample_epilogue="off",
                max_slots=slots, num_blocks=blocks, block_size=block,
                max_seq_len=max_seq, prefill_chunk=chunk,
                cache_dtype=cache_dtype,
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
    finally:
        engine_mod.final_logits = real_logits
        transformer.rms_norm = real_norm
    del engine
    return [dict(prompt=list(map(int, r.prompt)), tokens=list(r.generated),
                 logits=np.stack(got[r.req_id])) for r in reqs]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--samples", type=int, default=4)
    ap.add_argument("--new", type=int, default=48,
                    help="answer tokens a request (decode ticks)")
    ap.add_argument("--prompt", type=int, nargs="*", default=None,
                    help="prompt lengths (default: spread over the mix's range)")
    ap.add_argument("--attn", choices=("pallas", "xla"), default="pallas")
    ap.add_argument("--control", action="append", default=[],
                    choices=CONTROLS + ("all",))
    ap.add_argument("--reference-precision", choices=("highest", "default"),
                    default="highest")
    ap.add_argument("--given-experts", action="store_true",
                    help="also: the plain bf16 forward against the float32 "
                    "reference given the bf16 forward's experts")
    ap.add_argument("--q-block", type=int, default=512)
    ap.add_argument("--data-root", default=None,
                    help="directory that holds BENCHMARK.json (tests: a copy)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference_deepseek_v3 as ref
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
    first = cfg_dict.get("first_expert", 0)
    held = range(first, first + cfg_dict["n_routed_experts"])

    @jax.jit
    def plain(params, ids):
        from llm_np_cp_tpu.models.transformer import forward

        x, _, aux = forward(params, ids, config, skip_logits=True,
                            output_experts=True)
        return x, aux["experts"]
    rows, ok = [], True
    for control in [None] + controls:
        t = time.time()
        served_reqs = serve(params, config, spec, prompts, args.new, args.attn,
                            control)
        t_serve = time.time() - t
        verdicts = rule.check([(r["prompt"], r["tokens"]) for r in served_reqs])
        offs, gaps, same, finite = [], [], [], True
        t = time.time()
        for r in served_reqs:
            seq = np.asarray(r["prompt"] + r["tokens"], np.int32)
            lo = len(r["prompt"]) - 1
            want = np.asarray(ref.forward(
                params, cfg_dict, seq, q_block=args.q_block, logits_from=lo,
                precision=args.reference_precision), np.float32)[:len(r["tokens"])]
            got = r["logits"].astype(np.float32)
            finite = finite and bool(np.isfinite(got).all() and np.isfinite(want).all())
            top = want.max(-1)
            spread = np.maximum(top - want.mean(-1), 1e-9)
            toks = np.asarray(r["tokens"])
            offs.append(np.abs(got - want).max(-1) / spread)
            gaps.append((top - want[np.arange(len(toks)), toks]) / spread)
            same.append(want.argmax(-1) == toks)
        given = None
        if args.given_experts and control is None:
            from llm_np_cp_tpu.models.transformer import final_logits

            g_off, p_off, s_off, flips, flips_held = [], [], [], [], []
            for r in served_reqs:
                seq = np.asarray(r["prompt"] + r["tokens"], np.int32)
                lo, n = len(r["prompt"]) - 1, len(r["tokens"])
                x, chosen = plain(params, jnp.asarray(seq)[None])
                bf16 = np.asarray(final_logits(
                    params, x[:, lo:lo + n], config)[0], np.float32)
                chosen = np.asarray(chosen)[:, 0]  # [expert layers, S, k]
                own, own_chosen = ref.forward(
                    params, cfg_dict, seq, q_block=args.q_block, logits_from=lo,
                    return_experts=True)
                want_given = np.asarray(ref.forward(
                    params, cfg_dict, seq, q_block=args.q_block, logits_from=lo,
                    experts=list(chosen)), np.float32)[:n]
                own = np.asarray(own, np.float32)[:n]
                spread = np.maximum(own.max(-1) - own.mean(-1), 1e-9)
                g_off.append(np.abs(bf16 - want_given).max(-1) / spread)
                p_off.append(np.abs(bf16 - own).max(-1) / spread)
                s_off.append(np.abs(r["logits"].astype(np.float32) - want_given
                                    ).max(-1) / spread)
                a = np.sort(chosen, -1)
                b = np.sort(np.asarray(own_chosen), -1)
                differ = (a != b).any(-1)
                flips.append(differ.mean())
                # an expert held here goes or comes: the sets of held
                # experts chosen differ
                flips_held.append(np.mean([
                    set(x_[np.isin(x_, held)]) != set(y_[np.isin(y_, held)])
                    for x_, y_ in zip(a.reshape(-1, a.shape[-1]),
                                      b.reshape(-1, b.shape[-1]))]))
            given = dict(
                off_given=summary(np.concatenate(g_off)),
                off_plain_vs_float32=summary(np.concatenate(p_off)),
                off_served_vs_given=summary(np.concatenate(s_off)),
                flip_share=float(np.mean(flips)),
                flip_share_held=float(np.mean(flips_held)))
        off, gap = np.concatenate(offs), np.concatenate(gaps)
        within = bool(finite and off.mean() <= OFF_MEAN_LIMIT
                      and np.quantile(off, 0.99) <= OFF_P99_LIMIT)
        row = dict(
            control=control, attn=args.attn, prompts=lengths, new=args.new,
            reference_precision=args.reference_precision, finite=finite,
            first_token_off=[float(o[0]) for o in offs],
            off=summary(off), gap=summary(gap),
            same_argmax=float(np.concatenate(same).mean()),
            within_limits=within,
            limits=dict(off_mean=OFF_MEAN_LIMIT, off_p99=OFF_P99_LIMIT),
            rule_correct=bool(all(v["ok"] for v in verdicts)),
            rule_worst_ratio=max(v["worst_ratio"] for v in verdicts),
            rule_p99=max(v["ratio_quantiles"][2] for v in verdicts),
            seconds=dict(serve=round(t_serve, 1), reference=round(time.time() - t, 1)))
        if given is not None:
            row["given_experts"] = given
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
