#!/usr/bin/env python3
"""How close is the SERVED path to float32?  The plain float32 reference ON
THE CHIP, at the configuration's widths, against what the unified tick itself
produced on the same seeded weights - the question ``correct`` cannot ask (it
ranks the served tokens under the program's own bf16 ``models.forward``).

    python benchmark/parity_mimo_v2.py --workload <cell> --seed <n>

makes the weights from the seed as ``run.py`` does, builds a ``ServeEngine``
with the cell's block size, chunking, tick budget and dtypes (a few slots:
the reference needs the room the cell's pool takes), serves ``--samples``
requests with prompts spread over the traffic mix's range and ``--new`` answer
tokens each - prefill in slices of a chunk, then decode, through BOTH page
classes (a 4,096-token prompt sends its window chain round its ring of five
blocks a dozen times), the Pallas kernel (``--attn xla``: its XLA twin) - and
keeps the LOGITS every served token was drawn from (the XLA tail, wrapped
with a callback).  Per request they are compared with
``reference_mimo_v2.py`` (float32, ``highest``, attention in query blocks,
routing over all 256 experts with the same share held) over prompt + the
served tokens:

- ``off``: the largest difference of any logit at a position, as a share of
  the reference's (max - mean) spread there: mean / p99 / worst;
- ``gap``: how far the served token lies below the float32 maximum
  (``reference.py``'s gap, measured against float32).

The run FAILS (exit 1) when ``off`` passes ``OFF_MEAN_LIMIT`` /
``OFF_P99_LIMIT``.  Each ``--control`` serves the same requests with ONE
equation of the PROGRAM changed and reports the same numbers, and beside them
the verdict of ``benchmark/reference.py``'s rule (the comparison that decides
``correct``) on the control's tokens.  Controls, each of which must come out
NOT within the limits: ``no_sink`` (the window layers' sink logits at -1e30),
``window_off_by_one`` (a window of 129), ``no_value_scale``, ``rotate_all``
(all 192 columns rotated), ``recycled_early`` (a window block let go one tick
before its last reader); ``--reference-precision default`` runs the float32
reference at the default matmul precision instead (bf16 operands on a TPU:
what the served path computes in anyway), ``--reference-precision fp8`` on
weights rounded to float8 (e4m3) first: the nearest precision below the bf16
the configuration states, which has to come out NOT within the limits.

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
# readings on the chip at the published widths (PERF.md section 6, PR 43):
# what the served path read over three seeds (mean 0.0135-0.0151, p99
# 0.048-0.061), and what the nearest broken program reads (a window of 129:
# mean 0.0301, p99 0.072; a window block recycled a tick early 0.064 / 0.105;
# every other control 0.18 or more) and the reference on weights rounded to
# float8 (0.206 / 0.256).  A bf16 program against float32 is rounding: it moves
# every logit a little; a wrong equation moves them by a share of the spread.
# The mean is the steadier of the two (a p99 of 192 tokens is its second
# largest): the window of 129 fails by the mean alone.
OFF_MEAN_LIMIT = 0.022
OFF_P99_LIMIT = 0.08

CONTROLS = ("no_sink", "window_off_by_one", "no_value_scale", "rotate_all",
            "recycled_early")


def summary(x) -> dict:
    import numpy as np

    return dict(mean=float(np.mean(x)), p99=float(np.quantile(x, 0.99)),
                worst=float(np.max(x)))


def broken_params(params: dict, control: str | None) -> dict:
    """``params`` with every window layer's sink out of reach of any score
    (same shapes: exp(-1e30 - m) is 0)."""
    import jax.numpy as jnp

    if control != "no_sink":
        return params
    return dict(params, layers=[
        {k: (jnp.full_like(v, -1e30) if k == "attn_sink" else v)
         for k, v in g.items()} for g in params["layers"]])


def broken_config(config, control: str | None):
    """One number of the layer declaration changed."""
    change = {
        "window_off_by_one": dict(sliding_window=config.sliding_window + 1),
        "no_value_scale": dict(attention_value_scale=1.0),
        "rotate_all": dict(rope_dim=config.head_dim),
    }.get(control)
    return dataclasses.replace(config, **change) if change else config


@contextlib.contextmanager
def broken_program(control: str | None):
    """The allocator's rule changed while an engine serves: a ring that lets
    a block go while this tick's first query still sees it."""
    import numpy as np

    from llm_np_cp_tpu.serve.block_pool import WindowRings

    real = WindowRings.advance

    def early(self, slot, start, n):
        got = real(self, slot, start, n)
        first = np.maximum(np.asarray(start) + n - self.window + 1, 0) // self.block_size
        self.first[slot] = np.minimum(np.maximum(first, self.first[slot]),
                                      self.end[slot] - 1)
        return got

    if control == "recycled_early":
        WindowRings.advance = early
    try:
        yield
    finally:
        WindowRings.advance = real


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
                broken_params(params, control), config,
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
    ap.add_argument("--samples", type=int, default=4)
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

    import reference_mimo_v2 as ref
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
        served_reqs = serve(params, config, spec, prompts, args.new, args.attn,
                            control)
        t_serve = time.time() - t
        verdicts = rule.check([(r["prompt"], r["tokens"]) for r in served_reqs])
        offs, gaps, same, finite = [], [], [], True
        t = time.time()
        if args.reference_precision == "fp8":
            ref_params = jax.tree.map(to_float8, params)
        for r in served_reqs:
            seq = np.asarray(r["prompt"] + r["tokens"], np.int32)
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
