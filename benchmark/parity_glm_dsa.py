#!/usr/bin/env python3
"""How close is the SERVED path of a ``glm_moe_dsa`` cell to float32, and does it
attend the positions the float32 layer attends?  ``parity_deepseek_v3.py``'s
question (the plain float32 reference ON THE CHIP against what the unified tick
itself produced on the same seeded weights) and one more that the logits cannot
answer here: with seeded weights a softmax over thousands of positions is
nearly flat, so the logits hardly say WHICH ``index_topk`` positions a token
attended.

    python benchmark/parity_glm_dsa.py --workload <cell> --seed <n> [--selection]

makes the weights from the seed as ``run.py`` does, builds a ``ServeEngine``
with the cell's block size, chunking and dtypes (a few slots), serves
``--samples`` requests with prompts spread over the traffic mix's range and
``--new`` answer tokens each - prefill in chunks, then decode through the pool
and the three kernels (``--attn xla``: their XLA twins) - and keeps the LOGITS
every served token was drawn from.  Per request they are compared with
``reference_glm_dsa.py`` (float32, ``highest``) over prompt + the served
tokens:

- ``off``: the largest difference of any logit at a position, as a share of
  the reference's (max - mean) spread there: mean / p99 / worst;
- ``gap``: how far the served token lies below the float32 maximum.

``--selection`` also fetches, a layer and answer token, the SELECTION the
served tick made (the select kernel's mask, or the twin's, through a callback
on the decode rows) and reports

- ``select_overlap``: the share of a token's served selection that is also the
  float32 reference's, mean and least over (token, layer);
- ``select_flip_share``: the (token, layer) pairs whose two selections differ
  in any position;
- ``off_given``: ``off`` against the reference computed GIVEN the served
  selection of the answer's tokens (the prompt's tokens keep the reference's
  own: theirs are not fetched), as ``--given-experts`` does for routing.

The run FAILS (exit 1) when ``off`` passes ``OFF_MEAN_LIMIT`` /
``OFF_P99_LIMIT``, ``off_given``'s mean passes ``OFF_GIVEN_MEAN_LIMIT`` or
``select_overlap``'s mean falls under ``SELECT_OVERLAP_LIMIT``.  Each ``--control`` serves the same requests with ONE
equation of the PROGRAM changed and reports the same numbers, and beside them
the verdict of ``benchmark/reference.py``'s rule (the comparison that decides
``correct``) on the control's tokens: ``recent_2048`` (the last ``index_topk``
positions instead of the best), ``no_index_rope``, ``index_weights_one``,
``dense`` (no selection), ``no_q_a_layernorm``, ``halfsplit_rope``.  A control
must FAIL a limit; what each comparison can and cannot see is PERF.md
section 6.

A builder's diagnostic: not a metric, not part of ``correct``; writes
``benchmark/out/<cell>-<seed>.parity.json``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

from parity_deepseek_v3 import summary  # noqa: E402

# Limits between the two readings on the chip at the published widths (PERF.md
# section 6, PR 58; ONE seed, 3800000019: prompts 8,192 / 7,168 / 6,144, 31
# decode tokens each, 465 (token, layer) selections): what the served path
# read (off mean 0.099, p99 0.138; select_overlap mean 0.943, least 0.873;
# off_given mean 0.0138) and what the nearest control reads (no_q_a_layernorm:
# off mean 0.654, p99 0.831, overlap 0.623; the lowest off_given of a control
# whose selection is the broken part is no_index_rope's 0.055).  Unlike a dense
# layer's, these logits DO say which positions were attended: a served token's
# selection differs from float32's in 5.7 % of its 2,048 positions (bf16 scores
# against float32 at the threshold), and that alone moves the logits by a
# tenth of their spread; given the served selection what is left is rounding.
OFF_MEAN_LIMIT = 0.25
OFF_P99_LIMIT = 0.35
OFF_GIVEN_MEAN_LIMIT = 0.03
SELECT_OVERLAP_LIMIT = 0.8

CONTROLS = ("recent_2048", "no_index_rope", "index_weights_one", "dense",
            "no_q_a_layernorm", "halfsplit_rope")


@contextlib.contextmanager
def broken_program(control: str | None, config, fetch=None):
    """One equation of the program changed while an engine is built and traced;
    ``fetch(mask [tiles or tokens, S], row, slot, qlen)``: the served selection
    of the tiles / tokens of a dispatch, a layer a call."""
    import jax
    import jax.numpy as jnp

    from llm_np_cp_tpu.models import transformer
    from llm_np_cp_tpu.ops import sparse_index
    from llm_np_cp_tpu.ops.pallas import sparse_index as kernels

    undo = []

    def patch(mod, name, new):
        undo.append((mod, name, getattr(mod, name)))
        setattr(mod, name, new)

    scores_k, scores_x = kernels.ragged_index_scores, kernels.ragged_index_scores_xla
    if control == "recent_2048":
        # a score that grows with the position: the best are the latest
        def recent(real):
            def scores(*a, **kw):
                out = real(*a, **kw)
                return jnp.broadcast_to(
                    jnp.arange(out.shape[-1], dtype=out.dtype), out.shape)
            return scores

        patch(kernels, "ragged_index_scores", recent(scores_k))
        patch(kernels, "ragged_index_scores_xla", recent(scores_x))
    elif control == "index_weights_one":
        def ones(real):
            return lambda q, w, *a, **kw: real(q, jnp.ones_like(w), *a, **kw)

        patch(kernels, "ragged_index_scores", ones(scores_k))
        patch(kernels, "ragged_index_scores_xla", ones(scores_x))
    elif control == "no_index_rope":
        patch(sparse_index, "rope_leading", lambda x, cos, sin, **kw: x)
    elif control == "no_q_a_layernorm":
        real_norm = transformer.rms_norm
        patch(transformer, "rms_norm", lambda x, w, **kw: (
            x if x.shape[-1] == config.q_lora_rank else real_norm(x, w, **kw)))
    if fetch is not None:
        real_tiles, real_xla = kernels.select_topk_tiles, kernels.select_xla

        def tiles(scores, tables, tile_row, tile_qpos0, tile_qlen, *a, **kw):
            mask = real_tiles(scores, tables, tile_row, tile_qpos0, tile_qlen,
                              *a, **kw)
            # a decode row's tile holds its one token in lane 0
            jax.debug.callback(fetch, mask[:, 0] > 0.5, tile_row, tile_qpos0,
                               tile_qlen)
            return mask

        def twin(scores, tok_slot, tok_live, tok_pad, topk):
            mask = real_xla(scores, tok_slot, tok_live, tok_pad, topk)
            # (the twin's selection has no row beside it: a token's cache slot
            # names it, where the requests' slots differ)
            jax.debug.callback(fetch, mask, tok_slot, tok_slot,
                               tok_live.astype(jnp.int32))
            return mask

        patch(kernels, "select_topk_tiles", tiles)
        patch(kernels, "select_xla", twin)
    try:
        yield
    finally:
        for mod, name, old in reversed(undo):
            setattr(mod, name, old)


def serve(params, config, spec, prompts, new: int, attn: str,
          control: str | None, selection: bool) -> list[dict]:
    """The requests through a fresh engine: per request its tokens, the logits
    each was drawn from ``[new, V]`` float32 and (``selection``) the positions
    each answer token attended a layer ``{position: [layers, S] bool}``."""
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
    if control == "halfsplit_rope":
        config = dataclasses.replace(config, rope_interleave=False)
    if control == "dense":
        config = dataclasses.replace(config, index_topk=1 << 24)
    ticks: list[np.ndarray] = []
    layer_calls: list[tuple] = []  # this dispatch's fetches, in layer order
    real_logits = engine_mod.final_logits

    def probed(p, x, cfg, **kw):
        logits = real_logits(p, x, cfg, **kw)
        jax.debug.callback(lambda a: ticks.append(np.asarray(a)), logits)
        return logits

    def fetch(mask, row, slot, qlen):
        layer_calls.append(tuple(np.asarray(a) for a in (mask, row, slot, qlen)))

    engine_mod.final_logits = probed
    picked: dict[int, dict[int, list]] = {}
    try:
        with broken_program(control, config, fetch if selection else None):
            engine = ServeEngine(
                params, config, sampler=Sampler(kind="greedy"),
                sample_epilogue="off", max_slots=slots, num_blocks=blocks,
                block_size=block, max_seq_len=max_seq, prefill_chunk=chunk,
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
                before = {r.req_id: (len(r.generated), r.cache_len)
                          for r in reqs}
                layer_calls.clear()
                more = engine.step()
                jax.effects_barrier()
                for r in reqs:
                    n0, slots0 = before[r.req_id]
                    if len(r.generated) > n0:
                        slot = (r.slot if r.slot is not None and r.slot >= 0
                                else r.extra["_slot"])
                        got[r.req_id].append(ticks[-1][slot, 0])
                        # a DECODE tick fed this row's last token, which lies
                        # in cache slot ``slots0 - 1`` (left pad included):
                        # its selection, layer by layer, by position
                        if selection and n0 >= 1 and len(layer_calls) == (
                                config.num_hidden_layers):
                            # a tile's columns start at its pad's block
                            first = r.pad if attn == "xla" else r.pad % block
                            rows = []
                            for mask, row, at, qlen in layer_calls:
                                hit = np.flatnonzero(
                                    (qlen == 1) & (at == slots0 - 1)
                                    & ((row == slot) | (attn == "xla")))
                                if len(hit) == 1:
                                    rows.append(mask[hit[0], first:])
                            if len(rows) == config.num_hidden_layers:
                                picked.setdefault(r.req_id, {})[
                                    slots0 - 1 - r.pad] = rows
                    if r.slot is not None and r.slot >= 0:
                        r.extra["_slot"] = r.slot
    finally:
        engine_mod.final_logits = real_logits
    out = [dict(prompt=list(map(int, r.prompt)), tokens=list(r.generated),
                logits=np.stack(got[r.req_id]),
                picked=picked.get(r.req_id, {})) for r in reqs]
    # the engine's pool and the weight leaves it laid out anew go before the
    # reference's float32 copies come
    del engine, reqs
    gc.collect()
    return out


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
    ap.add_argument("--selection", action="store_true",
                    help="also: the served selection against the float32 "
                    "reference's, and the reference given the served one")
    ap.add_argument("--q-block", type=int, default=256)
    ap.add_argument("--data-root", default=None,
                    help="directory that holds BENCHMARK.json (tests: a copy)")
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np

    import reference_glm_dsa as ref
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
    rows, ok = [], True
    for control in [None] + controls:
        t = time.time()
        served_reqs = serve(params, config, spec, prompts, args.new, args.attn,
                            control, args.selection)
        t_serve = time.time() - t
        verdicts = rule.check([(r["prompt"], r["tokens"]) for r in served_reqs])
        offs, gaps, same, finite = [], [], [], True
        overlaps, flips, g_offs = [], [], []
        t = time.time()
        for r in served_reqs:
            seq = np.asarray(r["prompt"] + r["tokens"], np.int32)
            lo, n = len(r["prompt"]) - 1, len(r["tokens"])
            out = ref.forward(
                params, cfg_dict, seq, q_block=args.q_block, logits_from=lo,
                precision=args.reference_precision,
                return_selection=args.selection)
            want, theirs = out if args.selection else (out, None)
            want = np.asarray(want, np.float32)[:n]
            got = r["logits"].astype(np.float32)
            finite = finite and bool(np.isfinite(got).all() and np.isfinite(want).all())
            top = want.max(-1)
            spread = np.maximum(top - want.mean(-1), 1e-9)
            toks = np.asarray(r["tokens"])
            offs.append(np.abs(got - want).max(-1) / spread)
            gaps.append((top - want[np.arange(n), toks]) / spread)
            same.append(want.argmax(-1) == toks)
            if args.selection and r["picked"]:
                theirs = np.asarray(theirs)  # [layers, S - lo, S]
                forced = theirs.copy()
                for pos, masks in r["picked"].items():
                    mine = np.stack(masks)[:, :len(seq)]  # [layers, S]
                    both = (mine & theirs[:, pos - lo]).sum(-1)
                    overlaps.append(both / np.maximum(mine.sum(-1), 1))
                    flips.append((mine != theirs[:, pos - lo]).any(-1))
                    forced[:, pos - lo] = mine
                given = np.asarray(ref.forward(
                    params, cfg_dict, seq, q_block=args.q_block, logits_from=lo,
                    precision=args.reference_precision,
                    selections=list(forced)), np.float32)[:n]
                g_offs.append(np.abs(got - given).max(-1) / spread)
        off, gap = np.concatenate(offs), np.concatenate(gaps)
        within = bool(finite and off.mean() <= OFF_MEAN_LIMIT
                      and np.quantile(off, 0.99) <= OFF_P99_LIMIT)
        row = dict(
            control=control, attn=args.attn, prompts=lengths, new=args.new,
            reference_precision=args.reference_precision, finite=finite,
            first_token_off=[float(o[0]) for o in offs],
            off=summary(off), gap=summary(gap),
            same_argmax=float(np.concatenate(same).mean()),
            limits=dict(off_mean=OFF_MEAN_LIMIT, off_p99=OFF_P99_LIMIT,
                        off_given_mean=OFF_GIVEN_MEAN_LIMIT,
                        select_overlap=SELECT_OVERLAP_LIMIT),
            rule_correct=bool(all(v["ok"] for v in verdicts)),
            rule_worst_ratio=max(v["worst_ratio"] for v in verdicts),
            rule_p99=max(v["ratio_quantiles"][2] for v in verdicts),
            seconds=dict(serve=round(t_serve, 1), reference=round(time.time() - t, 1)))
        if overlaps:
            overlap = np.concatenate(overlaps)
            row.update(
                select_overlap=dict(mean=float(overlap.mean()),
                                    least=float(overlap.min()),
                                    tokens_x_layers=int(overlap.size)),
                select_flip_share=float(np.concatenate(flips).mean()),
                off_given=summary(np.concatenate(g_offs)))
            within = within and bool(
                overlap.mean() >= SELECT_OVERLAP_LIMIT
                and row["off_given"]["mean"] <= OFF_GIVEN_MEAN_LIMIT)
        row["within_limits"] = within
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
          f"{OFF_P99_LIMIT:g})"
          + (f", select_overlap mean {base['select_overlap']['mean']:.4f} "
             f"(limit {SELECT_OVERLAP_LIMIT:g})" if "select_overlap" in base else "")
          + "; controls: " + ", ".join(
              f"{r['control']} within_limits={r['within_limits']} "
              f"rule_correct={r['rule_correct']}" for r in rows[1:]), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
