#!/usr/bin/env python3
"""How close is the SERVED path of a ``ling_hybrid`` stack to float32?  The
plain float32 reference ON THE CHIP, at the configuration's widths, against
what the unified tick itself produced on the same seeded weights - the question
``correct`` cannot ask (it ranks the served tokens under the program's own bf16
``models.forward``).

    python benchmark/parity_ling_v3.py --workload <cell> --seed <n>

makes the weights from the seed as ``run.py`` does, builds a ``ServeEngine``
with the cell's block size, chunking and dtypes and ``--slots`` slots (the
cell's by default), and serves ``slots + slots // 8`` requests with prompts
drawn over the traffic mix's range and ``--new`` answer tokens each: prefill in
chunks beside decode rows, then decode through the matrix state and the latent
pool, and - because there are more requests than slots - requests that START IN
A SLOT ANOTHER HAS LEFT, whose state they must not read.  The LOGITS every
served token was drawn from are kept (the XLA tail, wrapped with a callback)
for ``--samples`` requests, half of them from the second wave.  Per request
they are compared with ``reference_ling_v3.py`` (float32, ``highest``, the
recurrence token by token, expanded attention, routing over all experts with
the same share held) over prompt + the served tokens:

- ``off``: the largest difference of any logit at a position, as a share of
  the reference's (max - mean) spread there: mean / p99 / worst;
- ``gap``: how far the served token lies below the float32 maximum
  (``reference.py``'s gap, measured against float32).

The run FAILS (exit 1) when ``off`` passes ``OFF_MEAN_LIMIT`` /
``OFF_P99_LIMIT``.  Each ``--control`` serves the same requests with ONE
equation of the PROGRAM changed and reports the same numbers, and beside them
the verdict of ``benchmark/reference.py``'s rule (the comparison that decides
``correct``) on the control's tokens: what each comparison can and cannot see
is PERF.md section 6.  Controls: ``state_zeroed`` (every tick starts every
row's state from zero), ``fresh_ignored`` (a new request reads the state its
slot's last request left), ``no_decay``, ``no_delta`` (``u = v``),
``no_group_mask``, ``no_bias`` (the router's selection bias zeroed),
``no_shared`` (the shared expert's output zeroed), ``bf16_state`` (the matrix
state kept in bf16), ``bf16_router``.

``--given-experts`` adds, for the unbroken program, what is left of the
difference when no expert differs: the program's plain ``models.forward`` is
teacher-forced over the same tokens with every expert layer's choices, and the
float32 reference computed again GIVEN those choices (``off_given``: rounding
alone), with ``flip_share`` the (token, expert layer) pairs whose chosen
experts differ and ``flip_share_held`` those in which an expert this chip
HOLDS goes or comes.

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
# readings on the chip at the published widths, 64 slots, 72 requests of
# which 8 compared over 96 tokens (PERF.md section 6, PR 47): what the served
# path read over three seeds (mean 0.0199-0.0202, p99 0.0303-0.0309), and
# what the nearest broken program reads (the matrix state kept in bf16: mean
# 0.0276, p99 0.0392; the group mask left out 0.0406 / 0.0534, the router's
# bias 0.0472 / 0.0630; every control that touches the recurrence or the
# shared expert 0.31 or more).  A bf16 ROUTER reads what the unbroken program
# reads (0.0200 / 0.0306) and is NOT seen by these limits.  A bf16 program
# against float32 is rounding: it moves every logit a little (given the
# experts: 0.0165 / 0.0199); a wrong equation moves them by a share of the
# spread.
OFF_MEAN_LIMIT = 0.024
OFF_P99_LIMIT = 0.035

CONTROLS = ("state_zeroed", "fresh_ignored", "no_decay", "no_delta",
            "no_group_mask", "no_bias", "no_shared", "bf16_state",
            "bf16_router")


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


def slow_packed(decay: bool, delta: bool):
    """``ops/kda.kda_packed``'s contract, token by token over the packed
    axis on each token's own row of the state, with the decay or the delta
    correction left out: the two controls no argument of the real one
    expresses."""
    import jax.numpy as jnp
    from jax import lax

    def packed(state, layer, q, k, v, g, beta, *, tok_row, start, count,
               fresh, **_):
        t = q.shape[0]
        f32 = jnp.float32
        zero = jnp.int32(0)

        def token(i, carry):
            state, o = carry
            row = tok_row[i]
            at = i - start[row]
            live = (at >= 0) & (at < count[row])
            where = (layer, row, zero, zero, zero)
            s = lax.dynamic_slice(state, where, (1, 1) + state.shape[2:])[0, 0]
            s = jnp.where(fresh[row] & (at == 0), 0.0, s.astype(f32))
            s1 = s * jnp.exp(g[i])[:, :, None] if decay else s
            u = v[i] - jnp.einsum("hkv,hk->hv", s1, k[i]) if delta else v[i]
            s1 = s1 + (beta[i][:, None] * k[i])[:, :, None] * u[:, None, :]
            o_i = jnp.einsum("hkv,hk->hv", s1, q[i])
            s1 = jnp.where(live, s1, s)
            state = lax.dynamic_update_slice(
                state, s1[None, None].astype(state.dtype), where)
            return state, o.at[i].set(jnp.where(live, o_i, 0.0))

        state, o = lax.fori_loop(
            0, t, token, (state, jnp.zeros(q.shape, f32)))
        return o, state

    return packed


@contextlib.contextmanager
def broken_program(control: str | None):
    """One equation of the program changed while an engine is built and
    traced (controls that are no change of the parameters)."""
    import jax.numpy as jnp

    from llm_np_cp_tpu.ops import kda, moe

    undo = []

    def patch(mod, name, new):
        undo.append((mod, name, getattr(mod, name)))
        setattr(mod, name, new)

    real_packed = kda.kda_packed
    if control == "state_zeroed":
        patch(kda, "kda_packed", lambda *a, **kw: real_packed(
            *a, **dict(kw, fresh=kw["count"] > 0)))
    elif control == "fresh_ignored":
        patch(kda, "kda_packed", lambda *a, **kw: real_packed(
            *a, **dict(kw, fresh=jnp.zeros_like(kw["fresh"]))))
    elif control in ("no_decay", "no_delta"):
        patch(kda, "kda_packed", slow_packed(
            decay=control != "no_decay", delta=control != "no_delta"))
    elif control == "bf16_router":
        real_route = moe.route_sigmoid_topk
        patch(moe, "route_sigmoid_topk", lambda *a, **kw: real_route(
            *a, **dict(kw, score_dtype=jnp.bfloat16)))
        moe.moe_dropless.clear_cache()  # traced once a program otherwise
    try:
        yield
    finally:
        for mod, name, old in reversed(undo):
            setattr(mod, name, old)
        if control == "bf16_router":
            moe.moe_dropless.clear_cache()


def serve(params, config, spec, prompts, new: int, slots: int, keep: list[int],
          control: str | None) -> tuple[list[dict], str]:
    """The requests through a fresh engine: for the requests ``keep`` their
    tokens and the logits each was drawn from ``[new, V]`` float32; and
    which form advanced the matrix state."""
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
    _, blocks, max_seq = pool_geometry(
        max(len(p) for p in prompts), new, slots, block, prefill_chunk=chunk)
    cache_dtype = jnp.bfloat16 if serve_cfg.get(
        "cache_dtype", "bf16") == "bf16" else jnp.float32
    if control == "no_group_mask":
        config = dataclasses.replace(config, n_group=1, topk_group=1)
    ticks: list[np.ndarray] = []
    real_logits = engine_mod.final_logits

    def probed(p, x, cfg, **kw):
        logits = real_logits(p, x, cfg, **kw)
        jax.debug.callback(lambda a: ticks.append(np.asarray(a[:, 0])), logits)
        return logits

    engine_mod.final_logits = probed
    try:
        with broken_program(control):
            engine = ServeEngine(
                broken_params(params, control), config,
                sampler=Sampler(kind="greedy"), sample_epilogue="off",
                max_slots=slots, num_blocks=blocks, block_size=block,
                max_seq_len=max_seq, prefill_chunk=chunk,
                cache_dtype=cache_dtype)
            if control == "bf16_state":
                pages = engine.pool.pages
                engine.pool.pages = pages._replace(state=dict(
                    pages.state, kda=pages.state["kda"].astype(jnp.bfloat16)))
            impl = engine.kda_state_impl
            reqs = [engine.submit(p, max_new_tokens=new, seed=i)
                    for i, p in enumerate(prompts)]
            kept = {reqs[i].req_id for i in keep}
            got: dict[int, list] = {r.req_id: [] for r in reqs}
            more = True
            while more:
                before = {r.req_id: len(r.generated) for r in reqs}
                more = engine.step()
                jax.effects_barrier()
                for r in reqs:
                    if len(r.generated) > before[r.req_id] and r.req_id in kept:
                        slot = (r.slot if r.slot is not None and r.slot >= 0
                                else r.extra["_slot"])
                        got[r.req_id].append(ticks[-1][slot])
                    if r.slot is not None and r.slot >= 0:
                        r.extra["_slot"] = r.slot
                del ticks[:-1]
    finally:
        engine_mod.final_logits = real_logits
    del engine
    return [dict(index=i, prompt=list(map(int, reqs[i].prompt)),
                 tokens=list(reqs[i].generated),
                 logits=np.stack(got[reqs[i].req_id])) for i in keep], impl


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--slots", type=int, default=0, help="default: the cell's")
    ap.add_argument("--samples", type=int, default=8)
    ap.add_argument("--new", type=int, default=96,
                    help="answer tokens a request (decode ticks)")
    ap.add_argument("--control", action="append", default=[],
                    choices=CONTROLS + ("all",))
    ap.add_argument("--reference-precision", choices=("highest", "default"),
                    default="highest")
    ap.add_argument("--expert-out-std", type=float, default=None,
                    help="draw the routed experts' output projections at this "
                    "scale instead of the configuration's")
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

    import reference_ling_v3 as ref
    import run as harness
    from llm_np_cp_tpu.config import ModelConfig
    from reference import Reference

    spec = harness.load_spec(Path(args.data_root or harness.ROOT), args.workload)
    cfg_dict = dict(spec["config"])
    if args.expert_out_std is not None:
        cfg_dict["init_expert_out_std"] = args.expert_out_std
    config = ModelConfig.from_hf_dict(cfg_dict)
    served = jnp.bfloat16 if cfg_dict.get("serve", {}).get(
        "dtype", "bf16") == "bf16" else jnp.float32
    params = harness.make_weights(config, args.seed, served, False)
    slots = args.slots or spec["params"]["slots"]
    dist = spec["traffic"]["prompt_tokens"]
    rng = np.random.default_rng(args.seed)
    n_req = slots + max(slots // 8, 1)
    lengths = rng.integers(dist["min"], dist["max"] + 1, n_req).tolist()
    prompts = [rng.integers(0, config.vocab_size, n).tolist() for n in lengths]
    # half of the compared requests from the second wave (a reused slot)
    late = min(args.samples // 2, n_req - slots)
    keep = list(range(args.samples - late)) + list(range(n_req - late, n_req))
    controls = list(CONTROLS) if "all" in args.control else args.control
    rule = Reference(params, config, length=max(lengths) + args.new,
                     batch=min(4, len(keep)))
    first = cfg_dict.get("first_expert", 0)
    held = range(first, first + cfg_dict["num_experts"])

    @jax.jit
    def plain(params, ids):
        from llm_np_cp_tpu.models.transformer import forward

        x, _, aux = forward(params, ids, config, skip_logits=True,
                            output_experts=True)
        return x, aux["experts"]

    rows, ok = [], True
    for control in [None] + controls:
        t = time.time()
        served_reqs, impl = serve(params, config, spec, prompts, args.new,
                                  slots, keep, control)
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

            g_off, p_off, flips, flips_held = [], [], [], []
            for r in served_reqs:
                seq = np.asarray(r["prompt"] + r["tokens"], np.int32)
                lo, n = len(r["prompt"]) - 1, len(r["tokens"])
                # right-padded to one length (causal: what follows a position
                # cannot change it), so the plain forward compiles once
                padded = np.zeros(max(lengths) + args.new, np.int32)
                padded[:len(seq)] = seq
                x, chosen = plain(params, jnp.asarray(padded)[None])
                bf16 = np.asarray(final_logits(
                    params, x[:, lo:lo + n], config)[0], np.float32)
                chosen = np.asarray(chosen)[:, 0, :len(seq)]  # [expert layers, S, k]
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
                a = np.sort(chosen, -1)
                b = np.sort(np.asarray(own_chosen), -1)
                flips.append((a != b).any(-1).mean())
                flips_held.append(np.mean([
                    set(x_[np.isin(x_, held)]) != set(y_[np.isin(y_, held)])
                    for x_, y_ in zip(a.reshape(-1, a.shape[-1]),
                                      b.reshape(-1, b.shape[-1]))]))
            given = dict(
                off_given=summary(np.concatenate(g_off)),
                off_plain_vs_float32=summary(np.concatenate(p_off)),
                flip_share=float(np.mean(flips)),
                flip_share_held=float(np.mean(flips_held)))
        off, gap = np.concatenate(offs), np.concatenate(gaps)
        n_first = args.samples - late
        within = bool(finite and off.mean() <= OFF_MEAN_LIMIT
                      and np.quantile(off, 0.99) <= OFF_P99_LIMIT)
        row = dict(
            control=control, state_update=impl, slots=slots, requests=n_req,
            compared=keep, prompts=[len(r["prompt"]) for r in served_reqs],
            new=args.new, expert_out_std=config.init_expert_out_std,
            reference_precision=args.reference_precision, finite=finite,
            off=summary(off), gap=summary(gap),
            off_first_wave=summary(np.concatenate(offs[:n_first])),
            off_reused_slots=(summary(np.concatenate(offs[n_first:]))
                              if late else None),
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
          f"({args.reference_precision}), state update {base['state_update']}: "
          f"off mean {base['off']['mean']:.4f} p99 {base['off']['p99']:.4f} of "
          f"the spread (limits {OFF_MEAN_LIMIT:g} / {OFF_P99_LIMIT:g}); "
          "controls: " + ", ".join(
              f"{r['control']} within_limits={r['within_limits']} "
              f"rule_correct={r['rule_correct']}" for r in rows[1:]), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
