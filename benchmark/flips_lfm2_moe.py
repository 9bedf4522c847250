#!/usr/bin/env python3
"""Expert flips or an error?  The float32 reference ON THE CHIP, at the
published widths, against what a traced run of an ``lfm2_moe`` cell served.

    python benchmark/flips_lfm2_moe.py --workload <cell> --seed <n>

reads ``benchmark/out/<cell>-<seed>/client.json`` (a ``--trace 1`` run of
``run.py`` with the same seed keeps it), takes the requests ``run.py`` samples
for its own check (the longest prompt among them), makes the weights from the
seed again and, for each request, teacher-forces prompt + the server's own
tokens through three forwards:

- ``bf16``: ``models.forward`` as ``benchmark/reference.py`` runs it (the
  comparison that decides ``correct``), with every expert layer's choices;
- ``f32``: ``reference_lfm2_moe.forward``, float32, its own choices;
- ``f32 | bf16's experts``: the same reference GIVEN the bf16 forward's
  choices - what is left of the difference when no expert differs.

Per request it prints the gap distribution ``reference.py`` reports (the
served token's distance below the maximum, as a share of the logit spread)
under each forward, the share of (token, expert layer) pairs whose chosen
experts differ between ``bf16`` and ``f32``, and how far the bf16 logits are
from the float32 ones with and without the same experts.  A builder's
diagnostic: not a metric, not part of ``correct``, writes
``benchmark/out/<cell>-<seed>.flips.json``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def quantiles(x) -> list[float]:
    import numpy as np

    return [float(np.quantile(x, q)) for q in (0.5, 0.9, 0.99, 0.999, 1.0)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--samples", type=int, default=8)
    ap.add_argument("--data-root", default=None,
                    help="directory that holds BENCHMARK.json (tests: a copy)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference_lfm2_moe as ref
    import run as harness
    import traffic as traffic_mod
    from llm_np_cp_tpu.config import ModelConfig
    from llm_np_cp_tpu.models.transformer import forward

    spec = harness.load_spec(Path(args.data_root or harness.ROOT), args.workload)
    cfg_dict = spec["config"]
    config = ModelConfig.from_hf_dict(cfg_dict)
    out_dir = spec["dir"] / "out"
    with open(out_dir / f"{args.workload}-{args.seed}" / "client.json") as f:
        rec = json.load(f)
    picked = harness.reference_samples(rec, args.seed)[:args.samples]
    table = traffic_mod.request_table(
        spec["traffic"], args.seed, max(r["idx"] for r in picked) + 1, config.vocab_size)
    served = spec["config"].get("serve", {}).get("dtype", "bf16")
    params = harness.make_weights(
        config, args.seed, jnp.bfloat16 if served == "bf16" else jnp.float32, False)
    p_max, m_max = traffic_mod.limits(spec["traffic"])
    length = p_max + m_max  # one shape, one compile; causal: the tail changes nothing

    @jax.jit
    def bf16_forward(params, ids):
        logits, _, aux = forward(params, ids, config, output_experts=True)
        return logits[0], aux["experts"][:, 0]

    def gaps(logits, seq, lo, hi):
        """reference.py's numbers for positions lo..hi-1 predicting seq[lo+1..hi]."""
        lg = np.asarray(logits[lo:hi], np.float32)
        tok = np.asarray(seq[lo + 1:hi + 1])
        top = lg.max(-1)
        ratio = (top - lg[np.arange(hi - lo), tok]) / np.maximum(top - lg.mean(-1), 1e-9)
        return dict(quantiles=quantiles(ratio), mean=float(ratio.mean()),
                    exact=int((ratio == 0).sum()), tokens=int(hi - lo))

    report = []
    for r in picked:
        prompt, tokens = table[r["idx"]]["prompt"], r["tokens"]
        seq = list(prompt) + list(tokens)
        ids = np.zeros(length, np.int32)
        ids[:len(seq)] = seq
        lo, hi = len(prompt) - 1, len(seq) - 1
        lg_b, ex_b = bf16_forward(params, ids[None])
        lg_f, ex_f = ref.forward(params, cfg_dict, ids, return_experts=True)
        lg_g = ref.forward(params, cfg_dict, ids, experts=list(ex_b))
        ex_b, ex_f = np.sort(np.asarray(ex_b), -1), np.sort(np.asarray(ex_f), -1)
        differ = (ex_b != ex_f).any(-1)[:, :len(seq)]  # [expert layers, S]
        served, f = np.asarray(lg_b[lo:hi], np.float32), np.asarray(lg_f[lo:hi], np.float32)
        spread = f.max(-1) - f.mean(-1)
        off_own = np.abs(served - f).max(-1) / spread
        off_given = np.abs(served - np.asarray(lg_g[lo:hi], np.float32)).max(-1) / spread
        row = dict(
            idx=r["idx"], prompt_len=len(prompt), tokens=len(tokens),
            served_under_bf16=gaps(lg_b, seq, lo, hi),
            served_under_f32=gaps(lg_f, seq, lo, hi),
            served_under_f32_given_bf16_experts=gaps(lg_g, seq, lo, hi),
            flip_share=float(differ.mean()),
            flip_share_by_layer=[float(x) for x in differ.mean(-1)],
            tokens_with_a_flip=float(differ.any(0).mean()),
            bf16_vs_f32_logits=quantiles(off_own),
            bf16_vs_f32_given_experts_logits=quantiles(off_given),
        )
        report.append(row)
        print(f"[flips] request {r['idx']}: prompt {len(prompt)}, {len(tokens)} tokens; "
              f"(token, layer) pairs whose experts differ bf16 / f32: {row['flip_share']:.2%}, "
              f"tokens with one or more: {row['tokens_with_a_flip']:.1%}; served tokens' gap "
              f"p99 / worst under bf16 {row['served_under_bf16']['quantiles'][2]:.2%} / "
              f"{row['served_under_bf16']['quantiles'][4]:.2%}, under f32 "
              f"{row['served_under_f32']['quantiles'][2]:.2%} / "
              f"{row['served_under_f32']['quantiles'][4]:.2%}, under f32 given bf16's experts "
              f"{row['served_under_f32_given_bf16_experts']['quantiles'][2]:.2%} / "
              f"{row['served_under_f32_given_bf16_experts']['quantiles'][4]:.2%}; largest logit "
              f"difference bf16 - f32 (p50 / p99 / worst, of the spread) "
              f"{row['bf16_vs_f32_logits'][0]:.2%} / {row['bf16_vs_f32_logits'][2]:.2%} / "
              f"{row['bf16_vs_f32_logits'][4]:.2%}, given the same experts "
              f"{row['bf16_vs_f32_given_experts_logits'][0]:.2%} / "
              f"{row['bf16_vs_f32_given_experts_logits'][2]:.2%} / "
              f"{row['bf16_vs_f32_given_experts_logits'][4]:.2%}", flush=True)
    (out_dir / f"{args.workload}-{args.seed}.flips.json").write_text(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
