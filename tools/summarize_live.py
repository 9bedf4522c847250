"""Compact table view of a bench live-capture artifact.

Usage: python tools/summarize_live.py BENCH_TPU_LIVE_r5.json

Prints decode/prefill/spec/ragged rows with their headline fields and
the A/B deltas the round cares about (kernel vs XLA twin, quant modes vs
bf16 anchor, spec vs plain), so a capture can be read at a glance.
"""

from __future__ import annotations

import json
import sys

# (experiment row, its baseline twin) — positive delta = experiment wins
TWINS = [
    ("llama1b_bs8_fdec", "llama1b_bs8"),
    ("llama1b_bs8_fdec_kvq8", "llama1b_bs8"),
    ("llama1b_bs8_unroll2", "llama1b_bs8"),
    ("int8_bs8", "llama1b_bs8"),
    ("int8a8_bs8", "int8_bs8"),
    ("int4_bs8", "int8_bs8"),
    ("int4a8_bs8", "int4_bs8"),
    ("ragged_bs8_fdec", "ragged_bs8_xla"),
    ("prefill8k_flash", "prefill8k_xla"),
    ("prefill8k_chunked", "prefill8k_xla"),
    ("spec_int4_bs1_g2", "llama1b_bs1"),
    ("spec_int4_bs1_g4", "llama1b_bs1"),
    ("spec_trunc8_bs1_g4", "llama1b_bs1"),
    ("int8_spec_bs8", "llama1b_bs8"),
]


def _rate(row: dict) -> float | None:
    for k in ("decode_tok_s_chip", "decode_tok_s_chip_marginal",
              "decode_tok_s_chip_e2e", "prefill_tok_s"):
        if k in row:
            return row[k]
    return None


def main() -> None:
    path = sys.argv[1] if len(sys.argv) > 1 else "BENCH_TPU_LIVE_r5.json"
    with open(path) as f:
        art = json.load(f)
    detail = art.get("detail", {})
    print(f"headline: {art.get('value')} tok/s/chip "
          f"(vs_baseline {art.get('vs_baseline')})")
    if art.get("error"):
        print(f"error: {art['error'][:100]}")
    print(f"{'config':26} {'tok/s':>9} {'roofline':>9} {'ttft':>8}  extra")
    for name, row in detail.items():
        if not isinstance(row, dict) or name in (
            "probe", "warm", "kernels", "quality", "merge_provenance",
            "prior_capture",
        ):
            continue
        if not row.get("ok"):
            print(f"{name:26} {'FAIL':>9}  {str(row.get('error'))[:50]}")
            continue
        rate = _rate(row)
        roof = row.get("hbm_roofline_frac")
        extras = []
        for k in ("mfu", "acceptance_rate", "decode_tok_s_chip_marginal",
                  "kernel_downgraded_to_xla"):
            if k in row and rate != row.get(k):
                extras.append(f"{k}={row[k]}")
        print(
            f"{name:26} {rate if rate is not None else '':>9} "
            f"{roof if roof is not None else '':>9} "
            f"{row.get('ttft_s_p50', ''):>8}  {' '.join(extras)[:48]}"
        )
    print("\nA/B deltas (experiment vs twin, + = experiment wins):")
    for exp, base in TWINS:
        a, b = detail.get(exp, {}), detail.get(base, {})
        ra, rb = _rate(a) if a.get("ok") else None, _rate(b) if b.get("ok") else None
        if ra and rb:
            print(f"  {exp:26} {ra:>9.1f} vs {base:20} {rb:>9.1f}  "
                  f"{(ra / rb - 1) * 100:+6.1f}%")
    if "kernels" in detail:
        k = detail["kernels"]
        verdicts = {
            n: v for n, v in k.items()
            if n not in ("config", "ok", "backend", "total_s")
        }
        print(f"\nkernels ({k.get('backend')}): {verdicts}")
    if "decomp" in detail and detail["decomp"].get("ok"):
        d = detail["decomp"]
        print("\ndecomp (fixed vs per-layer ms):")
        for mode in ("bf16", "int8", "int8_a8"):
            if mode in d:
                print(f"  {mode}: {d[mode]}")
        print(f"  lm_head_ms: {d.get('lm_head_ms')}")


if __name__ == "__main__":
    main()
