"""The comparison that decides ``correct``: a plain forward of the same weights.

``models.forward`` jitted as it stands (XLA attention, no Pallas kernel,
no cache, no batching tricks), teacher-forced over prompt + the server's
OWN tokens: position ``p`` of the sequence predicts token ``p + 1``, so
one forward ranks every token the server chose under the reference's
logits for the same context.  A token's *gap* is (reference maximum -
reference logit of the server's token) as a share of the reference's
(max - mean) logit spread at that position: 0 when the server chose the
reference's own argmax.

Why a gap rule and not bit-equality (measured on the chip, PR 21,
PERF.md section 6): in bf16 the packed width of a tick changes with what
else is in it, a matmul rounds differently per width, and with random
weights the top two of 151,936 logits are often a few bf16 ulps apart -
two identical greedy requests in one run diverged at token 63 of 64,
0.0001 apart under the plain forward.

What the gaps look like (my chip runs, PR 23): of about 4,000 served
tokens per run 89 % were the plain forward's exact argmax, the mean gap
was 0.10-0.11 % of the spread, a request's 99th percentile 2.6-2.7 % and
the worst token 4.1-4.4 % (Qwen2.5-1.5B, three runs) and 8.7 %
(Qwen2.5-3B, 36 layers, one run) - the far tail of rounding noise over
thousands of tokens, where chip_smoke.py's 5 % rule had only ever looked
at first tokens.  A kernel that computes garbage, a stale cache block or
another request's K/V puts tokens near 100 % of the spread down.  So the
rule reads the distribution, with room over what was measured: every
logit finite, each request's 99th-percentile gap within
``LOGIT_GAP_P99`` of the spread, and NO token further than
``LOGIT_GAP_LIMIT`` below the maximum.  The share within the 5 %
near-tie tolerance is reported beside them.  What the rule cannot do:
an int8 K/V cache passed it with the same numbers (mean 0.104 %, worst
4.4 %), so it separates wrong from right, not bf16 from int8 K/V.

Every sequence is right-padded to one length (causal attention: what
follows a position cannot change it), so a cell costs ONE compile.
"""

from __future__ import annotations

LOGIT_GAP_TOLERANCE = 0.05
LOGIT_GAP_P99 = 0.10
LOGIT_GAP_LIMIT = 0.25


class Reference:
    def __init__(self, params, config, length: int, batch: int) -> None:
        import jax
        import jax.numpy as jnp
        from jax import lax

        from llm_np_cp_tpu.models.transformer import final_logits, forward

        self.params, self.length, self.batch = params, length, batch

        @jax.jit
        def gaps(params, ids):
            x, _ = forward(params, ids, config, skip_logits=True)
            nxt = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1)

            def one(args):
                row, tok = args
                logits = final_logits(params, row[None], config)[0]
                top = logits.max(axis=-1)
                chosen = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
                return top - chosen, top - logits.mean(axis=-1), \
                    jnp.isfinite(logits).all(axis=-1)

            return lax.map(one, (x, nxt))

        self._gaps = gaps

    def check(self, samples: list[tuple[list[int], list[int]]]) -> list[dict]:
        """For each (prompt, served tokens): the worst position."""
        import numpy as np

        out = []
        for i in range(0, len(samples), self.batch):
            chunk = samples[i:i + self.batch]
            ids = np.zeros((self.batch, self.length), np.int32)
            for j, (prompt, tokens) in enumerate(chunk):
                seq = list(prompt) + list(tokens)
                ids[j, :len(seq)] = seq
            gap, spread, finite = (np.asarray(a) for a in self._gaps(self.params, ids))
            for j, (prompt, tokens) in enumerate(chunk):
                lo, hi = len(prompt) - 1, len(prompt) + len(tokens) - 1
                ratio = gap[j, lo:hi] / np.maximum(spread[j, lo:hi], 1e-9)
                worst = int(ratio.argmax())
                near = float((ratio <= LOGIT_GAP_TOLERANCE).mean())
                out.append(dict(
                    prompt_len=len(prompt), tokens=len(tokens),
                    finite=bool(finite[j, lo:hi].all()),
                    worst_token=worst, worst_gap=float(gap[j, lo + worst]),
                    spread=float(spread[j, lo + worst]),
                    worst_ratio=float(ratio[worst]),
                    exact_argmax=int((gap[j, lo:hi] == 0).sum()),
                    near_tie_share=near, mean_ratio=float(ratio.mean()),
                    ratio_quantiles=[float(np.quantile(ratio, q))
                                     for q in (0.5, 0.9, 0.99, 0.999)],
                    ok=bool(finite[j, lo:hi].all()
                            and np.quantile(ratio, 0.99) <= LOGIT_GAP_P99
                            and ratio[worst] <= LOGIT_GAP_LIMIT)))
        return out
