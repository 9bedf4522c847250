"""Speculative decoding: draft-and-verify autoregressive generation.

Framework extension (the reference decodes strictly one token per forward,
llama3.2_model.py:865-902).  A cheap *draft* model proposes γ tokens
autoregressively; the *target* model scores all of them in ONE forward
(prefill-shaped, MXU-friendly); accepted prefixes keep the target's exact
output distribution via the Leviathan et al. accept/resample rule:

    accept dᵢ with prob min(1, p(dᵢ)/q(dᵢ));
    on first rejection resample from norm(max(p − q, 0));
    if all γ accepted, sample a bonus token from p — so every round emits
    between 1 and γ+1 tokens and the sampled distribution is *identical*
    to decoding with the target alone (greedy: byte-identical output).

TPU-native shape: one jitted ``spec_round`` per (γ, sampler) — the draft
loop is a ``lax.scan``, verification is a single γ+1-token forward, and
rejected tokens are rolled back with ``cache.truncate`` (an O(1) bitmap
mask — the preallocated cache never moves).  p and q are the *filtered*
sampler distributions (``Sampler.filtered_logits``), so min-p/top-k/top-p
speculation is exact too, not just plain-softmax sampling.

The default draft is the int8-quantized target (quant.py) — "self
speculation": no second checkpoint, ~2× cheaper per draft step, and
high acceptance because the quantized model rarely disagrees with bf16.
A genuinely smaller draft model can be passed explicitly.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from llm_np_cp_tpu.cache import KVCache, align_capacity, truncate
from llm_np_cp_tpu.config import ModelConfig
from llm_np_cp_tpu.generate import _check_capacity, make_prefill_fn
from llm_np_cp_tpu.models.transformer import forward
from llm_np_cp_tpu.ops.sampling import Sampler

Params = dict[str, Any]


@dataclasses.dataclass
class SpecResult:
    tokens: np.ndarray  # [num_generated] (1-D prompt) or [B, num_generated]
    ttft_s: float
    decode_tokens_per_s: float  # aggregate over rows (== per-seq at bs=1)
    num_generated: int
    rounds: int
    acceptance_rate: float  # accepted draft tokens / proposed (active rows)
    tokens_per_round: float  # mean per active row


def truncated_draft(
    params: Params,
    config: ModelConfig,
    num_layers: int,
    *,
    bits: int | None = None,
) -> tuple[Params, ModelConfig]:
    """Layer-skip self-draft: the first ``num_layers`` decoder layers of
    the target plus its embedding / final norm / head, optionally
    quantized to ``bits``.

    No second checkpoint needed (the draft IS a prefix of the target, so
    vocab/tokenizer match by construction) and the draft's weight stream
    shrinks with the layer count — at 8/16 layers + int4 the draft step
    streams ~1/6 of the bf16 target.  Draft quality is what it is (the
    early layers were never trained to feed the head directly); the
    accept/resample rule keeps the OUTPUT distribution exactly the
    target's regardless, so a weak draft costs speed only, never
    correctness.  (Framework extension — the reference has no
    speculation at all, llama3.2_model.py:865-902.)
    """
    if not 0 < num_layers <= config.num_hidden_layers:
        raise ValueError(
            f"num_layers must be in 1..{config.num_hidden_layers}, got {num_layers}"
        )
    draft = dict(params)
    # stacked [L, ...] leaves: keep the first num_layers of each
    draft["layers"] = jax.tree.map(lambda x: x[:num_layers], params["layers"])
    draft_config = dataclasses.replace(config, num_hidden_layers=num_layers)
    if bits is not None:
        from llm_np_cp_tpu.quant import quantize_params

        draft = quantize_params(draft, bits=bits)
    return draft, draft_config


def _as_rows(length: jnp.ndarray, batch: int) -> jnp.ndarray:
    """Cache length as per-row [B] (broadcasting a scalar on first use)."""
    length = jnp.asarray(length, jnp.int32)
    return jnp.broadcast_to(length, (batch,)) if length.ndim == 0 else length


def _spec_round_core(
    draft_params: Params,
    target_params: Params,
    t0: jnp.ndarray,
    dcache: KVCache,
    tcache: KVCache,
    key: jax.Array,
    *,
    draft_config: ModelConfig,
    target_config: ModelConfig,
    gamma: int,
    sampler: Sampler,
    draft_sampler: Sampler,
    active: jnp.ndarray | None = None,
    pad_offsets: jnp.ndarray | None = None,
):
    """Traced body of one speculative round, batched over rows.

    t0: [B] int32 — the verified input token per row.  Every row drafts γ
    tokens and verifies them in one target forward; each row accepts its
    own prefix length n_b, and the caches roll back PER ROW (vector
    ``length`` — cache.truncate/update_layer handle [B] offsets), so rows
    at different acceptance rates advance independently.

    active: optional [B] bool — rows that already finished (hit a stop
    token / budget) are frozen: their count is 0 and their cache rows roll
    back to where they started, so they burn no capacity.

    pad_offsets: optional [B] int32 — per-row LEFT-pad amounts for ragged
    batches (generate_ragged); threaded into every forward so RoPE
    positions and causal masks stay row-exact.

    Returns (emitted [B, γ+1] (first count_b real per row), count [B],
    dcache, tcache, next_t0 [B]).
    """
    b = t0.shape[0]
    kd, ku, kc = jax.random.split(key, 3)
    t_base = _as_rows(tcache.length, b)
    d_base = _as_rows(dcache.length, b)
    tcache = tcache._replace(length=t_base)
    dcache = dcache._replace(length=d_base)

    # --- draft: γ+1 steps (the extra step's proposal is discarded but
    # leaves the draft cache covering every verified input, so the
    # post-round rollback target base+n+1 always exists)
    def dstep(carry, k):
        tok, dc = carry
        logits, dc = forward(
            draft_params, tok[:, None], draft_config, dc, logits_last_only=True,
            pad_offsets=pad_offsets,
        )
        fl = draft_sampler.filtered_logits(logits[:, -1])  # [B, V]
        nxt = jax.random.categorical(k, fl, axis=-1).astype(jnp.int32)
        return (nxt, dc), (nxt, jax.nn.softmax(fl, axis=-1))

    dkeys = jax.random.split(kd, gamma + 1)
    (_, dcache2), (drafts, qprobs) = lax.scan(dstep, (t0, dcache), dkeys)
    d = jnp.moveaxis(drafts[:gamma], 0, 1)  # [B, γ] proposals d_1..d_γ
    qp = jnp.moveaxis(qprobs, 0, 1)  # [B, γ+1, V]

    # --- target: verify all proposals in one forward
    inp = jnp.concatenate([t0[:, None], d], axis=1)  # [B, γ+1]
    tlogits, tcache2 = forward(
        target_params, inp, target_config, tcache, pad_offsets=pad_offsets
    )
    p = jax.nn.softmax(sampler.filtered_logits(tlogits), axis=-1)  # [B, γ+1, V]

    # --- accept/reject (multiplied form avoids div-by-zero; q(d) > 0
    # by construction since d was sampled from q)
    p_d = jnp.take_along_axis(p[:, :gamma], d[..., None], axis=-1)[..., 0]
    q_d = jnp.take_along_axis(qp[:, :gamma], d[..., None], axis=-1)[..., 0]
    u = jax.random.uniform(ku, (b, gamma), dtype=jnp.float32)
    accept = u * q_d < p_d  # [B, γ]
    n = jnp.where(
        jnp.all(accept, axis=-1), gamma, jnp.argmin(accept, axis=-1)
    )  # [B]

    # --- correction (n < γ: residual norm(max(p−q, 0))) or bonus
    # (n == γ: plain p) — unified by a zero row AT position γ (qp has
    # γ+1 rows; its last row is the discarded extra draft step's
    # distribution and must NOT leak into the bonus sample)
    q_pad = qp.at[:, gamma].set(0.0)
    sel = lambda a: jnp.take_along_axis(a, n[:, None, None], axis=1)[:, 0]  # [B, V]
    residual = jnp.maximum(sel(p) - sel(q_pad), 0.0)
    total = jnp.sum(residual, axis=-1, keepdims=True)
    dist = jnp.where(total > 0, residual / jnp.maximum(total, 1e-38), sel(p))
    c = jax.random.categorical(kc, jnp.log(dist + 1e-38), axis=-1).astype(jnp.int32)

    emitted = jnp.concatenate(
        [d, jnp.zeros((b, 1), jnp.int32)], axis=1
    ).at[jnp.arange(b), n].set(c)
    count = n + 1  # [B]
    next_t0 = c
    if active is not None:
        count = jnp.where(active, count, 0)
        next_t0 = jnp.where(active, next_t0, t0)

    # --- roll both caches back to the accepted inputs t0..d_n, per row
    tcache2 = truncate(tcache2, t_base + count)
    dcache2 = truncate(dcache2, d_base + count)
    return emitted, count, dcache2, tcache2, next_t0


def make_spec_round_fn(
    draft_config: ModelConfig,
    target_config: ModelConfig,
    gamma: int,
    sampler: Sampler,
    draft_sampler: Sampler | None = None,
):
    """One jitted speculative round (granular API; one dispatch per round).

    (draft_params, target_params, t0 [B], dcache, tcache, key) →
    (emitted [B, γ+1] (only the first ``count_b`` of each row are real),
    count [B], dcache, tcache, next_t0 [B]).

    Both caches are DONATED (updated in place); callers must rebind them
    from the return value and never reuse the inputs.  Cache ``length``
    comes back as a per-row [B] vector from the first round on.
    """
    from functools import partial

    return jax.jit(
        partial(
            _spec_round_core,
            draft_config=draft_config,
            target_config=target_config,
            gamma=gamma,
            sampler=sampler,
            draft_sampler=draft_sampler or sampler,
        ),
        donate_argnums=(3, 4),  # both caches update in place; callers rebind
    )


def make_spec_decode_fn(
    draft_config: ModelConfig,
    target_config: ModelConfig,
    gamma: int,
    sampler: Sampler,
    draft_sampler: Sampler | None = None,
    stop_tokens: tuple[int, ...] = (),
):
    """The fused loop: ALL speculative rounds in one ``lax.while_loop`` —
    a single device dispatch for the whole generation (a per-round host
    sync would idle the device for a dispatch + fetch every round, the
    same reason generate.py fuses its decode scan).  Batched: rows accept draft
    prefixes independently (per-row cache lengths); rows that hit their
    budget or a stop token freeze (count 0, caches pinned) while the rest
    keep going, and the loop ends when every row is done.

    (draft_params, target_params, t0 [B], dcache, tcache, key, max_new) →
    (buf [B, max_new+γ+1] (first ``total_b`` real per row, t0 included),
    total [B], rounds [B] (rounds each row was ACTIVE in), accepted,
    proposed (scalars, summed over active rows), dcache, tcache).
    """
    from functools import partial

    draft_sampler_ = draft_sampler or sampler
    stops = jnp.asarray(stop_tokens, dtype=jnp.int32) if stop_tokens else None

    @partial(jax.jit, static_argnums=(6,), donate_argnums=(3, 4))
    def spec_decode(
        draft_params: Params,
        target_params: Params,
        t0: jnp.ndarray,
        dcache: KVCache,
        tcache: KVCache,
        key: jax.Array,
        max_new: int,
        pad_offsets: jnp.ndarray | None = None,
    ):
        b = t0.shape[0]
        # per-row lengths from round one, so the while-carry type is stable
        dcache = dcache._replace(length=_as_rows(dcache.length, b))
        tcache = tcache._replace(length=_as_rows(tcache.length, b))
        buf = jnp.zeros((b, max_new + gamma + 1), jnp.int32).at[:, 0].set(t0)
        done0 = (
            jnp.any(t0[:, None] == stops[None, :], axis=-1)
            if stops is not None
            else jnp.zeros((b,), jnp.bool_)
        )
        state = (
            jnp.ones((b,), jnp.int32),  # total emitted per row (t0 included)
            done0,
            t0,
            dcache,
            tcache,
            key,
            buf,
            jnp.zeros((b,), jnp.int32),  # rounds each row was active in
            jnp.zeros((), jnp.int32),  # accepted draft tokens (active rows)
            jnp.zeros((), jnp.int32),  # proposed draft tokens (active rows)
        )

        def cond(state):
            total, done = state[0], state[1]
            return jnp.any((total < max_new) & ~done)

        def body(state):
            (total, done, t, dcache, tcache, key, buf, rounds, accepted,
             proposed) = state
            key, kr = jax.random.split(key)
            active = (total < max_new) & ~done
            emitted, count, dcache, tcache, t = _spec_round_core(
                draft_params, target_params, t, dcache, tcache, kr,
                draft_config=draft_config, target_config=target_config,
                gamma=gamma, sampler=sampler, draft_sampler=draft_sampler_,
                active=active, pad_offsets=pad_offsets,
            )
            # write the whole γ+1 window at each row's total; slots past
            # `count_b` are garbage overwritten next round (buf is oversized
            # by γ+1 for the tail; frozen rows write only past their data)
            buf = jax.vmap(
                lambda row, em, tot: lax.dynamic_update_slice(row, em, (tot,))
            )(buf, emitted, total)
            if stops is not None:
                real = jnp.arange(gamma + 1)[None, :] < count[:, None]
                done = done | jnp.any(
                    real[:, :, None]
                    & (emitted[:, :, None] == stops[None, None, :]),
                    axis=(1, 2),
                )
            return (
                total + count, done, t, dcache, tcache, key, buf,
                rounds + active.astype(jnp.int32),
                accepted + jnp.sum(jnp.maximum(count - 1, 0)),
                proposed + gamma * jnp.sum(active.astype(jnp.int32)),
            )

        (total, _, _, dcache, tcache, _, buf, rounds, accepted, proposed) = (
            lax.while_loop(cond, body, state)
        )
        return buf, total, rounds, accepted, proposed, dcache, tcache

    return spec_decode


class SpeculativeGenerator:
    """Owns the jitted prefill + spec-round programs.

    Batched: a [B, S] prompt runs B speculative streams in one program —
    rows accept draft prefixes independently via per-row cache lengths
    (cache.py vector ``length``), so a slow row never rolls back a fast
    one.  1-D prompts keep the original batch-1 surface.

    draft defaults to the int8-quantized target params (self-speculation);
    pass ``draft_params``/``draft_config`` for a separate small model
    (they must share the tokenizer/vocab).
    """

    def __init__(
        self,
        params: Params,
        config: ModelConfig,
        *,
        draft_params: Params | None = None,
        draft_config: ModelConfig | None = None,
        gamma: int = 4,
        sampler: Sampler | None = None,
        draft_sampler: Sampler | None = None,
        cache_dtype: jnp.dtype = jnp.bfloat16,
        prefill_chunk: int | None = None,
    ) -> None:
        if draft_params is None:
            from llm_np_cp_tpu.quant import is_quantized, quantize_params

            if is_quantized(params["layers"].get("q_proj")):
                # target already int8 — nothing cheaper to derive; a
                # perfect draft (p == q) still pipelines γ+1 tokens/round
                draft_params = params
            else:
                draft_params = quantize_params(params)
        self.params = params
        self.config = config
        self.draft_params = draft_params
        self.draft_config = draft_config or config
        self.gamma = gamma
        self.sampler = sampler or Sampler()
        if prefill_chunk:
            from llm_np_cp_tpu.generate import make_chunked_prefill_fn

            self._prefill_t = make_chunked_prefill_fn(
                config, self.sampler, prefill_chunk
            )
            self._prefill_d = make_chunked_prefill_fn(
                self.draft_config, self.sampler, prefill_chunk
            )
        else:
            self._prefill_t = make_prefill_fn(config, self.sampler)
            self._prefill_d = make_prefill_fn(self.draft_config, self.sampler)
        self._draft_sampler = draft_sampler
        self._loops: dict[tuple, Any] = {}  # fused loop per stop-token set
        self.cache_dtype = cache_dtype

    def _loop(self, stop_tokens: tuple[int, ...]):
        if stop_tokens not in self._loops:
            self._loops[stop_tokens] = make_spec_decode_fn(
                self.draft_config, self.config, self.gamma, self.sampler,
                self._draft_sampler, stop_tokens,
            )
        return self._loops[stop_tokens]

    def generate(
        self,
        prompt_ids: np.ndarray,
        max_new_tokens: int,
        *,
        max_seq_len: int | None = None,
        seed: int = 0,
        stop_tokens: tuple[int, ...] = (),
    ) -> SpecResult:
        prompt_ids = jnp.asarray(prompt_ids, dtype=jnp.int32)
        squeeze = prompt_ids.ndim == 1
        if squeeze:
            prompt_ids = prompt_ids[None, :]
        return self._run(
            prompt_ids, max_new_tokens, max_seq_len, seed, stop_tokens,
            squeeze=squeeze,
        )

    def generate_ragged(
        self,
        prompts: list[np.ndarray | list[int]],
        max_new_tokens: int,
        *,
        max_seq_len: int | None = None,
        seed: int = 0,
        stop_tokens: tuple[int, ...] = (),
    ) -> SpecResult:
        """Speculative generation over prompts of different lengths.

        Same left-pad contract as Generator.generate_ragged: rows pad on
        the LEFT, per-row ``pad_offsets`` keep RoPE positions and masks
        exact through every draft/verify forward, and the per-row cache
        lengths the accept/rollback machinery already uses handle the
        rest — each row behaves as if it ran alone (verified in tests).
        """
        from llm_np_cp_tpu.generate import Generator

        ids, mask, pads = Generator.left_pad(prompts)
        return self._run(
            jnp.asarray(ids), max_new_tokens, max_seq_len, seed, stop_tokens,
            attn_mask=jnp.asarray(mask), pad_offsets=jnp.asarray(pads),
        )

    def _run(
        self,
        prompt_ids: jnp.ndarray,
        max_new_tokens: int,
        max_seq_len: int | None,
        seed: int,
        stop_tokens: tuple[int, ...],
        *,
        attn_mask: jnp.ndarray | None = None,
        pad_offsets: jnp.ndarray | None = None,
        squeeze: bool = False,
    ) -> SpecResult:
        b, s = prompt_ids.shape
        # rounds overshoot by up to γ+1 tokens before rollback trims them
        max_seq_len = max_seq_len or s + max_new_tokens + self.gamma + 1
        _check_capacity(s, max_new_tokens + self.gamma + 1, max_seq_len)
        # 128-aligned capacities (same contract as Generator._init_cache):
        # extra slots are masked off, and the Pallas decode kernel's
        # kv-block search stays near its requested size.
        max_seq_len = align_capacity(max_seq_len)

        key = jax.random.PRNGKey(seed)
        key, kp = jax.random.split(key)
        tcache = KVCache.init(self.config, b, max_seq_len, dtype=self.cache_dtype)
        dcache = KVCache.init(self.draft_config, b, max_seq_len, dtype=self.cache_dtype)

        t0_wall = time.perf_counter()
        tok, tcache, _ = self._prefill_t(
            self.params, prompt_ids, tcache, kp, attn_mask, pad_offsets
        )
        _, dcache, _ = self._prefill_d(
            self.draft_params, prompt_ids, dcache, kp, attn_mask, pad_offsets
        )
        # force BOTH prefills (draft included) so its cost lands in TTFT,
        # not in the decode timer
        np.asarray(tok)
        np.asarray(dcache.length)
        ttft = time.perf_counter() - t0_wall

        # the whole speculative loop is ONE dispatch (lax.while_loop)
        t_dec = time.perf_counter()
        buf, total, rounds, accepted, proposed, dcache, tcache = self._loop(
            stop_tokens
        )(
            self.draft_params, self.params, tok, dcache, tcache, key,
            max_new_tokens, pad_offsets,
        )
        buf = np.asarray(buf)  # forces completion (D2H)
        decode_s = time.perf_counter() - t_dec
        total = np.asarray(total)
        rounds_b = np.asarray(rounds)
        accepted, proposed = int(accepted), int(proposed)

        tokens = buf[:, :max_new_tokens].astype(np.int32)
        # rate over the tokens actually RETURNED (the final round can
        # overshoot max_new_tokens by up to γ per row; those are trimmed
        # and must not inflate the reported rate)
        n_dec_b = np.minimum(total, max_new_tokens) - 1
        n_dec = int(n_dec_b.sum())
        if stop_tokens:
            from llm_np_cp_tpu.generate import _trim_after_stop

            tokens = _trim_after_stop(tokens, tuple(stop_tokens))
        if squeeze:
            tokens = tokens[0]
            if stop_tokens:
                hits = np.isin(tokens, stop_tokens).nonzero()[0]
                if hits.size:
                    tokens = tokens[: hits[0] + 1]
        act = rounds_b > 0
        return SpecResult(
            tokens=tokens,
            ttft_s=ttft,
            decode_tokens_per_s=n_dec / decode_s if decode_s > 0 else float("nan"),
            num_generated=tokens.shape[-1],
            rounds=int(rounds_b.max()),
            acceptance_rate=accepted / proposed if proposed else 0.0,
            # mean over rows of (tokens the row emitted / rounds it was
            # active in) — rows finishing early don't deflate the metric
            tokens_per_round=(
                float(np.mean(n_dec_b[act] / rounds_b[act])) if act.any() else 0.0
            ),
        )
