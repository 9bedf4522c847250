"""Generation: prefill + decode loops (the reference's L5 layer).

The reference's ``generate`` (llama3.2_model.py:865-902) re-enters Python
every token: re-tokenize → forward → sample → decode → print.  On a TPU
every step of that loop is a host dispatch plus a device→host fetch, so
the loop shape is the bottleneck regardless of model speed.  Two
TPU-native paths replace it:

- **fused** (default): prefill is one jitted call; the whole decode loop is a
  second jitted call — ``lax.scan`` over decode steps with sampling *on
  device*, so N tokens cost one dispatch.  Used by bench.py.
- **streaming**: a Python loop around the jitted single-token step, emitting
  token text as produced (the reference's UX, llama3.2_model.py:899-901) —
  one dispatch per token, with incremental detokenization instead of the
  reference's token→text→token roundtrip (:873-883, which can re-merge
  tokens differently).

Both enforce the KV-cache capacity contract host-side (overflow is silent
under jit — see cache.update_layer) and report the metrics BASELINE.md
tracks: p50-able TTFT and decode tokens/sec.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from llm_np_cp_tpu.cache import KVCache, align_capacity
from llm_np_cp_tpu.config import ModelConfig
from llm_np_cp_tpu.models.transformer import forward
from llm_np_cp_tpu.ops.sampling import Sampler

Params = dict[str, Any]


@dataclasses.dataclass
class GenerateResult:
    tokens: np.ndarray  # [B, num_generated]
    ttft_s: float  # time to first token (prefill + first sample)
    decode_tokens_per_s: float  # steady-state decode rate (per sequence)
    num_generated: int
    text: list[str] | None = None
    # decode-loop steps actually EXECUTED (== num_generated-1 for the
    # fixed-trip scan; < that when early_stop exits before the budget).
    # The rate above divides by this, not the budget — an early-stopped
    # batch must not overstate its tok/s (ADVICE r5).
    steps: int = 0


def _check_capacity(prompt_len: int, max_new_tokens: int, max_seq_len: int) -> None:
    need = prompt_len + max_new_tokens
    if need > max_seq_len:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) = "
            f"{need} exceeds KV-cache capacity {max_seq_len}; writes past "
            f"capacity are silently clamped under jit"
        )


# ----------------------------------------------------------------------
# Jitted building blocks
# ----------------------------------------------------------------------

def make_prefill_fn(
    config: ModelConfig, sampler: Sampler, attn_impl: str = "xla"
) -> Callable:
    """(params, prompt_ids, cache, key) → (first_token [B], cache, logits).

    attn_impl="flash" routes prefill attention through the Pallas kernel
    (valid here: prefill always starts from a fresh cache, offset 0);
    "ring" routes it through sequence-parallel ring attention (needs an
    ambient mesh with a "seq" axis — parallel/ring_attention.py).

    The cache argument is DONATED: it is the largest live buffer (layers ×
    batch × max_seq × kv_heads × head_dim) and every call rebinds it, so
    XLA updates the slabs in place instead of allocating a second copy —
    free HBM headroom at bs=32 / long context.  Callers must not reuse the
    input cache object after the call (all in-repo callers rebind).
    """

    @partial(jax.jit, donate_argnums=(2,))
    def prefill(
        params: Params,
        prompt_ids: jnp.ndarray,
        cache: KVCache,
        key: jax.Array,
        attn_mask: jnp.ndarray | None = None,
        pad_offsets: jnp.ndarray | None = None,
    ):
        logits, cache = forward(
            params, prompt_ids, config, cache, logits_last_only=True,
            attn_mask=attn_mask, pad_offsets=pad_offsets,
            attn_impl=attn_impl,
        )
        tok = sampler(key, logits[:, -1])
        return tok, cache, logits[:, -1]

    return prefill


def make_ragged_prefill_step(config: ModelConfig) -> Callable:
    """(params, ids, cache, mask, pads) → (last_logits [B, V], cache) —
    one ragged (left-padded) prefill chunk at the cache's running offset.

    The cache's validity bitmap persists pad slots masked in earlier
    chunks (models/transformer.py), and positions derive from the running
    cache offset minus pad_offsets — so a chunk-sliced attn_mask composes
    exactly with chunking.  The cache is DONATED; callers rebind it.

    Module-level factory so the serving engine (serve/engine.py) compiles
    the SAME program shape the chunked prefill path dispatches.
    """

    @partial(jax.jit, donate_argnums=(2,))
    def ragged_step(
        params: Params, ids: jnp.ndarray, cache: KVCache,
        mask: jnp.ndarray, pads: jnp.ndarray,
    ):
        logits, cache = forward(
            params, ids, config, cache, logits_last_only=True,
            attn_mask=mask, pad_offsets=pads, attn_impl="xla",
        )
        return logits[:, -1], cache

    return ragged_step


def make_chunked_prefill_fn(
    config: ModelConfig,
    sampler: Sampler,
    chunk_size: int,
    attn_impl: str = "xla",
) -> Callable:
    """(params, prompt_ids, cache, key) → (first_token [B], cache, logits)
    — same contract as make_prefill_fn, but the prompt is consumed in
    fixed-width chunks of ``chunk_size`` tokens.

    Each chunk is a cached q_len>1 forward at the cache's running offset
    (the positions-based masks make this exact — the reference mis-masks
    this path, llama3.2_model.py:471-478, so it cannot chunk).  Compile
    cost is O(chunk_size) instead of O(prompt_len): an 8k prompt is
    8 dispatches of ONE compiled 1k-wide program (+ at most one remainder
    shape), not a single monolithic 8k-wide compile — the plausible cause
    of the r2 prefill8k bench timeouts.

    ``attn_impl`` ("flash"/"ring") applies to the FIRST chunk only (those
    kernels read the freshly projected K/V and require a fresh cache —
    models/transformer.py guards this); later chunks attend cached
    history and use the XLA path.
    """
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")

    def _make_step(impl: str):
        @partial(jax.jit, donate_argnums=(2,))
        def step(params: Params, ids: jnp.ndarray, cache: KVCache):
            logits, cache = forward(
                params, ids, config, cache, logits_last_only=True,
                attn_impl=impl,
            )
            return logits[:, -1], cache

        return step

    chunk_step = _make_step("xla")
    first_step = chunk_step if attn_impl == "xla" else _make_step(attn_impl)

    # Ragged (left-padded) chunks: a separate jitted step so the dense
    # program keeps its shape (see make_ragged_prefill_step).
    ragged_step = make_ragged_prefill_step(config)

    def prefill_chunked(
        params: Params,
        prompt_ids: jnp.ndarray,
        cache: KVCache,
        key: jax.Array,
        attn_mask: jnp.ndarray | None = None,
        pad_offsets: jnp.ndarray | None = None,
    ):
        ragged = attn_mask is not None or pad_offsets is not None
        if ragged and (attn_mask is None or pad_offsets is None):
            raise ValueError(
                "ragged chunked prefill needs BOTH attn_mask and pad_offsets"
            )
        if ragged and attn_impl != "xla":
            # same contract as the one-shot path: flash/ring masks are
            # slot-index-based and cannot see per-row pads
            raise ValueError(
                f"attn_impl={attn_impl!r} does not support ragged batches; "
                "use attn_impl='xla'"
            )
        s = prompt_ids.shape[1]
        off, step, last = 0, first_step, None
        while off < s:
            w = min(chunk_size, s - off)
            if ragged:
                last, cache = ragged_step(
                    params, prompt_ids[:, off:off + w], cache,
                    attn_mask[:, off:off + w], pad_offsets,
                )
            else:
                last, cache = step(params, prompt_ids[:, off:off + w], cache)
            step, off = chunk_step, off + w
        tok = sampler(key, last)
        return tok, cache, last

    # expose the jitted steps so AOT warmers compile the PROGRAM the
    # measured path dispatches (bench.run_warm; a make_prefill_fn lowered
    # at the chunk shape is a different program and misses the cache)
    prefill_chunked.chunk_step = chunk_step
    prefill_chunked.first_step = first_step
    prefill_chunked.ragged_step = ragged_step
    return prefill_chunked


def _make_sample_tail(
    config: ModelConfig, sampler: Sampler, fused_epilogue: bool
) -> Callable:
    """``(params, key, fwd_out) → next_tok [B]`` — the decode tail.

    fused_epilogue=True: ``fwd_out`` is the pre-final-norm hidden state
    (``forward(..., skip_logits=True)``) and the tail is the ONE Pallas
    ``sample_epilogue`` kernel (norm → lm_head → greedy sample streamed
    over vocab tiles; ``[B, 1, V]`` logits never materialize) via the
    shared ``transformer.sample_epilogue_tail`` invocation.  Callers
    gate on ``transformer.epilogue_gate_error`` (Generator does) — the
    draw is bit-identical to the sampler tail, pinned in tests.
    False: the classic ``sampler(key, logits[:, -1])`` tail/oracle."""
    if not fused_epilogue:
        return lambda params, key, logits: sampler(key, logits[:, -1])
    from llm_np_cp_tpu.models.transformer import sample_epilogue_tail

    def tail(params: Params, key: jax.Array, hid: jnp.ndarray):
        return sample_epilogue_tail(params, hid[:, -1], config)

    return tail


def make_decode_step_fn(
    config: ModelConfig, sampler: Sampler, attn_impl: str = "xla",
    fused_epilogue: bool = False,
) -> Callable:
    """(params, tok [B], cache, key) → (next_tok [B], cache) — one token.
    The cache is donated (updated in place); callers rebind it.
    ``fused_epilogue`` swaps the logits+sampler tail for the fused
    sampling-epilogue kernel (see _make_sample_tail)."""
    sample_tail = _make_sample_tail(config, sampler, fused_epilogue)

    @partial(jax.jit, donate_argnums=(2,))
    def step(params: Params, tok: jnp.ndarray, cache: KVCache, key: jax.Array):
        out, cache = forward(
            params, tok[:, None], config, cache, logits_last_only=True,
            attn_impl=attn_impl, skip_logits=fused_epilogue,
        )
        return sample_tail(params, key, out), cache

    return step


def make_decode_loop_fn(
    config: ModelConfig,
    sampler: Sampler,
    stop_tokens: tuple[int, ...] = (),
    attn_impl: str = "xla",
    early_stop: bool = False,
    fused_epilogue: bool = False,
) -> Callable:
    """(params, first_tok, cache, key, num_steps) →
    (tokens [B, num_steps], cache, steps_executed int32).

    The fused loop: ``lax.scan`` over decode steps entirely on device.
    ``num_steps`` is static (one compile per distinct value).  Sequences
    that hit a stop token keep feeding it (outputs past EOS are repeats the
    caller trims) — branchless, so the scan stays a single fused program.
    attn_impl="flash_decode" routes each step's attention through the
    fused Pallas decode kernel (benchmark-gated; default XLA).

    early_stop=True (requires stop_tokens) swaps the scan for a
    ``lax.while_loop`` that exits once EVERY row is done — a batch whose
    rows all hit EOS early stops paying weight-stream steps for tokens
    nobody will read.  Unfilled tail slots hold 0 and every caller
    normalizes through ``_trim_after_stop``, so outputs are identical to
    the scan path (pinned in tests).  Opt-in: a fixed-trip scan is the
    better program when generation usually runs to the budget.
    """
    stops = jnp.asarray(stop_tokens, dtype=jnp.int32) if stop_tokens else None
    if early_stop and stops is None:
        raise ValueError("early_stop requires stop_tokens")
    sample_tail = _make_sample_tail(config, sampler, fused_epilogue)

    @partial(jax.jit, static_argnums=(4,), donate_argnums=(2,))
    def decode_loop(
        params: Params,
        first_tok: jnp.ndarray,
        cache: KVCache,
        key: jax.Array,
        num_steps: int,
        pad_offsets: jnp.ndarray | None = None,
    ):
        def step(tok, cache, done, k):
            out, cache = forward(
                params, tok[:, None], config, cache, logits_last_only=True,
                pad_offsets=pad_offsets, attn_impl=attn_impl,
                skip_logits=fused_epilogue,
            )
            nxt = sample_tail(params, k, out)
            if stops is not None:
                nxt = jnp.where(done, tok, nxt)
                done = done | jnp.any(nxt[:, None] == stops[None, :], axis=-1)
            return nxt, cache, done

        done0 = (
            jnp.any(first_tok[:, None] == stops[None, :], axis=-1)
            if stops is not None
            else jnp.zeros(first_tok.shape, dtype=jnp.bool_)
        )

        if early_stop:
            b = first_tok.shape[0]
            keys = jax.random.split(key, num_steps)
            buf0 = jnp.zeros((b, num_steps), jnp.int32)

            def cond(state):
                i, _, _, done, _ = state
                return (i < num_steps) & ~jnp.all(done)

            def body(state):
                i, tok, cache, done, buf = state
                nxt, cache, done = step(tok, cache, done, keys[i])
                buf = lax.dynamic_update_slice(buf, nxt[:, None], (0, i))
                return i + 1, nxt, cache, done, buf

            i, _, cache, _, buf = lax.while_loop(
                cond, body, (jnp.zeros((), jnp.int32), first_tok, cache,
                             done0, buf0)
            )
            # i = steps actually EXECUTED (< num_steps when every row hit
            # EOS early); callers compute tok/s from it, not the budget
            return buf, cache, i  # [B, steps]; tail zeros normalized by trim

        keys = jax.random.split(key, num_steps)

        def scan_body(carry, k):
            tok, cache, done = carry
            nxt, cache, done = step(tok, cache, done, k)
            return (nxt, cache, done), nxt

        (_, cache, _), toks = lax.scan(scan_body, (first_tok, cache, done0), keys)
        steps = jnp.asarray(num_steps, jnp.int32)  # fixed-trip: all executed
        return jnp.moveaxis(toks, 0, 1), cache, steps  # [B, steps]

    return decode_loop


# ----------------------------------------------------------------------
# High-level API
# ----------------------------------------------------------------------

class IncrementalDetok:
    """Incremental detokenization: decode the full id list on every push
    and emit only the delta, holding back while the tail may still change
    (mid-UTF-8 merge — avoids the reference's per-step token→text→token
    roundtrip, llama3.2_model.py:873-883).  The ONE held-back rule shared
    by Generator.stream_text and the serving engine's per-request
    streams."""

    def __init__(self, tokenizer: Any) -> None:
        self.tokenizer = tokenizer
        self.ids: list[int] = []
        self.emitted = ""

    def push(self, token_id: int) -> str | None:
        """Append one id; return the newly-stable text delta, if any."""
        self.ids.append(int(token_id))
        text = self.tokenizer.decode(self.ids, skip_special_tokens=True)
        if text.endswith("�"):
            return None
        delta, self.emitted = text[len(self.emitted):], text
        return delta or None

    def flush(self) -> str | None:
        """Emit any held-back tail (call once, after the last push)."""
        text = self.tokenizer.decode(self.ids, skip_special_tokens=True)
        delta = text[len(self.emitted):]
        self.emitted = text
        return delta or None


class Generator:
    """Owns jitted prefill/decode programs for one (model, sampler) pair.

    Compiles lazily per (batch, prompt_len, num_steps) shape; repeated calls
    with the same shapes reuse the compiled programs (jit cache).
    """

    def __init__(
        self,
        params: Params,
        config: ModelConfig,
        *,
        sampler: Sampler | None = None,
        stop_tokens: tuple[int, ...] = (),
        cache_dtype: jnp.dtype = jnp.bfloat16,
        prefill_attn_impl: str = "xla",
        prefill_chunk: int | None = None,
        decode_attn: str = "xla",
        early_stop: bool = False,
    ) -> None:
        self.params = params
        self.config = config
        self.sampler = sampler or Sampler()
        self.stop_tokens = tuple(stop_tokens)
        self.cache_dtype = cache_dtype
        if decode_attn not in ("xla", "flash_decode"):
            # the CLI's user-facing name is "pallas"; catch it (and typos)
            # here instead of silently falling back to the XLA path in
            # run_decoder_layer
            raise ValueError(
                f"decode_attn must be 'xla' or 'flash_decode', "
                f"got {decode_attn!r}"
            )
        # Mosaic gate: a Pallas impl that fails to compile on the live
        # backend downgrades to XLA with one warning instead of dying at
        # first dispatch (ops/pallas/support.py; r3 postmortem).
        from llm_np_cp_tpu.ops.pallas.support import gate_attn_impl

        prefill_attn_impl = gate_attn_impl(prefill_attn_impl)
        decode_attn = gate_attn_impl(
            decode_attn,
            int8_cache=jnp.dtype(cache_dtype) == jnp.int8,
        )
        if prefill_chunk:
            self._prefill = make_chunked_prefill_fn(
                config, self.sampler, prefill_chunk, prefill_attn_impl
            )
        else:
            self._prefill = make_prefill_fn(config, self.sampler, prefill_attn_impl)
        self.last_stream_stats: dict[str, Any] = {}
        # fused sampling epilogue (tick-tail fusion, the serve engine's
        # gate shared verbatim via transformer.epilogue_gate_error):
        # greedy sampler + float/int8-"q" head + probe pass → the
        # decode tail runs norm→lm_head→sample as one Pallas kernel and
        # the [B, 1, V] logits never materialize; anything else keeps
        # the logits+Sampler tail (the oracle)
        from llm_np_cp_tpu.models.transformer import epilogue_gate_error

        self.epilogue_impl = (
            "fused"
            if epilogue_gate_error(params, config, self.sampler.kind)
            is None else "xla"
        )
        fused_epi = self.epilogue_impl == "fused"
        self._step = make_decode_step_fn(
            config, self.sampler, decode_attn,
            fused_epilogue=fused_epi,
        )
        self._loop = make_decode_loop_fn(
            config, self.sampler, self.stop_tokens, decode_attn,
            early_stop=early_stop, fused_epilogue=fused_epi,
        )

    def _init_cache(self, batch: int, max_seq_len: int) -> KVCache:
        # Capacity is rounded UP to a multiple of 128: slots past the
        # requested length are masked off (validity masks use per-row
        # lengths, not capacity), decode_attention's kv-block search never
        # collapses toward block_s=1 on a prime capacity, and seq-axis
        # sharding divisibility is automatic.  Contract documented in
        # cache.py.
        return KVCache.init(
            self.config, batch, align_capacity(max_seq_len),
            dtype=self.cache_dtype,
        )

    def _run_fused(
        self,
        prompt_ids: jnp.ndarray,
        max_new_tokens: int,
        max_seq_len: int | None,
        seed: int,
        attn_mask: jnp.ndarray | None = None,
        pad_offsets: jnp.ndarray | None = None,
    ) -> GenerateResult:
        """Shared fused runner: prefill dispatch + decode-scan dispatch."""
        b, s = prompt_ids.shape
        max_seq_len = max_seq_len or s + max_new_tokens
        _check_capacity(s, max_new_tokens, max_seq_len)

        key = jax.random.PRNGKey(seed)
        k_pre, k_loop = jax.random.split(key)
        cache = self._init_cache(b, max_seq_len)

        t0 = time.perf_counter()
        tok0, cache, _ = self._prefill(
            self.params, prompt_ids, cache, k_pre, attn_mask, pad_offsets
        )
        tok0.block_until_ready()
        t1 = time.perf_counter()

        if max_new_tokens > 1:
            rest, cache, steps_dev = self._loop(
                self.params, tok0, cache, k_loop, max_new_tokens - 1, pad_offsets
            )
            rest.block_until_ready()
            t2 = time.perf_counter()
            tokens = np.concatenate([np.asarray(tok0)[:, None], np.asarray(rest)], axis=1)
            # rate over steps actually EXECUTED: under early_stop the
            # while_loop may exit before the budget, and dividing the
            # budget by the (shorter) loop time overstated tok/s
            steps = int(np.asarray(steps_dev))
            rate = steps / (t2 - t1) if steps > 0 else float("nan")
        else:
            tokens = np.asarray(tok0)[:, None]
            rate = float("nan")
            steps = 0

        tokens = _trim_after_stop(tokens, self.stop_tokens)
        return GenerateResult(
            tokens=tokens,
            ttft_s=t1 - t0,
            decode_tokens_per_s=rate,
            num_generated=tokens.shape[1],
            steps=steps,
        )

    # -- fused ---------------------------------------------------------
    def generate(
        self,
        prompt_ids: np.ndarray | jnp.ndarray,
        max_new_tokens: int,
        *,
        max_seq_len: int | None = None,
        seed: int = 0,
    ) -> GenerateResult:
        """Fused generation: 2 device dispatches total (prefill, decode scan)."""
        prompt_ids = jnp.asarray(prompt_ids, dtype=jnp.int32)
        if prompt_ids.ndim == 1:
            prompt_ids = prompt_ids[None, :]
        return self._run_fused(prompt_ids, max_new_tokens, max_seq_len, seed)

    # -- ragged batch --------------------------------------------------
    @staticmethod
    def left_pad(
        prompts: list[np.ndarray | list[int]],
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The ragged left-pad contract, in ONE place: prompts → (ids
        [B, S] zero-left-padded, mask [B, S] valid, pads [B] per-row pad
        counts).  Used by Generator.generate_ragged and
        SpeculativeGenerator.generate_ragged."""
        arrs = [np.asarray(p, dtype=np.int32).reshape(-1) for p in prompts]
        if not arrs:
            raise ValueError("left_pad needs at least one prompt")
        empty = [i for i, a in enumerate(arrs) if a.size == 0]
        if empty:
            # an all-pad row would sample its first token from a fully
            # masked attention — fail fast instead of emitting garbage
            raise ValueError(f"empty prompt at index {empty[0]}")
        s = max(a.size for a in arrs)
        b = len(arrs)
        ids = np.zeros((b, s), dtype=np.int32)
        mask = np.zeros((b, s), dtype=bool)
        pads = np.zeros(b, dtype=np.int32)
        for i, a in enumerate(arrs):
            pads[i] = s - a.size
            ids[i, pads[i]:] = a
            mask[i, pads[i]:] = True
        return ids, mask, pads

    def generate_ragged(
        self,
        prompts: list[np.ndarray | list[int]],
        max_new_tokens: int,
        *,
        max_seq_len: int | None = None,
        seed: int = 0,
    ) -> GenerateResult:
        """Batch generation over prompts of different lengths.

        Prompts are LEFT-padded to a common length; per-row ``pad_offsets``
        keep RoPE positions and causal masks exact (each row behaves as if
        it ran alone — verified in tests), and the pad slots are marked
        invalid in the cache bitmap.  The reference has no batching at all
        (its generate loop is bs=1, llama3.2_model.py:865-902).
        """
        ids, mask, pads = self.left_pad(prompts)
        return self._run_fused(
            jnp.asarray(ids),
            max_new_tokens,
            max_seq_len,
            seed,
            attn_mask=jnp.asarray(mask),
            pad_offsets=jnp.asarray(pads),
        )

    def generate_many(
        self,
        prompts: list[np.ndarray | list[int]],
        max_new_tokens: int,
        *,
        batch_size: int = 8,
        max_seq_len: int | None = None,
        seed: int = 0,
    ) -> list[GenerateResult]:
        """Dynamic batching over a workload of any size: prompts are
        grouped (longest-first, so rows in a batch have similar lengths
        and waste little pad) into ragged batches of ``batch_size`` and
        each batch runs the fused path; returns one GenerateResult PER
        PROMPT (a single-row tokens array), in the caller's original
        prompt order, each carrying its own batch's ttft/rate.

        With ``early_stop`` on the Generator, a batch whose rows all hit
        EOS early releases the chip to the next batch — throughput-
        oriented offline serving without a resident server.  (The
        reference processes one prompt at a time, llama3.2_model.py:865.)
        """
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        order = sorted(
            range(len(prompts)), key=lambda i: -len(np.asarray(prompts[i]).reshape(-1))
        )
        results: list[GenerateResult | None] = [None] * len(prompts)
        for start in range(0, len(order), batch_size):
            idx = order[start:start + batch_size]
            res = self.generate_ragged(
                [prompts[i] for i in idx], max_new_tokens,
                max_seq_len=max_seq_len, seed=seed + start,
            )
            for row, i in enumerate(idx):
                results[i] = GenerateResult(
                    tokens=res.tokens[row:row + 1],
                    ttft_s=res.ttft_s,
                    decode_tokens_per_s=res.decode_tokens_per_s,
                    num_generated=res.num_generated,
                    steps=res.steps,
                )
        return results  # type: ignore[return-value]

    # -- streaming -----------------------------------------------------
    def stream(
        self,
        prompt_ids: np.ndarray | jnp.ndarray,
        max_new_tokens: int,
        *,
        max_seq_len: int | None = None,
        seed: int = 0,
    ) -> Iterator[int]:
        """Yield token ids one at a time (batch size 1)."""
        prompt_ids = jnp.asarray(prompt_ids, dtype=jnp.int32)
        if prompt_ids.ndim == 1:
            prompt_ids = prompt_ids[None, :]
        if prompt_ids.shape[0] != 1:
            raise ValueError("streaming supports batch size 1")
        s = prompt_ids.shape[1]
        max_seq_len = max_seq_len or s + max_new_tokens
        _check_capacity(s, max_new_tokens, max_seq_len)

        key = jax.random.PRNGKey(seed)
        cache = self._init_cache(1, max_seq_len)
        key, k = jax.random.split(key)
        tok, cache, _ = self._prefill(self.params, prompt_ids, cache, k)
        t = int(tok[0])
        yield t
        for _ in range(max_new_tokens - 1):
            if t in self.stop_tokens:
                return
            key, k = jax.random.split(key)
            tok, cache = self._step(self.params, tok, cache, k)
            t = int(tok[0])
            yield t

    def stream_text(
        self,
        tokenizer: Any,
        prompt: str,
        max_new_tokens: int,
        *,
        seed: int = 0,
        echo: Callable[[str], None] | None = None,
    ) -> str:
        """Streaming text generation with incremental detokenization.

        Emits only the *delta* between successive decodes of the generated
        ids — avoids the reference's per-step token→text→token roundtrip
        (llama3.2_model.py:873-883) while handling multi-byte merges.
        """
        prompt_ids = tokenizer(prompt, return_tensors="np")["input_ids"][0]
        detok = IncrementalDetok(tokenizer)
        t0 = time.perf_counter()
        ttft = None
        for t in self.stream(prompt_ids, max_new_tokens, seed=seed):
            if ttft is None:
                ttft = time.perf_counter() - t0
            delta = detok.push(t)
            if echo and delta:
                echo(delta)
        tail = detok.flush()
        if echo and tail:
            echo(tail)
        self.last_stream_stats = {
            "tokens": len(detok.ids),
            "ttft_s": ttft,
            "duration_s": time.perf_counter() - t0,
        }
        return detok.emitted


def _trim_after_stop(tokens: np.ndarray, stop_tokens: tuple[int, ...]) -> np.ndarray:
    """Replace everything after the first stop token with that stop token
    (fused decode keeps generating repeats past EOS by construction)."""
    if not stop_tokens:
        return tokens
    out = tokens.copy()
    for b in range(out.shape[0]):
        hits = np.isin(out[b], stop_tokens).nonzero()[0]
        if hits.size:
            out[b, hits[0]:] = out[b, hits[0]]
    return out
