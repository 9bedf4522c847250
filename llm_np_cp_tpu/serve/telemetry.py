"""Device roofline telemetry: per-tick bandwidth accounting and
per-request cost attribution.

The serve workload is bandwidth-bound (ROADMAP: the 819 GB/s HBM
roofline is the number left to chase), but the observability plane so
far only measures WALL time — nobody can say what fraction of the
roofline a tick achieved, which is the prerequisite for the operation-
fusion work ("LLM Inference Acceleration via Efficient Operation
Fusion", PAPERS.md) and for telling whether the ragged kernel ("Ragged
Paged Attention") is bandwidth-bound or dispatch-bound on a given
trace.  This module closes that gap with an ANALYTIC byte/FLOP model:

- **weight traffic** — every dispatch streams the decoder stack once
  (layers + final norm + lm_head; the tied lm_head re-reads the
  embedding matrix), plus one embedding row per packed token.
- **KV traffic** — reads from the planned tick composition (the
  per-request generalization of the engine's ``_kv_bytes_tick_mixed``:
  the ragged kernel streams each q tile's visible blocks, window-aware
  per layer, speculative verify lanes included; the XLA fallback
  materializes the padded view), writes one K/V column per packed
  token per layer.  int8 pools count their f32 scale pages.
- **FLOPs** — ``2 * active_params * tokens`` (attention FLOPs are
  second-order at serving context lengths and deliberately left out of
  the estimate — the model is for MFU *trend*, not a FLOP audit).
- **sampling-tail traffic** — the XLA epilogue materializes
  ``[rows, V]`` float32 logits (lm_head write + sampler read-back);
  that rides the weight-bytes term so attribution/conservation follow
  for free.  The FUSED epilogue (``ServeEngine.epilogue_impl ==
  "fused"``) streams lm_head tiles through VMEM and pays ZERO here —
  the model must never bill phantom logits traffic the fused kernel
  retired (``_epilogue_logits_bytes`` is the one rule; the engine's
  kv-bytes gauges keep delegating here, so gauge and model cannot
  drift).

Combined with the measured dispatch→host-sync wall of the SAME tick,
that yields **achieved GB/s**, **roofline utilization** vs
``--hbm-gbps`` (819 by default), and an **MFU estimate** — emitted as
tick args in the trace plane, gauges/histograms on ``/metrics``, and a
``roofline_deficit`` pseudo-phase the ``TickSentinel`` baselines like
any other phase, so a persistent utilization regression pages exactly
like a host_sync one (deficit = measured wall minus the roofline-ideal
wall for the tick's bytes; utilization drops = deficit grows).

**Cost attribution**: each tick's KV bytes are exact per request (the
model is per-row already); weight bytes and device time are amortized
by token share.  The engine accumulates them on ``Request``
(``kv_bytes_read`` / ``kv_bytes_written`` / ``weight_bytes_amortized``
/ ``device_time_s``) and the canonical request log carries them — the
cost basis per-tenant SLOs will bill against (ROADMAP item 2).
Attribution CONSERVES: per-request values sum to the tick totals
(test-pinned).

CALIBRATION: the byte model is analytic, not measured — on CPU the
absolute GB/s numbers are meaningless (no HBM) and on TPU they assume
perfect overlap of weight and KV streams.  Calibrating against a live
``--jax-profile`` device capture is recorded ROADMAP debt.

ZERO-OVERHEAD WHEN OFF (the FaultInjector discipline, pinned by
tools/lint R4): nothing constructs a ``TelemetryModel`` unless
requested (``--roofline``), every engine hook is a single ``is None``
check, and everything here is host-side Python/NumPy arithmetic —
attaching telemetry adds zero dispatches and zero recompiles (pinned
by the compile-counter telemetry section).

THREAD SAFETY: ``TelemetryModel`` is immutable after construction
(config-derived constants only), so one instance is safely shared
across clone_fresh rebuilds and fleet replicas; all mutable
accumulation lives in ``ServeMetrics`` (under its lock) and on
``Request`` (engine-thread-owned).
"""

from __future__ import annotations

from typing import Any

# The HBM roofline the utilization ratio is computed against, GB/s.
# 819 GB/s is the chip the ROADMAP anchors on (BENCH_TPU_LIVE_r4's
# capture); override per deployment with --hbm-gbps.
HBM_GBPS_DEFAULT = 819.0
# Peak dense bf16 throughput for the MFU estimate, TFLOP/s.
PEAK_TFLOPS_DEFAULT = 197.0


def _leaves(tree: Any):
    """Yield array leaves of a params tree without importing jax (any
    object with .nbytes/.size counts — jax arrays and numpy both do)."""
    if isinstance(tree, dict):
        for v in tree.values():
            yield from _leaves(v)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            yield from _leaves(v)
    elif hasattr(tree, "nbytes"):
        yield tree


def _per_slot_bytes(config: Any, cache_itemsize: int) -> int:
    """K+V bytes one cache slot costs per layer (int8 pools stream
    their f32 scale pages alongside the quantized blocks)."""
    b = config.num_key_value_heads * config.head_dim * cache_itemsize * 2
    if cache_itemsize == 1:  # int8 pool: per-slot f32 scales, K and V
        b += config.num_key_value_heads * 4 * 2
    return b


def mixed_tick_kv_read(
    eng: Any,
    decode_rows: list,
    prefill_segs: list,
    *,
    per_request: bool = True,
) -> tuple[int, dict[int, int]]:
    """K/V bytes one mixed tick's attention reads — total AND per
    request (the per-request generalization of the engine's
    ``_kv_bytes_tick_mixed``; the engine's method delegates here so the
    two can never drift).  A speculating decode row's verify slice
    (``draft_len`` extra q positions) is counted when the caller runs
    the model BEFORE the accept walk resets ``draft_len`` — the
    engine's metrics call (post-walk, draft_len 0) reproduces the
    historical numbers exactly.  ``per_request=False`` skips the
    per-row dict (empty in the result) — the every-tick metrics gauge
    runs telemetry-off too and must not pay an allocation for it."""
    cfg = eng.config
    per_slot = _per_slot_bytes(cfg, eng.cache_dtype.itemsize)
    n_layers = cfg.num_hidden_layers
    qb = eng._q_tile
    per: dict[int, int] = {}
    total = 0
    if eng.ragged_attn_impl != "pallas":
        # the XLA fallback materializes every live token's full padded
        # row view (prefill tiles pad to the q tile)
        s_full = eng.max_seq_len * n_layers * per_slot
        for r in decode_rows:
            b = (1 + r.draft_len) * s_full
            total += b
            if per_request:
                per[r.req_id] = b
        for r, n in prefill_segs:
            b = (-(-n // qb) * qb) * s_full
            total += b
            if per_request:
                per[r.req_id] = b
        return total, per
    win = cfg.sliding_window
    n_sliding = (
        sum(cfg.layer_is_sliding(i) for i in range(n_layers))
        if win is not None else 0
    )
    bs = eng.block_size

    def tile_slots(pad: int, qpos0: int, qlast: int) -> tuple[int, int]:
        full = (qlast // bs - pad // bs + 1) * bs
        if not n_sliding:
            return full, 0
        lo = max(pad, qpos0 - win + 1)
        return full, (qlast // bs - lo // bs + 1) * bs

    def seg_bytes(pad: int, start: int, n: int, wide: int = 0) -> int:
        # the segment's query tiles as the packer lays them: whole wide
        # tiles as far as they reach, tiles of ``qb`` for the rest — each
        # streams its row's visible pages once
        full = n // wide * wide if wide else 0
        firsts = list(range(0, full, wide or 1)) + list(range(full, n, qb))
        slot_layers = 0
        for q0, q1 in zip(firsts, firsts[1:] + [n]):
            g_full, g_win = tile_slots(pad, start + q0, start + q1 - 1)
            slot_layers += (
                (n_layers - n_sliding) * g_full + n_sliding * g_win
            )
        return slot_layers * per_slot

    # (the tick's program, as ``_pack_mixed`` picks it, says whether its
    # prompt segments lie in wide tiles)
    sizes = [1 + r.draft_len for r in decode_rows]
    sizes.extend(n for _, n in prefill_segs)
    wide = eng._wide_program(eng._pick_bucket(
        sum(-(-n // qb) * qb for n in sizes), sum(sizes))[0]) if (
            prefill_segs and eng._wide_tile) else 0
    for r in decode_rows:
        b = seg_bytes(r.pad, r.cache_len - 1, 1 + r.draft_len)
        total += b
        if per_request:
            per[r.req_id] = b
    for r, n in prefill_segs:
        b = seg_bytes(r.pad, r.pad + r.prefill_done, n, wide)
        total += b
        if per_request:
            per[r.req_id] = b
    return total, per


def _epilogue_logits_bytes(eng: Any, sample_rows: int) -> float:
    """HBM traffic of the step's SAMPLING TAIL: the XLA epilogue
    materializes ``[sample_rows, V]`` float32 logits (written by the
    lm_head einsum, read back by the sampler — 8 bytes per pair, every
    slot including inactive ones: the step samples at full static
    width).  The fused epilogue never leaves VMEM with them, so it
    pays zero — billing the difference is exactly what makes the
    fused-vs-unfused roofline delta visible to ``slo_gate
    --min-bandwidth-util``."""
    if getattr(eng, "epilogue_impl", "xla") == "fused":
        return 0.0
    return float(sample_rows * eng.config.vocab_size * 4 * 2)


class TelemetryModel:
    """The analytic cost model, frozen at engine-build time from the
    params tree and config.  Methods take the engine (geometry and
    composition live there); the model itself holds no mutable state,
    so ``clone_fresh`` rebuilds and fleet replicas share one instance.
    """

    def __init__(
        self,
        config: Any,
        params: Any,
        *,
        hbm_gbps: float = HBM_GBPS_DEFAULT,
        peak_tflops: float = PEAK_TFLOPS_DEFAULT,
    ) -> None:
        if hbm_gbps <= 0:
            raise ValueError(f"hbm_gbps must be > 0, got {hbm_gbps}")
        if peak_tflops <= 0:
            raise ValueError(
                f"peak_tflops must be > 0, got {peak_tflops}"
            )
        self.hbm_gbps = float(hbm_gbps)
        self.peak_tflops = float(peak_tflops)
        total_b = total_n = 0
        for leaf in _leaves(params):
            total_b += int(leaf.nbytes)
            total_n += int(leaf.size)
        # the embed entry may itself be a subtree (quantize_params turns
        # it into {"q", "scale"}) — sum its leaves like the total does
        embed = params.get("embed_tokens") if isinstance(params, dict) \
            else None
        embed_b = embed_n = 0
        for leaf in _leaves(embed):
            embed_b += int(leaf.nbytes)
            embed_n += int(leaf.size)
        # bytes every dispatch streams: the decoder stack + final norm
        # (+ the untied lm_head, already a leaf); the embedding table is
        # GATHERED (one row per token), not streamed
        self.stream_bytes = total_b - embed_b
        # a tied lm_head re-reads the full embedding matrix for logits
        tied = bool(getattr(config, "tie_word_embeddings", False))
        self.lm_head_bytes = embed_b if tied else 0
        self.embed_row_bytes = (
            embed_b // max(config.vocab_size, 1) if embed_b else 0
        )
        # parameters that do a multiply-add per token (MFU numerator)
        self.n_flop_params = (total_n - embed_n) + (embed_n if tied else 0)

    # ------------------------------------------------------------------
    def weight_bytes(self, tokens: int, n_dispatches: int = 1) -> int:
        """HBM weight traffic for ``n_dispatches`` forward dispatches
        covering ``tokens`` packed tokens."""
        return (n_dispatches * (self.stream_bytes + self.lm_head_bytes)
                + tokens * self.embed_row_bytes)

    def _cost(self, kind: str, rows: list, kv_read: float,
              n_dispatches: int = 1,
              tail_bytes: float = 0.0) -> dict[str, Any]:
        tokens = sum(t for _, t, _, _ in rows)
        return {
            "kind": kind,
            "tokens": tokens,
            "kv_read_bytes": kv_read,
            "kv_write_bytes": float(sum(w for _, _, _, w in rows)),
            # the sampling tail's logits traffic (zero when fused)
            # rides the weight term: same streamed-per-dispatch shape,
            # and attribution/conservation follow unchanged
            "weight_bytes": float(
                self.weight_bytes(tokens, n_dispatches) + tail_bytes
            ),
            "flops": 2.0 * self.n_flop_params * tokens,
            "rows": rows,
        }

    def mixed_tick_cost(self, eng: Any, decode_rows: list,
                        prefill_segs: list) -> dict[str, Any]:
        """The unified tick's planned byte/FLOP bill.  Must run BEFORE
        the dispatch's accept walk (verify lanes live in ``draft_len``
        only until then)."""
        kv_read, per_read = mixed_tick_kv_read(eng, decode_rows,
                                               prefill_segs)
        wslot = (_per_slot_bytes(eng.config, eng.cache_dtype.itemsize)
                 * eng.config.num_hidden_layers)
        rows = []
        for r in decode_rows:
            t = 1 + r.draft_len
            rows.append((r, t, float(per_read[r.req_id]),
                         float(t * wslot)))
        for r, n in prefill_segs:
            rows.append((r, n, float(per_read[r.req_id]),
                         float(n * wslot)))
        return self._cost(
            "mixed", rows, float(kv_read),
            tail_bytes=_epilogue_logits_bytes(
                eng, eng.scheduler.max_slots * eng._spec_w
            ),
        )

    # ------------------------------------------------------------------
    def finish(self, cost: dict[str, Any],
               device_time_s: float) -> dict[str, Any]:
        """Combine a planned cost with the measured dispatch→host-sync
        wall of the same tick → the telemetry record the metrics/trace/
        sentinel planes consume."""
        total = (cost["kv_read_bytes"] + cost["kv_write_bytes"]
                 + cost["weight_bytes"])
        dev = max(float(device_time_s), 1e-9)
        achieved_gbps = total / dev / 1e9
        ideal_s = total / (self.hbm_gbps * 1e9)
        return {
            "kind": cost["kind"],
            "tokens": cost["tokens"],
            "device_time_s": float(device_time_s),
            "kv_read_bytes": cost["kv_read_bytes"],
            "kv_write_bytes": cost["kv_write_bytes"],
            "weight_bytes": cost["weight_bytes"],
            "achieved_gbps": achieved_gbps,
            "roofline_util": achieved_gbps / self.hbm_gbps,
            "mfu": cost["flops"] / dev / (self.peak_tflops * 1e12),
            # the sentinel's food: wall past the roofline-ideal wall for
            # this tick's bytes, in µs — utilization drops = deficit
            # grows, so EWMA baselining flags persistent regressions
            "deficit_us": max(dev - ideal_s, 0.0) * 1e6,
            "hbm_gbps": self.hbm_gbps,
        }

    def attribute(self, cost: dict[str, Any],
                  device_time_s: float) -> None:
        """Apportion one tick's bill to its requests: KV bytes exact
        per row, weight bytes and device time by token share.  Sums
        conserve (test-pinned)."""
        total_tokens = cost["tokens"]
        if total_tokens <= 0:
            return
        wb = cost["weight_bytes"]
        for req, t, kv_read, kv_write in cost["rows"]:
            frac = t / total_tokens
            req.kv_bytes_read += kv_read
            req.kv_bytes_written += kv_write
            req.weight_bytes_amortized += wb * frac
            req.device_time_s += device_time_s * frac
