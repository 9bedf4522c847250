"""Parameters, streamed bytes and operations of a ``falcon_h1`` stack, from
its configuration keys (``costs.py`` knows one kind of layer, a dense GQA
block; here every block also runs a Mamba-2 mixer with a recurrent state).

Per layer, from the published equations (PERF.md section 4):

- attention: q and o H x (heads x D), k and v H x (kv heads x D);
- mixer: ``in_proj`` H x (2 x d_ssm + 2 x groups x d_state + heads), a
  depthwise convolution of ``mamba_d_conv`` taps + bias over (d_ssm + 2 x
  groups x d_state) channels, ``dt_bias`` / ``A_log`` / ``D`` one scalar a
  head each, the gated norm d_ssm, ``out_proj`` d_ssm x H;
- feed-forward: 3 x H x ``intermediate_size``;
- two H-wide norms a layer, one after the last; the embedding and the
  (untied) head, vocabulary x H each.

What a tick has to move: every layer's weights and the head once (of the
embedding only the rows of the tick's tokens), K/V of the live context, and
for every row the tick touches the row's recurrent state ``H`` (heads x
d_head x d_state, float32) and its convolution history ((taps - 1) x
channels, served dtype), each READ AND WRITTEN, in every layer.
Operations: a matmul costs 2 x its weights a token, the head a sampled row,
the recurrence about 6 x d_head x d_state a head and token.
"""

from __future__ import annotations

from costs import ITEMSIZE, least_seconds  # noqa: F401 - re-exported


def _head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def _d_ssm(c: dict) -> int:
    return c.get("mamba_d_ssm") or c["mamba_expand"] * c["hidden_size"]


def conv_channels(c: dict) -> int:
    return _d_ssm(c) + 2 * c.get("mamba_n_groups", 1) * c["mamba_d_state"]


def attn_params(c: dict) -> int:
    h, d = c["hidden_size"], _head_dim(c)
    return 2 * h * c["num_attention_heads"] * d + 2 * h * c["num_key_value_heads"] * d


def mixer_parts(c: dict) -> dict[str, int]:
    h, d_ssm, heads = c["hidden_size"], _d_ssm(c), c["mamba_n_heads"]
    taps = c.get("mamba_d_conv", 4) + (1 if c.get("mamba_conv_bias", True) else 0)
    return dict(in_proj=h * (d_ssm + conv_channels(c) + heads),
                conv=conv_channels(c) * taps, scalars=3 * heads, norm=d_ssm,
                out_proj=d_ssm * h)


def mixer_params(c: dict) -> int:
    return sum(mixer_parts(c).values())


def ff_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_params(c: dict) -> int:
    return attn_params(c) + mixer_params(c) + ff_params(c) + 2 * c["hidden_size"]


def head_params(c: dict) -> int:
    return c["vocab_size"] * c["hidden_size"]


def param_count(c: dict) -> int:
    """Embedding, the untied head, the final norm and every layer."""
    return (2 * head_params(c) + c["hidden_size"]
            + c["num_hidden_layers"] * layer_params(c))


def weight_bytes(c: dict, dtype: str = "bf16") -> int:
    """(The 3 x heads float32 scalars a layer are counted at ``dtype``:
    576 B of 10.5 GB.)"""
    return param_count(c) * ITEMSIZE[dtype]


def kv_bytes_per_token(c: dict, dtype: str = "bf16") -> int:
    """K and V of every layer: each has attention."""
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"] * _head_dim(c)
            * ITEMSIZE[dtype])


def ssm_state_bytes_per_row(c: dict) -> int:
    """ONE layer's recurrent state of one row: float32 whatever is served."""
    return c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"] * 4


def conv_state_bytes_per_row(c: dict, dtype: str = "bf16") -> int:
    """ONE layer's convolution history of one row."""
    return (c.get("mamba_d_conv", 4) - 1) * conv_channels(c) * ITEMSIZE[dtype]


def state_bytes_per_slot(c: dict, dtype: str = "bf16") -> int:
    """What a slot holds besides K/V, all layers."""
    return c["num_hidden_layers"] * (
        ssm_state_bytes_per_row(c) + conv_state_bytes_per_row(c, dtype))


def streamed_params(c: dict) -> int:
    """Weights a tick reads whole: all but the embedding matrix."""
    return param_count(c) - head_params(c)


def tick_cost(c: dict, *, tokens: float, rows: float, context_tokens: float,
              state_rows: float | None = None, dtype: str = "bf16",
              cache_dtype: str = "bf16") -> dict:
    """Bytes and operations of one tick on the chip: ``tokens`` packed
    tokens, ``rows`` live rows (each sampled), ``context_tokens`` the summed
    context of the live rows, ``state_rows`` rows whose state the tick read
    and wrote (``rows`` where the program does not say)."""
    d, layers = _head_dim(c), c["num_hidden_layers"]
    state_rows = rows if state_rows is None else state_rows
    parts = dict(
        layer_weights=layers * layer_params(c) * ITEMSIZE[dtype],
        head=(head_params(c) + c["hidden_size"]) * ITEMSIZE[dtype],
        embedding_rows=tokens * c["hidden_size"] * ITEMSIZE[dtype],
        state=2 * state_rows * state_bytes_per_slot(c, dtype),
        kv=kv_bytes_per_token(c, cache_dtype) * (context_tokens + tokens))
    attended = context_tokens * tokens / max(rows, 1.0)
    flops = (2 * layers * (layer_params(c) - 2 * c["hidden_size"]) * tokens
             + 2 * head_params(c) * rows
             + 4 * d * c["num_attention_heads"] * layers * attended
             + 6 * c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"]
             * layers * tokens)
    return dict(bytes=sum(parts.values()), flops=flops, parts=parts)
