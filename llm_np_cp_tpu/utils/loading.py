"""HF checkpoint loading: sharded safetensors → stacked param pytree.

Reference behavior being replaced (SURVEY §2.1, §3.1):
- ``load_sharded_safetensors_via_weight_map`` (llama3.2_model.py:1033-1073)
  parses ``model.safetensors.index.json``, loads every shard into one big
  host dict of torch tensors, with a bare try/except falling back to
  single-file ``model.safetensors``;
- ``load_weights(key)`` then copies each tensor host→device one at a time
  inside every module constructor, with weight tying done by rewriting the
  key ``lm_head.weight`` → ``model.embed_tokens.weight`` (:1077-1078);
- dtype policy is inconsistent: Llama casts to fp32, Gemma keeps checkpoint
  dtype (gemma2_model.py:1137-1138).

TPU-native design:
- torch-free: safetensors' numpy framework reads bf16 via ml_dtypes;
- streaming: tensors are copied shard-by-shard directly into preallocated
  stacked host buffers ``[num_layers, ...]`` (the layout ``lax.scan``
  consumes), so peak host memory is one shard + the param set — not the
  reference's full-dict-then-model double residency (important for 9B);
- projections are transposed once to (in, out) at load;
- explicit dtype policy (bf16 default, fp32 for parity runs);
- optional ``shardings`` pytree: each stacked buffer is ``jax.device_put``
  onto its mesh sharding as soon as it completes, so a TP-sharded load
  never materializes the full model on one chip.

Weight tying: with ``tie_word_embeddings`` the checkpoint has no
``lm_head.weight`` and the forward pass reuses ``embed_tokens`` directly —
same semantics as the reference's key rewrite, zero extra memory.
"""

from __future__ import annotations

import json
import logging
import re
import time
from pathlib import Path
from typing import Any, Callable

import jax
import ml_dtypes
import numpy as np
from safetensors import safe_open

from llm_np_cp_tpu.config import ModelConfig
from llm_np_cp_tpu.models import (
    afmoe,
    brumby,
    deepseek_v3,
    falcon_h1,
    gemma2,
    glm_moe_dsa,
    lfm2_moe,
    ling_hybrid,
    llama,
    mimo_v2,
    qwen2,
)
from llm_np_cp_tpu.models.transformer import CONV_FILTER_LEAVES, param_shapes

log = logging.getLogger("llm_np_cp_tpu")

_LAYER_RE = re.compile(r"^model\.layers\.(\d+)\.(.+)$")

# Transient shard-read IO (NFS blips, object-store mounts dropping a
# connection) gets a bounded retry instead of killing a multi-minute
# load; backoff doubles per attempt.  Module-level so tests can shrink
# the backoff.
SHARD_READ_RETRIES = 2
SHARD_READ_BACKOFF_S = 0.5

# These OSError subclasses are configuration mistakes, not flaky IO —
# retrying a missing file three times only delays and mislabels the
# diagnosis.
_PERMANENT_OS_ERRORS = (
    FileNotFoundError, PermissionError, IsADirectoryError,
    NotADirectoryError,
)

# Fault-injection seam: when set, called with the shard path before each
# read attempt and may raise OSError to simulate transient IO.  Wired by
# llm_np_cp_tpu.serve.faults.install() — the hook lives HERE so
# checkpoint loading never imports the serving stack (utils stays below
# serve in the layering).
SHARD_READ_HOOK: Callable[[Path], None] | None = None


def _read_shard(
    path: Path, use_native: bool, consume: Callable[[Any, bool], None],
) -> None:
    """Open one shard and run ``consume(f, native)`` over it, with a
    bounded retry on transient ``OSError`` and shard-named, actionable
    errors otherwise — a failed 9B load must say WHICH shard and tensor
    disagreed, not dump a raw safetensors traceback.

    Retrying the whole shard is safe: ``consume`` only copies tensors
    into preallocated buffers (idempotent) and ``filled`` is a set.
    """
    for attempt in range(SHARD_READ_RETRIES + 1):
        try:
            if SHARD_READ_HOOK is not None:
                SHARD_READ_HOOK(path)
            f, native = _open_shard(path, use_native)
            with f:
                consume(f, native)
            log.info("%s: read through the %s reader", path.name,
                     "native C++" if native else "python safetensors")
            return
        except _PERMANENT_OS_ERRORS:
            raise  # the OS message already names the path
        except OSError as e:
            if attempt >= SHARD_READ_RETRIES:
                raise OSError(
                    f"{path.name}: shard read failed after "
                    f"{SHARD_READ_RETRIES + 1} attempts: {e}"
                ) from e
            time.sleep(SHARD_READ_BACKOFF_S * (2 ** attempt))
        except ValueError as e:
            # size/key mismatch — permanent; name the shard and re-raise
            raise ValueError(f"{path.name}: {e}") from e


# leaves that stay float32 whatever is served: the experts' selection
# bias, a state-space recurrence's own scalars
F32_LEAVES = (frozenset(("expert_bias",)) | falcon_h1.F32_LEAVES
              | mimo_v2.F32_LEAVES | ling_hybrid.F32_LEAVES)
# depthwise Conv1d weights, stored [C, 1, taps]
CONV1D_LEAVES = CONV_FILTER_LEAVES


def hybrid_family(config: ModelConfig):
    """The family module whose ``layer_tensors`` places a hybrid stack's
    checkpoint tensors (``(HF key, run, leaf, index, transpose?)``)."""
    return {"falcon_h1": falcon_h1, "deepseek_v3": deepseek_v3,
            "mimo_v2": mimo_v2, "ling_hybrid": ling_hybrid,
            "afmoe": afmoe, "brumby": brumby,
            "glm_moe_dsa": glm_moe_dsa}.get(
        config.model_type, lfm2_moe)


def _key_maps(config: ModelConfig):
    if config.is_hybrid:
        # a per-layer tensor's place is not leaf[layer] there: the
        # family's own table (``layer_tensors``) says where
        return {}, hybrid_family(config).TOP_KEY_MAP
    family = {"gemma2": gemma2, "qwen2": qwen2}.get(config.model_type, llama)
    return family.LAYER_KEY_MAP, family.TOP_KEY_MAP


def _np_dtype(dtype) -> np.dtype:
    import jax.numpy as jnp

    return np.dtype(
        {jnp.bfloat16: ml_dtypes.bfloat16, jnp.float32: np.float32,
         jnp.float16: np.float16}.get(dtype, dtype)
    )


def shard_files(model_dir: str | Path) -> list[Path]:
    """Resolve checkpoint shards: index file first, single-file fallback
    (the reference's fallback, llama3.2_model.py:1063-1065 — kept, but
    explicit instead of a bare ``except:``)."""
    model_dir = Path(model_dir)
    index = model_dir / "model.safetensors.index.json"
    if index.exists():
        with open(index) as f:
            weight_map: dict[str, str] = json.load(f)["weight_map"]
        return [model_dir / fn for fn in sorted(set(weight_map.values()))]
    single = model_dir / "model.safetensors"
    if single.exists():
        return [single]
    raise FileNotFoundError(
        f"no model.safetensors.index.json or model.safetensors in {model_dir}"
    )


def _open_shard(path: Path, use_native: bool):
    """Returns (file, native: bool).  The native reader mmaps the shard and
    does threaded transpose/cast (llm_np_cp_tpu/native); the safetensors
    python reader is the fallback."""
    if use_native:
        from llm_np_cp_tpu.native import NativeSafetensorsFile, is_available

        if is_available():
            try:
                return NativeSafetensorsFile(path), True
            except (OSError, ValueError) as e:
                log.warning("%s: native reader refused the shard (%s: %s); "
                            "using the python safetensors reader",
                            path.name, type(e).__name__, e)
    return safe_open(path, framework="np"), False


def load_params(
    model_dir: str | Path,
    config: ModelConfig | None = None,
    *,
    dtype=None,
    shardings: Any = None,
    use_native: bool = True,
    on_host: bool = False,
) -> tuple[dict[str, Any], ModelConfig]:
    """Load an HF checkpoint directory into the model's param pytree.

    dtype: target dtype (default jnp.bfloat16; pass jnp.float32 for parity).
    shardings: optional pytree of jax.sharding.Sharding matching the param
        tree; each buffer is device_put onto it as soon as it is filled.
    use_native: route tensor bytes through the C++ reader when built.
    on_host: return the stacked numpy buffers without touching a device —
        for callers that place the params themselves on devices that are
        not the default one (N serve replicas, each on its own chip: a
        load onto the default device would pile every copy's source onto
        chip 0 and keep it there).
    Returns (params, config).
    """
    import jax.numpy as jnp

    model_dir = Path(model_dir)
    if config is None:
        config = ModelConfig.from_json(model_dir / "config.json")
    dtype = dtype or jnp.bfloat16
    np_dtype = _np_dtype(dtype)
    layer_map, top_map = _key_maps(config)
    shapes = param_shapes(config)

    # Preallocated stacked host buffers.
    def buffers(leaves: dict) -> dict:
        return {name: np.empty(shape, dtype=(
            np.float32 if name in F32_LEAVES else np_dtype))
            for name, shape in leaves.items()}

    host: dict[str, Any] = {
        "embed_tokens": np.empty(shapes["embed_tokens"], dtype=np_dtype),
        "final_norm": np.empty(shapes["final_norm"], dtype=np_dtype),
        "layers": ([buffers(g) for g in shapes["layers"]]
                   if config.is_hybrid else buffers(shapes["layers"])),
    }
    # a hybrid stack's per-layer tensors: HF key → (run, leaf, index, T?)
    placed = {key: rest for key, *rest
              in hybrid_family(config).layer_tensors(config)
              } if config.is_hybrid else {}
    if "lm_head" in shapes:
        host["lm_head"] = np.empty(shapes["lm_head"], dtype=np_dtype)

    filled: set[str] = set()

    def fill(f, native: bool, key: str, dest: np.ndarray, transpose: bool) -> None:
        if native:
            try:
                f.copy_into(key, dest, transpose=transpose)
            except ValueError as e:
                raise ValueError(f"{key}: checkpoint shape mismatch: {e}") from e
            return
        value = f.get_tensor(key)
        if transpose:
            value = value.T
        if value.ndim == 3 and dest.ndim == 2 and value.shape[1] == 1:
            value = value[:, 0]  # a depthwise Conv1d weight [H, 1, L]
        if dest.shape != value.shape:
            raise ValueError(
                f"{key}: checkpoint shape {value.shape} != expected {dest.shape}"
            )
        dest[...] = value.astype(dest.dtype)

    def consume(f: Any, native: bool) -> None:
        for key in f.keys():
            if key in placed:
                run, leaf, index, transpose = placed[key]
                dest = host["layers"][run][leaf][index]
                if native and leaf in CONV1D_LEAVES:
                    dest = dest[:, None, :]  # as stored: [H, 1, L]
                fill(f, native, key, dest, transpose)
                filled.add(key)
                continue
            m = _LAYER_RE.match(key)
            if m and not placed:
                idx, suffix = int(m.group(1)), m.group(2)
                if suffix not in layer_map:
                    continue  # e.g. rotary inv_freq buffers
                name, transpose = layer_map[suffix]
                if name not in host["layers"]:
                    if name.endswith("_bias"):
                        # A bias tensor the config gated OFF is
                        # PRESENT in the checkpoint — loading would
                        # silently drop it and produce wrong logits
                        # (the round-1 silent-wrongness class)
                        raise ValueError(
                            f"{key}: checkpoint carries this bias but "
                            f"the config disables it "
                            f"(attention_bias={config.attention_bias}, "
                            f"attention_out_bias={config.attention_out_bias}, "
                            f"mlp_bias={config.mlp_bias})"
                        )
                    continue
                fill(f, native, key, host["layers"][name][idx], transpose)
                filled.add(f"layers.{name}.{idx}")
            elif key in top_map:
                name, transpose = top_map[key]
                if name == "lm_head" and config.tie_word_embeddings:
                    continue  # tied: forward reuses embed_tokens
                if name not in host:
                    continue
                fill(f, native, key, host[name], transpose)
                filled.add(name)

    for path in shard_files(model_dir):
        _read_shard(path, use_native, consume)

    if placed:
        missing = sorted(set(placed) - filled)
        if missing:
            raise ValueError(
                f"checkpoint incomplete: {len(missing)} tensors missing "
                f"({', '.join(missing[:6])}"
                + (", ..." if len(missing) > 6 else "") + ")")
    _check_complete(host, filled, config)

    def place(path_: tuple, buf: np.ndarray):
        if on_host:
            return buf
        if shardings is not None:
            shard = _tree_get(shardings, path_)
            if shard is not None:
                return jax.device_put(buf, shard)
        return jax.device_put(jnp.asarray(buf))

    params: dict[str, Any] = {}
    for k, v in host.items():
        if isinstance(v, dict):
            params[k] = {k2: place((k, k2), v2) for k2, v2 in v.items()}
        elif isinstance(v, list):
            params[k] = [{k2: place((k, i, k2), v2) for k2, v2 in g.items()}
                         for i, g in enumerate(v)]
        else:
            params[k] = place((k,), v)
    return params, config


def _tree_get(tree: Any, path: tuple):
    node = tree
    for p in path:
        if node is None:
            return None
        if isinstance(node, dict):
            node = node.get(p)
        elif isinstance(node, (list, tuple)) and isinstance(p, int):
            node = node[p] if p < len(node) else None
        else:
            node = None
    return node


def _check_complete(host: dict, filled: set, config: ModelConfig) -> None:
    missing: list[str] = []
    for name in host:
        if name == "layers" and isinstance(host["layers"], list):
            continue  # a hybrid stack: checked against its own table
        if name == "layers":
            for lname in host["layers"]:
                for i in range(config.num_hidden_layers):
                    if f"layers.{lname}.{i}" not in filled:
                        missing.append(f"model.layers.{i}.<{lname}>")
        elif name not in filled:
            missing.append(name)
    if missing:
        preview = ", ".join(missing[:6])
        raise ValueError(
            f"checkpoint incomplete: {len(missing)} tensors missing ({preview}"
            + (", ..." if len(missing) > 6 else "") + ")"
        )


# ----------------------------------------------------------------------
# Convenience: the reference's load_model() equivalent
# ----------------------------------------------------------------------

def load_model(
    model_name_or_dir: str,
    *,
    dtype=None,
    shardings: Any = None,
    tokenizer: bool = True,
    on_host: bool = False,
):
    """(tokenizer, params, config) from a local dir or an HF repo id.

    Mirrors the reference's ``load_model`` surface (llama3.2_model.py:
    1082-1099) — AutoTokenizer + snapshot_download + weight load — but
    network access is attempted only when the argument is not an existing
    local directory.
    """
    path = Path(model_name_or_dir)
    if not path.exists():
        from huggingface_hub import snapshot_download

        path = Path(snapshot_download(repo_id=model_name_or_dir))
    tok = None
    if tokenizer:
        from transformers import AutoTokenizer

        tok = AutoTokenizer.from_pretrained(str(path))
    params, config = load_params(path, dtype=dtype, shardings=shardings,
                                 on_host=on_host)
    return tok, params, config
