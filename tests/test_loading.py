"""Checkpoint loader tests against synthetic HF-format checkpoints (SURVEY §2.1)."""

import json

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from safetensors.numpy import save_file

from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models.transformer import forward, init_params
from llm_np_cp_tpu.utils import synthetic
from llm_np_cp_tpu.utils.loading import load_params, shard_files


def hf_tensors(params_np, model_type):
    """Convert a stacked param pytree into HF-named [out,in] tensors."""
    return synthetic.hf_state_dict(params_np, tiny_config(model_type))


def write_checkpoint(tmp_path, cfg, tensors, shards=2, extra_cfg=None):
    synthetic.write_hf_checkpoint(tmp_path, cfg, tensors, shards=shards,
                                  extra_config=extra_cfg)


@pytest.mark.parametrize("model_type", ["llama", "gemma2"])
def test_roundtrip_sharded(tmp_path, model_type):
    cfg = tiny_config(model_type)
    src = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    src_np = jax.tree.map(lambda x: np.asarray(x, np.float32), src)
    write_checkpoint(tmp_path, cfg, hf_tensors(src_np, model_type), shards=3)

    params, loaded_cfg = load_params(tmp_path, dtype=jnp.float32)
    assert loaded_cfg.model_type == cfg.model_type
    assert loaded_cfg.num_hidden_layers == cfg.num_hidden_layers
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), b), params, src_np
    )

    # loaded params drive a working forward
    logits, _ = forward(params, jnp.array([[1, 2, 3]]), loaded_cfg)
    assert np.isfinite(np.asarray(logits)).all()


def test_single_file_fallback(tmp_path):
    """Index-less checkpoints load via model.safetensors (the reference's
    fallback path, llama3.2_model.py:1063-1065)."""
    cfg = tiny_config("llama", num_hidden_layers=2)
    src_np = jax.tree.map(
        lambda x: np.asarray(x, np.float32),
        init_params(jax.random.PRNGKey(1), cfg, dtype=jnp.float32),
    )
    save_file(hf_tensors(src_np, "llama"), str(tmp_path / "model.safetensors"))
    write_checkpoint(tmp_path, cfg, {}, shards=0)  # writes config.json only

    assert [p.name for p in shard_files(tmp_path)] == ["model.safetensors"]
    params, _ = load_params(tmp_path, dtype=jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(params["embed_tokens"]), src_np["embed_tokens"]
    )


def test_bf16_dtype_policy(tmp_path):
    """bf16 checkpoint tensors load as bf16 without a torch round-trip."""
    cfg = tiny_config("llama", num_hidden_layers=2)
    src_np = jax.tree.map(
        lambda x: np.asarray(x).astype(ml_dtypes.bfloat16),
        init_params(jax.random.PRNGKey(2), cfg, dtype=jnp.float32),
    )
    write_checkpoint(tmp_path, cfg, hf_tensors(src_np, "llama"))
    params, _ = load_params(tmp_path)  # default bf16
    assert params["embed_tokens"].dtype == jnp.bfloat16
    params32, _ = load_params(tmp_path, dtype=jnp.float32)
    assert params32["embed_tokens"].dtype == jnp.float32


def test_untied_lm_head(tmp_path):
    cfg = tiny_config("llama", num_hidden_layers=2, tie_word_embeddings=False)
    src = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    src_np = jax.tree.map(lambda x: np.asarray(x, np.float32), src)
    tensors = hf_tensors(src_np, "llama")
    tensors["lm_head.weight"] = np.ascontiguousarray(src_np["lm_head"].T)
    write_checkpoint(tmp_path, cfg, tensors)
    params, _ = load_params(tmp_path, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(params["lm_head"]), src_np["lm_head"])


def test_incomplete_checkpoint_fails_loudly(tmp_path):
    """No silent partial loads (vs the reference's bare try/except,
    SURVEY §5 failure-detection row)."""
    cfg = tiny_config("llama", num_hidden_layers=2)
    src_np = jax.tree.map(
        lambda x: np.asarray(x, np.float32),
        init_params(jax.random.PRNGKey(4), cfg, dtype=jnp.float32),
    )
    tensors = hf_tensors(src_np, "llama")
    del tensors["model.layers.1.mlp.down_proj.weight"]
    write_checkpoint(tmp_path, cfg, tensors)
    with pytest.raises(ValueError, match="checkpoint incomplete"):
        load_params(tmp_path, dtype=jnp.float32)


def test_shape_mismatch_fails_loudly(tmp_path):
    cfg = tiny_config("llama", num_hidden_layers=2)
    src_np = jax.tree.map(
        lambda x: np.asarray(x, np.float32),
        init_params(jax.random.PRNGKey(5), cfg, dtype=jnp.float32),
    )
    tensors = hf_tensors(src_np, "llama")
    tensors["model.norm.weight"] = np.zeros(7, dtype=np.float32)
    write_checkpoint(tmp_path, cfg, tensors)
    with pytest.raises(ValueError, match="shape") as ei:
        load_params(tmp_path, dtype=jnp.float32)
    # actionable: the error names the shard AND the offending key with
    # expected/actual shapes — not a raw safetensors traceback
    assert ".safetensors" in str(ei.value)
    assert "model.norm.weight" in str(ei.value)


@pytest.mark.chaos
def test_transient_shard_read_error_retries_and_succeeds(tmp_path, monkeypatch):
    """Two injected transient IOErrors on shard reads (the NFS-blip /
    object-store-reset shape): the bounded retry absorbs them and the
    load completes bit-identically."""
    from llm_np_cp_tpu.serve import faults
    from llm_np_cp_tpu.utils import loading

    cfg = tiny_config("llama", num_hidden_layers=2)
    src_np = jax.tree.map(
        lambda x: np.asarray(x, np.float32),
        init_params(jax.random.PRNGKey(6), cfg, dtype=jnp.float32),
    )
    write_checkpoint(tmp_path, cfg, hf_tensors(src_np, "llama"))
    monkeypatch.setattr(loading, "SHARD_READ_BACKOFF_S", 0.0)
    inj = faults.FaultInjector("ckpt_read@1:2")
    faults.install(inj)
    try:
        params, _ = load_params(tmp_path, dtype=jnp.float32)
    finally:
        faults.install(None)
    assert inj.injected["ckpt_read"] == 2
    np.testing.assert_array_equal(
        np.asarray(params["embed_tokens"]), src_np["embed_tokens"]
    )


@pytest.mark.chaos
def test_persistent_shard_read_error_fails_actionably(tmp_path, monkeypatch):
    """More consecutive IOErrors than the retry budget: the final error
    names the shard and the attempt count."""
    from llm_np_cp_tpu.serve import faults
    from llm_np_cp_tpu.utils import loading

    cfg = tiny_config("llama", num_hidden_layers=2)
    src_np = jax.tree.map(
        lambda x: np.asarray(x, np.float32),
        init_params(jax.random.PRNGKey(7), cfg, dtype=jnp.float32),
    )
    write_checkpoint(tmp_path, cfg, hf_tensors(src_np, "llama"))
    monkeypatch.setattr(loading, "SHARD_READ_BACKOFF_S", 0.0)
    faults.install(faults.FaultInjector("ckpt_read@1:99"))
    try:
        with pytest.raises(OSError, match="after 3 attempts") as ei:
            load_params(tmp_path, dtype=jnp.float32)
    finally:
        faults.install(None)
    assert ".safetensors" in str(ei.value)


def test_hf_config_dict_roundtrips_every_preset():
    """synthetic.hf_config_dict is what chip_smoke.py writes as
    config.json: the loader must read back an EQUAL config, or the
    smoke would quietly serve a different model than the one it names."""
    from llm_np_cp_tpu.config import PRESETS, ModelConfig

    for cfg in [*PRESETS.values(), tiny_config("qwen2"),
                tiny_config("gemma2"), tiny_config("llama")]:
        assert ModelConfig.from_hf_dict(synthetic.hf_config_dict(cfg)) == cfg


def test_random_checkpoint_is_seeded_and_loadable(tmp_path):
    cfg = tiny_config("qwen2")
    for d, seed in (("a", 0), ("b", 0), ("c", 1)):
        synthetic.write_random_checkpoint(tmp_path / d, cfg, seed=seed,
                                          dtype=np.float32, workers=3)
    a, cfg_a = load_params(tmp_path / "a", dtype=jnp.float32, on_host=True)
    b, _ = load_params(tmp_path / "b", dtype=jnp.float32, on_host=True)
    c, _ = load_params(tmp_path / "c", dtype=jnp.float32, on_host=True)
    assert cfg_a == cfg
    assert isinstance(a["embed_tokens"], np.ndarray)  # on_host: no device
    jax.tree.map(np.testing.assert_array_equal, a, b)
    assert not np.array_equal(a["embed_tokens"], c["embed_tokens"])
    assert np.all(a["final_norm"] == 1.0)
    assert 0.015 < float(a["embed_tokens"].std()) < 0.025
