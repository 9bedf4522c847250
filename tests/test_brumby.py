"""Brumby's language model (``model_type: brumby``) on the CPU at a tiny size,
seeded random float32 weights: the declaration (``from_hf_dict`` on the catalog
row's keys, each refusal by its key, a stack with NO layer that has pages),
``models.forward`` with and without the offline cache against
``benchmark/reference_brumby.py`` (the attention form: no feature map, no
state) on logits, what the reference's controls change, the cost file's
arithmetic and the checkpoint names.  The served engine is
tests/test_brumby_serve.py, the recurrence itself tests/test_retention.py.
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmark"))

import costs_brumby  # noqa: E402
import reference_brumby as ref  # noqa: E402

from llm_np_cp_tpu.cache import KVCache  # noqa: E402
from llm_np_cp_tpu.config import (  # noqa: E402
    KNOWN_MODEL_TYPES,
    STATE_ONLY_OPS,
    ModelConfig,
    tiny_config,
)
from llm_np_cp_tpu.models.transformer import forward, init_params  # noqa: E402
from llm_np_cp_tpu.utils.synthetic import hf_config_dict  # noqa: E402

# largest logit difference as a share of the reference's spread: float32
# against float32, the state form against the attention form (a token whose
# query is nearly orthogonal to the keys it weighs is conditioned by |q|^2
# |k|^2 / (q . k)^2: tests/test_retention.py)
TOL = 1e-4
CELL_FILE = ROOT / "benchmark" / "configs" / "brumby-14b-5l.json"


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("brumby")
    hf = hf_config_dict(cfg)
    assert cfg == ModelConfig.from_hf_dict(hf)
    return cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32), hf


def _gap(got: np.ndarray, want: np.ndarray) -> float:
    spread = float((want.max(-1) - want.mean(-1)).mean())
    return float(np.abs(got - want).max()) / spread


_REF: dict = {}


def _reference(params, hf, seq, controls=frozenset()) -> np.ndarray:
    """The reference's logits for ``seq``, on the sequence padded to a
    multiple of 32 tokens (causal: what follows cannot change a position)."""
    n = -(-len(seq) // 32) * 32
    if (n, controls) not in _REF:
        _REF[n, controls] = jax.jit(
            lambda p, ids: ref.forward(p, hf, ids, controls))
    ids = np.zeros((n,), np.int32)
    ids[:len(seq)] = seq
    return np.asarray(_REF[n, controls](params, ids))[:len(seq)]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


# ----------------------------------------------------------------------
# the declaration
# ----------------------------------------------------------------------

def test_the_cells_file_declares_a_stack_with_no_pages_at_all():
    d = json.loads(CELL_FILE.read_text())
    cfg = ModelConfig.from_hf_dict(d)
    assert "brumby" in KNOWN_MODEL_TYPES and "retention" in STATE_ONLY_OPS
    assert [cfg.layer_op(i) for i in range(5)] == ["retention"] * 5
    assert cfg.layer_groups() == (("retention", "dense", 0, 5),)
    assert cfg.attn_layers == () and cfg.retention_layers == (0, 1, 2, 3, 4)
    assert cfg.global_layers == () and cfg.window_layers == ()
    assert cfg.carries_state and cfg.is_hybrid and not cfg.is_latent
    assert cfg.state_kind == "power-retention"
    assert cfg.kv_bytes_per_token() == 0
    # two float32 leaves a kv head: S over the monomials in whole registers
    # (8,704 rows for 8,256) and Z = sum k k^T
    from llm_np_cp_tpu.ops import retention

    assert cfg.retention_rows == retention.phi_rows(cfg.head_dim) == 8704
    assert not cfg.has_pages and tiny_config("llama").has_pages
    assert cfg.state_shapes(32, "bfloat16") == {
        "retention": ((5, 32, 8, 8704, 128), "float32"),
        "retention_z": ((5, 32, 8, 128, 128), "float32")}
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
            ) == (40, 8, 128)
    assert cfg.qk_norm and not cfg.tie_word_embeddings
    assert cfg.rope_theta == 1000000 and cfg.rms_norm_eps == 1e-6
    assert cfg.max_position_embeddings == 32768
    # every key of the catalog row is in the file, unchanged but the depth
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Brumby-14B-Base"' in line) if Path(
        "/opt/skills/guides/model-configs/architectures.jsonl").exists() else None
    if row is not None:
        assert {k for k, v in row["config"].items() if d[k] != v} == {
            "num_hidden_layers"}
    assert d["reduced"] == ["num_hidden_layers"] and d["source"].endswith(
        "manifestai/Brumby-14B-Base/blob/main/config.json")
    # the file's sizes are the cost file's, which reads the program's shapes
    sizes = d["sizes"]
    assert costs_brumby.param_count(d) == sizes["parameters"] == 3207594240
    assert costs_brumby.weight_bytes(d) == sizes["weight_bytes_bf16"]
    assert costs_brumby.state_bytes_per_row(d) == sizes[
        "state_bytes_per_slot_and_layer_f32_needed"] == 34080768
    assert costs_brumby.state_bytes_held_per_row(d) == sizes[
        "state_bytes_per_slot_and_layer_f32_held"] == 36175872
    held = sizes["weight_bytes_bf16"] + 32 * 5 * 36175872
    assert held == sizes["held_bytes_before_temporaries"]
    assert abs(held / 2**20 / 11318 - 1) < 0.03
    whole = ModelConfig.from_hf_dict(dict(d, num_hidden_layers=40))
    assert whole.attn_layers == () and len(whole.retention_layers) == 40
    # a decode tick of 32 rows: 15.8 GB, 69 % of it the state
    cost = costs_brumby.tick_cost(d, tokens=32, rows=32, state_rows=32)
    assert round(cost["bytes"] / 1e9, 1) == 15.8
    assert round(costs_brumby.state_update_bytes(d, 32) / cost["bytes"], 2) == 0.69


@pytest.mark.parametrize("key, value", [
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("use_sliding_window", True), ("attention_bias", True),
    ("tie_word_embeddings", True), ("mlp_bias", True),
    ("retention_degree", 4), ("model_type", "brumby_v2"),
])
def test_what_has_no_equations_is_refused_by_its_key(key, value):
    d = json.loads(CELL_FILE.read_text())
    match = {"model_type": "unknown model_type 'brumby_v2'",
             "retention_degree": "degree 2"}.get(key, key)
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_dict(dict(d, **{key: value}))


# ----------------------------------------------------------------------
# the forward against the reference
# ----------------------------------------------------------------------

def test_forward_is_the_attention_form_reference(tiny):
    """Ragged right-padded rows in one call: the state form over whole
    sequences (a chunk boundary inside: 70 > 64) against the reference's
    attention form, which has no feature map and no state."""
    cfg, params, hf = tiny
    prompts = _prompts([70, 33, 9])
    ids = np.zeros((3, 70), np.int32)
    mask = np.zeros((3, 70), bool)
    for i, p in enumerate(prompts):
        ids[i, :len(p)], mask[i, :len(p)] = p, True
    logits, _ = jax.jit(lambda p, i, m: forward(p, i, cfg, attn_mask=m))(
        params, ids, mask)
    for i, p in enumerate(prompts):
        want = _reference(params, hf, p)
        assert _gap(np.asarray(logits[i, :len(p)]), want) <= TOL, i


def test_prefill_then_decode_through_the_cache_is_the_reference(tiny):
    """A prompt in two chunks, then token by token through ``KVCache``'s two
    retention leaves: every position's logits are the reference's full
    forward's."""
    cfg, params, hf = tiny
    seq = _prompts([41], seed=3)[0]
    cache = KVCache.init(cfg, 1, 64, dtype=jnp.float32)
    assert cache.k.shape[0] == 0 and cache.retention.shape == (3, 1, 2, 64, 8)
    step = jax.jit(lambda p, i, c: forward(p, i, cfg, c))
    got = []
    for lo, hi in ((0, 17), (17, 30)):
        lg, cache = step(params, jnp.asarray([seq[lo:hi]]), cache)
        got.append(np.asarray(lg[0]))
    for t in range(30, 41):
        lg, cache = step(params, jnp.asarray([[seq[t]]]), cache)
        got.append(np.asarray(lg[0]))
    assert int(cache.length) == 41
    assert _gap(np.concatenate(got), _reference(params, hf, seq)) <= TOL


@pytest.mark.parametrize("control", ["no_normaliser", "no_gate"])
def test_a_control_of_the_reference_is_another_model(tiny, control):
    """What a comparison must be able to tell from the model: the numerator
    alone, and every gate at one, move the logits by a share of the spread
    (the tolerance above is 1e-4 of it)."""
    cfg, params, hf = tiny
    seq = _prompts([40], seed=5)[0]
    want = _reference(params, hf, seq)
    broken = _reference(params, hf, seq, frozenset([control]))
    assert _gap(broken, want) > 100 * TOL


def test_hf_key_map_round_trip(tiny, tmp_path):
    """A checkpoint written under the family's names loads back leaf for
    leaf."""
    from llm_np_cp_tpu.utils.loading import load_params
    from llm_np_cp_tpu.utils.synthetic import (
        hf_state_dict,
        hf_tensor_shapes,
        write_hf_checkpoint,
    )

    cfg, params, _ = tiny
    host = jax.tree.map(np.asarray, params)
    tensors = hf_state_dict(host, cfg)
    assert {k: v.shape for k, v in tensors.items()} == hf_tensor_shapes(cfg)
    assert tensors["model.layers.0.self_attn.g_proj.weight"].shape == (2, 64)
    assert tensors["model.layers.2.self_attn.q_norm.weight"].shape == (8,)
    assert "lm_head.weight" in tensors
    write_hf_checkpoint(tmp_path, cfg, tensors)
    loaded, cfg2 = load_params(tmp_path, dtype=jnp.float32, on_host=True)
    assert cfg2 == cfg
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(a, b)
