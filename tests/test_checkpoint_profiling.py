"""Orbax checkpoint save/resume (SURVEY §5 rows)."""

import jax
import jax.numpy as jnp
import numpy as np

from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.parallel.sharding import MeshPlan, make_mesh, shard_params
from llm_np_cp_tpu.train import default_optimizer, make_train_step
from llm_np_cp_tpu.utils.checkpoint import restore_checkpoint, save_checkpoint


def test_checkpoint_roundtrip(tmp_path):
    cfg = tiny_config("llama", num_hidden_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    state = {"params": params, "step": np.int32(7)}
    save_checkpoint(tmp_path / "ckpt", state)
    restored = restore_checkpoint(tmp_path / "ckpt")
    assert int(restored["step"]) == 7
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        restored["params"], params,
    )


def test_checkpoint_resume_training(tmp_path):
    """Save mid-training, restore, continue — losses continue from the same
    trajectory (resume capability the reference lacks)."""
    cfg = tiny_config(
        "llama", num_attention_heads=8, num_key_value_heads=4,
        head_dim=8, hidden_size=64,
    )
    opt = default_optimizer(1e-3)
    step = make_train_step(cfg, opt)
    batch = jnp.asarray(
        np.random.default_rng(0).integers(0, 255, (2, 12)), jnp.int32
    )

    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    opt_state = opt.init(params)
    for _ in range(2):
        params, opt_state, _ = step(params, opt_state, batch)
    save_checkpoint(tmp_path / "mid", {"params": params, "opt_state": opt_state})
    params_c, opt_state_c, loss_c = step(params, opt_state, batch)

    restored = restore_checkpoint(
        tmp_path / "mid", like={"params": params, "opt_state": opt_state}
    )
    _, _, loss_r = step(restored["params"], restored["opt_state"], batch)
    assert float(loss_r) == float(loss_c)


def test_checkpoint_restore_onto_mesh(tmp_path):
    cfg = tiny_config(
        "llama", num_attention_heads=8, num_key_value_heads=4,
        head_dim=8, hidden_size=64,
    )
    params = init_params(jax.random.PRNGKey(1), cfg, dtype=jnp.float32)
    save_checkpoint(tmp_path / "m", {"params": params})

    plan = MeshPlan(model=4)
    mesh = make_mesh(plan)
    target = shard_params(params, cfg, plan, mesh)
    restored = restore_checkpoint(tmp_path / "m", like={"params": target})
    leaf = restored["params"]["layers"]["q_proj"]
    assert len(leaf.sharding.device_set) == 4  # actually sharded on restore
    np.testing.assert_array_equal(
        np.asarray(leaf), np.asarray(params["layers"]["q_proj"])
    )


def test_quantized_params_checkpoint_roundtrip(tmp_path):
    """Quantized pytrees ({q|qa|q4, s} dict leaves) save/restore through
    orbax unchanged — quantize once, serve from the checkpoint."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_np_cp_tpu.config import tiny_config
    from llm_np_cp_tpu.models.transformer import init_params
    from llm_np_cp_tpu.quant import quantize_params
    from llm_np_cp_tpu.utils.checkpoint import restore_checkpoint, save_checkpoint

    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    for kwargs in (dict(bits=8), dict(bits=4), dict(bits=8, act_quant=True)):
        q = quantize_params(params, **kwargs)
        path = tmp_path / f"ck_{kwargs.get('bits')}_{kwargs.get('act_quant', False)}"
        save_checkpoint(path, {"params": q, "step": 7})
        back = restore_checkpoint(path)
        assert int(back["step"]) == 7
        flat_a = jax.tree.leaves(q)
        flat_b = jax.tree.leaves(back["params"])
        assert len(flat_a) == len(flat_b)
        for a, b in zip(flat_a, flat_b):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
