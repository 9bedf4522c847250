"""Mixture-of-Experts layer + expert parallelism.

Framework extension beyond the reference (SURVEY §2.9 lists EP as N/A):
Mixtral-style top-k routed SwiGLU experts via static dispatch/combine
einsums.  Invariants:
- a 1-expert MoE is exactly the dense model (routing collapses to identity)
- EP/TP-sharded MoE logits match the unsharded ones
- training decreases the combined loss; router gradients are nonzero
- cached decode equals the no-cache forward (MoE in the decode path)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.cache import KVCache
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models.transformer import forward, init_params
from llm_np_cp_tpu.ops.moe import moe_mlp
from llm_np_cp_tpu.parallel.sharding import (
    MeshPlan,
    batch_spec,
    make_mesh,
    shard_params,
    to_shardings,
)
from llm_np_cp_tpu.train import causal_lm_loss, default_optimizer, make_train_step


def _moe_cfg(**over):
    kw = dict(num_local_experts=4, num_experts_per_tok=2)
    kw.update(over)
    return tiny_config("llama", **kw)


def test_single_expert_equals_dense():
    cfg_moe = _moe_cfg(num_local_experts=1, num_experts_per_tok=1)
    cfg_dense = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg_moe, dtype=jnp.float32)
    dense_params = jax.tree.map(lambda x: x, params)
    layers = dict(dense_params["layers"])
    del layers["router"]
    for k in ("gate_proj", "up_proj", "down_proj"):
        layers[k] = layers[k][:, 0]  # squeeze the 1-expert axis
    dense_params["layers"] = layers

    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg_moe.vocab_size, (2, 10)), jnp.int32
    )
    got, _ = forward(params, ids, cfg_moe, None)
    want, _ = forward(dense_params, ids, cfg_dense, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_moe_forward_finite_and_aux_loss():
    cfg = _moe_cfg()
    params = init_params(jax.random.PRNGKey(1), cfg, dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 16)), jnp.int32
    )
    logits, _, aux = forward(params, ids, cfg, None, output_router_losses=True)
    assert np.all(np.isfinite(np.asarray(logits)))
    aux_loss = float(aux["moe_aux_loss"])
    # balanced routing gives ~1.0; any valid routing is >= 1 in expectation
    assert 0.5 < aux_loss < 4.0


def test_moe_capacity_drop_is_graceful():
    """With a tiny capacity factor most tokens overflow; output must stay
    finite (dropped tokens ride the residual)."""
    cfg = _moe_cfg(moe_capacity_factor=0.05)
    params = init_params(jax.random.PRNGKey(2), cfg, dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 32)), jnp.int32
    )
    logits, _ = forward(params, ids, cfg, None)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_moe_cached_decode_matches_nocache():
    cfg = _moe_cfg()
    params = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    ids = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab_size, (1, 8)), jnp.int32
    )
    ref, _ = forward(params, ids, cfg, None)

    cache = KVCache.init(cfg, 1, 16, dtype=jnp.float32)
    _, cache = forward(params, ids[:, :5], cfg, cache)
    outs = []
    for i in range(5, 8):
        logits, cache = forward(params, ids[:, i : i + 1], cfg, cache)
        outs.append(logits[:, -1])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref[:, 5:8]), atol=2e-4
    )


def test_moe_ep_tp_sharded_matches_unsharded():
    cfg = _moe_cfg(num_attention_heads=4, num_key_value_heads=2)
    plan = MeshPlan(data=2, expert=2, model=2)
    plan.validate(cfg)
    mesh = make_mesh(plan)
    params = init_params(jax.random.PRNGKey(4), cfg, dtype=jnp.float32)
    sharded = shard_params(params, cfg, plan, mesh)
    ids = jnp.asarray(
        np.random.default_rng(4).integers(0, cfg.vocab_size, (4, 12)), jnp.int32
    )
    want, _ = forward(params, ids, cfg, None)
    with jax.set_mesh(mesh):
        ids_sh = jax.device_put(ids, to_shardings(mesh, batch_spec(plan)))
        got, _ = jax.jit(lambda p, i: forward(p, i, cfg, None))(sharded, ids_sh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_moe_train_step_improves_and_router_learns():
    cfg = _moe_cfg()
    params = init_params(jax.random.PRNGKey(5), cfg, dtype=jnp.float32)
    batch = jnp.asarray(
        np.random.default_rng(5).integers(0, cfg.vocab_size, (4, 16)), jnp.int32
    )
    grads = jax.grad(causal_lm_loss)(params, batch, cfg)
    assert float(jnp.abs(grads["layers"]["router"]).max()) > 0.0

    opt = default_optimizer(1e-2)
    step = make_train_step(cfg, opt)
    opt_state = opt.init(params)
    _, _, loss0 = step(params, opt_state, batch)
    p, s = params, opt_state
    for _ in range(5):
        p, s, loss = step(p, s, batch)
    assert float(loss) < float(loss0)


def test_meshplan_expert_validation():
    with pytest.raises(ValueError, match="requires a MoE config"):
        MeshPlan(expert=2).validate(tiny_config("llama"))
    with pytest.raises(ValueError, match="not divisible"):
        MeshPlan(expert=3).validate(_moe_cfg(num_local_experts=4))


def test_moe_mlp_routes_all_tokens_with_ample_capacity():
    """Direct op test: with capacity_factor covering all tokens, the output
    is a convex combination of expert outputs (weights sum to 1 per token),
    so running with identical experts equals the single dense MLP."""
    rng = np.random.default_rng(6)
    b, s, h, i, e = 2, 8, 16, 32, 4
    x = jnp.asarray(rng.normal(size=(b, s, h)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(h, e)), jnp.float32)
    g1 = jnp.asarray(rng.normal(size=(h, i)) * 0.1, jnp.float32)
    u1 = jnp.asarray(rng.normal(size=(h, i)) * 0.1, jnp.float32)
    d1 = jnp.asarray(rng.normal(size=(i, h)) * 0.1, jnp.float32)
    tile = lambda w: jnp.broadcast_to(w, (e, *w.shape))
    act = jax.nn.silu
    out, _ = moe_mlp(
        x, router, tile(g1), tile(u1), tile(d1),
        act=act, top_k=2, capacity_factor=float(e),  # no drops possible
    )
    want = (act(x @ g1) * (x @ u1)) @ d1
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


# ----------------------------------------------------------------------
# sigmoid routing: the normaliser's epsilon is the configuration's, and a
# holder of a share computes the pairs whose expert it holds
# ----------------------------------------------------------------------

def _sigmoid_layer(seed=0, *, t=24, h=16, i=8, e=8):
    rng = np.random.default_rng(seed)
    draw = lambda *shape, s=1.0: jnp.asarray(rng.normal(size=shape) * s, jnp.float32)
    return dict(x=draw(t, h), router_w=draw(h, e), expert_bias=draw(e, s=0.3),
                w1=draw(e, h, i, s=0.3), w3=draw(e, h, i, s=0.3),
                w2=draw(e, i, h, s=0.3))


@pytest.mark.parametrize("eps, family", [(1e-6, "lfm2_moe"), (1e-20, "deepseek_v3")])
def test_the_normalisers_epsilon_is_the_configurations(eps, family):
    from llm_np_cp_tpu.ops.moe import route_sigmoid_topk

    assert tiny_config(family).router_norm_eps == eps
    layer = _sigmoid_layer()
    x, bias = layer["x"], layer["expert_bias"]
    # a router of zeros scores every expert 0.5: a token's two weights sum
    # to 1 / (1 + eps), which float32 tells from 1 at 1e-6 and not at 1e-20
    idx, wts = route_sigmoid_topk(
        x, layer["router_w"] * 0.0, bias, top_k=2, norm_eps=eps)
    assert idx.shape == (24, 2) and wts.dtype == jnp.float32
    total = np.asarray(wts.sum(-1), np.float64)
    np.testing.assert_allclose(total, 1.0 / (1.0 + eps), rtol=1e-7)
    assert (total < 1.0).all() == (eps == 1e-6)
    # the default is LFM2's, whose cell must not move
    _, default = route_sigmoid_topk(x, layer["router_w"], bias, top_k=2)
    _, lfm2 = route_sigmoid_topk(x, layer["router_w"], bias, top_k=2, norm_eps=1e-6)
    assert np.array_equal(default, lfm2)


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_holders_shares_of_a_sigmoid_routed_layer_add_up(held):
    from llm_np_cp_tpu.ops.moe import moe_dropless

    layer = _sigmoid_layer(seed=held)
    kw = dict(act=jax.nn.silu, top_k=3, scaling=2.448, norm_eps=1e-20)
    x, router_w, bias = layer["x"], layer["router_w"], layer["expert_bias"]
    with jax.default_matmul_precision("highest"):
        whole, chosen, load = moe_dropless(
            x, router_w, bias, layer["w1"], layer["w3"], layer["w2"], **kw)
        total, loads = jnp.zeros_like(whole), []
        for first in range(0, 8, held):
            cut = slice(first, first + held)
            part, part_chosen, part_load = moe_dropless(
                x, router_w, bias, layer["w1"][cut], layer["w3"][cut],
                layer["w2"][cut], first_expert=first, **kw)
            assert np.array_equal(part_chosen, chosen)  # one router, 8 wide
            assert part_load.shape == (held,)
            total, loads = total + part, loads + [part_load]
    assert np.array_equal(jnp.concatenate(loads), load)
    assert int(load.sum()) == 24 * 3
    assert float(jnp.abs(total - whole).max()) < 1e-5 * float(jnp.abs(whole).max())


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_holders_shares_add_up_where_the_calls_move_the_rows(held):
    """The same sum through the Pallas grouped matmul (in the interpreter),
    whose two calls gather a holder's rows and add their weighted results
    by token themselves: each holder moves the rows of ITS pairs alone —
    a token none of whose experts it holds reads exact zeros there — and
    the shares add up to the layer on ``lax.ragged_dot`` over all eight
    experts, float32 to rounding."""
    from llm_np_cp_tpu.ops.moe import expert_rows_in_call, moe_dropless

    layer = _sigmoid_layer(seed=held, h=128, i=128)
    kw = dict(act=jax.nn.silu, top_k=3, scaling=2.448, norm_eps=1e-20,
              live=jnp.arange(24) < 21)
    x, router_w, bias = layer["x"], layer["router_w"] * 0.1, layer["expert_bias"]
    assert expert_rows_in_call(layer["w1"][:held], 24, 3, 16)
    with jax.default_matmul_precision("highest"):
        whole, chosen, load = moe_dropless(
            x, router_w, bias, layer["w1"], layer["w3"], layer["w2"], **kw)
        total = jnp.zeros_like(whole)
        for first in range(0, 8, held):
            cut = slice(first, first + held)
            part, part_chosen, part_load = moe_dropless(
                x, router_w, bias, layer["w1"][cut], layer["w3"][cut],
                layer["w2"][cut], first_expert=first, interpret=True, **kw)
            assert np.array_equal(part_chosen, chosen)
            assert np.array_equal(part_load, load[cut])
            here = (np.asarray(chosen) >= first) & (np.asarray(chosen) < first + held)
            here[21:] = False  # dead lanes
            assert (np.asarray(part)[~here.any(-1)] == 0).all()
            assert here.any(-1).all() == (np.asarray(part) != 0).any(-1).all()
            total = total + part
    assert int(load.sum()) == 21 * 3
    assert float(jnp.abs(total - whole).max()) < 1e-5 * float(jnp.abs(whole).max())
