"""Training step: causal-LM loss + optimizer update, mesh-sharded.

The reference is inference-only (``loss`` is always ``None``,
llama3.2_model.py:809).  The framework closes that gap with a minimal but
real training path — cross-entropy over shifted targets, ``jax.grad``
through the same ``models.transformer.forward`` used for inference, optax
updates, and the full thing jit-compiled over a device mesh (DP on batch,
TP on weights) so the multi-chip story covers training too.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import optax

from llm_np_cp_tpu.config import ModelConfig
from llm_np_cp_tpu.models.transformer import forward

Params = dict[str, Any]


def causal_lm_loss(
    params: Params,
    batch: jnp.ndarray,
    config: ModelConfig,
    *,
    loss_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Mean next-token cross-entropy.  batch: [B, S] int32; positions
    t < S-1 predict t+1.  loss_mask: optional [B, S-1] weighting.
    MoE configs add ``router_aux_loss_coef ×`` the load-balancing loss."""
    inputs = batch[:, :-1]
    targets = batch[:, 1:]
    if config.is_moe:
        logits, _, aux = forward(params, inputs, config, output_router_losses=True)
    else:
        logits, _ = forward(params, inputs, config)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if loss_mask is not None:
        loss = jnp.sum(nll * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1.0)
    else:
        loss = jnp.mean(nll)
    if config.is_moe:
        loss = loss + config.router_aux_loss_coef * aux["moe_aux_loss"]
    return loss


def make_train_step(config: ModelConfig, optimizer: optax.GradientTransformation):
    """Returns jitted ``step(params, opt_state, batch) → (params, opt_state,
    loss)``.  Shard params/batch before calling; GSPMD partitions the
    backward pass and gradient psums over the mesh automatically."""

    @jax.jit
    def step(params: Params, opt_state, batch: jnp.ndarray):
        loss, grads = jax.value_and_grad(causal_lm_loss)(params, batch, config)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def default_optimizer(lr: float = 1e-4) -> optax.GradientTransformation:
    return optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(lr))


# ----------------------------------------------------------------------
# CLI: the user entrypoint for every training-side mesh axis
# ----------------------------------------------------------------------

def build_parser():
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m llm_np_cp_tpu.train",
        description="Mesh-sharded causal-LM training (DP/TP/PP/EP). The "
        "reference is inference-only; this is the training entrypoint the "
        "dryrun exercises, exposed (SURVEY §5 checkpoint/resume row).",
    )
    p.add_argument("--model", default="tiny",
                   help="preset (tiny, tiny_moe, llama1b, llama3b, gemma2_2b "
                        "— random init) or an HF checkpoint dir/repo id")
    p.add_argument("--mesh", default="1,1,1",
                   help="named axes data=2,pipe=2,model=2 (any of data/seq/"
                        "model/pipe/expert) or positional data,seq,model")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--microbatches", type=int, default=2,
                   help="GPipe microbatches per step (pipe>1 only)")
    p.add_argument("--dtype", choices=["bf16", "f32"], default="f32",
                   help="parameter dtype (f32 default: optimizer math)")
    p.add_argument("--data", default=None,
                   help="UTF-8 text file tokenized with the model tokenizer "
                        "(checkpoint models only); default: synthetic tokens")
    p.add_argument("--layers", type=int, default=None,
                   help="override the preset's layer count (e.g. to make it "
                        "divisible by pipe)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint-dir", default=None,
                   help="save an orbax checkpoint here after training")
    p.add_argument("--virtual-devices", type=int, default=None, metavar="N",
                   help="with JAX_PLATFORMS=cpu: N virtual devices to test "
                        "multi-chip meshes on one host")
    return p


def _resolve_model(args):
    from llm_np_cp_tpu.config import (
        GEMMA_2_2B, LLAMA_3_2_1B, LLAMA_3_2_3B, tiny_config,
    )
    from llm_np_cp_tpu.models.transformer import init_params

    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    tiny_kw = dict(num_hidden_layers=args.layers) if args.layers else {}
    presets = {
        "tiny": lambda: tiny_config("llama", **tiny_kw),
        "tiny_moe": lambda: tiny_config(
            "llama", num_local_experts=4, num_experts_per_tok=2, **tiny_kw
        ),
        "llama1b": lambda: LLAMA_3_2_1B,
        "llama3b": lambda: LLAMA_3_2_3B,
        "gemma2_2b": lambda: GEMMA_2_2B,
    }
    if args.model in presets:
        if args.layers and args.model not in ("tiny", "tiny_moe"):
            raise SystemExit("--layers applies to the tiny presets only")
        config = presets[args.model]()
        params = init_params(jax.random.PRNGKey(args.seed), config, dtype=dtype)
        return None, params, config
    if args.layers:
        raise SystemExit("--layers applies to the tiny presets only")
    from llm_np_cp_tpu.utils.loading import load_model

    return load_model(args.model, dtype=dtype)


def _batches(args, tokenizer, vocab_size):
    """Yield [batch, seq_len] int32 arrays forever."""
    import numpy as np

    if args.data:
        if tokenizer is None:
            raise SystemExit("--data needs a checkpoint model (tokenizer)")
        text = open(args.data, encoding="utf-8").read()
        ids = np.asarray(tokenizer(text)["input_ids"], dtype=np.int32)
        need = args.batch * args.seq_len
        if ids.size < need:
            ids = np.tile(ids, need // ids.size + 1)
        off = 0
        while True:
            if off + need > ids.size:
                off = 0
            yield ids[off:off + need].reshape(args.batch, args.seq_len)
            off += need
    else:
        # synthetic mode: a small FIXED corpus cycled forever (not fresh
        # noise per step), so a smoke run shows the loss actually falling
        # as the model memorizes it
        rng = np.random.default_rng(args.seed)
        corpus = [
            rng.integers(0, vocab_size, (args.batch, args.seq_len), dtype=np.int32)
            for _ in range(2)
        ]
        i = 0
        while True:
            yield corpus[i % len(corpus)]
            i += 1


def run(argv: list[str] | None = None) -> list[float]:
    """Train for --steps steps; returns the per-step losses (also printed)."""
    import contextlib
    import sys
    import time

    from llm_np_cp_tpu.parallel.sharding import (
        make_mesh, parse_mesh_spec, shard_params,
    )

    from llm_np_cp_tpu.utils.runtime import configure_compile_cache

    args = build_parser().parse_args(argv)
    configure_compile_cache()
    if args.virtual_devices:
        jax.config.update("jax_num_cpu_devices", args.virtual_devices)
    plan = parse_mesh_spec(args.mesh)
    tokenizer, params, config = _resolve_model(args)

    mesh = None
    if plan.num_devices > 1:
        plan.validate(config)
        if args.batch % max(plan.data, 1):
            raise SystemExit(
                f"--batch {args.batch} not divisible by data={plan.data}"
            )
        mesh = make_mesh(plan)
        params = shard_params(params, config, plan, mesh)
    if plan.pipe > 1 and args.batch % args.microbatches:
        raise SystemExit(
            f"--batch {args.batch} not divisible by "
            f"--microbatches {args.microbatches}"
        )

    opt = default_optimizer(args.lr)
    opt_state = opt.init(params)
    if plan.pipe > 1:
        from llm_np_cp_tpu.parallel.pipeline import make_pp_train_step

        step = make_pp_train_step(
            config, opt, plan, mesh, num_microbatches=args.microbatches
        )
    else:
        step = make_train_step(config, opt)

    ctx = jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    losses: list[float] = []
    toks = args.batch * (args.seq_len - 1)
    with ctx:
        gen = _batches(args, tokenizer, config.vocab_size)
        for i in range(args.steps):
            t0 = time.perf_counter()
            params, opt_state, loss = step(
                params, opt_state, jnp.asarray(next(gen))
            )
            loss = float(loss)  # blocks: step wall-clock is real
            dt = time.perf_counter() - t0
            losses.append(loss)
            print(
                f"step {i:4d}  loss {loss:.4f}  {toks / dt:,.0f} tok/s"
                + ("  (compile)" if i == 0 else ""),
                file=sys.stderr,
            )
    if args.checkpoint_dir:
        from llm_np_cp_tpu.utils.checkpoint import save_checkpoint

        save_checkpoint(
            args.checkpoint_dir,
            {"params": params, "opt_state": opt_state, "step": args.steps},
        )
        print(f"saved checkpoint to {args.checkpoint_dir}", file=sys.stderr)
    return losses


if __name__ == "__main__":
    run()
