"""Standalone serve process for the ``kill -9`` e2e test and the
``serve_restart`` bench.

Builds a DETERMINISTIC random-weight model (fixed PRNG seed, so a
restarted process serves the bit-identical model — the property that
makes journal replay token-identical across process death), wires an
optional durable request journal (``--journal``) and chaos schedule
(``--chaos``, e.g. ``proc_kill@25`` to SIGKILL itself after 25 busy
ticks), and runs the HTTP server until SIGTERM.

Run from the repo root::

    python tools/serve_proc.py --model tiny --port 0 \
        --port-file /tmp/pf --journal /tmp/serve.journal \
        --chaos 'proc_kill@25'

The first spawn can use ``--port 0`` (ephemeral); the restart re-spawns
with the SAME concrete port (from the port file) and the SAME journal
path, and clients resume their dropped SSE streams via Last-Event-ID.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", choices=["tiny", "llama1b"], default="tiny")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--port-file", default=None)
    p.add_argument("--journal", default=None)
    p.add_argument("--journal-sync", choices=["async", "admission"],
                   default="async")
    p.add_argument("--chaos", default=None)
    p.add_argument("--chaos-seed", type=int, default=0)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--block-size", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=24)
    p.add_argument("--max-tokens", type=int, default=16)
    p.add_argument("--max-restarts", type=int, default=3)
    args = p.parse_args()

    # the platform is whatever JAX_PLATFORMS says (tests export cpu; on
    # the chip machine the server takes the chip, so its launcher must
    # not hold it — utils/runtime.require_uninitialized_backend)
    import jax
    import jax.numpy as jnp

    from llm_np_cp_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()

    from llm_np_cp_tpu.config import LLAMA_3_2_1B, tiny_config
    from llm_np_cp_tpu.models.transformer import init_params
    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.serve import FaultInjector, ServeEngine
    from llm_np_cp_tpu.serve.engine import pool_geometry
    from llm_np_cp_tpu.serve.faults import install
    from llm_np_cp_tpu.serve.http import serve_forever

    if args.model == "tiny":
        config = tiny_config("llama")
        dtype = jnp.float32  # exact across processes, nothing to chance
    else:
        config = LLAMA_3_2_1B
        dtype = jnp.bfloat16
    # the SAME key every spawn: a restarted process must serve the
    # bit-identical model or teacher-forced replay cannot be
    # token-identical
    params = init_params(jax.random.PRNGKey(0), config, dtype=dtype)

    injector = FaultInjector.from_spec(args.chaos, seed=args.chaos_seed)
    if injector is not None:
        install(injector)
        print(f"[serve-proc] chaos ACTIVE: {args.chaos!r}", flush=True)
    journal = None
    if args.journal:
        from llm_np_cp_tpu.serve.journal import RequestJournal

        journal = RequestJournal(
            args.journal, fault_injector=injector,
            sync_admissions=args.journal_sync == "admission")
        print(f"[serve-proc] journal ACTIVE: {args.journal} "
              f"(epoch {journal.epoch}, sync={args.journal_sync}, "
              f"{journal.stats()['replayed']} to replay)", flush=True)

    chunk = args.block_size * 2
    _, num_blocks, max_seq_len = pool_geometry(
        args.prompt_len, args.max_tokens, args.slots, args.block_size,
        prefill_chunk=chunk,
    )
    engine = ServeEngine(
        params, config,
        sampler=Sampler(kind="greedy"),
        max_slots=args.slots,
        num_blocks=num_blocks,
        block_size=args.block_size,
        max_seq_len=max_seq_len,
        prefill_chunk=chunk,
        cache_dtype=dtype,
        fault_injector=injector,
        journal=journal,
    )
    engine.warmup([args.prompt_len], max_new_tokens=args.max_tokens)
    print("[serve-proc] warm, serving", flush=True)
    serve_forever(
        engine,
        model_id=args.model,
        host=args.host,
        port=args.port,
        port_file=args.port_file,
        drain_timeout=15.0,
        default_max_tokens=args.max_tokens,
        max_tokens_cap=args.max_tokens,
        max_restarts=args.max_restarts,
        restart_backoff_s=0.1,
    )
    print("[serve-proc] drained, bye", flush=True)


if __name__ == "__main__":
    main()
