#!/usr/bin/env python3
"""Compile one cell's tick for the chip WITHOUT the chip, and read its memory.

    JAX_PLATFORMS=cpu python benchmark/tick_memory.py --workload <name>

The TPU compiler is installed in the sandbox and compiles for a v5e that
is described, not attached (on-chip-measurement guide, section 2.3).
This builds the cell's ``ServeEngine`` on the CPU from abstract
parameters (shapes only; the pool is real host memory), lowers its
unified step at the widest packed-width bucket against one described
v5e device and prints ``compiled.memory_analysis()``: arguments (weights
+ pool + operands), outputs, temporaries.  Nothing runs; it is never a
time or a result.  A scratch tool for sizing a cell before chip time is
spent on it, not part of a run.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--slots", type=int, default=0, help="override the cell's")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run as harness
    import traffic as traffic_mod
    from llm_np_cp_tpu.config import ModelConfig
    from llm_np_cp_tpu.models import init_params
    from llm_np_cp_tpu.serve import ServeEngine
    from llm_np_cp_tpu.serve.engine import pool_geometry

    jax.config.update("jax_enable_compilation_cache", False)
    spec = harness.load_spec(harness.ROOT, args.workload)
    serve = spec["config"].get("serve", {})
    config = ModelConfig.from_hf_dict(spec["config"])
    slots = args.slots or spec["params"]["slots"]
    block = int(serve.get("block_size", 64))
    p_max, m_max = traffic_mod.limits(spec["traffic"])
    chunk = min(block * 2, 256)  # cli._build_serve_engine's chunking
    _, blocks, max_seq = pool_geometry(p_max, m_max, slots, block, prefill_chunk=chunk)
    blocks = spec["params"].get("num_blocks") or blocks
    dtype = jnp.bfloat16 if serve.get("dtype", "bf16") == "bf16" else jnp.float32
    abstract = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), config, dtype=dtype))
    engine = ServeEngine(
        abstract, config, max_slots=slots, num_blocks=blocks, block_size=block,
        max_seq_len=max_seq, prefill_chunk=chunk, cache_dtype=dtype,
        mixed_step="auto", sample_epilogue="auto")
    print(f"engine: mixed={engine.mixed} ragged={engine.ragged_attn_impl} "
          f"epilogue={engine.epilogue_impl} pool={blocks}x{block} "
          f"buckets={engine.mixed_buckets}")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def aval(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    t_w, qb = engine.mixed_buckets[-1], engine._q_tile
    b, mb, w = slots, engine.max_blocks_per_seq, engine._spec_w
    i32 = np.int32
    operands = [((t_w,), i32)] * 6 + [((t_w,), bool)] + [((t_w // qb,), i32)] * 3 \
        + [((b, mb), i32), ((b,), i32), ((b, w), i32), ((b, w), i32),
           ((b,), np.uint32), ((b,), i32)]
    avals = [jax.tree.map(aval, abstract), jax.tree.map(aval, engine.pool.pages)] \
        + [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in operands]
    # the kernels pick interpret mode from the backend they see: show
    # them the one they are being compiled for
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = engine._mixed_step.lower(*avals).compile()
    finally:
        jax.default_backend = real
    m = compiled.memory_analysis()
    mib = 2**20
    print(f"tick at packed width {t_w}, {slots} slots, pool {blocks}x{block}: "
          f"arguments {m.argument_size_in_bytes / mib:.0f} MiB, "
          f"outputs {m.output_size_in_bytes / mib:.0f} MiB, "
          f"aliased {m.alias_size_in_bytes / mib:.0f} MiB, "
          f"temporaries {m.temp_size_in_bytes / mib:.0f} MiB, "
          f"code {m.generated_code_size_in_bytes / mib:.1f} MiB")
    return 0


if __name__ == "__main__":
    sys.exit(main())
