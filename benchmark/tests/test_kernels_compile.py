"""The default path's Pallas kernels compile for a v5e at every
configuration's OWN head shapes (the chip's share under TP) - the TPU
compiler is installed here and compiles for a chip that is described,
not attached.  What only the chip can say is whether they compute the
right numbers: the benchmark's reference check does that in every run."""

import json
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # noqa: BLE001 - no libtpu, nothing to compile with
        pytest.skip(f"no deviceless TPU topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def chip_shape(name: str):
    from llm_np_cp_tpu.ops.pallas.support import KernelShape
    from llm_np_cp_tpu.parallel.sharding import parse_mesh_spec

    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    mesh = c.get("serve", {}).get("mesh", "")
    tp = parse_mesh_spec(mesh).model if mesh else 1
    return tp, KernelShape(
        name=name, heads=c["num_attention_heads"] // tp,
        kv_heads=c["num_key_value_heads"] // tp, head_dim=c["head_dim"],
        hidden=c["hidden_size"], vocab=c["vocab_size"],
        tied=bool(c.get("tie_word_embeddings")))


@pytest.mark.parametrize("name", CONFIGS)
def test_default_path_kernels_compile_at_the_configurations_shapes(v5e, name):
    from llm_np_cp_tpu.ops.pallas import support

    tp, shape = chip_shape(name)
    # under TP the sampling tail is XLA by design: only attention is a kernel
    kernels = ["ragged_paged_attention"] + ([] if tp > 1 else ["sample_epilogue"])
    real = jax.default_backend
    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"  # the kernels pick interpret mode from it
    try:
        for kernel in kernels:
            make_args, run, _ = support.kernel_case(kernel, shape, 64)
            avals = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e)
                     for a in jax.eval_shape(make_args)]
            jax.jit(run).lower(*avals).compile()
    finally:
        jax.default_backend = real
        jax.config.update("jax_enable_compilation_cache", True)
