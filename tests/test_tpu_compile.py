"""Ahead-of-time compiles of the main path for a described TPU v5e chip.

The TPU compiler is installed even where no chip is attached, and it
compiles for a chip described by `jax.experimental.topologies`: what Mosaic
or XLA:TPU would refuse (a primitive with no TPU lowering, a block shape
off the tiling, a program over the chip's memory) fails here, at no chip
time. Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports every test file. The persistent compilation cache is off
around these compiles, since a TPU executable written to it cannot be read
back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.refine import RefineConfig, refine_with_gate
from repro.core.retrieval import topk_dense
from repro.index.pallas_backend import topk_sim_packed
from repro.kernels.topk_sim.kernel import topk_sim_pallas
from repro.models import model as M

V5E_HBM_BYTES = 16 * 1024**3
D = 384  # BagEncoder width: the tool table's row width


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _placed(tree, sharding):
    return jax.tree.map(lambda s: _struct(s.shape, s.dtype, sharding), tree)


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    return used - mem.alias_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.parametrize(
    "q,t,k",
    [
        (128, 2413, 5),  # paper scale (ToolBench), gateway k
        (128, 100_000, 5),  # MCP-registry scale
        (128, 2413, 25),  # k * candidate_multiplier with the re-ranker on
    ],
)
def test_topk_sim_pallas_compiles_for_v5e(one_chip, q, t, k):
    compiled = topk_sim_pallas.lower(
        _struct((q, D), jnp.float32, one_chip), _struct((t, D), jnp.float32, one_chip), k
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel, not a fallback
    assert _fits(compiled)


def test_topk_dense_compiles_for_v5e(one_chip):
    compiled = topk_dense.lower(
        _struct((128, D), jnp.float32, one_chip),
        _struct((100_000, D), jnp.float32, one_chip),
        5,
    ).compile()
    assert _fits(compiled)


@pytest.mark.parametrize(
    "program,q,t,k",
    [
        ("topk_dense", 64, 2413, 5),  # the benchmark cells' shape
        ("topk_sim_packed", 128, 2413, 5),  # the Pallas backend's served program
    ],
)
def test_packed_topk_programs_compile_for_v5e(one_chip, program, q, t, k):
    queries = _struct((q, D), jnp.float32, one_chip)
    table = _struct((t, D), jnp.float32, one_chip)
    if program == "topk_dense":
        lowered = topk_dense.lower(queries, table, k)
    else:
        lowered = topk_sim_packed.lower(queries, table, k, use_pallas=True)
    out = lowered.out_info
    assert (out.shape, out.dtype) == ((q, 2 * k), jnp.int32)  # one packed block
    compiled = lowered.compile()
    if program == "topk_sim_packed":
        # packing in the same program did not push the kernel out
        assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled)


def test_refine_with_gate_compiles_for_v5e(one_chip):
    """The OATS-S1 fit at the ToolBench cell's shapes: 2,413 tools, 357 fit and
    63 gate queries with their candidate pools, three unrolled passes."""
    t, q_fit, q_val = 2413, 357, 63

    def fit(tools, qf, rf, qv, rv, mf, mv):
        res = refine_with_gate(tools, qf, rf, qv, rv, RefineConfig(iterations=3), mf, mv)
        return res.embeddings, res.accepted, res.history

    f32 = lambda *shape: _struct(shape, jnp.float32, one_chip)  # noqa: E731
    lowered = jax.jit(fit).lower(
        f32(t, D), f32(q_fit, D), f32(q_fit, t), f32(q_val, D), f32(q_val, t),
        f32(q_fit, t), f32(q_val, t),
    )
    assert lowered.out_info[2].shape == (4, t, D)  # the original table and 3 passes
    assert _fits(lowered.compile())


def test_full_width_qwen_decode_step_compiles_for_v5e(one_chip):
    cfg = get_config("qwen2.5-3b")
    params = jax.eval_shape(lambda key: M.init(cfg, key), jax.random.PRNGKey(0))
    prompt = {"tokens": jax.ShapeDtypeStruct((1, 32), jnp.int32)}
    _, cache = jax.eval_shape(
        lambda p, b: M.prefill(cfg, p, b, max_cache_len=64), params, prompt
    )
    batch = {
        "token": _struct((1, 1), jnp.int32, one_chip),
        "pos": _struct((), jnp.int32, one_chip),
    }
    compiled = jax.jit(lambda p, c, b: M.decode_step(cfg, p, c, b)).lower(
        _placed(params, one_chip), _placed(cache, one_chip), batch
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 6e9  # full width: ~3.1B bf16 weights
    assert _fits(compiled)
