"""Sharding rules, input specs, state specs, HLO analysis, traffic model.

These run with 1 CPU device (the 512-device mesh is exercised only by
`python -m repro.launch.dryrun`); rule resolution is tested against
synthetic mesh axis descriptions, and a real 1-device lowering proves the
model code path is mesh-agnostic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.common.sharding import spec_for
from repro.configs import get_config
from repro.launch.hlo_analysis import CollectiveStats, parse_collectives, roofline_terms
from repro.launch.specs import SHAPES, input_specs, variant_for_shape
from repro.launch.state_specs import opt_state_structs
from repro.launch.hbm_model import analytic_hbm_bytes
from repro.models import model as M
from repro.models.config import reduced
from repro.models.params import param_structs


MESH_AXES = ("pod", "data", "model")
SIZES = {"pod": 2, "data": 16, "model": 16}


def test_spec_resolution_basic():
    assert spec_for(("batch", None, "heads", None), MESH_AXES) == P(
        ("pod", "data"), None, "model", None
    )
    # single-pod mesh: "pod" silently drops
    assert spec_for(("batch", None), ("data", "model")) == P("data", None)


def test_divisibility_drops_axis():
    # kv_heads=8 cannot shard over model=16 -> replicated
    spec = spec_for(("layers", "embed", "kv_heads", None), MESH_AXES, (32, 4096, 8, 128), SIZES)
    assert spec == P(None, "data", None, None)
    # but 32 kv heads shard fine
    spec = spec_for(("layers", "embed", "kv_heads", None), MESH_AXES, (32, 4096, 32, 128), SIZES)
    assert spec == P(None, "data", "model", None)
    # odd vocab replicates
    spec = spec_for(("vocab", "embed"), MESH_AXES, (49155, 4096), SIZES)
    assert spec == P(None, "data")


def test_input_specs_cover_all_shapes():
    for arch in ("qwen2.5-3b", "musicgen-medium", "llama-3.2-vision-90b", "mamba2-2.7b"):
        cfg = get_config(arch)
        for name, shape in SHAPES.items():
            c = variant_for_shape(cfg, shape)
            specs = input_specs(c, shape)
            if shape.kind in ("train", "prefill"):
                toks = specs["tokens"]
                assert toks.shape[0] == shape.global_batch
                assert toks.shape[1] == shape.seq_len
                if c.cross_attn_every:
                    assert "image_embeds" in specs
            else:
                assert specs["token"].shape[:2] == (shape.global_batch, 1)


def test_long_context_variant_policy():
    long = SHAPES["long_500k"]
    # SSM/hybrid: native (no window added)
    assert variant_for_shape(get_config("mamba2-2.7b"), long).sliding_window == 0
    assert variant_for_shape(get_config("hymba-1.5b"), long).sliding_window == 1024
    # dense: explicit sliding-window variant
    v = variant_for_shape(get_config("qwen2.5-3b"), long)
    assert v.sliding_window == 8192 and v.name.endswith("+swa")
    # decode_32k unchanged (full attention is allowed there)
    assert variant_for_shape(get_config("qwen2.5-3b"), SHAPES["decode_32k"]).sliding_window == 0


def test_opt_state_structs_match_runtime():
    """Dry-run optimizer structs must exactly match optimizer.init shapes."""
    from repro import optim

    cfg = reduced(get_config("granite-3-8b"))
    specs = M.make_specs(cfg)
    params = M.init(cfg, jax.random.PRNGKey(0))
    for name, opt in [("adamw", optim.adamw(1e-3)), ("adafactor", optim.adafactor(1e-3))]:
        structs = opt_state_structs(name, specs, mesh=None)
        real = opt.init(params)
        s_shapes = [x.shape for x in jax.tree.leaves(structs)]
        r_shapes = [x.shape for x in jax.tree.leaves(real)]
        assert s_shapes == r_shapes, name


def test_collective_parser():
    hlo = """
  %ag = bf16[16,512]{1,0} all-gather(%x), replica_groups=...
  %ar = f32[1024]{0} all-reduce(%y), to_apply=%sum
  %aa = (f32[8,64]{1,0}, f32[8,64]{1,0}) all-to-all(%a, %b), dimensions={0}
  %cp = bf16[4,4]{1,0} collective-permute(%z), source_target_pairs=...
  %nn = f32[2,2]{1,0} add(%p, %q)
"""
    stats = parse_collectives(hlo)
    assert stats.count_by_type["all-gather"] == 1
    assert stats.bytes_by_type["all-gather"] == 16 * 512 * 2
    assert stats.bytes_by_type["all-reduce"] == 1024 * 4
    assert stats.bytes_by_type["all-to-all"] == 2 * 8 * 64 * 4
    assert stats.bytes_by_type["collective-permute"] == 16 * 2
    # all-reduce weighted 2x on the wire
    assert stats.wire_bytes == pytest.approx(
        2 * 1024 * 4 + 16 * 512 * 2 + 2 * 8 * 64 * 4 + 16 * 2
    )


def test_roofline_terms_dominance():
    t = roofline_terms(197e12, 0.0, 0.0)  # exactly 1s of compute
    assert t["dominant"] == "compute" and t["compute_s"] == pytest.approx(1.0)
    t = roofline_terms(0.0, 819e9, 0.0)
    assert t["dominant"] == "memory" and t["memory_s"] == pytest.approx(1.0)
    t = roofline_terms(0.0, 0.0, 200e9)
    assert t["dominant"] == "collective" and t["collective_s"] == pytest.approx(1.0)


def test_traffic_model_decode_is_weight_dominated():
    cfg = get_config("qwen2.5-3b")
    tr = analytic_hbm_bytes(cfg, "decode", 128, 32768, 256, 16)
    assert tr["weights"] > 0 and tr["cache_read"] > 0
    # windowed variant shrinks cache traffic by ~seq/window
    v = variant_for_shape(cfg, SHAPES["long_500k"])
    tr_l = analytic_hbm_bytes(v, "decode", 1, 524288, 256, 16)
    full = analytic_hbm_bytes(cfg, "decode", 1, 524288, 256, 16)
    assert tr_l["cache_read"] < full["cache_read"] / 10


def test_single_device_lowering_smoke():
    """The dry-run program shape lowers on the local 1-device 'mesh' too."""
    cfg = reduced(get_config("qwen2.5-3b"))
    specs = M.make_specs(cfg)
    pstructs = param_structs(specs, dtype=jnp.float32)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    lowered = jax.jit(lambda p, b: M.loss_fn(cfg, p, b)[0]).lower(pstructs, batch)
    compiled = lowered.compile()
    from repro.common.meshctx import cost_analysis_dict
    assert cost_analysis_dict(compiled)["flops"] > 0


def test_compile_cache_env_dir_wins_else_one_checkout_dir(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no cache path (JAX
    reads the variable itself); without it the cache goes to the one fixed
    directory inside the checkout, which git ignores."""
    from pathlib import Path

    from repro.common import compile_cache

    repo = Path(__file__).resolve().parents[1]
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert ".jax_cache/" in (repo / ".gitignore").read_text().splitlines()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
