"""Sharding-rule unit tests + the launch-layer spec/analysis plumbing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.sharding.rules import (
    batch_spec, cache_specs, constrain, constrain_axes, leaf_param_spec,
    param_specs, set_mesh_context,
)


def mk_mesh(shape=(2, 2), axes=("data", "model")):
    n = len(jax.devices())
    if np.prod(shape) > n:
        pytest.skip("needs more devices")
    return make_mesh(shape, axes)


class FakeMesh:
    """Shape-only stand-in so rules can be tested for 16×16 without devices."""
    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        import numpy as _np
        self.devices = _np.empty(tuple(sizes.values()), dtype=object)
        self.shape = sizes


M = FakeMesh({"data": 16, "model": 16})
MP = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_fsdp_rule_last_divisible_dim_to_model():
    assert leaf_param_spec("unembed", (4096, 128256), M) == P("data", "model")
    assert leaf_param_spec("embed", (128256, 4096), M) == P("data", "model")


def test_nondivisible_dims_replicate():
    # mamba2 in_proj output dim 8456 = 8·1057 not divisible by 16
    spec = leaf_param_spec("layers/mamba/conv_b", (8456,), M)
    assert spec == P(None)


def test_stacked_layer_dim_never_sharded():
    spec = leaf_param_spec("layers/attn/wq", (22, 2048, 32, 64), M)
    assert spec[0] is None
    assert "model" in tuple(spec)


def test_multipod_folds_pod_into_data():
    spec = leaf_param_spec("unembed", (4096, 128256), MP)
    assert spec == P(("data", "pod"), "model")


def test_small_tensors_replicate():
    assert leaf_param_spec("final_norm", (7,), M) == P(None)


def test_batch_spec_shards_batch_dim():
    assert batch_spec((256, 4096), M) == P("data", None)
    assert batch_spec((256, 4096), MP) == P(("pod", "data"), None)


def test_batch_one_falls_back_to_sequence():
    # long_500k: batch 1 → context parallelism over the seq dim
    assert batch_spec((1, 524288), M, seq_dim=1) == P(None, "data")


def test_cache_rule_decode():
    cache = {"k": jax.ShapeDtypeStruct((32, 128, 32768, 8, 128), jnp.bfloat16)}
    spec = cache_specs(cache, M)["k"]
    assert spec[1] == "data"          # batch
    assert spec[4] == "model"         # head_dim (kv=8 not divisible by 16)


def test_cache_rule_batch1_shards_window():
    cache = {"k": jax.ShapeDtypeStruct((32, 1, 8192, 8, 128), jnp.bfloat16)}
    spec = cache_specs(cache, M)["k"]
    assert spec[1] is None
    assert spec[2] == "data"


def test_constrain_is_noop_without_context():
    x = jnp.ones((4, 4, 4))
    y = constrain(x, "bsd")
    assert y is x
    z = constrain_axes(x, {0: "batch"})
    assert z is x


def test_constrain_applies_with_context():
    mesh = mk_mesh((1, 1))
    set_mesh_context(mesh)
    try:
        x = jnp.ones((4, 8, 16))
        y = constrain(x, "bsd")
        assert y.shape == x.shape
    finally:
        set_mesh_context(None)


def test_param_specs_cover_full_model():
    """Every leaf of a full-size model gets a valid spec: dims either
    replicated or exactly divisible."""
    from repro.configs import get_config
    from repro.launch.steps import abstract_params
    for arch in ("llama3-8b", "grok-1-314b", "mamba2-1.3b", "zamba2-7b"):
        params = abstract_params(get_config(arch))
        specs = param_specs(params, M)
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for leaf, spec in zip(flat_p, flat_s):
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                size = 16 if ax in ("data", "model") else 32
                assert leaf.shape[dim] % size == 0, (leaf.shape, spec)


def test_input_specs_match_make_batch():
    """Abstract input specs must mirror the real batch structure."""
    from repro.configs import get_smoke_config
    from repro.launch.steps import batch_struct
    from repro.models.api import make_batch
    for arch in ("llama3-8b", "phi-3-vision-4.2b", "hubert-xlarge"):
        cfg = get_smoke_config(arch)
        real = make_batch(cfg, 2, 64)
        spec = batch_struct(cfg, 2, 64, with_targets=True)
        assert set(real) == set(spec)
        for k in real:
            assert real[k].shape == spec[k].shape, (arch, k)


def test_collective_bytes_parser():
    from repro.launch.analysis import collective_bytes
    hlo = """
  %ag = f32[16,128]{1,0} all-gather(f32[16,8]{1,0} %x), replica_groups={}
  %ar.1 = bf16[1024]{0} all-reduce(bf16[1024]{0} %y), to_apply=%add
  %rs = f32[4,4]{1,0} reduce-scatter(f32[16,4]{1,0} %z), dimensions={0}
  %dn = f32[8]{0} all-reduce-done(f32[8]{0} %h)
  %cp = u32[2]{0} collective-permute(u32[2]{0} %w), source_target_pairs={{0,1}}
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 128 * 4
    assert out["all-reduce"] == 1024 * 2
    assert out["reduce-scatter"] == 4 * 4 * 4
    assert out["collective-permute"] == 2 * 4
    assert out["total"] == sum(out[k] for k in
                               ("all-gather", "all-reduce", "reduce-scatter",
                                "all-to-all", "collective-permute"))


def test_extrapolate_costs_linear():
    from repro.launch.analysis import extrapolate_costs
    assert extrapolate_costs(10.0, 14.0, 5) == 10.0 + 4 * 4.0
    d = extrapolate_costs({"a": 1, "total": 3}, {"a": 2, "total": 5}, 3)
    assert d == {"a": 3, "total": 7}


def test_active_param_counts_sane():
    """Analytic N ≈ the assigned sizes (within 25% — embeddings etc.)."""
    from repro.configs import get_config
    from repro.launch.analysis import active_param_count, total_param_count
    expect = {
        "tinyllama-1.1b": 1.1e9, "llama3-8b": 8e9, "yi-34b": 34e9,
        "yi-9b": 9e9, "mamba2-1.3b": 1.3e9,
    }
    for arch, n in expect.items():
        got = active_param_count(get_config(arch))
        assert abs(got - n) / n < 0.35, (arch, got, n)
    # grok-1 total ≈ 314B, active far less
    g = get_config("grok-1-314b")
    assert abs(total_param_count(g) - 314e9) / 314e9 < 0.15
    assert active_param_count(g) < 0.4 * total_param_count(g)
