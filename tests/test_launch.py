"""Launch-layer integration: train/serve steps on real (CPU) devices, and
the dry-run plumbing on a 1×1 mesh (the 512-device path is exercised by
`python -m repro.launch.dryrun`, which must own the XLA device-count flag)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import InputShape, TrainerConfig
from repro.core import rules as server_rules
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (
    abstract_params, abstract_server_state, input_specs, make_decode_step,
    make_prefill_step, make_train_step, server_config, shardings_for,
)
from repro.models.api import make_batch
from repro.models.transformer import init_model


SMALL = InputShape("small", 64, 2, "train")
SMALL_DEC = InputShape("small_dec", 64, 2, "decode")
SMALL_PRE = InputShape("small_pre", 64, 2, "prefill")


def test_train_step_runs_and_advances_timestamp():
    cfg = get_smoke_config("tinyllama-1.1b")
    tc = TrainerConfig(rule="fasgd", lr=0.05)
    params = init_model(jax.random.PRNGKey(0), cfg)
    state = server_rules.init(server_config(tc), params)
    batch = make_batch(cfg, 2, 64)
    step = jax.jit(make_train_step(cfg, tc))
    l0 = None
    for i in range(5):
        state, m = step(state, batch)
        if l0 is None:
            l0 = float(m["loss"])
    assert int(state.timestamp) == 5
    assert float(m["loss"]) < l0            # same batch → loss must drop


def test_train_step_respects_stats_dtype():
    cfg = get_smoke_config("tinyllama-1.1b")
    tc = TrainerConfig(rule="fasgd", stats_dtype="bfloat16")
    st = abstract_server_state(cfg, tc)
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(st.n))
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(st.v))


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-1.3b",
                                  "hubert-xlarge"])
def test_input_specs_cover_kinds(arch):
    cfg = get_smoke_config(arch)
    sp = input_specs(cfg, SMALL)
    assert "batch" in sp and "targets" in sp["batch"]
    sp = input_specs(cfg, SMALL_PRE)
    assert "targets" not in sp["batch"]
    if cfg.supports_decode():
        sp = input_specs(cfg, SMALL_DEC)
        assert sp["token"].shape == (2, 1)
        assert sp["pos"].shape == ()
    else:
        with pytest.raises(AssertionError):
            input_specs(cfg, SMALL_DEC)


def test_abstract_params_match_real_init():
    cfg = get_smoke_config("zamba2-7b")
    ab = abstract_params(cfg)
    real = init_model(jax.random.PRNGKey(0), cfg)
    fa, fr = jax.tree.leaves(ab), jax.tree.leaves(real)
    assert len(fa) == len(fr)
    for a, r in zip(fa, fr):
        assert a.shape == r.shape and a.dtype == r.dtype


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "grok-1-314b",
                                  "mamba2-1.3b", "zamba2-7b",
                                  "deepseek-v2-236b"])
def test_shardings_lower_on_host_mesh(arch):
    """shardings_for + lower + compile on a 1×1 mesh for all step kinds —
    the same code path the 512-device dry-run uses."""
    from repro.sharding import set_mesh_context
    cfg = get_smoke_config(arch)
    mesh = make_host_mesh()
    set_mesh_context(mesh)
    try:
        for shape in (SMALL, SMALL_PRE, SMALL_DEC):
            if shape.kind == "decode" and not cfg.supports_decode():
                continue
            fn, args, shard = shardings_for(cfg, shape, mesh)
            jax.jit(fn, in_shardings=shard).lower(*args).compile()
    finally:
        set_mesh_context(None)


def test_decode_step_runs():
    from repro.models.serving import init_cache
    cfg = get_smoke_config("llama3-8b")
    params = init_model(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, 2, 16)
    step = jax.jit(make_decode_step(cfg))
    tok = jnp.zeros((2, 1), jnp.int32)
    logits, cache2 = step(params, tok, cache, jnp.int32(0))
    assert logits.shape == (2, 1, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits[..., : cfg.vocab_size]).all())


def test_encoder_prefill_step():
    cfg = get_smoke_config("hubert-xlarge")
    params = init_model(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg, 2, 32)
    batch.pop("targets")
    step = jax.jit(make_prefill_step(cfg))
    logits = step(params, batch)
    assert logits.shape == (2, 32, cfg.padded_vocab)


def test_dryrun_pair_list_covers_assignment():
    from repro.launch.dryrun import pair_list
    pairs = pair_list()
    assert len(pairs) == 40
    skips = [p for p in pairs if p[3]]
    assert len(skips) == 2                        # hubert decode_32k+long_500k
    assert all(p[0] == "hubert-xlarge" for p in skips)
    # dense archs get the sliding-window override for long_500k
    ov = {(p[0], p[1]): p[2] for p in pairs if p[2] is not None}
    assert ov[("llama3-8b", "long_500k")]["attn_window"] == 8192
    assert "attn_window" not in ov.get(("mamba2-1.3b", "long_500k"), {})
    # train pairs get remat
    assert ov[("yi-34b", "train_4k")]["remat"] is True


@pytest.mark.parametrize("clients,shards", [(2, 4), (6, 4)])
def test_train_cli_refuses_clients_that_straddle_shards(clients, shards):
    """`--server-shards S` lays the clients over the server's S devices,
    C/S whole clients a device; a C that S does not divide is refused
    before anything is built, with the reason."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--smoke",
         "--clients", str(clients), "--server-shards", str(shards)],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 2
    assert (f"--clients {clients} is not a multiple of --server-shards "
            f"{shards}") in r.stderr
    assert "straddle" in r.stderr
