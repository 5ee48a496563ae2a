"""Capture golden serial-path trajectories for the regression suite.

The FRED serial path carries the repo's strongest correctness contract:
bitwise determinism from the seed, K-invariance, and bitwise identity with
the pre-engine-refactor simulator.  This script freezes that contract into
small npz files under ``tests/goldens/`` — one per config — which
``tests/test_goldens.py`` replays *bitwise* in CI (diffs are uploaded as
artifacts on failure).  Each file records the ``jax.__version__`` it was
captured under: XLA numerics and the default PRNG stream change across jax
releases, so a golden replays only on the version that wrote it.

Regenerate after an *intentional* trajectory change:

    PYTHONPATH=src python scripts/capture_goldens.py

The model is deliberately small (784-16-10, ~12.9k params) so every golden
stays ~50 KB.
"""
from __future__ import annotations

import os

import jax
import numpy as np

GOLDEN_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tests", "goldens")

SIZES = (784, 16, 10)
STEPS = 48
SEED = 3

# tiny-lm arch: a 2-layer d=64 transformer (smoke tinyllama shrunk further)
# on the markov token task — freezes the serial trajectory over a *nested*
# pytree (stacked layers, embed/unembed) through models/lm.py.
TINY_LM = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
               d_ff=128, vocab_size=128, head_dim=16)
TINY_LM_SEQ = 16
TINY_LM_STEPS = 24


def golden_configs():
    """name -> capture spec for every frozen trajectory: a bare SimConfig
    runs the paper's MLP; an ``('tiny-lm', SimConfig)`` pair runs the tiny
    transformer through the LM adapter (same serial contract).

    Covers: every registry rule on the plain serial path, scalar push+fetch
    gating under both drop policies, the §5 per-tensor modes (fetch, and
    push+fetch combined), and the transformer serial path (plain fasgd +
    per-tensor-gated asgd on the nested pytree)."""
    from repro.core import rules as server_rules
    from repro.core.bandwidth import BandwidthConfig
    from repro.core.rules import ServerConfig
    from repro.sim.fred import SimConfig

    configs = {}
    for rule in server_rules.registered_rules():
        disp = ("roundrobin" if server_rules.get_rule(rule).synchronous
                else "uniform")
        configs[f"rule_{rule}"] = SimConfig(
            num_clients=4, batch_size=8, dispatcher=disp, seed=SEED,
            server=ServerConfig(rule=rule, lr=0.01, num_clients=4))
    for policy in ("cache", "skip"):
        configs[f"gated_{policy}"] = SimConfig(
            num_clients=4, batch_size=8, seed=7,
            server=ServerConfig(rule="fasgd", lr=0.01),
            bandwidth=BandwidthConfig(c_push=2.0, c_fetch=2.0,
                                      drop_policy=policy))
    configs["per_tensor_fetch"] = SimConfig(
        num_clients=4, batch_size=8, seed=5,
        server=ServerConfig(rule="fasgd", lr=0.005),
        bandwidth=BandwidthConfig(c_fetch=0.05, per_tensor_fetch=True))
    configs["per_tensor_push_fetch"] = SimConfig(
        num_clients=4, batch_size=8, seed=5,
        server=ServerConfig(rule="fasgd", lr=0.005),
        bandwidth=BandwidthConfig(c_push=0.02, c_fetch=0.05,
                                  per_tensor_push=True,
                                  per_tensor_fetch=True,
                                  drop_policy="skip"))
    configs["tiny_lm_fasgd"] = ("tiny-lm", SimConfig(
        num_clients=4, batch_size=4, seed=SEED,
        server=ServerConfig(rule="fasgd", lr=0.01)))
    configs["tiny_lm_asgd_per_tensor"] = ("tiny-lm", SimConfig(
        num_clients=4, batch_size=4, seed=5,
        server=ServerConfig(rule="asgd", lr=0.01),
        bandwidth=BandwidthConfig(c_push=0.5, c_fetch=0.5,
                                  per_tensor_push=True,
                                  per_tensor_fetch=True,
                                  drop_policy="skip")))
    return configs


def _golden_arrays(out):
    arrays = {"val_cost": np.asarray(out["val_cost"], np.float64),
              "final_timestamp": np.int64(out["final_timestamp"])}
    for i, leaf in enumerate(jax.tree.leaves(out["state"].server.params)):
        arrays[f"param_leaf_{i}"] = np.asarray(leaf)
    for name, val in sorted(out["counters"].items()):
        arrays[f"counter_{name}"] = np.float64(val)
    return arrays


def run_config(cfg):
    """One deterministic serial run -> dict of numpy arrays (the golden)."""
    if isinstance(cfg, tuple):
        arch, cfg = cfg
        assert arch == "tiny-lm", arch
        return _run_lm_config(cfg)
    from repro.data.mnist import make_synth_mnist
    from repro.models.mlp import init_mlp, nll_loss
    from repro.sim.fred import run_simulation

    params = init_mlp(jax.random.PRNGKey(0), SIZES)
    ds = make_synth_mnist(n_train=512, n_valid=256)
    out = run_simulation(cfg, nll_loss, params, ds.x_train, ds.y_train,
                         STEPS, eval_every=STEPS,
                         eval_fn=lambda p: nll_loss(p, ds.x_valid, ds.y_valid))
    return _golden_arrays(out)


def _run_lm_config(cfg):
    """The tiny-lm arch: serial FRED over the transformer via models/lm.py."""
    from repro.configs import get_smoke_config
    from repro.data.tokens import TokenDataConfig, make_batch
    from repro.models.lm import make_lm_loss
    from repro.models.transformer import init_model
    from repro.sim.fred import run_simulation

    mcfg = get_smoke_config("tinyllama-1.1b", **TINY_LM)
    loss = make_lm_loss(mcfg)
    params = init_model(jax.random.PRNGKey(0), mcfg)
    tcfg = TokenDataConfig(vocab_size=mcfg.vocab_size, seq_len=TINY_LM_SEQ,
                           batch_size=128, temperature=0.5)
    tok, tgt = make_batch(tcfg, 0)
    out = run_simulation(cfg, loss, params, tok, tgt, TINY_LM_STEPS,
                         eval_every=TINY_LM_STEPS,
                         eval_fn=lambda p: loss(p, tok[:16], tgt[:16]))
    return _golden_arrays(out)


def main():
    os.makedirs(GOLDEN_DIR, exist_ok=True)
    for name, cfg in golden_configs().items():
        arrays = run_config(cfg)
        arrays["jax_version"] = np.str_(jax.__version__)
        path = os.path.join(GOLDEN_DIR, f"{name}.npz")
        np.savez_compressed(path, **arrays)
        print(f"  captured {name}: {os.path.getsize(path) / 1024:.0f} KB "
              f"(T={int(arrays['final_timestamp'])}, "
              f"val={arrays['val_cost'][-1]:.6f})")
    print(f"goldens written to {GOLDEN_DIR} (jax {jax.__version__})")


if __name__ == "__main__":
    main()
