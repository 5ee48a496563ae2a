"""Every protocol layer of the FRED window and the round step carries its
`jax.named_scope`, so a device trace can put each op down to its layer.

XLA keeps the name stack as each instruction's `op_name` metadata, and the
profiler copies it into the op's `tf_op` stat: these names are what the
benchmark's reduction by scope (`bench/scopes.py`) reads.  Each case
compiles one program at smoke size on the CPU, the Pallas apply in
interpret mode so that its packing is lowered too, and looks for every
scope the program's path must carry.
"""
import re

import jax
import pytest

from repro.configs.base import TrainerConfig
from repro.core.round_trainer import build_round_step, init_round_state
from repro.core.rules import ServerConfig
from repro.models.mlp import init_mlp, nll_loss
from repro.sim.fred import SimConfig, build_step_fn, init_sim

SIZES = (16, 8, 4)
LAM, K, MU = 6, 4, 3


def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _scopes(op_names):
    """Every name that appears as a component of some op's path, with the
    transforms around it taken off (`vmap(jvp(x))` counts as `x`)."""
    return {w for n in op_names
            for w in re.findall(r"[A-Za-z_][A-Za-z0-9_]*", n)}


def _fred_window(rule, fused_mode, use_fused_kernel):
    params = init_mlp(jax.random.PRNGKey(0), SIZES)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, SIZES[0]))
    y = jax.random.randint(jax.random.PRNGKey(2), (32,), 0, SIZES[-1])
    cfg = SimConfig(
        num_clients=LAM, batch_size=MU, events_per_step=K,
        apply_mode="fused", fused_mode=fused_mode,
        server=ServerConfig(rule=rule, lr=0.01,
                            use_fused_kernel=use_fused_kernel,
                            kernel_interpret=True))
    step = build_step_fn(cfg, nll_loss, x, y)
    state = init_sim(cfg, params)
    keys = jax.random.split(jax.random.PRNGKey(3), (1, K))
    return jax.jit(lambda s, k: jax.lax.scan(step, s, k)).lower(
        state, keys).compile().as_text()


FRED = {"dispatch", "minibatch", "stale_gather", "client_grad",
        "server_apply", "fetch_scatter"}


@pytest.mark.parametrize("rule,fused_mode,kernel,scopes", [
    ("fasgd", "materialized", True, FRED | {"apply_pack"}),
    ("asgd", "cotangent", False, FRED),
], ids=["fused_fasgd", "cotangent_asgd"])
def test_fred_window_scopes(rule, fused_mode, kernel, scopes):
    names = _op_names(_fred_window(rule, fused_mode, kernel))
    missing = scopes - _scopes(names)
    assert not missing, f"scopes missing from the FRED window: {missing}"
    # the clients' backward carries the scope as well as their forward
    grads = [n for n in names if "client_grad" in n]
    assert any("transpose(" in n for n in grads)
    assert any("transpose(" not in n for n in grads)


def test_fred_kernel_apply_packs_under_server_apply():
    """The pads around the kernel nest inside the apply, so the innermost
    scope tells them apart from the kernel's own time."""
    names = _op_names(_fred_window("fasgd", "materialized", True))
    packs = [n for n in names if "apply_pack" in n]
    assert packs and all("server_apply" in n for n in packs)


def test_round_step_scopes():
    params = init_mlp(jax.random.PRNGKey(0), SIZES)
    C = 4
    x = jax.random.normal(jax.random.PRNGKey(1), (C, MU, SIZES[0]))
    y = jax.random.randint(jax.random.PRNGKey(2), (C, MU), 0, SIZES[-1])
    tc = TrainerConfig(num_round_clients=C, rule="fasgd", lr=0.02,
                       use_fused_kernel=True, kernel_interpret=True)

    def grad_fn(p, batch):
        return jax.value_and_grad(nll_loss)(p, *batch)

    step = build_round_step(tc, grad_fn, apply_mode="fused")
    state = init_round_state(tc, params)
    text = jax.jit(step).lower(
        state, (x, y), jax.random.PRNGKey(4)).compile().as_text()
    names = _op_names(text)
    missing = ({"dispatch", "client_grad", "server_apply", "apply_pack",
                "fetch_refresh"} - _scopes(names))
    assert not missing, f"scopes missing from the round step: {missing}"
    assert any(n.startswith("jit(round_step)/dispatch/") for n in names)
