"""Every protocol layer of the FRED window and the round step carries its
`jax.named_scope`, so a device trace can put each op down to its layer.

XLA keeps the name stack as each instruction's `op_name` metadata, and the
profiler copies it into the op's `tf_op` stat: these names are what the
benchmark's reduction by scope (`bench/scopes.py`) reads.  Each case
compiles one program at smoke size on the CPU, the Pallas apply in
interpret mode so that its views of the leaves are lowered too, and looks for every
scope the program's path must carry.
"""
import math
import os
import re
import subprocess
import sys
import textwrap

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


def _pack_ops(compiled_text):
    """(opcode, elements) of every compiled op under `apply_pack`."""
    pat = (r'^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(.*'
           r'op_name="[^"]*apply_pack')
    return [(op, math.prod(int(d) for d in dims.split(",") if d))
            for dims, op in re.findall(pat, compiled_text, re.M)]


def _lowered_pack_ops(lowered_text):
    """(op, location) of every op the lowering put under `apply_pack`."""
    locs = dict(re.findall(r"^#(loc\d+) = (.*)$", lowered_text, re.M))
    ops = re.findall(r'^\s*%[^=]+= "?([\w.]+)"?[ (].*loc\(#(loc\d+)\)\s*$',
                     lowered_text, re.M)
    return [(op, locs[loc]) for op, loc in ops
            if "apply_pack" in locs.get(loc, "")]


def _assert_pack_is_views(lowered, params):
    """The kernel reads each leaf in its own layout: what `apply_pack`
    holds is views of the leaves (reshapes in the lowering, and transposes
    of a leaf narrower than a lane tile), and what is left of it after
    compiling moves at most one leaf's state (bitcasts, layout copies,
    transposes): never a pad, a slice or anything of the [K, ...] batch's
    size."""
    ops = _lowered_pack_ops(lowered.as_text(debug_info=True))
    assert {op for op, _ in ops} <= {"stablehlo.reshape",
                                     "stablehlo.transpose"}, ops
    largest = max(x.size for x in jax.tree.leaves(params))
    left = _pack_ops(lowered.compile().as_text())
    assert all(op not in ("pad", "slice") and size <= largest
               for op, size in left), left


def _fred_window(rule, fused_mode, use_fused_kernel):
    return _fred_lowered(rule, fused_mode, use_fused_kernel).compile(
    ).as_text()


def _fred_lowered(rule, fused_mode, use_fused_kernel):
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
    return jax.jit(lambda s, k: jax.lax.scan(step, s, k)).lower(state, keys)


FRED = {"dispatch", "minibatch", "stale_gather", "client_grad",
        "server_apply", "fetch_scatter"}


@pytest.mark.parametrize("rule,fused_mode,kernel,scopes", [
    ("fasgd", "materialized", True, FRED),
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
    if kernel:
        _assert_pack_is_views(_fred_lowered(rule, fused_mode, kernel),
                              init_mlp(jax.random.PRNGKey(0), SIZES))


def test_fred_kernel_apply_packs_under_server_apply():
    """The kernel's views of the leaves nest inside the apply, so the
    innermost scope tells whatever they cost apart from the kernel's own
    time, and the kernel itself runs under the apply."""
    lowered = _fred_lowered("fasgd", "materialized", True)
    packs = _lowered_pack_ops(lowered.as_text(debug_info=True))
    assert packs and all("server_apply/apply_pack" in p for _, p in packs)
    names = _op_names(lowered.compile().as_text())
    assert all("server_apply" in n for n in names if "apply_pack" in n)
    kernel = [n for n in names if "fused_event_apply" in n]
    assert kernel and all("server_apply" in n for n in kernel)


@pytest.mark.parametrize("rule,fused_mode,kernel", [
    ("fasgd", "materialized", True),
    ("asgd", "cotangent", False),
], ids=["fused_fasgd", "cotangent_asgd"])
def test_fred_fetch_kernel_under_fetch_scatter(rule, fused_mode, kernel):
    """The fetch's row-write kernel (`name="fetch_rows"`, interpret mode
    here) runs under `fetch_scatter`, so a device trace puts its time down
    to the fetch and not to the apply."""
    names = _op_names(_fred_window(rule, fused_mode, kernel))
    fetch = [n for n in names if "fetch_rows" in n]
    assert fetch and all("dispatch/fetch_scatter/fetch_rows" in n
                         for n in fetch)


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
    lowered = jax.jit(step).lower(state, (x, y), jax.random.PRNGKey(4))
    names = _op_names(lowered.compile().as_text())
    missing = ({"dispatch", "client_grad", "server_apply", "fetch_refresh"}
               - _scopes(names))
    assert not missing, f"scopes missing from the round step: {missing}"
    assert any(n.startswith("jit(round_step)/dispatch/") for n in names)
    _assert_pack_is_views(lowered, params)


_SHARDED_ROUND = textwrap.dedent("""
    import re
    import jax
    from repro.configs.base import TrainerConfig
    from repro.core.round_trainer import (
        build_round_step, init_round_state, shard_round_state)
    from repro.launch.mesh import make_server_mesh
    from repro.models.mlp import init_mlp, nll_loss

    C, MU, SIZES = 4, 3, (16, 8, 4)
    params = init_mlp(jax.random.PRNGKey(0), SIZES)
    x = jax.random.normal(jax.random.PRNGKey(1), (C, MU, SIZES[0]))
    y = jax.random.randint(jax.random.PRNGKey(2), (C, MU), 0, SIZES[-1])
    tc = TrainerConfig(num_round_clients=C, rule="fasgd", lr=0.02,
                       use_fused_kernel=True, kernel_interpret=True,
                       server_shards=4)
    mesh = make_server_mesh(server=4)

    def grad_fn(p, batch):
        return jax.value_and_grad(nll_loss)(p, *batch)

    state = shard_round_state(init_round_state(tc, params), mesh)
    step = build_round_step(tc, grad_fn, apply_mode="fused", mesh=mesh)
    text = jax.jit(step, donate_argnums=0).lower(
        state, (x, y), jax.random.PRNGKey(4)).compile().as_text()
    for line in text.splitlines():
        m = re.search(r" (all-to-all|all-gather)\\(.*op_name=\\"([^\\"]*)\\"",
                      line)
        if m:
            print("COLLECTIVE", m.group(1), m.group(2))
    for name in re.findall(r'op_name="([^"]*)"', text):
        print("OP", name)
""")


def test_sharded_round_step_scopes():
    """With the clients over the sharded server's four devices, the push's
    route of the gradients to the server's blocks runs under
    `server_apply/push_route` and the fetch's gather of the new W under
    `fetch_refresh/fetch_gather`, each an exchange between the devices."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _SHARDED_ROUND],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    lines = r.stdout.splitlines()
    names = {l[3:] for l in lines if l.startswith("OP ")}
    missing = ({"dispatch", "client_grad", "server_apply", "fetch_refresh",
                "push_route", "fetch_gather"} - _scopes(names))
    assert not missing, f"scopes missing from the sharded round: {missing}"
    colls = [l.split()[1:] for l in lines if l.startswith("COLLECTIVE ")]
    assert any(op == "all-to-all" and "server_apply/push_route" in n
               for op, n in colls), colls
    assert any(op == "all-gather" and "fetch_refresh/fetch_gather" in n
               for op, n in colls), colls
