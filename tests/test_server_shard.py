"""Sharded parameter server (core/server_shard.py): routing properties,
the replicated≡sharded equivalence invariant, and the counter filter.

The S>1 data-plane tests need more than one device, so they run in one
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count — the
same simulated-multi-device recipe docs/SHARDING.md documents.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rules as server_rules
from repro.core import server_shard
from repro.core.rules import ServerConfig
from repro.sim.fred import SimConfig, run_simulation
from repro.core.bandwidth import BandwidthConfig

from conftest import tree_equal


RULES = server_rules.registered_rules()
ASYNC_RULES = tuple(r for r in RULES
                    if not server_rules.get_rule(r).synchronous)


def _tree(key=0):
    """A server-like pytree with divisible, non-divisible, and scalar leaves."""
    k = jax.random.PRNGKey(key)
    return {
        "w1": jax.random.normal(k, (784, 200)),
        "b1": jnp.zeros((200,)),
        "w2": jax.random.normal(k, (200, 10)),
        "b2": jnp.zeros((10,)),
        "odd": jnp.zeros((7,)),          # 7 is not divisible by 2/4 → replicates
        "t": jnp.zeros((), jnp.int32),   # scalar → replicates
    }


# ---------------------------------------------------------------------------
# routing properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S", [1, 2, 4])
def test_every_leaf_has_exactly_one_owner(S):
    plan = server_shard.make_shard_plan(_tree(), S)
    assert len(plan.owners) == len(jax.tree.leaves(_tree()))
    assert all(0 <= o < S for o in plan.owners)


@pytest.mark.parametrize("S", [1, 2, 4])
def test_byte_accounting_conserved(S):
    """Σ owned == total, and resident bytes decompose into blocks + replicas."""
    plan = server_shard.make_shard_plan(_tree(), S)
    assert sum(plan.owned_bytes) == plan.total_bytes
    assert sum(plan.leaf_bytes) == plan.total_bytes
    for s in range(S):
        assert plan.resident_bytes(s) == plan.shard_bytes[s] + plan.replicated_bytes
    # block bytes + S copies of the replicated remainder cover the state
    assert sum(plan.shard_bytes) + plan.replicated_bytes == plan.total_bytes


def test_plan_deterministic():
    p1 = server_shard.make_shard_plan(_tree(), 4)
    p2 = server_shard.make_shard_plan(_tree(), 4)
    assert p1 == p2


def test_leaf_spec_routing():
    """Divisible last dim carries the axis; otherwise replicate; S=1 is P()."""
    P = server_shard.server_leaf_spec
    assert P((784, 200), 1) == jax.sharding.PartitionSpec()
    assert P((784, 200), 4) == jax.sharding.PartitionSpec(None, "server")
    # last divisible dim wins scanning from the end; 10 is not 4-divisible
    assert P((200, 10), 4) == jax.sharding.PartitionSpec("server", None)
    assert P((7,), 4) == jax.sharding.PartitionSpec()
    assert P((), 4) == jax.sharding.PartitionSpec()


def test_peak_bytes_shrink_with_shards():
    """peak resident bytes ≈ total/S + replicated remainder (the ~1/S claim)."""
    tree = _tree()
    total = server_shard.make_shard_plan(tree, 1).total_bytes
    peaks = {S: server_shard.peak_shard_bytes(tree, S) for S in (1, 2, 4)}
    assert peaks[1] == total
    assert peaks[4] < peaks[2] < peaks[1]
    repl = server_shard.make_shard_plan(tree, 2).replicated_bytes
    for S in (2, 4):
        exact = (total - server_shard.make_shard_plan(tree, S).replicated_bytes
                 ) / S + server_shard.make_shard_plan(tree, S).replicated_bytes
        assert peaks[S] == pytest.approx(exact)
    assert repl < 0.01 * total           # replicas are a tiny remainder here


def test_validate_server_mesh_rejects():
    with pytest.raises(ValueError, match="server_shards=2"):
        server_shard.validate_server_mesh(None, 2)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("server", "data"))
    with pytest.raises(ValueError, match="axis size 1"):
        server_shard.validate_server_mesh(mesh, 2)
    server_shard.validate_server_mesh(mesh, 1)   # exact size passes


# ---------------------------------------------------------------------------
# S=1 bitwise invariant: the sharded path with one shard IS the replicated
# server, for every registry rule × apply mode × per-tensor gating
# ---------------------------------------------------------------------------

def _sim_cfg(rule, apply_mode, per_tensor, shards=1):
    sync = server_rules.get_rule(rule).synchronous
    return SimConfig(
        num_clients=4, batch_size=8, seed=5,
        apply_mode=apply_mode,
        dispatcher="roundrobin" if sync else "uniform",
        server=ServerConfig(rule=rule, lr=0.01, num_clients=4,
                            kasync_k=2 if rule == "kasync" else 0),
        bandwidth=BandwidthConfig(
            c_push=0.5 if not sync else 0.0, c_fetch=0.5,
            per_tensor_push=per_tensor and not sync,
            per_tensor_fetch=per_tensor),
        server_shards=shards,
    )


def _run(mlp_setup, cfg, mesh=None, steps=32):
    params, ds, loss = mlp_setup
    return run_simulation(
        cfg, loss, params, ds.x_train, ds.y_train, steps, eval_every=steps,
        eval_fn=lambda p: loss(p, ds.x_valid, ds.y_valid), mesh=mesh)


@pytest.mark.parametrize("per_tensor", [False, True],
                         ids=["whole-copy", "per-tensor"])
@pytest.mark.parametrize("apply_mode", ["serial", "fused"])
@pytest.mark.parametrize("rule", RULES)
def test_one_shard_bitwise_identical(mlp_setup, rule, apply_mode, per_tensor):
    """server_shards=1 + a size-1 'server' mesh axis must be a placement
    no-op: bitwise-identical trajectory AND identical (shard-free) counters
    versus the plain replicated run."""
    sync = server_rules.get_rule(rule).synchronous
    if sync and apply_mode == "fused":
        pytest.skip("synchronous rules do not support the fused apply")
    if sync and per_tensor:
        pytest.skip("per-tensor gating is undefined at a sync barrier")
    cfg = _sim_cfg(rule, apply_mode, per_tensor, shards=1)
    base = _run(mlp_setup, cfg)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("server",))
    sharded = _run(mlp_setup, cfg, mesh=mesh)

    assert tree_equal(base["state"].server.params,
                      sharded["state"].server.params)
    assert base["val_cost"] == sharded["val_cost"]
    assert base["counters"] == sharded["counters"]
    assert not any(k.startswith("shard_") for k in base["counters"])


def test_shard_counters_filtered_when_off(mlp_setup):
    """The serialized counter dict carries no shard_* keys at S=1 — the
    golden-stability contract (same as queue_* / scenario_* / kernel_*)."""
    out = _run(mlp_setup, _sim_cfg("fasgd", "serial", False))
    assert not any(k.startswith("shard_") for k in out["counters"])
    # the Counters pytree itself still carries zeroed fields
    assert hasattr(out["state"].counters, "shard_applies")


def test_round_trainer_shard_fold_bitwise(mlp_setup):
    """tc.server_shards>1 without placement changes ONLY the shard_*
    telemetry — the update math is untouched (the data plane is pure
    placement, so on one device the trajectories are bitwise equal)."""
    from repro.configs.base import TrainerConfig
    from repro.core.round_trainer import build_round_step, init_round_state

    params, ds, loss = mlp_setup

    def grad_fn(p, batch):
        x, y = batch
        return loss(p, x, y), jax.grad(loss)(p, x, y)

    def run(shards):
        tc = TrainerConfig(num_round_clients=4, rule="fasgd",
                           c_push=1.0, c_fetch=1.0, server_shards=shards)
        state = init_round_state(tc, params)
        step = jax.jit(build_round_step(tc, grad_fn))
        batch = (ds.x_train[:32].reshape(4, 8, -1),
                 ds.y_train[:32].reshape(4, 8))
        for i in range(4):
            state, _ = step(state, batch,
                            jax.random.fold_in(jax.random.PRNGKey(2), i))
        return state

    s1, s2 = run(1), run(2)
    assert tree_equal(s1.server.params, s2.server.params)
    assert int(s1.counters.shard_applies) == 0
    assert int(s2.counters.shard_applies) == 4
    assert float(s2.counters.shard_bytes_peak) == pytest.approx(
        server_shard.peak_shard_bytes(s2.server, 2))


def test_trainer_rejects_bad_shards():
    from repro.configs.base import TrainerConfig
    from repro.core.round_trainer import build_round_step
    with pytest.raises(ValueError, match="server_shards"):
        build_round_step(TrainerConfig(server_shards=0), lambda p, b: None)
    with pytest.raises(ValueError, match="server_shards"):
        SimConfig(server_shards=0)


# ---------------------------------------------------------------------------
# S>1 allclose: forced-multi-device CPU, one subprocess for all rules
# ---------------------------------------------------------------------------

_MULTIDEV_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import rules as server_rules
    from repro.core.rules import ServerConfig
    from repro.core.bandwidth import BandwidthConfig
    from repro.sim.fred import SimConfig, run_simulation
    from repro.launch.mesh import make_mesh
    from repro.models.mlp import init_mlp, nll_loss
    from repro.data.mnist import make_synth_mnist

    assert len(jax.devices()) == 2, jax.devices()
    params = init_mlp(jax.random.PRNGKey(0))
    ds = make_synth_mnist(n_train=256, n_valid=128)
    mesh = make_mesh((2,), ("server",))

    def run(rule, shards, mesh):
        sync = server_rules.get_rule(rule).synchronous
        cfg = SimConfig(
            num_clients=4, batch_size=8, seed=5,
            dispatcher="roundrobin" if sync else "uniform",
            server=ServerConfig(rule=rule, lr=0.01, num_clients=4,
                                kasync_k=2 if rule == "kasync" else 0),
            bandwidth=BandwidthConfig(c_push=0.0 if sync else 0.5,
                                      c_fetch=0.5),
            server_shards=shards)
        return run_simulation(
            cfg, nll_loss, params, ds.x_train, ds.y_train, 24,
            eval_every=24,
            eval_fn=lambda p: nll_loss(p, ds.x_valid, ds.y_valid),
            mesh=mesh if shards > 1 else None)

    for rule in server_rules.registered_rules():
        base = run(rule, 1, None)
        shard = run(rule, 2, mesh)
        for a, b in zip(jax.tree.leaves(base["state"].server.params),
                        jax.tree.leaves(shard["state"].server.params)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                       err_msg=rule)
        assert shard["counters"]["shard_applies"] > 0, rule
        assert shard["counters"]["shard_bytes_peak"] > 0, rule
        assert not any(k.startswith("shard_") for k in base["counters"])
        print(rule, "ok", float(shard["counters"]["shard_bytes_peak"]))
    print("ALL_RULES_ALLCLOSE")
""")


def test_sharded_allclose_all_rules_multidevice():
    """serial-vs-sharded allclose for every registry rule on forced
    2-device CPU (subprocess: the device count is locked at jax init)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ALL_RULES_ALLCLOSE" in r.stdout
