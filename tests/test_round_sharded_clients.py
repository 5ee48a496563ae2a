"""The round trainer with its clients over the sharded server's devices,
one client a device: three fasgd rounds of a 2-layer Mamba2 at the
`mamba2_1_3b.SMOKE` widths, C = 4 on four forced CPU devices, the one-kernel
apply interpreted.

One subprocess (the device count is locked at jax init) runs the rounds
three ways, each from the same seeded weights and batches: the one-device
step, the sharded step placed as `launch.train.run_round_trainer` places
it (state donated), and the plain blocked reference
(`bench/reference/mamba2_lm_blocked.py`).  The tests read its record.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import functools, json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import TrainerConfig
    from repro.configs.mamba2_1_3b import SMOKE as cfg
    from repro.core import server_shard
    from repro.core.round_trainer import (
        build_round_step, init_round_state, round_state_shardings)
    from repro.launch.mesh import make_server_mesh
    from repro.models.transformer import init_model, loss_fn
    from bench.reference import mamba2_lm_blocked

    assert len(jax.devices()) == 4, jax.devices()
    C, B, L, ROUNDS = 4, 1, 64, 3
    params = init_model(jax.random.PRNGKey(0), cfg)
    keys = jax.random.split(jax.random.PRNGKey(1), 2 * ROUNDS)
    batches = [{"tokens": jax.random.randint(keys[2 * r], (C, B, L), 0,
                                             cfg.vocab_size),
                "targets": jax.random.randint(keys[2 * r + 1], (C, B, L), 0,
                                              cfg.vocab_size)}
               for r in range(ROUNDS)]

    def grad_fn(p, b):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, cfg, b)
        return loss, g

    def run(shards):
        tc = TrainerConfig(num_round_clients=C, rule="fasgd", lr=0.005,
                           use_fused_kernel=True, kernel_interpret=True,
                           server_shards=shards)
        init = functools.partial(init_round_state, tc)
        mesh = placed = None
        if shards > 1:
            mesh = make_server_mesh(server=shards)
            placed = round_state_shardings(jax.eval_shape(init, params),
                                           mesh)
        state = jax.jit(init, **({"out_shardings": placed} if placed
                                 else {}))(params)
        step = jax.jit(build_round_step(tc, grad_fn, apply_mode="fused",
                                        mesh=mesh), donate_argnums=0)
        losses, shards_bytes = [], None
        for r, b in enumerate(batches):
            if mesh is not None:
                b = server_shard.shard_clients(b, mesh)
                shards_bytes = [
                    [[s.device.id, s.index[0].start, s.data.nbytes]
                     for s in leaf.addressable_shards] + [leaf.nbytes]
                    for leaf in jax.tree.leaves(state.client_params)]
            state, m = step(state, b, jax.random.PRNGKey(10 + r))
            losses.append(float(m["loss"]))
        return {"losses": losses,
                "params": [np.asarray(l).tolist()
                           for l in jax.tree.leaves(state.server.params)],
                "client_shards": shards_bytes}

    rc = {"d_model": cfg.d_model, "n_layer": cfg.num_layers,
          "vocab_size": cfg.vocab_size, "d_state": cfg.ssm_state,
          "expand": cfg.ssm_expand, "headdim": cfg.ssm_headdim,
          "norm_eps": cfg.norm_eps}
    tc = TrainerConfig()
    ref = mamba2_lm_blocked.run_rounds(
        params, [(b["tokens"], b["targets"]) for b in batches], rc,
        lr=0.005, gamma=tc.gamma, beta=tc.beta, eps=tc.eps)
    print(json.dumps({
        "one": run(1), "sharded": run(4), "theta0": [
            np.asarray(l).tolist() for l in jax.tree.leaves(params)],
        "ref": {"losses": ref["losses"], "params": [
            np.asarray(l).tolist() for l in jax.tree.leaves(ref["params"])]},
    }))
""")


@pytest.fixture(scope="module")
def record():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    for run in ("one", "sharded", "ref"):
        out[run]["params"] = [np.asarray(p) for p in out[run]["params"]]
    out["theta0"] = [np.asarray(p) for p in out["theta0"]]
    return out


def test_sharded_clients_step_matches_one_device(record):
    """Placement alone: the clients' forward and backward are the same
    computations on other devices, and the apply differs only in where the
    sum over the four gradients runs, so float32 rounding is all that
    separates the two."""
    one, sharded = record["one"], record["sharded"]
    np.testing.assert_allclose(sharded["losses"], one["losses"], rtol=1e-6)
    for a, b in zip(sharded["params"], one["params"]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_sharded_clients_step_matches_blocked_reference(record):
    """The sharded rounds against the plain reference on the same weights
    and tokens.  Losses: the program's chunked SSD and the reference's
    quadratic form over the sequence sum the same terms in another order,
    in float32 (readings near 1e-7; tolerance 1e-5).  Parameters: each
    leaf's change over the three rounds, ‖Δprog − Δref‖ / ‖Δref‖, where
    fasgd's α/(v·τ + ε) divides gradients that carry that same rounding
    (readings under 1e-4; tolerance 1e-3, far below the 1 that a missing
    or doubled client's gradient gives)."""
    sharded, ref = record["sharded"], record["ref"]
    np.testing.assert_allclose(sharded["losses"], ref["losses"], rtol=1e-5)
    for p, r, p0 in zip(sharded["params"], ref["params"], record["theta0"]):
        dref = np.linalg.norm(r - p0)
        assert dref > 0
        assert np.linalg.norm(p - r) / dref < 1e-3


def test_each_device_holds_one_clients_copy(record):
    """Every [C]-leading leaf of the clients' copies lies over the four
    devices: each device holds a quarter of the stacked bytes, which is
    one client's whole copy, and no client is held twice."""
    for leaf in record["sharded"]["client_shards"]:
        *shards, total = leaf
        assert len(shards) == 4
        assert all(nbytes * 4 == total for _, _, nbytes in shards)
        assert sorted(start for _, start, _ in shards) == [0, 1, 2, 3]
        assert len({dev for dev, _, _ in shards}) == 4


_PROTOCOL = textwrap.dedent("""
    import jax
    import numpy as np
    from repro.configs.base import TrainerConfig
    from repro.core import server_shard
    from repro.core.round_trainer import (
        build_round_step, init_round_state, shard_round_state)
    from repro.launch.mesh import make_server_mesh
    from repro.models.mlp import init_mlp, nll_loss

    C, MU, SIZES = 4, 8, (16, 8, 4)
    params = init_mlp(jax.random.PRNGKey(0), SIZES)
    x = jax.random.normal(jax.random.PRNGKey(1), (C, MU, SIZES[0]))
    y = jax.random.randint(jax.random.PRNGKey(2), (C, MU), 0, SIZES[-1])
    mesh = make_server_mesh(server=4)

    def grad_fn(p, batch):
        return jax.value_and_grad(nll_loss)(p, *batch)

    CASES = {
        "whole_copy_gates": dict(c_push=1.0, c_fetch=1.0),
        "per_tensor_gates": dict(c_push=1.0, c_fetch=1.0,
                                 per_tensor_push=True,
                                 per_tensor_fetch=True),
        "queue": dict(queue_capacity=8, drain_policy="drain_k", drain_k=2,
                      admission_policy="reject", c_fetch=1.0),
        "kernel_gated": dict(c_fetch=1.0, use_fused_kernel=True,
                             kernel_interpret=True),
    }
    for name, kw in CASES.items():
        for mode in ("serial", "fused"):
            if mode == "serial" and name == "kernel_gated":
                continue
            out = []
            for shards in (1, 4):
                tc = TrainerConfig(num_round_clients=C, rule="fasgd",
                                   lr=0.05, server_shards=shards, **kw)
                m = mesh if shards > 1 else None
                # jitted, so n and b hold buffers of their own to donate
                state = jax.jit(lambda p: init_round_state(tc, p))(params)
                batch = (x, y)
                if m is not None:
                    state = shard_round_state(state, m)
                    batch = server_shard.shard_clients(batch, m)
                step = jax.jit(build_round_step(tc, grad_fn, mode, mesh=m),
                               donate_argnums=0)
                for r in range(4):
                    state, _ = step(state, batch, jax.random.PRNGKey(r))
                out.append(state)
            one, sharded = out
            for a, b in zip(jax.tree.leaves((one.server, one.client_params,
                                             one.client_ts)),
                            jax.tree.leaves((sharded.server,
                                             sharded.client_params,
                                             sharded.client_ts))):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                           err_msg=name + "/" + mode)
            print("ok", name, mode)
    print("ALL_PROTOCOL_CASES")
""")


def test_gated_and_queued_rounds_match_one_device():
    """Eq. 9 gating (the whole-copy gate's v̄ is a mean across the
    server's blocks; T a replicated scalar), per-tensor gating and
    staleness, a lossy ingress queue and the serial apply all run with the
    clients over the server's devices, and end where the one-device rounds
    end, up to float32 rounding."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _PROTOCOL], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert "ALL_PROTOCOL_CASES" in r.stdout


@pytest.mark.parametrize("build", ["round_state_shardings",
                                   "build_round_step"])
@pytest.mark.parametrize("clients", [2, 6])
def test_clients_that_straddle_devices_are_refused(build, clients):
    """On a four-device server axis the clients lie over the devices, C/S
    whole clients a device: a C that four does not divide is refused where
    the state is placed and where the step is built, before any device is
    touched (an abstract mesh)."""
    import jax
    from jax.sharding import AbstractMesh
    from repro.configs.base import TrainerConfig
    from repro.core import round_trainer
    from repro.models.mlp import init_mlp

    mesh = AbstractMesh((4,), ("server",))
    tc = TrainerConfig(num_round_clients=clients, rule="fasgd",
                       server_shards=4)
    params = init_mlp(jax.random.PRNGKey(0), (16, 8, 4))
    with pytest.raises(ValueError, match="not a multiple of the 4 devices"):
        if build == "build_round_step":
            round_trainer.build_round_step(tc, lambda p, b: None, mesh=mesh)
        else:
            round_trainer.round_state_shardings(jax.eval_shape(
                lambda p: round_trainer.init_round_state(tc, p), params),
                mesh)
