"""The fetch's row-DMA kernel (`kernels/fetch_rows.py`) against the XLA
scatter it replaces, bitwise.

The kernel runs in interpret mode on the CPU.  The first test drives it
alone on the MLP fleet's leaf shapes; the next two run the fetch's entry,
`ops.fetch_into_fleet`, on the MLP and transformer fleets, and check how
it groups leaves into launches within the VMEM budget; the last runs
FRED's fused and queued steps with the fetch on the kernel and on the
scatter, and compares everything the steps return.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bandwidth import BandwidthConfig
from repro.core.rules import ServerConfig
from repro.kernels import fetch_rows as fetch_rows_kernel
from repro.kernels import ops
from repro.models.mlp import init_mlp, nll_loss
from repro.sim import fred
from repro.sim.fred import SimConfig, build_step_fn, init_sim

from conftest import tree_equal

LAM = 6


def _scatter(fleet, server, rows):
    """The fetch as it was: K broadcast copies scattered into the fleet."""
    return fleet.at[rows].set(
        jnp.broadcast_to(server[None], rows.shape + server.shape),
        mode="drop")


# (leaf rows, dtype, clients, fetched, VMEM budget); 0 = the default budget
_KERNEL_CASES = {
    "w1": ((784, 200), jnp.float32, [3, 1, 4, 0], [1, 1, 1, 1], 0),
    "w2": ((200, 10), jnp.float32, [3, 1, 4, 0], [1, 1, 1, 1], 0),
    "bf16": ((784, 200), jnp.bfloat16, [5, 2, 0, 1], [1, 1, 1, 1], 0),
    "repeated": ((784, 200), jnp.float32, [2, 2, 5, 2], [1, 1, 1, 1], 0),
    "dropped": ((200, 10), jnp.float32, [1, 4, 3, 5], [1, 0, 1, 0], 0),
    "all_dropped": ((784, 200), jnp.float32, [1, 4, 3, 5], [0, 0, 0, 0], 0),
    "k1": ((784, 200), jnp.float32, [4], [1], 0),
    "chunked": ((784, 200), jnp.float32, [3, 1, 3, 0], [1, 0, 1, 1],
                6 * 64 * 256 * 4),
    "ragged_chunks": ((20, 130), jnp.float32, [5, 0, 2, 3], [0, 1, 1, 1],
                      6 * 8 * 256 * 4),
    "rank3_row": ((3, 20, 130), jnp.float32, [0, 5, 0, 2], [1, 1, 1, 0],
                  6 * 24 * 256 * 4),
}


@pytest.mark.parametrize("case", list(_KERNEL_CASES))
def test_fetch_rows_kernel_matches_scatter(case):
    row, dtype, clients, fetched, budget = _KERNEL_CASES[case]
    k1, k2 = jax.random.split(jax.random.PRNGKey(len(case)))
    fleet = jax.random.normal(k1, (LAM,) + row).astype(dtype)
    server = jax.random.normal(k2, row).astype(dtype)
    cs = jnp.asarray(clients, jnp.int32)
    mask = jnp.asarray(fetched, bool)
    rows = fred.fetch_rows_of(cs, mask, LAM)
    kw = {"vmem_budget": budget} if budget else {}
    got, = fetch_rows_kernel.fetch_rows([fleet], [server], [rows],
                                        interpret=True, **kw)
    # against the scatter of the raw indices, duplicates and all
    want = _scatter(fleet, server, jnp.where(mask, cs, LAM))
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _fleets(name):
    """A fleet and a new server for `name`'s parameters."""
    if name == "mlp":
        params = init_mlp(jax.random.PRNGKey(0))
    else:  # the smoke transformer `benchmarks/lm_training.py` trains
        from repro.configs import get_smoke_config
        from repro.models.transformer import init_model
        params = init_model(jax.random.PRNGKey(0),
                            get_smoke_config("tinyllama-1.1b"))
    fleet = jax.tree.map(
        lambda p: jnp.stack([p * (i + 1) for i in range(LAM)]), params)
    return fleet, jax.tree.map(jnp.negative, params)


@pytest.mark.parametrize("model,launches", [("mlp", 1), ("transformer", 5)])
def test_fetch_into_fleet_matches_scatter(model, launches, monkeypatch):
    """Leaves of different shapes and rows: the MLP's matrices in one
    launch, the transformer's (rows of rank 2 to 4) in the launches that
    `fetch_rows.launches` plans within the VMEM budget, the vectors on the
    scatter."""
    calls = []
    kernel = fetch_rows_kernel.fetch_rows
    monkeypatch.setattr(fetch_rows_kernel, "fetch_rows",
                        lambda *a, **k: calls.append(len(a[0]))
                        or kernel(*a, **k))
    fleet, server = _fleets(model)
    leaves = jax.tree.leaves(fleet)
    rows = jax.tree.unflatten(jax.tree.structure(fleet), [
        jnp.asarray([[4, 0, LAM], [LAM, 2, 1]][i % 2], jnp.int32)
        for i in range(len(leaves))])
    got = ops.fetch_into_fleet(fleet, server, rows, interpret=True)
    assert len(calls) == launches
    assert sum(calls) == sum(x.ndim >= 3 for x in leaves)
    want = jax.tree.map(_scatter, fleet, server, rows)
    assert tree_equal(got, want)
    # sharded, every leaf takes the scatter
    calls.clear()
    got = ops.fetch_into_fleet(fleet, server, rows, sharded=True,
                               interpret=True)
    assert not calls and tree_equal(got, want)


_PLAN_CASES = {
    # (leaf shapes, dtype, VMEM budget, launches)
    "mlp": ([(784, 200), (200,), (200, 10), (10,)], jnp.float32, 12 << 20,
            [[0, 2]]),
    "split": ([(4, 784, 200)] * 5, jnp.float32, 12 << 20,
              [[0, 1], [2, 3], [4]]),
    "slice_past_vmem": ([(2, 2048, 5632), (784, 200)], jnp.float32,
                        12 << 20, [[1]]),
    "bf16_tight": ([(64, 256)] * 3, jnp.bfloat16, 6 * 16 * 256 * 2 * 2,
                   [[0, 1], [2]]),
}


@pytest.mark.parametrize("case", list(_PLAN_CASES))
def test_fetch_rows_launches_fit_budget(case):
    """Every leaf's smallest chunk fits its share of the budget in its
    launch; vectors and leaves whose smallest chunk alone is past the
    budget stay out."""
    shapes, dtype, budget, want = _PLAN_CASES[case]
    servers = [jax.ShapeDtypeStruct(s, dtype) for s in shapes]
    got = fetch_rows_kernel.launches(servers, budget)
    assert got == want
    for group in got:
        share = budget // (6 * len(group))
        assert all(fetch_rows_kernel._unit_bytes(servers[i].shape, dtype)
                   <= share for i in group)


SIZES = (64, 32, 10)
K, MU, WINDOWS = 4, 3, 3
_GATING = {
    "none": BandwidthConfig(),
    "fetch_gate": BandwidthConfig(c_fetch=2.0),
    "per_tensor": BandwidthConfig(c_push=1.0, c_fetch=2.0,
                                  per_tensor_push=True,
                                  per_tensor_fetch=True),
}


def _sim_config(rule, gating, queued):
    queue = (dict(queue_capacity=8, admission_policy="reject",
                  drain_policy="drain_k", drain_k=3)
             if queued else {})
    bw = _GATING[gating]
    if queued and bw.c_push > 0:
        bw = dataclasses.replace(bw, drop_policy="skip")
    return SimConfig(
        num_clients=LAM, batch_size=MU, events_per_step=K,
        apply_mode="fused", bandwidth=bw,
        server=ServerConfig(rule=rule, lr=0.01,
                            use_fused_kernel=(rule == "fasgd"),
                            kernel_interpret=True),
        **queue)


def _windows(cfg):
    params = init_mlp(jax.random.PRNGKey(0), SIZES)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, SIZES[0]))
    y = jax.random.randint(jax.random.PRNGKey(2), (64,), 0, SIZES[-1])
    step = build_step_fn(cfg, nll_loss, x, y)
    keys = jax.random.split(jax.random.PRNGKey(3), (WINDOWS, K))
    return jax.jit(lambda s, k: jax.lax.scan(step, s, k))(
        init_sim(cfg, params), keys)


@pytest.mark.parametrize("queued", [False, True], ids=["fused", "queued"])
@pytest.mark.parametrize("gating", list(_GATING))
@pytest.mark.parametrize("rule", ["fasgd", "asgd"])
def test_fred_step_fetch_kernel_bitwise(rule, gating, queued, monkeypatch):
    """Three windows with the fetch on the kernel and on the scatter: the
    state and the metrics bitwise equal."""
    cfg = _sim_config(rule, gating, queued)
    calls = []
    kernel = fetch_rows_kernel.fetch_rows
    with monkeypatch.context() as mp:
        mp.setattr(fetch_rows_kernel, "fetch_rows",
                   lambda *a, **k: calls.append(1) or kernel(*a, **k))
        state, metrics = _windows(cfg)
    assert calls, "the fetch did not take the kernel"
    with monkeypatch.context() as mp:
        mp.setattr(fetch_rows_kernel, "launches", lambda *a, **k: [])
        ref_state, ref_metrics = _windows(cfg)
    assert tree_equal(metrics, ref_metrics)
    assert tree_equal(state, ref_state)
    # the fleet moved: some fetch delivered a new server
    assert not tree_equal(state.client_params,
                          init_sim(cfg, init_mlp(jax.random.PRNGKey(0),
                                                 SIZES)).client_params)
