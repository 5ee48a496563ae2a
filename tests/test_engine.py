"""Shared-engine tests: event batching, serial/fused equivalence, counters.

The contract under test (core/engine.py + sim/fred.py):

* serial mode is **K-invariant**: batching K events per scan step must be
  *bitwise* identical to the K=1 legacy one-event-per-step trajectory,
  because per-event RNG keys derive from the global event index — for every
  rule in the registry (this is the refactor's no-regression guarantee; the
  K=1 path was verified bitwise against the pre-refactor simulator when the
  engine landed);
* fused mode matches serial exactly at K=1 for fused-capable rules (one
  stats step on the single gradient = the serial protocol);
* the batched Pallas scale-and-accumulate kernel equals the generic
  per-leaf scale_leaf reduction;
* FRED and the round trainer account push/fetch opportunities through the
  same engine counters.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainerConfig
from repro.core import engine
from repro.core import rules as server_rules
from repro.core.bandwidth import BandwidthConfig
from repro.core.round_trainer import build_round_step, init_round_state
from repro.core.rules import ServerConfig
from repro.sim.fred import SimConfig, run_simulation

from conftest import tree_allclose, tree_equal

ALL_RULES = server_rules.registered_rules()
FUSED_RULES = tuple(r for r in ALL_RULES
                    if server_rules.get_rule(r).supports_fused)


def _cfg(rule, **kw):
    disp = ("roundrobin" if server_rules.get_rule(rule).synchronous
            else kw.pop("dispatcher", "uniform"))
    return SimConfig(
        num_clients=kw.pop("num_clients", 4), batch_size=8, dispatcher=disp,
        seed=kw.pop("seed", 3),
        server=ServerConfig(rule=rule, lr=0.01, num_clients=4,
                            **kw.pop("server_kwargs", {})),
        **kw)


def _run(cfg, setup, steps=48):
    params, ds, loss = setup
    return run_simulation(
        cfg, loss, params, ds.x_train, ds.y_train, steps, eval_every=steps,
        eval_fn=lambda p: loss(p, ds.x_valid, ds.y_valid))


@pytest.fixture(scope="module")
def setup(mlp_setup):
    return mlp_setup


@pytest.mark.parametrize("rule", ALL_RULES)
def test_serial_event_batching_is_bitwise_k_invariant(setup, rule):
    """Serial K=4 (and a non-divisor K=5) == serial K=1, bitwise, per rule."""
    base = _run(_cfg(rule), setup)
    for k in (4, 5):
        batched = _run(dataclasses.replace(_cfg(rule), events_per_step=k),
                       setup)
        assert tree_equal(base["state"].server.params,
                          batched["state"].server.params), (rule, k)
        assert base["counters"] == batched["counters"], (rule, k)
        assert base["final_timestamp"] == batched["final_timestamp"], (rule, k)


def test_serial_k_invariant_with_gating_and_cache(setup):
    cfg = _cfg("fasgd", seed=7,
               bandwidth=BandwidthConfig(c_push=2.0, c_fetch=2.0,
                                         drop_policy="cache"))
    base = _run(cfg, setup, steps=64)
    batched = _run(dataclasses.replace(cfg, events_per_step=8), setup,
                   steps=64)
    assert tree_equal(base["state"].server.params,
                      batched["state"].server.params)
    assert base["counters"] == batched["counters"]


def test_serial_k_invariant_heterogeneous(setup):
    cfg = _cfg("fasgd", seed=5, num_clients=8, dispatcher="heterogeneous")
    base = _run(cfg, setup, steps=64)
    batched = _run(dataclasses.replace(cfg, events_per_step=16), setup,
                   steps=64)
    assert tree_equal(base["state"].server.params,
                      batched["state"].server.params)


@pytest.mark.parametrize("apply_mode", ["serial", "fused"])
@pytest.mark.parametrize("steps,k", [(7, 1), (130, 1), (130, 8), (100, 16),
                                     (7, 8)])
def test_num_steps_honored_exactly(setup, steps, k, apply_mode):
    """Legacy bug: num_steps < eval_every ran eval_every events; the
    remainder past the last eval chunk was silently dropped.  num_steps must
    be exact for every events_per_step (including K ∤ num_steps remainders
    and num_steps < K) in both apply modes."""
    cfg = dataclasses.replace(_cfg("asgd"), events_per_step=k,
                              apply_mode=apply_mode)
    r = _run_steps(cfg, setup, steps)
    assert r["final_timestamp"] == steps, (steps, k)
    assert r["counters"]["push_potential"] == steps


def _run_steps(cfg, setup, steps):
    params, ds, loss = setup
    return run_simulation(cfg, loss, params, ds.x_train, ds.y_train, steps,
                          eval_every=64)


@pytest.mark.parametrize("rule", FUSED_RULES)
def test_fused_k1_matches_serial(setup, rule):
    """At K=1 the fused masked-sum *is* the serial protocol (one stats step
    on the single gradient) — must hold for every fused-capable rule."""
    serial = _run(_cfg(rule), setup)
    fused = _run(dataclasses.replace(_cfg(rule), apply_mode="fused"), setup)
    assert tree_allclose(serial["state"].server.params,
                         fused["state"].server.params, rtol=1e-4)
    assert serial["final_timestamp"] == fused["final_timestamp"]


@pytest.mark.parametrize("rule", FUSED_RULES)
def test_fused_event_batch_converges(setup, rule):
    """K>1 fused: T advances per push, loss decreases, counters add up."""
    cfg = dataclasses.replace(
        _cfg(rule, num_clients=16), events_per_step=8, apply_mode="fused")
    r = _run(cfg, setup, steps=64)
    assert r["final_timestamp"] == 64
    assert r["counters"]["push_potential"] == 64
    assert r["counters"]["fetch_actual"] == 64
    assert np.isfinite(r["val_cost"]).all()


def test_fused_gating_cache_advances_t_skip_freezes(setup):
    base = dict(num_clients=8, seed=7, events_per_step=4, apply_mode="fused")
    cache = _run(dataclasses.replace(
        _cfg("fasgd", bandwidth=BandwidthConfig(c_push=3.0)), **base),
        setup, steps=64)
    skip = _run(dataclasses.replace(
        _cfg("fasgd", bandwidth=BandwidthConfig(c_push=3.0,
                                                drop_policy="skip")), **base),
        setup, steps=64)
    # cache: every opportunity applies *some* gradient → T = events
    assert cache["final_timestamp"] == 64
    assert cache["counters"]["push_actual"] < 64
    # skip: T advances only on transmitted pushes
    assert skip["final_timestamp"] == skip["counters"]["push_actual"] < 64


def test_rejects_unsupported_configs(setup):
    with pytest.raises(AssertionError, match="fused"):
        _cfg("ssgd", apply_mode="fused")
    # a partially-transmitted gradient is undefined at a round barrier
    with pytest.raises(AssertionError, match="per_tensor_push"):
        _cfg("ssgd", bandwidth=BandwidthConfig(per_tensor_push=True))
    # per-tensor gating in fused mode is exercised (not just constructed)
    # by tests/test_per_tensor.py::test_fused_k1_matches_serial_per_tensor


def test_batched_kernel_matches_generic_fused(setup):
    """use_fused_kernel routes the fused delta through the Pallas batched
    scale-and-accumulate; must equal the generic scale_leaf reduction."""
    for rule in ("fasgd", "sasgd", "asgd"):
        cfg = dataclasses.replace(
            _cfg(rule, num_clients=8), events_per_step=4, apply_mode="fused")
        kcfg = dataclasses.replace(
            cfg, server=dataclasses.replace(cfg.server, use_fused_kernel=True))
        r1 = _run(cfg, setup, steps=16)
        r2 = _run(kcfg, setup, steps=16)
        assert tree_allclose(r1["state"].server.params,
                             r2["state"].server.params,
                             rtol=1e-5, atol=1e-6), rule


def test_last_event_scatter_is_last_wins():
    tree = jnp.zeros((4, 3))
    clients = jnp.array([1, 2, 1, 3])
    values = jnp.arange(12, dtype=jnp.float32).reshape(4, 3) + 1.0
    eligible = jnp.array([True, True, True, False])
    out = engine.last_event_scatter(tree, clients, values, eligible, 4)
    np.testing.assert_array_equal(np.asarray(out[1]), values[2])  # later wins
    np.testing.assert_array_equal(np.asarray(out[2]), values[1])
    np.testing.assert_array_equal(np.asarray(out[3]), np.zeros(3))  # ineligible
    np.testing.assert_array_equal(np.asarray(out[0]), np.zeros(3))


def test_counters_shared_between_fred_and_round_trainer(setup):
    """Both consumers account opportunities through engine.count_events:
    with no gating, actual == potential == events on each path."""
    params, ds, loss = setup
    events = 32
    fred = _run(dataclasses.replace(
        _cfg("fasgd"), events_per_step=8, apply_mode="fused"), setup,
        steps=events)
    assert fred["counters"]["push_potential"] == events
    assert fred["counters"]["push_actual"] == events
    assert fred["counters"]["fetch_actual"] == events

    tc = TrainerConfig(num_round_clients=4, rule="fasgd", lr=0.01)
    st = init_round_state(tc, params)
    step = jax.jit(build_round_step(tc, lambda p, b: jax.value_and_grad(loss)(
        p, b[0], b[1])))
    batch = (jnp.stack([ds.x_train[:8]] * 4), jnp.stack([ds.y_train[:8]] * 4))
    for i in range(events // 4):
        st, _ = step(st, batch, jax.random.PRNGKey(i))
    c = st.counters
    assert int(c.push_potential) == int(c.push_actual) == events
    assert int(c.fetch_potential) == int(c.fetch_actual) == events
    # identical Counters structure from the shared core
    assert type(c) is type(engine.init_counters())


def test_shard_map_fleet_runs_on_host_mesh(setup):
    """Optional client-axis sharding: a 1-device 'clients' mesh must produce
    the same fused trajectory as the unsharded run."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("clients",))
    params, ds, loss = setup
    cfg = dataclasses.replace(
        _cfg("fasgd", num_clients=8), events_per_step=4, apply_mode="fused")
    plain = run_simulation(cfg, loss, params, ds.x_train, ds.y_train, 16,
                           eval_every=16)
    sharded = run_simulation(cfg, loss, params, ds.x_train, ds.y_train, 16,
                             eval_every=16, mesh=mesh)
    assert tree_allclose(plain["state"].server.params,
                         sharded["state"].server.params)


# ---------------------------------------------------------------------------
# cotangent fused path + event dedup
# ---------------------------------------------------------------------------

COTANGENT_RULES = tuple(
    r for r in ALL_RULES
    if server_rules.get_rule(r).coeffs_are_v_independent)


def test_cotangent_rule_flags_consistent():
    """Every v-independent-coefficient rule must also be 'coeff'
    kernelizable and fused-capable (the flag refines, never contradicts)."""
    assert COTANGENT_RULES == ("asgd", "exp", "poly", "sasgd")
    for r in COTANGENT_RULES:
        rule = server_rules.get_rule(r)
        assert rule.supports_fused and rule.batched_pallas_mode == "coeff"
    for r in ("fasgd", "gap", "ssgd"):
        assert not server_rules.get_rule(r).coeffs_are_v_independent


@pytest.mark.parametrize("rule", COTANGENT_RULES)
def test_cotangent_k1_matches_serial(setup, rule):
    """At K=1 the cotangent fused path is the serial protocol, like the
    materialized path (one stats step on the single gradient)."""
    serial = _run(_cfg(rule), setup)
    cot = _run(dataclasses.replace(_cfg(rule), apply_mode="fused",
                                   fused_mode="cotangent"), setup)
    assert tree_allclose(serial["state"].server.params,
                         cot["state"].server.params, rtol=1e-4)
    assert serial["final_timestamp"] == cot["final_timestamp"]


@pytest.mark.parametrize("rule", COTANGENT_RULES)
def test_cotangent_matches_materialized_k8(setup, rule):
    """K>1: cotangent vjp reduction ≡ materialized [K, P] reduction (the
    default uniform dispatcher at λ=4 produces heavy ts collisions, so the
    dedup grouping is exercised with group sizes > 1)."""
    base = dataclasses.replace(
        _cfg(rule), events_per_step=8, apply_mode="fused")
    mat = _run(dataclasses.replace(base, fused_mode="materialized"),
               setup, steps=64)
    cot = _run(dataclasses.replace(base, fused_mode="cotangent"),
               setup, steps=64)
    assert tree_allclose(mat["state"].server.params,
                         cot["state"].server.params, rtol=1e-4, atol=1e-6)
    assert mat["final_timestamp"] == cot["final_timestamp"]
    assert mat["counters"] == cot["counters"]


def test_cotangent_matches_materialized_gated_skip(setup):
    """Push gating (skip policy) rides the cotangent weights: w_k = m_k·c_k."""
    bw = BandwidthConfig(c_push=2.0, c_fetch=2.0, drop_policy="skip")
    base = dataclasses.replace(
        _cfg("sasgd", seed=7, bandwidth=bw),
        events_per_step=8, apply_mode="fused")
    mat = _run(dataclasses.replace(base, fused_mode="materialized"),
               setup, steps=64)
    cot = _run(dataclasses.replace(base, fused_mode="cotangent"),
               setup, steps=64)
    assert tree_allclose(mat["state"].server.params,
                         cot["state"].server.params, rtol=1e-4, atol=1e-6)
    assert mat["counters"] == cot["counters"]
    assert mat["final_timestamp"] == cot["final_timestamp"] < 64


def test_fused_auto_mode_selection(setup):
    """'auto' takes the cotangent path exactly when eligible: bitwise equal
    to the explicit mode it resolves to."""
    sasgd = dataclasses.replace(_cfg("sasgd"), events_per_step=4,
                                apply_mode="fused")
    auto = _run(sasgd, setup)
    cot = _run(dataclasses.replace(sasgd, fused_mode="cotangent"), setup)
    assert tree_equal(auto["state"].server.params,
                      cot["state"].server.params)
    # fasgd is v-dependent: auto must resolve to materialized
    fasgd = dataclasses.replace(_cfg("fasgd"), events_per_step=4,
                                apply_mode="fused")
    assert not fasgd.cotangent_eligible()
    auto_f = _run(fasgd, setup)
    mat_f = _run(dataclasses.replace(fasgd, fused_mode="materialized"),
                 setup)
    assert tree_equal(auto_f["state"].server.params,
                      mat_f["state"].server.params)


def test_cotangent_rejects_ineligible_configs(setup):
    # v-dependent, non-separable rule (gap-aware scale needs the stale
    # copies the cotangent path never materializes; fasgd itself is now
    # v_separable and rides the cotangent path on explicit request)
    with pytest.raises(AssertionError, match="cotangent"):
        dataclasses.replace(_cfg("gap"), apply_mode="fused",
                            fused_mode="cotangent")
    # gradient cache stores per-event gradients the cotangent path never
    # materializes
    with pytest.raises(AssertionError, match="cotangent"):
        dataclasses.replace(
            _cfg("sasgd", bandwidth=BandwidthConfig(c_push=1.0,
                                                    drop_policy="cache")),
            apply_mode="fused", fused_mode="cotangent")
    # per-leaf masks need per-leaf weight vectors
    with pytest.raises(AssertionError, match="cotangent"):
        dataclasses.replace(
            _cfg("sasgd", bandwidth=BandwidthConfig(per_tensor_fetch=True)),
            apply_mode="fused", fused_mode="cotangent")
    # engine-level guards
    params = {"w": jnp.ones((4, 3))}
    scfg = ServerConfig(rule="gap")
    server = server_rules.init(scfg, params)
    with pytest.raises(ValueError, match="cotangent"):
        engine.fused_apply_cotangent(
            scfg, server, lambda W, d: jnp.zeros((2,)),
            engine.tree_stack(params, 2), jnp.ones((2,), bool),
            jnp.zeros((2,), jnp.int32))


def test_dedup_events_grouping():
    ts = jnp.array([3, 5, 3, 7, 5], jnp.int32)
    rep, counts, is_rep = engine.dedup_events(ts)
    np.testing.assert_array_equal(np.asarray(rep), [0, 1, 0, 3, 1])
    np.testing.assert_array_equal(np.asarray(counts), [2, 2, 2, 1, 2])
    np.testing.assert_array_equal(np.asarray(is_rep),
                                  [True, True, False, True, False])
    # all-distinct timestamps: dedup is the identity (no-op)
    rep, counts, is_rep = engine.dedup_events(
        jnp.array([9, 2, 4], jnp.int32))
    np.testing.assert_array_equal(np.asarray(rep), [0, 1, 2])
    np.testing.assert_array_equal(np.asarray(counts), [1, 1, 1])
    assert np.asarray(is_rep).all()
    # per-leaf rows (client_leaf_ts): a group needs ALL leaves to match
    rows = jnp.array([[1, 2], [1, 3], [1, 2]], jnp.int32)
    rep, counts, _ = engine.dedup_events(rows)
    np.testing.assert_array_equal(np.asarray(rep), [0, 1, 0])
    np.testing.assert_array_equal(np.asarray(counts), [2, 1, 2])


def test_event_batched_mlp_loss_matches_vmap(setup):
    """The shared/delta MLP form == vmap(nll_loss) over effective params."""
    from repro.models.mlp import init_mlp, nll_loss
    k_p, k_d, k_x, k_y = jax.random.split(jax.random.PRNGKey(0), 4)
    W = init_mlp(k_p, (10, 6, 4))
    K, mu = 5, 3
    stale = jax.tree.map(
        lambda l: l[None] + 0.05 * jax.random.normal(
            jax.random.fold_in(k_d, l.size), (K,) + l.shape), W)
    deltas = jax.tree.map(lambda s, w: s - w[None], stale, W)
    x = jax.random.normal(k_x, (K, mu, 10))
    y = jax.random.randint(k_y, (K, mu), 0, 4)
    fast = nll_loss.event_batched(W, deltas, x, y)
    generic = engine.event_batched_losses(nll_loss)(W, deltas, x, y)
    direct = jax.vmap(nll_loss)(
        jax.tree.map(lambda w, d: w[None] + d, W, deltas), x, y)
    np.testing.assert_allclose(np.asarray(fast), np.asarray(direct),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(generic), np.asarray(direct),
                               rtol=1e-6, atol=1e-7)


def test_fused_tracks_stats_consistently_with_serial(setup):
    """track_stats=False now skips the fused stats step like the serial
    path does (n/b/v stay at init); the parameter trajectory for a
    v-independent rule is unaffected."""
    cfg = dataclasses.replace(
        _cfg("sasgd", server_kwargs={"track_stats": False}),
        events_per_step=4, apply_mode="fused", fused_mode="materialized")
    on = dataclasses.replace(
        _cfg("sasgd"), events_per_step=4, apply_mode="fused",
        fused_mode="materialized")
    r_off = _run(cfg, setup)
    r_on = _run(on, setup)
    assert tree_allclose(r_off["state"].server.params,
                         r_on["state"].server.params, rtol=1e-5)
    assert tree_equal(r_off["state"].server.v,
                      jax.tree.map(jnp.ones_like, r_off["state"].server.v))
