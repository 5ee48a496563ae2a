"""Bounded server ingress queue (core/queue.py) — ring mechanics, admission
and drain policies, byte accounting, load telemetry, and the end-to-end
queued simulation/trainer paths.

The tentpole invariant: with ``queue_capacity=1`` and ``drain_all`` the
queued simulation is *bitwise identical* to the immediate-apply path for
every asynchronous registry rule — the queue is a strict generalization of
the existing protocol, not a parallel implementation of it.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainerConfig
from repro.core import engine
from repro.core import queue as qlib
from repro.core import rules as server_rules
from repro.core.bandwidth import BandwidthConfig, tree_bytes
from repro.core.round_trainer import build_round_step, init_round_state
from repro.core.rules import ServerConfig
from repro.sim.fred import SimConfig, run_simulation

from conftest import tree_allclose, tree_equal

ASYNC_RULES = [r for r in server_rules.registered_rules()
               if not server_rules.get_rule(r).synchronous]


def _cfg(rule, **kw):
    return SimConfig(
        num_clients=kw.pop("num_clients", 4), batch_size=8,
        dispatcher=kw.pop("dispatcher", "uniform"), seed=kw.pop("seed", 3),
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


# ---------------------------------------------------------------------------
# ring mechanics (pure queue ops)
# ---------------------------------------------------------------------------

def _mk_queue(cap):
    return qlib.init_queue(cap, {"x": jnp.zeros((), jnp.float32)})


def _arrivals(vals, valid=None, ts=None, clients=None):
    vals = jnp.asarray(vals, jnp.float32)
    k = vals.shape[0]
    return qlib.Arrivals(
        payload={"x": vals},
        ts=jnp.asarray(ts if ts is not None else np.zeros(k), jnp.int32),
        client=jnp.asarray(
            clients if clients is not None else np.arange(k), jnp.int32),
        valid=jnp.asarray(
            valid if valid is not None else np.ones(k, bool)))


def _drain_all_values(q):
    q, batch = qlib.dequeue(q, q.size)
    return np.asarray(batch.payload["x"])[np.asarray(batch.valid)]


def test_ring_fifo_order_and_wraparound():
    q = _mk_queue(4)
    q, adm, rej, drop = qlib.enqueue(q, _arrivals([1, 2, 3]), "reject", 0)
    assert adm.all() and int(rej) == 0 and int(drop) == 0
    q, batch = qlib.dequeue(q, jnp.int32(2))        # pops 1, 2; head wraps
    got = np.asarray(batch.payload["x"])[np.asarray(batch.valid)]
    np.testing.assert_array_equal(got, [1, 2])
    q, adm, _, _ = qlib.enqueue(q, _arrivals([4, 5, 6]), "reject", 0)
    assert adm.all()
    assert int(q.size) == 4
    np.testing.assert_array_equal(_drain_all_values(q), [3, 4, 5, 6])


def test_invalid_arrivals_never_enqueue():
    q = _mk_queue(4)
    q, adm, rej, drop = qlib.enqueue(
        q, _arrivals([1, 2, 3, 4], valid=[True, False, True, False]),
        "reject", 0)
    np.testing.assert_array_equal(np.asarray(adm), [True, False, True, False])
    assert int(rej) == 0 and int(q.size) == 2
    np.testing.assert_array_equal(_drain_all_values(q), [1, 3])


def test_reject_admits_in_arrival_order():
    q = _mk_queue(2)
    q, adm, rej, drop = qlib.enqueue(q, _arrivals([1, 2, 3, 4]), "reject", 0)
    np.testing.assert_array_equal(np.asarray(adm), [True, True, False, False])
    assert int(rej) == 2 and int(drop) == 0 and int(q.size) == 2
    np.testing.assert_array_equal(_drain_all_values(q), [1, 2])


def test_drop_oldest_evicts_head():
    q = _mk_queue(3)
    q, _, _, _ = qlib.enqueue(q, _arrivals([1, 2, 3]), "drop_oldest", 0)
    q, adm, rej, drop = qlib.enqueue(q, _arrivals([4, 5]), "drop_oldest", 0)
    assert adm.all() and int(rej) == 0 and int(drop) == 2
    np.testing.assert_array_equal(_drain_all_values(q), [3, 4, 5])


def test_drop_oldest_window_beyond_capacity_keeps_newest():
    q = _mk_queue(2)
    q, adm, rej, drop = qlib.enqueue(
        q, _arrivals([1, 2, 3, 4, 5]), "drop_oldest", 0)
    assert adm.all()                 # all transmitted (then partly evicted)
    assert int(drop) == 3 and int(q.size) == 2
    np.testing.assert_array_equal(_drain_all_values(q), [4, 5])


def test_enqueue_stamps_admission_timestamp():
    q = _mk_queue(3)
    q, _, _, _ = qlib.enqueue(q, _arrivals([1]), "reject", 7)
    q, _, _, _ = qlib.enqueue(q, _arrivals([2]), "reject", 9)
    _, batch = qlib.dequeue(q, q.size)
    valid = np.asarray(batch.valid)
    np.testing.assert_array_equal(np.asarray(batch.enq_T)[valid], [7, 9])


def test_drain_count_policies():
    size = jnp.int32(10)
    assert int(qlib.drain_count(size, "drain_all")) == 10
    assert int(qlib.drain_count(size, "drain_k", drain_k=3)) == 3
    assert int(qlib.drain_count(jnp.int32(2), "drain_k", drain_k=3)) == 2
    # adaptive: ceil(gain·size) with a drain_k floor, capped at size
    assert int(qlib.drain_count(size, "adaptive", drain_k=1, gain=0.5)) == 5
    assert int(qlib.drain_count(jnp.int32(3), "adaptive",
                                drain_k=1, gain=0.5)) == 2
    assert int(qlib.drain_count(jnp.int32(1), "adaptive",
                                drain_k=4, gain=0.1)) == 1   # capped at size
    assert int(qlib.drain_count(jnp.int32(9), "adaptive",
                                drain_k=4, gain=0.1)) == 4   # floor wins
    assert int(qlib.drain_count(jnp.int32(0), "adaptive",
                                drain_k=2, gain=0.5)) == 0


# ---------------------------------------------------------------------------
# tentpole: cap=1 drain_all ≡ immediate apply, bitwise, every async rule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule", ASYNC_RULES)
def test_queue_cap1_drain_all_bitwise_identical(setup, rule):
    base = _run(_cfg(rule), setup)
    queued = _run(dataclasses.replace(
        _cfg(rule), queue_capacity=1, drain_policy="drain_all",
        admission_policy="block"), setup)
    assert tree_equal(base["state"].server.params,
                      queued["state"].server.params)
    assert base["val_cost"] == queued["val_cost"]
    assert base["final_timestamp"] == queued["final_timestamp"]
    # every shared counter agrees; the queued run adds only queue telemetry
    for k, v in base["counters"].items():
        assert queued["counters"][k] == v, k
    assert queued["counters"]["queue_drained"] == base["final_timestamp"]


def test_queue_cap1_drain_all_bitwise_identical_gated(setup):
    """Same identity under eq.-9 gating ('skip' drop policy: a gated-out
    push never arrives, so it never enqueues)."""
    bw = BandwidthConfig(c_push=1e-3, c_fetch=1e-3, drop_policy="skip")
    base = _run(_cfg("asgd", bandwidth=bw, seed=5), setup)
    queued = _run(dataclasses.replace(
        _cfg("asgd", bandwidth=bw, seed=5), queue_capacity=1,
        drain_policy="drain_all", admission_policy="block"), setup)
    assert tree_equal(base["state"].server.params,
                      queued["state"].server.params)
    for k, v in base["counters"].items():
        assert queued["counters"][k] == v, k


def test_queue_counters_reported_in_all_apply_modes(setup):
    """Queue depth/drop/latency telemetry must surface from the serial,
    fused-materialized, and fused-cotangent apply paths alike."""
    runs = {}
    for name, extra in {
        "serial": dict(apply_mode="serial"),
        "materialized": dict(apply_mode="fused", fused_mode="materialized"),
        "cotangent": dict(apply_mode="fused", fused_mode="cotangent"),
    }.items():
        cfg = dataclasses.replace(
            _cfg("asgd", num_clients=8), events_per_step=4,
            queue_capacity=16, drain_policy="drain_k", drain_k=2,
            admission_policy="reject", **extra)
        runs[name] = _run(cfg, setup, steps=32)
        c = runs[name]["counters"]
        for key in ("queue_enqueued", "queue_rejected", "queue_dropped",
                    "queue_drained", "queue_depth_sum", "queue_depth_peak",
                    "queue_latency_sum", "queue_windows"):
            assert key in c, (name, key)
        assert c["queue_windows"] == 8
        assert c["queue_depth_peak"] > 0
        assert c["queue_latency_sum"] > 0          # backlog ⇒ waiting events
        # conservation: everything admitted is still queued or was applied
        assert (c["queue_enqueued"] - c["queue_drained"]
                == float(runs[name]["state"].queue.size))
    # all three modes drain the same schedule; the two fused reductions of
    # the same drained batches must agree numerically
    assert (runs["materialized"]["counters"]
            == runs["cotangent"]["counters"])
    assert tree_allclose(runs["materialized"]["state"].server.params,
                         runs["cotangent"]["state"].server.params,
                         rtol=1e-5, atol=1e-6)


def test_queue_immediate_path_reports_no_queue_counters(setup):
    r = _run(_cfg("asgd"), setup, steps=8)
    assert not any(k.startswith("queue_") for k in r["counters"])


def test_queue_with_batched_pallas_kernel(setup):
    """The drained fused batch routes through the batched Pallas kernel
    under use_fused_kernel — must match the generic reduction."""
    cfg = dataclasses.replace(
        _cfg("fasgd", num_clients=8), events_per_step=4, apply_mode="fused",
        queue_capacity=16, drain_policy="drain_k", drain_k=2,
        admission_policy="reject")
    kcfg = dataclasses.replace(
        cfg, server=dataclasses.replace(cfg.server, use_fused_kernel=True))
    r1 = _run(cfg, setup, steps=16)
    r2 = _run(kcfg, setup, steps=16)
    assert tree_allclose(r1["state"].server.params,
                         r2["state"].server.params, rtol=1e-5, atol=1e-6)
    # kernel-on adds the kernel_* telemetry keys (filtered when off); the
    # protocol counters themselves must be untouched by the kernel path
    c2 = {k: v for k, v in r2["counters"].items()
          if not k.startswith("kernel_")}
    assert r1["counters"] == c2
    assert r2["counters"]["kernel_launches"] > 0
    assert r2["counters"]["kernel_events"] == r2["counters"]["queue_drained"]


def test_queue_per_tensor_gating_end_to_end(setup):
    """Per-leaf push masks and per-tensor staleness ride the ring (leaf_mask
    / leaf_ts fields) through both apply modes."""
    bw = BandwidthConfig(c_push=1e-4, c_fetch=1e-4, drop_policy="skip",
                         per_tensor_push=True, per_tensor_fetch=True)
    for mode in ("serial", "fused"):
        cfg = dataclasses.replace(
            _cfg("fasgd", num_clients=8, bandwidth=bw), events_per_step=4,
            apply_mode=mode, queue_capacity=16, drain_policy="drain_k",
            drain_k=2, admission_policy="reject")
        r = _run(cfg, setup, steps=32)
        c = r["counters"]
        assert c["queue_windows"] == 8, mode
        # per-leaf byte resolution survives admission accounting
        assert c["push_bytes_sent"] <= c["push_bytes_total"]
        assert c["queue_enqueued"] <= c["push_potential"]


# ---------------------------------------------------------------------------
# byte accounting under each admission policy (satellite: no double-counting)
# ---------------------------------------------------------------------------

def _loaded_cfg(admission, **kw):
    """Deterministic load: ungated roundrobin pushes, 4 arrivals/window
    against a capacity-2 ring drained 1 event/window."""
    return dataclasses.replace(
        _cfg("asgd", dispatcher="roundrobin"), events_per_step=4,
        queue_capacity=2, drain_policy="drain_k", drain_k=1,
        admission_policy=admission, **kw)


def test_reject_byte_accounting_pinned(setup):
    """cap=2, 4 arrivals/window, drain 1/window, 8 windows: the window-by-
    window admission arithmetic is exact — and rejected pushes contribute
    zero sent bytes."""
    params, _, _ = setup
    model_bytes = float(tree_bytes(params))
    r = _run(_loaded_cfg("reject"), setup, steps=32)
    c = r["counters"]
    # w1 admits 2 (ring empty), then the steady state admits 1 per window
    assert c["queue_enqueued"] == 9
    assert c["queue_rejected"] == 23
    assert c["queue_dropped"] == 0
    assert c["queue_drained"] == 8
    assert c["queue_windows"] == 8
    assert c["queue_depth_peak"] == 2
    assert c["queue_depth_sum"] == 8          # post-drain depth is 1/window
    # e1 drains the window it arrived (lat 0); every later drain waited one
    # window during which T advanced by 1
    assert c["queue_latency_sum"] == 7
    assert r["final_timestamp"] == 8          # one applied push per window
    # byte accounting: sent == admitted only; potential == every opportunity
    assert c["push_actual"] == 9
    assert c["push_potential"] == 32
    assert c["push_bytes_sent"] == 9 * model_bytes
    assert c["push_bytes_total"] == 32 * model_bytes


def test_drop_oldest_byte_accounting_pinned(setup):
    """drop_oldest admits (and bills) every push — eviction discards the
    gradient but the bytes already crossed the wire, exactly once."""
    params, _, _ = setup
    model_bytes = float(tree_bytes(params))
    r = _run(_loaded_cfg("drop_oldest"), setup, steps=32)
    c = r["counters"]
    assert c["queue_enqueued"] == 32          # everything admitted
    assert c["queue_rejected"] == 0
    assert c["queue_dropped"] == 23           # w1 drops 2, then 3 per window
    assert c["queue_drained"] == 8
    assert c["push_actual"] == 32
    assert c["push_bytes_sent"] == 32 * model_bytes
    assert c["push_bytes_total"] == 32 * model_bytes
    # conservation: admitted = drained + evicted + still queued
    assert (c["queue_enqueued"] - c["queue_drained"] - c["queue_dropped"]
            == float(r["state"].queue.size))


def test_block_byte_accounting_lossless(setup):
    """'block' is validated to make overflow impossible: nothing is ever
    rejected or dropped and sent bytes equal potential bytes."""
    params, _, _ = setup
    model_bytes = float(tree_bytes(params))
    cfg = dataclasses.replace(
        _cfg("asgd", dispatcher="roundrobin"), events_per_step=4,
        queue_capacity=4, drain_policy="drain_all", admission_policy="block")
    r = _run(cfg, setup, steps=32)
    c = r["counters"]
    assert c["queue_rejected"] == 0 and c["queue_dropped"] == 0
    assert c["queue_enqueued"] == c["queue_drained"] == 32
    assert c["push_bytes_sent"] == c["push_bytes_total"] == 32 * model_bytes


def test_adaptive_drain_tracks_backlog(setup):
    """adaptive drains ceil(gain·depth): deep backlogs shed in large batches
    (no rejects at this capacity) while drain_k=1 at the same load must
    shed arrivals."""
    base = dict(events_per_step=8, queue_capacity=24,
                admission_policy="reject")
    adaptive = _run(dataclasses.replace(
        _cfg("asgd", num_clients=8, dispatcher="roundrobin"),
        drain_policy="adaptive", drain_k=1, drain_adaptive_gain=0.5,
        **base), setup, steps=64)
    fixed = _run(dataclasses.replace(
        _cfg("asgd", num_clients=8, dispatcher="roundrobin"),
        drain_policy="drain_k", drain_k=1, **base), setup, steps=64)
    ca, cf = adaptive["counters"], fixed["counters"]
    assert ca["queue_rejected"] == 0          # adaptive keeps up
    assert cf["queue_rejected"] > 0           # fixed rate cannot
    assert ca["queue_drained"] > cf["queue_drained"]
    # adaptive keeps the backlog shallow; the fixed drain pins it at capacity
    depth_a = ca["queue_depth_sum"] / ca["queue_windows"]
    depth_f = cf["queue_depth_sum"] / cf["queue_windows"]
    assert depth_a < depth_f
    assert cf["queue_depth_peak"] == 24


# ---------------------------------------------------------------------------
# config validation (satellite: clear errors, not silent misbehavior)
# ---------------------------------------------------------------------------

def test_sim_config_queue_validation():
    ok = dict(queue_capacity=4, drain_policy="drain_all",
              admission_policy="block")
    _cfg("asgd", **ok)                        # sanity: the base is valid
    with pytest.raises(ValueError, match="queue_capacity must be >= 0"):
        _cfg("asgd", queue_capacity=-1)
    with pytest.raises(ValueError, match="unknown drain_policy"):
        _cfg("asgd", **{**ok, "drain_policy": "bogus"})
    with pytest.raises(ValueError, match="unknown admission_policy"):
        _cfg("asgd", **{**ok, "admission_policy": "bogus"})
    with pytest.raises(ValueError, match="synchronous rule"):
        SimConfig(dispatcher="roundrobin",
                  server=ServerConfig(rule="ssgd"), **ok)
    with pytest.raises(ValueError, match="drain_k must be >= 1"):
        _cfg("asgd", queue_capacity=4, drain_policy="drain_k", drain_k=0,
             admission_policy="reject")
    with pytest.raises(ValueError, match="drain_adaptive_gain"):
        _cfg("asgd", queue_capacity=4, drain_policy="adaptive",
             drain_adaptive_gain=0.0, admission_policy="reject")
    with pytest.raises(ValueError, match="gradient cache"):
        _cfg("asgd", bandwidth=BandwidthConfig(c_push=1.0,
                                               drop_policy="cache"), **ok)
    # 'block' requires overflow to be impossible by construction
    with pytest.raises(ValueError, match="lossless backpressure"):
        _cfg("asgd", queue_capacity=4, drain_policy="drain_k",
             admission_policy="block")
    with pytest.raises(ValueError, match="queue_capacity >= events_per_step"):
        _cfg("asgd", events_per_step=8, **{**ok, "queue_capacity": 4})


def test_round_trainer_queue_validation():
    grad_fn = lambda p, b: (jnp.float32(0), p)
    with pytest.raises(ValueError, match="synchronous rule"):
        build_round_step(TrainerConfig(rule="ssgd", queue_capacity=4),
                         grad_fn)
    with pytest.raises(ValueError, match="num_round_clients"):
        build_round_step(TrainerConfig(num_round_clients=8,
                                       queue_capacity=4), grad_fn)
    with pytest.raises(ValueError, match="cotangent"):
        build_round_step(
            TrainerConfig(queue_capacity=8, rule="asgd",
                          drop_policy="discard", fused_mode="cotangent"),
            grad_fn, apply_mode="fused")
    with pytest.raises(ValueError, match="unknown drain_policy"):
        build_round_step(TrainerConfig(queue_capacity=4,
                                       drain_policy="nope"), grad_fn)


def test_queue_rejects_client_axis_mesh(setup):
    from repro.launch.mesh import make_mesh
    params, ds, loss = setup
    cfg = dataclasses.replace(
        _cfg("fasgd", num_clients=8), events_per_step=4, apply_mode="fused",
        queue_capacity=8, drain_policy="drain_all", admission_policy="block")
    with pytest.raises(ValueError, match="client-axis mesh"):
        run_simulation(cfg, loss, params, ds.x_train, ds.y_train, 8,
                       eval_every=8, mesh=make_mesh((1,), ("clients",)))


# ---------------------------------------------------------------------------
# round trainer end-to-end
# ---------------------------------------------------------------------------

def _round_run(tc, setup, apply_mode, rounds=8):
    params, ds, loss = setup
    C = tc.num_round_clients
    state = init_round_state(tc, params)
    step = jax.jit(build_round_step(
        tc, lambda p, b: jax.value_and_grad(loss)(p, b[0], b[1]),
        apply_mode=apply_mode))
    batch = (jnp.stack([ds.x_train[:8]] * C), jnp.stack([ds.y_train[:8]] * C))
    for i in range(rounds):
        state, metrics = step(state, batch, jax.random.PRNGKey(i))
    return state, metrics


@pytest.mark.parametrize("apply_mode", ["serial", "fused"])
def test_round_trainer_queue_drain_all_identity(setup, apply_mode):
    """drain_all with room for all C pushes reduces to the unqueued round."""
    base, _ = _round_run(TrainerConfig(num_round_clients=4, rule="fasgd",
                                       lr=0.01), setup, apply_mode)
    queued, m = _round_run(
        TrainerConfig(num_round_clients=4, rule="fasgd", lr=0.01,
                      queue_capacity=4, drain_policy="drain_all",
                      admission_policy="block"), setup, apply_mode)
    assert tree_equal(base.server.params, queued.server.params)
    assert int(base.server.timestamp) == int(queued.server.timestamp)
    assert int(queued.counters.queue_rejected) == 0
    assert float(m["queue_depth"]) == 0.0


def test_round_trainer_queue_loaded_server(setup):
    """A rate-limited drain builds backlog: staleness grows, rejected pushes
    fall back to the client's drop_policy, telemetry accounts every event."""
    tc = TrainerConfig(num_round_clients=4, rule="fasgd", lr=0.01,
                       queue_capacity=6, drain_policy="drain_k", drain_k=2,
                       admission_policy="reject")
    state, metrics = _round_run(tc, setup, "fused", rounds=8)
    c = state.counters
    assert int(c.queue_rejected) > 0
    assert int(c.push_actual) == int(c.queue_enqueued)
    assert (int(c.queue_enqueued) - int(c.queue_drained)
            == int(state.queue.size))
    assert int(c.queue_depth_peak) == 6
    assert float(metrics["mean_tau"]) > 1.0   # backlog ⇒ stale applies
