"""One-kernel event loop (kernels/fused_event_apply.py) vs the split path.

The contract under test (ISSUE: one Pallas launch per leaf per drained
window):

* the kernel body (interpret=True) and the streaming XLA oracle agree with
  each other and with the generic per-leaf fused apply, for both weight
  modes ('coeff' prefolded scalars, 'fasgd' in-kernel eq. 7 scales);
* a FRED simulation with ``use_fused_kernel=True`` is allclose to the
  generic fused path for every ``batched_pallas_mode`` rule, across
  per-tensor gating, event dedup, and all ingress-queue drain policies;
* fasgd's explicit cotangent path (v_separable ε-reparameterization via
  the `reweight_by_v` pullback) is allclose to the materialized reduction;
* kernel-path telemetry (`kernel_launches` / `kernel_events`) appears in
  the counters exactly when the kernel path is on — kernel-off runs keep
  the pre-kernel counter dict, so the replay goldens stay bitwise valid.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core import rules as server_rules
from repro.core.bandwidth import BandwidthConfig
from repro.core.rules import ServerConfig
from repro.kernels.fused_event_apply import LANES, fused_event_apply_2d
from repro.kernels.ops import (APPLY_VMEM_BUDGET, apply_blocks,
                               fused_event_apply, sublanes)
from repro.kernels.ref import fused_event_apply_ref
from repro.sim.fred import SimConfig, run_simulation

from conftest import tree_allclose, tree_equal

KERNEL_RULES = tuple(
    r for r in server_rules.registered_rules()
    if server_rules.get_rule(r).batched_pallas_mode is not None)


def _mk_batch(K, rows, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    p = jax.random.normal(ks[0], (rows, LANES), jnp.float32)
    g = 0.1 * jax.random.normal(ks[1], (K, rows, LANES), jnp.float32)
    n = jnp.abs(0.01 * jax.random.normal(ks[2], (rows, LANES)))
    b = 0.05 * jax.random.normal(ks[3], (rows, LANES))
    v = 1.0 + 0.1 * jax.random.normal(ks[4], (rows, LANES))
    w = jnp.abs(jax.random.normal(ks[5], (K,)))
    wm = jax.nn.softmax(jax.random.normal(ks[6], (K,)))
    taus = jax.random.randint(ks[7], (K,), 1, 6).astype(jnp.float32)
    return p, g, n, b, v, w, wm, taus


@pytest.mark.parametrize("mode", ["fasgd", "coeff"])
@pytest.mark.parametrize("block_rows", [8, 64])
@pytest.mark.parametrize("has_push", [1.0, 0.0])
def test_kernel_2d_matches_ref(mode, block_rows, has_push):
    """Interpreted kernel body == streaming oracle, both modes, push held."""
    K, rows = 5, 64
    p, g, n, b, v, w, wm, taus = _mk_batch(K, rows)
    out_k = fused_event_apply_2d(
        p, g, n, b, v, w, wm, taus, 0.01, has_push, mode=mode,
        block_rows=block_rows, interpret=True)
    out_r = fused_event_apply_ref(
        p, g, n, b, v, w, wm, taus, 0.01, has_push, mode=mode)
    for a, r in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-5, atol=1e-6)
    if has_push == 0.0:   # stats must be held bit-exactly when nothing pushed
        for a, s in zip(out_k[1:], (n, b, v)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(s))


@pytest.mark.parametrize("track_stats", [True, False])
def test_kernel_2d_track_stats_toggle(track_stats):
    """track_stats=False passes n/b/v through and still applies the delta."""
    K, rows = 3, 32
    p, g, n, b, v, w, wm, taus = _mk_batch(K, rows, seed=2)
    po, no, bo, vo = fused_event_apply_2d(
        p, g, n, b, v, w, wm, taus, 0.01, 1.0, mode="coeff",
        track_stats=track_stats, block_rows=8, interpret=True)
    if not track_stats:
        np.testing.assert_array_equal(np.asarray(no), np.asarray(n))
        np.testing.assert_array_equal(np.asarray(vo), np.asarray(v))
    assert not np.allclose(np.asarray(po), np.asarray(p))


F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.mark.parametrize("shape,dtype,block_rows", [
    pytest.param((7,), F32, 0, id="shape0"),
    pytest.param((130,), F32, 0, id="shape1"),
    pytest.param((3, 5, 7), F32, 0, id="shape2"),
    pytest.param((256, 128), F32, 0, id="shape3"),
    # the MLP's leaves, as the FRED cells hold them
    pytest.param((784, 200), F32, 0, id="mlp_w1"),
    pytest.param((200,), F32, 0, id="mlp_b1"),
    pytest.param((200, 10), F32, 0, id="mlp_w2"),
    pytest.param((10,), F32, 0, id="mlp_b2"),
    # layer-stacked: 32 rows merge into (128, 136); 5 rows cannot, and
    # the 4 layers take a squeezed grid axis
    pytest.param((4, 32, 136), F32, 0, id="stacked"),
    pytest.param((4, 5, 136), F32, 0, id="stacked_squeezed"),
    # 100 rows in blocks of 16: a ragged last row block
    pytest.param((100, 200), F32, 16, id="ragged_rows"),
    # wide enough that the lane block is narrower than C, ragged at the end
    pytest.param((16, 20000), F32, 0, id="lane_blocks"),
    # bf16 params and gradients, float32 statistics
    pytest.param((48, 136), BF16, 0, id="bf16_params"),
])
def test_ops_wrapper_ragged_shapes(shape, dtype, block_rows):
    """ops.fused_event_apply reads each leaf in its own layout, in blocks
    that need not divide it; the interpret and streaming-XLA dispatch paths
    agree with the oracle."""
    K = 4
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    p = jax.random.normal(ks[0], shape).astype(dtype)
    g = (0.1 * jax.random.normal(ks[1], (K,) + shape)).astype(dtype)
    n = jnp.abs(0.01 * jax.random.normal(ks[2], shape))
    b = jnp.zeros(shape)
    v = 1.0 + 0.1 * jnp.abs(jax.random.normal(ks[3], shape))
    w = jnp.array([0.5, 0.0, 1.0, 0.25])
    wm = jnp.array([0.25] * K)
    taus = jnp.array([1.0, 2.0, 3.0, 4.0])
    tree = lambda x: {"a": x, "b": x * 2}
    ref = fused_event_apply_ref(p, g, n, b, v, w, wm, taus, 0.01, 1.0)
    # one bf16 unit in the last place, where the params are bf16
    rtol = [1e-5 if dtype == F32 else 8e-3] + [1e-5] * 3
    for interp in (True, None):   # None → CPU auto → streaming XLA path
        out = fused_event_apply(
            tree(p), tree(g), tree(n), tree(b), tree(v), tree(w), tree(wm),
            tree(taus), tree(jnp.asarray(1.0)), lr=0.01, interpret=interp,
            block_rows=block_rows)
        for o, r, tol in zip(out, ref, rtol):
            np.testing.assert_allclose(
                np.asarray(o["a"], np.float32), np.asarray(r, np.float32),
                rtol=tol, atol=1e-6)
        assert out[0]["a"].shape == shape and out[0]["a"].dtype == dtype


def _block_vmem(br, bc, K, p_dtype, g_dtype):
    """VMEM of one grid step: each operand's block at its native tile's
    padding, double-buffered, and eight float32 temporaries."""
    def tile(rows, dtype):
        return (-(-rows // sublanes(dtype)) * sublanes(dtype)
                * -(-bc // LANES) * LANES * jnp.dtype(dtype).itemsize)
    blocks = (K * tile(br, g_dtype) + 2 * tile(br, p_dtype)
              + 6 * tile(br, jnp.float32))
    return 2 * blocks + 8 * tile(br, jnp.float32)


@pytest.mark.parametrize("R,C,K,dtype", [
    (784, 200, 128, F32), (1, 200, 128, F32), (200, 10, 128, F32),
    (1, 10, 128, F32),                                 # the MLP at K=128
    (2048, 50304, 4, BF16), (50304, 2048, 4, BF16),    # the LM's vocab leaves
    (8192, 8512, 4, BF16), (4, 4352, 4, BF16),         # in_proj, conv_w
    (16, 20000, 4, F32), (100, 200, 4, F32),
], ids=["w1", "b1", "w2", "b2", "unembed", "embed", "in_proj", "conv_w",
        "wide", "small"])
def test_apply_blocks_fit_vmem_budget(R, C, K, dtype):
    """The blocks come from one VMEM budget: rows R or a multiple of the
    sublane tile, lanes C or a multiple of 128, within the budget, and the
    full width wherever a sublane-tall full-width block fits."""
    sub = max(sublanes(dtype), sublanes(jnp.float32))
    br, bc = apply_blocks(R, C, K, dtype, dtype)
    assert br == R or (br % sub == 0 and br < R)
    assert bc == C or (bc % LANES == 0 and bc < C)
    assert _block_vmem(br, bc, K, dtype, dtype) <= APPLY_VMEM_BUDGET
    assert (bc == C) == (_block_vmem(sub, C, K, dtype, dtype)
                         <= APPLY_VMEM_BUDGET)
    # an override sets the row block, rounded to the sublane tile
    assert apply_blocks(R, C, K, dtype, dtype, block_rows=R + 1)[0] == R
    if R > 2 * sub:
        assert apply_blocks(R, C, K, dtype, dtype,
                            block_rows=sub + 1)[0] == sub


def _cfg(rule, **kw):
    return SimConfig(
        num_clients=kw.pop("num_clients", 4), batch_size=8,
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


def _strip_kernel(counters):
    return {k: v for k, v in counters.items() if not k.startswith("kernel_")}


@pytest.mark.parametrize("rule", KERNEL_RULES)
def test_one_kernel_sim_matches_generic(setup, rule):
    """Kernel-on fused run == kernel-off fused run, for every kernelizable
    rule, with eq.-9 gating on both directions.  The first windows start
    all-clients-at-ts-0, so event dedup grouping is exercised too."""
    base = dataclasses.replace(
        _cfg(rule, seed=7,
             bandwidth=BandwidthConfig(c_push=2.0, c_fetch=2.0)),
        events_per_step=8, apply_mode="fused", fused_mode="materialized")
    off = _run(base, setup, steps=64)
    on = _run(dataclasses.replace(
        base, server=dataclasses.replace(base.server,
                                         use_fused_kernel=True)),
        setup, steps=64)
    assert tree_allclose(off["state"].server.params,
                         on["state"].server.params, rtol=1e-4, atol=1e-6)
    assert tree_allclose(off["state"].server.v, on["state"].server.v,
                         rtol=1e-4, atol=1e-6)
    assert off["final_timestamp"] == on["final_timestamp"]
    assert off["counters"] == _strip_kernel(on["counters"])


def test_one_kernel_interpret_matches_generic(setup):
    """The actual Pallas kernel body (interpret=True) inside a short fused
    simulation — not just the streaming-XLA stand-in."""
    base = dataclasses.replace(_cfg("fasgd", seed=5), events_per_step=4,
                               apply_mode="fused")
    off = _run(base, setup, steps=16)
    on = _run(dataclasses.replace(
        base, server=dataclasses.replace(
            base.server, use_fused_kernel=True, kernel_interpret=True,
            kernel_block_rows=8)),
        setup, steps=16)
    assert tree_allclose(off["state"].server.params,
                         on["state"].server.params, rtol=1e-4, atol=1e-6)


def test_one_kernel_per_tensor_gating(setup):
    """Per-leaf push masks and per-leaf staleness ride the kernel's SMEM
    weight vectors (one launch per leaf, leaf-specific w/τ)."""
    base = dataclasses.replace(
        _cfg("fasgd", seed=9,
             bandwidth=BandwidthConfig(c_push=2.0, c_fetch=2.0,
                                       per_tensor_push=True,
                                       per_tensor_fetch=True)),
        events_per_step=8, apply_mode="fused")
    off = _run(base, setup, steps=48)
    on = _run(dataclasses.replace(
        base, server=dataclasses.replace(base.server,
                                         use_fused_kernel=True)),
        setup, steps=48)
    assert tree_allclose(off["state"].server.params,
                         on["state"].server.params, rtol=1e-4, atol=1e-6)
    assert off["counters"] == _strip_kernel(on["counters"])


@pytest.mark.parametrize("drain_policy", ["drain_all", "drain_k", "adaptive"])
def test_one_kernel_queue_drain(setup, drain_policy):
    """Every drained window feeds the kernel in one launch per leaf, for
    each drain policy; trajectory matches the kernel-off queue run."""
    base = dataclasses.replace(
        _cfg("fasgd", seed=11), events_per_step=4, apply_mode="fused",
        queue_capacity=8, admission_policy="reject",
        drain_policy=drain_policy, drain_k=2)
    off = _run(base, setup, steps=48)
    on = _run(dataclasses.replace(
        base, server=dataclasses.replace(base.server,
                                         use_fused_kernel=True)),
        setup, steps=48)
    assert tree_allclose(off["state"].server.params,
                         on["state"].server.params, rtol=1e-4, atol=1e-6)
    assert off["counters"] == _strip_kernel(on["counters"])
    assert on["counters"]["kernel_events"] \
        == on["counters"]["queue_drained"]


def test_cotangent_fasgd_matches_materialized(setup):
    """fasgd's explicit cotangent opt-in (v_separable split through the
    reweight_by_v pullback) tracks the materialized fused reduction; 'auto'
    must NOT resolve to it (the split is ε-approximate)."""
    base = dataclasses.replace(_cfg("fasgd", seed=7), events_per_step=8,
                               apply_mode="fused")
    assert base.cotangent_serviceable() and not base.cotangent_eligible()
    mat = _run(dataclasses.replace(base, fused_mode="materialized"),
               setup, steps=64)
    cot = _run(dataclasses.replace(base, fused_mode="cotangent"),
               setup, steps=64)
    auto = _run(base, setup, steps=64)
    assert tree_allclose(mat["state"].server.params,
                         cot["state"].server.params, rtol=1e-4, atol=1e-6)
    assert mat["counters"] == cot["counters"]
    # 'auto' resolves to materialized for v_separable-only rules: bitwise
    assert tree_equal(mat["state"].server.params,
                      auto["state"].server.params)


def test_kernel_counters_only_when_kernel_on(setup):
    """kernel_launches/kernel_events appear iff the kernel path is on —
    kernel-off counter dicts are unchanged, keeping replay goldens bitwise
    valid."""
    base = dataclasses.replace(_cfg("fasgd"), events_per_step=4,
                               apply_mode="fused")
    off = _run(base, setup, steps=16)
    assert not any(k.startswith("kernel_") for k in off["counters"])
    on = _run(dataclasses.replace(
        base, server=dataclasses.replace(base.server,
                                         use_fused_kernel=True)),
        setup, steps=16)
    n_leaves = len(jax.tree.leaves(on["state"].server.params))
    assert on["counters"]["kernel_launches"] == 4 * n_leaves  # 4 windows
    assert on["counters"]["kernel_events"] == 16


def test_reweight_by_v_pullback():
    """The custom vjp carries v through: d/dW of (W·vfac-contraction) is
    exactly the elementwise vfactor scaling of the cotangent."""
    vfac = {"w": jnp.array([0.5, 2.0, 4.0])}
    W = {"w": jnp.array([1.0, 1.0, 1.0])}
    _, pull = jax.vjp(lambda p: engine.reweight_by_v(p, vfac), W)
    ct = pull({"w": jnp.array([1.0, 10.0, 100.0])})[0]
    np.testing.assert_allclose(np.asarray(ct["w"]), [0.5, 20.0, 400.0])
