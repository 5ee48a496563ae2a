"""The main path's kernels, compiled for a TPU v5e that is described, not
attached.

Each case lowers and compiles for one chip (or the 2x2 host) of a described
``v5e:2x2`` topology: the TPU compiler refuses what interpret mode accepts
(unaligned tiles, too much VMEM, programs over HBM, kernels it cannot
partition).  Nothing runs, so these cases say nothing about results or
times.  The topology is described inside a fixture, never at import: only
the xdist worker given this file loads the TPU compiler.
"""
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import server_shard
from repro.data.mnist import make_synth_mnist
from repro.kernels import ops
from repro.models.mlp import init_mlp, nll_loss
from repro.sim.fred import build_step_fn, init_sim

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MLP_LEAF = (784, 200)          # the paper's first layer
MLP_W2 = (200, 10)             # its second, narrower than a lane tile
LM_LEAF = (2048, 5632)         # tinyllama-1.1b's d_model x d_ff
LM_HEAD = (2048, 50304)        # the Mamba2 cell's unembedding


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _instructions(text, opcode):
    """{name: (result type, line)} of the compiled program's `opcode`
    instructions, in every computation."""
    pat = rf"^\s*(?:ROOT )?%(\S+) = (\S+) {re.escape(opcode)}\("
    return {m.group(1): (m.group(2), m.group(0))
            for m in re.finditer(pat + r".*$", text, re.M)}


def _producer(text, name):
    """The instruction that defines `%name`, through any bitcasts:
    (opcode, result type, op_name)."""
    while True:
        m = re.search(rf"^\s*(?:ROOT )?%{re.escape(name)} = (\S+) "
                      rf"([\w-]+)\((%[^,)\s]+)?(.*)$", text, re.M)
        opcode, operand = m.group(2), m.group(3)
        if opcode != "bitcast":
            op_name = re.search(r'op_name="([^"]*)"', m.group(4))
            return opcode, m.group(1), op_name and op_name.group(1)
        name = operand[1:]


def _launches(text, kernel):
    """The compiled program's custom calls named after `kernel` (the
    `pallas_call`'s `name=`), as a device trace shows them."""
    return re.findall(rf"%{kernel}(?:\.\d+)? = [^\n]*custom-call\(", text)


def _compile_cases():
    leaves = {"mlp": MLP_LEAF, "lm": LM_LEAF}
    dtypes = {"f32": jnp.float32, "bf16": jnp.bfloat16}
    cases = [pytest.param(K, leaves[l], dtypes[d], id=f"{K}-{l}-{d}")
             for K in (16, 128) for l in leaves for d in dtypes]
    # the widest leaf of the LM cell, whose lane block is what keeps the
    # kernel inside its VMEM
    return cases + [pytest.param(4, LM_HEAD, jnp.bfloat16,
                                 id="4-lm_head-bf16")]


@pytest.mark.parametrize("K,leaf,dtype", _compile_cases())
def test_fused_event_apply_compiles(one_chip, K, leaf, dtype):
    """The one-kernel apply at the blocks its VMEM budget gives, reading
    the leaf in its own layout: no pad, one launch."""
    f32 = jnp.float32
    args = [_abstract(leaf, dtype, one_chip),
            _abstract((K,) + leaf, dtype, one_chip)]
    args += [_abstract(leaf, f32, one_chip)] * 3
    args += [_abstract((K,), f32, one_chip)] * 3

    def apply(p, g, n, b, v, w, wm, t):
        return ops.fused_event_apply(
            {"w": p}, {"w": g}, {"w": n}, {"w": b}, {"w": v}, w, wm, t,
            jnp.bool_(True), lr=0.005, interpret=False)

    text = _kernel_text(apply, *args)
    assert "tpu_custom_call" in text
    assert len(_launches(text, "fused_event_apply")) == 1
    assert not _instructions(text, "pad")
    if leaf == LM_HEAD:
        assert ops.apply_blocks(*leaf, K, dtype, dtype)[1] < leaf[1]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fasgd_update_compiles(one_chip, dtype):
    """The single-event fasgd kernel on the LM's largest leaf."""
    leaf = _abstract(LM_LEAF, dtype, one_chip)

    def update(p, g, n, b, v):
        return ops.fasgd_update({"w": p}, {"w": g}, {"w": n}, {"w": b},
                                {"w": v}, 0.005, 3.0, interpret=False)

    text = _kernel_text(update, *[leaf] * 5)
    assert "tpu_custom_call" in text
    assert len(_launches(text, "fasgd_update")) == 1


def _fred_step_text(cfg, mesh, place, sharding):
    """Compile one scanned window of `cfg`'s fused FRED step, its state
    placed by `place` (abstract leaf -> sharding)."""
    ds = make_synth_mnist(0, n_train=1024, n_valid=16)
    params = init_mlp(jax.random.PRNGKey(0))
    step = build_step_fn(cfg, nll_loss, ds.x_train, ds.y_train, mesh=mesh)
    state = jax.eval_shape(lambda p: init_sim(cfg, p), params)
    state = place(state)
    keys = _abstract((1, cfg.events_per_step, 2), jnp.uint32, sharding)
    return _kernel_text(lambda s, k: jax.lax.scan(step, s, k), state, keys)


def test_fred_fused_step_holds_kernel(one_chip):
    """Phase A's whole fused FRED step, steered to the native kernel the
    way a user would (`ServerConfig(kernel_interpret=False)`)."""
    cs = _chip_smoke()
    cfg = cs.fred_config(lam=cs.FRED_LAM, K=cs.FRED_K,
                         kernel_interpret=False)
    place = lambda tree: jax.tree.map(
        lambda x: _abstract(x.shape, x.dtype, one_chip), tree)
    text = _fred_step_text(cfg, None, place, one_chip)
    assert "tpu_custom_call" in text
    # one launch per leaf of the MLP
    assert len(_launches(text, "fused_event_apply")) == 4
    # W1's launch reads the backward's [K, 784, 200] gradient batch as the
    # dot wrote it, and W2's (200 x 10, narrower than a lane tile) its
    # transposed [K, 10, 200] one: nothing between client_grad and the kernel
    K = cs.FRED_K
    for batch in (f"f32[{K},{MLP_LEAF[0]},{MLP_LEAF[1]}]",
                  f"f32[{K},{MLP_W2[1]},{MLP_W2[0]}]"):
        launch = [l for l in re.findall(r"^\s*%fused_event_apply\S* = .*$",
                                        text, re.M)
                  if f"{batch}{{2,1,0}}}}" in l]
        assert len(launch) == 1, f"no launch reads {batch} in place"
        operands = re.search(r"custom-call\(([^)]*)\)", launch[0]).group(1)
        grad = re.findall(r"%([\w.-]+)", operands)[-1]
        opcode, shape, op_name = _producer(text, grad)
        assert shape.startswith(batch) and opcode == "fusion", (opcode, shape)
        assert "client_grad" in op_name and "transpose(" in op_name, op_name
    # and the program holds no pad of an event batch, nor a copy of W1's
    size = K * MLP_LEAF[0] * MLP_LEAF[1]
    assert not [t for t, _ in _instructions(text, "pad").values()
                if t.startswith(f"f32[{K},")]
    assert not [t for t, _ in _instructions(text, "copy").values()
                if t.startswith(f"f32[{K},") and math.prod(
                    map(int, t[4:t.index("]")].split(","))) >= size]


@pytest.mark.parametrize("axis", ["server", "clients"])
def test_fred_fused_step_four_chips(topo, axis):
    """The kernel inside a step partitioned over the 2x2 host: the server
    state block-routed over a 4-wide 'server' axis, or the fleet sharded
    over a 4-wide 'clients' axis.  The compiler cannot partition a Mosaic
    kernel by itself; each device must apply its own blocks."""
    cs = _chip_smoke()
    n = len(topo.devices)
    mesh = jax.sharding.Mesh(np.array(topo.devices), (axis,))
    rep = NamedSharding(mesh, PartitionSpec())
    replicate = lambda tree: jax.tree.map(
        lambda x: _abstract(x.shape, x.dtype, rep), tree)
    if axis == "server":
        cfg = cs.fred_config(lam=cs.FRED_LAM, K=cs.FRED_K, server_shards=n,
                             kernel_interpret=False)

        def place(state):
            block = lambda x: _abstract(x.shape, x.dtype, NamedSharding(
                mesh, server_shard.server_leaf_spec(x.shape, n)))
            state = replicate(state)
            return state._replace(server=jax.tree.map(block, state.server))
    else:
        cfg = cs.fred_config(lam=cs.FRED_LAM, K=cs.FRED_K,
                             fused_mode="materialized",
                             kernel_interpret=False)
        fleet = NamedSharding(mesh, PartitionSpec("clients"))

        def place(state):
            state = replicate(state)
            return state._replace(
                client_params=jax.tree.map(
                    lambda x: _abstract(x.shape, x.dtype, fleet),
                    state.client_params),
                client_ts=_abstract(state.client_ts.shape,
                                    state.client_ts.dtype, fleet))
    assert "tpu_custom_call" in _fred_step_text(cfg, mesh, place, rep)
