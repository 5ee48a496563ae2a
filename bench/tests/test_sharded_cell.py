"""The four-chip LM cell's yardstick at a size the CPU holds: its blocked
reference against the plain one, its exchange reader on synthetic planes,
and its per-device leaves against the unsharded model's bytes."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import smoke
from bench import flops, trace
from bench.drivers import round as rnd
from bench.metrics import exchange_share
from bench.reference import mamba2_lm, mamba2_lm_blocked

SMALL = {"name": "small", "d_model": 64, "n_layer": 3, "vocab_size": 200,
         "d_state": 16, "d_conv": 4, "expand": 2, "headdim": 16,
         "chunk_size": 16, "norm_eps": 1e-5, "dtype": "float32"}


@pytest.mark.parametrize("keep", [None, lambda C: np.arange(C) < C // 2],
                         ids=["all", "half"])
def test_blocked_reference_equals_the_plain_one(keep):
    """Scan and per-τ sums in place of a loop over the layers and a list
    of the clients' gradients: the same numbers up to float32 rounding."""
    params0 = rnd.init_params(jax.random.PRNGKey(0), SMALL, 256)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    rounds = [(jax.random.randint(jax.random.fold_in(k1, r), (2, 2, 32), 0,
                                  200),
               jax.random.randint(jax.random.fold_in(k2, r), (2, 2, 32), 0,
                                  200)) for r in range(3)]
    kw = dict(lr=0.005, gamma=0.9, beta=0.9, eps=1e-8, keep=keep)
    plain = mamba2_lm.run_rounds(params0, rounds, SMALL, **kw)
    blocked = mamba2_lm_blocked.run_rounds(params0, rounds, SMALL, **kw)
    np.testing.assert_allclose(blocked["losses"], plain["losses"], rtol=1e-6)
    np.testing.assert_allclose(blocked["gbar"], plain["gbar"], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(blocked["params"]),
                    jax.tree.leaves(plain["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def _op(name, opcode):
    return f"%{name} = bf16[4,128]{{1,0}} {opcode}(bf16[4,128]{{1,0}} %p)"


def test_exchange_share_counts_collectives_over_all_devices():
    ops = {_op("all-to-all.3", "all-to-all"): [0.10, 4],
           _op("all-gather-start.1", "all-gather-start"): [0.02, 4],
           _op("all-gather-done.1", "all-gather-done"): [0.06, 4],
           _op("all-reduce.2", "all-reduce"): [0.01, 4],
           _op("reduce-scatter.4", "reduce-scatter"): [0.005, 4],
           _op("collective-permute-done.7", "collective-permute-done"):
               [0.005, 4],
           _op("fusion.12", "fusion"): [1.0, 40],
           _op("copy.9", "copy"): [0.3, 8],
           _op("custom-call.5", "custom-call"): [0.4, 48]}
    red = {"ops": ops, "window_s": 2.0, "devices": 4}
    ctx = type("Ctx", (), {"red": red,
                           "op_time": staticmethod(
                               lambda m: trace.op_time(red, m))})
    assert exchange_share.read(ctx) == pytest.approx(100 * 0.2 / (2.0 * 4))
    red["ops"] = {k: v for k, v in ops.items()
                  if not exchange_share.is_collective(k)}
    assert exchange_share.read(ctx) is None


_LEAVES = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
    import jax
    import smoke
    from bench.drivers import round_sharded
    config, traffic = smoke.spec("round.fasgd.l48.server4")
    traffic["clients"] = 4
    cell = round_sharded.Cell(config, traffic, 5, interpret=True)
    cell.setup()
    whole = [(l.shape, l.dtype.itemsize)
             for l in jax.tree.leaves(cell.state.server.params)]
    print(json.dumps({{"devices": len(jax.devices()),
                      "blocks": cell.leaves, "whole": whole,
                      "K": cell.events_per_apply}}))
""")


def test_per_device_leaves_count_each_byte_once():
    """The driver lists every device's block of every server leaf; the
    kernel's bytes over that list are the unsharded model's, and there is
    one entry per device and leaf, as there is one launch."""
    root = smoke.ROOT
    script = _LEAVES.format(root=root, src=os.path.join(root, "src"),
                            tests=os.path.join(root, "bench", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    blocks = [(tuple(s), i) for s, i in out["blocks"]]
    whole = [(tuple(s), i) for s, i in out["whole"]]
    assert out["devices"] == 4
    assert len(blocks) == 4 * len(whole)
    assert (flops.apply_kernel_bytes(blocks, out["K"])
            == flops.apply_kernel_bytes(whole, out["K"]))
