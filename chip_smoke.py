"""Chip smoke: the async-SGD main paths, driven once on a TPU.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: sharded server and
                                      # clients-axis fleet vs one chip

Phase A is the paper's FRED simulation of the 784-200-10 MLP at λ=256
clients and K=128 events per window, applied by the one-kernel Pallas
server apply (`kernels/fused_event_apply.py`).  It checks that the compiled
step holds the kernel, that the kernel agrees with its plain reference
(`kernels/ref.fused_event_apply_ref`) on one window's inputs, and that the
validation cost falls; a short asgd arm runs the kernel-free cotangent path.

Phase B is the LM round trainer exactly as ``python -m repro.launch.train``
runs it (`launch/train.run_round_trainer`), on tinyllama-1.1b at its
published widths with the depth cut to fit one chip.

With ``--chips 4`` only the two multi-chip paths run: FRED with the server
state partitioned over a 4-wide ``'server'`` axis, and FRED with the fleet
sharded over a 4-wide ``'clients'`` axis, each compared with the same run
on one chip.

Every phase raises on a failed check.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only after every phase passed
on a TPU; the script exits non-zero, with no such line, on any other
platform or when ``REPRO_KERNEL_INTERPRET`` would divert the kernel.
One process holds the chip; no subprocess is started.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import TrainerConfig  # noqa: E402
from repro.core.bandwidth import tree_bytes  # noqa: E402
from repro.core.rules import ServerConfig  # noqa: E402
from repro.data.mnist import make_synth_mnist  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.ref import fused_event_apply_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import (  # noqa: E402
    make_host_mesh, make_mesh, make_server_mesh)
from repro.launch.train import run_round_trainer  # noqa: E402
from repro.models.api import param_count  # noqa: E402
from repro.models.mlp import init_mlp, nll_loss  # noqa: E402
from repro.models.transformer import init_model  # noqa: E402
from repro.sharding import set_mesh_context  # noqa: E402
from repro.sim.fred import (  # noqa: E402
    SimConfig, build_step_fn, init_sim, run_simulation)

MLP_SIZES = (784, 200, 10)      # the paper's model
FRED_LAM = 256                  # clients (ROADMAP A1's first cell)
FRED_K = 128                    # events per window
FRED_EVENTS = 512               # four windows
FRED_ASGD_EVENTS = 256
FRED_MU = 4                     # per-event minibatch (benchmarks' MU)
FRED_LR = 0.005
# f32 kernel vs f32 reference: same arithmetic, different fusion and
# transcendental (sqrt, divide) lowering — a few ulps, compounded over K
KERNEL_RTOL, KERNEL_ATOL = 1e-4, 1e-6
# one chip vs four: the same trajectory under a different partitioning of
# the client GEMMs, whose reductions may then sum in another order
SHARD_RTOL, SHARD_ATOL = 1e-4, 1e-5

LM_ARCH = "tinyllama-1.1b"
# depth is the only cut: 4 of 22 layers.  At seq 2048 with 4 client copies
# the round step needs remat and seq-chunked CE to fit 16 GB (AOT memory
# analysis on v5e: 22.2 GB without them, 13.3 GB with); both are exact.
LM_LAYERS = 4
LM_SEQ = 2048
LM_CLIENTS = 4
LM_BATCH = 4                    # one sequence per client group
LM_ROUNDS = 3
LM_OVERRIDES = dict(num_layers=LM_LAYERS, remat=True, loss_chunk=512)


def log(msg: str) -> None:
    """Informative line (everything before the final JSON line)."""
    print(msg, flush=True)


def fred_config(*, lam, K, rule="fasgd", use_fused_kernel=True,
                kernel_interpret=None, server_shards=1,
                fused_mode="auto", seed=0) -> SimConfig:
    """Phase A's FRED configuration on the paper's MLP (fused apply)."""
    return SimConfig(
        num_clients=lam, batch_size=FRED_MU, seed=seed,
        events_per_step=K, apply_mode="fused", fused_mode=fused_mode,
        server=ServerConfig(rule=rule, lr=FRED_LR, num_clients=lam,
                            use_fused_kernel=use_fused_kernel,
                            kernel_interpret=kernel_interpret),
        server_shards=server_shards)


def _fred_run(cfg, params, ds, events, mesh=None):
    eval_fn = lambda p: nll_loss(p, ds.x_valid, ds.y_valid)
    return run_simulation(cfg, nll_loss, params, ds.x_train, ds.y_train,
                          events, eval_every=events, eval_fn=eval_fn,
                          mesh=mesh)


def phase_fred(*, lam=FRED_LAM, K=FRED_K, events=FRED_EVENTS,
               asgd_events=FRED_ASGD_EVENTS, n_train=32768, n_valid=4096,
               kernel_interpret=None, seed=0) -> dict:
    """Phase A: FRED on the paper's MLP through the one-kernel apply.

    Returns the measured values; raises when the validation cost is not
    finite or does not fall, or the kernel disagrees with its reference.
    ``kernel_in_step`` says whether the lowered step holds a
    ``tpu_custom_call`` (the caller decides whether it must).
    """
    ds = make_synth_mnist(seed, n_train=n_train, n_valid=n_valid)
    params = init_mlp(jax.random.PRNGKey(seed), MLP_SIZES)
    cfg = fred_config(lam=lam, K=K, kernel_interpret=kernel_interpret,
                      seed=seed)
    log(f"[A] FRED {'-'.join(map(str, MLP_SIZES))} MLP "
        f"({param_count(params):,} params), λ={lam}, K={K}, "
        f"{events} events, rule=fasgd, apply=fused one-kernel")

    # the dataset as an argument, as run_simulation passes it
    step = lambda s, k, x, y: build_step_fn(cfg, nll_loss, x, y)(s, k)
    keys = jax.random.split(jax.random.PRNGKey(seed), K)
    hlo = jax.jit(step).lower(init_sim(cfg, params), keys, ds.x_train,
                              ds.y_train).as_text()
    kernel_in_step = "tpu_custom_call" in hlo
    log(f"[A] tpu_custom_call in the lowered step: {kernel_in_step}")

    before = float(jax.jit(nll_loss)(params, ds.x_valid, ds.y_valid))
    t0 = time.perf_counter()
    out = _fred_run(cfg, params, ds, events)
    after = out["val_cost"][-1]
    log(f"[A] validation cost {before:.6f} -> {after:.6f} "
        f"({time.perf_counter() - t0:.2f}s incl. compile)")
    log(f"[A] counters: " + ", ".join(
        f"{k}={v:g}" for k, v in sorted(out["counters"].items())))
    if not (math.isfinite(after) and after < before):
        raise RuntimeError(
            f"validation cost did not fall: {before} -> {after}")
    if out["counters"]["kernel_events"] != events:
        raise RuntimeError(f"kernel consumed {out['counters']['kernel_events']}"
                           f" of {events} events")

    # one window's inputs at the first layer's 784x200 leaf: the gradients
    # K events would push against the trained server, and its statistics
    srv = out["state"].server
    k_idx, k_tau = jax.random.split(jax.random.PRNGKey(seed + 1))
    idx = jax.random.randint(k_idx, (K, FRED_MU), 0, n_train)
    grads = jax.vmap(jax.grad(nll_loss), in_axes=(None, 0, 0))(
        srv.params, ds.x_train[idx], ds.y_train[idx])
    leaf = lambda tree: {"w": tree[0]["w"]}
    taus = jax.random.randint(k_tau, (K,), 1, lam).astype(jnp.float32)
    weights = jnp.ones((K,), jnp.float32)
    wmean = jnp.full((K,), 1.0 / K, jnp.float32)
    args = (leaf(srv.params), leaf(grads), leaf(srv.n), leaf(srv.b),
            leaf(srv.v), weights, wmean, taus, True)
    got = jax.jit(lambda *a: ops.fused_event_apply(
        *a, lr=FRED_LR, interpret=kernel_interpret))(*args)
    with jax.default_matmul_precision("float32"):
        want = jax.jit(lambda p, g, n, b, v, w, wm, t, hp: fused_event_apply_ref(
            p["w"], g["w"], n["w"], b["w"], v["w"], w, wm, t, FRED_LR, hp))(
                *args)
    kernel_err = 0.0
    for name, g_out, w_out in zip("pnbv", got, want):
        g_out = np.asarray(g_out["w"])
        w_out = np.asarray(w_out)
        np.testing.assert_allclose(g_out, w_out, rtol=KERNEL_RTOL,
                                   atol=KERNEL_ATOL, err_msg=f"kernel {name}'")
        kernel_err = max(kernel_err, float(np.max(np.abs(g_out - w_out))))
    log(f"[A] kernel vs fused_event_apply_ref on one window "
        f"(K={K}, 784x200): allclose, max |diff| {kernel_err:.3e}")

    asgd = fred_config(lam=lam, K=K, rule="asgd", use_fused_kernel=False,
                       seed=seed)
    if not asgd.cotangent_eligible():
        raise RuntimeError("asgd arm does not take the cotangent path")
    out_asgd = _fred_run(asgd, params, ds, asgd_events)
    asgd_cost = out_asgd["val_cost"][-1]
    log(f"[A] asgd cotangent arm: {asgd_events} events, validation cost "
        f"{before:.6f} -> {asgd_cost:.6f}")
    if not math.isfinite(asgd_cost):
        raise RuntimeError(f"asgd arm cost is not finite: {asgd_cost}")
    return {"cost_before": before, "cost_after": after,
            "kernel_in_step": kernel_in_step, "counters": out["counters"]}


def phase_lm(cfg, *, seq=LM_SEQ, clients=LM_CLIENTS, batch=LM_BATCH,
             rounds=LM_ROUNDS, kernel_interpret=None, seed=0) -> dict:
    """Phase B: the round trainer through `launch.train.run_round_trainer`
    with ``--clients C --apply-mode fused --use-fused-kernel --rule fasgd``.

    Raises when any round's loss is not finite.
    """
    tc = TrainerConfig(num_round_clients=clients, rule="fasgd",
                       use_fused_kernel=True,
                       kernel_interpret=kernel_interpret, seed=seed)
    params = init_model(jax.random.PRNGKey(seed), cfg)
    n_params = param_count(params)
    log(f"[B] {cfg.name}: d_model={cfg.d_model} heads={cfg.num_heads} "
        f"kv_heads={cfg.num_kv_heads} head_dim={cfg.hd} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} dtype={cfg.param_dtype} "
        f"layers={cfg.num_layers} seq={seq} clients={clients} "
        f"batch={batch}: {n_params:,} params")
    set_mesh_context(make_host_mesh(data=len(jax.devices())))  # as train.main
    try:
        res = run_round_trainer(cfg, tc, params, apply_mode="fused",
                                steps=rounds, batch=batch, seq=seq,
                                log_every=1)
    finally:
        set_mesh_context(None)
    state = res["state"]
    server_b = tree_bytes((state.server.params, state.server.n,
                           state.server.b, state.server.v))
    fleet_b = tree_bytes(state.client_params)
    log(f"[B] server state {server_b / 2**30:.3f} GiB, client fleet "
        f"{fleet_b / 2**30:.3f} GiB")
    mem = res["compiled"].memory_analysis()
    if mem is not None:
        log(f"[B] compiled step: arguments "
            f"{mem.argument_size_in_bytes / 2**30:.3f} GiB, outputs "
            f"{mem.output_size_in_bytes / 2**30:.3f} GiB, temporaries "
            f"{mem.temp_size_in_bytes / 2**30:.3f} GiB")
    kernel_in_step = "tpu_custom_call" in res["compiled"].as_text()
    log(f"[B] tpu_custom_call in the compiled round step: {kernel_in_step}")
    log(f"[B] losses per round: {res['losses']}")
    if len(res["losses"]) != rounds or not all(
            math.isfinite(l) for l in res["losses"]):
        raise RuntimeError(f"non-finite or missing losses: {res['losses']}")
    return {"params": n_params, "server_bytes": server_b,
            "fleet_bytes": fleet_b, "losses": res["losses"],
            "kernel_in_step": kernel_in_step}


def _assert_spans(x, n, what):
    if len(x.sharding.device_set) != n or x.sharding.is_fully_replicated:
        raise RuntimeError(
            f"{what} is not partitioned over {n} devices: {x.sharding}")
    log(f"[4] {what} {x.shape} spans {n} devices, shard "
        f"{x.addressable_shards[0].data.shape}")


def _assert_trajectories_close(one, many, what):
    for a, b in zip(jax.tree.leaves(one["state"].server.params),
                    jax.tree.leaves(many["state"].server.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=SHARD_RTOL, atol=SHARD_ATOL,
                                   err_msg=what)
    np.testing.assert_allclose(one["val_cost"], many["val_cost"],
                               rtol=SHARD_RTOL, err_msg=what)
    log(f"[4] {what}: allclose to one chip, validation cost "
        f"{one['val_cost'][-1]:.6f} vs {many['val_cost'][-1]:.6f}")


def phase_four_chips(*, n=4, lam=FRED_LAM, K=FRED_K, events=2 * FRED_K,
                     n_train=32768, n_valid=4096, kernel_interpret=None,
                     seed=0) -> None:
    """The two multi-chip paths of Phase A's FRED run, each against the
    same run on one device: the server partitioned over an ``n``-wide
    ``'server'`` axis, and the fleet sharded over an ``n``-wide
    ``'clients'`` axis (materialized fused reduction under shard_map)."""
    if len(jax.devices()) < n:
        raise RuntimeError(f"needs {n} devices, found {jax.devices()}")
    ds = make_synth_mnist(seed, n_train=n_train, n_valid=n_valid)
    params = init_mlp(jax.random.PRNGKey(seed), MLP_SIZES)
    base = _fred_run(fred_config(lam=lam, K=K, seed=seed,
                                 kernel_interpret=kernel_interpret),
                     params, ds, events)

    shard_cfg = fred_config(lam=lam, K=K, seed=seed, server_shards=n,
                            kernel_interpret=kernel_interpret)
    sharded = _fred_run(shard_cfg, params, ds, events,
                        mesh=make_server_mesh(server=n))
    _assert_spans(sharded["state"].server.params[0]["w"], n,
                  "server W (layer 1)")
    _assert_spans(sharded["state"].server.v[0]["w"], n, "server v (layer 1)")
    _assert_trajectories_close(base, sharded, f"server_shards={n}")

    fleet_cfg = fred_config(lam=lam, K=K, seed=seed,
                            fused_mode="materialized",
                            kernel_interpret=kernel_interpret)
    fleet = _fred_run(fleet_cfg, params, ds, events,
                      mesh=make_mesh((n,), ("clients",)))
    _assert_spans(fleet["state"].client_params[0]["w"], n,
                  "client fleet (layer 1)")
    _assert_trajectories_close(base, fleet, f"clients axis {n}")

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:n]]
    log(f"[4] peak_bytes_in_use per device: {peaks}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded-server and clients-axis "
                         "phases, each against one chip")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_KERNEL_INTERPRET"):
        raise SystemExit("REPRO_KERNEL_INTERPRET is set: it would send the "
                         "kernel to interpret mode or the XLA reference")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {jax.devices()}")
    cache = enable_compile_cache()
    log(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {cache}")

    t_start = time.perf_counter()
    if args.chips == 4:
        phase_four_chips()
    else:
        a = phase_fred()
        if not a["kernel_in_step"]:
            raise RuntimeError("the FRED step did not lower to the Pallas "
                               "kernel (no tpu_custom_call)")
        cfg = get_config(LM_ARCH, **LM_OVERRIDES)
        log(f"[B] depth cut: {LM_LAYERS} of "
            f"{get_config(LM_ARCH).num_layers} layers (one chip's 16 GB "
            f"holds the server state, {LM_CLIENTS} client copies and their "
            f"gradients at seq {LM_SEQ})")
        b = phase_lm(cfg)
        if not b["kernel_in_step"]:
            raise RuntimeError("the round step did not compile to the "
                               "Pallas kernel (no tpu_custom_call)")
        stats = dev.memory_stats() or {}
        log(f"[B] peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
            f"bytes_limit {stats.get('bytes_limit')}")
    log(f"total {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
