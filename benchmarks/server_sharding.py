"""Server sharding: applied-events/sec and peak per-device server bytes vs S.

The sharded parameter server (core/server_shard.py, docs/SHARDING.md)
block-partitions W and the eq. 4–6 statistics across S devices along a
``'server'`` mesh axis.  This benchmark measures the two claims that layer
makes, on forced-multi-device CPU (the simulated multi-host recipe):

* **peak per-device server-state bytes shrink ~1/S** — computed from the
  static routing plan (`make_shard_plan.peak_resident_bytes`: each shard's
  block bytes plus the replicated remainder of non-divisible leaves), and
  the headline acceptance number;
* **steady-state applied-events/sec** of the warm jit-compiled window scan
  with the server state placed on the S-shard mesh — on host-simulated
  devices this mostly prices the partitioning overhead XLA inserts (real
  multi-host wins come from memory capacity, not CPU throughput), so the
  events/sec column is a regression canary rather than a speedup claim.

Every sharded arm also replays the S=1 trajectory and checks the final
parameters are allclose (the equivalence invariant, pinned harder in
tests/test_server_shard.py).

Methodology matches benchmarks/sim_throughput.py: the window scan is
compiled once per arm, events/sec is the best of several invocations of
the warm executable (steady-state, jit excluded), and one-time compile
seconds are reported separately.

Writes ``BENCH_server_sharding.json`` at the repo root (and a copy under
``benchmarks/results/``), schema-checked by scripts/check_bench_schema.py:

    PYTHONPATH=src python -m benchmarks.server_sharding --quick   # CI smoke
    PYTHONPATH=src python -m benchmarks.server_sharding           # full grid
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede any jax import: jax locks the device count on first use.
#   4 simulated CPU devices cover the full shard grid [1, 2, 4].

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import server_shard
from repro.core.rules import ServerConfig
from repro.data.mnist import load_mnist
from repro.launch.mesh import make_mesh
from repro.models.mlp import init_mlp, nll_loss
from repro.sim.fred import SimConfig, build_step_fn, init_sim

from benchmarks.common import save_bench

SIZES = (784, 64, 10)   # hidden 64: every weight matrix splits 4 ways
MU = 4
RULE = "fasgd"
LAM = 32
K = 16                  # events per fused window


def _cfg(shards, seed=0):
    return SimConfig(
        num_clients=LAM, batch_size=MU, seed=seed,
        server=ServerConfig(rule=RULE, lr=0.005),
        events_per_step=K, apply_mode="fused",
        server_shards=shards,
    )


def measure(params, ds, cfg, *, n_windows, reps, seed=0):
    """Warm-scan applied-events/sec with the server placed on S shards.

    Returns (events_per_sec, compile_s, final_params): the scan is compiled
    once against the placed state, timed over repeated invocations of the
    warm executable, and the final server parameters come back for the
    allclose cross-check against the S=1 arm.
    """
    S = cfg.server_shards
    state = init_sim(cfg, params)
    if S > 1:
        mesh = make_mesh((S,), (cfg.server_axis,))
        server_shard.validate_server_mesh(mesh, S, cfg.server_axis)
        state = state._replace(server=server_shard.shard_server_state(
            state.server, mesh, cfg.server_axis))
    step = build_step_fn(cfg, nll_loss, ds.x_train, ds.y_train, events=K)
    base = jax.random.PRNGKey(seed)

    @jax.jit
    def span(state, start):
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
            start + jnp.arange(n_windows * K))
        keys = keys.reshape((n_windows, K) + keys.shape[1:])
        return jax.lax.scan(step, state, keys)

    t0 = time.time()
    warm, _ = span(state, jnp.int32(0))
    jax.block_until_ready(warm)
    compile_s = time.time() - t0

    best = 0.0
    for _ in range(reps):
        t0 = time.time()
        out, _ = span(state, jnp.int32(0))
        jax.block_until_ready(out)
        best = max(best, 1.0 / (time.time() - t0))
    return (round(n_windows * K * best, 1), round(compile_s, 2),
            out.server.params)


def run(shard_counts, *, quick, seed=0):
    params = init_mlp(jax.random.PRNGKey(seed), SIZES)
    ds = load_mnist(seed=seed)
    n_windows = 8 if quick else 32
    reps = 3 if quick else 5

    server_tree = init_sim(_cfg(1, seed=seed), params).server
    peak1 = server_shard.peak_shard_bytes(server_tree, 1)

    rows = []
    ref_params = None
    for S in shard_counts:
        ev, cs, final = measure(params, ds, _cfg(S, seed=seed),
                                n_windows=n_windows, reps=reps, seed=seed)
        peak = server_shard.peak_shard_bytes(server_tree, S)
        if S == 1:
            ref_params = final
            close = True
        else:
            close = all(
                np.allclose(a, b, rtol=1e-5, atol=1e-6)
                for a, b in zip(jax.tree.leaves(ref_params),
                                jax.tree.leaves(final)))
        rows.append({
            "shards": S,
            "applied_events_per_sec": ev,
            "compile_s": cs,
            "peak_server_bytes": peak,
            "bytes_vs_replicated": round(peak / peak1, 4),
            "allclose_vs_replicated": bool(close),
        })
        print(f"  S={S}  {ev:10.1f} ev/s  peak={peak / 2**10:8.2f} KiB/shard "
              f"({peak / peak1:.3f}x of replicated)  "
              f"allclose={close}  compile={cs}s")
    return rows, peak1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: shards [1, 2], fewer windows")
    ap.add_argument("--shards", type=int, nargs="*", default=[1, 2, 4])
    args = ap.parse_args()
    counts = tuple(args.shards[:2]) if args.quick else tuple(args.shards)
    navail = len(jax.devices())
    counts = tuple(S for S in counts if S <= navail)

    rows, peak1 = run(counts, quick=args.quick)
    smax = max(r["shards"] for r in rows)
    peak_max = next(r["peak_server_bytes"] for r in rows
                    if r["shards"] == smax)
    summary = {
        "max_shards": smax,
        "peak_bytes_shrink": round(peak1 / peak_max, 3),
        "ideal_shrink": smax,
    }
    print(f"  peak server bytes shrink {summary['peak_bytes_shrink']:.2f}x "
          f"at S={smax} (ideal {smax}x)")
    assert all(r["allclose_vs_replicated"] for r in rows)
    if not args.quick and smax > 1:
        # acceptance: ~1/S — within 25% of ideal (the replicated remainder
        # of non-divisible leaves is the only slack on this model)
        assert summary["peak_bytes_shrink"] >= 0.75 * smax, summary

    payload = {
        "model_sizes": list(SIZES),
        "batch_size": MU,
        "rule": RULE,
        "lam": LAM,
        "events_per_window": K,
        "num_devices": navail,
        "methodology": "warm jit-compiled window scan with the server state "
                       "block-partitioned on a forced-multi-device CPU "
                       "'server' mesh axis; events/sec is best of repeated "
                       "warm invocations; peak bytes are the static routing "
                       "plan's max per-shard resident bytes (blocks + "
                       "replicated remainder)",
        "quick": args.quick,
        "rows": rows,
        "summary": summary,
    }
    path = save_bench("BENCH_server_sharding.json", payload)
    print(f"  wrote {path}")


if __name__ == "__main__":
    main()
