"""Training driver: FASGD (round-based or pod-sync) on any assigned arch.

Runs for real on whatever devices exist (CPU here, TPU pod in production):

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke \\
      --steps 100 --clients 4 --rule fasgd --c-fetch 2.0

Modes:
  --clients C > 0 → the divergent-copy round trainer (core.round_trainer):
      C client groups, B-FASGD push/fetch gating, real staleness.
  --clients 0     → the pod-sync FASGD step (launch.steps.make_train_step):
      one data-parallel gradient + FASGD server update per step.
"""
from __future__ import annotations

import argparse
import functools
import time

import jax

from repro.checkpoint import save_checkpoint, restore_checkpoint, latest_step
from repro.configs import get_config, get_smoke_config
from repro.configs.base import TrainerConfig
from repro.core import rules as server_rules
from repro.core import scenarios
from repro.core import server_shard
from repro.core.round_trainer import (
    build_round_step, init_round_state, round_state_shardings)
from repro.data.tokens import TokenDataConfig, make_batch as make_token_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_server_mesh
from repro.launch.steps import make_train_step, server_config
from repro.models.api import make_batch, param_count
from repro.models.lm import make_lm_loss
from repro.models.transformer import init_model, loss_fn
from repro.sharding import set_mesh_context


def batch_for_step(cfg, B, S, step):
    """Deterministic synthetic batch (markov-chain tokens for LM archs,
    gaussian embeddings for audio/vlm)."""
    if cfg.arch_type in ("audio", "vlm"):
        return make_batch(cfg, B, S, jax.random.fold_in(jax.random.PRNGKey(7), step))
    tcfg = TokenDataConfig(vocab_size=cfg.vocab_size, seq_len=S, batch_size=B)
    tokens, targets = make_token_batch(tcfg, step)
    return {"tokens": tokens, "targets": targets}


def run_round_trainer(cfg, tc: TrainerConfig, params, *, apply_mode: str,
                      steps: int, batch: int, seq: int, log_every: int = 10,
                      ckpt_dir: str = "", ckpt_every: int = 0,
                      scenario_name: str = "off") -> dict:
    """Round-trainer training loop (the CLI's ``--clients C > 0`` mode).

    Builds the round state for ``tc`` around ``params``, placed where the
    step runs: when ``tc.server_shards > 1`` the server sharded and the
    clients and their batches over the same devices, C/S whole clients a
    device (`round_trainer.round_state_shardings`).  Compiles one round
    step ahead of time, with the state donated, and runs rounds
    ``[start, steps)`` on synthetic batches of ``batch`` sequences of
    ``seq`` tokens, split over the ``tc.num_round_clients`` client groups
    (``start`` > 0 when resuming from ``ckpt_dir``).  Prints
    the CLI's log lines, the compile time apart from tracing and lowering
    (what a persistent-cache hit saves), and returns ``{"state", "losses",
    "compiled"}``: ``losses`` holds one mean loss per round run, and
    ``compiled`` is the AOT-compiled step (``memory_analysis()``,
    ``as_text()``).
    """
    def grad_fn(p, b):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, cfg, b)
        return loss, g

    # token archs get the shared/delta event-batched loss so the fused
    # cotangent reduction applies to the transformer stack (models/lm.py);
    # audio/vlm batches carry extra modal keys the adapter doesn't thread.
    batched_loss_fn = None
    if cfg.arch_type not in ("audio", "vlm"):
        lm_loss = make_lm_loss(cfg)

        def batched_loss_fn(W, deltas, b):
            return lm_loss.event_batched(W, deltas, b["tokens"], b["targets"])

    C = tc.num_round_clients
    if batch % C:
        raise ValueError(f"global batch {batch} must divide over {C} clients")
    Bc = batch // C

    init = functools.partial(init_round_state, tc)
    smesh = placed = None
    if tc.server_shards > 1:
        smesh = make_server_mesh(server=tc.server_shards)
        server_shard.validate_server_mesh(
            smesh, tc.server_shards, tc.server_axis)
        placed = round_state_shardings(
            jax.eval_shape(init, params), smesh, tc.server_axis)
        print(f"[train] server sharded: {tc.server_shards} shards on "
              f"axis '{tc.server_axis}' (mesh {dict(smesh.shape)}), "
              f"clients over the same devices")
    # built by one jitted call, so every leaf has a buffer of its own for
    # the step to donate, each made where the step holds it
    state = jax.jit(init, **({"out_shardings": placed} if placed else {}))(
        params)

    start = 0
    if ckpt_dir and latest_step(ckpt_dir) is not None:
        state, start, _ = restore_checkpoint(ckpt_dir, state)
        if placed:
            state = jax.device_put(state, placed)
        print(f"[train] resumed from step {start}")

    def client_batch(step):
        flat = batch_for_step(cfg, batch, seq, step)
        out = jax.tree.map(lambda l: l.reshape((C, Bc) + l.shape[1:]), flat)
        return (server_shard.shard_clients(out, smesh, tc.server_axis)
                if smesh is not None else out)

    def round_key(step):
        return jax.random.fold_in(jax.random.PRNGKey(tc.seed), step)

    t0 = time.perf_counter()
    lowered = jax.jit(build_round_step(
        tc, grad_fn, apply_mode=apply_mode, batched_loss_fn=batched_loss_fn,
        mesh=smesh,
    ), donate_argnums=0).lower(state, client_batch(start), round_key(start))
    t1 = time.perf_counter()
    step_fn = lowered.compile()
    compile_s = time.perf_counter() - t1
    print(f"[train] round step traced and lowered in {t1 - t0:.2f}s, "
          f"compiled in {compile_s:.2f}s")

    losses = []
    t0 = time.perf_counter()
    for step in range(start, steps):
        state, m = step_fn(state, client_batch(step), round_key(step))
        losses.append(m["loss"])
        if step % log_every == 0 or step == steps - 1:
            wall = (f" wall={float(m['wall']):.2f}"
                    if "wall" in m else "")
            print(f"  step {step:5d} loss={float(m['loss']):.4f} "
                  f"tau={float(m['mean_tau']):.2f} "
                  f"push={int(m['pushes'])}/{C} fetch={int(m['fetches'])}/{C} "
                  f"T={int(m['timestamp'])}{wall}")
        if ckpt_every and ckpt_dir and (step + 1) % ckpt_every == 0:
            save_checkpoint(ckpt_dir, step + 1, state)
    losses = [float(l) for l in losses]
    dt = time.perf_counter() - t0
    print(f"[train] done: {steps - start} rounds in {dt:.1f}s "
          f"({(steps - start) / max(dt, 1e-9):.2f} rounds/s)")
    cnt = state.counters
    sent = float(cnt.push_bytes_sent + cnt.fetch_bytes_sent)
    total = float(cnt.push_bytes_total + cnt.fetch_bytes_total)
    if total > 0:
        print(f"[train] bandwidth: {sent / 2**20:.1f} MiB sent of "
              f"{total / 2**20:.1f} MiB potential "
              f"({sent / total:.1%} transmitted, "
              f"{total / max(sent, 1e-9):.1f}x reduction)")
    if tc.queue_capacity:
        w = max(int(cnt.queue_windows), 1)
        print(f"[train] queue: {int(cnt.queue_drained)} drained / "
              f"{int(cnt.queue_enqueued)} admitted "
              f"({int(cnt.queue_rejected)} rejected, "
              f"{int(cnt.queue_dropped)} dropped), "
              f"mean depth {float(cnt.queue_depth_sum) / w:.2f}, "
              f"peak {int(cnt.queue_depth_peak)}, "
              f"mean latency "
              f"{float(cnt.queue_latency_sum) / max(int(cnt.queue_drained), 1):.2f} T-ticks")
    if tc.use_fused_kernel:
        n_leaves = len(jax.tree.leaves(state.server.params))
        launches = int(cnt.kernel_launches)
        windows = launches // max(n_leaves, 1)
        events = int(cnt.kernel_events)
        print(f"[train] kernel: {launches} launches "
              f"({windows} apply windows x {n_leaves} leaves), "
              f"{events} events consumed "
              f"({events / max(windows, 1):.1f} events/window)")
    if tc.server_shards > 1:
        print(f"[train] shards: {tc.server_shards} server shards, "
              f"{int(cnt.shard_events)} events over "
              f"{int(cnt.shard_applies)} apply windows "
              f"(peak window batch {int(cnt.shard_depth_peak)}), "
              f"peak resident "
              f"{float(cnt.shard_bytes_peak) / 2**20:.2f} MiB/shard")
    if tc.scenario is not None:
        rounds = max(int(cnt.scenario_windows), 1)
        k_used = (tc.kasync_k or C) if server_rules.get_rule(
            tc.rule).synchronous else C
        print(f"[train] scenario '{scenario_name}': "
              f"wall={float(cnt.wall_clock):.2f} "
              f"({float(cnt.wall_clock) / rounds:.3f}/round, "
              f"barrier {k_used}/{C}), "
              f"mean active {float(cnt.scenario_active_sum) / rounds:.1f}"
              f"/{C} over {rounds} rounds")
    return {"state": state, "losses": losses, "compiled": step_fn}


def main():
    """CLI entry point: round-based (--clients C > 0) or pod-sync FASGD
    training on the assigned arch (see module docstring for usage)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--rule", default="fasgd",
                    choices=list(server_rules.registered_rules()))
    ap.add_argument("--lr", type=float, default=0.005)
    ap.add_argument("--clients", type=int, default=4,
                    help="round-trainer client groups; 0 = pod-sync step")
    ap.add_argument("--apply-mode", default="serial", choices=["serial", "fused"])
    ap.add_argument("--fused-mode", default="auto",
                    choices=["auto", "materialized", "cotangent"],
                    help="fused-apply gradient reduction: 'auto' rides the "
                         "engine's cotangent path for v-independent rules "
                         "when eligible, 'materialized' forces the [C, P] "
                         "per-event reduction, 'cotangent' demands the "
                         "contraction (error if ineligible)")
    ap.add_argument("--drop-policy", default="local_apply",
                    choices=["local_apply", "discard"],
                    help="what a gated-out push does with its gradient "
                         "(cotangent reduction needs 'discard')")
    ap.add_argument("--c-push", type=float, default=0.0)
    ap.add_argument("--c-fetch", type=float, default=0.0)
    ap.add_argument("--per-tensor", action="store_true",
                    help="gate each parameter tensor independently on both "
                         "directions (per-leaf eq. 9 + per-tensor staleness)")
    ap.add_argument("--variant", default="intent", choices=["intent", "literal"])
    ap.add_argument("--queue-capacity", type=int, default=0,
                    help="bounded server ingress queue (core/queue.py); "
                         "0 = apply pushes immediately")
    ap.add_argument("--drain-policy", default="drain_all",
                    choices=["drain_all", "drain_k", "adaptive"],
                    help="how many queued pushes each round applies")
    ap.add_argument("--drain-k", type=int, default=1,
                    help="per-round drain budget (drain_k; adaptive floor)")
    ap.add_argument("--admission-policy", default="block",
                    choices=["block", "reject", "drop_oldest"],
                    help="what happens to a push arriving at a full queue")
    ap.add_argument("--scenario", default="off",
                    choices=["off"] + sorted(scenarios.SCENARIO_PRESETS),
                    help="modeled arrival process (core/scenarios.py): "
                         "rounds get wall-clock durations from per-client "
                         "service draws; pushes apply fastest-first")
    ap.add_argument("--kasync-k", type=int, default=0,
                    help="partial-barrier K for --rule kasync "
                         "(0 = clients // 2 when the rule is kasync)")
    ap.add_argument("--use-fused-kernel", action="store_true",
                    help="route the server apply through the one-kernel "
                         "Pallas path (kernels/fused_event_apply.py); on "
                         "CPU it runs the streaming XLA reference unless "
                         "REPRO_KERNEL_INTERPRET/--kernel-interpret forces "
                         "interpret mode")
    ap.add_argument("--kernel-interpret", default="auto",
                    choices=["auto", "on", "off"],
                    help="Pallas interpret-mode toggle for the kernel path "
                         "(auto = env REPRO_KERNEL_INTERPRET, then platform)")
    ap.add_argument("--kernel-block-rows", type=int, default=0,
                    help="row block of the one-kernel apply "
                         "(0 = derived from a VMEM budget)")
    ap.add_argument("--server-shards", type=int, default=1,
                    help="partition the server state (W + eq. 4-6 stats) "
                         "across S devices along a 'server' mesh axis "
                         "(core/server_shard.py, docs/SHARDING.md); 1 = "
                         "replicated server; on CPU force S devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=S")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    scn = (None if args.scenario == "off"
           else scenarios.preset(args.scenario))
    if scn is not None and args.clients <= 0:
        ap.error("--scenario needs the round trainer (--clients C > 0)")
    if args.server_shards > 1 and args.clients <= 0:
        ap.error("--server-shards needs the round trainer (--clients C > 0)")
    if args.server_shards > 1 and args.clients % args.server_shards:
        ap.error(f"--clients {args.clients} is not a multiple of "
                 f"--server-shards {args.server_shards}: the clients lie "
                 f"over the server's devices, C/S whole clients a device, "
                 f"and a client cannot straddle two")
    kasync_k = args.kasync_k
    if args.rule == "kasync" and kasync_k == 0:
        # a full-barrier default would make kasync ≡ ssgd; half the fleet
        # is the interesting operating point out of the box
        kasync_k = max(1, args.clients // 2)
    tc = TrainerConfig(
        num_round_clients=max(args.clients, 1), rule=args.rule, lr=args.lr,
        c_push=args.c_push, c_fetch=args.c_fetch, variant=args.variant,
        per_tensor_push=args.per_tensor, per_tensor_fetch=args.per_tensor,
        fused_mode=args.fused_mode, drop_policy=args.drop_policy,
        queue_capacity=args.queue_capacity, drain_policy=args.drain_policy,
        drain_k=args.drain_k, admission_policy=args.admission_policy,
        scenario=scn, kasync_k=kasync_k,
        server_shards=args.server_shards,
        use_fused_kernel=args.use_fused_kernel,
        kernel_interpret=(None if args.kernel_interpret == "auto"
                          else args.kernel_interpret == "on"),
        kernel_block_rows=args.kernel_block_rows,
        seed=args.seed,
    )
    mesh = make_host_mesh(data=len(jax.devices()))
    if args.server_shards <= 1:
        # a sharded server places each client on a device of its own mesh,
        # where the model's activation constraints have nothing to split
        set_mesh_context(mesh)

    params = init_model(jax.random.PRNGKey(args.seed), cfg)
    print(f"[train] {cfg.name}: {param_count(params):,} params, "
          f"rule={args.rule}, clients={args.clients}, mesh={mesh.shape}")

    if args.clients > 0:
        run_round_trainer(
            cfg, tc, params, apply_mode=args.apply_mode, steps=args.steps,
            batch=args.batch, seq=args.seq, log_every=args.log_every,
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
            scenario_name=args.scenario)
    else:
        scfg = server_config(tc)
        state = server_rules.init(scfg, params)
        train_step = jax.jit(make_train_step(cfg, tc))
        t0 = time.time()
        for step in range(args.steps):
            batch = batch_for_step(cfg, args.batch, args.seq, step)
            state, m = train_step(state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"  step {step:5d} loss={float(m['loss']):.4f} "
                      f"scale={float(m['mean_scale']):.5f}")
            if args.ckpt_every and args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, step + 1, state.params)
        dt = time.time() - t0
        print(f"[train] done: {args.steps} steps in {dt:.1f}s")
    set_mesh_context(None)


if __name__ == "__main__":
    main()
