"""Round-based FASGD: the paper's async protocol mapped onto SPMD hardware.

A lock-based parameter server is an anti-pattern on a TPU pod; what survives
the port (DESIGN.md §2) is the *decision structure* of FASGD/B-FASGD:

 - C client groups hold **divergent** parameter copies (a leading [C] array
   axis over otherwise FSDP-sharded leaves).  Divergence is real: a client
   that skips fetches keeps training on old parameters, and its step
   staleness τ_c = T − ts_c grows.
 - Each round every client computes a gradient on *its own* copy.
 - The B-FASGD gate (eq. 9) decides per client whether that gradient is
   **pushed** into the canonical server update and whether the client
   **fetches** the new canonical parameters.  A skipped push/fetch is an
   *elided collective* (reduce / broadcast over the client axis) — this is
   exactly the paper's bandwidth saving expressed in ICI bytes.
 - Pushed gradients update the server under any `core.rules` rule (FASGD's
   per-parameter α/(v·τ) modulation by default).

The push/fetch/apply decision structure itself lives in `core/engine.py`
(shared with the FRED simulator); this module is the thin SPMD adapter:

 - ``apply_mode='serial'`` (paper-faithful): `engine.serial_apply` — pushed
   gradients one-at-a-time in client order via `lax.scan`, bit-identical to
   the lock protocol with that arrival order; T advances by 1 per push.
 - ``apply_mode='fused'`` (beyond-paper): `engine.fused_apply` — one
   masked-sum update θ ← θ − Σ_c m_c·(α/(v·τ_c))·g_c with a single stats
   update on the mean pushed gradient; one reduction instead of C sequential
   passes — the collective-friendly schedule.  With
   ``TrainerConfig(use_fused_kernel=True)`` the reduction runs in the
   batched Pallas kernel for rules that support it.

Dropped pushes follow ``drop_policy``:
 - ``'local_apply'`` (default): the client applies its own gradient to its
   own copy (local-SGD semantics — the paper's "averaging unsent gradients
   on the clients" speculation).
 - ``'discard'``: the gradient is simply dropped.

**Bounded ingress queue** (``TrainerConfig.queue_capacity > 0``,
`core/queue.py`): pushed gradients are admitted into a fixed-capacity ring
instead of applying immediately; each round drains ``drain_count`` queued
events into the canonical update, so the server models a bounded apply rate
and the backlog (hence staleness) grows when C pushes/round outpace it.  A
push the admission policy rejects falls back to the client's ``drop_policy``
(its bytes are *not* counted as sent — it was refused before transmission).
The cotangent fused path is not wired through the round trainer's queue
(it would need the round's minibatch queued alongside each stale copy, as
FRED does); ``fused_mode='auto'`` falls back to the materialized reduction
and an explicit ``'cotangent'`` with a queue is rejected.

**Sharded server** (``TrainerConfig.server_shards > 1``,
`core/server_shard.py`): `shard_round_state` block-partitions the server
state (and the ingress-queue payload) across a ``'server'`` mesh axis, so
the canonical update runs with each shard owning its slice of W and the
eq. 4–6 statistics — the same placement contract as FRED's
``run_simulation(mesh=...)``.  The clients lie over the same devices, C/S
whole clients a device (C must be a multiple of the axis size S): their
copies, timestamps and batches, and their forward and backward.  A push is
then an exchange (``push_route``: each client's gradient split into the
server's blocks), and so is a fetch (``fetch_gather``: the new W's blocks
gathered into the fetching clients' copies); see docs/SHARDING.md.

**Scenario-lite wall clock** (``TrainerConfig.scenario``,
`core/scenarios.py`): each round the C clients draw modeled service times
from per-client streams keyed by ``(seed, client, round_idx)``; the server
applies pushes in arrival (fastest-first) order, so a partial-barrier rule
(``'kasync'``) accepts the fastest K clients, and the round's wall cost is
the ``barrier_k``-th order statistic of the draws (t_(C) for a full
barrier or an async rule).  Churn/elastic scenario knobs are FRED-only —
the round trainer's fleet is a fixed SPMD program (`build_round_step`
raises).  See docs/SCENARIOS.md.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import TrainerConfig
from repro.core import engine
from repro.core import queue as qlib
from repro.core import rules as server_rules
from repro.core import scenarios as scen
from repro.core import server_shard
from repro.core.bandwidth import masked_bytes, tree_bytes
from repro.core.engine import Counters
from repro.core.rules import ServerConfig, ServerState


class RoundState(NamedTuple):
    """Server + C divergent client copies + engine counters (leaves [C, ...])."""

    server: ServerState
    client_params: Any          # pytree, leaves [C, ...]
    client_ts: jnp.ndarray      # [C] int32
    round_idx: jnp.ndarray      # int32
    counters: Counters          # shared engine bookkeeping (as in FRED)
    # per-tensor gating (§5): [C, n_leaves] int32 — the timestamp at which
    # each TENSOR of each client group's copy last synchronized.
    client_leaf_ts: Any = None
    # bounded server ingress queue (tc.queue_capacity > 0; core/queue.py)
    queue: Optional[qlib.QueueState] = None


def server_config(tc: TrainerConfig) -> ServerConfig:
    """Project the trainer config onto the engine's `ServerConfig`."""
    return ServerConfig(
        rule=tc.rule, lr=tc.lr, gamma=tc.gamma, beta=tc.beta, eps=tc.eps,
        kappa=tc.kappa, poly_power=tc.poly_power,
        variant=tc.variant, num_clients=tc.num_round_clients,
        use_fused_kernel=tc.use_fused_kernel,
        kasync_k=tc.kasync_k,
        kernel_interpret=tc.kernel_interpret,
        kernel_block_rows=tc.kernel_block_rows,
    )


def _queue_payload_example(tc: TrainerConfig, params):
    """Single-event payload the round trainer's ingress queue stores: the
    pushed gradient, plus the pushing copy for gap-aware rules."""
    payload = {"grad": params}
    if server_rules.get_rule(tc.rule).needs_client_params:
        payload["copy"] = params
    return payload


def init_round_state(tc: TrainerConfig, params) -> RoundState:
    """Fresh `RoundState`: server at T = 0, C identical client copies,
    zeroed counters (and per-tensor timestamps / an empty ingress queue
    when configured)."""
    scfg = server_config(tc)
    n_leaves = len(jax.tree.leaves(params))
    return RoundState(
        server=server_rules.init(scfg, params),
        client_params=engine.tree_stack(params, tc.num_round_clients),
        client_ts=jnp.zeros((tc.num_round_clients,), jnp.int32),
        round_idx=jnp.zeros((), jnp.int32),
        counters=engine.init_counters(),
        client_leaf_ts=(
            jnp.zeros((tc.num_round_clients, n_leaves), jnp.int32)
            if tc.per_tensor_fetch else None),
        queue=(qlib.init_queue(
            tc.queue_capacity, _queue_payload_example(tc, params),
            n_leaves=n_leaves if tc.per_tensor_fetch else 0,
            mask_like=params if tc.per_tensor_push else None)
            if tc.queue_capacity else None),
    )


def round_state_shardings(state: RoundState, mesh,
                          axis: str = server_shard.SERVER_AXIS) -> RoundState:
    """The `NamedSharding` of every leaf of `state` (arrays or shapes, e.g.
    from `jax.eval_shape`) on a sharded-server mesh.

    ``state.server`` (W and the eq. 4–6 statistics) and the ingress-queue
    payload are block-partitioned across the ``axis`` devices per
    `server_shard.server_leaf_spec`.  The [C]-leading client leaves
    (copies, ``client_ts``, ``client_leaf_ts``) lie over the axis too, C/S
    whole clients a device (`server_shard.check_clients_divide`).  Scalars,
    counters and the queue's slot bookkeeping replicate.
    """
    S = server_shard.mesh_axis_size(mesh, axis)
    server_shard.check_clients_divide(jnp.shape(state.client_ts)[0], mesh,
                                      axis)
    at = lambda spec: NamedSharding(mesh, spec)
    everywhere = lambda tree: jax.tree.map(
        lambda _: at(PartitionSpec()), tree)

    def blocks(tree, lead=0):
        return jax.tree.map(lambda l: at(server_shard.server_leaf_spec(
            jnp.shape(l), S, axis, lead=lead)), tree)

    clients = lambda tree: jax.tree.map(lambda l: at(
        server_shard.client_leaf_spec(jnp.ndim(l), axis) if S > 1
        else PartitionSpec()), tree)
    queue = None
    if state.queue is not None:
        queue = everywhere(state.queue)._replace(
            payload=blocks(state.queue.payload, lead=1))
    return RoundState(
        server=blocks(state.server),
        client_params=clients(state.client_params),
        client_ts=clients(state.client_ts),
        round_idx=everywhere(state.round_idx),
        counters=everywhere(state.counters),
        client_leaf_ts=clients(state.client_leaf_ts),
        queue=queue,
    )


def shard_round_state(state: RoundState, mesh,
                      axis: str = server_shard.SERVER_AXIS) -> RoundState:
    """Place a `RoundState` on a sharded-server mesh
    (`round_state_shardings`).  A mesh whose ``axis`` has size 1 (or no
    ``axis``) is a no-op, preserving the ``server_shards=1`` bitwise
    contract.
    """
    if server_shard.mesh_axis_size(mesh, axis) <= 1:
        return state
    return jax.device_put(state, round_state_shardings(state, mesh, axis))


def build_round_step(
    tc: TrainerConfig,
    grad_fn: Callable,     # grad_fn(params, batch) -> (loss, grads)
    apply_mode: str = "serial",
    batched_loss_fn: Callable = None,   # batched(W, deltas, batch) -> [C]
    mesh=None,
):
    """Returns round_step(state, batch, key) -> (state, metrics).

    `batch` leaves must have a leading [C] axis (one shard per client group).
    `mesh` is the mesh `shard_round_state` placed the state on, if any; the
    one-kernel apply then runs each shard's block on its own device.  On a
    server axis of S > 1 devices the clients lie over it too
    (`round_state_shardings`; C a multiple of S): each device computes the
    forward and backward of its own clients alone (a `jax.shard_map` over
    the client axis), the pushed gradients are routed to the server's
    blocks (``push_route``, in the apply) and the new W's blocks gathered
    into the fetching copies (``fetch_gather``).

    With ``apply_mode='fused'`` and ``tc.fused_mode`` 'auto'/'cotangent' the
    per-client gradients are reduced by the engine's cotangent path when the
    configuration is eligible (see `TrainerConfig.fused_mode`): the weighted
    sum Σ_c m_c·c(τ_c)·g_c and the stats mean gradient come from two
    pullbacks of the batched forward — `batched_loss_fn(W, deltas, batch) ->
    [C]` supplies the shared/delta form, and the [C, P] per-client gradient
    batch is never materialized.  Alternatively a model-attached
    `grad_fn.event_batched` is picked up; it uses the model convention
    `batched(W, deltas, *batch)` (the same form `loss_fn.event_batched`
    carries in FRED, e.g. `mlp.nll_loss_event_batched(W, deltas, x, y)`),
    so `batch` must then be a tuple of the loss's data arguments.
    """
    assert apply_mode in ("serial", "fused"), apply_mode
    assert tc.fused_mode in ("auto", "materialized", "cotangent"), \
        tc.fused_mode
    scfg = server_config(tc)
    # same restriction as SimConfig: a partially-transmitted gradient has no
    # coherent meaning at a synchronous round barrier (see fred.SimConfig)
    assert not (tc.per_tensor_push
                and server_rules.get_rule(tc.rule).synchronous), \
        f"per_tensor_push is undefined for synchronous rule {tc.rule!r}"

    rule = server_rules.get_rule(tc.rule)
    use_queue = tc.queue_capacity > 0
    if tc.server_shards < 1:
        raise ValueError(
            f"server_shards must be >= 1 (1 = replicated server), got "
            f"{tc.server_shards}")
    if tc.queue_capacity < 0:
        raise ValueError(
            f"queue_capacity must be >= 0 (0 disables the queue), got "
            f"{tc.queue_capacity}")
    if tc.drain_policy not in qlib.DRAIN_POLICIES:
        raise ValueError(
            f"unknown drain_policy {tc.drain_policy!r}: expected one of "
            f"{qlib.DRAIN_POLICIES}")
    if tc.admission_policy not in qlib.ADMISSION_POLICIES:
        raise ValueError(
            f"unknown admission_policy {tc.admission_policy!r}: expected "
            f"one of {qlib.ADMISSION_POLICIES}")
    if use_queue:
        if rule.synchronous:
            raise ValueError(
                f"queue_capacity > 0 is undefined for synchronous rule "
                f"{tc.rule!r}: the barrier already buffers a full round "
                f"server-side — use an async rule or queue_capacity=0")
        if tc.drain_k < 1:
            raise ValueError(f"drain_k must be >= 1, got {tc.drain_k}")
        if (tc.drain_policy == "adaptive"
                and not 0.0 < tc.drain_adaptive_gain <= 1.0):
            raise ValueError(
                f"drain_adaptive_gain must be in (0, 1], got "
                f"{tc.drain_adaptive_gain}")
        if tc.admission_policy == "block":
            if tc.drain_policy != "drain_all":
                raise ValueError(
                    "admission_policy='block' models lossless backpressure "
                    "— only sound when overflow is impossible: use "
                    "drain_policy='drain_all', or admission "
                    "'reject'/'drop_oldest' for a lossy loaded server")
            if tc.queue_capacity < tc.num_round_clients:
                raise ValueError(
                    f"admission_policy='block' requires queue_capacity >= "
                    f"num_round_clients (got {tc.queue_capacity} < "
                    f"{tc.num_round_clients}): all C round pushes must fit "
                    f"the drained-empty ring — raise queue_capacity or use "
                    f"'reject'/'drop_oldest'")
        if tc.fused_mode == "cotangent":
            raise ValueError(
                "fused_mode='cotangent' is not wired through the round "
                "trainer's ingress queue (the round's minibatch would have "
                "to be queued alongside each stale copy, as FRED does) — "
                "use fused_mode='auto'/'materialized' with queue_capacity "
                "> 0, or FRED for queued cotangent runs")
    use_scenario = tc.scenario is not None
    if use_scenario:
        if tc.scenario.has_churn():
            raise ValueError(
                "churn/elastic scenario knobs (dropout_rate, rejoin_rate, "
                "initial_active_frac < 1, resize_at) are FRED-only: the "
                "round trainer's fleet is a fixed SPMD program — use "
                "sim.fred for churny fleets, or a pure service-time "
                "scenario (e.g. 'stragglers', 'hotspot') here")
        scen.client_scales(tc.scenario, tc.num_round_clients)  # validate
    batched_losses = batched_loss_fn
    if batched_losses is None:
        attached = getattr(grad_fn, "event_batched", None)
        if attached is not None:
            # model convention: batched(W, deltas, x, y, ...) — adapt to
            # this module's opaque batch argument by splatting the tuple
            batched_losses = lambda W, deltas, batch: attached(
                W, deltas, *batch)
    # v_separable rules (fasgd's ε-reparameterized eq. 7) ride the cotangent
    # path only on explicit request — 'auto' never silently picks the
    # ~1e-8-approximate scale (mirrors SimConfig.cotangent_eligible).
    use_cotangent = (
        apply_mode == "fused"
        and tc.fused_mode in ("auto", "cotangent")
        and rule.supports_fused
        and (rule.coeffs_are_v_independent
             or (rule.v_separable and tc.fused_mode == "cotangent"))
        and not tc.per_tensor_push and not tc.per_tensor_fetch
        and tc.drop_policy == "discard"
        and not tc.use_fused_kernel
        and not use_queue
        and batched_losses is not None)
    if tc.fused_mode == "cotangent" and not use_cotangent:
        raise ValueError(
            "fused_mode='cotangent' needs apply_mode='fused', a "
            "coeffs_are_v_independent (or v_separable) rule, whole-copy "
            "gating, drop_policy='discard', use_fused_kernel=False, and an "
            "event-batched loss (batched_loss_fn or grad_fn.event_batched)")

    axis = tc.server_axis
    server_shard.check_clients_divide(tc.num_round_clients, mesh, axis)
    spread = server_shard.mesh_axis_size(mesh, axis) > 1
    vgrad = jax.vmap(grad_fn)
    if spread:
        # each device runs its own clients' forward and backward on the
        # copies and batch rows it holds: no client's work is repeated
        on_clients = PartitionSpec(axis)
        vgrad = jax.shard_map(vgrad, mesh=mesh,
                              in_specs=(on_clients, on_clients),
                              out_specs=(on_clients, on_clients),
                              check_vma=False)

    @jax.named_scope("dispatch")
    def round_step(state: RoundState, batch, key):
        k_push, k_fetch = jax.random.split(key)
        C = tc.num_round_clients
        model_bytes = tree_bytes(state.server.params)

        # --- scenario-lite wall clock: per-round [C] service draws ---
        # The server sees this round's pushes in arrival (fastest-first)
        # order, so a partial-barrier rule (kasync) accepts the fastest K;
        # the round's wall cost is the k-th order statistic of the draws.
        svc = svc_order = None
        if use_scenario:
            svc = scen.round_service_times(tc.scenario, C, state.round_idx)
            svc_order = jnp.argsort(svc)

        if not use_cotangent:
            with jax.named_scope("client_grad"):
                losses, grads = vgrad(state.client_params, batch)
        else:
            grads = None        # cotangent: losses come from the vjp forward

        # --- push gates (eq. 9; per-leaf eq. 9 in per-tensor mode) ---
        if tc.per_tensor_push:
            push = jax.vmap(lambda k: engine.per_tensor_gate(
                k, state.server, tc.c_push, tc.eps)[0]
            )(jax.random.split(k_push, C))                   # leaves [C]
            push_event = engine.any_leaf(push)               # [C]
            push_sent = masked_bytes(push, state.server.params)
        else:
            push = push_event = (
                engine.transmit_gate(k_push, state.server, tc.c_push,
                                     tc.eps, (C,))
                if tc.c_push > 0 else jnp.ones((C,), bool)
            )
            push_sent = jnp.sum(push.astype(jnp.float32)) * model_bytes

        grad_ts = state.client_ts
        if tc.per_tensor_fetch:
            # per-tensor staleness: each tensor's τ from its own last sync
            treedef = jax.tree.structure(state.server.params)
            grad_ts = jax.tree.unflatten(
                treedef, [state.client_leaf_ts[:, i]
                          for i in range(state.client_leaf_ts.shape[1])])

        queue = state.queue
        admitted = push_event
        if use_queue:
            # --- admission: this round's pushes enter the bounded ring ---
            payload = {"grad": grads}
            if rule.needs_client_params:
                payload["copy"] = state.client_params
            arrivals = qlib.Arrivals(
                payload=payload, ts=state.client_ts,
                client=jnp.arange(C, dtype=jnp.int32), valid=push_event,
                leaf_ts=(state.client_leaf_ts if tc.per_tensor_fetch
                         else None),
                leaf_mask=push if tc.per_tensor_push else None)
            if svc_order is not None:
                # ring order = arrival order: fastest clients enqueue (and
                # under a lossy admission policy, survive) first
                arrivals = jax.tree.map(lambda a: a[svc_order], arrivals)
            queue, admitted, n_rejected, n_dropped = qlib.enqueue(
                state.queue, arrivals, tc.admission_policy,
                state.server.timestamp)
            if svc_order is not None:
                # back to client order — downstream consumers (refresh,
                # byte accounting) index `admitted` by client
                admitted = admitted[jnp.argsort(svc_order)]
            depth_peak = queue.size
            # only admitted pushes crossed the wire — override the
            # gate-level byte estimate (a rejected push is refused before
            # transmission and must not count as sent)
            if tc.per_tensor_push:
                push_sent = masked_bytes(
                    jax.tree.map(lambda m: m & admitted, push),
                    state.server.params)
            else:
                push_sent = (jnp.sum(admitted.astype(jnp.float32))
                             * model_bytes)

            # --- drain: apply the k_eff oldest queued pushes ---
            k_eff = qlib.drain_count(
                queue.size, tc.drain_policy,
                drain_k=tc.drain_k, gain=tc.drain_adaptive_gain)
            queue, qbatch = qlib.dequeue(queue, k_eff)
            latency_sum = jnp.sum(jnp.where(
                qbatch.valid,
                (state.server.timestamp - qbatch.enq_T).astype(jnp.float32),
                0.0))
            if tc.per_tensor_fetch:
                treedef = jax.tree.structure(state.server.params)
                q_ts = jax.tree.unflatten(
                    treedef, [qbatch.leaf_ts[:, i]
                              for i in range(qbatch.leaf_ts.shape[1])])
            else:
                q_ts = qbatch.ts
            q_push = qlib.drained_push_arg(qbatch, tc.per_tensor_push)
            q_cp = qbatch.payload.get("copy")
            if apply_mode == "serial":
                server, taus = engine.serial_apply(
                    scfg, state.server, qbatch.payload["grad"], q_push,
                    q_ts, q_cp)
            else:
                server, taus = engine.fused_apply(
                    scfg, state.server, qbatch.payload["grad"], q_push,
                    q_ts, client_params=q_cp, mesh=mesh,
                    server_axis=tc.server_axis)
            mean_tau = (jnp.sum(qbatch.valid.astype(jnp.float32) * taus)
                        / jnp.maximum(k_eff, 1))
        elif use_cotangent:
            server, taus, losses = engine.fused_apply_cotangent(
                scfg, state.server,
                lambda W, deltas: batched_losses(W, deltas, batch),
                state.client_params, push, grad_ts)
        elif apply_mode == "serial":
            g_srv, p_srv, t_srv, cp_srv = (
                grads, push, grad_ts, state.client_params)
            if svc_order is not None:
                g_srv, p_srv, t_srv, cp_srv = jax.tree.map(
                    lambda a: a[svc_order], (g_srv, p_srv, t_srv, cp_srv))
            server, taus = engine.serial_apply(
                scfg, state.server, g_srv, p_srv, t_srv, cp_srv)
        else:
            server, taus = engine.fused_apply(
                scfg, state.server, grads, push, grad_ts,
                state.client_params, mesh=mesh, server_axis=tc.server_axis)
        if not use_queue:
            mean_tau = jnp.mean(taus)

        # --- fetch gates ---
        if tc.per_tensor_fetch:
            fmask = jax.vmap(lambda k: engine.per_tensor_gate(
                k, server, tc.c_fetch, tc.eps)[0]
            )(jax.random.split(k_fetch, C))                  # leaves [C]
            fetch = jnp.stack(jax.tree.leaves(fmask)).all(axis=0)  # [C]
            fetch_sent = masked_bytes(fmask, server.params)
        else:
            fmask = None
            fetch = (
                engine.transmit_gate(k_fetch, server, tc.c_fetch, tc.eps, (C,))
                if tc.c_fetch > 0 else jnp.ones((C,), bool)
            )
            fetch_sent = jnp.sum(fetch.astype(jnp.float32)) * model_bytes

        # --- client-side parameter refresh ---
        @jax.named_scope("fetch_refresh")
        def upd_leaf(cp, sp, g, p, f):
            exp = (-1,) + (1,) * (cp.ndim - 1)
            f = f.reshape(exp)
            p = p.reshape(exp)
            # g is None on the cotangent path, which requires 'discard' —
            # the un-pushed local gradient is never needed there.
            local = cp - tc.lr * g if tc.drop_policy == "local_apply" else cp
            kept = jnp.where(p, cp, local)       # un-pushed grad applied locally
            if not spread:
                return jnp.where(f, sp[None], kept)  # fetched get canonical
            with jax.named_scope("fetch_gather"):
                # the server's blocks of the new W, gathered whole onto
                # the devices of the clients that fetch them
                sp = jax.lax.with_sharding_constraint(
                    sp, NamedSharding(mesh, PartitionSpec()))
            new = jnp.where(f, sp[None], kept)
            return jax.lax.with_sharding_constraint(new, NamedSharding(
                mesh, server_shard.client_leaf_spec(new.ndim, axis)))

        # with a queue, a push the admission policy refused behaves like a
        # gated-out push on the client: it falls back to drop_policy
        refresh_push = push
        if use_queue:
            refresh_push = (jax.tree.map(lambda m: m & admitted, push)
                            if tc.per_tensor_push else admitted)
        n_leaves = len(jax.tree.leaves(server.params))
        g_leaves = (jax.tree.leaves(grads) if grads is not None
                    else [None] * n_leaves)
        p_leaves = (jax.tree.leaves(refresh_push) if tc.per_tensor_push
                    else [refresh_push] * n_leaves)
        f_leaves = (jax.tree.leaves(fmask) if tc.per_tensor_fetch
                    else [fetch] * n_leaves)
        treedef = jax.tree.structure(server.params)
        client_params = jax.tree.unflatten(treedef, [
            upd_leaf(cp, sp, g, p, f)
            for cp, sp, g, p, f in zip(
                jax.tree.leaves(state.client_params),
                jax.tree.leaves(server.params),
                g_leaves, p_leaves, f_leaves)])
        client_ts = jnp.where(fetch, server.timestamp, state.client_ts)
        client_leaf_ts = state.client_leaf_ts
        if tc.per_tensor_fetch:
            client_leaf_ts = jnp.stack(
                [jnp.where(m, server.timestamp, state.client_leaf_ts[:, i])
                 for i, m in enumerate(jax.tree.leaves(fmask))], axis=1)

        counters = engine.count_events(
            state.counters, admitted, fetch,
            push_bytes_sent=push_sent, push_bytes_total=C * model_bytes,
            fetch_bytes_sent=fetch_sent,
            fetch_bytes_total=C * model_bytes)
        if use_queue:
            counters = qlib.count_queue(
                counters,
                enqueued=jnp.sum(admitted.astype(jnp.int32)),
                rejected=n_rejected, dropped=n_dropped, drained=k_eff,
                depth_post=queue.size, depth_peak=depth_peak,
                latency_sum=latency_sum)
        # kernel-path telemetry (one launch per leaf per fused window; per
        # scanned event on the serial path) — same folds as sim/fred.py
        if apply_mode == "fused" and not use_cotangent \
                and engine.fused_kernel_active(scfg):
            counters = engine.count_kernel(
                counters, n_leaves, k_eff if use_queue else C)
        elif apply_mode == "serial" \
                and engine.serial_kernel_active(scfg, tc.per_tensor_fetch):
            rows = qbatch.valid.shape[0] if use_queue else C
            counters = engine.count_kernel(
                counters, rows * n_leaves, k_eff if use_queue else C)
        if tc.server_shards > 1:
            counters = server_shard.count_shard(
                counters, applies=1, events=k_eff if use_queue else C,
                bytes_peak=server_shard.peak_shard_bytes(
                    state.server, tc.server_shards, tc.server_axis),
                depth_peak=k_eff if use_queue else C)
        if use_scenario:
            # a sync rule's round ends at its partial barrier (the K-th
            # arrival); an async round is charged the full straggler t_(C)
            k_used = rule.barrier_k(scfg) if rule.synchronous else C
            round_dt = jnp.sort(svc)[k_used - 1]
            counters = scen.advance_wall(counters, round_dt, active_count=C)
        new_state = RoundState(
            server=server,
            client_params=client_params,
            client_ts=client_ts,
            round_idx=state.round_idx + 1,
            counters=counters,
            client_leaf_ts=client_leaf_ts,
            queue=queue,
        )
        metrics = {
            "loss": jnp.mean(losses),
            "loss_per_client": losses,
            "mean_tau": mean_tau,
            "pushes": jnp.sum(admitted.astype(jnp.int32)),
            "fetches": jnp.sum(fetch.astype(jnp.int32)),
            "timestamp": server.timestamp,
        }
        if use_queue:
            metrics.update(
                queue_depth=queue.size, drained=k_eff,
                rejected=n_rejected, dropped=n_dropped)
        if use_scenario:
            metrics.update(wall=counters.wall_clock, round_dt=round_dt)
        return new_state, metrics

    return round_step


def bandwidth_saved_bytes(tc: TrainerConfig, params, num_rounds: int,
                          push_rate: float, fetch_rate: float) -> dict:
    """ICI-byte accounting for the elided collectives (EXPERIMENTS.md §Perf).

    A push is a reduce of one gradient copy; a fetch is a broadcast of one
    parameter copy.  Rates are measured actual/potential ratios.
    """
    pbytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
    C = tc.num_round_clients
    full = num_rounds * C * pbytes
    return {
        "full_push_bytes": full,
        "full_fetch_bytes": full,
        "actual_push_bytes": int(full * push_rate),
        "actual_fetch_bytes": int(full * fetch_rate),
        "total_saving_factor": 2.0 / max(push_rate + fetch_rate, 1e-9),
    }
