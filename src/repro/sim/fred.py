"""FRED-in-JAX: deterministic single-node simulation of distributed SGD.

This is the paper's §3 experimental vehicle rebuilt as a pure-JAX program:
the (server, λ clients, dispatcher) system is a single fixed-shape pytree
advanced by `jax.lax.scan`, so every run is bitwise reproducible from its
seed, on one machine, with no real network.

Semantics follow the paper's Async SGD protocol:

* each simulation *event* = one client finishing one minibatch gradient;
* the dispatcher decides *which* client that is (uniform / round-robin /
  heterogeneous-speed schedules);
* the gradient is computed on the parameters that client fetched at its last
  interaction — its *stale* copy — and carries that copy's timestamp;
* the server applies the update under the configured rule (any rule in the
  `core.rules` registry) and the client receives the new parameters — unless
  B-FASGD gating drops the push and/or the fetch (paper §2.3).

The protocol decision structure (gates, gated/serial/fused application,
counters) lives in `core/engine.py`, shared with the SPMD round trainer.

**Event batching** (the λ-scaling hot path): each `lax.scan` step advances
`events_per_step = K` client events.

* ``apply_mode='serial'`` (default, paper-faithful): the K events are
  processed one at a time inside the step — for every K this produces the
  *bitwise identical* trajectory to the legacy one-event-per-step simulator,
  because per-event RNG keys are derived from the global event index.
* ``apply_mode='fused'``: the K gradients are computed with one `vmap`
  (optionally `shard_map`-sharded over devices) and applied through the
  engine's fused masked-sum path — one stats step on the mean pushed
  gradient, T advances by the number of pushes.  This models K clients
  finishing within one dispatch window (they all read the pre-window server
  state) and is the ~K× faster mode that makes λ ≥ 1024 sweeps tractable.

**Fused-path variants** (``SimConfig.fused_mode``): events are first
deduplicated by fetch timestamp (`engine.dedup_events` — clients that
fetched at the same T hold bitwise-identical copies, so the stale-parameter
batch is gathered through group representatives).  Then either

* ``'materialized'``: `vmap(grad_fn)` materializes the [K, P] per-event
  gradient batch and `engine.fused_apply` reduces it (required for the
  gradient-cache drop policy, per-tensor gating, gap-aware rules, and the
  batched Pallas kernel); or
* ``'cotangent'``: for rules with v-independent coefficients
  (`UpdateRule.coeffs_are_v_independent`) the weighted gradient sum and the
  stats mean gradient are computed as vjps of the batched forward with
  per-event cotangent weights (`engine.fused_apply_cotangent`) — the [K, P]
  batch is never materialized, which is what breaks the fused path's CPU
  memory wall (see benchmarks/sim_throughput.py).
* ``'auto'`` (default) picks 'cotangent' whenever the configuration is
  eligible, else 'materialized'.

Dropped pushes follow the paper's server-side gradient cache by default
(`drop_policy='cache'`: re-apply that client's most recent transmitted
gradient), or `'skip'` (no server update at that opportunity).

**Bounded ingress queue** (``SimConfig.queue_capacity > 0``, `core/queue.py`):
instead of applying each push the instant it arrives, arrivals are admitted
into a fixed-capacity ring buffer and a drain policy decides how many queued
events each server pass applies — the simulator then models a *loaded*
parameter server whose backlog (and therefore staleness) grows when arrivals
outpace application.  Each scan step is one *drain window*: K arrival events
(dispatch → stale-copy gradient → eq.-9 push gate → admission), one drain
(`serial_apply` / `fused_apply` / `fused_apply_cotangent` on the drained
batch — queue-induced same-timestamp collisions feed `dedup_events` as the
common case), then all K arriving clients run their fetch gates against the
post-drain server.  With ``queue_capacity=1`` and ``drain_policy='drain_all'``
this reduces bitwise to the immediate-apply path.  See
``SimConfig.queue_capacity`` / ``drain_policy`` / ``admission_policy`` and
docs/ARCHITECTURE.md §"Server ingress queue".

**Sharded parameter server** (``SimConfig.server_shards > 1``,
`core/server_shard.py`): pass ``run_simulation(mesh=...)`` a mesh carrying
a ``server_axis`` ('server' by default) of exactly S devices and the server
state itself — W, the eq. 4–6 statistics n/b/v, and the ingress-queue
payload — is block-partitioned across those devices, so each shard owns its
slice of the statistics and of every apply.  With S=1 the placement is a
no-op (bitwise-identical trajectories); the partition math, the
replicated≡sharded equivalence invariant, and the multi-process
(`jax.distributed`) launch recipe live in docs/SHARDING.md.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core import queue as qlib
from repro.core import rules as server_rules
from repro.core import scenarios as scen
from repro.core import server_shard
from repro.core.bandwidth import BandwidthConfig, masked_bytes, tree_bytes
from repro.core.engine import (
    Counters,
    tree_index,
    tree_set,
    tree_stack,
    tree_where,
    tree_where_axis,
)
from repro.core.rules import ServerConfig, ServerState


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """One FRED fleet: λ clients, a server rule, and the event schedule.

    Groups four orthogonal axes of the simulation — the update protocol
    (`server`, `bandwidth`), the event engine (`events_per_step`,
    `apply_mode`, `fused_mode`), the server's ingress queue
    (`queue_capacity` + policies), and the modeled arrival-time process
    (`scenario`).  `__post_init__` rejects combinations with no coherent
    semantics rather than letting them run and mislead.
    """

    num_clients: int = 4
    batch_size: int = 32
    server: ServerConfig = ServerConfig()
    bandwidth: BandwidthConfig = BandwidthConfig()
    dispatcher: str = "uniform"   # 'uniform' | 'roundrobin' | 'heterogeneous'
    het_skew: float = 1.5         # log-speed std for the heterogeneous schedule
    seed: int = 0
    # --- event batching (core/engine.py) ---
    events_per_step: int = 1      # K client events per scan step
    apply_mode: str = "serial"    # 'serial' (paper-faithful) | 'fused'
    # 'auto' | 'materialized' | 'cotangent' — how fused gradients are
    # reduced (see module docstring); 'auto' takes the cotangent path
    # whenever the rule/bandwidth configuration is eligible.
    fused_mode: str = "auto"
    # --- bounded server ingress queue (core/queue.py) ---
    queue_capacity: int = 0       # 0 = immediate apply (no queue)
    drain_policy: str = "drain_all"     # 'drain_all' | 'drain_k' | 'adaptive'
    drain_k: int = 1              # per-window drain budget ('drain_k' floor
                                  # of the 'adaptive' batch)
    drain_adaptive_gain: float = 0.5    # 'adaptive': drain ceil(gain·depth)
    admission_policy: str = "block"     # 'block' | 'reject' | 'drop_oldest'
    # --- modeled arrival-time process (core/scenarios.py) ---
    # None = the classic fixed K-per-window arrival model with a unit event
    # clock; a ScenarioConfig replaces the dispatcher with a discrete-event
    # service-time race (stragglers / hotspots / churn / elastic resize) and
    # gives every run a modeled wall-clock axis (docs/SCENARIOS.md).
    scenario: Optional[scen.ScenarioConfig] = None
    # --- sharded parameter server (core/server_shard.py; docs/SHARDING.md) ---
    # 1 = replicated server (default, bitwise-identical to every prior
    # trajectory).  S > 1 block-partitions W/n/b/v (and the queue payload)
    # across the `server_axis` of the mesh passed to run_simulation; that
    # mesh axis must have exactly S devices (validate_server_mesh).
    server_shards: int = 1
    server_axis: str = "server"

    def cotangent_serviceable(self) -> bool:
        """True iff `fused_apply_cotangent` can serve this configuration.

        Needs a rule whose fused scale rides the cotangent machinery —
        v-independent coefficients, or the weaker `v_separable` split
        (fasgd's ε-reparameterized lr/τ_k · 1/(v+ε), applied through the
        `reweight_by_v` pullback) — plus whole-copy (non-per-tensor)
        gating, no server-side gradient cache (the cache stores per-event
        gradients the cotangent path never materializes), and the XLA
        reduction (`use_fused_kernel` selects the one-kernel materialized
        path instead).
        """
        rule = server_rules.get_rule(self.server.rule)
        use_cache = (self.bandwidth.c_push > 0
                     and self.bandwidth.drop_policy == "cache")
        return (rule.supports_fused
                and (rule.coeffs_are_v_independent or rule.v_separable)
                and not self.bandwidth.per_tensor_push
                and not self.bandwidth.per_tensor_fetch
                and not use_cache
                and not self.server.use_fused_kernel)

    def cotangent_eligible(self) -> bool:
        """True iff fused_mode='auto' resolves to the cotangent path.

        Stricter than `cotangent_serviceable`: 'auto' promises numerical
        parity with the materialized reduction, so only rules with exactly
        v-independent coefficients qualify — `v_separable` rules (fasgd)
        carry a documented ε-reparameterization and are served only by the
        explicit fused_mode='cotangent' opt-in.
        """
        return (self.cotangent_serviceable()
                and server_rules.get_rule(
                    self.server.rule).coeffs_are_v_independent)

    def __post_init__(self):
        assert self.dispatcher in ("uniform", "roundrobin", "heterogeneous")
        assert self.apply_mode in ("serial", "fused"), self.apply_mode
        assert self.fused_mode in ("auto", "materialized", "cotangent"), \
            self.fused_mode
        assert self.events_per_step >= 1, self.events_per_step
        if self.fused_mode == "cotangent":
            assert self.apply_mode == "fused", \
                "fused_mode='cotangent' requires apply_mode='fused'"
            assert self.cotangent_serviceable(), (
                f"configuration is not cotangent-serviceable: rule "
                f"{self.server.rule!r} must declare coeffs_are_v_independent "
                f"or v_separable, and gating must be whole-copy without a "
                f"gradient cache (see SimConfig.cotangent_serviceable)")
        rule = server_rules.get_rule(self.server.rule)
        if rule.synchronous:
            # A synchronous barrier only makes sense with a fair schedule —
            # either round-robin dispatch, or a scenario (whose sync_round
            # delivers every client exactly once per round, fastest-first).
            assert self.scenario is not None \
                or self.dispatcher == "roundrobin", \
                f"{self.server.rule} requires roundrobin"
            # Per-leaf push masks would desync the barrier's pending-sum /
            # count invariant (leaves revert independently while the scalar
            # count advances) — a partially-transmitted gradient has no
            # coherent meaning at a round barrier.
            assert not self.bandwidth.per_tensor_push, \
                f"per_tensor_push is undefined for synchronous rule " \
                f"{self.server.rule!r}"
        if self.apply_mode == "fused":
            assert rule.supports_fused, \
                f"rule {self.server.rule!r} does not support apply_mode='fused'"
        # --- sharded-server validation (core/server_shard.py) ---
        if self.server_shards < 1:
            raise ValueError(
                f"server_shards must be >= 1 (1 = replicated server), got "
                f"{self.server_shards}")
        # --- ingress-queue validation (clear errors, not silent misbehavior) ---
        if self.queue_capacity < 0:
            raise ValueError(
                f"queue_capacity must be >= 0 (0 disables the queue), got "
                f"{self.queue_capacity}")
        if self.drain_policy not in qlib.DRAIN_POLICIES:
            raise ValueError(
                f"unknown drain_policy {self.drain_policy!r}: expected one "
                f"of {qlib.DRAIN_POLICIES}")
        if self.admission_policy not in qlib.ADMISSION_POLICIES:
            raise ValueError(
                f"unknown admission_policy {self.admission_policy!r}: "
                f"expected one of {qlib.ADMISSION_POLICIES}")
        if self.queue_capacity:
            if rule.synchronous:
                raise ValueError(
                    f"queue_capacity > 0 is undefined for synchronous rule "
                    f"{self.server.rule!r}: a barrier rule already buffers a "
                    f"full round server-side, so an ingress queue in front of "
                    f"the barrier would double-buffer the same gradients — "
                    f"use an async rule or queue_capacity=0")
            if self.drain_k < 1:
                raise ValueError(
                    f"drain_k must be >= 1, got {self.drain_k}")
            if (self.drain_policy == "adaptive"
                    and not 0.0 < self.drain_adaptive_gain <= 1.0):
                raise ValueError(
                    f"drain_adaptive_gain must be in (0, 1], got "
                    f"{self.drain_adaptive_gain} (1.0 degenerates to "
                    f"drain_all; <= 0 would never drain above the drain_k "
                    f"floor)")
            if (self.bandwidth.c_push > 0
                    and self.bandwidth.drop_policy == "cache"):
                raise ValueError(
                    "drop_policy='cache' (server-side gradient cache) is "
                    "incompatible with an ingress queue: a gated-out push "
                    "never reaches the server, so there is no arrival to "
                    "admit and no cached re-application slot at drain time "
                    "— use drop_policy='skip' with queue_capacity > 0")
            if self.admission_policy == "block":
                if self.drain_policy != "drain_all":
                    raise ValueError(
                        "admission_policy='block' models lossless "
                        "backpressure, which the fixed-shape scan can only "
                        "honor when overflow is impossible (a blocked client "
                        "cannot be suspended mid-window): use "
                        "drain_policy='drain_all', or admission "
                        "'reject'/'drop_oldest' for a lossy loaded server")
                if self.queue_capacity < self.events_per_step:
                    raise ValueError(
                        f"admission_policy='block' requires queue_capacity "
                        f">= events_per_step (got {self.queue_capacity} < "
                        f"{self.events_per_step}): a full arrival window "
                        f"must always fit the drained-empty ring — raise "
                        f"queue_capacity or use 'reject'/'drop_oldest'")
        # --- scenario validation (core/scenarios.py; docs/SCENARIOS.md) ---
        if self.scenario is not None:
            if self.dispatcher == "heterogeneous":
                raise ValueError(
                    "a scenario's service-time model replaces the "
                    "heterogeneous dispatcher's speed schedule: configure "
                    "hotspot/straggler client scales in ScenarioConfig "
                    "instead (dispatcher='uniform' or 'roundrobin' are "
                    "accepted and ignored for arrival ordering)")
            # raises early on inconsistent straggler/hotspot fractions
            scen.client_scales(self.scenario, self.num_clients)
            if rule.synchronous:
                if self.events_per_step != self.num_clients:
                    raise ValueError(
                        f"a synchronous rule under a scenario advances one "
                        f"round of λ arrivals per scan step: set "
                        f"events_per_step = num_clients (got "
                        f"{self.events_per_step} != {self.num_clients})")
                if self.scenario.has_churn():
                    raise ValueError(
                        f"synchronous rule {self.server.rule!r} cannot run "
                        f"under dropout/rejoin/elastic churn: a barrier "
                        f"over a changing fleet deadlocks — that failure "
                        f"mode is exactly why kasync exists; use an async "
                        f"rule, or a churn-free scenario "
                        f"(stragglers/hotspot)")


class SimState(NamedTuple):
    """Scan carry: server + λ stale client copies + protocol bookkeeping."""

    server: ServerState
    client_params: Any            # pytree, leaves [λ, ...]
    client_ts: jnp.ndarray        # [λ] int32 — timestamp of each client's copy
    grad_cache: Optional[Any]     # pytree [λ, ...] or None (cache drop policy)
    rr_pos: jnp.ndarray           # int32, round-robin cursor
    counters: Counters
    # per-tensor fetch mode (§5 extension): [λ, n_leaves] int32 — the
    # timestamp at which each TENSOR of each client's copy last synchronized
    # (maintained by both apply modes; per-leaf τ in serial AND fused).
    client_leaf_ts: Optional[jnp.ndarray] = None
    # bounded server ingress queue (queue_capacity > 0; core/queue.py) —
    # server-side state, replicated like the server itself.
    queue: Optional[qlib.QueueState] = None
    # modeled arrival-process state (SimConfig.scenario; core/scenarios.py)
    # — tiny [λ] arrays, replicated like the server under shard_fleet.
    scenario: Optional[scen.ScenarioState] = None


def _queue_uses_cotangent(config: SimConfig) -> bool:
    """True iff the queued fused path defers grads to a drain-time vjp."""
    return (config.apply_mode == "fused"
            and (config.fused_mode == "cotangent"
                 or (config.fused_mode == "auto"
                     and config.cotangent_eligible())))


def _queue_payload_example(config: SimConfig, params):
    """Single-event payload pytree the ingress queue stores per slot.

    Materialized modes queue the gradient + its arrival loss (+ the stale
    copy for gap-aware rules); the cotangent fused path instead queues the
    stale copy + minibatch indices and defers the forward/backward to drain
    time (the [K, P] gradient batch is never materialized, queued or not).
    """
    if _queue_uses_cotangent(config):
        return {"copy": params,
                "idx": jnp.zeros((config.batch_size,), jnp.int32)}
    payload = {"grad": params, "loss": jnp.zeros((), jnp.float32)}
    if server_rules.get_rule(config.server.rule).needs_client_params:
        payload["copy"] = params
    return payload


def init_sim(config: SimConfig, params) -> SimState:
    """Fresh `SimState`: server at T = 0, λ identical client copies, and
    whatever optional carry the config asks for (gradient cache, per-tensor
    timestamps, ingress queue, scenario arrival state)."""
    lam = config.num_clients
    server = server_rules.init(config.server, params)
    use_cache = config.bandwidth.c_push > 0 and config.bandwidth.drop_policy == "cache"
    return SimState(
        server=server,
        client_params=tree_stack(params, lam),
        client_ts=jnp.zeros((lam,), jnp.int32),
        grad_cache=jax.tree.map(jnp.zeros_like, tree_stack(params, lam))
        if use_cache
        else None,
        rr_pos=jnp.zeros((), jnp.int32),
        counters=engine.init_counters(),
        client_leaf_ts=(jnp.zeros((lam, len(jax.tree.leaves(params))), jnp.int32)
                        if config.bandwidth.per_tensor_fetch else None),
        queue=(qlib.init_queue(
            config.queue_capacity, _queue_payload_example(config, params),
            n_leaves=(len(jax.tree.leaves(params))
                      if config.bandwidth.per_tensor_fetch else 0),
            mask_like=(params if config.bandwidth.per_tensor_push else None),
            track_wall=config.scenario is not None)
            if config.queue_capacity else None),
        scenario=(scen.init_scenario(config.scenario, lam)
                  if config.scenario is not None else None),
    )


def shard_fleet(state: SimState, mesh, client_axis: str = "clients") -> SimState:
    """Shard every [λ, ...] fleet array over `mesh[client_axis]`; the server
    state stays replicated.  The mesh axis size must divide λ (and must
    divide `events_per_step` for the shard_map'd event batch)."""
    from jax.sharding import NamedSharding, PartitionSpec

    def put(tree):
        if tree is None:
            return None
        return jax.tree.map(
            lambda l: jax.device_put(
                l, NamedSharding(mesh, PartitionSpec(client_axis))), tree)

    return state._replace(
        client_params=put(state.client_params),
        client_ts=put(state.client_ts),
        grad_cache=put(state.grad_cache),
        client_leaf_ts=put(state.client_leaf_ts),
    )


def _het_logits(config: SimConfig):
    """Fixed per-client speed logits, drawn once from the config seed (hoisted
    out of the traced step — the draw used to re-trace every step)."""
    if config.dispatcher != "heterogeneous":
        return None
    speed_key = jax.random.PRNGKey(config.seed ^ 0x5EED)
    return config.het_skew * jax.random.normal(speed_key, (config.num_clients,))


def _dispatch(config: SimConfig, rr_pos, key, het_logits):
    lam = config.num_clients
    if config.dispatcher == "roundrobin":
        return rr_pos % lam
    if config.dispatcher == "uniform":
        return jax.random.randint(key, (), 0, lam)
    # heterogeneous: faster clients are picked proportionally more often (so
    # slow clients accumulate more staleness, the paper's "heterogeneous
    # cluster" regime).
    return jax.random.categorical(key, het_logits)


def _build_queue_step(config: SimConfig, loss_fn, data_x, data_y, K,
                      batched_loss_fn=None, mesh=None):
    """step(state, keys) for the queued protocol: one drain window per call.

    K arrivals (dispatch → stale-copy gradient → eq.-9 push gate →
    admission into the ring), one drain (the drained batch goes through the
    configured engine apply path), then all K arriving clients run their
    fetch gates against the post-drain server.  Serial arrivals compute
    each gradient with the scalar `grad_fn` inside a `lax.scan` so the
    ``queue_capacity=1`` / ``drain_all`` trajectory is bitwise the
    immediate-apply serial path; fused arrivals vmap the gradients through
    `dedup_events` representatives exactly like the unqueued fused step.
    """
    grad_fn = jax.value_and_grad(loss_fn)
    bw = config.bandwidth
    scfg = config.server
    lam = config.num_clients
    het_logits = _het_logits(config)
    rule = server_rules.get_rule(scfg.rule)
    use_cotangent = _queue_uses_cotangent(config)
    batched_losses = (
        engine.resolve_event_batched_loss(loss_fn, batched_loss_fn)
        if use_cotangent else None)
    vgrad = jax.named_scope("client_grad")(jax.vmap(grad_fn))
    scn = config.scenario
    scn_scales = scen.client_scales(scn, lam) if scn is not None else None

    @jax.named_scope("dispatch")
    def step(state: SimState, keys):
        ks = jax.vmap(lambda k: jax.random.split(k, 4))(keys)    # [K, 4, ...]
        k_disp, k_batch = ks[:, 0], ks[:, 1]
        k_push, k_fetch = ks[:, 2], ks[:, 3]
        model_bytes = tree_bytes(state.server.params)

        # --- dispatch K arrival events (a scenario replaces the dispatcher:
        # arrival order and finish times come from the modeled service race,
        # so the ingress queue sees realistic hotspot/straggler load) ---
        scn_state, t_fin = state.scenario, None
        if scn is not None:
            scn_state, active, n_drop, n_rejoin = scen.window_prologue(
                scn, lam, state.scenario, scn_scales)
            scn_state, cs, t_fin = scen.async_window(
                scn, lam, scn_state, scn_scales, active, K)
        elif config.dispatcher == "roundrobin":
            cs = (state.rr_pos + jnp.arange(K)) % lam
        elif config.dispatcher == "uniform":
            cs = jax.vmap(lambda k: jax.random.randint(k, (), 0, lam))(k_disp)
        else:
            cs = jax.vmap(
                lambda k: jax.random.categorical(k, het_logits))(k_disp)
        idx = jax.vmap(
            lambda k: jax.random.randint(
                k, (config.batch_size,), 0, data_x.shape[0]))(k_batch)

        # --- push gates at arrival (pre-window server state); scalar draws
        # per event (vmap) so the K=1 stream is bitwise the serial path ---
        if bw.per_tensor_push:
            push = jax.vmap(lambda k: engine.per_tensor_gate(
                k, state.server, bw.c_push, bw.eps)[0])(k_push)  # leaves [K]
            push_event = engine.any_leaf(push)                   # [K]
        else:
            push = push_event = jax.vmap(lambda k: engine.transmit_gate(
                k, state.server, bw.c_push, bw.eps))(k_push)     # [K]

        # stale-copy timestamps double as the dedup grouping key
        dedup_key = (state.client_leaf_ts[cs] if bw.per_tensor_fetch
                     else state.client_ts[cs])

        # --- arrival-side gradient work → queue payload ---
        if use_cotangent:
            # queue the stale copies + minibatch indices; the forward and
            # the cotangent backward both run at drain time
            rep, _, _ = engine.dedup_events(dedup_key)
            payload = {"copy": tree_index(state.client_params, cs[rep]),
                       "idx": idx}
        elif config.apply_mode == "fused":
            rep, _, _ = engine.dedup_events(dedup_key)
            p_e = tree_index(state.client_params, cs[rep])       # [K, ...]
            losses, grads = vgrad(p_e, data_x[idx], data_y[idx])
            payload = {"grad": grads, "loss": losses}
            if rule.needs_client_params:
                payload["copy"] = p_e
        else:
            # serial arrivals: scalar grad_fn per event (bitwise-faithful)
            def one_arrival(carry, inp):
                c, rows = inp
                p_c = tree_index(state.client_params, c)
                loss, g = grad_fn(p_c, data_x[rows], data_y[rows])
                out = {"grad": g, "loss": loss}
                if rule.needs_client_params:
                    out["copy"] = p_c
                return carry, out
            _, payload = jax.lax.scan(one_arrival, 0, (cs, idx))

        # --- admission ---
        arrivals = qlib.Arrivals(
            payload=payload, ts=state.client_ts[cs], client=cs,
            valid=push_event,
            leaf_ts=(dedup_key if bw.per_tensor_fetch else None),
            leaf_mask=(push if bw.per_tensor_push else None),
            wall=t_fin)
        queue, admitted, n_rejected, n_dropped = qlib.enqueue(
            state.queue, arrivals, config.admission_policy,
            state.server.timestamp)
        depth_peak = queue.size
        # bytes: only admitted pushes crossed the wire — a rejected push is
        # refused at admission, before transmission (never counted as sent)
        if bw.per_tensor_push:
            push_sent = masked_bytes(
                jax.tree.map(lambda m: m & admitted, push),
                state.server.params)
        else:
            push_sent = jnp.sum(admitted.astype(jnp.float32)) * model_bytes

        # --- drain: apply the k_eff oldest queued events in one pass ---
        k_eff = qlib.drain_count(
            queue.size, config.drain_policy,
            drain_k=config.drain_k, gain=config.drain_adaptive_gain)
        queue, batch = qlib.dequeue(queue, k_eff)
        latency_sum = jnp.sum(jnp.where(
            batch.valid,
            (state.server.timestamp - batch.enq_T).astype(jnp.float32), 0.0))
        latency_wall_sum = (
            jnp.sum(jnp.where(batch.valid,
                              scn_state.now - batch.enq_wall, 0.0))
            if scn is not None else None)

        if bw.per_tensor_fetch:
            treedef = jax.tree.structure(state.server.params)
            grad_ts = jax.tree.unflatten(
                treedef, [batch.leaf_ts[:, i]
                          for i in range(batch.leaf_ts.shape[1])])
        else:
            grad_ts = batch.ts
        push_arg = qlib.drained_push_arg(batch, bw.per_tensor_push)
        cp = batch.payload.get("copy") if rule.needs_client_params else None

        if use_cotangent:
            xb, yb = data_x[batch.payload["idx"]], data_y[batch.payload["idx"]]
            new_server, taus, dlosses = engine.fused_apply_cotangent(
                scfg, state.server,
                lambda W, deltas: batched_losses(W, deltas, xb, yb),
                batch.payload["copy"], push_arg, grad_ts)
        elif config.apply_mode == "fused":
            new_server, taus = engine.fused_apply(
                scfg, state.server, batch.payload["grad"], push_arg, grad_ts,
                client_params=cp, mesh=mesh, server_axis=config.server_axis)
            dlosses = batch.payload["loss"]
        else:
            new_server, taus = engine.serial_apply(
                scfg, state.server, batch.payload["grad"], push_arg, grad_ts,
                cp)
            dlosses = batch.payload["loss"]

        # --- fetch gates: the K arriving clients sync against the
        # post-drain server (scalar draws per event, like the push side) ---
        if bw.per_tensor_fetch:
            fmask = jax.vmap(lambda k: engine.per_tensor_gate(
                k, new_server, bw.c_fetch, bw.eps)[0])(k_fetch)  # leaves [K]
            fetch = jnp.stack(jax.tree.leaves(fmask)).all(axis=0)  # [K]
            fetch_sent = masked_bytes(fmask, new_server.params)

            def fetch_leaf(m, cl, sp):
                i = jnp.where(m, cs, lam)            # dropped when ¬fetched
                return cl.at[i].set(
                    jnp.broadcast_to(sp[None], (K,) + sp.shape), mode="drop")
            client_params = jax.tree.map(
                fetch_leaf, fmask, state.client_params, new_server.params)
            leaf_cols = []
            for i, m in enumerate(jax.tree.leaves(fmask)):
                rows = jnp.where(m, cs, lam)
                leaf_cols.append(
                    state.client_leaf_ts[:, i].at[rows].set(
                        jnp.broadcast_to(new_server.timestamp, (K,)),
                        mode="drop"))
            client_leaf_ts = jnp.stack(leaf_cols, axis=1)
        else:
            fetch = jax.vmap(lambda k: engine.transmit_gate(
                k, new_server, bw.c_fetch, bw.eps))(k_fetch)     # [K]
            fetch_sent = jnp.sum(fetch.astype(jnp.float32)) * model_bytes
            fidx = jnp.where(fetch, cs, lam)           # dropped when ¬fetch
            client_params = jax.tree.map(
                lambda cl, sp: cl.at[fidx].set(
                    jnp.broadcast_to(sp[None], (K,) + sp.shape), mode="drop"),
                state.client_params, new_server.params)
            client_leaf_ts = state.client_leaf_ts
        fetch_idx = jnp.where(fetch, cs, lam)
        client_ts = state.client_ts.at[fetch_idx].set(
            jnp.broadcast_to(new_server.timestamp, (K,)), mode="drop")

        counters = engine.count_events(
            state.counters, admitted, fetch,
            push_bytes_sent=push_sent, push_bytes_total=K * model_bytes,
            fetch_bytes_sent=fetch_sent, fetch_bytes_total=K * model_bytes)
        counters = qlib.count_queue(
            counters,
            enqueued=jnp.sum(admitted.astype(jnp.int32)),
            rejected=n_rejected, dropped=n_dropped, drained=k_eff,
            depth_post=queue.size, depth_peak=depth_peak,
            latency_sum=latency_sum, latency_wall_sum=latency_wall_sum)
        # kernel-path telemetry: the drained window feeds the one-kernel
        # apply directly (one launch per leaf consumes k_eff real events);
        # the serial drain launches the per-event Pallas op capacity times.
        n_leaves = len(jax.tree.leaves(state.server.params))
        if (config.apply_mode == "fused" and not use_cotangent
                and engine.fused_kernel_active(scfg)):
            counters = engine.count_kernel(counters, n_leaves, k_eff)
        elif (config.apply_mode == "serial"
              and engine.serial_kernel_active(scfg, bw.per_tensor_fetch)):
            counters = engine.count_kernel(
                counters, batch.valid.shape[0] * n_leaves, k_eff)
        if config.server_shards > 1:
            # one drain window = one apply against the partitioned server;
            # every shard consumes the same k_eff-event drained batch (its
            # own blocks of it), so the per-shard depth is k_eff
            counters = server_shard.count_shard(
                counters, applies=1, events=k_eff,
                bytes_peak=server_shard.peak_shard_bytes(
                    state.server, config.server_shards, config.server_axis),
                depth_peak=k_eff)
        if scn is not None:
            counters = scen.count_scenario(
                counters, now=scn_state.now,
                active_count=jnp.sum(active.astype(jnp.float32)),
                dropouts=n_drop, rejoins=n_rejoin)

        new_state = SimState(
            server=new_server,
            client_params=client_params,
            client_ts=client_ts,
            grad_cache=None,       # 'cache' drop policy rejected with a queue
            rr_pos=state.rr_pos + K,
            counters=counters,
            client_leaf_ts=client_leaf_ts,
            queue=queue,
            scenario=scn_state,
        )
        validf = batch.valid.astype(jnp.float32)
        nz = jnp.maximum(k_eff, 1).astype(jnp.float32)
        metrics = {
            # per-window scalars: means over the drained (not arriving) events
            "loss": jnp.sum(validf * dlosses) / nz,
            "tau": jnp.sum(validf * taus) / nz,
            "client": cs,
            "pushed": push_event,
            "fetched": fetch,
            "queue_depth": queue.size,                 # post-drain backlog
            "drained": k_eff,
            "admitted": jnp.sum(admitted.astype(jnp.int32)),
            "rejected": n_rejected,
            "dropped": n_dropped,
        }
        if t_fin is not None:
            metrics["wall"] = t_fin                    # per-arrival wall time
        return new_state, metrics

    return step


def build_step_fn(
    config: SimConfig,
    loss_fn: Callable,          # loss_fn(params, xb, yb) -> scalar
    data_x,
    data_y,
    events: Optional[int] = None,   # override config.events_per_step
    mesh=None,                      # optional: shard_map grads over the
    client_axis: str = "clients",   # event axis of this mesh axis
    batched_loss_fn: Callable = None,   # event-batched loss for the
                                        # cotangent fused path (see below)
):
    """Returns step(state, keys) -> (state, metrics) for lax.scan.

    `keys` carries one PRNG key per event, shape [K, ...]; metrics leaves
    are per-event [K] arrays.  Keys must be derived from the *global* event
    index (see `run_simulation`) so serial trajectories are K-invariant.

    `batched_loss_fn(W, deltas, xb, yb) -> [K]` optionally supplies the
    shared/delta event-batched loss the cotangent fused path contracts over
    (falls back to `loss_fn.event_batched`, then to the generic
    `engine.event_batched_losses` wrapper — see
    `engine.resolve_event_batched_loss`).
    """
    grad_fn = jax.value_and_grad(loss_fn)
    bw = config.bandwidth
    scfg = config.server
    lam = config.num_clients
    K = events if events is not None else config.events_per_step
    het_logits = _het_logits(config)
    rule = server_rules.get_rule(scfg.rule)
    scn = config.scenario
    scn_scales = scen.client_scales(scn, lam) if scn is not None else None
    if scn is not None and rule.synchronous and K != lam:
        raise ValueError(
            f"synchronous scenario rounds advance exactly λ={lam} events "
            f"per step, got a {K}-event window: num_steps and eval_every "
            f"must be multiples of num_clients")

    # A mesh only drives the shard_map'd gradient batch when it actually
    # carries the client axis; a server-only mesh (server sharding,
    # core/server_shard.py) flows through jit's partitioner instead and
    # composes with every path below, the ingress queue included.  The
    # unsupported-combination checks key on the axis *name* (a size-1
    # client axis still states intent), the shard_map wrap on size > 1.
    names_client_axis = (mesh is not None
                         and client_axis in getattr(mesh, "axis_names", ()))
    client_mesh = (mesh if names_client_axis
                   and int(mesh.shape[client_axis]) > 1 else None)

    if config.queue_capacity:
        if names_client_axis:
            raise ValueError(
                "queue_capacity > 0 does not support a client-axis mesh: "
                "the ring buffer is replicated server state and the "
                "shard_map'd arrival gradients are not wired through it "
                "yet — run the queued simulation unsharded")
        return _build_queue_step(
            config, loss_fn, data_x, data_y, K,
            batched_loss_fn=batched_loss_fn, mesh=mesh)

    @jax.named_scope("dispatch")
    def event_body(state: SimState, inp):
        """One client event — the paper's protocol, verbatim.

        `inp` is the event's PRNG key; under a scenario it is ``(key, c)``
        with the firing client precomputed by the arrival process (the
        dispatch key is split but unused, so the per-event batch/gate
        streams are position-independent either way).
        """
        if scn is None:
            key = inp
        else:
            key, c = inp
        k_disp, k_batch, k_push, k_fetch = jax.random.split(key, 4)
        if scn is None:
            c = _dispatch(config, state.rr_pos, k_disp, het_logits)
        model_bytes = tree_bytes(state.server.params)

        # --- client computes a stochastic gradient on its (stale) params ---
        with jax.named_scope("minibatch"):
            idx = jax.random.randint(k_batch, (config.batch_size,), 0, data_x.shape[0])
            xb, yb = data_x[idx], data_y[idx]
        with jax.named_scope("stale_gather"):
            p_c = tree_index(state.client_params, c)
        with jax.named_scope("client_grad"):
            loss, g = grad_fn(p_c, xb, yb)

        # --- push gate (B-FASGD eq. 9; per-leaf in per-tensor mode) ---
        if bw.per_tensor_push:
            # §5 extension, push side: each gradient tensor transmits
            # independently, gated by its own v̄ moving average.
            push, push_sent, push_total = engine.per_tensor_gate(
                k_push, state.server, bw.c_push, bw.eps)
            push_event = engine.any_leaf(push)
        else:
            push = push_event = engine.transmit_gate(
                k_push, state.server, bw.c_push, bw.eps)
            push_sent = push.astype(jnp.float32) * model_bytes
            push_total = model_bytes

        if bw.per_tensor_fetch:
            # per-tensor timestamps → per-leaf staleness in the update rule
            leaf_ts = state.client_leaf_ts[c]                   # [n_leaves]
            treedef = jax.tree.structure(state.server.params)
            grad_ts = jax.tree.unflatten(
                treedef, [leaf_ts[i] for i in range(leaf_ts.shape[0])])
        else:
            grad_ts = state.client_ts[c]

        # --- gated server application (engine: cache / skip drop policy) ---
        cached = (tree_index(state.grad_cache, c)
                  if state.grad_cache is not None else None)
        new_server, aux = engine.apply_gated(
            scfg, state.server, g, push, grad_ts,
            client_params=p_c, cached_grad=cached)
        grad_cache = state.grad_cache
        if grad_cache is not None:
            if bw.per_tensor_push:
                # per-leaf cache: a leaf only becomes "most recent
                # transmitted" if that leaf actually crossed the wire
                grad_cache = jax.tree.map(
                    lambda cache, gv, m: cache.at[c].set(
                        jnp.where(m, gv, cache[c])),
                    grad_cache, g, push)
            else:
                grad_cache = jax.tree.map(
                    lambda cache, gv: cache.at[c].set(
                        jnp.where(push, gv, cache[c])),
                    grad_cache, g)

        # --- fetch gate ---
        if bw.per_tensor_fetch:
            # paper §5 extension: each tensor synchronizes independently,
            # gated by its own gradient-std statistics.
            mask, fetch_sent, fetch_total = engine.per_tensor_gate(
                k_fetch, new_server, bw.c_fetch, bw.eps)
            new_p_c = jax.tree.map(
                lambda m, sp, cp: jnp.where(m, sp, cp),
                mask, new_server.params, p_c)
            fetch = jnp.stack(jax.tree.leaves(mask)).all()
            leaf_mask = jnp.stack(jax.tree.leaves(mask))        # [n_leaves]
            new_leaf_ts = jnp.where(
                leaf_mask, new_server.timestamp, state.client_leaf_ts[c])
            client_leaf_ts = state.client_leaf_ts.at[c].set(new_leaf_ts)
        else:
            fetch = engine.transmit_gate(k_fetch, new_server, bw.c_fetch, bw.eps)
            fetch_sent = fetch.astype(jnp.float32) * model_bytes
            fetch_total = model_bytes
            client_leaf_ts = state.client_leaf_ts
            new_p_c = tree_where(fetch, new_server.params, p_c)
        with jax.named_scope("fetch_scatter"):
            client_params = tree_set(state.client_params, c, new_p_c)
            client_ts = state.client_ts.at[c].set(
                jnp.where(fetch, new_server.timestamp, state.client_ts[c])
            )

        if server_rules.get_rule(scfg.rule).synchronous:
            # when a sync round completes, *every* client receives the new
            # parameters (the paper's `unblock`).
            applied = aux["applied"]
            client_params = jax.tree.map(
                lambda all_p, sp: jnp.where(applied, jnp.broadcast_to(sp, all_p.shape), all_p),
                client_params,
                new_server.params,
            )
            client_ts = jnp.where(applied, new_server.timestamp, client_ts)

        counters = engine.count_events(
            state.counters, push_event, fetch,
            push_bytes_sent=push_sent, push_bytes_total=push_total,
            fetch_bytes_sent=fetch_sent, fetch_bytes_total=fetch_total)
        if engine.serial_kernel_active(scfg, bw.per_tensor_fetch):
            # each event stages one per-leaf launch of the rule's Pallas op
            counters = engine.count_kernel(
                counters, len(jax.tree.leaves(state.server.params)), 1)
        if config.server_shards > 1:
            # serial lock order: every event is its own one-event apply
            # window against the partitioned server
            counters = server_shard.count_shard(
                counters, applies=1, events=1,
                bytes_peak=server_shard.peak_shard_bytes(
                    state.server, config.server_shards, config.server_axis),
                depth_peak=1)

        new_state = SimState(
            server=new_server,
            client_params=client_params,
            client_ts=client_ts,
            grad_cache=grad_cache,
            rr_pos=state.rr_pos + 1,
            counters=counters,
            client_leaf_ts=client_leaf_ts,
            queue=state.queue,
            scenario=state.scenario,
        )
        metrics = {
            "loss": loss,
            "tau": aux["tau"],
            "client": c,
            "pushed": push_event,
            "fetched": fetch,
        }
        return new_state, metrics

    if config.apply_mode == "serial":
        if scn is None:
            def step(state: SimState, keys):
                return jax.lax.scan(event_body, state, keys)
            return step

        sync_k = rule.barrier_k(scfg) if rule.synchronous else None

        def step(state: SimState, keys):
            # window prologue: elastic activation + churn, then the modeled
            # arrival order — a sorted λ-round for barrier rules, a K-event
            # discrete-event race otherwise (core/scenarios.py).
            scn_state, active, n_drop, n_rejoin = scen.window_prologue(
                scn, lam, state.scenario, scn_scales)
            if rule.synchronous:
                scn_state, cs, t_fin = scen.sync_round(
                    scn, lam, scn_state, scn_scales, sync_k)
            else:
                scn_state, cs, t_fin = scen.async_window(
                    scn, lam, scn_state, scn_scales, active, K)
            counters = scen.count_scenario(
                state.counters, now=scn_state.now,
                active_count=jnp.sum(active.astype(jnp.float32)),
                dropouts=n_drop, rejoins=n_rejoin)
            state = state._replace(scenario=scn_state, counters=counters)
            state, metrics = jax.lax.scan(event_body, state, (keys, cs))
            metrics["wall"] = t_fin
            return state, metrics
        return step

    # ----- fused: all K events advance in one batched protocol round -----
    use_cotangent = (config.fused_mode == "cotangent"
                     or (config.fused_mode == "auto"
                         and config.cotangent_eligible()))
    if use_cotangent and names_client_axis:
        if config.fused_mode == "cotangent":
            raise ValueError(
                "fused_mode='cotangent' does not support a client-axis mesh "
                "(shard_map wraps the materialized per-event gradients)")
        use_cotangent = client_mesh is None
    batched_losses = (
        engine.resolve_event_batched_loss(loss_fn, batched_loss_fn)
        if use_cotangent else None)
    vgrad = jax.vmap(grad_fn)
    if client_mesh is not None:
        from jax.sharding import PartitionSpec
        spec = PartitionSpec(client_axis)
        vgrad = jax.shard_map(
            jax.vmap(grad_fn), mesh=client_mesh,
            in_specs=(spec, spec, spec), out_specs=(spec, spec),
            check_vma=False)
    vgrad = jax.named_scope("client_grad")(vgrad)

    @jax.named_scope("dispatch")
    def step(state: SimState, keys):
        ks = jax.vmap(lambda k: jax.random.split(k, 4))(keys)    # [K, 4, ...]
        k_disp, k_batch = ks[:, 0], ks[:, 1]
        k_push, k_fetch = ks[:, 2], ks[:, 3]
        model_bytes = tree_bytes(state.server.params)

        # --- dispatch K events (λ-vectorized; a scenario replaces the
        # dispatcher with the modeled service race — the scenario state is
        # replicated, so the shard_map'd gradient batch is untouched) ---
        scn_state, t_fin = state.scenario, None
        if scn is not None:
            scn_state, active, n_drop, n_rejoin = scen.window_prologue(
                scn, lam, state.scenario, scn_scales)
            scn_state, cs, t_fin = scen.async_window(
                scn, lam, scn_state, scn_scales, active, K)
        elif config.dispatcher == "roundrobin":
            cs = (state.rr_pos + jnp.arange(K)) % lam
        elif config.dispatcher == "uniform":
            cs = jax.vmap(lambda k: jax.random.randint(k, (), 0, lam))(k_disp)
        else:
            cs = jax.vmap(
                lambda k: jax.random.categorical(k, het_logits))(k_disp)

        # --- per-event minibatch draws ---
        with jax.named_scope("minibatch"):
            idx = jax.vmap(
                lambda k: jax.random.randint(
                    k, (config.batch_size,), 0, data_x.shape[0]))(k_batch)
            xb, yb = data_x[idx], data_y[idx]                    # [K, μ, ...]

        # --- event dedup: clients that fetched at the same T hold bitwise-
        # identical copies, so the stale-parameter batch is gathered through
        # group representatives (engine.dedup_events; a no-op permutation of
        # identical values when every timestamp is distinct).  Under
        # per-tensor fetch the group key is the client_leaf_ts row (all
        # tensors must match for two copies to be identical).
        dedup_key = (state.client_leaf_ts[cs] if bw.per_tensor_fetch
                     else state.client_ts[cs])
        rep, _, _ = engine.dedup_events(dedup_key)
        with jax.named_scope("stale_gather"):
            p_e = tree_index(state.client_params, cs[rep])       # [K, ...]

        # --- push gates (pre-window server state, like the serial path) ---
        if bw.per_tensor_push:
            # per-event keys (vmap) so the K=1 draws match serial bitwise
            push = jax.vmap(lambda k: engine.per_tensor_gate(
                k, state.server, bw.c_push, bw.eps)[0])(k_push)  # leaves [K]
            push_event = engine.any_leaf(push)                   # [K]
            push_sent = masked_bytes(push, state.server.params)
        else:
            push = push_event = engine.transmit_gate(
                k_push[0], state.server, bw.c_push, bw.eps, shape=(K,))
            push_sent = jnp.sum(push.astype(jnp.float32)) * model_bytes
        push_total = K * model_bytes

        if bw.per_tensor_fetch:
            # per-tensor staleness: each tensor's τ measured from its own
            # last synchronization (client_leaf_ts lifted into fused mode)
            leaf_ts = dedup_key                              # [K, n_leaves]
            treedef = jax.tree.structure(state.server.params)
            grad_ts = jax.tree.unflatten(
                treedef, [leaf_ts[:, i] for i in range(leaf_ts.shape[1])])
        else:
            grad_ts = dedup_key                                  # [K]

        if use_cotangent:
            # cotangent path: Σ_k w_k·g_k and the stats mean gradient are
            # two pullbacks of the batched forward — the [K, P] per-event
            # gradient batch is never materialized.  Eligibility (checked
            # statically above) rules out the gradient cache, per-tensor
            # gating, and gap rules.
            new_server, taus, losses = engine.fused_apply_cotangent(
                scfg, state.server,
                lambda W, deltas: batched_losses(W, deltas, xb, yb),
                p_e, push, grad_ts)
            grad_cache = state.grad_cache
        elif state.grad_cache is not None:
            # cache policy: every opportunity applies *some* gradient (per
            # leaf, in per-tensor mode), so the fused mask is all-ones over
            # the effective gradients.
            losses, grads = vgrad(p_e, xb, yb)
            cache_e = tree_index(state.grad_cache, cs)
            g_eff = (engine.tree_select_axis(push, grads, cache_e)
                     if bw.per_tensor_push
                     else tree_where_axis(push, grads, cache_e))
            new_server, taus = engine.fused_apply(
                scfg, state.server, g_eff, jnp.ones((K,), bool), grad_ts,
                client_params=p_e, mesh=mesh, server_axis=config.server_axis)
            grad_cache = engine.last_event_scatter(
                state.grad_cache, cs, grads, push, lam)
        else:
            losses, grads = vgrad(p_e, xb, yb)
            new_server, taus = engine.fused_apply(
                scfg, state.server, grads, push, grad_ts,
                client_params=p_e, mesh=mesh, server_axis=config.server_axis)
            grad_cache = None

        # --- fetch gates (post-apply server state) ---
        # Every fetch delivers the same canonical parameters, so duplicate
        # clients in the batch all write identical rows — the scatters are
        # deterministic and touch K rows, never the full λ fleet.
        with jax.named_scope("fetch_scatter"):
            if bw.per_tensor_fetch:
                fmask = jax.vmap(lambda k: engine.per_tensor_gate(
                    k, new_server, bw.c_fetch, bw.eps)[0])(k_fetch)  # leaves [K]
                fetch = jnp.stack(jax.tree.leaves(fmask)).all(axis=0)  # [K]
                fetch_sent = masked_bytes(fmask, new_server.params)

                def fetch_leaf(m, cp, sp):
                    i = jnp.where(m, cs, lam)            # dropped when ¬fetched
                    return cp.at[i].set(
                        jnp.broadcast_to(sp[None], (K,) + sp.shape), mode="drop")
                client_params = jax.tree.map(
                    fetch_leaf, fmask, state.client_params, new_server.params)
                leaf_cols = []
                for i, m in enumerate(jax.tree.leaves(fmask)):
                    rows = jnp.where(m, cs, lam)
                    leaf_cols.append(
                        state.client_leaf_ts[:, i].at[rows].set(
                            jnp.broadcast_to(new_server.timestamp, (K,)),
                            mode="drop"))
                client_leaf_ts = jnp.stack(leaf_cols, axis=1)
            else:
                fetch = engine.transmit_gate(
                    k_fetch[0], new_server, bw.c_fetch, bw.eps, shape=(K,))
                fetch_sent = jnp.sum(fetch.astype(jnp.float32)) * model_bytes
                idx = jnp.where(fetch, cs, lam)            # dropped when ¬fetch
                client_params = jax.tree.map(
                    lambda cp, sp: cp.at[idx].set(
                        jnp.broadcast_to(sp[None], (K,) + sp.shape), mode="drop"),
                    state.client_params, new_server.params)
                client_leaf_ts = state.client_leaf_ts
            fetch_idx = jnp.where(fetch, cs, lam)
            client_ts = state.client_ts.at[fetch_idx].set(
                jnp.broadcast_to(new_server.timestamp, (K,)), mode="drop")

        counters = engine.count_events(
            state.counters, push_event, fetch,
            push_bytes_sent=push_sent, push_bytes_total=push_total,
            fetch_bytes_sent=fetch_sent, fetch_bytes_total=K * model_bytes)
        if not use_cotangent and engine.fused_kernel_active(scfg):
            # one fused window = one launch per leaf consuming all K events
            counters = engine.count_kernel(
                counters, len(jax.tree.leaves(state.server.params)), K)
        if config.server_shards > 1:
            # one fused window = one apply against the partitioned server,
            # every shard consuming its blocks of all K events
            counters = server_shard.count_shard(
                counters, applies=1, events=K,
                bytes_peak=server_shard.peak_shard_bytes(
                    state.server, config.server_shards, config.server_axis),
                depth_peak=K)
        if scn is not None:
            counters = scen.count_scenario(
                counters, now=scn_state.now,
                active_count=jnp.sum(active.astype(jnp.float32)),
                dropouts=n_drop, rejoins=n_rejoin)

        new_state = SimState(
            server=new_server,
            client_params=client_params,
            client_ts=client_ts,
            grad_cache=grad_cache,
            rr_pos=state.rr_pos + K,
            counters=counters,
            client_leaf_ts=client_leaf_ts,
            queue=state.queue,
            scenario=scn_state,
        )
        metrics = {
            "loss": losses,
            "tau": taus,
            "client": cs,
            "pushed": push_event,
            "fetched": fetch,
        }
        if t_fin is not None:
            metrics["wall"] = t_fin
        return new_state, metrics

    return step


def run_simulation(
    config: SimConfig,
    loss_fn: Callable,
    init_params,
    data_x,
    data_y,
    num_steps: int,
    eval_every: int = 500,
    eval_fn: Optional[Callable] = None,   # eval_fn(server_params) -> scalar cost
    collect_step_metrics: bool = False,
    mesh=None,                            # optional mesh: client-axis
    client_axis: str = "clients",         # shard_map and/or server partition
    batched_loss_fn=None,                 # cotangent-path event-batched loss
):
    """Run the deterministic simulation; returns a results dict.

    `num_steps` counts client *events* and is honored exactly — with
    `events_per_step = K` each scan step advances K events and a shorter
    final batch covers any remainder.  Validation cost is measured on the
    *server* parameters every `eval_every` events, exactly like the paper's
    figures.

    `mesh` may carry a `client_axis` (the [λ, ...] fleet arrays shard and
    the fused gradient batch shard_maps over it), a
    ``config.server_axis`` (the server state block-partitions over it when
    ``config.server_shards > 1``, `core/server_shard.py`), or both.  A
    `jax.distributed` multi-process mesh works the same way: every process
    calls `run_simulation` with the same global mesh — simulate one with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (recipe in
    docs/SHARDING.md).
    """
    state = init_sim(config, init_params)
    if mesh is not None and client_axis in getattr(mesh, "axis_names", ()):
        state = shard_fleet(state, mesh, client_axis)
    if config.server_shards > 1:
        server_shard.validate_server_mesh(
            mesh, config.server_shards, config.server_axis)
        state = state._replace(
            server=server_shard.shard_server_state(
                state.server, mesh, config.server_axis),
            queue=server_shard.shard_queue_state(
                state.queue, mesh, config.server_axis))
    K = config.events_per_step
    base = jax.random.PRNGKey(config.seed)
    data_x, data_y = jnp.asarray(data_x), jnp.asarray(data_y)

    # the dataset is an argument, not a closed-over constant: a constant
    # would be baked into the executable (and every persistent-cache entry)
    @functools.partial(jax.jit, static_argnames=("n_batches", "k_events"))
    def run_span(state, start_event, data_x, data_y, n_batches, k_events):
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
            start_event + jnp.arange(n_batches * k_events))
        keys = keys.reshape((n_batches, k_events) + keys.shape[1:])
        step = build_step_fn(config, loss_fn, data_x, data_y,
                             events=k_events, mesh=mesh,
                             client_axis=client_axis,
                             batched_loss_fn=batched_loss_fn)
        return jax.lax.scan(step, state, keys)

    eval_jit = jax.jit(eval_fn) if eval_fn is not None else None

    def collect(metrics):
        train_losses.append(metrics["loss"].reshape(-1))
        taus.append(metrics["tau"].reshape(-1))

    curve_steps, curve_cost, curve_wall = [], [], []
    train_losses, taus = [], []
    done = 0
    while done < num_steps:
        span = min(eval_every, num_steps - done)
        n_batches, rem = divmod(span, K)
        if n_batches:
            state, metrics = run_span(state, jnp.int32(done), data_x, data_y,
                                      n_batches, K)
            if collect_step_metrics:
                collect(metrics)
            done += n_batches * K
        if rem:
            state, metrics = run_span(state, jnp.int32(done), data_x, data_y,
                                      1, rem)
            if collect_step_metrics:
                collect(metrics)
            done += rem
        if eval_jit is not None:
            curve_steps.append(done)
            curve_cost.append(float(eval_jit(state.server.params)))
            # error-vs-wall-clock axis: the modeled wall time under a
            # scenario, else the unit event clock (1 event = 1 tick)
            curve_wall.append(
                float(state.counters.wall_clock)
                if config.scenario is not None else float(done))

    counters = jax.tree.map(float, state.counters._asdict())
    if not config.queue_capacity:
        # keep the immediate-apply output schema (and the goldens) stable:
        # the queue telemetry only appears when a queue is configured
        counters = {k: v for k, v in counters.items()
                    if not k.startswith("queue_")}
    if config.scenario is None:
        # same stability contract for the wall-clock/scenario telemetry
        counters = {k: v for k, v in counters.items()
                    if k != "wall_clock" and not k.startswith("scenario_")}
    if not config.server.use_fused_kernel:
        # kernel-path telemetry only appears when the kernel path can run
        counters = {k: v for k, v in counters.items()
                    if not k.startswith("kernel_")}
    if config.server_shards <= 1:
        # partitioned-server telemetry only appears when the server shards
        counters = {k: v for k, v in counters.items()
                    if not k.startswith("shard_")}
    out = {
        "state": state,
        "steps": curve_steps,
        "val_cost": curve_cost,
        "wall_clock": curve_wall,
        "counters": counters,
        "final_timestamp": int(state.server.timestamp),
    }
    if collect_step_metrics:
        out["train_loss"] = jnp.concatenate(train_losses)
        out["tau"] = jnp.concatenate(taus)
    return out
