"""The shared async-SGD protocol core ("the engine").

`sim/fred.py` (the paper's §3 deterministic simulator) and
`core/round_trainer.py` (the SPMD mapping of the same protocol onto pod
hardware) used to each re-implement the push/fetch/apply decision structure.
This module is the single source of protocol truth both now consume:

 - **gates** — the B-FASGD eq. 9 Bernoulli push/fetch draws, batched over an
   arbitrary leading event/client axis (`transmit_gate`);
 - **gated application** — one server update under a push decision with the
   FRED drop policies (`apply_gated`: 'cache' re-applies the client's last
   transmitted gradient, 'skip' masks the whole update);
 - **serial application** — pushed gradients applied one-at-a-time in event
   order via `lax.scan` (`serial_apply`), bit-identical to the paper's lock
   protocol with that arrival order;
 - **fused application** — one masked-sum update θ ← θ − Σ_c m_c·scale(v,τ_c)·g_c
   with a single stats step on the mean pushed gradient (`fused_apply`),
   optionally routed through the one-kernel event loop
   (`kernels/fused_event_apply.py`: stats + delta in a single per-leaf
   Pallas launch) for rules that declare `batched_pallas_mode`;
 - **cotangent fused application** — for rules whose fused coefficients are
   v-independent (`UpdateRule.coeffs_are_v_independent`: asgd/sasgd/exp/poly)
   the weight delta Σ_k w_k·g_k and the stats mean gradient are both vjps of
   the batched forward with per-event cotangent weights
   (`fused_apply_cotangent`) — the [K, P] per-event weight-gradient batch is
   never materialized (docs/ARCHITECTURE.md §"Cotangent fused path");
   `v_separable` rules (fasgd) join via the `reweight_by_v` custom-vjp
   pullback that carries the elementwise v-factor;
 - **event dedup** — clients that fetched at the same T hold bitwise-identical
   stale copies; `dedup_events` groups an event batch by that key so the
   stale-copy gather reads one distinct fleet row per group (a memory-
   locality win under heavy fetch collisions) and each group's summed
   cotangent weight meets its shared copy inside the backward's event-axis
   contraction.  Per-event *data* work is not deduplicated — every event
   keeps its own minibatch, so the grouping is numerically a no-op;
 - **bookkeeping** — push/fetch opportunity `Counters` shared by both paths
   (`init_counters` / `count_events`), and the deterministic last-event-wins
   scatter used when an event batch targets duplicate clients
   (`last_event_scatter`).

Every function is pure over `ServerState`/pytrees so it can live inside
`jax.lax.scan` / `jax.jit` / `shard_map`.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

import functools

from repro.core import rules as server_rules
from repro.core.bandwidth import per_tensor_transmit_mask, transmit_prob
from repro.core.rules import ServerConfig, ServerState


# ---------------------------------------------------------------------------
# pytree helpers shared by both consumers
# ---------------------------------------------------------------------------

def tree_index(tree, i):
    """Gather leaf[i] (i may be an int array — gathers along the leading axis)."""
    return jax.tree.map(lambda l: l[i], tree)


def tree_set(tree, i, val):
    """Scatter `val` leaves into row i of every leaf's leading axis."""
    return jax.tree.map(lambda l, v: l.at[i].set(v), tree, val)


def tree_where(pred, a, b):
    """Scalar-predicate select over matching pytrees."""
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def tree_where_axis(pred, a, b):
    """Per-row select: `pred` is [K] over the leading axis of every leaf."""
    return jax.tree.map(
        lambda x, y: jnp.where(pred.reshape((-1,) + (1,) * (x.ndim - 1)), x, y),
        a, b)


def tree_stack(tree, n):
    """Replicate a pytree along a new leading axis of size n."""
    return jax.tree.map(
        lambda l: jnp.broadcast_to(l, (n,) + l.shape).copy(), tree)


def is_per_leaf(x, like) -> bool:
    """True iff `x` is a pytree of per-leaf values mirroring `like` (as
    opposed to one shared scalar/array for the whole tree)."""
    return jax.tree.structure(x) == jax.tree.structure(like)


def tree_select(mask_tree, a, b):
    """Leaf-aligned select: `mask_tree` mirrors `a`/`b`, leaves broadcast."""
    return jax.tree.map(lambda m, x, y: jnp.where(m, x, y), mask_tree, a, b)


def tree_select_axis(mask_tree, a, b):
    """Per-leaf per-row select: each mask leaf is [K] over the leading axis
    of the matching `a`/`b` leaf."""
    return jax.tree.map(
        lambda m, x, y: jnp.where(
            m.reshape((-1,) + (1,) * (x.ndim - 1)), x, y),
        mask_tree, a, b)


def any_leaf(mask_tree):
    """OR-reduce a per-leaf bool pytree to one shared mask (scalar or [K])."""
    return functools.reduce(jnp.logical_or, jax.tree.leaves(mask_tree))


# ---------------------------------------------------------------------------
# counters — opportunity / transmission bookkeeping (FRED §3, EXPERIMENTS §Perf)
# ---------------------------------------------------------------------------

class Counters(NamedTuple):
    """Push/fetch opportunity accounting shared by FRED and the round trainer.

    Event counts (`*_potential` / `*_actual`) count transmit opportunities;
    byte counters carry the per-leaf resolution: a pushed byte is one byte of
    a gradient tensor that actually reached the server, a fetched byte one
    byte of a canonical parameter tensor that actually reached a client.
    Scalar gating accounts whole-copy bytes; per-tensor gating accounts each
    tensor independently.

    The `queue_*` fields are the ingress-queue telemetry (`core/queue.py`,
    folded in by `queue.count_queue`); they stay zero on the immediate-apply
    path.  `push_actual`/`push_bytes_sent` count *admitted* pushes only —
    a push the admission policy rejects is refused before transmission and
    must never be double-counted as sent bytes.

    The `wall_clock` / `scenario_*` fields carry the modeled wall-clock axis
    (`core/scenarios.py`, folded in by `scenarios.count_scenario` /
    `scenarios.advance_wall`) and stay zero when no scenario is configured.
    The `shard_*` fields carry the partitioned-server telemetry
    (`core/server_shard.py`, folded in by `server_shard.count_shard`) and
    stay zero when `server_shards <= 1`.  Every field is documented with
    its mode matrix in the "Counters telemetry glossary" of
    docs/ARCHITECTURE.md.

    No jnp defaults here on purpose: NamedTuple defaults are evaluated at
    module import, which would stage device ops before the caller configures
    jax — use `init_counters()`.
    """
    push_potential: jnp.ndarray   # int32 scalar
    push_actual: jnp.ndarray
    fetch_potential: jnp.ndarray
    fetch_actual: jnp.ndarray
    # byte-resolution accounting (floats; per-leaf in per-tensor mode)
    push_bytes_sent: jnp.ndarray
    push_bytes_total: jnp.ndarray
    fetch_bytes_sent: jnp.ndarray
    fetch_bytes_total: jnp.ndarray
    # ingress-queue telemetry (core/queue.py; zero when the queue is off)
    queue_enqueued: jnp.ndarray     # int32 — pushes admitted to the ring
    queue_rejected: jnp.ndarray     # int32 — refused before transmission
    queue_dropped: jnp.ndarray      # int32 — evicted by drop_oldest
    queue_drained: jnp.ndarray      # int32 — events applied from the ring
    queue_depth_sum: jnp.ndarray    # float32 — Σ post-drain depth per window
    queue_depth_peak: jnp.ndarray   # int32 — max post-admission depth
    queue_latency_sum: jnp.ndarray  # float32 — Σ admission→drain T-ticks
    queue_windows: jnp.ndarray      # int32 — drain windows accumulated
    # modeled wall-clock / scenario telemetry (core/scenarios.py; zero when
    # no scenario is configured — see docs/SCENARIOS.md)
    wall_clock: jnp.ndarray          # float32 — latest modeled wall time
    scenario_dropouts: jnp.ndarray   # int32 — clients lost to churn
    scenario_rejoins: jnp.ndarray    # int32 — clients recovered by churn
    scenario_active_sum: jnp.ndarray  # float32 — Σ active clients per window
    scenario_windows: jnp.ndarray    # int32 — scenario windows accumulated
    queue_latency_wall_sum: jnp.ndarray  # float32 — Σ admission→drain wall
    # one-kernel apply-path telemetry (kernels/fused_event_apply.py +
    # kernels/fasgd_update.py; folded in by `count_kernel`, zero when
    # `use_fused_kernel` is off)
    kernel_launches: jnp.ndarray     # int32 — per-leaf kernel launches
    kernel_events: jnp.ndarray       # int32 — events consumed by those windows
    # sharded-server telemetry (core/server_shard.py; folded in by
    # `server_shard.count_shard`, zero when `server_shards <= 1`)
    shard_applies: jnp.ndarray       # int32 — partitioned apply windows
    shard_events: jnp.ndarray        # int32 — events those windows consumed
    shard_bytes_peak: jnp.ndarray    # float32 — max per-shard resident bytes
    shard_depth_peak: jnp.ndarray    # int32 — max per-window shard batch


def init_counters() -> Counters:
    """All-zero `Counters` (see the class docstring for why not defaults)."""
    zero = jnp.zeros((), jnp.int32)
    zf = jnp.zeros((), jnp.float32)
    return Counters(zero, zero, zero, zero, zf, zf, zf, zf,
                    zero, zero, zero, zero, zf, zero, zf, zero,
                    zf, zero, zero, zf, zero, zf, zero, zero,
                    zero, zero, zf, zero)


def _acc_bytes(prev, amount):
    if amount is None:
        return prev
    return prev + jnp.asarray(amount, jnp.float32)


def count_events(counters: Counters, push, fetch,
                 push_bytes_sent=None, push_bytes_total=None,
                 fetch_bytes_sent=None, fetch_bytes_total=None) -> Counters:
    """Fold one batch of events in: `push`/`fetch` are bool scalars or [K].

    On the queued path `push` must be the *admitted* mask, not the raw gate
    decision: a rejected push never crossed the wire, so it contributes to
    neither `push_actual` nor `push_bytes_sent` (the queue's own
    `queue_rejected` counter records it instead).
    """
    push = jnp.atleast_1d(push)
    fetch = jnp.atleast_1d(fetch)
    return counters._replace(
        push_potential=counters.push_potential + jnp.int32(push.size),
        push_actual=counters.push_actual + jnp.sum(push.astype(jnp.int32)),
        fetch_potential=counters.fetch_potential + jnp.int32(fetch.size),
        fetch_actual=counters.fetch_actual + jnp.sum(fetch.astype(jnp.int32)),
        push_bytes_sent=_acc_bytes(counters.push_bytes_sent, push_bytes_sent),
        push_bytes_total=_acc_bytes(counters.push_bytes_total,
                                    push_bytes_total),
        fetch_bytes_sent=_acc_bytes(counters.fetch_bytes_sent,
                                    fetch_bytes_sent),
        fetch_bytes_total=_acc_bytes(counters.fetch_bytes_total,
                                     fetch_bytes_total),
    )


def count_kernel(counters: Counters, launches, events) -> Counters:
    """Fold one kernel-path application window into the telemetry.

    `launches` is the number of per-leaf kernel launches the window staged
    (n_leaves for one fused window; K·n_leaves for a serial scan whose every
    event launches the per-leaf fasgd kernel), `events` the gradient events
    the window consumed — events/launches·n_leaves is the amortization the
    one-kernel path buys.  Call sites gate on the static predicates below so
    the counters stay exactly zero (and are filtered from serialized
    metrics) when the kernel path is off.
    """
    return counters._replace(
        kernel_launches=(counters.kernel_launches
                         + jnp.asarray(launches, jnp.int32)),
        kernel_events=counters.kernel_events + jnp.asarray(events, jnp.int32),
    )


def fused_kernel_active(scfg: ServerConfig) -> bool:
    """Static predicate: `fused_apply` routes through the one-kernel path.

    Mirrors the dispatch inside `fused_apply`: the kernel consumes rules
    with a `batched_pallas_mode` and no per-leaf gap tensors (gap-aware
    rules declare `needs_client_params` and never set a mode, so the
    rule flags alone decide).
    """
    rule = server_rules.get_rule(scfg.rule)
    return bool(scfg.use_fused_kernel
                and rule.batched_pallas_mode is not None
                and not rule.needs_client_params)


def serial_kernel_active(scfg: ServerConfig,
                         per_tensor_tau: bool = False) -> bool:
    """Static predicate: serial `apply_update` routes through the rule's
    Pallas op (`UpdateRule._apply_pallas`) — matches the dispatch in
    `UpdateRule.apply`."""
    rule = server_rules.get_rule(scfg.rule)
    return bool(scfg.use_fused_kernel and rule.pallas_op is not None
                and not per_tensor_tau)


# ---------------------------------------------------------------------------
# gates — B-FASGD eq. 9
# ---------------------------------------------------------------------------

@jax.named_scope("dispatch")
def transmit_gate(key, server: ServerState, c, eps, shape=()):
    """Bernoulli eq.-9 draw(s): r < 1/(1 + c/(v̄+ε)).

    `c = 0` gives probability exactly 1 (uniform is in [0, 1)), so always
    drawing keeps the RNG stream identical whether or not gating is on.
    """
    return jax.random.uniform(key, shape) < transmit_prob(
        server_rules.vbar(server), c, eps)


@jax.named_scope("dispatch")
def per_tensor_gate(key, server: ServerState, c, eps):
    """Per-leaf eq.-9 draws, one per parameter tensor, driven by that
    tensor's own v̄ moving average (§5 extension, both directions).

    Returns (mask_tree mirroring server.params with scalar bool leaves,
    transmitted_bytes, total_bytes); event batches `jax.vmap` this over
    per-event keys.  As with `transmit_gate`, `c = 0` gives probability
    exactly 1 for every leaf while still consuming the same RNG, so turning
    gating off does not perturb any other stream.
    """
    return per_tensor_transmit_mask(key, server.v, c, eps)


# ---------------------------------------------------------------------------
# gated application — one event
# ---------------------------------------------------------------------------

def _merge_extra(extra_old, extra_new, push, like, any_push):
    """Per-leaf merge of rule-private `ServerState.extra`: entries that
    mirror the params tree (gap's ĝ EMA) follow the per-leaf mask; anything
    else (scalars, buffers) takes the updated value iff any leaf pushed."""
    if extra_old is None:
        return extra_new
    if isinstance(extra_old, dict):
        like_def = jax.tree.structure(like)
        return {
            k: (tree_select(push, extra_new[k], sub)
                if jax.tree.structure(sub) == like_def
                else tree_where(any_push, extra_new[k], sub))
            for k, sub in extra_old.items()
        }
    return tree_where(any_push, extra_new, extra_old)


def merge_gated_state(old: ServerState, cand: ServerState,
                      push) -> ServerState:
    """Per-leaf 'skip' semantics: keep the candidate update only for pushed
    leaves.  Parameters and the FASGD statistics (which mirror the params
    tree leaf-for-leaf) revert per leaf; T advances iff any leaf pushed
    (one server update happened, even if partial).

    Not meaningful for synchronous (barrier) rules: their pending-sum /
    count invariant cannot survive leaves reverting independently — the
    configs (SimConfig / build_round_step) reject that combination."""
    any_push = jnp.any(jnp.stack(jax.tree.leaves(
        jax.tree.map(jnp.any, push))))
    return ServerState(
        params=tree_select(push, cand.params, old.params),
        timestamp=jnp.where(any_push, cand.timestamp, old.timestamp),
        n=tree_select(push, cand.n, old.n),
        b=tree_select(push, cand.b, old.b),
        v=tree_select(push, cand.v, old.v),
        extra=_merge_extra(old.extra, cand.extra, push, old.params, any_push),
    )


@jax.named_scope("server_apply")
def apply_gated(scfg: ServerConfig, server: ServerState, grad, push, grad_ts,
                *, client_params=None, cached_grad=None):
    """One server application under a push decision.

    `push` is either one bool for the whole gradient or a per-leaf bool
    pytree mirroring the params tree (§5 per-tensor push gating — each
    tensor of the gradient transmits independently).

    cached_grad is not None  → the paper's 'cache' drop policy: a dropped
      push re-applies that client's most recent transmitted gradient (per
      leaf, in per-tensor mode), so the server still moves and T still
      advances.
    cached_grad is None      → 'skip' (or no gating): a dropped push masks
      the update out — whole-state for a scalar decision, leaf-wise for a
      per-leaf one (T then advances iff any leaf transmitted).

    Returns (new_server, aux).
    """
    per_leaf = is_per_leaf(push, server.params)
    if cached_grad is not None:
        g_eff = (tree_select(push, grad, cached_grad) if per_leaf
                 else tree_where(push, grad, cached_grad))
        return server_rules.apply_update(
            scfg, server, g_eff, grad_ts, client_params=client_params)
    cand, aux = server_rules.apply_update(
        scfg, server, grad, grad_ts, client_params=client_params)
    if per_leaf:
        return merge_gated_state(server, cand, push), aux
    return tree_where(push, cand, server), aux


# ---------------------------------------------------------------------------
# serial application — the paper-faithful lock order
# ---------------------------------------------------------------------------

@jax.named_scope("server_apply")
def serial_apply(scfg: ServerConfig, server: ServerState, grads, push,
                 grad_ts, client_params=None):
    """Apply pushed gradients one at a time in event order (lock = order).

    `grads` leaves are [K, ...]; `push`/`grad_ts` are [K] — or per-leaf
    pytrees mirroring the params tree with [K] leaves (per-tensor push
    gating / per-tensor staleness; `lax.scan` slices each leaf, so the body
    sees per-event per-leaf scalars and `apply_gated` resolves them);
    `client_params` (optional, [K, ...]) feeds gap-aware rules.
    Returns (server, taus [K]).
    """
    xs = (grads, push, grad_ts)
    if client_params is not None:
        def body(sv, inp):
            g_c, push_c, ts_c, cp_c = inp
            new, aux = apply_gated(scfg, sv, g_c, push_c, ts_c,
                                   client_params=cp_c)
            return new, aux["tau"]
        xs = xs + (client_params,)
    else:
        def body(sv, inp):
            g_c, push_c, ts_c = inp
            new, aux = apply_gated(scfg, sv, g_c, push_c, ts_c)
            return new, aux["tau"]
    return jax.lax.scan(body, server, xs)


# ---------------------------------------------------------------------------
# fused application — one masked-sum update over the whole event batch
# ---------------------------------------------------------------------------

@jax.named_scope("server_apply")
def fused_apply(scfg: ServerConfig, server: ServerState, grads, push,
                client_ts, client_params=None, *, mesh=None,
                server_axis: str = "server"):
    """One masked-sum application of all pushed gradients (beyond-paper).

    `grads` leaves are [K, ...] over the matching `server.params` leaves;
    `push`/`client_ts` are [K] (or per-leaf pytrees, below).  Stats (n, b, v,
    extra) advance once with the mean pushed gradient iff
    `scfg.track_stats` or the rule requires them (matching the serial
    path's `UpdateRule.apply` contract); the weight delta is
    Σ_c m_c·scale(v, τ_c)·g_c computed against the *post-stats* statistics
    via the registered rule's `scale_leaf`, and T advances by the number of
    pushes.  With `scfg.use_fused_kernel` and a rule that declares
    `batched_pallas_mode`, the whole application runs as the one-kernel
    event loop (`kernels/fused_event_apply.py`): one Pallas launch per leaf
    fuses the statistics step and the weight delta, reading and writing
    each leaf once per batch.

    Per-tensor mode (§5 extension): `push` may be a per-leaf bool pytree
    mirroring the params tree with [K] leaves (per-tensor push gating —
    each gradient tensor is masked independently; T advances by the number
    of events that pushed *any* leaf), and `client_ts` may be a per-leaf
    int32 pytree with [K] leaves (per-tensor staleness — each tensor's τ is
    measured from its own last synchronization; the per-leaf τ reaches the
    batched Pallas kernel as that leaf's SMEM τ vector).

    `mesh` is the device mesh the state is placed on, if any: the pushed
    gradients are first routed to the server's blocks (``push_route``,
    `server_shard.route_events`), and the kernel then applies each leaf's
    `server_axis` block on the device that holds it (`core/server_shard.py`
    routing; every leaf replicated when the mesh has no such axis).

    Returns (server, taus [K] — the per-event staleness, averaged over
    leaves in per-tensor mode).
    """
    rule = server_rules.get_rule(scfg.rule)
    if not rule.supports_fused:
        raise ValueError(
            f"rule {scfg.rule!r} does not support the fused apply mode")
    per_leaf_push = is_per_leaf(push, server.params)
    per_leaf_ts = is_per_leaf(client_ts, server.params)
    track_stats = scfg.track_stats or rule.requires_stats
    if mesh is not None:
        from repro.core import server_shard
        if server_shard.mesh_axis_size(mesh, server_axis) > 1:
            with jax.named_scope("push_route"):
                grads = server_shard.route_events(grads, mesh, server_axis)

    if per_leaf_push:
        pushf = jax.tree.map(lambda m: m.astype(jnp.float32), push)
        # an event is a server update iff it transmitted at least one leaf
        n_push = jnp.sum(any_leaf(push).astype(jnp.int32))
        n_push_leaf = jax.tree.map(
            lambda m: jnp.sum(m.astype(jnp.int32)), pushf)
    else:
        n_push = jnp.sum(push.astype(jnp.int32))
        pushf = push.astype(jnp.float32)

    gap = None
    if rule.needs_client_params and client_params is not None:
        # per-client parameter-space divergence θ_T − θ_ts, leaves [K, ...]
        gap = jax.tree.map(
            lambda sp, cp: sp[None].astype(jnp.float32)
            - cp.astype(jnp.float32),
            server.params, client_params)

    # One-kernel dispatch (kernels/fused_event_apply.py): stats step + weight
    # delta in a single per-leaf launch, each leaf read once and written once
    # per event batch.  The kernel owns the statistics step only when the
    # rule uses the shared eq. 4-6 moving averages with no `extra` state to
    # merge; otherwise the XLA stats block below runs first and the kernel
    # applies the delta alone (its track_stats=False pass-through).
    use_kernel = (scfg.use_fused_kernel
                  and rule.batched_pallas_mode is not None and gap is None)
    kernel_stats = (
        use_kernel and track_stats and server.extra is None
        and type(rule).update_stats is server_rules.UpdateRule.update_stats)

    if track_stats and not kernel_stats:
        if per_leaf_push:
            mean_g = jax.tree.map(
                lambda m, g, n: jnp.einsum("c,c...->...", m, g)
                / jnp.maximum(n, 1),
                pushf, grads, n_push_leaf)
            stats_state = rule.update_stats(scfg, server, mean_g)
            has_push_leaf = jax.tree.map(lambda n: n > 0, n_push_leaf)
            any_push = n_push > 0
            server = server._replace(
                n=tree_select(has_push_leaf, stats_state.n, server.n),
                b=tree_select(has_push_leaf, stats_state.b, server.b),
                v=tree_select(has_push_leaf, stats_state.v, server.v),
                extra=_merge_extra(server.extra, stats_state.extra,
                                   has_push_leaf, server.params, any_push),
            )
        else:
            mean_g = jax.tree.map(
                lambda g: jnp.einsum("c,c...->...", pushf, g)
                / jnp.maximum(n_push, 1),
                grads,
            )
            has_push = n_push > 0
            stats_state = rule.update_stats(scfg, server, mean_g)
            server = tree_where(has_push, stats_state, server)

    if per_leaf_ts:
        taus_tree = jax.tree.map(
            lambda ts: server_rules.step_staleness(server.timestamp, ts),
            client_ts)                                       # leaves [K]
        taus = server_rules.mean_leaf_tau(taus_tree)          # [K] diagnostic
    else:
        taus_tree = None
        taus = server_rules.step_staleness(server.timestamp, client_ts)  # [K]

    n_leaves = len(jax.tree.leaves(server.params))
    t_leaves = (jax.tree.leaves(taus_tree) if per_leaf_ts
                else [taus] * n_leaves)
    m_leaves = (jax.tree.leaves(pushf) if per_leaf_push
                else [pushf] * n_leaves)

    treedef = jax.tree.structure(server.params)
    if use_kernel:
        # One-kernel event loop: per leaf, ONE launch consumes the whole
        # batch — push mask, dedup count weighting, and rule coefficient
        # pre-folded into the SMEM weight vector ('coeff' mode), or the
        # mask alone with fasgd's eq. 7 scale computed in-kernel against
        # the resident post-stats v tile ('fasgd' mode).  When
        # `kernel_stats`, the same launch also advances n/b/v with the
        # mean pushed gradient, so the leaf never round-trips HBM between
        # the statistics step and the delta.
        from repro.kernels.ops import fused_event_apply
        leaf_specs = None
        if mesh is not None:
            S = server_shard.mesh_axis_size(mesh, server_axis)
            leaf_specs = [server_shard.server_leaf_spec(p.shape, S, server_axis)
                          for p in jax.tree.leaves(server.params)]
        if rule.batched_pallas_mode == "coeff":
            w_leaves = [rule.fused_coeffs(scfg, t) * m
                        for t, m in zip(t_leaves, m_leaves)]
        else:
            w_leaves = m_leaves
        if per_leaf_push:
            np_leaves = jax.tree.leaves(n_push_leaf)
            wm_leaves = [m / jnp.maximum(c, 1)
                         for m, c in zip(m_leaves, np_leaves)]
            hp_leaves = [c > 0 for c in np_leaves]
        else:
            wm_leaves = [pushf / jnp.maximum(n_push, 1)] * n_leaves
            hp_leaves = [n_push > 0] * n_leaves
        unfl = lambda ls: jax.tree.unflatten(treedef, ls)
        f32 = lambda tr: jax.tree.map(
            lambda l: l.astype(jnp.float32), tr)
        new_params, n_new, b_new, v_new = fused_event_apply(
            server.params, grads, f32(server.n), f32(server.b),
            f32(server.v), unfl(w_leaves), unfl(wm_leaves),
            unfl(t_leaves), unfl(hp_leaves), lr=scfg.lr,
            gamma=scfg.gamma, beta=scfg.beta, eps=scfg.eps,
            variant=scfg.variant, mode=rule.batched_pallas_mode,
            track_stats=kernel_stats,
            block_rows=scfg.kernel_block_rows,
            interpret=scfg.kernel_interpret, mesh=mesh,
            leaf_specs=leaf_specs)
        if kernel_stats:
            cast = lambda new, old: jax.tree.map(
                lambda a, o: a.astype(o.dtype), new, old)
            server = server._replace(
                n=cast(n_new, server.n), b=cast(b_new, server.b),
                v=cast(v_new, server.v))
    elif rule.batched_pallas_mode == "coeff" and gap is None:
        # v-independent scale: the delta is a plain weighted sum over the
        # event axis — one contraction per leaf, no [K, *s] scale tensor.
        g_leaves = jax.tree.leaves(grads)
        new = [p - jnp.einsum("k,k...->...",
                              rule.fused_coeffs(scfg, t) * m, g)
               for p, g, t, m in zip(jax.tree.leaves(server.params),
                                     g_leaves, t_leaves, m_leaves)]
        new_params = jax.tree.unflatten(treedef, new)
    else:
        v_leaves = jax.tree.leaves(server.v)
        g_leaves = jax.tree.leaves(grads)
        gap_leaves = (jax.tree.leaves(gap) if gap is not None
                      else [None] * len(v_leaves))
        e_leaves = server_rules.extra_leaf_dicts(server.extra, server.v)

        deltas = []
        for v_leaf, g_leaf, e_leaf, gap_leaf, t_leaf, m_leaf in zip(
                v_leaves, g_leaves, e_leaves, gap_leaves, t_leaves,
                m_leaves):
            expand = (-1,) + (1,) * v_leaf.ndim
            scale = rule.scale_leaf(
                scfg, v_leaf[None], t_leaf.reshape(expand),
                extra=e_leaf, gap=gap_leaf)
            m = m_leaf.reshape(expand)
            deltas.append(jnp.sum(m * scale * g_leaf, axis=0))
        delta = jax.tree.unflatten(treedef, deltas)
        new_params = jax.tree.map(jnp.subtract, server.params, delta)
    server = server._replace(
        params=new_params, timestamp=server.timestamp + n_push
    )
    return server, taus


# ---------------------------------------------------------------------------
# cotangent fused application — v-independent coefficient rules
# ---------------------------------------------------------------------------

def event_batched_losses(loss_fn):
    """Generic event-batched loss: per-event losses [K] from shared W + δ_k.

    Returns `batched(W, deltas, *batch) -> [K]` where each event's stale
    parameters enter as p_k = W + δ_k with δ_k = stop_gradient(p_k − W)
    (`deltas` leaves are [K, ...]), so a vjp w.r.t. W yields cotangent-
    weighted gradient sums Σ_k w_k·g_k.

    This fallback vmaps `loss_fn` over per-event effective parameters — it
    is correct for ANY loss, but the backward of the per-event GEMMs still
    materializes a [K, P] gradient batch before summing.  For the full
    cotangent speedup a model should provide a shared/delta-structured form
    whose differentiable operand is the shared W (the weight-grad GEMMs then
    contract over the event axis) and expose it as `loss_fn.event_batched` —
    see `repro.models.mlp.nll_loss_event_batched`.
    """
    def batched(W, deltas, *batch):
        p_eff = jax.tree.map(lambda w, d: w[None] + d, W, deltas)
        return jax.vmap(lambda p, *b: loss_fn(p, *b))(p_eff, *batch)
    return batched


def resolve_event_batched_loss(loss_fn, batched_loss_fn=None):
    """The event-batched form of `loss_fn` for the cotangent fused path.

    Resolution order: an explicit `batched_loss_fn`, the model-attached
    `loss_fn.event_batched` attribute, then the generic
    `event_batched_losses` fallback.  The result has the signature
    `batched(W, deltas, *batch) -> [K]`.
    """
    if batched_loss_fn is not None:
        return batched_loss_fn
    attached = getattr(loss_fn, "event_batched", None)
    if attached is not None:
        return attached
    return event_batched_losses(loss_fn)


@jax.named_scope("dispatch")
def dedup_events(ts):
    """Group an event batch by identical fetch timestamps.

    Clients that fetched at the same T hold bitwise-identical stale copies
    (every fetch delivers the canonical parameters of that timestamp), so
    events whose `ts` rows collide can share one stale-copy row.  `ts` is
    the per-event [K] int32 timestamp vector, or [K, n_leaves] rows of
    `client_leaf_ts` under per-tensor fetch (a group then requires ALL
    leaf timestamps to match).

    Returns `(rep, counts, is_rep)`: `rep[k]` is the index of the first
    event with an identical timestamp (`rep == arange(K)` iff all
    timestamps are distinct — dedup is then a no-op), `counts[k]` the size
    of event k's group, `is_rep[k]` whether k is its group's
    representative.  O(K²) boolean work, negligible next to the gradient
    evaluation.
    """
    t = ts if ts.ndim == 2 else ts[:, None]
    same = jnp.all(t[:, None, :] == t[None, :, :], axis=-1)      # [K, K]
    rep = jnp.argmax(same, axis=1).astype(jnp.int32)             # first True
    counts = jnp.sum(same.astype(jnp.int32), axis=1)
    is_rep = rep == jnp.arange(t.shape[0], dtype=jnp.int32)
    return rep, counts, is_rep


@jax.custom_vjp
def reweight_by_v(W, vfac):
    """Identity in `W` whose pullback scales cotangents elementwise by `vfac`.

    The fused delta of a `v_separable` rule factorizes as
    Δθ = vfac(v) ⊙ Σ_k w_k·g_k with per-event scalars w_k (fasgd:
    w_k = m_k·lr/τ_k, vfac = 1/(v+ε) — eq. 7 up to the documented
    ε-reparameterization).  Because this pullback is elementwise-linear it
    commutes with the event-axis contraction, so applying it to the
    already-contracted raw delta is exact: `fused_apply_cotangent` runs the
    batched backward once with the scalar weights, then pulls the result
    through `vjp(lambda W: reweight_by_v(W, vfac))` against the POST-stats
    v — the [K, P] per-event gradient batch is still never materialized.
    """
    return W


def _reweight_by_v_fwd(W, vfac):
    return W, vfac


def _reweight_by_v_bwd(vfac, ct):
    return (jax.tree.map(lambda f, c: (f * c).astype(c.dtype), vfac, ct),
            jax.tree.map(jnp.zeros_like, vfac))


reweight_by_v.defvjp(_reweight_by_v_fwd, _reweight_by_v_bwd)


def fused_apply_cotangent(scfg: ServerConfig, server: ServerState,
                          event_losses, stale_params, push, client_ts):
    """Fused application via cotangent-weighted vjps — no [K, P] grad batch.

    For rules with v-independent coefficients
    (`UpdateRule.coeffs_are_v_independent`) the fused update consumes only

        Δθ = Σ_k m_k·c(τ_k)·g_k      and      ḡ = Σ_k m_k·g_k / n_push,

    both linear in the per-event gradients — so both are pullbacks of the
    batched forward with per-event cotangent weights.  `v_separable` rules
    (fasgd) ride the same machinery: their scale factorizes as a per-event
    scalar times one elementwise v-factor, so the contraction runs with the
    scalar coefficients and the v-factor applies afterwards through the
    `reweight_by_v` pullback against the post-stats v.  `event_losses(W,
    deltas) -> [K]` evaluates every event's loss with its stale parameters
    expressed as p_k = W + δ_k, δ_k = stop_gradient(p_k − W) (`deltas`
    leaves [K, ...] are built here from `stale_params`); the vjp w.r.t. W
    then contracts the weight-gradient GEMMs over the event axis instead of
    materializing per-event weight gradients.  The two pullbacks run as one
    vmapped backward.  Callers may gather `stale_params` through
    `dedup_events` representatives — numerically a no-op (same-T rows are
    bitwise-identical; the gather just touches fewer distinct fleet rows),
    with each group's summed cotangent weight landing on its shared copy
    inside the backward's contraction.

    `push`/`client_ts` are [K]; per-leaf pytrees are rejected (a per-leaf
    mask or τ needs per-leaf weight vectors — that is the materialized
    path's job).  Stats advance once with ḡ iff `scfg.track_stats` or the
    rule requires them, exactly like `fused_apply`; T advances by the
    number of pushes.

    Returns (server, taus [K], losses [K]).
    """
    rule = server_rules.get_rule(scfg.rule)
    if not (rule.supports_fused
            and (rule.coeffs_are_v_independent or rule.v_separable)):
        raise ValueError(
            f"rule {scfg.rule!r} does not support the cotangent fused path "
            f"(needs supports_fused and coeffs_are_v_independent or "
            f"v_separable)")
    if is_per_leaf(push, server.params) or is_per_leaf(client_ts,
                                                      server.params):
        raise ValueError(
            "per-leaf push masks / timestamps require the materialized "
            "fused path (per-leaf weights cannot ride one cotangent vector)")
    pushf = push.astype(jnp.float32)
    n_push = jnp.sum(push.astype(jnp.int32))
    taus = server_rules.step_staleness(server.timestamp, client_ts)   # [K]
    coeffs = rule.fused_coeffs(scfg, taus)                            # [K]
    track_stats = scfg.track_stats or rule.requires_stats

    # the clients' forward and both pullbacks: their gradients, contracted
    with jax.named_scope("client_grad"):
        deltas = jax.tree.map(
            lambda p, w: jax.lax.stop_gradient(p - w[None]),
            stale_params, server.params)
        losses, pullback = jax.vjp(lambda W: event_losses(W, deltas),
                                   server.params)
        w_delta = (pushf * coeffs).astype(losses.dtype)
        if track_stats:
            w_mean = (pushf / jnp.maximum(n_push, 1)).astype(losses.dtype)
            # one vmapped backward for both weighted sums
            both = jax.vmap(lambda ct: pullback(ct)[0])(
                jnp.stack([w_delta, w_mean]))
            delta = jax.tree.map(lambda l: l[0], both)
            mean_g = jax.tree.map(lambda l: l[1], both)
        else:
            delta = pullback(w_delta)[0]

    with jax.named_scope("server_apply"):
        if track_stats:
            stats_state = rule.update_stats(scfg, server, mean_g)
            server = tree_where(n_push > 0, stats_state, server)
        if not rule.coeffs_are_v_independent:
            # v_separable rules (fasgd): the per-event coefficients above
            # carry only the scalar part (lr/τ_k); the elementwise v-factor
            # 1/(v+ε) applies once, against the post-stats v, via the
            # re-weighting pullback (exact — see `reweight_by_v`).
            vfac = rule.fused_vfactor(scfg, server.v)
            _, rw_pullback = jax.vjp(
                lambda W: reweight_by_v(W, vfac), server.params)
            delta = rw_pullback(delta)[0]
        new_params = jax.tree.map(jnp.subtract, server.params, delta)
        server = server._replace(
            params=new_params, timestamp=server.timestamp + n_push)
    return server, taus, losses


# ---------------------------------------------------------------------------
# deterministic duplicate-client resolution for event batches
# ---------------------------------------------------------------------------

def last_event_winners(clients, eligible=None):
    """[K] bool: event k wins iff no later eligible event targets its client.

    jnp scatter with duplicate indices has unspecified application order —
    FRED's bitwise-determinism contract forbids relying on it.  This computes
    the explicit last-event-wins mask (O(K²) booleans, negligible next to the
    gradient work) so each surviving index is unique.
    """
    k = clients.shape[0]
    order = jnp.arange(k)
    if eligible is None:
        eligible = jnp.ones((k,), bool)
    later_same = (
        (clients[None, :] == clients[:, None])
        & eligible[None, :]
        & (order[None, :] > order[:, None])
    )
    return eligible & ~jnp.any(later_same, axis=1)


def last_event_scatter(tree, clients, values, eligible, num_slots):
    """Scatter per-event `values` ([K, ...] leaves) into per-client `tree`
    ([λ, ...] leaves) with deterministic last-eligible-event-wins semantics.

    `eligible` is one [K] mask shared by every leaf, or a per-leaf pytree of
    [K] masks mirroring `tree` (per-tensor push gating: each leaf of the
    gradient cache only advances where *that* leaf transmitted).

    Losing/ineligible events are redirected to the out-of-bounds index
    `num_slots` and dropped by the scatter, so the surviving indices are
    unique — O(K) rows touched, never a fleet-sized copy.
    """
    if is_per_leaf(eligible, tree):
        def one(l, v, e):
            win = last_event_winners(clients, e)
            idx = jnp.where(win, clients, num_slots)
            return l.at[idx].set(v, mode="drop")
        return jax.tree.map(one, tree, values, eligible)
    win = last_event_winners(clients, eligible)
    idx = jnp.where(win, clients, num_slots)
    return jax.tree.map(
        lambda l, v: l.at[idx].set(v, mode="drop"), tree, values)
