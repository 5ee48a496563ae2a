"""Server update rules as a pluggable registry: ASGD, SASGD, FASGD (paper
§2), exponential penalty, synchronous SGD, Gap-Aware, and polynomial decay.

Every rule is an `UpdateRule` subclass registered by name::

    @register_rule("myrule")
    class MyRule(UpdateRule):
        def scale_leaf(self, config, v, tau, extra=None, gap=None):
            return config.lr / (1.0 + jnp.asarray(tau, jnp.float32)) * jnp.ones_like(v)

That one definition is consumed everywhere a rule can run: the serial
`apply_update` path, `round_trainer`'s fused masked-sum path, and the FRED
simulator — adding a rule is a one-file change.  A rule declares

* ``init_extra_state(config, params)`` — rule-private state stored in
  ``ServerState.extra`` (e.g. Gap-Aware's step-size EMA, sync SGD's pending
  gradient buffer);
* ``update_stats(config, state, grad)`` — one statistics step (defaults to
  the shared FASGD moving averages, eqs. 4–6; override to extend ``extra``);
* ``scale_leaf(config, v, tau, extra, gap)`` — the per-leaf effective
  learning rate, written in broadcastable jnp ops so the same body serves a
  single gradient (``v: [*s]``, scalar ``tau``) and the fused per-client
  batch (``v: [1, *s]``, ``tau: [C, 1, ...]``, ``gap: [C, *s]``);
* class attributes: ``synchronous`` (round-barrier apply), ``requires_stats``
  (consumes n/b/v), ``needs_client_params`` (scale uses the parameter-space
  gap θ_T − θ_ts), ``supports_fused`` (usable in the masked-sum path), and
  ``pallas_op`` (name of a fused Pallas fast path in `kernels.ops`).

All rules are pure functions over a `ServerState` pytree so they can live
inside `jax.lax.scan` / `jax.jit` / `shard_map`.  The FASGD moving-average
statistics (eqs. 4–6) are maintained for *every* rule when
`config.track_stats` is on (B-FASGD gating needs them even under SASGD
baselines); rules other than FASGD simply don't use them in the update.

Faithfulness note (see DESIGN.md §1.1): eq. (6) as printed maintains a moving
average of the *inverse* std and then divides by it, which contradicts the
prose ("dividing the learning rate by the standard deviation") and the
B-FASGD gate direction.  `variant="intent"` (default) averages the std itself;
`variant="literal"` implements the printed equation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.staleness import step_staleness

Rule = str  # a registry key — see registered_rules()

_REGISTRY: Dict[str, "UpdateRule"] = {}


def register_rule(name: str):
    """Class decorator: instantiate `cls` and register it under `name`."""

    def deco(cls):
        if name in _REGISTRY:
            raise ValueError(f"duplicate update-rule name {name!r}")
        cls.name = name
        _REGISTRY[name] = cls()
        return cls

    return deco


def get_rule(name: str) -> "UpdateRule":
    """Look up a registered `UpdateRule` by name (KeyError with the registry
    listing otherwise)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown update rule {name!r}; registered: {registered_rules()}"
        ) from None


def registered_rules() -> Tuple[str, ...]:
    """All registered rule names, sorted (the registry's public listing)."""
    return tuple(sorted(_REGISTRY))


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """Hyper-parameters of the server update (rule + eq. 4-8 constants)."""

    rule: Rule = "fasgd"
    lr: float = 0.005
    gamma: float = 0.9          # MA decay for n (2nd moment) and b (1st moment)
    beta: float = 0.9           # MA decay for v (std average)
    eps: float = 1e-8
    variant: str = "intent"     # 'intent' | 'literal'  (DESIGN.md §1.1)
    kappa: float = 0.15         # exp-penalty strength: lr * exp(-kappa * tau)
    poly_power: float = 0.5     # 'poly' exponent p in lr / tau**p (Zhang et al.)
    track_stats: bool = True    # maintain n/b/v even for non-FASGD rules
    num_clients: int = 1        # ssgd needs to know when a round is complete
    use_fused_kernel: bool = False  # route updates through a rule's Pallas op
    kasync_k: int = 0           # kasync partial-barrier K (0 → num_clients)
    # Pallas execution toggles (kernels/ops.py): force interpret-mode (True;
    # the kernel body runs on CPU for CI correctness), force native compile
    # (False), or auto (None — native on TPU, interpret / XLA-streaming
    # fallback elsewhere; overridable via REPRO_KERNEL_INTERPRET).
    kernel_interpret: Optional[bool] = None
    kernel_block_rows: int = 0  # 0 → derived from a VMEM budget (ops.apply_blocks)

    def __post_init__(self):
        get_rule(self.rule)     # raises KeyError for unregistered names
        assert self.variant in ("intent", "literal"), self.variant
        if self.kernel_block_rows < 0:
            raise ValueError(
                f"kernel_block_rows={self.kernel_block_rows} must be >= 0")
        if self.kasync_k < 0:
            raise ValueError(f"kasync_k={self.kasync_k} must be >= 0")
        if self.kasync_k > max(self.num_clients, 1):
            raise ValueError(
                f"kasync_k={self.kasync_k} exceeds num_clients="
                f"{self.num_clients} (set num_clients to the fleet size)")


class ServerState(NamedTuple):
    """Canonical parameters + timestamp + FASGD statistics.

    `n`, `b`, `v` mirror the params pytree (zeros/ones-init); `extra` holds
    rule-private state from `UpdateRule.init_extra_state` (None for rules
    that need none — scan requires fixed structure, and the sim keeps all
    fields live).
    """
    params: Any
    timestamp: jnp.ndarray          # int32 scalar, "T" in the paper
    n: Any                          # MA of g^2        (eq. 4)
    b: Any                          # MA of g          (eq. 5)
    v: Any                          # MA of std        (eq. 6; see variant)
    extra: Any = None               # rule-specific (gap: ĝ EMA; ssgd: pending)


def init(config: ServerConfig, params) -> ServerState:
    """Fresh `ServerState` for `params`: T = 0, n = b = 0, v = 1, plus the
    rule's `init_extra_state` (leaves mirror the params pytree)."""
    rule = get_rule(config.rule)
    zeros = jax.tree.map(jnp.zeros_like, params)
    # v starts at 1 so that the first few FASGD updates are ~plain ASGD
    # instead of dividing by ~0.
    ones = jax.tree.map(jnp.ones_like, params)
    return ServerState(
        params=params,
        timestamp=jnp.zeros((), jnp.int32),
        n=zeros,
        b=zeros,
        v=ones,
        extra=rule.init_extra_state(config, params),
    )


def _std(config: ServerConfig, n_leaf, b_leaf):
    return jnp.sqrt(jnp.maximum(n_leaf - b_leaf**2, 0.0) + config.eps)


def _shared_stats(config: ServerConfig, state: ServerState, grad) -> ServerState:
    """Eqs. 4–6: one moving-average step with gradient `grad`."""
    g, be = config.gamma, config.beta
    n = jax.tree.map(lambda m, x: g * m + (1 - g) * x * x, state.n, grad)
    b = jax.tree.map(lambda m, x: g * m + (1 - g) * x, state.b, grad)
    if config.variant == "intent":
        v = jax.tree.map(
            lambda m, nn, bb: be * m + (1 - be) * _std(config, nn, bb), state.v, n, b
        )
    else:  # literal: MA of inverse std, exactly eq. (6) as printed
        v = jax.tree.map(
            lambda m, nn, bb: be * m + (1 - be) / _std(config, nn, bb), state.v, n, b
        )
    return state._replace(n=n, b=b, v=v)


def update_stats(config: ServerConfig, state: ServerState, grad) -> ServerState:
    """One statistics step under the configured rule (eqs. 4–6 plus any
    rule-private `extra` statistics)."""
    return get_rule(config.rule).update_stats(config, state, grad)


def _tau_tree(state: ServerState, tau):
    """Broadcast a scalar staleness to a per-leaf pytree.  `tau` may already
    be a pytree (per-tensor staleness — the paper's §5 extension, where each
    tensor of a client copy may have synchronized at a different T)."""
    if jax.tree.structure(tau) == jax.tree.structure(state.v):
        return tau
    return jax.tree.map(lambda _: tau, state.v)


def extra_leaf_dicts(extra, like):
    """Slice `ServerState.extra` into per-leaf dicts for `scale_leaf`.

    Only entries whose tree structure mirrors `like` (the params/v tree) are
    passed through, leaf-aligned; anything else (scalars, buffers) is
    rule-private apply state.
    """
    n_leaves = len(jax.tree.leaves(like))
    if not isinstance(extra, dict):
        return [None] * n_leaves
    like_def = jax.tree.structure(like)
    mirrored = {
        k: jax.tree.leaves(sub)
        for k, sub in extra.items()
        if jax.tree.structure(sub) == like_def
    }
    if not mirrored:
        return [None] * n_leaves
    return [{k: leaves[i] for k, leaves in mirrored.items()}
            for i in range(n_leaves)]


def effective_scale(config: ServerConfig, state: ServerState, tau, gap=None):
    """Per-parameter learning-rate pytree for one gradient with staleness
    tau (scalar or per-leaf pytree).  `gap` optionally carries θ_T − θ_ts
    per leaf for gap-aware rules."""
    rule = get_rule(config.rule)
    taus = _tau_tree(state, tau)
    treedef = jax.tree.structure(state.v)
    v_leaves = jax.tree.leaves(state.v)
    t_leaves = jax.tree.leaves(taus)
    gap_leaves = (jax.tree.leaves(gap) if gap is not None
                  else [None] * len(v_leaves))
    e_leaves = extra_leaf_dicts(state.extra, state.v)
    scales = [
        rule.scale_leaf(config, v, t, extra=e, gap=g)
        for v, t, e, g in zip(v_leaves, t_leaves, e_leaves, gap_leaves)
    ]
    return jax.tree.unflatten(treedef, scales)


def mean_leaf_tau(tau_tree):
    """Collapse a per-leaf staleness pytree to one diagnostic τ (the mean
    over leaves — leaves may be scalars or [K] event vectors)."""
    leaves = jax.tree.leaves(tau_tree)
    return sum(jnp.asarray(t, jnp.float32) for t in leaves) / max(
        len(leaves), 1)


def _mean_scale(scale) -> jnp.ndarray:
    # NB: the count is a python float — >2B-param models overflow an i32
    # literal if it is staged as an int.
    return sum(jnp.sum(s) for s in jax.tree.leaves(scale)) / float(
        sum(s.size for s in jax.tree.leaves(scale)))


def _gap_tree(state: ServerState, client_params):
    """Parameter-space divergence θ_T − θ_ts of the pushing client."""
    return jax.tree.map(
        lambda sp, cp: sp.astype(jnp.float32) - cp.astype(jnp.float32),
        state.params, client_params)


class UpdateRule:
    """Base class for server update rules; subclass + `@register_rule`."""

    name: str = "?"
    synchronous: bool = False        # apply() buffers until a round completes
    needs_client_params: bool = False  # scale uses the gap θ_T − θ_ts
    requires_stats: bool = False     # rule consumes n/b/v (or extra stats)
    supports_fused: bool = True      # usable in the engine's fused apply path
    pallas_op: Optional[str] = None  # kernels.ops fast path, if any
    # Batched Pallas scale-and-accumulate support (kernels/batched_update.py):
    #   'coeff' — scale is a per-event scalar, v-independent: the rule
    #             provides `fused_coeffs(config, taus) -> [K]` and the kernel
    #             reduces Σ_k m_k·coeff_k·g_k in one HBM pass per leaf;
    #   'fasgd' — scale = lr/(v·τ_k + eps) elementwise in v, computed inside
    #             the kernel;
    #   None    — not kernelizable (gap needs per-leaf gap tensors; ssgd is
    #             a barrier).
    batched_pallas_mode: Optional[str] = None
    # The rule's fused update consumes only Σ_k w_k·g_k with per-event scalar
    # weights w_k = m_k·fused_coeffs(τ_k) that do NOT depend on the server
    # statistics v (nor on the per-leaf gap).  For such rules the engine can
    # compute the whole fused weight delta as a single vjp of the batched
    # forward with per-event cotangent weights — without ever materializing
    # the [K, P] per-event weight-gradient batch (engine.fused_apply_cotangent;
    # see docs/ARCHITECTURE.md).  True for asgd / sasgd / exp / poly; False
    # for fasgd (scale is elementwise in v, eq. 7) and gap (scale needs the
    # per-leaf parameter gap).
    coeffs_are_v_independent: bool = False
    # Weaker property: the fused scale factorizes as
    # scale(v, τ_k) = fused_coeffs(τ_k) · fused_vfactor(v) — a per-event
    # scalar times ONE elementwise v-factor shared by the whole batch.  True
    # for fasgd via an ε-reparameterization: lr/(τ_k·(v+ε)) = lr/(v·τ_k +
    # ε·τ_k) ≈ eq. 7's lr/(v·τ_k + ε) with relative error ≤ ε/(v+ε) ~ 1e-8.
    # Lets `fused_apply_cotangent` serve v-dependent rules: the per-event
    # contraction runs with the scalar coefficients, then a custom-vjp
    # re-weighting pullback applies the v-factor against the post-stats v —
    # still no [K, P] materialization.  Because it is ≈ (not bitwise) the
    # materialized reduction, fused_mode='auto' never picks it; only the
    # explicit 'cotangent' opt-in does.
    v_separable: bool = False

    def barrier_k(self, config: ServerConfig) -> int:
        """Round size K of a synchronous rule's (partial) barrier.

        The number of arrivals per round the rule actually waits for: λ for
        a full barrier (ssgd), ``kasync_k`` for the K-async partial barrier.
        Scenario wall-clock accounting advances a synchronous round by the
        K-th order statistic of the per-client service times
        (`scenarios.sync_round`); async rules never call this.
        """
        return max(config.num_clients, 1)

    def fused_coeffs(self, config: ServerConfig, taus):
        """Per-event scalar effective lr [K] for `batched_pallas_mode='coeff'`.

        `taus` is a [K] float32 staleness vector (engine-computed via
        `step_staleness`); the result multiplies each event's gradient in the
        fused reduction Σ_k m_k·coeff_k·g_k.
        """
        raise NotImplementedError(self.name)

    def fused_vfactor(self, config: ServerConfig, v):
        """Elementwise v-factor pytree for `v_separable` rules.

        Multiplies the coefficient-weighted fused delta once per leaf
        (post-stats v); see `v_separable` and `engine.fused_apply_cotangent`.
        """
        raise NotImplementedError(self.name)

    def init_extra_state(self, config: ServerConfig, params):
        """Rule-private state stored in `ServerState.extra` (or None).

        Entries whose pytree structure mirrors `params` are merged per leaf
        under per-tensor gating; anything else follows the whole-update mask.
        """
        return None

    def update_stats(self, config: ServerConfig, state: ServerState, grad):
        """One statistics step (default: the shared eq. 4-6 moving averages).

        `grad` mirrors the params pytree.  Override to extend
        `ServerState.extra` with rule-private statistics (e.g. gap's ĝ EMA).
        """
        return _shared_stats(config, state, grad)

    def scale_leaf(self, config: ServerConfig, v, tau, extra=None, gap=None):
        """Per-leaf effective lr; must broadcast `v` against `tau`/`gap`.

        Serves both a single gradient (`v: [*s]`, scalar `tau`) and the
        fused per-event batch (`v: [1, *s]`, `tau: [K, 1, ...]`,
        `gap: [K, *s]`) with the same broadcastable body.
        """
        raise NotImplementedError(self.name)

    def _apply_pallas(self, config, state, grad, tau, tau_scalar):
        raise NotImplementedError(self.name)

    def apply(self, config: ServerConfig, state: ServerState, grad, tau,
              tau_scalar, client_params=None):
        """One server update: stats step, scale, SGD step, T ← T + 1."""
        per_tensor_tau = (
            jax.tree.structure(tau) == jax.tree.structure(state.params))
        if (config.use_fused_kernel and self.pallas_op is not None
                and not per_tensor_tau):
            return self._apply_pallas(config, state, grad, tau, tau_scalar)
        if config.track_stats or self.requires_stats:
            state = self.update_stats(config, state, grad)
        gap = (_gap_tree(state, client_params)
               if self.needs_client_params and client_params is not None
               else None)
        scale = effective_scale(config, state, tau, gap=gap)
        new_params = jax.tree.map(
            lambda p, s, g: (p.astype(jnp.float32)
                             - s * g.astype(jnp.float32)).astype(p.dtype),
            state.params, scale, grad,
        )
        new_state = state._replace(
            params=new_params, timestamp=state.timestamp + 1)
        return new_state, {"tau": tau_scalar, "mean_scale": _mean_scale(scale)}


def _bshape(v, tau):
    return jnp.broadcast_shapes(jnp.shape(v), jnp.shape(jnp.asarray(tau)))


@register_rule("asgd")
class AsgdRule(UpdateRule):
    """Plain async SGD: θ ← θ − α·g, staleness ignored (eq. 1)."""

    batched_pallas_mode = "coeff"
    coeffs_are_v_independent = True

    def scale_leaf(self, config, v, tau, extra=None, gap=None):
        """Constant α broadcast over the leaf (eq. 1)."""
        return jnp.full(_bshape(v, tau), config.lr, jnp.float32)

    def fused_coeffs(self, config, taus):
        """Constant α per event (eq. 1)."""
        return jnp.full_like(jnp.asarray(taus, jnp.float32), config.lr)


@register_rule("sasgd")
class SasgdRule(UpdateRule):
    """Staleness-aware SGD (Zhang et al.): α/τ (eq. 2)."""

    batched_pallas_mode = "coeff"
    coeffs_are_v_independent = True

    def scale_leaf(self, config, v, tau, extra=None, gap=None):
        """α/τ broadcast over the leaf (eq. 2)."""
        t = jnp.asarray(tau, jnp.float32)
        return jnp.broadcast_to(config.lr / t, _bshape(v, tau))

    def fused_coeffs(self, config, taus):
        """α/τ_k per event (eq. 2)."""
        return config.lr / jnp.asarray(taus, jnp.float32)


@register_rule("exp")
class ExpPenaltyRule(UpdateRule):
    """Exponential staleness penalty (Chan & Lane): α·e^{−κ(τ−1)}."""

    batched_pallas_mode = "coeff"
    coeffs_are_v_independent = True

    def scale_leaf(self, config, v, tau, extra=None, gap=None):
        """α·e^{−κ(τ−1)} broadcast over the leaf."""
        t = jnp.asarray(tau, jnp.float32)
        return jnp.broadcast_to(
            config.lr * jnp.exp(-config.kappa * (t - 1.0)), _bshape(v, tau))

    def fused_coeffs(self, config, taus):
        """α·e^{−κ(τ_k−1)} per event."""
        t = jnp.asarray(taus, jnp.float32)
        return config.lr * jnp.exp(-config.kappa * (t - 1.0))


@register_rule("poly")
class PolyRule(UpdateRule):
    """Polynomial staleness decay: α/τ^p (Zhang et al., arXiv:1511.05950).

    `p = config.poly_power`; p = 1 recovers SASGD, p < 1 penalizes stale
    gradients more gently (the regime Zhang et al. found stable for large
    staleness), p > 1 more harshly.
    """

    batched_pallas_mode = "coeff"
    coeffs_are_v_independent = True

    def scale_leaf(self, config, v, tau, extra=None, gap=None):
        """α/τ^p broadcast over the leaf."""
        t = jnp.asarray(tau, jnp.float32)
        return jnp.broadcast_to(
            config.lr / t ** config.poly_power, _bshape(v, tau))

    def fused_coeffs(self, config, taus):
        """α/τ_k^p per event."""
        t = jnp.asarray(taus, jnp.float32)
        return config.lr / t ** config.poly_power


@register_rule("fasgd")
class FasgdRule(UpdateRule):
    """FASGD (the paper): α / (v·τ), elementwise in the std MA v (eq. 7)."""

    requires_stats = True
    pallas_op = "fasgd_update"
    batched_pallas_mode = "fasgd"
    v_separable = True

    def scale_leaf(self, config, v, tau, extra=None, gap=None):
        """α/(v·τ + ε) elementwise in the std moving average v (eq. 7)."""
        return config.lr / (v * jnp.asarray(tau, jnp.float32) + config.eps)

    def fused_coeffs(self, config, taus):
        """ε-reparameterized per-event factor α/τ_k (v_separable split).

        Together with `fused_vfactor` this gives α/(τ_k·(v+ε)) =
        α/(v·τ_k + ε·τ_k), eq. 7 with its ε guard scaled by τ_k — relative
        error ≤ ε/(v+ε) ~ 1e-8, far inside fused-path test tolerances.
        """
        return config.lr / jnp.asarray(taus, jnp.float32)

    def fused_vfactor(self, config, v):
        """Elementwise 1/(v+ε) against the post-stats std MA (eq. 7)."""
        return jax.tree.map(
            lambda l: 1.0 / (l.astype(jnp.float32) + config.eps), v)

    def _apply_pallas(self, config, state, grad, tau, tau_scalar):
        # Pallas fast path: eqs. 4-8 fused into one HBM pass per leaf
        # (kernels/fasgd_update; interpret-mode on CPU).  Semantically equal
        # to the unfused path — tests/test_kernels_fasgd.py.
        from repro.kernels.ops import fasgd_update
        n32 = jax.tree.map(lambda l: l.astype(jnp.float32), state.n)
        b32 = jax.tree.map(lambda l: l.astype(jnp.float32), state.b)
        v32 = jax.tree.map(lambda l: l.astype(jnp.float32), state.v)
        new_params, n_new, b_new, v_new = fasgd_update(
            state.params, grad, n32, b32, v32, config.lr, tau,
            gamma=config.gamma, beta=config.beta, eps=config.eps,
            variant=config.variant,
            block_rows=config.kernel_block_rows or 256,
            interpret=config.kernel_interpret)
        cast = lambda new, old: jax.tree.map(
            lambda a, o: a.astype(o.dtype), new, old)
        new_state = state._replace(
            params=new_params, n=cast(n_new, state.n), b=cast(b_new, state.b),
            v=cast(v_new, state.v), timestamp=state.timestamp + 1)
        scale = effective_scale(config, new_state._replace(v=v_new), tau)
        return new_state, {"tau": tau_scalar, "mean_scale": _mean_scale(scale)}


@register_rule("gap")
class GapAwareRule(UpdateRule):
    """Gap-Aware staleness mitigation (Barkai et al., arXiv:1909.10802).

    Penalizes a stale gradient by the *parameter-space* gap it was computed
    across rather than its step count: C = max(1, |θ_T − θ_ts| / ĝ)
    elementwise, where ĝ is an EMA of the typical per-step parameter
    movement α·|g|; the effective lr is α / C.  A client whose copy barely
    diverged pays no penalty even at large τ — the same insight as FASGD's
    B-Staleness, realized through the parameter gap instead of gradient std.

    When no client copy is available to measure against (`gap=None`, e.g. a
    bare `apply_update` without `client_params`) the penalty is 1 (ASGD).
    """

    needs_client_params = True
    requires_stats = True

    def init_extra_state(self, config, params):
        """ĝ EMA of the typical per-step parameter movement (zeros-init,
        mirrors the params pytree)."""
        return {"gbar": jax.tree.map(
            lambda l: jnp.zeros(l.shape, jnp.float32), params)}

    def update_stats(self, config, state, grad):
        """Shared eq. 4-6 step plus the ĝ EMA of α·|g| (Barkai et al. §4)."""
        state = _shared_stats(config, state, grad)
        gbar = jax.tree.map(
            lambda m, g: (config.gamma * m
                          + (1 - config.gamma)
                          * config.lr * jnp.abs(g.astype(jnp.float32))),
            state.extra["gbar"], grad)
        return state._replace(extra={"gbar": gbar})

    def scale_leaf(self, config, v, tau, extra=None, gap=None):
        """α / max(1, |gap|/ĝ) elementwise; α (ASGD) when no gap is given."""
        shape = _bshape(v, tau)
        if gap is None or extra is None:
            return jnp.full(shape, config.lr, jnp.float32)
        penalty = jnp.maximum(
            1.0, jnp.abs(gap) / (extra["gbar"] + config.eps))
        return jnp.broadcast_to(
            config.lr / penalty, jnp.broadcast_shapes(shape, penalty.shape))


@register_rule("ssgd")
class SsgdRule(UpdateRule):
    """Synchronous SGD barrier: buffer gradients, step once per full round."""

    synchronous = True
    supports_fused = False

    def init_extra_state(self, config, params):
        """Pending-gradient buffer (mirrors params) + arrival count."""
        return {"pending": jax.tree.map(jnp.zeros_like, params),
                "count": jnp.zeros((), jnp.int32)}

    def scale_leaf(self, config, v, tau, extra=None, gap=None):
        """α/λ broadcast over the leaf (the per-round mean step)."""
        return jnp.full(
            _bshape(v, tau), config.lr / max(config.num_clients, 1),
            jnp.float32)

    def apply(self, config, state, grad, tau, tau_scalar, client_params=None):
        """Buffer `grad`; step θ once `num_clients` gradients arrived."""
        pending = jax.tree.map(jnp.add, state.extra["pending"], grad)
        count = state.extra["count"] + 1
        full = count >= config.num_clients

        def do_apply(_):
            new_params = jax.tree.map(
                lambda p, s: p - config.lr * s / config.num_clients,
                state.params,
                pending,
            )
            return (new_params, jax.tree.map(jnp.zeros_like, pending),
                    jnp.zeros((), jnp.int32), state.timestamp + 1)

        def no_apply(_):
            return state.params, pending, count, state.timestamp

        params, pending, count, ts = jax.lax.cond(full, do_apply, no_apply, None)
        new_state = state._replace(
            params=params, timestamp=ts,
            extra={"pending": pending, "count": count},
        )
        if config.track_stats:
            new_state = self.update_stats(config, new_state, grad)
        return new_state, {"tau": tau_scalar, "applied": full}


@register_rule("kasync")
class KAsyncRule(UpdateRule):
    """K-async partial barrier (Dutta et al., arXiv:1803.01113 §3).

    The sync↔async midpoint: each round waits for the fastest
    K = ``config.kasync_k`` of the λ = ``config.num_clients`` arrivals and
    steps θ ← θ − α·(Σ g)/K; the remaining λ − K arrivals of the round are
    *discarded* (Dutta et al.'s cancellation semantics — the stragglers'
    gradients are dropped, not buffered).  ``kasync_k = 0`` means K = λ,
    which is bitwise-identical to `ssgd` (property-tested); K = 1
    approaches the async limit while keeping zero-staleness updates.

    A round is a window of λ consecutive arrivals tracked by the ``seen``
    cursor; the first K pushed gradients of each window are accumulated and
    the rest ignored (under a scenario, `scenarios.sync_round` delivers
    arrivals fastest-first, so "first K" = "fastest K").  The wall clock of
    a round is the K-th order statistic of the service times — the whole
    point of the rule: E[t₍ₖ₎] ≪ E[t₍λ₎] under heavy-tailed stragglers.
    """

    synchronous = True
    supports_fused = False

    def _k(self, config: ServerConfig) -> int:
        return config.kasync_k or max(config.num_clients, 1)

    def barrier_k(self, config: ServerConfig) -> int:
        """Partial-barrier round size K (``kasync_k``, 0 → λ)."""
        return self._k(config)

    def init_extra_state(self, config, params):
        """Pending buffer + taken-count + round-arrival cursor ``seen``."""
        return {"pending": jax.tree.map(jnp.zeros_like, params),
                "count": jnp.zeros((), jnp.int32),
                "seen": jnp.zeros((), jnp.int32)}

    def scale_leaf(self, config, v, tau, extra=None, gap=None):
        """α/K broadcast over the leaf (the per-round mean over the K kept)."""
        return jnp.full(_bshape(v, tau), config.lr / self._k(config),
                        jnp.float32)

    def apply(self, config, state, grad, tau, tau_scalar, client_params=None):
        """Accumulate the first K arrivals of the round; discard the rest."""
        k = self._k(config)
        lam = max(config.num_clients, 1)
        take = state.extra["seen"] < k
        pending = jax.tree.map(
            lambda acc, g: jnp.where(take, acc + g, acc),
            state.extra["pending"], grad)
        count = state.extra["count"] + take.astype(jnp.int32)
        full = count >= k

        def do_apply(_):
            new_params = jax.tree.map(
                lambda p, s: p - config.lr * s / k,
                state.params,
                pending,
            )
            return (new_params, jax.tree.map(jnp.zeros_like, pending),
                    jnp.zeros((), jnp.int32), state.timestamp + 1)

        def no_apply(_):
            return state.params, pending, count, state.timestamp

        params, pending, count, ts = jax.lax.cond(full, do_apply, no_apply, None)
        seen = jnp.where(state.extra["seen"] + 1 >= lam,
                         jnp.zeros((), jnp.int32), state.extra["seen"] + 1)
        new_state = state._replace(
            params=params, timestamp=ts,
            extra={"pending": pending, "count": count, "seen": seen},
        )
        if config.track_stats:
            # Discarded arrivals leave the eq. 4-6 statistics untouched too:
            # a cancelled gradient never reached the server.
            tracked = self.update_stats(config, new_state, grad)
            new_state = jax.tree.map(
                lambda a, b: jnp.where(take, a, b), tracked, new_state)
        return new_state, {"tau": tau_scalar, "applied": full}


def apply_update(config: ServerConfig, state: ServerState, grad,
                 grad_timestamp, *, client_params=None):
    """One server update (paper's Async SGD protocol step 2 + FASGD eqs. 4-8).

    Returns (new_state, aux) where aux carries the staleness and the mean
    effective lr for diagnostics.  `grad_timestamp` may be a scalar or a
    per-tensor pytree (§5 extension).  `client_params` optionally carries the
    parameter copy the gradient was computed on — rules with
    `needs_client_params` (gap-aware) use it to measure the divergence.
    For synchronous rules the gradient is accumulated and parameters only
    move once `num_clients` gradients arrived.
    """
    rule = get_rule(config.rule)
    if jax.tree.structure(grad_timestamp) == jax.tree.structure(state.params):
        # per-tensor timestamps (§5 extension)
        tau = jax.tree.map(
            lambda ts: step_staleness(state.timestamp, ts), grad_timestamp)
        tau_scalar = mean_leaf_tau(tau)
    else:
        tau = tau_scalar = step_staleness(state.timestamp, grad_timestamp)
    return rule.apply(config, state, grad, tau, tau_scalar,
                      client_params=client_params)


def vbar(state: ServerState) -> jnp.ndarray:
    """Mean over all parameters of the std moving average (B-FASGD's v̄)."""
    leaves = jax.tree.leaves(state.v)
    total = sum(jnp.sum(l.astype(jnp.float32)) for l in leaves)
    return total / float(sum(l.size for l in leaves))
