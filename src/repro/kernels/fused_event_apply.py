"""One-kernel event loop: gate→coeff→stats→accumulate in a single Pallas pass.

The engine's fused application previously split one K-event batch into three
XLA/kernel stages per parameter leaf — a stats einsum on the mean pushed
gradient, the eq. 4-6 moving-average updates, and the weighted delta
reduction (`batched_update.py`) — re-reading the leaf-sized buffers between
stages.  This kernel is the whole server apply for one leaf in ONE launch:

 1. the per-event push mask, dedup group weighting, and rule coefficient
    arrive pre-folded as one SMEM weight vector ``w[K]`` (plus the stats
    mean-weight vector ``wmean[K]`` and the staleness vector ``taus[K]``) —
    a different event batch never recompiles;
 2. the mean pushed gradient ḡ = Σ_k wmean_k·g_k accumulates in VMEM and the
    eq. 4-6 statistics (n, b, v) advance against it, held still when no
    event pushed this leaf (``has_push``);
 3. the weight delta accumulates against the POST-stats statistics: per
    event either the pre-folded scalar weight (``mode='coeff'``) or fasgd's
    elementwise eq. 7 scale lr/(v'·τ_k + ε) computed in-kernel against the
    resident v tile (``mode='fasgd'``).

Each leaf is read once (θ, n, b, v + the K gradient tiles) and written once
(θ', n', b', v'): K + 8 HBM passes of the parameter footprint per batch,
versus ≈ 6K + 14 for the split schedule (stats contraction K+1, moving
averages ~10, broadcast delta 5K+3).  See `benchmarks/kernels.py`
(``hbm_model_one_kernel``) — the bound is also *measured* there.

Layout follows `batched_update.py`: (rows, 128) lane-aligned tiles, gradients
stacked [K, rows, 128], per-event scalars in SMEM.  ``interpret=True``
executes the identical kernel on CPU for CI correctness
(`ops.fused_event_apply` additionally offers an XLA streaming fallback with
the same semantics for off-TPU *timing* — see `ref.fused_event_apply_ref`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

LANES = 128


def _kernel(scal_ref, w_ref, wm_ref, tau_ref,
            p_ref, n_ref, b_ref, v_ref, g_ref,
            po_ref, no_ref, bo_ref, vo_ref,
            *, num_events: int, mode: str, gamma: float, beta: float,
            eps: float, variant: str, track_stats: bool):
    lr = scal_ref[0]
    has_push = scal_ref[1]          # 1.0 iff any event pushed this leaf
    shape = p_ref.shape
    n0, b0, v0 = n_ref[...], b_ref[...], v_ref[...]

    if track_stats:
        def mean_body(k, acc):
            return acc + wm_ref[k] * g_ref[k].astype(jnp.float32)
        gbar = jax.lax.fori_loop(
            0, num_events, mean_body, jnp.zeros(shape, jnp.float32))
        n1 = gamma * n0 + (1.0 - gamma) * gbar * gbar        # eq. 4
        b1 = gamma * b0 + (1.0 - gamma) * gbar               # eq. 5
        std = jnp.sqrt(jnp.maximum(n1 - b1 * b1, 0.0) + eps)
        if variant == "intent":
            v1 = beta * v0 + (1.0 - beta) * std              # eq. 6 (prose)
        else:
            v1 = beta * v0 + (1.0 - beta) / std              # eq. 6 (printed)
        # no event pushed this leaf → the moving averages hold still
        n1 = jnp.where(has_push > 0.0, n1, n0)
        b1 = jnp.where(has_push > 0.0, b1, b0)
        v1 = jnp.where(has_push > 0.0, v1, v0)
    else:
        n1, b1, v1 = n0, b0, v0

    def body(k, acc):
        g = g_ref[k].astype(jnp.float32)
        if mode == "fasgd":
            scale = lr / (v1 * tau_ref[k] + eps)    # eq. 7, post-stats v
            return acc + w_ref[k] * scale * g
        return acc + w_ref[k] * g

    acc = jax.lax.fori_loop(
        0, num_events, body, jnp.zeros(shape, jnp.float32))
    po_ref[...] = (p_ref[...].astype(jnp.float32) - acc).astype(po_ref.dtype)
    no_ref[...] = n1
    bo_ref[...] = b1
    vo_ref[...] = v1


def fused_event_apply_2d(
    params: jax.Array,   # (R, 128) — any float dtype
    grads: jax.Array,    # (K, R, 128)
    n: jax.Array,        # (R, 128) float32
    b: jax.Array,        # (R, 128) float32
    v: jax.Array,        # (R, 128) float32
    weights: jax.Array,  # (K,) float32 — mask×coeff ('coeff') or mask ('fasgd')
    wmean: jax.Array,    # (K,) float32 — m_k / max(n_push, 1)
    taus: jax.Array,     # (K,) float32 — this leaf's per-event staleness
    lr,
    has_push,            # scalar — any event pushed this leaf
    *,
    gamma: float = 0.9,
    beta: float = 0.9,
    eps: float = 1e-8,
    variant: str = "intent",
    mode: str = "fasgd",
    track_stats: bool = True,
    block_rows: int = 256,
    interpret: bool = False,
):
    """One fused K-event server apply over tile-aligned buffers.

    Returns ``(params', n', b', v')``; with ``track_stats=False`` the
    statistics pass through unchanged (the caller already advanced them, or
    tracking is off).  Semantically equal to `ref.fused_event_apply_ref`.
    """
    assert mode in ("coeff", "fasgd"), mode
    K, R, lanes = grads.shape
    assert lanes == LANES and params.shape == (R, LANES), (grads.shape,
                                                           params.shape)
    assert R % block_rows == 0, (R, block_rows)
    grid = (R // block_rows,)
    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    gtile = pl.BlockSpec((K, block_rows, LANES), lambda i: (0, i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         jnp.asarray(has_push, jnp.float32)])
    kern = functools.partial(
        _kernel, num_events=K, mode=mode, gamma=gamma, beta=beta, eps=eps,
        variant=variant, track_stats=track_stats)
    f32 = jax.ShapeDtypeStruct((R, LANES), jnp.float32)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[smem, smem, smem, smem,       # (lr, has_push), w, wmean, τ
                  tile, tile, tile, tile, gtile],
        out_specs=[tile, tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((R, LANES), params.dtype),
                   f32, f32, f32],
        name="fused_event_apply",
        interpret=interpret,
    )(scalars, weights.astype(jnp.float32), wmean.astype(jnp.float32),
      taus.astype(jnp.float32), params, n, b, v, grads)
