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

Layout: each leaf in its own layout, viewed as (R, C) or, where its leading
dims cannot merge into the rows without moving data, (L, R, C) with L a
squeezed grid axis; gradients stacked [K, ...] over the same view, per-event
scalars in SMEM.  The grid tiles R and C with (block_rows, block_cols)
blocks; a ragged last block reads past the edge and its out-of-bounds writes
are dropped, which an elementwise kernel allows.  ``interpret=True``
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
    params: jax.Array,   # (R, C) or (L, R, C) — any float dtype
    grads: jax.Array,    # (K, *params.shape)
    n: jax.Array,        # params.shape, float32
    b: jax.Array,        # params.shape, float32
    v: jax.Array,        # params.shape, float32
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
    block_cols: int | None = None,
    interpret: bool = False,
):
    """One fused K-event server apply over a leaf's (R, C) or (L, R, C) view.

    ``block_rows`` must be R or a multiple of the sublane tile, and
    ``block_cols`` (None: C) C or a multiple of 128.  Returns
    ``(params', n', b', v')``; with ``track_stats=False`` the statistics
    pass through unchanged (the caller already advanced them, or tracking
    is off).  Semantically equal to `ref.fused_event_apply_ref`.
    """
    assert mode in ("coeff", "fasgd"), mode
    K = grads.shape[0]
    assert params.ndim in (2, 3) and grads.shape == (K,) + params.shape, (
        grads.shape, params.shape)
    *lead, R, C = params.shape
    br, bc = min(block_rows, R), min(block_cols or C, C)
    grid = (*lead, pl.cdiv(R, br), pl.cdiv(C, bc))
    if lead:
        tile = pl.BlockSpec((pl.squeezed, br, bc), lambda l, i, j: (l, i, j))
        gtile = pl.BlockSpec((K, pl.squeezed, br, bc),
                             lambda l, i, j: (0, l, i, j))
    else:
        tile = pl.BlockSpec((br, bc), lambda i, j: (i, j))
        gtile = pl.BlockSpec((K, br, bc), lambda i, j: (0, i, j))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         jnp.asarray(has_push, jnp.float32)])
    kern = functools.partial(
        _kernel, num_events=K, mode=mode, gamma=gamma, beta=beta, eps=eps,
        variant=variant, track_stats=track_stats)
    f32 = jax.ShapeDtypeStruct(params.shape, jnp.float32)
    # What a launch costs, for XLA's scheduling and its placement of the
    # buffers around the kernel: each element reads θ, n, b, v and its K
    # gradients and writes θ', n', b', v' once; per event 2 flops of the
    # mean gradient and 2 ('coeff') or 6 (eq. 7's scale) of the delta, and
    # the statistics step's ~17 flops and one square root.
    stats = int(track_stats)
    per_event = 2 * stats + (6 if mode == "fasgd" else 2)
    cost = pl.CostEstimate(
        flops=params.size * (per_event * K + 17 * stats + 1),
        transcendentals=params.size * stats,
        bytes_accessed=params.size * (K * grads.dtype.itemsize
                                      + 2 * params.dtype.itemsize + 6 * 4))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[smem, smem, smem, smem,       # (lr, has_push), w, wmean, τ
                  tile, tile, tile, tile, gtile],
        out_specs=[tile, tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct(params.shape, params.dtype),
                   f32, f32, f32],
        name="fused_event_apply",
        cost_estimate=cost,
        interpret=interpret,
    )(scalars, weights.astype(jnp.float32), wmean.astype(jnp.float32),
      taus.astype(jnp.float32), params, n, b, v, grads)
