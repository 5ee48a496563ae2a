"""Batched scale-and-accumulate Pallas TPU kernel for the fused apply path.

The engine's fused application (core/engine.py) computes, per parameter leaf,

    Δθ = Σ_k m_k · scale(v, τ_k) · g_k          (k over the event/client axis)

Executed as XLA ops this broadcasts a [K, *s] scale tensor and reduces it —
K+1 HBM-sized intermediates for a result that only ever needs θ, v, and one
streaming pass over the K gradients.  Fused, the kernel reads each gradient
tile once, keeps the accumulator in VMEM/VREGs, and writes θ once: exactly
(K+2) reads + 1 write of the parameter footprint, the HBM lower bound.

Two scale families cover every kernelizable registry rule
(`UpdateRule.batched_pallas_mode`):

 - ``mode='coeff'``: scale is a per-event *scalar* c_k (asgd / sasgd / exp /
   poly — anything v-independent).
 - ``mode='fasgd'``: scale = lr / (v·τ_k + eps) elementwise in the std MA v
   (paper eq. 7).

The push decision arrives as its own SMEM mask vector m_k ∈ {0, 1},
separate from the rule coefficient — with per-tensor push gating (§5
extension) each parameter leaf launches with *its* mask and *its* τ vector,
so per-leaf gating and per-leaf staleness are just different SMEM contents,
never a recompile or an extra HBM pass.

Layout follows `fasgd_update.py`: (rows, 128) lane-aligned tiles, gradients
stacked [K, rows, 128]; per-event scalars (m_k, c_k, τ_k) live in SMEM so a
different event batch does not recompile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

LANES = 128


def _kernel(*refs, num_events: int, mode: str, eps: float, has_mask: bool):
    if has_mask:
        scal_ref, mask_ref, coeff_ref, tau_ref, p_ref, v_ref, g_ref, po_ref \
            = refs
    else:
        # coefficient plumbing for pre-folded batches: the engine folds the
        # push mask (and any dedup count weighting) into the coefficient
        # vector, so the launch carries one SMEM weight operand per leaf.
        scal_ref, coeff_ref, tau_ref, p_ref, v_ref, g_ref, po_ref = refs
        mask_ref = None
    lr = scal_ref[0]
    block_shape = p_ref.shape
    v = v_ref[...] if mode == "fasgd" else None

    def body(k, acc):
        g = g_ref[k].astype(jnp.float32)
        w = (coeff_ref[k] if mask_ref is None
             else mask_ref[k] * coeff_ref[k])
        if mode == "fasgd":
            scale = lr / (v * tau_ref[k] + eps)            # eq. 7, per event
            return acc + w * scale * g
        return acc + w * g

    acc = jax.lax.fori_loop(
        0, num_events, body, jnp.zeros(block_shape, jnp.float32))
    po_ref[...] = (p_ref[...].astype(jnp.float32) - acc).astype(po_ref.dtype)


def batched_scale_apply_2d(
    params: jax.Array,   # (R, 128) — any float dtype
    grads: jax.Array,    # (K, R, 128)
    v: jax.Array,        # (R, 128) float32 (read only in mode='fasgd')
    coeffs: jax.Array,   # (K,) float32 — per-event rule coefficient
    taus: jax.Array,     # (K,) float32 — this leaf's per-event staleness
    lr,
    *,
    masks: jax.Array = None,   # (K,) float32 ∈ {0,1} — this leaf's push mask
    eps: float = 1e-8,
    mode: str = "fasgd",
    block_rows: int = 256,
    interpret: bool = False,
):
    """One fused Σ_k m_k·c_k·scale(v,τ_k)·g_k apply over tile-aligned
    buffers.

    `masks=None` launches without the mask SMEM operand entirely — the
    caller pre-folded the push decision (and any event-dedup count
    weighting) into `coeffs`, or every event pushed this leaf.  Bitwise
    identical to passing an all-ones mask.
    """
    assert mode in ("coeff", "fasgd"), mode
    K, R, lanes = grads.shape
    assert lanes == LANES and params.shape == (R, LANES), (grads.shape,
                                                           params.shape)
    assert R % block_rows == 0, (R, block_rows)
    has_mask = masks is not None
    grid = (R // block_rows,)
    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    gtile = pl.BlockSpec((K, block_rows, LANES), lambda i: (0, i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    scalars = jnp.asarray(lr, jnp.float32).reshape(1)
    kern = functools.partial(_kernel, num_events=K, mode=mode, eps=eps,
                             has_mask=has_mask)
    mask_ops = (masks.astype(jnp.float32),) if has_mask else ()
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=(
            [smem]                          # (lr,)
            + ([smem] if has_mask else [])  # masks [K]
            + [smem, smem,                  # coeffs [K], taus [K]
               tile, tile, gtile]
        ),
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((R, LANES), params.dtype),
        name="batched_update",
        interpret=interpret,
    )(scalars, *mask_ops, coeffs.astype(jnp.float32),
      taus.astype(jnp.float32), params, v, grads)
