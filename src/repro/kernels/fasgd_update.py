"""Fused FASGD server-update Pallas TPU kernel.

The FASGD server update (paper eqs. 4–8) touches five parameter-sized buffers
(θ, n, b, v, g) and is purely elementwise — i.e. strictly HBM-bandwidth-bound.
Executed as separate XLA ops it costs ~9 HBM round-trips of the parameter
footprint (read+write n, read+write b, read+write v, read g, read+write θ,
plus intermediates); fused it is exactly 5 reads + 4 writes with all
arithmetic in VMEM/VREGs in one pass.  This is the paper's compute hot-spot:
the server applies one such update per client push.

TPU adaptation: the update is laid out as (rows, 128) lane-aligned tiles so
the VPU operates on full (8, 128) vregs; scalars (lr, τ) arrive via SMEM so a
change of staleness does not recompile.

Shapes: all tensor operands are (R, 128) with R a multiple of the row-block.
`ops.fasgd_update` handles flattening/padding of arbitrary pytrees.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

LANES = 128


def _kernel(scal_ref, p_ref, g_ref, n_ref, b_ref, v_ref,
            po_ref, no_ref, bo_ref, vo_ref,
            *, gamma: float, beta: float, eps: float, variant: str):
    lr = scal_ref[0]
    tau = scal_ref[1]
    g = g_ref[...].astype(jnp.float32)
    n = gamma * n_ref[...] + (1.0 - gamma) * g * g            # eq. 4
    b = gamma * b_ref[...] + (1.0 - gamma) * g                # eq. 5
    std = jnp.sqrt(jnp.maximum(n - b * b, 0.0) + eps)
    if variant == "intent":
        v = beta * v_ref[...] + (1.0 - beta) * std            # eq. 6 (prose)
    else:
        v = beta * v_ref[...] + (1.0 - beta) / std            # eq. 6 (printed)
    scale = lr / (v * tau + eps)                              # eq. 7
    po_ref[...] = (p_ref[...].astype(jnp.float32) - scale * g).astype(po_ref.dtype)
    no_ref[...] = n
    bo_ref[...] = b
    vo_ref[...] = v


def fasgd_update_2d(
    params: jax.Array,   # (R, 128) — any float dtype
    grads: jax.Array,    # (R, 128)
    n: jax.Array,        # (R, 128) float32
    b: jax.Array,
    v: jax.Array,
    lr,
    tau,
    *,
    gamma: float = 0.9,
    beta: float = 0.9,
    eps: float = 1e-8,
    variant: str = "intent",
    block_rows: int = 256,
    interpret: bool = False,
):
    """One fused FASGD update over a (R, 128) tile-aligned buffer."""
    R, lanes = params.shape
    assert lanes == LANES, params.shape
    assert R % block_rows == 0, (R, block_rows)
    grid = (R // block_rows,)
    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32), jnp.asarray(tau, jnp.float32)])
    kern = functools.partial(_kernel, gamma=gamma, beta=beta, eps=eps, variant=variant)
    f32 = jax.ShapeDtypeStruct((R, LANES), jnp.float32)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # (lr, tau)
            tile, tile, tile, tile, tile,
        ],
        out_specs=[tile, tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((R, LANES), params.dtype), f32, f32, f32],
        name="fasgd_update",
        interpret=interpret,
    )(scalars, params, grads, n, b, v)
