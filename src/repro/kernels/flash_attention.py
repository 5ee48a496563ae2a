"""Blockwise (flash) attention Pallas TPU kernel.

Supports causal masking, sliding windows (`window > 0` keeps each query's
last `window` keys — how dense archs run the 500k-token decode shape), and
GQA (q heads grouped over fewer kv heads) — the union of what the assigned
architectures need for the prefill shapes.

TPU adaptation notes:
 - grid is (batch, q_head, q_blocks, kv_blocks) with the kv dimension
   innermost: TPU grids execute sequentially per core, so the running
   (m, l, acc) softmax state lives in VMEM scratch and is carried across
   kv-block iterations, with `pl.when` init/flush at the ends — no HBM
   traffic for the statistics.
 - block shapes default to (128, 128): MXU-aligned on both matmul dims.
 - softmax statistics are kept (block_q, 128)-shaped so reductions stay in
   native (8, 128) vreg layout instead of 1D scalars.
 - fully-masked kv blocks are skipped with `pl.when` (they still occupy grid
   steps; a production variant would prune them with a kv index map — see
   EXPERIMENTS.md §Perf for the measured effect).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

DEFAULT_BLOCK = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, sm_scale: float, causal: bool, window: int,
            block_q: int, block_k: int, kv_len: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute positions of this block's queries/keys; queries sit at the
    # *end* of the kv axis when kv_len > q_len (decode/prefill-with-cache).
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # cheap block-level skip test (static per grid step given iq/ik):
    blk_q_max = iq * block_q + block_q - 1 + q_offset
    blk_q_min = iq * block_q + q_offset
    blk_k_min = ik * block_k
    blk_k_max = ik * block_k + block_k - 1
    live = jnp.asarray(True)
    if causal:
        live = jnp.logical_and(live, blk_k_min <= blk_q_max)
    if window > 0:
        live = jnp.logical_and(live, blk_k_max >= blk_q_min - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)          # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                  # [bq, bk]
        mask = k_pos < kv_len                         # ragged tail
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window > 0:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...][:, :1]                    # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)     # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # [bq, bk]
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                # [bq, 1]
        l_new = corr * l_scr[...][:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _flush():
        l = l_scr[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,            # [B, Hq, Lq, D]
    k: jax.Array,            # [B, Hkv, Lk, D]
    v: jax.Array,            # [B, Hkv, Lk, D]
    *,
    causal: bool = True,
    window: int = 0,         # 0 = unlimited; >0 = sliding window width
    sm_scale: float | None = None,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    B, Hq, Lq, D = q.shape
    _, Hkv, Lk, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)

    block_q = min(block_q, Lq)
    block_k = min(block_k, Lk)
    # pad seq lens up to block multiples (masked out inside the kernel)
    pad_q = (-Lq) % block_q
    pad_k = (-Lk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (Lq + pad_q) // block_q
    nk = (Lk + pad_k) // block_k
    # queries occupy the last Lq positions of the kv axis (decode semantics)
    q_offset = Lk - Lq

    kern = functools.partial(
        _kernel,
        sm_scale=sm_scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_len=Lk, q_offset=q_offset,
    )
    out = pl.pallas_call(
        kern,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Lq + pad_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
    if pad_q:
        out = out[:, :, :Lq, :]
    return out
