"""Jit'd public wrappers around the Pallas kernels.

On CPU (this container) the kernels execute in `interpret=True` mode for
correctness; on TPU they compile natively.  `interpret=None` means
auto-detect; the ``REPRO_KERNEL_INTERPRET`` env var (1/0, true/false)
overrides the auto-detection for every kernel at once — CI's kernel jobs
set it to exercise the Pallas bodies on the CPU matrix without editing
configs.  `ServerConfig.kernel_interpret` carries the same toggle
per-config and is threaded here by the engine/rule call sites.
"""
from __future__ import annotations

import math
import os
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.kernels import batched_update as _bk
from repro.kernels import fasgd_update as _fk
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_event_apply as _fe
from repro.kernels.ref import attention_ref, fused_event_apply_ref

LANES = _fk.LANES


def _env_interpret():
    """Tri-state REPRO_KERNEL_INTERPRET override: True / False / unset."""
    val = os.environ.get("REPRO_KERNEL_INTERPRET", "").strip().lower()
    if val in ("1", "true", "yes", "on"):
        return True
    if val in ("0", "false", "no", "off"):
        return False
    return None


def _auto_interpret(interpret):
    if interpret is None:
        interpret = _env_interpret()
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


# `fused_event_apply` row-block tuning table, keyed by event count K: the
# [K, rows, 128] gradient block must fit VMEM alongside the five leaf tiles,
# so deeper event batches take narrower row blocks.  Measured by the
# `block_rows` sweep in benchmarks/kernels.py; override per-config with
# ServerConfig.kernel_block_rows.
_BLOCK_ROWS_TABLE = ((8, 512), (32, 256), (128, 64), (512, 16))


def default_block_rows(num_events: int) -> int:
    for k, rows in _BLOCK_ROWS_TABLE:
        if num_events <= k:
            return rows
    return 8


# The packing around a kernel launch (leaf to padded [rows, 128] tiles and
# back) runs under the `apply_pack` scope, so a device trace tells its
# copies and pads apart from the kernel's own time.

@jax.named_scope("apply_pack")
def _pad_to_tiles(x: jax.Array, block_rows: int):
    flat = x.reshape(-1)
    tile = block_rows * LANES
    pad = (-flat.size) % tile
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANES), pad


@jax.named_scope("apply_pack")
def _pad_events(g: jax.Array, rows: int):
    """[K, ...] event gradients as [K, rows, 128], zero-padded per event."""
    K = g.shape[0]
    gflat = g.reshape(K, -1)
    pad = rows * LANES - gflat.shape[1]
    if pad:
        gflat = jnp.pad(gflat, ((0, 0), (0, pad)))
    return gflat.reshape(K, -1, LANES)


@jax.named_scope("apply_pack")
def _unpad(a: jax.Array, shape, dtype=None):
    """A kernel output's padded tiles back to the leaf's shape (and dtype)."""
    out = a.reshape(-1)[:math.prod(shape)].reshape(shape)
    return out if dtype is None else out.astype(dtype)


def fasgd_update(params: Any, grads: Any, n: Any, b: Any, v: Any, lr, tau,
                 *, gamma=0.9, beta=0.9, eps=1e-8, variant="intent",
                 block_rows: int = 256, interpret: bool | None = None):
    """Fused FASGD update over arbitrary pytrees (leaf-wise kernel launches).

    Semantically identical to `ref.fasgd_update_ref` applied per leaf.
    """
    interpret = _auto_interpret(interpret)

    def one(p, g, nn, bb, vv):
        shape, dtype = p.shape, p.dtype
        (p2, _), (g2, _) = _pad_to_tiles(p, block_rows), _pad_to_tiles(g, block_rows)
        (n2, _), (b2, _), (v2, _) = (
            _pad_to_tiles(nn, block_rows),
            _pad_to_tiles(bb, block_rows),
            _pad_to_tiles(vv, block_rows),
        )
        rows = min(block_rows, p2.shape[0])
        po, no, bo, vo = _fk.fasgd_update_2d(
            p2, g2, n2, b2, v2, lr, tau,
            gamma=gamma, beta=beta, eps=eps, variant=variant,
            block_rows=rows, interpret=interpret,
        )
        return (_unpad(po, shape, dtype), _unpad(no, shape),
                _unpad(bo, shape), _unpad(vo, shape))

    outs = jax.tree.map(one, params, grads, n, b, v)
    # outs is a pytree of 4-tuples; transpose to 4 pytrees
    treedef = jax.tree.structure(params)
    flat = jax.tree.leaves(outs, is_leaf=lambda x: isinstance(x, tuple))
    unzip = tuple(jax.tree.unflatten(treedef, [t[i] for t in flat]) for i in range(4))
    return unzip  # (params, n, b, v)


def batched_scale_apply(params: Any, grads: Any, v: Any, coeffs, taus,
                        *, masks=None, lr, eps=1e-8, mode="fasgd",
                        block_rows: int = 256,
                        interpret: bool | None = None):
    """Fused Σ_k m_k·c_k·scale(v,τ_k)·g_k parameter update over arbitrary
    pytrees.

    `grads` leaves carry a leading [K] event axis over the matching `params`
    / `v` leaves; `coeffs`/`taus`/`masks` are [K] per-event vectors — either
    one shared vector for the whole tree, or per-leaf pytrees mirroring
    `params` (per-tensor push gating / per-tensor staleness: each leaf's
    kernel launch gets its own SMEM mask and τ vector).  `masks=None` means
    the push decision is already folded into `coeffs` (the engine's 'coeff'
    dispatch pre-multiplies mask×coefficient — and any event-dedup count
    weighting — into one weight vector), so each leaf launches with one
    fewer SMEM operand.  Semantically identical to the engine's generic
    per-leaf scale_leaf reduction for rules with `batched_pallas_mode`
    ('coeff' or 'fasgd'); one HBM pass per leaf instead of K+1 broadcast
    intermediates.
    """
    interpret = _auto_interpret(interpret)
    K = jax.tree.leaves(grads)[0].shape[0]
    # Bound the [K, rows, 128] gradient block to ~4 MB of VMEM.
    rows_budget = max(8, (4 << 20) // (LANES * 4 * max(K, 1)))
    block = min(block_rows, 1 << (rows_budget.bit_length() - 1))

    params_def = jax.tree.structure(params)

    def per_leaf(x, fill=None):
        """Broadcast a shared [K] vector (or None) to one entry per leaf."""
        if x is None:
            x = fill
        if x is None:
            return [None] * params_def.num_leaves
        if jax.tree.structure(x) == params_def:
            return jax.tree.leaves(x)
        return [x] * params_def.num_leaves

    coeff_leaves = per_leaf(coeffs)
    tau_leaves = per_leaf(taus)
    mask_leaves = per_leaf(masks)

    def one(p, g, vv, coeff, tau, mask):
        shape, dtype = p.shape, p.dtype
        (p2, _), (v2, _) = _pad_to_tiles(p, block), _pad_to_tiles(vv, block)
        g2 = _pad_events(g, p2.shape[0])
        rows = min(block, p2.shape[0])
        po = _bk.batched_scale_apply_2d(
            p2, g2, v2, coeff, tau, lr, masks=mask, eps=eps, mode=mode,
            block_rows=rows, interpret=interpret)
        return _unpad(po, shape, dtype)

    outs = [one(p, g, vv, c, t, m) for p, g, vv, c, t, m in zip(
        jax.tree.leaves(params), jax.tree.leaves(grads), jax.tree.leaves(v),
        coeff_leaves, tau_leaves, mask_leaves)]
    return jax.tree.unflatten(params_def, outs)


def _fused_event_path(interpret) -> str:
    """Dispatch for `fused_event_apply`: 'pallas' | 'interpret' | 'xla'.

    Explicit True forces the Pallas kernel in interpret mode (CPU-testable
    kernel body — CI correctness); explicit False forces the native compile;
    None auto-detects — native Pallas on TPU, otherwise the XLA streaming
    reference (`ref.fused_event_apply_ref`), which has the same semantics
    but realistic off-TPU *timing* (interpret mode is an emulator, far too
    slow to benchmark).
    """
    if interpret is None:
        interpret = _env_interpret()
    if interpret is True:
        return "interpret"
    if interpret is False:
        return "pallas"
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def fused_event_apply(params: Any, grads: Any, n: Any, b: Any, v: Any,
                      weights, wmean, taus, has_push, *, lr,
                      gamma=0.9, beta=0.9, eps=1e-8, variant="intent",
                      mode="fasgd", track_stats=True, block_rows: int = 0,
                      interpret: bool | None = None, mesh=None,
                      leaf_specs=None):
    """One-kernel K-event server apply over arbitrary pytrees.

    Per leaf, ONE launch of `fused_event_apply.fused_event_apply_2d`
    consumes the whole event batch: the mean-gradient statistics step
    (eqs. 4-6, skipped when `track_stats=False`), then the weighted delta —
    per-event SMEM weight alone ('coeff' mode: mask × rule coefficient
    pre-folded by the engine) or fasgd's in-kernel eq. 7 scale against the
    post-stats v tile ('fasgd' mode).

    `grads` leaves carry a leading [K] event axis; `weights`/`wmean`/`taus`
    are [K] vectors and `has_push` a bool scalar — each either shared for
    the whole tree or a per-leaf pytree mirroring `params` (per-tensor
    gating / per-tensor staleness).  `n`/`b`/`v` must be float32 (the
    engine casts); returns (params', n', b', v') with statistics in
    float32.  `block_rows=0` uses the per-K tuned table
    (`default_block_rows`); `interpret` dispatches per `_fused_event_path`.

    The compiler cannot partition a Mosaic kernel, so under a device `mesh`
    each leaf's launch runs inside `jax.shard_map`: every device applies
    the block of the leaf that its `leaf_specs` entry (one PartitionSpec
    per leaf, flatten order; default replicated) assigns it, the K
    gradients split the same way on their trailing dims, and the [K]
    vectors replicated.  The kernel is elementwise over the leaf, so a
    block applies on its own.
    """
    path = _fused_event_path(interpret)
    K = jax.tree.leaves(grads)[0].shape[0]
    rows = block_rows or default_block_rows(K)
    # Bound the [K, rows, 128] gradient block to ~4 MB of VMEM.
    rows_budget = max(8, (4 << 20) // (LANES * 4 * max(K, 1)))
    rows = min(rows, 1 << (rows_budget.bit_length() - 1))

    params_def = jax.tree.structure(params)

    def per_leaf(x):
        """Broadcast a shared [K] vector / scalar to one entry per leaf."""
        if jax.tree.structure(x) == params_def:
            return jax.tree.leaves(x)
        return [x] * params_def.num_leaves

    w_l, wm_l, t_l, hp_l = (per_leaf(weights), per_leaf(wmean),
                            per_leaf(taus), per_leaf(has_push))

    kw = dict(gamma=gamma, beta=beta, eps=eps, variant=variant, mode=mode,
              track_stats=track_stats)

    def launch(p, g, nn, bb, vv, w, wm, t, hp):
        shape, dtype = p.shape, p.dtype
        (p2, _), (n2, _), (b2, _), (v2, _) = (
            _pad_to_tiles(p, rows), _pad_to_tiles(nn, rows),
            _pad_to_tiles(bb, rows), _pad_to_tiles(vv, rows))
        g2 = _pad_events(g, p2.shape[0])
        block = min(rows, p2.shape[0])
        po, no, bo, vo = _fe.fused_event_apply_2d(
            p2, g2, n2, b2, v2, w, wm, t, lr, hp,
            block_rows=block, interpret=(path == "interpret"), **kw)
        return (_unpad(po, shape, dtype), _unpad(no, shape),
                _unpad(bo, shape), _unpad(vo, shape))

    def one(p, g, nn, bb, vv, w, wm, t, hp, spec):
        if path == "xla":
            return fused_event_apply_ref(p, g, nn, bb, vv, w, wm, t, lr, hp,
                                         **kw)
        if mesh is None:
            return launch(p, g, nn, bb, vv, w, wm, t, hp)
        rep = PartitionSpec()
        gspec = PartitionSpec(None, *spec)
        return jax.shard_map(
            launch, mesh=mesh,
            in_specs=(spec, gspec, spec, spec, spec, rep, rep, rep, rep),
            out_specs=(spec,) * 4, check_vma=False,
        )(p, g, nn, bb, vv, w, wm, t, hp)

    if leaf_specs is None:
        leaf_specs = [PartitionSpec()] * params_def.num_leaves
    outs = [one(*leaves) for leaves in zip(
        jax.tree.leaves(params), jax.tree.leaves(grads),
        jax.tree.leaves(n), jax.tree.leaves(b), jax.tree.leaves(v),
        w_l, wm_l, t_l, hp_l, leaf_specs)]
    unzip = tuple(jax.tree.unflatten(params_def, [o[i] for o in outs])
                  for i in range(4))
    return unzip  # (params, n, b, v)


def attention(q, k, v, *, causal=True, window=0, sm_scale=None,
              block_q=128, block_k=128, interpret: bool | None = None,
              use_kernel: bool = True):
    """Flash attention if `use_kernel` else the jnp oracle (same semantics)."""
    if not use_kernel:
        return attention_ref(q, k, v, causal=causal, window=window, sm_scale=sm_scale)
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=_auto_interpret(interpret),
    )
