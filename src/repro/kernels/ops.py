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


# VMEM one grid step of `fused_event_apply` may fill with its blocks: every
# operand's block double-buffered by the pipeline, and the body's float32
# temporaries.  Under the 16 MiB a TPU kernel's scoped VMEM holds by default.
APPLY_VMEM_BUDGET = 12 << 20
_F32_TEMPS = 8          # gbar, n1, b1, v1, std, scale, the event's g, acc


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def sublanes(dtype) -> int:
    """Rows of a dtype's native (sublanes, 128) tile: 8 f32, 16 bf16."""
    return 32 // jnp.dtype(dtype).itemsize


def apply_blocks(R: int, C: int, K: int, param_dtype, grad_dtype,
                 block_rows: int = 0):
    """(block_rows, block_cols) of `fused_event_apply` on an (R, C) view.

    Derived from one VMEM budget: a block of br × bc elements holds, per
    element, θ in and out, n/b/v in and out (float32) and the K gradients,
    each double-buffered, plus the body's float32 temporaries, all counted
    at the native tile's padding.  The lane block is C itself when a
    sublane-tall full-width block fits; otherwise a multiple of 128, the
    widest that fits.  The row block is R or a multiple of the sublane tile.
    Both are then evened out over their grid axis, so that a ragged last
    block wastes little.  `block_rows` > 0 overrides the row block.
    """
    p_item = jnp.dtype(param_dtype).itemsize
    g_item = jnp.dtype(grad_dtype).itemsize
    sub = max(sublanes(d) for d in (param_dtype, grad_dtype, jnp.float32))
    per_elem = 2 * (K * g_item + 2 * p_item + 6 * 4) + 4 * _F32_TEMPS
    cap = max(APPLY_VMEM_BUDGET // per_elem, sub * LANES)  # a block's elements
    if sub * _round_up(C, LANES) <= cap:
        bc = C
    else:
        lane_tiles = -(-C // LANES)
        n = -(-lane_tiles // (cap // (sub * LANES)))
        bc = -(-lane_tiles // n) * LANES
    if block_rows:
        br = block_rows
    else:
        br = max(sub, cap // _round_up(bc, LANES) // sub * sub)
        if br < R:
            br = _round_up(-(-R // -(-R // br)), sub)
    if br >= R:
        return R, bc
    return max(sub, br // sub * sub), bc


def _kernel_view(shape, dtypes):
    """The leaf's shape as the kernel reads it, by bitcasts alone: (1, C)
    for a vector, (R, C) for a matrix, and for a stacked leaf its leading
    dims merged into the rows where its second-minor dim is a multiple of
    every operand's sublane tile, else into one squeezed axis: (L, R, C)."""
    if len(shape) < 2:
        return (1, math.prod(shape))
    *lead, R, C = shape
    L = math.prod(lead)
    if L == 1:
        return (R, C)
    if R % max(sublanes(d) for d in dtypes) == 0:
        return (L * R, C)
    return (L, R, C)


# The packing around a kernel launch (leaf to padded [rows, 128] tiles and
# back for `fasgd_update` and `batched_scale_apply`; the views of
# `fused_event_apply`) runs under the `apply_pack` scope, so a device trace
# tells what it costs apart from the kernel's own time.

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
    float32.  The kernel reads and writes each leaf in its own layout
    (`_kernel_view`), in blocks that `apply_blocks` derives from the leaf's
    shape, its dtypes and K; `block_rows` > 0 overrides the row block.
    `interpret` dispatches per `_fused_event_path`.

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
        shape = p.shape
        view = _kernel_view(shape, (p.dtype, g.dtype, jnp.float32))
        # A leaf narrower than a lane tile is applied transposed, its longer
        # dim on the lanes: the kernel is elementwise, and XLA folds the
        # transpose into the layout in which the backward writes the
        # gradients, where 128 lanes would hold a handful of values.
        flip = view[-1] < LANES and view[-2] > view[-1]
        tr = (lambda x: jnp.swapaxes(x, -1, -2)) if flip else (lambda x: x)
        with jax.named_scope("apply_pack"):
            p2, n2, b2, v2 = (tr(x.reshape(view)) for x in (p, nn, bb, vv))
            g2 = tr(g.reshape((K,) + view))
        br, bc = apply_blocks(*p2.shape[-2:], K, p.dtype, g.dtype,
                              block_rows)
        outs = _fe.fused_event_apply_2d(
            p2, g2, n2, b2, v2, w, wm, t, lr, hp, block_rows=br,
            block_cols=bc, interpret=(path == "interpret"), **kw)
        with jax.named_scope("apply_pack"):
            return tuple(tr(o).reshape(shape) for o in outs)

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
