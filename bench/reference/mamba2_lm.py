"""Plain reference for the Mamba2 LM cells: loss and gradients in float32.

Nothing here comes from the program.  The layer follows arXiv:2405.21060
(Mamba2, one group of B/C shared by all heads):

    z, xBC, dt = split(h @ W_in)
    xBC        = silu(causal depthwise conv(xBC) + conv_b)
    x, B, C    = split(xBC);  dt = softplus(dt + dt_bias);  A = −exp(A_log)
    y_t        = Σ_{s≤t} (C_t·B_s) · exp(Σ_{s<r≤t} dt_r A) · dt_s x_s + D x_t
    out        = rmsnorm(y ⊙ silu(z)) @ W_out

with the state-space sum written in its quadratic (attention-like) form
over the whole sequence, not in the program's chunks.  A block is
h + mamba(rmsnorm(h)); the LM is embedding, the blocks, a final rmsnorm and
the unembedding, with cross-entropy over the published vocabulary (the
padded columns of a wider unembedding are left out of the softmax).

Weights arrive in their stored dtype and are computed in float32 at the
highest matmul precision.  `quant`, when given, rounds every GEMM operand
first: the control's lower precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _segsum(a):
    """S[..., t, s] = Σ_{s<r≤t} a_r for s ≤ t, −inf above the diagonal,
    summed directly (no difference of long cumulative sums)."""
    L = a.shape[-1]
    rep = jnp.broadcast_to(a[..., None], a.shape + (L,))       # [..., t, s]
    strict = jnp.tril(jnp.ones((L, L), bool), -1)
    acc = jnp.cumsum(jnp.where(strict, rep, 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((L, L), bool)), acc, -jnp.inf)


def mamba_layer(p, h, cfg, mm):
    """One Mamba2 mixer on h [L, d] (float32)."""
    d, N, P = cfg["d_model"], cfg["d_state"], cfg["headdim"]
    di = cfg["expand"] * d
    H = di // P
    zxbcdt = mm(h, p["in_proj"])
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * N],
                  zxbcdt[:, 2 * di + 2 * N:])
    W = p["conv_w"].shape[0]
    L = h.shape[0]
    pad = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
    conv = sum(pad[i:i + L] * p["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x = xbc[:, :di].reshape(L, H, P)
    B, C = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                     # [L, H]
    A = -jnp.exp(p["A_log"])                                    # [H]
    decay = jnp.exp(_segsum((dt * A).T))                        # [H, t, s]
    scores = jnp.einsum("tn,sn->ts", C, B, precision="highest")
    y = jnp.einsum("ts,hts,shp->thp", scores, decay, x * dt[..., None],
                   precision="highest")
    y = y + p["D"][None, :, None] * x
    y = _rms(y.reshape(L, di) * jax.nn.silu(z), p["out_norm"], cfg["norm_eps"])
    return mm(y, p["out_proj"])


def lm_loss(params, tokens, targets, cfg, quant=None):
    """Mean next-token cross-entropy of one sequence (tokens [L])."""
    q = quant or (lambda a: a)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(F32), t)
    mm = lambda a, w: jnp.dot(q(a), q(w), precision="highest")
    h = params["embed"][tokens].astype(F32)
    layers = f32(params["layers"])
    eps = cfg["norm_eps"]

    @jax.checkpoint
    def block(h, lp):
        return h + mamba_layer(lp["mamba"], _rms(h, lp["ln"], eps), cfg, mm)

    for i in range(cfg["n_layer"]):
        h = block(h, jax.tree.map(lambda a: a[i], layers))
    h = _rms(h, params["final_norm"].astype(F32), eps)
    V = cfg["vocab_size"]
    logits = mm(h, params["unembed"].astype(F32)[:, :V])
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def leaf_norms(tree):
    """Euclidean norm of every leaf, in flatten order, in float32."""
    return [float(jnp.sqrt(jnp.sum(jnp.square(l.astype(F32)))))
            for l in jax.tree.leaves(tree)]


def run_rounds(params0, rounds, cfg, *, lr, gamma, beta, eps, quant=None,
               keep=None):
    """Follow the round trainer's first rounds under fasgd.

    Each round every client c computes its gradient at the copy it holds:
    the mean over its B sequences (`rounds[r]` is (tokens [C, B, L],
    targets [C, B, L])) of each sequence's mean-token loss.  The server
    takes one step of eqs. 4–7 with the mean gradient for the statistics
    and Σ_c α/(v·τ_c + ε)·g_c for the weights, T grows by the pushes, and
    every client fetches.  The server's parameters and statistics are
    stored in the weights' dtype and computed in float32.  Returns
    per-round mean loss, the leaf norms of the first round's mean gradient
    and the final parameters.  `keep` -> bool [C] drops clients (a planted
    fault).
    """
    dt = jax.tree.leaves(params0)[0].dtype
    f32 = lambda t: jax.tree.map(lambda a: a.astype(F32), t)
    store = lambda t: jax.tree.map(lambda a: a.astype(dt), t)
    grad = jax.jit(jax.value_and_grad(
        lambda p, tok, tgt: lm_loss(p, tok, tgt, cfg, quant)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    def client_grad(held, tokens, targets):
        """Mean loss and gradient over one client's sequences."""
        loss_sum, gsum = 0.0, None
        for tok, tgt in zip(tokens, targets):
            loss, g = grad(held, tok, tgt)
            loss_sum += float(loss)
            gsum = g if gsum is None else add(gsum, g)
        B = len(tokens)
        return loss_sum / B, jax.tree.map(lambda a: a / B, gsum)

    theta = params0
    n = b = jax.tree.map(jnp.zeros_like, params0)
    v = jax.tree.map(jnp.ones_like, params0)
    T, losses, gbar0 = 0, [], None
    C = rounds[0][0].shape[0]
    ts = [0] * C
    for tokens, targets in rounds:
        m = [True] * C if keep is None else list(keep(C))
        live = [c for c in range(C) if m[c]]
        held = f32(theta)          # every client fetched the last step
        grads, loss_sum = [], 0.0
        for c in live:
            loss, g = client_grad(held, tokens[c], targets[c])
            loss_sum += loss
            grads.append(g)
        del held
        losses.append(loss_sum / len(live))
        gbar = jax.tree.map(lambda *a: sum(a) / len(live), *grads)
        if gbar0 is None:
            gbar0 = leaf_norms(gbar)
        n32 = jax.tree.map(lambda a, g: gamma * a.astype(F32)
                           + (1 - gamma) * g * g, n, gbar)
        b32 = jax.tree.map(lambda a, g: gamma * a.astype(F32)
                           + (1 - gamma) * g, b, gbar)
        del gbar
        v32 = jax.tree.map(
            lambda a, nn, bb: beta * a.astype(F32) + (1 - beta) * jnp.sqrt(
                jnp.maximum(nn - bb * bb, 0) + eps), v, n32, b32)
        n, b, v = store(n32), store(b32), store(v32)
        del n32, b32
        delta = None
        for c, g in zip(live, grads):
            tau = max(T - ts[c], 1)
            d = jax.tree.map(lambda vl, gl: lr / (vl * tau + eps) * gl,
                             v32, g)
            delta = d if delta is None else add(delta, d)
            del d
        del grads, v32
        theta = store(jax.tree.map(lambda a, d: a.astype(F32) - d,
                                   theta, delta))
        del delta
        T += len(live)
        ts = [T] * C
    return {"losses": losses, "gbar": gbar0, "params": theta}
