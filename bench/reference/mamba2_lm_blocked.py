"""Plain reference for the Mamba2 LM at its full depth, placed over the
devices so that it fits: the same mathematics as `mamba2_lm`.

The layer is `mamba2_lm.mamba_layer`, imported and unchanged, in float32 at
the highest matmul precision; the LM around it is `mamba2_lm.lm_loss`'s,
with the blocks run by a scan whose body is recomputed in the backward
(`jax.checkpoint`), so that the backward holds one layer's segment sums at
a time.  Nothing of the program is used.

At 48 layers a float32 copy of the parameters is 5.8 GB, and the rounds
hold the weights, their statistics n, b, v and the clients' gradients at
once: more than one 16 GB chip holds.  So θ, n, b, v and every gradient are
placed over all the devices there are by a `NamedSharding` on their last
dimension (`placement`), on a mesh of this module's own; XLA partitions
the rest.

`run_rounds` keeps `mamba2_lm.run_rounds`' signature and semantics.  Two
things differ in how it holds them: the clients' gradients are summed as
they come, one sum for each staleness τ among them (the weight step is
linear in the gradients of one τ), where `mamba2_lm` keeps every client's;
and each server step is one jitted update of all leaves with the old
state donated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from bench.reference.mamba2_lm import F32, _rms, mamba_layer

AXIS = "ref"


def placement(tree):
    """A `NamedSharding` for every leaf of `tree`: the last dimension split
    over all the devices where it divides, else the leaf whole on each."""
    devices = jax.devices()
    mesh = Mesh(np.array(devices), (AXIS,))

    def spec(a):
        if a.ndim and a.shape[-1] % len(devices) == 0:
            return PartitionSpec(*[None] * (a.ndim - 1), AXIS)
        return PartitionSpec()
    return jax.tree.map(lambda a: NamedSharding(mesh, spec(a)), tree)


def place(tree):
    """`tree` on the devices as `placement` says."""
    return jax.device_put(tree, placement(tree))


def lm_loss(params, tokens, targets, cfg, quant=None):
    """Mean next-token cross-entropy of one sequence (tokens [L])."""
    q = quant or (lambda a: a)
    mm = lambda a, w: jnp.dot(q(a), q(w), precision="highest")
    f32 = lambda t: jax.tree.map(lambda a: a.astype(F32), t)
    h = params["embed"][tokens].astype(F32)
    eps = cfg["norm_eps"]

    @jax.checkpoint
    def block(h, lp):
        lp = f32(lp)
        return h + mamba_layer(lp["mamba"], _rms(h, lp["ln"], eps), cfg,
                               mm), None

    h, _ = jax.lax.scan(block, h, params["layers"])
    h = _rms(h, params["final_norm"].astype(F32), eps)
    V = cfg["vocab_size"]
    logits = mm(h, params["unembed"].astype(F32)[:, :V])
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def run_rounds(params0, rounds, cfg, *, lr, gamma, beta, eps, quant=None,
               keep=None):
    """Follow the round trainer's first rounds under fasgd, as
    `mamba2_lm.run_rounds` does, with the state placed over the devices.

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
    at = placement(params0)
    f32 = jax.jit(lambda t: jax.tree.map(lambda a: a.astype(F32), t),
                  out_shardings=at)
    grad = jax.jit(jax.value_and_grad(
        lambda p, tok, tgt: lm_loss(p, tok, tgt, cfg, quant)),
        out_shardings=(None, at))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=0, out_shardings=at)

    @functools.partial(jax.jit, static_argnums=(6,),
                       donate_argnums=(0, 1, 2, 3), out_shardings=(
                           at, at, at, at, None))
    def server(theta, n, b, v, sums, live, taus):
        """One step of eqs. 4–7 on every leaf: `sums[k]` is the sum of the
        gradients of staleness `taus[k]`, `live` the number of pushes."""
        store = lambda a: a.astype(dt)
        gbar = jax.tree.map(lambda *s: sum(s) / live, *sums)
        n32 = jax.tree.map(lambda a, g: gamma * a.astype(F32)
                           + (1 - gamma) * g * g, n, gbar)
        b32 = jax.tree.map(lambda a, g: gamma * a.astype(F32)
                           + (1 - gamma) * g, b, gbar)
        v32 = jax.tree.map(
            lambda a, nn, bb: beta * a.astype(F32) + (1 - beta) * jnp.sqrt(
                jnp.maximum(nn - bb * bb, 0) + eps), v, n32, b32)
        delta = jax.tree.map(
            lambda vl, *s: sum(lr / (vl * tau + eps) * g
                               for tau, g in zip(taus, s)), v32, *sums)
        theta = jax.tree.map(lambda a, d: store(a.astype(F32) - d),
                             theta, delta)
        norms = [jnp.sqrt(jnp.sum(jnp.square(g)))
                 for g in jax.tree.leaves(gbar)]
        return (theta, jax.tree.map(store, n32), jax.tree.map(store, b32),
                jax.tree.map(store, v32), norms)

    def client_grad(held, tokens, targets):
        """Mean loss and summed gradient over one client's sequences."""
        loss_sum, gsum = 0.0, None
        for tok, tgt in zip(tokens, targets):
            loss, g = grad(held, tok, tgt)
            loss_sum += float(loss)
            gsum = g if gsum is None else add(gsum, g)
        return loss_sum / len(tokens), gsum

    theta = jax.device_put(params0, at, may_alias=False)   # donated below
    n = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                out_shardings=at)(theta)
    b = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                out_shardings=at)(theta)
    v = jax.jit(lambda t: jax.tree.map(jnp.ones_like, t),
                out_shardings=at)(theta)
    T, losses, gbar0 = 0, [], None
    rounds = [(np.asarray(tok), np.asarray(tgt)) for tok, tgt in rounds]
    C = rounds[0][0].shape[0]
    ts = [0] * C
    for tokens, targets in rounds:
        m = [True] * C if keep is None else list(keep(C))
        live = [c for c in range(C) if m[c]]
        held = f32(theta)          # every client fetched the last step
        sums, loss_sum = {}, 0.0   # τ -> Σ of the B-mean gradients
        for c in live:
            loss, g = client_grad(held, tokens[c], targets[c])
            loss_sum += loss
            g = jax.tree.map(lambda a: a / len(tokens[c]), g)
            tau = max(T - ts[c], 1)
            sums[tau] = g if tau not in sums else add(sums[tau], g)
        del held
        losses.append(loss_sum / len(live))
        taus = tuple(sorted(sums))
        theta, n, b, v, norms = server(
            theta, n, b, v, [sums.pop(t) for t in taus], float(len(live)),
            taus)
        if gbar0 is None:
            gbar0 = [float(x) for x in norms]
        T += len(live)
        ts = [T] * C
    return {"losses": losses, "gbar": gbar0, "params": theta}


__all__ = ["lm_loss", "place", "placement", "run_rounds"]
