"""Plain reference for the FRED cells: the paper's protocol, one window at a time.

Nothing here comes from the program.  A FRED window is K client events
that all read the server as it stood before the window: event k's client
c_k (uniform over the λ clients) computes the gradient of the 784-200-10
MLP's NLL on μ rows at the parameters it fetched last, with staleness
τ_k = max(T − ts_{c_k}, 1).  The server then takes one step of the
configured rule, T grows by K, and every client of the window fetches the
new parameters.

- fasgd (arXiv:1601.04033 eqs. 4–7): ḡ = mean_k g_k; n ← γn + (1−γ)ḡ²;
  b ← γb + (1−γ)ḡ; v ← βv + (1−β)·sqrt(max(n − b², 0) + ε);
  θ ← θ − Σ_k α/(v·τ_k + ε)·g_k.
- asgd (eq. 1): the same statistics, θ ← θ − Σ_k α·g_k.

The fleet is kept as what each client holds: the version of the server
parameters it fetched last, so only the windows' own versions are stored.
Which client fires and which rows it draws come from the window's event
keys, split as the traffic defines them: (dispatch, rows, push, fetch).
The cells gate nothing, so the push and fetch keys are unused here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def mlp_loss(params, x, y):
    """Mean NLL of a ReLU MLP, params [(w, b), ...]."""
    h = x
    for i, (w, b) in enumerate(params):
        h = h @ w + b
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    logp = h - jax.scipy.special.logsumexp(h, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def event_draws(keys, lam: int, mu: int, n_rows: int):
    """(clients [K], rows [K, μ]) of a window's event keys."""
    def one(key):
        k_disp, k_rows, _, _ = jax.random.split(key, 4)
        return (jax.random.randint(k_disp, (), 0, lam),
                jax.random.randint(k_rows, (mu,), 0, n_rows))
    return jax.vmap(one)(keys)


def _window_grads(copies, x, y, rows, dtype):
    xb = x[rows].astype(dtype)                   # [K, μ, 784]
    yb = y[rows]
    return jax.vmap(jax.value_and_grad(mlp_loss))(copies, xb, yb)


def run_windows(params0, x, y, window_keys, *, lam, mu, rule, lr, gamma,
                beta, eps, dtype=jnp.float32, keep=None):
    """Follow the first windows; returns per-window mean loss, the leaf
    norms of the first window's mean gradient, the final parameters and
    the fleet: every version of the server's parameters (`versions`), the
    version each client holds (`held`) and the T of its fetch (`ts`).

    `params0` is [(w, b), ...] float32; the reference computes in `dtype`
    (float32 at the highest matmul precision; a lower one for the control).
    `keep(k)` -> bool array [K] drops events (a planted fault: half of the
    batch left out, the mean taken over the rest).
    """
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)
    theta = cast(params0)
    zeros = jax.tree.map(jnp.zeros_like, theta)
    n, b, v = zeros, zeros, jax.tree.map(jnp.ones_like, theta)
    versions = [theta]
    held = np.zeros(lam, np.int64)               # version each client holds
    ts = np.zeros(lam, np.int64)                 # T at that fetch
    T = 0
    losses, gbar0 = [], None
    grads_fn = jax.jit(_window_grads, static_argnames=("dtype",))
    for keys in window_keys:
        clients, rows = event_draws(keys, lam, mu, x.shape[0])
        clients = np.asarray(clients)
        stack = jax.tree.map(lambda *a: jnp.stack(a), *versions)
        copies = jax.tree.map(lambda a: a[held[clients]], stack)
        with jax.default_matmul_precision("highest"):
            loss, g = grads_fn(copies, x, y, rows, dtype)
        K = clients.shape[0]
        m = np.ones(K, bool) if keep is None else np.asarray(keep(K))
        mf = jnp.asarray(m, dtype)
        losses.append(float(jnp.sum(loss * mf) / m.sum()))
        tau = jnp.asarray(np.maximum(T - ts[clients], 1), dtype)
        gbar = jax.tree.map(
            lambda l: jnp.einsum("k,k...->...", mf, l,
                                 precision="highest") / m.sum(), g)
        if gbar0 is None:
            gbar0 = [float(jnp.sqrt(jnp.sum(jnp.square(
                l.astype(jnp.float32))))) for l in jax.tree.leaves(gbar)]
        n = jax.tree.map(lambda a, c: gamma * a + (1 - gamma) * c * c, n, gbar)
        b = jax.tree.map(lambda a, c: gamma * a + (1 - gamma) * c, b, gbar)
        v = jax.tree.map(
            lambda a, nn, bb: beta * a + (1 - beta) * jnp.sqrt(
                jnp.maximum(nn - bb * bb, 0) + eps), v, n, b)

        def delta(vl, gl):
            w = mf.reshape((-1,) + (1,) * (gl.ndim - 1))
            if rule == "fasgd":
                t = tau.reshape(w.shape)
                return jnp.sum(w * lr / (vl[None] * t + eps) * gl, axis=0)
            if rule == "asgd":
                return lr * jnp.sum(w * gl, axis=0)
            raise ValueError(f"no reference for rule {rule!r}")
        theta = jax.tree.map(lambda th, vl, gl: th - delta(vl, gl),
                             theta, v, g)
        T += int(m.sum())
        versions.append(theta)
        held[clients] = len(versions) - 1
        ts[clients] = T
    return {"losses": losses, "gbar": gbar0, "params": theta,
            "versions": versions, "held": held, "ts": ts}
