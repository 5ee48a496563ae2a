"""The LM cells' data: a pool of token sequences made on the device.

A copy of the program's `data/tokens.make_batch` (a low-rank random Markov
chain over the vocabulary, so the sequences carry signal), generating the
whole pool in one jitted call during set-up.  The pool stands in for a
prefetching loader: the window cycles through it, one round's rows at a
time, so no input is generated inside the window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit,
                   static_argnames=("rows", "seq", "vocab", "rank"))
def make_pool(key, *, rows: int, seq: int, vocab: int, rank: int = 32,
              temperature: float = 1.0):
    """(tokens [rows, seq], targets [rows, seq]) int32 from `key`."""
    k_e, k_d, k0, kseq = jax.random.split(key, 4)
    scale = 1.0 / jnp.sqrt(rank)
    emb = jax.random.normal(k_e, (vocab, rank)) * scale
    dec = jax.random.normal(k_d, (rank, vocab)) * scale
    first = jax.random.randint(k0, (rows,), 0, vocab)

    def tick(cur, k):
        nxt = jax.random.categorical(k, (emb[cur] @ dec) / temperature,
                                     axis=-1)
        return nxt, nxt

    _, seq_ = jax.lax.scan(tick, first, jax.random.split(kseq, seq))
    full = jnp.concatenate([first[None], seq_], axis=0).T.astype(jnp.int32)
    return full[:, :-1], full[:, 1:]
