"""What the drivers share: keys from the seed, the pacing of a window, and
norms of parameter trees."""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key for every whole number: PRNGKey keeps only 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def quiet(name):
    """A note that records nothing."""
    return contextlib.nullcontext()


def back_to_back(launch, finite, *, until=None, count=None, note=None):
    """Launch calls back to back, one in flight while the one before it is
    waited for, until `count` calls ran or, once `until` seconds have
    passed, the call in flight ends.

    `launch()` dispatches one call and returns a small output of it;
    `finite(out)` waits for that output and says whether it is finite.
    `note(name)` is a context manager around each dispatch ("launch") and
    each wait ("sync"), e.g. a profiler annotation.  Returns (calls,
    seconds, calls whose output was not finite, the seconds from the start
    to the end of each wait).
    """
    note = note or quiet
    t0 = time.perf_counter()
    last, runs, failed, ends = launch(), 1, 0, []
    while True:
        go_on = (runs < count if count is not None
                 else time.perf_counter() - t0 < until)
        if go_on:
            nxt, runs = launch(), runs + 1
        with note("sync"):
            failed += int(not finite(last))
        ends.append(time.perf_counter() - t0)
        if not go_on:
            break
        last = nxt
    return runs, ends[-1], failed, ends


@jax.jit
def leaf_norms(tree):
    """The float32 norm of every leaf, in flatten order."""
    return [jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
            for l in jax.tree.leaves(tree)]


@jax.jit
def _change(after, before):
    return jax.tree.map(lambda a, b: a.astype(jnp.float32)
                        - b.astype(jnp.float32), after, before)


def change_norms(after, before):
    """‖after − before‖ of every leaf, in float32, on the device."""
    return [float(n) for n in leaf_norms(_change(after, before))]
