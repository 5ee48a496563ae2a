"""The FRED cells' data: synthetic MNIST made on the device from the seed.

A copy of the program's `data/mnist.make_synth_mnist` (class-conditional
Gaussians at MNIST's geometry and pixel scale), kept here so that no later
change to the program moves the benchmark's inputs.  It takes a PRNG key in
place of an integer seed, so the whole set is one jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "dim", "num_classes"))
def make_train_set(key, *, n: int, dim: int = 784, num_classes: int = 10,
                   mean_scale: float = 1.0, noise_scale: float = 4.0,
                   feature_std: float = 0.3):
    """(x [n, dim] float32, y [n] int32) from `key`."""
    k_mean, k_x, k_y = jax.random.split(key, 3)
    means = mean_scale * jax.random.normal(k_mean, (num_classes, dim))
    rescale = feature_std / jnp.sqrt(mean_scale ** 2 + noise_scale ** 2)
    y = jax.random.randint(k_y, (n,), 0, num_classes)
    x = (means[y] + noise_scale * jax.random.normal(k_x, (n, dim))) * rescale
    return x.astype(jnp.float32), y.astype(jnp.int32)
