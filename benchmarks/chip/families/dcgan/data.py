"""The DCGAN family's image traffic, made on the device from the seed.

A copy of the synthetic generator the repository uses for its datasets:
each dataset is a mixture of `n_modes` smooth patterns (a sum of four
low-frequency 2-D cosines with random frequencies, phases and
per-channel amplitudes), one pattern per image plus Gaussian noise,
squashed into (-1, 1) by tanh. It is kept here so that a change to the
program cannot change the inputs the benchmark feeds it, and it runs on
the device, one worker's shard at a time, so that set-up makes gigabytes
of images in one call without the host.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

NOISE = 0.15


def mode_patterns(key, n_modes: int, size: int, channels: int):
    """(n_modes, size, size, channels) float32 patterns."""
    yy, xx = jnp.meshgrid(jnp.arange(size, dtype=jnp.float32),
                          jnp.arange(size, dtype=jnp.float32), indexing="ij")

    def one(k):
        kf, kp, ka = jax.random.split(k, 3)
        freq = jax.random.uniform(kf, (4, 2), minval=0.5, maxval=3.0)
        phase = jax.random.uniform(kp, (4, 2), minval=0.0,
                                   maxval=2 * math.pi)
        amp = jax.random.uniform(ka, (4, channels), minval=0.3, maxval=1.0)
        wave = (jnp.cos(2 * math.pi * freq[:, 0, None, None] * yy / size
                        + phase[:, 0, None, None])
                * jnp.cos(2 * math.pi * freq[:, 1, None, None] * xx / size
                          + phase[:, 1, None, None]))          # (4, H, W)
        return jnp.einsum("thw,tc->hwc", wave, amp,
                          precision=jax.lax.Precision.HIGHEST)

    return jax.vmap(one)(jax.random.split(key, n_modes))


def worker_shard(key, worker, n_images: int, size: int, channels: int,
                 n_modes: int):
    """Worker `worker`'s (n_images, size, size, channels) float32 shard."""
    modes = mode_patterns(jax.random.fold_in(key, 0), n_modes, size,
                          channels)
    kl, kn = jax.random.split(jax.random.fold_in(key, worker + 1))
    labels = jax.random.randint(kl, (n_images,), 0, n_modes)
    noise = jax.random.normal(kn, (n_images, size, size, channels))
    return jnp.tanh(modes[labels] + NOISE * noise)


def make_shards(key, workers: int, n_images: int, cfg: dict, mesh=None):
    """(workers, n_images, H, W, C) shards on the device. With a mesh the
    array is made sharded over its "data" axis, one worker per chip,
    each chip making its own shard; otherwise the shards are made one
    after another on the default device."""
    args = (n_images, cfg["image_size"], cfg["nc"], cfg["n_modes"])
    if mesh is None:
        return jax.jit(lambda k: jax.lax.map(
            lambda w: worker_shard(k, w, *args), jnp.arange(workers)))(key)

    def local(k):
        return worker_shard(k, jax.lax.axis_index("data"), *args)[None]

    return jax.jit(
        jax.shard_map(local, mesh=mesh, in_specs=P(), out_specs=P(("data",))),
        out_shardings=NamedSharding(mesh, P(("data",))))(key)
