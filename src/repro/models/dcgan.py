"""DCGAN — the paper's experimental model [arXiv:1511.06434].

With the default config (nz=100, ngf=ndf=64, nc=3, 64x64) the parameter
counts match the paper's Section IV exactly:
  generator     3,576,704
  discriminator 2,765,568
(bias-free convs; batch-norm scale+bias counted).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import nn
from repro.configs.dcgan import DCGANConfig


def _n_stages(image_size: int) -> int:
    n = int(math.log2(image_size)) - 2      # 64 -> 4, 32 -> 3
    assert 2 ** (n + 2) == image_size, "image_size must be a power of two >= 8"
    return n


def generator_init(key, cfg: DCGANConfig):
    n = _n_stages(cfg.image_size)
    chain = [cfg.ngf * 2 ** k for k in range(n - 1, -1, -1)]  # e.g. [512,256,128,64]
    keys = jax.random.split(key, n + 1)
    layers = []
    # initial: z (1x1) -> 4x4 x chain[0]
    layers.append({"conv": nn.conv_transpose2d_init(keys[0], cfg.nz, chain[0], 4),
                   "bn": nn.batchnorm_init(chain[0])})
    for i in range(n - 1):
        layers.append({"conv": nn.conv_transpose2d_init(keys[i + 1], chain[i], chain[i + 1], 4),
                       "bn": nn.batchnorm_init(chain[i + 1])})
    layers.append({"conv": nn.conv_transpose2d_init(keys[n], chain[-1], cfg.nc, 4)})
    return {"layers": layers}


def generator_apply(params, cfg: DCGANConfig, z):
    """z: (b, nz) -> images (b, H, W, nc) in [-1, 1]."""
    x = z.reshape(z.shape[0], 1, 1, cfg.nz)
    layers = params["layers"]
    x = nn.conv_transpose2d_apply(layers[0]["conv"], x, stride=1, padding=0)
    x = jax.nn.relu(nn.batchnorm_apply(layers[0]["bn"], x))
    for layer in layers[1:-1]:
        x = nn.conv_transpose2d_apply(layer["conv"], x, stride=2, padding=1)
        x = jax.nn.relu(nn.batchnorm_apply(layer["bn"], x))
    x = nn.conv_transpose2d_apply(layers[-1]["conv"], x, stride=2, padding=1)
    return jnp.tanh(x)


def discriminator_init(key, cfg: DCGANConfig):
    n = _n_stages(cfg.image_size)
    chain = [cfg.ndf * 2 ** k for k in range(n)]              # e.g. [64,128,256,512]
    keys = jax.random.split(key, n + 1)
    layers = [{"conv": nn.conv2d_init(keys[0], cfg.nc, chain[0], 4)}]  # no BN on 1st
    for i in range(n - 1):
        layers.append({"conv": nn.conv2d_init(keys[i + 1], chain[i], chain[i + 1], 4),
                       "bn": nn.batchnorm_init(chain[i + 1])})
    layers.append({"conv": nn.conv2d_init(keys[n], chain[-1], 1, 4)})
    return {"layers": layers}


def discriminator_apply(params, cfg: DCGANConfig, images):
    """images: (b, H, W, nc) -> logits (b,)."""
    x = images
    layers = params["layers"]
    x = jax.nn.leaky_relu(nn.conv2d_apply(layers[0]["conv"], x), 0.2)
    for layer in layers[1:-1]:
        x = nn.conv2d_apply(layer["conv"], x)
        x = jax.nn.leaky_relu(nn.batchnorm_apply(layer["bn"], x), 0.2)
    # The last layer is a valid 4x4 conv over the 4x4 map with one output
    # channel: a single output pixel, i.e. a contraction over (h, w, c).
    w = layers[-1]["conv"]["w"]
    assert x.shape[1:] == w.shape[:3], (x.shape, w.shape)
    return jnp.einsum("bhwc,hwc->b", x, w[..., 0].astype(x.dtype))


def gan_init(key, cfg: DCGANConfig):
    kg, kd = jax.random.split(key)
    return {"gen": generator_init(kg, cfg), "disc": discriminator_init(kd, cfg)}
