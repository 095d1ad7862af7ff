"""Plain float32 DCGAN (arXiv:1511.06434) as the paper trains it
(arXiv:2107.08681 Sec. IV): its images, weights and networks for the
benchmark's reference, written from the papers and imported from
nowhere in the program.

What it shares with the program is the contract of a run, not code:
the parameter tree the program is handed (HWIO kernels, a transposed
convolution that correlates the zero-inserted input with the kernel as
stored, batch norm on batch statistics, a 4x4 valid head as a
contraction) and the noise the generator takes. Every matrix operation
runs at `Precision.HIGHEST`; under the "fp8" variant every convolution
and contraction takes float8 operands (`reference.mxu`): the precision
one step below the bf16 MXU passes the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.families.dcgan import data
from benchmarks.chip.reference import HI, mxu

MODEL_KEYS = ("nz", "ngf", "ndf", "nc", "image_size")


def make_shards(key, workers: int, cfg: dict, mesh=None):
    """(workers, train_images // workers, H, W, C) image shards on the
    device (`data.make_shards`)."""
    return data.make_shards(key, workers, cfg["train_images"] // workers,
                            cfg, mesh)


def _stages(image_size: int) -> int:
    return int(np.log2(image_size)) - 2


def init_params(key, cfg: dict):
    """DCGAN weights from `key`: conv kernels N(0, 0.02), batch-norm
    scale 1 and bias 0, in the program's parameter tree."""
    n = _stages(cfg["image_size"])
    g_chain = [cfg["ngf"] * 2 ** k for k in range(n - 1, -1, -1)]
    d_chain = [cfg["ndf"] * 2 ** k for k in range(n)]
    kg, kd = jax.random.split(key)
    kg, kd = jax.random.split(kg, n + 1), jax.random.split(kd, n + 1)

    def conv(k, c_in, c_out):
        return {"w": 0.02 * jax.random.normal(k, (4, 4, c_in, c_out))}

    def bn(c):
        return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}

    g_io = [(cfg["nz"], g_chain[0])] + list(zip(g_chain, g_chain[1:]))
    gen = [{"conv": conv(kg[i], a, b), "bn": bn(b)}
           for i, (a, b) in enumerate(g_io)]
    gen.append({"conv": conv(kg[n], g_chain[-1], cfg["nc"])})
    disc = [{"conv": conv(kd[0], cfg["nc"], d_chain[0])}]
    disc += [{"conv": conv(kd[i + 1], a, b), "bn": bn(b)}
             for i, (a, b) in enumerate(zip(d_chain, d_chain[1:]))]
    disc.append({"conv": conv(kd[n], d_chain[-1], 1)})
    return {"gen": {"layers": gen}, "disc": {"layers": disc}}


def noise(key, n: int, cfg: dict):
    """(n, nz) standard normal latents."""
    return jax.random.normal(key, (n, cfg["nz"]))


def _conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _conv_transpose(x, w, stride, pad):
    """Fractionally strided convolution: insert stride-1 zeros between
    input pixels, pad by k-1-pad, correlate with the kernel as stored.
    Output size (in-1)*stride - 2*pad + k."""
    k = w.shape[0]
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), ((k - 1 - pad, k - 1 - pad),) * 2,
        lhs_dilation=(stride, stride),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _batchnorm(p, x):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def generator(gen, z, cfg: dict, variant=None):
    x = z.reshape(z.shape[0], 1, 1, cfg["nz"])
    layers = gen["layers"]
    for i, layer in enumerate(layers[:-1]):
        stride, pad = (1, 0) if i == 0 else (2, 1)
        x = mxu(functools.partial(_conv_transpose, stride=stride, pad=pad),
                x, layer["conv"]["w"], variant)
        x = jax.nn.relu(_batchnorm(layer["bn"], x))
    x = mxu(functools.partial(_conv_transpose, stride=2, pad=1), x,
            layers[-1]["conv"]["w"], variant)
    return jnp.tanh(x)


def discriminator(disc, x, cfg: dict, variant=None):
    """(n,) logits of the images x."""
    layers = disc["layers"]
    conv = functools.partial(_conv, stride=2, pad=1)
    x = jax.nn.leaky_relu(mxu(conv, x, layers[0]["conv"]["w"], variant), 0.2)
    for layer in layers[1:-1]:
        x = mxu(conv, x, layer["conv"]["w"], variant)
        x = jax.nn.leaky_relu(_batchnorm(layer["bn"], x), 0.2)
    head = lambda a, w: jnp.einsum("bhwc,hwc->b", a, w[..., 0], precision=HI)
    return mxu(head, x, layers[-1]["conv"]["w"], variant)
