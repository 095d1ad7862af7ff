"""Required FLOPs and bytes of one communication round of the proposed
protocol on a DCGAN, counted from the configuration's shapes alone.

Convention: a convolution costs 2 FLOPs per multiply-add of its dense
definition. A transposed convolution of kernel k and stride s is counted
by its INPUT pixels: in_pixels x C_in x C_out x k^2 x 2, the work of
scattering every input pixel through the kernel. Lowered as an
lhs-dilated convolution, its dense count would include the inserted
zeros (about s^2 times more); those are not required work. The
discriminator's last layer, a valid 4x4 convolution to one channel, is
a contraction of 2 x 4 x 4 x C FLOPs. Batch norm, activations, the loss,
SGD and the uplink quantizer are not counted: they do not run on the
MXU and are a small share of the work.

Per round (K workers, n_d local steps of m_k real and m_k fake images,
n_g server steps of M images):
  Algorithm 1  K x n_d x (2 m_k) x 3 x D_fwd   (forward + backward)
  fakes        chips_with_workers x n_d x m_k x G_fwd
               (the shared noise makes every worker's fakes identical,
               so one generator forward per step per chip is required)
  Algorithm 3  n_g x M x (3 G_fwd + 2 D_fwd)   (G forward and backward;
               D forward and the backward to its input), counted once
               per round even where a mesh replicates it.
"""
from __future__ import annotations

import math


def _stages(image_size: int) -> int:
    n = int(math.log2(image_size)) - 2
    if 2 ** (n + 2) != image_size:
        raise ValueError(f"image_size {image_size} is not a power of two >= 8")
    return n


def disc_forward_flops(cfg: dict) -> float:
    """One image through the discriminator."""
    n, size = _stages(cfg["image_size"]), cfg["image_size"]
    chain = [cfg["ndf"] * 2 ** k for k in range(n)]
    c_in, flops = cfg["nc"], 0.0
    for c_out in chain:               # 4x4, stride 2, pad 1: size halves
        size //= 2
        flops += 2.0 * size * size * c_out * c_in * 16
        c_in = c_out
    return flops + 2.0 * 16 * c_in    # 4x4 valid head to one logit


def gen_forward_flops(cfg: dict) -> float:
    """One latent vector through the generator (transposed convs counted
    by input pixels)."""
    n = _stages(cfg["image_size"])
    chain = [cfg["ngf"] * 2 ** k for k in range(n - 1, -1, -1)]
    flops = 2.0 * 1 * cfg["nz"] * chain[0] * 16       # 1x1 -> 4x4
    size = 4
    for c_in, c_out in zip(chain, chain[1:] + [cfg["nc"]]):
        flops += 2.0 * size * size * c_in * c_out * 16
        size *= 2
    return flops


def disc_params(cfg: dict) -> int:
    """Parameters of the discriminator: the Algorithm-2 payload N."""
    n = _stages(cfg["image_size"])
    chain = [cfg["ndf"] * 2 ** k for k in range(n)]
    count, c_in = 16 * cfg["nc"] * chain[0], chain[0]
    for c_out in chain[1:]:
        count += 16 * c_in * c_out + 2 * c_out       # conv + bn scale/bias
        c_in = c_out
    return count + 16 * c_in


def round_flops(cfg: dict, traffic: dict, chips: int) -> dict:
    """Required FLOPs of one round, by part and in total."""
    d, g = disc_forward_flops(cfg), gen_forward_flops(cfg)
    k, n_d, n_g = traffic["workers"], traffic["n_d"], traffic["n_g"]
    m, big_m = traffic["m_k"], traffic["M"]
    chips_with_workers = min(chips, k)
    parts = {
        "algorithm1": k * n_d * 2 * m * 3 * d,
        "fakes": chips_with_workers * n_d * m * g,
        "algorithm3": n_g * big_m * (3 * g + 2 * d),
    }
    parts["total"] = sum(parts.values())
    return parts


def wavg_bytes(cfg: dict, traffic: dict) -> float:
    """HBM bytes one Algorithm-2 `wavg` call needs: the (K, N) f32 uploads
    read, the (N,) average written, the K weights read; N unpadded."""
    k, n = traffic["workers"], disc_params(cfg)
    return 4.0 * (k * n + n + k)
