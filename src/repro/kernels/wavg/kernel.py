"""Algorithm 2 as a Pallas TPU kernel.

    out[n] = sum_k w[k] * x[k, n]        (weights pre-normalized)

The stacked parameter matrix (K, N) streams through VMEM in (K, BN)
tiles; the weighted reduction over K is a (1, K) x (K, BN) matmul on
the MXU. BN = 2048 lanes (16 sublanes x 128) keeps the tile ~0.5 MB for
K <= 64 in f32 — comfortably inside the ~16 MB A VMEM budget while deep
enough to amortize the HBM->VMEM copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 2048


def _wavg_kernel(w_ref, x_ref, o_ref):
    # w: (1, K) f32, x: (K, BN), out: (1, BN)
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    # HIGHEST: an f32 contraction on the MXU, never a bf16 pass
    o_ref[...] = jnp.dot(w, x, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wavg_pallas(x, w, *, interpret: bool = False):
    """x: (K, N) stacked parameters; w: (K,) normalized weights -> (N,)."""
    k, n = x.shape
    assert n % BLOCK_N == 0, "ops.py pads N to BLOCK_N"
    grid = (n // BLOCK_N,)
    out = pl.pallas_call(
        _wavg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0)),          # weights
            pl.BlockSpec((k, BLOCK_N), lambda i: (0, i)),    # param tile
        ],
        out_specs=pl.BlockSpec((1, BLOCK_N), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), x.dtype),
        interpret=interpret,
    )(w.reshape(1, k), x)
    return out[0]
