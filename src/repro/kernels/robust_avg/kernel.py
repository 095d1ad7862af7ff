"""Coordinate trimmed-mean as a Pallas TPU kernel — the robust variant
of the Algorithm-2 `wavg` reduction.

    out[n] = sum_{k in S_n} w[k] x[k, n] / sum_{k in S_n} w[k]

where S_n starts as the participants (w[k] > 0) and, per coordinate n,
`trim` (max, min) PAIRS of extreme values are removed — classic
coordinate-wise trimmed mean, weighted. The effective trim count is
clamped so at least one participant survives per coordinate:
pair i is removed only while n_participants >= 2 i + 3.

The stacked payload streams through VMEM in the same (K, BN) tiles as
the `wavg` kernel (BLOCK_N shared), but the reduction is a VPU
masked-select-and-reduce rather than an MXU matmul: each of the
`trim` unrolled steps finds the per-column masked max (then min) and
knocks out its FIRST row occurrence (ties broken by lowest worker
index — exactly reproducible in the numpy ref twin, and load-bearing:
free-riders replaying identical stale payloads produce real ties).

Weights are the RAW participation-aware weights (0 = dropped/straggler)
— normalization happens per coordinate inside the kernel, because the
surviving set S_n differs per coordinate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.wavg.kernel import BLOCK_N


def _trimmed_kernel(w_ref, x_ref, o_ref, *, trim: int, k: int):
    # w: (1, K) f32 raw weights, x: (K, BN), out: (1, BN)
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32).reshape(k, 1)      # (K, 1)
    part = w > 0.0                                        # (K, 1)
    inc = jnp.broadcast_to(part, x.shape)                 # (K, BN)
    ridx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    n_part = jnp.sum(part.astype(jnp.int32))

    for i in range(trim):
        # per-column constant gate: trim pair i only while a strict
        # majority of participants would survive (>= 1 row after it)
        gate = n_part >= 2 * i + 3
        big = jnp.where(inc, x, -jnp.inf)
        mx = jnp.max(big, axis=0, keepdims=True)
        is_mx = inc & (big == mx)
        first = jnp.min(jnp.where(is_mx, ridx, k), axis=0, keepdims=True)
        rem_max = is_mx & (ridx == first)
        inc_mid = inc & ~rem_max
        small = jnp.where(inc_mid, x, jnp.inf)
        mn = jnp.min(small, axis=0, keepdims=True)
        is_mn = inc_mid & (small == mn)
        first = jnp.min(jnp.where(is_mn, ridx, k), axis=0, keepdims=True)
        rem_min = is_mn & (ridx == first)
        # plain mask arithmetic: Mosaic has no select on boolean vectors
        inc = inc & ~(gate & (rem_max | rem_min))

    wk = jnp.where(inc, jnp.broadcast_to(w, x.shape), 0.0)
    num = jnp.sum(wk * x, axis=0, keepdims=True)
    den = jnp.sum(wk, axis=0, keepdims=True)
    o_ref[...] = (num / jnp.maximum(den, 1e-12)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("trim", "interpret"))
def trimmed_wavg_pallas(x, w, *, trim: int, interpret: bool = False):
    """x: (K, N) stacked payload; w: (K,) RAW weights -> (N,) f32."""
    k, n = x.shape
    assert n % BLOCK_N == 0, "ops.py pads N to BLOCK_N"
    grid = (n // BLOCK_N,)
    out = pl.pallas_call(
        functools.partial(_trimmed_kernel, trim=trim, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0)),          # weights
            pl.BlockSpec((k, BLOCK_N), lambda i: (0, i)),    # param tile
        ],
        out_specs=pl.BlockSpec((1, BLOCK_N), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(w.reshape(1, k).astype(jnp.float32), x.astype(jnp.float32))
    return out[0]
