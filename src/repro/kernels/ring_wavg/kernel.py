"""Dequantize-and-accumulate Pallas kernel for the ring reduction.

    out[b, :] = acc[b, :] + coef[b] * q[b, :]

One grid step per ROWS wire blocks of BLOCK_N. `q` is the ENCODED
uplink payload (int16 for the paper's 16-bit quantizer, int32 for
17..31 bits, f32 for unquantized) and
`coef[b] = w_norm[src] * scale[b]` folds the source worker's normalized
Algorithm-2 weight AND its per-tensor quantization scale into one
in-register multiplier — the payload is decoded during
the accumulate, so no per-rank f32 tree is ever materialized.
`input_output_aliases` updates the f32 accumulator in place: the ring
(ops.py) calls this once per received chunk per hop.

BLOCK_N is shared with the flat `wavg` kernel so both hot paths tile
HBM->VMEM identically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.wavg.kernel import BLOCK_N


# Wire blocks per grid step: int16's native (16, 128) tile, and a
# multiple of the 8 sublanes every block's row count must be.
ROWS = 16


def _ring_accum_kernel(coef_ref, q_ref, acc_ref, o_ref):
    # coef: (ROWS, 1) f32, q: (ROWS, BN) wire dtype, acc/out: (ROWS, BN) f32
    o_ref[...] = (acc_ref[...]
                  + coef_ref[...] * q_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ring_accum_pallas(acc, q, coef, *, interpret: bool = False):
    """acc: (nb, BLOCK_N) f32 accumulator; q: (nb, BLOCK_N) wire blocks;
    coef: (nb,) f32 per-block multiplier. Returns the updated
    accumulator (aliased onto `acc`). Each grid step takes ROWS wire
    blocks (all nb when fewer); a ragged last step is masked."""
    nb, bn = acc.shape
    assert bn == BLOCK_N, "ops.py pads the wire payload to BLOCK_N"
    assert q.shape == acc.shape and coef.shape == (nb,)
    rows = min(ROWS, nb)
    return pl.pallas_call(
        _ring_accum_kernel,
        grid=(pl.cdiv(nb, rows),),
        in_specs=[
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),        # coef
            pl.BlockSpec((rows, BLOCK_N), lambda i: (i, 0)),  # wire blocks
            pl.BlockSpec((rows, BLOCK_N), lambda i: (i, 0)),  # accumulator
        ],
        out_specs=pl.BlockSpec((rows, BLOCK_N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, bn), jnp.float32),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(coef.reshape(nb, 1).astype(jnp.float32), q, acc)
