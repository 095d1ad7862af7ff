"""Jit'd wrapper: pads/reshapes arbitrary parameter tensors for the
wavg kernel and exposes the pytree-level Algorithm 2 entry point."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.wavg.kernel import wavg_pallas, BLOCK_N
from repro.kernels.wavg.ref import wavg_ref


def weighted_average(x, w, *, interpret: bool | None = None):
    """Weighted average over the leading (device) axis of one tensor.

    x: (K, ...) stacked parameter tensor; w: (K,) normalized weights.

    The flattened payload is zero-padded up to BLOCK_N for the kernel
    and the padded tail sliced off the (N_padded,) output before the
    reshape — exact at every block edge (n = 1, BLOCK_N, BLOCK_N + 1:
    tests/test_kernels.py). Also the entry point for the mesh-round hot
    path: `core.averaging.weighted_average_psum(impl="pallas")` calls
    this on the all-gathered flat payload, x = (K, N_total).
    """
    interpret = interpret_mode(interpret)
    k = x.shape[0]
    flat = x.reshape(k, -1)
    n = flat.shape[1]
    pad = (-n) % BLOCK_N
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    out = wavg_pallas(flat, w.astype(jnp.float32), interpret=interpret)
    return out[:n].reshape(x.shape[1:])


def weighted_average_tree(tree, w, *, interpret: bool | None = None):
    """Algorithm 2 over a stacked parameter pytree."""
    return jax.tree.map(
        lambda x: weighted_average(x, w, interpret=interpret), tree)


__all__ = ["weighted_average", "weighted_average_tree", "wavg_ref"]
