"""Megatron-style tensor-parallel collectives for shard_map slices.

Inside a mesh slice the `model` axis is MANUAL (jax.shard_map), so the
classic Megatron f/g operators are expressed as custom-vjp pairs over
`lax.psum` / `lax.all_gather` instead of GSPMD sharding constraints:

  `copy_to_tp`     — Megatron "f": identity forward, psum backward.
      Marks a REPLICATED activation entering a column-parallel matmul;
      the backward all-reduce sums each rank's partial dx.
  `reduce_from_tp` — Megatron "g": psum forward, identity backward.
      Closes a row-parallel matmul: the forward all-reduce sums the
      partial products over the sharded contraction dim, and the
      (replicated) cotangent flows straight through.
  `gather_from_tp` — all_gather forward, local-slice backward.
      Rematerializes a full activation from a column-parallel output
      when the next op needs the whole feature dim.

Why custom_vjp instead of differentiating raw `lax.psum`: under
`check_vma=False` JAX transposes collectives mechanically,
which silently DROPS the cross-rank dx sum of a column-parallel matmul
(each rank's local AD only sees its own partial product). The pairs
below pin the collective placement on both sides of the tape.

All three are identity when `axis` is None, so TP-aware model code runs
unchanged outside shard_map (tp=1, the host oracle, the stacked layout).
"""
from __future__ import annotations

import functools

import jax


# custom_vjp calling convention: fwd takes the PRIMAL argument order
# (nondiff args in place); bwd takes the nondiff args FIRST, then
# residuals, then the cotangent.

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _copy_to_tp(x, axis):
    return x


def _copy_fwd(x, axis):
    return x, None


def _copy_bwd(axis, _res, g):
    return (jax.lax.psum(g, axis),)


_copy_to_tp.defvjp(_copy_fwd, _copy_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _reduce_from_tp(x, axis):
    return jax.lax.psum(x, axis)


def _reduce_fwd(x, axis):
    return jax.lax.psum(x, axis), None


def _reduce_bwd(axis, _res, g):
    return (g,)


_reduce_from_tp.defvjp(_reduce_fwd, _reduce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gather_from_tp(x, axis, dim):
    return jax.lax.all_gather(x, axis, axis=dim, tiled=True)


def _gather_fwd(x, axis, dim):
    return _gather_from_tp(x, axis, dim), x.shape[dim]


def _gather_bwd(axis, dim, local, g):
    rank = jax.lax.axis_index(axis)
    return (jax.lax.dynamic_slice_in_dim(g, rank * local, local, axis=dim),)


_gather_from_tp.defvjp(_gather_fwd, _gather_bwd)


def copy_to_tp(x, axis):
    """Identity fwd / psum bwd (column-parallel input). No-op axis=None."""
    return x if axis is None else _copy_to_tp(x, axis)


def reduce_from_tp(x, axis):
    """psum fwd / identity bwd (row-parallel output). No-op axis=None."""
    return x if axis is None else _reduce_from_tp(x, axis)


def gather_from_tp(x, axis, dim=-1):
    """all_gather fwd / own-slice bwd (column-parallel output gather).
    No-op when axis is None."""
    return x if axis is None else _gather_from_tp(x, axis, dim % x.ndim)


def tp_rank(axis):
    """This slice's index on the model axis (0 when axis is None)."""
    return 0 if axis is None else jax.lax.axis_index(axis)
