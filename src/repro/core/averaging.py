"""Algorithm 2 — server discriminator averaging.

    phi = (sum_{k in S} m_k phi_k) / (sum_{k in S} m_k)

Scheduling is expressed through the weight vector: w_k = m_k for
scheduled devices and 0 otherwise, so one weighted mean covers partial
participation, stragglers, and unequal sample sizes.

Four interchangeable implementations:
  * `weighted_average`      — stacked leading device axis (pjit/GSPMD path;
                              the mean over the stacked axis lowers to the
                              all-reduce when that axis is mesh-sharded)
  * `weighted_average_psum` — explicit collective for the shard_map
    (mesh-layout) path: per-leaf weighted psum with ``impl="jnp"``, or
    the mesh hot path with ``impl="pallas"`` — the local tree flattened
    into one payload, all-gathered once, and reduced by the Pallas
    `wavg` kernel (the default inside `shard_round.shard_rounds_scan`)
  * the Pallas `wavg` kernel (repro.kernels.wavg) — the MXU reduction
    both ``impl="pallas"`` paths call into (interpret mode on CPU)
  * ``impl="ring"`` (repro.kernels.ring_wavg) — chunked double-buffered
    `lax.ppermute` ring with dequantize-and-accumulate fused into the
    Pallas kernel: the quantized uplink payload stays ENCODED on the
    wire (int16 at 16 bits) and per-rank wire bytes drop from the flat
    path's K*N*4 to ~(K-1)*N*2 — the large-K scaling path. Single
    device axis, tp=1, no robust reducers (those stay flat). Pass
    ``quantize_key``/``quantize_bits`` to keep the wire encoded.

NO-SURVIVOR SEMANTICS: a round where every weight is zero (all workers
dropped) has no defined average — `_normalized`'s `max(total, 1e-12)`
guard would otherwise multiply the global by ~0. Every impl (host
stacked, jnp, pallas, robust, ring) accepts ``fallback``: a pytree
shaped like the result that is returned unchanged when the total weight
is zero, so callers keep the previous global parameters
(tests/test_no_survivor.py pins this under FaultConfig(dropout=1.0)).

ROBUST REDUCERS: ``impl`` may also name a robust aggregation method
from `repro.kernels.robust_avg` (`ROBUST_METHODS`: "trimmed_mean",
"norm_clip", "krum") with a `RobustConfig` supplying its parameters.
They ride the SAME flatten -> one all-gather -> one Pallas kernel hot
path as ``impl="pallas"`` but reduce with participation-mask-aware RAW
weights (0 = dropped worker contributes nothing, payload shape
unchanged) — the counter-measure to hostile uploads (core/faults.py).
In their identity regimes (trim=0 / clip_factor large / krum_f=0) they
reproduce the plain wavg weights bitwise.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import stages
from repro.kernels.robust_avg.ops import ROBUST_METHODS, RobustConfig


def _normalized(weights):
    weights = weights.astype(jnp.float32)
    total = jnp.sum(weights)
    return weights / jnp.maximum(total, 1e-12)


def _flatten_stacked(stacked_params):
    """Flatten a stacked pytree (leading axis K on every leaf) into one
    (K, N) f32 matrix — the SAME leaf order and per-leaf ravel as the
    psum path's per-slice concat, so stacked and mesh robust reductions
    see identical payload columns."""
    leaves, treedef = jax.tree_util.tree_flatten(stacked_params)
    k = leaves[0].shape[0]
    flat = jnp.concatenate(
        [x.reshape(k, -1).astype(jnp.float32) for x in leaves], axis=1)
    return flat, leaves, treedef


def _unflatten_row(avg_flat, leaves, treedef):
    out, off = [], 0
    for x in leaves:
        size = x.size // x.shape[0]
        out.append(avg_flat[off:off + size].reshape(x.shape[1:])
                   .astype(x.dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)


def _apply_fallback(avg, fallback, total):
    """Keep `fallback` (the previous global) when no worker survived."""
    if fallback is None:
        return avg
    return jax.tree.map(
        lambda a, f: jnp.where(total > 0, a, f.astype(a.dtype)),
        avg, fallback)


@stages.stage(stages.A2_AVERAGE)
def weighted_average(stacked_params, weights, *, impl: str = "jnp",
                     robust: Optional[RobustConfig] = None,
                     interpret=None, fallback=None):
    """stacked_params: pytree with leading device axis K; weights: (K,).

    Returns the weighted average with the leading axis contracted.
    `robust` selects a robust reducer (repro.kernels.robust_avg) run on
    the flattened (K, N) payload with the RAW weights — one Pallas call
    for the whole tree, matching the mesh hot path column-for-column.
    `fallback` (unstacked, result-shaped) is returned when the total
    weight is zero — the no-survivor round keeps the previous global.
    """
    if robust is not None:
        from repro.kernels.robust_avg import ops as robust_ops

        flat, leaves, treedef = _flatten_stacked(stacked_params)
        if not leaves:
            return stacked_params
        avg_flat = robust_ops.robust_average(
            flat, weights.astype(jnp.float32), robust, interpret=interpret)
        avg = _unflatten_row(avg_flat, leaves, treedef)
        return _apply_fallback(avg, fallback,
                               jnp.sum(weights.astype(jnp.float32)))

    w = _normalized(weights)

    if impl == "pallas":
        from repro.kernels.wavg import ops as wavg_ops

        def avg_leaf(x):
            return wavg_ops.weighted_average(x, w).astype(x.dtype)
    else:
        def avg_leaf(x):
            wx = w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(jnp.float32)
            return jnp.sum(x.astype(jnp.float32) * wx, axis=0).astype(x.dtype)

    avg = jax.tree.map(avg_leaf, stacked_params)
    return _apply_fallback(avg, fallback,
                           jnp.sum(weights.astype(jnp.float32)))


@stages.stage(stages.A2_AVERAGE)
def weighted_average_psum(local_params, local_weight, *, axis_names,
                          impl: str = "jnp", robust: Optional[RobustConfig] = None,
                          interpret=None, fallback=None,
                          quantize_key=None, quantize_bits: int = 32):
    """shard_map path: every mesh slice holds ITS device's parameters;
    Algorithm 2 is a weighted reduction over the device axes.

    `axis_names` may be a SUBSET of the live mesh axes: on the 2-D
    (device x model) mesh the reduction runs over the device axes only,
    so each tensor-parallel rank averages just its parameter shard —
    the all-gather payload shrinks by the TP factor and the result
    stays sharded over the model axis
    (tests/test_averaging_property.py::TestAxisSubsetAveraging).

    impl="jnp"    — per-leaf weighted psum (one collective per leaf).
    impl="pallas" — the mesh hot path: the local tree is flattened into
        ONE contiguous f32 payload, all-gathered over the device axes
        into a (K, N) matrix, and reduced by the Pallas `wavg` kernel
        ((1, K) x (K, N) on the MXU) — one collective + one kernel per
        round instead of a tree of jnp means. `interpret=None` lets the
        kernel wrapper pick interpret mode on CPU, so the same code path
        runs everywhere (tests force it through interpret on host).

    A non-None `robust` routes the SAME flat-gather path through the
    selected robust reducer with the RAW gathered weights (0 = dropped
    worker contributes nothing) — still exactly one payload all-gather
    + one Pallas kernel call per round.

    impl="ring"  — the ring collective (repro.kernels.ring_wavg): k-1
        chunked `lax.ppermute` hops with dequantize-and-accumulate
        fused into the Pallas kernel. With `quantize_key` and
        `quantize_bits` < 32 the payload travels ENCODED (int16 at 16
        bits) using the same `quantize_tree` stream as the flat path's
        uplink roundtrip. Single device axis only; does not compose
        with `robust`.

    `fallback` (local-params-shaped) is returned when the gathered
    total weight is zero — every impl keeps the previous global on a
    no-survivor round instead of multiplying it by ~0.
    """
    if impl == "ring":
        if robust is not None:
            raise ValueError(
                "impl='ring' does not compose with robust reducers; "
                "robust aggregation stays on the flat gather path")
        from repro.kernels.ring_wavg import ops as ring_ops

        return ring_ops.ring_average_psum(
            local_params, local_weight, axis_names=axis_names,
            quantize_key=quantize_key, bits=quantize_bits,
            interpret=interpret, fallback=fallback)

    if impl == "pallas" or robust is not None:
        from repro.kernels.wavg import ops as wavg_ops

        leaves, treedef = jax.tree_util.tree_flatten(local_params)
        if not leaves:
            return local_params
        flat = jnp.concatenate(
            [jnp.ravel(x).astype(jnp.float32) for x in leaves])
        stacked = jax.lax.all_gather(flat, axis_names)       # (K, N)
        w_full = jax.lax.all_gather(
            local_weight.astype(jnp.float32), axis_names)    # (K,)
        if robust is not None:
            from repro.kernels.robust_avg import ops as robust_ops

            avg_flat = robust_ops.robust_average(stacked, w_full, robust,
                                                 interpret=interpret)
        else:
            w_norm = _normalized(w_full)
            avg_flat = wavg_ops.weighted_average(stacked, w_norm,
                                                 interpret=interpret)
        out, off = [], 0
        for x in leaves:
            out.append(avg_flat[off:off + x.size].reshape(x.shape)
                       .astype(x.dtype))
            off += x.size
        avg = jax.tree_util.tree_unflatten(treedef, out)
        return _apply_fallback(avg, fallback, jnp.sum(w_full))

    if impl != "jnp":
        raise ValueError(f"unknown weighted_average_psum impl {impl!r}")

    total = jax.lax.psum(local_weight.astype(jnp.float32), axis_names)

    def avg_leaf(x):
        contrib = x.astype(jnp.float32) * local_weight.astype(jnp.float32)
        summed = jax.lax.psum(contrib, axis_names)
        return (summed / jnp.maximum(total, 1e-12)).astype(x.dtype)

    avg = jax.tree.map(avg_leaf, local_params)
    return _apply_fallback(avg, fallback, total)


def broadcast_like(params, n: int):
    """Tile a pytree to a stacked leading device axis (Step 5 broadcast)."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), params)


def select_tree(mask_scalar, tree_true, tree_false):
    """Per-device jnp.where over pytrees (straggler exclusion)."""
    return jax.tree.map(
        lambda a, b: jnp.where(mask_scalar.reshape((-1,) + (1,) * (a.ndim - 1)),
                               a, b),
        tree_true, tree_false)
