"""Algorithm 2 as a chunked, double-buffered ring collective.

The flat hot path (`averaging.weighted_average_psum(impl="pallas")`)
all-gathers every worker's FULL f32 payload before reducing — per-rank
wire bytes grow as K * N * 4 even when the uplink was quantized to 16
bits, because the payload is dequantized BEFORE the collective. This
module replaces it for ``impl="ring"``:

  * the uplink payload stays ENCODED on the wire (int16 at the paper's
    16 bits; int32 for 17..31; f32 when unquantized), reshaped into
    (n_blocks, BLOCK_N) wire blocks with a travelling (n_blocks,) f32
    per-block scale vector (each leaf's per-tensor scale broadcast over
    its blocks);
  * the reduction is k-1 `lax.ppermute` hops around the device ring;
    after hop h every rank holds worker (my - h) mod k's payload and
    accumulates coef = w_norm[src] * scale into a resident f32
    accumulator via the `ring_accum` Pallas kernel — dequantize fused
    into the accumulate, no per-rank f32 tree materialized;
  * each hop is CHUNKED (default 4 chunks): chunk c+1's permute is
    issued before chunk c's accumulate kernel runs, so XLA's async
    collective-permute overlaps the wire transfer of the next chunk
    with the reduction of the current one (double buffering).

Per-rank wire bytes: (k-1) * n_blocks * (BLOCK_N * wire_itemsize + 4)
vs the flat path's k * N * 4 — about 2x less at 16 bits (pinned by
tests/test_hlo_costs.py against what the HLO actually moves).

Quantization reuses `core.quantize.quantize_tree` with the SAME
`device_uplink_key` stream as the flat path's roundtrip, so the ring
changes only reduction order/precision, never the quantized values.
Restrictions (checked by `shard_round.check_ring_support` at build
time): single device axis, tp == 1, no robust reducers, no
upload-corrupting fault programs (those operate on dequantized trees
and stay on the flat path). Dropout/straggler faults compose fine —
they only zero weights.

No-survivor semantics: when every weight is zero (all workers dropped)
the average is undefined; with ``fallback`` the previous global
parameters are kept instead of the ~0 tree that `max(total, 1e-12)`
normalization would produce.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.core import quantize
from repro.kernels.ring_wavg.kernel import BLOCK_N, ring_accum_pallas

# Chunks per hop: enough to overlap permute/accumulate without
# shrinking blocks below useful DMA sizes at small payloads.
DEFAULT_CHUNKS = 4


def _single_axis(axis_names):
    if isinstance(axis_names, (tuple, list)):
        if len(axis_names) != 1:
            raise NotImplementedError(
                f"impl='ring' reduces over a single device axis; "
                f"got {axis_names!r}")
        return axis_names[0]
    return axis_names


def wire_dtype(bits: int):
    """Wire dtype for the encoded payload at a given uplink bit width.
    quantize_tree clips to [-levels-1, levels] = [-2**(bits-1),
    2**(bits-1)-1], so bits <= 16 fits int16 exactly."""
    if bits >= 32:
        return jnp.float32
    return jnp.int16 if bits <= 16 else jnp.int32


def ring_wire_bytes_per_rank(tree, bits: int, k: int) -> int:
    """Analytic per-rank bytes sent by the ring: (k-1) hops, each moving
    the padded wire payload plus the travelling block-scale vector.
    The twin of `driver_bench.allgather_bytes_per_rank` for the flat
    path; pinned against the lowered HLO in tests/test_hlo_costs.py."""
    sizes = [int(x.size) for x in jax.tree_util.tree_leaves(tree)]
    n_blocks = sum(-(-s // BLOCK_N) for s in sizes)
    itemsize = jnp.dtype(wire_dtype(bits)).itemsize
    return (k - 1) * n_blocks * (BLOCK_N * itemsize + 4)


def _chunk_bounds(n_blocks: int, n_chunks: int):
    """Static block-row ranges per chunk; ragged last chunks (no extra
    chunk-multiple padding — at most 2 distinct kernel shapes)."""
    n_chunks = max(1, min(n_chunks, n_blocks))
    base, rem = divmod(n_blocks, n_chunks)
    bounds, r0 = [], 0
    for c in range(n_chunks):
        r1 = r0 + base + (1 if c < rem else 0)
        bounds.append((r0, r1))
        r0 = r1
    return bounds


def _encode(local_params, quantize_key, bits: int):
    """Leaf trees -> ((n_blocks, BLOCK_N) wire payload, (n_blocks,) f32
    block scales, per-leaf metadata for decode)."""
    leaves, treedef = jax.tree_util.tree_flatten(local_params)
    metas = [(x.shape, x.dtype, int(x.size)) for x in leaves]
    wdt = wire_dtype(bits)
    if quantize_key is not None and bits < 32:
        q_tree, s_tree = quantize.quantize_tree(quantize_key, local_params,
                                                bits)
        q_leaves = jax.tree_util.tree_leaves(q_tree)
        s_leaves = jax.tree_util.tree_leaves(s_tree)
    else:
        q_leaves = leaves
        s_leaves = [jnp.asarray(1.0, jnp.float32) for _ in leaves]
    blocks, bscales = [], []
    for q, s in zip(q_leaves, s_leaves):
        flat = jnp.ravel(q).astype(wdt)
        pad = (-flat.size) % BLOCK_N
        if pad:
            flat = jnp.pad(flat, (0, pad))
        nb = flat.size // BLOCK_N
        blocks.append(flat.reshape(nb, BLOCK_N))
        bscales.append(jnp.broadcast_to(
            jnp.asarray(s, jnp.float32).reshape(()), (nb,)))
    return (jnp.concatenate(blocks, axis=0),
            jnp.concatenate(bscales), metas, treedef)


def _decode(acc, metas, treedef):
    out, row = [], 0
    for shape, dtype, size in metas:
        nb = -(-size // BLOCK_N)
        flat = acc[row:row + nb].reshape(-1)[:size]
        out.append(flat.reshape(shape).astype(dtype))
        row += nb
    return jax.tree_util.tree_unflatten(treedef, out)


def ring_average_psum(local_params, local_weight, *, axis_names,
                      quantize_key=None, bits: int = 32,
                      n_chunks: Optional[int] = None,
                      interpret: Optional[bool] = None, fallback=None):
    """Ring-collective Algorithm 2: the `weighted_average_psum` twin for
    ``impl="ring"``. Every mesh slice holds ITS device's parameters;
    returns the weighted average, replicated on every slice.

    quantize_key/bits: when bits < 32 and a key is given, the payload is
    quantized with `quantize.quantize_tree` (same stream as the flat
    path's uplink roundtrip) and travels encoded. fallback: pytree
    shaped like `local_params`; returned when the total weight is zero
    (no-survivor round).
    """
    axis = _single_axis(axis_names)
    interpret = interpret_mode(interpret)
    if not jax.tree_util.tree_leaves(local_params):
        return local_params

    k = int(jax.lax.psum(1, axis))          # static ring size
    my = jax.lax.axis_index(axis)
    w_full = jax.lax.all_gather(local_weight.astype(jnp.float32), axis)
    total = jnp.sum(w_full)
    w_norm = w_full / jnp.maximum(total, 1e-12)

    payload, scales, metas, treedef = _encode(local_params, quantize_key,
                                              bits)
    n_blocks = payload.shape[0]
    bounds = _chunk_bounds(
        n_blocks, DEFAULT_CHUNKS if n_chunks is None else n_chunks)

    # Hop 0: accumulate the rank's OWN contribution (no wire traffic).
    acc = ring_accum_pallas(jnp.zeros(payload.shape, jnp.float32),
                            payload, w_norm[my] * scales,
                            interpret=interpret)

    if k > 1:
        perm = [(j, (j + 1) % k) for j in range(k)]

        def hop(carry, h):
            buf, sbuf, acc = carry
            # The block scales travel with the payload: after this hop
            # every rank holds the scales of worker (my - h) mod k.
            sbuf = jax.lax.ppermute(sbuf, axis, perm)
            src = jnp.mod(my - h, k)
            coef = w_norm[src] * sbuf
            # Double buffering: chunk c+1's permute is issued BEFORE
            # chunk c's accumulate so the async collective-permute
            # overlaps the next transfer with the current reduction.
            recv = [jax.lax.ppermute(buf[bounds[0][0]:bounds[0][1]],
                                     axis, perm)]
            accs = []
            for c, (r0, r1) in enumerate(bounds):
                if c + 1 < len(bounds):
                    n0, n1 = bounds[c + 1]
                    recv.append(jax.lax.ppermute(buf[n0:n1], axis, perm))
                accs.append(ring_accum_pallas(acc[r0:r1], recv[c],
                                              coef[r0:r1],
                                              interpret=interpret))
            nbuf = recv[0] if len(recv) == 1 else jnp.concatenate(recv, 0)
            nacc = accs[0] if len(accs) == 1 else jnp.concatenate(accs, 0)
            return (nbuf, sbuf, nacc), None

        (_, _, acc), _ = jax.lax.scan(hop, (payload, scales, acc),
                                      jnp.arange(1, k))

    avg = _decode(acc, metas, treedef)
    if fallback is not None:
        avg = jax.tree.map(
            lambda a, f: jnp.where(total > 0, a, f.astype(a.dtype)),
            avg, fallback)
    return avg


__all__ = ["ring_average_psum", "ring_wire_bytes_per_rank", "wire_dtype",
           "ring_accum_pallas", "BLOCK_N", "DEFAULT_CHUNKS"]
