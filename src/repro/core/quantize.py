"""Uplink quantization (paper Section IV: 16 bits per parameter).

Uniform stochastic quantization with a per-tensor scale. With the
default 16 bits the quantization error is negligible (matching the
paper's implicit assumption); lower bit widths are exposed for
communication-efficiency ablations.

Since PR 2 the uplink is quantized INSIDE the round math
(`protocol.gan_round` Step 3, `fedgan.fedgan_round`), so both drivers
— the per-round host oracle and the fused `lax.scan` engine — and the
shard_map path apply bitwise-identical quantization: device k's
round-t draw is keyed by fold_in(fold_in(round_key, _SALT_QUANT), k),
independent of how the device axis is executed (vmap, scan, or a mesh
slice). `tree_bits` also feeds the channel's uplink payload-size
timing, so ablation bit widths shrink simulated upload time too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import stages

# Salt separating the quantization stream from the shared-noise /
# data-sampling streams of core.protocol.
_SALT_QUANT = 0x0b175


def _quantize_leaf(x, rnd, amax, levels):
    """One leaf's uniform stochastic quantization: (q_int32, scale).
    The ONE definition of the scale floor / rounding / clip math —
    `quantize_tree` and `roundtrip_tp` both call it, so the tp-bitwise
    contract (TP width never changes the quantizer) cannot drift.

    All math runs in float32 regardless of the leaf dtype: under bf16
    type promotion the clip bound `levels` = 32767 is not representable
    (it rounds to 32768), so a bf16-domain clip can emit q outside its
    own [-levels-1, levels] contract — overflowing the int16 wire the
    ring collective (kernels/ring_wavg) puts the payload on."""
    scale = jnp.maximum(amax.astype(jnp.float32), 1e-12) / levels
    scaled = x.astype(jnp.float32) / scale
    low = jnp.floor(scaled)
    q = low + (rnd < scaled - low)
    return jnp.clip(q, -levels - 1, levels).astype(jnp.int32), scale


def quantize_tree(key, tree, bits: int = 16):
    """Returns (quantized_int_tree, scales_tree).

    The stochastic-rounding randomness is ONE uniform draw over the
    whole flattened payload, sliced per leaf — an order of magnitude
    fewer threefry dispatches than per-leaf keys at typical leaf
    counts, which matters inside the fused driver's per-round scan.
    """
    levels = 2 ** (bits - 1) - 1
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sizes = [int(x.size) for x in leaves]
    rnd_flat = jax.random.uniform(key, (sum(sizes),))

    q_leaves, scales = [], []
    off = 0
    for x, size in zip(leaves, sizes):
        rnd = rnd_flat[off:off + size].reshape(x.shape)
        off += size
        q, scale = _quantize_leaf(x, rnd, jnp.max(jnp.abs(x)), levels)
        q_leaves.append(q)
        scales.append(scale)
    return (jax.tree_util.tree_unflatten(treedef, q_leaves),
            jax.tree_util.tree_unflatten(treedef, scales))


def dequantize_tree(q_tree, scales_tree):
    return jax.tree.map(lambda q, s: q.astype(jnp.float32) * s,
                        q_tree, scales_tree)


def roundtrip(key, tree, bits: int = 16):
    """Quantize-dequantize (what the server receives on the uplink)."""
    if bits >= 32:
        return tree
    q, s = quantize_tree(key, tree, bits)
    deq = dequantize_tree(q, s)
    return jax.tree.map(lambda d, x: d.astype(x.dtype), deq, tree)


@stages.stage(stages.UPLINK)
def roundtrip_tp(key, tree, bits: int = 16, *, tp_axis=None, tp: int = 1,
                 shard_dims=None):
    """`roundtrip` for a TENSOR-PARALLEL shard of the upload payload.

    Inside a (device x model) mesh slice each TP rank holds only its
    Megatron shard of `tree`, but the paper's worker quantizes the WHOLE
    model with one stream. This reconstructs exactly that: the
    stochastic-rounding uniforms are drawn over the GLOBAL flattened
    payload (same key, same draw order as `roundtrip` at tp=1) and each
    rank slices its shard's positions; the per-tensor scale comes from
    the GLOBAL abs-max via `lax.pmax` over the model axis. A tp=2 run
    therefore quantizes bitwise-identically to tp=1 given the same
    values — TP changes the arithmetic only through matmul reduction
    order, never through the quantizer.

    shard_dims: per-leaf shard dim (negative) or None, as a tuple
    aligned with `tree_flatten(tree)` order — produced by
    `sharding.rules.tp_tree_dims` on the GLOBAL payload tree. Leaves
    with None replicate: every rank quantizes the full leaf with the
    same slice of the stream, staying replicated.

    KNOWN LIMITATION: reconstructing the worker-global stream means
    each rank materializes O(global payload) uniforms (rnd_flat + one
    global-shaped buffer per leaf) transiently during Step 3 — the
    quantizer's peak memory does NOT shrink with tp, only the persistent
    state and the Algorithm-2 all-gather do. That is the price of the
    tp-bitwise contract (tp must never change the quantizer); a
    counter-level sliced stream that keeps the contract without the
    global buffer is a ROADMAP item.
    """
    if bits >= 32:
        return tree
    if tp_axis is None or tp <= 1:
        return roundtrip(key, tree, bits)
    levels = 2 ** (bits - 1) - 1
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    assert shard_dims is not None and len(shard_dims) == len(leaves)
    rank = jax.lax.axis_index(tp_axis)

    # Global shapes/sizes: the sharded dim is tp x its local extent.
    gshapes = []
    for x, d in zip(leaves, shard_dims):
        shape = list(x.shape)
        if d is not None:
            shape[d] = shape[d] * tp
        gshapes.append(tuple(shape))
    gsizes = [1 for _ in gshapes]
    for i, shape in enumerate(gshapes):
        for s in shape:
            gsizes[i] *= s
    rnd_flat = jax.random.uniform(key, (sum(gsizes),))

    out, off = [], 0
    for x, d, gshape, gsize in zip(leaves, shard_dims, gshapes, gsizes):
        rnd = rnd_flat[off:off + gsize].reshape(gshape)
        off += gsize
        amax = jnp.max(jnp.abs(x))
        if d is not None:
            start = [0] * x.ndim
            start[d % x.ndim] = rank * x.shape[d]
            rnd = jax.lax.dynamic_slice(rnd, start, x.shape)
            amax = jax.lax.pmax(amax, tp_axis)
        q, scale = _quantize_leaf(x, rnd, amax, levels)
        out.append((q.astype(jnp.float32) * scale).astype(x.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def device_uplink_key(round_key, dev_index):
    """Key for device `dev_index`'s uplink quantization this round.

    One definition shared by every execution layout of the device axis
    (vmap in `gan_round`, per-slice in `shard_round`), so they quantize
    bitwise-identically.
    """
    return jax.random.fold_in(jax.random.fold_in(round_key, _SALT_QUANT),
                              dev_index)


@stages.stage(stages.UPLINK)
def roundtrip_stacked(round_key, stacked_tree, bits: int = 16):
    """Per-device quantize-dequantize of a pytree with leading axis K
    (Step 3: every scheduled device quantizes its OWN upload with its
    own stream)."""
    if bits >= 32:
        return stacked_tree
    n_devices = jax.tree_util.tree_leaves(stacked_tree)[0].shape[0]
    keys = jax.vmap(lambda i: device_uplink_key(round_key, i))(
        jnp.arange(n_devices))
    return jax.vmap(lambda k, t: roundtrip(k, t, bits))(keys, stacked_tree)


def tree_bits(tree, bits: int = 16) -> int:
    """Total uplink payload in bits for a parameter pytree."""
    return bits * sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))
