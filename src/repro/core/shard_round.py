"""Mesh execution layout for protocol rounds: shard_map + explicit
collectives, single-round and FUSED multi-round, for EVERY mesh-capable
algorithm (proposed protocol AND the FedGAN baseline), on a 1-D
`(device,)` or 2-D `(device, model)` mesh.

The round engine has two first-class execution layouts (see
core/engine.py for the driver/layout matrix):

  layout="stacked" — the paper's K devices are a stacked leading axis;
      vmap/GSPMD insert the averaging all-reduce (`protocol.gan_round`,
      `protocol.rounds_scan`).
  layout="mesh"    — THIS module: every mesh slice IS a device under
      `jax.shard_map`. Local updates touch no collective (Algorithm 1,
      and FedGAN's joint D+G local iterations, are embarrassingly
      parallel), Algorithm-2-style averaging is an explicit weighted
      reduction over the device axes, and any replicated server math is
      shared-seed computation (identical per-slice results, no gradient
      collective).

TENSOR PARALLELISM (`tp_axis`/`tp`): each paper-worker slice may itself
be a TP group over the mesh's `model` axis. The TP-shardable leaves
(`sharding.rules.tp_leaf_dim` name rules) enter shard_map split over
`tp_axis`, the per-slice model math runs Megatron column/row-parallel
matmuls with nested psum/all_gather collectives on the model axis
(nn/tp.py pairs, baked into the TP-aware `GanModelSpec`), while
EVERYTHING the paper defines over workers — scheduling masks, channel
timing, the quantized uplink keying, and the Algorithm-2 reduction —
stays on the DEVICE axes only. Each TP rank therefore averages just its
parameter shard: the Algorithm-2 all-gather payload shrinks by the TP
factor. The uplink quantizer reconstructs the worker-global stream and
scale per shard (`quantize.roundtrip_tp`), so tp>1 quantizes
bitwise-identically to tp=1 given the same values; tp=1 (the default)
takes the exact pre-TP code paths.

The engine is ALGORITHM-PARAMETRIC: `_mesh_single_round` and
`_mesh_rounds_scan` own all the layout plumbing — state (un)stacking,
Step 1 scheduling + channel timing via `protocol.schedule_and_time`
(per-round keys shared verbatim with the stacked engine, so masks agree
bitwise across layouts), the wall-clock composition, the donated
`lax.scan` dispatch, and the shard_map spec construction — while a
per-slice ROUND BODY supplies the algorithm's Steps 2-5:

  `_proposed_slice_round` — Algorithm 1 local disc steps, the quantized
      one-net uplink, Algorithm 2 over the device axes, the replicated
      Algorithm 3 server update.
  `_fedgan_slice_round`   — FedGAN's n_d local (disc, gen) iteration
      pairs, the single TWO-NET quantized uplink payload (keyed exactly
      like `fedgan_round`'s `roundtrip_stacked`, so both layouts
      quantize bitwise-identically), and Algorithm-2-style averaging of
      BOTH networks in one reduction.

Four entry points, two per algorithm:

  `shard_map_round` / `fedgan_shard_map_round` — ONE round per dispatch
      (weights supplied by the host). The per-round oracles of the mesh
      layout and the baselines `benchmarks/driver_bench.py --layout
      mesh` measures fused speedups against.
  `shard_rounds_scan` / `fedgan_shard_rounds_scan` — the fused engines:
      R complete rounds run INSIDE shard_map as one `lax.scan` — one
      XLA dispatch per chunk, donated state, the same carry/out
      structure as `protocol.rounds_scan`, so `engine.Trainer` drives
      either through the unchanged fused driver.

Every builder MEMOIZES on its full (mesh, config) signature at module
level, so repeated `Trainer` constructions (or `build_train_step`
calls) in one process reuse the jitted shard_map closures — and their
compiles — instead of rebuilding per call. Inside a builder the jitted
closure is additionally keyed by the state/data tree signature, so one
builder serves differently-shaped models without stale specs.

Algorithm 2 on the mesh defaults to
`averaging.weighted_average_psum(impl="pallas")`: the local tree (both
nets, for FedGAN; each rank's shards, under TP) is flattened into ONE
payload, all-gathered once over the DEVICE axes, and reduced by the
Pallas `wavg` kernel on the MXU (interpret mode on CPU) — one
collective + one kernel per round instead of a per-leaf psum tree.

Equivalence contract (tests/test_driver_equivalence.py mesh matrices,
tests/test_multidevice.py, tests/test_tp_equivalence.py): on a forced
multi-device host mesh both layouts of BOTH algorithms — at tp=1 AND
tp=2 — reproduce the host oracle's masks BITWISE (the per-round keys
come from `protocol.schedule_and_time`, shared verbatim) and its
params/metrics to float32 round-off.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ProtocolConfig
from repro.core import faults as faults_lib
from repro.core import fedgan as fedgan_mod
from repro.core import jax_channel, quantize, stages
from repro.core.protocol import (GanModelSpec, count_params, device_update,
                                 schedule_and_time, server_update,
                                 uplink_payload_bits)
from repro.core.averaging import weighted_average_psum
from repro.sharding import rules

# Per-algorithm mesh conventions: which state entries carry a leading
# per-device axis, the metric names the slice round body returns (they
# must match the host oracle's round function exactly, since the
# equivalence tests compare metric dicts key-for-key), and the uplink
# payload tree (whose structure keys the TP shard dims for the
# quantizer — `rules.tp_tree_dims` on the GLOBAL state).
PROPOSED_STACKED_KEYS = ("disc_opt",)
PROPOSED_METRICS = ("disc_objective", "gen_objective", "participation")
PROPOSED_PAYLOAD = lambda state: state["disc"]
FEDGAN_STACKED_KEYS = ("gen_opt", "disc_opt")
FEDGAN_METRICS = ("participation",)
FEDGAN_PAYLOAD = lambda state: {"gen": state["gen"],
                                "disc": state["disc"]}


@dataclasses.dataclass(frozen=True)
class TpCtx:
    """In-slice tensor-parallel context handed to the slice round
    bodies: the model-axis name, its (static) size, and the uplink
    payload's per-leaf shard dims (tree_flatten-aligned tuple, computed
    on the GLOBAL payload by `rules.tp_tree_dims`)."""
    axis: str
    size: int
    payload_dims: Tuple


def _shard_map(f, *, mesh, in_specs, out_specs):
    """shard_map without the replication checker: the round bodies
    replicate server math by shared-seed computation, which it cannot
    see."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _placed(jitted, mesh, in_specs):
    """`jitted` with its arguments first put on the shardings of
    `in_specs`. jit keys its compiles on the committed shardings of its
    inputs: host-placed first-call arguments and the mesh-sharded
    outputs fed back in later would otherwise compile the program twice.
    A no-op for arguments already placed."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), in_specs,
                             is_leaf=lambda s: isinstance(s, P))

    def run(*args):
        with stages.span(stages.PLACE):
            args = jax.device_put(args, shardings)
        return jitted(*args)

    return run


def _unstack_state(state, stacked_keys):
    """Drop the local size-1 leading axis of the per-device entries."""
    return {k: (jax.tree.map(lambda x: x[0], v) if k in stacked_keys else v)
            for k, v in state.items()}


def _restack_state(state, stacked_keys):
    """Re-add the local leading axis so out specs see the stacked shape."""
    return {k: (jax.tree.map(lambda x: x[None], v) if k in stacked_keys
                else v)
            for k, v in state.items()}


def _tree_sig(tree):
    """Hashable (treedef, shapes/dtypes) signature of a pytree — the
    per-builder closure-cache key, so one memoized builder serves
    differently-shaped states without reusing stale specs."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, tuple((tuple(x.shape), str(getattr(x, "dtype", "?")))
                          for x in leaves)


# Per-builder jitted-closure cache bound: builders live in the
# module-level _BUILDER_CACHE, so their inner per-signature caches
# would otherwise outlive every Trainer and accumulate one compiled
# executable per distinct model shape for the process lifetime (e.g. a
# width sweep reusing one spec object). Real runs use one or two
# signatures per builder; LRU-evict beyond a small bound.
_SIG_CACHE_MAX = 8


def _sig_cache_get(cache: dict, sig, build: Callable,
                   cap: int = _SIG_CACHE_MAX):
    fn = cache.pop(sig, None)    # pop+reinsert: LRU recency
    if fn is None:
        fn = build()
    cache[sig] = fn
    while len(cache) > cap:
        cache.pop(next(iter(cache)))
    return fn


def _tp_ctx(payload_fn, state, tp_axis, tp) -> Optional[TpCtx]:
    """TpCtx from the GLOBAL state (divisibility decided on global
    dims), or None when the model axis is absent/trivial."""
    if tp_axis is None or tp <= 1:
        return None
    return TpCtx(tp_axis, tp, rules.tp_tree_dims(payload_fn(state), tp))


@stages.stage(stages.UPLINK)
def _quantize_uplink(tp_ctx: Optional[TpCtx], key, payload, bits: int):
    """The Step-3 uplink quantizer, per TP regime: the plain worker
    stream at tp=1, the worker-global reconstructed stream per shard
    under TP (bitwise-identical results for identical values)."""
    if tp_ctx is None:
        return quantize.roundtrip(key, payload, bits)
    return quantize.roundtrip_tp(key, payload, bits, tp_axis=tp_ctx.axis,
                                 tp=tp_ctx.size,
                                 shard_dims=tp_ctx.payload_dims)


# ---------------------------------------------------------------------------
# Per-slice round bodies (Steps 2-5, one algorithm each)
# ---------------------------------------------------------------------------

def _proposed_slice_round(spec: GanModelSpec, pcfg: ProtocolConfig, axis,
                          faults, robust,
                          avg_impl: str, tp_ctx: Optional[TpCtx], my_index,
                          st, data_k, w_k, weights, weight_sum, round_key):
    """The proposed protocol's Steps 2-5 as seen by ONE mesh slice.

    st: per-slice state {"gen", "disc", "gen_opt", "disc_opt"} (already
    unstacked; under TP every model-parallel leaf is this rank's
    shard — the spec's apply functions own the in-slice collectives).
    An optional replicated "fault" entry carries the free-rider stale
    cache (core/faults.py); `faults` corrupts THIS slice's upload keyed
    by (round_key, my_index) — bitwise what the stacked layout's
    vmapped lane realizes — and `robust` selects the robust reducer in
    the Algorithm-2 reduction.
    Returns (new_st, metrics).
    """
    disc_k, disc_opt_k, disc_obj = device_update(
        spec, pcfg, st["gen"], st["disc"], st["disc_opt"], data_k,
        round_key, my_index)

    if avg_impl == "ring":
        # Ring hot path: the quantized uplink stays ENCODED on the wire
        # — weighted_average_psum(impl="ring") quantizes with the SAME
        # device_uplink_key stream as the flat path's roundtrip and
        # streams the int16 payload around a chunked ppermute ring with
        # dequantize-and-accumulate fused into the Pallas kernel
        # (kernels/ring_wavg). Corrupting faults / robust reducers
        # operate on dequantized trees, so they are flat-path-only
        # (rejected at build time by `check_ring_support`).
        disc_avg = weighted_average_psum(
            disc_k, w_k, axis_names=axis, impl="ring",
            quantize_key=quantize.device_uplink_key(round_key, my_index),
            quantize_bits=pcfg.quantize_bits, fallback=st["disc"])
    else:
        # Step 3 — quantized uplink, keyed exactly as the stacked
        # layout's `roundtrip_stacked` (device index = this slice's
        # DEVICE-axes index, shared by all its TP ranks), so every
        # layout and TP width quantizes bitwise-identically.
        if pcfg.quantize_bits < 32:
            disc_k = _quantize_uplink(
                tp_ctx, quantize.device_uplink_key(round_key, my_index),
                disc_k, pcfg.quantize_bits)

        prog = faults_lib.fault_program(faults)
        if prog is not None and prog.corrupts:
            stale = st["fault"]["stale"] if "fault" in st else None
            disc_k = faults_lib.corrupt_upload(prog, round_key, my_index,
                                               disc_k, stale=stale)

        # Algorithm 2 over the DEVICE axes only — Pallas wavg kernel on
        # the flat all-gathered payload by default (one collective + one
        # kernel), per-leaf psum with impl="jnp"; `robust` routes the
        # SAME flat-gather path through a robust reducer. Under TP each
        # rank reduces just its shard: the gathered payload is 1/tp the
        # model. On a no-survivor round the fallback keeps the previous
        # global discriminator.
        disc_avg = weighted_average_psum(disc_k, w_k, axis_names=axis,
                                         impl=avg_impl, robust=robust,
                                         fallback=st["disc"])

    disc_for_gen = disc_avg if pcfg.schedule == "serial" else st["disc"]
    gen, gen_opt, gen_obj = server_update(spec, pcfg, st["gen"],
                                          st["gen_opt"], disc_for_gen,
                                          round_key)

    w = w_k.astype(jnp.float32)
    wsum = jnp.maximum(weight_sum, 1e-12)
    metrics = {
        "disc_objective": jax.lax.psum(disc_obj * w, axis) / wsum,
        "gen_objective": gen_obj,
        "participation": (weights > 0).astype(jnp.float32).mean(),
    }
    new_st = {"gen": gen, "disc": disc_avg, "gen_opt": gen_opt,
              "disc_opt": disc_opt_k}
    if "fault" in st:
        new_st["fault"] = {"stale": st["disc"]}
    return new_st, metrics


def _fedgan_slice_round(spec: GanModelSpec, pcfg: ProtocolConfig, axis,
                        faults, robust,
                        avg_impl: str, tp_ctx: Optional[TpCtx], my_index,
                        st, data_k, w_k, weights, weight_sum, round_key):
    """One FedGAN round as seen by ONE mesh slice: n_d local (disc, gen)
    iteration pairs on the slice's shard, then the server's model-only
    averaging of BOTH networks.

    The uplink is the single two-net payload of `fedgan.fedgan_round`:
    {"gen": ..., "disc": ...} quantized as ONE tree per device (one
    stochastic-rounding draw over the concatenated payload), keyed by
    `device_uplink_key(round_key, my_index)` — the same tree structure
    and key `roundtrip_stacked` uses on the stacked layout, so both
    layouts quantize bitwise-identically (under TP each rank draws its
    shard's slice of that same stream). Averaging reduces the same
    combined tree in one `weighted_average_psum` call over the device
    axes: with impl="pallas" that is ONE all-gather + ONE wavg kernel
    for both networks — per TP rank, 1/tp of the two-net payload.
    """
    gen_k, disc_k, gen_opt_k, disc_opt_k = fedgan_mod.fedgan_device_update(
        spec, pcfg, st["gen"], st["disc"], st["gen_opt"], st["disc_opt"],
        data_k, round_key, my_index)

    payload = {"gen": gen_k, "disc": disc_k}
    prev = {"gen": st["gen"], "disc": st["disc"]}
    if avg_impl == "ring":
        # Same ring hot path as the proposed protocol: one encoded
        # two-net payload streamed around the ring, dequantized in the
        # accumulate kernel (see _proposed_slice_round).
        avg = weighted_average_psum(
            payload, w_k, axis_names=axis, impl="ring",
            quantize_key=quantize.device_uplink_key(round_key, my_index),
            quantize_bits=pcfg.quantize_bits, fallback=prev)
    else:
        if pcfg.quantize_bits < 32:
            payload = _quantize_uplink(
                tp_ctx, quantize.device_uplink_key(round_key, my_index),
                payload, pcfg.quantize_bits)

        prog = faults_lib.fault_program(faults)
        if prog is not None and prog.corrupts:
            stale = st["fault"]["stale"] if "fault" in st else None
            payload = faults_lib.corrupt_upload(prog, round_key, my_index,
                                                payload, stale=stale)

        avg = weighted_average_psum(payload, w_k, axis_names=axis,
                                    impl=avg_impl, robust=robust,
                                    fallback=prev)
    new_st = {"gen": avg["gen"], "disc": avg["disc"],
              "gen_opt": gen_opt_k, "disc_opt": disc_opt_k}
    if "fault" in st:
        new_st["fault"] = {"stale": {"gen": st["gen"],
                                     "disc": st["disc"]}}
    metrics = {"participation": (weights > 0).astype(jnp.float32).mean()}
    return new_st, metrics


# ---------------------------------------------------------------------------
# One round per dispatch (host-scheduled weights — the mesh oracles)
# ---------------------------------------------------------------------------

def _mesh_single_round(slice_round_fn: Callable, stacked_keys, metric_names,
                       payload_fn: Callable, mesh, device_axes,
                       avg_impl: str, tp_axis=None, tp: int = 1):
    """Build a jitted single-round function over `mesh` with explicit
    collectives. Expects the `stacked_keys` state entries /data/weights
    stacked over the device axes (leading K == prod of device-axis
    sizes); TP-shardable leaves enter split over `tp_axis` when set.

    The jitted shard_map closure is cached per state/data signature, so
    repeated per-round dispatches pay dispatch latency only — this is
    the baseline the fused scans are benchmarked against. It runs the
    SAME per-slice round math (including the averaging impl, pallas by
    default), so the driver bench isolates pure dispatch overhead.
    """
    axis = device_axes
    stacked, rep = P(device_axes), P()
    cache = {}

    def build(state, data_stacked):
        tp_ctx = _tp_ctx(payload_fn, state, tp_axis, tp)

        def round_body(state, data_local, weight_local, round_key):
            # inside shard_map: leading stacked axis has local size 1
            my_index = jax.lax.axis_index(axis)
            data_k = jax.tree.map(lambda x: x[0], data_local)
            st = _unstack_state(state, stacked_keys)
            w_k = weight_local[0]
            weights = jax.lax.all_gather(w_k, axis)
            wsum = jax.lax.psum(w_k.astype(jnp.float32), axis)
            new_st, metrics = slice_round_fn(
                avg_impl, tp_ctx, my_index, st, data_k, w_k, weights,
                wsum, round_key)
            return _restack_state(new_st, stacked_keys), metrics

        in_specs = (
            rules.shard_round_state_specs(state, device_axes,
                                          stacked_keys,
                                          tp_axis=tp_axis, tp=tp),
            rules.tree_specs(data_stacked, stacked),
            stacked,
            rep,
        )
        out_specs = (
            rules.shard_round_state_specs(state, device_axes,
                                          stacked_keys,
                                          tp_axis=tp_axis, tp=tp),
            {name: rep for name in metric_names},
        )
        return _placed(jax.jit(_shard_map(round_body, mesh=mesh,
                                          in_specs=in_specs,
                                          out_specs=out_specs)),
                       mesh, in_specs)

    def run(state, data_stacked, weights, round_key):
        with stages.span(stages.SIGNATURE):
            sig = (_tree_sig(state), _tree_sig(data_stacked))
            fn = _sig_cache_get(cache, sig,
                                lambda: build(state, data_stacked))
        return fn(state, data_stacked, weights, round_key)

    return run


# ---------------------------------------------------------------------------
# Builder memoization — reuse jitted shard_map closures per (mesh, config)
# ---------------------------------------------------------------------------

_BUILDER_CACHE: dict = {}
# LRU bound: spec objects hash by the identity of their callables, so
# callers that rebuild specs per call (sweeps, fresh make_backbone_spec
# per chunk length) insert entries they can never hit again — the
# bound keeps those from pinning compiled executables for the process
# lifetime, while callers that DO reuse spec objects (module-level
# specs, the Trainer tests, repeated Trainer constructions) stay hot.
_BUILDER_CACHE_MAX = 64


def _memo_builder(key_parts, build: Callable):
    """Memoize a builder on its full config signature when every part is
    hashable (specs/pcfg/mesh/scheduler are frozen dataclasses, channel
    keys by its config tuple); unhashable parts fall back to building
    fresh. Correct because every closure input is part of the key and
    the built `run` re-derives its jitted fn per state signature."""
    try:
        key = tuple(key_parts)
        hash(key)
    except TypeError:
        return build()
    return _sig_cache_get(_BUILDER_CACHE, key, build,
                          cap=_BUILDER_CACHE_MAX)


def _channel_key(channel):
    return tuple(dataclasses.astuple(channel.cfg))


def check_faults_tp(faults, robust, tp_axis, tp: int):
    """Fault injection / robust reduction compose with the mesh layout
    at tp=1 only: under TP the per-slice payload is a model-axis shard,
    so byzantine noise keying, the stale cache, and shard-local norms/
    distances would all diverge from the worker-global semantics.

    THE one definition of this contract — called from the mesh round
    builders below, `engine.Trainer`, and `launch.steps`."""
    if tp_axis is not None and tp > 1 and (faults is not None
                                           or robust is not None):
        raise NotImplementedError(
            "faults/robust reducers are not supported under tensor "
            "parallelism (tp > 1); run tp=1")


# Backwards-compatible alias (pre-PR-9 private name).
_check_faults_tp = check_faults_tp


def check_ring_support(avg_impl: str, device_axes, tp_axis, tp: int,
                       faults, robust):
    """Build-time contract for `avg_impl="ring"`: a single device axis
    (the ring order is the axis order), tp == 1 (the encoded payload is
    worker-global), no robust reducers and no upload-corrupting fault
    programs (both operate on dequantized per-worker trees, which the
    ring never materializes — they stay on the flat gather path).
    Dropout/straggler fault programs compose fine: they only zero
    weights."""
    if avg_impl != "ring":
        return
    axes = (device_axes if isinstance(device_axes, (tuple, list))
            else (device_axes,))
    if len(axes) != 1:
        raise NotImplementedError(
            f"avg_impl='ring' reduces over a single device axis; "
            f"got {tuple(axes)!r}")
    if tp_axis is not None and tp > 1:
        raise NotImplementedError(
            "avg_impl='ring' is not supported under tensor parallelism "
            "(tp > 1); the encoded ring payload is worker-global")
    if robust is not None:
        raise NotImplementedError(
            "avg_impl='ring' does not compose with robust reducers; "
            "use the flat path (avg_impl='pallas')")
    prog = faults_lib.fault_program(faults)
    if prog is not None and prog.corrupts:
        raise NotImplementedError(
            "avg_impl='ring' does not compose with upload-corrupting "
            "fault programs (free riders / byzantine); use the flat "
            "path (avg_impl='pallas')")


def shard_map_round(spec: GanModelSpec, pcfg: ProtocolConfig, mesh,
                    device_axes=("data",), avg_impl: str = "pallas",
                    tp_axis=None, tp: int = 1, faults=None, robust=None):
    """Single proposed-protocol round per dispatch (the mesh oracle).
    With `faults`, the host drives scheduling/dropout and this dispatch
    realizes the matching upload corruption; `robust` selects the
    Algorithm-2 robust reducer."""
    check_faults_tp(faults, robust, tp_axis, tp)
    check_ring_support(avg_impl, device_axes, tp_axis, tp, faults,
                       robust)
    return _memo_builder(
        ("proposed_round", spec, pcfg, mesh, tuple(device_axes), avg_impl,
         tp_axis, tp, faults, robust),
        lambda: _mesh_single_round(
            partial(_proposed_slice_round, spec, pcfg, device_axes,
                    faults, robust),
            PROPOSED_STACKED_KEYS, PROPOSED_METRICS, PROPOSED_PAYLOAD,
            mesh, device_axes, avg_impl, tp_axis, tp))


def fedgan_shard_map_round(spec: GanModelSpec, pcfg: ProtocolConfig, mesh,
                           device_axes=("data",),
                           avg_impl: str = "pallas",
                           tp_axis=None, tp: int = 1, faults=None,
                           robust=None):
    """Single FedGAN round per dispatch (the mesh FedGAN oracle).
    Expects gen_opt AND disc_opt stacked (every device trains both
    nets)."""
    check_faults_tp(faults, robust, tp_axis, tp)
    check_ring_support(avg_impl, device_axes, tp_axis, tp, faults,
                       robust)
    return _memo_builder(
        ("fedgan_round", spec, pcfg, mesh, tuple(device_axes), avg_impl,
         tp_axis, tp, faults, robust),
        lambda: _mesh_single_round(
            partial(_fedgan_slice_round, spec, pcfg, device_axes,
                    faults, robust),
            FEDGAN_STACKED_KEYS, FEDGAN_METRICS, FEDGAN_PAYLOAD,
            mesh, device_axes, avg_impl, tp_axis, tp))


# ---------------------------------------------------------------------------
# Fused multi-round scan INSIDE shard_map — R rounds per dispatch
# ---------------------------------------------------------------------------

def _mesh_rounds_scan(slice_round_fn: Callable, stacked_keys, metric_names,
                      payload_fn: Callable, pcfg: ProtocolConfig, mesh,
                      n_rounds: int, *, channel, scheduler, device_axes,
                      disc_step_flops: float, gen_step_flops: float,
                      uplink_bits: Optional[int], avg_impl: str,
                      fedgan: bool, eval_fn: Optional[Callable],
                      eval_every: int, tp_axis=None, tp: int = 1,
                      faults=None):
    """The unified fused round engine on the MESH layout, parametrized
    by the algorithm's per-slice round body.

    Builds `run(state, sched_carry, data_stacked, key, start_round) ->
    (state, sched_carry, out)` — the exact chunk signature of the
    stacked layout's `engine.Trainer._chunk_fn`, with state and
    scheduler carry donated. `out` stacks per-round {"metrics",
    "wallclock_s", "mask", "weights"[, "fid", "fid_eval"]} exactly like
    `protocol.rounds_scan`.

    Everything runs INSIDE shard_map: scheduling and channel timing are
    replicated per-slice computation (deterministic given the round key,
    so every slice agrees without a collective), local updates touch no
    device-axes collective (under TP they carry the in-slice Megatron
    psums on the model axis), the quantized uplink uses the slice's
    DEVICE-axes index as its device key, and the averaging is
    `weighted_average_psum` over the device axes — by default
    `impl="pallas"`: one all-gather of the flat payload (per TP rank,
    1/tp of the model) + one Pallas `wavg` kernel per round
    (interpret-mode on CPU hosts).

    The channel accounting always sees the WORKER-global parameter
    counts and payload bits (computed host-side from the global state),
    so simulated timing/wallclock is identical at every tp — TP is an
    implementation detail inside a worker, invisible to the paper's
    channel model.

    channel:   core.jax_channel.JaxChannel over K = prod(device axes)
    scheduler: core.jax_scheduling.JaxScheduler
    fedgan:    selects the FedGAN timing/wallclock composition and the
        two-net default uplink payload size
    eval_fn:   optional JITTABLE (gen_params, t, key) -> scalar run
        in-scan via lax.cond on rounds where (t+1) % eval_every == 0
        (replicated — gen is replicated, so every slice evaluates the
        same FID). Not supported under tp > 1 (the in-slice gen is a
        shard).
    """
    axis = device_axes
    if (tp_axis is not None and tp > 1 and eval_fn is not None
            and eval_every > 0):
        raise NotImplementedError(
            "in-scan FID under tensor parallelism is not supported: the "
            "per-slice generator is a model-axis shard; run eval_every=0 "
            "or tp=1")
    stacked, rep = P(device_axes), P()
    cache = {}

    def build(state, sched_carry, data_stacked):
        tp_ctx = _tp_ctx(payload_fn, state, tp_axis, tp)
        # Worker-global counts, from the GLOBAL (pre-split) state —
        # inside shard_map the leaves are 1/tp shards under TP.
        disc_nparams = count_params(state["disc"])
        gen_nparams = count_params(state["gen"])
        bits = uplink_bits
        if bits is None:
            bits = uplink_payload_bits(state, pcfg, fedgan=fedgan)

        def body(state, sched_carry, data_local, key, start_round):
            my_index = jax.lax.axis_index(axis)
            data_k = jax.tree.map(lambda x: x[0], data_local)
            st = _unstack_state(state, stacked_keys)

            def round_body(carry, t):
                st, sc = carry
                round_key = jax.random.fold_in(key, t)

                # Step 1 + channel accounting: same helper (same
                # salts, same draw order) as the stacked layout —
                # masks are bitwise identical across layouts and vs
                # the host oracle.
                mask, sc, timing, weights = schedule_and_time(
                    pcfg, channel, scheduler, sc, round_key,
                    disc_nparams=disc_nparams,
                    gen_nparams=gen_nparams,
                    disc_step_flops=disc_step_flops,
                    gen_step_flops=gen_step_flops, fedgan=fedgan,
                    uplink_bits=bits, faults=faults)
                w_k = weights[my_index]

                new_st, metrics = slice_round_fn(
                    avg_impl, tp_ctx, my_index, st, data_k, w_k,
                    weights, weights.sum(), round_key)

                wall = jax_channel.round_wallclock(
                    timing, mask, schedule=pcfg.schedule,
                    fedgan=fedgan)
                out = {"metrics": metrics, "wallclock_s": wall,
                       "mask": mask, "weights": weights}
                if eval_fn is not None and eval_every > 0:
                    do_eval = (t + 1) % eval_every == 0
                    out["fid"] = jax.lax.cond(
                        do_eval,
                        lambda g: jnp.float32(eval_fn(g, t, key)),
                        lambda g: jnp.float32(jnp.nan),
                        new_st["gen"])
                    out["fid_eval"] = do_eval
                return (new_st, sc), out

            rounds = jnp.asarray(start_round) + jnp.arange(n_rounds)
            (st, sched_carry), out = jax.lax.scan(
                round_body, (st, sched_carry), rounds)
            return _restack_state(st, stacked_keys), sched_carry, out

        state_specs = rules.shard_round_state_specs(
            state, device_axes, stacked_keys, tp_axis=tp_axis, tp=tp)
        out_round = {"metrics": {name: rep for name in metric_names},
                     "wallclock_s": rep, "mask": rep, "weights": rep}
        if eval_fn is not None and eval_every > 0:
            out_round["fid"] = rep
            out_round["fid_eval"] = rep
        in_specs = (state_specs,
                    rules.tree_specs(sched_carry, rep),
                    rules.tree_specs(data_stacked, stacked),
                    rep, rep)
        out_specs = (state_specs,
                     rules.tree_specs(sched_carry, rep),
                     out_round)
        return _placed(jax.jit(
            _shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs),
            donate_argnums=(0, 1)), mesh, in_specs)

    def run(state, sched_carry, data_stacked, key, start_round):
        with stages.span(stages.SIGNATURE):
            sig = (_tree_sig(state), _tree_sig(sched_carry),
                   _tree_sig(data_stacked))
            fn = _sig_cache_get(
                cache, sig, lambda: build(state, sched_carry, data_stacked))
        return fn(state, sched_carry, data_stacked, key, start_round)

    return run


def _scan_memo_key(kind, spec, pcfg, mesh, n_rounds, channel, scheduler,
                   device_axes, disc_step_flops, gen_step_flops,
                   uplink_bits, avg_impl, tp_axis, tp, faults=None,
                   robust=None):
    return (kind, spec, pcfg, mesh, n_rounds, _channel_key(channel),
            scheduler, tuple(device_axes), disc_step_flops,
            gen_step_flops, uplink_bits, avg_impl, tp_axis, tp, faults,
            robust)


def shard_rounds_scan(spec: GanModelSpec, pcfg: ProtocolConfig, mesh,
                      n_rounds: int, *, channel, scheduler,
                      device_axes=("data",), disc_step_flops: float = 1e9,
                      gen_step_flops: float = 1e9,
                      uplink_bits: Optional[int] = None,
                      avg_impl: str = "pallas",
                      eval_fn: Optional[Callable] = None,
                      eval_every: int = 0, tp_axis=None, tp: int = 1,
                      faults=None, robust=None):
    """R fused rounds of the PROPOSED protocol on the mesh layout
    (see `_mesh_rounds_scan`), keyed bitwise-identically to
    `protocol.gan_rounds_scan` — including the fault realization
    (dropout masks, corruption draws) under a FaultConfig."""
    check_faults_tp(faults, robust, tp_axis, tp)
    check_ring_support(avg_impl, device_axes, tp_axis, tp, faults,
                       robust)
    build = lambda: _mesh_rounds_scan(
        partial(_proposed_slice_round, spec, pcfg, device_axes,
                faults, robust),
        PROPOSED_STACKED_KEYS, PROPOSED_METRICS, PROPOSED_PAYLOAD, pcfg,
        mesh, n_rounds, channel=channel, scheduler=scheduler,
        device_axes=device_axes, disc_step_flops=disc_step_flops,
        gen_step_flops=gen_step_flops, uplink_bits=uplink_bits,
        avg_impl=avg_impl, fedgan=False, eval_fn=eval_fn,
        eval_every=eval_every, tp_axis=tp_axis, tp=tp, faults=faults)
    if eval_fn is not None:
        return build()   # per-run closures; never memoized
    return _memo_builder(
        _scan_memo_key("proposed_scan", spec, pcfg, mesh, n_rounds,
                       channel, scheduler, device_axes, disc_step_flops,
                       gen_step_flops, uplink_bits, avg_impl, tp_axis,
                       tp, faults, robust),
        build)


def fedgan_shard_rounds_scan(spec: GanModelSpec, pcfg: ProtocolConfig, mesh,
                             n_rounds: int, *, channel, scheduler,
                             device_axes=("data",),
                             disc_step_flops: float = 1e9,
                             gen_step_flops: float = 1e9,
                             uplink_bits: Optional[int] = None,
                             avg_impl: str = "pallas",
                             eval_fn: Optional[Callable] = None,
                             eval_every: int = 0, tp_axis=None,
                             tp: int = 1, faults=None, robust=None):
    """R fused FEDGAN rounds on the mesh layout: per-device joint D+G
    local iterations, the single two-net quantized uplink payload,
    Algorithm-2-style averaging of BOTH networks, and the FedGAN
    wall-clock composition — one donated shard_map `lax.scan` dispatch,
    keyed bitwise-identically to `fedgan.fedgan_rounds_scan` so the
    host oracle pins it."""
    check_faults_tp(faults, robust, tp_axis, tp)
    check_ring_support(avg_impl, device_axes, tp_axis, tp, faults,
                       robust)
    build = lambda: _mesh_rounds_scan(
        partial(_fedgan_slice_round, spec, pcfg, device_axes,
                faults, robust),
        FEDGAN_STACKED_KEYS, FEDGAN_METRICS, FEDGAN_PAYLOAD, pcfg, mesh,
        n_rounds, channel=channel, scheduler=scheduler,
        device_axes=device_axes, disc_step_flops=disc_step_flops,
        gen_step_flops=gen_step_flops, uplink_bits=uplink_bits,
        avg_impl=avg_impl, fedgan=True, eval_fn=eval_fn,
        eval_every=eval_every, tp_axis=tp_axis, tp=tp, faults=faults)
    if eval_fn is not None:
        return build()
    return _memo_builder(
        _scan_memo_key("fedgan_scan", spec, pcfg, mesh, n_rounds,
                       channel, scheduler, device_axes, disc_step_flops,
                       gen_step_flops, uplink_bits, avg_impl, tp_axis,
                       tp, faults, robust),
        build)
