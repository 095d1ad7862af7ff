"""THE PAPER'S CONTRIBUTION — the distributed GAN training protocol.

One communication round (Section II-B, Section III):

  Step 1  server schedules S ⊆ K devices          (core.scheduling, host)
  Step 2  scheduled devices run Algorithm 1 (n_d local discriminator SGD
          steps); under the PARALLEL schedule the server simultaneously
          runs Algorithm 3 from the same round-start parameters, with
          shared-seed noise
  Step 3  devices upload local discriminators     (16-bit, core.quantize)
  Step 4  server averages them — Algorithm 2      (core.averaging)
  Step 5  server broadcasts the global GAN
  SERIAL schedule: Algorithm 3 runs after Step 4 against the fresh
          global discriminator.

`gan_round` is a pure jittable function: the paper's K devices appear as
a stacked leading axis, so the SAME code runs (a) on one chip or the CPU
for the paper-scale experiments and (b) under pjit on the production
mesh where the stacked axis is sharded over ("pod","data") and Algorithm
2's weighted mean lowers to the ICI all-reduce (DESIGN.md §2).

How Step 2 runs over the stacked axis depends on that sharding alone:
- unsharded (no `constrain_stacked`, every `Trainer` round): one device
  after another (`devices_round`, a `lax.map`), each on plain
  convolutions of batch m_k, with the shared fake batches made once per
  local step for all of them;
- sharded (`constrain_stacked`, launch/steps.py's GSPMD path):
  `jax.vmap(device_update)`, so each slice of the sharded axis computes
  only its own devices; the batching makes every discriminator
  convolution a K-way grouped one.
`hoist_fakes` keeps the vmap too (`devices_round_hoisted`).

The model is abstracted by `GanModelSpec`, so DCGAN (the paper's
experiment) and every assigned backbone-GAN use one protocol
implementation.

FUSED MULTI-ROUND ENGINE: `rounds_scan` folds R complete rounds of ANY
round function — Step 1 scheduling (core.jax_scheduling), channel
timing + straggler exclusion (core.jax_channel) with the actual
quantized payload size, the round's model math (with the Step 3
quantized uplink inside), optional IN-SCAN FID via `lax.cond`, and the
Fig. 1/Fig. 2 wall-clock composition — into a single `lax.scan`, so one
XLA dispatch advances R communication rounds and returns stacked
per-round metrics/wallclock/masks[/fid]. `gan_rounds_scan` instantiates
it for the proposed protocol and `fedgan.fedgan_rounds_scan` for the
FedGAN baseline (Fig. 5's comparison runs both fused). The host-side
per-round loop in `core.engine.Trainer(driver="host")` is retained as
the equivalence ORACLE: for deterministic schedulers (or
`fading=False`) the fused path must reproduce its masks bitwise and its
params/metrics to float32 round-off (tests/test_driver_equivalence.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ProtocolConfig
from repro.core import faults as faults_lib
from repro.core import jax_channel, jax_scheduling, losses, quantize
from repro.core import stages
from repro.core.averaging import weighted_average, broadcast_like
from repro.optim import make_optimizer, apply_updates
from repro.optim.optimizers import tree_add


def _accumulated_grad(loss_fn, params, batch_axis_trees, total: int,
                      micro: Optional[int]):
    """value_and_grad with gradient accumulation over microbatches.

    loss_fn(params, *slices) -> scalar mean loss over the slice.
    batch_axis_trees: pytrees whose leaves have leading axis `total`,
    sliced jointly into `total // micro` chunks.
    """
    if micro is None or micro >= total:
        loss, grads = jax.value_and_grad(loss_fn)(params, *batch_axis_trees)
        return loss, grads
    assert total % micro == 0, f"micro {micro} must divide batch {total}"
    n_chunks = total // micro

    def chunk(i, tree):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, i * micro, micro,
                                                   axis=0), tree)

    def body(carry, i):
        loss_acc, grad_acc = carry
        slices = [chunk(i, t) for t in batch_axis_trees]
        loss, grads = jax.value_and_grad(loss_fn)(params, *slices)
        return (loss_acc + loss, tree_add(grad_acc, grads)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (loss_sum, grad_sum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zeros), jnp.arange(n_chunks))
    scale = 1.0 / n_chunks
    return loss_sum * scale, jax.tree.map(lambda g: g * scale, grad_sum)

# PRNG salts: the SHARED noise stream (paper: "identical pseudo random
# sequence" between server and devices) vs device-private data sampling.
_SALT_SHARED_Z = 0x5EED
_SALT_DATA = 0xDA7A


@dataclasses.dataclass(frozen=True)
class GanModelSpec:
    """Adapter between the protocol and a concrete (G, D) pair.

    sample_z(key, n)                 -> noise batch
    gen_apply(gen_params, z)         -> fake data batch
    disc_real(disc_params, batch)    -> logits (n,) on real data
    disc_fake(disc_params, fake)     -> logits (n,) on generated data

    tp_axis: set by TP-aware builders (`make_backbone_spec(tp_axis=)`,
    `gan.mlp_gan_spec(tp_axis=)`) when the apply functions contain
    in-slice Megatron collectives over that manual mesh axis — the
    params they receive must then be model-axis SHARDS. The mesh
    engine validates this against its own tp setting
    (`engine.Trainer(tp=)`), because a mismatch computes silently
    wrong results: a dense spec consumes shards shape-consistently but
    never psums the partial products.
    """
    sample_z: Callable
    gen_apply: Callable
    disc_real: Callable
    disc_fake: Callable
    gen_loss_variant: str = "minimax"
    tp_axis: Optional[str] = None


def make_train_state(key, init_fn, pcfg: ProtocolConfig, n_devices: int):
    """init_fn(key) -> {"gen": ..., "disc": ...}."""
    params = init_fn(key)
    gen_opt = make_optimizer(pcfg.optimizer, pcfg.lr_g).init(params["gen"])
    disc_opt_one = make_optimizer(pcfg.optimizer, pcfg.lr_d).init(params["disc"])
    # per-device local optimizer state (persists locally, never averaged)
    disc_opt = broadcast_like(disc_opt_one, n_devices)
    return {"gen": params["gen"], "disc": params["disc"],
            "gen_opt": gen_opt, "disc_opt": disc_opt}


# ---------------------------------------------------------------------------
# Algorithm 1 — device k's update
# ---------------------------------------------------------------------------

def _shared_z_key(round_key, j):
    """Local/server step j's key on the SHARED noise stream."""
    return jax.random.fold_in(jax.random.fold_in(round_key, _SALT_SHARED_Z), j)


@stages.stage(stages.A1_LOCAL)
def shared_fakes(spec: GanModelSpec, pcfg: ProtocolConfig, gen_params,
                 round_key):
    """The n_d fake batches of Algorithm 1, stacked (n_d, m_k, ...): G at
    the round-start theta on the shared stream, the same for every
    device, so one generator forward per local step serves them all."""
    return jax.lax.map(
        lambda j: spec.gen_apply(gen_params, spec.sample_z(
            _shared_z_key(round_key, j), pcfg.sample_size)),
        jnp.arange(pcfg.n_d))


@stages.stage(stages.A1_LOCAL)
def device_update(spec: GanModelSpec, pcfg: ProtocolConfig, gen_params,
                  disc_params, disc_opt, data_local, round_key, dev_index,
                  fakes=None, stacked=False):
    """n_d mini-batch steps ascending eq (2) on the LOCAL data shard.

    data_local: pytree with leading axis n_k (the device's private data);
    with `stacked`, every device's shards (K, n_k, ...), of which the
    steps read row `dev_index`'s with one gather per step.
    Fresh samples each step (Algorithm 1 line 5): m_k indices drawn with
    replacement from the local shard; noise from the SHARED stream.
    fakes: the `shared_fakes` batches, (n_d, m_k, ...); None makes them
    here, one generator forward per step.
    """
    n_local = jax.tree_util.tree_leaves(data_local)[0].shape[int(stacked)]
    m = pcfg.sample_size
    opt = make_optimizer(pcfg.optimizer, pcfg.lr_d)

    def rows(a, idx):
        return a[dev_index, idx] if stacked else jnp.take(a, idx, axis=0)

    def one_step(carry, j):
        disc, opt_state = carry
        kz = _shared_z_key(round_key, j)
        kx = jax.random.fold_in(
            jax.random.fold_in(jax.random.fold_in(round_key, _SALT_DATA),
                               dev_index), j)
        idx = jax.random.randint(kx, (m,), 0, n_local)
        x = jax.tree.map(lambda a: rows(a, idx), data_local)
        if fakes is None:                         # round-start theta
            fake = spec.gen_apply(gen_params, spec.sample_z(kz, m))
        else:
            fake = fakes[j]

        def neg_obj(phi, x_mb, fake_mb):
            return -losses.disc_objective(spec.disc_real(phi, x_mb),
                                          spec.disc_fake(phi, fake_mb))

        loss, grads = _accumulated_grad(neg_obj, disc, [x, fake], m,
                                        pcfg.micro_batch_d)
        updates, opt_state = opt.update(grads, opt_state, disc)
        disc = apply_updates(disc, updates)       # eq (3): ascent on eq (2)
        return (disc, opt_state), -loss

    (disc, opt_state), objs = jax.lax.scan(
        one_step, (disc_params, disc_opt), jnp.arange(pcfg.n_d))
    return disc, opt_state, objs[-1]


@stages.stage(stages.A1_LOCAL)
def devices_round(spec: GanModelSpec, pcfg: ProtocolConfig, gen_params,
                  disc_params, disc_opt_stacked, data_stacked, round_key):
    """Algorithm 1 for ALL devices, one device after another.

    Every device starts from the global `disc_params` and runs its n_d
    steps on plain convolutions of batch m_k, reading its m_k rows of
    `data_stacked` (K, n_k, ...) with one gather a step; the shared fake
    batches are made once for all of them (`shared_fakes`). The same
    math as `jax.vmap(device_update)`, whose batching turns each
    discriminator convolution into one K-way grouped convolution.
    Returns the stacked (discs, opt states, objectives).
    """
    n_devices = jax.tree_util.tree_leaves(data_stacked)[0].shape[0]
    fakes = shared_fakes(spec, pcfg, gen_params, round_key)
    return jax.lax.map(
        lambda xs: device_update(spec, pcfg, gen_params, disc_params, xs[0],
                                 data_stacked, round_key, xs[1], fakes,
                                 True),
        (disc_opt_stacked, jnp.arange(n_devices)))


@stages.stage(stages.A1_LOCAL)
def devices_round_hoisted(spec: GanModelSpec, pcfg: ProtocolConfig,
                          gen_params, disc_stacked, disc_opt_stacked,
                          data_stacked, round_key):
    """Algorithm 1 for ALL devices with the fake batch HOISTED.

    The shared noise stream (Section III-A) makes every device's fake
    batch at local step j identical, so G(theta, z_j) runs ONCE per step
    — batch-shardable over the device axes — instead of once per device.
    Bitwise-identical math to the vmapped path; K x fewer generator
    forwards. Loop order becomes scan-over-steps(vmap-over-devices).
    """
    n_devices = jax.tree_util.tree_leaves(data_stacked)[0].shape[0]
    n_local = jax.tree_util.tree_leaves(data_stacked)[0].shape[1]
    m = pcfg.sample_size
    opt = make_optimizer(pcfg.optimizer, pcfg.lr_d)

    def one_step(carry, j):
        discs, opts = carry
        z = spec.sample_z(_shared_z_key(round_key, j), m)
        fake = spec.gen_apply(gen_params, z)      # once, for every device

        def one_device(disc, opt_state, data_local, dev_index):
            kx = jax.random.fold_in(
                jax.random.fold_in(jax.random.fold_in(round_key, _SALT_DATA),
                                   dev_index), j)
            idx = jax.random.randint(kx, (m,), 0, n_local)
            x = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), data_local)

            def neg_obj(phi, x_mb, fake_mb):
                return -losses.disc_objective(spec.disc_real(phi, x_mb),
                                              spec.disc_fake(phi, fake_mb))

            loss, grads = _accumulated_grad(neg_obj, disc, [x, fake], m,
                                            pcfg.micro_batch_d)
            updates, opt_state = opt.update(grads, opt_state, disc)
            return apply_updates(disc, updates), opt_state, -loss

        discs, opts, objs = jax.vmap(one_device, in_axes=(0, 0, 0, 0))(
            discs, opts, data_stacked, jnp.arange(n_devices))
        return (discs, opts), objs

    (discs, opts), objs = jax.lax.scan(
        one_step, (disc_stacked, disc_opt_stacked), jnp.arange(pcfg.n_d))
    return discs, opts, objs[-1]


# ---------------------------------------------------------------------------
# Algorithm 3 — server generator update
# ---------------------------------------------------------------------------

@stages.stage(stages.A3_SERVER)
def server_update(spec: GanModelSpec, pcfg: ProtocolConfig, gen_params,
                  gen_opt, disc_params, round_key):
    """n_g steps descending eq (1) against the given discriminator.
    Uses the SAME shared noise stream as the devices (parallel-schedule
    seed consistency, Section III-A)."""
    M = pcfg.server_sample_size
    opt = make_optimizer(pcfg.optimizer, pcfg.lr_g)

    def one_step(carry, j):
        gen, opt_state = carry
        z = spec.sample_z(_shared_z_key(round_key, j), M)

        def obj(theta, z_mb):
            fake = spec.gen_apply(theta, z_mb)
            return losses.gen_objective(spec.disc_fake(disc_params, fake),
                                        variant=spec.gen_loss_variant)

        loss, grads = _accumulated_grad(obj, gen, [z], M, pcfg.micro_batch_g)
        updates, opt_state = opt.update(grads, opt_state, gen)
        gen = apply_updates(gen, updates)         # eq (4): descent on eq (1)
        return (gen, opt_state), loss

    (gen, gen_opt), objs = jax.lax.scan(
        one_step, (gen_params, gen_opt), jnp.arange(pcfg.n_g))
    return gen, gen_opt, objs[-1]


# ---------------------------------------------------------------------------
# One communication round (Steps 1–5)
# ---------------------------------------------------------------------------

def gan_round(spec: GanModelSpec, pcfg: ProtocolConfig, state, data_stacked,
              weights, round_key, *, constrain_stacked=None, faults=None,
              reducer=None):
    """One full round.

    state: {"gen", "disc", "gen_opt", "disc_opt"} — disc/disc_opt are the
           GLOBAL discriminator (post-broadcast) and the per-device local
           optimizer states (stacked K). An optional "fault" entry holds
           the free-rider stale-upload cache (core/faults.py).
    data_stacked: pytree, leading axes (K, n_k, ...) — device-private shards.
    weights: (K,) — m_k for scheduled devices, 0 otherwise (Step 1 output;
           also encodes straggler exclusion, footnote 1).
    faults:  optional FaultConfig — free-riders replay the stale cache and
           byzantine workers upload scaled noise, keyed by `round_key` so
           every execution layout realizes identical corruption.
    reducer: optional RobustConfig — Step 4 aggregates with the selected
           robust reducer instead of the plain weighted mean.
    Returns (new_state, metrics).
    """
    n_devices = weights.shape[0]
    if pcfg.hoist_fakes or constrain_stacked is not None:
        # Step 2 — Algorithm 1 on every device slice, vmapped: each
        # device's discriminator convolutions batch into one K-way grouped
        # convolution. On the pod mesh (`constrain_stacked`) the stacked
        # axis is sharded, so each slice computes only its own.
        disc_stacked = broadcast_like(state["disc"], n_devices)  # Step 5
        if constrain_stacked is not None:
            # pjit path: pin the per-device replicas to the device mesh
            # axes so GSPMD keeps Algorithm 1 embarrassingly parallel.
            disc_stacked = constrain_stacked(disc_stacked)
        if pcfg.hoist_fakes:
            new_discs, new_disc_opt, disc_objs = devices_round_hoisted(
                spec, pcfg, state["gen"], disc_stacked, state["disc_opt"],
                data_stacked, round_key)
        else:
            dev_fn = jax.vmap(
                lambda d, o, x, i: device_update(spec, pcfg, state["gen"],
                                                 d, o, x, round_key, i),
                in_axes=(0, 0, 0, 0))
            new_discs, new_disc_opt, disc_objs = dev_fn(
                disc_stacked, state["disc_opt"], data_stacked,
                jnp.arange(n_devices))
    else:
        # Step 2 — Algorithm 1 one device at a time, on plain convolutions
        # of batch m_k, each from the global discriminator (Step 5 of the
        # previous round); nothing is broadcast.
        new_discs, new_disc_opt, disc_objs = devices_round(
            spec, pcfg, state["gen"], state["disc"], state["disc_opt"],
            data_stacked, round_key)

    # Step 3 — each device quantizes its upload (paper Section IV,
    # 16 bits/param by default; >=32 bits is the float32 identity).
    new_discs = quantize.roundtrip_stacked(round_key, new_discs,
                                           pcfg.quantize_bits)

    # Hostile uploads (core/faults.py): free-riders replay the stale
    # cache, byzantine devices upload scaled noise — applied AFTER the
    # quantized uplink, exactly where the server receives payloads.
    prog = faults_lib.fault_program(faults)
    if prog is not None and prog.corrupts:
        stale = state["fault"]["stale"] if "fault" in state else None
        new_discs = faults_lib.corrupt_uploads_stacked(
            prog, round_key, new_discs, stale=stale)

    # Steps 3–4 — Algorithm 2: weighted averaging (the uplink collective),
    # optionally through a robust reducer (kernels/robust_avg). On a
    # no-survivor round (every weight zero) the previous global
    # discriminator is kept — averaging nothing is not "multiply by ~0".
    disc_avg = weighted_average(new_discs, weights, robust=reducer,
                                fallback=state["disc"])

    # Algorithm 3 — serial: against fresh phi^{t+1}; parallel: against the
    # round-start phi^t, dataflow-independent of the averaging collective.
    disc_for_gen = disc_avg if pcfg.schedule == "serial" else state["disc"]
    new_gen, new_gen_opt, gen_obj = server_update(
        spec, pcfg, state["gen"], state["gen_opt"], disc_for_gen, round_key)

    w = weights.astype(jnp.float32)
    wsum = jnp.maximum(w.sum(), 1e-12)
    metrics = {
        "disc_objective": jnp.sum(disc_objs * w) / wsum,
        "gen_objective": gen_obj,
        "participation": (w > 0).astype(jnp.float32).mean(),
    }
    new_state = {"gen": new_gen, "disc": disc_avg,
                 "gen_opt": new_gen_opt, "disc_opt": new_disc_opt}
    if "fault" in state:
        # advance the one-round-stale free-rider cache to this round's
        # broadcast payload (what a free-rider would have received and
        # can replay next round without computing)
        new_state["fault"] = {"stale": state["disc"]}
    return new_state, metrics


# ---------------------------------------------------------------------------
# Fused multi-round driver — R rounds per XLA dispatch
# ---------------------------------------------------------------------------

# PRNG salts for the per-round channel/scheduler randomness. The host
# loop's numpy stream is sequential; the fused path derives independent
# keys per round from the SAME root key the host loop folds for model
# math, so model randomness (and hence params) agrees round-for-round.
_SALT_RATES = 0x4A7E5
_SALT_SCHED = 0x5C4ED
_SALT_TIMING = 0x7133


def count_params(tree) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))


def schedule_and_time(pcfg: ProtocolConfig, channel, scheduler, sched_carry,
                      round_key, *, disc_nparams: int, gen_nparams: int,
                      disc_step_flops: float, gen_step_flops: float,
                      fedgan: bool, uplink_bits, faults=None):
    """Step 1 + channel accounting for one round, shared by EVERY
    execution layout of the fused engine (stacked `rounds_scan` and the
    mesh `shard_round.shard_rounds_scan`): the per-round rates/scheduler/
    timing keys are derived from `round_key` with fixed salts, so both
    layouts see bitwise-identical masks, stragglers, and weights.

    With a FaultConfig, per-round dropout (keyed off the SAME round_key,
    core/faults.py) knocks scheduled devices out of the mask before
    timing, and the program's per-device compute multipliers (stragglers
    slower, free-riders free) feed the wallclock model.

    Returns (mask, new_sched_carry, timing, weights).
    """
    k_rates = jax.random.fold_in(round_key, _SALT_RATES)
    k_sched = jax.random.fold_in(round_key, _SALT_SCHED)
    k_timing = jax.random.fold_in(round_key, _SALT_TIMING)

    # Schedule against a fresh fading draw, then time the round (second
    # draw, mirroring the host loop's two rng calls).
    rates = channel.uplink_rates(k_rates, scheduler.n_scheduled)
    mask, sched_carry = jax_scheduling.schedule_step(scheduler, sched_carry,
                                                     rates, k_sched)
    prog = faults_lib.fault_program(faults)
    compute_mult = None
    if prog is not None:
        mask = mask & ~prog.dropout_mask(round_key)
        compute_mult = prog.compute_mult
    timing = channel.round_timing(
        k_timing, mask, disc_params=disc_nparams, gen_params=gen_nparams,
        disc_step_flops=disc_step_flops, gen_step_flops=gen_step_flops,
        n_d=pcfg.n_d, n_g=pcfg.n_g, fedgan=fedgan, uplink_bits=uplink_bits,
        compute_mult=compute_mult)
    active = mask & ~timing.stragglers
    weights = jnp.where(active, float(pcfg.sample_size),
                        0.0).astype(jnp.float32)
    return mask, sched_carry, timing, weights


def uplink_payload_bits(state, pcfg: ProtocolConfig, *,
                        fedgan: bool = False) -> int:
    """Per-device upload payload in bits at the protocol's quantization
    width: phi only for the proposed framework, theta AND phi for FedGAN
    (the communication asymmetry Fig. 5 measures)."""
    bits = quantize.tree_bits(state["disc"], pcfg.quantize_bits)
    if fedgan:
        bits += quantize.tree_bits(state["gen"], pcfg.quantize_bits)
    return bits


def rounds_scan(round_fn, pcfg: ProtocolConfig, state, data_stacked, key,
                n_rounds: int, *, channel, scheduler, sched_carry=None,
                start_round=0, disc_step_flops: float = 1e9,
                gen_step_flops: float = 1e9, fedgan: bool = False,
                uplink_bits: Optional[int] = None,
                eval_fn: Optional[Callable] = None, eval_every: int = 0,
                faults=None):
    """The UNIFIED fused round engine: R communication rounds of ANY
    round function in one `lax.scan`.

    round_fn:  (state, data_stacked, weights, round_key) -> (state,
               metrics) — `gan_round` (via `gan_rounds_scan`) or
               `fedgan.fedgan_round` (via `fedgan.fedgan_rounds_scan`).
    channel:   core.jax_channel.JaxChannel (static placement, jittable)
    scheduler: core.jax_scheduling.JaxScheduler (policy static)
    sched_carry: scheduler carry from a previous chunk (None = fresh)
    start_round: absolute index of the first round; round t's model key
        is `fold_in(key, t)`, matching the host loop's per-round fold so
        chunked fused runs and the host oracle see identical streams.
    fedgan:    switches the channel's timing/wallclock composition to
        the FedGAN round shape (local G+D compute, both nets uploaded).
    uplink_bits: per-device upload payload in bits; None computes it
        from the state at `pcfg.quantize_bits` (`uplink_payload_bits`),
        so ablation bit widths shrink the simulated upload time too.
    eval_fn:   optional JITTABLE (gen_params, t) -> scalar, evaluated
        IN-SCAN via `lax.cond` on rounds where (t+1) % eval_every == 0;
        out["fid"] is the per-round series (NaN placeholder on skipped
        rounds) and out["fid_eval"] the boolean did-evaluate mask.

    Returns (state, sched_carry, out) where out stacks per-round
    {"metrics": {...: (R,)}, "wallclock_s": (R,), "mask": (R, K) bool,
    "weights": (R, K)[, "fid": (R,), "fid_eval": (R,)]}.
    """
    if sched_carry is None:
        sched_carry = scheduler.init_carry()
    disc_nparams = count_params(state["disc"])
    gen_nparams = count_params(state["gen"])
    if uplink_bits is None:
        uplink_bits = uplink_payload_bits(state, pcfg, fedgan=fedgan)

    def body(carry, t):
        st, sc = carry
        round_key = jax.random.fold_in(key, t)

        # Step 1 + channel accounting (layout-shared keying)
        mask, sc, timing, weights = schedule_and_time(
            pcfg, channel, scheduler, sc, round_key,
            disc_nparams=disc_nparams, gen_nparams=gen_nparams,
            disc_step_flops=disc_step_flops, gen_step_flops=gen_step_flops,
            fedgan=fedgan, uplink_bits=uplink_bits, faults=faults)

        # Steps 2-5
        st, metrics = round_fn(st, data_stacked, weights, round_key)
        wall = jax_channel.round_wallclock(timing, mask,
                                           schedule=pcfg.schedule,
                                           fedgan=fedgan)
        out = {"metrics": metrics, "wallclock_s": wall, "mask": mask,
               "weights": weights}
        if eval_fn is not None and eval_every > 0:
            # In-scan eval: lax.cond skips the branch on non-eval rounds
            # at runtime, so eval cost is paid only every eval_every
            # rounds while the chunk stays ONE compiled function. The
            # explicit eval mask (not a NaN sentinel) keeps a genuinely
            # NaN metric on an eval round distinguishable from "no eval".
            do_eval = (t + 1) % eval_every == 0
            out["fid"] = jax.lax.cond(
                do_eval,
                lambda g: jnp.float32(eval_fn(g, t)),
                lambda g: jnp.float32(jnp.nan), st["gen"])
            out["fid_eval"] = do_eval
        return (st, sc), out

    rounds = jnp.asarray(start_round) + jnp.arange(n_rounds)
    (state, sched_carry), out = jax.lax.scan(body, (state, sched_carry),
                                             rounds)
    return state, sched_carry, out


def gan_rounds_scan(spec: GanModelSpec, pcfg: ProtocolConfig, state,
                    data_stacked, key, n_rounds: int, *,
                    channel, scheduler, sched_carry=None, start_round=0,
                    disc_step_flops: float = 1e9,
                    gen_step_flops: float = 1e9,
                    uplink_bits: Optional[int] = None,
                    eval_fn: Optional[Callable] = None,
                    eval_every: int = 0, faults=None, reducer=None):
    """R fused rounds of the PROPOSED protocol (see `rounds_scan`)."""
    round_fn = lambda st, d, w, k: gan_round(spec, pcfg, st, d, w, k,
                                             faults=faults, reducer=reducer)
    return rounds_scan(round_fn, pcfg, state, data_stacked, key, n_rounds,
                       channel=channel, scheduler=scheduler,
                       sched_carry=sched_carry, start_round=start_round,
                       disc_step_flops=disc_step_flops,
                       gen_step_flops=gen_step_flops, fedgan=False,
                       uplink_bits=uplink_bits, eval_fn=eval_fn,
                       eval_every=eval_every, faults=faults)


def centralized_step(spec: GanModelSpec, pcfg: ProtocolConfig, state, data,
                     round_key):
    """Centralized baseline (Fig. 4): one worker, same budget — n_d
    discriminator steps on the pooled data then n_g generator steps."""
    disc, disc_opt, disc_obj = device_update(
        spec, pcfg, state["gen"], state["disc"],
        jax.tree.map(lambda x: x[0], state["disc_opt"]), data, round_key,
        jnp.int32(0))
    gen, gen_opt, gen_obj = server_update(
        spec, pcfg, state["gen"], state["gen_opt"], disc, round_key)
    new_state = {"gen": gen, "disc": disc, "gen_opt": gen_opt,
                 "disc_opt": jax.tree.map(lambda x: x[None], disc_opt)}
    return new_state, {"disc_objective": disc_obj, "gen_objective": gen_obj,
                       "participation": jnp.float32(1.0)}
