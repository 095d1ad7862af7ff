"""FedGAN baseline [9] (Rasouli, Sun, Rajagopal, arXiv:2006.07228).

Each device trains BOTH a local generator and a local discriminator for
n local iterations (each iteration: one discriminator ascent step + one
generator descent step on local data); the server only averages the two
parameter sets. Compared with the proposed framework, each device does
~2x the computation per round and uploads ~2x the bytes (theta AND phi)
— the communication/computation asymmetry that Fig. 5 measures.

`fedgan_rounds_scan` runs R FedGAN rounds per XLA dispatch through the
same unified engine (`protocol.rounds_scan`) as the proposed protocol:
scheduling, channel timing with the FedGAN wallclock composition, the
quantized two-net uplink, and optional in-scan FID are all one
`lax.scan`. The per-round host loop in `core.engine` stays the oracle.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ProtocolConfig
from repro.core import faults as faults_lib
from repro.core import losses, quantize, stages
from repro.core.averaging import weighted_average, broadcast_like
from repro.core.protocol import (GanModelSpec, rounds_scan,
                                 _SALT_SHARED_Z, _SALT_DATA)
from repro.optim import make_optimizer, apply_updates


@stages.stage(stages.A1_LOCAL)
def fedgan_device_update(spec: GanModelSpec, pcfg: ProtocolConfig,
                         gen0, disc0, gen_opt, disc_opt, data_local,
                         round_key, dev_index):
    """n_d local iterations of (disc step, gen step) on device data."""
    n_local = jax.tree_util.tree_leaves(data_local)[0].shape[0]
    m = pcfg.sample_size
    d_opt = make_optimizer(pcfg.optimizer, pcfg.lr_d)
    g_opt = make_optimizer(pcfg.optimizer, pcfg.lr_g)

    def one_iter(carry, j):
        gen, disc, g_state, d_state = carry
        kz = jax.random.fold_in(jax.random.fold_in(round_key, _SALT_SHARED_Z), j)
        kx = jax.random.fold_in(
            jax.random.fold_in(jax.random.fold_in(round_key, _SALT_DATA),
                               dev_index), j)
        idx = jax.random.randint(kx, (m,), 0, n_local)
        x = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), data_local)
        z = spec.sample_z(kz, m)

        # discriminator ascent on eq (2)
        fake = spec.gen_apply(gen, z)

        def neg_obj(phi):
            return -losses.disc_objective(spec.disc_real(phi, x),
                                          spec.disc_fake(phi, fake))

        d_grads = jax.grad(neg_obj)(disc)
        d_updates, d_state = d_opt.update(d_grads, d_state, disc)
        disc = apply_updates(disc, d_updates)

        # generator descent on eq (1) against the freshly updated disc
        def gen_obj(theta):
            f = spec.gen_apply(theta, z)
            return losses.gen_objective(spec.disc_fake(disc, f),
                                        variant=spec.gen_loss_variant)

        g_grads = jax.grad(gen_obj)(gen)
        g_updates, g_state = g_opt.update(g_grads, g_state, gen)
        gen = apply_updates(gen, g_updates)
        return (gen, disc, g_state, d_state), None

    (gen, disc, g_state, d_state), _ = jax.lax.scan(
        one_iter, (gen0, disc0, gen_opt, disc_opt), jnp.arange(pcfg.n_d))
    return gen, disc, g_state, d_state


def fedgan_round(spec: GanModelSpec, pcfg: ProtocolConfig, state,
                 data_stacked, weights, round_key, *, faults=None,
                 reducer=None):
    """One FedGAN communication round: local joint updates, average BOTH
    generators and discriminators (server does model averaging only).
    `faults`/`reducer` mirror `protocol.gan_round`: corruption hits the
    COMBINED {"gen", "disc"} payload after the quantized uplink, and the
    robust reducer aggregates that combined tree in ONE reduction
    (matching the mesh layout's single-payload hot path) before the two
    nets are split back out."""
    n_devices = weights.shape[0]
    gen_stacked = broadcast_like(state["gen"], n_devices)
    disc_stacked = broadcast_like(state["disc"], n_devices)

    dev_fn = jax.vmap(
        lambda g, d, go, do, x, i: fedgan_device_update(
            spec, pcfg, g, d, go, do, x, round_key, i),
        in_axes=(0, 0, 0, 0, 0, 0))
    new_gens, new_discs, new_gen_opt, new_disc_opt = dev_fn(
        gen_stacked, disc_stacked, state["gen_opt"], state["disc_opt"],
        data_stacked, jnp.arange(n_devices))

    # FedGAN uploads BOTH nets in one payload — quantized as a single
    # tree per device (one stochastic-rounding draw per upload), keyed
    # from round_key alone so the host oracle and the fused engine
    # quantize bitwise-identically.
    payload = quantize.roundtrip_stacked(
        round_key, {"gen": new_gens, "disc": new_discs},
        pcfg.quantize_bits)

    prog = faults_lib.fault_program(faults)
    if prog is not None and prog.corrupts:
        stale = state["fault"]["stale"] if "fault" in state else None
        payload = faults_lib.corrupt_uploads_stacked(
            prog, round_key, payload, stale=stale)

    # No-survivor rounds keep the previous globals (see protocol.gan_round).
    prev = {"gen": state["gen"], "disc": state["disc"]}
    if reducer is not None:
        avg = weighted_average(payload, weights, robust=reducer,
                               fallback=prev)
        gen_avg, disc_avg = avg["gen"], avg["disc"]
    else:
        gen_avg = weighted_average(payload["gen"], weights,
                                   fallback=prev["gen"])
        disc_avg = weighted_average(payload["disc"], weights,
                                    fallback=prev["disc"])
    new_state = {"gen": gen_avg, "disc": disc_avg,
                 "gen_opt": new_gen_opt, "disc_opt": new_disc_opt}
    if "fault" in state:
        new_state["fault"] = {"stale": {"gen": state["gen"],
                                        "disc": state["disc"]}}
    return new_state, {"participation": (weights > 0).astype(jnp.float32).mean()}


def fedgan_rounds_scan(spec: GanModelSpec, pcfg: ProtocolConfig, state,
                       data_stacked, key, n_rounds: int, *,
                       channel, scheduler, sched_carry=None, start_round=0,
                       disc_step_flops: float = 1e9,
                       gen_step_flops: float = 1e9,
                       uplink_bits: Optional[int] = None,
                       eval_fn: Optional[Callable] = None,
                       eval_every: int = 0, faults=None, reducer=None):
    """R fused FedGAN rounds (see `protocol.rounds_scan`): the baseline
    gets the same one-dispatch-per-chunk engine as the proposed
    protocol, with `fedgan=True` selecting the two-net upload payload
    and the Fig. 5 wallclock composition."""
    round_fn = lambda st, d, w, k: fedgan_round(spec, pcfg, st, d, w, k,
                                                faults=faults,
                                                reducer=reducer)
    return rounds_scan(round_fn, pcfg, state, data_stacked, key, n_rounds,
                       channel=channel, scheduler=scheduler,
                       sched_carry=sched_carry, start_round=start_round,
                       disc_step_flops=disc_step_flops,
                       gen_step_flops=gen_step_flops, fedgan=True,
                       uplink_bits=uplink_bits, eval_fn=eval_fn,
                       eval_every=eval_every, faults=faults)


def make_fedgan_state(key, init_fn, pcfg: ProtocolConfig, n_devices: int):
    params = init_fn(key)
    g_opt = make_optimizer(pcfg.optimizer, pcfg.lr_g).init(params["gen"])
    d_opt = make_optimizer(pcfg.optimizer, pcfg.lr_d).init(params["disc"])
    return {"gen": params["gen"], "disc": params["disc"],
            "gen_opt": broadcast_like(g_opt, n_devices),
            "disc_opt": broadcast_like(d_opt, n_devices)}
