"""Training engine: drives communication rounds with device scheduling,
the wireless channel simulator, wall-clock accounting, and periodic
evaluation. This is the paper's experimental harness (Figs 3-6).

The round-execution stack has THREE orthogonal axes — ALGORITHM x
LAYOUT x DRIVER — and the matrix is COMPLETE for every combination
that is meaningful:

                    layout="stacked"          layout="mesh"
  proposed       host + fused              host + fused
  fedgan         host + fused              host + fused
  centralized    host only                 — (no device structure)

EXECUTION LAYOUT — how the paper's K devices map onto hardware:

  layout="stacked" (default) — devices are a stacked leading axis on
      one logical device; the proposed protocol's local updates run one
      device after another (FedGAN's are vmapped), and the averaging
      is a weighted mean over the axis (GSPMD lowers it to the
      all-reduce when the axis is mesh-sharded through launch/steps.py).
  layout="mesh" — devices are mesh slices under `jax.shard_map` with
      explicit collectives (core.shard_round): local updates touch no
      collective, the averaging is one all-gather + the Pallas `wavg`
      kernel per round (both nets in ONE payload for FedGAN), and any
      server math is replicated shared-seed computation. Requires >= K
      addressable devices (pass `mesh=` or let the Trainer build a
      (K, tp) host mesh). With `tp > 1` the mesh is 2-D
      (device x model): each paper-worker slice is a TP group running
      Megatron column/row-parallel matmuls with in-slice collectives on
      the `model` axis (the spec must be built TP-aware, e.g.
      `models.gan.mlp_gan_spec(tp_axis="model")` /
      `make_backbone_spec(tp_axis="model")`), while scheduling, channel
      timing, uplink keying, and the Algorithm-2 reduction stay on the
      device axes — each TP rank averages just its parameter shard.
      State, checkpoints, and histories stay GLOBAL-shaped (shard_map
      splits/reassembles), so checkpoints interoperate across tp
      widths. tp > 1 requires layout="mesh" (stacked TP is the GSPMD
      path through launch/steps.py).

DRIVER — how rounds are dispatched:

  driver="fused" — chunks of R rounds run as ONE XLA dispatch
      (`protocol.rounds_scan` / `fedgan.fedgan_rounds_scan` on the
      stacked layout, `shard_round.shard_rounds_scan` /
      `shard_round.fedgan_shard_rounds_scan` on the mesh layout):
      scheduling, channel timing, the quantized uplink, the model math,
      and wall-clock accounting all inside one `lax.scan`, state
      donated. With a JITTABLE fid_fn, FID runs IN-SCAN via lax.cond; a
      non-traceable fid_fn falls back to eval-boundary chunking.
  driver="host" — one round per dispatch with numpy scheduling/channel
      state. On the stacked layout this is the original per-round loop,
      retained as the EQUIVALENCE ORACLE: the fused drivers (BOTH
      layouts, BOTH fused algorithms) must reproduce its masks bitwise
      and params/metrics to float32 round-off
      (tests/test_driver_equivalence.py). On the mesh layout it
      dispatches the algorithm's single-round shard_map entry per round
      — the baseline `benchmarks/driver_bench.py --layout mesh`
      measures fused speedup against.
  driver="auto" (default) — fused where supported, host otherwise.

The per-algorithm construction (state init, per-round host function,
stacked fused scan, and the mesh single-round/fused-scan entries) lives
in the `_ALGORITHMS` strategy table instead of `__init__` branching.
Unsupported combinations RAISE instead of silently degrading: the
centralized baseline has no fused path and no mesh layout (its round
has no scheduling/channel/device structure to fold), so requesting
either for it is a ValueError.

CHECKPOINT/RESUME: `save_checkpoint`/`restore` serialize the model
state together with `_round_index`, `_clock`, and the scheduler carry
through `repro.checkpoint`, so a resumed fused run (either layout)
continues masks, params, AND the wallclock curve exactly — every
per-round random draw is keyed from the root key and the absolute round
index. Host-driver resume is exact only for deterministic schedulers
with fading off (its numpy streams are not serialized).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ProtocolConfig
from repro.core import protocol, fedgan, shard_round, stages
from repro.core import faults as faults_lib
from repro.core.channel import ChannelConfig, ChannelSimulator, round_wallclock
from repro.core.faults import FaultConfig
from repro.core.jax_channel import JaxChannel
from repro.core.jax_scheduling import JaxScheduler
from repro.core.scheduling import SchedulerState, schedule_round
from repro.kernels.robust_avg import ROBUST_METHODS, RobustConfig


@dataclasses.dataclass(frozen=True)
class _Algorithm:
    """Strategy record: how one algorithm builds state, its per-round
    host function, (when fused-capable) its stacked rounds-scan, and
    (when mesh-capable) its shard_map single-round / fused-scan
    entries."""
    make_state: Callable          # (key, init_fn, pcfg, n_devices) -> state
    round_fn: Callable  # (spec, pcfg, faults, reducer) -> (s,d,w,k) -> (s, m)
    rounds_scan: Optional[Callable] = None   # unified stacked engine entry
    mesh_round: Optional[Callable] = None    # (spec, pcfg, mesh,
    #                                  device_axes=, tp_axis=, tp=)
    mesh_rounds_scan: Optional[Callable] = None  # fused mesh engine entry
    payload: Optional[Callable] = None  # state -> uplink payload tree (the
    #                                  free-rider stale-cache initializer)
    fedgan: bool = False
    pooled: bool = False          # centralized: pools the data shards

    @property
    def fused(self) -> bool:
        return self.rounds_scan is not None

    @property
    def mesh(self) -> bool:
        return self.mesh_round is not None


_ALGORITHMS = {
    "proposed": _Algorithm(
        make_state=protocol.make_train_state,
        round_fn=lambda spec, pcfg, faults, reducer: (
            lambda s, d, w, k: protocol.gan_round(
                spec, pcfg, s, d, w, k, faults=faults, reducer=reducer)),
        rounds_scan=protocol.gan_rounds_scan,
        mesh_round=shard_round.shard_map_round,
        mesh_rounds_scan=shard_round.shard_rounds_scan,
        payload=shard_round.PROPOSED_PAYLOAD),
    "fedgan": _Algorithm(
        make_state=fedgan.make_fedgan_state,
        round_fn=lambda spec, pcfg, faults, reducer: (
            lambda s, d, w, k: fedgan.fedgan_round(
                spec, pcfg, s, d, w, k, faults=faults, reducer=reducer)),
        rounds_scan=fedgan.fedgan_rounds_scan,
        mesh_round=shard_round.fedgan_shard_map_round,
        mesh_rounds_scan=shard_round.fedgan_shard_rounds_scan,
        payload=shard_round.FEDGAN_PAYLOAD,
        fedgan=True),
    "centralized": _Algorithm(
        make_state=lambda key, init_fn, pcfg, n: protocol.make_train_state(
            key, init_fn, pcfg, 1),
        round_fn=lambda spec, pcfg, faults, reducer: (
            lambda s, d, w, k: protocol.centralized_step(spec, pcfg, s, d, k)),
        pooled=True),
}

# Algorithms with a fused multi-round scan path (the unified engine).
FUSED_ALGORITHMS = tuple(name for name, a in _ALGORITHMS.items() if a.fused)
# Algorithms with a mesh (shard_map) execution layout.
MESH_ALGORITHMS = tuple(name for name, a in _ALGORITHMS.items() if a.mesh)
LAYOUTS = ("stacked", "mesh")
# Algorithm-2 collective implementations on the mesh layout
# (core/averaging.py): flat gather + wavg kernel ("pallas", the
# default), per-leaf psum ("jnp"), or the quantized-payload ring
# collective ("ring", kernels/ring_wavg).
MESH_AVG_IMPLS = ("pallas", "jnp", "ring")


def mesh_algorithm(name: str) -> _Algorithm:
    """The strategy record for a mesh-capable algorithm — the ONE
    registry the launch layer (launch/steps.py, launch/train.py) reuses
    for state init and the fused mesh scan, so adding an algorithm here
    reaches every layer without parallel per-algorithm tables."""
    algo = _ALGORITHMS.get(name)
    if algo is None or not algo.mesh:
        raise ValueError(f"layout='mesh' supports algorithms "
                         f"{MESH_ALGORITHMS} (got {name!r})")
    return algo


# the per-round outputs of a fused chunk that the host reads back
_READBACK = ("metrics", "wallclock_s", "mask", "fid", "fid_eval")


@dataclasses.dataclass
class RoundRecord:
    round: int
    wallclock_s: float
    cumulative_s: float
    metrics: dict
    fid: Optional[float] = None
    mask: Optional[np.ndarray] = None   # (K,) bool — scheduled devices


class Trainer:
    """Runs the proposed protocol, FedGAN, or centralized training over a
    simulated device fleet. All model math is jitted; the fused driver
    additionally folds scheduling + channel timing into the same
    dispatch, while the host driver keeps them in numpy. See the module
    docstring for the algorithm x layout x driver matrix."""

    def __init__(self, spec: protocol.GanModelSpec, pcfg: ProtocolConfig,
                 init_fn: Callable, data_stacked, key, *,
                 algorithm: str = "proposed",
                 channel_cfg: Optional[ChannelConfig] = None,
                 disc_step_flops: float = 1e9, gen_step_flops: float = 1e9,
                 driver: str = "auto", layout: str = "stacked",
                 mesh=None, device_axes=("data",), tp: int = 1,
                 avg_impl: str = "pallas",
                 faults: Optional[FaultConfig] = None, reducer=None,
                 partition: Optional[str] = None, labels=None,
                 partition_alpha: float = 0.5, partition_seed: int = 0):
        if algorithm not in _ALGORITHMS:
            raise ValueError(f"unknown algorithm {algorithm!r} "
                             f"(have {tuple(_ALGORITHMS)})")
        algo = _ALGORITHMS[algorithm]
        if layout not in LAYOUTS:
            raise ValueError(f"unknown layout {layout!r} (have {LAYOUTS})")
        if layout == "mesh" and not algo.mesh:
            raise ValueError(
                f"layout='mesh' is not supported for algorithm "
                f"{algorithm!r} (mesh algorithms: {MESH_ALGORITHMS}); "
                f"use layout='stacked'")
        if tp < 1:
            raise ValueError(f"tp must be >= 1 (got {tp})")
        if tp > 1 and layout != "mesh":
            raise ValueError(
                f"tp={tp} requires layout='mesh' (in-slice tensor "
                f"parallelism is the 2-D shard_map engine; on the "
                f"stacked layout TP comes from GSPMD through "
                f"launch/steps.py)")
        # The spec's TP-awareness must match the engine's: a dense spec
        # consumes sharded params shape-consistently but never psums
        # the partial products — silently wrong, so refuse up front.
        spec_tp_axis = getattr(spec, "tp_axis", None)
        want_tp_axis = "model" if tp > 1 else None
        if layout == "mesh" and spec_tp_axis != want_tp_axis:
            raise ValueError(
                f"tp={tp} needs a spec built with "
                f"tp_axis={want_tp_axis!r}, got tp_axis="
                f"{spec_tp_axis!r} — rebuild it (e.g. "
                f"make_backbone_spec(tp_axis=...) / "
                f"mlp_gan_spec(tp_axis=...))")
        if layout != "mesh" and spec_tp_axis is not None:
            raise ValueError(
                f"spec was built with tp_axis={spec_tp_axis!r} (in-slice "
                f"collectives) but layout={layout!r} runs no shard_map; "
                f"rebuild the spec with tp_axis=None")
        if driver not in ("auto", "fused", "host"):
            raise ValueError(f"unknown driver {driver!r}")
        if driver == "fused" and not algo.fused:
            raise ValueError(
                f"driver='fused' is not supported for algorithm "
                f"{algorithm!r} (fused algorithms: {FUSED_ALGORITHMS}); "
                f"use driver='host' or 'auto'")
        if driver == "auto":
            driver = "fused" if algo.fused else "host"

        # Hostile-worker regime (core/faults.py + kernels/robust_avg):
        # `reducer` accepts a method name ("mean" = plain weighted
        # average), or a full RobustConfig for non-default parameters.
        if isinstance(reducer, str):
            reducer = None if reducer == "mean" else RobustConfig(
                method=reducer)
        if reducer is not None and not isinstance(reducer, RobustConfig):
            raise ValueError(
                f"reducer must be 'mean', one of {ROBUST_METHODS}, or a "
                f"RobustConfig (got {reducer!r})")
        if algo.payload is None and (faults is not None
                                     or reducer is not None):
            raise ValueError(
                f"faults/reducer are not supported for algorithm "
                f"{algorithm!r} (no device uploads to corrupt or "
                f"robustly aggregate)")
        if faults is not None and faults.n_devices != pcfg.n_devices:
            raise ValueError(
                f"faults.n_devices={faults.n_devices} must match "
                f"pcfg.n_devices={pcfg.n_devices}")
        # One definition of the tp x faults/robust contract — shared
        # with the mesh round builders and launch/steps.py.
        shard_round.check_faults_tp(faults, reducer,
                                    "model" if tp > 1 else None, tp)
        if avg_impl not in MESH_AVG_IMPLS:
            raise ValueError(f"unknown avg_impl {avg_impl!r} "
                             f"(have {MESH_AVG_IMPLS})")
        if avg_impl != "pallas" and layout != "mesh":
            raise ValueError(
                f"avg_impl={avg_impl!r} selects the mesh layout's "
                f"Algorithm-2 collective; layout={layout!r} has no "
                f"explicit collective (use layout='mesh' or the default "
                f"avg_impl='pallas')")
        shard_round.check_ring_support(avg_impl, device_axes,
                                       "model" if tp > 1 else None, tp,
                                       faults, reducer)
        self.avg_impl = avg_impl
        self.faults, self.reducer = faults, reducer
        self._fault_prog = faults_lib.fault_program(faults)

        # Dormant-data wiring: partition a FLAT dataset into per-device
        # shards (data/partition.py) so non-IID splits compose with
        # faults. `partition=None` keeps the pre-sharded contract.
        if partition is not None:
            if not hasattr(data_stacked, "shape"):
                raise ValueError(
                    "partition=... expects a single flat data array "
                    "(N, ...); pre-shard pytree datasets yourself")
            from repro.data.partition import partition as partition_fn
            data_stacked = jnp.asarray(partition_fn(
                np.asarray(data_stacked), pcfg.n_devices, labels=labels,
                kind=partition, alpha=partition_alpha,
                seed=partition_seed))

        self.spec, self.pcfg = spec, pcfg
        self.algorithm, self._algo = algorithm, algo
        self.driver, self.layout = driver, layout
        self.key = key
        self.data = data_stacked
        self.n_devices = pcfg.n_devices
        channel_cfg = channel_cfg or ChannelConfig(n_devices=pcfg.n_devices)
        self.channel = ChannelSimulator(channel_cfg)
        self.sched = SchedulerState(
            policy=pcfg.scheduler, n_devices=pcfg.n_devices,
            ratio=pcfg.scheduling_ratio)
        self.rng = np.random.default_rng(0)
        self.disc_step_flops = disc_step_flops
        self.gen_step_flops = gen_step_flops

        self.state = algo.make_state(key, init_fn, pcfg, self.n_devices)
        # Free-rider stale-upload cache: part of the state tree, so it
        # rides the scan carry / mesh replication / checkpoints like any
        # other state entry (resume under faults is exact).
        self.state = faults_lib.attach_fault_state(self.state, faults,
                                                   algo.payload)
        if algo.pooled:
            self._pooled = jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[2:]), data_stacked)

        self.device_axes = device_axes
        self.tp = tp
        self.tp_axis = "model" if tp > 1 else None
        self.mesh = None
        if layout == "mesh":
            if mesh is None:
                from repro.launch.mesh import make_host_mesh
                mesh = make_host_mesh(pcfg.n_devices, tp)
            else:
                from repro.launch.mesh import tp_mesh_error
                err = tp_mesh_error(mesh, tp)
                if err:
                    raise ValueError(err)
            self.mesh = mesh
            self._round = algo.mesh_round(spec, pcfg, mesh,
                                          device_axes=device_axes,
                                          avg_impl=avg_impl,
                                          tp_axis=self.tp_axis, tp=tp,
                                          faults=faults, robust=reducer)
        else:
            self._round = jax.jit(algo.round_fn(spec, pcfg, faults,
                                                reducer))

        if self.driver == "fused":
            self.jax_channel = JaxChannel(channel_cfg)
            self.jax_sched = JaxScheduler(
                policy=pcfg.scheduler, n_devices=pcfg.n_devices,
                ratio=pcfg.scheduling_ratio)
            self._sched_carry = self.jax_sched.init_carry()
            self._chunk_fns: dict[tuple, tuple] = {}

        self._disc_nparams = protocol.count_params(self.state["disc"])
        self._gen_nparams = protocol.count_params(self.state["gen"])
        # Actual uplink payload at the protocol's quantization width
        # (both nets for FedGAN) — drives the channel's upload timing.
        self._uplink_bits = protocol.uplink_payload_bits(
            self.state, pcfg, fedgan=algo.fedgan)
        self.history: list[RoundRecord] = []
        self._clock = 0.0
        self._round_index = 0

    # ------------------------------------------------------------------
    def run(self, n_rounds: int, *, eval_every: int = 0,
            fid_fn: Optional[Callable] = None, verbose: bool = False):
        if self.driver == "fused":
            return self._run_fused(n_rounds, eval_every=eval_every,
                                   fid_fn=fid_fn, verbose=verbose)
        return self._run_host(n_rounds, eval_every=eval_every,
                              fid_fn=fid_fn, verbose=verbose)

    # ------------------------------------------------------------------
    # fused driver — R rounds per dispatch (both layouts)
    # ------------------------------------------------------------------
    def _chunk_fn(self, n: int, eval_every: int = 0,
                  fid_fn: Optional[Callable] = None):
        """Chunk function over a fixed length n, per layout: the jitted
        stacked `rounds_scan` or the algorithm's mesh rounds-scan, with
        the signature (state, sched_carry, data, key, start_round) and
        donated state/carry. The start round is traced, so one compile
        serves every chunk of this length. With eval_every > 0 the
        (jittable) fid_fn is folded into the scan via lax.cond, so FID
        rounds need no chunk boundary."""
        cache_key = (n, eval_every)
        entry = self._chunk_fns.get(cache_key)
        # The cache holds a strong reference to the fid_fn each chunk
        # closed over, so a different (even same-id after gc) fid_fn
        # can never silently reuse a stale compiled closure.
        if entry is not None and (not eval_every or entry[0] is fid_fn):
            return entry[1]
        spec, pcfg = self.spec, self.pcfg

        if self.layout == "mesh":
            eval_fn = None
            if eval_every:
                eval_fn = lambda gen, t, key: fid_fn(
                    gen, jax.random.fold_in(key, 10_000 + t))
            fn = self._algo.mesh_rounds_scan(
                spec, pcfg, self.mesh, n,
                channel=self.jax_channel, scheduler=self.jax_sched,
                device_axes=self.device_axes,
                disc_step_flops=self.disc_step_flops,
                gen_step_flops=self.gen_step_flops,
                uplink_bits=self._uplink_bits,
                avg_impl=self.avg_impl,
                eval_fn=eval_fn, eval_every=eval_every,
                tp_axis=self.tp_axis, tp=self.tp,
                faults=self.faults, robust=self.reducer)
        else:
            scan = self._algo.rounds_scan

            def run_chunk(state, sched_carry, data, key, start_round):
                eval_fn = None
                if eval_every:
                    eval_fn = lambda gen, t: fid_fn(
                        gen, jax.random.fold_in(key, 10_000 + t))
                return scan(
                    spec, pcfg, state, data, key, n,
                    channel=self.jax_channel, scheduler=self.jax_sched,
                    sched_carry=sched_carry, start_round=start_round,
                    disc_step_flops=self.disc_step_flops,
                    gen_step_flops=self.gen_step_flops,
                    uplink_bits=self._uplink_bits,
                    eval_fn=eval_fn, eval_every=eval_every,
                    faults=self.faults, reducer=self.reducer)

            fn = jax.jit(run_chunk, donate_argnums=(0, 1))
        self._chunk_fns[cache_key] = (fid_fn if eval_every else None, fn)
        return fn

    def _fid_jittable(self, fid_fn) -> bool:
        """True when fid_fn traces (pure jnp), so it can run in-scan;
        numpy-based fid_fns fall back to eval-boundary chunking."""
        try:
            jax.eval_shape(fid_fn, self.state["gen"], self.key)
            return True
        except Exception:
            return False

    def _eval_boundaries(self, n_rounds: int, eval_every: int,
                        have_fid: bool):
        """Chunk lengths whose boundaries land on the FID-eval rounds
        (host-eval fallback for non-jittable fid_fns)."""
        if not (have_fid and eval_every):
            return [n_rounds] if n_rounds else []
        chunks, done = [], 0
        start = self._round_index
        while done < n_rounds:
            # next multiple of eval_every past the current absolute round
            nxt = ((start + done) // eval_every + 1) * eval_every
            chunks.append(min(nxt - (start + done), n_rounds - done))
            done += chunks[-1]
        return chunks

    def _run_fused(self, n_rounds: int, *, eval_every: int,
                   fid_fn: Optional[Callable], verbose: bool):
        in_scan_fid = bool(fid_fn is not None and eval_every
                           and self._fid_jittable(fid_fn))
        if in_scan_fid:
            chunks = [n_rounds] if n_rounds else []
        else:
            chunks = self._eval_boundaries(n_rounds, eval_every,
                                           fid_fn is not None)
        for chunk in chunks:
            start = self._round_index
            with stages.dispatch_span(start):
                with stages.span(stages.ENQUEUE):
                    fn = self._chunk_fn(chunk,
                                        eval_every if in_scan_fid else 0,
                                        fid_fn if in_scan_fid else None)
                    self.state, self._sched_carry, out = fn(
                        self.state, self._sched_carry, self.data, self.key,
                        jnp.int32(start))
                # the one place the host waits for the device
                with stages.span(stages.WAIT):
                    jax.block_until_ready(out)
                with stages.span(stages.READBACK):
                    out = {k: jax.tree.map(np.asarray, out[k])
                           for k in _READBACK if k in out}
                with stages.span(stages.RECORDS):
                    self._record_chunk(start, chunk, out, eval_every,
                                       fid_fn, verbose)
            self._round_index += chunk
        return self.history

    def _record_chunk(self, start: int, chunk: int, out, eval_every: int,
                      fid_fn: Optional[Callable], verbose: bool):
        """Append the chunk's `RoundRecord`s from its host-side outputs
        (the host-eval fallback computes FID on eval rounds here)."""
        metrics, walls = out["metrics"], out["wallclock_s"]
        fids = out.get("fid")
        for i in range(chunk):
            t = start + i
            self._clock += float(walls[i])
            fid = None
            if fids is not None:
                # explicit eval mask: a NaN FID on an eval round is
                # reported as NaN, exactly like the host loop
                if out["fid_eval"][i]:
                    fid = float(fids[i])
            elif (fid_fn is not None and eval_every
                    and (t + 1) % eval_every == 0):
                fid = float(fid_fn(self.state["gen"],
                                   jax.random.fold_in(self.key, 10_000 + t)))
            rec = RoundRecord(
                t, float(walls[i]), self._clock,
                {k: float(v[i]) for k, v in metrics.items()}, fid,
                mask=out["mask"][i])
            self.history.append(rec)
            if verbose:
                self._print_record(rec)

    # ------------------------------------------------------------------
    # host driver — one round per dispatch (the oracle)
    # ------------------------------------------------------------------
    def _run_host(self, n_rounds: int, *, eval_every: int,
                  fid_fn: Optional[Callable], verbose: bool):
        for _ in range(n_rounds):
            t = self._round_index
            round_key = jax.random.fold_in(self.key, t)

            # Step 1: schedule + channel state. Fault dropout knocks
            # scheduled devices out BEFORE timing, realized from the
            # SAME round key as the fused drivers so masks stay bitwise
            # identical across every engine (core/faults.py).
            rates = self.channel.uplink_rates(self.sched.n_scheduled)
            mask = schedule_round(self.sched, rates, self.rng)
            compute_mult = None
            if self._fault_prog is not None:
                mask = mask & ~self._fault_prog.dropout_mask_np(round_key)
                compute_mult = self._fault_prog.compute_mult_np
            timing = self.channel.round_timing(
                mask=mask, disc_params=self._disc_nparams,
                gen_params=self._gen_nparams,
                disc_step_flops=self.disc_step_flops,
                gen_step_flops=self.gen_step_flops,
                n_d=self.pcfg.n_d, n_g=self.pcfg.n_g,
                fedgan=self._algo.fedgan,
                uplink_bits=self._uplink_bits,
                compute_mult=compute_mult)
            active = mask & ~timing.stragglers
            weights = jnp.asarray(
                np.where(active, float(self.pcfg.sample_size), 0.0),
                dtype=jnp.float32)

            # Steps 2-5 (jitted)
            data = self._pooled if self._algo.pooled else self.data
            self.state, metrics = self._round(self.state, data, weights,
                                              round_key)

            wall = round_wallclock(timing, mask,
                                   schedule=self.pcfg.schedule,
                                   fedgan=self._algo.fedgan)
            self._clock += wall
            fid = None
            if fid_fn is not None and eval_every and (t + 1) % eval_every == 0:
                fid = float(fid_fn(self.state["gen"],
                                   jax.random.fold_in(self.key, 10_000 + t)))
            rec = RoundRecord(t, wall, self._clock,
                              {k: float(v) for k, v in metrics.items()}, fid,
                              mask=mask.copy())
            self.history.append(rec)
            self._round_index += 1
            if verbose:
                self._print_record(rec)
        return self.history

    # ------------------------------------------------------------------
    # checkpoint / resume
    # ------------------------------------------------------------------
    def save_checkpoint(self, directory: str):
        """Serialize model state + round index + wallclock + scheduler
        carry, so `restore` continues the run — including the wallclock
        curve — exactly (fused drivers; see module docstring for the
        host-driver caveat)."""
        from repro.checkpoint import save_checkpoint
        carry = (jax.device_get(self._sched_carry)
                 if self.driver == "fused" else
                 {"rr_cursor": np.int32(self.sched.rr_cursor),
                  # native f64: the numpy EWMA stream must resume exactly
                  "ewma_rate": np.asarray(self.sched.ewma_rate)})
        tree = {"state": self.state,
                "trainer": {"round_index": np.int64(self._round_index),
                            "clock": np.float64(self._clock),
                            "sched_carry": carry}}
        return save_checkpoint(
            directory, self._round_index, tree,
            metadata={"algorithm": self.algorithm, "layout": self.layout,
                      "driver": self.driver})

    def restore(self, directory: str, step: Optional[int] = None):
        """Load a checkpoint written by `save_checkpoint` (latest by
        default) and position the trainer to continue from it."""
        from repro.checkpoint import load_checkpoint
        tree, step, _ = load_checkpoint(directory, step)
        self.state = jax.tree.map(
            lambda ref, x: jnp.asarray(x, getattr(ref, "dtype", None)),
            self.state, tree["state"])
        extra = tree["trainer"]
        self._round_index = int(extra["round_index"])
        self._clock = float(extra["clock"])
        carry = extra["sched_carry"]
        if self.driver == "fused":
            self._sched_carry = {
                "rr_cursor": jnp.int32(carry["rr_cursor"]),
                "ewma_rate": jnp.asarray(carry["ewma_rate"], jnp.float32)}
        else:
            self.sched.rr_cursor = int(carry["rr_cursor"])
            self.sched.ewma_rate = np.asarray(carry["ewma_rate"],
                                              np.float64)
        return step

    # ------------------------------------------------------------------
    @staticmethod
    def _print_record(rec: RoundRecord):
        msg = (f"round {rec.round:4d}  t={rec.cumulative_s:9.2f}s  "
               f"D={rec.metrics.get('disc_objective', float('nan')):+.4f}")
        if rec.fid is not None:
            msg += f"  FID={rec.fid:8.2f}"
        print(msg)
