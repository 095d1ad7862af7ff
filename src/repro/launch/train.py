"""Cluster launcher: run protocol training rounds on the production mesh.

On a real TPU pod this is the entry point (one process per host,
jax.distributed.initialize handles the rest). On CPU it degenerates to a
single-device run of the same jitted round — useful with a forced host
device count to exercise either mesh path end-to-end:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --data-dim 8 --rounds 4 --seq-len 64 --batch 32 \
        --layout mesh --fuse-rounds 2

Execution layouts (see launch/steps.build_train_step):

  --layout stacked  GSPMD/pjit rounds, device axis sharded (default);
                    --model-dim is the GSPMD tensor-parallel axis
  --layout mesh     shard_map rounds with explicit collectives; the
                    fused multi-round scan runs INSIDE shard_map. The
                    mesh is (data x model) = (--data-dim x --tp): with
                    --tp > 1 every worker slice is a Megatron TP group
                    on the model axis (feed-forward column/row-parallel,
                    state sharded, Algorithm-2 all-gather payload 1/tp
                    per rank); --tp 1 replicates the model axis exactly
                    like the pre-TP engine. Needs data_dim x tp
                    addressable devices. Checkpoints stay GLOBAL-shaped
                    regardless of --tp (shard_map splits/reassembles),
                    so --resume works across TP widths.

Both layouts chunk `--rounds` into `--fuse-rounds`-sized dispatches with
the state DONATED across chunks; any round count works — the remainder
runs as a shorter final chunk through a per-length compile cache (the
`engine.Trainer._chunk_fn` pattern). Checkpoint writes overlap the next
dispatch: the state is device-copied, the next chunk is dispatched, and
a background thread serializes the copy while the devices compute.

The mesh layout runs EITHER fused algorithm (--algorithm proposed |
fedgan — the latter is the two-net FedGAN baseline inside the same
shard_map scan). Checkpoints serialize the scheduler carry, the
absolute round index, and the simulated wallclock alongside the model
state, so `--resume` continues masks AND the wallclock curve exactly.
"""
from __future__ import annotations

import argparse
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch_config, list_archs
from repro.configs.base import MeshConfig, ProtocolConfig, ShapeConfig
from repro.data import make_token_dataset
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh


class AsyncCheckpointer:
    """Overlap checkpoint serialization with the next training dispatch.

    `submit` takes a DEVICE-SIDE copy of the state (so donation of the
    live buffers into the next chunk is safe), returns immediately, and
    writes the copy from a background thread — the host callback blocks
    only on the device copy, never on the next chunk's compute. One
    write is in flight at a time; `finish()` drains the last one.
    """

    def __init__(self, directory: str):
        self.directory = directory
        self._thread = None
        self._error = None

    def submit(self, step_index: int, state, metadata=None):
        from repro.checkpoint import save_checkpoint
        self.finish()
        # device arrays get a device-side copy (donation safety); host
        # scalars (round index, f64 sim wallclock) keep their numpy
        # dtype — jnp.copy would silently downcast f64 with x64 off
        snapshot = jax.tree.map(
            lambda x: jnp.copy(x) if isinstance(x, jax.Array)
            else np.copy(x), state)

        def _write():
            try:
                save_checkpoint(self.directory, step_index, snapshot,
                                metadata=metadata)
            except BaseException as e:   # re-raised at the next finish()
                self._error = e

        self._thread = threading.Thread(target=_write, daemon=True)
        self._thread.start()

    def finish(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError(
                f"async checkpoint write to {self.directory} failed") from err


def chunk_lengths(rounds: int, fuse: int):
    """`rounds` split into fuse-sized dispatches + a shorter remainder
    chunk (each distinct length costs one compile, served by a cache)."""
    chunks = [fuse] * (rounds // fuse)
    if rounds % fuse:
        chunks.append(rounds % fuse)
    return chunks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="mamba2-130m")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU debugging)")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--data-dim", type=int, default=4)
    ap.add_argument("--model-dim", type=int, default=None,
                    help="GSPMD model axis, layout stacked only "
                         "(default 2); the mesh layout's model axis "
                         "comes from --tp instead — passing both "
                         "--layout mesh and --model-dim is an error "
                         "rather than a silent reinterpretation")
    ap.add_argument("--tp", type=int, default=1,
                    help="layout mesh only: in-slice tensor parallelism "
                         "— every paper-worker slice is a TP group of "
                         "this width on the 'model' axis (Megatron "
                         "column/row-parallel feed-forward, state "
                         "sharded over model, per-rank Algorithm-2 "
                         "payload 1/tp). 1 = replicate the model axis "
                         "(identical to the pre-TP engine). Checkpoints "
                         "are global-shaped, so --resume works across "
                         "--tp widths")
    ap.add_argument("--schedule", choices=["serial", "parallel"],
                    default="serial")
    ap.add_argument("--layout", choices=["stacked", "mesh"],
                    default="stacked",
                    help="stacked = GSPMD/pjit rounds; mesh = shard_map "
                         "rounds with the fused in-scan engine")
    ap.add_argument("--algorithm", choices=["proposed", "fedgan"],
                    default="proposed",
                    help="proposed = the paper's protocol; fedgan = the "
                         "two-net FedGAN baseline (layout mesh only on "
                         "this builder)")
    ap.add_argument("--fuse-rounds", type=int, default=1,
                    help="rounds fused per XLA dispatch (lax.scan); any "
                         "--rounds works — the remainder runs as a "
                         "shorter final chunk")
    ap.add_argument("--quantize-bits", type=int, default=16,
                    help="uplink quantization width (paper: 16; >=32 "
                         "disables quantization)")
    ap.add_argument("--avg-impl", choices=["pallas", "jnp", "ring"],
                    default="pallas",
                    help="Algorithm-2 collective (layout mesh only): "
                         "pallas = flat all-gather + wavg kernel; jnp = "
                         "per-leaf psum; ring = the quantized-payload "
                         "ppermute ring (kernels/ring_wavg) — the uplink "
                         "stays encoded on the wire, ~2x fewer per-rank "
                         "bytes at 16 bits (tp=1, plain mean, no "
                         "free-riders/byzantine)")
    ap.add_argument("--reducer", default="mean",
                    choices=["mean", "trimmed_mean", "norm_clip", "krum"],
                    help="server aggregation rule (layout mesh only): "
                         "mean = plain weighted average; the robust "
                         "reducers tolerate corrupted uploads at the "
                         "same one-gather + one-Pallas-kernel cost")
    ap.add_argument("--trim", type=int, default=1,
                    help="--reducer trimmed_mean: extreme pairs removed "
                         "per coordinate")
    ap.add_argument("--clip-factor", type=float, default=2.0,
                    help="--reducer norm_clip: clip uploads to this "
                         "multiple of the median participant norm")
    ap.add_argument("--krum-f", type=int, default=1,
                    help="--reducer krum: assumed byzantine count f")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="fault injection: per-round iid worker dropout "
                         "probability (layout mesh only)")
    ap.add_argument("--free-riders", type=int, default=0,
                    help="fault injection: workers replaying the stale "
                         "round-start global model instead of training")
    ap.add_argument("--byzantine", type=int, default=0,
                    help="fault injection: workers uploading scaled "
                         "Gaussian noise")
    ap.add_argument("--byz-scale", type=float, default=10.0,
                    help="byzantine noise scale (x N(0,1))")
    ap.add_argument("--straggler-factor", type=float, default=1.0,
                    help="fault injection: per-worker compute slowdown "
                         "~ U[1, factor] fed into the wallclock model")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the static fault roles (who is a "
                         "free-rider/byzantine/straggler)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint every N rounds (0 = final only); "
                         "writes overlap the next dispatch")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint in --ckpt-dir "
                         "(state + scheduler carry + round index + sim "
                         "wallclock) and continue to --rounds")
    ap.add_argument("--distributed", action="store_true",
                    help="multi-host TPU: call jax.distributed.initialize")
    args = ap.parse_args()
    fuse = max(1, args.fuse_rounds)
    if args.algorithm != "proposed" and args.layout != "mesh":
        ap.error("--algorithm fedgan requires --layout mesh on this "
                 "builder (stacked FedGAN runs through core.engine.Trainer)")
    if args.resume and not args.ckpt_dir:
        ap.error("--resume requires --ckpt-dir")
    if args.tp < 1:
        ap.error("--tp must be >= 1")
    if args.tp > 1 and args.layout != "mesh":
        ap.error("--tp applies to --layout mesh (stacked tensor "
                 "parallelism is --model-dim through GSPMD)")
    if args.layout == "mesh" and args.model_dim is not None:
        ap.error("--model-dim applies to --layout stacked; the mesh "
                 "layout's model axis is --tp (refusing to silently "
                 "reinterpret the mesh shape)")

    from repro.core.faults import FaultConfig
    faults = None
    if (args.dropout > 0.0 or args.free_riders > 0 or args.byzantine > 0
            or args.straggler_factor > 1.0):
        faults = FaultConfig(
            n_devices=args.data_dim, dropout_prob=args.dropout,
            n_free_riders=args.free_riders, n_byzantine=args.byzantine,
            byz_scale=args.byz_scale,
            straggler_factor=args.straggler_factor, seed=args.fault_seed)
    reducer = None
    if args.reducer != "mean":
        from repro.kernels.robust_avg import RobustConfig
        reducer = RobustConfig(method=args.reducer, trim=args.trim,
                               clip_factor=args.clip_factor,
                               krum_f=args.krum_f)
    if (faults is not None or reducer is not None) \
            and args.layout != "mesh":
        ap.error("fault injection / robust reducers run on the fused "
                 "mesh engine: use --layout mesh")
    if (faults is not None or reducer is not None) and args.tp > 1:
        ap.error("faults/robust reducers are not supported under tensor "
                 "parallelism yet; use --tp 1")
    if args.avg_impl != "pallas" and args.layout != "mesh":
        ap.error("--avg-impl selects the mesh layout's Algorithm-2 "
                 "collective: use --layout mesh")
    if args.avg_impl == "ring":
        if args.tp > 1:
            ap.error("--avg-impl ring is not supported under tensor "
                     "parallelism; use --tp 1")
        if reducer is not None:
            ap.error("--avg-impl ring does not compose with robust "
                     "reducers; use --avg-impl pallas")
        if args.free_riders > 0 or args.byzantine > 0:
            ap.error("--avg-impl ring does not compose with "
                     "upload-corrupting faults (free-riders/byzantine); "
                     "use --avg-impl pallas")

    enable_compile_cache()
    if args.distributed:
        jax.distributed.initialize()

    cfg = get_arch_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    # stacked: (data x model) GSPMD mesh; mesh layout: the model axis IS
    # the in-slice TP width (--tp), every (data, model) slice one rank.
    model_dim = (args.tp if args.layout == "mesh"
                 else (2 if args.model_dim is None else args.model_dim))
    mesh = make_mesh((args.data_dim, model_dim), ("data", "model"))
    mesh_cfg = MeshConfig()
    shape = ShapeConfig("train_cli", args.seq_len, args.batch, "train")

    # per-chunk-length compile cache (the engine._chunk_fn pattern): the
    # remainder chunk reuses everything but the scan length
    step_cache: dict = {}

    def get_step(length: int):
        if length not in step_cache:
            step_cache[length] = steps_mod.build_train_step(
                cfg, shape, mesh, mesh_cfg, schedule=args.schedule,
                fuse_rounds=length, layout=args.layout,
                algorithm=args.algorithm,
                tp=args.tp if args.layout == "mesh" else None,
                pcfg_overrides={"quantize_bits": args.quantize_bits},
                faults=faults, reducer=reducer, avg_impl=args.avg_impl)
        return step_cache[length]

    _, abstract_args = get_step(min(fuse, args.rounds) or 1)

    # materialize real inputs matching the abstract specs
    k_dev = args.data_dim
    n_k = args.batch // k_dev
    toks, _ = make_token_dataset(args.batch, args.seq_len, cfg.vocab)
    tokens = jnp.asarray(toks.reshape(k_dev, n_k, args.seq_len))
    batch = {"tokens": tokens}
    state_abs = abstract_args[0]
    if args.layout == "stacked" and "enc_feats" in abstract_args[1]:
        ef = abstract_args[1]["enc_feats"]
        batch["enc_feats"] = jnp.zeros(ef.shape, ef.dtype)

    from repro.core.engine import mesh_algorithm
    from repro.core.jax_scheduling import JaxScheduler
    from repro.models import gan as gan_model
    pcfg = ProtocolConfig(n_devices=k_dev, n_d=2, n_g=2, sample_size=n_k,
                          server_sample_size=k_dev, schedule=args.schedule)
    weights = jnp.full((k_dev,), float(n_k))
    key = jax.random.PRNGKey(0)
    sched_carry = JaxScheduler(policy="all", n_devices=k_dev).init_carry()

    ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    since_ckpt = 0
    wall_total = 0.0
    start_round = 0
    if args.resume:
        from repro.checkpoint import load_checkpoint
        tree, step_idx, meta = load_checkpoint(args.ckpt_dir)
        # NOTE: tp is deliberately NOT checked — checkpoints are
        # global-shaped, so a run may resume at a different TP width.
        for field, want in (("algorithm", args.algorithm),
                            ("layout", args.layout)):
            got = meta.get(field)
            if got is not None and got != want:
                raise SystemExit(
                    f"checkpoint {args.ckpt_dir} was saved with "
                    f"{field}={got}; refusing to resume with "
                    f"--{field.replace('_', '-')} {want}")
        if not (isinstance(tree, dict) and "state" in tree
                and "trainer" in tree):
            raise SystemExit(
                f"checkpoint {args.ckpt_dir} predates --resume support "
                f"(raw state, no trainer record); it cannot restore the "
                f"round index/scheduler carry — restart without --resume")
        # the checkpoint replaces the init entirely — cast against the
        # abstract template instead of materializing a random state
        # only to throw it away
        state = jax.tree.map(lambda a, x: jnp.asarray(x, a.dtype),
                             state_abs, tree["state"])
        extra = tree["trainer"]
        start_round = int(extra["round_index"])
        wall_total = float(extra["sim_wall"])
        sched_carry = jax.tree.map(
            lambda a, x: jnp.asarray(x, a.dtype), sched_carry,
            extra["sched_carry"])
        print(f"resumed {args.ckpt_dir} at round {start_round} "
              f"(sim_wall={wall_total:.1f}s)")
        if start_round >= args.rounds:
            # negative remainders in chunk_lengths would otherwise train
            # a spurious chunk past the requested round count
            print(f"checkpoint already at round {start_round} >= "
                  f"--rounds {args.rounds}; nothing to do")
            return
    else:
        # real init (the dry-run uses ShapeDtypeStructs; here we train)
        # — per-algorithm state init comes from the ONE strategy
        # registry (both CLI algorithms are mesh-capable, so the
        # accessor covers the stacked layout's proposed-only case too)
        algo = mesh_algorithm(args.algorithm)
        state = algo.make_state(
            jax.random.PRNGKey(0), lambda k: gan_model.gan_init(k, cfg),
            pcfg, k_dev)
        # free-rider fault programs carry a stale-upload cache inside the
        # state (and inside checkpoints) — seed it to match state_abs
        from repro.core.faults import attach_fault_state
        state = attach_fault_state(state, faults, algo.payload)
        state = jax.tree.map(
            lambda x, a: jnp.asarray(x, a.dtype), state, state_abs)

    def ckpt_tree(state):
        # scheduler carry + round index + sim wallclock ride along, so a
        # resumed run continues masks and the wallclock curve exactly
        return {"state": state,
                "trainer": {"round_index": np.int64(r),
                            "sim_wall": np.float64(wall_total),
                            "sched_carry": sched_carry}}

    with jax.sharding.set_mesh(mesh):
        r = start_round
        for chunk in chunk_lengths(args.rounds - start_round, fuse):
            t0 = time.time()
            step, _ = get_step(chunk)
            if args.layout == "mesh":
                state, sched_carry, out = step(state, sched_carry, tokens,
                                               key, jnp.int32(r))
                metrics = out["metrics"]
                jax.block_until_ready(metrics)
                wall_total += float(np.asarray(out["wallclock_s"]).sum())
            else:
                state, metrics = step(state, batch, weights, jnp.int32(r))
                jax.block_until_ready(metrics)
            dt = time.time() - t0
            # metric keys are per-algorithm (FedGAN's server only
            # averages, so it reports participation, not objectives)
            stats = " ".join(
                f"{k}={np.atleast_1d(np.asarray(v))[-1]:+.4f}"
                for k, v in sorted(metrics.items()))
            label = (f"round {r}" if chunk == 1 else
                     f"rounds {r}..{r + chunk - 1}")
            extra = (f" sim_wall={wall_total:.1f}s"
                     if args.layout == "mesh" else "")
            print(f"{label}: {stats} "
                  f"({dt:.2f}s, {chunk / dt:.1f} rounds/s){extra}")
            r += chunk
            since_ckpt += chunk
            if ckpt and args.ckpt_every and since_ckpt >= args.ckpt_every \
                    and r < args.rounds:
                # device-copy now, write in the background while the
                # next chunk runs on the donated live buffers
                ckpt.submit(r, ckpt_tree(state),
                            metadata={"layout": args.layout,
                                      "algorithm": args.algorithm,
                                      "tp": args.tp})
                since_ckpt = 0

    if ckpt:
        ckpt.finish()
        ckpt.submit(args.rounds, ckpt_tree(state),
                    metadata={"layout": args.layout,
                              "algorithm": args.algorithm,
                              "tp": args.tp})
        ckpt.finish()
        print(f"saved {args.ckpt_dir}")


if __name__ == "__main__":
    main()
