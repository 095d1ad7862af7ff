#!/usr/bin/env python3
"""Smoke run of the paper's distributed-GAN trainer on TPU chips.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host with four chips

One chip: the paper's job (DCGAN 64x64, K=10, n_d=n_g=5, m_k=128,
16-bit uplink, serial schedule, stacked layout) for 3 rounds through
`core.engine.Trainer` with the fused driver, checked against the same 3
rounds from the same seed with the host driver (the equivalence
oracle); then the mesh layout's Algorithm-2 Pallas kernel (`wavg`) on a
K=10 payload of the paper discriminator's size, checked against
`wavg_ref` and for a compiled kernel in its HLO.

Four chips: the same DCGAN with K=4 workers, one per chip on a (4, 1)
mesh (`layout="mesh"`, fused), once with the flat all-gather + `wavg`
and once with the ring collective, each checked against the K=4 job on
the stacked layout; the flat gather also with an f32 uplink.

Data are synthetic CelebA-geometry images and weights are random, both
from `--seed`. Each phase prints one JSON line with its checks, compile
and steady seconds, labelled a smoke run: these are not benchmark
numbers. The last line is {"ok": true, "device": {...}}. Any failed
check exits non-zero, and the script refuses to run on anything but a
TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs.base import ProtocolConfig  # noqa: E402
from repro.configs.dcgan import DCGANConfig  # noqa: E402
from repro.core import Trainer  # noqa: E402
from repro.data import make_image_dataset  # noqa: E402
from repro.kernels.wavg import ops as wavg_ops  # noqa: E402
from repro.kernels.wavg.ref import wavg_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import dcgan  # noqa: E402
from repro.models.specs import make_dcgan_spec  # noqa: E402

ROUNDS = 3
# The engines agree to float32 round-off, so they are compared with f32
# matmuls and convs. At the TPU's DEFAULT precision XLA picks the
# bf16-pass algorithm per program, and two programs of the same math
# then differ by more than f32 round-off.
ORACLE_PRECISION = "highest"
# tests/test_driver_equivalence.py: fused vs host and flat mesh vs the
# stacked layout agree to 2e-5; the ring rotates the accumulation order
# across ranks and is held to 1e-4.
PARAM_ATOL = 2e-5
RING_PARAM_ATOL = 1e-4
WAVG_ATOL = 1e-5           # tests/test_kernels.py, f32 payload
IMAGES_PER_WORKER = 128
PAPER_DISC_PARAMS = 2_765_568


def emit(phase: str, **fields):
    print(json.dumps({"phase": phase, "smoke_run_not_a_benchmark": True,
                      **fields}), flush=True)


def make_trainer(dcfg: DCGANConfig, pcfg: ProtocolConfig, images, seed,
                 **kw):
    """The paper's DCGAN through the Trainer, data split IID over the
    pcfg.n_devices workers."""
    return Trainer(make_dcgan_spec(dcfg), pcfg,
                   lambda k: dcgan.gan_init(k, dcfg), images,
                   jax.random.PRNGKey(seed), partition="iid",
                   partition_seed=seed, **kw)


def timed_rounds(trainer, rounds: int, precision=None):
    """Run `rounds` rounds twice at `precision` (None: the default);
    the first call compiles. Returns the first call's history, a host
    copy of the state after it, the first call's seconds (compile
    included) and the steady seconds per round of the second."""
    with jax.default_matmul_precision(precision):
        t0 = time.perf_counter()
        hist = list(trainer.run(rounds))
        jax.block_until_ready(trainer.state)
        first_s = time.perf_counter() - t0
        state = jax.device_get(trainer.state)
        t0 = time.perf_counter()
        trainer.run(rounds)
        jax.block_until_ready(trainer.state)
        steady = (time.perf_counter() - t0) / rounds
    return hist, state, first_s, steady


def losses_finite(hist) -> bool:
    return all(np.isfinite(v) for r in hist for v in r.metrics.values())


def compare(ref, got, atol: float) -> dict:
    """Masks bitwise, params within `atol`, losses finite."""
    (ref_hist, ref_state), (hist, state) = ref, got
    la = jax.tree_util.tree_flatten_with_path(ref_state)[0]
    lb = jax.tree_util.tree_leaves(state)
    same_tree = (jax.tree_util.tree_structure(ref_state)
                 == jax.tree_util.tree_structure(state)
                 and all(a.shape == b.shape for (_, a), b in zip(la, lb)))
    diffs = [(float(np.max(np.abs(np.asarray(a, np.float32)
                                  - np.asarray(b, np.float32)))),
              jax.tree_util.keystr(path))
             for (path, a), b in zip(la, lb)] if same_tree else []
    max_param, max_leaf = max(diffs, default=(float("inf"), None))
    max_metric = max(abs(a.metrics[k] - b.metrics[k])
                     for a, b in zip(ref_hist, hist) for k in a.metrics)
    return {
        "masks_bitwise": len(ref_hist) == len(hist) and all(
            np.array_equal(a.mask, b.mask) for a, b in zip(ref_hist, hist)),
        "params_within_atol": same_tree and max_param <= atol,
        "losses_finite": losses_finite(ref_hist) and losses_finite(hist),
        "param_atol": atol,
        "max_abs_param_diff": max_param,
        "max_diff_leaf": max_leaf,
        "max_abs_metric_diff": max_metric,
    }


def failed(checks: dict) -> list:
    return [k for k, v in checks.items() if v is False]


def phase_paper_job(dcfg: DCGANConfig, pcfg: ProtocolConfig, seed: int,
                    rounds: int = ROUNDS) -> list:
    """The job on the stacked layout as users run it (fused driver,
    default precision), then fused vs host driver at ORACLE_PRECISION.
    Returns failed checks."""
    images, _ = make_image_dataset("celeba",
                                   pcfg.n_devices * IMAGES_PER_WORKER,
                                   seed=seed)
    job = dict(workers=pcfg.n_devices, n_d=pcfg.n_d, n_g=pcfg.n_g,
               m_k=pcfg.sample_size, bits=pcfg.quantize_bits,
               image_size=dcfg.image_size, rounds=rounds)
    hist, _, first_s, steady = timed_rounds(
        make_trainer(dcfg, pcfg, images, seed, driver="fused",
                     layout="stacked"), rounds)
    bad = [] if losses_finite(hist) else ["paper_job:losses_finite"]
    emit("paper_job", **job, driver="fused", precision="default",
         losses_finite=not bad, first_call_s=first_s,
         steady_s_per_round=steady,
         compile_s_est=first_s - rounds * steady)

    runs = {d: timed_rounds(make_trainer(dcfg, pcfg, images, seed,
                                         driver=d, layout="stacked"),
                            rounds, ORACLE_PRECISION)
            for d in ("fused", "host")}
    checks = compare(runs["host"][:2], runs["fused"][:2], PARAM_ATOL)
    emit("paper_job_fused_vs_host", **job, precision=ORACLE_PRECISION,
         **checks,
         **{f"{d}_first_call_s": r[2] for d, r in runs.items()},
         **{f"{d}_steady_s_per_round": r[3] for d, r in runs.items()})
    return bad + failed(checks)


def phase_wavg(k: int, n: int, seed: int) -> list:
    """The Pallas `wavg` kernel on a (k, n) payload vs `wavg_ref`."""
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (k, n), jnp.float32)
    w = jax.random.uniform(kw, (k,), jnp.float32)
    w = w / jnp.sum(w)
    t0 = time.perf_counter()
    compiled = jax.jit(wavg_ops.weighted_average).lower(x, w).compile()
    compile_s = time.perf_counter() - t0
    out = jax.block_until_ready(compiled(x, w))
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(compiled(x, w))
    steady_s = (time.perf_counter() - t0) / 5
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(wavg_ref)(x, w)
    err = float(jnp.max(jnp.abs(out - ref)))
    hlo = compiled.as_text()
    checks = {"tpu_custom_call_in_hlo": "tpu_custom_call" in hlo,
              "matches_wavg_ref": err <= WAVG_ATOL,
              "finite": bool(jnp.all(jnp.isfinite(out)))}
    emit("wavg_kernel", k=k, n=n, **checks, atol=WAVG_ATOL,
         max_abs_err=err, compile_s=compile_s, steady_s_per_call=steady_s)
    return failed(checks)


def workers_on_distinct_chips(mesh, k: int) -> list:
    """Device id that holds each worker slot of the mesh's data axis."""
    probe = jax.jit(jax.shard_map(
        lambda x: x + jax.lax.axis_index("data"), mesh=mesh,
        in_specs=P("data"), out_specs=P("data")))(jnp.zeros((k,), jnp.int32))
    slot = {int(s.data[0]): s.device.id for s in probe.addressable_shards}
    return [slot.get(i) for i in range(k)]


def phase_four_chips(dcfg: DCGANConfig, pcfg: ProtocolConfig, seed: int,
                     rounds: int = ROUNDS) -> list:
    """Mesh layout (flat gather and ring) vs the stacked layout, all at
    ORACLE_PRECISION; the flat gather also with an f32 uplink, where no
    stochastic quantizer sits between the two layouts."""
    k = pcfg.n_devices
    images, _ = make_image_dataset("celeba", k * IMAGES_PER_WORKER,
                                   seed=seed)
    bad = []
    for bits, impls in ((pcfg.quantize_bits, ("pallas", "ring")),
                        (32, ("pallas",))):
        pcfg_b = dataclasses.replace(pcfg, quantize_bits=bits)
        stacked = timed_rounds(make_trainer(dcfg, pcfg_b, images, seed,
                                            driver="fused",
                                            layout="stacked"),
                               rounds, ORACLE_PRECISION)
        for impl in impls:
            atol = RING_PARAM_ATOL if impl == "ring" else PARAM_ATOL
            trainer = make_trainer(dcfg, pcfg_b, images, seed,
                                   driver="fused", layout="mesh",
                                   avg_impl=impl)
            placement = workers_on_distinct_chips(trainer.mesh, k)
            run = timed_rounds(trainer, rounds, ORACLE_PRECISION)
            checks = compare(stacked[:2], run[:2], atol)
            checks["one_worker_per_chip"] = (
                len(set(placement)) == k and None not in placement
                and trainer.mesh.shape["data"] == k)
            name = f"mesh_{impl}_vs_stacked_bits{bits}"
            emit(name, workers=k, rounds=rounds,
                 image_size=dcfg.image_size, precision=ORACLE_PRECISION,
                 worker_device_ids=placement, **checks,
                 mesh_first_call_s=run[2], mesh_steady_s_per_round=run[3],
                 stacked_first_call_s=stacked[2],
                 stacked_steady_s_per_round=stacked[3])
            bad += [f"{name}:{c}" for c in failed(checks)]
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {platform!r} "
              f"({devices[0].device_kind}); not running on it",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 TPU chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    cache_dir = Path(enable_compile_cache())
    warm = cache_dir.is_dir() and any(cache_dir.iterdir())
    emit("device", platform=platform, device_kind=devices[0].device_kind,
         count=len(devices), jax=jax.__version__,
         compile_cache=str(cache_dir), compile_cache_warm=warm)

    dcfg = DCGANConfig()
    if args.four_chips:
        bad = phase_four_chips(dcfg, ProtocolConfig(n_devices=4), args.seed)
    else:
        bad = phase_paper_job(dcfg, ProtocolConfig(), args.seed)
        bad += phase_wavg(10, PAPER_DISC_PARAMS, args.seed)
    if bad:
        print(f"chip_smoke: FAILED checks: {bad}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
