"""Serve a trained generator: continuous-batching decode CLI.

Loads a GLOBAL-shaped training checkpoint (any `--tp` width it was
trained at — checkpoints are reassembled to global shapes on save, see
launch/train.py) and serves it through `repro.serving.ServingEngine` at
any serving `--tp`, with the paged KV/SSM cache on by default:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --reduced --ckpt-dir runs/q17 --demo 8 --max-new 16

    # tensor-parallel serving over 2 forced host devices, dense cache
    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --reduced --tp 2 --block-size 0 --demo 4

Without `--ckpt-dir` the generator is randomly initialised (useful for
smoke runs and latency measurement). `--block-size 0` disables paging
and reserves dense per-slot `max_len` caches; otherwise the block pool
defaults to the worst case (`batch * ceil(max_len/block) + 1` blocks)
and can be capped with `--n-blocks` to bound memory — the engine queues
admissions when the pool is exhausted instead of failing.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import gan
from repro.serving import Request, ServingEngine


def load_generator_params(ckpt_dir: str, step=None):
    """Extract generator params from a training checkpoint tree.

    Accepts the Trainer layout ({"state": {"gen": ...}}), a bare
    {"gen": ...} tree, or raw generator params.
    """
    from repro.checkpoint import load_checkpoint
    tree, step, _ = load_checkpoint(ckpt_dir, step)
    if "state" in tree and "gen" in tree["state"]:
        params = tree["state"]["gen"]
    elif "gen" in tree:
        params = tree["gen"]
    else:
        params = tree
    return jax.tree.map(jax.numpy.asarray, params), step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced (test-size) config")
    ap.add_argument("--ckpt-dir", default="",
                    help="load generator from this checkpoint directory "
                         "(global-shaped; any training tp width)")
    ap.add_argument("--step", type=int, default=None,
                    help="checkpoint step (default: latest)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel width for serving; needs tp "
                         "addressable devices")
    ap.add_argument("--batch", type=int, default=4,
                    help="engine slots (max concurrent requests)")
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged-cache block size; 0 = dense caches")
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="cap the paged block pool (default worst-case)")
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--demo", type=int, default=4,
                    help="serve N random demo prompts and print tokens")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.ckpt_dir:
        params, step = load_generator_params(args.ckpt_dir, args.step)
        print(f"loaded generator from {args.ckpt_dir} @ step {step}")
    else:
        params = gan.generator_init(jax.random.PRNGKey(args.seed), cfg)
        print("no --ckpt-dir: serving a randomly initialised generator")

    block = args.block_size if args.block_size > 0 else None
    engine = ServingEngine(cfg, params, batch_size=args.batch,
                           max_len=args.max_len, block_size=block,
                           n_blocks=args.n_blocks,
                           prefill_chunk=args.prefill_chunk,
                           seed=args.seed, tp=args.tp)
    print(f"engine: arch={args.arch} tp={args.tp} slots={args.batch} "
          f"max_len={args.max_len} "
          f"cache={'paged/' + str(block) if block else 'dense'} "
          f"({engine.cache_bytes()} bytes)")

    rng = np.random.default_rng(args.seed)
    for i in range(args.demo):
        prompt = rng.integers(1, cfg.vocab, rng.integers(4, 17))
        engine.submit(Request(rid=i, prompt=prompt.astype(np.int32),
                              max_new_tokens=args.max_new,
                              temperature=args.temperature))
    t0 = time.perf_counter()
    finished = engine.run()
    wall = time.perf_counter() - t0
    n_tok = sum(len(r.out_tokens) for r in finished)
    for req in sorted(finished, key=lambda r: r.rid):
        print(f"  rid={req.rid}: {req.out_tokens}")
    for req in engine.rejected:
        print(f"  rid={req.rid}: REJECTED ({req.failed})")
    print(f"{len(finished)} requests, {n_tok} tokens in {wall:.2f}s "
          f"({n_tok / wall:.1f} tok/s), {engine.dispatch_count} steps, "
          f"{engine.compile_count} compiles")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
