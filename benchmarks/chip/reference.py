"""Plain float32 reference of one communication round of the proposed
protocol (arXiv:2107.08681 Algorithms 1-3), written from the paper and
imported from nowhere in the program. The model is the configuration's
family's (`families/<family>/reference.py`): its networks, weights and
data.

What it shares with the program is the contract of a run, not code:
the parameter tree the family hands the program, and the random streams
that make a round reproducible (the per-round key, the shared noise per
local step, each worker's sample indices, and each worker's uniform
draw for the stochastic 16-bit uplink over its flattened payload).
Every matrix operation runs at `Precision.HIGHEST`, so the reference is
f32 throughout.

`variant` puts a deliberately broken or lowered reference in the
program's place for the calibration of the limits:
  "fp8"         the family's generator and discriminator take float8
                operands (`mxu`): the precision one step below the one
                the configuration states;
  "half_batch"  Algorithm 1 uses half of each local batch, the mean
                taken over the rest;
  "no_exchange" Algorithm 2 is left out: the server keeps worker 0's
                upload as the global discriminator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import spec

HI = jax.lax.Precision.HIGHEST
# Salts of the protocol's random streams (the shared noise, the workers'
# sample indices, the uplink quantizer's draw).
SALT_SHARED_Z = 0x5EED
SALT_DATA = 0xDA7A
SALT_QUANT = 0x0B175
VARIANTS = (None, "fp8", "half_batch", "no_exchange")


def family(cfg: dict):
    """The reference module of the configuration's model family."""
    return spec.family_module(cfg["family"], "reference")


# ---------------------------------------------------------------------------
# a matrix operation at f32, or with fp8 operands (the control)
# ---------------------------------------------------------------------------

def _fp8(v):
    """Round to float8 e4m3 with a per-tensor scale; exact zeros stay."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(v)))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (v / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mxu(fn, x, w, variant=None):
    """fn(x, w) at f32, or under the "fp8" variant with both operands of
    the forward and of each backward product rounded to scaled fp8,
    accumulating in f32."""
    if variant != "fp8":
        return fn(x, w)

    @jax.custom_vjp
    def f(x, w):
        return fn(_fp8(x), _fp8(w))

    def fwd(x, w):
        xq, wq = _fp8(x), _fp8(w)
        return fn(xq, wq), (xq, wq)

    def bwd(res, g):
        _, vjp = jax.vjp(fn, *res)
        return vjp(_fp8(g))

    f.defvjp(fwd, bwd)
    return f(x, w)


def _log_sigmoid(v):
    return -jax.nn.softplus(-v)


def disc_objective(disc, real, fake, cfg, variant=None):
    """Eq. (2), to be maximised: E log D(x) + E log(1 - D(G(z)))."""
    d = family(cfg).discriminator
    return (jnp.mean(_log_sigmoid(d(disc, real, cfg, variant)))
            + jnp.mean(_log_sigmoid(-d(disc, fake, cfg, variant))))


def gen_objective(gen, disc, z, cfg, variant=None):
    """Eq. (1), the minimax generator loss, to be minimised."""
    fam = family(cfg)
    fake = fam.generator(gen, z, cfg, variant)
    return jnp.mean(_log_sigmoid(-fam.discriminator(disc, fake, cfg,
                                                    variant)))


def quantize_upload(key, tree, bits: int):
    """Uniform stochastic quantization with a per-tensor scale
    amax / (2^(bits-1) - 1); one uniform draw over the flattened payload
    in leaf order. Returns what the server receives."""
    if bits >= 32:
        return tree
    levels = 2.0 ** (bits - 1) - 1
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    u = jax.random.uniform(key, (sum(x.size for x in leaves),))
    out, off = [], 0
    for x in leaves:
        r = u[off:off + x.size].reshape(x.shape)
        off += x.size
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / levels
        v = x / scale
        q = jnp.clip(jnp.floor(v) + (r < v - jnp.floor(v)), -levels - 1,
                     levels)
        out.append(q * scale)
    return jax.tree_util.tree_unflatten(treedef, out)


def sample_indices(round_key, workers: int, n_d: int, m: int, n_local: int):
    """(workers, n_d, m) sample indices of each worker's local steps."""
    base = jax.random.fold_in(round_key, SALT_DATA)
    return jax.vmap(lambda k: jax.vmap(lambda j: jax.random.randint(
        jax.random.fold_in(jax.random.fold_in(base, k), j), (m,), 0,
        n_local))(jnp.arange(n_d)))(jnp.arange(workers))


def _shared_noise(round_key, j, n, cfg):
    return family(cfg).noise(
        jax.random.fold_in(jax.random.fold_in(round_key, SALT_SHARED_Z), j),
        n, cfg)


@functools.partial(jax.jit, static_argnames=("cfg_items", "traffic_items",
                                             "variant"))
def reference_round(params, real, round_key, *, cfg_items, traffic_items,
                    variant=None):
    """One serial round with every worker scheduled.

    real: (K, n_d, m_k, ...), worker k's real batches of its local
    steps. Returns (params, (disc_objective, gen_objective)) with the
    objectives as the program reports them: the workers' mean local
    objective at their last step, and the server's loss at its last."""
    cfg, tr = dict(cfg_items), dict(traffic_items)
    k_workers, n_d, n_g = tr["workers"], tr["n_d"], tr["n_g"]
    m, big_m = tr["m_k"], tr["M"]
    gen, disc = params["gen"], params["disc"]
    fakes = jax.lax.map(                                 # same for all k
        lambda j: family(cfg).generator(
            gen, _shared_noise(round_key, j, m, cfg), cfg, variant),
        jnp.arange(n_d))
    used = m // 2 if variant == "half_batch" else m

    def worker(args):
        k, real_k = args

        def step(d, inp):
            x, fake = inp
            obj, grad = jax.value_and_grad(disc_objective)(
                d, x[:used], fake[:used], cfg, variant)
            return jax.tree.map(lambda p, g: p + tr["lr_d"] * g, d,
                                grad), obj               # ascent on eq. (2)

        d, objs = jax.lax.scan(step, disc, (real_k, fakes))
        qkey = jax.random.fold_in(jax.random.fold_in(round_key, SALT_QUANT),
                                  k)
        return quantize_upload(qkey, d, tr["quantize_bits"]), objs[-1]

    uploads, objs = jax.lax.map(worker, (jnp.arange(k_workers), real))
    if variant == "no_exchange":
        disc = jax.tree.map(lambda u: u[0], uploads)
    else:                                 # Algorithm 2, weights m_k
        w = jnp.full((k_workers,), float(m)) / (k_workers * float(m))
        disc = jax.tree.map(lambda u: jnp.tensordot(w, u, 1, precision=HI),
                            uploads)

    def server_step(g, j):
        z = _shared_noise(round_key, j, big_m, cfg)
        loss, grad = jax.value_and_grad(gen_objective)(g, disc, z, cfg,
                                                       variant)
        return jax.tree.map(lambda p, q: p - tr["lr_g"] * q, g, grad), loss

    gen, losses = jax.lax.scan(server_step, gen, jnp.arange(n_g))
    return {"gen": gen, "disc": disc}, (jnp.mean(objs), losses[-1])


_PROTOCOL_KEYS = ("workers", "n_d", "n_g", "m_k", "M", "lr_d", "lr_g",
                  "quantize_bits")


@jax.jit
def _gather(src, row, idx):
    """Samples idx of row `row` of src: one worker's shard is gathered at
    a time, so that a relayout for the gather copies one shard, not all."""
    return jnp.take(jax.lax.dynamic_index_in_dim(src, row, 0, False), idx,
                    axis=0)


def worker_sources(shards):
    """(array, row) holding each worker's data: the whole stacked array
    on one device, or on a mesh each chip's own (1, n_k, ...) shard."""
    if len(shards.sharding.device_set) == 1:
        return [(shards, k) for k in range(shards.shape[0])]
    pieces = sorted(shards.addressable_shards, key=lambda s: s.index[0].start)
    if [p.index[0].start for p in pieces] != list(range(shards.shape[0])):
        raise ValueError("expected one worker's shard per device")
    return [(p.data, 0) for p in pieces]


def run(cfg: dict, traffic: dict, params, shards, key, n_rounds: int,
        record_after=(), variant=None, device=None):
    """`n_rounds` reference rounds from `params` over the workers'
    `shards` ((K, n_k, ...), on any devices), computed on `device`.
    Returns (per-round objectives (n_rounds, 2), {round: params on the
    host after that many rounds} for each round in `record_after`)."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown reference variant {variant!r}")
    if (traffic["algorithm"], traffic["schedule"], traffic["scheduler"]) \
            != ("proposed", "serial", "all"):
        raise ValueError("the reference covers the proposed algorithm's "
                         "serial schedule with every worker scheduled")
    device = device or jax.devices()[0]
    cfg_items = (("family", cfg["family"]),) + tuple(
        (k, cfg[k]) for k in family(cfg).MODEL_KEYS)
    tr_items = tuple((k, traffic[k]) for k in _PROTOCOL_KEYS)
    k_workers, n_d, m = traffic["workers"], traffic["n_d"], traffic["m_k"]
    n_local = shards.shape[1]
    indices = jax.jit(sample_indices, static_argnums=(1, 2, 3, 4))
    sources = worker_sources(shards)
    params = jax.device_put(params, device)
    objectives, recorded = [], {}
    for t in range(n_rounds):
        round_key = jax.random.fold_in(key, t)
        idx = np.asarray(indices(round_key, k_workers, n_d, m, n_local))
        real = jnp.stack([jax.device_put(_gather(src, row, idx[k].ravel()),
                                         device)
                          for k, (src, row) in enumerate(sources)])
        real = real.reshape((k_workers, n_d, m) + shards.shape[2:])
        params, objs = reference_round(
            params, real, jax.device_put(round_key, device),
            cfg_items=cfg_items, traffic_items=tr_items, variant=variant)
        objectives.append(objs)
        if t + 1 in record_after:
            recorded[t + 1] = jax.device_get(params)
    return np.asarray(jax.device_get(objectives), np.float64), recorded
