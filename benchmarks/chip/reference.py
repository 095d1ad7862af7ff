"""Plain float32 reference of one communication round of the proposed
protocol on a DCGAN (arXiv:2107.08681 Algorithms 1-3; DCGAN
arXiv:1511.06434), written from the papers and imported from nowhere
in the program.

What it shares with the program is the contract of a run, not code:
the parameter tree the program is handed (HWIO kernels, a transposed
convolution that correlates the zero-inserted input with the kernel as
stored, batch norm on batch statistics, a 4x4 valid head as a
contraction), and the random streams that make a round reproducible
(the per-round key, the shared noise per local step, each worker's
sample indices, and each worker's uniform draw for the stochastic
16-bit uplink over its flattened payload). Every matrix operation runs
at `Precision.HIGHEST`, so the reference is f32 throughout.

`variant` puts a deliberately broken or lowered reference in the
program's place for the calibration of the limits:
  "fp8"         every convolution and contraction takes float8 (e4m3)
                operands with a per-tensor scale in the forward and in
                both backward products, accumulating in f32: the
                precision one step below the bf16 MXU passes the
                configuration states;
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

HI = jax.lax.Precision.HIGHEST
# Salts of the protocol's random streams (the shared noise, the workers'
# sample indices, the uplink quantizer's draw).
SALT_SHARED_Z = 0x5EED
SALT_DATA = 0xDA7A
SALT_QUANT = 0x0B175
VARIANTS = (None, "fp8", "half_batch", "no_exchange")


def _stages(image_size: int) -> int:
    return int(np.log2(image_size)) - 2


def init_params(key, cfg: dict):
    """DCGAN weights from `key`: conv kernels N(0, 0.02), batch-norm
    scale 1 and bias 0, in the program's parameter tree."""
    n = _stages(cfg["image_size"])
    g_chain = [cfg["ngf"] * 2 ** k for k in range(n - 1, -1, -1)]
    d_chain = [cfg["ndf"] * 2 ** k for k in range(n)]
    kg, kd = jax.random.split(key)
    kg, kd = jax.random.split(kg, n + 1), jax.random.split(kd, n + 1)

    def conv(k, c_in, c_out):
        return {"w": 0.02 * jax.random.normal(k, (4, 4, c_in, c_out))}

    def bn(c):
        return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}

    g_io = [(cfg["nz"], g_chain[0])] + list(zip(g_chain, g_chain[1:]))
    gen = [{"conv": conv(kg[i], a, b), "bn": bn(b)}
           for i, (a, b) in enumerate(g_io)]
    gen.append({"conv": conv(kg[n], g_chain[-1], cfg["nc"])})
    disc = [{"conv": conv(kd[0], cfg["nc"], d_chain[0])}]
    disc += [{"conv": conv(kd[i + 1], a, b), "bn": bn(b)}
             for i, (a, b) in enumerate(zip(d_chain, d_chain[1:]))]
    disc.append({"conv": conv(kd[n], d_chain[-1], 1)})
    return {"gen": {"layers": gen}, "disc": {"layers": disc}}


# ---------------------------------------------------------------------------
# the matrix operations, at f32 or with fp8 operands (the control)
# ---------------------------------------------------------------------------

def _fp8(v):
    """Round to float8 e4m3 with a per-tensor scale; exact zeros stay."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(v)))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (v / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mxu(fn, x, w, fp8: bool):
    """fn(x, w) at f32, or with both operands of the forward and of each
    backward product rounded to scaled fp8."""
    if not fp8:
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


def _conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _conv_transpose(x, w, stride, pad):
    """Fractionally strided convolution: insert stride-1 zeros between
    input pixels, pad by k-1-pad, correlate with the kernel as stored.
    Output size (in-1)*stride - 2*pad + k."""
    k = w.shape[0]
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), ((k - 1 - pad, k - 1 - pad),) * 2,
        lhs_dilation=(stride, stride),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _batchnorm(p, x):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def generator(gen, z, cfg: dict, fp8: bool = False):
    x = z.reshape(z.shape[0], 1, 1, cfg["nz"])
    layers = gen["layers"]
    for i, layer in enumerate(layers[:-1]):
        stride, pad = (1, 0) if i == 0 else (2, 1)
        x = _mxu(functools.partial(_conv_transpose, stride=stride, pad=pad),
                 x, layer["conv"]["w"], fp8)
        x = jax.nn.relu(_batchnorm(layer["bn"], x))
    x = _mxu(functools.partial(_conv_transpose, stride=2, pad=1), x,
             layers[-1]["conv"]["w"], fp8)
    return jnp.tanh(x)


def discriminator(disc, x, fp8: bool = False):
    layers = disc["layers"]
    conv = functools.partial(_conv, stride=2, pad=1)
    x = jax.nn.leaky_relu(_mxu(conv, x, layers[0]["conv"]["w"], fp8), 0.2)
    for layer in layers[1:-1]:
        x = _mxu(conv, x, layer["conv"]["w"], fp8)
        x = jax.nn.leaky_relu(_batchnorm(layer["bn"], x), 0.2)
    head = lambda a, w: jnp.einsum("bhwc,hwc->b", a, w[..., 0], precision=HI)
    return _mxu(head, x, layers[-1]["conv"]["w"], fp8)


def _log_sigmoid(v):
    return -jax.nn.softplus(-v)


def disc_objective(disc, real, fake, fp8=False):
    """Eq. (2), to be maximised: E log D(x) + E log(1 - D(G(z)))."""
    return (jnp.mean(_log_sigmoid(discriminator(disc, real, fp8)))
            + jnp.mean(_log_sigmoid(-discriminator(disc, fake, fp8))))


def gen_objective(gen, disc, z, cfg, fp8=False):
    """Eq. (1), the minimax generator loss, to be minimised."""
    return jnp.mean(_log_sigmoid(-discriminator(disc, generator(gen, z, cfg,
                                                                fp8), fp8)))


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


def _shared_noise(round_key, j, n, nz):
    return jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(round_key, SALT_SHARED_Z), j),
        (n, nz))


@functools.partial(jax.jit, static_argnames=("cfg_items", "traffic_items",
                                             "variant"))
def reference_round(params, real, round_key, *, cfg_items, traffic_items,
                    variant=None):
    """One serial round with every worker scheduled.

    real: (K, n_d, m_k, H, W, C), worker k's real batches of its local
    steps. Returns (params, (disc_objective, gen_objective)) with the
    objectives as the program reports them: the workers' mean local
    objective at their last step, and the server's loss at its last."""
    cfg, tr = dict(cfg_items), dict(traffic_items)
    fp8 = variant == "fp8"
    k_workers, n_d, n_g = tr["workers"], tr["n_d"], tr["n_g"]
    m, big_m, nz = tr["m_k"], tr["M"], cfg["nz"]
    gen, disc = params["gen"], params["disc"]
    fakes = jax.lax.map(
        lambda j: generator(gen, _shared_noise(round_key, j, m, nz), cfg,
                            fp8), jnp.arange(n_d))       # same for all k
    used = m // 2 if variant == "half_batch" else m

    def worker(args):
        k, real_k = args

        def step(d, inp):
            x, fake = inp
            obj, grad = jax.value_and_grad(disc_objective)(
                d, x[:used], fake[:used], fp8)
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
        z = _shared_noise(round_key, j, big_m, nz)
        loss, grad = jax.value_and_grad(gen_objective)(g, disc, z, cfg, fp8)
        return jax.tree.map(lambda p, q: p - tr["lr_g"] * q, g, grad), loss

    gen, losses = jax.lax.scan(server_step, gen, jnp.arange(n_g))
    return {"gen": gen, "disc": disc}, (jnp.mean(objs), losses[-1])


_PROTOCOL_KEYS = ("workers", "n_d", "n_g", "m_k", "M", "lr_d", "lr_g",
                  "quantize_bits")
_MODEL_KEYS = ("nz", "ngf", "ndf", "nc", "image_size")


@jax.jit
def _gather(src, row, idx):
    """Images idx of row `row` of src: one worker's shard is gathered at
    a time, so that a relayout for the gather copies one shard, not all."""
    return jnp.take(jax.lax.dynamic_index_in_dim(src, row, 0, False), idx,
                    axis=0)


def worker_sources(shards):
    """(array, row) holding each worker's images: the whole stacked array
    on one device, or on a mesh each chip's own (1, n_k, ...) shard."""
    if len(shards.sharding.device_set) == 1:
        return [(shards, k) for k in range(shards.shape[0])]
    pieces = sorted(shards.addressable_shards, key=lambda s: s.index[0].start)
    if [p.index[0].start for p in pieces] != list(range(shards.shape[0])):
        raise ValueError("expected one worker's shard per device")
    return [(p.data, 0) for p in pieces]


def run(cfg: dict, traffic: dict, params, shards, key, n_rounds: int,
        record_after=(), variant=None, device=None):
    """`n_rounds` reference rounds from `params` over the workers' image
    `shards` ((K, n_k, H, W, C), on any devices), computed on `device`.
    Returns (per-round objectives (n_rounds, 2), {round: params on the
    host after that many rounds} for each round in `record_after`)."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown reference variant {variant!r}")
    if (traffic["algorithm"], traffic["schedule"], traffic["scheduler"]) \
            != ("proposed", "serial", "all"):
        raise ValueError("the reference covers the proposed algorithm's "
                         "serial schedule with every worker scheduled")
    device = device or jax.devices()[0]
    cfg_items = tuple((k, cfg[k]) for k in _MODEL_KEYS)
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
