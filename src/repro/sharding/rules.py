"""Parameter / activation / cache sharding rules.

Megatron-style tensor parallelism on the `model` axis plus optional
FSDP over the device axes for the largest generators:

  * "in" projections  (wq wk wv w_in w_gate in_proj z_proj router):
        tensor-parallel on the OUTPUT dim, FSDP on the input dim
  * "out" projections (wo w_out out_proj lm_head score):
        tensor-parallel on the INPUT dim, FSDP on the output dim
  * embedding tables (vocab, d): d over `model` (vocab sizes are not
        uniformly divisible — e.g. granite's 49155 is odd)
  * vectors / norms / gates: replicated
  * expert tensors (G, E, a, b): same in/out rules on (a, b); the expert
        axis stays unsharded when E doesn't divide the mesh (8, 40 vs 16)
        — expert-parallel rebalancing is a §Perf hillclimb lever.

Decode caches: batch over device axes when divisible, otherwise the
sequence/length dim (long_500k's b=1), which makes GSPMD lower a
distributed flash-decode (sharded softmax reductions + partial-sum
all-reduce).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, MeshConfig, ShapeConfig

_IN_PROJ = {"wq", "wk", "wv", "w_in", "w_gate", "in_proj", "z_proj",
            "router", "conv_w", "wqkv", "w_inga"}
_OUT_PROJ = {"wo", "w_out", "out_proj", "lm_head", "score"}
_EMBED = {"table"}

# generators at/above this parameter count get FSDP over the device axes
FSDP_THRESHOLD = 5_000_000_000


@dataclasses.dataclass(frozen=True)
class ParallelismPlan:
    tp_axis: str = "model"
    fsdp_axes: Optional[Tuple[str, ...]] = None    # e.g. ("data",) or ("pod","data")
    dev_axes: Tuple[str, ...] = ("data",)          # the paper's device axes

    def axis_size(self, mesh, name) -> int:
        return mesh.shape[name]


def plan_for(cfg: ArchConfig, mesh_cfg: MeshConfig, *,
             n_params: Optional[int] = None) -> ParallelismPlan:
    dev_axes = ("pod", "data") if mesh_cfg.multi_pod else ("data",)
    fsdp = None
    if mesh_cfg.fsdp or (n_params or _rough_params(cfg)) >= FSDP_THRESHOLD:
        fsdp = dev_axes
    return ParallelismPlan(fsdp_axes=fsdp, dev_axes=dev_axes)


def _rough_params(cfg: ArchConfig) -> int:
    d, L = cfg.d_model, cfg.n_layers
    per_layer = 4 * d * d * (1 if cfg.family in ("ssm",) else 1)
    if cfg.moe:
        per_layer += 3 * d * cfg.moe.d_ff_expert * cfg.moe.n_experts
    else:
        per_layer += 3 * d * cfg.d_ff
    return L * per_layer + 2 * cfg.vocab * d


def _divisible(dim: int, mesh, axes) -> bool:
    if axes is None:
        return False
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return dim > 0 and dim % size == 0


def _leaf_spec(path_names, leaf, mesh, plan: ParallelismPlan,
               fsdp: bool) -> P:
    name = path_names[-1]
    shape = leaf.shape
    ndim = len(shape)
    tp = plan.tp_axis
    fsdp_axes = plan.fsdp_axes if fsdp else None

    if ndim <= 1:
        return P()
    if name in _EMBED:
        spec = [None] * ndim
        if _divisible(shape[-1], mesh, tp):
            spec[-1] = tp
        return P(*spec)
    if name in _IN_PROJ:
        spec = [None] * ndim
        if _divisible(shape[-1], mesh, tp):
            spec[-1] = tp
        if ndim >= 2 and fsdp_axes and _divisible(shape[-2], mesh, fsdp_axes):
            spec[-2] = fsdp_axes
        return P(*spec)
    if name in _OUT_PROJ:
        spec = [None] * ndim
        if ndim >= 2 and _divisible(shape[-2], mesh, tp):
            spec[-2] = tp
        if fsdp_axes and _divisible(shape[-1], mesh, fsdp_axes):
            spec[-1] = fsdp_axes
        return P(*spec)
    return P()


def param_specs(params, mesh, plan: ParallelismPlan, *, fsdp: bool = False):
    """Pytree of PartitionSpecs matching `params`."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        specs.append(_leaf_spec(names, leaf, mesh, plan, fsdp))
    return jax.tree_util.tree_unflatten(treedef, specs)


def stacked_specs(tree, mesh, plan: ParallelismPlan):
    """Specs for per-device stacked trees (leading K axis over dev_axes)."""
    inner = param_specs(jax.tree.map(lambda x: x[0], tree), mesh, plan)
    return jax.tree.map(
        lambda s: P(plan.dev_axes, *s), inner,
        is_leaf=lambda s: isinstance(s, P))


def state_specs(state, mesh, plan: ParallelismPlan, *, gen_fsdp: bool):
    """Shardings for the protocol TrainState
    {"gen","disc","gen_opt","disc_opt"(stacked)}."""
    return {
        "gen": param_specs(state["gen"], mesh, plan, fsdp=gen_fsdp),
        "disc": param_specs(state["disc"], mesh, plan, fsdp=False),
        "gen_opt": param_specs_opt(state["gen_opt"], state["gen"], mesh, plan,
                                   fsdp=gen_fsdp),
        "disc_opt": stacked_opt_specs(state["disc_opt"], state["disc"], mesh,
                                      plan),
    }


def param_specs_opt(opt_state, params, mesh, plan, *, fsdp: bool):
    """Optimizer moments share their parameter's sharding; scalars replicate."""
    pspecs = param_specs(params, mesh, plan, fsdp=fsdp)

    def match(node):
        if isinstance(node, dict) and set(node) == set(("m", "v", "t")):
            return {"m": pspecs, "v": pspecs, "t": P()}
        if isinstance(node, dict) and set(node) == set(("mu",)):
            return {"mu": pspecs}
        return jax.tree.map(lambda _: P(), node)

    return match(opt_state)


def stacked_opt_specs(opt_state, params, mesh, plan):
    inner = param_specs(params, mesh, plan, fsdp=False)
    stacked = jax.tree.map(lambda s: P(plan.dev_axes, *s), inner,
                           is_leaf=lambda s: isinstance(s, P))

    def match(node):
        if isinstance(node, dict) and set(node) == set(("m", "v", "t")):
            return {"m": stacked, "v": stacked, "t": P(plan.dev_axes)}
        if isinstance(node, dict) and set(node) == set(("mu",)):
            return {"mu": stacked}
        return jax.tree.map(lambda _: P(plan.dev_axes), node)

    return match(opt_state)


def data_spec(plan: ParallelismPlan):
    """Token shards (K, n_k, seq): device axis over the paper's devices."""
    return P(plan.dev_axes)


def enc_feats_spec(cfg: ArchConfig, mesh, plan: ParallelismPlan):
    """(n, t, d_model) stub frontend features."""
    spec = [None, None, None]
    if _divisible(cfg.d_model, mesh, plan.tp_axis):
        spec[-1] = plan.tp_axis
    return P(*spec)


# ---------------------------------------------------------------------------
# shard_map (mesh-layout) specs — explicit-collective protocol rounds
# ---------------------------------------------------------------------------

# In-slice tensor parallelism (the mesh layout's `model` axis): which
# leaf NAMES carry a Megatron shard, and on which dim. Column-parallel
# weights (and their biases) shard the output dim; row-parallel weights
# shard the input dim. Negative dims make the same rule cover plain
# params, optimizer moments (same leaf names under m/v/mu), and
# device-stacked trees (the leading K axis shifts positive indices but
# not negative ones). Leaves with other names (attention, norms, convs,
# embeds, ssm) replicate over the model axis — and so does EVERYTHING
# under an "experts" subtree: MoE experts reuse the mlp leaf names but
# `moe_apply` has no in-slice collectives, so sharding them would
# silently drop the cross-rank reduction (expert parallelism is an
# open ROADMAP item; `make_backbone_spec` rejects moe + tp_axis).
# TP-named leaves whose dim tp doesn't divide are an ERROR, not a
# replication fallback — see tp_leaf_dim.
_TP_COL = {"w_in", "w_gate", "b_in"}      # output-dim shard
_TP_ROW = {"w_out"}                       # input-dim shard
_TP_REPLICATED_SUBTREES = {"experts"}


def tp_leaf_dim(name: str, shape, tp: int):
    """The model-axis shard dim of one leaf (negative), or None when the
    leaf replicates by name.

    A TP-NAMED leaf whose shard dim `tp` doesn't divide RAISES instead
    of silently replicating: unlike the GSPMD rules above (where the
    compiler inserts the collectives, so replication is a safe
    fallback), the manual Megatron apply path psums unconditionally —
    a replicated leaf would have its outputs inflated by exactly tp.
    """
    if tp <= 1:
        return None
    if name in _TP_COL and len(shape) >= 1:
        dim = -1
    elif name in _TP_ROW and len(shape) >= 2:
        dim = -2
    else:
        return None
    if shape[dim] % tp != 0:
        raise ValueError(
            f"tensor-parallel leaf {name!r} {tuple(shape)}: shard dim "
            f"{shape[dim]} is not divisible by tp={tp} — the Megatron "
            f"apply path would psum un-sharded products (outputs x{tp}); "
            f"pick a divisible width or a different tp")
    return dim


def _tp_path_dim(path_names, shape, tp: int):
    """`tp_leaf_dim` with the leaf's PATH context: any leaf under a
    replicated subtree (MoE experts) stays replicated regardless of
    its name."""
    if any(n in _TP_REPLICATED_SUBTREES for n in path_names):
        return None
    name = path_names[-1] if path_names else ""
    return tp_leaf_dim(name, shape, tp)


def tp_tree_dims(tree, tp: int):
    """Shard dims for every leaf of `tree`, as a tuple aligned with
    `jax.tree_util.tree_flatten(tree)` order (None entries don't
    survive a pytree, so the aligned-tuple form is the contract —
    `quantize.roundtrip_tp` consumes it the same way).

    IMPORTANT: call this on GLOBAL-shaped trees. Divisibility is
    decided on the global dim; deciding it again on local shards could
    disagree (e.g. global 6 % 2 == 0 but local 3 % 2 != 0).
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    dims = []
    for path, leaf in flat:
        names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        dims.append(_tp_path_dim(names, leaf.shape, tp))
    return tuple(dims)


def tp_local_size(tree, tp: int) -> int:
    """Per-TP-rank element count of `tree` (global): sharded leaves
    contribute size/tp — the Algorithm-2 all-gather payload per slice."""
    flat = jax.tree_util.tree_leaves(tree)
    dims = tp_tree_dims(tree, tp)
    return sum(int(x.size) // (tp if d is not None else 1)
               for x, d in zip(flat, dims))


def tree_specs(tree, spec_leaf: P):
    """Broadcast one PartitionSpec over every leaf of `tree` (None leaves
    included, as optimizer states may carry them)."""
    return jax.tree.map(lambda _: spec_leaf, tree,
                        is_leaf=lambda x: x is None)


def _tp_entry_specs(tree, device_axes, stacked: bool, tp_axis: str,
                    tp: int):
    """Per-leaf specs for ONE TrainState entry with in-slice TP: the
    model axis lands on the leaf's shard dim, the device axes on dim 0
    of stacked entries."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: x is None)
    specs = []
    for path, leaf in flat:
        if leaf is None:
            specs.append(P())
            continue
        names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        ndim = len(leaf.shape)
        dim = _tp_path_dim(names, leaf.shape, tp)
        entries = [None] * ndim
        if stacked and ndim >= 1:
            entries[0] = device_axes
        if dim is not None:
            entries[ndim + dim] = tp_axis
        while entries and entries[-1] is None:   # P(None) != P()
            entries.pop()
        specs.append(P(*entries))
    return jax.tree_util.tree_unflatten(treedef, specs)


def tp_param_specs(tree, tp_axis: str, tp: int):
    """Public per-leaf shard_map specs for a bare parameter tree with
    in-slice Megatron TP: each TP-named leaf (`tp_leaf_dim` name rules)
    carries `tp_axis` on its shard dim, everything else replicates.

    This is the train-to-serve contract: a serving engine wraps its
    decode step in shard_map with these in_specs and an unmodified
    GLOBAL-shaped training checkpoint shards on entry, exactly as
    `shard_round_state_specs` shards it for training. Call with the
    GLOBAL tree (divisibility is decided on global dims)."""
    return _tp_entry_specs(tree, (), False, tp_axis, tp)


def shard_round_state_specs(state, device_axes,
                            stacked_keys=("disc_opt",),
                            tp_axis=None, tp: int = 1) -> dict:
    """shard_map in/out specs for a TrainState under the mesh layout.

    Entries in `stacked_keys` carry a leading K axis stacked over the
    device axes (each slice IS one of the paper's K devices); the rest
    replicate over the device axes (the server is shared-seed replicated
    computation). Proposed protocol: only `disc_opt` is per-device.
    FedGAN: both optimizer states are per-device (`gen_opt` AND
    `disc_opt`), since every device trains a local generator too.

    With `tp_axis`/`tp` set (the 2-D device x model mesh), TP-shardable
    leaves additionally carry the model axis on their Megatron shard dim
    (`tp_leaf_dim` name rules) in EVERY entry — params, opt moments, and
    stacked trees alike — so shard_map splits/reassembles the global
    state and each slice sees only its parameter shard. Call with the
    GLOBAL state (divisibility is decided on global dims).
    """
    if tp_axis is not None and tp > 1:
        return {k: _tp_entry_specs(v, device_axes, k in stacked_keys,
                                   tp_axis, tp)
                for k, v in state.items()}
    stacked, rep = P(device_axes), P()
    return {k: tree_specs(v, stacked if k in stacked_keys else rep)
            for k, v in state.items()}


# ---------------------------------------------------------------------------
# Serving (cache) shardings
# ---------------------------------------------------------------------------

def cache_specs(cfg: ArchConfig, caches, batch: int, mesh,
                plan: ParallelismPlan):
    """Specs for decode caches (leading group axis G on every leaf).

    Strategy: shard batch over the device axes when divisible; otherwise
    (long_500k, b=1) shard the KV length dim over (dev_axes + model) for
    distributed flash-decode. kv-heads/head_dim stay unsharded unless
    the batch path already consumed the device axes and kv divides model.
    """
    dev = plan.dev_axes
    tp = plan.tp_axis
    batch_shardable = _divisible(batch, mesh, dev)

    def leaf_spec(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        name = names[-1]
        shape = leaf.shape  # (G, b, ...)
        spec = [None] * len(shape)
        if batch_shardable and len(shape) >= 2 and shape[1] == batch:
            spec[1] = dev
        if name in ("k", "v", "pos", "valid") and len(shape) >= 3:
            # length dim is index 2 for k/v (G,b,L,kv,hd) and (G,b,L) for pos
            length = shape[2]
            if not batch_shardable:
                axes = dev + (tp,)
                if _divisible(length, mesh, axes):
                    spec[2] = axes
                elif _divisible(length, mesh, dev):
                    spec[2] = dev
            elif name in ("k", "v") and _divisible(length, mesh, tp):
                spec[2] = tp
        if name == "ssm" and len(shape) == 5:
            # (G, b, h, n, p): shard heads over model when divisible
            if _divisible(shape[2], mesh, tp):
                spec[2] = tp
        if name == "conv" and len(shape) == 4:
            if _divisible(shape[3], mesh, tp):
                spec[3] = tp
        return P(*spec)

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
    return jax.tree_util.tree_unflatten(
        treedef, [leaf_spec(p, l) for p, l in flat])
