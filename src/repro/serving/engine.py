"""Continuous-batching serving engine for the trained generator-as-LM.

One jitted step per engine iteration, covering the whole request mix:

  * any-position batched decode — the step takes a per-slot position
    VECTOR, so every active slot decodes every step regardless of where
    it is in its sequence (no per-position grouping, no head-of-line
    blocking), with greedy/temperature sampling fused on-device (the
    host reads back one small token array per step, never logits);
  * chunked prefill interleaved with decode — one prompt chunk (padded
    to a power-of-two bucket, so prefill compiles O(log max_len) times)
    runs through the SAME jitted call as the decode batch, against the
    same caches, using exact no-op masking for the padded tail;
  * paged KV cache (serving.cache) — full-attention caches are shared
    block pools addressed through per-slot block tables, so persistent
    memory scales with live tokens instead of batch x max_len;
  * optional tensor-parallel decode (tp > 1): the step body runs inside
    a shard_map over a (1, model=tp) mesh with `rules.tp_param_specs`
    in_specs — an unmodified GLOBAL-shaped training checkpoint shards
    on entry exactly as training shards it (train-to-serve), the MLP
    psums of `nn/tp.py` keep activations replicated, and sampling is
    computed identically on every rank.

Sampling streams are keyed by (seed, rid, token_index), so a request's
tokens are a deterministic function of the request alone — independent
of scheduling, batch composition, and paged-vs-dense backend.

Host-side: deque admission (FIFO by rid), a rejection path for requests
that can never fit (marked failed; the engine keeps running), and a
block allocator for the paged pool (pool exhaustion queues the head
rather than failing it).
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import gan
from repro.models.backbone import (init_decode_caches, cross_decode_kv,
                                   encoder_apply)
from repro.serving import cache as paging
from repro.sharding import rules
from repro.launch.mesh import make_host_mesh, tp_mesh_error, devices_error


@dataclasses.dataclass
class Request:
    rid: Optional[int]
    prompt: np.ndarray                  # (len,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0            # 0 => greedy
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    failed: Optional[str] = None        # rejection reason (engine keeps going)


@dataclasses.dataclass
class _Slot:
    req: Request
    pos: int = 0                 # prompt cursor (prefill) / next write index
    blocks: list = dataclasses.field(default_factory=list)
    prefilled: bool = False


def _pow2_bucket(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _sample_one(key, logits, temp):
    """Greedy/temperature sampling fused on-device. temp <= 0 => argmax."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled = jax.random.categorical(
        key, logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6))
    return jnp.where(temp > 0, sampled.astype(jnp.int32), greedy)


_DEC_FIELDS = ("tokens", "pos", "active", "temp", "rid", "nout")
_PF_FIELDS = ("tokens", "slot", "pos0", "nvalid", "rid", "temp")


class ServingEngine:
    """Slot-based continuous batching over a fixed decode batch of B
    slots. See module docstring. block_size=None serves from dense
    per-slot caches (the baseline); an int turns on the paged pool."""

    def __init__(self, cfg: ArchConfig, gen_params, *, batch_size: int = 4,
                 max_len: int = 256, block_size: Optional[int] = None,
                 n_blocks: Optional[int] = None, prefill_chunk: int = 32,
                 enc_feats_fn: Optional[Callable] = None, seed: int = 0,
                 tp: int = 1, cache_dtype=jnp.float32):
        self.cfg = cfg
        self.params = gen_params
        self.b = batch_size
        self.max_len = max_len
        self.seed = seed
        self.enc_feats_fn = enc_feats_fn
        self.prefill_chunk = max(1, min(prefill_chunk, max_len))
        self.paged = block_size is not None
        self.tp = tp

        if tp > 1:
            if cfg.moe is not None:
                raise ValueError(
                    f"{cfg.name}: MoE serving is tp=1 only (expert "
                    f"parallelism is a ROADMAP item)")
            if cfg.fuse_proj:
                raise ValueError(
                    f"{cfg.name}: fuse_proj=True cannot be tensor-parallel "
                    f"(fused leaves have no per-shard name rule)")
            err = devices_error(tp, context=f"serving --tp {tp}")
            if err:
                raise RuntimeError(err)
            self._mesh = make_host_mesh(1, tp)
            err = tp_mesh_error(self._mesh, tp)
            if err:
                raise ValueError(err)
            self._pspecs = rules.tp_param_specs(gen_params, "model", tp)

        if self.paged:
            self.caches, meta = paging.init_paged_caches(
                cfg, batch_size, max_len, block_size=block_size,
                n_blocks=n_blocks, dtype=cache_dtype)
            self.block_size = meta["block_size"]
            self.n_blocks = meta["n_blocks"]
            self.max_blocks = meta["max_blocks"]
            self._paged_subs = frozenset(meta["paged_subs"])
            self.alloc = paging.BlockAllocator(self.n_blocks)
        else:
            self.caches = init_decode_caches(cfg, batch_size, max_len,
                                             dtype=cache_dtype)
            self.max_blocks = 1
            self._paged_subs = frozenset()
            self.alloc = None
        self.table = np.zeros((batch_size, self.max_blocks), dtype=np.int32)

        self._fill_cross_caches()
        self.slots: list[Optional[_Slot]] = [None] * batch_size
        self.queue: deque[Request] = deque()
        self.rejected: list[Request] = []
        self.finished: list[Request] = []
        self._pf_order: deque[int] = deque()   # slots awaiting prefill, FIFO
        self._next_rid = 0
        self._steps = {}                       # chunk bucket -> jitted step
        self.dispatch_count = 0                # jitted calls issued
        self._clear_fn = None
        self._reset_fn = None

    # -- construction helpers -------------------------------------------

    def _fill_cross_caches(self):
        """Populate per-slot cross-attention caches once: the stub
        frontend features are request-independent, so every slot shares
        the same projected encoder k/v."""
        if self.cfg.family not in ("encdec", "vlm"):
            return
        assert self.enc_feats_fn is not None, f"{self.cfg.name} needs enc feats"
        feats = self.enc_feats_fn(1)
        if self.cfg.family == "encdec":
            enc_h = jax.jit(
                lambda p, f: encoder_apply(p, self.cfg, f, remat=False)
            )(self.params["encoder"], feats)
        else:
            enc_h = feats
        kvs = jax.jit(
            lambda p, e: cross_decode_kv(p, self.cfg, e)
        )(self.params["backbone"], enc_h)
        for name, kv in kvs.items():
            tgt = self.caches[name]
            self.caches[name] = {
                leaf: jnp.broadcast_to(
                    kv[leaf][:, 0][:, None].astype(tgt[leaf].dtype),
                    tgt[leaf].shape).copy()
                for leaf in tgt}

    # -- the jitted step -------------------------------------------------

    def _split_slot_caches(self, caches, slot):
        """Views for a one-slot prefill: paged pools pass whole (they are
        slot-agnostic — the block table isolates slots), per-slot dense
        leaves are sliced to batch row `slot`."""
        def slice_sub(sub):
            return jax.tree.map(
                lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, axis=1),
                sub)
        return {name: (sub if name in self._paged_subs else slice_sub(sub))
                for name, sub in caches.items()}

    def _merge_slot_caches(self, caches, new_sub, slot):
        def merge(full, part):
            return jax.lax.dynamic_update_slice_in_dim(
                full, part.astype(full.dtype), slot, axis=1)
        return {name: (new_sub[name] if name in self._paged_subs
                       else jax.tree.map(merge, caches[name], new_sub[name]))
                for name in caches}

    def _build_step(self, chunk: Optional[int]):
        """One fused serving step: an optional prefill chunk for a single
        slot, then the any-position decode batch, then on-device
        sampling. chunk=None builds the decode-only variant."""
        cfg = self.cfg
        paged = self.paged
        tp_axis = "model" if self.tp > 1 else None

        def body(params, caches, table, seed, dec, pf=None):
            base = jax.random.PRNGKey(seed)
            pf_token = jnp.zeros((), dtype=jnp.int32)
            if chunk is not None:
                sl = pf["slot"]
                row = jax.lax.dynamic_slice_in_dim(table, sl, 1, axis=0)
                positions = (pf["pos0"]
                             + jnp.arange(chunk, dtype=jnp.int32))[None]
                mask = (jnp.arange(chunk, dtype=jnp.int32)
                        < pf["nvalid"])[None]
                out = gan.generator_lm_apply(
                    params, cfg, pf["tokens"], mode="decode",
                    caches=self._split_slot_caches(caches, sl),
                    positions=positions, cache_write_mask=mask,
                    paged_table=row if paged else None, remat=False,
                    tp_axis=tp_axis)
                caches = self._merge_slot_caches(caches, out["caches"], sl)
                last = jax.lax.dynamic_index_in_dim(
                    out["logits"][0], pf["nvalid"] - 1, axis=0,
                    keepdims=False)
                pf_key = jax.random.fold_in(
                    jax.random.fold_in(base, pf["rid"]), 0)
                pf_token = _sample_one(pf_key, last, pf["temp"])
            out = gan.generator_lm_apply(
                params, cfg, dec["tokens"], mode="decode", caches=caches,
                positions=dec["pos"][:, None],
                cache_write_mask=dec["active"][:, None],
                paged_table=jnp.asarray(table) if paged else None,
                remat=False, tp_axis=tp_axis)
            logits = out["logits"][:, 0]
            keys = jax.vmap(lambda r, n: jax.random.fold_in(
                jax.random.fold_in(base, r), n))(dec["rid"], dec["nout"])
            toks = jax.vmap(_sample_one)(keys, logits, dec["temp"])
            return out["caches"], toks, pf_token

        if self.tp > 1:
            rep = lambda tree: jax.tree.map(lambda _: P(), tree)
            in_specs = [self._pspecs, rep(self.caches), P(), P(),
                        {k: P() for k in _DEC_FIELDS}]
            if chunk is not None:
                in_specs.append({k: P() for k in _PF_FIELDS})
            body = jax.shard_map(
                body, mesh=self._mesh, in_specs=tuple(in_specs),
                out_specs=(rep(self.caches), P(), P()), check_vma=False)
        return jax.jit(body, donate_argnums=(1,))

    def _get_step(self, chunk: Optional[int]):
        if chunk not in self._steps:
            self._steps[chunk] = self._build_step(chunk)
        return self._steps[chunk]

    @property
    def compile_count(self) -> int:
        """Distinct (prefill-bucket) step programs built so far — bounded
        by 1 + log2(prefill_chunk) + 1 regardless of prompt mix."""
        return len(self._steps)

    def cache_bytes(self) -> int:
        return paging.cache_bytes(self.caches)

    # -- host logic ------------------------------------------------------

    def submit(self, req: Request):
        if req.rid is None:
            req.rid = self._next_rid
        self._next_rid = max(self._next_rid, req.rid) + 1
        self.queue.append(req)

    def _reject(self, req: Request, reason: str):
        req.failed = reason
        self.rejected.append(req)

    def _admit(self):
        """FIFO admission (deque order == rid order): validation failures
        are rejected and skipped; a head that merely can't fit RIGHT NOW
        (no free slot / pool exhausted) blocks the queue — later
        requests never overtake it."""
        while self.queue:
            req = self.queue[0]
            plen = len(req.prompt)
            total = plen + req.max_new_tokens
            if plen == 0:
                self.queue.popleft()
                self._reject(req, "empty prompt")
                continue
            if total > self.max_len:
                self.queue.popleft()
                self._reject(
                    req, f"needs {total} tokens > engine max_len "
                         f"{self.max_len}")
                continue
            slot = next((s for s in range(self.b) if self.slots[s] is None),
                        None)
            if slot is None:
                return
            blocks = []
            if self.paged:
                need = -(-total // self.block_size)
                blocks = self.alloc.alloc(need)
                if blocks is None:
                    return          # pool exhausted: head waits, FIFO holds
            self.queue.popleft()
            self.table[slot, :] = 0
            if blocks:
                self.table[slot, :len(blocks)] = blocks
            self.caches = self._reset_slot(self.caches, slot)
            self.slots[slot] = _Slot(req=req, pos=0, blocks=blocks)
            self._pf_order.append(slot)

    def _reset_slot(self, caches, slot: int):
        """Wipe the per-slot dense state a previous occupant left behind:
        SSM/conv carries zero, attention ring/cache valid bits drop.
        (Paged pools need no reset — the fresh block table isolates the
        slot, and retired blocks are invalidated on free. Cross caches
        hold the shared encoder k/v and must persist.)"""
        if self._reset_fn is None:
            paged_subs = self._paged_subs

            def reset(caches, slot):
                def reset_sub(sub):
                    out = {}
                    for leaf, l in sub.items():
                        if leaf == "valid":
                            out[leaf] = l.at[:, slot].set(False)
                        elif leaf in ("ssm", "conv"):
                            out[leaf] = l.at[:, slot].set(0)
                        else:
                            out[leaf] = l
                    return out
                return {name: (sub if name in paged_subs
                               else reset_sub(sub))
                        for name, sub in caches.items()}

            self._reset_fn = jax.jit(reset)
        return self._reset_fn(caches, np.int32(slot))

    def _retire(self, slot: int):
        sl = self.slots[slot]
        sl.req.done = True
        self.finished.append(sl.req)
        if self.paged and sl.blocks:
            ids = np.zeros((self.max_blocks,), dtype=np.int32)
            ids[:len(sl.blocks)] = sl.blocks
            if self._clear_fn is None:
                subs = self._paged_subs
                self._clear_fn = jax.jit(
                    lambda c, i: paging.invalidate_blocks(c, sorted(subs), i))
            self.caches = self._clear_fn(self.caches, jnp.asarray(ids))
            self.alloc.free(sl.blocks)
        self.table[slot, :] = 0
        self.slots[slot] = None

    def _next_prefill(self):
        """The oldest admitted slot still prefilling, with its next chunk
        (bucketed to a power of two <= prefill_chunk)."""
        while self._pf_order and (
                self.slots[self._pf_order[0]] is None
                or self.slots[self._pf_order[0]].prefilled):
            self._pf_order.popleft()
        if not self._pf_order:
            return None
        slot = self._pf_order[0]
        sl = self.slots[slot]
        plen = len(sl.req.prompt)
        remaining = plen - sl.pos
        bucket = (self.prefill_chunk if remaining >= self.prefill_chunk
                  else _pow2_bucket(remaining))
        nvalid = min(remaining, bucket)
        tokens = np.zeros((1, bucket), dtype=np.int32)
        tokens[0, :nvalid] = sl.req.prompt[sl.pos:sl.pos + nvalid]
        pf = {"tokens": tokens, "slot": np.int32(slot),
              "pos0": np.int32(sl.pos), "nvalid": np.int32(nvalid),
              "rid": np.int32(sl.req.rid),
              "temp": np.float32(sl.req.temperature)}
        return slot, pf, bucket, nvalid

    def step(self) -> bool:
        """One engine iteration: admit, run ONE jitted call covering the
        next prefill chunk (if any) + every active decode slot, retire
        finished requests. Returns whether any work ran."""
        self._admit()
        pf_work = self._next_prefill()
        dec_slots = [s for s in range(self.b)
                     if self.slots[s] is not None and self.slots[s].prefilled]
        if pf_work is None and not dec_slots:
            return False

        dec = {"tokens": np.zeros((self.b, 1), dtype=np.int32),
               "pos": np.zeros((self.b,), dtype=np.int32),
               "active": np.zeros((self.b,), dtype=bool),
               "temp": np.zeros((self.b,), dtype=np.float32),
               "rid": np.zeros((self.b,), dtype=np.int32),
               "nout": np.zeros((self.b,), dtype=np.int32)}
        for s in dec_slots:
            sl = self.slots[s]
            dec["tokens"][s, 0] = sl.req.out_tokens[-1]
            dec["pos"][s] = sl.pos
            dec["active"][s] = True
            dec["temp"][s] = sl.req.temperature
            dec["rid"][s] = sl.req.rid
            dec["nout"][s] = len(sl.req.out_tokens)

        table = self.table.copy()
        if pf_work is not None:
            pf_slot, pf, bucket, nvalid = pf_work
            step_fn = self._get_step(bucket)
            self.caches, toks, pf_token = step_fn(
                self.params, self.caches, table, np.int32(self.seed),
                dec, pf)
        else:
            step_fn = self._get_step(None)
            self.caches, toks, pf_token = step_fn(
                self.params, self.caches, table, np.int32(self.seed), dec)
        self.dispatch_count += 1
        toks = np.asarray(toks)

        if pf_work is not None:
            sl = self.slots[pf_slot]
            sl.pos += nvalid
            if sl.pos >= len(sl.req.prompt):
                sl.prefilled = True
                sl.req.out_tokens.append(int(pf_token))
                if len(sl.req.out_tokens) >= sl.req.max_new_tokens:
                    self._retire(pf_slot)

        for s in dec_slots:
            sl = self.slots[s]
            sl.req.out_tokens.append(int(toks[s]))
            sl.pos += 1
            if len(sl.req.out_tokens) >= sl.req.max_new_tokens:
                self._retire(s)
        return True

    def run(self, max_steps: int = 10_000):
        steps = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and steps < max_steps:
            if not self.step():
                break
            steps += 1
        return self.finished
