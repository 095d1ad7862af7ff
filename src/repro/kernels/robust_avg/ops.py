"""Robust Algorithm-2 reducers over the all-gathered flat payload —
alternate `impl`s of `core.averaging.weighted_average_psum` for hostile
worker populations (core/faults.py).

Every method keeps the mesh hot path at ONE all-gather + ONE Pallas
kernel call per round (pinned in tests/test_kernels.py):

  trimmed_mean — the dedicated Pallas kernel (kernel.py): per-
      coordinate masked extreme-pair removal + weighted mean, VPU
      select-and-reduce over the same (K, BN) tiles as `wavg`.
  norm_clip    — per-row L2 norms and the median-norm clip threshold
      are O(K) jnp on the already-gathered matrix; the clipped
      EFFECTIVE WEIGHTS feed the existing `wavg` MXU kernel.
  krum         — multi-Krum scoring from ONE (K, K) Gram matmul on the
      gathered matrix; the selected-set weights feed the `wavg` kernel.

Weights are RAW participation-aware weights (0 = dropped worker), so
dropped workers contribute zero without changing the payload shape.
Identity regimes (all-honest == plain wavg, bitwise on the weight
vector): trim=0, clip_factor large enough that no row clips, or
krum_f=0 (selects every participant).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.robust_avg.kernel import trimmed_wavg_pallas
from repro.kernels.wavg.kernel import BLOCK_N
from repro.kernels.wavg import ops as wavg_ops

ROBUST_METHODS = ("trimmed_mean", "norm_clip", "krum")


@dataclasses.dataclass(frozen=True)
class RobustConfig:
    """Robust-reducer selection + parameters (hashable: part of the
    mesh builder memo keys and `engine.Trainer`'s chunk cache keys)."""
    method: str = "trimmed_mean"
    trim: int = 1                       # (max, min) pairs per coordinate
    clip_factor: float = 2.0            # tau = factor x median norm
    krum_f: int = 1                     # assumed byzantine count
    krum_m: Optional[int] = None        # multi-Krum size (None: n_part - f)

    def __post_init__(self):
        if self.method not in ROBUST_METHODS:
            raise ValueError(f"unknown robust method {self.method!r} "
                             f"(have {ROBUST_METHODS})")
        if self.trim < 0:
            raise ValueError(f"trim must be >= 0 (got {self.trim})")
        if self.clip_factor <= 0:
            raise ValueError(
                f"clip_factor must be > 0 (got {self.clip_factor})")
        if self.krum_f < 0:
            raise ValueError(f"krum_f must be >= 0 (got {self.krum_f})")


def trimmed_average(x, w, *, trim: int, interpret: Optional[bool] = None):
    """Coordinate trimmed mean of x (K, N) with raw weights w (K,) ->
    (N,) f32. Pads N to BLOCK_N for the kernel and slices back (zero
    pad columns are harmless: the output tail is discarded)."""
    interpret = interpret_mode(interpret)
    n = x.shape[1]
    pad = (-n) % BLOCK_N
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    out = trimmed_wavg_pallas(x, w, trim=trim, interpret=interpret)
    return out[:n]


def _masked_median(v, mask):
    """Median of v[mask] (mean of the two middle order statistics, as
    np.median), 0 when the mask is empty."""
    k = v.shape[0]
    s = jnp.sort(jnp.where(mask, v, jnp.inf))
    n_part = jnp.sum(mask.astype(jnp.int32))
    lo = jnp.clip((n_part - 1) // 2, 0, k - 1)
    hi = jnp.clip(n_part // 2, 0, k - 1)
    return jnp.where(n_part > 0, 0.5 * (s[lo] + s[hi]), 0.0)


def clip_weights(x, w, *, clip_factor: float):
    """Norm-clipping as an effective-weight transform: row k scaled by
    s_k = min(1, clip_factor * median participant norm / ||x_k||), and
    the mean normalized by the ORIGINAL weight total (sum w_k s_k x_k /
    sum w_k — clipped rows shrink toward zero). Returns the normalized
    weight vector to feed the `wavg` kernel. With no row clipping the
    scales are exactly 1.0, so the vector is bitwise the plain
    normalized wavg weights."""
    part = w > 0.0
    norms = jnp.sqrt(jnp.sum(x * x, axis=1))
    tau = clip_factor * _masked_median(norms, part)
    scale = jnp.minimum(1.0, tau / jnp.maximum(norms, 1e-12))
    w_eff = jnp.where(part, w * scale, 0.0)
    return w_eff / jnp.maximum(jnp.sum(w), 1e-12)


def krum_weights(x, w, *, f: int, m: Optional[int] = None):
    """Multi-Krum selection as an effective-weight transform: score by
    the sum of the q = clamp(n_part - f - 2, 1, K-1) smallest squared
    distances to other participants (one Gram matmul), keep the
    m = max(n_part - f, 1) lowest scores (ties by lowest index), and
    return the selected weights normalized for the `wavg` kernel. With
    f=0 and m=None every participant is selected — bitwise the plain
    normalized weights."""
    k = x.shape[0]
    part = w > 0.0
    n_part = jnp.sum(part.astype(jnp.int32))
    sq = jnp.sum(x * x, axis=1)
    d2 = jnp.maximum(sq[:, None] + sq[None, :]
                     - 2.0 * jnp.dot(x, x.T,
                                     preferred_element_type=jnp.float32),
                     0.0)
    invalid = (~part[:, None] | ~part[None, :]
               | jnp.eye(k, dtype=bool))
    d2 = jnp.where(invalid, jnp.inf, d2)
    q = jnp.clip(n_part - f - 2, 1, k - 1)
    ds = jnp.sort(d2, axis=1)
    take = jnp.arange(k)[None, :] < q
    score = jnp.sum(jnp.where(take & jnp.isfinite(ds), ds, 0.0), axis=1)
    score = jnp.where(part, score, jnp.inf)
    m_sel = jnp.maximum(n_part - f, 1) if m is None else jnp.int32(m)
    m_sel = jnp.clip(m_sel, 1, jnp.maximum(n_part, 1))
    order = jnp.lexsort((jnp.arange(k), score))
    rank = jnp.zeros(k, jnp.int32).at[order].set(jnp.arange(k, dtype=jnp.int32))
    sel = (rank < m_sel) & part
    w_eff = jnp.where(sel, w, 0.0)
    return w_eff / jnp.maximum(jnp.sum(w_eff), 1e-12)


def robust_average(x, w, cfg: RobustConfig, *,
                   interpret: Optional[bool] = None):
    """Robust weighted aggregate of the gathered payload: x (K, N), raw
    weights w (K,) -> (N,) f32. Dispatches per `cfg.method`; norm_clip
    and krum compute effective weights in jnp and reduce with the
    existing `wavg` Pallas kernel, trimmed_mean runs its own kernel —
    every method is one Pallas call on the (K, N) payload."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if cfg.method == "trimmed_mean":
        return trimmed_average(x, w, trim=cfg.trim, interpret=interpret)
    if cfg.method == "norm_clip":
        v = clip_weights(x, w, clip_factor=cfg.clip_factor)
    elif cfg.method == "krum":
        v = krum_weights(x, w, f=cfg.krum_f, m=cfg.krum_m)
    else:
        raise ValueError(cfg.method)
    return wavg_ops.weighted_average(x, v, interpret=interpret)
