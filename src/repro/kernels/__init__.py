"""Pallas TPU kernels for the framework's compute hot spots.

  wavg        Algorithm 2 — weighted discriminator averaging (the paper's
              central server-side op), blocked over the flattened
              parameter vector.
  robust_avg  trimmed-mean / norm-clip / Krum variants of Algorithm 2.
  ring_wavg   dequantize-and-accumulate step of the ring collective.
  ssd_scan    Mamba-2 SSD chunked scan (mamba2/zamba2 mixers).
  flash_attn  online-softmax attention forward (serving prefill).

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper with padding/layout), ref.py (pure-jnp oracle). Kernels are
compiled for the TPU; on the CPU backend they run in interpret mode
(the kernel body runs in Python). `interpret_mode` makes that choice
when a kernel is called, never while a module is imported."""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    An explicit `interpret` wins. Otherwise it follows the platform of
    the default backend: interpret on "cpu", compiled on "tpu", and an
    error anywhere else — a kernel never quietly gives way to its
    reference or to the interpreter on a device it was not written
    for."""
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run compiled on 'tpu' or in "
                       f"interpret mode on 'cpu'; got platform "
                       f"{platform!r}")
