"""Jit'd wrapper: (b, s, h, p) mixer layout <-> kernel (BH, S, P) layout,
group expansion, chunk padding, and the `scan_impl` hook consumed by
`repro.nn.ssm.ssd_mixer_apply`."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, initial_state=None,
             return_final_state: bool = False, interpret: bool | None = None):
    """Drop-in replacement for repro.nn.ssm.ssd_scan_ref.

    x (b,s,h,p); dt (b,s,h); A (h,); B,C (b,s,g,n).
    initial_state is not supported by the kernel path (prefill starts
    from zero state); callers resume via the reference decode step.
    """
    assert initial_state is None, "kernel path starts from zero state"
    interpret = interpret_mode(interpret)
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g

    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad

    # (b, s, h, p) -> (b*h, s, p); expand groups to heads
    xk = jnp.moveaxis(x, 2, 1).reshape(b * h, sp, p)
    dtk = jnp.moveaxis(dt, 2, 1).reshape(b * h, sp)
    a = dtk * jnp.tile(A.astype(jnp.float32), b).reshape(b * h, 1)
    Bh = jnp.repeat(B, rep, axis=2)
    Ch = jnp.repeat(C, rep, axis=2)
    Bk = jnp.moveaxis(Bh, 2, 1).reshape(b * h, sp, n)
    Ck = jnp.moveaxis(Ch, 2, 1).reshape(b * h, sp, n)

    y, state = ssd_scan_pallas(xk, dtk, a, Bk, Ck, chunk=min(chunk, sp),
                               interpret=interpret)
    y = jnp.moveaxis(y.reshape(b, h, sp, p), 1, 2)[:, :s]
    if return_final_state:
        return y, state.reshape(b, h, n, p)
    return y
