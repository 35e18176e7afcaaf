"""jit'd public wrapper for the SSD chunk scan."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import ssd_scan_pallas
from .ref import reference_ssd_scan


@functools.partial(jax.jit, static_argnames=("chunk", "use_kernel", "interpret"))
def ssd_scan(x, dt, a, bmat, cmat, *, chunk: int = 256,
             use_kernel: bool = True, interpret: bool | None = None):
    """Model-layout SSD scan (drop-in for models.mamba2.ssd_chunked).

    x: (B, L, nh, hd); dt: (B, L, nh); a: (nh,); bmat/cmat: (B, L, N).
    Returns (y: (B, L, nh, hd), h_final: (B, nh, hd, N)).
    """
    b, length, nh, hd = x.shape
    n = bmat.shape[-1]
    assert length % chunk == 0
    nc = length // chunk
    da = dt * a[None, None, :]                              # (B, L, nh)
    # fold heads into rows: (B*nh, NC, Q, ...)
    xk = x.transpose(0, 2, 1, 3).reshape(b * nh, nc, chunk, hd)
    dak = da.transpose(0, 2, 1).reshape(b * nh, nc, chunk)
    dtk = dt.transpose(0, 2, 1).reshape(b * nh, nc, chunk)
    bk = jnp.broadcast_to(bmat[:, None], (b, nh, length, n)).reshape(
        b * nh, nc, chunk, n)
    ck = jnp.broadcast_to(cmat[:, None], (b, nh, length, n)).reshape(
        b * nh, nc, chunk, n)
    if use_kernel:
        y, h = ssd_scan_pallas(xk, dak, dtk, bk, ck, interpret=interpret)
    else:
        y, h = reference_ssd_scan(xk, dak, dtk, bk, ck)
    y = y.reshape(b, nh, length, hd).transpose(0, 2, 1, 3)
    h = h.reshape(b, nh, hd, n)
    return y.astype(x.dtype), h
