"""Kernel substrate benchmark: us/call of the jnp reference paths plus the
Pallas kernel's max error against them.  The kernel is compiled on a TPU
and interpreted elsewhere (``repro.kernels.backend``); wall-clock times
mean something only on the chip."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


def _time(fn, *args, n=5, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def kernels(quick: bool = False):
    rows = []
    key = jax.random.PRNGKey(0)
    s = 256 if quick else 512

    # flash attention
    q = jax.random.normal(key, (1, s, 8, 64), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, s, 2, 64), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, s, 2, 64), jnp.float32)
    us = _time(flash_attention, q, k, v, use_kernel=False)
    kk = flash_attention(q, k, v, block_q=128, block_k=128)
    rr = flash_attention(q, k, v, use_kernel=False)
    err = float(np.abs(np.asarray(kk) - np.asarray(rr)).max())
    rows.append((f"kernels/flash_attention_s{s}", us, f"kernel_max_err={err:.2e}"))

    # decode attention
    t = 2048 if quick else 8192
    q1 = jax.random.normal(key, (4, 8, 64), jnp.float32)
    k1 = jax.random.normal(jax.random.fold_in(key, 3), (4, t, 2, 64), jnp.float32)
    v1 = jax.random.normal(jax.random.fold_in(key, 4), (4, t, 2, 64), jnp.float32)
    pos = jnp.array([t - 1, t // 2, 7, t - 100], jnp.int32)
    us = _time(decode_attention, q1, k1, v1, pos, use_kernel=False)
    kk = decode_attention(q1, k1, v1, pos, block_k=512)
    rr = decode_attention(q1, k1, v1, pos, use_kernel=False)
    err = float(np.abs(np.asarray(kk) - np.asarray(rr)).max())
    rows.append((f"kernels/decode_attention_t{t}", us, f"kernel_max_err={err:.2e}"))

    # ssd scan
    L = 512 if quick else 1024
    x = jax.random.normal(key, (1, L, 4, 32), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 5), (1, L, 4)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 6), (4,)) * 0.3)
    bm = jax.random.normal(jax.random.fold_in(key, 7), (1, L, 32)) * 0.3
    cm = jax.random.normal(jax.random.fold_in(key, 8), (1, L, 32)) * 0.3
    us = _time(ssd_scan, x, dt, a, bm, cm, chunk=128, use_kernel=False)
    yk, hk = ssd_scan(x, dt, a, bm, cm, chunk=128)
    yr, hr = ssd_scan(x, dt, a, bm, cm, chunk=128, use_kernel=False)
    err = float(max(np.abs(np.asarray(yk) - np.asarray(yr)).max(),
                    np.abs(np.asarray(hk) - np.asarray(hr)).max()))
    rows.append((f"kernels/ssd_scan_L{L}", us, f"kernel_max_err={err:.2e}"))
    return rows
