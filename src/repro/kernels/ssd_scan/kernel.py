"""Mamba2 SSD chunk-scan Pallas TPU kernel.

TPU-native formulation of the SSD (state-space duality) forward: the
sequence is pre-chunked (B, NC, Q, ...); the grid walks (batch*head,
chunk) with the chunk axis innermost and *sequential*, carrying the
running (hd, N) recurrent state in VMEM scratch across grid steps — the
standard TPU trick for inter-block recurrences (cf. flash attention's
running softmax).  Per grid step the kernel computes, entirely in VMEM:

  intra-chunk (MXU):  y += ((C B^T) .* decay .* dt) @ x      (Q x Q dots)
  inter-chunk (MXU):  y += exp(cum) .* (C @ h_prev^T)
  state update:       h  = exp(cum_last) h_prev + (decay_out dt B)^T x

Working set per step: Q*(hd + 2N) + Q*Q + hd*N floats — with Q = 256,
hd = 64, N = 128 that's ~0.4 MB fp32, VMEM-friendly with double buffering.

Validated against models/mamba2.ssd_chunked (the pure-jnp oracle, re-used
as ref) in tests/test_kernels_ssd.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import resolve_interpret


def _ssd_kernel(x_ref, da_ref, dt_ref, b_ref, c_ref, y_ref, hout_ref,
                h_ref, *, q: int, nc: int):
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, hd)
    da = da_ref[0, 0].astype(jnp.float32)        # (1, Q)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (1, Q)
    bmat = b_ref[0, 0].astype(jnp.float32)       # (Q, N)
    cmat = c_ref[0, 0].astype(jnp.float32)       # (Q, N)

    # inclusive in-chunk decay cumsum, as a column and as a row.  Masked
    # sums stand in for cumsum and transpose, which Mosaic does not lower.
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = ii >= jj
    eye = ii == jj
    cum_col = jnp.sum(jnp.where(tri, da, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(eye, cum_col, 0.0), axis=0, keepdims=True)
    cum_last = jnp.sum(da)
    # intra-chunk: decay[i,j] = exp(cum_i - cum_j) for i >= j
    decay = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)
    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    att = scores * decay * dt
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state h_prev (hd, N)
    h_prev = h_ref[...]
    y += jnp.exp(cum_col) * jax.lax.dot_general(
        cmat, h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: h = exp(cum_last) h_prev + sum_j w_j x_j B_j^T, with
    # w_j = exp(cum_last - cum_j) dt_j scaling the rows of x
    w_row = jnp.exp(cum_last - cum_row) * dt      # (1, Q)
    w_col = jnp.sum(jnp.where(eye, w_row, 0.0), axis=1, keepdims=True)
    s_chunk = jax.lax.dot_general(x * w_col, bmat,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    h_ref[...] = jnp.exp(cum_last) * h_prev + s_chunk  # (hd, N)

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(cj == nc - 1)
    def _emit_state():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def ssd_scan_pallas(x, da, dt, bmat, cmat, *, interpret: bool | None = None):
    """Chunked SSD scan.

    x:    (BH, NC, Q, hd)  per-(batch*head) chunked inputs
    da:   (BH, NC, Q)      log-decay  dt*A  (negative)
    dt:   (BH, NC, Q)      step sizes
    bmat: (BH, NC, Q, N)   input projections  (already head-broadcast)
    cmat: (BH, NC, Q, N)   output projections
    Returns (y: (BH, NC, Q, hd), h_final: (BH, hd, N)), fp32.

    The TPU tiles the last two dims of every block, so ``da``/``dt``
    travel as (BH, NC, 1, Q) rows.
    """
    bh, nc, q, hd = x.shape
    n = bmat.shape[-1]
    kernel = functools.partial(_ssd_kernel, q=q, nc=nc)
    return pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, q, hd), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, hd), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, hd, n), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, nc, q, hd), jnp.float32),
            jax.ShapeDtypeStruct((bh, hd, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, n), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, da[:, :, None, :], dt[:, :, None, :], bmat, cmat)
