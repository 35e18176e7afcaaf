"""Flash-attention Pallas TPU kernel (prefill/train path).

Blocked online-softmax attention with explicit BlockSpec VMEM tiling:
  grid = (batch, q_head, S/bq, T/bk), kv-block innermost & sequential;
  running (m, l, acc) state lives in VMEM scratch and is re-initialized at
  kv-block 0, finalized (acc / l) at the last kv block.

Supports GQA (q-head -> kv-head via integer division in the k/v index
maps), causal and local-window masking (gemma2), attention-logit softcap,
and fp32 accumulation regardless of input dtype.

Block shapes: (bq, head_dim) q tiles and (bk, head_dim) k/v tiles — the
working set per grid step is bq*hd + 2*bk*hd + bq*bk floats; with
bq = bk = 512, hd = 128 that is ~0.9 MB fp32, comfortably inside the
~16 MB/core VMEM with double buffering.  MXU alignment: hd is a multiple
of 128 for every assigned arch except whisper (64).

Validated against ref.reference_attention in interpret mode
(tests/test_kernels_flash.py) over shape/dtype sweeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import resolve_interpret

NEG_INF = -2.3819763e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, softcap: float,
                  bq: int, bk: int, kv_blocks: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)                  # rows with no valid kv
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0, scale: float | None = None,
                           block_q: int = 512, block_k: int = 512,
                           interpret: bool | None = None):
    """q: (B, H, S, hd); k, v: (B, KV, T, hd).  Returns (B, H, S, hd)."""
    b, h, s, hd = q.shape
    _, kv, t, _ = k.shape
    assert h % kv == 0, (h, kv)
    group = h // kv
    bq = min(block_q, s)
    bk = min(block_k, t)
    assert s % bq == 0 and t % bk == 0, (s, bq, t, bk)
    kv_blocks = t // bk
    scale = hd ** -0.5 if scale is None else scale

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, kv_blocks=kv_blocks)

    return pl.pallas_call(
        kernel,
        grid=(b, h, s // bq, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b_, h_, i, j, g=group: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b_, h_, i, j, g=group: (b_, h_ // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max m
            pltpu.VMEM((bq,), jnp.float32),      # running sum l
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
