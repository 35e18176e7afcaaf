"""Single-token GQA decode attention over a long KV cache (Pallas TPU).

The serve_step hot loop: one query token per sequence against a KV cache
of up to 524288 positions.  Memory-bound: the kernel streams the cache
through VMEM in (bk, KV, hd) blocks, once per query head, while
accumulating the online softmax.

  grid = (batch, q_head, T/bk); kv-block innermost/sequential.
  Per-sequence valid length arrives via scalar prefetch (SMEM) — tokens
  beyond `pos` are masked, so ragged continuous-batching batches work.

Validated against ref.reference_decode_attention in interpret mode
(tests/test_kernels_decode.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import resolve_interpret

NEG_INF = -2.3819763e38


def _kv_head(ref, kvh):
    """(bk, hd) fp32 tile of KV head ``kvh`` from a (1, bk, KV, hd) block.
    Each head is read at a static offset and the dynamic one is selected
    arithmetically: packed dtypes store pairs of heads in one sublane, so
    a dynamic index along that axis does not lower."""
    kv = ref.shape[2]
    out = ref[0, :, 0].astype(jnp.float32)
    for i in range(1, kv):
        out = jnp.where(kvh == i, ref[0, :, i].astype(jnp.float32), out)
    return out


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   scale: float, softcap: float, window: int,
                   bk: int, kv_blocks: int, group: int):
    b = pl.program_id(0)
    kvh = pl.program_id(1) // group
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (1, hd)
    k = _kv_head(k_ref, kvh)                       # (bk, hd)
    v = _kv_head(v_ref, kvh)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)        # (1, bk)

    pos = pos_ref[b]
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    mask = kpos <= pos
    if window:
        mask &= kpos > pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == kv_blocks - 1)
    def _finalize():
        l = l_ref[0, 0]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, pos, *, softcap: float = 0.0,
                            window: int = 0, scale: float | None = None,
                            block_k: int = 1024,
                            interpret: bool | None = None):
    """q: (B, H, hd); k, v: (B, T, KV, hd); pos: (B,) int32.

    Returns (B, H, hd).  KV layout is the cache layout (seq-major).  The
    TPU tiles the last two dims of every block, so q travels as
    (B, H, 1, hd) and each K/V block spans the whole (KV, hd) tail; the
    kernel picks its KV head (q head // group) inside the block.
    """
    b, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    group = h // kv
    bk = min(block_k, t)
    assert t % bk == 0, (t, bk)
    kv_blocks = t // bk
    scale = hd ** -0.5 if scale is None else scale

    kernel = functools.partial(
        _decode_kernel, scale=scale, softcap=softcap, window=window,
        bk=bk, kv_blocks=kv_blocks, group=group)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd), lambda b_, h_, j, pos_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, bk, kv, hd), lambda b_, h_, j, pos_: (b_, j, 0, 0)),
            pl.BlockSpec((1, bk, kv, hd), lambda b_, h_, j, pos_: (b_, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd),
                               lambda b_, h_, j, pos_: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(pos, q[:, :, None, :], k, v)
    return out[:, :, 0, :]
