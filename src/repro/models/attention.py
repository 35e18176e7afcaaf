"""Attention: MHA/GQA/MQA, causal + local-window, softcap, KV-cache decode.

Full-sequence paths can route through the Pallas flash-attention kernel
(kernels/flash_attention) when ``use_kernel`` is set; the default is the
pure-jnp reference path (identical math — the kernel is validated against
it in tests/test_kernels_*.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.logical import current_rules
from .layers import apply_rope, dense_init, rmsnorm, rmsnorm_init, softcap

__all__ = ["attn_init", "attn_apply", "attn_prefill", "attn_decode", "init_kv_cache"]

NEG_INF = -2.3819763e38  # bf16-safe large negative


def attn_init(key, cfg: ModelConfig, dtype, *, cross: bool = False):
    hd = cfg.resolved_head_dim
    h, kv = cfg.n_heads, (cfg.n_heads if cross else cfg.n_kv_heads)
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (cfg.d_model, h * hd), dtype),
        "wk": dense_init(ks[1], (cfg.d_model, kv * hd), dtype),
        "wv": dense_init(ks[2], (cfg.d_model, kv * hd), dtype),
        "wo": dense_init(ks[3], (h * hd, cfg.d_model), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _project_qkv(p, cfg: ModelConfig, xq, xkv):
    hd = cfg.resolved_head_dim
    q = (xq @ p["wq"]).reshape(*xq.shape[:-1], -1, hd)
    k = (xkv @ p["wk"]).reshape(*xkv.shape[:-1], -1, hd)
    v = (xkv @ p["wv"]).reshape(*xkv.shape[:-1], -1, hd)
    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _sdpa(cfg: ModelConfig, q, k, v, mask, *, k_scale=None, v_scale=None):
    """q: (B,S,H,hd); k,v: (B,T,KV,hd); mask: (B,1,1,S,T) or None.

    k_scale/v_scale: (B,T,KV) dequant scales for int8 KV — they factor out
    of the contraction over hd (k) and fold into probs (v), so the int8
    codes feed the MXU directly and no dequantized cache is materialized.
    """
    b, s, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    groups = h // kv
    qg = q.reshape(b, s, kv, groups, hd)
    logits = jnp.einsum("bskgd,btkd->bkgst",
                        qg, k.astype(q.dtype)).astype(jnp.float32)
    if k_scale is not None:
        logits *= k_scale.transpose(0, 2, 1)[:, :, None, None, :]
    logits *= hd ** -0.5
    if cfg.attn_softcap:
        logits = softcap(logits, cfg.attn_softcap)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if v_scale is not None:
        probs = probs * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    probs = probs.astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(q.dtype))
    return out.reshape(b, s, h, hd)


def _sdpa_chunked(cfg: ModelConfig, q, k, v, *, causal: bool, window: int,
                  chunk: int = 1024):
    """Flash-style online-softmax attention: lax.scan over KV chunks, never
    materializing the (S, T) score matrix.  Pure-jnp twin of
    kernels/flash_attention (same math, XLA-visible memory savings on the
    dry-run; the Pallas kernel is the on-TPU fast path).  Selected via the
    logical rule ``attn=chunked`` (§Perf lever)."""
    b, s, h, hd = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    ck = min(chunk, t)
    if t % ck:
        return None                                   # caller falls back
    nc = t // ck
    f32 = jnp.float32
    qg = q.reshape(b, s, kvh, g, hd).astype(f32) * hd ** -0.5
    kc = jnp.moveaxis(k.reshape(b, nc, ck, kvh, hd), 1, 0).astype(f32)
    vc = jnp.moveaxis(v.reshape(b, nc, ck, kvh, hd), 1, 0).astype(f32)
    qpos = jnp.arange(s)

    def body(carry, inp):
        m, l, acc = carry
        kj, vj, j = inp
        logits = jnp.einsum("bskgd,bckd->bkgsc", qg, kj)
        if cfg.attn_softcap:
            logits = softcap(logits, cfg.attn_softcap)
        kpos = j * ck + jnp.arange(ck)
        mask = jnp.ones((s, ck), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1))
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum("bkgsc,bckd->bkgsd", p, vj)
        return (m_new, l, acc), None

    m0 = jnp.full((b, kvh, g, s), NEG_INF, f32)
    l0 = jnp.zeros((b, kvh, g, s), f32)
    a0 = jnp.zeros((b, kvh, g, s, hd), f32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kc, vc, jnp.arange(nc)))
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    out = jnp.moveaxis(out, -2, 1).reshape(b, s, h, hd)
    return out.astype(q.dtype)


def _attention(cfg: ModelConfig, q, k, v, mask, *, causal: bool, window: int):
    """Dispatch on the `attn` logical rule:
      chunked — lax.scan online-softmax (flash twin, §Perf A3)
      pallas  — the actual Pallas kernel (interpret off-TPU)
      default — straightforward masked sdpa (paper-faithful baseline)."""
    rules, _ = current_rules()
    impl = rules.get("attn") if rules is not None else None
    if impl == "chunked" and causal:
        out = _sdpa_chunked(cfg, q, k, v, causal=causal, window=window)
        if out is not None:
            return out
    if impl == "pallas" and causal:
        s, t = q.shape[1], k.shape[1]
        bq, bk = min(512, s), min(512, t)
        if s % bq == 0 and t % bk == 0:
            from repro.kernels.flash_attention import flash_attention
            return flash_attention(
                q, k, v, causal=True, window=window,
                softcap=cfg.attn_softcap, block_q=bq, block_k=bk)
    return _sdpa(cfg, q, k, v, mask)


def _causal_mask(s: int, t: int, q_offset, local_window: int):
    """(s,t) bool mask; q position i attends kv position j<=i (+window)."""
    qpos = jnp.arange(s) + q_offset
    kpos = jnp.arange(t)
    m = kpos[None, :] <= qpos[:, None]
    if local_window:
        m &= kpos[None, :] > qpos[:, None] - local_window
    return m


def attn_apply(p, cfg: ModelConfig, x, positions, *, local: bool = False,
               causal: bool = True, xkv=None, kv_positions=None):
    """Full-sequence attention (train / encoder / cross)."""
    xkv = x if xkv is None else xkv
    q, k, v = _project_qkv(p, cfg, x, xkv)
    if cfg.use_rope and xkv is x:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    mask = None
    window = cfg.local_window if local else 0
    if causal:
        mask = _causal_mask(x.shape[1], xkv.shape[1], 0, window)
        mask = mask[None, None, None]                     # (1,1,1,S,T)
    out = _attention(cfg, q, k, v, mask, causal=causal, window=window)
    return out.reshape(*x.shape[:-1], -1) @ p["wo"]


# ---------------------------------------------------------------------------
# KV-cache paths
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                  *, local: bool = False):
    hd = cfg.resolved_head_dim
    if local and cfg.kv_ring and cfg.local_window:
        max_len = min(max_len, cfg.local_window)
    shape = (batch, max_len, cfg.n_kv_heads, hd)
    if cfg.kv_quant:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:3], jnp.float32),
                "v_scale": jnp.zeros(shape[:3], jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _quantize_kv(x):
    """x: (..., hd) -> (int8 codes, per-row scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-20)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale


def attn_prefill(p, cfg: ModelConfig, x, positions, *, local: bool = False):
    """Like attn_apply but also returns the cache entry for decode."""
    q, k, v = _project_qkv(p, cfg, x, x)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    window = cfg.local_window if local else 0
    mask = _causal_mask(x.shape[1], x.shape[1], 0, window)[None, None, None]
    out = _attention(cfg, q, k, v, mask, causal=True, window=window)
    y = out.reshape(*x.shape[:-1], -1) @ p["wo"]
    if cfg.kv_quant:
        k8, ks = _quantize_kv(k)
        v8, vs = _quantize_kv(v)
        return y, {"k": k8, "v": v8, "k_scale": ks, "v_scale": vs}
    return y, {"k": k, "v": v}


def attn_decode(p, cfg: ModelConfig, x, cache, pos, *, local: bool = False):
    """Single-token decode. x: (B,1,D); pos: (B,) int32; cache k/v (B,T,KV,hd).

    Returns (y, new_cache).  The KV write is a per-sequence dynamic scatter
    so ragged batches (continuous batching) are supported.  Supports int8
    caches (cfg.kv_quant) and ring-buffer local-window caches
    (cfg.kv_ring: cache length == window, writes at pos % window).
    """
    b = x.shape[0]
    q, k, v = _project_qkv(p, cfg, x, x)                   # q: (B,1,H,hd)
    if cfg.use_rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
    bi = jnp.arange(b)
    t = cache["k"].shape[1]
    ring = local and cfg.kv_ring and cfg.local_window and t == cfg.local_window
    wpos = pos % t if ring else pos
    new_cache = {}
    if cfg.kv_quant:
        k8, ks = _quantize_kv(k[:, 0])
        v8, vs = _quantize_kv(v[:, 0])
        ck = cache["k"].at[bi, wpos].set(k8)
        cv = cache["v"].at[bi, wpos].set(v8)
        cks = cache["k_scale"].at[bi, wpos].set(ks)
        cvs = cache["v_scale"].at[bi, wpos].set(vs)
        new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
        scales = {"k_scale": cks, "v_scale": cvs}
    else:
        ck = cache["k"].at[bi, wpos].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[bi, wpos].set(v[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": ck, "v": cv}
        scales = {}
    kpos = jnp.arange(t)[None, :]                          # (1,T)
    if ring:
        # every slot holds the latest position congruent to it (<= pos);
        # before the window fills, only slots <= pos are valid.  Stored k
        # carry their absolute-position RoPE, so order doesn't matter.
        mask = (kpos <= pos[:, None]) | (pos[:, None] >= t)
    else:
        mask = kpos <= pos[:, None]
        if local and cfg.local_window:
            mask &= kpos > (pos[:, None] - cfg.local_window)
    out = _sdpa(cfg, q, ck, cv, mask[:, None, None, None, :],
                k_scale=scales.get("k_scale"), v_scale=scales.get("v_scale"))
    y = out.reshape(b, 1, -1) @ p["wo"]
    return y, new_cache
