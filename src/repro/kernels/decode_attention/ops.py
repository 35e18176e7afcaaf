"""jit'd public wrapper for decode attention."""

from __future__ import annotations

import functools

import jax

from .kernel import decode_attention_pallas
from .ref import reference_decode_attention


@functools.partial(jax.jit, static_argnames=(
    "softcap", "window", "use_kernel", "block_k", "interpret"))
def decode_attention(q, k, v, pos, *, softcap: float = 0.0, window: int = 0,
                     use_kernel: bool = True, block_k: int = 1024,
                     interpret: bool | None = None):
    """q: (B, H, hd); k, v cache: (B, T, KV, hd); pos: (B,) -> (B, H, hd)."""
    if use_kernel:
        return decode_attention_pallas(q, k, v, pos, softcap=softcap,
                                       window=window, block_k=block_k,
                                       interpret=interpret)
    return reference_decode_attention(q, k, v, pos, softcap=softcap,
                                      window=window)
