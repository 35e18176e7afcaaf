"""jit'd public wrapper for flash attention (kernel or oracle path).

``flash_attention`` takes model-layout tensors (B, S, H, hd) / (B, T, KV,
hd) like models/attention.py produces, transposes to the kernel layout,
and dispatches to the Pallas kernel (compiled on a TPU, interpreted
elsewhere) or the reference oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_pallas
from .ref import reference_attention


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "use_kernel", "block_q", "block_k",
    "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, use_kernel: bool = True,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool | None = None):
    """q: (B, S, H, hd); k, v: (B, T, KV, hd) -> (B, S, H, hd)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if use_kernel:
        out = flash_attention_pallas(
            qt, kt, vt, causal=causal, window=window, softcap=softcap,
            block_q=block_q, block_k=block_k, interpret=interpret)
    else:
        out = reference_attention(qt, kt, vt, causal=causal, window=window,
                                  softcap=softcap)
    return jnp.swapaxes(out, 1, 2)
