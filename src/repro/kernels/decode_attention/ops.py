"""jit'd wrapper for decode_attention: model layout (B, H, D) /
(B, S, KV, D) ↔ kernel layout (B, KV, rep, D) / (B, KV, S, D)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention_pallas
from .ref import decode_attention_ref


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "use_kernel", "interpret"))
def decode_attention(q, k, v, pos, *, window=0, softcap=0.0,
                     use_kernel=True, interpret=False):
    """q: (B, H, D); k, v: (B, S, KV, D); pos scalar → (B, H, D)."""
    if not use_kernel:
        return decode_attention_ref(q, k, v, pos, window=window, softcap=softcap)
    B, H, D = q.shape
    KV = k.shape[2]
    rep = H // KV
    qk = q.reshape(B, KV, rep, D)
    out = decode_attention_pallas(
        qk, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), pos,
        window=window, softcap=softcap,
        interpret=interpret,
    )
    return out.reshape(B, H, D)
