"""jit'd wrapper around the flash_attention kernel.

Layout: models use (B, S, H, D); the kernel wants (B, H, S, D).
``use_kernel=False`` runs the jnp oracle instead."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_pallas
from .ref import flash_attention_ref


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "use_kernel", "interpret"),
)
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    use_kernel=True, interpret=False):
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D) → (B, Sq, H, D)."""
    if not use_kernel:
        return flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=softcap)
    out = flash_attention_pallas(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=causal, window=window, softcap=softcap,
        interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3)
