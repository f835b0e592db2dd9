"""Public wrapper: [B, S, H, D] layout, GQA folding, pad/unpad."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel


def flash_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, Hkv, D]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    interpret: bool | None = None,  # None -> platform default
) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    g = h // hkv
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    with jax.named_scope("repro/flash_attention"):
        o = flash_attention_kernel(
            qf, kf, vf, groups=g, causal=causal, window=window,
            q_offset=q_offset, interpret=interpret,
        )
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
