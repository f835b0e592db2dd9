"""jnp ragged paged-decode reference over the fused page layout.

Gathers only the lanes' page rows out of the pool and then runs the
*exact* legacy decode-branch math from ``layers.attention.attn_apply``
(same einsum strings, op order and dtypes), so the reference is bitwise
the PR 4 legacy decode — on the float path and on the quantized-resident
path (whose fused mirrors decode bitwise, see ``layout``). The Pallas
kernel streams the same pages with an online softmax and per-KV-chunk P
quantization, so kernel-vs-reference is tolerance-equivalent — the same
dense-vs-flash granularity precedent as ``layers.attention._flash_attn``.

Lane ``i`` attends over page slots ``[0, lengths[i])`` of pool row
``rows[i]``; ``lengths[i] == min(pos + 1, W)`` reproduces the legacy
ring-write validity mask (a wrapped ring has all ``W`` slots valid).
``lengths[i] == 0`` (a parked lane) yields a zero output row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import mx as mxlib
from repro.kernels.paged_attention import layout


def ragged_paged_decode_ref(
    q: jax.Array,  # [L, Hkv, G, Dh] — mx path: already MXFP4-fake-quant bf16
    rows: jax.Array,  # int32 [L] pool row per lane
    lengths: jax.Array,  # int32 [L] valid slots per lane, in [0, W]
    *,
    kv: jax.Array | None = None,  # [P, W, 2Hkv, >= Dh] raw pages (float path)
    quant: dict | None = None,  # fused code mirrors (quantized-resident)
    scale: float,
) -> jax.Array:
    """Returns [L, Hkv, G, Dh]; bf16 on the mx path, ``kv.dtype`` on the
    float path (exactly the legacy decode output dtypes)."""
    hd = q.shape[-1]
    mx = quant is not None
    if mx:
        kvc = jnp.take(quant["kv_codes"], rows, axis=0)
        kd = layout.dequant_k_pages(
            kvc, jnp.take(quant["k_exps"], rows, axis=0), hd
        )
        vd = layout.dequant_v_pages(
            kvc, jnp.take(quant["v_exps"], rows, axis=0), hd
        )
        w = kvc.shape[1]
    else:
        pages = jnp.take(kv, rows, axis=0)  # [L, W, 2Hkv, >= Dh]
        kd, vd = layout.split_pages(pages, hd)
        w = kv.shape[1]
    valid = jnp.arange(w)[None, :] < lengths[:, None]  # [L, W]
    sc = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q[:, None], kd,
        preferred_element_type=jnp.float32,
    ) * scale
    if mx:
        sc = sc.astype(jnp.bfloat16).astype(jnp.float32)  # systolic round
    sc = jnp.where(valid[:, None, None, None, :], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    # zero-length lanes: all-masked softmax is NaN; the legacy decode
    # never sees length 0 (pos >= 0 always validates slot 0), so this
    # guard is an exact no-op on every legacy-reachable input
    pr = jnp.where(valid.any(-1)[:, None, None, None, None], pr, 0.0)
    if mx:
        pr = mxlib.fake_quant(pr)  # P quantized along the key axis
        den = jnp.sum(pr, axis=-1, keepdims=True)
        den = jnp.where(den == 0.0, 1.0, den)
        o = jnp.einsum(
            "bhgqk,bkhd->bqhgd", pr.astype(jnp.bfloat16),
            vd.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
        )
        o = (o / jnp.moveaxis(den, -2, 1)).astype(jnp.bfloat16)
    else:
        o = jnp.einsum("bhgqk,bkhd->bqhgd", pr.astype(vd.dtype), vd)
    return o[:, 0]
