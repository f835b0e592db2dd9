"""Public dispatch for ragged paged flash-decode + shape-derived knobs.

``ragged_paged_decode`` is what ``layers.attention`` calls from the fused
decode branch: ``use_pallas`` (from ``RunCtx.impl="auto"`` dispatch)
selects the streaming Pallas kernel, otherwise the bitwise jnp reference
runs. ``pick_bk``/``pick_buffers`` derive the chunk width and DMA ring
depth from the page shape — short pages double-buffer, long pages (many
chunks in flight) quad-buffer so compute never waits on HBM.
"""

from __future__ import annotations

import jax

from repro.core import mx as mxlib
from repro.kernels.paged_attention import kernel as pk
from repro.kernels.paged_attention import ref as pref

BLOCK = mxlib.BLOCK
MAX_BK = 128


def pick_bk(w: int) -> int:
    """Chunk width for a page of ``w`` slots: a multiple of 32 (so V
    slot-blocks tile cleanly) capped at 128; sub-32 pages stream whole."""
    if w < BLOCK:
        return w
    return min(MAX_BK, (w // BLOCK) * BLOCK)


def pick_buffers(w: int, bk: int) -> int:
    """DMA ring depth: quad-buffer once a max-length lane runs >= 8
    chunks (long pages — deeper prefetch hides HBM latency jitter),
    double-buffer otherwise."""
    nchunks = -(-w // bk)
    return 4 if nchunks >= 8 else 2


def ragged_paged_decode(
    q: jax.Array,  # [L, Hkv, G, Dh] (mx path: already fake-quant bf16)
    rows: jax.Array,  # int32 [L] pool row per lane
    lengths: jax.Array,  # int32 [L] valid slots per lane
    *,
    kv: jax.Array | None = None,  # fused raw pages [P, W, 2Hkv, >= Dh]
    quant: dict | None = None,  # fused code mirrors (quantized-resident)
    scale: float,
    use_pallas: bool = False,
    interpret: bool | None = None,
    bk: int | None = None,
    buffers: int | None = None,  # DMA ring depth (float kernel only)
) -> jax.Array:
    """Returns [L, Hkv, G, Dh]. Exactly one of ``kv`` / ``quant``."""
    if (kv is None) == (quant is None):
        raise ValueError("pass exactly one of kv= (float) or quant= (mx)")
    if not use_pallas:
        with jax.named_scope("repro/paged_attention.ref"):
            return pref.ragged_paged_decode_ref(
                q, rows, lengths, kv=kv, quant=quant, scale=scale
            )
    w = (kv if quant is None else quant["kv_codes"]).shape[1]
    bk = bk or pick_bk(w)
    buffers = buffers or pick_buffers(w, bk)
    if quant is None:
        with jax.named_scope("repro/paged_attention"):
            return pk.paged_flash_decode(
                q, kv, rows, lengths, scale=scale, bk=bk, buffers=buffers,
                interpret=interpret,
            )
    with jax.named_scope("repro/paged_attention.mx"):
        return pk.paged_flash_decode_mx(
            q, quant["kv_codes"], quant["k_exps"], quant["v_exps"], rows,
            lengths, scale=scale, bk=bk, interpret=interpret,
        )
