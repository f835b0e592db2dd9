"""Fused head-interleaved KV page layout, shared by the ragged paged
flash-decode kernel, its jnp reference, and the attention decode path.

Raw pages interleave K and V per KV head so that *one* HBM->VMEM async
copy per (lane, token-chunk) streams both SDPA operands of every head::

    kv [B, W, 2*Hkv, Dl]      K_h = kv[:, :, 2h]   V_h = kv[:, :, 2h+1]

where ``Dl = lane_dim(Dh)`` pads head_dim to whole 128-lane rows (zeros).

Quantized-resident pools (hybrid / fully-digital MXFP4 SDPA) mirror the
pages in the MXFP4 code domain. Codes are nibble-packed along head_dim
for both operands — a V row's codes sit next to the K row they decode
with, so the same fused copy streams both — while the shared exponents
keep each operand's own blocking (K per row along head_dim, V per
32-slot block along the *key* axis, exactly the PR 4 legacy mirrors)::

    kv_codes [B, W, 2*Hkv, Dpad//2]    uint8  (Dpad = head_dim padded to 32)
    k_exps   [B, W, Hkv, Dpad//32]     int8   per-row head_dim blocks
    v_exps   [B, ceil(W/32), Hkv, Dh]  int8   per 32-slot key block

Dequantizing a bk-token chunk therefore needs one contiguous ``kv_codes``
slice, one ``k_exps`` slice, and at most ``bk//32 + 1`` ``v_exps`` rows.
The quantize calls below are the same ones the legacy split mirrors run
(``layers.attention._quant_cache_full`` / ``_quant_cache_step``), only
repacked — nibble packing is lossless, so the fused mirrors decode
bitwise to the legacy requant-per-step reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import mx as mxlib

BLOCK = mxlib.BLOCK
LANES = 128


def padded_head_dim(hd: int) -> int:
    return -(-hd // BLOCK) * BLOCK


def lane_dim(hd: int) -> int:
    """Minor dim of the raw fused pages: head_dim padded to whole 128-lane
    rows. The paged kernel's HBM->VMEM copies need a lane-aligned minor
    dim (XLA's TPU layouts tile it in 128 lanes anyway)."""
    return -(-hd // LANES) * LANES


def fuse_kv(k: jax.Array, v: jax.Array) -> jax.Array:
    """k, v [..., H, D] -> fused [..., 2H, D] (K even / V odd rows)."""
    s = k.shape
    return jnp.stack([k, v], axis=-2).reshape(s[:-2] + (2 * s[-2], s[-1]))


def split_kv(kv: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused [..., 2H, D] -> (k, v) each [..., H, D]."""
    return kv[..., 0::2, :], kv[..., 1::2, :]


def fuse_pages(k: jax.Array, v: jax.Array) -> jax.Array:
    """Raw k, v [..., H, Dh] -> fused pages [..., 2H, lane_dim(Dh)]."""
    d = lane_dim(k.shape[-1])
    return fuse_kv(_pad_d(k, d), _pad_d(v, d))


def split_pages(kv: jax.Array, hd: int) -> tuple[jax.Array, jax.Array]:
    """Fused pages [..., 2H, lane_dim(hd)] -> (k, v) each [..., H, hd]."""
    k, v = split_kv(kv)
    return k[..., :hd], v[..., :hd]


def fused_cache_init(batch: int, w: int, n_kv: int, hd: int,
                     dtype=jnp.bfloat16) -> dict:
    return {"kv": jnp.zeros((batch, w, 2 * n_kv, lane_dim(hd)), dtype)}


def fused_quant_init(batch: int, w: int, n_kv: int, hd: int) -> dict:
    """Quantized mirrors of a zero page: zero blocks quantize to zero
    codes (packed byte 0) with the E8M0 floor exponent — matching what
    ``quant_page_full`` would produce on zeros."""
    dpad = padded_head_dim(hd)
    nwb = -(-w // BLOCK)
    return {
        "kv_codes": jnp.zeros((batch, w, 2 * n_kv, dpad // 2), jnp.uint8),
        "k_exps": jnp.full(
            (batch, w, n_kv, dpad // BLOCK), mxlib.E8M0_MIN, jnp.int8
        ),
        "v_exps": jnp.full((batch, nwb, n_kv, hd), mxlib.E8M0_MIN, jnp.int8),
    }


def _pad_d(x: jax.Array, dpad: int) -> jax.Array:
    if x.shape[-1] == dpad:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, dpad - x.shape[-1])]
    return jnp.pad(x, pad)


def quant_page_full(kw: jax.Array, vw: jax.Array) -> dict:
    """Quantize whole cache-shaped K/V pages [B, W, Hkv, Dh]
    (prefill-into-cache) into the fused mirrors. Same quantize calls as
    the legacy mirror fill, repacked into the fused layout."""
    w, hd = kw.shape[1], kw.shape[-1]
    dpad = padded_head_dim(hd)
    kq = mxlib.quantize(kw.astype(jnp.float32))  # codes [B, W, Hkv, Dpad]
    vq = mxlib.quantize_axis(vw.astype(jnp.float32), 1)  # key axis last
    v_codes = _pad_d(jnp.moveaxis(vq.codes[..., :w], -1, 1), dpad)
    return {
        "kv_codes": mxlib.pack_codes(fuse_kv(kq.codes, v_codes)),
        "k_exps": kq.exps,
        "v_exps": jnp.moveaxis(vq.exps, -1, 1),  # [B, ceil(W/32), Hkv, Dh]
    }


def quant_page_step(quant: dict, kv: jax.Array, rows: jax.Array,
                    slot: jax.Array) -> dict:
    """Per-step resident update of the fused mirrors — the fused port of
    ``layers.attention._quant_cache_step``: re-quantize only the written
    K row and the active 32-slot V block, reading raw values back from
    the just-updated fused pool ``kv`` [P, W, 2Hkv, lane_dim(Dh)] at pool
    rows ``rows`` (int32 [L], one per decode lane; ``slot`` int32 [L])."""
    w, hd = kv.shape[1], quant["v_exps"].shape[-1]
    hkv = kv.shape[2] // 2
    dpad = padded_head_dim(hd)
    even = 2 * jnp.arange(hkv)
    kq = mxlib.quantize(kv[rows, slot][:, 0::2, :hd].astype(jnp.float32))
    out = {
        "kv_codes": quant["kv_codes"].at[
            rows[:, None], slot[:, None], even[None, :]
        ].set(mxlib.pack_codes(kq.codes)),
        "k_exps": quant["k_exps"].at[rows, slot].set(kq.exps),
    }
    start = (slot // BLOCK) * BLOCK  # [L]
    idx = start[:, None] + jnp.arange(BLOCK)  # [L, 32]
    blk = kv[rows[:, None], jnp.minimum(idx, w - 1)][..., 1::2, :hd]
    blk = jnp.where((idx < w)[:, :, None, None], blk, 0)  # partial end block
    vq = mxlib.quantize_axis(blk.astype(jnp.float32), 1)  # [L, Hkv, Dh, 32]
    v_codes = _pad_d(jnp.moveaxis(vq.codes, -1, 1), dpad)  # [L, 32, Hkv, Dpad]
    out["kv_codes"] = out["kv_codes"].at[
        rows[:, None, None], idx[:, :, None], (even + 1)[None, None, :]
    ].set(mxlib.pack_codes(v_codes), mode="drop")
    out["v_exps"] = quant["v_exps"].at[rows, slot // BLOCK].set(
        vq.exps[..., 0]
    )
    return out


def _scale_blocks(codes: jax.Array, exps: jax.Array) -> jax.Array:
    """bf16 code values [..., K] * 2^(e-1) from int8 exps [..., K//32].
    Codes (<= 4 significant bits) times a power of two are exact in bf16,
    so this matches the legacy f32 ``mxlib.dequantize(...).astype(bf16)``
    bitwise."""
    shp = codes.shape
    cb = codes.reshape(shp[:-1] + (shp[-1] // BLOCK, BLOCK))
    scale = mxlib.exp2i(exps.astype(jnp.int32) - 1).astype(jnp.bfloat16)
    return (cb * scale[..., None]).reshape(shp)


def dequant_k_pages(kv_codes: jax.Array, k_exps: jax.Array,
                    hd: int) -> jax.Array:
    """Fused codes [..., W, 2Hkv, Dpad//2] + exps [..., W, Hkv, Dpad//32]
    -> bf16 K pages [..., W, Hkv, Dh]."""
    codes = mxlib.unpack_pairs_bf16(kv_codes[..., 0::2, :])
    return _scale_blocks(codes, k_exps)[..., :hd]


def dequant_v_pages(kv_codes: jax.Array, v_exps: jax.Array,
                    hd: int) -> jax.Array:
    """Fused codes + slot-block-major exps [..., ceil(W/32), Hkv, Dh]
    -> bf16 V pages [..., W, Hkv, Dh]. The shared exponent of slot ``s``
    is row ``s // 32`` of ``v_exps``."""
    codes = mxlib.unpack_pairs_bf16(kv_codes[..., 1::2, :])[..., :hd]
    w = codes.shape[-3]
    scale = mxlib.exp2i(v_exps.astype(jnp.int32) - 1).astype(jnp.bfloat16)
    scale = jnp.repeat(scale, BLOCK, axis=-3)[..., :w, :, :]
    return codes * scale
