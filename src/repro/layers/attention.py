"""Grouped-query attention with RoPE/M-RoPE, causal/sliding-window/
local:global masking, KV-cache decode, and a memory-efficient
online-softmax (FlashAttention-style) path for long sequences.

This is the model-level attention; the paper's digital-stage numerics
simulator lives in ``repro.core.digital`` and the TPU kernel in
``repro.kernels.flash_attention``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import mx as mxlib
from repro.kernels.paged_attention import layout as paged_layout
from repro.kernels.paged_attention import ops as paged_ops
from repro.layers import rope as ropelib
from repro.layers.common import (
    RunCtx,
    linear_apply,
    linear_init,
    norm_apply,
    norm_init,
    rmsnorm_apply,
)


@dataclasses.dataclass(frozen=True)
class AttnStatic:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    causal: bool = True
    window: int = 0  # 0 = full attention; >0 = sliding window
    rope_theta: float = 1e4
    use_rope: bool = True
    mrope: bool = False
    qk_norm: bool = False
    use_bias: bool = False
    norm: str = "rmsnorm"
    softmax_scale: float | None = None

    @property
    def scale(self) -> float:
        return (
            self.head_dim**-0.5
            if self.softmax_scale is None
            else self.softmax_scale
        )


def attn_init(key, cfg: AttnStatic):
    ks = jax.random.split(key, 5)
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    p, s = {}, {}
    p["ln"], s["ln"] = norm_init(cfg.norm, d)
    p["wq"], s["wq"] = linear_init(
        ks[0], d, h * hd, use_bias=cfg.use_bias, out_axis="qkv_fused"
    )
    p["wk"], s["wk"] = linear_init(
        ks[1], d, kv * hd, use_bias=cfg.use_bias, out_axis="kv_fused"
    )
    p["wv"], s["wv"] = linear_init(
        ks[2], d, kv * hd, use_bias=cfg.use_bias, out_axis="kv_fused"
    )
    p["wo"], s["wo"] = linear_init(
        ks[3], h * hd, d, use_bias=cfg.use_bias, in_axis="qkv_fused",
        out_axis="embed",
    )
    if cfg.qk_norm:
        p["qn"], s["qn"] = norm_init("rmsnorm", hd)
        p["kn"], s["kn"] = norm_init("rmsnorm", hd)
    return p, s


# Sentinel position marking padded K/V entries (fixed-shape serving
# prefill, flash-attention tile padding). Any key whose position is at or
# below the sentinel threshold is excluded from attention unconditionally —
# a plain causal mask (kp <= qp) would otherwise *include* large-negative
# pad positions for every query.
KV_PAD = -(10**9)
_KV_PAD_MIN = KV_PAD // 2


def _mask(q_pos, k_pos, causal: bool, window: int):
    """bool [..., Sq, Sk]; True = attend. Keys at KV_PAD never attend."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    m = jnp.broadcast_to(kp > _KV_PAD_MIN,
                         jnp.broadcast_shapes(qp.shape, kp.shape))
    if causal:
        m = m & (kp <= qp)
    if window > 0:
        m = m & (kp > qp - window)
    return m


# Digital MXFP4 systolic SDPA quantization (paper §4.4-4.5), shared by the
# dense, flash and decode paths so the hybrid numerics stay in one place.

def _mx_qk(q, k):
    """Quantize Q/K along the head_dim contraction (last axis). bf16
    inputs run the bf16-native chain (same quantize decisions — the input
    is already bf16 — without a f32 round-trip)."""
    return _mx_fq(q), _mx_fq(k)


def _mx_fq(t):
    if t.dtype == jnp.bfloat16:
        return mxlib.fake_quant(t)
    return mxlib.fake_quant(t.astype(jnp.float32))


def _mx_score_round(s):
    """BF16 systolic accumulator round of the QK^T scores."""
    return s.astype(jnp.bfloat16).astype(jnp.float32)


def _mx_pv(p, v):
    """Re-quantize P (last axis) and V (key axis 1) before the SV array.
    Returns (p_q, v_q, den): outputs must be divided by ``den`` — the sum
    of the *quantized* probabilities, i.e. the hardware normalizer block
    (same deferred-division semantics as ``core/digital.mx_attention`` and
    the flash path), so quantizing P introduces no systematic row scale."""
    p, den = _mx_p(p)
    vq = (mxlib.fake_quant_axis(v, 1) if v.dtype == jnp.bfloat16
          else mxlib.fake_quant_axis(v.astype(jnp.float32), 1))
    return p, vq, den


def _mx_p(p):
    """Quantize P along the key axis + the hardware normalizer sum."""
    p = mxlib.fake_quant(p)
    den = jnp.sum(p, axis=-1, keepdims=True)
    den = jnp.where(den == 0.0, 1.0, den)
    return p, den


# Quantized-resident KV cache (digital-SDPA decode). The requant-per-step
# reference quantizes the *entire* K cache along head_dim and the entire V
# cache along the key axis on every decode step — O(cache_len) quantize
# work per token. But K rows quantize per-row independently (a row's codes
# only change when the row is rewritten) and V's shared-exponent 32-blocks
# along the key axis only change when a write lands inside them; so the
# cache can keep codes + exponents *resident* and re-quantize only the
# written K row and the active V block per step — O(1) in cache length,
# bitwise identical to the reference.
#
# Layouts: K codes [B, W, Hkv, Dh_pad] quantized along head_dim (exps
# [B, W, Hkv, Dh_pad//32]); V codes [B, Hkv, Dh, W_pad] with the *key*
# axis last (exps [B, Hkv, Dh, W_pad//32]) so the quantized axis is the
# contiguous block axis in both.

def _quant_cache_sizes(w: int, hd: int):
    dpad = -(-hd // mxlib.BLOCK) * mxlib.BLOCK
    wpad = -(-w // mxlib.BLOCK) * mxlib.BLOCK
    return dpad, wpad


def quant_cache_init(batch: int, w: int, n_kv: int, hd: int) -> dict:
    """Quantized mirrors for a zero-initialized K/V cache: zero blocks
    quantize to zero codes with the E8M0 floor exponent."""
    dpad, wpad = _quant_cache_sizes(w, hd)
    return {
        "k_codes": jnp.zeros((batch, w, n_kv, dpad), jnp.int8),
        "k_exps": jnp.full(
            (batch, w, n_kv, dpad // mxlib.BLOCK), mxlib.E8M0_MIN, jnp.int8
        ),
        "v_codes": jnp.zeros((batch, n_kv, hd, wpad), jnp.int8),
        "v_exps": jnp.full(
            (batch, n_kv, hd, wpad // mxlib.BLOCK), mxlib.E8M0_MIN, jnp.int8
        ),
    }


def _quant_cache_full(kw: jax.Array, vw: jax.Array) -> dict:
    """Quantize a whole cache-shaped K/V pair (prefill-into-cache):
    K per row along head_dim, V along the key axis in 32-blocks."""
    kq = mxlib.quantize(kw.astype(jnp.float32))
    vq = mxlib.quantize_axis(vw.astype(jnp.float32), 1)  # key axis last
    return {"k_codes": kq.codes, "k_exps": kq.exps,
            "v_codes": vq.codes, "v_exps": vq.exps}


def _quant_cache_step(cache: dict, ck: jax.Array, cv: jax.Array,
                      lanes: jax.Array, slot: jax.Array) -> dict:
    """Per-step resident update: re-quantize the written K row and the
    active 32-block of V (from the just-updated raw caches ``ck``/``cv``),
    leaving every other block's codes untouched — they are bitwise what a
    full requant would recompute."""
    b, w = cv.shape[0], cv.shape[1]
    kq = mxlib.quantize(ck[lanes, slot].astype(jnp.float32))  # [B, Hkv, *]
    out = {
        "k_codes": cache["k_codes"].at[lanes, slot].set(kq.codes),
        "k_exps": cache["k_exps"].at[lanes, slot].set(kq.exps),
    }
    start = (slot // mxlib.BLOCK) * mxlib.BLOCK  # [B]
    idx = start[:, None] + jnp.arange(mxlib.BLOCK)  # [B, 32]
    blk = jnp.take_along_axis(
        cv, jnp.minimum(idx, w - 1)[:, :, None, None], axis=1
    )
    blk = jnp.where((idx < w)[:, :, None, None], blk, 0)  # partial end block
    vq = mxlib.quantize_axis(blk.astype(jnp.float32), 1)  # [B, Hkv, Dh, 32]
    out["v_codes"] = jax.vmap(
        lambda c, u, st: jax.lax.dynamic_update_slice(c, u, (0, 0, st))
    )(cache["v_codes"], vq.codes, start)
    out["v_exps"] = cache["v_exps"].at[
        lanes, :, :, slot // mxlib.BLOCK
    ].set(vq.exps[..., 0])
    return out


def _dense_attn(
    q, k, v, q_pos, k_pos, cfg: AttnStatic, extra_mask=None,
    mx_digital: bool = False,
):
    """q [B,Sq,Hkv,G,Dh]; k,v [B,Sk,Hkv,Dh].

    With ``mx_digital`` the SDPA runs on the paper's digital MXFP4
    systolic datapath (core/digital.py numerics): Q/K quantized along the
    head_dim contraction, BF16 score accumulation, P/V re-quantized along
    the key contraction before the SV array. This is the hybrid backend's
    dynamic stage — weights live in the analog array, SDPA stays digital.
    """
    if mx_digital:
        q, k = _mx_qk(q, k)
        # MXFP4 values are exactly bf16-representable (4-bit mantissa),
        # so the systolic operands move as bf16 with f32 accumulation —
        # half the GEMM traffic, bitwise the same scores
        q, k = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32
    ) * cfg.scale
    if mx_digital:
        s = _mx_score_round(s)
    m = _mask(q_pos, k_pos, cfg.causal, cfg.window)[:, None, None]
    if extra_mask is not None:
        m &= extra_mask[:, None, None]
    s = jnp.where(m, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isfinite(s).any(-1, keepdims=True), p, 0.0)
    if mx_digital:
        p, v, den = _mx_pv(p, v)
        o = jnp.einsum(
            "bhgqk,bkhd->bqhgd", p.astype(jnp.bfloat16),
            v.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
        )
        # deferred division by the quantized-P sum *after* the SV array —
        # the hardware normalizer block (core/digital.mx_attention and the
        # flash path do the same), and O(q*d) divides instead of O(q*k)
        o = o / jnp.moveaxis(den, -2, 1)
        return o.astype(jnp.bfloat16)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)


def _flash_attn(q, k, v, q_pos, k_pos, cfg: AttnStatic, ctx: RunCtx,
                mx_digital: bool = False):
    """Online-softmax attention, chunked over Q (lax.map) and KV (scan).
    Compiles to compact HLO and bounds live score memory to
    [B, qc, Hkv, G, kc]. Same tiling scheme as the Pallas kernel.

    With ``mx_digital`` (hybrid / fully-digital MXFP4 eval) Q/K are
    quantized along the head_dim contraction, scores get the BF16 systolic
    round, and P/V are re-quantized per KV tile along the key contraction —
    the same per-tile treatment as ``core/digital.mx_attention``, so the
    digital-SDPA semantics do not depend on which attention path the
    sequence length selects. Note the quantization *granularity* differs
    from the dense path (per KV tile vs whole key axis), so dense and
    flash are statistically — not bitwise — equivalent, mirroring the
    tiled systolic hardware."""
    if mx_digital:
        qq, kq = _mx_qk(q, k)
        q, k = qq.astype(q.dtype), kq.astype(k.dtype)
    b, sq, hkv, g, dh = q.shape
    sk = k.shape[1]
    kc = min(ctx.attn_chunk, sk)
    qc = min(ctx.q_chunk, sq)
    nkc = -(-sk // kc)
    nqc = -(-sq // qc)
    pad_k = nkc * kc - sk
    pad_q = nqc * qc - sq
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad_k)), constant_values=KV_PAD)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)))
    kcs = k.reshape(b, nkc, kc, hkv, dh)
    vcs = v.reshape(b, nkc, kc, hkv, dh)
    kps = k_pos.reshape(b, nkc, kc)

    def one_q_chunk(args):
        qi, qpi = args  # [B, qc, Hkv, G, Dh], [B, qc]

        def step(carry, xs):
            m_run, den, acc = carry
            kci, vci, kpi = xs  # [B, kc, ...]
            s = jnp.einsum(
                "bqhgd,bkhd->bqhgk", qi, kci,
                preferred_element_type=jnp.float32,
            ) * cfg.scale
            if mx_digital:
                s = _mx_score_round(s)
            msk = _mask(qpi, kpi, cfg.causal, cfg.window)  # [B, qc, kc]
            s = jnp.where(msk[:, :, None, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            corr = jnp.where(
                jnp.isfinite(m_run), jnp.exp(m_run - m_safe), 0.0
            )
            if mx_digital:  # per-tile P/V re-quant; den accumulates the
                # quantized-P sums, so the final division normalizes
                p, vq, _ = _mx_pv(p, vci)
                vci = vq.astype(vci.dtype)
            pv = jnp.einsum("bqhgk,bkhd->bqhgd", p.astype(vci.dtype), vci)
            acc = acc * corr[..., None].astype(acc.dtype) + pv.astype(jnp.float32)
            den = den * corr + jnp.sum(p, axis=-1)
            return (m_new, den, acc), None

        init = (
            jnp.full((b, qc, hkv, g), -jnp.inf, jnp.float32),
            jnp.zeros((b, qc, hkv, g), jnp.float32),
            jnp.zeros((b, qc, hkv, g, dh), jnp.float32),
        )
        (m_run, den, acc), _ = jax.lax.scan(
            step, init, (kcs.swapaxes(0, 1), vcs.swapaxes(0, 1), kps.swapaxes(0, 1))
        )
        den = jnp.where(den == 0.0, 1.0, den)
        return (acc / den[..., None]).astype(q.dtype)

    qcs = q.reshape(b, nqc, qc, hkv, g, dh).swapaxes(0, 1)
    qps = q_pos.reshape(b, nqc, qc).swapaxes(0, 1)
    out = jax.lax.map(one_q_chunk, (qcs, qps))  # [nqc, B, qc, Hkv, G, Dh]
    out = out.swapaxes(0, 1).reshape(b, nqc * qc, hkv, g, dh)
    return out[:, :sq]


def _qkv(ctx: RunCtx, cfg: AttnStatic, p: dict, x: jax.Array, positions,
         rope_tables=None):
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = linear_apply(ctx, p["wq"], x, name="wq").reshape(b, s, h, hd)
    k = linear_apply(ctx, p["wk"], x, name="wk").reshape(b, s, kv, hd)
    v = linear_apply(ctx, p["wv"], x, name="wv").reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm_apply(p["qn"], q)
        k = rmsnorm_apply(p["kn"], k)
    if cfg.use_rope:
        if cfg.mrope:
            mp = ropelib.text_mrope_positions(positions)
            sec = (hd // 8, hd * 3 // 16, hd * 3 // 16)
            q = ropelib.apply_mrope(q, mp, cfg.rope_theta, sec)
            k = ropelib.apply_mrope(k, mp, cfg.rope_theta, sec)
        else:
            q = ropelib.apply_rope(q, positions, cfg.rope_theta,
                                   tables=rope_tables)
            k = ropelib.apply_rope(k, positions, cfg.rope_theta,
                                   tables=rope_tables)
    return q, k, v


def attn_apply(
    ctx: RunCtx,
    cfg: AttnStatic,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    cache: dict | None = None,
    pos: jax.Array | None = None,
    rope_tables=None,
):
    """Pre-norm attention sublayer with residual.

    Train/prefill: ``cache=None``, positions [B, S].
    Decode: ``cache={'k','v'}`` ring/linear buffers, ``pos`` scalar int32
    (current length; the new token is written at slot pos % W).
    Chunked prefill: ``cache`` + S > 1 + ``pos`` (chunk start offset) —
    ``positions`` carry absolute prompt offsets and fresh rows land at
    their absolute page slots (serving engine ``chunk_len`` path).
    ``rope_tables`` shares precomputed RoPE cos/sin across layers.
    Returns (y, new_cache).
    """
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    g = h // kv
    mx_dig = ctx.hybrid_digital_sdpa
    xn = norm_apply(cfg.norm, p["ln"], x)
    q, k, v = _qkv(ctx, cfg, p, xn, positions, rope_tables=rope_tables)
    if s > 1:
        # zero K/V at KV_PAD positions (fixed-shape padded serving
        # prefill). The mask already excludes them from scores, but the
        # digital-MXFP4 SDPA quantizes V in shared-exponent blocks along
        # the key axis — garbage pad rows would perturb real rows' codes,
        # and they would land in the decode cache.
        kvm = (positions > _KV_PAD_MIN)[:, :, None, None]
        k = jnp.where(kvm, k, jnp.zeros((), k.dtype))
        v = jnp.where(kvm, v, jnp.zeros((), v.dtype))
    q = ctx.act(q.reshape(b, s, kv, g, hd), "batch", "seq", "kv_heads", "heads_g", "head_dim")

    fused = cache is not None and "kv" in cache
    if cache is not None and s > 1 and pos is not None:
        # chunked prefill into an existing page: the engine feeds one
        # fixed-shape [B, chunk] window of a longer prompt per step, with
        # ``positions`` carrying absolute prompt offsets (KV_PAD on pad
        # columns) and ``pos`` the chunk's start offset. Fresh K/V rows
        # are written at their absolute slots — pad columns map out of
        # bounds and are dropped — and this chunk's queries attend over
        # the *whole* page: rows beyond the written prefix are zero
        # (pages are reset/cloned at admission, see kvcache.clone_prefix)
        # and carry k_pos > q_pos, so the causal mask excludes them.
        w = (cache["kv"] if fused else cache["k"]).shape[1]
        rows = jnp.arange(b)[:, None]
        slot = jnp.where(positions > _KV_PAD_MIN, positions, w)
        if fused:
            dt = cache["kv"].dtype
            kvnew = paged_layout.fuse_pages(k.astype(dt), v.astype(dt))
            ckv = cache["kv"].at[rows, slot].set(kvnew, mode="drop")
            new_cache = {"kv": ckv}
            kpage, vpage = paged_layout.split_pages(ckv, hd)
            if "kv_codes" in cache:
                new_cache.update(paged_layout.quant_page_full(kpage, vpage))
        else:
            ck = cache["k"].at[rows, slot].set(
                k.astype(cache["k"].dtype), mode="drop")
            cv = cache["v"].at[rows, slot].set(
                v.astype(cache["v"].dtype), mode="drop")
            new_cache = {"k": ck, "v": cv}
            kpage, vpage = ck, cv
            if "k_codes" in cache:
                # the quantized-resident mirrors are recomputed for the
                # FULL page every chunk (O(page) per chunk, documented):
                # blockwise V exponents near the chunk boundary depend on
                # rows outside the chunk, and mirrors == full requant of
                # the raw page is the invariant that makes pages
                # content-addressable (serving/prefix.py). Nothing reads
                # the pre-chunk mirror state here, so a cloned page only
                # needs its raw rows copied.
                new_cache.update(_quant_cache_full(ck, cv))
        k_pos = jnp.broadcast_to(jnp.arange(w)[None], (b, w))
        o = _dense_attn(q, kpage, vpage, positions, k_pos, cfg,
                        mx_digital=mx_dig)
    elif cache is not None and s > 1:
        # prefill-into-cache: attention over the fresh K/V, cache filled
        # with the last W positions (ring convention: slot = pos % W)
        w = (cache["kv"] if fused else cache["k"]).shape[1]
        if s < w:
            kw = jnp.pad(k, ((0, 0), (0, w - s), (0, 0), (0, 0)))
            vw = jnp.pad(v, ((0, 0), (0, w - s), (0, 0), (0, 0)))
        else:
            kw, vw = k[:, -w:], v[:, -w:]
            roll = s % w
            if roll:
                kw = jnp.roll(kw, roll, axis=1)
                vw = jnp.roll(vw, roll, axis=1)
        if fused:
            dt = cache["kv"].dtype
            kcast, vcast = kw.astype(dt), vw.astype(dt)
            new_cache = {"kv": paged_layout.fuse_pages(kcast, vcast)}
            if "kv_codes" in cache:
                # same quantize calls as the legacy mirror fill, repacked
                new_cache.update(paged_layout.quant_page_full(kcast, vcast))
        else:
            new_cache = {"k": kw.astype(cache["k"].dtype),
                         "v": vw.astype(cache["v"].dtype)}
            if "k_codes" in cache:
                # quantized-resident pool: fill the code mirrors from the
                # cache-dtype-cast pages (what requant-per-step would see)
                new_cache.update(
                    _quant_cache_full(new_cache["k"], new_cache["v"])
                )
        k = ctx.act(k, "batch", "kv_seq", "kv_heads", "head_dim")
        v = ctx.act(v, "batch", "kv_seq", "kv_heads", "head_dim")
        if s <= ctx.dense_attn_max:
            o = _dense_attn(q, k, v, positions, positions, cfg,
                            mx_digital=mx_dig)
        else:
            o = _flash_attn(q, k, v, positions, positions, cfg, ctx,
                            mx_digital=mx_dig)
    elif fused:
        # fused paged decode: one ragged flash-decode call over the
        # head-interleaved page pool. ``ctx.paged_rows`` maps lanes to
        # pool rows (continuous-batching serving decodes in place — no
        # per-step gather/scatter of full pages); without it lane i reads
        # row i, which is exactly the legacy per-lane cache convention.
        w = cache["kv"].shape[1]
        pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
        rows = (jnp.arange(b) if ctx.paged_rows is None
                else ctx.paged_rows)
        slot = pos_b % w
        kvnew = paged_layout.fuse_pages(k[:, 0], v[:, 0])
        ckv = cache["kv"].at[rows, slot].set(
            kvnew.astype(cache["kv"].dtype)
        )
        new_cache = {"kv": ckv}
        resident = "kv_codes" in cache
        if resident:
            new_cache.update(
                paged_layout.quant_page_step(cache, ckv, rows, slot)
            )
        # min(pos+1, W) reproduces the legacy ring-write validity mask
        # ((idx <= pos) | (pos >= w)): a contiguous valid prefix, all W
        # slots once the ring has wrapped
        lengths = jnp.minimum(pos_b + 1, w)
        qd, kv_pages, quant = q, ckv, None
        if mx_dig:
            if not resident:
                raise ValueError(
                    "fused paged decode under a digital-SDPA backend "
                    "needs the quantized-resident mirrors — init the "
                    "cache with mx_digital=True"
                )
            qd = _mx_fq(q).astype(jnp.bfloat16)
            kv_pages = None
            quant = {name: new_cache[name]
                     for name in ("kv_codes", "k_exps", "v_exps")}
        o = paged_ops.ragged_paged_decode(
            qd[:, 0], rows, lengths, kv=kv_pages, quant=quant,
            scale=cfg.scale, use_pallas=ctx.use_pallas,
            interpret=ctx.interpret,
            buffers=ctx.paged_buffers or None,
        )[:, None]
    elif cache is not None:
        # pos may be a scalar (all lanes at the same position) or a [B]
        # vector (continuous-batching serving: each lane decodes at its own
        # position); both write slot pos % w per lane and mask per lane.
        w = cache["k"].shape[1]
        pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
        slot = pos_b % w
        lanes = jnp.arange(b)
        ck = cache["k"].at[lanes, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[lanes, slot].set(v[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": ck, "v": cv}
        resident = "k_codes" in cache
        if resident:
            new_cache.update(_quant_cache_step(cache, ck, cv, lanes, slot))
        idx = jnp.arange(w)
        valid = (idx[None, :] <= pos_b[:, None]) | (pos_b[:, None] >= w)
        qd, kd = q, ck
        if mx_dig:  # digital MXFP4 systolic SDPA for the hybrid backend
            qd = _mx_fq(q)
            if resident:  # O(1) per-step quantization: read K codes back
                kd = mxlib.dequantize(
                    mxlib.MX(new_cache["k_codes"], new_cache["k_exps"]),
                    out_len=hd,
                )
            else:  # requant-per-step reference: O(cache_len) quantize
                kd = _mx_fq(ck)
            # exact bf16 carriage of the quantized operands (see
            # _dense_attn)
            qd, kd = qd.astype(jnp.bfloat16), kd.astype(jnp.bfloat16)
        sc = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qd, kd, preferred_element_type=jnp.float32
        ) * cfg.scale
        if mx_dig:
            sc = _mx_score_round(sc)
        sc = jnp.where(valid[:, None, None, None, :], sc, -jnp.inf)
        if mx_dig:
            pr, den = _mx_p(jax.nn.softmax(sc, axis=-1))
            if resident:
                vd = jnp.moveaxis(
                    mxlib.dequantize(
                        mxlib.MX(new_cache["v_codes"], new_cache["v_exps"]),
                        out_len=w,
                    ),
                    -1, 1,
                )
            else:
                vd = mxlib.fake_quant_axis(cv, 1)  # bf16-native chain
            o = jnp.einsum(
                "bhgqk,bkhd->bqhgd", pr.astype(jnp.bfloat16),
                vd.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
            )
            o = (o / jnp.moveaxis(den, -2, 1)).astype(cv.dtype)
        else:
            pr = jax.nn.softmax(sc, axis=-1).astype(cv.dtype)
            o = jnp.einsum("bhgqk,bkhd->bqhgd", pr, cv)
    else:
        new_cache = None
        k = ctx.act(k, "batch", "kv_seq", "kv_heads", "head_dim")
        v = ctx.act(v, "batch", "kv_seq", "kv_heads", "head_dim")
        if s <= ctx.dense_attn_max:
            o = _dense_attn(q, k, v, positions, positions, cfg,
                            mx_digital=mx_dig)
        else:
            o = _flash_attn(q, k, v, positions, positions, cfg, ctx,
                            mx_digital=mx_dig)

    o = o.reshape(b, s, h * hd)
    y = linear_apply(ctx, p["wo"], o, name="wo")
    y = ctx.act(y, "batch", "seq", "embed")
    return x + y.astype(x.dtype), new_cache


def attn_cache_init(cfg: AttnStatic, batch: int, max_len: int,
                    dtype=jnp.bfloat16, mx_digital: bool = False,
                    fused: bool = False):
    """K/V decode cache; with ``mx_digital`` it additionally carries the
    quantized-resident code mirrors for the digital-SDPA decode path.
    ``fused`` selects the head-interleaved paged layout served by the
    ragged paged flash-decode kernel (see ``kernels.paged_attention``)."""
    w = min(cfg.window, max_len) if cfg.window > 0 else max_len
    if fused:
        cache = paged_layout.fused_cache_init(
            batch, w, cfg.n_kv, cfg.head_dim, dtype
        )
        if mx_digital:
            cache.update(
                paged_layout.fused_quant_init(batch, w, cfg.n_kv,
                                              cfg.head_dim)
            )
        return cache
    shape = (batch, w, cfg.n_kv, cfg.head_dim)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if mx_digital:
        cache.update(quant_cache_init(batch, w, cfg.n_kv, cfg.head_dim))
    return cache


ATTN_CACHE_SPECS = {
    "k": ("batch", "cache_seq", None, None),
    "v": ("batch", "cache_seq", None, None),
}

ATTN_QUANT_CACHE_SPECS = {
    **ATTN_CACHE_SPECS,
    "k_codes": ("batch", "cache_seq", None, None),
    "k_exps": ("batch", "cache_seq", None, None),
    "v_codes": ("batch", None, None, "cache_seq"),
    "v_exps": ("batch", None, None, None),
}

FUSED_ATTN_CACHE_SPECS = {
    "kv": ("batch", "cache_seq", None, None),
}

FUSED_ATTN_QUANT_CACHE_SPECS = {
    **FUSED_ATTN_CACHE_SPECS,
    "kv_codes": ("batch", "cache_seq", None, None),
    "k_exps": ("batch", "cache_seq", None, None),
    "v_exps": ("batch", None, None, None),  # slot-block-major key axis
}


def attn_cache_specs(mx_digital: bool = False,
                     fused: bool = False) -> dict:
    if fused:
        return (FUSED_ATTN_QUANT_CACHE_SPECS if mx_digital
                else FUSED_ATTN_CACHE_SPECS)
    return ATTN_QUANT_CACHE_SPECS if mx_digital else ATTN_CACHE_SPECS
