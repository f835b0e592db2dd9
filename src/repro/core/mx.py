"""MXFP4 microscaling numerics (OCP MX spec, paper §2.3 + Appendix A).

A length-``k`` (k = 32) block is stored as 32 E2M1 ("FP4") private elements
plus one shared E8M0 power-of-two scale:  V_i = P_i * 2^E.

Internally we carry FP4 elements as *integer codes* equal to ``2 * P_i``,
i.e. values in ``{0, ±1, ±2, ±3, ±4, ±6, ±8, ±12}`` — exactly the paper's
lossless INT5 affine encoding of FP4 (activations use the signed [-12, 12]
code directly; weights add the bias ``w_b = 12`` to land in [0, 24]).

All functions are jit-friendly pure jnp.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 32  # MX block size along the contraction axis
EMAX_ELEM = 2  # largest E2M1 exponent (6 = 1.5 * 2^2)
FP4_MAX = 6.0
CODE_MAX = 12  # 2 * FP4_MAX
WEIGHT_BIAS = 12  # INT5 affine bias for unsigned weight encoding
E8M0_MIN, E8M0_MAX = -127, 127

# |code| -> E2M1 nibble (sign bit added separately):  value = code / 2
#   e=0: {0, 0.5}; e=1: {1, 1.5}; e=2: {2, 3}; e=3: {4, 6}
_ABS_CODE_TO_NIBBLE = jnp.array(
    [0, 1, 2, 3, 4, 0, 5, 0, 6, 0, 0, 0, 7], dtype=jnp.uint8
)  # index = |code|, valid only at {0,1,2,3,4,6,8,12}
_NIBBLE_TO_CODE = jnp.array([0, 1, 2, 3, 4, 6, 8, 12], dtype=jnp.int8)


class MX(NamedTuple):
    """A block-quantized tensor. ``codes`` has the (zero-padded) original
    shape; ``exps`` replaces the quantized axis (last) by n_blocks.

    value[..., b*32 + i] = codes[..., b*32 + i] / 2 * 2^exps[..., b]
    """

    codes: jax.Array  # int8 in [-12, 12], shape [..., K_pad]
    exps: jax.Array  # int8 unbiased E8M0 exponent, shape [..., K_pad // 32]


def exp2i(e: jax.Array) -> jax.Array:
    """Exact 2^e (float32) for integer-valued ``e`` via exponent-field
    bit construction. ``jnp.exp2`` is only ~1-ulp accurate on CPU (it
    lowers to ``exp(x*ln2)``), which breaks bit-exactness; this is exact
    for e in [-252, 252] (split into two factors to cover beyond the
    single-factor [-126, 127] range)."""
    e = jnp.asarray(e, jnp.int32)
    h1 = jnp.clip(e >> 1, -126, 127)  # floor(e / 2); Mosaic has no `//`
    h2 = jnp.clip(e - h1, -126, 127)

    def f(h):
        return jax.lax.bitcast_convert_type(
            ((h + 127) << 23).astype(jnp.int32), jnp.float32
        )

    return f(h1) * f(h2)


def floor_ilog2(x: jax.Array) -> jax.Array:
    """Exact ``floor(log2(x))`` (int32) for finite ``x >= 0`` read straight
    from the IEEE-754 exponent field — no ``log2``/``floor`` transcendentals.

    ``jnp.log2`` is not correctly rounded: for ``x`` one ulp *below* a
    power of two it rounds up to the integer, so ``floor(log2(x))`` built
    from it overshoots by one (measured on CPU). This version is the true
    floor everywhere normal. Zero and subnormal inputs read as exponents
    ``<= -127``; every caller here clamps at the E8M0 floor (-127) or at
    the E2M1 element floor (0), so the exact subnormal exponent never
    matters.
    """
    bits = jax.lax.bitcast_convert_type(
        jnp.asarray(x, jnp.float32), jnp.int32
    )
    return ((bits >> 23) & 0xFF) - 127


def _pad_last(x: jax.Array, multiple: int = BLOCK) -> jax.Array:
    k = x.shape[-1]
    rem = (-k) % multiple
    if rem:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, rem)]
        x = jnp.pad(x, pad)
    return x


def _quant_scaled(xb: jax.Array):
    """Shared bit-level core of :func:`quantize` / :func:`fake_quant`.

    ``xb``: f32 blocks [..., nb, 32]. Returns ``(code_mag, ebf)`` where
    ``code_mag`` f32 [..., nb, 32] is ``2 * |fp4|`` in {0..12} and ``ebf``
    int32 [..., nb, 1] is the *biased* shared exponent field, clipped to
    [2, 254] so ``e_shared = ebf - 129`` covers exactly the E8M0 range
    [-127, 125] (zero / subnormal amax lands on the -127 floor, matching
    the OCP zero-block rule). Everything runs on IEEE-754 fields — one
    reduce plus a short fuseable elementwise chain, no transcendentals.
    """
    ax = jnp.abs(xb)
    amax = jnp.max(ax, axis=-1, keepdims=True)
    ebf = jnp.clip(
        (jax.lax.bitcast_convert_type(amax, jnp.int32) >> 23) & 0xFF, 2, 254
    )
    # y = |x| * 2^(129 - ebf) = |x| / 2^e_shared, exact power-of-two mul
    y = ax * jax.lax.bitcast_convert_type((256 - ebf) << 23, jnp.float32)
    # E2M1 round ties-to-even on the local grid (binade from the field)
    e = jnp.clip(
        (jax.lax.bitcast_convert_type(y, jnp.int32) >> 23) - 127, 0, EMAX_ELEM
    )
    q = jnp.rint(y * jax.lax.bitcast_convert_type((128 - e) << 23, jnp.float32))
    q = q * jax.lax.bitcast_convert_type((126 + e) << 23, jnp.float32)
    q = jnp.minimum(q, FP4_MAX)
    return 2.0 * q, ebf


def quantize_e2m1(y: jax.Array) -> jax.Array:
    """Round ``y`` to the E2M1 grid (round-to-nearest-even), returning
    integer codes ``2 * fp4`` as int8. Input must already be scaled.

    Transcendental-free: the local grid binade comes from the IEEE
    exponent field (:func:`floor_ilog2`) and the grid divide is an exact
    power-of-two multiply. Zero maps to exponent <= -127, clamped to the
    e=0 (step 0.5) grid, where ``rint(0) == 0``.
    """
    ay = jnp.abs(y)
    # piecewise grid step: 0.5 for |y|<2, 1 for [2,4), 2 for [4,6]
    e = jnp.clip(floor_ilog2(ay), 0, EMAX_ELEM)
    # ties-to-even on the local grid; ay/step == ay * 2^(1-e) exactly
    q = jnp.rint(ay * exp2i(1 - e)) * exp2i(e - 1)
    q = jnp.minimum(q, FP4_MAX)
    code = jnp.sign(y) * (2.0 * q)
    return code.astype(jnp.int8)


def quantize(x: jax.Array, axis: int = -1) -> MX:
    """Block-quantize ``x`` to MXFP4 along ``axis`` (padded to 32).

    This *is* the quantize-to-codes entry point: the returned :class:`MX`
    lives in the lossless INT5 code domain, so consumers that want codes
    (the CIM datapath, the quantized-resident KV cache, packed serving
    weights) take it directly with no dequantize round-trip;
    :func:`fake_quant` composes it with :func:`dequantize` for value-domain
    consumers.

    The shared exponent is extracted from the IEEE-754 exponent field of
    the block amax (exact ``floor(log2)``) — no transcendentals. Note
    ``jnp.log2`` is *not* correctly rounded at inputs one ulp below a
    power of two (it rounds up, skipping the OCP clamp-at-6 there); this
    implementation is the exact OCP MX rule everywhere. Inputs on the
    bf16 grid — all model activations/weights here — cannot land in that
    one-ulp window, so the two rules are bitwise identical on model data.
    """
    x = jnp.moveaxis(x, axis, -1) if axis not in (-1, x.ndim - 1) else x
    x = _pad_last(x.astype(jnp.float32))
    shp = x.shape
    xb = x.reshape(shp[:-1] + (shp[-1] // BLOCK, BLOCK))
    code_mag, ebf = _quant_scaled(xb)
    codes = jnp.where(xb < 0, -code_mag, code_mag).astype(jnp.int8)
    return MX(codes.reshape(shp), (ebf[..., 0] - 129).astype(jnp.int8))


def quantize_axis(x: jax.Array, axis: int) -> MX:
    """Code-domain :func:`quantize` along an arbitrary axis: the quantized
    axis is *moved to the end* of both ``codes`` and ``exps`` (callers that
    keep resident codes want the block axis last — e.g. the KV cache's
    per-key-block V codes)."""
    if axis in (-1, x.ndim - 1):
        return quantize(x)
    return quantize(jnp.moveaxis(x, axis, -1))


def dequantize(mx: MX, out_len: int | None = None, dtype=jnp.float32) -> jax.Array:
    shp = mx.codes.shape
    cb = mx.codes.reshape(shp[:-1] + (shp[-1] // BLOCK, BLOCK))
    v = cb.astype(jnp.float32) * 0.5 * exp2i(mx.exps)[..., None]
    v = v.reshape(shp)
    if out_len is not None and out_len != shp[-1]:
        v = v[..., :out_len]
    return v.astype(dtype)


def encode_weight_unsigned(mx: MX) -> jax.Array:
    """INT5 affine map of weight codes into [0, 24] (uint8)."""
    return (mx.codes.astype(jnp.int16) + WEIGHT_BIAS).astype(jnp.uint8)


def decode_weight_unsigned(u: jax.Array) -> jax.Array:
    return (u.astype(jnp.int16) - WEIGHT_BIAS).astype(jnp.int8)


# ---------------------------------------------------------------- packing

def pack_codes(codes: jax.Array) -> jax.Array:
    """Pack int8 codes (2*fp4) into E2M1 nibbles, two per uint8.

    Nibble layout: [sign(1) | exp(2) | man(1)]; even element in low nibble.
    Last axis must be even (blocks of 32 always are).
    """
    sign = (codes < 0).astype(jnp.uint8)
    mag = jnp.abs(codes.astype(jnp.int32))
    nib = _ABS_CODE_TO_NIBBLE[mag] | (sign << 3)
    lo, hi = nib[..., 0::2], nib[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


def nibble_code2x(nib: jax.Array) -> jax.Array:
    """int32 E2M1 nibbles -> int32 *code* values (``2 * fp4``), exact:
    ``(-1)^s * (e == 0 ? m : (2 + m) << (e - 1))``. Shifts and selects
    only, so Pallas kernels decode packed weights with it in-tile."""
    m = nib & 1
    e = (nib >> 1) & 3
    c = jnp.where(e == 0, m, (2 + m) << jnp.maximum(e - 1, 0))
    return jnp.where(((nib >> 3) & 1) == 1, -c, c)


def unpack_codes(packed: jax.Array) -> jax.Array:
    lo = packed & 0x0F
    hi = (packed >> 4) & 0x0F
    nib = jnp.stack([lo, hi], axis=-1).reshape(packed.shape[:-1] + (-1,))
    mag = _NIBBLE_TO_CODE[(nib & 0x7).astype(jnp.int32)]
    sign = jnp.where((nib >> 3) & 1, -1, 1).astype(jnp.int8)
    return (sign * mag).astype(jnp.int8)


def _build_pair_table() -> np.ndarray:
    """256-entry byte -> uint32 table: low/high u16 halves hold the bf16
    bit patterns of the two E2M1 *code* values (2 * fp4 in [-12, 12]) a
    packed byte carries (even element in the low nibble). One gather + one
    bitcast decodes a whole byte — the per-nibble shift/select chain of
    :func:`unpack_codes` was the dominant cost of jnp dequant on CPU."""
    byte = np.arange(256)

    def val(nib):
        m = nib & 1
        e = (nib >> 1) & 3
        c = np.where(e == 0, m, (2 + m) << np.maximum(e - 1, 0))
        return np.where((nib >> 3) & 1, -c, c).astype(np.float32)

    def bf16_bits(v):  # round-to-nearest is exact for these integers
        return (v.astype(">f4").view(">u4") >> 16).astype(np.uint32)

    return bf16_bits(val(byte & 15)) | (bf16_bits(val(byte >> 4)) << 16)


PAIR_TABLE = _build_pair_table()


def unpack_pairs_bf16(packed: jax.Array) -> jax.Array:
    """Packed uint8 nibble pairs [..., K//2] -> bf16 *code* values
    (``2 * fp4``) [..., K] through :data:`PAIR_TABLE`: one gather + one
    bitcast per byte, no shift/select chain. Element ``2i`` comes from the
    low nibble of byte ``i`` (the :func:`pack_codes` layout)."""
    pair = jnp.asarray(PAIR_TABLE)[packed.astype(jnp.int32)]  # [..., K//2]
    u16 = jax.lax.bitcast_convert_type(pair, jnp.uint16)  # [..., K//2, 2] LE
    cb = jax.lax.bitcast_convert_type(u16, jnp.bfloat16)
    return cb.reshape(packed.shape[:-1] + (-1,))


def exps_to_biased(exps: jax.Array) -> jax.Array:
    """Unbiased int8 exponent -> biased uint8 (E8M0 storage)."""
    return (exps.astype(jnp.int16) + 127).astype(jnp.uint8)


def exps_from_biased(b: jax.Array) -> jax.Array:
    return (b.astype(jnp.int16) - 127).astype(jnp.int8)


# ------------------------------------------------------------- fake quant

def _bf16_pow2(field: jax.Array) -> jax.Array:
    """bf16 with exponent *field* ``field`` (int32 in [0, 255]) — bf16
    shares IEEE-754's 8-bit exponent layout at bit 7."""
    return jax.lax.bitcast_convert_type(
        (field << 7).astype(jnp.uint16), jnp.bfloat16
    )


@jax.custom_vjp
def fake_quant(x: jax.Array) -> jax.Array:
    """Quantize-dequantize along the last axis with a straight-through
    estimator (QAT-style). Shape is preserved (pad/unpad internally).
    Bitwise ``dequantize(quantize(x))`` without the int8 code round-trip;
    bf16 inputs run the chain natively in bf16 (see
    :func:`_fake_quant_impl`)."""
    return _fake_quant_impl(x, x.ndim - 1)


def _fq_fwd(x):
    return fake_quant(x), None


def _fq_bwd(_, g):
    return (g,)


fake_quant.defvjp(_fq_fwd, _fq_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def fake_quant_axis(x: jax.Array, axis: int) -> jax.Array:
    """:func:`fake_quant` along an arbitrary axis (STE gradient)."""
    return _fake_quant_impl(x, axis)


def _fake_quant_impl(x: jax.Array, axis: int) -> jax.Array:
    """The one MXFP4 quantize-dequantize chain, computed *in layout*: the
    quantized axis reshapes in place to (nb, 32) and everything broadcasts
    over it — no moveaxis transposes, bitwise the moved-axis composition
    (identical elements, blocks and ops; reductions are order-free).

    bf16 inputs run natively in bf16: every E2M1 grid value, tie point and
    power-of-two scale is exactly bf16-representable (bf16 shares the
    8-bit IEEE exponent layout), so the decisions — and the values, both
    paths flushing the sub-2^-126 scale window to zero on CPU — are
    bitwise ``chain(x.astype(f32)).astype(bf16)`` without the f32
    round-trip that doubled the SDPA operand traffic."""
    a = axis % x.ndim
    k = x.shape[a]
    rem = (-k) % BLOCK
    bf16 = x.dtype == jnp.bfloat16  # bf16-native (see _fake_quant_bf16)
    xf = x if bf16 else x.astype(jnp.float32)
    if rem:
        pad = [(0, 0)] * x.ndim
        pad[a] = (0, rem)
        xf = jnp.pad(xf, pad)
    shp = xf.shape
    xb = xf.reshape(shp[:a] + ((k + rem) // BLOCK, BLOCK) + shp[a + 1:])
    amax = jnp.max(jnp.abs(xb), axis=a + 1, keepdims=True)
    v = fake_quant_at(xb, amax).reshape(shp)
    if rem:
        v = jax.lax.slice_in_dim(v, 0, k, axis=a)
    return v.astype(x.dtype)


def fake_quant_at(x: jax.Array, amax: jax.Array) -> jax.Array:
    """The quantize-dequantize chain of :func:`fake_quant` for f32 or bf16
    ``x``, given each element's block amax (broadcastable to ``x``).
    Callers that cannot reshape into 32-blocks (a Pallas kernel) find the
    amax their own way."""
    bf16 = x.dtype == jnp.bfloat16

    def field(t):  # biased IEEE exponent field (bf16 and f32 share it)
        if bf16:
            b = jax.lax.bitcast_convert_type(t, jnp.uint16).astype(jnp.int32)
            return (b >> 7) & 0xFF
        return (jax.lax.bitcast_convert_type(t, jnp.int32) >> 23) & 0xFF

    def pow2(f):  # value with exponent field f (int32)
        if bf16:
            return _bf16_pow2(f)
        return jax.lax.bitcast_convert_type(f << 23, jnp.float32)

    ebf = jnp.clip(field(amax), 2, 254)
    y = jnp.abs(x) * pow2(256 - ebf)
    e = jnp.clip(field(y) - 127, 0, EMAX_ELEM)
    q = jnp.rint(y * pow2(128 - e))
    q = q * pow2(126 + e)
    q = jnp.minimum(q, jnp.asarray(FP4_MAX, x.dtype))
    scale = _bf16_pow2(ebf - 2) if bf16 else exp2i(ebf - 129)
    return jnp.where(x < 0, -q, q) * scale  # q * 2^e_shared


def _fqa_fwd(x, axis):
    return fake_quant_axis(x, axis), None


def _fqa_bwd(axis, _, g):
    return (g,)


fake_quant_axis.defvjp(_fqa_fwd, _fqa_bwd)


# ------------------------------------------------- fidelity observability

def bucket_counts(v: jax.Array, buckets: tuple, weights: jax.Array | None = None):
    """Bucket ``v`` on the boundaries ``buckets`` with the exact semantics
    of ``Histogram.observe`` (``bisect_left``: a value equal to a boundary
    lands in that ``le`` bucket; the implicit +Inf bucket catches the
    tail). Returns int32 counts of length ``len(buckets) + 1`` ready for
    ``Histogram.merge_counts``. ``weights`` (0/1) masks elements out."""
    b = jnp.asarray(buckets, jnp.float32)
    idx = jnp.searchsorted(b, v.astype(jnp.float32).ravel(), side="left")
    w = (jnp.ones(idx.shape, jnp.int32) if weights is None
         else weights.ravel().astype(jnp.int32))
    return jnp.zeros((len(buckets) + 1,), jnp.int32).at[idx].add(w)


@functools.partial(jax.jit, static_argnames=("exp_buckets",))
def quant_health(x: jax.Array, exp_buckets: tuple = ()) -> dict:
    """MXFP4 quantizer health over one tensor (last-axis blocks) — the
    fidelity-observability companion to :func:`quantize`; never on the
    hot path, the forward keeps calling :func:`quantize`/:func:`fake_quant`
    untouched.

    Reports, over the unpadded elements (padding is all-zero and zeros
    are neither clipped nor counted as underflow):

    - ``clipped``: values beyond the top of the E2M1 grid,
      ``|x| > 6 * 2^E`` — saturated to max magnitude by the OCP clamp;
    - ``underflow``: nonzero values flushed to code 0 by the shared
      block exponent (the block amax set ``E`` too hot for them);
    - ``total``: element count (static Python int);

    plus the shared-exponent distribution over *live* (nonzero-amax)
    blocks, bucketed on ``exp_buckets`` for ``Histogram.merge_counts``:
    ``exp_counts`` / ``exp_sum`` / ``exp_n`` / ``exp_min`` / ``exp_max``.
    """
    xf = _pad_last(jnp.asarray(x).astype(jnp.float32))
    shp = xf.shape
    xb = xf.reshape(shp[:-1] + (shp[-1] // BLOCK, BLOCK))
    code_mag, ebf = _quant_scaled(xb)
    scale = exp2i(ebf - 129)  # 2^e_shared, exact
    clipped = jnp.sum(jnp.abs(xb) > FP4_MAX * scale)
    underflow = jnp.sum((xb != 0) & (code_mag == 0))

    e = ebf[..., 0] - 129  # [..., nb] shared exponent per block
    live = jnp.any(xb != 0, axis=-1)  # zero-amax blocks sit on the floor
    n_live = jnp.sum(live)
    big = jnp.int32(10**6)
    return {
        "total": int(np.prod(x.shape)),
        "clipped": clipped,
        "underflow": underflow,
        "exp_counts": bucket_counts(e, exp_buckets, weights=live),
        "exp_sum": jnp.sum(jnp.where(live, e, 0)),
        "exp_n": n_live,
        "exp_min": jnp.min(jnp.where(live, e, big)),
        "exp_max": jnp.max(jnp.where(live, e, -big)),
    }


# ------------------------------------------------------------ bf16 helper

def to_bf16(x: jax.Array) -> jax.Array:
    return x.astype(jnp.bfloat16)


def mx_dot_bf16(a: MX, b: MX, bf16_partials: bool = False) -> jax.Array:
    """Digital-path dot product a @ b with MXFP4 operands and BF16-style
    accumulation semantics (paper §4.5).

    a: quantized along last axis, codes [..., K]; b: quantized along FIRST
    axis of a [K, N] weight (codes [K, N], exps [Kb, N] — produced by
    ``quantize(w.T).T``-style helpers below).

    With ``bf16_partials`` the per-32-block partial sums are rounded to
    BF16 before the cross-block accumulation (emulating the systolic
    array's BF16 accumulator at block granularity); otherwise f32
    accumulation with a final bf16 round (fast path).
    """
    va = dequantize(a)  # [..., K]
    vb = dequantize_w(b)  # [K, N]
    K = vb.shape[0]
    if bf16_partials:
        nb = K // BLOCK
        vab = va[..., :K].reshape(va.shape[:-1] + (nb, BLOCK))
        vbb = vb.reshape(nb, BLOCK, -1)
        parts = jnp.einsum("...bk,bkn->...bn", vab, vbb)
        parts = parts.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.sum(parts, axis=-2).astype(jnp.bfloat16)
    return jnp.matmul(va[..., :K], vb).astype(jnp.bfloat16)


class MXW(NamedTuple):
    """Weight matrix [K, N] quantized along K (contraction axis).

    codes: int8 [K_pad, N]; exps: int8 [K_pad//32, N].
    """

    codes: jax.Array
    exps: jax.Array


def quantize_w(w: jax.Array) -> MXW:
    """Quantize a [K, N] weight along K (axis 0)."""
    mx = quantize(w.T)  # blocks along K
    return MXW(jnp.swapaxes(mx.codes, -1, -2), jnp.swapaxes(mx.exps, -1, -2))


def dequantize_w(w: MXW, dtype=jnp.float32) -> jax.Array:
    mx = MX(jnp.swapaxes(w.codes, -1, -2), jnp.swapaxes(w.exps, -1, -2))
    return jnp.swapaxes(dequantize(mx, dtype=dtype), -1, -2)
