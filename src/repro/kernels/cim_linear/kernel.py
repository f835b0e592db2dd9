"""Analog CTT-CIM forward Pallas kernel: per-32-block integer partial
sums, exponent alignment to the calibrated target E_N under a CM-bit
mirror window (underflow-to-zero below, shift-clamp above), Row-Hist
2-pass merge, and n-bit ADC quantization of each (pass, column) sum.

The activation quantize is *fused*: raw activations stream in [bm, bk]
VMEM tiles and are block-quantized to the INT5 signed code domain
(codes = 2*fp4 in [-12, 12]) in-register — exponent extraction and E2M1
rounding by IEEE-754 exponent-field bit manipulation, bitwise the
``core/mx.quantize`` rule — so activation codes/exps never round-trip
HBM. Weights are resident codes + per-block exponents, exactly the
paper's eq. (1)-(3) datapath. The block dot products are exact in f32
(|S| <= 32*144), so the MXU carries the "analog" accumulation.

Grid (nm, nn, nk), K innermost with f32 VMEM pass-1/pass-2 accumulators
(the CTT array is weight-stationary along K; tiling K bounds VMEM at
hidden x hidden macro scale, paper §4.3). The k-grid walks blocks in
ascending order, so accumulation order matches the jnp scan reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret


def _exp2i(e: jax.Array) -> jax.Array:
    """Exact 2^e via IEEE exponent-field construction (e in [-126, 127])."""
    return jax.lax.bitcast_convert_type(
        (jnp.clip(e, -126, 127) + 127).astype(jnp.int32) << 23, jnp.float32
    )


def _exp2i_wide(e: jax.Array) -> jax.Array:
    """Two-factor 2^e covering the full block-exponent-sum range
    [-254, 252]: out-of-range negatives underflow to 0 / subnormal powers
    (still exact), positives overflow to inf — both sides behave correctly
    under the linear-domain window compare."""
    h1 = jnp.clip(e // 2, -126, 127)
    return _exp2i(h1) * _exp2i(e - h1)


def _floor_ilog2(x: jax.Array) -> jax.Array:
    """Exact floor(log2(x)) for finite x >= 0 from the exponent field;
    zero/subnormal read as <= -127 (callers clamp)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return ((bits >> 23) & 0xFF) - 127


def _quantize_block(xb: jax.Array):
    """One 32-block MXFP4 quantize of [bm, 32] raw f32 activations ->
    (codes f32 [bm, 32] in [-12, 12], block exponent int32 [bm, 1]).
    Bitwise ``core/mx.quantize``: shared exp = floor(log2(amax)) - 2
    clamped to E8M0 (zero blocks land on -127 via the clamp), elements
    rounded ties-to-even on the scaled E2M1 grid and clamped at 6."""
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    ex = jnp.clip(_floor_ilog2(amax) - 2, -127, 127)
    y = xb * _exp2i(-ex)
    ay = jnp.abs(y)
    e = jnp.clip(_floor_ilog2(ay), 0, 2)
    q = jnp.rint(ay * _exp2i(1 - e)) * _exp2i(e - 1)
    q = jnp.minimum(q, 6.0)
    codes = jnp.sign(y) * (2.0 * q)
    return codes, ex


def _kernel(
    x_ref, wc_ref, we_ref, cal_ref, o_ref, a1_ref, a2_ref,
    *, nk: int, nb_tile: int, cm: int, adc_bits: int | None, two_pass: bool,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        a1_ref[...] = jnp.zeros_like(a1_ref)
        a2_ref[...] = jnp.zeros_like(a2_ref)

    # E_N broadcast to a vector: Mosaic bitcasts vectors only, and the
    # store's 2^E_N scale is built by exponent-field bitcast
    e_n = jnp.full((1, 1), cal_ref[0, 0]).astype(jnp.int32)
    fs = cal_ref[0, 1]
    xt = x_ref[...].astype(jnp.float32)  # [bm, bk] raw activations

    lo = 2.0 ** -cm
    lo2 = 2.0 ** -(2 * cm)
    for b in range(nb_tile):  # static unroll over the tile's 32-blocks
        cx, ex = _quantize_block(xt[:, b * 32:(b + 1) * 32])
        wb = wc_ref[pl.ds(b * 32, 32), :].astype(jnp.float32)
        s = jax.lax.dot(cx, wb, preferred_element_type=jnp.float32)
        ew = we_ref[pl.ds(b, 1), :].astype(jnp.int32)  # [1, bn]
        # linear-domain alignment (same identity as core/cim._scan_blocks):
        # uv = 2^(E_X - E_N) * 2^(E_W) is an exact power-of-two product,
        # and 2^clip(sh,-cm,0)*[sh >= -cm] == where(uv < 2^-cm, 0, min(uv, 1))
        uv = _exp2i_wide(ex - e_n) * _exp2i_wide(ew)  # [bm, bn] == 2^sh
        under1 = uv < lo
        a1_ref[...] += s * jnp.where(under1, 0.0, jnp.minimum(uv, 1.0))
        if two_pass:
            # pass-2 target E_N2 = E_N - CM: window sh in [-2cm, -cm)
            a2_ref[...] += s * jnp.where(
                under1 & (uv >= lo2), uv * (2.0 ** cm), 0.0
            )

    @pl.when(ki == nk - 1)
    def _store():
        def adc(c):
            if adc_bits is None:
                return c
            half = 2.0 ** (adc_bits - 1)
            delta = fs / half
            return jnp.clip(jnp.round(c / delta), -half, half - 1.0) * delta

        y = adc(a1_ref[...]) * _exp2i(e_n) * 0.25
        if two_pass:
            y += adc(a2_ref[...]) * _exp2i(e_n - cm) * 0.25
        o_ref[...] = y


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "cm", "adc_bits", "two_pass",
                     "interpret"),
)
def cim_linear_kernel(
    x: jax.Array,  # f32/bf16 [M, K] raw activations (quantize is fused)
    w_codes: jax.Array,  # int8 [K, N]
    w_exps: jax.Array,  # int8 [K//32, N]
    calib: jax.Array,  # f32 [1, 2] = (E_N, adc_fs)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    cm: int = 3,
    adc_bits: int | None = 10,
    two_pass: bool = True,
    interpret: bool | None = None,  # None -> platform default
):
    if interpret is None:
        interpret = default_interpret()
    m, k = x.shape
    n = w_codes.shape[1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % 32 == 0
    nm, nn, nk = m // bm, n // bn, k // bk
    return pl.pallas_call(
        functools.partial(
            _kernel, nk=nk, nb_tile=bk // 32, cm=cm, adc_bits=adc_bits,
            two_pass=two_pass,
        ),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((bk // 32, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((1, 2), lambda i, j, ki: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        interpret=interpret,
    )(x, w_codes, w_exps, calib)
