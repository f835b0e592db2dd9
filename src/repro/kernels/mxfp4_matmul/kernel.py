"""Fused MXFP4 dequant-matmul Pallas kernel — the TPU analogue of the
CTT-CIM array: weights live in memory as packed 4-bit E2M1 codes + E8M0
scales (4.25 bits/param) and are expanded to f32 only inside the VMEM tile
feeding the MXU. Weights are never materialised at high precision in HBM.

Layout:  x [M, K] bf16;  codes [K//2, N] uint8 (two E2M1 nibbles per byte
along K, even row in the low nibble);  exps [K//32, N] uint8 (biased E8M0).
Grid (nm, nn, nk), K innermost, f32 VMEM accumulator scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import mx as mxlib
from repro.kernels import default_interpret


def _decode_tile(codes_u8: jax.Array, exps_u8: jax.Array) -> jax.Array:
    """[bk//2, bn] packed nibbles + [bk//32, bn] biased exps -> f32 [bk, bn].

    Integer-exact E2M1 decode: code2x = (-1)^s * (e==0 ? m : (2+m) << (e-1))
    equals 2x the FP4 value; the E8M0 scale is built by placing the biased
    exponent directly into the IEEE-754 exponent field (bit-exact, unlike
    jnp.exp2 which lowers to exp(x*ln2)).
    """
    kk2, bn = codes_u8.shape
    c = codes_u8.astype(jnp.int32)  # Mosaic has no uint8 logical shift
    lo = c & 0x0F
    hi = (c >> 4) & 0x0F
    nib = jnp.stack([lo, hi], axis=1).reshape(kk2 * 2, bn)
    code2x = mxlib.nibble_code2x(nib).astype(jnp.float32)
    scale = jax.lax.bitcast_convert_type(
        exps_u8.astype(jnp.int32) << 23, jnp.float32
    )  # [bk//32, bn] == 2^(e-127)
    vals = code2x.reshape(kk2 * 2 // 32, 32, bn) * (0.5 * scale)[:, None, :]
    return vals.reshape(kk2 * 2, bn)


def _kernel(x_ref, c_ref, e_ref, o_ref, acc_ref, *, nk: int, out_dtype):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _decode_tile(c_ref[...], e_ref[...])
    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w,
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"),
)
def mxfp4_matmul_kernel(
    x: jax.Array,
    codes: jax.Array,
    exps: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=jnp.bfloat16,
    interpret: bool | None = None,  # None -> platform default
):
    if interpret is None:
        interpret = default_interpret()
    m, k = x.shape
    n = codes.shape[1]
    assert codes.shape == (k // 2, n) and exps.shape == (k // 32, n)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % 32 == 0
    nm, nn, nk = m // bm, n // bn, k // bk
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, out_dtype=out_dtype),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((bk // 32, bn), lambda i, j, ki: (ki, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, codes, exps)
