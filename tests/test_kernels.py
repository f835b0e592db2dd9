"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cim as cimlib
from repro.core import mx as mxlib
from repro.kernels.cim_linear import ops as cim_ops
from repro.kernels.cim_linear import ref as cim_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.mxfp4_matmul import kernel as mm_kernel
from repro.kernels.mxfp4_matmul import ops as mm_ops
from repro.kernels.mxfp4_matmul import ref as mm_ref


def _packed_weight(key, k, n):
    w = jax.random.normal(key, (k, n), jnp.float32)
    wq = mxlib.quantize_w(w)
    codes = mxlib.pack_codes(wq.codes.T).T
    exps = mxlib.exps_to_biased(wq.exps)
    return w, wq, codes, exps


# The last three are ViT-B/16's tiles: several K tiles accumulating at
# bk=256 and bk=512 (768, 3072), and the 1000-class head as one N tile.
WIDE_TILES = [(16, 768, 256), (8, 3072, 128), (4, 768, 1000)]
# Branches of the tile plan (``mm_ops.plan``): one M block at M = 1, 12,
# 197 and 512 (16, 16, 256 and 512 rows executed); bn of 256 and 512, and
# N whole (1000); K whole in one chunk of 128 code rows (256), in several
# chunks (768, 1024), and K of no multiple of 256 (96, 1152) as one chunk.
PLAN_TILES = [(1, 256, 256), (12, 768, 512), (197, 1152, 1000),
              (512, 1024, 512)]
# Several K tiles: the plan takes K whole wherever VMEM allows, so these
# cases name bk themselves (K tiles of one and of two chunks).
K_TILES = {(40, 1024, 256): 256, (16, 3072, 384): 512}


def _bf16_ulp(v):
    """Spacing of bf16 values at |v| (v != 0): 2^(floor(log2|v|) - 7)."""
    v = np.maximum(np.abs(v), np.finfo(np.float32).tiny)
    return np.exp2(np.floor(np.log2(v)) - 7)


@pytest.mark.parametrize(
    "m,k,n",
    [(8, 64, 16), (128, 128, 128), (33, 96, 48), (256, 512, 64)]
    + WIDE_TILES + PLAN_TILES + list(K_TILES),
)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_mxfp4_matmul_sweep(m, k, n, dtype):
    """bf16 x: bf16 operands whose products are exact in the f32
    accumulator, so only the summation order differs from the reference:
    every output within one bf16 ulp of it. f32 x: f32 operands, the f32
    output within 1e-6 of the f32 reference, where x rounded to bf16
    would miss by far more."""
    kx, kw = jax.random.split(jax.random.PRNGKey(m * 7 + k + n))
    x = jax.random.normal(kx, (m, k), dtype)
    _, _, codes, exps = _packed_weight(kw, k, n)
    out_dtype = jnp.float32 if dtype == jnp.float32 else jnp.bfloat16
    bk = K_TILES.get((m, k, n))
    if bk is None and dtype == jnp.bfloat16:
        out = mm_ops.mxfp4_matmul(x, codes, exps, interpret=True)
    else:
        p = mm_ops.plan(m, k, n, dtype, out_dtype)
        out = mm_kernel.mxfp4_matmul_kernel(
            jnp.pad(x, ((0, p.rows - m), (0, 0))), codes, exps, bm=p.bm,
            bn=p.bn, bk=bk or p.bk, out_dtype=out_dtype, interpret=True,
        )[:m]
    ref = np.asarray(mm_ref.mxfp4_matmul_ref(x, codes, exps, out_dtype),
                     np.float32)
    out = np.asarray(out, np.float32)
    if dtype == jnp.bfloat16:
        gap = np.abs(out - ref)
        assert (gap <= _bf16_ulp(np.maximum(np.abs(out), np.abs(ref)))).all()
    else:
        tol = dict(rtol=1e-6, atol=1e-6 * np.abs(ref).max())
        np.testing.assert_allclose(out, ref, **tol)
        rounded = np.asarray(mm_ref.mxfp4_matmul_ref(
            x.astype(jnp.bfloat16), codes, exps, jnp.float32))
        assert not np.allclose(rounded, ref, **tol)


def test_decode_is_dequant_ref_bit_for_bit():
    """The in-tile decode over every code byte (so all 16 nibbles in both
    halves) and every biased exponent the quantizer emits, 0 (an all-zero
    block) to 252 (2^125, the largest finite block scale): the bits of
    ``dequant_ref`` in f32 and in bf16. Blocks with an exponent of 0 or 1
    hold only values the f32 reference flushes to zero; there the zeros
    agree as values (the reference keeps a negative code's sign)."""
    lo_exp = mxlib.exps_to_biased(mxlib.quantize(jnp.zeros((1, 32))).exps)
    hi_exp = mxlib.exps_to_biased(
        mxlib.quantize(jnp.full((1, 32), np.finfo(np.float32).max)).exps)
    assert (int(lo_exp[0, 0]), int(hi_exp[0, 0])) == (0, 252)
    e = np.arange(253, dtype=np.uint8)
    codes = np.repeat(np.arange(256, dtype=np.uint8)[:, None], e.size, 1)
    exps = np.repeat(e[None, :], 512 // 32, 0)
    want = np.asarray(mm_ref.dequant_ref(jnp.asarray(codes),
                                         jnp.asarray(exps)))
    c = jnp.asarray(codes, jnp.int32)
    ei = jnp.asarray(exps, jnp.int32)
    normal = e >= 2
    for dtype in (jnp.float32, jnp.bfloat16):
        for half, nib in ((0, c), (1, c >> 4)):
            got = np.asarray(mm_kernel.decode(nib, ei, dtype), np.float32)
            ref = want[half::2]
            np.testing.assert_array_equal(got, ref)
            np.testing.assert_array_equal(got[:, normal].view(np.int32),
                                          ref[:, normal].view(np.int32))


def test_mxfp4_matmul_batched_and_bitexact_dequant():
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (2, 3, 64), jnp.bfloat16)
    _, wq, codes, exps = _packed_weight(kw, 64, 32)
    out = mm_ops.mxfp4_matmul(x, codes, exps, interpret=True)
    assert out.shape == (2, 3, 32)
    # dequant path in ref == core mx dequant (bit exact)
    d1 = np.asarray(mm_ref.dequant_ref(codes, exps))
    d2 = np.asarray(mxlib.dequantize_w(wq))
    np.testing.assert_array_equal(d1, d2)


@pytest.mark.parametrize("m,k,n", [(16, 64, 16), (64, 128, 32)] + WIDE_TILES)
@pytest.mark.parametrize("adc,cm,two", [(10, 3, True), (None, 2, False), (8, 4, True)])
def test_cim_linear_kernel_matches_sim(m, k, n, adc, cm, two):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + k + n + cm))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    wq = mxlib.quantize_w(w)
    cfg = cimlib.CIMConfig(adc_bits=adc, cm_bits=cm, two_pass=two)
    calib = cimlib.calibrate_rowhist([x], wq, cfg)
    out = cim_ops.cim_linear(x, wq, calib, cfg=cfg, interpret=True)
    ref = cim_ref.cim_linear_ref(x, wq, calib, cfg)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("sq,sk,h,hkv,d", [
    (32, 32, 4, 4, 16),
    (64, 64, 8, 2, 32),
    (33, 48, 4, 1, 16),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24), (False, 0)])
def test_flash_attention_sweep(sq, sk, h, hkv, d, causal, window):
    if sq != sk and causal:
        return  # self-attention shapes only for causal sweep
    keys = jax.random.split(jax.random.PRNGKey(sq + h + window), 3)
    q = jax.random.normal(keys[0], (2, sq, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (2, sk, hkv, d), jnp.float32)
    v = jax.random.normal(keys[2], (2, sk, hkv, d), jnp.float32)
    out = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 interpret=True)
    ref = fa_ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_flash_attention_bf16_and_offset():
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (1, 16, 4, 32), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, 64, 4, 32), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, 64, 4, 32), jnp.bfloat16)
    # q is the last 16 positions of a 64-long sequence
    out = fa_ops.flash_attention(q, k, v, causal=True, q_offset=48,
                                 interpret=True)
    ref = fa_ref.flash_attention_ref(q, k, v, causal=True, q_offset=48)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_kernel_lowers_for_tpu_shapes():
    """The kernels must at least lower (trace) without interpret mode
    errors at TPU-aligned shapes."""
    kx, kw = jax.random.split(jax.random.PRNGKey(2))
    x = jax.ShapeDtypeStruct((256, 512), jnp.bfloat16)
    codes = jax.ShapeDtypeStruct((256, 256), jnp.uint8)
    exps = jax.ShapeDtypeStruct((16, 256), jnp.uint8)
    jax.eval_shape(
        lambda a, c, e: mm_ops.mxfp4_matmul(a, c, e, interpret=True),
        x, codes, exps,
    )
