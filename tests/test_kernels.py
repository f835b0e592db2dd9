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


@pytest.mark.parametrize(
    "m,k,n",
    [(8, 64, 16), (128, 128, 128), (33, 96, 48), (256, 512, 64)]
    + WIDE_TILES,
)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_mxfp4_matmul_sweep(m, k, n, dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(m * 7 + k + n))
    x = jax.random.normal(kx, (m, k), dtype)
    _, _, codes, exps = _packed_weight(kw, k, n)
    out = mm_ops.mxfp4_matmul(x, codes, exps, interpret=True)
    ref = mm_ref.mxfp4_matmul_ref(x, codes, exps)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2 * np.abs(np.asarray(ref, np.float32)).max(),
    )


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
