"""Quickstart: MXFP4 microscaling + the analog CTT-CIM path in 2 minutes.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cim, digital, mx

print("== 1. MXFP4 block quantization (32 x E2M1 + shared E8M0) ==")
x = jax.random.normal(jax.random.PRNGKey(0), (4, 64)) * 3.0
q = mx.quantize(x)
deq = mx.dequantize(q, out_len=64)
print(f"codes int8 in [-12,12]: {np.asarray(q.codes)[0, :8]}")
print(f"shared exponents:       {np.asarray(q.exps)[0]}")
print(f"quantization rel-err:   {float(jnp.linalg.norm(deq - x) / jnp.linalg.norm(x)):.4f}")
packed = mx.pack_codes(q.codes)
print(f"packed storage: {q.codes.shape} int8 -> {packed.shape} uint8 "
      f"(4.25 bits/param with scales)\n")

print("== 2. Analog CTT-CIM linear (Row-Hist 2-pass, CM=3, 10-bit ADC) ==")
w = jax.random.normal(jax.random.PRNGKey(1), (64, 32)) * 0.2
wq = mx.quantize_w(w)
cfg = cim.CIMConfig(adc_bits=10, cm_bits=3, two_pass=True, collect_stats=True)
calib = cim.calibrate_rowhist([x], wq, cfg)
print(f"calibrated per-layer target exponent E_N = {int(calib.e_n)}, "
      f"ADC full-scale = {float(calib.adc_fs):.1f}")
y_analog, stats = cim.cim_linear(x, wq, cfg, calib)
y_digital = mx.dequantize(mx.quantize(x), out_len=64) @ mx.dequantize_w(wq)
err = float(jnp.linalg.norm(y_analog - y_digital) / jnp.linalg.norm(y_digital))
print(f"analog vs digital-MXFP4 rel-err: {err:.4f} "
      f"(overflow rate {float(stats['overflow_rate']):.3f})\n")

print("== 3. Digital-stage attention (MXFP4 ops, BF16 accum, flash softmax) ==")
q_, k_, v_ = (jax.random.normal(jax.random.PRNGKey(i), (1, 32, 16))
              for i in (2, 3, 4))
out = digital.mx_attention(q_, k_, v_, causal=True)
ref = digital.attention_ref(q_, k_, v_, causal=True)
print(f"attention rel-err vs fp32: "
      f"{float(jnp.linalg.norm(out.astype(jnp.float32) - ref) / jnp.linalg.norm(ref)):.4f}")

print("\n== 4. Pallas kernels (compiled on TPU, interpreted elsewhere) ==")
from repro.kernels.mxfp4_matmul import ops as mm_ops

out_k = mm_ops.mxfp4_matmul(
    x.astype(jnp.bfloat16), mx.pack_codes(wq.codes.T).T,
    mx.exps_to_biased(wq.exps),
)
rel = float(
    jnp.linalg.norm(out_k.astype(jnp.float32) - y_digital)
    / jnp.linalg.norm(y_digital)
)
print(f"fused dequant-matmul kernel rel-err vs digital: {rel:.4f} "
      f"(bf16 output rounding)")
print("done.")
