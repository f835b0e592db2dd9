"""Jitted wrapper for the fused CIM kernel: raw activations stream in
(the activation quantize runs *inside* the kernel tile — codes/exps never
round-trip HBM) against resident MXFP4 weights + Row-Hist calibration."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import cim as cimlib
from repro.core import mx as mxlib
from repro.kernels.cim_linear.kernel import cim_linear_kernel
from repro.kernels.mxfp4_matmul.ops import _round_up, pick_bm


def pick_bn(n: int) -> int:
    """N tile: 128 lanes where they divide ``n``, else the whole of ``n``
    (Mosaic needs a block's last dim to be a multiple of 128 or the whole
    axis; padding N would copy the resident weights every call). ViT's
    1000-class head is one tile."""
    return 128 if n % 128 == 0 else n


BK_MAX = 512


def pick_bk(k: int) -> int:
    """K tile. The exponent operand's block is ``(bk // 32, bn)``, and
    Mosaic needs its rows to be a multiple of 8 or the whole ``k // 32``
    axis: so ``bk`` is the largest multiple of 256 that divides ``k``, at
    most ``BK_MAX`` (the tile holds the ADC emulation's intermediates),
    and the whole of ``k`` where none does (gemma3's 1152)."""
    step = 8 * 32
    for bk in range(min(BK_MAX, k) // step * step, 0, -step):
        if k % bk == 0:
            return bk
    return k


def cim_linear(
    x: jax.Array,
    w: mxlib.MXW,
    calib: cimlib.LayerCalib,
    *,
    cfg: cimlib.CIMConfig | None = None,
    interpret: bool | None = None,  # None -> platform default
) -> jax.Array:
    """x [..., K] float -> [..., N] f32 through the analog CIM kernel."""
    cfg = cfg or cimlib.CIMConfig()
    k = w.codes.shape[0]
    n = w.codes.shape[1]
    lead = x.shape[:-1]
    xm = x.reshape(-1, x.shape[-1])[..., :k].astype(jnp.float32)
    m = xm.shape[0]
    bm = pick_bm(m)  # pad M up to the tile, never shrink toward divisors
    pm = _round_up(m, bm) - m
    if pm:
        xm = jnp.pad(xm, ((0, pm), (0, 0)))
    cal = jnp.array(
        [[jnp.asarray(calib.e_n, jnp.float32), calib.adc_fs]], jnp.float32
    )
    with jax.named_scope("repro/cim_linear"):
        out = cim_linear_kernel(
            xm, w.codes, w.exps, cal,
            bm=bm, bn=pick_bn(n), bk=pick_bk(k), cm=cfg.cm_bits,
            adc_bits=cfg.adc_bits, two_pass=cfg.two_pass,
            interpret=interpret,
        )
    if pm:
        out = out[:m]
    return out.reshape(lead + (n,))
