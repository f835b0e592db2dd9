"""Jitted wrapper for the fused CIM kernel: raw activations stream in
(the activation quantize runs *inside* the kernel tile — codes/exps never
round-trip HBM) against resident MXFP4 weights + Row-Hist calibration."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import cim as cimlib
from repro.core import mx as mxlib
from repro.kernels.cim_linear.kernel import cim_linear_kernel
from repro.kernels.mxfp4_matmul.ops import (
    _round_up,
    pick_bk,
    pick_bm,
    pick_bn,
)
from repro.obs.profile import profiled_call


def cim_linear(
    x: jax.Array,
    w: mxlib.MXW,
    calib: cimlib.LayerCalib,
    *,
    cfg: cimlib.CIMConfig | None = None,
    interpret: bool | None = None,  # None -> platform default
    obs=None,  # repro.obs.Obs: named timing scope + optional wall capture
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
    out = profiled_call(
        "cim_linear", obs,
        lambda: cim_linear_kernel(
            xm, w.codes, w.exps, cal,
            bm=bm, bn=pick_bn(n), bk=pick_bk(k), cm=cfg.cm_bits,
            adc_bits=cfg.adc_bits, two_pass=cfg.two_pass,
            interpret=interpret,
        ),
    )
    if pm:
        out = out[:m]
    return out.reshape(lead + (n,))
