"""Jitted public wrapper for the MXFP4 dequant-matmul kernel: handles
arbitrary leading batch dims, non-aligned shapes (pad), and the
CPU-interpret / TPU-compiled switch."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.mxfp4_matmul.kernel import mxfp4_matmul_kernel
from repro.obs.profile import profiled_call


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pick_bm(m: int, bm: int = 128) -> int:
    """M tile: never shrink below hardware alignment — small/odd M *pads
    up* to the tile instead (ViT's M=197 pads to 2x128, a tiny M=8 pads
    to one 16-row tile). Shrinking toward M's divisors produced degenerate
    tiles (e.g. bm=6) that cannot lower on TPU."""
    return min(bm, _round_up(max(m, 1), 16))


def pick_bn(n: int) -> int:
    """N tile: 128 lanes where they divide ``n``, else the whole of ``n``
    (Mosaic needs a block's last dim to be a multiple of 128 or the whole
    axis; padding N would copy the resident weights every call). ViT's
    1000-class head is one tile."""
    return 128 if n % 128 == 0 else n


BK_MAX = 512


def pick_bk(k: int) -> int:
    """K tile for the kernels that stream per-32-block exponents beside
    their codes. The exponent operand's block is ``(bk // 32, bn)``, and
    Mosaic needs its rows to be a multiple of 8 or the whole ``k // 32``
    axis: so ``bk`` is the largest multiple of 256 that divides ``k``, at
    most ``BK_MAX``, and the whole of ``k`` where none does (gemma3's
    1152)."""
    step = 8 * 32
    for bk in range(min(BK_MAX, k) // step * step, 0, -step):
        if k % bk == 0:
            return bk
    return k


def mxfp4_matmul(
    x: jax.Array,
    codes: jax.Array,
    exps: jax.Array,
    *,
    interpret: bool | None = None,  # None -> platform default
    obs=None,  # repro.obs.Obs: named timing scope + optional wall capture
) -> jax.Array:
    """x [..., K] @ dequant(codes [K//2, N], exps [K//32, N]) -> [..., N]."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = codes.shape[1]
    xm = x.reshape(-1, k)
    m = xm.shape[0]
    bm = pick_bm(m)
    pm = _round_up(m, bm) - m
    if pm:
        xm = jnp.pad(xm, ((0, pm), (0, 0)))
    out = profiled_call(
        "mxfp4_matmul", obs,
        lambda: mxfp4_matmul_kernel(
            xm, codes, exps, bm=bm, bn=pick_bn(n), bk=pick_bk(k),
            out_dtype=jnp.bfloat16, interpret=interpret,
        ),
    )
    if pm:
        out = out[:m]
    return out.reshape(lead + (n,))
