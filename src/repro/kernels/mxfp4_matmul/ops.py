"""Jitted public wrapper for the MXFP4 dequant-matmul kernel: handles
arbitrary leading batch dims, non-aligned shapes (pad), the tile plan, and
the CPU-interpret / TPU-compiled switch."""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.mxfp4_matmul.kernel import mxfp4_matmul_kernel, vmem_bytes


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pick_bm(m: int, bm: int = 128) -> int:
    """M alignment tile: never shrink below hardware alignment — small/odd
    M *pads up* to the tile instead (ViT's M=197 pads to 2x128, a tiny M=8
    pads to one 16-row tile). Shrinking toward M's divisors produced
    degenerate tiles (e.g. bm=6) that cannot lower on TPU. The rows a
    kernel executes are M rounded up to this tile."""
    return min(bm, _round_up(max(m, 1), 16))


VMEM_BUDGET = 48 * 2**20  # of a v5e core's 128 MiB VMEM
BN_MAX = 512  # lanes a grid step covers (decoded in kernel.STRIP strips)


class Plan(NamedTuple):
    rows: int  # M padded: the rows the kernel executes
    bm: int
    bn: int
    bk: int


def plan(m: int, k: int, n: int, x_dtype=jnp.bfloat16,
         out_dtype=jnp.bfloat16) -> Plan:
    """Tile plan of one ``mxfp4_matmul`` call, derived from its shapes.

    - rows: M rounded up to ``pick_bm(m)``; one M block spans them all, so
      every weight element is decoded once per call. Only where no tiling
      fits ``VMEM_BUDGET`` does M split, into the largest blocks that fit,
      each a multiple of ``pick_bm(m)`` that divides ``rows``.
    - bn: the widest multiple of 128 that divides N, at most ``BN_MAX``,
      and the whole of N where 128 does not divide it (Mosaic needs a
      block's last dim to be a multiple of 128 or the whole axis; padding
      N would copy the resident weights every call). ViT's 1000-class head
      is one tile.
    - bk: the whole of K where it fits, else the largest multiple of 256
      that divides K and fits: the exponent block ``(bk // 32, bn)`` needs
      a multiple of 8 rows or the whole axis. K of no multiple of 256
      (gemma3's 1152) is taken whole.
    """
    tile = pick_bm(m)
    rows = _round_up(m, tile)
    bms = [b for b in range(rows, 0, -tile) if rows % b == 0]
    bns = [b for b in range(min(n, BN_MAX) // 128 * 128, 0, -128)
           if n % b == 0] if n % 128 == 0 else [n]
    bks = [k] + [b for b in range(k // 256 * 256, 0, -256)
                 if k % b == 0 and b != k]
    for bm in bms:
        for bn in bns:
            for bk in bks:
                if vmem_bytes(bm, bn, bk, x_dtype, out_dtype) <= VMEM_BUDGET:
                    return Plan(rows, bm, bn, bk)
    return Plan(rows, bms[-1], bns[-1], bks[-1])


def mxfp4_matmul(
    x: jax.Array,
    codes: jax.Array,
    exps: jax.Array,
    *,
    interpret: bool | None = None,  # None -> platform default
) -> jax.Array:
    """x [..., K] @ dequant(codes [K//2, N], exps [K//32, N]) -> [..., N]."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = codes.shape[1]
    xm = x.reshape(-1, k)
    m = xm.shape[0]
    p = plan(m, k, n, xm.dtype)
    if p.rows > m:
        xm = jnp.pad(xm, ((0, p.rows - m), (0, 0)))
    with jax.named_scope("repro/mxfp4_matmul"):
        out = mxfp4_matmul_kernel(
            xm, codes, exps, bm=p.bm, bn=p.bn, bk=p.bk,
            out_dtype=jnp.bfloat16, interpret=interpret,
        )
    if p.rows > m:
        out = out[:m]
    return out.reshape(lead + (n,))
