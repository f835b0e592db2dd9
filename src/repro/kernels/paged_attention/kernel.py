"""Ragged paged flash-decode Pallas kernels.

One grid program per lane, looping over its KV heads. The float kernel
keeps the KV pool HBM-resident (``memory_space=ANY``); per-lane page rows
and valid lengths arrive as scalar prefetch, and the program streams its
lane's page in ``bk``-token chunks through a ``buffers``-deep VMEM ring of
async copies — chunk ``c + buffers`` starts as soon as slot
``c % buffers``'s tile has been consumed, so the HBM reads for upcoming
chunks overlap the flash softmax/SV compute of the current one (double
buffering at ``buffers=2``, quad at 4). One copy per chunk streams every
head's K and V rows of the fused head-interleaved layout (see
``layout``): Mosaic copies only whole rows of the tiled minor dims, which
is also why the pages pad head_dim to 128 lanes.

Ragged lengths are handled per lane: ``nchunks = ceil(len / bk)`` drives
a dynamic ``fori_loop``, a zero-length lane runs no chunks and stores
zeros, and the tail chunk of a page whose width is not a multiple of
``bk`` is fetched at a clamped offset (re-reading a little overlap) with
the overlap masked out of the online softmax.

The quantized-resident variant reads the MXFP4 code mirrors instead of
raw pages (packed codes, K row exponents, V slot-block exponents) and
decodes them to bf16 *inside* the VMEM tile, so the HBM-resident cache
never leaves the code domain (~4.25 bits/value of KV traffic instead of
16). Its mirrors' minor dims (2Hkv nibble rows, Dpad/32 exponents) are
too narrow for Mosaic to slice in a copy, so Pallas fetches each lane's
whole page of every mirror as one block, and the chunk loop slices it in
VMEM. Nibble unpacking and the per-block scale spreads are lane moves,
which Mosaic has no gather or interleave for: they run on the MXU as
exact 0/1 selection matmuls. V blocks are 32-slot-aligned in the pool, so
the in-tile V dequant is exactly the global quantization; P re-quantizes
per chunk along the key axis, the same granularity precedent as
``layers.attention._flash_attn``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import mx as mxlib
from repro.kernels import default_interpret

BLOCK = mxlib.BLOCK
NEG_INF = -1e30


def _select(shape, pick) -> jax.Array:
    """bf16 0/1 matrix with ones where ``pick(row, col)``. A matmul with it
    moves values between lanes exactly (one nonzero term per output):
    Mosaic has no lane gather, interleave or repeat, the MXU does these."""
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return pick(r, c).astype(jnp.bfloat16)


def _mm(a, b):
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


def _interleave(n2: int) -> tuple[jax.Array, jax.Array]:
    """The [n2, 2*n2] selections that place low nibbles at even lanes and
    high nibbles at odd lanes (``_unpack_codes``)."""
    return (_select((n2, 2 * n2), lambda r, c: c == 2 * r),
            _select((n2, 2 * n2), lambda r, c: c == 2 * r + 1))


def _unpack_codes(packed: jax.Array, even: jax.Array,
                  odd: jax.Array) -> jax.Array:
    """uint8 nibble pairs [bk, D//2] -> bf16 code values [bk, D]; element
    ``2i`` is byte ``i``'s low nibble (the ``core/mx.pack_codes`` layout,
    decoded bitwise like ``mx.unpack_pairs_bf16``). ``even``/``odd`` come
    from ``_interleave``."""
    b = packed.astype(jnp.int32)
    lo = mxlib.nibble_code2x(b & 0x0F).astype(jnp.bfloat16)
    hi = mxlib.nibble_code2x((b >> 4) & 0x0F).astype(jnp.bfloat16)
    return (_mm(lo, even) + _mm(hi, odd)).astype(jnp.bfloat16)


def _half_scale(e: jax.Array) -> jax.Array:
    """bf16 ``2^(e - 1)`` for int32 E8M0 exponents: the dequant scale of a
    code (``2 * fp4``) value."""
    return mxlib.exp2i(e - 1).astype(jnp.bfloat16)


def _fake_quant_rows(x: jax.Array) -> jax.Array:
    """``mx.fake_quant`` of f32 ``x`` [G, bk] along its last axis, bitwise,
    without the [G, bk//32, 32] reshape Mosaic cannot lay out: each lane
    reads its 32-block's amax from a masked row maximum."""
    ax = jnp.abs(x)
    blk = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) >> 5
    amax = jnp.zeros_like(ax)
    for b in range(-(-x.shape[-1] // BLOCK)):
        mine = blk == b
        amax = jnp.where(
            mine, jnp.max(jnp.where(mine, ax, 0.0), axis=-1, keepdims=True),
            amax,
        )
    return mxlib.fake_quant_at(x, amax)


def _online_update(s, live, v, m_ref, l_ref, acc_ref, h: int, mx: bool):
    """One flash-softmax accumulation step of KV head ``h``. s f32
    [G, bk]; v bf16 [bk, Dh]; live bool [bk]."""
    s = jnp.where(live[None, :], s, NEG_INF)
    m_prev = m_ref[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(live[None, :], p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    if mx:
        # per-chunk P quantization + quantized-P running normalizer
        p = _fake_quant_rows(p)
    # f32 accumulator on both branches: Mosaic's matmul accumulates in
    # 32 bits only
    pv = jnp.einsum(
        "gk,kd->gd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    acc_ref[h] = acc_ref[h] * corr[:, None] + pv
    l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1)
    m_ref[h] = m_new


def _store(o_ref, acc_ref, l_ref):
    for h in range(acc_ref.shape[0]):
        den = jnp.where(l_ref[h] == 0.0, 1.0, l_ref[h])
        o_ref[0, h] = (acc_ref[h] / den[:, None]).astype(o_ref.dtype)


def _decode_kernel(
    rows_ref, lens_ref,  # scalar prefetch: int32 [L]
    q_ref,  # [1, Hkv, G, Dh] VMEM
    kv_ref,  # [P, W, 2Hkv, Dl] ANY (HBM), Dl = head_dim padded to 128
    o_ref,  # [1, Hkv, G, Dh]
    buf, sem, acc_ref, m_ref, l_ref,
    *, bk: int, buffers: int, scale: float,
):
    li = pl.program_id(0)
    row, ln = rows_ref[li], lens_ref[li]
    w = kv_ref.shape[1]
    hkv = q_ref.shape[1]
    nchunks = pl.cdiv(ln, bk)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def offset(c):  # clamped tail fetch; overlap masked via `live`
        return jnp.minimum(c * bk, w - bk)

    def dma(slot, c):  # all heads' K and V rows of the chunk
        return pltpu.make_async_copy(
            kv_ref.at[row, pl.ds(offset(c), bk)], buf.at[slot],
            sem.at[slot],
        )

    for i in range(buffers):  # warm-up: fill the ring
        @pl.when(i < nchunks)
        def _():
            dma(i, i).start()

    def body(c, _):
        slot = jax.lax.rem(c, buffers)
        dma(slot, c).wait()
        pos = offset(c) + jax.lax.broadcasted_iota(jnp.int32, (bk,), 0)
        live = (pos >= c * bk) & (pos < ln)
        page = buf[slot]  # [bk, 2Hkv, Dh]
        for h in range(hkv):
            k, v = page[:, 2 * h], page[:, 2 * h + 1]  # [bk, Dh]
            s = jnp.einsum(
                "gd,kd->gk", q_ref[0, h], k,
                preferred_element_type=jnp.float32,
            ) * scale
            _online_update(s, live, v, m_ref, l_ref, acc_ref, h, mx=False)

        @pl.when(c + buffers < nchunks)  # slot just freed: fetch ahead
        def _():
            dma(slot, c + buffers).start()

        return 0

    jax.lax.fori_loop(0, nchunks, body, 0)
    _store(o_ref, acc_ref, l_ref)


def _decode_kernel_mx(
    rows_ref, lens_ref,
    q_ref,  # [1, Hkv, G, Dh] — already MXFP4-fake-quant bf16
    kvc_ref,  # [1, W, 2Hkv, Dpad//2] uint8: the lane's page
    ke_ref,  # [1, W, Hkv, Dpad//32] int8
    ve_ref,  # [1, ceil(W/32), Hkv, Dh] int8
    o_ref,  # [1, Hkv, G, Dh]
    acc_ref, m_ref, l_ref,
    *, bk: int, nvb: int, scale: float, hd: int,
):
    ln = lens_ref[pl.program_id(0)]
    w = kvc_ref.shape[1]
    hkv = q_ref.shape[1]
    nbd = ke_ref.shape[-1]
    nwb = ve_ref.shape[1]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    # selection matrices, built once per program: [bk, nbd] -> [bk, Dpad]
    # scale expansion (each block's 32 lanes) and the nibble interleave
    kspread = _select((nbd, nbd * BLOCK), lambda r, c: c // BLOCK == r)
    even, odd = _interleave(kvc_ref.shape[-1])

    def body(c, _):
        offs = jnp.minimum(c * bk, w - bk)  # clamped tail; overlap masked
        vb0 = jnp.minimum(offs // BLOCK, nwb - nvb)  # first v_exps row
        pos = offs + jax.lax.broadcasted_iota(jnp.int32, (bk,), 0)
        live = (pos >= c * bk) & (pos < ln)
        # key row r of the chunk takes v_exps row (offs + r) // 32 - vb0
        vrow = _select(
            (bk, nvb),
            lambda r, j: (r + offs % BLOCK >> 5) + offs // BLOCK - vb0 == j,
        )
        codes = kvc_ref[0, pl.ds(offs, bk)]  # [bk, 2Hkv, Dpad//2]
        for h in range(hkv):
            # in-tile dequant: codes -> bf16, * 2^(e-1) (exact)
            kscale = _mm(
                _half_scale(ke_ref[0, pl.ds(offs, bk), h].astype(jnp.int32)),
                kspread,
            ).astype(jnp.bfloat16)
            k = (_unpack_codes(codes[:, 2 * h], even, odd) * kscale)[:, :hd]
            vscale = _mm(  # [bk, Dh]: slot-block exponents, globally aligned
                vrow,
                _half_scale(ve_ref[0, pl.ds(vb0, nvb), h].astype(jnp.int32)),
            ).astype(jnp.bfloat16)
            v = _unpack_codes(codes[:, 2 * h + 1], even, odd)[:, :hd] * vscale
            s = jnp.einsum(
                "gd,kd->gk", q_ref[0, h], k,
                preferred_element_type=jnp.float32,
            ) * scale
            s = s.astype(jnp.bfloat16).astype(jnp.float32)  # systolic round
            _online_update(s, live, v, m_ref, l_ref, acc_ref, h, mx=True)
        return 0

    jax.lax.fori_loop(0, pl.cdiv(ln, bk), body, 0)
    _store(o_ref, acc_ref, l_ref)


@functools.partial(
    jax.jit, static_argnames=("scale", "bk", "buffers", "interpret")
)
def paged_flash_decode(
    q: jax.Array,  # [L, Hkv, G, Dh]
    kv: jax.Array,  # [P, W, 2Hkv, Dl >= Dh] fused pages, zero beyond Dh
    rows: jax.Array,  # int32 [L]
    lengths: jax.Array,  # int32 [L], in [0, W]
    *,
    scale: float,
    bk: int = 128,
    buffers: int = 2,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    hd = q.shape[-1]
    if kv.shape[-1] > hd:  # lane-padded pages: zero q lanes add nothing
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, kv.shape[-1] - hd),))
    L, hkv, g, dh = q.shape
    w = kv.shape[1]
    assert bk <= w, (bk, w)
    scratch = [
        pltpu.VMEM((buffers, bk, 2 * hkv, dh), kv.dtype),
        pltpu.SemaphoreType.DMA((buffers,)),
        pltpu.VMEM((hkv, g, dh), jnp.float32),
        pltpu.VMEM((hkv, g), jnp.float32),
        pltpu.VMEM((hkv, g), jnp.float32),
    ]
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, bk=bk, buffers=buffers, scale=scale
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(L,),
            in_specs=[
                pl.BlockSpec((1, hkv, g, dh), lambda l, *_: (l, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, hkv, g, dh), lambda l, *_: (l, 0, 0, 0)
            ),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((L, hkv, g, dh), kv.dtype),
        interpret=interpret,
    )(rows, lengths, q, kv)[..., :hd]


@functools.partial(jax.jit, static_argnames=("scale", "bk", "interpret"))
def paged_flash_decode_mx(
    q: jax.Array,  # [L, Hkv, G, Dh] — already MXFP4-fake-quant bf16
    kv_codes: jax.Array,  # [P, W, 2Hkv, Dpad//2] uint8
    k_exps: jax.Array,  # [P, W, Hkv, Dpad//32] int8
    v_exps: jax.Array,  # [P, ceil(W/32), Hkv, Dh] int8
    rows: jax.Array,
    lengths: jax.Array,
    *,
    scale: float,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    L, hkv, g, dh = q.shape
    w = kv_codes.shape[1]
    assert bk <= w, (bk, w)
    nwb = v_exps.shape[1]
    nvb = min(bk // BLOCK + 1, nwb) if bk >= BLOCK else 1

    def page(a):  # the lane's whole page of a mirror, fetched by Pallas
        return pl.BlockSpec((1,) + a.shape[1:],
                            lambda l, rows, lens: (rows[l], 0, 0, 0))

    return pl.pallas_call(
        functools.partial(
            _decode_kernel_mx, bk=bk, nvb=nvb, scale=scale, hd=dh
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(L,),
            in_specs=[
                pl.BlockSpec((1, hkv, g, dh), lambda l, *_: (l, 0, 0, 0)),
                page(kv_codes), page(k_exps), page(v_exps),
            ],
            out_specs=pl.BlockSpec(
                (1, hkv, g, dh), lambda l, *_: (l, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((hkv, g, dh), jnp.float32),
                pltpu.VMEM((hkv, g), jnp.float32),
                pltpu.VMEM((hkv, g), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((L, hkv, g, dh), jnp.bfloat16),
        interpret=interpret,
    )(rows, lengths, q, kv_codes, k_exps, v_exps)
