"""Fused MXFP4 dequant-matmul Pallas kernel — the TPU analogue of the
CTT-CIM array: weights live in memory as packed 4-bit E2M1 codes + E8M0
scales (4.25 bits/param) and are decoded only inside the VMEM tile feeding
the MXU. Weights are never materialised at high precision in HBM.

Layout:  x [M, K];  codes [K//2, N] uint8 (two E2M1 nibbles per byte along
K, even row in the low nibble);  exps [K//32, N] uint8 (biased E8M0).

Each weight element is decoded once per call, straight from its nibble and
its block's exponent into the IEEE-754 bit pattern of its value (no int to
float convert, no scale multiply), in the operand dtype of the dot: bf16
when ``x`` is bf16 (every E2M1 value times a power of two is exact in bf16,
and bf16 products are exact in the f32 accumulator), f32 otherwise.

The nibbles are never interleaved back into K order: the low nibbles of
a code row hold K's even rows and the high nibbles its odd rows, so the
kernel de-interleaves x instead, chunk by chunk of ``2 * kc`` columns (a
dot with a 0/1 permutation, exact: each output is one input times one),
into a VMEM copy whose chunk ``c`` holds x's even columns of K rows
``[2 c kc, 2 (c + 1) kc)`` followed by the odd ones. One dot per chunk
then takes both decoded halves. The x block stays put across the N axis
when K is one tile, so the copy is made once per M block.

Grid ``(nm, nn, nk)``, K innermost, f32 VMEM accumulator. The M block
spans every executed row (``nm == 1``) unless the tile plan
(``ops.plan``) splits it; inside a grid step a loop walks the K tile in
chunks of ``kc`` code rows, and each chunk is decoded and multiplied in
strips of ``STRIP`` lanes, so the decode's live values stay one chunk
deep and one strip wide whatever the tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

SCOPED_VMEM_DEFAULT = 16 * 2**20  # Mosaic's scoped VMEM limit on a v5e
# lanes decoded at a time: a 128-row chunk 128 lanes wide is 16 of the 64
# int32 vector registers a value, so the decode's live values fit the file
STRIP = 128


def chunk_rows(k: int) -> int:
    """Code rows per decode chunk: 128 (a 256-deep dot) where 256 divides
    ``k``, else the whole of ``k // 2`` (one chunk: a chunk of x is a lane
    slice, which Mosaic needs 128-aligned or whole)."""
    return 128 if k % 256 == 0 else k // 2


def operand_dtype(x_dtype) -> jnp.dtype:
    """The dot's operand dtype: bf16 for bf16 activations, else f32."""
    return jnp.dtype(jnp.bfloat16 if x_dtype == jnp.bfloat16 else jnp.float32)


def vmem_bytes(bm: int, bn: int, bk: int, x_dtype, out_dtype) -> int:
    """VMEM one grid step holds: double-buffered x, codes, exps and output
    blocks, the de-interleaved x, the int32 exponent copy, the f32
    accumulator, the permutation and the decode's live values (a few
    chunk-sized int32 arrays)."""
    xb = jnp.dtype(x_dtype).itemsize
    wb = operand_dtype(x_dtype).itemsize
    ob = jnp.dtype(out_dtype).itemsize
    kc = chunk_rows(bk)
    exp_rows = -(-(bk // 32) // 32) * 32  # uint8 tiles are 32 rows deep
    return (2 * bm * bk * xb + bm * bk * wb + 2 * (bk // 2) * bn
            + 2 * exp_rows * bn + 2 * bm * bn * ob + (bk // 32) * bn * 4
            + bm * bn * 4 + 4 * kc * kc * wb + 8 * kc * bn * 4)


def decode(nib: jax.Array, exps: jax.Array, dtype) -> jax.Array:
    """E2M1 nibbles [r, bn] (int32; bits above the low four must be zero
    except where noted) and their blocks' biased E8M0 exponents
    [r // 16, bn] (int32; one per 16 rows: a half of a 32-row block) ->
    ``dtype`` values [r, bn], bit for bit ``dequant_ref``'s.

    With ``t`` the nibble's magnitude bits (element exponent ``e``, mantissa
    ``m``) and ``E`` the block's biased exponent, the value's f32 fields
    are: sign = the nibble's sign; exponent = e + E - 1; mantissa = m. The
    codes ±0.5 (t == 1) take exponent E - 1 and no mantissa; t == 0 is
    +0. Blocks with E <= 1 hold only values below 2^-126, which the f32
    reference flushes to zero; they read zero here too. Only bit 3 of the
    nibble is read above ``t``, so a code byte's low nibble can be passed
    as the byte itself.
    """
    r, bn = nib.shape
    nib = nib.reshape(r // 16, 16, bn)
    e = exps[:, None, :]
    t = nib & jnp.where(e >= 2, 7, 0)
    eb = (e - 1) << 23
    mag = jnp.where(t == 1, eb, (t << 22) + eb)
    bits = jnp.where(t == 0, 0, mag | ((nib & 8) << 28))
    w = jax.lax.bitcast_convert_type(bits.reshape(r, bn), jnp.float32)
    return w.astype(dtype)


def deinterleave_perm(kc: int, dtype) -> jax.Array:
    """[2 kc, 2 kc] 0/1 matrix P with (x @ P)[:, c] = x[:, 2 c] for
    c < kc and x[:, 2 (c - kc) + 1] for the rest: a chunk's even columns,
    then its odd ones."""
    r = jax.lax.broadcasted_iota(jnp.int32, (2 * kc, 2 * kc), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (2 * kc, 2 * kc), 1)
    src = jnp.where(c < kc, 2 * c, 2 * (c - kc) + 1)
    return (r == src).astype(dtype)


def _kernel(x_ref, c_ref, e_ref, o_ref, xs_ref, e_scr, acc_ref, *, nk: int,
            kc: int, wc: int, w_dtype):
    j, ki = pl.program_id(1), pl.program_id(2)

    def rows(i, size):
        if isinstance(i, int):
            return pl.ds(i * size, size)
        return pl.ds(pl.multiple_of(i * size, size), size)

    def loop(n, body):
        if n == 1:  # static offsets: a whole-K chunk need not be 8-aligned
            body(0, None)
        else:
            jax.lax.fori_loop(0, n, body, None)

    n = xs_ref.shape[0]
    # f32 operands keep f32 products; bf16 ones are exact at any precision
    precision = (None if w_dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)

    def deinterleave():
        perm = deinterleave_perm(kc, w_dtype)

        def chunk(i, carry):
            xs_ref[i] = jax.lax.dot(
                x_ref[:, rows(i, 2 * kc)].astype(w_dtype), perm,
                precision=precision, preferred_element_type=jnp.float32,
            ).astype(w_dtype)
            return carry

        loop(n, chunk)

    if nk == 1:  # the x block is the same for every N step
        pl.when(j == 0)(deinterleave)
    else:
        deinterleave()

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # uint8 exponent rows sit in 32-row tiles; an int32 copy slices at the
    # chunk's 8-row offsets
    e_scr[...] = e_ref[...].astype(jnp.int32)
    er = kc // 16

    def chunk(i, carry):
        for s in range(acc_ref.shape[1] // wc):  # lane strips of the tile
            cols = pl.ds(s * wc, wc)
            c = c_ref[rows(i, kc), cols].astype(jnp.int32)
            e = e_scr[rows(i, er), cols]
            w = jnp.concatenate(
                [decode(c, e, w_dtype), decode(c >> 4, e, w_dtype)], axis=0)
            acc_ref[:, cols] += jax.lax.dot(
                xs_ref[i], w, precision=precision,
                preferred_element_type=jnp.float32)
        return carry

    loop(n, chunk)

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "wc", "out_dtype", "interpret"),
)
def mxfp4_matmul_kernel(
    x: jax.Array,
    codes: jax.Array,
    exps: jax.Array,
    *,
    bm: int,
    bn: int,
    bk: int,
    wc: int | None = None,
    out_dtype=jnp.bfloat16,
    interpret: bool | None = None,  # None -> platform default
):
    """x [M, K] @ dequant(codes, exps) -> [M, N] ``out_dtype`` on an
    (M // bm, N // bn, K // bk) grid. ``bk`` is a multiple of 256 or the
    whole of K; ``bn`` a multiple of 128 or the whole of N. A grid step
    decodes ``chunk_rows(bk)`` code rows by ``wc`` lanes at a time (default
    ``STRIP``, or ``bn`` where ``STRIP`` does not divide it)."""
    if interpret is None:
        interpret = default_interpret()
    m, k = x.shape
    n = codes.shape[1]
    assert codes.shape == (k // 2, n) and exps.shape == (k // 32, n)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % 32 == 0
    kc = chunk_rows(bk)
    wc = wc or (STRIP if bn % STRIP == 0 else bn)
    assert bk % (2 * kc) == 0 and bn % wc == 0
    nm, nn, nk = m // bm, n // bn, k // bk
    w_dtype = operand_dtype(x.dtype)
    need = vmem_bytes(bm, bn, bk, x.dtype, out_dtype)
    limit = None if need <= SCOPED_VMEM_DEFAULT else need + need // 4
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, kc=kc, wc=wc, w_dtype=w_dtype),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((bk // 32, bn), lambda i, j, ki: (ki, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bk // (2 * kc), bm, 2 * kc), w_dtype),
                        pltpu.VMEM((bk // 32, bn), jnp.int32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # N in order: its first step de-interleaves a resident x block
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=limit),
        interpret=interpret,
    )(x, codes, exps)
