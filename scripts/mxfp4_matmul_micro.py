#!/usr/bin/env python
"""Time the ``mxfp4_matmul`` kernel and its parts on one TPU, apart from
the model.

Two families of kernels over the same packed weights:

- ``old*``: the kernel as it was before the decode-once rewrite (128-row
  M tiles, 128-lane N tiles, K tiles of at most 512, f32 operands, the
  nibbles interleaved back into K order), and variants of it that drop one
  part at a time, to split its time among the grid step, the relayout,
  the decode and the f32 dot:
  ``old_empty`` (the same grid and blocks, no decode, no dot),
  ``old_decode`` (decode and relayout, no dot: the tile summed instead),
  ``old_dot`` (an f32 dot against an undecoded tile),
  ``old_norelayout`` (halves decoded apart, two f32 dots against
  de-interleaved x), ``old_norelayout_bf16`` (the same with bf16
  operands);
- ``new``: ``ops.mxfp4_matmul`` as it is, ``new_f32`` (f32 x, so f32
  operands, f32 output), and the kernel at other tiles than its plan's:
  ``new_bn128``, ``new_bn256`` (narrower N), ``new_bk512`` (K split),
  ``new_wc256``, ``new_wcbn`` (the decode in lane strips of 256 or of the
  whole tile, against the plan's 128: live values in more vector
  registers).

Each variant runs ``--reps`` times inside one jitted loop whose iterations
depend on each other; the best of ``--repeats`` loops, less an empty loop,
over the reps is its time a call. Every ``new*`` output is checked against
the exact product (float64, on the host): within one bf16 ulp for bf16 x,
1e-6 for f32 x (``gap_over_bound`` at most 1). Prints one JSON line per
shape; exits 1 if any gap is over its bound.

Usage (on the chip; no PYTHONPATH needed):
  python scripts/mxfp4_matmul_micro.py
  python scripts/mxfp4_matmul_micro.py --interpret   # CPU, tiny shapes
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from repro.core import mx as mxlib  # noqa: E402
from repro.kernels.mxfp4_matmul import kernel, ops, ref  # noqa: E402

SHAPES = [(32, 2560, 6912), (512, 2560, 6912), (197, 768, 3072)]
TINY = [(8, 512, 256), (40, 256, 128)]


# ------------------------------------------------- the kernel before


def old_tiles(m: int, k: int, n: int):
    bm = min(128, -(-max(m, 1) // 16) * 16)
    bn = 128 if n % 128 == 0 else n
    bk = next((b for b in range(min(512, k) // 256 * 256, 0, -256)
               if k % b == 0), k)
    return bm, bn, bk


def _code2x_f32(nib):
    return mxlib.nibble_code2x(nib).astype(jnp.float32)


def _scale(e_u8):
    return jax.lax.bitcast_convert_type(
        e_u8.astype(jnp.int32) << 23, jnp.float32)


def _old_decode(c_u8, e_u8):
    kk2, bn = c_u8.shape
    c = c_u8.astype(jnp.int32)
    nib = jnp.stack([c & 0x0F, (c >> 4) & 0x0F], axis=1).reshape(kk2 * 2, bn)
    vals = _code2x_f32(nib).reshape(kk2 * 2 // 32, 32, bn) * (
        0.5 * _scale(e_u8))[:, None, :]
    return vals.reshape(kk2 * 2, bn)


def _half(nib, e_u8):
    kk2, bn = nib.shape
    v = _code2x_f32(nib).reshape(kk2 // 16, 16, bn) * (
        0.5 * _scale(e_u8))[:, None, :]
    return v.reshape(kk2, bn)


def _old_body(part, x_ref, c_ref, e_ref, o_ref, acc_ref, *, nk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bn = acc_ref.shape[1]
    if part == "old":
        w = _old_decode(c_ref[...], e_ref[...])
        acc_ref[...] += jax.lax.dot(x_ref[...].astype(jnp.float32), w,
                                    preferred_element_type=jnp.float32)
    elif part == "old_empty":
        acc_ref[...] += x_ref[:, :bn].astype(jnp.float32)
    elif part == "old_decode":
        w = _old_decode(c_ref[...], e_ref[...])
        acc_ref[0:8, :] += w.reshape(-1, 8, bn).sum(axis=0)
    elif part == "old_dot":
        w = c_ref[...].astype(jnp.int32).astype(jnp.float32)
        w = jnp.concatenate([w, w], axis=0)
        acc_ref[...] += jax.lax.dot(x_ref[...].astype(jnp.float32), w,
                                    preferred_element_type=jnp.float32)
    else:  # old_norelayout[_bf16]: x_ref is (2, bm, bk // 2)
        dt = jnp.bfloat16 if part.endswith("bf16") else jnp.float32
        c = c_ref[...].astype(jnp.int32)
        lo = _half(c & 0x0F, e_ref[...]).astype(dt)
        hi = _half((c >> 4) & 0x0F, e_ref[...]).astype(dt)
        acc_ref[...] += (
            jax.lax.dot(x_ref[0].astype(dt), lo,
                        preferred_element_type=jnp.float32)
            + jax.lax.dot(x_ref[1].astype(dt), hi,
                          preferred_element_type=jnp.float32))

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def old_call(part, x, codes, exps, interpret):
    """Before-rewrite kernel (or one of its part variants) on x [M, K]
    already padded to its M tile."""
    m, k = x.shape
    n = codes.shape[1]
    bm, bn, bk = old_tiles(m, k, n)
    nm, nn, nk = m // bm, n // bn, k // bk
    if part.startswith("old_norelayout"):
        xs = x.reshape(m, k // 2, 2).transpose(2, 0, 1)
        x_spec = pl.BlockSpec((2, bm, bk // 2), lambda i, j, ki: (0, i, ki))
    else:
        xs = x
        x_spec = pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki))
    return pl.pallas_call(
        functools.partial(_old_body, part, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            x_spec,
            pl.BlockSpec((bk // 2, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((bk // 32, bn), lambda i, j, ki: (ki, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.bfloat16),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xs, codes, exps)


# ------------------------------------------------------------ timing


def timed(f, x, codes, exps, reps: int, repeats: int) -> float:
    """Best seconds of ``reps`` dependent calls of f(x, codes, exps)."""

    def loop(x, c, e):
        def body(_, x):
            out = f(x, c, e)
            return x.at[0, 0].add((out[0, 0] * 0).astype(x.dtype))
        return jax.lax.fori_loop(0, reps, body, x)

    run = jax.jit(loop)
    run(x, codes, exps).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        run(x, codes, exps).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def exact_ref(x, codes, exps) -> np.ndarray:
    """x @ dequant_ref(codes, exps) in float64 on the host: exact to well
    below an f32 ulp."""
    w = np.asarray(ref.dequant_ref(codes, exps), np.float64)
    return np.asarray(x.astype(jnp.float32), np.float64) @ w


def gap(out, want: np.ndarray, f32: bool) -> float:
    """Widest gap of ``out`` from the exact ``want``, over its bound (one
    bf16 ulp of the larger magnitude for a bf16 output; 1e-6 relative plus
    1e-6 of the largest magnitude for an f32 one): at most 1 passes."""
    out = np.asarray(out, np.float64)
    d = np.abs(out - want)
    if f32:
        return float((d / (1e-6 * np.abs(want) + 1e-6 * np.abs(want).max()))
                     .max())
    v = np.maximum(np.maximum(np.abs(out), np.abs(want)),
                   np.finfo(np.float32).tiny)
    return float((d / np.exp2(np.floor(np.log2(v)) - 7)).max())


def measure(m, k, n, reps, repeats, interpret):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + k + n))
    w = jax.random.normal(kw, (k, n), jnp.float32)
    wq = mxlib.quantize_w(w)
    codes = mxlib.pack_codes(wq.codes.T).T
    exps = mxlib.exps_to_biased(wq.exps)
    p = ops.plan(m, k, n)
    x = jax.random.normal(kx, (p.rows, k), jnp.float32)
    xb = x.astype(jnp.bfloat16)
    tile = functools.partial(kernel.mxfp4_matmul_kernel, bm=p.bm,
                             interpret=interpret)
    news = {
        "new": functools.partial(ops.mxfp4_matmul, interpret=interpret),
        "new_f32": functools.partial(tile, bn=p.bn, bk=p.bk,
                                     out_dtype=jnp.float32),
    }
    plan_strip = kernel.STRIP if p.bn % kernel.STRIP == 0 else p.bn
    # the new kernel at other tiles than the plan's: narrower N, K split,
    # the decode in narrower lane strips than the tile
    for name, bn, bk, wc in (("new_bn128", 128, p.bk, None),
                             ("new_bn256", 256, p.bk, None),
                             ("new_bk512", p.bn, 512, None),
                             ("new_wc256", p.bn, p.bk, 256),
                             ("new_wcbn", p.bn, p.bk, p.bn)):
        if n % bn or k % bk or bn % (wc or bn):
            continue
        strip = wc or (kernel.STRIP if bn % kernel.STRIP == 0 else bn)
        if (bn, bk, strip) != (p.bn, p.bk, plan_strip):
            news[name] = functools.partial(tile, bn=bn, bk=bk, wc=wc)
    want = exact_ref(xb, codes, exps)
    want32 = exact_ref(x, codes, exps)
    gaps = {name: round(gap(f(x if name == "new_f32" else xb, codes, exps),
                            want32 if name == "new_f32" else want,
                            name == "new_f32"), 4)
            for name, f in news.items()}
    variants = {
        "null": lambda x, c, e: x[:, :1],
        **{part: functools.partial(old_call, part, interpret=interpret)
           for part in ("old", "old_empty", "old_decode", "old_dot",
                        "old_norelayout", "old_norelayout_bf16")},
        **news,
    }
    secs = {name: timed(f, x if name == "new_f32" else xb, codes, exps,
                        reps, repeats)
            for name, f in variants.items()}
    null = secs.pop("null")
    us = {name: 1e6 * max(s - null, 0.0) / reps for name, s in secs.items()}
    bm, bn, bk = old_tiles(m, k, n)
    old_steps = (p.rows // bm) * (n // bn) * (k // bk)
    new_steps = (p.rows // p.bm) * (n // p.bn) * (k // p.bk)
    return {
        "m": m, "k": k, "n": n, "rows": p.rows,
        "old_tiles": [bm, bn, bk], "old_steps": old_steps,
        "new_plan": list(p), "new_steps": new_steps,
        "us_per_call": {name: round(v, 2) for name, v in us.items()},
        "old_us_per_step": {name: round(v / old_steps, 4)
                            for name, v in us.items() if name.startswith("old")},
        "g_decodes_per_s": {
            "old": round(k * n * (p.rows // bm) / us["old"] / 1e3, 2)
            if us["old"] else None,
            "new": round(k * n / us["new"] / 1e3, 2) if us["new"] else None,
        },
        "gap_over_bound": gaps,
        "null_us_per_rep": round(1e6 * null / reps, 2),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--interpret", action="store_true",
                    help="CPU interpret mode at tiny shapes (a smoke check)")
    a = ap.parse_args()
    shapes = TINY if a.interpret else SHAPES
    if not a.interpret and jax.default_backend() != "tpu":
        print("no TPU: run with --interpret for the CPU smoke check",
              file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    bad = False
    for m, k, n in shapes:
        rec = measure(m, k, n, 2 if a.interpret else a.reps,
                      1 if a.interpret else a.repeats, a.interpret)
        rec["device"] = {"platform": dev.platform,
                         "device_kind": dev.device_kind}
        print(json.dumps(rec), flush=True)
        bad = bad or max(rec["gap_over_bound"].values()) > 1
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
