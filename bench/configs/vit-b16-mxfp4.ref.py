"""Plain float32 reference for ViT-B/16 (arXiv:2010.11929, Table 1) with
weight-only MXFP4 linears, and the seeded weights and frames that both
this reference and the served model are made from.

The model as configured: 16x16 patches unfolded row-major (patch row,
patch column, then pixel row, pixel column, channel) and embedded by a
linear; a learned class token and position embeddings; 12 pre-LayerNorm
blocks of bidirectional multi-head attention and a GELU MLP (the tanh
form, as served); final LayerNorm and a linear head on the class token.
Every linear weight is MXFP4 as in the danube reference: 32-element
blocks along the input axis, E2M1 elements, a power-of-two scale of
exponent floor(log2(block amax)) - 2, nearest-even rounding, clamp at 6.
Biases, LayerNorm parameters, the class token and position embeddings
are bf16 as served. This file imports nothing of the system under test.

Activations are float32 at ``highest`` matmul precision; ``control=True``
rounds every activation the served model keeps in bf16 to float8 e4m3.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0


def base_key(seed: int) -> jax.Array:
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def dims(m: dict) -> dict:
    d = m["hidden_size"]
    g = m["image_size"] // m["patch_size"]
    return {"d": d, "h": m["num_attention_heads"],
            "hd": d // m["num_attention_heads"], "f": m["intermediate_size"],
            "n": m["num_hidden_layers"], "c": m["num_classes"],
            "p": m["patch_size"], "ch": m["num_channels"], "grid": g,
            "seq": g * g + 1, "pdim": m["patch_size"] ** 2 * m["num_channels"]}


def layer_weights(key, m: dict, i) -> dict:
    """Float32 weights of block ``i``: linears [in, out] with std
    1/sqrt(in), biases std 0.02, LayerNorm gains 1 and shifts 0."""
    s = dims(m)
    d, f = s["d"], s["f"]
    k = jax.random.fold_in(key, i)
    shapes = {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
              "w1": (d, f), "w2": (f, d)}
    out = {}
    for j, (name, shape) in enumerate(shapes.items()):
        kk = jax.random.fold_in(k, j)
        out[name] = jax.random.normal(kk, shape, F32) * shape[0] ** -0.5
        out["b" + name[1:]] = jax.random.normal(
            jax.random.fold_in(kk, 1), (shape[1],), F32) * 0.02
    return out


def outer_weights(key, m: dict) -> dict:
    s = dims(m)
    d, c, pd = s["d"], s["c"], s["pdim"]
    k = jax.random.fold_in(key, 1_000_001)
    n = lambda j, shape, std: jax.random.normal(  # noqa: E731
        jax.random.fold_in(k, j), shape, F32) * std
    return {"patch_w": n(0, (pd, d), pd ** -0.5), "patch_b": n(1, (d,), 0.02),
            "cls": n(2, (1, 1, d), 0.02), "pos": n(3, (1, s["seq"], d), 0.02),
            "head_w": n(4, (d, c), d ** -0.5), "head_b": n(5, (c,), 0.02)}


def frames(seed: int, m: dict, n: int) -> jax.Array:
    """``n`` seeded frames [n, H, W, C], standard normal pixels."""
    s = dims(m)
    size = m["image_size"]
    return jax.random.normal(jax.random.fold_in(base_key(seed), 7),
                             (n, size, size, s["ch"]), F32)


def mxfp4(w: jax.Array) -> jax.Array:
    """Quantize-dequantize ``w`` [K, N] in 32-blocks along K."""
    k, n = w.shape
    b = w.reshape(k // 32, 32, n)
    amax = jnp.max(jnp.abs(b), axis=1, keepdims=True)
    _, ex = jnp.frexp(amax)
    scale = jnp.clip(ex - 1 - 2, -127, 125)
    y = jnp.ldexp(jnp.abs(b), -scale)
    step = jnp.where(y < 2.0, 0.5, jnp.where(y < 4.0, 1.0, 2.0))
    q = jnp.minimum(jnp.round(y / step) * step, 6.0)
    out = jnp.where(amax > 0, jnp.sign(b) * jnp.ldexp(q, scale), 0.0)
    return out.reshape(k, n)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(F32)


def _round(x, control: bool):
    if not control:
        return x
    return jnp.clip(x, -FP8_MAX, FP8_MAX).astype(jnp.float8_e4m3fn).astype(F32)


def _ln(x, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _served(w: dict) -> dict:
    """Linear weights as MXFP4 values; biases and the rest as bf16."""
    return {k: (mxfp4(v) if v.ndim == 2 and k[0] == "w" or k.endswith("_w")
                else _bf16(v)) for k, v in w.items()}


@functools.partial(jax.jit, static_argnums=(1,))
def _qlayer(key, mkey, i):
    return _served(layer_weights(key, dict(mkey), i))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed(img, w, mkey, control):
    s = dims(dict(mkey))
    r = functools.partial(_round, control=control)
    b, p, g = img.shape[0], s["p"], s["grid"]
    x = img.reshape(b, g, p, g, p, s["ch"]).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, g * g, s["pdim"])
    x = r(x @ w["patch_w"] + w["patch_b"])
    cls = jnp.broadcast_to(w["cls"], (b, 1, s["d"]))
    return r(jnp.concatenate([cls, x], 1) + w["pos"])


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block(x, w, mkey, control):
    s = dims(dict(mkey))
    r = functools.partial(_round, control=control)
    b, n, d = x.shape
    xn = r(_ln(x))
    heads = lambda t: t.reshape(b, n, s["h"], s["hd"])  # noqa: E731
    q = heads(r(xn @ w["wq"] + w["bq"]))
    k = heads(r(xn @ w["wk"] + w["bk"]))
    v = heads(r(xn @ w["wv"] + w["bv"]))
    p = jax.nn.softmax(jnp.einsum("bqhd,bkhd->bhqk", q, k) * s["hd"] ** -0.5)
    o = r(jnp.einsum("bhqk,bkhd->bqhd", r(p), v).reshape(b, n, d))
    x = r(x + r(o @ w["wo"] + w["bo"]))
    xn = r(_ln(x))
    h = r(jax.nn.gelu(r(xn @ w["w1"] + w["b1"]), approximate=True))
    return r(x + r(h @ w["w2"] + w["b2"]))


@functools.partial(jax.jit, static_argnums=(2,))
def _head(x, w, control):
    r = functools.partial(_round, control=control)
    return r(r(_ln(x[:, 0])) @ w["head_w"] + w["head_b"])


def logits(seed: int, m: dict, images, control: bool = False,
           rows: int = 32) -> np.ndarray:
    """Float32 [N, classes] logits of ``images``, ``rows`` frames at a
    time through one layer's weights after another."""
    key = base_key(seed)
    mkey = tuple(sorted(m.items()))
    s = dims(m)
    with jax.default_matmul_precision("highest"):
        outer = jax.jit(_served)(outer_weights(key, m))
        xs = [_embed(images[i:i + rows], outer, mkey, control)
              for i in range(0, images.shape[0], rows)]
        for i in range(s["n"]):
            w = _qlayer(key, mkey, i)
            xs = [_block(x, w, mkey, control) for x in xs]
        return np.concatenate([np.asarray(_head(x, outer, control))
                               for x in xs])
