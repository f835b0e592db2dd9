"""Plain float32 reference for h2o-danube-1.8b (Mistral architecture,
arXiv:2401.16818) with weight-only MXFP4 linears, and the seeded weights
that both this reference and the served model are made from.

The model as configured: token embedding in bf16; per layer a pre-norm
(RMSNorm) grouped-query attention block with half-split RoPE and a causal
sliding window, and a pre-norm SwiGLU block; final RMSNorm and an untied
output head. Every linear weight is MXFP4 (OCP MX: 32-element blocks along
the input axis, E2M1 elements, one power-of-two scale per block whose
exponent is floor(log2(block amax)) - 2, round to nearest even, clamp at
6). This file quantizes the weights itself; it imports nothing of the
system under test.

Activations are float32 at ``highest`` matmul precision. ``control=True``
rounds every activation the served model keeps in bf16 to float8 e4m3
instead: the next precision below the configuration's bf16.

Weights are drawn layer by layer from the seed, so the reference builds
one layer at a time and never holds the whole model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")
FP8_MAX = 448.0


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (more than 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def dims(m: dict) -> dict:
    d, h = m["hidden_size"], m["num_attention_heads"]
    hd = m.get("head_dim") or d // h
    return {"d": d, "h": h, "kv": m["num_key_value_heads"], "hd": hd,
            "f": m["intermediate_size"], "v": m["vocab_size"],
            "n": m["num_hidden_layers"]}


def leaf_shapes(m: dict) -> dict:
    s = dims(m)
    d, q, kv, f = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"], s["f"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w1": (d, f), "w3": (d, f), "w2": (f, d)}


def layer_weights(key, m: dict, i) -> dict:
    """Float32 weights of layer ``i`` ([in, out], std 1/sqrt(in))."""
    k = jax.random.fold_in(key, i)
    return {
        name: jax.random.normal(jax.random.fold_in(k, j), shape, F32)
        * shape[0] ** -0.5
        for j, (name, shape) in enumerate(leaf_shapes(m).items())
    }


def embed_weights(key, m: dict) -> jax.Array:
    s = dims(m)
    return jax.random.normal(jax.random.fold_in(key, 1_000_001),
                             (s["v"], s["d"]), F32) * s["d"] ** -0.5


def head_weights(key, m: dict) -> jax.Array:
    s = dims(m)
    return jax.random.normal(jax.random.fold_in(key, 1_000_002),
                             (s["d"], s["v"]), F32) * s["d"] ** -0.5


def mxfp4(w: jax.Array) -> jax.Array:
    """Quantize-dequantize ``w`` [K, N] in 32-blocks along K."""
    k, n = w.shape
    b = w.reshape(k // 32, 32, n)
    amax = jnp.max(jnp.abs(b), axis=1, keepdims=True)
    _, ex = jnp.frexp(amax)  # amax = frac * 2^ex, frac in [0.5, 1)
    scale = jnp.clip(ex - 1 - 2, -127, 125)  # floor(log2 amax) - 2
    y = jnp.ldexp(jnp.abs(b), -scale)
    step = jnp.where(y < 2.0, 0.5, jnp.where(y < 4.0, 1.0, 2.0))
    q = jnp.minimum(jnp.round(y / step) * step, 6.0)
    out = jnp.where(amax > 0, jnp.sign(b) * jnp.ldexp(q, scale), 0.0)
    return out.reshape(k, n)


def _round(x, control: bool):
    if not control:
        return x
    x = jnp.clip(x, -FP8_MAX, FP8_MAX)
    return x.astype(jnp.float8_e4m3fn).astype(F32)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """Half-split rotary embedding on x [B, L, H, D]."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos[..., None].astype(F32) * inv  # [B, L, D/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(1,))
def _qlayer(key, mkey, i):
    m = dict(mkey)
    return {k: mxfp4(v) for k, v in layer_weights(key, m, i).items()}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block(x, w, mkey, control):
    m = dict(mkey)
    s = dims(m)
    r = functools.partial(_round, control=control)
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    window = m.get("sliding_window") or 0
    b, n, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(n)[None], (b, n))
    xn = r(_rmsnorm(x, eps))
    q = r(xn @ w["wq"]).reshape(b, n, s["h"], s["hd"])
    k = r(xn @ w["wk"]).reshape(b, n, s["kv"], s["hd"])
    v = r(xn @ w["wv"]).reshape(b, n, s["kv"], s["hd"])
    q, k = r(_rope(q, pos, theta)), r(_rope(k, pos, theta))
    g = s["h"] // s["kv"]
    qi, ki = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    mask = ki <= qi
    if window:
        mask &= ki > qi - window

    def attend(args):  # one sequence at a time bounds the score memory
        qb, kb, vb = args
        qb = qb.reshape(n, s["kv"], g, s["hd"])
        sc = jnp.einsum("qhgd,khd->hgqk", qb, kb) * s["hd"] ** -0.5
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, vb).reshape(n, -1)

    o = r(jax.lax.map(attend, (q, k, v)))
    x = r(x + r(o @ w["wo"]))
    xn = r(_rmsnorm(x, eps))
    hdn = r(r(jax.nn.silu(r(xn @ w["w1"]))) * r(xn @ w["w3"]))
    return r(x + r(hdn @ w["w2"]))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _logits(x, rows, head, mkey, control):
    m = dict(mkey)
    h = _round(_rmsnorm(x.reshape(-1, x.shape[-1])[rows], m["rms_norm_eps"]),
               control)
    return _round(h @ head, control)


def logits_at(seed: int, m: dict, seqs: list, reads: list,
              control: bool = False) -> list:
    """Teacher-forced logits: ``seqs`` are token-id arrays, ``reads[j]``
    the positions of ``seqs[j]`` whose next-token logits are wanted.
    Returns one float32 [len(reads[j]), vocab] array per sequence."""
    key = base_key(seed)
    mkey = tuple(sorted(m.items()))
    s = dims(m)
    b = len(seqs)
    n = -(-max(len(t) for t in seqs) // 128) * 128
    ids = np.zeros((b, n), np.int32)
    for j, t in enumerate(seqs):
        ids[j, : len(t)] = t
    rows = np.concatenate([j * n + np.asarray(r, np.int64)
                           for j, r in enumerate(reads)])
    with jax.default_matmul_precision("highest"):
        emb = embed_weights(key, m).astype(jnp.bfloat16).astype(F32)
        x = _round(emb[jnp.asarray(ids)], control)
        del emb
        for i in range(s["n"]):
            x = _block(x, _qlayer(key, mkey, i), mkey, control)
        head = jax.jit(mxfp4)(head_weights(key, m))
        out = np.asarray(_logits(x, jnp.asarray(rows), head, mkey, control))
    splits = np.cumsum([len(r) for r in reads])[:-1]
    return np.split(out, splits)
