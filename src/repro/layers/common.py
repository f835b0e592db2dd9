"""Shared layer utilities: sharding context, quantized linear, norms.

Parameters are plain nested dicts of ``jax.Array``; every ``*_init``
returns ``(params, specs)`` where ``specs`` mirrors the params tree with
tuples of *logical* axis names (resolved to mesh axes by
``repro.distributed.sharding``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import kernels as kernels_lib
from repro.core import mx as mxlib
from repro.layers import backends as backends_lib
from repro.layers.backends import (  # noqa: F401  (re-exported API)
    ActivationTap,
    _dequant_packed,
    _quantize_packed,
    backend_names,
    calibrate_taps,
    convert_params_cim,
    get_backend,
    quantize_linear_params,
    register_backend,
    resolve_backend,
)


# --------------------------------------------------------------- sharding

DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "expert_mlp": None,
    "kv_seq": None,  # attention K/V sequence axis (SP when heads unshardable)
    "cache_seq": None,  # resident KV-cache sequence axis (flash-decoding)
    "state_heads": None,  # SSM/xLSTM state head axis
    "qkv_fused": None,
    "kv_fused": None,
    "heads_g": None,
    "exp_group": ("pod", "data"),  # grouped MoE dispatch (per DP shard)
    "exp_e": None,  # replicated expert axis around dispatch/combine
    "exp_cap": None,
    "conv": None,
    "state": None,
    "zero": None,
    "layers": None,
    "replicated": None,
}


@dataclasses.dataclass
class ShardingCtx:
    """Resolves logical axis names to mesh axes and applies activation
    sharding constraints. With ``mesh=None`` everything is a no-op (single
    device smoke tests)."""

    mesh: Any = None
    rules: dict[str, Any] = dataclasses.field(default_factory=dict)

    def resolve(self, logical_axes) -> P:
        names = []
        used = set()
        for ax in logical_axes:
            r = self.rules.get(ax, DEFAULT_RULES.get(ax)) if ax else None
            if isinstance(r, (list, tuple)):
                r = tuple(a for a in r if self.mesh and a in self.mesh.axis_names)
                r = tuple(a for a in r if a not in used) or None
            elif r is not None:
                if self.mesh is not None and r not in self.mesh.axis_names:
                    r = None
                if r in used:
                    r = None
            if r is not None:
                used.update(r if isinstance(r, tuple) else (r,))
            names.append(r)
        return P(*names)

    def act(self, x: jax.Array, *logical_axes) -> jax.Array:
        """Apply a sharding constraint to an activation."""
        if self.mesh is None:
            return x
        assert len(logical_axes) == x.ndim, (logical_axes, x.shape)
        spec = self.resolve(logical_axes)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec)
        )


@dataclasses.dataclass(frozen=True)
class RunCtx:
    """Per-call context threaded through model apply functions.

    ``quant`` names a linear-execution backend from
    ``repro.layers.backends`` (aliases: ``none -> float_bf16``,
    ``cim -> cim_analog``); unknown names raise ``ValueError`` at the first
    linear. ``impl`` selects the linear execution engine: ``"auto"`` (the
    default) runs compiled Pallas kernels on real accelerators and the
    pure-jnp reference on CPU (see :meth:`use_pallas`); ``"jnp"`` /
    ``"pallas"`` force one side. ``interpret`` is threaded into every
    ``pallas_call``; its default is platform-derived (True only on CPU,
    where there is no Mosaic lowering) so TPU runs never silently
    interpret.
    """

    shd: ShardingCtx
    quant: str = "none"  # backend name: none|mxfp4_ste|mxfp4_ste_prequant|mxfp4_wonly|cim
    impl: str = "auto"  # auto | jnp | pallas
    interpret: bool = dataclasses.field(
        default_factory=kernels_lib.default_interpret
    )  # Pallas interpret mode (platform default: True only on CPU)
    decode: bool = False
    attn_chunk: int = 1024  # KV chunk for the online-softmax path
    q_chunk: int = 2048
    dense_attn_max: int = 2048  # below this seq len use the dense path
    unroll_scans: bool = False  # blockwise cost analysis: count loop trips
    cim: Any = None  # CIMConfig override for the cim_analog backend
    tap: Any = None  # ActivationTap during eager calibration capture
    scope: str = ""  # param-tree path prefix while a tap is active
    # Unroll scanned layer stacks into a Python loop. XLA fuses the whole
    # scan body into one computation, and 1-ulp fusion differences in
    # log2/div flip MXFP4 codes at rounding boundaries — so cross-graph
    # numerics-identity checks (analog vs digital) are only bitwise under
    # unrolled op-by-op execution. Implied by an active tap.
    unroll_layers: bool = False
    # int32 [batch] pool row per lane for the fused paged-KV decode path
    # (None: lane i reads cache row i). Threaded *inside* the traced step
    # via dataclasses.replace — an array field, so a RunCtx carrying it
    # must never be closed over as a static value.
    paged_rows: Any = None
    paged_buffers: int = 0  # DMA ring depth override for the paged kernel (0: auto)
    # Numerical-fidelity probe (repro.obs.FidelityProbe) during an eager
    # instrumented run: per-layer MXFP4/ADC health keyed by the same
    # scoped paths as calibration. Host-side Python object — only ever
    # closed over, never traced; implies unrolled layer execution like an
    # active tap. None (the default) leaves the hot path untouched.
    fidelity: Any = None

    def act(self, x, *axes):
        return self.shd.act(x, *axes)

    def scoped(self, name: str) -> "RunCtx":
        """Extend the capture scope. No-op (returns self) unless an
        ActivationTap or FidelityProbe is active, so traced paths never
        pay for it."""
        if self.tap is None and self.fidelity is None:
            return self
        return dataclasses.replace(
            self, scope=f"{self.scope}/{name}" if self.scope else name
        )

    @property
    def use_pallas(self) -> bool:
        """Linear-engine dispatch: ``impl="auto"`` selects compiled Pallas
        on TPU and the jnp reference elsewhere (the kernels are
        Mosaic/TPU kernels — on CPU/GPU they would only run under the
        slow interpreter)."""
        if self.impl == "auto":
            return jax.default_backend() == "tpu"
        return self.impl == "pallas"

    @property
    def hybrid_digital_sdpa(self) -> bool:
        """Under the hybrid analog backend (and the fully-digital MXFP4
        eval mode), SDPA runs on the digital MXFP4 systolic path (paper
        §4.4-4.5); QKV/O stay analog for ``cim``."""
        return self.quant in ("cim", "cim_analog", "mxfp4_digital")


# ----------------------------------------------------------------- linear

def linear_init(
    key,
    k: int,
    n: int,
    *,
    use_bias: bool = False,
    in_axis: str = "embed",
    out_axis: str = "mlp",
    scale: float | None = None,
):
    scale = (1.0 / k) ** 0.5 if scale is None else scale
    w = jax.random.normal(key, (k, n), jnp.float32) * scale
    params = {"w": w}
    specs = {"w": (in_axis, out_axis)}
    if use_bias:
        params["b"] = jnp.zeros((n,), jnp.float32)
        specs["b"] = (out_axis,)
    return params, specs


def linear_apply(
    ctx: RunCtx, params: dict, x: jax.Array, name: str | None = None
) -> jax.Array:
    """Backend-dispatched linear. x: [..., K] (bf16).

    Execution is resolved by ``repro.layers.backends``: converted-param
    markers (packed MXFP4 codes, resident CIM codes + calib) win, otherwise
    ``ctx.quant`` names the backend; unknown names raise ``ValueError``.
    ``name`` is the call-site's local param key ("wq", "w1", ...) — with an
    active ``ActivationTap`` it extends ``ctx.scope`` into the full
    param-tree path used to key Row-Hist calibration.
    """
    if ctx.tap is not None and name is not None:
        path = f"{ctx.scope}/{name}" if ctx.scope else name
        ctx.tap.record(path, params, x)
    if ctx.fidelity is not None and name is not None:
        path = f"{ctx.scope}/{name}" if ctx.scope else name
        ctx.fidelity.observe_linear(path, ctx, params, x)
    y = backends_lib.resolve_backend(ctx, params).forward(ctx, params, x)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


def is_linear_params(p) -> bool:
    return isinstance(p, dict) and "w" in p and getattr(p["w"], "ndim", 0) == 2


def quantize_weights_tree(tree):
    """Step-boundary weight fake-quant for training ("prequant"): exact
    hoisting of the per-linear fake-quant out of the microbatch loop
    (weights are constant within a step), which also makes every FSDP
    all-gather move bf16 instead of f32 and runs the quant ops once
    instead of k_micro times per step."""

    def rec(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if (
                    k == "w"
                    and getattr(v, "ndim", 0) in (2, 3)  # incl. layer-stacked
                    and v.shape[-2] % 32 == 0
                ):
                    out[k] = mxlib.fake_quant_axis(v, -2).astype(jnp.bfloat16)
                elif (
                    k in ("w1", "w2", "w3")
                    and getattr(v, "ndim", 0) in (3, 4)  # incl. layer-stacked
                    and v.shape[-2] % 32 == 0
                ):
                    out[k] = mxlib.fake_quant_axis(v, -2).astype(jnp.bfloat16)
                else:
                    out[k] = rec(v)
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(rec(v) for v in node)
        if hasattr(node, "dtype") and node.dtype == jnp.float32 and node.ndim >= 2:
            return node.astype(jnp.bfloat16)
        return node

    return rec(tree)


def convert_params_mxfp4(tree, min_n: int = 256):
    """Serving transform: every static linear weight with a 32-aligned
    contraction dim and a wide-enough output dim becomes packed MXFP4
    (4.25 b/param resident, the FWS analogue); remaining float params are
    cast to bf16. Pure jnp — usable under jax.eval_shape for dry-runs."""

    def rec(node):
        if isinstance(node, dict):
            out = {}
            if (
                "w" in node
                and getattr(node["w"], "ndim", 0) in (2, 3)
                and node["w"].shape[-2] % 32 == 0
                and node["w"].shape[-1] >= min_n
            ):
                out.update(quantize_linear_params(node))
                for k, v in node.items():
                    if k not in ("w", "b"):
                        out[k] = rec(v)
                return out
            for k, v in node.items():
                if (
                    k in ("w1", "w2", "w3")
                    and getattr(v, "ndim", 0) in (3, 4)
                    and v.shape[-2] % 32 == 0
                ):
                    out[k] = _quantize_packed(v)
                else:
                    out[k] = rec(v)
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(rec(v) for v in node)
        if hasattr(node, "dtype") and node.dtype == jnp.float32:
            return node.astype(jnp.bfloat16)
        return node

    return rec(tree)


def convert_specs_mxfp4(specs, params_struct, min_n: int = 256):
    """Mirror of convert_params_mxfp4 on the logical-axis spec tree.
    params_struct is the *pre-conversion* shape tree (for the gates)."""

    def rec(spec_node, p_node):
        if isinstance(spec_node, dict):
            out = {}
            if (
                "w" in spec_node
                and getattr(p_node.get("w"), "ndim", 0) in (2, 3)
                and p_node["w"].shape[-2] % 32 == 0
                and p_node["w"].shape[-1] >= min_n
            ):
                out["codes"] = spec_node["w"]
                out["exps"] = spec_node["w"]
                for k, v in spec_node.items():
                    if k == "w":
                        continue
                    out[k] = v if k == "b" else rec(v, p_node[k])
                return out
            for k, v in spec_node.items():
                if (
                    k in ("w1", "w2", "w3")
                    and getattr(p_node.get(k), "ndim", 0) in (3, 4)
                    and p_node[k].shape[-2] % 32 == 0
                ):
                    out[k] = {"codes": v, "exps": v}
                else:
                    out[k] = rec(v, p_node[k])
            return out
        if isinstance(spec_node, (list, tuple)) and not _spec_leaf(spec_node):
            return type(spec_node)(
                rec(v, p) for v, p in zip(spec_node, p_node)
            )
        return spec_node

    return rec(specs, params_struct)


def _spec_leaf(x) -> bool:
    return isinstance(x, tuple) and all(
        isinstance(a, (str, type(None))) for a in x
    )


# ------------------------------------------------------------------ norms

def rmsnorm_init(d: int):
    return {"gamma": jnp.ones((d,), jnp.float32)}, {"gamma": ("embed",)}


def rmsnorm_apply(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * params["gamma"]
    return y.astype(x.dtype)


def layernorm_init(d: int):
    return (
        {"gamma": jnp.ones((d,), jnp.float32), "beta": jnp.zeros((d,), jnp.float32)},
        {"gamma": ("embed",), "beta": ("embed",)},
    )


def layernorm_apply(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * params["gamma"] + params["beta"]
    return y.astype(x.dtype)


def norm_init(kind: str, d: int):
    return rmsnorm_init(d) if kind == "rmsnorm" else layernorm_init(d)


def norm_apply(kind: str, params: dict, x: jax.Array) -> jax.Array:
    return rmsnorm_apply(params, x) if kind == "rmsnorm" else layernorm_apply(params, x)


# ------------------------------------------------------------- embeddings

def embed_init(key, vocab: int, d: int):
    emb = jax.random.normal(key, (vocab, d), jnp.float32) * (d**-0.5)
    return {"emb": emb}, {"emb": ("vocab", "embed")}


def embed_apply(ctx: RunCtx, params: dict, ids: jax.Array) -> jax.Array:
    out = jnp.take(params["emb"].astype(jnp.bfloat16), ids, axis=0)
    return out
