"""Pluggable linear-execution backends (the paper's hybrid partition).

Every quantized-linear in the model stack executes through a *backend*
registered here instead of an inline ``ctx.quant`` string-``if`` chain.
A backend owns three things:

- ``forward(ctx, params, x)``: the matmul numerics (pure-jnp reference and
  a Pallas implementation selected by ``ctx.impl``, with the kernel
  ``interpret`` flag threaded from ``ctx.interpret``),
- ``convert(params, ...)``: the offline serving transform of one linear
  param node (e.g. packed MXFP4 codes, or resident INT5 codes + exps +
  Row-Hist calibration for the analog CTT array),
- ``handles(params)``: the converted-param marker, so serving trees
  dispatch by what is resident rather than by context string.

Registered backends:

==================  =======================================================
``float_bf16``      unquantized BF16 matmul (training/eval baseline)
``mxfp4_ste``       QAT fake-quant of weights + activations (STE)
``mxfp4_ste_prequant``  activations fake-quantized per call; weights were
                    fake-quantized once at the step boundary
``mxfp4_wonly``     weight-only packed MXFP4 (4.25 b/param FWS serving)
``cim_analog``      analog CTT-CIM array: resident INT5 codes, per-block
                    exponents, Row-Hist ``LayerCalib`` (paper §3, §5.2.2)
==================  =======================================================

``ctx.quant`` aliases: ``"none" -> float_bf16``, ``"cim" -> cim_analog``.
Unknown names raise ``ValueError`` (no silent float fallthrough).

The hybrid analog/digital split (paper §4): *static* dense linears
(QKV/O projections, FFN up/gate/down, shared-block projections, LM head)
convert to ``cim_analog`` resident arrays; *dynamic* compute (SDPA, MoE
expert dispatch) stays on the digital MXFP4 path.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cim as cimlib
from repro.core import mx as mxlib


# ----------------------------------------------------------- param packing

# byte -> two bf16 code values; shared with the paged-attention kernel's
# in-tile KV dequant (repro.kernels.paged_attention), which decodes the
# same nibble packing inside VMEM
_PAIR_TABLE = mxlib.PAIR_TABLE


def _dequant_packed(codes: jax.Array, exps: jax.Array) -> jax.Array:
    """packed uint8 codes [K//2, N] + biased exps [K//32, N] -> bf16 [K, N].

    All-bf16 arithmetic: codes/2 and 2^e are exactly representable in
    bf16, so this is bit-identical to the f32 path while cutting the
    dequant intermediate traffic ~3x (decode is weight-read bound —
    EXPERIMENTS.md §Perf; the Pallas kernel removes even this by
    expanding inside VMEM). Each byte decodes through the u32 pair table
    (:data:`repro.core.mx.PAIR_TABLE`) in one gather."""
    kp2, n = codes.shape[-2], codes.shape[-1]
    k = kp2 * 2
    pair = jnp.asarray(_PAIR_TABLE)[codes.astype(jnp.int32)]  # [..., K//2, N]
    u16 = jax.lax.bitcast_convert_type(pair, jnp.uint16)  # [..., 2] LE: 0=lo
    cb = jax.lax.bitcast_convert_type(u16, jnp.bfloat16)
    cb = jnp.swapaxes(cb, -1, -2).reshape(codes.shape[:-2] + (k, n))
    scale = mxlib.exp2i(mxlib.exps_from_biased(exps) - 1).astype(
        jnp.bfloat16
    )  # 2^(e-1) == 0.5 * 2^e, exact
    w = cb.reshape(codes.shape[:-2] + (k // 32, 32, n)) * scale[..., :, None, :]
    return w.reshape(codes.shape[:-2] + (k, n))


def _quantize_packed(w: jax.Array) -> dict:
    """[..., K, N] float -> packed MXFP4 {codes [..., K//2, N] uint8,
    exps [..., K//32, N] uint8} quantized along K."""
    mxq = mxlib.quantize(jnp.swapaxes(w, -1, -2))
    codes = jnp.swapaxes(mxq.codes, -1, -2)
    packed = jnp.swapaxes(
        mxlib.pack_codes(jnp.swapaxes(codes, -1, -2)), -1, -2
    )
    exps = mxlib.exps_to_biased(jnp.swapaxes(mxq.exps, -1, -2))
    return {"codes": packed, "exps": exps}


def quantize_linear_params(params: dict) -> dict:
    """Convert a float linear param dict to packed MXFP4 (weight-only)."""
    out = _quantize_packed(params["w"])
    if "b" in params:
        out["b"] = params["b"]
    return out


# --------------------------------------------------------------- registry

class LinearBackend:
    """Base class: one linear-execution strategy."""

    name = "?"

    def handles(self, params: dict) -> bool:
        """True if ``params`` is this backend's converted serving node."""
        return False

    def convert(self, params: dict, **kw) -> dict:
        return params

    def forward(self, ctx, params: dict, x: jax.Array) -> jax.Array:
        raise NotImplementedError


_REGISTRY: dict[str, LinearBackend] = {}
# "mxfp4_digital" is the fully-digital MXFP4 accelerator eval mode: W+A
# fake-quant linears (same numerics as the STE training forward) plus the
# digital MXFP4 SDPA — the apples-to-apples baseline for the hybrid
# analog path (RunCtx.hybrid_digital_sdpa covers both).
_ALIASES = {
    "none": "float_bf16",
    "cim": "cim_analog",
    "mxfp4_digital": "mxfp4_ste",
}


def register_backend(backend: LinearBackend) -> LinearBackend:
    _REGISTRY[backend.name] = backend
    return backend


def backend_names() -> list[str]:
    return sorted(_REGISTRY)


def get_backend(name: str) -> LinearBackend:
    key = _ALIASES.get(name, name)
    if key not in _REGISTRY:
        raise ValueError(
            f"unknown linear-execution backend {name!r}; known: "
            f"{backend_names()} (aliases {sorted(_ALIASES)})"
        )
    return _REGISTRY[key]


def resolve_backend(ctx, params: dict) -> LinearBackend:
    """Converted-param markers win (what is resident in the array decides
    execution); otherwise ``ctx.quant`` names the backend. Raises
    ``ValueError`` on an unknown name."""
    for marker in ("cim_analog", "mxfp4_wonly"):
        b = _REGISTRY[marker]
        if b.handles(params):
            return b
    return get_backend(ctx.quant)


def cim_config(ctx) -> cimlib.CIMConfig:
    """The CIM array config for this run (paper operating point when the
    context does not override it: 10b ADC, CM=3, Row-Hist 2-pass)."""
    return ctx.cim if getattr(ctx, "cim", None) is not None else cimlib.CIMConfig()


# --------------------------------------------------------------- backends

def _register(cls):
    register_backend(cls())
    return cls


@_register
class _FloatBF16(LinearBackend):
    name = "float_bf16"

    def forward(self, ctx, params, x):
        return jnp.matmul(x.astype(jnp.bfloat16), params["w"].astype(jnp.bfloat16))


@_register
class _MXFP4STE(LinearBackend):
    name = "mxfp4_ste"

    def forward(self, ctx, params, x):
        wq = mxlib.fake_quant_axis(params["w"], axis=0)
        xq = mxlib.fake_quant(x.astype(jnp.float32))
        return jnp.matmul(xq.astype(jnp.bfloat16), wq.astype(jnp.bfloat16))


@_register
class _MXFP4STEPrequant(LinearBackend):
    name = "mxfp4_ste_prequant"

    def forward(self, ctx, params, x):
        # weights were fake-quantized once at the step boundary (exact:
        # weights are constant within a step) — gathers move bf16 instead
        # of f32 and the quant ops run once, not k_micro times
        xq = mxlib.fake_quant(x.astype(jnp.float32))
        return jnp.matmul(xq.astype(jnp.bfloat16), params["w"].astype(jnp.bfloat16))


@_register
class _MXFP4WeightOnly(LinearBackend):
    name = "mxfp4_wonly"

    def handles(self, params):
        return "codes" in params and "e_n" not in params

    def convert(self, params, **kw):
        return quantize_linear_params(params)

    def forward(self, ctx, params, x):
        if "codes" not in params:
            # not yet converted (eval on a float tree): weight-only quant
            # happens at convert time, so this is the plain bf16 matmul
            return _REGISTRY["float_bf16"].forward(ctx, params, x)
        if ctx.use_pallas:
            from repro.kernels.mxfp4_matmul import ops as mmops

            # bf16 activations, as on the jnp path below (the ViT's patch
            # embedding is the one caller that hands over f32 pixels)
            return mmops.mxfp4_matmul(
                x.astype(jnp.bfloat16), params["codes"], params["exps"],
                interpret=ctx.interpret,
            )
        w = _dequant_packed(params["codes"], params["exps"])
        return jnp.matmul(x.astype(jnp.bfloat16), w)


@_register
class _CIMAnalog(LinearBackend):
    """Analog CTT-CIM array execution of a static linear.

    Converted node layout (all jax arrays, scan-stackable along a leading
    layer axis): ``codes`` int8 [K, N] signed INT5 weight codes resident in
    the array, ``exps`` int8 [K//32, N] per-block weight exponents,
    ``e_n`` int32 [] Row-Hist target exponent, ``adc_fs`` f32 [] calibrated
    ADC full scale, optional ``b`` (digital bias add after read-out).
    """

    name = "cim_analog"

    def handles(self, params):
        return "e_n" in params

    def convert(self, params, calib: cimlib.LayerCalib,
                wq: mxlib.MXW | None = None, **kw):
        # the converted node is independent of the CIMConfig operating
        # point — only the LayerCalib (computed under a config) carries it
        if wq is None:
            wq = mxlib.quantize_w(params["w"].astype(jnp.float32))
        out = {
            "codes": wq.codes,
            "exps": wq.exps,
            "e_n": jnp.asarray(calib.e_n, jnp.int32),
            "adc_fs": jnp.asarray(calib.adc_fs, jnp.float32),
        }
        if "b" in params:
            out["b"] = params["b"].astype(jnp.bfloat16)
        return out

    def forward(self, ctx, params, x):
        if "e_n" not in params:
            # hybrid partition: linears without a resident analog copy
            # (uncalibrated / too small, e.g. routers and SSM projections)
            # execute on the digital MXFP4 W+A path — same numerics as the
            # fully-digital baseline, so hybrid-vs-digital deltas isolate
            # the analog layers
            return _REGISTRY["mxfp4_ste"].forward(ctx, params, x)
        cfg = cim_config(ctx)
        w = mxlib.MXW(params["codes"], params["exps"])
        calib = cimlib.LayerCalib(e_n=params["e_n"], adc_fs=params["adc_fs"])
        if ctx.use_pallas:
            from repro.kernels.cim_linear import ops as cim_ops

            y = cim_ops.cim_linear(
                x, w, calib, cfg=cfg, interpret=ctx.interpret
            )
        else:
            y, _ = cimlib.cim_linear(x, w, cfg, calib)
        return y.astype(jnp.bfloat16)


# --------------------------------------------------- MoE expert weights

def expert_weight(ctx, w) -> jax.Array:
    """Resolve a stacked [E, K, N] expert weight for the digital expert
    einsum. MoE experts stay on the digital MXFP4 path under every backend
    (expert dispatch is dynamic — the paper's hybrid partition keeps only
    static-weight linears in the analog array). Validates ``ctx.quant``
    against the registry, so unknown names raise instead of silently
    running float."""
    if isinstance(w, dict):  # serving-converted packed MXFP4
        return jax.vmap(_dequant_packed)(w["codes"], w["exps"])
    backend = get_backend(ctx.quant)  # raises on unknown backend names
    if backend.name in ("mxfp4_ste", "cim_analog"):
        # digital MXFP4 W+A numerics; under the hybrid backend an
        # unconverted expert bank must still quantize digitally so
        # hybrid-vs-digital deltas isolate the analog layers
        w = mxlib.fake_quant_axis(w, axis=1)
    # "mxfp4_ste_prequant": already quantized at the step boundary
    return w.astype(jnp.bfloat16)


# --------------------------------------------------- Row-Hist calibration

@dataclasses.dataclass
class ActivationTap:
    """Records per-linear input activations during an *eager* capture run.

    ``linear_apply`` calls :meth:`record` with the param-tree path of the
    linear (built from ``RunCtx.scoped`` scopes + the call-site name) when a
    tap is active on the context. Only static analog candidates are kept:
    2-D weights with a 32-aligned contraction dim and a wide-enough output
    dim. Rows are subsampled to ``max_rows`` per call to bound memory.

    Captures stay *on device*: ``record`` only slices/casts (async under
    the eager capture run — no ``jax.device_get`` host sync per linear per
    batch mid-forward); the single host transfer happens when
    ``calibrate_taps`` consumes the records.
    """

    min_n: int = 256
    max_rows: int = 512
    records: dict = dataclasses.field(default_factory=dict)
    weights: dict = dataclasses.field(default_factory=dict)
    # also capture at already-converted serving nodes (resident CIM codes
    # or packed MXFP4) — the SQNR tracer runs the same tap over the
    # converted tree and compares captures path-by-path against a float
    # reference run; calibration keeps the default (float-only) gate
    include_converted: bool = False

    def _in_dim(self, params) -> int | None:
        """Contraction dim of a capturable linear node, else None."""
        if not isinstance(params, dict):
            return None
        w = params.get("w")
        if getattr(w, "ndim", 0) == 2:
            k, n = w.shape
            return k if k % mxlib.BLOCK == 0 and n >= self.min_n else None
        if not self.include_converted:
            return None
        c = params.get("codes")
        if getattr(c, "ndim", 0) != 2:
            return None
        # cim_analog: int8 codes [K, N]; mxfp4_wonly: packed nibble pairs
        # [K//2, N]
        k = c.shape[0] * (1 if "e_n" in params else 2)
        return k if c.shape[1] >= self.min_n else None

    def eligible(self, params) -> bool:
        return self._in_dim(params) is not None

    def record(self, path: str, params: dict, x: jax.Array) -> None:
        k = self._in_dim(params)
        if k is None:
            return
        xf = x.astype(jnp.float32).reshape(-1, k)
        if xf.shape[0] > self.max_rows:
            # deterministic in shape: ref and instrumented runs of the
            # same batch subsample identical rows, so captures compare
            idx = np.linspace(0, xf.shape[0] - 1, self.max_rows).astype(int)
            xf = jnp.take(xf, jnp.asarray(idx), axis=0)
        self.records.setdefault(path, []).append(xf)
        if "w" in params:
            self.weights[path] = params["w"]


def calibrate_taps(
    tap: ActivationTap,
    cfg: cimlib.CIMConfig | None = None,
    wq_cache: dict | None = None,
) -> dict[str, cimlib.LayerCalib]:
    """Offline Row-Hist calibration (paper §3.2.1) of every tapped linear:
    per-layer target exponent E_N + ADC full scale from the recorded
    representative activations. The records arrive as device arrays (the
    tap never host-syncs mid-forward) and feed the jitted calibration
    passes directly — no host round-trip at all. Pass a dict as
    ``wq_cache`` to receive the quantized MXW per path, so conversion
    skips re-quantizing."""
    cfg = cfg or cimlib.CIMConfig()
    out = {}
    for path, xs in tap.records.items():
        wq = mxlib.quantize_w(
            jnp.asarray(tap.weights[path]).astype(jnp.float32)
        )
        if wq_cache is not None:
            wq_cache[path] = wq
        out[path] = cimlib.calibrate_rowhist(list(xs), wq, cfg)
    return out


def _stacked_keys(path: str, n_layers: int) -> list[str]:
    """Capture keys for a layer-stacked param node: the unrolled capture
    run scopes each layer as ``segments/<i>/L<j>/...`` while the stacked
    tree path is ``segments/<i>/...``."""
    parts = path.split("/")
    if len(parts) < 2 or parts[0] != "segments":
        return []
    return [
        "/".join(parts[:2] + [f"L{j}"] + parts[2:]) for j in range(n_layers)
    ]


def convert_params_cim(
    tree,
    calibs: dict[str, cimlib.LayerCalib],
    min_n: int = 256,
    wq_cache: dict | None = None,
):
    """Serving transform for the hybrid analog/digital deployment.

    Static linears with Row-Hist calibration (keyed by param-tree path,
    from :func:`calibrate_taps`) become resident ``cim_analog`` nodes —
    INT5 codes + block exponents + per-layer calib, stacked along the layer
    axis for scanned segments so ``lax.scan`` slices per-layer calibration
    exactly like the weights. MoE expert banks become packed digital MXFP4
    (dynamic dispatch stays digital); everything else is cast to bf16.
    """
    cim = _REGISTRY["cim_analog"]
    wq_cache = wq_cache or {}

    def convert_stacked(node, path):
        w = node["w"]
        n_layers = w.shape[0]
        keys = _stacked_keys(path, n_layers)
        if not keys or not all(k in calibs for k in keys):
            return None
        per = []
        for j, key in enumerate(keys):
            nj = {"w": w[j]}
            if "b" in node:
                nj["b"] = node["b"][j]
            per.append(cim.convert(nj, calibs[key],
                                   wq=wq_cache.get(key)))
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per)

    def rec(node, path):
        if isinstance(node, dict):
            w = node.get("w")
            if (
                getattr(w, "ndim", 0) == 2
                and w.shape[0] % mxlib.BLOCK == 0
                and w.shape[1] >= min_n
                and path in calibs
            ):
                return cim.convert(node, calibs[path],
                                   wq=wq_cache.get(path))
            if (
                getattr(w, "ndim", 0) == 3
                and w.shape[1] % mxlib.BLOCK == 0
                and w.shape[2] >= min_n
            ):
                conv = convert_stacked(node, path)
                if conv is not None:
                    return conv
            out = {}
            for k, v in node.items():
                if (
                    k in ("w1", "w2", "w3")
                    and getattr(v, "ndim", 0) in (3, 4)
                    and v.shape[-2] % mxlib.BLOCK == 0
                ):
                    out[k] = _quantize_packed(v)  # digital FWS experts
                else:
                    out[k] = rec(v, f"{path}/{k}" if path else k)
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(
                rec(v, f"{path}/{i}" if path else str(i))
                for i, v in enumerate(node)
            )
        if hasattr(node, "dtype") and node.dtype == jnp.float32:
            return node.astype(jnp.bfloat16)
        return node

    return rec(tree, "")
