"""The main paths' Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered with ``interpret=False`` for a chip
that is described, not attached, so the TPU compiler refuses here what it
would refuse on the chip (block tilings, vector layouts, accumulator
types) — faults that interpret mode hides. Shapes come from the published
configurations: ViT-B/16's M=197 linears and head, gemma3-1b's MLP,
h2o-danube-1.8b's five linears at its decode and prefill rows, and the
paged decode pools of gemma3-1b and h2o-danube.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs as C
from repro.core import cim as cimlib
from repro.core import mx as mxlib
from repro.kernels.cim_linear import ops as cim_ops
from repro.kernels.mxfp4_matmul import ops as mm_ops
from repro.kernels.paged_attention import layout
from repro.kernels.paged_attention import ops as paged_ops

LANES, SLOTS = 4, 6  # decode lanes, pool pages (the serve defaults)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compiles cannot be read back from the persistent
    # cache; keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiles_to_kernel(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


VIT = C.VISION_ARCHS["vit-b16"]
GEMMA = C.ARCHS["gemma3-1b"]
DANUBE = C.ARCHS["h2o-danube-1.8b"]
VIT_LINEARS = [(VIT.seq_len, VIT.d_model, 3 * VIT.d_model),  # 768 -> 2304
               (VIT.seq_len, VIT.d_model, VIT.d_ff),  # 768 -> 3072
               (VIT.seq_len, VIT.d_ff, VIT.d_model),  # 3072 -> 768
               (1, VIT.d_model, VIT.n_classes)]  # CLS head: 1000 classes
GEMMA_MLP = [(m, k, n) for m in (LANES, 200)  # decode and prefill rows
             for k, n in ((GEMMA.d_model, GEMMA.d_ff),  # 1152 -> 6912
                          (GEMMA.d_ff, GEMMA.d_model))]  # 6912 -> 1152
# q and o (2560 -> 2560), k and v (2560 -> 640), gate and up
# (2560 -> 6912), down (6912 -> 2560), head (2560 -> 32000); at the decode
# rows of 12 and 32 lanes, the 256-token prefill and the 512-token chunk
DANUBE_LINEARS = [
    (m, k, n) for m in (12, 32, 256, 512)
    for k, n in ((DANUBE.d_model, DANUBE.n_heads * DANUBE.hd),
                 (DANUBE.d_model, DANUBE.n_kv_heads * DANUBE.hd),
                 (DANUBE.d_model, DANUBE.d_ff),
                 (DANUBE.d_ff, DANUBE.d_model),
                 (DANUBE.d_model, DANUBE.vocab_size))]


@pytest.mark.parametrize("m,k,n", VIT_LINEARS)
def test_cim_linear_compiles(one_chip, m, k, n):
    def fn(x, codes, exps, e_n, fs):
        return cim_ops.cim_linear(
            x, mxlib.MXW(codes, exps), cimlib.LayerCalib(e_n, fs),
            interpret=False,
        )

    _compiles_to_kernel(
        one_chip, fn, ((m, k), jnp.bfloat16), ((k, n), jnp.int8),
        ((k // 32, n), jnp.int8), ((), jnp.int32), ((), jnp.float32),
    )


@pytest.mark.parametrize("m,k,n", VIT_LINEARS + GEMMA_MLP + DANUBE_LINEARS)
def test_mxfp4_matmul_compiles(one_chip, m, k, n):
    """One kernel per linear, named for the kernel: the benchmark's
    roofline shares divide by one ``mxfp4_matmul`` event per call."""
    hlo = _compiles_to_kernel(
        one_chip,
        lambda x, c, e: mm_ops.mxfp4_matmul(x, c, e, interpret=False),
        ((m, k), jnp.bfloat16), ((k // 2, n), jnp.uint8),
        ((k // 32, n), jnp.uint8),
    )
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert "mxfp4_matmul" in calls[0].split("=", 1)[0]


def _pool(cfg, init):
    w = min(cfg.window or 512, 512)  # the engine keeps a page in the window
    tree = jax.eval_shape(lambda: init(SLOTS + LANES, w, cfg.n_kv_heads,
                                       cfg.hd))
    return {k: (v.shape, v.dtype) for k, v in tree.items()}


def _q(cfg):
    return (LANES, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd)


@pytest.mark.parametrize("cfg", [GEMMA, DANUBE], ids=lambda c: c.name)
def test_paged_decode_float_compiles(one_chip, cfg):
    pool = _pool(cfg, layout.fused_cache_init)

    def fn(q, kv, rows, lens):
        return paged_ops.ragged_paged_decode(
            q, rows, lens, kv=kv, scale=cfg.hd ** -0.5,
            use_pallas=True, interpret=False,
        )

    _compiles_to_kernel(
        one_chip, fn, (_q(cfg), jnp.bfloat16), pool["kv"],
        ((LANES,), jnp.int32), ((LANES,), jnp.int32),
    )


def test_paged_decode_mx_compiles(one_chip):
    cfg = GEMMA
    pool = _pool(cfg, layout.fused_quant_init)

    def fn(q, codes, k_exps, v_exps, rows, lens):
        quant = {"kv_codes": codes, "k_exps": k_exps, "v_exps": v_exps}
        return paged_ops.ragged_paged_decode(
            q, rows, lens, quant=quant, scale=cfg.hd ** -0.5,
            use_pallas=True, interpret=False,
        )

    _compiles_to_kernel(
        one_chip, fn, (_q(cfg), jnp.bfloat16), pool["kv_codes"],
        pool["k_exps"], pool["v_exps"], ((LANES,), jnp.int32),
        ((LANES,), jnp.int32),
    )
