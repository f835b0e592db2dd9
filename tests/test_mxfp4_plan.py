"""The MXFP4 matmul's tile plan against what the benchmark counts, the
cim kernel's tiles, and the served programs' operand dtype.

- The rows the kernel executes are the rows the roofline shares count
  (``bench/work/mxfp4_matmul.py::rows``), so those shares stay true.
- ``cim_linear`` keeps its own 128x128 tiles (its tile holds the ADC
  emulation's intermediates); only ``mxfp4_matmul`` plans wide tiles.
- Every ``mxfp4_matmul`` call of h2o-danube-1.8b's decode and chunk
  programs and of ViT-B/16's frame program gets bf16 activations, so each
  takes the bf16-operand path: 169 calls a step (24 layers of 7 linears
  and the head) and 74 a frame (patch embedding, 12 layers of 6, head).
  Nothing runs: the programs are traced at published widths from shapes.
"""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.kernels.cim_linear import ops as cim_ops
from repro.kernels.mxfp4_matmul import ops as mm_ops
from repro.layers.common import RunCtx, ShardingCtx, convert_params_mxfp4
from repro.models import lm, vit
from repro.serving.engine import Engine, EngineConfig
from repro.serving.vision import VisionEngine

DANUBE = C.ARCHS["h2o-danube-1.8b"]
VIT = C.VISION_ARCHS["vit-b16"]
# the benchmark's linears: danube (k, n) and ViT-B/16's
SHAPES = [(2560, 2560), (2560, 640), (2560, 6912), (6912, 2560),
          (2560, 32000), (768, 2304), (768, 3072), (3072, 768), (768, 1000)]


def _bench_work():
    path = (Path(__file__).resolve().parents[1] / "bench" / "work"
            / "mxfp4_matmul.py")
    spec = importlib.util.spec_from_file_location("bench_work_mxfp4", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("m", [1, 12, 32, 197, 256, 512])
def test_executed_rows_are_the_rooflines_rows(m):
    rows = _bench_work().rows(m)
    for k, n in SHAPES:
        p = mm_ops.plan(m, k, n)
        assert p.rows == rows and p.bm == rows  # one M block: decoded once
        assert n % p.bn == 0 and k % p.bk == 0
        assert p.bn % 128 == 0 or p.bn == n
        assert p.bk % 256 == 0 or p.bk == k


def test_m_splits_only_past_the_vmem_budget():
    """A prefill far past any cell's rows: M tiles come back, each a
    multiple of the alignment tile that divides the executed rows."""
    m = 40_000
    p = mm_ops.plan(m, 6912, 2560)
    assert p.rows == _bench_work().rows(m) == 40_064
    assert p.bm < p.rows and p.bm % 128 == 0 and p.rows % p.bm == 0


@pytest.mark.parametrize("k,n,plan", [
    (2560, 2560, (512, 2560)), (2560, 640, (128, 2560)),
    (2560, 6912, (384, 2560)), (6912, 2560, (512, 6912)),
    (2560, 32000, (256, 2560)), (768, 1000, (1000, 768)),
    (1152, 6912, (384, 1152)),
])
def test_wide_tiles_from_shapes(k, n, plan):
    """bn: the widest multiple of 128 dividing N up to 512, N whole where
    128 does not divide it; bk: K whole (gemma3's 1152 too)."""
    for m in (12, 512):
        p = mm_ops.plan(m, k, n)
        assert (p.bn, p.bk) == plan


@pytest.mark.parametrize("m,k,n,tiles", [
    (197, 768, 2304, (128, 128, 256)), (197, 768, 3072, (128, 128, 256)),
    (197, 3072, 768, (128, 128, 512)), (1, 768, 1000, (16, 1000, 256)),
])
def test_cim_linear_keeps_its_tiles(m, k, n, tiles):
    assert (mm_ops.pick_bm(m), cim_ops.pick_bn(n), cim_ops.pick_bk(k)) \
        == tiles


def _matmul_inputs(jaxpr, mult=1):
    """[(x dtype, times executed)] of every ``mxfp4_matmul_kernel`` call
    in a jaxpr, counting a scan body once per iteration."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.params.get("name") == "mxfp4_matmul_kernel":
            found.append((eqn.invars[0].aval.dtype, mult))
            continue
        inner = mult * eqn.params.get("length", 1)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    found += _matmul_inputs(sub, inner)
    return found


def _pallas_ctx():
    return RunCtx(shd=ShardingCtx(), quant="mxfp4_wonly", dense_attn_max=256,
                  impl="pallas", interpret=True)


def _abstract(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def test_danube_programs_feed_bf16_to_every_matmul():
    params = jax.eval_shape(lambda key: convert_params_mxfp4(
        lm.init_model(key, DANUBE)[0]), jax.random.PRNGKey(0))
    eng = Engine(params, DANUBE, _pallas_ctx(),
                 EngineConfig(lanes=2, num_slots=2, page_len=64,
                              prefill_len=32, chunk_len=32,
                              policy="chunked"))
    pool = _abstract(eng.kv.pool)
    i32 = jnp.int32
    decode = jax.make_jaxpr(eng._decode)(
        params, pool, np.zeros(2, i32), np.zeros((2, 1), i32),
        np.zeros(2, i32))
    chunk = jax.make_jaxpr(eng._chunk)(
        params, pool, np.zeros(1, i32), np.zeros((1, 32), i32),
        np.zeros((1, 32), i32), i32(0), i32(31))
    for program in (decode, chunk):
        calls = _matmul_inputs(program.jaxpr)
        assert sum(n for _, n in calls) == 24 * 7 + 1 == 169
        assert {dt for dt, _ in calls} == {jnp.dtype(jnp.bfloat16)}


def test_vit_frame_program_feeds_bf16_to_every_matmul():
    params = jax.eval_shape(lambda key: convert_params_mxfp4(
        vit.init_model(key, VIT)[0]), jax.random.PRNGKey(0))
    eng = VisionEngine(params, VIT, _pallas_ctx())
    frame = jax.make_jaxpr(eng._chain[0][0])(
        params, jax.ShapeDtypeStruct((1, VIT.image_size, VIT.image_size, 3),
                                     jnp.float32))
    calls = _matmul_inputs(frame.jaxpr)
    assert sum(n for _, n in calls) == 1 + 12 * 6 + 1 == 74
    assert {dt for dt, _ in calls} == {jnp.dtype(jnp.bfloat16)}
