"""Vision subsystem: executable ViT models on the hybrid CIM stack +
image-stream FWS serving.

The crux checks, mirroring ``tests/test_backends.py`` for the encoder
family:

- *Backend invariant*: with a lossless CIM config the hybrid analog ViT
  is numerically identical to the fully digital MXFP4 ViT (unrolled);
  at the paper operating point the float<->cim top-1 agreement on
  synthetic images is bounded and asserted.
- *Pipeline fidelity*: the FWS pipeline steady-state FPS driven by the
  ViT engine's *measured* stage traffic matches PAPER_TABLE7 within 5%
  for vit-b16 (single chip) and vit-l32 (dual chip, 12+12 partition).
- *Encoder attention* (satellite): bidirectional dense-vs-flash equality
  at a non-multiple-of-chunk length (N=197, the ViT-B/16 token count) —
  the KV_PAD masking fix exercised on the non-causal path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.core import cim as cimlib
from repro.core.metrics import sqnr_db
from repro.distributed.sharding import stage_partition
from repro.hwmodel import perf, specs as S
from repro.layers import attention as attn_mod
from repro.layers.common import RunCtx, ShardingCtx, convert_params_mxfp4
from repro.models import calibrate, vit
from repro.serving import pipeline as pipe
from repro.serving.vision import VisionEngine, synthetic_stream_report

CTX = RunCtx(shd=ShardingCtx(), dense_attn_max=256)
TINY = C.tiny_vit(C.VISION_ARCHS["vit-b16"])


@pytest.fixture(scope="module")
def tiny_model():
    params, _ = vit.init_model(jax.random.PRNGKey(0), TINY)
    batches = vit.calibration_images(TINY, n_batches=2, batch=2)
    return params, batches


@pytest.fixture(scope="module")
def cim_tiny(tiny_model):
    params, batches = tiny_model
    cim_cfg = cimlib.CIMConfig()
    conv, calibs = calibrate.convert_model_cim(
        params, TINY, CTX, batches, cim_cfg=cim_cfg, min_n=32,
        forward_fn=vit.forward,
    )
    return conv, calibs, dataclasses.replace(CTX, quant="cim", cim=cim_cfg)


# ------------------------------------------------------------- geometry

def test_configs_match_hwmodel_workloads():
    """The executable configs bill exactly the token traffic the paper's
    analytical model (and Table 7) uses."""
    for name in ("vit-b16", "vit-l32"):
        cfg = C.VISION_ARCHS[name]
        w = S.WORKLOADS[name]
        assert cfg.seq_len == w.seq, name
        assert cfg.d_model == w.d, name
        assert cfg.n_layers == w.layers, name
        assert cfg.chips == w.chips, name
    assert C.VISION_ARCHS["vit-l32"].chips == 2


def test_geometry_tiny_preserves_traffic_shape():
    for name in ("vit-b16", "vit-l32"):
        full = C.VISION_ARCHS[name]
        g = C.geometry_tiny_vit(full)
        assert g.seq_len == full.seq_len
        assert g.n_layers == full.n_layers
        assert g.chips == full.chips
        assert g.d_model < full.d_model


def test_patchify():
    img = jnp.arange(2 * 8 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 8, 3)
    p = vit.patchify(img, 4)
    assert p.shape == (2, 4, 48)
    # first patch is the top-left 4x4 block, row-major
    np.testing.assert_array_equal(
        np.asarray(p[0, 0]).reshape(4, 4, 3), np.asarray(img[0, :4, :4])
    )
    # tiny config keeps the patch projection analog-eligible
    assert TINY.patch_dim % 32 == 0


# ------------------------------------------------- backends on the vit

def test_forward_runs_under_all_backends(tiny_model):
    params, batches = tiny_model
    img = batches[0]
    outs = {}
    for name, (p, ctx) in {
        "float": (params, CTX),
        "mxfp4_digital": (params,
                          dataclasses.replace(CTX, quant="mxfp4_digital")),
        "mxfp4_wonly": (convert_params_mxfp4(params, min_n=32),
                        dataclasses.replace(CTX, quant="mxfp4_wonly")),
    }.items():
        lo, cache = vit.forward(p, TINY, ctx, img)
        assert cache is None
        assert lo.shape == (2, TINY.n_classes)
        assert bool(jnp.isfinite(lo.astype(jnp.float32)).all()), name
        outs[name] = np.asarray(lo, np.float32)
    # weight-only quant stays close to float on a tiny model (measured
    # ~8.5 dB on this random-init seed; near-uniform logits are the
    # worst case)
    assert sqnr_db(outs["float"], outs["mxfp4_wonly"]) > 5.0


def test_calibration_paths_cover_patch_trunk_and_head(cim_tiny):
    conv, calibs, _ = cim_tiny
    assert "patch" in calibs and "head" in calibs
    for j in range(TINY.n_layers):
        for leaf in ("attn/wq", "attn/wk", "attn/wv", "attn/wo",
                     "ffn/w1", "ffn/w2"):
            assert f"segments/0/L{j}/{leaf}" in calibs
    # converted trunk is layer-stacked with per-layer calib
    node = conv["segments"][0]["ffn"]["w1"]
    assert node["codes"].shape == (TINY.n_layers, TINY.d_model, TINY.d_ff)
    assert node["e_n"].shape == (TINY.n_layers,)
    assert conv["patch"]["e_n"].shape == ()
    assert conv["head"]["e_n"].shape == ()


def test_vit_lossless_cim_equals_digital_mxfp4(tiny_model):
    """Tiny-ViT invariant mirroring tests/test_backends.py: the lossless
    hybrid analog ViT IS the digital MXFP4 ViT — through patch embedding,
    bidirectional SDPA, FFN and head — under unrolled op-by-op execution
    (scan-fusion 1-ulp boundary flips make cross-graph checks bounded,
    not bitwise; see test_backends.py docstring)."""
    params, batches = tiny_model
    lossless = cimlib.CIMConfig(adc_bits=None, cm_bits=64, two_pass=False)
    conv, _ = calibrate.convert_model_cim(
        params, TINY, CTX, batches, cim_cfg=lossless, min_n=32,
        forward_fn=vit.forward,
    )
    dig_ctx = dataclasses.replace(CTX, quant="mxfp4_digital",
                                  unroll_layers=True)
    hyb_ctx = dataclasses.replace(CTX, quant="cim", cim=lossless,
                                  unroll_layers=True)
    d, _ = vit.forward(params, TINY, dig_ctx, batches[0])
    h, _ = vit.forward(conv, TINY, hyb_ctx, batches[0])
    d = np.asarray(d, np.float32)
    h = np.asarray(h, np.float32)
    assert sqnr_db(d, h) > 60.0  # measured ~299
    assert (d.argmax(-1) == h.argmax(-1)).all()
    # scanned execution: same wiring, fused compilation -> bounded
    ds, _ = vit.forward(
        params, TINY,
        dataclasses.replace(dig_ctx, unroll_layers=False), batches[0]
    )
    hs, _ = vit.forward(
        conv, TINY,
        dataclasses.replace(hyb_ctx, unroll_layers=False), batches[0]
    )
    assert sqnr_db(np.asarray(ds, np.float32),
                   np.asarray(hs, np.float32)) > 12.0


def test_vit_paper_operating_point_top1_agreement(tiny_model, cim_tiny):
    """Float-vs-cim top-1 agreement at the paper operating point (10b
    ADC, CM=3, 2-pass) on synthetic images, bounded and asserted.
    Random-init near-uniform logits are the worst case: even
    float-vs-*digital* agreement is only ~0.2-0.5 here (the MXFP4 delta,
    not the analog stage, dominates — measured f-d 0.19 / f-h 0.25 /
    d-h 0.63 on this seed), so the bounds are (a) far above the 1/32
    chance rate and (b) the analog stage costs little on top of the
    digital quantization."""
    params, batches = tiny_model
    conv, _, hyb_ctx = cim_tiny
    images = vit.calibration_images(TINY, n_batches=1, batch=16, seed=77)[0]
    f, _ = vit.forward(params, TINY, CTX, images)
    d, _ = vit.forward(
        params, TINY, dataclasses.replace(CTX, quant="mxfp4_digital"), images
    )
    h, _ = vit.forward(conv, TINY, hyb_ctx, images)
    f = np.asarray(f, np.float32)
    d = np.asarray(d, np.float32)
    h = np.asarray(h, np.float32)
    assert sqnr_db(d, h) > 5.0  # analog effects vs the digital baseline
    agree_fh = float((f.argmax(-1) == h.argmax(-1)).mean())
    agree_fd = float((f.argmax(-1) == d.argmax(-1)).mean())
    agree_dh = float((d.argmax(-1) == h.argmax(-1)).mean())
    chance = 1.0 / TINY.n_classes
    assert agree_fh >= 4 * chance  # measured 0.25 vs chance 0.031
    assert agree_fh >= agree_fd - 0.2  # analog adds little on top of MXFP4
    assert agree_dh >= 0.5  # the analog-only delta (measured 0.63)
    rel = np.abs(h - f).max() / max(np.abs(f).max(), 1e-6)
    assert rel < 1.0


# ------------------------------------- satellite: encoder attention path

def _rand_qkv(key, b, s, hkv, g, dh):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, hkv, g, dh), jnp.float32)
    k = jax.random.normal(kk, (b, s, hkv, dh), jnp.float32)
    v = jax.random.normal(kv, (b, s, hkv, dh), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("n", [197, 145])
def test_encoder_dense_vs_flash_non_multiple_of_chunk(n):
    """Bidirectional (non-causal) dense-vs-flash equality at the paper's
    encoder token counts (197 = ViT-B/16, 145 = ViT-L/32) — both are
    non-multiples of the KV/Q chunk, so the flash path pads keys with
    KV_PAD positions; the PR-2 ``_mask`` fix must exclude them on the
    non-causal path too, else every query attends garbage pad keys."""
    cfg = attn_mod.AttnStatic(d_model=32, n_heads=2, n_kv=2, head_dim=16,
                              causal=False, use_rope=False)
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, n, 2, 1, 16)
    pos = jnp.broadcast_to(jnp.arange(n)[None], (2, n))
    dense = attn_mod._dense_attn(q, k, v, pos, pos, cfg)
    ctx = dataclasses.replace(CTX, attn_chunk=64, q_chunk=64)
    flash = attn_mod._flash_attn(q, k, v, pos, pos, cfg, ctx)
    np.testing.assert_allclose(
        np.asarray(flash, np.float32), np.asarray(dense, np.float32),
        rtol=2e-5, atol=2e-5,
    )


def test_encoder_dense_vs_flash_mx_digital_bounded():
    """Digital-MXFP4 SDPA: dense and flash quantize P/V at different
    granularity (whole key axis vs per KV tile) so they are statistically
    — not bitwise — equivalent; pin the bound at N=197."""
    cfg = attn_mod.AttnStatic(d_model=32, n_heads=2, n_kv=2, head_dim=16,
                              causal=False, use_rope=False)
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 2, 197, 2, 1, 16)
    pos = jnp.broadcast_to(jnp.arange(197)[None], (2, 197))
    dense = attn_mod._dense_attn(q, k, v, pos, pos, cfg, mx_digital=True)
    ctx = dataclasses.replace(CTX, attn_chunk=64, q_chunk=64)
    flash = attn_mod._flash_attn(q, k, v, pos, pos, cfg, ctx,
                                 mx_digital=True)
    assert sqnr_db(np.asarray(dense, np.float32),
                   np.asarray(flash, np.float32)) > 10.0  # measured ~14


# ---------------------------------------------------- chip partitioning

def test_stage_partition():
    assert stage_partition(24, 2) == [(0, 12), (12, 24)]
    assert stage_partition(12, 1) == [(0, 12)]
    assert stage_partition(5, 2) == [(0, 3), (3, 5)]
    with pytest.raises(ValueError):
        stage_partition(4, 5)
    with pytest.raises(ValueError):
        stage_partition(4, 0)


def test_dual_chip_split_matches_monolithic_float():
    cfg = dataclasses.replace(TINY, n_layers=4, chips=2)
    params, _ = vit.init_model(jax.random.PRNGKey(3), cfg)
    img = vit.calibration_images(cfg, n_batches=1, batch=2, seed=5)[0]
    mono, _ = vit.forward(params, cfg, CTX, img)
    x = img["images"]
    chips = vit.split_chips(params, cfg, 2)
    assert [n for _, n in chips] == [2, 2]
    for ci, (chip_params, n) in enumerate(chips):
        x = vit.forward_chip(chip_params, cfg, CTX, x, n,
                             first=ci == 0, last=ci == len(chips) - 1)
    np.testing.assert_array_equal(np.asarray(x, np.float32),
                                  np.asarray(mono, np.float32))


def test_dual_chip_split_matches_monolithic_cim(tiny_model, cim_tiny):
    """The chip chain slices resident analog nodes (codes, exps, per-layer
    e_n/adc_fs) along the layer axis exactly like weights."""
    _, batches = tiny_model
    conv, _, hyb_ctx = cim_tiny

    def chip_chain(ctx):
        x = batches[0]["images"]
        chips = vit.split_chips(conv, TINY, 2)
        for ci, (chip_params, n) in enumerate(chips):
            x = vit.forward_chip(chip_params, TINY, ctx, x, n,
                                 first=ci == 0, last=ci == len(chips) - 1)
        return np.asarray(x, np.float32)

    # op-by-op (unrolled) execution: bitwise — the slice really carries
    # the per-layer calibration with the weights
    u_ctx = dataclasses.replace(hyb_ctx, unroll_layers=True)
    mono_u, _ = vit.forward(conv, TINY, u_ctx, batches[0])
    np.testing.assert_array_equal(chip_chain(u_ctx),
                                  np.asarray(mono_u, np.float32))
    # scanned monolithic vs per-chip graphs: bounded (cross-graph 1-ulp
    # MXFP4 boundary flips; see test_backends.py docstring; measured ~11)
    mono_s, _ = vit.forward(conv, TINY, hyb_ctx, batches[0])
    assert sqnr_db(np.asarray(mono_s, np.float32), chip_chain(hyb_ctx)) > 8.0


# ------------------------------------------------ FWS pipeline fidelity

def _streamed_engine(workload, n_frames=3, chips=None):
    cfg = C.geometry_tiny_vit(C.VISION_ARCHS[workload])
    params, _ = vit.init_model(jax.random.PRNGKey(0), cfg)
    eng = VisionEngine(params, cfg, CTX, chips=chips)
    frames = jax.random.normal(
        jax.random.PRNGKey(1), (n_frames, cfg.image_size, cfg.image_size, 3)
    )
    labels = eng.stream(frames)
    assert len(labels) == n_frames
    assert eng.trace == [cfg.seq_len] * n_frames  # measured stage traffic
    return eng


def test_frames_carry_their_phases():
    """Each frame records its ``vision.frame`` span and its three phases
    in order; the frame's record ends where its readback ends."""
    eng = _streamed_engine("vit-b16")
    for ev in eng.obs.steps:
        (name, lo, hi), *inner = ev.phases
        assert name == "vision.frame"
        assert [n for n, _, _ in inner] == ["vision.dispatch", "vision.sync",
                                            "vision.emit"]
        ends = [lo] + [t for _, a, b in inner for t in (a, b)] + [hi]
        assert ends == sorted(ends)
        assert ev.t_end == pytest.approx(inner[1][2], abs=1e-3)
    h = eng.obs.registry.histogram("serve_step_phase_seconds", labels={
        "kind": "frame", "phase": "vision.sync"})
    assert h.count == len(eng.obs.steps) == 3


def test_vit_b16_measured_traffic_matches_table7():
    """Acceptance: steady-state FPS from the engine's measured traffic
    matches PAPER_TABLE7 within 5% for vit-b16 (single chip)."""
    eng = _streamed_engine("vit-b16")
    rep = eng.fws_report(workload="vit-b16")
    assert rep.chips == 1 and rep.n_tokens == 197
    assert rep.fps == pytest.approx(S.PAPER_TABLE7["vit-b16"][1], rel=0.05)
    assert rep.fps == pytest.approx(perf.steady_state_fps(197, 768),
                                    rel=1e-6)


def test_vit_l32_dual_chip_measured_traffic_matches_table7():
    """Acceptance: vit-l32 dual-chip (24 layers split 12+12 with an
    inter-chip hop) within 5% of the paper's 58,275 FPS."""
    eng = _streamed_engine("vit-l32")
    assert eng.chips == 2
    assert len(eng._chain) == 2  # 12+12 stage partition drove execution
    rep = eng.fws_report(workload="vit-l32")
    assert rep.chips == 2 and rep.n_tokens == 145
    assert rep.fps == pytest.approx(S.PAPER_TABLE7["vit-l32"][1], rel=0.05)
    # the hop deepens the pipeline but never bounds throughput ...
    t = perf.stage_time(145, 1024)
    hop = perf.t_interchip(145, 1024)
    assert 0 < hop < t
    assert rep.fps == pytest.approx(1.0 / t, rel=1e-6)
    # ... and one frame's fill latency is 24 compute stages + one hop
    assert rep.frame_latency_s == pytest.approx(24 * t + hop, rel=1e-9)


def test_traffic_shaped_rows_vit_b32_and_bert_base():
    for name in ("vit-b32", "bert-base"):
        w = S.WORKLOADS[name]
        rep = synthetic_stream_report(w.seq, w.d, chips=w.chips)
        assert rep.fps == pytest.approx(S.PAPER_TABLE7[name][1], rel=0.05)


def test_fws_report_guards():
    eng = _streamed_engine("vit-b16")
    with pytest.raises(ValueError, match="measured stage traffic"):
        eng.fws_report(workload="bert-base")  # 197 != 512 tokens
    empty = VisionEngine(*vit.init_model(jax.random.PRNGKey(0), TINY)[:1],
                         TINY, CTX)
    with pytest.raises(ValueError, match="no frames"):
        empty.fws_report()


def test_multichip_pipeline_model_properties():
    """chips=1 is exactly the legacy simulate; chips=2 keeps throughput
    but deepens latency by one chip's stages + the hop."""
    jobs = [pipe.Job(0.0, 145) for _ in range(80)]
    one = pipe.simulate(jobs, 1024)
    two = pipe.simulate(jobs, 1024, chips=2)
    assert two.steady_state_fps == pytest.approx(one.steady_state_fps,
                                                 rel=1e-9)
    t = perf.stage_time(145, 1024)
    hop = perf.t_interchip(145, 1024)
    assert one.timings[0].latency == pytest.approx(12 * t)
    assert two.timings[0].latency == pytest.approx(24 * t + hop)
