#!/usr/bin/env python3
"""Bring-up smoke test: the main serving paths on a TPU, at published widths.

    python chip_smoke.py              # one chip: vit-b16 (cim) + gemma3-1b
    python chip_smoke.py --chips 4    # 2x2 mesh: vit-l32 StagePipeline only

One process drives the chip(s); nothing falls back to the CPU. Phases:

  device gate   refuses anything but a TPU, and checks that a default
                ``RunCtx`` resolves compiled Pallas (``use_pallas=True``,
                ``interpret=False``).
  vit-b16       ``launch/serve.py``'s vision path on the cim backend: init,
                Row-Hist calibration + conversion, ``VisionEngine.stream``
                over 8 seeded frames. The step's HLO must hold the Pallas
                kernels, each analog linear must match the jnp simulation
                on its teacher-forced input, and the logits must match the
                same converted params run through the jnp reference.
  gemma3-1b     ``serving.Engine`` with the mxfp4 backend as
                ``--serve-trace`` configures it, once with the legacy and
                once with the fused KV layout: 6 seeded requests, 16 new
                tokens each. Every request must finish, the fused decode
                step must hold the Pallas kernels, and each request's
                first-decode-step logits must agree across the layouts.
                Then the quantized-mirror paged decode kernel at gemma3-1b's
                widths against the jnp reference.
  pipeline      (``--chips 4`` only) vit-l32 on the cim backend split into
                the paper's 12+12 stages on a 2x2 (replica x stage) mesh
                through ``build_vit_pipeline``, against the single-device
                ``vit.forward`` on the same converted params.

Each phase prints its set-up seconds (init, conversion, compilation) and
its wall seconds; neither is a benchmark figure. A failed check raises and
ends the run non-zero. The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
N_FRAMES = 8
N_REQUESTS = 6
NEW_TOKENS = 16
PROMPT_LENS = (32, 200)

# SQNR floors (dB). Each compares two programs that compute the same thing
# on the chip; the values come from the chip runs recorded in CHANGES.md.
# Each analog linear of vit-b16 on the input the jnp model gives it, the
# cim_linear Pallas kernel vs the jnp simulation of the same datapath: they
# differ only where an ADC or activation code sits on a rounding boundary.
LINEAR_SQNR_MIN_DB = 40.0
# vit-b16 logits, the same two implementations through all 12 blocks: a
# code flipped at a rounding boundary moves the next layer's MXFP4 codes
# and ADC reads, and on random-init weights that compounds (8.16 dB
# measured on the chip).
VIT_SQNR_MIN_DB = 6.0
# gemma3-1b first-decode-step logits, legacy split-page decode vs the
# fused paged flash-decode kernel: same math in another softmax and
# accumulation order, through 26 layers (36.11 dB per request, worst).
GEMMA_SQNR_MIN_DB = 30.0
# The quantized-resident paged decode kernel at gemma3-1b's widths vs the
# jnp reference: the kernel re-quantizes P per 128-key chunk where the
# reference quantizes the whole key axis, so P codes flip (29.95 dB
# measured on the chip, and the same in CPU interpret mode).
PAGED_MX_REF_MIN_DB = 25.0
# vit-l32, the 2x2 StagePipeline vs the single-device forward: the same
# kernels in another XLA program, so rounding-boundary flips compound as
# in the vit-b16 comparison.
PIPELINE_SQNR_MIN_DB = 6.0


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg) -> None:
    """A failed check ends the run (not ``assert``: ``-O`` drops those)."""
    if not ok:
        raise RuntimeError(msg)


def has_tpu_custom_call(jitted, *args) -> bool:
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


def serve_args(*argv: str):
    from repro.launch import serve

    return serve.parser().parse_args(list(argv))


def vit_cim(cfg_name: str, args):
    """``serve_vision``'s build: init -> calibration -> cim conversion.
    Returns (cfg, converted params, RunCtx)."""
    import jax

    from repro import configs as C
    from repro.launch import serve
    from repro.models import vit

    cfg = C.VISION_ARCHS[cfg_name]
    fparams, _ = vit.init_model(jax.random.PRNGKey(SEED), cfg)
    batches = vit.calibration_images(
        cfg, n_batches=args.calib_batches, batch=args.batch
    )
    params, ctx = serve.build_backend(
        args, cfg, fparams, batches=batches, forward_fn=vit.forward
    )
    return cfg, params, ctx


def frames_for(cfg, n: int):
    import jax

    return jax.random.normal(
        jax.random.PRNGKey(SEED + 1),
        (n, cfg.image_size, cfg.image_size, cfg.in_channels),
    )


def node_at(params, path: str) -> dict:
    """The converted linear node a tapped forward records under ``path``:
    ``segments/<i>/L<j>/...`` is layer ``j`` of a layer-stacked segment."""
    import jax

    parts = path.split("/")
    node, layer = params, None
    if parts[0] == "segments":
        node, layer, parts = (params["segments"][int(parts[1])],
                              int(parts[2][1:]), parts[3:])
    for part in parts:
        node = node[part]
    return node if layer is None else jax.tree.map(lambda a: a[layer], node)


def check_analog_linears(cfg, params, ctx, ref_ctx, frame):
    """Every analog linear of the model, the Pallas kernel against the jnp
    simulation, each fed the input the jnp model gives it (teacher-forced:
    an eager, op-by-op jnp forward records them). Then the same eager
    forward with the kernels: the first linear whose input differs between
    the two shows where the runs part. Returns frame 0's eager logits
    ``(pallas, jnp)``."""
    import jax
    import numpy as np

    from repro.core import cim as cimlib
    from repro.core import mx as mxlib
    from repro.kernels.cim_linear import ops as cim_ops
    from repro.layers import backends
    from repro.models import calibrate, vit
    from repro.obs import sqnr_db

    cim_cfg = backends.cim_config(ctx)

    @jax.jit
    def both(x, codes, exps, e_n, adc_fs):
        w, calib = mxlib.MXW(codes, exps), cimlib.LayerCalib(e_n, adc_fs)
        return (cim_ops.cim_linear(x, w, calib, cfg=cim_cfg),
                cimlib.cim_linear(x, w, cim_cfg, calib)[0])

    def capture(c):
        caps, out = calibrate.capture_linear_inputs(
            params, cfg, c, {"images": frame}, forward_fn=vit.forward)
        return caps, np.asarray(out[0], np.float32)

    caps, eager_ref = capture(ref_ctx)
    tiles: dict = {}  # (K, N) -> [linears, min SQNR, outputs differing, of]
    for path, x in caps.items():
        node = node_at(params, path)
        if "e_n" not in node:
            continue
        got, ref = (np.asarray(a, np.float32) for a in both(
            x, node["codes"], node["exps"], node["e_n"], node["adc_fs"]))
        t = tiles.setdefault(x.shape[-1:] + got.shape[-1:],
                             [0, np.inf, 0, 0])
        t[0] += 1
        t[1] = min(t[1], float(sqnr_db(ref, got)))
        t[2] += int((got != ref).sum())
        t[3] += got.size
    check(sum(t[0] for t in tiles.values()) == len(caps),
          "a captured linear of vit-b16 is not analog")
    for (k, n), (count, sq, diff, size) in sorted(tiles.items()):
        log(f"vit-b16 cim: {k}->{n} x{count} (bk={cim_ops.pick_bk(k)},"
            f" bn={cim_ops.pick_bn(n)}), kernel vs jnp on teacher-forced "
            f"inputs: min SQNR {sq:.2f} dB, {diff}/{size} outputs differ "
            f"(bound {LINEAR_SQNR_MIN_DB} dB)")
    caps_pl, eager_pl = capture(ctx)
    parted = next((p for p in caps if not np.array_equal(caps[p],
                                                         caps_pl[p])), None)
    log(f"vit-b16 cim: eager op-by-op forward of frame 0, pallas vs jnp: "
        f"logits SQNR {float(sqnr_db(eager_ref, eager_pl)):.2f} dB, first "
        f"linear whose input differs: {parted}")
    worst = min(t[1] for t in tiles.values())
    check(worst >= LINEAR_SQNR_MIN_DB, f"cim_linear kernel SQNR {worst:.2f}")
    return eager_pl, eager_ref


def phase_vit() -> None:
    import jax
    import numpy as np

    from repro.obs import sqnr_db
    from repro.models import vit
    from repro.serving.vision import VisionEngine

    t0 = time.perf_counter()
    args = serve_args("--model", "vit-b16", "--no-tiny", "--backend", "cim",
                      "--frames", str(N_FRAMES))
    cfg, params, ctx = vit_cim("vit-b16", args)
    eng = VisionEngine(params, cfg, ctx)
    frames = frames_for(cfg, N_FRAMES)
    # the engine's own step function (VisionEngine's single-chip chain)
    step = jax.jit(lambda p, img: vit.forward(p, cfg, ctx,
                                              {"images": img})[0])
    check(has_tpu_custom_call(step, params, frames[:1]),
          "vit-b16 cim step holds no Pallas kernel")
    eng.stream(frames[:1])  # compile
    setup = time.perf_counter() - t0

    t1 = time.perf_counter()
    labels = eng.stream(frames)
    wall = time.perf_counter() - t1

    got = np.stack([np.asarray(step(params, f[None]), np.float32)[0]
                    for f in frames])
    check(got.shape == (N_FRAMES, cfg.n_classes), got.shape)
    check(np.isfinite(got).all(), "non-finite vit-b16 logits")
    check(labels == got.argmax(-1).tolist(), (labels, got.argmax(-1)))
    ref_ctx = dataclasses.replace(ctx, impl="jnp")
    ref_step = jax.jit(lambda p, img: vit.forward(p, cfg, ref_ctx,
                                                  {"images": img})[0])
    ref = np.asarray(ref_step(params, frames), np.float32)
    eager_pl, eager_ref = check_analog_linears(cfg, params, ctx, ref_ctx,
                                               frames[:1])
    log(f"vit-b16 cim: frame 0 jitted vs eager op-by-op logits SQNR: "
        f"pallas {float(sqnr_db(eager_pl, got[:1])):.2f} dB, jnp "
        f"{float(sqnr_db(eager_ref, ref[:1])):.2f} dB")
    sq = float(sqnr_db(ref, got))
    agree = int((ref.argmax(-1) == got.argmax(-1)).sum())
    log(f"vit-b16 cim: top-1 agreement pallas vs jnp {agree}/{N_FRAMES}")
    log(f"vit-b16 cim: logits SQNR pallas vs jnp {sq:.2f} dB "
        f"(bound {VIT_SQNR_MIN_DB} dB)")
    log(f"vit-b16 cim: setup_s={setup:.1f} wall_s={wall:.3f} "
        f"frames={len(labels)} (set-up and wall time, not benchmarks)")
    check(sq >= VIT_SQNR_MIN_DB, f"vit-b16 SQNR {sq:.2f} dB below bound")


def run_engine(params, cfg, ctx, layout: str, prompts):
    """Serve ``prompts`` through an Engine configured like ``--serve-trace``.
    Returns ({i: tokens}, {i: first-decode-step logits}, setup_s, wall_s,
    whether the decode step's HLO holds a Pallas kernel), keyed by the
    index into ``prompts``."""
    import jax
    import numpy as np

    from repro.launch import serve
    from repro.serving import Engine

    t0 = time.perf_counter()
    args = serve_args(
        "--arch", "gemma3-1b", "--no-tiny", "--backend", "mxfp4",
        "--serve-trace", "--prompt-len", str(PROMPT_LENS[1]),
        "--tokens", str(NEW_TOKENS), "--lanes", "4",
        "--requests", str(N_REQUESTS), "--kv-layout", layout,
    )
    ecfg = serve.engine_config(args, cfg)
    check(ecfg.page_len <= 512 and ecfg.lanes == 4, ecfg)
    eng = Engine(params, cfg, ctx, ecfg)

    # first-decode-step logits: the engine's own decode body, un-donated,
    # evaluated on each step's inputs before the step itself runs
    logits_fn = jax.jit(eng.decode_logits)
    decode = eng._decode
    first: dict[int, np.ndarray] = {}
    kernel = []

    def spy(p, pool, rows, ids, pos):
        if not kernel:
            kernel.append(has_tpu_custom_call(decode, p, pool, rows, ids,
                                              pos))
        fresh = [(lane, r.rid) for lane, r in eng.sched.running.items()
                 if len(r.out) == 1 and r.rid not in first]
        if fresh:
            lg = np.asarray(logits_fn(p, pool, rows, ids, pos)[0],
                            np.float32)
            for lane, rid in fresh:
                first[rid] = lg[lane]
        return decode(p, pool, rows, ids, pos)

    eng._decode = spy
    eng.add_request(prompts[0], max_new=2)  # warm-up: compile both steps
    eng.run()
    setup = time.perf_counter() - t0

    rids = [eng.add_request(p, max_new=NEW_TOKENS) for p in prompts]
    t1 = time.perf_counter()
    out = eng.run()
    wall = time.perf_counter() - t1
    for rid in rids:
        req = eng.requests[rid]
        check(req.done and len(out[rid]) == NEW_TOKENS,
              f"{layout}: request {rid} unfinished ({len(out[rid])} tokens)")
    return ({i: out[r] for i, r in enumerate(rids)},
            {i: first[r] for i, r in enumerate(rids)},
            setup, wall, kernel[0])


def check_paged_mx(cfg) -> None:
    """``ragged_paged_decode`` on quantized-resident MXFP4 mirrors (what
    the hybrid backends' digital SDPA reads) at gemma3-1b's widths: a
    512-slot page, 4 lanes of ragged lengths, seeded pages."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import mx as mxlib
    from repro.kernels.paged_attention import layout
    from repro.kernels.paged_attention import ops as paged_ops
    from repro.obs import sqnr_db

    w, hkv, dh = 512, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(jax.random.PRNGKey(SEED + 3), 3)
    k, v = (jax.random.normal(kk, (8, w, hkv, dh), jnp.bfloat16)
            for kk in ks[:2])
    quant = jax.jit(layout.quant_page_full)(k, v)
    q = mxlib.fake_quant(jax.random.normal(
        ks[2], (4, hkv, cfg.n_heads // hkv, dh))).astype(jnp.bfloat16)
    rows = jnp.asarray([5, 2, 7, 0], jnp.int32)
    lens = jnp.asarray([1, 200, 333, w], jnp.int32)

    def decode(**kw):
        fn = jax.jit(lambda *a: paged_ops.ragged_paged_decode(
            *a, quant=quant, scale=dh ** -0.5, **kw))
        return fn, np.asarray(fn(q, rows, lens), np.float32)

    fn, got = decode(use_pallas=True, interpret=False)
    check(has_tpu_custom_call(fn, q, rows, lens),
          "paged mx decode holds no Pallas kernel")
    _, ref = decode(use_pallas=False)
    check(np.isfinite(got).all(), "non-finite paged mx decode")
    sq = float(sqnr_db(ref, got))
    log(f"gemma3-1b paged mx decode (W={w}, Hkv={hkv}, Dh={dh}, lengths "
        f"{lens.tolist()}): kernel vs jnp reference SQNR {sq:.2f} dB "
        f"(bound {PAGED_MX_REF_MIN_DB} dB)")
    check(sq >= PAGED_MX_REF_MIN_DB, f"paged mx decode: {sq:.2f} dB")


def phase_gemma() -> None:
    import jax
    import numpy as np

    from repro import configs as C
    from repro.obs import sqnr_db
    from repro.launch import serve
    from repro.models import lm

    t0 = time.perf_counter()
    cfg = C.ARCHS["gemma3-1b"]
    args = serve_args("--arch", "gemma3-1b", "--no-tiny", "--backend",
                      "mxfp4")
    fparams, _ = lm.init_model(jax.random.PRNGKey(SEED), cfg)
    params, ctx = serve.build_backend(args, cfg, fparams)
    del fparams
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0

    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).tolist()
               for n in rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1,
                                     N_REQUESTS)]
    runs = {}
    for layout in ("legacy", "fused"):
        toks, first, setup, wall, kernel = run_engine(params, cfg, ctx,
                                                      layout, prompts)
        runs[layout] = (toks, first)
        log(f"gemma3-1b mxfp4 {layout}: setup_s={setup:.1f} wall_s="
            f"{wall:.3f} requests={len(toks)} tokens="
            f"{sum(map(len, toks.values()))} decode_pallas={kernel} "
            f"(set-up and wall time, not benchmarks)")
        if layout == "fused":
            check(kernel,
                  "gemma3-1b fused decode step holds no Pallas kernel")

    (tl, fl), (tf, ff) = runs["legacy"], runs["fused"]
    same = sum(a == b for i in tl for a, b in zip(tl[i], tf[i]))
    total = sum(len(v) for v in tl.values())
    log(f"gemma3-1b: token agreement legacy vs fused {same}/{total}")
    per = [float(sqnr_db(fl[i], ff[i])) for i in sorted(fl)]
    sq = float(sqnr_db(np.stack([fl[i] for i in sorted(fl)]),
                       np.stack([ff[i] for i in sorted(ff)])))
    log(f"gemma3-1b: first-decode logits SQNR legacy vs fused {sq:.2f} dB, "
        f"per request min {min(per):.2f} dB (bound {GEMMA_SQNR_MIN_DB} dB); "
        f"init_s={init_s:.1f}")
    check(all(np.isfinite(fl[i]).all() and np.isfinite(ff[i]).all()
              for i in fl), "non-finite gemma3-1b logits")
    check(min(per) >= GEMMA_SQNR_MIN_DB,
          f"gemma3-1b layouts disagree: {min(per):.2f} dB")
    check_paged_mx(cfg)


def phase_pipeline() -> None:
    import jax
    import numpy as np

    from repro.obs import sqnr_db
    from repro.distributed import pipeline_exec as pex
    from repro.launch import serve
    from repro.models import vit
    from repro.serving.vision import VisionEngine

    t0 = time.perf_counter()
    args = serve_args("--model", "vit-l32", "--no-tiny", "--backend", "cim",
                      "--mesh", "2x2", "--frames", str(N_FRAMES))
    cfg, params, ctx = vit_cim("vit-l32", args)
    replicas, stages = serve.pipeline_shape(args)
    runner = pex.build_vit_pipeline(
        params, cfg, ctx, stages=stages, replicas=replicas,
        microbatches=args.microbatches,
        mb_size=max(1, -(-args.frames // (replicas * args.microbatches))),
    )
    check(runner.bounds == [(0, 12), (12, 24)], runner.bounds)
    check(runner.trunk_resident(), "trunk weights not resident per stage")
    eng = VisionEngine(params, cfg, ctx, runner=runner)
    frames = frames_for(cfg, N_FRAMES)
    got = np.asarray(runner.forward({"images": frames}), np.float32)
    setup = time.perf_counter() - t0

    t1 = time.perf_counter()
    labels = eng.stream(frames)
    wall = time.perf_counter() - t1

    base = jax.jit(lambda p, img: vit.forward(p, cfg, ctx,
                                              {"images": img})[0])
    ref = np.asarray(base(params, frames), np.float32)
    check(np.isfinite(got).all(), "non-finite vit-l32 logits")
    check(labels == got.argmax(-1).tolist(), (labels, got.argmax(-1)))
    devs = {sh.device.id for a in jax.tree.leaves(runner.trunk)
            for sh in a.addressable_shards}
    bitwise = bool((got == ref).all())
    sq = float(sqnr_db(ref, got))
    agree = int((ref.argmax(-1) == got.argmax(-1)).sum())
    log(f"vit-l32 2x2 pipeline: stage cuts {runner.bounds}, trunk on "
        f"devices {sorted(devs)}, resident per stage: True")
    log(f"vit-l32 2x2 pipeline: top-1 agreement vs single device "
        f"{agree}/{N_FRAMES}")
    log(f"vit-l32 2x2 pipeline: bitwise={bitwise} logits SQNR vs single "
        f"device {sq:.2f} dB (bound {PIPELINE_SQNR_MIN_DB} dB)")
    log(f"vit-l32 2x2 pipeline: setup_s={setup:.1f} wall_s={wall:.3f} "
        f"frames={len(labels)} (set-up and wall time, not benchmarks)")
    check(bitwise or sq >= PIPELINE_SQNR_MIN_DB,
          f"pipeline diverged from single device: {sq:.2f} dB")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 2x2 vit-l32 StagePipeline phase")
    opts = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro sources under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import compile_cache

    cache = compile_cache.enable()
    import jax

    from repro.layers.common import RunCtx, ShardingCtx

    devs = jax.devices()
    d0 = devs[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} compile_cache={cache}")
    if d0.platform != "tpu":
        print("chip_smoke: no TPU found; refusing to run on "
              f"{d0.platform}", file=sys.stderr)
        return 1
    if len(devs) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} needs {opts.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1
    ctx = RunCtx(shd=ShardingCtx())
    check(ctx.use_pallas and not ctx.interpret,
          "default RunCtx does not resolve to compiled Pallas")

    phases = ([phase_pipeline] if opts.chips == 4
              else [phase_vit, phase_gemma])
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        log(f"{phase.__name__}: passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
