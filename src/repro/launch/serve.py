"""Serving launcher: pluggable linear-execution backends.

``--backend mxfp4`` (default): packed MXFP4 weight-only resident weights
(the digital FWS mode). ``--backend cim``: offline Row-Hist calibration +
conversion to resident analog CTT arrays, then an end-to-end *hybrid*
analog/digital decode — static linears on the ``cim_analog`` backend,
SDPA on the digital MXFP4 systolic path. ``--backend float``: bf16.

``--model vit-b16`` / ``--model vit-l32`` serve the vision (encoder)
workloads instead: a single-stream frame engine whose measured stage
traffic drives the twelve-stage FWS pipeline model and is cross-checked
against the paper's Table 7 FPS row (dual-chip 12+12 for vit-l32).

Telemetry: every run carries a ``repro.obs`` handle — request-trace
spans + pipeline occupancy metrics land in a metrics registry that
``--metrics-out PATH`` dumps as a JSON snapshot plus a Prometheus text
exposition (``PATH`` with a ``.prom`` suffix); the engines' step phases
land there as ``serve_step_phase_seconds{kind,phase}``.
``--slo-ttft-ms`` / ``--slo-token-ms`` score the run against latency
targets. ``--log-level`` controls the structured per-step log lines.
``--fidelity`` adds a numerical-fidelity pass over the freshly built
serving tree (per-layer SQNR vs the float reference, MXFP4 clip /
underflow counters, ADC saturation + code-utilization histograms, and
the calibration-drift check) before serving starts; the metrics land in
the same ``--metrics-out`` snapshot.

Local smoke:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --tiny \
      --tokens 16
  PYTHONPATH=src python -m repro.launch.serve --tiny --backend cim
  PYTHONPATH=src python -m repro.launch.serve --model vit-b16 --backend cim
  PYTHONPATH=src python -m repro.launch.serve --tiny --serve-trace \
      --metrics-out metrics.json --log-level debug
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import configs as C
from repro import obs as obs_lib
from repro.core import cim as cimlib
from repro.layers.common import RunCtx, ShardingCtx, convert_params_mxfp4
from repro.models import calibrate, lm
from repro.models.lm import build_segments


def conversion_args(args) -> dict:
    """Backend-conversion knobs, read from the CLI in exactly one place.

    Both the LM and vision serve paths call :func:`build_backend`, which
    consumes this dict — so a new conversion flag wired here applies to
    every path at once instead of silently reaching only one of them (the
    ``--cim-min-n`` class of bug)."""
    return dict(
        min_n=args.cim_min_n,  # MXFP4 packing and CIM conversion alike
        adc_bits=args.adc_bits,
        cm_bits=args.cm_bits,
        calib_batches=args.calib_batches,
    )


def build_backend(args, cfg, params, batches=None, forward_fn=None):
    """Returns (converted_params, RunCtx) for the requested backend.

    ``batches``/``forward_fn`` select the calibration capture for the cim
    backend (default: LM token batches through ``lm.forward``; the vision
    path passes synthetic images through ``vit.forward``). All
    conversion knobs come from :func:`conversion_args` — callers no
    longer plumb them per path.
    """
    conv_kw = conversion_args(args)
    shd = ShardingCtx()
    kw = dict(shd=shd, dense_attn_max=256, impl=args.impl)
    if getattr(args, "interpret", None) is not None:
        kw["interpret"] = args.interpret  # else: platform default
    log = obs_lib.get_logger("repro.serve", getattr(args, "log_level", "info"))
    if args.backend == "float":
        return params, RunCtx(**kw)
    if args.backend == "mxfp4":
        return (
            convert_params_mxfp4(params, min_n=conv_kw["min_n"]),
            RunCtx(quant="mxfp4_wonly", **kw),
        )
    if args.backend == "cim":
        cim_cfg = cimlib.CIMConfig(
            adc_bits=conv_kw["adc_bits"], cm_bits=conv_kw["cm_bits"],
            two_pass=True,
        )
        base_ctx = RunCtx(shd=shd, dense_attn_max=256)
        if batches is None:
            batches = calibrate.calibration_batches(
                cfg, n_batches=conv_kw["calib_batches"], batch=args.batch,
                seq=args.prompt_len,
            )
        t0 = time.time()
        conv, calibs = calibrate.convert_model_cim(
            params, cfg, base_ctx, batches,
            cim_cfg=cim_cfg, min_n=conv_kw["min_n"], forward_fn=forward_fn,
        )
        log.info(
            "row-hist calibration: %s",
            obs_lib.kv(linears=len(calibs), wall_s=time.time() - t0),
        )
        return conv, RunCtx(quant="cim", cim=cim_cfg, **kw)
    raise SystemExit(f"unknown --backend {args.backend!r}")


def pipeline_shape(args) -> tuple[int, int] | None:
    """(replicas, stages) from ``--mesh RxS`` / ``--stages``, or None when
    pipelined execution is off."""
    if args.mesh:
        try:
            r, s = args.mesh.lower().split("x")
            shape = (int(r), int(s))
        except ValueError:
            raise SystemExit(f"--mesh wants REPLICASxSTAGES, got "
                             f"{args.mesh!r}")
        if shape[0] < 1 or shape[1] < 1:
            raise SystemExit(f"--mesh axes must be >= 1, got {args.mesh!r}")
        return shape
    if args.stages:
        return (1, args.stages)
    return None


def _run_fidelity(args, cfg, fparams, params, ctx, obs, batch,
                  forward_fn=None):
    """``--fidelity``: one numerical-fidelity pass over the serving tree —
    per-layer SQNR against the float tree, quantizer / ADC health
    counters, and the calibration-drift check — published into the run's
    metrics registry before the snapshot is written. For the cim backend
    the reference runs on the digital MXFP4 path (the calibration-matched
    distribution, isolating the analog stack's noise); the other backends
    reference bf16 float, measuring total quantization error."""
    log = obs_lib.get_logger("repro.serve", args.log_level)
    ref_quant = "mxfp4_digital" if args.backend == "cim" else "none"
    t0 = time.time()
    _, rep = obs_lib.run_fidelity_pass(
        fparams, params, cfg, ctx, batch,
        obs=obs, forward_fn=forward_fn,
        ref_quant=ref_quant, quant=ctx.quant, min_n=args.cim_min_n,
    )
    log.info("fidelity: %s", obs_lib.kv(
        layers=len(rep["layers"]),
        output_sqnr_db=rep["sqnr_db"].get("output"),
        drifted=rep["drift"]["n_drifted"],
        wall_s=time.time() - t0,
    ))
    return rep


def _finish_metrics(args, obs: obs_lib.Obs, log) -> None:
    """Score SLOs (when targets given) and write the metrics snapshot."""
    targets = obs_lib.SLOTargets(
        ttft_p99_s=args.slo_ttft_ms / 1e3 if args.slo_ttft_ms else None,
        token_p99_s=args.slo_token_ms / 1e3 if args.slo_token_ms else None,
    )
    slo = None
    if any(v is not None for v in targets.asdict().values()):
        slo = obs_lib.evaluate_slo(obs.finished, targets)
        log.info("slo: %s", obs_lib.kv(
            ok=slo["pass"], **{k: v for k, v in slo["violations"].items()}
        ))
    if args.metrics_out:
        extra = {"requests": obs.request_summary()}
        if slo is not None:
            extra["slo"] = slo
        jp, pp = obs_lib.write_metrics(obs.registry, args.metrics_out,
                                       extra=extra)
        log.info("metrics written: %s", obs_lib.kv(json=jp, prom=pp))


def engine_config(args, cfg):
    """The ``EngineConfig`` the CLI's engine paths serve with. Page budget:
    full-attention archs take prompt+tokens; sliding-window archs keep the
    page inside the narrowest window (no ring wrap)."""
    from repro.serving import EngineConfig

    windows = [s.attn.window for s in build_segments(cfg)
               if s.attn is not None and s.attn.window > 0]
    page_len = args.prompt_len + args.tokens
    if windows:
        page_len = min(page_len, min(windows))
    return EngineConfig(
        lanes=args.lanes, num_slots=args.slots, page_len=page_len,
        prefill_len=max(2, page_len - args.tokens), policy=args.policy,
        kv_layout=args.kv_layout, chunk_len=args.chunk_len or None,
        prefix_cache=args.prefix_cache,
    )


def serve_trace(args, cfg, params, ctx, obs: obs_lib.Obs):
    """Continuous-batching serving demo: a burst of staggered synthetic
    requests through ``serving.Engine``, then the schedule mapped onto the
    twelve-stage FWS pipeline model (simulated latency / throughput)."""
    import numpy as np

    from repro.serving import Engine

    log = obs_lib.get_logger("repro.serve", args.log_level)
    ecfg = engine_config(args, cfg)
    page_len, prefill_len = ecfg.page_len, ecfg.prefill_len
    eng = Engine(params, cfg, ctx, ecfg, obs=obs)
    t0 = time.time()
    tokens_done = 0

    def step_logged():
        nonlocal tokens_done
        done = eng.step()
        if not obs.steps:
            return done
        ev = obs.steps[-1]
        live = eng.sched.num_active
        tokens_done += len(ev.rids) if ev.kind == "decode" else 1
        log.debug("step %s", obs_lib.kv(
            n=len(obs.steps), kind=ev.kind, live=live,
            free_slots=eng.kv.num_free, queued=len(eng.sched.waiting),
            wall_ms=ev.wall_s * 1e3,
        ))
        if len(obs.steps) % args.log_every == 0:
            log.info("progress %s", obs_lib.kv(
                step=len(obs.steps), live=live,
                free_slots=eng.kv.num_free, queued=len(eng.sched.waiting),
                tokens=tokens_done,
                tok_s=tokens_done / max(time.time() - t0, 1e-9),
            ))
        return done

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        n = int(rng.integers(2, prefill_len + 1))
        prompt = rng.integers(0, cfg.vocab_size, size=n).tolist()
        eng.add_request(prompt, max_new=min(args.tokens,
                                            page_len - n))
        # staggered arrivals: a couple of engine steps between submissions
        for _ in range(int(rng.integers(0, 3))):
            step_logged()
    while eng.sched.has_work:
        step_logged()
    out = {rid: list(r.out) for rid, r in eng.requests.items()}
    dt = time.time() - t0
    rep = eng.trace_report()
    rep.publish(obs.registry)
    lat = sorted(rep.request_latency.values())
    n_tok = sum(len(v) for v in out.values())
    log.info(
        "%s [%s] serve-trace done: %s", cfg.name, args.backend,
        obs_lib.kv(requests=len(out), tokens=n_tok, wall_s=dt,
                   tok_s_host=n_tok / dt),
    )
    log.info("engine: %s", obs_lib.kv(
        policy=ecfg.policy, lanes=ecfg.lanes, slots=ecfg.num_slots,
        page=ecfg.page_len, slot_util=eng.slot_utilization,
    ))
    if eng.prefix is not None:
        log.info("prefix-cache: %s", obs_lib.kv(**eng.prefix_stats()))
    log.info("fws-pipeline d=%d: %s", cfg.d_model, obs_lib.kv(
        sim_tok_s=rep.tokens_per_s,
        steady_state_fps=rep.pipeline.steady_state_fps,
        stage_occupancy=rep.pipeline.stage_utilization,
        bubble=rep.pipeline.bubble_fraction,
        fill_latency_us=rep.pipeline.fill_latency_s * 1e6,
        analog_util=rep.pipeline.analog_utilization,
        digital_util=rep.pipeline.digital_utilization,
    ))
    host = obs.request_summary()
    if host["ttft_s"]:
        log.info("host-latency: %s", obs_lib.kv(
            ttft_p50_ms=host["ttft_s"]["p50"] * 1e3,
            ttft_p99_ms=host["ttft_s"]["p99"] * 1e3,
            token_p50_ms=(host["token_latency_s"] or {}).get("p50", 0) * 1e3,
            queue_p99_ms=(host["queue_wait_s"] or {}).get("p99", 0) * 1e3,
        ))
    log.info("sim-latency: %s", obs_lib.kv(
        p50_us=lat[len(lat) // 2] * 1e6, max_us=lat[-1] * 1e6
    ))
    for rid in sorted(out)[:4]:
        log.debug("rid %d: %s", rid, out[rid])
    _finish_metrics(args, obs, log)


def serve_load(args, cfg, params, ctx, obs: obs_lib.Obs):
    """``--arrivals``: trace-driven load harness. Replays a Poisson /
    scripted-burst / recorded-trace arrival process with mixed prompt
    and output lengths (and shared system prompts, what the prefix cache
    deduplicates) through the real engine on the host wall clock, then
    scores SLOs and publishes the load report."""
    import numpy as np

    from repro.serving import Engine
    from repro.serving import load as load_mod

    log = obs_lib.get_logger("repro.serve", args.log_level)
    ecfg = engine_config(args, cfg)
    page_len, prefill_len, chunk = (ecfg.page_len, ecfg.prefill_len,
                                    ecfg.chunk_len)
    eng = Engine(params, cfg, ctx, ecfg, obs=obs)

    kind, val = load_mod.parse_arrivals(args.arrivals)
    rng = np.random.default_rng(0)
    if kind == "trace":
        trace = load_mod.load_trace(val)
    else:
        max_prompt = (page_len if chunk else prefill_len) - 1
        sys_len = max(2, min(2 * chunk if chunk else 4, max_prompt - 2))
        spec = load_mod.WorkloadSpec(
            vocab_size=cfg.vocab_size,
            prompt_len=(2, max(2, max_prompt - sys_len)),
            out_len=(2, max(2, args.tokens)),
            system_len=sys_len, max_prompt=max_prompt,
        )
        reqs = load_mod.synth_requests(spec, args.requests, rng)
        times = (load_mod.poisson_arrivals(val, len(reqs), rng)
                 if kind == "poisson"
                 else load_mod.burst_arrivals(len(reqs), *val))
        trace = load_mod.make_trace(times, reqs)

    # warm the compiled steps on a throwaway request so the replay's
    # arrival clock measures serving, not XLA compilation
    eng.add_request(list(trace[0].prompt), max_new=2)
    eng.run()
    obs.reset()

    log.info("load: replaying %s", obs_lib.kv(
        arrivals=args.arrivals, requests=len(trace),
        chunk_len=chunk or 0, prefix_cache=args.prefix_cache,
        policy=ecfg.policy,
    ))
    res = load_mod.replay(eng, trace)
    rep = load_mod.load_report(eng, wall_s=res["wall_s"])
    eng.trace_report().publish(obs.registry)
    ttft = rep["ttft_s"] or {}
    tokl = rep["token_latency_s"] or {}
    log.info("load done: %s", obs_lib.kv(
        requests=rep["n_requests"], tokens=rep["tokens_generated"],
        wall_s=rep["wall_s"], tok_s=rep["tokens_per_s_wall"],
        ttft_p50_ms=ttft.get("p50", 0) * 1e3,
        ttft_p99_ms=ttft.get("p99", 0) * 1e3,
        token_p50_ms=tokl.get("p50", 0) * 1e3,
        token_p99_ms=tokl.get("p99", 0) * 1e3,
        page_evictions=rep["page_evictions"],
    ))
    if rep["prefix"]:
        log.info("prefix-cache: %s", obs_lib.kv(**rep["prefix"]))
    _finish_metrics(args, obs, log)


def serve_vision(args, cfg_full):
    """Vision (encoder) serving: stream frames through the fixed-shape
    jitted forward, then cross-validate the measured stage traffic against
    the paper's Table 7 row on the FWS pipeline model."""
    from repro.hwmodel import specs as S
    from repro.models import vit
    from repro.serving.vision import VisionEngine

    log = obs_lib.get_logger("repro.serve", args.log_level)
    obs = obs_lib.Obs()

    # --tiny keeps the paper's token geometry (patch grid, layers, chips)
    # and shrinks only the width, so the measured traffic still reproduces
    # Table 7; --no-tiny runs the full-size model.
    cfg = C.geometry_tiny_vit(cfg_full) if args.tiny else cfg_full
    fparams, _ = vit.init_model(jax.random.PRNGKey(0), cfg)
    batches = vit.calibration_images(
        cfg, n_batches=args.calib_batches, batch=args.batch
    )
    params, ctx = build_backend(
        args, cfg, fparams, batches=batches, forward_fn=vit.forward,
    )
    if args.fidelity:
        _run_fidelity(args, cfg, fparams, params, ctx, obs, batches[0],
                      forward_fn=vit.forward)
    runner = None
    pshape = pipeline_shape(args)
    if pshape is not None:
        from repro.distributed import pipeline_exec as pex

        replicas, stages = pshape
        runner = pex.build_vit_pipeline(
            params, cfg, ctx, stages=stages, replicas=replicas,
            microbatches=args.microbatches,
            mb_size=max(1, -(-args.frames // (replicas *
                                              args.microbatches))),
        )
        log.info("pipelined mesh: %s", obs_lib.kv(
            replicas=replicas, stages=stages,
            microbatches=args.microbatches, capacity=runner.capacity,
            stage_cuts=runner.bounds, trunk_mb=runner.trunk_bytes / 2**20,
        ))
    eng = VisionEngine(params, cfg, ctx, obs=obs, runner=runner)
    frames = jax.random.normal(
        jax.random.PRNGKey(1),
        (args.frames, cfg.image_size, cfg.image_size, cfg.in_channels),
    )
    t0 = time.time()
    labels = eng.stream(frames)
    dt = time.time() - t0
    log.info(
        "%s [%s] vision-stream: %s", cfg.name, args.backend,
        obs_lib.kv(frames=len(labels), tokens_each=cfg.seq_len, wall_s=dt,
                   fps_host=len(labels) / dt, top1=labels),
    )
    workload = cfg_full.name if cfg_full.name in S.WORKLOADS else None
    rep = eng.fws_report(workload=workload)
    rep.publish(obs.registry)
    fields = dict(
        chips=rep.chips, d=rep.d_model, n_tokens=rep.n_tokens,
        fps=rep.fps, frame_latency_us=rep.frame_latency_s * 1e6,
        stage_occupancy=rep.pipeline.stage_utilization,
        bubble=rep.pipeline.bubble_fraction,
    )
    if rep.paper_fps:
        fields.update(paper_fps=rep.paper_fps,
                      err_pct=100 * rep.fps_error)
    log.info("fws-pipeline: %s", obs_lib.kv(**fields))
    if runner is not None:
        mrep = eng.measured_report(frames, reps=2)
        mrep.publish(obs.registry)
        log.info("fws-pipeline-measured: %s", obs_lib.kv(
            stages=mrep.n_stages, replicas=mrep.n_replicas,
            step_wall_ms=mrep.step_wall_s * 1e3,
            fps=mrep.throughput_items_per_s,
            steady_fps=mrep.steady_items_per_s,
            bubble=mrep.bubble_fraction,
            fill_ms=mrep.fill_latency_s * 1e3,
        ))
    _finish_metrics(args, obs, log)


def serve_pipelined_lm(args, cfg, params, ctx, obs: obs_lib.Obs,
                       pshape: tuple[int, int]):
    """``--stages``/``--mesh`` LM path: the prefill/scoring forward runs
    stage-parallel on a real device mesh — per-stage resident weights,
    overlapping microbatches — and reports measured pipeline health next
    to the single-device baseline (decode stays on the existing engine)."""
    log = obs_lib.get_logger("repro.serve", args.log_level)
    replicas, stages = pshape
    mb = max(1, -(-args.batch // (replicas * args.microbatches)))
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size,
    )
    batch = {"ids": ids}
    out, runner = lm.forward_pipelined(
        params, cfg, ctx, batch, stages=stages, replicas=replicas,
        microbatches=args.microbatches, mb_size=mb,
    )
    log.info("pipelined mesh: %s", obs_lib.kv(
        replicas=replicas, stages=stages, microbatches=args.microbatches,
        mb_size=mb, capacity=runner.capacity, stage_cuts=runner.bounds,
        trunk_mb=runner.trunk_bytes / 2**20,
    ))
    rep = runner.measure(batch, reps=2)
    rep.publish(obs.registry)
    # single-device baseline on the same batch, same backend
    base = jax.jit(lambda p, b: lm.forward(p, cfg, ctx, b)[0])
    ref = jax.block_until_ready(base(params, batch))
    t0 = time.perf_counter()
    ref = jax.block_until_ready(base(params, batch))
    base_wall = time.perf_counter() - t0
    match = bool((out == ref).all())
    log.info(
        "%s [%s] pipelined forward: %s", cfg.name, args.backend,
        obs_lib.kv(
            rows=args.batch, tokens=args.batch * args.prompt_len,
            step_wall_ms=rep.step_wall_s * 1e3,
            base_wall_ms=base_wall * 1e3,
            rows_s=rep.throughput_items_per_s,
            steady_rows_s=rep.steady_items_per_s,
            bubble=rep.bubble_fraction,
            fill_ms=rep.fill_latency_s * 1e3,
            parity="bitwise" if match else "diverged",
        ),
    )
    _finish_metrics(args, obs, log)


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", "--model", dest="arch", default="gemma3-1b")
    ap.add_argument("--tiny", action="store_true", default=True,
                    help="reduced smoke config (default)")
    ap.add_argument("--no-tiny", dest="tiny", action="store_false",
                    help="run the full-size architecture")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--backend", default="mxfp4",
                    choices=("float", "mxfp4", "cim"))
    ap.add_argument("--calib-batches", type=int, default=2)
    ap.add_argument("--cim-min-n", type=int, default=32)
    ap.add_argument("--adc-bits", type=int, default=10)
    ap.add_argument("--cm-bits", type=int, default=3)
    ap.add_argument("--impl", default="auto",
                    choices=("auto", "jnp", "pallas"),
                    help="linear engine: auto = compiled Pallas on real "
                         "accelerators, jnp reference on CPU")
    ap.add_argument("--interpret", default=None,
                    type=lambda s: s.lower() in ("1", "true", "yes"),
                    help="force the Pallas interpret flag (default: "
                         "platform-derived — interpret only on CPU)")
    ap.add_argument("--serve-trace", action="store_true",
                    help="continuous-batching engine demo: staggered "
                         "requests + FWS pipeline occupancy report")
    ap.add_argument("--requests", type=int, default=8,
                    help="synthetic request count for --serve-trace")
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--slots", type=int, default=6)
    ap.add_argument("--kv-layout", default="legacy",
                    choices=["legacy", "fused"],
                    help="KV pool layout: legacy split K/V pages, or the "
                         "fused head-interleaved paged layout decoded by "
                         "the ragged paged flash-decode path")
    ap.add_argument("--policy", default="prefill",
                    choices=("prefill", "decode", "chunked"),
                    help="admission policy; 'chunked' interleaves prefill "
                         "chunks with decode steps (needs --chunk-len)")
    ap.add_argument("--chunk-len", type=int, default=0,
                    help="chunked prefill: run prompts through a fixed "
                         "[1, chunk_len] step in absolute-position "
                         "windows, lifting the prompt cap from "
                         "prefill_len to page_len (0 = single-shot "
                         "padded prefill)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over the KV page pool: "
                         "shared prompt prefixes reuse refcounted, "
                         "content-addressed pages (requires --chunk-len)")
    ap.add_argument("--arrivals", default=None,
                    help="trace-driven load harness: replay this arrival "
                         "process through the engine on the host wall "
                         "clock (poisson:RATE | trace:FILE | "
                         "burst:N:GAP_S) instead of the --serve-trace "
                         "staggered demo")
    ap.add_argument("--frames", type=int, default=4,
                    help="synthetic frame count for vision (--model vit-*)")
    # ------------------------------------------- multi-device FWS pipeline
    ap.add_argument("--stages", type=int, default=0,
                    help="run the forward stage-parallel over this many "
                         "pipeline stages (one device each, weights "
                         "resident per stage); 0 = off")
    ap.add_argument("--microbatches", type=int, default=2,
                    help="overlapping microbatches per pipeline replica "
                         "for --stages/--mesh")
    ap.add_argument("--mesh", default=None,
                    help="REPLICASxSTAGES device mesh for pipelined "
                         "execution (e.g. 2x4: two data-parallel pipeline "
                         "replicas of four stages); overrides --stages. "
                         "On CPU force devices first: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8")
    # ----------------------------------------------------- observability
    ap.add_argument("--metrics-out", default=None,
                    help="write a JSON metrics snapshot here (plus the "
                         "Prometheus text exposition at the same path "
                         "with a .prom suffix)")
    ap.add_argument("--fidelity", action="store_true",
                    help="numerical-fidelity pass after the backend build: "
                         "per-layer SQNR vs the float tree, MXFP4 clip/"
                         "underflow + ADC saturation/code-utilization "
                         "counters, calibration-drift check (eager; "
                         "metrics land in --metrics-out)")
    ap.add_argument("--log-level", default="info", choices=obs_lib.log.LEVELS
                    if hasattr(obs_lib, "log") else
                    ("debug", "info", "warning", "error"),
                    help="structured log verbosity (debug: one line per "
                         "engine step)")
    ap.add_argument("--log-every", type=int, default=16,
                    help="info-level progress summary every N engine steps")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="TTFT p99 SLO target in ms (host wall)")
    ap.add_argument("--slo-token-ms", type=float, default=None,
                    help="per-token latency p99 SLO target in ms")
    return ap


def main():
    from repro.launch import compile_cache

    compile_cache.enable()
    args = parser().parse_args()

    log = obs_lib.get_logger("repro.serve", args.log_level)

    if args.arch in C.VISION_ARCHS:
        serve_vision(args, C.VISION_ARCHS[args.arch])
        return

    cfg = C.tiny(C.ARCHS[args.arch]) if args.tiny else C.ARCHS[args.arch]
    if not cfg.supports_decode:
        raise SystemExit(f"{cfg.name} is encoder-only; no decode")
    obs = obs_lib.Obs()
    fparams, _ = lm.init_model(jax.random.PRNGKey(0), cfg)
    params, ctx = build_backend(args, cfg, fparams)
    if args.fidelity:
        fb = calibrate.calibration_batches(
            cfg, n_batches=1, batch=args.batch, seq=args.prompt_len
        )[0]
        _run_fidelity(args, cfg, fparams, params, ctx, obs, fb)

    pshape = pipeline_shape(args)
    if pshape is not None:
        serve_pipelined_lm(args, cfg, params, ctx, obs, pshape)
        return

    if args.arrivals:
        serve_load(args, cfg, params, ctx, obs)
        return

    if args.serve_trace:
        serve_trace(args, cfg, params, ctx, obs)
        return

    max_len = args.prompt_len + args.tokens
    caches = lm.init_cache(cfg, args.batch, max_len)
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size,
    )
    # head over the last position only (a [B, S, V] logits tensor is
    # wasteful at real vocab sizes), still through the active backend
    # (analog read-out under --backend cim)
    hidden, caches = lm.forward(
        params, cfg, ctx, {"ids": prompt}, caches=caches, return_hidden=True
    )
    logits = lm._head(ctx, cfg, params, hidden[:, -1:])
    ids = jnp.argmax(logits[:, -1].astype(jnp.float32), -1)[:, None]

    step = jax.jit(lambda p, c, i, pos: lm.decode_step(p, cfg, ctx, i, pos, c))
    t0, outs = time.time(), [ids]
    tok_hist = obs.registry.histogram(
        "serve_token_latency_seconds", "inter-token decode gap (host wall)"
    )
    t_prev = time.perf_counter()
    for t in range(args.tokens - 1):
        logits, caches = step(params, caches, ids,
                              jnp.int32(args.prompt_len + t))
        ids = jnp.argmax(logits.astype(jnp.float32), -1)[:, None]
        ids.block_until_ready()
        now = time.perf_counter()
        tok_hist.observe(now - t_prev)
        t_prev = now
        outs.append(ids)
    dt = time.time() - t0
    log.info(
        "%s [%s] greedy decode: %s", cfg.name, args.backend,
        obs_lib.kv(tokens=(args.tokens - 1) * args.batch, wall_s=dt,
                   token_p50_ms=tok_hist.quantile(0.5) * 1e3,
                   ids0=jnp.concatenate(outs, 1)[0].tolist()),
    )
    _finish_metrics(args, obs, log)


if __name__ == "__main__":
    main()
