"""Single-stream image-throughput serving for encoder (ViT) workloads.

The paper's headline numbers (Table 7) are frames-per-second figures for
vision encoders on the twelve-stage FWS pipeline — this module makes them
*measured* instead of closed-form: a :class:`VisionEngine` streams frames
one at a time through one fixed-shape jitted forward (encoders have no KV
cache and no decode step, so the whole serving problem is a feed-forward
pipeline), records the per-frame stage traffic (token count = patch grid
+ CLS), and maps that measured traffic onto the
``serving/pipeline.py`` discrete-event model of the §5.3 pipeline.

Dual-chip workloads (vit-l32: 24 blocks split 12+12, paper §5.3) run the
trunk as a chip chain — ``vit.split_chips`` slices the layer-stacked
params with ``distributed.sharding.stage_partition``, each chip owns its
own jitted step, and the hidden-state handoff between chips is the
inter-chip hop that ``pipeline.simulate(chips=2)`` bills as an extra
link stage (``perf.t_interchip``).

``fws_report(workload=...)`` cross-validates: the engine's *measured*
token traffic drives the pipeline at the named workload's hardware shape
(d_model, chip count) and the steady-state FPS must land on the paper's
Table 7 row (checked within 5% in tests/test_vision.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod
from repro.hwmodel import specs as S
from repro.models import vit
from repro.serving import pipeline as pipe_mod


@dataclasses.dataclass(frozen=True)
class VisionReport:
    pipeline: pipe_mod.PipelineReport
    fps: float  # steady-state frames/s of the FWS pipeline model
    frame_latency_s: float  # one frame through the full (multi-chip) pipe
    n_tokens: int  # measured stage traffic per frame
    d_model: int  # hardware width the pipeline was billed at
    chips: int
    paper_fps: float | None = None  # Table 7 row, when cross-validating

    @property
    def fps_error(self) -> float | None:
        if not self.paper_fps:
            return None
        return abs(self.fps - self.paper_fps) / self.paper_fps

    def publish(self, registry, prefix: str = "pipeline") -> None:
        """Export the FWS pipeline gauges plus the vision-specific frame
        latency (and paper cross-check, when present) into a registry."""
        self.pipeline.publish(registry, prefix=prefix)
        registry.gauge(
            f"{prefix}_frame_latency_seconds",
            "one frame through the full (multi-chip) pipeline",
        ).set(self.frame_latency_s)
        if self.paper_fps:
            registry.gauge(
                f"{prefix}_paper_fps_error",
                "relative error vs the paper's Table 7 row",
            ).set(self.fps_error)


class VisionEngine:
    """Fixed-shape single-stream frame engine over the backend registry.

    Works under any linear-execution backend (float / mxfp4 / cim): the
    jitted steps just call ``vit.forward`` / ``vit.forward_chip`` with
    whatever converted params + RunCtx the caller built.
    """

    def __init__(self, params, cfg: vit.ViTConfig, ctx, chips: int | None = None,
                 obs=None, runner=None):
        self.params = params
        self.cfg = cfg
        self.ctx = ctx
        self.chips = chips or cfg.chips
        self.obs = obs if obs is not None else obs_mod.Obs()
        self._next_fid = 0
        self.runner = runner  # distributed.pipeline_exec.StagePipeline
        if runner is not None:
            # real stage-parallel execution on a device mesh: frames run in
            # pipelined slabs of runner.capacity, the chip chain is unused
            self.chips = runner.n_stages
            self._chain = []
        elif self.chips == 1:
            self._chain = [(
                jax.jit(lambda p, img: vit.forward(p, cfg, ctx,
                                                   {"images": img})[0]),
                params, None,
            )]
        else:
            self._chain = []
            chip_trees = vit.split_chips(params, cfg, self.chips)
            for ci, (chip_params, n_layers) in enumerate(chip_trees):
                first = ci == 0
                last = ci == self.chips - 1

                def step(p, x, n=n_layers, first=first, last=last):
                    return vit.forward_chip(p, cfg, ctx, x, n, first, last)

                self._chain.append((jax.jit(step), chip_params, n_layers))

    # --------------------------------------------------------- execution

    def classify_frame(self, image: jax.Array) -> int:
        """One frame [H, W, C] through the chip chain; returns the top-1
        class and records the frame's stage traffic as a typed event.

        The frame runs under a ``vision.frame`` span (carrying its id)
        with phases ``vision.dispatch`` (input staging and the chip
        chain's calls), ``vision.sync`` (the logits' readback) and
        ``vision.emit``."""
        if self.runner is not None:
            return self._stream_pipelined(jnp.asarray(image)[None])[0]
        obs = self.obs
        fid = self._next_fid
        self._next_fid += 1
        with obs.step_span("vision.frame", frame=fid) as st:
            with obs.span("vision.dispatch"):
                x = jnp.asarray(image)[None]  # fixed shape [1, H, W, C]
                for fn, chip_params, _ in self._chain:
                    x = fn(chip_params, x)  # hidden handoff == inter-chip hop
            with obs.span("vision.sync"):
                logits = np.asarray(jax.device_get(x), np.float32)[0]
            with obs.span("vision.emit"):
                st.record("frame", (fid,), self.cfg.seq_len)
                if obs.enabled:
                    obs.registry.counter(
                        "vision_frames_total", "frames streamed"
                    ).inc()
                return int(logits.argmax())

    @property
    def trace(self) -> list:
        """Derived view: n_tokens per streamed frame (the measured stage
        traffic), rebuilt from the typed frame events."""
        return [e.n_tokens for e in self.obs.steps if e.kind == "frame"]

    def stream(self, frames) -> list[int]:
        """Stream frames ([N, H, W, C] or iterable of [H, W, C]): one at a
        time through the chip chain (single-stream serving, the Table 7
        operating mode), or — with a stage-parallel ``runner`` — in
        pipelined slabs of overlapping microbatches on the device mesh."""
        if self.runner is not None:
            return self._stream_pipelined(jnp.asarray(frames))
        return [self.classify_frame(f) for f in frames]

    def _stream_pipelined(self, frames: jax.Array) -> list[int]:
        """Slabs of ``runner.capacity`` frames, each under one
        ``vision.frame`` span with the phases of :meth:`classify_frame`."""
        obs = self.obs
        out: list[int] = []
        cap = self.runner.capacity
        for i in range(0, frames.shape[0], cap):
            n = min(cap, frames.shape[0] - i)
            with obs.step_span("vision.frame", frame=self._next_fid,
                               frames=n) as st:
                with obs.span("vision.dispatch"):
                    logits = self.runner.forward(
                        {"images": frames[i:i + cap]})
                with obs.span("vision.sync"):
                    logits = np.asarray(jax.device_get(logits), np.float32)
                with obs.span("vision.emit"):
                    # one event per frame, so the derived trace keeps
                    # them; the step bills each a slice of the slab wall
                    for _ in range(n):
                        st.record("frame", (self._next_fid,),
                                  self.cfg.seq_len)
                        self._next_fid += 1
                    if obs.enabled:
                        obs.registry.counter(
                            "vision_frames_total", "frames streamed"
                        ).inc(n)
                    out.extend(int(v.argmax()) for v in logits)
        return out

    def measured_report(self, frames, reps: int = 3):
        """Measured pipeline health from real multi-device runs (requires
        a stage-parallel runner): per-stage walls, occupancy, bubble —
        the hardware-measured counterpart of :meth:`fws_report`."""
        if self.runner is None:
            raise ValueError("measured_report needs a pipelined runner")
        batch = jnp.asarray(frames)[: self.runner.capacity]
        return self.runner.measure({"images": batch}, reps=reps)

    # ----------------------------------------------------------- reports

    def fws_report(self, workload: str | None = None,
                   min_frames: int = 240) -> VisionReport:
        """Map the measured per-frame stage traffic onto the FWS pipeline.

        ``workload`` names a ``hwmodel.specs.WORKLOADS`` entry to
        cross-validate against: the pipeline is billed at that workload's
        hardware shape (d_model, chips) — the engine may run a width-tiny
        but geometry-true model — and the measured token count must match
        the workload's. The measured trace is tiled up to ``min_frames``
        jobs so the pipeline reaches steady state.
        """
        if not self.trace:
            raise ValueError("no frames streamed yet")
        d_model, chips, paper_fps = self.cfg.d_model, self.chips, None
        if workload is not None:
            w = S.WORKLOADS[workload]
            if w.seq != self.cfg.seq_len:
                raise ValueError(
                    f"measured stage traffic ({self.cfg.seq_len} tokens) "
                    f"!= workload {workload!r} ({w.seq} tokens)"
                )
            d_model, chips = w.d, w.chips
            if workload in S.PAPER_TABLE7:
                paper_fps = S.PAPER_TABLE7[workload][1]
            elif workload in S.PAPER_TABLE9:
                paper_fps = S.PAPER_TABLE9[workload]
        trace = list(self.trace)
        while len(trace) < min_frames:
            trace.extend(self.trace)
        rep = pipe_mod.simulate(
            [pipe_mod.Job(0.0, n) for n in trace], d_model, chips=chips
        )
        return VisionReport(
            pipeline=rep,
            fps=rep.steady_state_fps,
            frame_latency_s=rep.timings[0].latency,
            n_tokens=self.trace[0],
            d_model=d_model,
            chips=chips,
            paper_fps=paper_fps,
        )


def synthetic_stream_report(n_tokens: int, d_model: int, chips: int = 1,
                            n_frames: int = 240,
                            paper_fps: float | None = None) -> VisionReport:
    """FWS pipeline report for traffic-shaped-only streams (no executable
    model run): e.g. bert-base-shaped traffic (N=512 jobs) or full-size
    Table 7 rows where only the (N, d, chips) shape matters."""
    rep = pipe_mod.simulate(
        [pipe_mod.Job(0.0, n_tokens) for _ in range(n_frames)],
        d_model, chips=chips,
    )
    return VisionReport(
        pipeline=rep,
        fps=rep.steady_state_fps,
        frame_latency_s=rep.timings[0].latency,
        n_tokens=n_tokens,
        d_model=d_model,
        chips=chips,
        paper_fps=paper_fps,
    )
