"""Span-based request tracer + typed engine lifecycle events.

The serving engines emit typed events through an :class:`Obs` handle —
``enqueue -> admitted -> prefill/first-token -> per-decode-step ->
finish/evict`` for LM requests, one ``frame`` span per streamed image
for the vision engine — and the tracer turns them into per-request
metrics (TTFT, queue wait, per-token latency, end-to-end latency) plus
registry counters/gauges/histograms.

Each step runs under :meth:`Obs.step_span`, which owns the step's record
(its bounds, and the event kept when it closes), and the engines mark its
host phases with :meth:`Obs.span`. Both are ``jax.profiler.TraceAnnotation``
s (so a profiled run shows them on the device ops' clock); when enabled,
each also leaves a ``(name, t0, t1)`` record on ``Obs.clock`` in the
step's ``StepEvent.phases`` and the ``serve_step_phase_seconds{kind,phase}``
histogram, and a step far slower than the median of its kind is logged
with its phases.

The old ad-hoc ``(kind, rids, n_tokens)`` tuple list survives as a
*derived view* (:meth:`Obs.legacy_trace`) so ``pipeline.simulate_trace``
and every existing consumer keep working unchanged.

``Obs(enabled=False)`` keeps the step-event record (the pre-PR trace
equivalent, needed by the pipeline model) but skips all per-request
span tracking, phase records and registry updates; its spans are bare
profiler annotations — the measured-overhead baseline.
"""

from __future__ import annotations

import dataclasses
import time

import jax

from repro.obs import log as log_mod
from repro.obs import registry as reg_mod

# a step whose wall exceeds this many times the median of its kind (once
# 16 of them were seen), and SLOW_STEP_MIN_S, is logged with its phases
SLOW_STEP = 5.0
SLOW_STEP_MIN_S = 0.01


@dataclasses.dataclass(frozen=True)
class StepEvent:
    """One scheduled unit of engine work (a prefill, a decode step over
    the live lanes, or a streamed vision frame)."""

    kind: str  # prefill | decode | frame
    rids: tuple
    n_tokens: int
    t_start: float
    t_end: float  # the step's result was on the host (after its sync)
    # (name, t0, t1) of the step's span and of each phase inside it, by
    # start; the span ends after the sync, once the tokens are emitted.
    # Empty when the Obs is disabled.
    phases: tuple = ()

    @property
    def legacy(self) -> tuple:
        return (self.kind, self.rids, self.n_tokens)

    @property
    def wall_s(self) -> float:
        return self.t_end - self.t_start


@dataclasses.dataclass
class RequestMetrics:
    """Per-request span record, finalized at finish/evict."""

    rid: int
    n_prompt: int = 0
    t_enqueue: float = 0.0
    t_admitted: float | None = None
    t_first_token: float | None = None
    t_finish: float | None = None
    finish_reason: str | None = None
    token_times: list = dataclasses.field(default_factory=list)

    @property
    def n_generated(self) -> int:
        return len(self.token_times)

    @property
    def queue_wait_s(self) -> float | None:
        if self.t_admitted is None:
            return None
        return self.t_admitted - self.t_enqueue

    @property
    def ttft_s(self) -> float | None:
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_enqueue

    @property
    def e2e_s(self) -> float | None:
        if self.t_finish is None:
            return None
        return self.t_finish - self.t_enqueue

    @property
    def token_intervals_s(self) -> list:
        """Inter-token gaps after the first token (decode cadence)."""
        t = self.token_times
        return [b - a for a, b in zip(t, t[1:])]


class _Span:
    """:meth:`Obs.span`'s context manager (a class: a generator-based one
    costs a few microseconds more on every step)."""

    __slots__ = ("obs", "name", "ann", "t0")

    def __init__(self, obs: "Obs", name: str, meta: dict):
        self.obs = obs
        self.name = name
        self.ann = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self):
        self.ann.__enter__()
        if self.obs._phases is not None:
            self.t0 = self.obs.clock()
        return self

    def __exit__(self, *exc):
        phases = self.obs._phases
        if phases is not None:
            phases.append((self.name, self.t0, self.obs.clock()))
        self.ann.__exit__(*exc)
        return False


class _StepSpan:
    """:meth:`Obs.step_span`'s context manager: the step's own span, which
    owns its record."""

    __slots__ = ("obs", "name", "ann", "t0", "t_end", "events")

    def __init__(self, obs: "Obs", name: str, meta: dict):
        self.obs = obs
        self.name = name
        self.ann = jax.profiler.TraceAnnotation(name, **meta)
        self.t_end = None
        self.events: list = []

    def __enter__(self):
        self.ann.__enter__()
        obs = self.obs
        if obs.enabled:
            obs._phases = []
        self.t0 = obs.clock()
        return self

    def record(self, kind: str, rids: tuple, n_tokens: int,
               lanes: int | None = None) -> float:
        """The step's result is on the host: keep a step event for when
        the span closes. The first call stamps the step's end, which it
        returns; a step that records several events (a slab of frames)
        bills each an equal slice of its wall."""
        if self.t_end is None:
            self.t_end = self.obs.clock()
        self.events.append((kind, tuple(rids), n_tokens, lanes))
        return self.t_end

    def __exit__(self, *exc):
        obs = self.obs
        phases, obs._phases = obs._phases, None
        try:
            if phases is not None:
                phases.sort(key=lambda p: (p[1], -p[2]))
                phases.insert(0, (self.name, self.t0, obs.clock()))
            t0, t1, k = self.t0, self.t_end, len(self.events)
            for j, (kind, rids, n, lanes) in enumerate(self.events):
                last = j == k - 1
                obs.step_recorded(
                    kind, rids, n, t0 + (t1 - t0) * j / k,
                    t1 if k == 1 else t0 + (t1 - t0) * (j + 1) / k,
                    lanes=lanes,
                    phases=tuple(phases) if last and phases else ())
        finally:
            self.ann.__exit__(*exc)
        return False


class Obs:
    """Telemetry handle threaded through the serving stack.

    Carries the metrics registry, the step-event record with each
    step's phases, and per-request spans. Engines accept one at
    construction; ``None`` means "create a private enabled one", so
    telemetry is on by default without any caller changes.
    """

    def __init__(self, registry: reg_mod.MetricsRegistry | None = None,
                 enabled: bool = True, clock=time.perf_counter):
        self.registry = registry or reg_mod.MetricsRegistry()
        self.enabled = enabled
        self.clock = clock
        self.steps: list[StepEvent] = []
        self.live: dict[int, RequestMetrics] = {}
        self.finished: list[RequestMetrics] = []
        # the closed phases of the open step; None outside an enabled one
        self._phases: list | None = None
        self._hists: dict = {}  # (kind, phase) -> its histogram

    # ------------------------------------------------------------ spans

    def step_span(self, name: str, **meta) -> "_StepSpan":
        """One scheduled step: a profiler annotation carrying ``meta``
        that owns the step's record, which runs from the span's start to
        its first :meth:`_StepSpan.record` call (the result is on the
        host). The event(s) recorded are kept when the span closes, even
        when disabled; when enabled, with the step's own ``(name, t0,
        t1)`` and those of the :meth:`span` phases inside it as their
        ``phases``."""
        return _StepSpan(self, name, meta)

    def span(self, name: str, **meta) -> "_Span":
        """A named host phase of the open step: a profiler annotation
        carrying ``meta`` and, when enabled, a ``(name, t0, t1)`` record
        in the step's phases. Outside a step it is only the annotation."""
        return _Span(self, name, meta)

    def _phase_hist(self, kind: str, phase: str) -> reg_mod.Histogram:
        h = self._hists.get((kind, phase))
        if h is None:
            h = self._hists[kind, phase] = self.registry.histogram(
                "serve_step_phase_seconds",
                "host wall per step phase (the step's own span included)",
                labels={"kind": kind, "phase": phase})
        return h

    def _observe_phases(self, kind: str, phases: tuple) -> None:
        name, t0, t1 = phases[0]
        h = self._phase_hist(kind, name)
        if t1 - t0 > SLOW_STEP_MIN_S and h.count >= 16:
            med = h.quantile(0.5)
            if t1 - t0 > SLOW_STEP * med:
                log_mod.get_logger("repro.obs").warning(
                    "slow step %s: %.4f s, median %.4f s: %s", name,
                    t1 - t0, med,
                    log_mod.kv(**{n: b - a for n, a, b in phases[1:]}))
        for name, t0, t1 in phases:
            self._phase_hist(kind, name).observe(t1 - t0)

    # -------------------------------------------------- request lifecycle

    def request_enqueued(self, rid: int, n_prompt: int = 0,
                         t: float | None = None) -> None:
        if not self.enabled:
            return
        self.live[rid] = RequestMetrics(
            rid=rid, n_prompt=n_prompt,
            t_enqueue=self.clock() if t is None else t,
        )
        self.registry.counter(
            "serve_requests_total", "requests submitted to the engine"
        ).inc()

    def request_admitted(self, rid: int, t: float | None = None) -> None:
        if not self.enabled:
            return
        r = self.live.get(rid)
        if r is None or r.t_admitted is not None:
            return
        r.t_admitted = self.clock() if t is None else t
        self.registry.histogram(
            "serve_queue_wait_seconds", "enqueue -> admission wait"
        ).observe(r.queue_wait_s)

    def token_emitted(self, rid: int, t: float | None = None) -> None:
        if not self.enabled:
            return
        r = self.live.get(rid)
        if r is None:
            return
        t = self.clock() if t is None else t
        if r.t_first_token is None:
            r.t_first_token = t
            self.registry.histogram(
                "serve_ttft_seconds", "enqueue -> first token (host wall)"
            ).observe(r.ttft_s)
        else:
            self.registry.histogram(
                "serve_token_latency_seconds",
                "inter-token decode gap (host wall)",
            ).observe(t - r.token_times[-1])
        r.token_times.append(t)
        self.registry.counter(
            "serve_tokens_generated_total", "tokens emitted"
        ).inc()

    def request_finished(self, rid: int, reason: str = "max_new",
                         t: float | None = None) -> None:
        if not self.enabled:
            return
        r = self.live.pop(rid, None)
        if r is None:
            return
        r.t_finish = self.clock() if t is None else t
        r.finish_reason = reason
        self.finished.append(r)
        self.registry.counter(
            "serve_requests_finished_total", "completed requests by reason",
            labels={"reason": reason},
        ).inc()
        if reason == "page_exhausted":
            self.registry.counter(
                "serve_evictions_total",
                "requests evicted on KV-page exhaustion",
            ).inc()
        self.registry.histogram(
            "serve_request_latency_seconds", "enqueue -> finish (host wall)"
        ).observe(r.e2e_s)

    # ------------------------------------------------------- engine steps

    def step_recorded(self, kind: str, rids: tuple, n_tokens: int,
                      t_start: float, t_end: float,
                      lanes: int | None = None, phases: tuple = ()) -> None:
        """Record one scheduled step. Always kept (it is the pipeline
        model's input); registry updates only when enabled."""
        self.steps.append(StepEvent(kind, tuple(rids), n_tokens,
                                    t_start, t_end, phases))
        if not self.enabled:
            return
        if phases:
            self._observe_phases(kind, phases)
        self.registry.counter(
            "serve_steps_total", "scheduled engine steps by kind",
            labels={"kind": kind},
        ).inc()
        self.registry.histogram(
            "serve_step_wall_seconds", "host wall per scheduled step",
            labels={"kind": kind},
        ).observe(t_end - t_start)
        if kind == "decode" and lanes:
            self.registry.histogram(
                "serve_decode_occupancy",
                "live lanes / total lanes per decode step",
                buckets=reg_mod.RATIO_BUCKETS,
            ).observe(len(rids) / lanes)

    def lanes_state(self, queued: int, active: int, free_slots: int) -> None:
        if not self.enabled:
            return
        self.registry.gauge("serve_queue_depth", "waiting requests").set(queued)
        self.registry.gauge("serve_active_lanes", "lanes decoding live work").set(active)
        self.registry.gauge("serve_free_slots", "free KV pool slots").set(free_slots)

    # ------------------------------------------------------ derived views

    def legacy_trace(self) -> list:
        """The pre-PR ``(kind, rids, n_tokens)`` tuple list, derived."""
        return [e.legacy for e in self.steps]

    def reset(self) -> None:
        """Drop recorded steps and finished spans (e.g. after a jit
        warmup run) — registered metric values are left alone."""
        self.steps.clear()
        self.finished.clear()
        self.live.clear()

    def request_summary(self) -> dict:
        """Percentile summary over finished requests (host wall)."""

        def pct(samples):
            if not samples:
                return None
            s = sorted(samples)

            def at(q):
                return s[min(int(q * len(s)), len(s) - 1)]

            return {"p50": at(0.5), "p90": at(0.9), "p99": at(0.99),
                    "mean": sum(s) / len(s), "n": len(s)}

        reqs = self.finished
        intervals = [iv for r in reqs for iv in r.token_intervals_s]
        return {
            "n_requests": len(reqs),
            "n_tokens": sum(r.n_generated for r in reqs),
            "ttft_s": pct([r.ttft_s for r in reqs if r.ttft_s is not None]),
            "queue_wait_s": pct(
                [r.queue_wait_s for r in reqs if r.queue_wait_s is not None]
            ),
            "token_latency_s": pct(intervals),
            "e2e_s": pct([r.e2e_s for r in reqs if r.e2e_s is not None]),
            "finish_reasons": _count_by(
                r.finish_reason for r in reqs
            ),
        }


def _count_by(items) -> dict:
    out: dict = {}
    for x in items:
        out[x] = out.get(x, 0) + 1
    return out
