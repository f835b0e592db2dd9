"""Telemetry subsystem invariants (host-side; no models).

Crux checks: the Prometheus text exposition round-trips through the
repo's own parser bit-for-bit in value space (the tier-1 exporter
acceptance), histogram quantiles are sane under the fixed-bucket
estimator, the span tracer derives the legacy trace view exactly,
``Obs.span`` nests step phases on its clock (and only annotates when
disabled), and every kernel wrapper's named scope reaches the lowered
program.
"""

import math

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.obs import export as export_mod
from repro.obs.registry import RATIO_BUCKETS


# ------------------------------------------------------------- registry

def test_counter_monotonic():
    r = obs.MetricsRegistry()
    c = r.counter("c_total", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_inc_dec():
    g = obs.MetricsRegistry().gauge("g", "")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0


def test_registry_type_conflict_raises():
    r = obs.MetricsRegistry()
    r.counter("m", "")
    with pytest.raises(ValueError):
        r.gauge("m", "")


def test_registry_label_series_distinct():
    r = obs.MetricsRegistry()
    a = r.counter("m_total", "", labels={"k": "a"})
    b = r.counter("m_total", "", labels={"k": "b"})
    a.inc(3)
    b.inc(5)
    assert (a.value, b.value) == (3, 5)
    # same label set -> same series object
    assert r.counter("m_total", "", labels={"k": "a"}) is a


def test_histogram_quantiles_uniform():
    h = obs.Histogram(buckets=tuple(float(i) for i in range(1, 101)))
    for i in range(1, 101):
        h.observe(i - 0.5)
    assert h.count == 100
    assert h.quantile(0.5) == pytest.approx(50, abs=1.0)
    assert h.quantile(0.99) == pytest.approx(99, abs=1.0)
    # clamped to observed extremes: no bucket-edge extrapolation
    assert h.quantile(0.0) >= h.min
    assert h.quantile(1.0) <= h.max
    p = h.percentiles()
    assert set(p) == {"p50", "p90", "p99"}


def test_histogram_empty_and_overflow():
    h = obs.Histogram(buckets=(1.0, 2.0))
    assert h.quantile(0.5) == 0.0
    h.observe(100.0)  # lands in +Inf bucket
    assert h.counts[-1] == 1
    assert h.quantile(0.99) == pytest.approx(100.0)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        obs.Histogram(buckets=(2.0, 1.0))


# ------------------------------------------------------------ exporters

def _populated_registry():
    r = obs.MetricsRegistry()
    r.counter("serve_requests_total", "requests in").inc(7)
    r.gauge("serve_queue_depth", "waiting").set(3)
    r.counter("steps_total", "by kind", labels={"kind": "decode"}).inc(4)
    r.counter("steps_total", "by kind", labels={"kind": "prefill"}).inc(2)
    h = r.histogram("serve_ttft_seconds", "ttft")
    for v in (1e-4, 2e-4, 5e-3, 0.1):
        h.observe(v)
    occ = r.histogram("occupancy", "ratio", buckets=RATIO_BUCKETS)
    occ.observe(0.75)
    r.gauge("weird", 'help with "quotes"\nand newline',
            labels={"path": 'a"b\\c'}).set(1.5)
    return r


def test_prometheus_round_trip():
    r = _populated_registry()
    text = export_mod.to_prometheus(r)
    samples = export_mod.parse_prometheus(text)
    assert samples[("serve_requests_total", ())] == 7
    assert samples[("serve_queue_depth", ())] == 3
    assert samples[("steps_total", (("kind", "decode"),))] == 4
    assert samples[("serve_ttft_seconds_count", ())] == 4
    assert samples[("serve_ttft_seconds_sum", ())] == pytest.approx(0.1053)
    assert samples[("weird", (("path", 'a"b\\c'),))] == 1.5
    # cumulative buckets: monotone, +Inf equals _count
    lad = sorted(
        (float("inf") if dict(ls)["le"] == "+Inf" else float(dict(ls)["le"]),
         v)
        for (name, ls), v in samples.items()
        if name == "serve_ttft_seconds_bucket"
    )
    counts = [v for _, v in lad]
    assert counts == sorted(counts)
    assert counts[-1] == 4


def test_prometheus_exposition_format_lines():
    text = export_mod.to_prometheus(_populated_registry())
    assert "# TYPE serve_requests_total counter" in text
    assert "# TYPE serve_ttft_seconds histogram" in text
    assert '_bucket{le="+Inf"} 4' in text


def test_json_snapshot_and_write(tmp_path):
    r = _populated_registry()
    snap = export_mod.to_json(r, extra={"slo": {"pass": True}})
    assert snap["slo"]["pass"] is True
    fam = snap["metrics"]["serve_ttft_seconds"]
    assert fam["type"] == "histogram"
    s = fam["series"][0]
    assert s["count"] == 4 and "p99" in s and "buckets" in s

    import json

    jp, pp = obs.write_metrics(r, str(tmp_path / "m.json"))
    assert pp.endswith(".prom")
    reloaded = json.load(open(jp))
    assert reloaded["metrics"]["serve_queue_depth"]["series"][0]["value"] == 3
    assert export_mod.parse_prometheus(open(pp).read())[
        ("serve_requests_total", ())
    ] == 7


# ------------------------------------------------------------ span tracer

def _fake_clock():
    t = [0.0]

    def clock():
        t[0] += 0.01
        return t[0]

    return clock


def test_obs_request_lifecycle_metrics():
    o = obs.Obs(clock=_fake_clock())
    o.request_enqueued(0, n_prompt=5, t=1.0)
    o.request_admitted(0, t=1.5)
    o.token_emitted(0, t=2.0)   # first token -> ttft
    o.token_emitted(0, t=2.25)  # inter-token gap
    o.request_finished(0, reason="max_new", t=3.0)
    [r] = o.finished
    assert r.queue_wait_s == pytest.approx(0.5)
    assert r.ttft_s == pytest.approx(1.0)
    assert r.e2e_s == pytest.approx(2.0)
    assert r.token_intervals_s == [pytest.approx(0.25)]
    assert o.registry.histogram("serve_ttft_seconds").count == 1
    assert o.registry.histogram("serve_token_latency_seconds").count == 1
    summ = o.request_summary()
    assert summ["n_requests"] == 1 and summ["n_tokens"] == 2
    assert summ["finish_reasons"] == {"max_new": 1}


def test_obs_eviction_counted():
    o = obs.Obs()
    o.request_enqueued(3)
    o.request_finished(3, reason="page_exhausted")
    assert o.registry.counter("serve_evictions_total").value == 1


def test_obs_legacy_trace_derived():
    o = obs.Obs()
    o.step_recorded("prefill", (0,), 8, 0.0, 1.0)
    o.step_recorded("decode", (0, 1), 2, 1.0, 1.5, lanes=4)
    assert o.legacy_trace() == [("prefill", (0,), 8), ("decode", (0, 1), 2)]
    assert o.steps[1].wall_s == pytest.approx(0.5)
    o.reset()
    assert o.legacy_trace() == []


def test_obs_disabled_keeps_steps_skips_registry():
    o = obs.Obs(enabled=False)
    o.request_enqueued(0)
    o.step_recorded("decode", (0,), 1, 0.0, 0.1, lanes=4)
    o.token_emitted(0)
    o.request_finished(0)
    assert len(o.steps) == 1  # pipeline-model input survives
    assert o.registry.families() == []  # no metric work
    assert o.finished == []


# ------------------------------------------------------------------- slo

def test_slo_pass_fail_and_violations():
    reqs = []
    for i in range(10):
        r = obs.RequestMetrics(rid=i, t_enqueue=0.0)
        r.t_first_token = 0.010 if i else 0.500  # one slow outlier
        r.token_times = [r.t_first_token, r.t_first_token + 0.002]
        r.t_finish = r.token_times[-1]
        reqs.append(r)
    ok = obs.evaluate_slo(reqs, obs.SLOTargets(ttft_p99_s=1.0))
    assert ok["pass"] is True and ok["violations"]["ttft_over_p99_target"] == 0
    bad = obs.evaluate_slo(reqs, obs.SLOTargets(ttft_p99_s=0.1))
    assert bad["pass"] is False
    assert bad["violations"]["ttft_over_p99_target"] == 1
    assert bad["checks"]["ttft_p99_s"]["ok"] is False


def test_slo_no_samples_is_indeterminate_not_failing():
    res = obs.evaluate_slo([], obs.SLOTargets(ttft_p99_s=0.1))
    assert res["checks"]["ttft_p99_s"]["ok"] is None
    assert res["pass"] is True


# ------------------------------------------------------------ step spans

def _tick_clock(step=1.0):
    t = [0.0]

    def clock():
        t[0] += step
        return t[0]

    return clock


def test_span_nests_orders_and_attaches_phases_to_the_step():
    o = obs.Obs(clock=_tick_clock())
    with o.step_span("engine.decode", step=1) as st:  # t0=1
        with o.span("engine.schedule"):             # 2..3
            pass
        with o.span("engine.sync"):                 # 4..5
            pass
        with o.span("engine.emit"):                 # 6..9
            assert st.record("decode", (0,), 1) == 7.0
            o.clock()                               # 8
    [ev] = o.steps                                  # step closes at 10
    assert ev.phases == (("engine.decode", 1.0, 10.0),
                         ("engine.schedule", 2.0, 3.0),
                         ("engine.sync", 4.0, 5.0),
                         ("engine.emit", 6.0, 9.0))
    # the step record runs from the span's start until its result was
    # recorded; self time is the step less its sync child
    assert (ev.t_start, ev.t_end) == (1.0, 7.0)
    (_, t0, t1), sync = ev.phases[0], ev.phases[2]
    assert (t1 - t0) - (sync[2] - sync[1]) == 8.0
    h = o.registry.histogram("serve_step_phase_seconds",
                             labels={"kind": "decode", "phase": "engine.emit"})
    assert (h.count, h.sum) == (1, 3.0)
    assert o.registry.histogram("serve_step_wall_seconds",
                                labels={"kind": "decode"}).sum == 6.0


def test_span_without_a_step_inside_attaches_nothing():
    o = obs.Obs(clock=_tick_clock())
    # a phase outside a step is only an annotation: it reads no clock
    with o.span("engine.sync"):
        pass
    assert o.clock() == 1.0
    # a step that raised before its result was recorded keeps nothing
    with pytest.raises(RuntimeError):
        with o.step_span("engine.decode"):
            with o.span("engine.sync"):
                raise RuntimeError("device lost")
    assert o.steps == [] and o.registry.families() == []
    # the next step starts from an empty phase list
    with o.step_span("engine.decode") as st:
        st.record("decode", (0,), 1)
    assert [n for n, _, _ in o.steps[0].phases] == ["engine.decode"]


def test_step_span_bills_a_slab_of_frames_equal_slices():
    o = obs.Obs(clock=_tick_clock())
    with o.step_span("vision.frame", frame=0, frames=3) as st:  # t0=1
        with o.span("vision.sync"):                 # 2..3
            pass
        for fid in range(3):
            st.record("frame", (fid,), 197)          # end stamped at 4
    assert [(e.rids, e.t_start, e.t_end) for e in o.steps] == [
        ((0,), 1.0, 2.0), ((1,), 2.0, 3.0), ((2,), 3.0, 4.0)]
    # the phases ride on the slab's last frame
    assert [len(e.phases) for e in o.steps] == [0, 0, 2]
    assert o.registry.histogram("serve_step_wall_seconds",
                                labels={"kind": "frame"}).count == 3


def test_disabled_span_only_annotates(monkeypatch):
    entered = []

    class Annotation:
        def __init__(self, name, **meta):
            entered.append((name, meta))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    for enabled in (False, True):
        o = obs.Obs(enabled=enabled, clock=_tick_clock())
        with o.step_span("vision.frame", frame=3) as st:
            with o.span("vision.sync"):
                st.record("frame", (3,), 197)
        assert entered == [("vision.frame", {"frame": 3}),
                           ("vision.sync", {})]
        entered.clear()
        # the step record is kept either way (the pipeline model's input)
        [ev] = o.steps
        assert ev.wall_s > 0
        assert len(ev.phases) == (2 if enabled else 0)
        assert bool(o.registry.families()) == enabled


def test_slow_step_is_logged_with_its_phases():
    import logging

    class Keep(logging.Handler):
        def __init__(self):
            super().__init__()
            self.lines = []

        def emit(self, record):
            self.lines.append(record.getMessage())

    keep = Keep()
    logger = logging.getLogger("repro.obs")
    logger.addHandler(keep)
    try:
        # a compiling warm-up step first, then the steady steps; then
        # steps of microseconds, whose outliers are no stall
        t = [0.0]
        for walls in ([3.0] + [0.1] * 16 + [0.3, 2.0, 1.5],
                      [1e-4] * 16 + [5e-3]):
            o = obs.Obs(clock=lambda: t[0])
            for w in walls:
                with o.step_span("engine.decode") as st:
                    with o.span("engine.sync"):
                        t[0] += w
                    with o.span("engine.emit"):
                        st.record("decode", (0,), 1)
    finally:
        logger.removeHandler(keep)
    # every step over 5x the median of its kind, however slow the
    # warm-up or an earlier stall was
    assert len(keep.lines) == 2
    for line, wall in zip(keep.lines, ("2.0000", "1.5000")):
        assert line.startswith(f"slow step engine.decode: {wall} s")
        assert f"engine.sync={float(wall):.4g}" in line


# ------------------------------------------------- kernel named scopes

def _scoped_kernels():
    from repro.core import cim as cimlib
    from repro.core import mx as mxlib
    from repro.kernels.cim_linear import ops as cim_ops
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.kernels.mxfp4_matmul import ops as mm_ops
    from repro.kernels.paged_attention import ops as pa_ops

    x = jnp.ones((16, 64), jnp.float32)
    wq = mxlib.quantize_w(jnp.ones((64, 32), jnp.float32))
    cfg = cimlib.CIMConfig()
    calib = cimlib.calibrate_rowhist([x], wq, cfg)
    codes = mxlib.pack_codes(wq.codes.T).T
    exps = mxlib.exps_to_biased(wq.exps)
    q = jnp.ones((1, 8, 2, 16), jnp.float32)
    pages = jnp.ones((2, 16, 4, 16), jnp.float32)
    lanes = jnp.zeros((2,), jnp.int32)
    return {
        "mxfp4_matmul": lambda: mm_ops.mxfp4_matmul(
            x.astype(jnp.bfloat16), codes, exps, interpret=True),
        "cim_linear": lambda: cim_ops.cim_linear(
            x, wq, calib, cfg=cfg, interpret=True),
        "flash_attention": lambda: fa_ops.flash_attention(
            q, q, q, interpret=True),
        "paged_attention.ref": lambda: pa_ops.ragged_paged_decode(
            jnp.ones((2, 2, 1, 16), jnp.float32), lanes, lanes + 3,
            kv=pages, scale=0.25),
    }


@pytest.mark.parametrize("kernel", ["mxfp4_matmul", "cim_linear",
                                    "flash_attention", "paged_attention.ref"])
def test_kernel_named_scope_reaches_hlo_metadata(kernel):
    """The wrappers' ``repro/<kernel>`` scopes cost nothing at run time
    and name the kernel's ops in the lowered program's locations."""
    text = jax.jit(_scoped_kernels()[kernel]).lower().as_text(
        debug_info=True)
    assert f"/repro/{kernel}/" in text


# ------------------------------------------------------------- fidelity

def test_sqnr_reexport_compat():
    from repro.core.metrics import sqnr_db as legacy
    from repro.obs import sqnr_db

    assert legacy is sqnr_db
    assert sqnr_db([1.0, 2.0], [1.0, 2.0]) > 200  # exact match -> cap
    assert sqnr_db([1.0, 0.0], [0.0, 0.0]) == pytest.approx(
        10 * math.log10(0.5 / 0.5)
    )
