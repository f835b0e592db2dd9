"""Serving telemetry subsystem (pure Python host-side, no new deps).

- :mod:`repro.obs.registry` — counters / gauges / fixed-bucket
  histograms with p50/p90/p99 quantile extraction, grouped into
  Prometheus-style metric families.
- :mod:`repro.obs.tracing` — the :class:`Obs` handle the serving
  engines emit typed lifecycle events through (enqueue -> admitted ->
  prefill/first-token -> decode steps -> finish/evict), yielding TTFT,
  queue-wait, per-token latency, occupancy and eviction metrics, and
  ``Obs.span``, the engines' per-step host phases on both the host
  clock and the profiler's; the old ``(kind, rids, n_tokens)`` tuple
  trace is a derived view.
- :mod:`repro.obs.export` — Prometheus text exposition + JSON snapshot
  writers (and the parser the round-trip test uses).
- :mod:`repro.obs.slo` — configurable TTFT / per-token latency targets
  scored over finished-request spans.
- :mod:`repro.obs.fidelity` — numerical-fidelity observability:
  ``sqnr_db`` / per-layer SQNR tracing, the :class:`FidelityProbe`
  (MXFP4 clip/underflow counters, ADC saturation + code-utilization
  histograms via ``RunCtx.fidelity``), and the calibration-drift
  detector comparing live Row-Hist statistics against stored
  ``LayerCalib``.
"""

from repro.obs.export import (  # noqa: F401
    parse_prometheus,
    to_json,
    to_prometheus,
    write_metrics,
)
from repro.obs.fidelity import (  # noqa: F401
    FidelityProbe,
    run_fidelity_pass,
    scale_adc_fs,
    sqnr_db,
    sqnr_trace,
)
from repro.obs.log import get_logger, kv  # noqa: F401
from repro.obs.registry import (  # noqa: F401
    EXP_BUCKETS,
    LATENCY_BUCKETS_S,
    RATIO_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.slo import SLOTargets, evaluate_slo  # noqa: F401
from repro.obs.tracing import Obs, RequestMetrics, StepEvent  # noqa: F401
