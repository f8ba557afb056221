"""Observability: device-resident serving metrics, request tracing, and
the offline calibration recorder.

Three planes, three sync disciplines:

- **metrics** (``obs.metrics``): a device pytree of counters/histograms
  updated with pure ``jnp`` inside the jitted serve_step; the host-side
  ``MetricsCollector`` harvests only at run end / window close.  Zero
  per-step syncs — machine-checked by reprolint's ``obs-discipline``;
- **tracing** (``obs.tracing``): the engine's host spans, on the device
  trace's clock when a profiler runs, and per-request Chrome/Perfetto
  trace JSON.  Diagnostic mode: on only with a ``TraceRecorder`` attached;
  per-slot device snapshots are opt-in;
- **calibration** (``obs.calibration``): nocache per-layer delta recorder
  for SmoothCache/spectral schedules.  Offline, syncs freely;
- **audit** (``obs.audit``): the shadow-compute quality plane — on a
  deterministic seeded fraction of serve steps the jitted step also runs
  the full uncached forward and folds cached-vs-true error into the
  metrics pytree and the per-request accumulators.  Pure ``jnp`` under one
  ``lax.cond``; statically dead when ``audit_fraction == 0``.
"""
from repro.obs.audit import (DEFAULT_AUDIT_FRACTION, audit_mask,
                             audit_report)
from repro.obs.calibration import (load_calibration, record_calibration,
                                   save_calibration)
from repro.obs.metrics import (METRICS, MetricsCollector, MetricSpec,
                               counter, histogram, histogram_quantile,
                               init_device_metrics, parse_prometheus)
from repro.obs.tracing import TraceRecorder, validate_trace

__all__ = [
    "DEFAULT_AUDIT_FRACTION", "METRICS", "MetricSpec", "MetricsCollector",
    "TraceRecorder", "audit_mask", "audit_report", "counter", "histogram",
    "histogram_quantile", "init_device_metrics", "load_calibration",
    "parse_prometheus", "record_calibration", "save_calibration",
    "validate_trace",
]
