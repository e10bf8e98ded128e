"""repro.obs — the observability plane.

Spans device and host:

  * :mod:`repro.obs.rings` — device-side per-tick telemetry rings
    (``ObsState``, structurally absent when disabled) drained at chunk
    boundaries by the scan/shard engines;
  * :mod:`repro.obs.trace` — host spans of the sweep driver and the
    chunk driver (bucket pick, chunk launch, wait for ``done``), to
    Chrome trace-event / Perfetto JSON and, whenever a ``jax.profiler``
    session is active, into the profiler's trace on the device's clock;
  * :mod:`repro.obs.metrics` — process metrics registry (counters /
    gauges / histograms) with JSONL + Prometheus-textfile export;
  * :mod:`repro.obs.timing` — the shared benchmark timers;
  * :mod:`repro.obs.manifest` — run manifests with round-trippable
    config hashes;
  * :mod:`repro.obs.report` — ring-history and forecast-rows summaries;
  * :mod:`repro.obs.analyze` — vectorized post-drain detectors (EWMA /
    CUSUM / burst / coverage-drift / SLO burn-rate) over ring
    histories;
  * :mod:`repro.obs.alerts` — the alert-rule watchdog the sweep driver
    evaluates per cell;
  * :mod:`repro.obs.dashboard` — stdlib-only static HTML report from
    run artifacts.

Import-light on purpose: nothing here imports ``repro.sim`` (the sim
imports us), and jax is only touched lazily where a device is involved.
"""
from repro.obs.alerts import (DEFAULT_RULES, AlertRule, evaluate_rules,
                              write_alert_log)
from repro.obs.analyze import (Detection, burn_rate_detect, burst_detect,
                               coverage_drift_detect, cusum_detect,
                               ewma_detect)
from repro.obs.config import ObsConfig
from repro.obs.dashboard import render_dashboard
from repro.obs.manifest import (build_manifest, cell_hash, config_hash,
                                load_manifest, write_manifest)
from repro.obs.metrics import REGISTRY, MetricsRegistry
from repro.obs.report import (bucketed_row_overhead, compact_history,
                              masked_row_overhead, obs_summary)
from repro.obs.timing import best_of, time_us
from repro.obs.trace import (Tracer, current_tracer, span, tracing,
                             validate_trace)

__all__ = [
    "ObsConfig",
    "REGISTRY", "MetricsRegistry",
    "Tracer", "span", "tracing", "current_tracer", "validate_trace",
    "best_of", "time_us",
    "config_hash", "cell_hash", "build_manifest", "write_manifest",
    "load_manifest",
    "masked_row_overhead", "bucketed_row_overhead",
    "obs_summary", "compact_history",
    "Detection", "ewma_detect", "cusum_detect", "burst_detect",
    "coverage_drift_detect", "burn_rate_detect",
    "AlertRule", "DEFAULT_RULES", "evaluate_rules", "write_alert_log",
    "render_dashboard",
]
