"""repro.telemetry — the instrumentation floor of the serving stack.

Three stdlib-only modules (no jax imports — telemetry must be loadable
from any layer without cycles, and must never put wall-clock reads
inside jitted code; timestamps are taken only at host sync points):

- :mod:`repro.telemetry.registry` — a process-global metrics registry of
  counters, gauges and fixed-bucket histograms.  **Naming a metric**:
  dotted lowercase ``subsystem.metric[_unit]`` — ``serving.ttft_s``,
  ``serving.decode_tokens``, ``autotune.plan_cache_hits``.  The unit
  suffix (``_s``, ``_ms``, ``_pages``, ``_tokens``) is part of the name;
  the registry never rescales.  ``registry()`` returns the global
  instance; ``publish(prefix, mapping)`` mirrors an ad-hoc metrics dict
  into gauges so legacy ``metrics()`` surfaces and the registry agree.
- :mod:`repro.telemetry.tracing` — a span tracer with a **zero-overhead
  no-op default**: ``tracing.current()`` returns a process-wide
  singleton whose ``span()`` returns one reusable no-op context manager
  (no per-call allocation), so hot loops may be instrumented
  unconditionally.  **Adding a span**: ``with tracing.current().span(
  "phase_name"):`` around the host-side section — never inside a jitted
  function (the span would measure trace time, not run time).  Install a
  real :class:`~repro.telemetry.tracing.Tracer` to collect; ``export()``
  writes **Chrome/Perfetto trace-event JSON**: ``{"traceEvents": [...],
  "displayTimeUnit": "ms"}``, spans as phase-``X`` complete events
  (``ts``/``dur`` in microseconds), lifecycle/fault marks as
  phase-``i`` instants — load the file directly in ``ui.perfetto.dev``
  or ``chrome://tracing``.  Its spans are also ``jax.profiler``
  annotations (jax imported when a tracer is made), so a profiler
  session records them on the device trace's clock.
- :mod:`repro.telemetry.gemm_account` — per-GEMM dispatch accounting at
  the same seams ``repro.graph.trace.trace_gemms()`` hooks
  (``dispatch.mte_gemm``, ``kernels/ops.py``, compiled-program node
  execution), recording signature, format, the paper's M/N/K shape
  class (square vs tall/skinny), plan source (cache-hit / solver /
  pinned-geometry) and modeled time — the Fig. 7 traffic table for a
  live serving run.  Like ``trace_gemms``, hooks fire at jax *trace*
  time: counts are distinct compiled dispatches, not executed steps.

Three analysis modules turn those raw streams into answers (these import
jax / the planner lazily inside functions, so the package itself stays
import-light and cycle-free):

- :mod:`repro.telemetry.profiler` — the continuous profiler:
  :class:`DispatchProfiler` times dispatches per plan signature at host
  sync points, joins wall clock against ``perfmodel`` predictions and
  the accountant's provenance into a per-(shape_class, fmt, plan_source)
  **calibration table**, and runs the **plan-regret audit** (granted
  plan vs analytic runner-up, feeding ``PlanCache.recalibrate``).
- :mod:`repro.telemetry.slo` — declarative objectives over the registry
  (tail latency percentile, error-rate, pool headroom) evaluated as
  multi-window burn rates; :class:`SloMonitor` hooks the engine step.
- :mod:`repro.telemetry.export` — Prometheus text exposition of the
  whole registry plus the structured :func:`health` JSON snapshot
  (``launch/serve.py --prom`` / ``--status-json``).
"""
from repro.telemetry.registry import (Counter, Gauge, Histogram,
                                      MetricsRegistry, publish, registry,
                                      reset_registry)
from repro.telemetry.tracing import Tracer, validate_trace
from repro.telemetry.gemm_account import (GemmAccountant, GemmRecord,
                                          account_gemms, shape_class)
from repro.telemetry.profiler import DispatchProfiler, profile_records
from repro.telemetry.slo import (Slo, SloMonitor, SloReport, SloStatus,
                                 default_slos)
from repro.telemetry.export import (health, parse_prometheus,
                                    render_prometheus, validate_health)

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry", "publish",
           "registry", "reset_registry", "Tracer", "validate_trace",
           "GemmAccountant", "GemmRecord", "account_gemms", "shape_class",
           "DispatchProfiler", "profile_records",
           "Slo", "SloMonitor", "SloReport", "SloStatus", "default_slos",
           "health", "parse_prometheus", "render_prometheus",
           "validate_health"]
