"""Unified run telemetry: span tracing, in-rollout health probes, and
run reports.

Three layers (see ``docs/observability.md``):

* :mod:`repro.obs.trace`  -- :class:`Tracer`: nestable wall-clock spans
  on monotonic clocks, a bounded in-memory ring + JSONL sink, and a
  Chrome/Perfetto trace-event exporter; each span is also a
  ``jax.profiler.TraceAnnotation``, on the profiler's clock. Threaded
  through the segment drivers, the online refresh controller, the fault
  injector, and the training launcher (``launch/train.run``).
* :mod:`repro.obs.probes` -- :class:`HealthProbes`: the paper's
  convergence-predicting quantities (consensus distance, Assumption-4
  gradient deviation, Prop. 2 tau_bar at the live Pi_hat) computed
  INSIDE compiled rollouts as pure value computations -- zero retraces,
  a sample every step.
* :mod:`repro.obs.report` -- :class:`RunReport` (one versioned
  JSON/markdown document aggregating metrics, byte fates, events,
  health series, spans, and compiles) and :class:`RetraceGuard` (the
  first-class jit cache-miss counter behind the repo-wide
  "retraces == 0" invariant).
"""

from .probes import (
    HealthProbes,
    compute_probes,
    consensus_sq,
    grad_deviation_sq,
    mix_pi_arrays,
    tau_bar_arrays,
    w_frobenius_sq,
    w_minus_j_frobenius_sq,
)
from .report import (
    REPORT_SCHEMA,
    RetraceGuard,
    RunReport,
    load_report,
    validate_report,
)
from .trace import SpanRecord, Tracer, read_jsonl

__all__ = [
    "Tracer",
    "SpanRecord",
    "read_jsonl",
    "HealthProbes",
    "compute_probes",
    "consensus_sq",
    "grad_deviation_sq",
    "mix_pi_arrays",
    "tau_bar_arrays",
    "w_frobenius_sq",
    "w_minus_j_frobenius_sq",
    "RunReport",
    "RetraceGuard",
    "REPORT_SCHEMA",
    "validate_report",
    "load_report",
]
