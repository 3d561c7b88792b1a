"""Training telemetry: structured metrics, profiling spans, trace analysis.

The framework's north star is "as fast as the hardware allows" — which is
unclaimable without instrumentation. This package is the single home for
everything that *observes* a run, so every perf PR can ship a recomputable
evidence trail instead of prose:

- ``metrics``      the recording surface: ``MetricsRecorder`` (in-memory
                   counters / gauges / timers / per-step histograms),
                   ``JsonlMetrics`` (the versioned JSONL sink) and
                   ``NullMetrics`` (the default: recording disabled costs
                   nothing on the hot path but its spans, which are real);
- ``spans``        host spans, the one source of host-side timing, always
                   on: ``HOST_SPANS`` (the vocabulary), the process's bounded
                   span log (``log()``: every closed span and every compile
                   event, recorder or none), ``jax.profiler``
                   TraceAnnotations (so the phases of a session's
                   construction, device puts, an epoch's dispatch and
                   readback are labeled inside profiler captures) and
                   ``span`` records where a recorder is bound; the package's
                   one compile listener (``listen_to_compiles``); plus
                   ``capture`` wrapping ``jax.profiler.trace``;
- ``trace_stats``  the chrome-trace analyzer behind docs/performance.md's
                   roofline numbers (promoted from scripts/ to an importable,
                   tested module; the script remains as a thin shim);
- ``flight``       the step-level flight recorder: a bounded ring buffer of
                   per-step (loss, grad-norm, param-norm) samples, fed by
                   the fused epoch programs' aux outputs (never host
                   callbacks inside the scan) and emitted as schema-v2
                   ``step`` records;
- ``health``       the numerics health monitor: NaN/Inf, rolling-window
                   loss-divergence and grad-spike checks over the flight
                   aux, with a record/warn/halt policy
                   (``TrainingSession(health=...)``, ``train.py --health``);
- ``stats``        the ONE percentile definition (np.percentile, linear
                   interpolation) shared by the serving engine's summary,
                   the fleet summary and the report CLI's killed-run
                   fallback — three consumers, one definition, so p99 can
                   never disagree with itself — plus the ONE
                   first-enqueue→last-complete serving-window definition
                   (``ThroughputWindow``) behind both summaries' rates;
- ``tracing``      distributed request tracing (schema-v10 ``trace``
                   records): the span ``Tracer`` the serving engine and
                   fleet emit through, cross-process clock alignment from
                   the fleet handshake's round-trip offset estimates, the
                   chain reader that joins parent + ``.r*`` shards onto
                   one parent timeline (refusing orphan/unclosed chains
                   for terminal requests), and the phase-attribution /
                   waterfall analysis behind the report's Tracing
                   section;
- ``rollup``       streaming rollups (schema-v11 ``rollup`` records):
                   tumbling-window online counters / gauges / EWMA rates
                   and the mergeable log-bucketed ``QuantileSketch``
                   (documented relative-error bound vs ``stats``'s
                   percentile), closed purely on record timestamps with
                   a bounded ring, plus the ``.r*``/``.p*`` shard merge
                   that re-aligns windows via the tracing clock offsets;
- ``slo``          SLO alerting (schema-v11 ``alert`` records):
                   multi-window multi-burn-rate rules, event-triggered
                   breaker/health rules, the firing→resolved lifecycle,
                   the ``AlertSink`` hook (ROADMAP item 4's autoscaler
                   contract) and ``LiveTelemetry`` — the rollup+rules
                   sensor the engine, fleet and training session own;
- ``watch``        the live dashboard CLI
                   (``python -m shallowspeed_tpu.observability.watch``):
                   tails live JSONL shards (``--follow``) or reads
                   finished runs (``--once``), rendering current-window
                   throughput / p50 / p99 / queue depth / alert state;
- ``costmodel``    analytical MLP FLOPs + ``Compiled.cost_analysis()``
                   cross-check + MFU accounting (the ``cost_model`` event,
                   ``achieved_flops_per_sec`` and ``mfu`` gauges per layout);
- ``program_audit`` the XLA program audit: collective census parsed from
                   ``Compiled.as_text()``, ``memory_analysis()`` through
                   one shared helper, the analytical comms model derived
                   from the layout + lowered tick tables, and the
                   census-vs-contract cross-check that fails loudly
                   (``TrainingSession(audit=True)`` / ``train.py --audit``;
                   schema-v3 ``xla_audit`` records);
- ``report``       the run-report CLI
                   (``python -m shallowspeed_tpu.observability.report``):
                   throughput, MFU, span breakdown, bubble fraction,
                   step-loss sparkline, health verdict, and a
                   ``--baseline`` regression gate for CI/bench.

Wiring: ``TrainingSession(metrics=JsonlMetrics(path))`` records per-epoch
training telemetry (loss, samples/s, grad-norm when clipping), per-step
flight records, MFU gauges, compile-time spans, and — on mesh layouts — the
lowered pipeline program's static tick stats (ticks, sends, stage occupancy,
bubble fraction). The CLI flags are ``train.py --metrics-out FILE`` and
``--health record|warn|halt``. See docs/observability.md.
"""

from shallowspeed_tpu.observability.flight import FlightRecorder
from shallowspeed_tpu.observability.health import (
    HealthError,
    HealthMonitor,
)
from shallowspeed_tpu.observability.metrics import (
    SCHEMA_VERSION,
    JsonlMetrics,
    MetricsRecorder,
    NullMetrics,
    read_jsonl,
    replica_shard_path,
)
from shallowspeed_tpu.observability.program_audit import AuditMismatchError
from shallowspeed_tpu.observability.rollup import (
    QuantileSketch,
    RollupBuilder,
    merge_rollup_records,
)
from shallowspeed_tpu.observability.slo import (
    AlertSink,
    BurnRateRule,
    EventRule,
    LiveTelemetry,
    SloEvaluator,
    ThresholdRule,
)
from shallowspeed_tpu.observability.spans import Span, capture, span
from shallowspeed_tpu.observability.stats import ThroughputWindow, percentile
from shallowspeed_tpu.observability.tracing import TraceError, Tracer

__all__ = [
    "SCHEMA_VERSION",
    "AlertSink",
    "AuditMismatchError",
    "BurnRateRule",
    "EventRule",
    "FlightRecorder",
    "HealthError",
    "HealthMonitor",
    "JsonlMetrics",
    "LiveTelemetry",
    "MetricsRecorder",
    "NullMetrics",
    "QuantileSketch",
    "RollupBuilder",
    "SloEvaluator",
    "Span",
    "ThresholdRule",
    "ThroughputWindow",
    "TraceError",
    "Tracer",
    "capture",
    "merge_rollup_records",
    "percentile",
    "read_jsonl",
    "replica_shard_path",
    "span",
]
