"""Unified in-graph training telemetry (L-monitor).

Not in the reference: NVIDIA Apex observes training through three
disconnected holes — ``pyprof`` NVTX/kernel joins, per-example ``print``
logging, and whatever the trainer scripts hand-roll. This subsystem is the
one layer that answers "what was the loss, grad norm, loss scale, comm
volume, and MFU at step N" from a running job, with zero perturbation of
the step:

* :mod:`~apex_tpu.monitor.metrics` — :class:`Metrics`, a named-scalar
  pytree threaded through the jitted train step like the loss-scaler state
  (in-graph, donation-safe, zero extra compilations), plus
  :func:`global_norm` / :func:`train_metrics` collectors. Producers wired
  in: ``amp.LossScaler.metrics`` (scale + overflow/skip counters),
  ``parallel.DistributedDataParallel.average_gradients(metrics=...)``
  (per-bucket wire bytes + compression ratio),
  ``contrib.optimizers.DistributedFused{Adam,LAMB}.step(metrics=...)``
  (shard norms).
* :mod:`~apex_tpu.monitor.trace` — :func:`span` named ranges
  (``jax.named_scope`` + host ``TraceAnnotation``: one marker, visible in
  the trace viewer and in the compiled HLO's metadata), :func:`split_scope`
  (an instruction's ``op_name`` → phase and model scope), the program
  registry behind :func:`scope_table` (which scope each instruction of a
  compiled program belongs to) and :func:`host_log` (host spans and JAX's
  compile path on one clock, in memory). Its docstring lists the scope and
  host span names. The pipeline schedules emit ``pp_stage`` /
  ``pp_ring_shift`` spans for bubble attribution.
* :mod:`~apex_tpu.monitor.sink` — :class:`JsonlSink`, the process-0-gated,
  versioned, buffered, crash-safe JSONL writer; :func:`json_record` is the
  shared one-JSON-line convention every bench prints.
* :mod:`~apex_tpu.monitor.report` — :func:`step_report`, the measured-time
  × HLO-flops × bytes-on-wire join (MFU, ICI bandwidth, per-phase ms);
  :func:`mfu_check` / :func:`hlo_stats` compile-only variants.

Tier 2 (the serving side — request-level attribution, not step averages):

* :mod:`~apex_tpu.monitor.hist` — :class:`Histogram` /:class:`HistSpec`:
  fixed log-spaced-bucket streaming histograms (mergeable, constant
  memory, quantiles within ``rel_error``), host-side or as per-bucket
  counters on the :class:`Metrics` pytree (:func:`accumulate_hist`);
* :mod:`~apex_tpu.monitor.events` — :class:`EventLog` request-lifecycle
  recording on one monotonic clock (``submitted → … → retired`` + queue/
  occupancy gauges), JSONL via the sink and Chrome trace-event JSON via
  :func:`chrome_trace` (one Perfetto track per slot and per request);
* :mod:`~apex_tpu.monitor.slo` — :class:`SloSpec` declarative latency
  budgets → :class:`SloTracker` goodput/violation accounting over rolling
  windows;
* :mod:`~apex_tpu.monitor.regress` — :func:`compare_records` baseline
  diffing of bench records;
* :mod:`~apex_tpu.monitor.view` — ``python -m apex_tpu.monitor.view``
  latency/SLO summary CLI over any monitor JSONL file.

Tier 3 (the fleet side — live cross-host signal, not per-worker logs):

* **distributed tracing** — :meth:`EventLog.bind` threads a trace id
  (minted at router submission) plus the request's current host through
  every producer's events; :func:`request_spans` reconstructs per
  trace across merged multi-worker logs, :func:`stitch_traces` verifies
  the cross-host structure, and :func:`chrome_trace` renders one
  Perfetto track per HOST — a request that hops hosts or migrates under
  chaos is visibly one trace id in causal order;
* :mod:`~apex_tpu.monitor.registry` — :class:`MetricsRegistry`
  cardinality-bounded named series (counters/gauges/histograms) with
  Prometheus text exposition, snapshot/merge aggregation (histogram
  merge is associative — this is what it was built for), and the
  :class:`FleetScraper` pulling worker snapshots on the cluster clock
  into one :class:`~apex_tpu.monitor.registry.FleetView` (per-worker,
  per-tenant and rolled-up series; scrape_ms/coverage self-measured);
* :mod:`~apex_tpu.monitor.alerts` — declarative threshold / absence /
  rate rules evaluated over scraped series; firings are first-class
  ``alert_fire``/``alert_resolve`` events that drive the cluster's
  autoscaler and land in the JSONL stream;
* :mod:`~apex_tpu.monitor.flight` — :class:`FlightRecorder` bounded
  in-memory rings of recent records, dumped atomically (the
  ``resilience.checkpoint`` tmp+replace discipline) on chaos kill /
  watchdog fire / alert escalation;
* :mod:`~apex_tpu.monitor.postmortem` — ``python -m
  apex_tpu.monitor.postmortem DIR`` rebuilds the merged pre-failure
  timeline from flight dumps alone.

Tier 4 (performance forensics — why, who pays, and since when):

* :mod:`~apex_tpu.monitor.attrib` — per-request latency attribution
  derived purely from the EventLog lifecycle: every retired request's
  e2e decomposes into queue/prefill/transfer/decode/stall components
  that SUM to the measured e2e (migration/replay-safe, concatenation-
  order-independent); :class:`AttributionAccumulator` streams it into
  per-component histograms on ``engine.stats()``/``cluster.stats()``,
  and :func:`explain_regression` turns a stage-gate verdict into a
  diagnosis;
* :mod:`~apex_tpu.monitor.meter` — per-tenant resource metering
  (modeled flops, KV block-seconds, adapter residency, wire bytes)
  rolled up under a declarative :class:`CostModel` with
  ``cost_per_token``/``cost_per_request`` surfaced in stats, per-worker
  cost rates advertised on the membership heartbeat, and loud
  cardinality-bounded overflow accounting.
"""

from apex_tpu.monitor.alerts import (  # noqa: F401
    AbsenceRule,
    AlertEngine,
    AlertRule,
    Condition,
    RateRule,
)
from apex_tpu.monitor.attrib import (  # noqa: F401
    COMPONENTS,
    AttributionAccumulator,
    attribute_requests,
    attribution_summary,
    explain_regression,
)
from apex_tpu.monitor.events import (  # noqa: F401
    EventLog,
    chrome_trace,
    dedupe_events,
    request_spans,
    stitch_traces,
    write_chrome_trace,
)
from apex_tpu.monitor.meter import (  # noqa: F401
    CostModel,
    Meter,
    modeled_request_flops,
)
from apex_tpu.monitor.flight import (  # noqa: F401
    FlightRecorder,
)
from apex_tpu.monitor.registry import (  # noqa: F401
    FleetScraper,
    FleetView,
    MetricsRegistry,
    merge_snapshots,
)
from apex_tpu.monitor.hist import (  # noqa: F401
    DEFAULT_LATENCY_SPEC,
    HistSpec,
    Histogram,
    accumulate_hist,
    hist_counts,
    hist_from_metrics,
    hist_metric_names,
)

from apex_tpu.monitor.metrics import (  # noqa: F401
    Metrics,
    global_norm,
    train_metrics,
)
from apex_tpu.monitor.report import (  # noqa: F401
    format_step_report,
    gpt_analytic_flops_per_token,
    hlo_stats,
    mfu_check,
    phase_breakdown,
    pipeline_bubble_fraction,
    step_report,
)
from apex_tpu.monitor.sink import (  # noqa: F401
    SCHEMA_VERSION,
    JsonlSink,
    collect_provenance,
    json_record,
    read_jsonl,
    rotated_segments,
    set_provenance,
)
from apex_tpu.monitor.slo import (  # noqa: F401
    SloSpec,
    SloTracker,
)
from apex_tpu.monitor.trace import (  # noqa: F401
    host_log,
    register_program,
    scope_table,
    span,
    span_function,
    split_scope,
)


def __getattr__(name):
    # regress doubles as `python -m apex_tpu.monitor.regress`; importing
    # it eagerly here would make runpy warn about the pre-imported module
    # every CLI run, so its package-level names resolve lazily
    if name in ("compare_records", "load_record"):
        from apex_tpu.monitor import regress

        return getattr(regress, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "AbsenceRule",
    "AlertEngine",
    "AlertRule",
    "AttributionAccumulator",
    "COMPONENTS",
    "Condition",
    "CostModel",
    "DEFAULT_LATENCY_SPEC",
    "EventLog",
    "FleetScraper",
    "FleetView",
    "FlightRecorder",
    "HistSpec",
    "Histogram",
    "JsonlSink",
    "Meter",
    "Metrics",
    "MetricsRegistry",
    "RateRule",
    "SCHEMA_VERSION",
    "SloSpec",
    "SloTracker",
    "accumulate_hist",
    "attribute_requests",
    "attribution_summary",
    "chrome_trace",
    "collect_provenance",
    "compare_records",
    "dedupe_events",
    "explain_regression",
    "merge_snapshots",
    "modeled_request_flops",
    "format_step_report",
    "global_norm",
    "gpt_analytic_flops_per_token",
    "hist_counts",
    "hist_from_metrics",
    "hist_metric_names",
    "hlo_stats",
    "host_log",
    "json_record",
    "load_record",
    "mfu_check",
    "phase_breakdown",
    "pipeline_bubble_fraction",
    "read_jsonl",
    "register_program",
    "request_spans",
    "rotated_segments",
    "scope_table",
    "set_provenance",
    "span",
    "stitch_traces",
    "span_function",
    "split_scope",
    "step_report",
    "train_metrics",
    "write_chrome_trace",
]
