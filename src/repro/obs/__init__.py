"""repro.obs — tracing + metrics for the speculative serving stack.

SwiftSpec's argument is a latency decomposition; this package is the
instrument that measures it end to end:

``trace``
    ``Tracer`` — ring-buffered phase spans with monotonic timestamps,
    Chrome/Perfetto ``trace.json`` + JSONL export, and a zero-allocation
    disabled path (``NULL_TRACER``/``NOOP_SPAN``).  Woven through
    ``EngineSession``'s round (verify dispatch / draft lookahead / emitted
    sync / reconcile), ``EngineStepper`` (admit prefill, absorb, retire) and the
    serving runtimes (routing, queue pop, per-replica round spans).
``metrics``
    ``MetricsRegistry`` — labeled counters / gauges / fixed-bucket
    histograms / bounded sample series, with a structured ``snapshot()``
    and a Prometheus text dump.  The runtimes populate per-replica round
    counters, the accepted-depth histogram, TTFT, queue-depth-over-time
    and KV-truncation counts.
``report``
    ``phase_breakdown`` / ``breakdown_report`` — per-round draft vs.
    verify vs. absorb decomposition (the paper's imbalance, measured) with
    a span-coverage completeness check.

Quick start::

    from repro.obs import MetricsRegistry, Tracer, breakdown_report, phase_breakdown

    tracer, metrics = Tracer(), MetricsRegistry()
    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=4,
                                   tracer=tracer, metrics=metrics)
    ...
    tracer.write("trace.json")            # open in ui.perfetto.dev
    metrics.write("metrics.json", extra={"phase_breakdown": phase_breakdown(tracer)})
    print(breakdown_report(phase_breakdown(tracer)))
"""

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Series,
    merge_histograms,
)
from repro.obs.report import breakdown_report, phase_breakdown
from repro.obs.trace import NOOP_SPAN, NULL_TRACER, Span, Tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NOOP_SPAN",
    "NULL_TRACER",
    "Series",
    "Span",
    "Tracer",
    "breakdown_report",
    "merge_histograms",
    "phase_breakdown",
]
