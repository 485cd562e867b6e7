"""Observability for the exploration pipeline: traces, metrics, reports.

Zero-dependency and off by default — instrumented code paths cost ~one
dict lookup when nothing is enabled, and enabling them never changes a
computed bit (CI-tested).  Three cooperating pieces:

* :mod:`repro.obs.trace` — nested span tree per thread and asyncio
  task, Chrome trace-event / flat-jsonl export
  (``span("pnr", variant=..., app=...)``), mirrored on the
  ``jax.profiler`` clock while tracing is on;
* :mod:`repro.obs.metrics` — process-local counters/gauges/histograms;
  ``Explorer.stats`` is a :class:`~repro.obs.metrics.CounterView` over
  an explorer-owned registry;
* :mod:`repro.obs.jaxprof` — forwards ``jax.monitoring`` compile events
  into both, so a timeline separates compile from dispatch time.

Post-pnr utilization / operand-skew reports live in
:mod:`repro.obs.analyzer`; ``python -m repro.obs.report`` summarizes
exported artifacts.

The performance *trajectory* is first-class on top of these
(:mod:`repro.obs.manifest` / :mod:`repro.obs.diff` /
:mod:`repro.obs.history` / ``python -m repro.obs.regress``): every
artifact embeds a run manifest, benchmarks record median+IQR over
repeats instead of lone samples, two artifacts diff with noise-aware
thresholds (exact series: zero tolerance), and per-commit history rows
under ``results/history/`` back a CI-wired regression detector.
:mod:`repro.obs.memprof` adds per-stage host-peak / device-byte gauges
when telemetry is on.  Typical session::

    from repro import obs
    tracer = obs.enable_tracing()
    obs.jaxprof.enable()
    ...                       # run the pipeline
    tracer.write_chrome("out.trace.json")     # load in Perfetto
"""

from . import jaxprof
from .analyzer import OperandSkew, PnrReport, analyze_pnr

# process-wide switch for heavier instrumentation (anneal acceptance/cost
# curves need a differently-compiled kernel; results stay bit-identical,
# but the extra outputs are only materialized when this is on)
_TELEMETRY = False


def enable_telemetry(on: bool = True) -> None:
    global _TELEMETRY
    _TELEMETRY = bool(on)


def telemetry_enabled() -> bool:
    return _TELEMETRY


from .metrics import (CounterView, Histogram, MetricsRegistry,
                      global_registry, reset_global_registry)
from .trace import (Span, Tracer, async_span, current_span,
                    current as current_tracer, disable as disable_tracing,
                    enable as enable_tracing, event, record_span, span)
from .manifest import RunManifest, capture as capture_manifest
from .diff import (NoiseModel, StageDelta, diff_metrics, diff_traces,
                   summarize_repeats)
from . import diff, history, manifest, memprof

__all__ = [
    "span", "async_span", "event", "record_span", "current_span",
    "enable_tracing", "disable_tracing", "current_tracer",
    "Span", "Tracer",
    "MetricsRegistry", "CounterView", "Histogram", "global_registry",
    "reset_global_registry",
    "jaxprof", "enable_telemetry", "telemetry_enabled",
    "analyze_pnr", "PnrReport", "OperandSkew",
    "RunManifest", "capture_manifest",
    "NoiseModel", "StageDelta", "diff_metrics", "diff_traces",
    "summarize_repeats",
    "diff", "history", "manifest", "memprof",
]
