"""Cross-process telemetry plane (ISSUE 5) + fleet health & diagnosis
plane (ISSUE 7).

Five pillars, one package:

* **Metrics registry** (``registry.py``) — process-local counters /
  gauges / histograms with fixed log2 buckets, so merging registries
  from other processes is pure addition.  The ad-hoc diagnostics dicts
  (``Reader.diagnostics``, ``DataLoader.diagnostics``, pool
  ``shm_results``, cache-plane hits/misses, dispatcher ``stats``) are
  VIEWS over these registries; worker-side registries snapshot into the
  existing return channels (ProcessPool acks, service heartbeats) and
  merge in the parent.  ``summarize_hist`` is the ONE canonical
  histogram summary every surface prints.
* **Correlated spans** (``spans.py``) — bounded per-process span
  buffers keyed by correlation id (ventilator item position / service
  ``split/seq``), shipped over the existing ZMQ frames and merged into
  ONE ``benchmark.TraceRecorder`` timeline with per-process
  ``time.monotonic()`` clock-offset alignment.
* **Flight recorder** (``flight.py``) — an always-on bounded ring of
  periodic registry-snapshot frames per process, periodically persisted
  so a postmortem sees the minutes BEFORE a crash, not just the final
  totals.
* **Health engine** (``health.py``) — windowed snapshot deltas
  classified into actionable regimes (decode-bound / link-bound /
  lease-starved / cache-degraded / shm-degraded) with per-component
  scores, surfaced by dispatcher ``stats``, ``top``, and Prometheus
  gauges.
* **Introspection & diagnosis** (``top.py`` / ``diagnose.py``) — the
  ``petastorm-tpu-top`` live view and the ``petastorm-tpu-diagnose``
  verdict CLI over live fleets, flight dumps, and watchdog artifacts.

See ``docs/observability.md`` for the registry model, the span
catalogue, the verdict catalogue, and scrape examples.
"""

from petastorm_tpu.telemetry import decisions  # noqa: F401
from petastorm_tpu.telemetry import flight  # noqa: F401
from petastorm_tpu.telemetry import health  # noqa: F401
from petastorm_tpu.telemetry import provenance  # noqa: F401
from petastorm_tpu.telemetry.registry import (  # noqa: F401
    MetricsRegistry, hist_quantile, merge_snapshots, process_registry,
    snapshot_all, snapshot_delta, summarize_hist)
from petastorm_tpu.telemetry.spans import (  # noqa: F401
    SpanBuffer, Stages, attribute_stalls, current_buffer,
    measure_clock_offset, merge_into_recorder)

__all__ = ['MetricsRegistry', 'merge_snapshots', 'hist_quantile',
           'snapshot_all', 'snapshot_delta', 'summarize_hist',
           'process_registry', 'Stages',
           'SpanBuffer', 'current_buffer', 'merge_into_recorder',
           'measure_clock_offset', 'attribute_stalls', 'dump_state',
           'decisions', 'flight', 'health', 'provenance']


def dump_state():
    """One JSON-able dict of every live registry snapshot, every live
    ``TraceRecorder``'s events, the span-buffer residue, and the flight
    recorder's frame ring in this process — the crash-artifact dump the
    test-suite watchdog writes (``tests/conftest.py``), so the next
    silent-death bug ships with a timeline AND the minutes before it
    attached.  ``petastorm-tpu-diagnose --artifact`` ingests this shape."""
    from petastorm_tpu.benchmark.trace import all_recorder_events
    return {'registries': snapshot_all(),
            'trace_events': all_recorder_events(),
            'span_residue': current_buffer().peek(),
            'flight': flight.dump_current(),
            # Per-batch provenance journals (ISSUE 13): the causal
            # chains `petastorm-tpu-explain --artifact` reconstructs.
            'provenance': provenance.dump_journals(),
            # Control-plane decision journals (ISSUE 20): the records
            # `petastorm-tpu-why --artifact` explains.
            'decisions': decisions.dump_journals()}
