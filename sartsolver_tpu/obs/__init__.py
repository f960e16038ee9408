"""Host-side observability: metrics registry, trace spans, run artifacts.

The solver survives faults, preemption, hangs and OOM (docs/RESILIENCE.md),
but a fleet operator also needs to know *what happened* in a run without
parsing stdout: where each millisecond went, how many frames failed and
why, how deep the prefetch/writer queues ran, what degraded. This package
is that layer (docs/OBSERVABILITY.md):

- :mod:`~sartsolver_tpu.obs.metrics` — a process-wide registry of
  counters, gauges and histograms (per-frame solve wall ms, iterations,
  convergence, statuses, retries, queue depths, bytes ingested/written,
  frame-group ladder level). ``--timing``'s :class:`PhaseTimer` is a view
  over the same registry, so the printed summary and the exported
  artifact can never disagree.
- :mod:`~sartsolver_tpu.obs.trace` — trace spans fed by the *existing*
  watchdog beacon stream (resilience/watchdog.py) plus explicit
  :func:`~sartsolver_tpu.obs.trace.span` context managers around the
  pipeline's host phases; exported as Chrome trace-event JSON loadable in
  Perfetto, and, while ``jax.profiler`` captures (``--profile_dir``),
  written as ``TraceAnnotation`` spans on the host plane of the same
  xplane as the device operations.
- :mod:`~sartsolver_tpu.obs.schema` — the machine-readable record
  vocabulary (JSONL): one validated format shared by ``--metrics_out``
  run artifacts and ``bench.py``'s ``BENCH_*.json`` results.
- :mod:`~sartsolver_tpu.obs.sinks` — JSONL event+metrics log
  (``--metrics_out``), Prometheus textfile export (``SART_METRICS_PROM``,
  atomic rename for the node-exporter textfile collector), Chrome
  trace-event JSON (``SART_TRACE_EVENTS``).
- :mod:`~sartsolver_tpu.obs.run` — :class:`RunTelemetry`, the per-run
  driver the CLI wires in: frame/event records, multi-host counter
  aggregation (one end-of-run allgather), sink fan-out.
- :mod:`~sartsolver_tpu.obs.cli` — the ``sartsolve metrics`` subcommand:
  validate, summarize and diff metrics artifacts (the hook BENCH
  regression tooling consumes).

The layer is **host-side only and zero-cost when disabled**: nothing here
is ever traced (compile-audit goldens are byte-identical with it on or
off), the in-memory registry costs nanoseconds per update, trace
buffering only happens when a trace sink is configured, and with no sinks
configured the CLI's stdout and solution files are byte-identical to a
build without the layer.

This module (and everything it pulls in transitively) deliberately
imports only the standard library: ``bench.py``'s parent process — which
must never import jax — loads :mod:`~sartsolver_tpu.obs.schema` by file
path, and the registry is consulted from cold I/O paths where an import
cycle or a heavyweight import would hurt. jax is imported lazily, inside
the one function that needs it (multi-host aggregation); ``span()`` finds
the profiler through ``sys.modules`` and never imports it.
"""

from sartsolver_tpu.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    reset_registry,
)
from sartsolver_tpu.obs.trace import TraceBuffer, span  # noqa: F401
