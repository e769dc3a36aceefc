"""repro.obs — unified tracing + metrics for the whole stack.

Two submodules that import nothing of the repo, so every layer can depend
on them without cycles:

* :mod:`repro.obs.trace` — thread-safe span tracer: each span is a
  ``jax.profiler.TraceAnnotation`` (in the profiler's trace, on the
  device's clock, whenever a profiler session is active) with a
  ``perf_counter`` pair, and, gated by the ``REPRO_TRACE`` env var, a
  Chrome trace-event JSON export (Perfetto / ``chrome://tracing``).
* :mod:`repro.obs.metrics` — pure stdlib: counters / gauges /
  fixed-bucket histograms (p50/p95/p99) in a process-wide
  :data:`~repro.obs.metrics.REGISTRY`, with per-engine private registries
  backing ``Engine.stats`` and ``CNNEngine.stats``.

See EXPERIMENTS.md §Observability for capture/read workflows and
``scripts/bench_snapshot.py`` for the machine-readable benchmark record
built on top of both.
"""
from . import metrics, trace
from .metrics import REGISTRY, Registry
from .trace import TRACER, Tracer, span, traced

__all__ = ["metrics", "trace", "REGISTRY", "Registry", "TRACER", "Tracer",
           "span", "traced"]
