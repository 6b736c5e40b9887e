"""``repro.obs`` — dependency-light tracing + metrics for the tuning stack.

The paper's headline claim is as much about *optimization time* as about
the resulting throughput, so every layer of this repo's tuning stack
(ARCO loop halves, oracle measurement, all three executors, the remote
worker fabric, netopt phases) emits named spans into one
:class:`~repro.obs.trace.Tracer`.  A run's single ``wall_time_s`` then
decomposes into measure vs surrogate-refit vs mappo-update vs
executor-wait — per phase, per endpoint — instead of being one opaque
number.

The model paths emit into the same tracer: ``Server.step`` and
``Trainer.run`` open ``serve.*`` and ``train.*`` spans, and a tracer built
with ``annotate=jax.profiler.TraceAnnotation`` puts those spans on the
device trace's clock.

Design constraints, in order:

* **Near-zero cost when off.**  The ambient tracer defaults to a shared
  :data:`NOOP` singleton whose ``span()`` returns one reusable no-op
  context manager; instrumented hot paths pay an attribute lookup and a
  method call, nothing else.  Guarded by a tier-1 overhead test.
* **Stdlib only.**  This package sits below
  ``repro.compiler.executor`` and is imported by spawned workers and
  remote daemons, which must never pay a jax import.
* **Cross-host mergeable.**  Spans carry a wall-clock anchor
  (``time.time`` at tracer creation) alongside monotonic timestamps, so
  span batches shipped back from remote daemons land on the same
  timeline as the parent's and one session yields one merged
  Chrome-trace/Perfetto file.

Entry points: ``Tracer`` / ``NOOP`` / the ambient ``current()``+``use()``
pair (:mod:`repro.obs.trace`), the counters/gauges/histograms registry
(:mod:`repro.obs.metrics`), the ``REPRO_LOG``-leveled structured logger
(:mod:`repro.obs.log`), Chrome-trace/JSONL export
(:mod:`repro.obs.export`), and the ``tools/trace_summary.py`` report
over saved traces.
"""
from repro.obs.metrics import Metrics, NoopMetrics
from repro.obs.serve import MonitorServer, active_servers, prometheus_text
from repro.obs.trace import NOOP, NoopTracer, Tracer, current, use

__all__ = [
    "Metrics",
    "MonitorServer",
    "NOOP",
    "NoopMetrics",
    "NoopTracer",
    "Tracer",
    "active_servers",
    "current",
    "prometheus_text",
    "use",
]
