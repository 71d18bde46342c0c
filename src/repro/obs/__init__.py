"""TraceKit: dependency-free tracing + metrics for the train->serve stack.

Three small pieces, composable and individually optional:

- ``trace.Tracer`` — nestable wall-clock spans (monotonic ns, explicit
  parent ids, thread-safe buffer) plus instant events.  Disabled tracing
  is represented by ``tracer=None`` at every instrumentation site: the
  hot paths guard with a single ``is None`` check, so tracing off is a
  true no-op (the serving test suite bounds the residual overhead at
  <1% of a decode step).
- ``metrics.MetricsRegistry`` — typed counters / gauges / histograms
  with a plain-text dump consumable by the ``tools/check_*.py`` gates.
- ``export`` — pluggable exporters: JSONL event log, Chrome
  ``chrome://tracing`` / Perfetto trace JSON (one lane per slot/tenant
  on the serve side, one per stage on the train side), and the text
  metrics dump.

Instrumented layers (see ISSUE 6 / ROADMAP):

- serving: ``runtime/serve_loop.DecodeServer(tracer=...)`` — queue-wait,
  admission, chunked-prefill dispatches, decode steps, adapter
  swap/promote/evict, jit-compile events;
- training: ``runtime/train_loop.run(..., tracer=...)`` — per-step spans,
  the BlockLLM step split inside ``train_step`` (plan, dispatch,
  device_wait, ingest_norms, reselect, step_metrics), retroactive
  ``compile`` spans, and the structured ``StepEmitter`` (BlockLLM
  selection telemetry: q, block churn, gradient-norm concentration,
  reselection cadence).

Device time is read from the profiler's trace, where the train step's
phases carry ``jax.named_scope`` names (``core/blockllm.build_step_fn``)
and every span above appears in the host plane.

Surfaced via ``--trace <path>`` / ``--metrics-every`` on
``launch/train.py`` and ``launch/serve.py``; traces validated in CI by
``tools/check_trace.py``.
"""
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import Tracer, maybe_span
from repro.obs.emit import StepEmitter
from repro.obs.export import (chrome_trace_dict, load_trace_file,
                              merged_chrome_trace_dict,
                              write_chrome_trace, write_jsonl,
                              write_metrics_text, write_trace)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Tracer",
    "StepEmitter", "chrome_trace_dict", "load_trace_file",
    "merged_chrome_trace_dict", "write_chrome_trace", "write_jsonl",
    "write_metrics_text", "write_trace", "maybe_span",
]
