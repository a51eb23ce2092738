"""Serving-stack observability: typed metrics, structured traces, profiling.

The measurement spine of the serving stack (docs/observability.md):

  * ``registry``  — typed counters/gauges/histograms behind a dict-compatible
    ``MetricsView`` so engine code and tests keep their ``metrics["key"]``
    idiom while percentiles/peaks come from real distributions;
  * ``trace``     — a bounded ring of structured ``TraceEvent``s emitted by
    the scheduler (grant/pack/defer), allocator (alloc/free/cow/adopt) and
    engine phase loops (prefill/decode calls, spec verify, preemption), and
    ``TraceRing.span``, the engine's phase spans (ring event, profiler
    annotation and self-time counter under one name);
  * ``export``    — Chrome-trace/Perfetto JSON from the ring (plus schema
    validation used by the CI trace-schema lane);
  * ``replay``    — recompute counters from a trace stream; the conservation
    oracle (trace must reproduce the registry) tests pin;
  * ``jaxprof``   — ``jax.profiler`` TraceAnnotation/start_trace hooks so
    device timelines line up with host events;
  * ``overlap_probe`` — measures how much decode all-reduce the batch-split
    ISO schedule actually hides: ``overlap_efficiency = 1 - t_ovl/t_seq``.
"""
from repro.obs.registry import (ACCEPT_LEN_BUCKETS, GRANT_SIZE_BUCKETS,
                                TPOT_BUCKETS_S, TTFT_BUCKETS_S, Counter, Gauge,
                                Histogram, MetricsRegistry, MetricsView)
from repro.obs.trace import TraceEvent, TraceRing
from repro.obs.export import (chrome_trace, validate_chrome_trace,
                              write_chrome_trace)
from repro.obs.replay import replay_counters

__all__ = [
    "ACCEPT_LEN_BUCKETS", "GRANT_SIZE_BUCKETS", "TPOT_BUCKETS_S",
    "TTFT_BUCKETS_S", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "MetricsView", "TraceEvent", "TraceRing", "chrome_trace",
    "validate_chrome_trace", "write_chrome_trace", "replay_counters",
]
