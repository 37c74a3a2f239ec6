"""``pdnlp_tpu.obs`` — one telemetry plane: span tracing, phase breakdown,
per-request distributed tracing, cross-rank merge, live export, HBM
accounting, and regression detection.

The attribution layer the ROADMAP's "as fast as the hardware allows" needs
before any further hot-path work: a dispatch/block-aware span tracer
(``trace``), the canonical per-step phase taxonomy + aggregator
(``phases``), Chrome-trace/JSONL exporters (``export``), per-request hop
tracing with a joinable ``request_id`` (``request``), the cross-rank trace
merge with clock alignment (``merge``), the live Prometheus/healthz
exporter + flight recorder (``exporter``), device-memory accounting
(``memory``), and the EWMA step-time regression detector + trace differ
(``regress``).  The ``trace_tpu.py`` CLI at the repo root fronts the
offline half (``summarize`` / ``diff`` / ``export`` / ``merge`` /
``request``).

Off by default: entrypoints enable tracing with ``--trace`` (spans land
under ``<output_dir>/trace/trace_proc<i>.jsonl``), the live exporter with
``--metrics_port``; what the instrumentation costs when it is on is in
PERF.md (sections 6-7).  A LEAF span (``Tracer.leaf``: the decode worker's
host phases) has three answers: it records under ``--trace`` or while a
JAX profiler session is on; else, inside a worker's open round, it tallies
its seconds into that round's row of the tracer's ring of rounds
(``Tracer.rounds()``, ``phases.round_account``: on with no flag at all);
else it is the shared no-op.  ``trace.BUILDS`` counts the executables JAX
builds, where JAX builds them.
"""
from pdnlp_tpu.obs.exporter import MetricsExporter, prometheus_text
from pdnlp_tpu.obs.memory import MemorySampler, device_memory_stats, \
    memory_snapshot
from pdnlp_tpu.obs.phases import PHASES, StepBreakdown, format_table
from pdnlp_tpu.obs.regress import RegressionDetector, diff_breakdowns
from pdnlp_tpu.obs.request import (
    chain_issues, format_chain, hop_chain, mint_request_id, record_hop,
    validate_chains,
)
from pdnlp_tpu.obs.trace import (
    Span, Tracer, configure, configure_from_args, get_tracer,
)

__all__ = [
    "PHASES", "StepBreakdown", "format_table",
    "RegressionDetector", "diff_breakdowns",
    "Span", "Tracer", "configure", "configure_from_args", "get_tracer",
    "MetricsExporter", "prometheus_text",
    "MemorySampler", "device_memory_stats", "memory_snapshot",
    "mint_request_id", "record_hop", "hop_chain", "chain_issues",
    "format_chain", "validate_chains",
]
