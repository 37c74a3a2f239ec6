"""Batched inference serving — the subsystem ``predict_tpu.py`` lacked.

Training in this repo already kills the two costs that dominate BERT-class
serving (XLA retraces on ragged shapes; idle accelerator time between
requests) — this package applies the same treatment to inference:

- :mod:`pdnlp_tpu.serve.engine` — a long-lived jitted sharded forward over
  the existing mesh/sharding stack, with a compiled-function cache keyed on
  ``(bucket_seq_len, batch_rows)`` so steady-state serving never retraces;
- :mod:`pdnlp_tpu.serve.batcher` — bounded request queue with dynamic
  micro-batching (flush on size or ``max_wait_ms``), sequence-length
  bucketing, backpressure and per-request deadlines; ``serve_pack``
  bin-packs requests many-per-row into fixed token-budget packed batches
  (throughput scales with tokens, not requests);
- :mod:`pdnlp_tpu.serve.router` — N engine replicas behind tiered admission
  (backpressure -> shed -> reject), least-loaded dispatch, heartbeat-based
  health ejection with requeue/retry, warmup-gated reintegration, and
  rolling checkpoint hot-swap (``serve_tpu.py --replicas N``);
- :mod:`pdnlp_tpu.serve.metrics` — latency/occupancy/cache observability
  (plus router/per-replica instruments), plain-JSON snapshots;
- :mod:`pdnlp_tpu.serve.offline` — high-throughput whole-file scoring over
  the same bucketing (the deterministic surface the tests use);
- :mod:`pdnlp_tpu.serve.controller` — the feedback control plane: a
  :class:`ServeController` thread that closes the telemetry loop, auto-
  tuning replica count (warm-standby scaling), ``hedge_ms``, the flush age
  and the admission thresholds through one decision-recording, auto-
  reverting ``_actuate`` choke point (``serve_tpu.py --controller on``);
- :mod:`pdnlp_tpu.serve.replay` — trace-driven load replay: recorded
  request-hop chains reconstructed into arrival schedules, reshaped
  (steady / diurnal ramp / flash crowd) and re-driven at 1x/5x/20x speed
  (``tests/test_controller.py``);
- :mod:`pdnlp_tpu.serve.decode` — generative decoding: a paged, donated
  KV cache (optionally int8 against calibrated
  per-channel scale tables), bucketed prefill / one fixed-shape decode
  step, continuous batching with streaming responses, a declared KV HBM
  budget (``--kv_hbm_mb``), a decode replica router whose
  kill-recovery re-prefills orphan streams on survivors
  (``serve_tpu.py --decode``), and a :class:`DisaggDecodeRouter` that
  splits a paged fleet into prefill-role and decode-role engine pools
  with an audited KV page handoff and a live controller-driven pool
  split (``--disagg local|socket``);
- :mod:`pdnlp_tpu.serve.handoff` — the handoff wire: length-prefixed,
  CRC-checked socket framing (:class:`HandoffServer` /
  :class:`HandoffChannel`, per-frame acks, torn frames NACKed) moving
  exported page payloads between the disaggregated pools — the
  single-host rehearsal of a cross-process serving tier;
- :mod:`pdnlp_tpu.serve.kvpage` — the paged KV memory subsystem behind
  the decode engine: refcounted fixed-size page allocator with a
  free list, loud :class:`KVPagesExhausted` refusals, a leak-check
  ledger audit, and an LRU prefix index that shares repeated prompt
  prefixes across requests at page granularity (copy-on-write at the
  divergence page).

Entry point: ``serve_tpu.py`` at the repo root.
"""
from pdnlp_tpu.serve.batcher import (  # noqa: F401
    DEFAULT_BUCKETS, AdmissionControl, DeadlineExceeded, DynamicBatcher,
    LoadShedError, QueueFullError, pick_bucket, resolve_serve_pack,
)
from pdnlp_tpu.serve.controller import KnobSpec, ServeController  # noqa: F401
from pdnlp_tpu.serve.decode import (  # noqa: F401
    DecodeBatcher, DecodeRouter, DecodeStream,
    DisaggDecodeRouter, PagedDecodeEngine, PrefillWorker,
)
from pdnlp_tpu.serve.engine import InferenceEngine  # noqa: F401
from pdnlp_tpu.serve.kvpage import (  # noqa: F401
    KVPagesExhausted, PageAllocator, PrefixIndex,
)
from pdnlp_tpu.serve.fleet import (  # noqa: F401
    FleetRouter, ModelSpec, RolloutPlan, ShadowReport, drafter_spec,
    parse_fleet_spec, parse_speculate_spec,
)
from pdnlp_tpu.serve.metrics import (  # noqa: F401
    DecodeMetrics, FleetMetrics, ReplicaMetrics, RouterMetrics,
    ServeMetrics,
)
from pdnlp_tpu.serve.offline import score_texts  # noqa: F401
from pdnlp_tpu.serve.router import (  # noqa: F401
    ReplicaFailedError, ReplicaRouter,
)

__all__ = [
    "DEFAULT_BUCKETS",
    "AdmissionControl",
    "DeadlineExceeded",
    "DecodeBatcher",
    "DecodeMetrics",
    "DecodeRouter",
    "DecodeStream",
    "DynamicBatcher",
    "FleetMetrics",
    "FleetRouter",
    "InferenceEngine",
    "KVPagesExhausted",
    "KnobSpec",
    "LoadShedError",
    "ModelSpec",
    "PageAllocator",
    "PagedDecodeEngine",
    "PrefixIndex",
    "QueueFullError",
    "ReplicaFailedError",
    "ReplicaMetrics",
    "ReplicaRouter",
    "RolloutPlan",
    "RouterMetrics",
    "ServeController",
    "ServeMetrics",
    "ShadowReport",
    "drafter_spec",
    "parse_fleet_spec",
    "parse_speculate_spec",
    "pick_bucket",
    "resolve_serve_pack",
    "score_texts",
]
