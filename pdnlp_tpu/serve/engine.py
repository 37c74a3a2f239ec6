"""Long-lived inference engine: checkpoint -> jitted sharded forward.

``predict_tpu.py``'s original inline ``@jax.jit`` forward re-traced on every
new input shape and re-assembled the model per process.  The engine keeps
one process-lifetime forward instead:

- **checkpoint load** goes through ``pdnlp_tpu.train.checkpoint`` —
  shape-validated against the model template, so a ``bert-tiny`` file into a
  ``bert-base`` engine fails loudly at load, not as an XLA error mid-request
  (``load_raw`` pre-checks the embedding shape before any device transfer);
- **placement** rides the existing ``parallel.mesh``/``sharding`` machinery:
  params replicated over the data axis, batches split along it — inference
  is embarrassingly data-parallel, so the DDP layout is the right one (pass
  ``mesh=None`` for plain single-device jit, bitwise-identical to the old
  ``predict_tpu.py`` forward);
- **compile cache**: ``jax.jit`` already caches traces by shape, but
  silently — the engine tracks every ``(bucket_seq_len, batch_rows)`` shape
  it has served and counts hits/misses, and a counter INSIDE the traced
  function counts actual retraces (the Python body only runs when XLA
  traces), so "steady-state serving never retraces" is a measured property,
  not a hope.  ``warmup()`` pre-traces every bucket shape so the first real
  request never pays a compile.

Params can be swapped (``load_checkpoint``) without invalidating the cache:
the trace depends on shapes only, and every strategy checkpoint shares the
template's shapes — that is what lets ``predict_tpu.py`` sweep N checkpoints
through ONE compiled forward.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from pdnlp_tpu.data.collate import pad_ids_to_bucket
from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, get_or_build_vocab
from pdnlp_tpu.models import bert, families, get_config
from pdnlp_tpu.models.config import args_overrides
from pdnlp_tpu.obs.request import EXEMPLAR_CAP
from pdnlp_tpu.serve.metrics import ServeMetrics
from pdnlp_tpu.train import checkpoint as ckpt
from pdnlp_tpu.train.precision import resolve_dtype


class InferenceEngine:
    def __init__(self, args, tokenizer: Optional[WordPieceTokenizer] = None,
                 *, mesh=None, metrics: Optional[ServeMetrics] = None,
                 tracer=None):
        """``args`` supplies model/dtype/vocab knobs (an ``utils.config.Args``).

        ``mesh=None`` means plain ``jax.jit`` on the default device — the
        exact forward ``predict_tpu.py`` always ran.  With a mesh, batches
        shard along ``data`` and batch rows are padded up to a multiple of
        the axis size (``rows_multiple``).

        ``tracer`` (``pdnlp_tpu.obs``): the engine emits one span per
        executed batch — ``compile`` for a first-seen ``(seq, rows)`` shape
        (the trace shows exactly when/where retraces happen), ``forward``
        for a cache hit; both carry the serve ``dtype`` (and resolved
        ``attn_impl``) as span attrs so kernel/precision adoption is
        visible in ``trace_tpu.py summarize``/``diff``.  Defaults to the
        process-global tracer, configured from ``args`` so
        ``serve_tpu.py --trace true`` just works.

        ``args.serve_dtype`` picks the forward precision independently of
        the training dtype: ``"auto"`` follows ``args.dtype`` (the legacy
        behavior), ``"bf16"`` forces bfloat16 compute, ``"int8"`` serves
        per-channel int8 weights with bf16 activations (``serve.quant``) —
        ``load_checkpoint`` quantizes a float checkpoint on the fly or
        loads a prebuilt ``scripts/quantize_ckpt.py`` artifact directly.
        """
        from pdnlp_tpu.obs.trace import configure_from_args

        self.tracer = tracer if tracer is not None \
            else configure_from_args(args)
        self.args = args
        self.tokenizer = tokenizer or WordPieceTokenizer(get_or_build_vocab(args))
        self.cfg = get_config(args.model, vocab_size=self.tokenizer.vocab_size,
                              num_labels=args.num_labels, dropout=args.dropout,
                              attn_dropout=args.attn_dropout,
                              **args_overrides(args))
        self.serve_dtype = getattr(args, "serve_dtype", "auto") or "auto"
        if self.serve_dtype not in ("auto", "bf16", "int8"):
            raise ValueError("serve_dtype must be 'auto', 'bf16' or 'int8', "
                             f"got {self.serve_dtype!r}")
        if self.serve_dtype == "auto":
            self.dtype = resolve_dtype(args.dtype)
        else:  # int8 weights compute against bf16 activations
            self.dtype = resolve_dtype("bfloat16")
        # the impl the jitted forward routes to at the engine's max width
        # (deterministic serve: no dropout) — the headline a snapshot
        # reports.  Routing is PER BUCKET WIDTH (sub-128 buckets fall back
        # to XLA), so spans stamp :meth:`routed_attn` of their actual seq,
        # never this attribute.
        from pdnlp_tpu.ops.attention import (
            pin_auto_for_mesh, routed_impl_cached,
        )

        # a forward jitted over a multi-device mesh is partitioned by GSPMD,
        # which the kernel cannot follow: ``auto`` is pinned to XLA there
        self.attn_requested = pin_auto_for_mesh(args.attention_impl, mesh)
        self._impl_by_seq: Dict[int, str] = {}
        # routed directly (not via routed_attn) so _impl_by_seq records
        # only widths actually served, never the construction-time headline
        self.attn_impl = routed_impl_cached(self.attn_requested,
                                            args.max_seq_len)
        self.mesh = mesh
        self.metrics = metrics or ServeMetrics()
        self.rows_multiple = int(mesh.shape.get("data", 1)) if mesh else 1
        # the template: init-shaped params every checkpoint must match
        # (predict/test sweep semantics — setup_model's init, minus the
        # optimizer state serving never needs).  int8 mode quantizes the
        # template too, so the params' pytree STRUCTURE is identical before
        # and after every load — checkpoint swap stays retrace-free.
        # what builds and runs this config: looked up ONCE (models.families)
        self.family = families.of(self.cfg)
        if self.serve_dtype == "int8" and not self.family.int8:
            self.family.refuse("int8 weights (--serve_dtype int8)",
                               "serve it in the dtype it stores")
        key = jax.random.key(args.seed)
        if self.family.lazy_weights:
            # gigabytes of weights: made on the device, and the template is
            # their SHAPES, so that the model is never held twice
            self._template = jax.eval_shape(
                lambda: self.family.init_params(key, self.cfg))
            self._serving_template = self._template
            # ... and only when something first reads them: a caller that
            # brings its own weights sets ``params`` and these never exist
            self.params = lambda: self._put(
                self.family.init_params(key, self.cfg))
        else:
            self._template = self.family.init_params(key, self.cfg)
            # the serving-form template is also the int8 swap template —
            # built once here, not re-quantized on every load_checkpoint
            self._serving_template = self._serving_form(self._template)
            self.params = self._put(self._serving_template)
        self.checkpoint_path: Optional[str] = None
        self._seen_shapes: set = set()
        # extra attrs stamped on every forward/compile span — the replica
        # router labels each engine with its rank here, so per-replica
        # phase tables (obs.phases) can attribute engine time per replica
        self.span_attrs: Dict[str, object] = {}
        # HBM accounting over THIS engine's device slice (mesh devices, or
        # every local device for plain jit): sampled per executed batch
        # when tracing is on, and on demand for serve snapshots /
        # /metrics.  Graceful no-op (one flag read per call) on backends
        # without memory_stats — CPU tests run unchanged.
        from pdnlp_tpu.obs.memory import MemorySampler

        self.memory = MemorySampler(
            devices=list(mesh.devices.flat) if mesh is not None else None)

        metrics_ref = self.metrics
        attn_impl = self.attn_requested

        def _forward(params, batch):
            # Python body only executes while tracing: this IS the retrace
            # counter (jax.jit replays the compiled program otherwise)
            metrics_ref.retraces.inc()
            return bert.classify(params, self.cfg, batch, dtype=self.dtype,
                                 deterministic=True, attn_impl=attn_impl)

        if mesh is not None:
            from pdnlp_tpu.parallel.sharding import batch_sharding, replicated

            self._jit_forward = jax.jit(
                _forward,
                in_shardings=(replicated(mesh),
                              batch_sharding(mesh)),
                out_shardings=replicated(mesh),
            )
        else:
            self._jit_forward = jax.jit(_forward)

    # ------------------------------------------------------------ params
    @property
    def params(self):
        """The served weights.  A family whose weights are made lazily
        (``Family.lazy_weights``) stores a thunk until the first read."""
        if callable(self._params):
            self._params = self._params()
        return self._params

    @params.setter
    def params(self, value) -> None:
        self._params = value

    def _put(self, host_params):
        if self.mesh is not None:
            from pdnlp_tpu.parallel.sharding import replicated

            return jax.device_put(host_params, replicated(self.mesh))
        return jax.device_put(host_params)

    def _serving_form(self, host_params):
        """Host params -> what this engine actually serves: quantized
        (``serve.quant``) under ``--serve_dtype int8``, unchanged
        otherwise."""
        if self.serve_dtype != "int8":
            return host_params
        from pdnlp_tpu.serve.quant import quantize_params

        return quantize_params(host_params)

    def load_checkpoint(self, path: str) -> None:
        """Swap in a strategy checkpoint (shape-validated; cache survives).

        ``ckpt.load_params`` validates every leaf shape against the model
        template and raises a per-leaf ``ValueError`` on mismatch — all
        before any device transfer, so a wrong ``--model`` fails fast with
        one file parse (``ckpt.load_raw`` exists for template-free
        inspection when the error message isn't enough).

        Under ``--serve_dtype int8`` both artifact kinds load: a float
        checkpoint is quantized on the fly (identical math to the offline
        pass), and a ``scripts/quantize_ckpt.py`` artifact — recognized by
        its ``qscale`` leaves — is shape-validated against the QUANTIZED
        template and served as-is.  A quantized artifact into a float
        engine fails loudly (it cannot be de-quantized back to the
        training dtype losslessly; point ``--serve_dtype int8`` at it).
        """
        from pdnlp_tpu.serve.quant import is_quantized, quantize_params

        # ONE file read + msgpack decode: the raw tree feeds both the
        # quantization probe and the template-validated restore
        raw = ckpt.load_raw(path)
        if self.serve_dtype == "int8":
            if is_quantized(raw):
                host = ckpt.from_restored(
                    raw, self._serving_template, path=path)
            else:
                host = quantize_params(
                    ckpt.from_restored(raw, self._template, path=path))
        else:
            if is_quantized(raw):
                raise ValueError(
                    f"checkpoint {path!r} is an int8 artifact "
                    "(quantize_ckpt.py) but this engine serves "
                    f"{self.serve_dtype!r} — start it with --serve_dtype "
                    "int8, or point it at the float checkpoint")
            host = ckpt.from_restored(raw, self._template, path=path)
        self.params = self._put(host)
        self.checkpoint_path = path

    def _telemetry_attrs(self, request_ids) -> Dict:
        """Per-batch span extras: bounded ``request_ids`` exemplars (the
        join key from a slow batch back to concrete request hop chains)
        and the device slice's peak HBM — sampled BEFORE the span opens
        (a pure allocator-counter read, no sync).  The exemplars ride
        whatever records (a profiler session's leaves too); the memory
        sample is ``--trace``'s alone — a session must not pay a
        ``memory_stats()`` call on every engine call."""
        extra: Dict[str, object] = {}
        tr = self.tracer
        if not tr.recording:
            return extra
        if request_ids:
            extra["request_ids"] = list(request_ids)[:EXEMPLAR_CAP]
        if tr.enabled:
            mem = self.memory.sample()
            if mem is not None:
                extra["hbm_peak"] = mem["device_peak_bytes"]
        return extra

    def memory_snapshot(self) -> Dict:
        """JSON-ready HBM state of this engine's device slice (serve
        snapshots / the live exporter); ``{"supported": False}`` on CPU."""
        return self.memory.snapshot()

    def beat_memory(self) -> Dict:
        """The ``hbm``/``hbm_peak`` heartbeat fields (replica workers fold
        these into their watchdog beats)."""
        return self.memory.beat_payload()

    # ----------------------------------------------------------- forward
    def infer(self, batch: Dict[str, np.ndarray],
              request_ids=None) -> np.ndarray:
        """Fixed-shape batch -> host logits ``[rows, num_labels]`` (fp32).

        Tracks the compiled-shape cache: key is the batch's
        ``(seq_len, rows)``; a first-seen key is a miss (and will trace),
        every later one a hit that replays the compiled program.
        ``request_ids``: optional riding-request IDs, stamped (bounded)
        on the span as exemplars.
        """
        rows, seq = batch["input_ids"].shape
        key = (int(seq), int(rows))
        if key in self._seen_shapes:
            self.metrics.cache_hits.inc()
            span_name = "forward"
        else:
            self.metrics.cache_misses.inc()
            self._seen_shapes.add(key)
            span_name = "compile"  # first call at this shape traces
        fwd = {k: batch[k] for k in ("input_ids", "attention_mask",
                                     "token_type_ids")}
        # token-level occupancy: the padded path's honest waste number —
        # real tokens over the rows x width slots this forward pays for.
        # Compile (= warmup) batches are dummies at ~0.002 fill and are
        # excluded — every fill surface (these histograms, the replica
        # metrics, the phases fill column) must report steady state
        fill = float(batch["attention_mask"].sum()) / float(rows * seq)
        if span_name == "forward":
            self.metrics.fill_ratio.observe(fill)
            self.metrics.padding_waste.observe(1.0 - fill)
        if self.mesh is not None:
            from pdnlp_tpu.parallel.sharding import batch_sharding

            sh = batch_sharding(self.mesh)
            fwd = {k: jax.make_array_from_process_local_data(sh, v)
                   for k, v in fwd.items()}
        # the device_get fetch inside the span IS the completion barrier:
        # serve spans measure request-visible latency, dispatch + compute.
        # dtype/attn_impl attrs make int8/pallas adoption visible in
        # trace_tpu.py summarize and the trace-diff gate.
        with self.tracer.span(span_name, seq=int(seq), rows=int(rows),
                              dtype=self.dtype_label, fill=round(fill, 4),
                              attn_impl=self.routed_attn(int(seq)),
                              **self._telemetry_attrs(request_ids),
                              **self.span_attrs):
            logits = self._jit_forward(self.params, fwd)
            out = np.asarray(jax.device_get(logits))
        return out

    #: the channels a packed serve batch carries into the jitted forward —
    #: ``data.packing.pack_id_lists``'s layout, and exactly what
    #: ``models.bert.classify`` keys its packed (per-segment) program on
    PACKED_CHANNELS = ("input_ids", "attention_mask", "token_type_ids",
                       "segment_ids", "position_ids", "cls_positions")

    def infer_packed(self, batch: Dict[str, np.ndarray],
                     segments: int = 0, request_ids=None) -> np.ndarray:
        """Packed batch (``data.packing.pack_id_lists``) -> host logits
        ``[rows, max_segments, num_labels]`` (fp32) — one forward serving
        many requests per row.

        The compile-cache key is ``(seq, rows, "packed")``: every packed
        batch the batcher emits has the SAME fixed shape (rows x the pack
        width, segment capacity included), so the packed path holds exactly
        one compiled program and is retrace-free by construction once
        :meth:`warmup_packed` has traced it.  Spans carry ``packed``/
        ``fill``/``segments`` attrs so per-replica fill is visible in
        ``trace_tpu.py summarize``; ``segments`` is the number of real
        requests riding the batch.
        """
        rows, seq = batch["input_ids"].shape
        key = (int(seq), int(rows), "packed")
        if key in self._seen_shapes:
            self.metrics.cache_hits.inc()
            span_name = "forward"
        else:
            self.metrics.cache_misses.inc()
            self._seen_shapes.add(key)
            span_name = "compile"
        fill = float(batch["attention_mask"].sum()) / float(rows * seq)
        if span_name == "forward":  # warmup dummies stay out of steady state
            self.metrics.fill_ratio.observe(fill)
            self.metrics.padding_waste.observe(1.0 - fill)
        fwd = {k: batch[k] for k in self.PACKED_CHANNELS}
        if self.mesh is not None:
            from pdnlp_tpu.parallel.sharding import batch_sharding

            sh = batch_sharding(self.mesh)
            fwd = {k: jax.make_array_from_process_local_data(sh, v)
                   for k, v in fwd.items()}
        with self.tracer.span(span_name, seq=int(seq), rows=int(rows),
                              packed=True, fill=round(fill, 4),
                              segments=int(segments),
                              dtype=self.dtype_label,
                              attn_impl=self.routed_attn(int(seq),
                                                         segmented=True),
                              **self._telemetry_attrs(request_ids),
                              **self.span_attrs):
            logits = self._jit_forward(self.params, fwd)
            out = np.asarray(jax.device_get(logits))
        return out

    def infer_ids(self, id_lists: Sequence[Sequence[int]], seq_len: int,
                  rows: int = 0, request_ids=None) -> np.ndarray:
        """Ragged id-lists -> logits for the REAL rows only (filler dropped)."""
        rows = self.pad_rows(max(rows, len(id_lists)))
        batch = pad_ids_to_bucket(id_lists, seq_len, rows,
                                  pad_id=self.tokenizer.pad_id)
        return self.infer(batch, request_ids=request_ids)[: len(id_lists)]

    def classify_texts(self, texts: Sequence[str],
                       seq_len: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """(preds, logits) for a list of texts at one padded length —
        the single-call surface ``predict_tpu.py`` uses (``seq_len`` defaults
        to ``args.max_seq_len``, the exact legacy padding)."""
        seq_len = seq_len or self.args.max_seq_len
        ids = self.tokenizer.encode_ragged(texts, seq_len)
        logits = self.infer_ids(ids, seq_len)
        return np.argmax(logits, axis=-1), logits

    def routed_attn(self, seq: int, segmented: bool = False) -> str:
        """The attention impl a forward at this bucket width actually
        routes to (``ops.attention.routed_impl_cached``) — a requested
        pallas falls back to XLA below the 128-wide kernel blocks, so
        per-seq routing is what spans and per-bucket reporting must carry,
        not the max-width :attr:`attn_impl`.  ``segmented=True`` is the
        packed forward's route (block-diagonal mask from segment IDs —
        the segment-native pallas kernel where it applies).
        ``_impl_by_seq`` records the widths THIS engine served
        (:attr:`attn_impl_by_seq`); the memoization itself lives at the
        routing point."""
        from pdnlp_tpu.ops.attention import routed_impl_cached

        impl = routed_impl_cached(self.attn_requested, seq,
                                  segmented=segmented)
        self._impl_by_seq.setdefault(seq, impl)
        return impl

    @property
    def attn_impl_by_seq(self) -> Dict[int, str]:
        """{bucket width: routed impl} for every width this engine has
        routed so far — the honest per-bucket adoption record beside the
        max-width headline."""
        return dict(self._impl_by_seq)

    @property
    def dtype_label(self) -> str:
        """The serving precision as a span/JSON label: ``"int8"`` for
        weight-quantized serving, else the activation dtype name."""
        if self.serve_dtype == "int8":
            return "int8"
        import numpy as _np

        return _np.dtype(self.dtype).name

    # ------------------------------------------------------------ shapes
    def pad_rows(self, n: int) -> int:
        """Round a row count up to the mesh's data-axis multiple."""
        m = self.rows_multiple
        return max(m, ((n + m - 1) // m) * m)

    def warmup(self, buckets: Sequence[int], rows: int) -> None:
        """Pre-trace one dummy batch per bucket so live traffic never
        compiles.  The warmup calls count as the cache's misses; everything
        after is expected to hit."""
        rows = self.pad_rows(rows)
        for seq in buckets:
            self.infer_ids([[self.tokenizer.cls_id, self.tokenizer.sep_id]],
                           seq, rows)

    def warmup_packed(self, seq_len: int, rows: int,
                      max_segments: int) -> None:
        """Pre-trace the ONE packed shape (``(seq_len, rows, "packed")``):
        every packed batch the online path emits reuses this compiled
        program, so after this call the packed path cannot retrace."""
        from pdnlp_tpu.data.packing import pack_id_lists

        batch, _ = pack_id_lists(
            [[self.tokenizer.cls_id, self.tokenizer.sep_id]], seq_len,
            self.pad_rows(rows), max_segments, pad_id=self.tokenizer.pad_id)
        self.infer_packed(batch, segments=1)
