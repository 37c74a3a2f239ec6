"""Input pipeline — how batches reach the device.

The reference hides input cost behind ``DataLoader(num_workers=2)``
subprocesses (``multi-gpu-distributed-cls.py:318``); this repo's loader
already overlaps *tokenization* with compute, but the upload itself — the
``put(batch)`` host->device transfer — sat inside the timed step loop,
serializing the upload against dispatch.  Three modes behind one
interface (:func:`build_pipeline`) move it out:

- ``"resident"`` — the encoded split is uploaded to HBM ONCE,
  data-parallel-sharded on its row axis.  Per epoch, one tiny upload of the
  seeded permutation indices; per step, a jitted on-device gather assembles
  the batch from an on-device counter — steady-state per-step host->device
  transport is ZERO bytes.  The permutation reuses the loader's own
  :class:`DistributedShardSampler` chunks, so the batch stream (and every
  loss trace, resume fast-forward, and elastic test) is bitwise identical
  to the host loader's.  Default whenever the encoded split fits the
  ``--pipeline_hbm_mb`` budget (this corpus is ~14 MB at seq 128 — it
  always does), the run is single-process, and the loader carries an
  :class:`~pdnlp_tpu.data.collate.EncodedDataset` (a shuffling/augmenting
  *collator* has no frozen encoding to upload: resident mode is refused).
- ``"prefetch"`` — double-buffered host->device upload: a background
  worker ``put``s batch *k+1* while step *k* executes, with AT MOST ONE
  batch in flight (uploaded but not yet handed to the loop) — the tf.data
  prefetch the flat reference never had.  Fallback for corpora over
  budget, multi-process runs, and custom batch placements (sp/pp).
- ``"sync"`` — the reference behavior: upload inline in the step loop
  (kept as the parity reference: ``tests/test_pipeline.py`` holds the other
  two to its losses, bit for bit).

Every mode feeds :meth:`Trainer.train` through ``macro_batches(fuse)``,
yielding ``(device_batch, n_steps, fused, examples)`` — fused groups arrive
pre-stacked for the K-step ``multi_step`` — and records
:class:`~pdnlp_tpu.utils.metrics.TransportStats` so the transport win is
measured, not asserted.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Dict, Iterator, Optional, Tuple

import numpy as np

from pdnlp_tpu.utils.metrics import TransportStats

Batch = Dict[str, np.ndarray]


def _nbytes(batch) -> int:
    return sum(getattr(v, "nbytes", 0) for v in batch.values())


def _seq_of(batch) -> int:
    """Token width of a (host or device) batch — the bucket key."""
    return int(batch["input_ids"].shape[-1])


def _tokens_real(host: Batch) -> int:
    """Non-[PAD] token positions in a HOST batch (numpy sum — never called
    on device arrays; the resident pipeline counts from host lengths)."""
    return int(host["attention_mask"].sum())


class _MacroStage:
    """Preallocated staging buffers for the K-stacked macro-batch.

    ``Trainer._macro_batches`` used to build every fused group with a fresh
    ``np.stack`` per key — K x batch bytes of allocation churn per group.
    This stages into buffers allocated once and reused, ping-ponging
    between TWO buffers so the group yielded previously survives one
    further iteration (the prefetch pipeline's lookahead depth).

    Reuse is only sound when the upload COPIES the host memory.  An
    identity ``put`` (single-device Trainer default) or a zero-copy
    ``device_put`` would alias the staging buffer into the in-flight batch
    and the next group would overwrite it mid-step — :meth:`verify` checks
    exactly that on the first uploaded group (``np.shares_memory`` against
    the uploaded arrays' host view) and permanently disables reuse when
    aliasing is detected, falling back to fresh per-group stacks.
    """

    def __init__(self, k: int):
        self.k = int(k)
        self.enabled = True
        self.verified = False
        # buffers keyed by the group's shape signature: bucket mode feeds
        # several static shapes through one stage (one ping-pong pair per
        # bucket — still a bounded, len(buckets)-sized set)
        self._bufs: dict = {}
        self._i: dict = {}

    @staticmethod
    def _sig(batch: Batch) -> tuple:
        return tuple(sorted((key, v.shape, str(v.dtype))
                            for key, v in batch.items()))

    def stack(self, group) -> Batch:
        """One ``[K, ...]`` host macro-batch from ``k`` host batches."""
        if not self.enabled or self.k <= 1:
            return {key: np.stack([b[key] for b in group])
                    for key in group[0]}
        sig = self._sig(group[0])
        if sig not in self._bufs:
            def alloc():
                return {key: np.empty((self.k,) + v.shape, v.dtype)
                        for key, v in group[0].items()}
            self._bufs[sig] = (alloc(), alloc())
            self._i[sig] = 0
            # the stage must not alias its sources (a loader yielding views
            # of cached arrays would be corrupted by the copy-in below)
            assert not any(
                np.shares_memory(self._bufs[sig][0][key], b[key])
                for b in group for key in group[0])
        buf = self._bufs[sig][self._i[sig]]
        self._i[sig] ^= 1
        for i, b in enumerate(group):
            for key in buf:
                np.copyto(buf[key][i], b[key])
        return buf

    def verify(self, host: Batch, uploaded) -> None:
        """First-upload aliasing check: disable reuse if ``uploaded`` still
        reads the staging memory (identity put / zero-copy device_put)."""
        if self.verified or not self.enabled or not self._bufs:
            return
        self.verified = True
        for key, v in host.items():
            up = uploaded.get(key) if hasattr(uploaded, "get") else None
            if up is None:
                continue
            view = up if isinstance(up, np.ndarray) else None
            if view is None:
                try:
                    view = np.asarray(up)  # CPU jax.Array: possibly a view
                except Exception:
                    continue  # no host view obtainable -> device copy: safe
            if np.shares_memory(v, view):
                self.enabled = False
                self._bufs = {}
                return


def host_macro_batches(loader, k: int, stage: Optional[_MacroStage] = None,
                       ) -> Iterator[Tuple[Batch, int, bool, int]]:
    """Yield ``(host_batch, n_steps, fused, examples)``: groups of ``k``
    loader batches stacked on a leading step axis, remainder as singles.

    A fused group assembled through ``stage`` is only valid until the next
    iteration (the buffers are reused) — consumers must upload before
    advancing, which every pipeline and the Trainer's classic path do.

    Fusion is SHAPE-homogeneous: a group only stacks batches of identical
    shape (the scanned multi-step is one compiled program per shape).
    Under bucket mode the length-grouped sampler orders batches in
    ``k``-runs per bucket, so groups straddle a bucket boundary only at
    bucket tails — those flush as single-step dispatches and the compile
    count stays ``len(buckets) x {single, fused}``.
    """
    if k <= 1:
        for b in loader:
            yield b, 1, False, int(b["example_weight"].sum())
        return
    stage = stage or _MacroStage(k)
    buf = []
    for b in loader:
        if buf and _seq_of(b) != _seq_of(buf[0]):
            # bucket boundary: never stack mixed shapes — dispatch the
            # partial run as singles rather than compile a K'-step variant
            for x in buf:
                yield x, 1, False, int(x["example_weight"].sum())
            buf = []
        buf.append(b)
        if len(buf) == k:
            ex = sum(int(x["example_weight"].sum()) for x in buf)
            yield stage.stack(buf), k, True, ex
            buf = []
    for b in buf:
        yield b, 1, False, int(b["example_weight"].sum())


class InputPipeline:
    """Base: wraps a host ``DataLoader`` + the strategy's ``put``.

    Quacks like the loader (``len``/``set_epoch``/``iter`` over HOST
    batches) so existing call sites keep working; the Trainer consumes
    :meth:`macro_batches`, which yields DEVICE batches.
    """

    mode = "sync"

    def __init__(self, loader, put: Optional[Callable] = None,
                 put_fused: Optional[Callable] = None,
                 stats: Optional[TransportStats] = None, tracer=None):
        self.loader = loader
        self.put = put or (lambda b: b)
        self.put_fused = put_fused or self.put
        self.stats = stats or TransportStats()
        self.stats.mode = self.mode
        # obs tracer for h2d_put spans; None resolves to the process-global
        # tracer LAZILY (the Trainer configures it from --trace after the
        # pipeline is built)
        self._tracer = tracer

    @property
    def tracer(self):
        if self._tracer is not None:
            return self._tracer
        from pdnlp_tpu.obs.trace import get_tracer

        return get_tracer()

    def __len__(self) -> int:
        return len(self.loader)

    def set_epoch(self, epoch: int) -> None:
        self.loader.set_epoch(epoch)

    def __iter__(self):
        return iter(self.loader)

    def macro_batches(self, fuse: int = 1
                      ) -> Iterator[Tuple[Batch, int, bool, int]]:
        raise NotImplementedError

    def warmup_batch(self, fuse: int = 1):
        """One device batch with the hot loop's exact shape/sharding/
        placement (for resident mode: a real gather output) — what
        ``warmup_compile``/``probe_steps_per_sec`` lower against.  The
        underlying generator is closed immediately; no epoch state leaks."""
        gen = self.macro_batches(fuse)
        try:
            for batch, _n, _fused, _ex in gen:
                return batch
            return None
        finally:
            gen.close()


class SyncPipeline(InputPipeline):
    """The reference behavior, instrumented: upload inline in the loop."""

    mode = "sync"

    def macro_batches(self, fuse: int = 1):
        stage = _MacroStage(fuse)
        tr = self.tracer
        for host, n, fused, ex in host_macro_batches(self.loader, fuse,
                                                     stage):
            put = self.put_fused if fused else self.put
            # deliberately times HOST seconds blocked in the upload (the
            # put-wait metric), not device compute — no barrier wanted
            t0 = time.perf_counter()
            with tr.span("h2d_put", bytes=_nbytes(host)):
                dev = put(host)
            # jaxlint: disable=R4 — put-wait is a host metric by design
            self.stats.record_upload(_nbytes(host), time.perf_counter() - t0)
            if fused:
                stage.verify(host, dev)
            self.stats.record_batch(
                n, int(host["example_weight"].size), ex,
                seq_len=_seq_of(host), tokens=int(host["input_ids"].size),
                tokens_real=_tokens_real(host))
            yield dev, n, fused, ex


class DevicePrefetchPipeline(InputPipeline):
    """Double-buffered upload: ``put`` batch *k+1* while step *k* executes.

    A background worker uploads ahead of the loop, bounded by a 1-slot
    semaphore: at most ONE batch is ever in flight (uploaded but not yet
    handed over), released only when the loop asks for the next batch — so
    the upload of *k+1* genuinely overlaps step *k*'s device execution
    instead of queueing a pile of device memory.  Worker exceptions
    (collation or ``put``) propagate to the consumer; abandoning the
    iterator mid-epoch stops the worker in one bounded join.
    """

    mode = "prefetch"

    _POLL = 0.1

    def macro_batches(self, fuse: int = 1):
        q: queue.Queue = queue.Queue()
        slots = threading.Semaphore(1)
        stop = threading.Event()
        done = object()

        def worker():
            try:
                tr = self.tracer
                stage = _MacroStage(fuse)
                for host, n, fused, ex in host_macro_batches(
                        self.loader, fuse, stage):
                    while not slots.acquire(timeout=self._POLL):
                        if stop.is_set():
                            return
                    if stop.is_set():
                        return
                    self.stats.put_started()
                    put = self.put_fused if fused else self.put
                    t0 = time.perf_counter()
                    # span recorded from THIS worker thread: the export
                    # shows the upload overlapping the step on its own tid
                    with tr.span("h2d_put", bytes=_nbytes(host)):
                        dev = put(host)
                    self.stats.record_upload(
                        _nbytes(host),
                        # jaxlint: disable=R4 — put-wait is a host metric
                        time.perf_counter() - t0)
                    if fused:
                        stage.verify(host, dev)
                    # batch telemetry measured from the HOST batch here in
                    # the worker (the consumer only ever sees device arrays)
                    meta = (int(host["example_weight"].size), _seq_of(host),
                            int(host["input_ids"].size), _tokens_real(host))
                    q.put((dev, n, fused, ex, meta))  # unbounded: no block
                q.put(done)
            except BaseException as e:  # propagate, don't vanish
                q.put(e)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is done:
                    break
                if isinstance(item, BaseException):
                    raise item
                dev, n, fused, ex, meta = item
                rows, seq, tokens, tokens_real = meta
                self.stats.put_delivered()
                self.stats.record_batch(n, rows, ex, seq_len=seq,
                                        tokens=tokens,
                                        tokens_real=tokens_real)
                slots.release()  # let the worker upload the NEXT batch now
                yield dev, n, fused, ex
        finally:
            stop.set()
            t.join(timeout=2.0)  # puts/acquires are stop-aware: one join


class DeviceResidentPipeline(InputPipeline):
    """Zero-transport epochs: the encoded split lives in HBM.

    The :class:`EncodedDataset` arrays are uploaded once (sharded over the
    mesh's data axis when their row count divides it, replicated
    otherwise); each epoch uploads only the seeded permutation indices
    (``[steps, rows]`` int32, ~40 KB for this corpus) plus one zero
    counter.  Per step, a jitted gather indexes the permutation with an
    ON-DEVICE counter and masks filler rows — bitwise identical batches to
    ``EncodedDataset.take`` with zero steady-state host->device bytes.

    Resume fast-forward dispatches (cheap, transport-free) gathers for the
    skipped steps; the counter/order is untouched so the remaining stream
    is bitwise the host loader's.
    """

    mode = "resident"

    def __init__(self, loader, put: Optional[Callable] = None,
                 put_fused: Optional[Callable] = None, mesh=None,
                 stats: Optional[TransportStats] = None, tracer=None):
        super().__init__(loader, put, put_fused, stats, tracer)
        if loader.encoded is None:
            raise ValueError(
                "device-resident pipeline needs the loader's EncodedDataset "
                "— a collator-driven (shuffling/augmenting) loader has no "
                "frozen encoding to upload; use pipeline='prefetch'")
        import jax

        self.mesh = mesh
        self.rows = loader.batch_size
        # gathers keyed (k, seq_len): bucket mode compiles one per
        # (step-variant, bucket) — bounded, like the step programs.  The
        # RESIDENCY stays one full-width copy; a bucket batch is the same
        # gather plus a free on-device column slice, so per-bucket service
        # costs no extra HBM.
        self._gathers: Dict[tuple, Callable] = {}
        enc = loader.encoded
        self._seq = getattr(enc, "seq_len", None)
        self._lengths = enc.lengths() if hasattr(enc, "lengths") else None
        # per-row real-example counts (packed rows carry several; plain
        # encodings one) — host-side, for the transport telemetry only
        self._row_examples = (
            (enc.arrays["example_weight"] > 0).sum(1).astype(np.int64)
            if "example_weight" in enc.arrays else None)
        # label SLOTS per row (M for packed [N, M] channels, 1 otherwise):
        # the row-level waste ratio counts slots, matching what sync /
        # prefetch derive from the host batch's example_weight.size — the
        # physical row count alone would make rows_real exceed rows under
        # packing and push the ratio negative
        self._slots_per_row = (
            int(enc.arrays["example_weight"].shape[1])
            if self._row_examples is not None else 1)
        nbytes = sum(v.nbytes for v in enc.arrays.values())
        t0 = time.perf_counter()
        # the one-time residency upload: an amortized h2d_put span (the
        # trace shows the ~14 MB upload once, then silence every step)
        with self.tracer.span("h2d_put", bytes=nbytes, in_loop=False,
                              what="resident_dataset"):
            self.arrays = {k: self._place(v) for k, v in enc.arrays.items()}
            jax.block_until_ready(list(self.arrays.values()))
        self.stats.record_upload(nbytes, time.perf_counter() - t0,
                                 in_loop=False)

    # ------------------------------------------------------------ placement
    def _place(self, v: np.ndarray):
        import jax

        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from pdnlp_tpu.parallel.mesh import DATA_AXIS

            size = self.mesh.shape.get(DATA_AXIS, 1)
            spec = P(DATA_AXIS) if v.shape[0] % size == 0 else P()
            return jax.device_put(v, NamedSharding(self.mesh, spec))
        import jax.numpy as jnp

        return jnp.asarray(v)

    def _replicate(self, v: np.ndarray):
        import jax

        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            return jax.device_put(v, NamedSharding(self.mesh, P()))
        import jax.numpy as jnp

        return jnp.asarray(v)

    # ---------------------------------------------------------- the gather
    def _gather(self, k: int, seq_len: int = 0) -> Callable:
        """Jitted ``(arrays, perm, nreal, counter) -> (batch, counter+1)``.

        ``perm``: ``[G, k, rows]`` int32 epoch permutation; ``nreal``:
        ``[G, k]`` real-row counts.  The counter is a DEVICE scalar — after
        the per-epoch index upload, dispatching this costs zero
        host->device bytes.  Filler rows (index padding) are masked to the
        exact zeros ``EncodedDataset.take`` pads with, so the output is
        bitwise the host loader's batch.

        ``seq_len`` (bucket mode) column-slices the full-width token
        channels to the bucket on device — same bytes ``take(...,
        seq_len=...)`` produces on host, zero extra residency.  A dataset
        carrying its own ``example_weight`` channel (packed rows) keeps it:
        the row mask zeroes filler rows' weights exactly like the host
        path.
        """
        key = (k, int(seq_len))
        if key in self._gathers:
            return self._gathers[key]
        import jax
        import jax.numpy as jnp

        rows = self.rows
        full = self._seq

        def assemble(arrays, perm, nreal, counter):
            idx = jax.lax.dynamic_index_in_dim(perm, counter, 0,
                                               keepdims=False)   # [k, rows]
            nr = jax.lax.dynamic_index_in_dim(nreal, counter, 0,
                                              keepdims=False)    # [k]
            mask = jnp.arange(rows, dtype=jnp.int32)[None, :] < nr[:, None]
            batch = {}
            for akey, v in arrays.items():
                g = jnp.take(v, idx.reshape(-1), axis=0)
                if seq_len and v.ndim == 2 and full and v.shape[1] == full \
                        and seq_len < full:
                    g = g[:, :seq_len]
                g = g.reshape((k, rows) + g.shape[1:])
                m = mask.reshape(mask.shape + (1,) * (g.ndim - mask.ndim))
                g = g * m.astype(g.dtype)
                batch[akey] = g[0] if k == 1 else g
            if "example_weight" not in arrays:
                ew = mask.astype(jnp.float32)
                batch["example_weight"] = ew[0] if k == 1 else ew
            return batch, counter + 1

        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from pdnlp_tpu.parallel.mesh import DATA_AXIS

            rep = NamedSharding(self.mesh, P())
            row_spec = (P(DATA_AXIS) if k == 1 else P(None, DATA_AXIS)) \
                if self.rows % self.mesh.shape.get(DATA_AXIS, 1) == 0 else P()
            batch_sh = NamedSharding(self.mesh, row_spec)
            out_sh = ({out_key: batch_sh for out_key in
                       set(self.arrays) | {"example_weight"}}, rep)
            fn = jax.jit(assemble, out_shardings=out_sh)
        else:
            fn = jax.jit(assemble)
        self._gathers[key] = fn
        return fn

    # ------------------------------------------------------------ the epoch
    def macro_batches(self, fuse: int = 1):
        k = max(1, int(fuse))
        chunks = list(self.loader._chunks())  # the sampler's exact chunking
        if not chunks:
            return
        # consecutive same-bucket runs: under the length-grouped sampler a
        # run is one bucket's stretch of batches; the classic samplers
        # yield exactly one run (seq 0 = the dataset's full width), which
        # reproduces the old fused+tail segmentation bit for bit
        runs: list = []
        for c, seq in chunks:
            if not runs or runs[-1][0] != seq:
                runs.append((seq, []))
            runs[-1][1].append(c)

        # build every segment's gather object first (jit construction is
        # cheap; compilation happens at first dispatch, not in the timed
        # upload window), then time the index uploads as ONE amortized
        # record — whatever the run structure, resident mode's epoch
        # transport stays a single ~40 KB permutation upload
        t0 = time.perf_counter()
        tr0 = self.tracer.now()
        segments = []
        total_bytes = 4  # the zero counter(s)
        for seq, cs in runs:
            steps = len(cs)
            n_fused, n_tail = (steps // k, steps % k) if k > 1 else (0, steps)
            counts = np.asarray([len(c) for c in cs], np.int32)
            padded = np.zeros((steps, self.rows), np.int32)
            for i, c in enumerate(cs):
                padded[i, : len(c)] = c
            total_bytes += padded.nbytes + counts.nbytes
            if n_fused:
                segments.append((self._gather(k, seq), k, n_fused, seq,
                                 self._replicate(
                                     padded[: n_fused * k].reshape(
                                         n_fused, k, self.rows)),
                                 self._replicate(
                                     counts[: n_fused * k].reshape(
                                         n_fused, k)),
                                 cs[: n_fused * k]))
            if n_tail:
                segments.append((self._gather(1, seq), 1, n_tail, seq,
                                 self._replicate(
                                     padded[n_fused * k:].reshape(
                                         n_tail, 1, self.rows)),
                                 self._replicate(
                                     counts[n_fused * k:].reshape(
                                         n_tail, 1)),
                                 cs[n_fused * k:]))
        # the per-epoch permutation-index upload (~40 KB): the ONLY
        # steady-state transport resident mode pays — one amortized
        # h2d_put span per epoch in the trace
        self.tracer.record("h2d_put", tr0, self.tracer.now(),
                           bytes=total_bytes,
                           in_loop=False, what="epoch_indices")
        self.stats.record_upload(
            total_bytes,
            # jaxlint: disable=R4 — host wait of the index upload, by design
            time.perf_counter() - t0, in_loop=False)

        for gather, seg_k, groups, seq, perm, nreal, seg_chunks in segments:
            seq_eff = int(seq) if seq else int(self._seq or 0)
            # telemetry precomputed per segment (one host pass per epoch,
            # len(seg_chunks) == groups * seg_k by construction): the
            # dispatch loop below stays O(1) host work per group
            ex_g = np.asarray(
                [self._row_examples[c].sum()
                 if self._row_examples is not None else len(c)
                 for c in seg_chunks], np.int64).reshape(groups, seg_k).sum(1)
            tok_g = np.asarray(
                [self._lengths[c].sum() if self._lengths is not None else 0
                 for c in seg_chunks], np.int64).reshape(groups, seg_k).sum(1)
            counter = self._replicate(np.int32(0))
            for g in range(groups):
                batch, counter = gather(self.arrays, perm, nreal, counter)
                ex = int(ex_g[g])
                self.stats.record_batch(
                    seg_k, seg_k * self.rows * self._slots_per_row, ex,
                    seq_len=seq_eff,
                    tokens=seg_k * self.rows * seq_eff,
                    tokens_real=int(tok_g[g]))
                yield batch, seg_k, seg_k > 1, ex


def build_pipeline(args, loader, put: Optional[Callable] = None,
                   put_fused: Optional[Callable] = None, mesh=None,
                   allow_resident: bool = True,
                   stats: Optional[TransportStats] = None,
                   tracer=None) -> InputPipeline:
    """The mode decision, in one place.

    ``args.pipeline``: ``auto`` (default) picks ``resident`` when eligible,
    else ``prefetch``; naming a mode forces it — and forcing ``resident``
    when it must be refused raises with the reason instead of silently
    degrading.  Eligibility for ``resident``: the loader carries an
    ``EncodedDataset`` (deterministic frozen encoding — a shuffling or
    augmenting collator is refused), the encoded split fits the
    ``--pipeline_hbm_mb`` budget, the run is single-process, and the
    caller's batch placement is the plain data-axis upload
    (``allow_resident`` — sp/pp slice batches differently).
    """
    import jax

    mode = getattr(args, "pipeline", "auto") or "auto"
    if mode not in ("auto", "resident", "prefetch", "sync"):
        raise ValueError(f"unknown pipeline mode {mode!r}; use "
                         "auto|resident|prefetch|sync")
    refusal = None
    if not allow_resident:
        refusal = ("this strategy slices batches across seq/stage axes — "
                   "the resident gather assumes plain data-axis placement")
    elif getattr(loader, "encoded", None) is None \
            or not hasattr(loader.encoded, "arrays"):
        # no EncodedDataset, or an encoded-like without ONE rectangular
        # array set (MultiWidthPackedDataset holds per-width groups) —
        # nothing the resident gather could hold as a single residency
        refusal = ("loader has no resident-eligible EncodedDataset "
                   "(collator-driven batches may shuffle/augment per "
                   "epoch; multi-width packed splits have no single "
                   "rectangular encoding to hold resident)")
    elif jax.process_count() > 1:
        refusal = "multi-process run: the split spans host processes"
    else:
        budget = int(getattr(args, "pipeline_hbm_mb", 128)) * (1 << 20)
        nbytes = sum(v.nbytes for v in loader.encoded.arrays.values())
        if nbytes > budget:
            refusal = (f"encoded split is {nbytes / 2**20:.1f} MB, over the "
                       f"--pipeline_hbm_mb {budget // 2**20} MB budget")
    if mode == "resident" and refusal is not None:
        raise ValueError(f"pipeline='resident' refused: {refusal}")
    if mode == "auto":
        mode = "resident" if refusal is None else "prefetch"
    cls = {"resident": DeviceResidentPipeline,
           "prefetch": DevicePrefetchPipeline,
           "sync": SyncPipeline}[mode]
    if cls is DeviceResidentPipeline:
        return cls(loader, put, put_fused, mesh=mesh, stats=stats,
                   tracer=tracer)
    return cls(loader, put, put_fused, stats=stats, tracer=tracer)
