"""Generative decode engine: paged KV cache, prefill/decode split,
continuous batching.

The serving tier built since PR 6 scales a SCORER — one forward, one logit
row.  This module turns it into a text service, built on the observation
that autoregressive decode is memory-bandwidth-bound: tokens/s/chip is won
or lost on (a) never recomputing the prompt (the KV cache), (b) never
retracing (fixed shapes, donated buffers), and (c) never running the
decode batch partially empty (continuous batching).

- **paged KV cache** (:class:`PagedDecodeEngine`, ``serve.kvpage``): one
  preallocated ``[L, n_pages, page_sz, width]`` array per pool of the
  model family (``models.decoder`` layout note), DONATED across steps —
  steady-state decode allocates nothing.  A slot is a row of the decode
  batch and the unit of admission; PAGES are the unit of capacity: a
  stream reserves every page it can touch when it is seated, writes
  forward through its page table as it decodes, and frees them between
  steps when it finishes.  Pages replicate on a mesh; several chips are
  served by replicas (:class:`DecodeRouter`).
- **prefill/decode split**: prompts execute as bucketed ``[prefill_rows,
  bucket]`` causal forwards riding the same compile-cache discipline as
  the classifier engine (one trace per bucket, warmup pre-traces all);
  their new rows scatter into the claimed pages (out-of-bounds filler
  indices DROPPED — filler never touches a live page).  Decode is a
  ``[rows, 1]`` program per rung of the row extent (the seated rows, which
  the batcher keeps packed at the bottom of the slot block) and of the
  attention extent — retrace-free by the same construction as
  ``infer_packed``: after :meth:`PagedDecodeEngine.warmup_decode` every
  pair of rungs is compiled and nothing live traffic does can create
  another.
- **continuous batching** (:class:`DecodeBatcher`): between decode steps,
  finished streams leave and waiting streams claim freed slots (prefill
  rides the same worker, so the decode batch is re-filled before the
  next step).  The batcher is the online analogue of the token-packing
  PR 9 shipped: capacity is measured in slots and tokens, occupancy is
  ``live/slots`` per step, and freed-slot reuse latency is a first-class
  metric.
- **int8 KV** rides the PR-6 per-channel machinery: the cache stores
  int8 against calibrated ``[L, N, D]`` scale tables
  (``models.decoder.calibrate_kv_scales``; offline artifact via
  ``scripts/quantize_ckpt.py --kv_calib``, self-calibration at warmup
  otherwise) — half (vs bf16) to a quarter (vs fp32) the cache traffic,
  which is the decode roofline.
- **KV HBM budget** (``--kv_hbm_mb``, ``obs.memory.KVBudget``): the
  declared budget caps the preallocation loudly at construction and
  refuses oversized streams at admission with the budget math
  (:class:`~pdnlp_tpu.obs.memory.KVBudgetExceeded`) — never an OOM three
  layers deep; live occupancy is a ``/metrics`` gauge.
- **replica failure** (:class:`DecodeRouter`): a dead decode worker's
  live + waiting streams re-prefill on survivors from ``prompt +
  emitted-so-far`` — greedy decode is deterministic, so the continuation
  emits exactly the tokens the dead replica would have (no duplicates,
  no losses; the chain shows ``requeue`` then a second ``prefill``).

- **speculative decoding** (draft-k / verify-1): a paired CHEAP engine
  (the fleet's ``cheap`` role) drafts k tokens per round with its own
  paged cache via k fixed-shape decode steps, then the primary scores
  all k+1 window positions in ONE prefill-shaped ``verify_ids`` call
  (``models.decoder.paged_verify_step``, compile key ``("verify",
  slots, k+1)`` — retrace-free by construction).  The longest accepted
  greedy prefix commits to both caches: the primary's commit IS the
  verify call's K/V written through the page table (rejected tail
  positions stay invisible behind the position mask and are overwritten
  in place next round), the drafter's rejected pages stay under the
  two-owner draft custody (``kvpage.draft_owner`` + ``transfer``) until
  a later round commits across them.  Greedy verification makes the
  emitted sequence IDENTICAL to primary-only decode — every emitted
  token is a primary argmax (``tests/test_speculate.py``).
  A drafter death degrades the pair to primary-only decode (loud,
  decision-recorded); parity is unaffected because the primary cache
  already holds every committed token.

- **disaggregated prefill/decode pools** (:class:`PrefillWorker` +
  :class:`DisaggDecodeRouter`): the two phases have opposite compute
  profiles (prefill is FLOP-bound, decode is bandwidth-bound), so one
  interleaving worker lets a long prefill steal inter-token latency
  from every live stream.  The disaggregated pool splits the fleet into
  prefill-role engines (bucketed/chunked prefill only) and decode-role
  engines (steady fixed-shape decode only); a finished prefill's pages
  move to a decode engine via the KV **handoff**: a fixed-shape jitted
  page export (``models.decoder.gather_pool`` over the sentinel-padded
  table row — one compiled program whatever the stream's real page
  count), staged custody on the sender
  (``kvpage.stage_handoff`` — refcounts never blip, both allocators'
  ``leak_check`` reconcile to zero), and a fixed-shape import
  (``scatter_pool``) into the receiver's fresh cold reservation.
  Cross-pool the payload rides ``serve.handoff``'s length-prefixed
  stdlib-socket transport (loopback; the repo's first RPC boundary).
  The pool split is the controller's first STRUCTURAL knob
  (``prefill_share``), actuated through :meth:`DisaggDecodeRouter.
  set_prefill_share` — a retiring unit hands its streams back through
  the front door (greedy determinism keeps tokens identical).

Hop chains (``obs.request``): ``admit → prefill → (decode | draft
verify)* → complete``, with ``decode`` hops carrying
``slot``/``step``/``tokens_out`` and speculation rounds carrying
``draft``/``verify`` pairs (``k``/``accepted``/``drafter_model``) so
``trace_tpu.py request <id>`` reconstructs a stream's whole life.
Disaggregated streams insert a ``handoff`` hop after their prefill
(``admit → prefill → handoff → decode* → complete``) carrying the
custody story (``pages``/``bytes``/``from_replica``/``to_replica``/
``transport``).
"""
from __future__ import annotations

import heapq
import queue
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from pdnlp_tpu.models import decoder, families
from pdnlp_tpu.obs.decision import mint_decision_id, record_decision
from pdnlp_tpu.obs.memory import KVBudget, KVBudgetExceeded
from pdnlp_tpu.obs.phases import recent_round_account
from pdnlp_tpu.obs.request import mint_request_id, record_hop
from pdnlp_tpu.serve.batcher import (
    DEFAULT_BUCKETS, DeadlineExceeded, QueueFullError, pick_bucket,
    usable_buckets,
)
from pdnlp_tpu.serve.engine import InferenceEngine
from pdnlp_tpu.serve.handoff import (
    HandoffChannel, HandoffError, HandoffServer,
)
from pdnlp_tpu.serve.kvpage import (
    INDEX_OWNER, KVPagesExhausted, PageAllocator, PrefixHit, PrefixIndex,
    draft_owner, pages_needed, stage_handoff,
)
from pdnlp_tpu.serve.metrics import DecodeMetrics, ReplicaMetrics
from pdnlp_tpu.train import checkpoint as ckpt
from pdnlp_tpu.utils.metrics import merged_percentiles

#: sentinel closing a stream's token queue
_DONE = object()


def detokenize(tokenizer, ids: Sequence[int]) -> str:
    """Token ids -> text: wordpiece continuations (``##``) rejoin their
    word, CJK pieces concatenate bare, latin words get spaces — the
    inverse of ``data.tokenizer``'s basic+wordpiece split, close enough
    for a streamed response body."""
    out: List[str] = []
    for i in ids:
        piece = tokenizer.vocab_list[int(i)] \
            if 0 <= int(i) < tokenizer.vocab_size else "[UNK]"
        if piece.startswith("##"):
            if out:
                out[-1] += piece[2:]
            else:
                out.append(piece[2:])
        else:
            out.append(piece)
    return " ".join(out)


def greedy_ids(logits):
    """The greedy choice INSIDE a jitted program: ``int32`` argmax over the
    vocabulary axis of the float32 logits the program produces, first
    index on ties as ``np.argmax`` has it.  One more output of the same
    program — the engine's wrappers call it, never a family's step
    function — so every family and width gets it alike; sampling in the
    same program (ROADMAP, Reach B8) would replace this line."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class Chosen:
    """What a prefill / chunk / decode launch hands the host: the chosen
    token of each row, ALREADY fetched (``ids``, ``int32 [rows]`` — the
    ``<p>.fetch`` leaf's bytes), and the launch's float32 logits LEFT ON
    THE DEVICE.  A caller that wants the logits reads this like the array
    it used to be — ``np.asarray(result)``, ``result[i]``, ``np.argmax(
    result, -1)`` — and pays the ``[rows, vocab]`` fetch then, once.
    ``ids_device`` is the same choice as a device array, for a launch
    that would take it without a round trip."""

    __slots__ = ("ids", "ids_device", "_logits", "_rows", "_host")

    def __init__(self, ids: np.ndarray, ids_device, logits, rows: int):
        self.ids = ids
        self.ids_device = ids_device
        self._logits = logits
        self._rows = int(rows)
        self._host: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return self._rows

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        if self._host is None:
            self._host = np.asarray(
                jax.device_get(self._logits))[:self._rows]
        out = self._host if dtype is None \
            else self._host.astype(dtype, copy=False)
        return out.copy() if copy else out

    def __getitem__(self, key):
        return self.__array__()[key]


def chosen_ids(result) -> List[int]:
    """Each row's next token as Python ints: the ids a launch chose on
    the device where the engine's result carries them, the host argmax of
    an ARRAY where a wrapper of the engine call (a test, the benchmark's
    planted fault) handed the batcher one."""
    ids = getattr(result, "ids", None)
    if ids is None:
        ids = np.argmax(np.asarray(result), axis=-1)
    return ids.tolist()


class _PageClaim:
    """One stream's page reservation (``PagedDecodeEngine`` slot state):
    which kind of prefix hit it attached with, the continuation tokens it
    covers, and what the prefill phase still owes it (nothing for a full
    hit; the divergent suffix for a partial one)."""

    __slots__ = ("owner", "kind", "tokens", "n_prompt_pages",
                 "first_token", "suffix", "start", "draft_from")

    def __init__(self, owner: str, kind: str, tokens: List[int],
                 n_prompt_pages: int, first_token: Optional[int] = None,
                 suffix: Optional[List[int]] = None, start: int = 0):
        self.owner = owner
        self.kind = kind                    # "cold" | "partial" | "full"
        self.tokens = tokens                # prompt + emitted at attach
        self.n_prompt_pages = n_prompt_pages
        self.first_token = first_token      # full hits: stored token 0
        self.suffix = suffix or []          # partial hits: the chunk
        self.start = start                  # partial hits: suffix offset
        self.draft_from = None              # drafter engines: first page
        #                                     index under draft custody


class PagedDecodeEngine(InferenceEngine):
    """The classifier engine's checkpoint/mesh/metrics machinery with a
    generative decode path on top: LM head, paged KV pools
    (``serve.kvpage``), jitted prefill / page-insert / chunk / decode-step
    programs, and the KV budget.

    The inherited pieces carry over unchanged: template-validated
    checkpoint swap (trunk only — the LM head is its own small tree),
    int8 weight serving (``--serve_dtype int8`` quantizes trunk AND head
    through ``serve.quant``), per-batch HBM sampling, span conventions
    (``compile`` on a first-seen shape, the steady-state name after).
    Single-dispatcher contract: all decode/prefill calls come from ONE
    worker thread (:class:`DecodeBatcher`).

    Storage is ``[L, n_pages, page_sz, width]`` pages, one array per pool
    of the family (``models.families``; the layout the chip tiles exactly
    — ``models.decoder``'s paged-cache note), a per-stream page table
    drives every program's page reads, and capacity is PAGES, not slots:
    slots are pure decode-batch rows while ``--kv_hbm_mb`` caps the page
    pool, so short streams do not pay for ``max_len`` stripes and admitted
    concurrency scales with what streams actually use.

    The pool stays where it lies: every paged program
    (``models.decoder.paged_attend_layers`` behind ``paged_decode_step`` /
    ``paged_chunk_step`` / ``paged_verify_step``, and ``paged_insert``)
    takes the pools donated, writes rows or whole pages in place and
    reads whole pages through the table.  The decode step attends over a
    RUNG of page counts (:attr:`decode_rungs`, quarters of a stream's
    pages) chosen per step by the longest row, for a RUNG of rows
    (:attr:`row_rungs`) chosen by the highest attached slot: fixed-shape
    programs, one per pair, all traced in :meth:`warmup_decode`.

    A family with recurrent layers (``models.hybrid_linear``) pages only the
    layers that cache positions (the pools' ``L`` is the family's
    ``pool_layers``) and keeps, beside them, per-SLOT state arrays
    ``[slots, ...]`` (:attr:`_states`; ``state_bytes`` a slot, whatever the
    stream's length): a prefill hands each prompt's FINAL state to its slot
    through ``_pinsert_fn``, the decode step reads and writes the rows of
    its row rung, both donated like the pools.  What cannot follow such a
    state is refused at construction: prefix sharing, the speculative pair,
    the handoff, int8.

    Prefix sharing rides the :class:`~pdnlp_tpu.serve.kvpage.PrefixIndex`:
    a repeated prompt maps the indexed pages at refcount+1 and skips its
    prefill entirely (**full hit** — the stored first token is emitted
    straight from the index, so TTFT is bounded by one decode-step
    latency); a shared-prefix prompt maps the matching full pages and
    runs only the divergent suffix (**partial hit** —
    ``paged_chunk_step``); copy-on-write duplicates a full hit's trailing
    partial page before the stream writes into it.  Full pages are
    immutable once written, which is what makes sharing safe without
    copies.

    Parity contract: shared-prefix streams reuse K/V that is bitwise what
    their own prefill would have produced (same program, same inputs), so
    greedy continuations are TOKEN-identical to a cold engine's
    (``prefix_share=False``; ``tests/test_kvpage.py``) the same way
    re-prefilled kill survivors always have been.

    Pages replicate on a mesh (no ``NamedSharding`` axis): the page ->
    stream mapping is dynamic, so there is no static batch axis to shard;
    several chips are served by replicas, one engine a chip
    (:class:`DecodeRouter`).  An engine handed a mesh of several devices
    runs every program replicated over them, and a family's step that
    would take a Mosaic kernel keeps its XLA form there
    (``decoder.attend_form``)."""

    #: fixed copy-on-write batch rows — one compiled ``copy_pool``
    #: program per engine; unused rows ride the OOB sentinel
    COW_ROWS = 4
    #: rungs of the decode step's attention extent (quarters of a stream's
    #: pages): each is one compiled program, all traced in warmup
    DECODE_RUNGS = 4
    #: the small rung of the decode step's ROW extent (one bf16 tile of
    #: sublanes: fewer rows save the chip nothing), for an engine of at
    #: least four times as many slots; a smaller engine launches every row
    ROW_RUNG = 16

    def __init__(self, args, tokenizer=None, *, mesh=None, metrics=None,
                 tracer=None, slots: Optional[int] = None,
                 max_len: Optional[int] = None,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 prefill_rows: Optional[int] = None,
                 page_sz: Optional[int] = None,
                 prefix_share: Optional[bool] = None,
                 index_entries: int = 4096):
        super().__init__(args, tokenizer, mesh=mesh, metrics=metrics,
                         tracer=tracer)
        # the worker's leaf spans record under --trace OR whenever a JAX
        # profiler session is on (a harness that starts one itself)
        self.tracer.follow_profiler()
        cfg = self.cfg
        self.max_len = int(max_len or getattr(args, "decode_max_len", 0)
                           or args.max_seq_len)
        if self.max_len > cfg.max_position:
            raise ValueError(
                f"decode_max_len {self.max_len} exceeds {args.model}'s "
                f"{cfg.max_position}-position table — generated positions "
                "would gather garbage embeddings; use a long-position "
                "model or shrink it")
        # KV precision: auto follows the serve compute dtype; int8 stores
        # the cache against calibrated per-channel scale tables
        kv_req = getattr(args, "kv_dtype", "auto") or "auto"
        if kv_req not in ("auto", "fp32", "bf16", "int8"):
            raise ValueError(f"kv_dtype must be auto|fp32|bf16|int8, "
                             f"got {kv_req!r}")
        self.kv_int8 = kv_req == "int8"
        family = self.family
        if self.kv_int8 and not family.int8:
            family.refuse("an int8 cache (--kv_dtype int8)",
                          "its cache is stored in bf16")
        # ``None``: share where the family can.  A family with a recurrent
        # state cannot: a hit would need the state AT the shared boundary,
        # and no snapshot of it exists
        if prefix_share and not family.prefix:
            family.refuse("prefix sharing (prefix_share=True)",
                          "every prompt is prefilled whole")
        prefix_share = family.prefix if prefix_share is None else prefix_share
        self.kv_dtype = (jnp.int8 if self.kv_int8
                         else {"fp32": jnp.float32,
                               "bf16": jnp.bfloat16}.get(kv_req, self.dtype))
        self._kv_scales = None  # (k_scale, v_scale) [L, N, D] once known
        #: assignments to each held expert, summed over the launches whose
        #: fetch leaf recorded (a family with experts; else stays None)
        self.expert_load: Optional[np.ndarray] = None
        #: by program (``decode`` / ``prefill`` / ``chunk``): positions
        #: visible to its real queries and positions picked for them, summed
        #: like ``expert_load`` (a learned sparse attention; else empty)
        self.positions_seen: Dict[str, np.ndarray] = {}

        # --- capacity.  Pages, not slots, are the budgeted unit: the
        # declared HBM budget caps the page POOL (loud refusal at
        # construction, never an allocator OOM; floor: one maximum-length
        # stream) and the slot count stays the requested batch width —
        # admitted concurrency is bounded by what streams actually
        # RESERVE; admission re-checks per stream (KVBudgetExceeded)
        self.budget = KVBudget(getattr(args, "kv_hbm_mb", 0))
        requested = int(slots or getattr(args, "decode_slots", 8))
        # bytes a cached position takes: ONE place computes it, from the
        # family's pools over the layers that page, for budgets, refusals
        # and snapshots alike
        self.token_bytes = families.token_bytes(cfg, self.kv_dtype)
        m = self.rows_multiple
        self.slots = max(m, (requested // m) * m)
        #: what a SLOT keeps besides its pages (a recurrent family), as
        #: (shape, dtype), and its bytes a slot: a stream costs its pages AND
        #: this, whatever its length
        self._state_specs = tuple(
            (shape, jnp.dtype(dt or self.dtype))
            for shape, dt in family.state_shapes(cfg, self.slots))
        self.state_bytes = sum(
            int(np.prod(shape[1:])) * dt.itemsize
            for shape, dt in self._state_specs)
        ps = max(1, min(int(page_sz or getattr(args, "kv_page_sz", 0)
                            or 16), self.max_len))
        self.page_sz = ps
        self.pages_per_stream = pages_needed(self.max_len, ps)
        # the decode step's attention extents, in pages: one warmed
        # program per rung, chosen per step by the longest live row
        mp = self.pages_per_stream
        self.decode_rungs = sorted(
            {-(-mp * i // self.DECODE_RUNGS)
             for i in range(1, self.DECODE_RUNGS + 1)})
        self.page_bytes = self.token_bytes * ps
        req_pages = requested * self.pages_per_stream
        # the slots' states are allocated whole, so a budget pays for them
        # before it pays for a page
        self.n_pages = self.budget.cap_pages(
            req_pages, self.page_bytes, min_pages=self.pages_per_stream,
            reserved=self.slots * self.state_bytes)
        if self.n_pages < req_pages:
            print(f"[serve.decode] kv_hbm_mb caps KV pages "
                  f"{req_pages} -> {self.n_pages} "
                  f"({self.page_bytes / 2**20:.2f} MB/page, "
                  f"{self.pages_per_stream}/stream worst case)",
                  file=sys.stderr)
        # the decode step's row extents: one warmed program per (row rung,
        # page rung), chosen per step by the highest attached slot
        small = self.pad_rows(self.ROW_RUNG)
        self.row_rungs = ((small, self.slots) if self.slots >= 4 * small
                          else (self.slots,))
        self.prefill_rows = self.pad_rows(
            min(self.slots, int(prefill_rows or 8)))
        # prompt buckets: the serve bucket ladder capped at max_len, with
        # max_len always present so a requeue continuation (prompt +
        # emitted, bounded by admission at max_len) always has a bucket
        bk = usable_buckets(buckets, min(args.max_seq_len, self.max_len))
        if bk[-1] < self.max_len:
            bk = bk + (self.max_len,)
        self.prefill_buckets = bk

        # LM head: MLM-shaped, seeded beside the trunk template; a
        # trained head loads via load_lm_head.  int8 weight serving
        # quantizes it through the same serving-form door as the trunk.
        head_key = jax.random.key(args.seed + 1)
        if family.lazy_weights:
            self._head_template = jax.eval_shape(
                lambda: family.init_head(head_key, cfg))
            self.head = lambda: self._put(family.init_head(head_key, cfg))
        else:
            self._head_template = family.init_head(head_key, cfg)
            self.head = self._put(self._serving_form(self._head_template))
        self.head_path: Optional[str] = None

        # --- pools, allocator, prefix index, page tables
        self.prefix_share = bool(prefix_share)
        self._index_entries = int(index_entries)
        self._alloc_cache()

        # --- programs.  Every one takes the cache as ONE tuple of pools
        # (twin K and V pools, or the one latent pool: the family's) and,
        # AFTER the arguments it always had, ONE tuple of per-slot states
        # (empty — no operand — for a family without a recurrent layer),
        # both donated, then the int8 scale tables — none for a float cache;
        # what a family counts per launch rides back as ``aux``
        metrics_ref = self.metrics
        dtype, mesh = self.dtype, self.mesh

        def _prefill_fn(params, head, ids, mask, last_pos):
            metrics_ref.retraces.inc()  # body runs only while tracing
            # -> (logits, chosen ids, aux, the new rows of every pool, each
            #     prompt's final state)
            logits, aux, news, fin = family.prefill(
                params, head, cfg, ids, mask, last_pos, dtype)
            return logits, greedy_ids(logits), aux, news, fin

        def _pinsert_fn(pools, news, flat_pos, states, fin, slot_ids,
                        *scales):
            metrics_ref.retraces.inc()
            # ``slot_ids`` is None (no operand) where there is no state
            return (families.insert(pools, news, flat_pos, scales or None),
                    families.insert_states(states, fin, slot_ids))

        def _pdecode_fn(params, head, pools, tokens, table, pos, states,
                        *scales):
            metrics_ref.retraces.inc()
            logits, aux, pools, states = family.attend(
                params, head, cfg, tokens, pools, states, table, pos, None,
                "last", scales or None, dtype, mesh)
            return logits, greedy_ids(logits), aux, pools, states

        def _pchunk_fn(params, head, pools, tokens, table, start, nreal,
                       states, *scales):
            metrics_ref.retraces.inc()
            logits, aux, pools, states = family.attend(
                params, head, cfg, tokens, pools, states, table, start,
                nreal, "last", scales or None, dtype, mesh)
            return logits, greedy_ids(logits), aux, pools, states

        def _pverify_fn(params, head, pools, tokens, table, start, nreal,
                        states, *scales):
            metrics_ref.retraces.inc()
            return family.attend(params, head, cfg, tokens, pools, states,
                                 table, start, nreal, "all", scales or None,
                                 dtype, mesh)

        def _pcow_fn(pools, src, dst):
            metrics_ref.retraces.inc()
            return tuple(decoder.copy_pool(p, src, dst) for p in pools)

        def _pexport_fn(pools, src):
            metrics_ref.retraces.inc()
            return tuple(decoder.gather_pool(p, src) for p in pools)

        def _pimport_fn(pools, payloads, dst):
            metrics_ref.retraces.inc()
            return tuple(decoder.scatter_pool(p, x, dst)
                         for p, x in zip(pools, payloads))

        self._jit_prefill = jax.jit(_prefill_fn)
        self._jit_pinsert = jax.jit(_pinsert_fn, donate_argnums=(0, 3))
        self._jit_pdecode = jax.jit(_pdecode_fn, donate_argnums=(2, 6))
        self._jit_pchunk = jax.jit(_pchunk_fn, donate_argnums=(2, 7))
        self._jit_pverify = jax.jit(_pverify_fn, donate_argnums=(2, 7))
        self._jit_pcow = jax.jit(_pcow_fn, donate_argnums=(0,))
        # export reads the pool (no donation — the sender keeps serving
        # from it); import donates like every other cache writer
        self._jit_pexport = jax.jit(_pexport_fn)
        self._jit_pimport = jax.jit(_pimport_fn, donate_argnums=(0,))

    # the twin pools under the names the benchmark's kinds read
    # (``engine._cache_k.shape``); read-only views of ``_pools``, and a
    # one-pool family has no second
    @property
    def _cache_k(self):
        return self._pools[0]

    @property
    def _cache_v(self):
        return self._pools[1] if len(self._pools) > 1 else None

    @property
    def head(self):
        """The LM head (made on first read for a lazy family, like
        :attr:`params`)."""
        if callable(self._head):
            self._head = self._head()
        return self._head

    @head.setter
    def head(self, value) -> None:
        self._head = value

    # ----------------------------------------------------------- lifecycle
    def _alloc_cache(self) -> None:
        """(Re)allocate the page pool + a fresh allocator/index/table —
        construction and post-chaos :meth:`reset_cache`, never hot."""
        cfg = self.cfg

        def alloc(layers, width):
            # one position's values of one pool are ONE minor axis: [page_sz,
            # width] is what the chip tiles (models.decoder, paged-cache
            # note).  SEPARATE buffers: device_put of one shared zeros
            # array would alias the pools, and a donating program would
            # then donate the same buffer twice
            return jax.device_put(jnp.zeros(
                (layers, self.n_pages, self.page_sz, width), self.kv_dtype))

        #: the cache: one array per pool of the family (``models.families``),
        #: each over ITS layers and width, every one donated to each program
        self._pools = tuple(alloc(n, w)
                            for n, w in families.pool_shapes(cfg))
        #: what the slots keep besides pages (a recurrent family; else
        #: empty), ``[slots, ...]`` each and donated like the pools.  A
        #: slot's rows are written WHOLE by the prefill that seats a stream,
        #: so a detached slot's leftovers never reach the next stream
        self._states = tuple(jax.device_put(jnp.zeros(shape, dt))
                             for shape, dt in self._state_specs)
        self.allocator = PageAllocator(self.n_pages, self.page_sz,
                                       self.page_bytes)
        self.prefix = PrefixIndex(self.allocator, self.page_sz,
                                  max_entries=self._index_entries)
        if self.prefix_share:
            self.allocator.reclaimer = self.prefix.evict
        # per-slot page tables, host-resident and updated IN PLACE at
        # attach/detach (never rebuilt per step — jaxlint R16 polices
        # the rebuild-by-concatenate idiom); sentinel n_pages = dead row
        self._table = np.full((self.slots, self.pages_per_stream),
                              self.n_pages, np.int32)
        self._slot_state: List[Optional[_PageClaim]] = [None] * self.slots
        self._pending_cow: List[tuple] = []

    def reset_cache(self) -> None:
        self._alloc_cache()

    @property
    def prompt_limit(self) -> int:
        """Longest admissible prompt (the widest prefill bucket)."""
        return int(self.prefill_buckets[-1])

    # -------------------------------------------------------- admission
    def check_stream_admissible(self, prompt_len: int,
                                max_new: int) -> None:
        """The admission door's capacity + budget math, in one place.  On
        a BUDGETED engine an oversized stream refuses in the budget's own
        units, pages (:class:`~pdnlp_tpu.obs.memory.KVBudgetExceeded` with
        the MB math) — the refusal that replaces a mid-decode OOM; an
        unbudgeted engine reports the plain page-table extent."""
        total = int(prompt_len) + int(max_new)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt_len > self.prompt_limit:
            raise ValueError(
                f"prompt of {prompt_len} tokens exceeds the "
                f"{self.prompt_limit}-token prefill limit")
        if total > self.max_len:
            need = pages_needed(total, self.page_sz)
            if self.budget.budget_bytes is not None:
                state = (f" beside {self.state_bytes / 2**20:.2f} MB of "
                         "recurrent state" if self.state_bytes else "")
                raise KVBudgetExceeded(
                    f"stream needs {need} KV pages ({total} positions, "
                    f"{need * self.page_bytes / 2**20:.2f} MB{state}) but a "
                    f"stream's page table holds {self.pages_per_stream} "
                    f"pages ({self.max_len} positions) under --kv_hbm_mb")
            raise ValueError(
                f"prompt + max_new_tokens = {total} exceeds the "
                f"{self.max_len}-position page-table extent "
                "(--decode_max_len)")

    # ------------------------------------------------------------ KV int8
    def load_kv_scales(self, path: str) -> None:
        """Load the manifest-verified int8 KV scale tables
        (``scripts/quantize_ckpt.py --kv_calib`` sidecar)."""
        if not self.kv_int8:
            raise ValueError("KV scale tables only apply to --kv_dtype "
                             "int8 engines")
        raw = ckpt.load_raw(path)
        cfg = self.cfg
        want = (cfg.num_layers, cfg.num_heads, cfg.head_dim)
        for key in ("k_scale", "v_scale"):
            got = tuple(np.asarray(raw[key]).shape)
            if got != want:
                raise ValueError(f"KV scale table {key} has shape {got}, "
                                 f"expected {want} for {self.args.model}")
        self._kv_scales = (
            self._put(jnp.asarray(np.asarray(raw["k_scale"], np.float32))),
            self._put(jnp.asarray(np.asarray(raw["v_scale"], np.float32))))

    def calibrate_kv(self) -> None:
        """Self-calibrate the int8 KV scale tables from the SERVED params
        (the seeded synthetic forward ``models.decoder.calibrate_kv_scales``
        — byte-identical to the offline ``--kv_calib`` artifact for the
        same params).  Idempotent; a checkpoint swap clears the tables so
        the next warmup recalibrates."""
        if not self.kv_int8 or self._kv_scales is not None:
            return
        # default calibration width (NOT max_len): the table must be
        # byte-identical to the offline --kv_calib artifact for the same
        # params, whatever cache geometry this engine runs
        ks, vs = decoder.calibrate_kv_scales(self.params, self.cfg,
                                             dtype=self.dtype)
        self._kv_scales = (self._put(jnp.asarray(ks)),
                          self._put(jnp.asarray(vs)))

    def load_checkpoint(self, path: str) -> None:
        super().load_checkpoint(path)
        if self.kv_int8:
            self._kv_scales = None  # stale for the new weights
            import os

            stem = path.rsplit(".msgpack", 1)[0]
            for cand in (stem, stem.rsplit(".int8", 1)[0]):
                sidecar = cand + ".kvscales.msgpack"
                if os.path.exists(sidecar):
                    self.load_kv_scales(sidecar)
                    break

    def load_lm_head(self, path: str) -> None:
        """Swap the LM head (template-validated like the trunk; an int8
        artifact validates against the quantized template)."""
        from pdnlp_tpu.serve.quant import is_quantized, quantize_params

        raw = ckpt.load_raw(path)
        if self.serve_dtype == "int8":
            if is_quantized(raw):
                host = ckpt.from_restored(
                    raw, self._serving_form(self._head_template), path=path)
            else:
                host = quantize_params(
                    ckpt.from_restored(raw, self._head_template, path=path))
        else:
            if is_quantized(raw):
                raise ValueError(
                    f"LM head {path!r} is an int8 artifact but this engine "
                    f"serves {self.serve_dtype!r} — use --serve_dtype int8")
            host = ckpt.from_restored(raw, self._head_template, path=path)
        self.head = self._put(host)
        self.head_path = path

    def _scale_args(self) -> tuple:
        if not self.kv_int8:
            return ()
        if self._kv_scales is None:
            self.calibrate_kv()
        return self._kv_scales

    # ------------------------------------------------------------ forward
    def _shard_batch(self, arrays: Dict[str, np.ndarray]) -> Dict:
        if self.mesh is None:
            return arrays
        from pdnlp_tpu.parallel.sharding import batch_sharding

        sh = batch_sharding(self.mesh)
        return {k: jax.make_array_from_process_local_data(sh, v)
                for k, v in arrays.items()}

    def _seen(self, key: tuple, steady: str) -> str:
        """Compile-cache bookkeeping of one engine call: the ``phase`` its
        dispatch leaf carries (or its span's name) — ``"compile"`` for a
        first-seen shape key (the call will trace), ``steady`` for a
        replay."""
        if key in self._seen_shapes:
            self.metrics.cache_hits.inc()
            return steady
        self.metrics.cache_misses.inc()
        self._seen_shapes.add(key)
        return "compile"

    def _fetch(self, value, wait_leaf: str, fetch_leaf: str,
               aux=None) -> np.ndarray:
        """The two leaves that end an engine call: ``<p>.device_wait`` —
        ``block_until_ready`` on ``value`` (what the host reads back of the
        launch: the chosen ids; the logits of a verify window), nothing
        else, so the device's time is never smeared into the host's — then
        ``<p>.fetch``, the ``device_get`` to numpy, with the ``bytes`` it
        moved.  Untraced, the fetch is the one barrier it always was: the
        wait leaf comes back falsy and is never entered, and inside a
        worker's round the fetch leaf's tally span times ``device_get``
        whole — the round's ``wait_fetch``, never its ``fetch``.
        ``aux`` (a family with experts: this launch's assignments to each
        held expert and the rows the experts' products computed for them,
        both summed over the layers; under a learned sparse attention also
        the positions its real queries saw and picked) is fetched with it
        and lands on the fetch leaf."""
        tr = self.tracer
        sp = tr.leaf(wait_leaf, self.span_attrs)
        if sp:
            with sp:
                jax.block_until_ready(value)
        with tr.leaf(fetch_leaf, self.span_attrs) as sp:
            out = np.asarray(jax.device_get(value))
            if sp:
                nbytes = int(out.nbytes)
                if aux is not None:
                    load, rows, *picks = (np.asarray(a)
                                          for a in jax.device_get(aux))
                    nbytes += int(load.nbytes + rows.nbytes)
                    load = load.astype(np.int64)
                    self.expert_load = load if self.expert_load is None \
                        else self.expert_load + load
                    sp.set(expert_assignments=int(load.sum()),
                           expert_rows_computed=int(rows),
                           expert_tokens_max=int(load.max()),
                           experts_idle=int((load == 0).sum()))
                    if picks:
                        # a learned sparse attention's two counts
                        nbytes += sum(int(p.nbytes) for p in picks)
                        seen = self.positions_seen.setdefault(
                            fetch_leaf.split(".")[0], np.zeros(2, np.int64))
                        seen += np.asarray(picks, np.int64)
                        sp.set(positions_visible=int(picks[0]),
                               positions_picked=int(picks[1]))
                sp.set(bytes=nbytes)
        return out

    def _fetch_chosen(self, logits, ids, leaf: str, aux=None,
                      rows: Optional[int] = None) -> Chosen:
        """End a ``leaf`` (``decode`` / ``prefill`` / ``chunk``) call: the
        host reads back the ``[rows]`` ids the program chose — 4 bytes a
        row where the ``[rows, vocab]`` float32 logits used to cross — and
        the logits stay on the device behind the result (:class:`Chosen`)
        for a caller that asks for them."""
        host = self._fetch(ids, leaf + ".device_wait", leaf + ".fetch", aux)
        rows = len(host) if rows is None else rows
        return Chosen(host[:rows], ids, logits, rows)

    def _kv_label(self) -> str:
        return "int8" if self.kv_int8 else np.dtype(self.kv_dtype).name

    def infill_ids(self, id_lists: Sequence[Sequence[int]],
                   request_ids=None) -> np.ndarray:
        """MLM-infilling scoring: the BIDIRECTIONAL trunk + LM head over
        bucketed prompts — ``[n, bucket, vocab]`` fp32 logits (the caller
        reads its ``[MASK]`` positions).  Rides the prefill bucket ladder
        and compile cache (key ``(bucket, rows, "infill")``)."""
        n = len(id_lists)
        assert n and n <= self.prefill_rows
        bucket = pick_bucket(max(len(x) for x in id_lists),
                             self.prefill_buckets)
        rows = self.prefill_rows
        ids = np.zeros((rows, bucket), np.int32)
        mask = np.zeros((rows, bucket), np.int32)
        for i, x in enumerate(id_lists):
            ids[i, :len(x)] = x
            mask[i, :len(x)] = 1
        span_name = self._seen((int(bucket), int(rows), "infill"), "forward")
        if not hasattr(self, "_jit_infill"):
            metrics_ref = self.metrics
            cfg, dtype = self.cfg, self.dtype

            def _infill_fn(params, head, ids, mask):
                metrics_ref.retraces.inc()
                return decoder.infill_logits(params, head, cfg, ids, mask,
                                             dtype=dtype)

            self._jit_infill = jax.jit(_infill_fn)
        sharded = self._shard_batch({"ids": ids, "mask": mask})
        with self.tracer.span(span_name, seq=int(bucket), rows=int(rows),
                              infill=True, dtype=self.dtype_label,
                              **self._telemetry_attrs(request_ids),
                              **self.span_attrs):
            logits = self._jit_infill(self.params, self.head,
                                      sharded["ids"], sharded["mask"])
            out = np.asarray(jax.device_get(logits))
        return out[:n]

    # ------------------------------------- a stream's pages, attach to detach
    def peek_prefix(self, ids: Sequence[int]) -> Optional[str]:
        """Admission-time prefix peek for the ``admit`` hop's
        ``prefix_hit`` attr (None = this engine does not share)."""
        if not self.prefix_share:
            return None
        return self.prefix.lookup(ids, count=False).kind

    def attach_stream(self, slot: int, stream: "DecodeStream", *,
                      share: bool = True):
        """The per-stream allocator/index transaction: reserve EVERY
        page the stream can ever touch (``ceil((prompt + max_new) /
        page_sz)`` — full reservation, so decode never page-faults),
        sharing the indexed prefix pages at refcount+1 and allocating
        the rest fresh.  Raises
        :class:`~pdnlp_tpu.serve.kvpage.KVPagesExhausted` (after index
        eviction) when the pool cannot cover it — the batcher leaves the
        stream queued and retries as live streams drain.
        ``share=False``: cold claim regardless of the index (the
        KV-handoff import scatters into the reservation — writing into
        shared prefix pages would corrupt every other holder)."""
        tokens = list(stream.prompt_ids) + list(stream.emitted)
        total = min(len(stream.prompt_ids) + stream.max_new_tokens,
                    self.max_len)
        ps = self.page_sz
        need = pages_needed(total, ps)
        owner = stream.rid
        n_full = len(tokens) // ps
        hit = (self.prefix.lookup(tokens)
               if (self.prefix_share and share) else PrefixHit("miss"))
        row = np.full((self.pages_per_stream,), self.n_pages, np.int32)
        if hit.kind == "full" and hit.first_token is not None:
            shared = [int(p) for p in hit.pages[:n_full]]
            partial_src = (int(hit.pages[n_full])
                           if len(hit.pages) > n_full else None)
            # pin the shared pages (and the COW source) BEFORE the
            # private alloc: the alloc's index eviction may drop the
            # entries we just matched, and only the stream's own
            # references keep their pages from returning to the free
            # list mid-transaction
            pin = shared + ([partial_src] if partial_src is not None
                            else [])
            self.allocator.share(pin, owner)
            # ANY failure between the acquire and the page-table commit
            # below must hand the reservation back, or the pages leak:
            # KVPagesExhausted from the private alloc is just the common
            # case (hence BaseException, not a named tuple of "expected"
            # errors)
            try:
                private = self.allocator.alloc(need - n_full, owner)
                row[:n_full] = shared
                row[n_full:need] = private
                claim = _PageClaim(owner, "full", tokens,
                                   pages_needed(len(tokens), ps),
                                   first_token=int(hit.first_token))
                if partial_src is not None and len(tokens) % ps and private:
                    self._pending_cow.append((partial_src, private[0]))
                    self.allocator.count_cow()
            except BaseException:
                self.allocator.release_owner(owner)
                raise
        else:
            n_shared = len(hit.pages) if hit.kind == "partial" else 0
            if n_shared and n_shared * ps >= len(tokens):
                # keep at least one suffix token so the chunk forward
                # has a last-token logit row to emit from
                n_shared -= 1
            if n_shared:
                shared = [int(p) for p in hit.pages[:n_shared]]
                self.allocator.share(shared, owner)
                try:
                    private = self.allocator.alloc(need - n_shared,
                                                   owner)
                    row[:n_shared] = shared
                    row[n_shared:need] = private
                    claim = _PageClaim(owner, "partial", tokens,
                                       pages_needed(len(tokens), ps),
                                       suffix=tokens[n_shared * ps:],
                                       start=n_shared * ps)
                except BaseException:
                    self.allocator.release_owner(owner)
                    raise
            else:
                private = self.allocator.alloc(need, owner)
                try:
                    row[:need] = private
                    claim = _PageClaim(owner, "cold", tokens,
                                       pages_needed(len(tokens), ps))
                except BaseException:
                    self.allocator.release_owner(owner)
                    raise
        self._table[slot] = row
        self._slot_state[slot] = claim
        return claim

    def detach_slot(self, slot: int) -> None:
        """Release ``slot``'s page reservation.  Its rows of the per-slot
        states stay as they lie: nothing reads a detached slot's, and the
        prefill that seats the next stream writes them whole."""
        if not (0 <= slot < self.slots):
            return
        st = self._slot_state[slot]
        if st is None:
            return
        held = set(int(p) for p in self._table[slot]
                   if p < self.n_pages)
        # a stream that finished before its COW flushed (EOS on the
        # stored first token) must take its pending copies with it —
        # both sides of each pair were pinned by this owner only
        self._pending_cow = [(s, d) for (s, d) in self._pending_cow
                             if d not in held and s not in held]
        self._slot_state[slot] = None
        self._table[slot, :] = self.n_pages
        self.allocator.release_owner(st.owner)
        # drafter engines: tentative (uncommitted) pages live under the
        # draft owner — release them too or a drained audit reports the
        # "#draft" alias as a leak
        self.allocator.release_owner(draft_owner(st.owner))

    # ---------------------------------------------- draft page custody
    # Two-owner custody for speculative decoding (DRAFTER-side engine):
    # pages wholly beyond the committed cache length hold only tentative
    # drafted K/V, so they belong to ``draft_owner(rid)`` — the ledger
    # then names exactly which pages a rejection would strand, and
    # ``transfer`` (a leaklint-recognised releaser) moves each page to
    # the stream owner the moment a verify round commits across it.
    def split_draft_custody(self, slot: int, committed_len: int) -> None:
        """Move the reservation's pages wholly beyond ``committed_len``
        positions to the slot's draft owner (post-attach, pre-draft)."""
        st = self._slot_state[slot] if 0 <= slot < self.slots else None
        if st is None:
            return
        n_commit = pages_needed(committed_len, self.page_sz)
        pages = [int(p) for p in self._table[slot] if p < self.n_pages]
        tail = pages[n_commit:]
        if tail:
            self.allocator.transfer(tail, st.owner,
                                    draft_owner(st.owner))
        st.draft_from = n_commit

    def commit_draft(self, slot: int, committed_len: int) -> None:
        """A verify round accepted tokens through ``committed_len``
        positions: transfer every boundary-crossed page back to the
        stream owner.  Rejected pages simply stay under draft custody —
        the next round overwrites them in place."""
        st = self._slot_state[slot] if 0 <= slot < self.slots else None
        if st is None or st.draft_from is None:
            return
        n_commit = pages_needed(committed_len, self.page_sz)
        if n_commit <= st.draft_from:
            return
        pages = [int(p) for p in self._table[slot] if p < self.n_pages]
        crossed = pages[st.draft_from:n_commit]
        if crossed:
            self.allocator.transfer(crossed, draft_owner(st.owner),
                                    st.owner)
        st.draft_from = n_commit

    # ------------------------------------------------------- KV handoff
    # Disaggregated serving: a prefill-role engine exports one stream's
    # pages as a dense payload and a decode-role engine imports them
    # into its own fresh reservation.  Both programs are FIXED shape —
    # the src/dst rows are ALWAYS the ``pages_per_stream`` table extent,
    # sentinel-padded (jaxlint R18 polices the per-stream-count
    # retrace spelling), so one compiled export and one compiled import
    # serve every stream.
    def require_handoff(self) -> None:
        """Refuse, loudly, for a family whose cache has no payload form."""
        if not self.family.handoff:
            self.family.refuse(
                "the disaggregated handoff (a stream's pages as a payload)",
                "prefill and decode on one engine (--disagg off)")

    def export_pages(self, slot: int, request_ids=None):
        """Export ``slot``'s pages as a host ``[L, pages_per_stream,
        page_sz, H]`` payload pair (K, V) — raw cache bytes (int8
        cache exports int8; both pools calibrate identical scale tables
        from the same params, so no rescaling crosses the wire).  An
        out-of-range ``slot`` exports the sentinel row (zero payload) —
        the warmup path.  Compile key ``("export", pages_per_stream)``."""
        self.require_handoff()
        self._flush_cow()
        if 0 <= slot < self.slots:
            src = np.asarray(self._table[slot], np.int32)
        else:
            src = np.full((self.pages_per_stream,), self.n_pages,
                          np.int32)
        span_name = self._seen(("export", int(self.pages_per_stream)),
                               "handoff")
        with self.tracer.span(span_name, export=True, paged=True,
                              pages=int(self.pages_per_stream),
                              **self._telemetry_attrs(request_ids),
                              **self.span_attrs):
            k, v = self._jit_pexport(self._pools, src)
            out_k = np.asarray(jax.device_get(k))
            out_v = np.asarray(jax.device_get(v))
        return out_k, out_v

    def import_pages(self, slot: int, payload_k, payload_v,
                     request_ids=None) -> None:
        """Scatter a handoff payload into ``slot``'s (cold, freshly
        allocated) reservation.  Rows past the stream's real page count
        carry the sentinel and are dropped; geometry is validated
        loudly BEFORE anything writes.  An out-of-range ``slot``
        scatters against the sentinel row (all dropped) — the warmup
        path.  Compile key ``("import", pages_per_stream)``."""
        cfg = self.cfg
        want = (cfg.num_layers, self.pages_per_stream, self.page_sz,
                cfg.hidden_size)
        got = tuple(int(s) for s in np.shape(payload_k))
        if got != want or tuple(int(s)
                                for s in np.shape(payload_v)) != want:
            raise HandoffError(
                f"handoff payload shape {got} does not match this "
                f"engine's page geometry {want} — pools must share one "
                "model config and page size")
        self._flush_cow()
        if 0 <= slot < self.slots:
            dst = np.asarray(self._table[slot], np.int32)
        else:
            dst = np.full((self.pages_per_stream,), self.n_pages,
                          np.int32)
        span_name = self._seen(("import", int(self.pages_per_stream)),
                               "handoff")
        with self.tracer.span(span_name, import_=True, paged=True,
                              pages=int(self.pages_per_stream),
                              **self._telemetry_attrs(request_ids),
                              **self.span_attrs):
            self._pools = self._jit_pimport(
                self._pools, (jnp.asarray(payload_k),
                              jnp.asarray(payload_v)), dst)

    def begin_handoff(self, slot: int):
        """Stage ``slot``'s stream for handoff: move its page refs to
        the staging owner (:func:`~pdnlp_tpu.serve.kvpage.
        stage_handoff` — the custody acquire the caller must discharge
        with ``allocator.release_owner(staged)`` once the dispatch
        settles, success or failure) and clear the slot WITHOUT
        releasing anything — the slot row is immediately reusable while
        the pages stay pinned under the staged owner.  Returns
        ``(staged_owner, pages)``."""
        st = self._slot_state[slot] if 0 <= slot < self.slots else None
        if st is None:
            raise ValueError(f"begin_handoff on empty slot {slot}")
        pages = [int(p) for p in self._table[slot] if p < self.n_pages]
        # pending COW pairs rooted in this slot's pages travel with the
        # stream — but the payload was already exported post-flush, so
        # by construction none are pending here; drop defensively
        held = set(pages)
        self._pending_cow = [(s, d) for (s, d) in self._pending_cow
                             if d not in held and s not in held]
        self._slot_state[slot] = None
        self._table[slot, :] = self.n_pages
        staged = stage_handoff(self.allocator, pages, st.owner)
        # a full prefix hit with a partial tail page pinned the COW
        # SOURCE under the stream owner (attach's pin list); that page
        # is not in the table row, so the stage above left the pin
        # behind — and the payload was exported post-flush, so its job
        # is done.  Discharge the stream owner's leftovers here, or a
        # handed-off full-hit stream leaks its pin forever.
        self.allocator.release_owner(st.owner)
        return staged, pages

    def warmup_handoff(self) -> None:
        """Pre-trace the export and import programs (sentinel rows: the
        export reads zero-fill, the import drops every row — no live
        page is touched).  After this a handoff never compiles."""
        pk, pv = self.export_pages(self.slots)
        self.import_pages(self.slots, pk, pv)

    def register_slot(self, slot: int, first_token: int) -> None:
        """Index ``slot``'s freshly prefilled prompt for later sharing."""
        if not self.prefix_share:
            return
        st = self._slot_state[slot] if 0 <= slot < self.slots else None
        if st is None:
            return
        pages = [int(p) for p in self._table[slot][:st.n_prompt_pages]]
        self.prefix.register(st.tokens, pages,
                             first_token=int(first_token))

    def leak_check(self) -> Dict:
        """Allocator ledger audit + who still holds pages — the chaos
        tests call this after drain (every non-index owner must be gone,
        the refcount ledger must reconcile)."""
        audit = self.allocator.leak_check()
        audit["stream_owners"] = [o for o in self.allocator.owners()
                                  if o != INDEX_OWNER]
        audit["index_entries"] = len(self.prefix)
        audit["ok"] = bool(audit["ok"]) and not audit["stream_owners"]
        return audit

    # ----------------------------------------------------------- forward
    def _flush_cow(self, force: bool = False) -> None:
        """Execute pending copy-on-write page copies (fixed
        :data:`COW_ROWS`-row program; sentinel-padded).  Runs before any
        program that could read or write the copied pages — the paged
        prefill/chunk/decode entry points all call it first, BEFORE
        their own dispatch leaf opens (leaves never nest).  Enqueue
        only: nothing is waited for or fetched, so ``cow.dispatch`` is
        the call's one leaf."""
        if not self._pending_cow and not force:
            return
        P = self.n_pages
        pend = self._pending_cow
        self._pending_cow = []
        rows = self.COW_ROWS
        for i in range(0, max(len(pend), 1), rows):
            batch = pend[i:i + rows]
            with self.tracer.leaf("cow.dispatch", self.span_attrs) as sp:
                src = np.full((rows,), P, np.int32)
                dst = np.full((rows,), P, np.int32)
                for j, (s, d) in enumerate(batch):
                    src[j] = s
                    dst[j] = d
                phase = self._seen(("cow", rows), "prefill")
                if sp:
                    sp.set(phase=phase, cow=True, cow_pages=len(batch))
                self._pools = self._jit_pcow(self._pools, src, dst)

    def prefill_ids(self, id_lists: Sequence[Sequence[int]],
                    slot_ids: Sequence[int],
                    request_ids=None) -> Chosen:
        """Cold-path prefill of up to ``prefill_rows`` prompts into their
        claimed slots: ONE bucketed causal forward whatever the slot
        (bitwise-identical K/V for identical prompts — the sharing
        contract rests on this), scattered into pages through each
        claimed slot's table; returns each prompt's FIRST token (``.ids``,
        host) over its ``[n, vocab]`` fp32 logits (on the device until
        read: :class:`Chosen`).  Filler rows and padding carry the OOB
        sentinel, so they can never touch a live page.  The compile-cache
        key is ``(bucket, rows, "prefill")``; warmup pre-traces every
        bucket so steady traffic never compiles."""
        self._flush_cow()
        n = len(id_lists)
        assert n and n <= self.prefill_rows
        with self.tracer.leaf("prefill.dispatch", self.span_attrs) as sp:
            bucket = pick_bucket(max(len(x) for x in id_lists),
                                 self.prefill_buckets)
            rows = self.prefill_rows
            ps = self.page_sz
            # whole pages where the bucket is made of them (one index a
            # page), else position by position (decoder.paged_insert)
            unit = ps if bucket % ps == 0 else 1
            ids = np.zeros((rows, bucket), np.int32)
            mask = np.zeros((rows, bucket), np.int32)
            last = np.zeros((rows,), np.int32)
            flat = np.full((rows, bucket // unit),
                           self.n_pages * ps // unit, np.int32)
            # the slot each row's final state goes to (a recurrent family;
            # filler rows carry the sentinel ``slots`` and are dropped)
            seat = (np.full((rows,), self.slots, np.int32)
                    if self._states else None)
            for i, (x, s) in enumerate(zip(id_lists, slot_ids)):
                ids[i, :len(x)] = x
                mask[i, :len(x)] = 1
                last[i] = len(x) - 1
                if 0 <= s < self.slots and self._slot_state[s] is not None:
                    if seat is not None:
                        seat[i] = s
                    row = self._table[s]
                    if unit == ps:
                        n_pg = pages_needed(len(x), ps)
                        flat[i, :n_pg] = row[:n_pg]
                    else:
                        p = np.arange(len(x))
                        flat[i, :len(x)] = row[p // ps] * ps + p % ps
            phase = self._seen((int(bucket), int(rows), "prefill"),
                               "prefill")
            sharded = self._shard_batch({"ids": ids, "mask": mask})
            if sp:
                sp.set(phase=phase, seq=int(bucket), rows=int(rows),
                       streams=int(n), prefill=True, paged=True,
                       tokens=int(mask.sum()), dtype=self.dtype_label,
                       **self._telemetry_attrs(request_ids))
                if self._states:
                    # the final states the launch writes into its slots
                    sp.set(state_bytes=int(n) * self.state_bytes)
            logits, chosen, aux, news, fin = self._jit_prefill(
                self.params, self.head, sharded["ids"], sharded["mask"],
                last)
            self._pools, self._states = self._jit_pinsert(
                self._pools, news, flat, self._states, fin, seat,
                *self._scale_args())
        return self._fetch_chosen(logits, chosen, "prefill", aux, rows=n)

    def prefill_chunk(self, suffixes: Sequence[Sequence[int]],
                      slot_ids: Sequence[int], starts: Sequence[int],
                      request_ids=None) -> Chosen:
        """Partial-hit prefill: only the divergent SUFFIX runs
        (``decoder.paged_chunk_step`` — the chunk attends to the shared
        prefix pages through the table), bucketed over the same ladder
        as prompts (compile key ``(bucket, rows, "chunk")``; warmup
        pre-traces every bucket).  Returns each suffix's next token over
        its last-token logits ``[n, vocab]`` (:class:`Chosen`)."""
        self._flush_cow()
        n = len(suffixes)
        assert n and n <= self.prefill_rows
        with self.tracer.leaf("chunk.dispatch", self.span_attrs) as sp:
            bucket = pick_bucket(max(len(x) for x in suffixes),
                                 self.prefill_buckets)
            rows = self.prefill_rows
            tokens = np.zeros((rows, bucket), np.int32)
            start = np.zeros((rows,), np.int32)
            nreal = np.zeros((rows,), np.int32)
            table = np.full((rows, self.pages_per_stream), self.n_pages,
                            np.int32)
            for i, (x, s, st) in enumerate(zip(suffixes, slot_ids, starts)):
                tokens[i, :len(x)] = x
                start[i] = int(st)
                nreal[i] = len(x)
                if 0 <= s < self.slots:
                    table[i] = self._table[s]
            phase = self._seen((int(bucket), int(rows), "chunk"),
                               "prefill")
            if sp:
                sp.set(phase=phase, seq=int(bucket), rows=int(rows),
                       streams=int(n), prefill=True, paged=True,
                       chunk=True, tokens=int(nreal.sum()),
                       cached=int(sum(int(s) for s in starts)),
                       kv_positions_read=rows * self.max_len,
                       kv_positions_live=int((start + nreal)[:n].sum()),
                       cache_bytes_per_token=self.token_bytes,
                       dtype=self.dtype_label,
                       **self._telemetry_attrs(request_ids))
            logits, chosen, aux, self._pools, self._states = \
                self._jit_pchunk(
                    self.params, self.head, self._pools, tokens, table,
                    start, nreal, self._states, *self._scale_args())
        return self._fetch_chosen(logits, chosen, "chunk", aux, rows=n)

    def decode_batch(self, tokens: np.ndarray, pos: np.ndarray,
                     live: int, request_ids=None) -> Chosen:
        """One fixed-shape decode step over the slot block's seated rows:
        tokens ``[slots]`` (current token per slot; dead slots ride with
        junk), ``pos`` ``[slots]`` write positions, reading whole pages
        through the per-slot page tables.  The launch covers rows ``[0,
        r)``, ``r`` the smallest rung of :attr:`row_rungs` above the highest
        ATTACHED slot (the batcher seats the lowest free slot, so ``r``
        follows how many streams are seated); rows at ``r`` and above are
        not computed.  Returns each launched row's next token (``.ids``: the
        ``r x 4`` bytes the host fetches) over the ``[r, vocab]`` fp32
        logits, which stay on the device until a caller reads them
        (:class:`Chosen`).  The table is data, not shape; its WIDTH is the
        attention extent, cut to the smallest rung of :attr:`decode_rungs`
        that reaches the longest of those rows — compile key ``("decode",
        r, rung)``, every pair traced in warmup, so neither seating nor
        paging can retrace."""
        return self._decode_rows(tokens, pos, live, request_ids)

    def _decode_rows(self, tokens: np.ndarray, pos: np.ndarray, live: int,
                     request_ids=None, r: Optional[int] = None) -> Chosen:
        """:meth:`decode_batch` at the row rung ``r`` (warmup names rungs no
        attached slot calls for; ``None``: the one the table calls for)."""
        self._flush_cow()
        with self.tracer.leaf("decode.dispatch", self.span_attrs) as sp:
            if r is None:
                attached = np.flatnonzero(self._table[:, 0] < self.n_pages)
                top = int(attached[-1]) + 1 if len(attached) else 0
                r = next(g for g in self.row_rungs if g >= top)
            tok = np.asarray(tokens, np.int32).reshape(self.slots, 1)[:r]
            p = np.clip(np.asarray(pos, np.int32), 0, self.max_len - 1)[:r]
            table = self._table[:r]
            need = pages_needed(int(p.max()) + 1, self.page_sz)
            rung = next(g for g in self.decode_rungs if g >= need)
            phase = self._seen(("decode", r, rung), "decode")
            if sp:
                alive = table[:, 0] < self.n_pages
                # what attention reads: the seated rows' own pages where
                # the step walks them (the program's own choice, asked of
                # where it is made), else the rung of every row
                form = self.family.attend_form(1, self.kv_int8, self.mesh)
                # ... or what a learned sparse attention picks of it
                read = (int((p[alive] // self.page_sz + 1).sum())
                        * self.page_sz if form == "kernel" else
                        r * self.family.read_extent(self.cfg,
                                                    rung * self.page_sz))
                sp.set(phase=phase, rows=r, live=int(live),
                       decode=True, paged=True,
                       pages_live=self.allocator.used_pages,
                       attend=form,
                       kv_positions_read=read,
                       kv_positions_live=int((p[alive] + 1).sum()),
                       cache_bytes_per_token=self.token_bytes,
                       dtype=self.dtype_label, kv=self._kv_label(),
                       **self._telemetry_attrs(request_ids))
                if self._states:
                    # the launched rows' states, read and written
                    sp.set(state_bytes=2 * r * self.state_bytes)
            logits, chosen, aux, self._pools, self._states = \
                self._jit_pdecode(
                    self.params, self.head, self._pools, tok,
                    table[:, :rung], p, self._states, *self._scale_args())
        return self._fetch_chosen(logits, chosen, "decode", aux)

    def verify_ids(self, window: np.ndarray, pos: np.ndarray,
                   nreal: np.ndarray, live: int,
                   request_ids=None) -> np.ndarray:
        """Speculative verify-1: score a fixed ``[slots, k+1]`` token
        window (pending token + k drafts per live row) in ONE
        prefill-shaped call against the paged cache
        (``models.decoder.paged_verify_step``).  Returns ``[slots, k+1,
        vocab]`` fp32 logits — the greedy target at every window offset.
        The call IS the primary-side commit: accepted positions' K/V is
        already written through the table when it returns, and rejected
        tail writes are invisible behind the position mask (overwritten
        in place next round).  Compile key ``("verify", slots, k+1)`` —
        one program per k, retrace-free once warmed
        (:meth:`warmup_verify`); rows with ``nreal == 0`` are dead and
        write nothing (sentinel table rows)."""
        self._flush_cow()
        with self.tracer.leaf("verify.dispatch", self.span_attrs) as sp:
            k1 = int(window.shape[1])
            phase = self._seen(("verify", int(self.slots), k1), "verify")
            tok = np.asarray(window, np.int32).reshape(self.slots, k1)
            start = np.asarray(pos, np.int32)
            nr = np.asarray(nreal, np.int32)
            if sp:
                sp.set(phase=phase, rows=int(self.slots), seq=k1,
                       live=int(live), verify=True, paged=True,
                       pages_live=self.allocator.used_pages,
                       dtype=self.dtype_label, kv=self._kv_label(),
                       **self._telemetry_attrs(request_ids))
            logits, aux, self._pools, self._states = self._jit_pverify(
                self.params, self.head, self._pools, tok,
                jnp.asarray(self._table), start, nr, self._states,
                *self._scale_args())
        return self._fetch(logits, "verify.device_wait", "verify.fetch",
                           aux)

    def warmup_verify(self, k1: int) -> None:
        """Pre-trace the ``("verify", slots, k1)`` program (all-dead
        window: sentinel tables, zero ``nreal`` — no live page is
        touched).  The speculating batcher warms its configured
        ``draft_k + 1``; adapting k at runtime compiles the new width
        exactly once."""
        window = np.zeros((self.slots, int(k1)), np.int32)
        pos = np.zeros((self.slots,), np.int32)
        nreal = np.zeros((self.slots,), np.int32)
        self.verify_ids(window, pos, nreal, live=0)

    def warmup_decode(self) -> None:
        """Pre-trace every reachable paged shape: per-bucket prefill +
        paged insert, per-bucket suffix chunk, the decode step of every
        (row rung, page rung) pair, the fixed COW copy, and the int8
        calibration if pending."""
        self._scale_args()
        for b in self.prefill_buckets:
            # a bucket-FILLING dummy, so each bucket traces ITS shape;
            # OOB slot id: filler tables/flat sentinels — no live page
            # is touched
            self.prefill_ids([[self.tokenizer.cls_id] * b], [self.slots])
            if self.family.prefix:
                # the suffix chunk exists only after a prefix hit
                self.prefill_chunk([[self.tokenizer.cls_id] * b],
                                   [self.slots], [0])
        self._flush_cow(force=True)
        tok = np.zeros((self.slots,), np.int32)
        for rung in self.decode_rungs:
            # all-dead rows (sentinel tables write nothing) at a position
            # only this rung reaches
            pos = np.full((self.slots,), rung * self.page_sz - 1, np.int32)
            for r in self.row_rungs:
                self._decode_rows(tok, pos, live=0, r=r)

    def kv_snapshot(self) -> Dict:
        """Budget block + the paged story: allocator occupancy/free
        depth/COW and the prefix index's hit accounting — the leaves the
        Prometheus exporter flattens into gauges."""
        return {
            **self.budget.snapshot(),
            "layout": "paged",
            "slots": int(self.slots),
            "max_len": int(self.max_len),
            "kv_dtype": self._kv_label(),
            "cache_bytes": self.n_pages * self.page_bytes,
            # the residual a token carries between layers (a family's
            # ``hc_mult`` streams of it): never cached, beside token_bytes
            "stream_bytes_a_token": int(
                getattr(self.cfg, "hc_mult", 1) * self.cfg.hidden_size
                * np.dtype(self.dtype).itemsize),
            # of token_bytes, a learned sparse attention's index keys
            "index_bytes_a_token": int(
                getattr(self.cfg, "num_index_layers", 0)
                * getattr(self.cfg, "index_cache_width", 0)
                * np.dtype(self.kv_dtype).itemsize),
            "positions_seen": {k: [int(x) for x in v]
                               for k, v in self.positions_seen.items()},
            "kv_pool_bytes": int(sum(p.nbytes for p in self._pools)),
            "state_pool_bytes": int(sum(x.nbytes for x in self._states)),
            "weights_bytes": int(sum(
                x.nbytes for x in jax.tree_util.tree_leaves(
                    (self.params, self.head)))),
            "pages": self.allocator.snapshot(),
            "prefix": self.prefix.snapshot(),
        }


class DecodeStream:
    """A caller's handle on one generative request — future AND iterator:
    :meth:`result` blocks for the full generation, :meth:`tokens` yields
    token ids as they are produced (the streaming-response surface
    ``serve_tpu.py --decode`` prints from)."""

    __slots__ = ("rid", "prompt_ids", "max_new_tokens", "deadline",
                 "submitted", "born", "seated_at", "first_token_at",
                 "last_token_at", "emitted", "replica", "slot",
                 "prefix_hit", "traced", "spec_accepted",
                 "_clock", "_q", "_event", "_error")

    def __init__(self, prompt_ids: List[int], max_new_tokens: int,
                 deadline: Optional[float] = None,
                 clock: Callable[[], float] = time.perf_counter):
        """``clock``: the admitting batcher's ``Tracer.now`` — every stamp
        of a stream's life (``submitted``, ``seated_at``,
        ``first_token_at``, ``last_token_at``) is on the tracer's clock,
        so its ``request`` record lies on the leaves' timeline
        (``deadline`` is the one ``time.monotonic`` value, and is only
        ever compared with that clock)."""
        self.rid = mint_request_id()
        self.prompt_ids = list(prompt_ids)
        self.max_new_tokens = int(max_new_tokens)
        self.deadline = deadline
        self._clock = clock
        self.submitted = clock()
        self.born = self.submitted
        self.seated_at: Optional[float] = None  # FIRST slot assignment
        self.prefix_hit: Optional[str] = None   # its claim's kind
        self.traced = False  # first token inside a recording window
        self.first_token_at: Optional[float] = None
        self.last_token_at: Optional[float] = None
        self.emitted: List[int] = []
        self.replica: Optional[int] = None
        self.slot: Optional[int] = None
        self.spec_accepted = 0  # cumulative accepted drafts (monotone)
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._event = threading.Event()
        self._error: Optional[BaseException] = None

    # --- worker half ---
    def _push(self, token: int, now: Optional[float] = None) -> float:
        """Record one generated token; returns the inter-token gap in
        seconds (0.0 for the first — the caller observes ttft instead).
        ``now``: the stamp of a caller that read the clock once for a
        whole block of rows."""
        if now is None:
            now = self._clock()
        gap = 0.0 if self.last_token_at is None \
            else now - self.last_token_at
        if self.first_token_at is None:
            self.first_token_at = now
        self.last_token_at = now
        token = int(token)
        self.emitted.append(token)
        self._q.put(token)
        return gap

    def _finish(self, error: Optional[BaseException] = None) -> bool:
        if self._event.is_set():
            return False
        self._error = error
        self._event.set()
        self._q.put(_DONE)
        return True

    # --- caller half ---
    def done(self) -> bool:
        return self._event.is_set()

    def tokens(self, timeout: Optional[float] = 60.0):
        """Yield generated token ids as they arrive; raises the stream's
        error (if any) after the last token."""
        while True:
            item = self._q.get(timeout=timeout)
            if item is _DONE:
                break
            yield item
        if self._error is not None:
            raise self._error

    def result(self, timeout: Optional[float] = 60.0) -> List[int]:
        """Block until the stream finishes; returns ALL generated ids."""
        if not self._event.wait(timeout):
            raise TimeoutError("stream still generating")
        if self._error is not None:
            raise self._error
        return list(self.emitted)


def record_request(tracer, stream: DecodeStream, replica: int,
                   error: Optional[str] = None) -> None:
    """ONE ``request`` record per finished stream (not one per token —
    that is the ``--trace`` hop stream): where its time went before the
    first token, on the leaves' clock and under their switch.  A stream
    whose first token fell inside a recording window (``traced``) is OWED
    its record whenever it ends — a profiler session is seconds long and
    an answer outlives it.  Its leaves join on ``rid``: a ``<p>.dispatch``
    leaf it rode carries it among the bounded ``request_ids`` exemplars."""
    if not (stream.traced or tracer.recording):
        return
    t_done = tracer.now()
    attrs = {"rid": stream.rid, "t_submit": stream.submitted,
             "t_seated": stream.seated_at,
             "t_first_token": stream.first_token_at, "t_done": t_done,
             "tokens_out": len(stream.emitted),
             "prefix_hit": stream.prefix_hit, "replica": replica}
    if error is not None:
        attrs["error"] = error
    tracer.leaf_at("request", stream.submitted, t_done, attrs,
                   owed=stream.traced)


class _Slot:
    """A live row of the slot table.  ``stream`` is fixed for the seat's
    life; ``pos`` / ``next_token`` move in place, one step at a time."""
    __slots__ = ("stream", "pos", "next_token")

    def __init__(self, stream: DecodeStream, pos: int, next_token: int):
        self.stream = stream
        self.pos = pos              # write position of next_token
        self.next_token = next_token


class DecodeBatcher:
    """Continuous batching over one :class:`PagedDecodeEngine`: a single
    worker owns the engine (the repo's one-dispatcher contract) and loops
    claim → prefill → decode-step, with streams joining freed slots (the
    lowest free index first: no row is ever moved, holes fill from the
    bottom) and finished streams leaving BETWEEN steps — the decode batch
    is one of the engine's warmed shapes, and only which rows are live
    changes.

    ``on_death(replica, orphans, error)``: installed by
    :class:`DecodeRouter`; a worker that loses its engine hands over its
    live + waiting streams instead of failing them."""

    #: declared safe range for the ``draft_k`` knob (the controller
    #: clamps inside it; ``0`` = speculation off)
    DRAFT_K_MAX = 8

    def __init__(self, engine: PagedDecodeEngine, *, max_waiting: int = 256,
                 default_max_new: Optional[int] = None, replica: int = 0,
                 on_death: Optional[Callable] = None,
                 rmetrics: Optional[ReplicaMetrics] = None,
                 dmetrics: Optional[DecodeMetrics] = None,
                 drafter: Optional[PagedDecodeEngine] = None,
                 draft_k: int = 4):
        self.engine = engine
        self.tracer = engine.tracer
        self.replica = int(replica)
        engine.span_attrs.setdefault("replica", self.replica)
        # --- speculative decoding: a paired cheap drafter engine ---
        self.drafter: Optional[PagedDecodeEngine] = None
        self.drafter_model = ""
        self.draft_k = max(0, min(int(draft_k), self.DRAFT_K_MAX))
        self._drafter_poison: Optional[BaseException] = None
        self._spec_rounds = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        if drafter is not None:
            for e in (engine, drafter):
                if not e.family.verify:
                    e.family.refuse(
                        "the speculative pair (a drafter engine and its "
                        "verify window)", "decode with the primary alone")
            if (drafter.slots != engine.slots
                    or drafter.max_len != engine.max_len):
                raise ValueError(
                    f"drafter geometry (slots={drafter.slots}, "
                    f"max_len={drafter.max_len}) must match the "
                    f"primary (slots={engine.slots}, "
                    f"max_len={engine.max_len}) — the pair shares slot "
                    "indices and write positions")
            if drafter.prefix_share:
                raise ValueError(
                    "drafter engine must run prefix_share=False: its "
                    "cold prefill rewrites each stream's pages in "
                    "place, which would corrupt shared prefix pages")
            if drafter.tokenizer.vocab_size != engine.tokenizer.vocab_size:
                raise ValueError(
                    "drafter and primary must share one tokenizer: "
                    "drafted token ids are verified (and committed) "
                    "against the primary's vocab")
            drafter.span_attrs.setdefault("replica", self.replica)
            drafter.span_attrs.setdefault("role", "drafter")
            self.drafter = drafter
            self.drafter_model = str(getattr(drafter.args, "model",
                                             "drafter"))
        self.max_waiting = int(max_waiting)
        self.default_max_new = int(
            default_max_new
            or getattr(engine.args, "max_new_tokens", 32))
        self.eos_id = engine.tokenizer.sep_id
        self.on_death = on_death
        self.metrics = dmetrics or DecodeMetrics()
        self.rmetrics = rmetrics or ReplicaMetrics()
        self._slots: List[Optional[_Slot]] = [None] * engine.slots
        #: free slots as a heap: the LOWEST free index is seated next, so
        #: the seated rows stay packed at the bottom of the block and the
        #: engine's decode step covers a row rung, not all of them
        self._free: List[int] = list(range(engine.slots))
        self._freed_at: Dict[int, float] = {}
        self._waiting: deque = deque()
        #: streams arriving by KV handoff (disaggregated pools): already
        #: prefilled elsewhere, seated here with their imported payload
        self._handoffs: deque = deque()
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._stop = False
        self._poison: Optional[BaseException] = None
        self.dead = False
        self._worker: Optional[threading.Thread] = None
        self._peak_live = 0  # high-water concurrent live streams

    # ----------------------------------------------------------- lifecycle
    def start(self) -> "DecodeBatcher":
        if self._worker is None and not self.dead:
            self._stop = False
            self._worker = threading.Thread(
                target=self._run, daemon=True,
                name=f"pdnlp-decode-{self.replica}")
            self._worker.start()
        return self

    def stop(self, drain: bool = True) -> None:
        if self._worker is None:
            return
        if drain:
            with self._lock:
                while (not self.dead and not self._stop
                       and (self._waiting or self._handoffs
                            or self._live_count())):
                    self._wake.wait(timeout=0.05)
        with self._lock:
            self._stop = True
            self._wake.notify_all()
        self._worker.join(timeout=30)
        self._worker = None
        leftovers = []
        with self._lock:
            leftovers += [s for s in self._waiting]
            leftovers += [h[0] for h in self._handoffs]
            still_live = [i for i, sl in enumerate(self._slots)
                          if sl is not None]
            leftovers += [self._slots[i].stream for i in still_live]
            self._waiting.clear()
            self._handoffs.clear()
            self._slots = [None] * self.engine.slots
            self._free = list(range(self.engine.slots))
        for i in still_live:
            self.engine.detach_slot(i)  # pages back; leak_check clean
            if self.drafter is not None:
                self.drafter.detach_slot(i)
        for s in leftovers:
            if s._finish(RuntimeError("decode batcher stopped")):
                record_hop(self.tracer, s.rid, "failed",
                           error="batcher stopped")
                record_request(self.tracer, s, self.replica,
                               error="batcher stopped")

    def __enter__(self) -> "DecodeBatcher":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def kill(self, error: Optional[BaseException] = None) -> None:
        """Chaos hook (tests): the worker raises
        ``error`` before its next step — exactly the path a real engine
        failure takes."""
        with self._lock:
            self._poison = error or RuntimeError("injected replica kill")
            self._wake.notify_all()

    # ------------------------------------------------------------- submit
    def _live_count(self) -> int:
        return sum(1 for sl in self._slots if sl is not None)

    @property
    def load(self) -> int:
        with self._lock:
            return (self._live_count() + len(self._waiting)
                    + len(self._handoffs))

    def submit_ids(self, ids: Sequence[int],
                   max_new_tokens: Optional[int] = None,
                   deadline_ms: Optional[float] = None) -> DecodeStream:
        """Admit one generative stream; returns its
        :class:`DecodeStream`.  Refusals are LOUD and typed: capacity
        (``ValueError``), KV budget
        (:class:`~pdnlp_tpu.obs.memory.KVBudgetExceeded`), queue bound
        (:class:`~pdnlp_tpu.serve.batcher.QueueFullError`)."""
        ids = list(ids)
        if not ids:
            raise ValueError("empty prompt: submit at least one token id")
        max_new = int(self.default_max_new if max_new_tokens is None
                      else max_new_tokens)  # an explicit 0 must REFUSE,
        #                                     not silently take the default
        deadline = (time.monotonic() + deadline_ms / 1e3
                    if deadline_ms is not None else None)
        tr = self.tracer
        stream = DecodeStream(ids, max_new, deadline, clock=tr.now)
        try:
            self.engine.check_stream_admissible(len(ids), max_new)
        except BaseException as e:
            self.metrics.rejected_total.inc()
            record_hop(tr, stream.rid, "rejected",
                       reason=type(e).__name__)
            raise
        # admission-time peek (no side effects: LRU untouched, no hit
        # counters) — the admit hop advertises what sharing will buy
        peek = self.engine.peek_prefix(ids)
        extra = {} if peek is None else {"prefix_hit": peek}
        with self._lock:
            if self.dead or self._stop or self._worker is None:
                raise RuntimeError("decode batcher is not running")
            if len(self._waiting) >= self.max_waiting:
                self.metrics.rejected_total.inc()
                record_hop(tr, stream.rid, "rejected")
                raise QueueFullError(
                    f"decode queue full ({len(self._waiting)}"
                    f"/{self.max_waiting} waiting streams)")
            stream.replica = self.replica
            self._waiting.append(stream)
            self.metrics.streams_total.inc()
            self.metrics.waiting.set(len(self._waiting))
            record_hop(tr, stream.rid, "admit", streamed=True,
                       tokens=len(ids), max_new=max_new,
                       replica=self.replica, **extra)
            self._wake.notify()
        return stream

    def _adopt(self, stream: DecodeStream) -> bool:
        """Router re-home: enqueue an orphan stream's CONTINUATION
        (prompt + emitted-so-far re-prefills here; greedy decode then
        emits exactly the tokens the dead replica would have).  Bypasses
        admission — the stream was already accepted once."""
        with self._lock:
            if self.dead or self._stop or self._worker is None:
                return False
            stream.replica = self.replica
            self._waiting.append(stream)
            self.metrics.waiting.set(len(self._waiting))
            self.rmetrics.requeued_in.inc()
            self._wake.notify()
        return True

    def accept_handoff(self, stream: DecodeStream, pos: int,
                       next_token: int, payload_k, payload_v) -> bool:
        """Disaggregated pools: enqueue a stream whose prefill (and
        first token) already happened on a prefill-role engine.  The
        worker seats it on a cold reservation and scatters the payload
        in (:meth:`PagedDecodeEngine.import_pages`) — no prefill runs
        here, the next step is a plain decode.  Bypasses admission (the
        front door admitted it); ``False`` when this batcher cannot
        take it (dead/stopping), so the dispatcher tries the next
        decode engine — the payload is engine-agnostic."""
        with self._lock:
            if self.dead or self._stop or self._worker is None:
                return False
            stream.replica = self.replica
            self._handoffs.append((stream, int(pos), int(next_token),
                                   payload_k, payload_v))
            self._wake.notify()
        return True

    # ------------------------------------------------------------- worker
    def _run(self) -> None:
        """The worker's round: seat (``admit``) → import handoffs →
        prefill → one decode step.  Every host phase of it is a LEAF
        span (``Tracer.leaf``) stamped with the round's counter, so a
        trace shows where a round's host time went; the engine's calls
        bring their own (``<p>.dispatch`` / ``.device_wait`` /
        ``.fetch``), this class the ``admit`` and ``<p>.emit`` ones.
        Profiler or not, the round is also a ROW of the tracer's ring of
        rounds (``Tracer.open_round``): the same leaves summed, under the
        same ``replica`` and ``round``; an idle wait is no round."""
        tr = self.tracer
        rnd = 0
        try:
            while True:
                claims: List[tuple] = []
                imports: List[tuple] = []
                # the drafter is WORKER-CONFINED, not lock-guarded: the
                # ctor pairs it before start() and only _degrade_drafter
                # (this thread) ever clears it — a local read outside
                # the lock keeps it out of the lock's footprint
                dr = self.drafter
                rnd += 1
                attrs = self.engine.span_attrs
                attrs["round"] = rnd
                if dr is not None:
                    dr.span_attrs["round"] = rnd
                tr.open_round(self.replica, rnd)
                with self._lock:
                    if self._poison is not None:
                        raise self._poison
                    if self._stop:
                        return
                    if self._free and (self._waiting or self._handoffs):
                        # inside the lock, so the leaf times the seating
                        # and not the wait for a submitter to let go
                        with tr.leaf("admit", attrs) as sp:
                            self._seat_locked(dr, claims, imports)
                            if sp:
                                sp.set(seated=len(claims) + len(imports),
                                       waiting=len(self._waiting))
                    else:
                        self._expire_waiting_locked()
                    self.metrics.waiting.set(len(self._waiting))
                    live = self._live_count()
                    if not claims and live == 0:
                        if self._stop:
                            return
                        tr.drop_round()
                        rnd -= 1    # the rows' rounds count up by one
                        self._wake.notify_all()  # unblock stop(drain)
                        self._wake.wait(timeout=0.05)
                        continue
                if imports:
                    self._import_handoffs(imports)
                if claims:
                    self._prefill(claims)
                with self._lock:
                    # _slots is mutated under the lock from stop()/kill()
                    # callers — the between-steps liveness peek must not
                    # read it bare (threadlint T1)
                    any_live = self._live_count() > 0
                if any_live:
                    if self.drafter is not None and self.draft_k > 0:
                        self._speculate_step()
                    else:
                        self._decode_step()
                with self._lock:
                    self._wake.notify_all()
                tr.close_round(live, len(claims) + len(imports))
        except BaseException as e:  # noqa: BLE001 — a dead engine must
            self._die(e)           # never strand callers or streams
        finally:
            tr.drop_round()     # stopped or dead inside one: no half row

    def _seat_locked(self, dr, claims: List[tuple],
                     imports: List[tuple]) -> None:
        """Expire, then seat waiting streams into free slots (caller
        holds ``_lock``): appends ``(slot, stream, claim)`` to ``claims``
        and each seated handoff to ``imports``."""
        self._expire_waiting_locked()
        now = self.tracer.now
        # handed-off streams seat FIRST: their prefill cost is already
        # sunk on the prefill pool, and their payload pins host memory
        # until imported
        while self._free and self._handoffs:
            slot = heapq.heappop(self._free)
            ho = self._handoffs.popleft()
            stream = ho[0]
            try:
                # cold reservation (share=False): the import scatters
                # raw bytes into these pages
                self.engine.attach_stream(slot, stream, share=False)
            except KVPagesExhausted:
                heapq.heappush(self._free, slot)
                self._handoffs.appendleft(ho)
                break
            freed = self._freed_at.pop(slot, None)
            if freed is not None:
                self.rmetrics.slot_reuse_ms.observe(
                    (time.monotonic() - freed) * 1e3)
            stream.slot = slot
            if stream.seated_at is None:
                stream.seated_at = now()
            # the seat carries the pending first token and its write
            # position — the next decode step continues exactly where
            # the prefill pool left the stream
            self._slots[slot] = _Slot(stream, ho[1], ho[2])
            imports.append((slot,) + ho)
        while self._free and self._waiting:
            slot = heapq.heappop(self._free)
            stream = self._waiting.popleft()
            try:
                # the engine reserves the stream's pages here (sharing
                # any indexed prefix).  Exhausted
                # pool = put both back and wait for live streams to
                # drain — head-of-line order is preserved, and the pool
                # floor (>= one max-length stream) guarantees an empty
                # batch can always seat the head, so this cannot
                # deadlock.
                claim = self.engine.attach_stream(slot, stream)
            except KVPagesExhausted:
                heapq.heappush(self._free, slot)
                self._waiting.appendleft(stream)
                break
            if dr is not None:
                try:
                    dr.attach_stream(slot, stream)
                except KVPagesExhausted:
                    # the PAIR seats together or not at all: hand the
                    # primary reservation back and wait for live streams
                    # to drain (same no-deadlock floor argument as
                    # above, on the drafter's pool)
                    self.engine.detach_slot(slot)
                    heapq.heappush(self._free, slot)
                    self._waiting.appendleft(stream)
                    break
                except BaseException as e:  # noqa: BLE001
                    # drafter-side failure must not strand the stream:
                    # poison the drafter (the next speculate step
                    # degrades loudly to primary-only) and seat the
                    # stream without a draft cache
                    self._drafter_poison = e
            freed = self._freed_at.pop(slot, None)
            if freed is not None:
                self.rmetrics.slot_reuse_ms.observe(
                    (time.monotonic() - freed) * 1e3)
            stream.slot = slot
            if stream.seated_at is None:
                stream.seated_at = now()
                stream.prefix_hit = claim.kind
            # placeholder NOW: if the prefill below dies, the claimed
            # stream is already in _slots and the death path re-homes it
            # instead of losing it
            self._slots[slot] = _Slot(stream, 0, 0)
            claims.append((slot, stream, claim))

    def _expire_waiting_locked(self) -> None:
        now = time.monotonic()
        keep: deque = deque()
        for s in self._waiting:
            if s.deadline is not None and now >= s.deadline:
                self.metrics.deadline_expired_total.inc()
                if s._finish(DeadlineExceeded(
                        "deadline passed while waiting for a slot")):
                    record_hop(self.tracer, s.rid, "deadline")
            else:
                keep.append(s)
        self._waiting = keep

    def _import_handoffs(self, imports: List[tuple]) -> None:
        """Scatter each seated handoff's payload into its fresh
        reservation (worker-only, engine call off-lock).  No hop is
        recorded here — the SENDER records the ``handoff`` hop when the
        dispatch acks, and no token is emitted — the first token rode
        the payload and was already pushed by the prefill pool."""
        for slot, stream, _pos, _tok, pk, pv in imports:
            self.engine.import_pages(slot, pk, pv,
                                     request_ids=[stream.rid])
        self._update_kv_gauge()

    def retire(self) -> List[DecodeStream]:
        """Stop this worker WITHOUT failing its streams: detach every
        reservation and hand back live + waiting + queued-handoff
        streams.  The pool-resplit path
        (:meth:`DisaggDecodeRouter.set_prefill_share`) re-homes them
        through the front door — a live stream re-prefills ``prompt +
        emitted`` elsewhere, and greedy determinism keeps its remaining
        tokens identical."""
        with self._lock:
            self._stop = True
            self._wake.notify_all()
        if self._worker is not None:
            self._worker.join(timeout=30)
            self._worker = None
        leftovers: List[DecodeStream] = []
        with self._lock:
            leftovers += list(self._waiting)
            leftovers += [h[0] for h in self._handoffs]
            still_live = [i for i, sl in enumerate(self._slots)
                          if sl is not None]
            leftovers += [self._slots[i].stream for i in still_live]
            self._waiting.clear()
            self._handoffs.clear()
            self._slots = [None] * self.engine.slots
            self._free = list(range(self.engine.slots))
        for i in still_live:
            self.engine.detach_slot(i)
            if self.drafter is not None:
                self.drafter.detach_slot(i)
        return leftovers

    def _prefill(self, claims: List[tuple]) -> None:
        """Prefill claimed streams and emit each stream's FIRST token.

        Claims split three ways by prefix-hit kind: **full** hits run NO
        forward at all — the index stored the prompt's first greedy
        token, so it is emitted right here (``prefills_total`` does not
        move: a zero-prefill full hit is structural,
        ``tests/test_kvpage.py``); **partial** hits forward only the
        divergent suffix (:meth:`PagedDecodeEngine.prefill_chunk`);
        **cold** claims take the classic bucketed prefill, chunked to the
        engine's fixed prefill rows.  Every stream still records a ``prefill`` hop —
        the chain contract (no ``decode`` before ``prefill``) holds for
        hits too, with ``prefix_hit``/``cached_tokens`` telling the
        story."""
        rows = self.engine.prefill_rows
        if self.drafter is not None:
            # the drafter's cache needs the SAME prompt K/V before it
            # can draft: always the cold path (prefix_share is off on
            # drafter engines), chunked to the drafter's fixed rows,
            # then the reservation's uncommitted tail moves to the
            # draft owner.  Any failure here degrades the pair to
            # primary-only decode — the primary prefill below still
            # seats every stream.
            try:
                if self._drafter_poison is not None:
                    raise self._drafter_poison
                rows_d = self.drafter.prefill_rows
                for i in range(0, len(claims), rows_d):
                    ch = claims[i:i + rows_d]
                    self.drafter.prefill_ids(
                        [s.prompt_ids + s.emitted for _, s, _ in ch],
                        [slot for slot, _, _ in ch],
                        request_ids=[s.rid for _, s, _ in ch])
                    for slot, s, _ in ch:
                        self.drafter.split_draft_custody(
                            slot, len(s.prompt_ids) + len(s.emitted))
            except BaseException as e:  # noqa: BLE001
                self._degrade_drafter(e)
        full = [c for c in claims if c[2].kind == "full"]
        part = [c for c in claims if c[2].kind == "partial"]
        cold = [c for c in claims if c[2].kind == "cold"]
        tr, attrs = self.tracer, self.engine.span_attrs
        if full:
            # no engine call to follow: the index stored the first token
            with tr.leaf("prefill.emit", attrs) as sp:
                # the index entry's LRU standing is refreshed with it
                # (register of an existing key is a touch, not a re-insert)
                self._first_tokens(
                    [(slot, stream, int(c.first_token), len(c.tokens),
                      {"tokens_in": len(c.tokens), "prefix_hit": "full",
                       "cached_tokens": len(c.tokens)})
                     for slot, stream, c in full], bool(sp))
                if sp:
                    sp.set(rows=len(full), prefix_hit="full")
        for i in range(0, len(cold), rows):
            chunk = cold[i:i + rows]
            prompts = [s.prompt_ids + s.emitted for _, s, _ in chunk]
            first = self.engine.prefill_ids(
                prompts, [slot for slot, _, _ in chunk],
                request_ids=[s.rid for _, s, _ in chunk])
            with tr.leaf("prefill.emit", attrs) as sp:
                self.metrics.prefills_total.inc()
                self.metrics.prefill_tokens_total.inc(
                    sum(len(p) for p in prompts))
                toks = chosen_ids(first)
                self._first_tokens(
                    [(slot, stream, toks[j], len(prompts[j]),
                      {"tokens_in": len(prompts[j]), "prefix_hit": "miss"})
                     for j, (slot, stream, _) in enumerate(chunk)],
                    bool(sp))
                if sp:
                    sp.set(rows=len(chunk))
        for i in range(0, len(part), rows):
            chunk = part[i:i + rows]
            suffixes = [c.suffix for _, _, c in chunk]
            first = self.engine.prefill_chunk(
                suffixes, [slot for slot, _, _ in chunk],
                [c.start for _, _, c in chunk],
                request_ids=[s.rid for _, s, _ in chunk])
            with tr.leaf("chunk.emit", attrs) as sp:
                self.metrics.prefills_total.inc()
                self.metrics.prefill_tokens_total.inc(
                    sum(len(x) for x in suffixes))
                toks = chosen_ids(first)
                self._first_tokens(
                    [(slot, stream, toks[j], len(c.tokens),
                      {"tokens_in": len(suffixes[j]),
                       "prefix_hit": "partial", "cached_tokens": c.start})
                     for j, (slot, stream, c) in enumerate(chunk)],
                    bool(sp))
                if sp:
                    sp.set(rows=len(chunk))

    def _first_tokens(self, firsts: List[tuple], traced: bool) -> None:
        """The first token of each freshly prefilled stream — ``(slot,
        stream, token, pos, prefill-hop attrs)`` a row: the ``prefill`` hop,
        the time to first token, the prompt's entry in the prefix index,
        then the block's advance.  ``traced``: the emit leaf is recording,
        so each stream is owed its ``request`` record."""
        tr = self.tracer
        now = tr.now()
        for slot, stream, tok, _pos, hop in firsts:
            if tr.enabled:
                record_hop(tr, stream.rid, "prefill", slot=slot,
                           replica=self.replica, **hop)
            self.metrics.ttft_ms.observe((now - stream.born) * 1e3)
            self.engine.register_slot(slot, tok)
            if traced:
                stream.traced = True
        self._advance_rows([f[:4] for f in firsts], now)

    def _advance(self, slot: int, stream: DecodeStream, tok: int, *,
                 pos: int) -> None:
        """One row's advance (the speculative round emits a row's accepted
        tokens one after another): :meth:`_advance_rows` of a block of
        one."""
        self._advance_rows([(slot, stream, tok, pos)], self.tracer.now())

    def _advance_rows(self, rows: List[tuple], now: float) -> None:
        """Apply a block of newly produced tokens — ``(slot, stream, token,
        pos)`` a row, ``pos`` the write position the NEXT decode step would
        use — in one pass: emit each token (or take the EOS/stop decision),
        then keep the row live with the token as its next decode input, or
        finish the stream and free its slot.  ``now`` stamps every token of
        the block (one clock read a step).  The slot table is touched under
        ONE acquisition of the lock; pushes to streams stay outside it."""
        eos, max_len = self.eos_id, self.engine.max_len
        keep: List[tuple] = []
        done: List[tuple] = []
        gaps: List[float] = []
        pushed = 0
        for row in rows:
            _, stream, tok, pos = row
            n = len(stream.emitted)
            if tok == eos or n >= stream.max_new_tokens:
                done.append(row)  # EOS is a stop decision, not an emission
                continue
            gap = stream._push(tok, now)
            pushed += 1
            if gap > 0.0:
                gaps.append(gap * 1e3)
            if n + 1 >= stream.max_new_tokens or pos >= max_len:
                done.append(row)
            else:
                keep.append(row)
        if gaps:
            self.metrics.intertoken_ms.observe_many(gaps)
        if pushed:
            self.metrics.tokens_out_total.inc(pushed)
        with self._lock:
            for slot, _, tok, pos in keep:
                sl = self._slots[slot]
                sl.pos = pos
                sl.next_token = tok
            if done:
                freed = time.monotonic()
                for slot, _, _, _ in done:
                    self._slots[slot] = None
                    heapq.heappush(self._free, slot)
                    self._freed_at[slot] = freed
            live_tokens, live_slots = self._kv_live_locked()
        for slot, stream, _, _ in done:
            # release the stream's pages (refcount decrement — shared
            # prefix pages stay live under the index / other streams);
            # worker-only, so after the lock is fine
            self.engine.detach_slot(slot)
            if self.drafter is not None:
                self.drafter.detach_slot(slot)  # draft custody included
            if stream._finish():
                record_hop(self.tracer, stream.rid, "complete",
                           replica=self.replica, slot=slot,
                           tokens_out=len(stream.emitted))
                record_request(self.tracer, stream, self.replica)
        self._set_kv_gauge(live_tokens, live_slots)

    def _decode_step(self) -> None:
        """ONE fixed-shape decode step over the slot block; live rows
        advance their streams, dead rows ride as junk.  What comes back is
        the token each row chose on the device (:func:`chosen_ids`)."""
        eng, tr = self.engine, self.tracer
        tokens = np.zeros((eng.slots,), np.int32)
        pos = np.zeros((eng.slots,), np.int32)
        with self._lock:
            live = [(i, sl) for i, sl in enumerate(self._slots)
                    if sl is not None]
        if not live:
            return
        # pos / next_token of a live row move on this thread alone
        idx = [i for i, _ in live]
        tokens[idx] = [sl.next_token for _, sl in live]
        pos[idx] = [sl.pos for _, sl in live]
        result = eng.decode_batch(
            tokens, pos, live=len(live),
            request_ids=[sl.stream.rid for _, sl in live]
            if tr.recording else None)
        with tr.leaf("decode.emit", eng.span_attrs) as sp:
            toks = chosen_ids(result)
            self.metrics.decode_steps_total.inc()
            self.rmetrics.slot_occupancy.observe(
                len(live) / float(eng.slots))
            self.rmetrics.batches_total.inc()
            if tr.enabled:
                # hops BEFORE the advance so a completing stream's terminal
                # stays last; tokens_out = cumulative emissions including
                # this step (EOS is a stop decision, not an emission)
                for i, sl in live:
                    emitted = len(sl.stream.emitted)
                    record_hop(tr, sl.stream.rid, "decode", slot=i,
                               step=emitted,
                               tokens_out=emitted + (toks[i] != self.eos_id),
                               replica=self.replica)
            self._advance_rows(
                [(i, sl.stream, toks[i], sl.pos + 1) for i, sl in live],
                tr.now())
            if sp:
                sp.set(rows=len(live))

    # ------------------------------------------------------- speculation
    def _speculate_step(self) -> None:
        """One draft-k / verify-1 round over the slot block.

        The drafter runs k FIXED-shape decode steps against its own
        paged cache (feeding each argmax back in — the classic decode
        loop, just on the cheap model), then the primary scores the
        whole ``[slots, k+1]`` window ``[pending, draft_1..draft_k]`` in
        ONE :meth:`PagedDecodeEngine.verify_ids` call.  Row ``i``'s
        greedy targets ``t_0..t_k`` satisfy: ``t_j`` is the primary's
        next token after window position ``j``.  The longest prefix with
        ``draft_j == t_{j-1}`` (length ``a``) is accepted, and the round
        emits ``t_0..t_a`` — a+1 tokens, every one a PRIMARY argmax, so
        the emitted sequence is identical to primary-only greedy decode
        whatever the drafter says (worst case a=0 still emits ``t_0``,
        the plain decode step's token).  The verify call already wrote
        the accepted positions' K/V (primary commit); the drafter's
        boundary-crossed pages transfer to the stream owner
        (:meth:`PagedDecodeEngine.commit_draft`) and its rejected tail
        is overwritten in place next round.  A drafter failure anywhere
        degrades to :meth:`_decode_step` — loudly, decision-recorded —
        and the round re-runs primary-only."""
        k = self.draft_k
        eng, dr = self.engine, self.drafter
        tokens = np.zeros((eng.slots,), np.int32)
        pos = np.zeros((eng.slots,), np.int32)
        with self._lock:
            live = [(i, sl) for i, sl in enumerate(self._slots)
                    if sl is not None]
            for i, sl in live:
                tokens[i] = sl.next_token
                pos[i] = sl.pos
        if not live:
            return
        rids = [sl.stream.rid for _, sl in live]
        window = np.zeros((eng.slots, k + 1), np.int32)
        window[:, 0] = tokens
        try:
            if self._drafter_poison is not None:
                raise self._drafter_poison
            cur = tokens.copy()
            for j in range(k):
                dlogits = dr.decode_batch(cur, pos + j, live=len(live),
                                          request_ids=rids)
                # a launch answers for the rows below ITS row rung, every
                # live row among them; the rest draft token 0 unread
                cur = np.zeros_like(tokens)
                cur[:len(dlogits)] = np.argmax(dlogits, axis=-1)
                window[:, j + 1] = cur
        except BaseException as e:  # noqa: BLE001 — drafter death must
            self._degrade_drafter(e)  # never take the primary with it
            self._decode_step()
            return
        self.metrics.draft_tokens_total.inc(k * len(live))
        self.metrics.spec_rounds_total.inc()
        self._spec_rounds += 1
        nreal = np.zeros((eng.slots,), np.int32)
        for i, _ in live:
            nreal[i] = k + 1
        vlogits = eng.verify_ids(window, pos, nreal, live=len(live),
                                 request_ids=rids)
        self.metrics.verify_calls_total.inc()
        self.metrics.decode_steps_total.inc()
        targets = np.argmax(vlogits, axis=-1)        # [slots, k+1]
        self.rmetrics.slot_occupancy.observe(
            len(live) / float(eng.slots))
        self.rmetrics.batches_total.inc()
        for i, sl in live:
            a = 0
            while a < k and window[i, a + 1] == targets[i, a]:
                a += 1
            stream = sl.stream
            stream.spec_accepted += a
            self._spec_drafted += k
            self._spec_accepted += a
            self.metrics.accepted_tokens_total.inc(a)
            # hops BEFORE advancing, so a completing stream's terminal
            # stays last; accepted is CUMULATIVE per stream (the chain
            # rule pins it monotone)
            record_hop(self.tracer, stream.rid, "draft", slot=i, k=k,
                       drafter_model=self.drafter_model,
                       replica=self.replica)
            record_hop(self.tracer, stream.rid, "verify", slot=i, k=k,
                       matched=a, accepted=stream.spec_accepted,
                       replica=self.replica)
            base = sl.pos
            for m in range(a + 1):
                self._advance(i, stream, int(targets[i, m]),
                              pos=base + m + 1)
                with self._lock:
                    freed = self._slots[i] is None
                if freed:
                    break
            else:
                # stream survived the round: its committed cache length
                # is the new pending write position — move any
                # boundary-crossed draft pages to the stream owner
                dr.commit_draft(i, base + a + 1)
        if self._spec_drafted:
            self.metrics.accept_rate.set(
                self._spec_accepted / float(self._spec_drafted))
        self._update_kv_gauge()

    def _degrade_drafter(self, error: BaseException) -> None:
        """Drafter death mid-storm: degrade the pair to primary-only
        decode — LOUD, decision-recorded, streams keep flowing.  Parity
        is unaffected: the primary cache holds every committed token, so
        plain decode continues the exact greedy sequence.  Worker-only
        (like every engine call); must NOT be called with ``_lock``
        held."""
        dr, k_old = self.drafter, self.draft_k
        if dr is None:
            return
        self.drafter = None
        self._drafter_poison = None
        print(f"[serve.decode] replica {self.replica}: drafter "
              f"{self.drafter_model!r} died "
              f"({type(error).__name__}: {error}) — degrading to "
              "primary-only decode", file=sys.stderr)
        self.metrics.drafter_deaths_total.inc()
        did = mint_decision_id()
        record_decision(self.tracer, did, "action", knob="draft_k",
                        old=k_old, new=0, forced=True,
                        replica=self.replica,
                        cause={"kind": "drafter_death",
                               "error": type(error).__name__,
                               "drafter_model": self.drafter_model})
        record_decision(self.tracer, did, "outcome", knob="draft_k",
                        result="degraded", kept=True,
                        replica=self.replica)
        with self._lock:
            live = [i for i, sl in enumerate(self._slots)
                    if sl is not None]
        for i in live:
            try:
                dr.detach_slot(i)  # draft custody released with it
            except BaseException:  # noqa: BLE001 — best-effort: the
                pass               # engine may be the thing that died

    def kill_drafter(self, error: Optional[BaseException] = None) -> None:
        """Chaos hook (tests): the next
        speculation round sees the drafter raise — exactly the path a
        real drafter engine failure takes."""
        self._drafter_poison = error or RuntimeError(
            "injected drafter kill")

    def set_draft_k(self, k: int) -> int:
        """Actuate the ``draft_k`` knob (controller/router door): clamp
        into the declared safe range and apply before the next round.
        ``0`` pauses speculation (plain decode steps; the drafter cache
        goes stale, so acceptance restarts low if re-enabled — the
        controller's revert law owns that call).  A new k's verify
        width compiles exactly once."""
        k = max(0, min(int(k), self.DRAFT_K_MAX))
        with self._lock:
            self.draft_k = k
        return k

    def spec_snapshot(self) -> Dict:
        """Speculation accounting for ``control_snapshot``/``healthz``:
        configured k, live acceptance, and the per-model split the
        exporter renders with ``{model=...}`` labels."""
        drafted, accepted = self._spec_drafted, self._spec_accepted
        rate = accepted / float(drafted) if drafted else 0.0
        out = {
            "enabled": int(self.drafter is not None),
            "draft_k": int(self.draft_k),
            "draft_tokens": int(drafted),
            "accepted_tokens": int(accepted),
            "accept_rate": rate,
            "rounds": int(self._spec_rounds),
        }
        if self.drafter_model:
            primary = str(getattr(self.engine.args, "model", "primary"))
            # a same-architecture drafter (distilled checkpoint) shares
            # the primary's model name — suffix its label so the two
            # Prometheus series never collapse into one
            dm = self.drafter_model if self.drafter_model != primary \
                else self.drafter_model + "-draft"
            out["by_model"] = {
                dm: {"draft_tokens": int(drafted), "role": "drafter"},
                primary: {"accepted_tokens": int(accepted),
                          "accept_rate": rate},
            }
        return out

    def _kv_live_locked(self) -> tuple:
        """(cached positions, live rows) of the slot table; caller holds
        ``_lock``."""
        held = [sl.pos for sl in self._slots if sl is not None]
        return sum(held), len(held)

    def _update_kv_gauge(self) -> None:
        with self._lock:
            live_tokens, live_slots = self._kv_live_locked()
        self._set_kv_gauge(live_tokens, live_slots)

    def _set_kv_gauge(self, live_tokens: int, live_slots: int) -> None:
        nbytes = live_tokens * self.engine.token_bytes
        self.engine.budget.set_live(nbytes)
        self.metrics.kv_bytes_live.set(nbytes)
        self.metrics.kv_slots_live.set(live_slots)
        if live_slots > self._peak_live:
            self._peak_live = live_slots
            self.metrics.peak_live_streams.set(live_slots)
        alloc = self.engine.allocator
        self.metrics.kv_pages_live.set(alloc.used_pages)
        self.metrics.kv_pages_free.set(alloc.free_pages)

    def _die(self, error: BaseException) -> None:
        """Worker death: collect every stream this replica owes an answer
        (live slots + waiting) and hand them to the router — or fail them
        loudly when there is no router to re-home onto."""
        with self._lock:
            self.dead = True
            orphans = [sl.stream for sl in self._slots if sl is not None]
            orphans += [h[0] for h in self._handoffs]
            orphans += list(self._waiting)
            self._waiting.clear()
            self._handoffs.clear()
            self._slots = [None] * self.engine.slots
            self._free = list(range(self.engine.slots))
            self.rmetrics.ejections.inc()
            self._wake.notify_all()
        if self.on_death is not None:
            self.on_death(self.replica, orphans, error)
        else:
            for s in orphans:
                if s._finish(error):
                    record_hop(self.tracer, s.rid, "failed",
                               error=type(error).__name__)

    # ------------------------------------------------------------ surface
    def warmup(self) -> None:
        self.engine.warmup_decode()
        if self.drafter is not None:
            # drafter decode + the primary's verify width: after this,
            # a full speculation round compiles nothing
            self.drafter.warmup_decode()
            self.engine.warmup_verify(self.draft_k + 1)

    def snapshot(self) -> Dict:
        out = {
            "decode": self.metrics.snapshot(),
            "replica": self.rmetrics.snapshot(),
            "kv": self.engine.kv_snapshot(),
            "engine": self.engine.metrics.snapshot(),
            # where this worker's last seconds of rounds went, profiler
            # or not
            "rounds": recent_round_account(self.tracer, self.replica),
        }
        if self.drafter is not None or self._spec_rounds:
            out["speculation"] = self.spec_snapshot()
            if self.drafter is not None:
                out["drafter"] = {
                    "model": self.drafter_model,
                    "kv": self.drafter.kv_snapshot(),
                    "engine": self.drafter.metrics.snapshot(),
                }
        return out


class PrefillWorker:
    """Prefill-role half of a disaggregated pool: one worker owns one
    engine and runs ONLY the prefill phase — bucketed cold
    forwards, prefix full/partial hits, chunked suffixes — then moves
    each stream's pages to a decode-role engine through the KV handoff.
    Decode-role engines never see a prefill after warmup, so a prefill
    burst cannot steal inter-token latency from live streams (the
    disaggregation argument: the two phases have opposite compute
    profiles, DistServe OSDI'24 / Splitwise ISCA'24).

    Custody per handoff, in order: **export** (fixed-shape page gather
    to a host payload) → **stage** (:meth:`PagedDecodeEngine.
    begin_handoff` — the page refs move to the staging owner and the
    slot frees for the next prompt) → **dispatch** (the router
    callback: local seat or socket frame + ack) → **release** the
    staged owner — exactly ONE discharge point whatever the outcome,
    so both allocators' ``leak_check`` reconcile to zero after drain.
    A failed dispatch re-queues the stream for re-prefill (the payload
    is disposable: ``prompt + emitted`` regenerates it bitwise).

    A stream whose FIRST token already finishes it (EOS, budget 1)
    completes right here and never hands off — same ``complete``
    semantics as the interleaved batcher's prefill-time finish."""

    def __init__(self, engine: PagedDecodeEngine, *,
                 dispatch: Callable, max_waiting: int = 256,
                 default_max_new: Optional[int] = None, replica: int = 0,
                 on_death: Optional[Callable] = None,
                 rmetrics: Optional[ReplicaMetrics] = None,
                 dmetrics: Optional[DecodeMetrics] = None):
        engine.require_handoff()
        self.engine = engine
        self.tracer = engine.tracer
        self.replica = int(replica)
        engine.span_attrs.setdefault("replica", self.replica)
        engine.span_attrs["pool"] = "prefill"
        self.dispatch = dispatch
        self.max_waiting = int(max_waiting)
        self.default_max_new = int(
            default_max_new
            or getattr(engine.args, "max_new_tokens", 32))
        self.eos_id = engine.tokenizer.sep_id
        self.on_death = on_death
        self.metrics = dmetrics or DecodeMetrics()
        self.rmetrics = rmetrics or ReplicaMetrics()
        self._slots: List[Optional[_Slot]] = [None] * engine.slots
        self._free: deque = deque(range(engine.slots))
        self._freed_at: Dict[int, float] = {}
        self._waiting: deque = deque()
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._stop = False
        self._poison: Optional[BaseException] = None
        self.dead = False
        self._worker: Optional[threading.Thread] = None
        self._peak_live = 0

    # ----------------------------------------------------------- lifecycle
    def start(self) -> "PrefillWorker":
        if self._worker is None and not self.dead:
            self._stop = False
            self._worker = threading.Thread(
                target=self._run, daemon=True,
                name=f"pdnlp-prefill-{self.replica}")
            self._worker.start()
        return self

    def stop(self, drain: bool = True) -> None:
        if self._worker is None:
            return
        if drain:
            with self._lock:
                while (not self.dead and not self._stop
                       and (self._waiting or self._live_count())):
                    self._wake.wait(timeout=0.05)
        with self._lock:
            self._stop = True
            self._wake.notify_all()
        self._worker.join(timeout=30)
        self._worker = None
        leftovers = []
        with self._lock:
            leftovers += list(self._waiting)
            still_live = [i for i, sl in enumerate(self._slots)
                          if sl is not None]
            leftovers += [self._slots[i].stream for i in still_live]
            self._waiting.clear()
            self._slots = [None] * self.engine.slots
            self._free = deque(range(self.engine.slots))
        for i in still_live:
            self.engine.detach_slot(i)
        for s in leftovers:
            if s._finish(RuntimeError("prefill worker stopped")):
                record_hop(self.tracer, s.rid, "failed",
                           error="worker stopped")

    def retire(self) -> List[DecodeStream]:
        """Stop WITHOUT failing streams (pool re-split): detach every
        reservation and hand back waiting + mid-prefill streams for the
        router to re-home through the front door."""
        with self._lock:
            self._stop = True
            self._wake.notify_all()
        if self._worker is not None:
            self._worker.join(timeout=30)
            self._worker = None
        leftovers: List[DecodeStream] = []
        with self._lock:
            leftovers += list(self._waiting)
            still_live = [i for i, sl in enumerate(self._slots)
                          if sl is not None]
            leftovers += [self._slots[i].stream for i in still_live]
            self._waiting.clear()
            self._slots = [None] * self.engine.slots
            self._free = deque(range(self.engine.slots))
        for i in still_live:
            self.engine.detach_slot(i)
        return leftovers

    def __enter__(self) -> "PrefillWorker":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def kill(self, error: Optional[BaseException] = None) -> None:
        """Chaos hook: the worker raises before its next batch."""
        with self._lock:
            self._poison = error or RuntimeError("injected replica kill")
            self._wake.notify_all()

    # ------------------------------------------------------------- submit
    def _live_count(self) -> int:
        return sum(1 for sl in self._slots if sl is not None)

    @property
    def load(self) -> int:
        with self._lock:
            return self._live_count() + len(self._waiting)

    def submit_ids(self, ids: Sequence[int],
                   max_new_tokens: Optional[int] = None,
                   deadline_ms: Optional[float] = None) -> DecodeStream:
        """Admit one generative stream (the disaggregated front door —
        same typed refusals as :meth:`DecodeBatcher.submit_ids`)."""
        ids = list(ids)
        if not ids:
            raise ValueError("empty prompt: submit at least one token id")
        max_new = int(self.default_max_new if max_new_tokens is None
                      else max_new_tokens)
        deadline = (time.monotonic() + deadline_ms / 1e3
                    if deadline_ms is not None else None)
        tr = self.tracer
        stream = DecodeStream(ids, max_new, deadline, clock=tr.now)
        try:
            self.engine.check_stream_admissible(len(ids), max_new)
        except BaseException as e:
            self.metrics.rejected_total.inc()
            record_hop(tr, stream.rid, "rejected",
                       reason=type(e).__name__)
            raise
        peek = self.engine.peek_prefix(ids)
        extra = {} if peek is None else {"prefix_hit": peek}
        with self._lock:
            if self.dead or self._stop or self._worker is None:
                raise RuntimeError("prefill worker is not running")
            if len(self._waiting) >= self.max_waiting:
                self.metrics.rejected_total.inc()
                record_hop(tr, stream.rid, "rejected")
                raise QueueFullError(
                    f"prefill queue full ({len(self._waiting)}"
                    f"/{self.max_waiting} waiting streams)")
            stream.replica = self.replica
            self._waiting.append(stream)
            self.metrics.streams_total.inc()
            self.metrics.waiting.set(len(self._waiting))
            record_hop(tr, stream.rid, "admit", streamed=True,
                       tokens=len(ids), max_new=max_new,
                       replica=self.replica, pool="prefill", **extra)
            self._wake.notify()
        return stream

    def _adopt(self, stream: DecodeStream) -> bool:
        """Router re-home (replica death / pool re-split): enqueue an
        orphan's continuation — ``prompt + emitted`` re-prefills here
        and hands off again; greedy determinism keeps the remaining
        tokens identical.  Bypasses admission."""
        with self._lock:
            if self.dead or self._stop or self._worker is None:
                return False
            stream.replica = self.replica
            self._waiting.append(stream)
            self.metrics.waiting.set(len(self._waiting))
            self.rmetrics.requeued_in.inc()
            self._wake.notify()
        return True

    # ------------------------------------------------------------- worker
    def _run(self) -> None:
        """The prefill pool's round: claim one group → prefill → stage and
        dispatch.  A row of the tracer's ring of rounds like the
        interleaved batcher's (``Tracer.open_round``), with the engine
        calls' leaves; its seating, exports and dispatches have none and
        read as the row's ``other``."""
        tr = self.tracer
        rnd = 0
        try:
            while True:
                claims: List[tuple] = []
                rnd += 1
                tr.open_round(self.replica, rnd)
                with self._lock:
                    if self._poison is not None:
                        raise self._poison
                    if self._stop:
                        return
                    self._expire_waiting_locked()
                    # at most ONE prefill group per iteration: claiming
                    # every free slot would serialize several prefill
                    # forwards ahead of _dispatch_all, and an earlier
                    # group's staged streams would sit undispatched —
                    # their first decode-pool gap eating a later group's
                    # prefill cost (the stall disaggregation deletes)
                    rows = self.engine.prefill_rows
                    while self._free and self._waiting \
                            and len(claims) < rows:
                        slot = self._free.popleft()
                        stream = self._waiting.popleft()
                        try:
                            claim = self.engine.attach_stream(slot,
                                                              stream)
                        except KVPagesExhausted:
                            # retry as in-flight handoffs release their
                            # staged pages (same iteration, below) —
                            # the pool floor argument the interleaved
                            # batcher makes, on the staging ledger
                            self._free.appendleft(slot)
                            self._waiting.appendleft(stream)
                            break
                        freed = self._freed_at.pop(slot, None)
                        if freed is not None:
                            self.rmetrics.slot_reuse_ms.observe(
                                (time.monotonic() - freed) * 1e3)
                        stream.slot = slot
                        self._slots[slot] = _Slot(stream, 0, 0)
                        claims.append((slot, stream, claim))
                    self.metrics.waiting.set(len(self._waiting))
                    live = self._live_count()
                    if live > self._peak_live:
                        self._peak_live = live
                        self.metrics.peak_live_streams.set(live)
                    if not claims:
                        if self._stop:
                            return
                        tr.drop_round()     # an idle wait is no round
                        rnd -= 1
                        self._wake.notify_all()  # unblock stop(drain)
                        self._wake.wait(timeout=0.05)
                        continue
                self._prefill(claims)  # dispatches per staged stream
                with self._lock:
                    self._wake.notify_all()
                tr.close_round(live, len(claims))
        except BaseException as e:  # noqa: BLE001 — a dead engine must
            self._die(e)           # never strand callers or streams
        finally:
            tr.drop_round()     # stopped or dead inside one: no half row

    def _expire_waiting_locked(self) -> None:
        now = time.monotonic()
        keep: deque = deque()
        for s in self._waiting:
            if s.deadline is not None and now >= s.deadline:
                self.metrics.deadline_expired_total.inc()
                if s._finish(DeadlineExceeded(
                        "deadline passed while waiting for a slot")):
                    record_hop(self.tracer, s.rid, "deadline")
            else:
                keep.append(s)
        self._waiting = keep

    def _prefill(self, claims: List[tuple]) -> None:
        """Prefill the claimed batch (full/partial/cold — the
        interleaved batcher's exact three-way split), STAGE every
        surviving stream for handoff, and dispatch each the moment its
        export lands: a staged payload held back while a LATER stream's
        prefill forward runs would charge that forward to the earlier
        stream's first decode-pool gap — the exact stall the pool split
        exists to delete."""
        rows = self.engine.prefill_rows
        full = [c for c in claims if c[2].kind == "full"]
        part = [c for c in claims if c[2].kind == "partial"]
        cold = [c for c in claims if c[2].kind == "cold"]
        now = self.tracer.now()
        for slot, stream, claim in full:
            ntok = len(claim.tokens)
            record_hop(self.tracer, stream.rid, "prefill", slot=slot,
                       tokens_in=ntok, replica=self.replica,
                       prefix_hit="full", cached_tokens=ntok)
            self.metrics.ttft_ms.observe((now - stream.born) * 1e3)
            self.engine.register_slot(slot, claim.first_token)
            h = self._emit_first(slot, stream, int(claim.first_token),
                                 pos=ntok)
            if h is not None:
                self._dispatch_all([h])
        for i in range(0, len(cold), rows):
            chunk = cold[i:i + rows]
            prompts = [s.prompt_ids + s.emitted for _, s, _ in chunk]
            logits = self.engine.prefill_ids(
                prompts, [slot for slot, _, _ in chunk],
                request_ids=[s.rid for _, s, _ in chunk])
            self.metrics.prefills_total.inc()
            self.metrics.prefill_tokens_total.inc(
                sum(len(p) for p in prompts))
            now = self.tracer.now()
            for j, (slot, stream, claim) in enumerate(chunk):
                record_hop(self.tracer, stream.rid, "prefill",
                           slot=slot, tokens_in=len(prompts[j]),
                           replica=self.replica, prefix_hit="miss")
                self.metrics.ttft_ms.observe((now - stream.born) * 1e3)
                tok = int(np.argmax(logits[j]))
                self.engine.register_slot(slot, tok)
                h = self._emit_first(slot, stream, tok,
                                     pos=len(prompts[j]))
                if h is not None:
                    self._dispatch_all([h])
        for i in range(0, len(part), rows):
            chunk = part[i:i + rows]
            suffixes = [c.suffix for _, _, c in chunk]
            logits = self.engine.prefill_chunk(
                suffixes, [slot for slot, _, _ in chunk],
                [c.start for _, _, c in chunk],
                request_ids=[s.rid for _, s, _ in chunk])
            self.metrics.prefills_total.inc()
            self.metrics.prefill_tokens_total.inc(
                sum(len(x) for x in suffixes))
            now = self.tracer.now()
            for j, (slot, stream, claim) in enumerate(chunk):
                record_hop(self.tracer, stream.rid, "prefill",
                           slot=slot, tokens_in=len(suffixes[j]),
                           replica=self.replica, prefix_hit="partial",
                           cached_tokens=claim.start)
                self.metrics.ttft_ms.observe((now - stream.born) * 1e3)
                tok = int(np.argmax(logits[j]))
                self.engine.register_slot(slot, tok)
                h = self._emit_first(slot, stream, tok,
                                     pos=len(claim.tokens))
                if h is not None:
                    self._dispatch_all([h])
        self._update_kv_gauge()

    def _emit_first(self, slot: int, stream: DecodeStream, tok: int, *,
                    pos: int) -> Optional[tuple]:
        """Emit (or stop on) the prefill's first token.  A stream that
        completes AT prefill never hands off; every other stream
        exports its payload, stages custody, frees the slot, and
        returns the handoff tuple for :meth:`_dispatch_all`."""
        remaining = stream.max_new_tokens - len(stream.emitted)
        finish = False
        if tok == self.eos_id or remaining <= 0:
            finish = True       # EOS is a stop decision, not an emission
        else:
            stream._push(tok)   # first token: ttft already observed
            self.metrics.tokens_out_total.inc()
            if (len(stream.emitted) >= stream.max_new_tokens
                    or pos >= self.engine.max_len):
                finish = True
        if finish:
            with self._lock:
                self._slots[slot] = None
                self._free.append(slot)
                self._freed_at[slot] = time.monotonic()
            self.engine.detach_slot(slot)
            if stream._finish():
                record_hop(self.tracer, stream.rid, "complete",
                           replica=self.replica, slot=slot,
                           tokens_out=len(stream.emitted))
            return None
        pk, pv = self.engine.export_pages(slot,
                                          request_ids=[stream.rid])
        staged, pages = self.engine.begin_handoff(slot)
        with self._lock:
            self._slots[slot] = None
            self._free.append(slot)
            self._freed_at[slot] = time.monotonic()
        return (stream, pos, tok, staged, pages, pk, pv)

    def _dispatch_all(self, handoffs: List[tuple]) -> None:
        """Move each staged payload to a decode engine via the router's
        dispatch callback and settle its custody: the staged owner is
        released at exactly ONE point whatever happened (the payload is
        self-contained once exported; a failed dispatch regenerates it
        by re-prefill).  The ``handoff`` hop is recorded by the
        dispatcher per placement attempt (before the seat — the
        requeue-hop ordering precedent), so only metrics land here."""
        alloc = self.engine.allocator
        for stream, pos, tok, staged, pages, pk, pv in handoffs:
            t0 = time.monotonic()
            meta = {"rid": stream.rid, "pos": int(pos),
                    "next_token": int(tok),
                    "prompt_len": len(stream.prompt_ids),
                    "n_pages": len(pages)}
            placed = None
            try:
                placed = self.dispatch(stream, meta, pk, pv)
            except BaseException:  # noqa: BLE001 — a dispatch crash is
                placed = None      # a failed placement, not worker death
            finally:
                alloc.release_owner(staged)
            if placed is None:
                self.metrics.handoff_failures_total.inc()
                with self._lock:
                    if self._stop or self.dead:
                        lost = stream
                    else:
                        lost = None
                        self._waiting.appendleft(stream)  # re-prefill
                if lost is not None and lost._finish(RuntimeError(
                        "handoff dispatch failed")):
                    record_hop(self.tracer, lost.rid, "failed",
                               error="handoff dispatch failed")
                continue
            nbytes = int(pk.nbytes) + int(pv.nbytes)
            self.metrics.handoffs_total.inc()
            self.metrics.handoff_pages_total.inc(len(pages))
            self.metrics.handoff_bytes_total.inc(nbytes)
            self.metrics.handoff_ms.observe(
                (time.monotonic() - t0) * 1e3)

    def _update_kv_gauge(self) -> None:
        with self._lock:
            live_slots = self._live_count()
        self.metrics.kv_slots_live.set(live_slots)
        alloc = self.engine.allocator
        self.metrics.kv_pages_live.set(alloc.used_pages)
        self.metrics.kv_pages_free.set(alloc.free_pages)

    def _die(self, error: BaseException) -> None:
        with self._lock:
            self.dead = True
            orphans = [sl.stream for sl in self._slots
                       if sl is not None]
            orphans += list(self._waiting)
            self._waiting.clear()
            self._slots = [None] * self.engine.slots
            self._free = deque(range(self.engine.slots))
            self.rmetrics.ejections.inc()
            self._wake.notify_all()
        if self.on_death is not None:
            self.on_death(self.replica, orphans, error)
        else:
            for s in orphans:
                if s._finish(error):
                    record_hop(self.tracer, s.rid, "failed",
                               error=type(error).__name__)

    # ------------------------------------------------------------ surface
    def warmup(self) -> None:
        self.engine.warmup_decode()
        self.engine.warmup_handoff()

    def snapshot(self) -> Dict:
        return {
            "pool": "prefill",
            "decode": self.metrics.snapshot(),
            "replica": self.rmetrics.snapshot(),
            "kv": self.engine.kv_snapshot(),
            "engine": self.engine.metrics.snapshot(),
            "rounds": recent_round_account(self.tracer, self.replica),
        }


class DecodeRouter:
    """N decode engines behind one door: least-loaded stream placement,
    and on a replica death the orphan streams RE-PREFILL on survivors
    from ``prompt + emitted`` — greedy decode is deterministic, so the
    continuation yields exactly the tokens the dead replica would have
    produced (no duplicate, no loss through a mid-decode kill:
    ``tests/test_decode.py``).  Deliberately lean next to :class:`ReplicaRouter`:
    decode streams are long-lived and slot-bound, so health is the
    worker's own liveness (an engine failure IS the worker dying), not a
    heartbeat sidecar."""

    def __init__(self, engines: Sequence[PagedDecodeEngine], *,
                 max_waiting: int = 256,
                 default_max_new: Optional[int] = None,
                 drafters: Optional[Sequence[PagedDecodeEngine]] = None,
                 draft_k: int = 4):
        assert engines
        self.tracer = engines[0].tracer
        drafters = list(drafters or [])
        self.batchers = [
            DecodeBatcher(e, max_waiting=max_waiting,
                          default_max_new=default_max_new, replica=i,
                          on_death=self._on_death,
                          drafter=(drafters[i] if i < len(drafters)
                                   else None),
                          draft_k=draft_k)
            for i, e in enumerate(engines)]

    def start(self) -> "DecodeRouter":
        for b in self.batchers:
            b.start()
        return self

    def warmup(self) -> None:
        for b in self.batchers:
            b.warmup()

    def wait_ready(self) -> bool:
        return any(not b.dead for b in self.batchers)

    def stop(self, drain: bool = True) -> None:
        for b in self.batchers:
            b.stop(drain=drain)

    def engine(self, i: int = 0) -> PagedDecodeEngine:
        return self.batchers[i].engine

    def alive(self) -> List[DecodeBatcher]:
        return [b for b in self.batchers
                if not b.dead and b._worker is not None]

    def submit_ids(self, ids: Sequence[int],
                   max_new_tokens: Optional[int] = None,
                   deadline_ms: Optional[float] = None) -> DecodeStream:
        alive = self.alive()
        if not alive:
            raise RuntimeError("no live decode replica")
        target = min(alive, key=lambda b: b.load)
        return target.submit_ids(ids, max_new_tokens=max_new_tokens,
                                 deadline_ms=deadline_ms)

    def kill(self, replica: int,
             error: Optional[BaseException] = None) -> None:
        self.batchers[replica].kill(error)

    def kill_drafter(self, replica: int,
                     error: Optional[BaseException] = None) -> None:
        """Chaos hook: kill replica's DRAFTER only — the pair must
        degrade to primary-only decode, not stall."""
        self.batchers[replica].kill_drafter(error)

    # ------------------------------------------------- controller surface
    def knob_values(self) -> Dict:
        """The tuning surface the :class:`ServeController` senses (its
        ``router.knob_values()`` quack): ``draft_k`` is the one decode
        knob so far — present only when some pair actually speculates,
        so the controller's speculation law stays dormant on plain
        pools."""
        ks = [b.draft_k for b in self.batchers if b.drafter is not None]
        return {"draft_k": int(ks[0])} if ks else {}

    def apply_knob(self, knob: str, value) -> None:
        """Controller actuation door (``ServeController._actuate`` ->
        ``_apply``): fan the knob to every speculating pair."""
        if knob != "draft_k":
            raise ValueError(f"unknown decode knob {knob!r}")
        for b in self.batchers:
            if b.drafter is not None or b.draft_k != int(value):
                b.set_draft_k(int(value))

    def health_summary(self) -> Dict:
        """Compact ``/healthz`` block (exporter ``health_sources``):
        liveness + the speculation story at a glance."""
        spec = [b for b in self.batchers
                if b.drafter is not None or b._spec_rounds]
        drafted = sum(b._spec_drafted for b in spec)
        accepted = sum(b._spec_accepted for b in spec)
        return {
            "alive": len(self.alive()),
            "replicas": len(self.batchers),
            "speculating": sum(1 for b in self.batchers
                               if b.drafter is not None),
            "draft_k": self.knob_values().get("draft_k", 0),
            "accept_rate": (accepted / float(drafted) if drafted
                            else 0.0),
            "drafter_deaths": sum(
                int(b.metrics.drafter_deaths_total.value)
                for b in self.batchers),
        }

    def _on_death(self, replica: int, orphans: List[DecodeStream],
                  error: BaseException) -> None:
        alive = self.alive()
        for stream in orphans:
            homed = False
            for target in sorted(alive, key=lambda b: b.load):
                # hop BEFORE the adopt: once adopted, the target's worker
                # may prefill (even complete) the stream immediately, and
                # a requeue hop landing after the terminal would fail
                # chain validation.  If the target died in the window the
                # hop names a replica that never took the stream — rare,
                # benign (non-terminal), and the next attempt records its
                # own hop; the requeued_out counter stays truthful by
                # incrementing only on a successful re-home.
                record_hop(self.tracer, stream.rid, "requeue",
                           from_replica=replica,
                           to_replica=target.replica, streamed=True,
                           tokens_emitted=len(stream.emitted))
                if target._adopt(stream):
                    self.batchers[replica].rmetrics.requeued_out.inc()
                    homed = True
                    break
            if not homed:
                if stream._finish(error):
                    record_hop(self.tracer, stream.rid, "failed",
                               error=type(error).__name__)

    def snapshot(self) -> Dict:
        return {
            "replicas": {str(b.replica): b.snapshot()
                         for b in self.batchers},
            "alive": len(self.alive()),
        }

    def control_snapshot(self) -> Dict:
        """Fleet-level paging view (the ops door next to
        :meth:`snapshot`'s per-replica firehose): page occupancy, free
        depth, COW/eviction counts and the prefix index's hit accounting,
        aggregated across replicas — every numeric leaf flattens into a
        Prometheus gauge via ``obs.prom.prometheus_lines``."""
        reps: Dict[str, Dict] = {}
        agg = {"pages_total": 0, "pages_live": 0, "free_depth": 0,
               "cow_copies": 0, "evictions": 0, "alloc_failures": 0,
               "hits_full": 0, "hits_partial": 0, "misses": 0,
               "index_entries": 0}
        spec_agg = {"enabled": 0, "draft_tokens": 0,
                    "accepted_tokens": 0, "rounds": 0,
                    "drafter_deaths": 0}
        spec_models: Dict[str, Dict] = {}
        for b in self.batchers:
            kv = b.engine.kv_snapshot()
            rep: Dict = {"alive": int(not b.dead), "load": b.load,
                         "peak_live_streams": b._peak_live,
                         "layout": kv["layout"]}
            if b.drafter is not None or b._spec_rounds:
                sp = b.spec_snapshot()
                rep["speculation"] = sp
                spec_agg["enabled"] += sp["enabled"]
                spec_agg["draft_tokens"] += sp["draft_tokens"]
                spec_agg["accepted_tokens"] += sp["accepted_tokens"]
                spec_agg["rounds"] += sp["rounds"]
                for m, leaf in (sp.get("by_model") or {}).items():
                    dst = spec_models.setdefault(m, {})
                    for lk, lv in leaf.items():
                        if isinstance(lv, (int, float)) \
                                and not isinstance(lv, bool):
                            dst[lk] = dst.get(lk, 0) + lv
            spec_agg["drafter_deaths"] += int(
                b.metrics.drafter_deaths_total.value)
            pages = kv.get("pages")
            prefix = kv.get("prefix")
            if pages:
                rep["pages"] = pages
                agg["pages_total"] += pages["total_pages"]
                agg["pages_live"] += pages["pages_live"]
                agg["free_depth"] += pages["free_depth"]
                agg["cow_copies"] += pages["cow_copies"]
                agg["evictions"] += pages["evictions"]
                agg["alloc_failures"] += pages["alloc_failures"]
            if prefix:
                rep["prefix"] = prefix
                agg["hits_full"] += prefix["hits_full"]
                agg["hits_partial"] += prefix["hits_partial"]
                agg["misses"] += prefix["misses"]
                agg["index_entries"] += prefix["entries"]
            reps[str(b.replica)] = rep
        looked = agg["hits_full"] + agg["hits_partial"] + agg["misses"]
        agg["prefix_hit_rate"] = (
            (agg["hits_full"] + agg["hits_partial"]) / looked
            if looked else 0.0)
        agg["page_occupancy"] = (agg["pages_live"] / agg["pages_total"]
                                 if agg["pages_total"] else 0.0)
        spec_agg["accept_rate"] = (
            spec_agg["accepted_tokens"] / float(spec_agg["draft_tokens"])
            if spec_agg["draft_tokens"] else 0.0)
        if spec_models:
            spec_agg["by_model"] = spec_models
        return {"alive": len(self.alive()), "pages": agg,
                "knobs": self.knob_values(),
                "speculation": spec_agg,
                "replicas": reps}


class DisaggDecodeRouter:
    """Disaggregated prefill/decode engine pools behind one front door
    (ROADMAP item 4: DistServe OSDI'24 / Splitwise ISCA'24).

    All engines are PAGED and share one geometry; each is wrapped in a
    role unit — :class:`PrefillWorker` or :class:`DecodeBatcher` — with
    the engine index as its replica id.  Submissions land least-loaded
    on the prefill pool; a finished prefill hands its pages off
    least-loaded onto the decode pool.  ``transport="local"`` seats the
    exported payload in-process; ``"socket"`` pushes every payload
    through :mod:`pdnlp_tpu.serve.handoff`'s length-prefixed loopback
    framing (one :class:`HandoffServer` per decode unit, one connected
    :class:`HandoffChannel` per target) — the process-split rehearsal.

    The pool split is LIVE: :meth:`set_prefill_share` (the controller's
    ``prefill_share`` knob) retires units on the shrinking side,
    rebuilds them in the other role, and re-homes their streams through
    the front door (re-prefill; greedy decode is deterministic, so the
    continuation is bitwise unchanged).  Engines keep their jit caches
    across re-roles and :meth:`warmup` pre-traces EVERY program on
    EVERY engine, so neither a re-role nor a handoff ever compiles
    post-warmup (``tests/test_disagg.py`` counts retraces in both pools)."""

    def __init__(self, engines: Sequence[PagedDecodeEngine], *,
                 prefill_engines: int = 1, max_waiting: int = 256,
                 default_max_new: Optional[int] = None,
                 transport: str = "local"):
        if len(engines) < 2:
            raise ValueError(
                "disaggregated serving needs >= 2 engines (at least "
                "one per role); use DecodeRouter for a single engine")
        if transport not in ("local", "socket"):
            raise ValueError(f"unknown handoff transport {transport!r}")
        self.engines = list(engines)
        self.transport = transport
        self.tracer = engines[0].tracer
        self.max_waiting = int(max_waiting)
        self.default_max_new = default_max_new
        self._lock = threading.Lock()
        self._started = False
        n = len(self.engines)
        k = max(1, min(n - 1, int(prefill_engines)))
        self._servers: Dict[int, HandoffServer] = {}
        self._channels: Dict[int, HandoffChannel] = {}
        #: rid -> DecodeStream for payloads currently on the wire
        #: (socket transport; the frame carries metadata, the live
        #: stream object is joined back by rid on receive)
        self._inflight: Dict[str, DecodeStream] = {}
        self._units: List[object] = [
            self._build_unit(i, "prefill" if i < k else "decode")
            for i in range(n)]

    # ------------------------------------------------------ unit plumbing
    def _build_unit(self, i: int, role: str):
        """One engine, one role: wrap engine ``i`` as a PrefillWorker or
        DecodeBatcher (socket mode also gives each decode unit its
        receive server + the router's send channel to it)."""
        e = self.engines[i]
        e.span_attrs["pool"] = role  # re-assign: roles flip on re-split
        if role == "prefill":
            return PrefillWorker(
                e, dispatch=self._dispatch, max_waiting=self.max_waiting,
                default_max_new=self.default_max_new, replica=i,
                on_death=self._on_death)
        unit = DecodeBatcher(
            e, max_waiting=self.max_waiting,
            default_max_new=self.default_max_new, replica=i,
            on_death=self._on_death)
        if self.transport == "socket":
            srv = HandoffServer(self._make_receiver(i)).start()
            with self._lock:
                self._servers[i] = srv
                self._channels[i] = HandoffChannel(srv.address)
        return unit

    def _teardown_transport(self, i: int) -> None:
        with self._lock:
            ch = self._channels.pop(i, None)
            srv = self._servers.pop(i, None)
        if ch is not None:
            ch.close()
        if srv is not None:
            srv.stop()

    def _make_receiver(self, i: int) -> Callable:
        """Socket mode: decode unit ``i``'s frame callback.  The wire
        payload carries the stream METADATA; the live DecodeStream
        object (the caller's handle) is joined back by rid from the
        sender's in-flight table.  A raise here is the NACK the sender's
        custody logic keys on."""
        def on_payload(meta: Dict, k: np.ndarray, v: np.ndarray) -> None:
            with self._lock:
                stream = self._inflight.pop(meta["rid"], None)
            if stream is None:
                raise HandoffError(
                    f"no in-flight stream for rid {meta['rid']!r}")
            unit = self._units[i]
            if not isinstance(unit, DecodeBatcher) \
                    or not unit.accept_handoff(
                        stream, meta["pos"], meta["next_token"], k, v):
                raise HandoffError(
                    f"decode unit {i} refused the handoff")
        return on_payload

    def _prefill_units(self) -> List["PrefillWorker"]:
        with self._lock:
            return [u for u in self._units
                    if isinstance(u, PrefillWorker) and not u.dead
                    and u._worker is not None]

    def _decode_units(self) -> List[DecodeBatcher]:
        with self._lock:
            return [u for u in self._units
                    if isinstance(u, DecodeBatcher) and not u.dead
                    and u._worker is not None]

    # ----------------------------------------------------------- dispatch
    def _dispatch(self, stream: DecodeStream, meta: Dict,
                  payload_k, payload_v) -> Optional[tuple]:
        """PrefillWorker callback: place one exported payload on the
        least-loaded live decode unit; returns ``(to_replica,
        transport)`` or ``None`` when no decode unit took it.  The
        ``handoff`` hop is recorded per attempt BEFORE the seat (the
        requeue-hop ordering precedent: once seated, the decode worker
        may finish the stream immediately, and a handoff hop landing
        after the terminal would fail chain validation)."""
        from_replica = stream.replica
        nbytes = int(payload_k.nbytes) + int(payload_v.nbytes)
        for target in sorted(self._decode_units(), key=lambda b: b.load):
            record_hop(self.tracer, stream.rid, "handoff",
                       from_replica=from_replica,
                       to_replica=target.replica,
                       pages=meta["n_pages"], bytes=nbytes,
                       transport=self.transport)
            if self.transport == "local":
                if target.accept_handoff(stream, meta["pos"],
                                         meta["next_token"],
                                         payload_k, payload_v):
                    return (target.replica, "local")
                continue
            with self._lock:
                ch = self._channels.get(target.replica)
                self._inflight[stream.rid] = stream
            if ch is None:
                with self._lock:
                    self._inflight.pop(stream.rid, None)
                continue
            try:
                ch.send(meta, payload_k, payload_v)
                return (target.replica, "socket")
            except HandoffError:
                with self._lock:
                    self._inflight.pop(stream.rid, None)
                continue
        return None

    # ---------------------------------------------------------- lifecycle
    def start(self) -> "DisaggDecodeRouter":
        with self._lock:
            self._started = True
            units = list(self._units)
        for u in units:
            u.start()
        return self

    def warmup(self) -> None:
        """Pre-trace EVERY program on EVERY engine — prefill buckets,
        chunk, decode, COW, export AND import — so a handoff or a pool
        re-split never compiles (both roles run from warm caches)."""
        for e in self.engines:
            e.warmup_decode()
            e.warmup_handoff()

    def wait_ready(self) -> bool:
        return bool(self._prefill_units()) and bool(self._decode_units())

    def stop(self, drain: bool = True) -> None:
        with self._lock:
            units = list(self._units)
        # prefill first: its drain flushes queued streams THROUGH the
        # handoff, decode's drain then finishes them
        for u in units:
            if isinstance(u, PrefillWorker):
                u.stop(drain=drain)
        for u in units:
            if isinstance(u, DecodeBatcher):
                u.stop(drain=drain)
        with self._lock:
            channels = list(self._channels.values())
            servers = list(self._servers.values())
            self._channels.clear()
            self._servers.clear()
        for ch in channels:
            ch.close()
        for srv in servers:
            srv.stop()

    def engine(self, i: int = 0) -> PagedDecodeEngine:
        return self.engines[i]

    def alive(self) -> List[object]:
        with self._lock:
            return [u for u in self._units
                    if not u.dead and u._worker is not None]

    def kill(self, replica: int,
             error: Optional[BaseException] = None) -> None:
        self._units[replica].kill(error)

    # -------------------------------------------------------- front door
    def submit_ids(self, ids: Sequence[int],
                   max_new_tokens: Optional[int] = None,
                   deadline_ms: Optional[float] = None) -> DecodeStream:
        workers = self._prefill_units()
        if not workers:
            raise RuntimeError("no live prefill replica")
        target = min(workers, key=lambda w: w.load)
        return target.submit_ids(ids, max_new_tokens=max_new_tokens,
                                 deadline_ms=deadline_ms)

    def _reintake(self, streams: List[DecodeStream], from_replica: int,
                  error: Optional[BaseException] = None) -> None:
        """Re-home orphans (replica death, pool re-split) through the
        prefill pool: ``prompt + emitted`` re-prefills and hands off
        again — the transfer ledger's recovery story.  The requeue hop
        lands BEFORE the adopt (ordering precedent, see
        :meth:`DecodeRouter._on_death`)."""
        err = error or RuntimeError("no live prefill replica")
        for stream in streams:
            homed = False
            for target in sorted(self._prefill_units(),
                                 key=lambda w: w.load):
                record_hop(self.tracer, stream.rid, "requeue",
                           from_replica=from_replica,
                           to_replica=target.replica, streamed=True,
                           tokens_emitted=len(stream.emitted))
                if target._adopt(stream):
                    unit = self._units[from_replica]
                    if unit is not None:
                        unit.rmetrics.requeued_out.inc()
                    homed = True
                    break
            if not homed:
                if stream._finish(err):
                    record_hop(self.tracer, stream.rid, "failed",
                               error=type(err).__name__)

    def _on_death(self, replica: int, orphans: List[DecodeStream],
                  error: BaseException) -> None:
        self._reintake(orphans, replica, error)

    # ------------------------------------------------- controller surface
    def set_prefill_share(self, value: float) -> float:
        """Actuate the pool split: ``value`` is the FRACTION of engines
        in the prefill role, quantized to whole engines with a floor of
        one per role.  Units on the shrinking side retire (streams
        re-enter the front door), rebuild in the other role, and restart
        from the engine's warm jit caches.  Returns the applied
        (quantized) share — the exact value :meth:`knob_values` will
        report, so the controller's eval-window staleness check holds."""
        n = len(self.engines)
        step = round(1.0 / n, 6)
        k_new = max(1, min(n - 1, int(round(float(value) * n))))
        with self._lock:
            pre_idx = [i for i, u in enumerate(self._units)
                       if isinstance(u, PrefillWorker)]
            dec_idx = [i for i, u in enumerate(self._units)
                       if isinstance(u, DecodeBatcher)]
            started = self._started
        k_old = len(pre_idx)
        if k_new == k_old:
            return round(k_new * step, 6)
        if k_new > k_old:
            flip = sorted(dec_idx,
                          key=lambda i: self._units[i].load)[:k_new - k_old]
            role = "prefill"
        else:
            flip = sorted(pre_idx,
                          key=lambda i: self._units[i].load)[:k_old - k_new]
            role = "decode"
        leftovers: List[DecodeStream] = []
        for i in flip:
            old = self._units[i]
            leftovers += old.retire()
            if isinstance(old, DecodeBatcher):
                self._teardown_transport(i)
            new = self._build_unit(i, role)
            with self._lock:
                self._units[i] = new
            if started:
                new.start()
        for stream in leftovers:
            self._reintake([stream], stream.replica
                           if stream.replica is not None else flip[0])
        return round(k_new * step, 6)

    def knob_values(self) -> Dict:
        """Controller sense surface: the live split plus its quantum.
        The share is reported as ``k * step`` (both rounded the same
        way the split law composes them), so an actuated target and the
        re-sensed value compare EQUAL — the eval window's staleness
        check must not see ghosts."""
        n = len(self.engines)
        step = round(1.0 / n, 6)
        with self._lock:
            k = sum(1 for u in self._units
                    if isinstance(u, PrefillWorker))
        return {"prefill_share": round(k * step, 6),
                "prefill_share_step": step}

    def apply_knob(self, knob: str, value) -> None:
        if knob != "prefill_share":
            raise ValueError(f"unknown disagg knob {knob!r}")
        self.set_prefill_share(float(value))

    def health_summary(self) -> Dict:
        """Compact ``/healthz`` block: liveness + the split + per-pool
        pressure at a glance (``by_pool`` flattens with a ``pool``
        label on ``/metrics``)."""
        with self._lock:
            units = list(self._units)
        pre = [u for u in units if isinstance(u, PrefillWorker)]
        dec = [u for u in units if isinstance(u, DecodeBatcher)]
        return {
            "alive": len(self.alive()),
            "replicas": len(units),
            "transport": self.transport,
            "prefill_share": self.knob_values()["prefill_share"],
            "handoffs": sum(int(u.metrics.handoffs_total.value)
                            for u in pre),
            "handoff_failures": sum(
                int(u.metrics.handoff_failures_total.value)
                for u in pre),
            "by_pool": {
                "prefill": {
                    "engines": len(pre),
                    "alive": sum(1 for u in pre if not u.dead
                                 and u._worker is not None),
                    "backlog": sum(len(u._waiting) for u in pre),
                },
                "decode": {
                    "engines": len(dec),
                    "alive": sum(1 for u in dec if not u.dead
                                 and u._worker is not None),
                    "backlog": sum(len(u._handoffs) for u in dec),
                },
            },
        }

    def snapshot(self) -> Dict:
        with self._lock:
            units = list(self._units)
        return {
            "replicas": {str(u.replica): u.snapshot() for u in units},
            "alive": len(self.alive()),
            "transport": self.transport,
        }

    def control_snapshot(self) -> Dict:
        """The controller's sense surface: fleet paging view (same
        ``pages`` aggregate as :meth:`DecodeRouter.control_snapshot`)
        PLUS the two latency signals the pool-split law trades off —
        ``ttft_p99_ms`` vs ``inter_token_p99_ms``, pooled across every
        unit's own histogram windows (``merged_percentiles``: one
        fleet-level p99, not an average of per-unit p99s) — and a
        ``by_pool`` pressure block."""
        with self._lock:
            units = list(self._units)
        pre = [u for u in units if isinstance(u, PrefillWorker)]
        dec = [u for u in units if isinstance(u, DecodeBatcher)]
        ttft = merged_percentiles(
            [u.metrics.ttft_ms for u in units], (50, 99))
        itok = merged_percentiles(
            [u.metrics.intertoken_ms for u in units], (50, 99))
        agg = {"pages_total": 0, "pages_live": 0, "free_depth": 0,
               "cow_copies": 0, "evictions": 0, "alloc_failures": 0,
               "hits_full": 0, "hits_partial": 0, "misses": 0,
               "index_entries": 0}
        reps: Dict[str, Dict] = {}
        for u in units:
            kv = u.engine.kv_snapshot()
            rep: Dict = {"alive": int(not u.dead), "load": u.load,
                         "pool": ("prefill"
                                  if isinstance(u, PrefillWorker)
                                  else "decode"),
                         "peak_live_streams": u._peak_live}
            pages = kv.get("pages")
            prefix = kv.get("prefix")
            if pages:
                rep["pages"] = pages
                agg["pages_total"] += pages["total_pages"]
                agg["pages_live"] += pages["pages_live"]
                agg["free_depth"] += pages["free_depth"]
                agg["cow_copies"] += pages["cow_copies"]
                agg["evictions"] += pages["evictions"]
                agg["alloc_failures"] += pages["alloc_failures"]
            if prefix:
                rep["prefix"] = prefix
                agg["hits_full"] += prefix["hits_full"]
                agg["hits_partial"] += prefix["hits_partial"]
                agg["misses"] += prefix["misses"]
                agg["index_entries"] += prefix["entries"]
            reps[str(u.replica)] = rep
        looked = agg["hits_full"] + agg["hits_partial"] + agg["misses"]
        agg["prefix_hit_rate"] = (
            (agg["hits_full"] + agg["hits_partial"]) / looked
            if looked else 0.0)
        agg["page_occupancy"] = (agg["pages_live"] / agg["pages_total"]
                                 if agg["pages_total"] else 0.0)
        return {
            "alive": len(self.alive()),
            "pages": agg,
            "knobs": self.knob_values(),
            "latency": {
                "ttft_p50_ms": ttft[0], "ttft_p99_ms": ttft[1],
                "inter_token_p50_ms": itok[0],
                "inter_token_p99_ms": itok[1],
            },
            "by_pool": {
                "prefill": {
                    "engines": len(pre),
                    "alive": sum(1 for u in pre if not u.dead
                                 and u._worker is not None),
                    "backlog": sum(len(u._waiting) for u in pre),
                    "handoffs": sum(
                        int(u.metrics.handoffs_total.value)
                        for u in pre),
                    "handoff_failures": sum(
                        int(u.metrics.handoff_failures_total.value)
                        for u in pre),
                },
                "decode": {
                    "engines": len(dec),
                    "alive": sum(1 for u in dec if not u.dead
                                 and u._worker is not None),
                    "backlog": sum(len(u._handoffs) for u in dec),
                    "live": sum(
                        int(u.metrics.kv_slots_live.value)
                        for u in dec),
                },
            },
            "replicas": reps,
        }
