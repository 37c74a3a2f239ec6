#!/usr/bin/env python
"""Benchmark: north-star config (mesh DP + bf16) on the real corpus.

Prints ONE machine-parseable JSON line:
    {"metric": ..., "value": N, "unit": "min", "vs_baseline": N, ...}

``value`` is the TOTAL training wall-clock in minutes — every epoch of the
shipped recipe, the number a user actually waits for — with ``min_per_epoch``
and ``minutes_to_target`` (first in-loop eval >= the reference's 0.57
accuracy) alongside.  The reference's headline is its own total wall-clock
(one epoch, ``耗时：X分钟``, ``/root/reference/README.md:10-20``);
``vs_baseline`` is the speedup of this TOTAL against the published
north-star — 2-GPU DDP+AMP, 0.6336 min (``README.md:16``) — so > 1.0 beats
it outright, not per-epoch.

Accuracy: the reference fine-tunes *pretrained* ``hfl/chinese-bert-wwm-ext``
(dev acc ~0.57).  This environment has no egress, so the warm start is
produced in-repo: ``pretrain-tpu.py`` (masked-LM over the 40k-text corpus,
fine-tune dev split held out).  The bench fine-tunes from
``output/pretrained-tanh.msgpack`` (the cache name carries the activation;
``--gelu erf`` uses ``pretrained.msgpack``), regenerating it first if
absent (~20 min, one-time; reruns hit the cached file).  The pretrain stage is NOT part of
the timed epoch — the reference's download of model_hub weights isn't timed
either.

Scope: the bench is a SINGLE-HOST harness (the pretrain-cache check is a
local-filesystem gate; multi-host runs should pretrain explicitly first),
and ``mfu_pct`` assumes the default pure-DP mesh — under ``--mesh_shape``
with tp/sp axes the per-chip FLOP share changes and the field is not
comparable.

Flag note: ``--pipeline <mode|all>`` is the input-pipeline COMPARISON smoke
(``pipeline_smoke`` below, per-mode steps/s + transport counters), not a
knob of the headline bench — it intercepts before ``Args`` parsing.  The
headline bench always runs ``Args.pipeline="auto"`` (device-resident when
eligible; that IS the shipped optimization) and reports the resolved mode
plus measured transport in its JSON (``pipeline``/``transport``).  Other
entrypoints (``single-tpu-cls.py``, ``multi-tpu-*-cls.py``) expose
``--pipeline`` as the ordinary mode override.

Methodology notes (vs the reference's timing):
- the timed epoch starts AFTER the train step is compiled (AOT ``.lower()
  .compile()``), the analog of the reference's warm CUDA context; XLA's
  persistent compilation cache (``utils.config.enable_compilation_cache``)
  makes reruns cheap;
- dev accuracy is measured after the timer stops, like the reference's
  separate ``test()`` pass;
- training logs go to stderr; stdout carries only the JSON line.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys

NORTH_STAR_MIN = 0.6336       # 2-GPU DDP+AMP, README.md:16
SINGLE_GPU_MIN = 2.8276       # 1-GPU fp32, README.md:12
# per-chip bf16 peak FLOP/s by device kind (prefix-matched); MFU is only
# reported when the running chip is recognized
BF16_PEAK_BY_KIND = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,    # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,    # v6e / Trillium
    "TPU v6e": 918e12,
}


def bf16_peak(device) -> float | None:
    kind = getattr(device, "device_kind", "")
    for prefix, peak in BF16_PEAK_BY_KIND.items():
        if kind.startswith(prefix):
            return peak
    return None


def step_flops(cfg, batch: int, seq: int) -> float:
    """Matmul FLOPs of one fused train step (fwd + 2x bwd), excluding
    embedding gathers: 6 * (encoder matmul params) * tokens + attention."""
    H, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    mm_params = L * (4 * H * H + 2 * H * I) + H * H  # qkvo + mlp + pooler
    tokens = batch * seq
    dense = 6 * mm_params * tokens
    attn = L * 3 * 2 * 2 * batch * cfg.num_heads * seq * seq * cfg.head_dim
    return dense + attn


def serve_smoke(argv) -> None:
    """``--serve``: inference-serving smoke over the offline path.

    N mixed-length requests spanning >= 3 sequence buckets, driven through
    ``pdnlp_tpu.serve`` after a bucket warmup.  Reports req/s, latency
    p50/p99, batch occupancy, compile-cache hit/miss and — the acceptance
    bar — the retrace count AFTER warmup, which must be zero: steady-state
    serving never re-traces.  Writes the snapshot to ``results/
    serve_smoke.json`` (override: ``--serve_out``); request count:
    ``--serve_requests`` (default 120).  Deterministic and CPU-safe: texts
    are synthesized from a seeded RNG (over the corpus vocab when present,
    a fixed CJK set otherwise), so the smoke needs no dataset or
    checkpoint — though a checkpoint under ``--output_dir`` is used when
    one exists.
    """
    import random
    import time

    import jax

    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
    from pdnlp_tpu.parallel import make_mesh
    from pdnlp_tpu.serve import InferenceEngine
    from pdnlp_tpu.serve.offline import score_texts
    from pdnlp_tpu.utils.config import Args, parse_cli, pop_cli_flag

    argv, n_requests = pop_cli_flag(argv, "--serve_requests", 120, int)
    argv, out_path = pop_cli_flag(
        argv, "--serve_out", os.path.join("results", "serve_smoke.json"))
    args = parse_cli(argv, base=Args())

    # deterministic mixed-length traffic: char counts sized so token lengths
    # (chars + [CLS]/[SEP]) land in the 32/64/128 buckets
    chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐高兴悲伤讨厌愤怒"
    rng = random.Random(args.seed)
    lengths = [10, 24, 48, 60, 100, 120]
    texts = ["".join(rng.choice(chars) for _ in range(lengths[i % len(lengths)]))
             for i in range(n_requests)]

    if os.path.exists(args.data_path) or os.path.exists(args.vocab_path):
        from pdnlp_tpu.data.tokenizer import get_or_build_vocab

        tok = WordPieceTokenizer(get_or_build_vocab(args))
    else:
        # no corpus on this host: a vocab over the synthetic char set keeps
        # the smoke self-contained (latency/retrace numbers don't care)
        tok = WordPieceTokenizer(build_vocab(texts, size=256))

    buckets = (32, 64, 128)
    batch_size = 8
    mesh = make_mesh(num_devices=args.num_devices, shape=args.mesh_shape)
    engine = InferenceEngine(args, tokenizer=tok, mesh=mesh)
    from pdnlp_tpu.train import checkpoint as ckpt_mod

    ckpt_path = ckpt_mod.latest(args.output_dir)
    if ckpt_path:
        try:
            engine.load_checkpoint(ckpt_path)
        except Exception as e:
            print(f"checkpoint {ckpt_path} not loadable ({e}); "
                  "serving init weights", file=sys.stderr)

    engine.warmup(buckets, engine.pad_rows(batch_size))
    retraces_warmup = engine.metrics.retraces.value

    t0 = time.monotonic()
    preds, _ = score_texts(engine, texts, buckets=buckets,
                           batch_size=batch_size)
    elapsed = time.monotonic() - t0

    snap = engine.metrics.snapshot()
    retraces_post = engine.metrics.retraces.value - retraces_warmup
    result = {
        "metric": "serve_smoke",
        "requests": n_requests,
        "req_per_sec": round(n_requests / elapsed, 2),
        "elapsed_sec": round(elapsed, 3),
        "latency_ms_p50": snap["request_latency_ms"]["p50"],
        "latency_ms_p99": snap["request_latency_ms"]["p99"],
        "batch_occupancy_mean": snap["batch_occupancy"]["mean"],
        "buckets": list(buckets),
        "batch_size": batch_size,
        "retraces_warmup": retraces_warmup,
        "retraces_post_warmup": retraces_post,
        "cache_hits": snap["compile_cache"]["hits"],
        "cache_misses": snap["compile_cache"]["misses"],
        "checkpoint": engine.checkpoint_path,
        "model": args.model,
        "dtype": args.dtype,
        # what the engine actually serves: the forward precision label
        # ("int8" under --serve_dtype int8) and the routed attention impl
        # (headline at max_seq_len; per-bucket routing alongside — sub-128
        # buckets fall back to XLA under a pallas request)
        "serve_dtype": engine.dtype_label,
        "attn_impl": engine.attn_impl,
        "attn_impl_by_seq": {str(s): i for s, i
                             in sorted(engine.attn_impl_by_seq.items())},
        "devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
        "metrics": snap,
    }
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2)
        os.replace(tmp, out_path)
    print(json.dumps({k: v for k, v in result.items() if k != "metrics"}))
    if retraces_post != 0:
        # the smoke's whole point: steady-state serving never re-traces.
        # A nonzero count here is a shape-stability regression (dtype/
        # weak-type drift, bucket plumbing) — fail loudly, snapshot kept.
        sys.exit(f"serve smoke FAILED: {retraces_post} post-warmup retraces "
                 f"(expected 0) — see {out_path}")


def decode_smoke(argv) -> None:
    """``--decode``: the generative-decoding gate (ROADMAP item 1).

    A closed-loop storm of ``--decode_streams`` mixed-length prompts
    through the continuous-batching decode engine
    (``pdnlp_tpu.serve.decode``), gating the properties the KV cache
    exists to buy:

    - **tokens/s/chip >= 2x a no-cache re-prefill baseline** — the same
      prompts generating the same token counts by re-running the bucketed
      causal prefill per token (the cost of generation WITHOUT a cache,
      batched just as wide, on the same engine programs);
    - **zero post-warmup retraces** across the prefill buckets AND the
      one fixed ``[slots, 1]`` decode shape;
    - **inter-token p99 under ``--decode_p99_ms``** with continuous
      batching holding **mean slot occupancy >= 0.8** under the mixed
      stream mix;
    - **chain integrity through a mid-storm replica kill**: a 2-replica
      router storm, replica 0 killed once demonstrably mid-decode; every
      stream's hop chain must validate through the trace-file round trip
      AND every stream must emit EXACTLY the single-engine reference
      token sequence (orphans re-prefill on the survivor — no duplicated,
      no lost tokens);
    - **paged shared-prefix storm** (phase D, the paged-KV gate): an
      80%-shared prompt mix at EQUAL ``--kv_hbm_mb`` must seat >= 3x the
      slot layout's concurrent streams (peak live), every stream
      token-identical to the slot-cache baseline, a prefix-hit resubmit
      must run ZERO prefill forwards (TTFT bounded by one decode-step
      latency, by construction: the stored first token is emitted at
      claim), zero post-warmup retraces on the paged path, and the page
      allocator's ledger must reconcile to ZERO leaked pages after drain
      — including through a 2-replica paged kill storm whose re-prefilled
      survivors re-attach to shared prefix pages;
    - **speculative decoding** (phase E, ROADMAP item 3): draft-k /
      verify-1 over a paged primary/drafter pair must deliver >= 1.8x
      tokens/s vs primary-only decode at BITWISE token parity per
      stream, zero post-warmup retraces on both engines, zero leaked
      pages after drain (including through a mid-storm drafter kill
      that degrades the pair to primary-only at exact-token parity),
      complete draft -> verify hop chains through the trace-file round
      trip, and a ``ServeController`` that demonstrably adapts k on an
      injected low-acceptance stream — halve, disable, and auto-revert
      a regressing re-enable — with every actuation's decision chain
      complete.  The drafter/primary COST RATIO is the one emulated
      quantity (untrained weights can't give a genuinely cheap model a
      real acceptance rate), calibrated per host: every primary
      dispatch is padded to the MEASURED per-step cost of a real
      bert-small engine while the drafter runs bert-tiny at full speed.
    - **disaggregated pools** (phase F, ROADMAP item 4): the same mixed
      storm through an interleaved single-engine batcher and through a
      3-engine prefill/decode pool split (socket transport), with every
      prefill dispatch padded by a fixed cost on BOTH setups.  Gates:
      the interleaved inter-token p99 must inherit the prefill cost
      while the decode pool's p99 stays under it (the isolation claim),
      bitwise token parity between the two setups, zero post-warmup
      retraces across all four engines, complete hop chains with every
      stream crossing the pool boundary exactly through a ``handoff``
      hop, zero wire-frame errors, and — through a mid-storm decode-
      replica kill — requeued orphans that re-home through the front
      door at exact-token parity with reconciled survivor page ledgers.

    Deterministic and CPU-safe (seeded prompts over a synthetic vocab,
    greedy decode, EOS disabled so token counts are exact); snapshot at
    ``results/decode_smoke.json``, non-zero exit on any violation.
    """
    import tempfile
    import time

    import jax
    import numpy as np

    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
    from pdnlp_tpu.obs.decision import validate_decisions
    from pdnlp_tpu.obs.request import validate_chains
    from pdnlp_tpu.serve import (
        DecodeBatcher, DecodeEngine, DecodeRouter, PagedDecodeEngine,
        ServeController,
    )
    from pdnlp_tpu.serve.decode import DisaggDecodeRouter
    from pdnlp_tpu.utils.config import Args, parse_cli, pop_cli_flag

    argv, n_streams = pop_cli_flag(argv, "--decode_streams", 48, int)
    argv, slots = pop_cli_flag(argv, "--decode_slots_n", 8, int)
    argv, max_new = pop_cli_flag(argv, "--decode_max_new", 24, int)
    argv, p99_budget = pop_cli_flag(argv, "--decode_p99_ms", 500.0, float)
    argv, out_path = pop_cli_flag(
        argv, "--decode_out", os.path.join("results", "decode_smoke.json"))
    # jaxlint: disable=L1 — smoke artifact dir, kept for post-run triage
    trace_dir = tempfile.mkdtemp(prefix="decode_smoke_trace_")
    args = parse_cli(argv, base=Args(
        model="bert-tiny", decode_slots=slots, decode_max_len=96,
        max_new_tokens=max_new, trace=True, trace_dir=trace_dir))
    buckets = (16, 32, 64)

    chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐高兴悲伤讨厌愤怒"
    tok = WordPieceTokenizer(build_vocab([chars], size=256))
    rng = np.random.default_rng(args.seed)
    lens = rng.integers(3, 40, n_streams)
    prompts = [rng.integers(5, tok.vocab_size, int(k)).tolist()
               for k in lens]
    failures = []

    def make_engine():
        return DecodeEngine(args, tokenizer=tok, mesh=None,
                            buckets=buckets)

    # ---------------------------------------------- phase A: cached decode
    engine = make_engine()
    batcher = DecodeBatcher(engine, max_waiting=n_streams).start()
    batcher.eos_id = -1  # deterministic token counts
    batcher.warmup()
    retr0 = engine.metrics.retraces.value
    miss0 = engine.metrics.cache_misses.value
    t0 = time.monotonic()
    streams = [batcher.submit_ids(p, max_new_tokens=max_new)
               for p in prompts]
    refs = [s.result(timeout=600) for s in streams]
    decode_sec = time.monotonic() - t0
    snap = batcher.snapshot()
    batcher.stop()
    tokens_out = snap["decode"]["tokens_out_total"]
    retraces_post = engine.metrics.retraces.value - retr0
    misses_post = engine.metrics.cache_misses.value - miss0
    n_chips = jax.device_count()
    decode_tps_chip = tokens_out / decode_sec / n_chips
    occupancy_mean = snap["replica"]["slot_occupancy"]["mean"]
    intertoken_p99 = snap["decode"]["intertoken_ms"]["p99"]

    # ------------------------------------- phase B: no-cache re-prefill
    # the same generations WITHOUT a KV cache: every token re-runs the
    # bucketed causal prefill over prompt + generated-so-far, batched
    # prefill_rows wide on the same engine programs (filler slot ids, so
    # nothing touches the cache) — the honest cost of cacheless decoding
    rows = engine.prefill_rows
    t0 = time.monotonic()
    base_tokens = 0
    for i in range(0, n_streams, rows):
        group = list(range(i, min(i + rows, n_streams)))
        seqs = [list(prompts[g]) for g in group]
        done = [False] * len(group)
        while not all(done):
            live = [j for j in range(len(group)) if not done[j]]
            logits = engine.prefill_ids(
                [seqs[j] for j in live],
                [engine.slots] * len(live))  # OOB: cache untouched
            for r, j in enumerate(live):
                seqs[j].append(int(np.argmax(logits[r])))
                base_tokens += 1
                g = group[j]
                if len(seqs[j]) - len(prompts[g]) >= len(refs[g]):
                    done[j] = True
    baseline_sec = time.monotonic() - t0
    baseline_tps_chip = base_tokens / baseline_sec / n_chips
    speedup = decode_tps_chip / baseline_tps_chip

    # the baseline must reproduce the cached path's tokens — otherwise
    # the speedup compares garbage.  One seeded stream re-verified here
    # (the full bitwise contract is tier-1's test_decode job)
    parity_ok = True
    g0 = list(prompts[0])
    for t in refs[0]:
        lg = engine.prefill_ids([g0], [engine.slots])
        if int(np.argmax(lg[0])) != t:
            parity_ok = False
            break
        g0.append(t)

    # ------------------------------------------- phase C: replica kill
    engines = [make_engine() for _ in range(2)]
    tracer = engines[0].tracer
    for e in engines[1:]:
        e.tracer = tracer
    router = DecodeRouter(engines, max_waiting=n_streams).start()
    for b in router.batchers:
        b.eos_id = -1
    router.warmup()
    kill_retr0 = sum(e.metrics.retraces.value for e in engines)
    kstreams = [router.submit_ids(p, max_new_tokens=max_new)
                for p in prompts]
    deadline = time.monotonic() + 120
    while (router.batchers[0].metrics.tokens_out_total.value
           < max_new * slots and time.monotonic() < deadline):
        time.sleep(0.002)
    router.kill(0)
    kouts = [s.result(timeout=600) for s in kstreams]
    kill_retraces = sum(e.metrics.retraces.value
                        for e in engines) - kill_retr0
    requeued_in = router.batchers[1].rmetrics.requeued_in.value
    router.stop()
    kill_parity = kouts == refs

    # chain integrity through the FILE round trip: flush, re-read, check
    trace_path = tracer.flush()
    records = []
    with open(trace_path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    report = validate_chains(records, [s.rid for s in kstreams])

    # ----------------------------- phase D: paged shared-prefix storm
    # The paged-KV capacity claim, head to head at EQUAL --kv_hbm_mb: a
    # budget worth FOUR max-length slot stripes, an 80%-shared prompt
    # mix (one 32-token system prefix + short distinct suffixes; every
    # 5th prompt unique), and the same storm driven through (a) the slot
    # layout — capped to 4 slots — and (b) the paged layout, whose
    # shared streams pin the prefix's 2 pages once and reserve ~1
    # private page each.  Gates: >= 3x peak concurrent live streams,
    # token parity stream for stream, a structurally-zero-prefill
    # full-hit resubmit, zero post-warmup retraces, and a reconciled
    # (zero-leak) page ledger after drain — then once more through a
    # 2-replica paged kill storm.
    pd_slots, pd_page_sz, pd_max_len, pd_max_new = 16, 16, 96, 8
    probe_eng = PagedDecodeEngine(
        parse_cli([], base=Args(model="bert-tiny", decode_slots=1,
                                decode_max_len=pd_max_len,
                                kv_page_sz=pd_page_sz)),
        tokenizer=tok, mesh=None, buckets=buckets)
    budget_mb = 4 * probe_eng.token_bytes * pd_max_len / 2**20
    del probe_eng

    def pd_args():
        return parse_cli([], base=Args(
            model="bert-tiny", decode_slots=pd_slots,
            decode_max_len=pd_max_len, max_new_tokens=pd_max_new,
            kv_page_sz=pd_page_sz, kv_hbm_mb=budget_mb,
            seed=args.seed))

    n_shared_storm = 60
    shared_prefix = rng.integers(5, tok.vocab_size, 32).tolist()
    # one warm stream carries the shared prefix through a full prefill
    # BEFORE the storm (the realistic shape: the prefix is indexed from
    # earlier traffic) — without it the opening claim burst is all-cold
    # and the concurrency comparison measures nothing but the cold pool
    warm_prompt = shared_prefix + rng.integers(5, tok.vocab_size,
                                               4).tolist()
    storm_prompts = []
    for i in range(n_shared_storm):
        if i % 5 == 4:      # 20%: unique, same total length
            storm_prompts.append(
                rng.integers(5, tok.vocab_size, 36).tolist())
        else:               # 80%: shared 32-token prefix, distinct tail
            storm_prompts.append(
                shared_prefix + rng.integers(5, tok.vocab_size,
                                             4).tolist())

    def pd_storm(engine):
        b = DecodeBatcher(engine, max_waiting=n_shared_storm).start()
        b.eos_id = -1
        b.warmup()
        r0 = engine.metrics.retraces.value
        m0 = engine.metrics.cache_misses.value
        # identical warm stream on BOTH layouts (the slot engine just
        # runs one extra stream, the paged engine also indexes the
        # shared prefix) so the storms stay apples-to-apples
        b.submit_ids(warm_prompt,
                     max_new_tokens=pd_max_new).result(timeout=600)
        ss = [b.submit_ids(p, max_new_tokens=pd_max_new)
              for p in storm_prompts]
        outs = [s.result(timeout=600) for s in ss]
        return b, outs, r0, m0

    slot_b, slot_outs, _, _ = pd_storm(
        DecodeEngine(pd_args(), tokenizer=tok, mesh=None,
                     buckets=buckets))
    slot_peak = slot_b.metrics.peak_live_streams.value
    slot_cap = slot_b.engine.slots
    slot_b.stop()

    paged_eng = PagedDecodeEngine(pd_args(), tokenizer=tok, mesh=None,
                                  buckets=buckets)
    paged_b, paged_outs, pd_r0, pd_m0 = pd_storm(paged_eng)
    paged_peak = paged_b.metrics.peak_live_streams.value
    # full-hit probe: prime the index with one post-drain submission
    # (registers the prompt — its storm-time entry may have been under
    # eviction pressure), then an exact repeat must emit its first token
    # WITHOUT a prefill forward (TTFT is then bounded by one decode-step
    # wait, by construction)
    paged_b.submit_ids(storm_prompts[0],
                       max_new_tokens=pd_max_new).result(timeout=600)
    pre0 = paged_b.metrics.prefills_total.value
    hs = paged_b.submit_ids(storm_prompts[0], max_new_tokens=pd_max_new)
    hit_out = hs.result(timeout=600)
    hit_prefills = paged_b.metrics.prefills_total.value - pre0
    hit_ttft_ms = (hs.first_token_at - hs.born) * 1e3
    pd_retraces = paged_eng.metrics.retraces.value - pd_r0
    pd_misses = paged_eng.metrics.cache_misses.value - pd_m0
    paged_snap = paged_b.snapshot()
    paged_b.stop()
    leak = paged_eng.leak_check()
    paged_eng.prefix.clear()
    drained_clean = (leak["ok"] and not leak["stream_owners"]
                     and paged_eng.allocator.free_pages
                     == paged_eng.n_pages)
    pd_parity = (paged_outs == slot_outs
                 and hit_out == slot_outs[0])

    # 2-replica paged kill: orphans re-prefill on the survivor,
    # re-attaching to ITS shared prefix pages under the same request id
    pengines = [PagedDecodeEngine(pd_args(), tokenizer=tok, mesh=None,
                                  buckets=buckets) for _ in range(2)]
    for e in pengines[1:]:
        e.tracer = pengines[0].tracer
    prouter = DecodeRouter(pengines,
                           max_waiting=n_shared_storm).start()
    for b in prouter.batchers:
        b.eos_id = -1
    prouter.warmup()
    pkstreams = [prouter.submit_ids(p, max_new_tokens=pd_max_new)
                 for p in storm_prompts]
    deadline = time.monotonic() + 120
    while (prouter.batchers[0].metrics.tokens_out_total.value
           < pd_max_new * 4 and time.monotonic() < deadline):
        time.sleep(0.002)
    prouter.kill(0)
    pkouts = [s.result(timeout=600) for s in pkstreams]
    pk_requeued = prouter.batchers[1].rmetrics.requeued_in.value
    prouter.stop()
    survivor = prouter.batchers[1].engine
    pk_leak = survivor.leak_check()
    pk_hits = survivor.prefix.snapshot()
    survivor.prefix.clear()
    pk_clean = (pk_leak["ok"] and not pk_leak["stream_owners"]
                and survivor.allocator.free_pages == survivor.n_pages)
    pk_parity = pkouts == slot_outs

    # ------------------------------ phase E: speculative decoding
    # Draft-k / verify-1 (ROADMAP item 3): the cheap model drafts k
    # tokens through its own paged cache, the primary scores all k+1
    # positions in ONE fixed-shape verify call, and the longest accepted
    # greedy prefix commits to both caches — bitwise identical to
    # primary-only decode by construction.  Everything measured here is
    # REAL machinery — draft rounds, the [slots, k+1] verify program,
    # two-owner page custody, acceptance, retrace/leak ledgers, the
    # drafter-death degrade, the controller's k law — except the COST
    # RATIO between the two models: with untrained weights a genuinely
    # cheap model never agrees with a different random model, and an
    # equal-cost drafter has nothing to amortize.  So the pair runs
    # identical-seed bert-tiny weights (the acceptance ceiling) while
    # every primary dispatch is padded to the MEASURED per-step cost of
    # a real bert-small engine on this host.  The >= 1.8x gate is then
    # the round algebra — (k+1) tokens for k cheap drafts plus one
    # primary-priced verify — surviving the implementation's real
    # bookkeeping overhead at an honest, host-calibrated ratio.
    spec_k = 6

    def step_cost_s(model):
        # median warmed [slots, 1] decode-step wall time (all-dead rows:
        # sentinel tables, no live page touched — compute is identical)
        e = PagedDecodeEngine(
            parse_cli([], base=Args(model=model, decode_slots=pd_slots,
                                    decode_max_len=pd_max_len,
                                    kv_page_sz=pd_page_sz)),
            tokenizer=tok, mesh=None, buckets=buckets)
        e.warmup_decode()
        tk = np.zeros((pd_slots,), np.int32)
        ps = np.zeros((pd_slots,), np.int32)
        samples = []
        for _ in range(30):
            t0 = time.perf_counter()
            np.asarray(e.decode_batch(tk, ps, live=0))
            samples.append(time.perf_counter() - t0)
        return float(np.median(samples))

    tiny_step_s = step_cost_s("bert-tiny")
    small_step_s = step_cost_s("bert-small")

    def pad_primary(engine):
        # applied AFTER warmup: compile time stays unpadded and the
        # retrace/cache-miss ledgers are untouched — only dispatch wall
        # time moves, up to the measured bert-small step cost
        for name in ("decode_batch", "verify_ids", "prefill_ids"):
            orig = getattr(engine, name)

            def padded(*a, _orig=orig, **kw):
                t0 = time.perf_counter()
                out = np.asarray(_orig(*a, **kw))
                lack = small_step_s - (time.perf_counter() - t0)
                if lack > 0:
                    time.sleep(lack)
                return out
            setattr(engine, name, padded)

    sargs = parse_cli([], base=Args(
        model="bert-tiny", decode_slots=pd_slots,
        decode_max_len=pd_max_len, max_new_tokens=max_new,
        kv_page_sz=pd_page_sz, seed=args.seed, trace=True,
        trace_dir=trace_dir))
    spec_trace = []   # the phase-local tracer, shared by every engine

    def spec_engine(prefix_share=True):
        e = PagedDecodeEngine(
            sargs, tokenizer=tok, mesh=None, buckets=buckets,
            tracer=(spec_trace[0] if spec_trace else None),
            prefix_share=prefix_share)
        if not spec_trace:
            spec_trace.append(e.tracer)
        return e

    # E1 — primary-only reference: same engine class, same prompts,
    # same padded primary cost, no drafter.  Its outputs are the
    # bitwise-parity reference AND the tokens/s denominator.
    ref_eng = spec_engine()
    ref_b = DecodeBatcher(ref_eng, max_waiting=n_streams).start()
    ref_b.eos_id = -1
    ref_b.warmup()
    pad_primary(ref_eng)
    t0 = time.monotonic()
    ref_streams = [ref_b.submit_ids(p, max_new_tokens=max_new)
                   for p in prompts]
    sp_refs = [s.result(timeout=600) for s in ref_streams]
    sp_base_sec = time.monotonic() - t0
    ref_b.stop()
    sp_base_tps = sum(len(o) for o in sp_refs) / sp_base_sec

    # E2 — the speculative pair through a 1-replica DecodeRouter (the
    # fleet wiring: paired drafter, draft_k knob, control surface)
    sp_eng = spec_engine()
    sp_dr = spec_engine(prefix_share=False)
    srouter = DecodeRouter([sp_eng], drafters=[sp_dr], draft_k=spec_k,
                           max_waiting=n_streams).start()
    sb = srouter.batchers[0]
    sb.eos_id = -1
    srouter.warmup()
    sp_r0 = sp_eng.metrics.retraces.value + sp_dr.metrics.retraces.value
    sp_m0 = (sp_eng.metrics.cache_misses.value
             + sp_dr.metrics.cache_misses.value)
    pad_primary(sp_eng)
    t0 = time.monotonic()
    sstreams = [srouter.submit_ids(p, max_new_tokens=max_new)
                for p in prompts]
    sp_outs = [s.result(timeout=600) for s in sstreams]
    sp_sec = time.monotonic() - t0
    sp_tps = sum(len(o) for o in sp_outs) / sp_sec
    sp_speedup = sp_tps / sp_base_tps
    sp_retraces = (sp_eng.metrics.retraces.value
                   + sp_dr.metrics.retraces.value - sp_r0)
    sp_misses = (sp_eng.metrics.cache_misses.value
                 + sp_dr.metrics.cache_misses.value - sp_m0)
    sp_parity = sp_outs == sp_refs
    sp_snap = sb.spec_snapshot()

    # E3 — mid-storm drafter kill: the pair must degrade to
    # primary-only decode (loud, decision-recorded), every stream still
    # emitting EXACTLY the reference tokens, both page ledgers clean
    ck_eng = spec_engine()
    ck_dr = spec_engine(prefix_share=False)
    crouter = DecodeRouter([ck_eng], drafters=[ck_dr], draft_k=spec_k,
                           max_waiting=n_streams).start()
    cb = crouter.batchers[0]
    cb.eos_id = -1
    crouter.warmup()
    pad_primary(ck_eng)
    ckstreams = [crouter.submit_ids(p, max_new_tokens=max_new)
                 for p in prompts]
    deadline = time.monotonic() + 120
    while (cb.metrics.tokens_out_total.value < pd_slots
           and time.monotonic() < deadline):
        time.sleep(0.002)
    crouter.kill_drafter(0)    # demonstrably mid-storm: tokens landed,
    ckouts = [s.result(timeout=600) for s in ckstreams]   # many to go
    ck_degraded = cb.drafter is None
    ck_deaths = int(cb.metrics.drafter_deaths_total.value)
    crouter.stop()
    ck_leaks = [ck_eng.leak_check(), ck_dr.leak_check()]
    ck_parity = ckouts == sp_refs

    # E4 — the controller's speculation law on an INJECTED acceptance
    # trajectory (the idle E2 pair is the actuation target, so every
    # knob turn lands on real batchers): sustained low acceptance must
    # halve k, catastrophic acceptance must switch speculation OFF, and
    # a forced re-enable that regresses spec_waste must auto-revert —
    # each move decision-recorded through ServeController._actuate.
    class _SpecInject:
        """Real router surface (``__getattr__`` delegation keeps every
        actuation on the recorded controller path) with a scripted
        draft/accept counter stream replacing live speculation."""

        def __init__(self, router):
            self._router = router
            self.drafted = 0
            self.accepted = 0

        def __getattr__(self, name):
            return getattr(self._router, name)

        def feed(self, rate, n=1000):
            self.drafted += n
            self.accepted += int(n * rate)

        def control_snapshot(self):
            snap = self._router.control_snapshot()
            snap["speculation"] = dict(
                snap.get("speculation") or {},
                draft_tokens=self.drafted,
                accepted_tokens=self.accepted)
            return snap

    shim = _SpecInject(srouter)
    clk = [0.0]
    ctrl = ServeController(shim, interval_s=1.0, tracer=spec_trace[0],
                           clock=lambda: clk[0])
    k_path = [int(srouter.knob_values()["draft_k"])]

    def ctick(rate=None, dt=1.0):
        clk[0] += dt
        if rate is not None:
            shim.feed(rate)
        ctrl.step()
        k_path.append(int(srouter.knob_values().get("draft_k", -1)))

    ctick()                    # primes the counter deltas
    ctick(0.20)                # sustained low acceptance ...
    ctick(0.20)                # ... halves k: 6 -> 3
    clk[0] += 6                # clear the draft_k cooldown
    ctick(0.20)
    ctick(0.20)                # 3 -> 1
    clk[0] += 6
    ctick(0.10)
    ctick(0.10)                # catastrophic: speculation OFF (0)
    ctick(0.90)                # good window -> spec_waste baseline
    ctrl.inject("draft_k", spec_k, "bench revert probe")
    for _ in range(12):        # mid-band acceptance: the law stays
        ctick(0.50)            # silent while spec_waste regresses
    sp_k_final = int(srouter.knob_values().get("draft_k", -1))
    sp_reverts = int(ctrl.reverts_total)
    ctrl.stop()                # resolves stragglers: outcome recorded
    srouter.stop()
    sp_leaks = [sp_eng.leak_check(), sp_dr.leak_check()]
    sp_pages_clean = all(lk["ok"] and not lk["stream_owners"]
                         for lk in sp_leaks + ck_leaks)

    # draft -> verify chain integrity through the FILE round trip, plus
    # every controller/degrade decision chain, from one flush
    spec_path = spec_trace[0].flush()
    srecords = []
    with open(spec_path) as f:
        for line in f:
            line = line.strip()
            if line:
                srecords.append(json.loads(line))
    sp_report = validate_chains(
        srecords,
        [s.rid for s in sstreams] + [s.rid for s in ckstreams])
    sp_decisions = validate_decisions(srecords)

    # --------------------- phase F: disaggregated prefill/decode pools
    # The isolation claim (ROADMAP item 4, DistServe/Splitwise): when
    # prefill is expensive, interleaving it with decode on ONE engine
    # stalls every live stream for the full prefill cost, so the
    # inter-token tail inherits that cost; a prefill pool handing
    # finished pages to a decode pool moves the work off the decode
    # path — decode units only IMPORT pages (a cheap fixed-shape
    # scatter), so their tail stays flat.  As in phase E the cost is
    # synthetic but honest: every prefill dispatch is padded by a fixed
    # df_pad_s AFTER warmup, on BOTH setups, and the storm is the same
    # on both — mixed prompt lengths with a per-stream max_new spread,
    # so completions desynchronise and admissions land mid-decode (the
    # interleaved engine then cannot hide the prefill behind idle
    # slots).  Socket transport: the wire framing is part of the
    # measured decode-pool path, not a best case.
    df_pad_s = 0.05
    df_n = 32
    df_prompts = prompts[:df_n]
    df_max_new = [int(x) for x in rng.integers(8, max_new + 1, df_n)]

    def pad_prefill(engine):
        # after warmup, like pad_primary: compile time and the
        # retrace/miss ledgers stay untouched, only dispatch wall time
        for name in ("prefill_ids", "prefill_chunk"):
            orig = getattr(engine, name)

            def padded(*a, _orig=orig, **kw):
                out = _orig(*a, **kw)
                time.sleep(df_pad_s)
                return out
            setattr(engine, name, padded)

    dargs = parse_cli([], base=Args(
        model="bert-tiny", decode_slots=pd_slots,
        decode_max_len=pd_max_len, max_new_tokens=max_new,
        kv_page_sz=pd_page_sz, seed=args.seed, trace=True,
        trace_dir=trace_dir))

    # F1 — interleaved control: one paged engine doing both jobs.  Its
    # outputs are also the parity reference (greedy decode is weight-
    # deterministic; the pools must reproduce it token for token).
    il_eng = PagedDecodeEngine(dargs, tokenizer=tok, mesh=None,
                               buckets=buckets)
    il_b = DecodeBatcher(il_eng, max_waiting=df_n).start()
    il_b.eos_id = -1
    il_b.warmup()
    il_r0 = il_eng.metrics.retraces.value
    il_m0 = il_eng.metrics.cache_misses.value
    pad_prefill(il_eng)
    il_streams = [il_b.submit_ids(p, max_new_tokens=mn)
                  for p, mn in zip(df_prompts, df_max_new)]
    il_outs = [s.result(timeout=600) for s in il_streams]
    il_snap = il_b.snapshot()
    il_b.stop()
    il_retraces = il_eng.metrics.retraces.value - il_r0
    il_misses = il_eng.metrics.cache_misses.value - il_m0
    il_leak = il_eng.leak_check()
    il_itok_p50 = il_snap["decode"]["intertoken_ms"]["p50"]
    il_itok_p99 = il_snap["decode"]["intertoken_ms"]["p99"]

    # F2 — the pool split: 1 prefill + 2 decode engines, same storm
    dengines = [PagedDecodeEngine(dargs, tokenizer=tok, mesh=None,
                                  buckets=buckets) for _ in range(3)]
    for e in dengines[1:]:
        e.tracer = dengines[0].tracer
    drouter = DisaggDecodeRouter(dengines, prefill_engines=1,
                                 max_waiting=df_n,
                                 transport="socket").start()
    for u in drouter._units:
        u.eos_id = -1
    drouter.warmup()
    df_r0 = sum(e.metrics.retraces.value for e in dengines)
    df_m0 = sum(e.metrics.cache_misses.value for e in dengines)
    for e in dengines:
        pad_prefill(e)  # decode units never call these — the point
    df_streams = [drouter.submit_ids(p, max_new_tokens=mn)
                  for p, mn in zip(df_prompts, df_max_new)]
    df_outs = [s.result(timeout=600) for s in df_streams]
    # snapshot BEFORE the kill leg: the isolation numbers are the
    # healthy storm's; PrefillWorker never records inter-token gaps, so
    # the merged latency block IS the decode pool's histogram
    df_snap = drouter.control_snapshot()
    df_itok_p50 = df_snap["latency"]["inter_token_p50_ms"]
    df_itok_p99 = df_snap["latency"]["inter_token_p99_ms"]
    df_ttft_p99 = df_snap["latency"]["ttft_p99_ms"]
    df_frames_ok = sum(s.frames_ok for s in drouter._servers.values())
    df_frames_err = sum(s.frames_err for s in drouter._servers.values())
    df_parity = df_outs == il_outs

    # F3 — mid-storm decode-replica kill on the WARM router (the prefix
    # index is hot from F2, so re-submitted prompts take the full-hit
    # handoff path: COW-source custody rides the boundary too).  The
    # victim's orphans re-home through the front door — re-prefill,
    # second handoff — and must still emit exactly the reference tokens.
    dk_n = 24
    dk_v0 = int(drouter._units[1].metrics.tokens_out_total.value)
    dk_streams = [drouter.submit_ids(p, max_new_tokens=mn)
                  for p, mn in zip(df_prompts[:dk_n], df_max_new[:dk_n])]
    deadline = time.monotonic() + 120
    while (int(drouter._units[1].metrics.tokens_out_total.value)
           < dk_v0 + 5 and time.monotonic() < deadline):
        time.sleep(0.002)
    drouter.kill(1, RuntimeError("bench decode-pool chaos"))
    dk_outs = [s.result(timeout=600) for s in dk_streams]
    dk_parity = dk_outs == il_outs[:dk_n]
    df_retraces = sum(e.metrics.retraces.value for e in dengines) - df_r0
    df_misses = (sum(e.metrics.cache_misses.value for e in dengines)
                 - df_m0)
    df_health = drouter.health_summary()
    drouter.stop()
    # survivor ledgers only: the victim's allocator died with its cache
    # (the established kill contract — see the paged kill storm above)
    df_leaks = {i: dengines[i].leak_check() for i in (0, 2)}
    df_clean = all(lk["ok"] and not lk["stream_owners"]
                   for lk in list(df_leaks.values()) + [il_leak])

    # pool-boundary chain integrity through the FILE round trip
    df_path = dengines[0].tracer.flush()
    dfrecords = []
    with open(df_path) as f:
        for line in f:
            line = line.strip()
            if line:
                dfrecords.append(json.loads(line))
    df_report = validate_chains(
        dfrecords,
        [s.rid for s in df_streams] + [s.rid for s in dk_streams])

    # ------------------------------------------------------------- gates
    if speedup < 2.0:
        failures.append(f"decode tokens/s/chip only {speedup:.2f}x the "
                        "re-prefill baseline (gate: >= 2x)")
    if retraces_post != 0 or misses_post != 0:
        failures.append(f"{retraces_post} post-warmup retraces / "
                        f"{misses_post} compile-cache misses (gate: 0)")
    if kill_retraces != 0:
        failures.append(f"{kill_retraces} retraces in the kill storm "
                        "(gate: 0 — both replicas warmed)")
    if intertoken_p99 is None or intertoken_p99 > p99_budget:
        failures.append(f"inter-token p99 {intertoken_p99} ms over the "
                        f"{p99_budget} ms budget")
    if occupancy_mean is None or occupancy_mean < 0.8:
        failures.append(f"mean slot occupancy {occupancy_mean} under the "
                        "0.8 continuous-batching gate")
    if not parity_ok:
        failures.append("re-prefill baseline diverged from cached decode "
                        "(argmax) — the speedup comparison is invalid")
    if not kill_parity:
        failures.append("mid-storm kill duplicated or lost tokens "
                        "(continuations != single-engine reference)")
    if report["incomplete"]:
        failures.append(f"{len(report['incomplete'])} incomplete hop "
                        "chains through the kill storm")
    if report["requeued"] < 1 or report["re_prefilled"] < 1:
        failures.append("the kill never exercised requeue/re-prefill — "
                        "the chaos leg proved nothing")
    if paged_peak < 3 * slot_peak:
        failures.append(
            f"paged layout peaked at {paged_peak} concurrent streams vs "
            f"{slot_peak} for the slot layout at equal --kv_hbm_mb "
            "(gate: >= 3x on the 80%-shared mix)")
    if not pd_parity:
        failures.append("paged storm diverged from the slot-cache "
                        "baseline (greedy continuations must be "
                        "token-identical)")
    if hit_prefills != 0:
        failures.append(f"full prefix hit ran {hit_prefills} prefill "
                        "forward(s) (gate: structurally zero)")
    if pd_retraces != 0 or pd_misses != 0:
        failures.append(f"{pd_retraces} retraces / {pd_misses} compile "
                        "misses on the paged path post-warmup (gate: 0)")
    if not drained_clean:
        failures.append(f"paged storm leaked pages at drain: {leak}")
    if not pk_parity:
        failures.append("paged kill storm duplicated or lost tokens "
                        "(re-prefilled survivors must match the "
                        "slot-cache baseline)")
    if pk_requeued < 1:
        failures.append("the paged kill never requeued a stream — the "
                        "re-attach leg proved nothing")
    if not pk_clean:
        failures.append(f"paged kill storm leaked pages on the "
                        f"survivor: {pk_leak}")
    if sp_speedup < 1.8:
        failures.append(
            f"speculative decode only {sp_speedup:.2f}x primary-only "
            "tokens/s (gate: >= 1.8x at the calibrated "
            f"{small_step_s / tiny_step_s:.1f}x primary/drafter cost "
            "ratio)")
    if not sp_parity:
        failures.append("speculative decode diverged from primary-only "
                        "(greedy verify must be BITWISE identical)")
    if sp_retraces != 0 or sp_misses != 0:
        failures.append(f"{sp_retraces} retraces / {sp_misses} compile "
                        "misses across the speculation pair post-warmup "
                        "(gate: 0 — drafter decode, verify, commit all "
                        "warmed)")
    if not sp_pages_clean:
        failures.append("speculation legs leaked pages: "
                        f"pair={sp_leaks} kill={ck_leaks}")
    if not ck_degraded or ck_deaths < 1:
        failures.append("mid-storm drafter kill never degraded the pair "
                        "to primary-only (the chaos leg proved nothing)")
    if not ck_parity:
        failures.append("drafter-kill continuations diverged from the "
                        "primary-only reference (degrade must preserve "
                        "exact tokens)")
    if sp_report["incomplete"]:
        failures.append(f"{len(sp_report['incomplete'])} incomplete hop "
                        "chains through the speculation storms")
    if sp_report["speculated"] < 1 or not sp_report["accept_rate"]:
        failures.append("trace round trip shows no speculated chains — "
                        "the draft/verify hops never reached the file")
    if not (3 in k_path and 0 in k_path):
        failures.append(f"controller never adapted k on the injected "
                        f"low-acceptance stream (k path {k_path})")
    if sp_reverts < 1 or sp_k_final != 0:
        failures.append(f"regressing re-enable was not auto-reverted "
                        f"(reverts={sp_reverts}, draft_k={sp_k_final})")
    if sp_decisions["incomplete"]:
        failures.append(f"{len(sp_decisions['incomplete'])} incomplete "
                        "decision chains (every actuation needs action "
                        "-> outcome)")
    if sp_decisions["by_knob"].get("draft_k", 0) < 3:
        failures.append("fewer than 3 draft_k decisions recorded — the "
                        "adaptation demo did not go through _actuate")
    df_pad_ms = df_pad_s * 1e3
    if il_itok_p99 is None or il_itok_p99 < df_pad_ms:
        failures.append(
            f"interleaved control inter-token p99 {il_itok_p99} ms never "
            f"inherited the {df_pad_ms:.0f} ms prefill pad — the "
            "isolation comparison measured nothing")
    if df_itok_p99 is None or df_itok_p99 >= df_pad_ms:
        failures.append(
            f"disaggregated decode-pool inter-token p99 {df_itok_p99} ms "
            f"not isolated from the {df_pad_ms:.0f} ms prefill pad "
            "(gate: decode units must never eat a prefill)")
    if not df_parity:
        failures.append("disaggregated storm diverged from the "
                        "interleaved reference (pool split must be "
                        "token-invisible)")
    if not dk_parity:
        failures.append("decode-replica kill duplicated or lost tokens "
                        "(re-homed orphans must match the interleaved "
                        "reference)")
    if df_retraces != 0 or df_misses != 0 or il_retraces != 0 \
            or il_misses != 0:
        failures.append(
            f"disagg phase retraced post-warmup (pools {df_retraces}/"
            f"{df_misses}, interleaved {il_retraces}/{il_misses}; "
            "gate: 0 — every engine warms both roles)")
    if df_frames_err != 0 or df_frames_ok < df_n:
        failures.append(
            f"socket handoff frames ok={df_frames_ok} err="
            f"{df_frames_err} (gate: every healthy-storm stream crosses "
            "the wire cleanly)")
    if df_report["incomplete"]:
        failures.append(f"{len(df_report['incomplete'])} incomplete hop "
                        "chains through the disaggregated storms")
    if df_report["handed_off"] != df_n + dk_n:
        failures.append(
            f"{df_report['handed_off']}/{df_n + dk_n} chains crossed "
            "the pool boundary via a handoff hop (gate: all of them)")
    if df_report["requeued"] < 1 or df_report["re_prefilled"] < 1:
        failures.append("the decode-pool kill never requeued/"
                        "re-prefilled a stream — the recovery leg "
                        "proved nothing")
    if not df_clean:
        failures.append("disagg phase leaked pages: "
                        f"survivors={df_leaks} interleaved={il_leak}")

    result = {
        "metric": "decode_smoke",
        "streams": n_streams,
        "slots": engine.slots,
        "max_new_tokens": max_new,
        "prompt_lens": [int(lens.min()), int(lens.max())],
        "decode": {
            "tokens_out": int(tokens_out),
            "elapsed_sec": round(decode_sec, 3),
            "tokens_per_sec_per_chip": round(decode_tps_chip, 1),
            "intertoken_ms_p50": snap["decode"]["intertoken_ms"]["p50"],
            "intertoken_ms_p99": intertoken_p99,
            "ttft_ms_p50": snap["decode"]["ttft_ms"]["p50"],
            "slot_occupancy_mean": occupancy_mean,
            "slot_reuse_ms_p50": snap["replica"]["slot_reuse_ms"]["p50"],
            "retraces_post_warmup": int(retraces_post),
            "kv": snap["kv"],
        },
        "reprefill_baseline": {
            "tokens_out": int(base_tokens),
            "elapsed_sec": round(baseline_sec, 3),
            "tokens_per_sec_per_chip": round(baseline_tps_chip, 1),
            "argmax_parity_with_cached": bool(parity_ok),
        },
        "speedup_vs_reprefill": round(speedup, 2),
        "kill_storm": {
            "replicas": 2,
            "token_parity_with_reference": bool(kill_parity),
            "retraces": int(kill_retraces),
            "requeued_to_survivor": int(requeued_in),
            "chains_checked": report["checked"],
            "chains_complete": report["complete"],
            "chains_requeued": report["requeued"],
            "chains_re_prefilled": report["re_prefilled"],
        },
        "paged_storm": {
            "streams": n_shared_storm,
            "shared_fraction": 0.8,
            "shared_prefix_tokens": len(shared_prefix),
            "page_sz": pd_page_sz,
            "kv_hbm_mb": round(budget_mb, 3),
            "slot_layout_slots": int(slot_cap),
            "slot_peak_live": int(slot_peak),
            "paged_pages": int(paged_eng.n_pages),
            "paged_peak_live": int(paged_peak),
            "concurrency_gain": round(paged_peak / max(slot_peak, 1), 2),
            "token_parity_with_slot_baseline": bool(pd_parity),
            "full_hit_prefill_forwards": int(hit_prefills),
            "full_hit_ttft_ms": round(hit_ttft_ms, 2),
            "retraces_post_warmup": int(pd_retraces),
            "pages": paged_snap["kv"]["pages"],
            "prefix": paged_snap["kv"]["prefix"],
            "leak_check": leak,
            "kill": {
                "replicas": 2,
                "token_parity_with_slot_baseline": bool(pk_parity),
                "requeued_to_survivor": int(pk_requeued),
                "survivor_prefix_hits": pk_hits,
                "survivor_leak_check": pk_leak,
            },
        },
        "speculation": {
            "draft_k": spec_k,
            "streams": n_streams,
            "max_new_tokens": max_new,
            "drafter_model": "bert-tiny",
            "primary_cost_model": "bert-small",
            "drafter_step_ms": round(tiny_step_s * 1e3, 3),
            "primary_step_ms": round(small_step_s * 1e3, 3),
            "cost_ratio": round(small_step_s / tiny_step_s, 2),
            "primary_only_tokens_per_sec": round(sp_base_tps, 1),
            "speculative_tokens_per_sec": round(sp_tps, 1),
            "speedup": round(sp_speedup, 2),
            "accept_rate": round(sp_snap["accept_rate"], 4),
            "rounds": sp_snap["rounds"],
            "draft_tokens": sp_snap["draft_tokens"],
            "accepted_tokens": sp_snap["accepted_tokens"],
            "token_parity_with_primary_only": bool(sp_parity),
            "retraces_post_warmup": int(sp_retraces),
            "compile_misses_post_warmup": int(sp_misses),
            "leak_checks": sp_leaks,
            "chains": {"checked": sp_report["checked"],
                       "complete": sp_report["complete"],
                       "speculated": sp_report["speculated"],
                       "accept_rate": sp_report["accept_rate"]},
            "drafter_kill": {
                "degraded_to_primary_only": bool(ck_degraded),
                "drafter_deaths": ck_deaths,
                "token_parity_with_primary_only": bool(ck_parity),
                "leak_checks": ck_leaks,
            },
            "controller": {
                "k_path": k_path,
                "final_draft_k": sp_k_final,
                "reverts": sp_reverts,
                "decisions_checked": sp_decisions["checked"],
                "decisions_complete": sp_decisions["complete"],
                "decisions_by_knob": sp_decisions["by_knob"],
            },
        },
        "disaggregation": {
            "engines": len(dengines),
            "pools": df_snap["by_pool"],
            "transport": "socket",
            "streams": df_n,
            "prefill_pad_ms": round(df_pad_ms, 1),
            "interleaved_intertoken_ms_p50": il_itok_p50,
            "interleaved_intertoken_ms_p99": il_itok_p99,
            "decode_pool_intertoken_ms_p50": df_itok_p50,
            "decode_pool_intertoken_ms_p99": df_itok_p99,
            "decode_pool_ttft_ms_p99": df_ttft_p99,
            "isolation_gain_p99": round(
                il_itok_p99 / df_itok_p99, 2) if df_itok_p99 else None,
            "token_parity_with_interleaved": bool(df_parity),
            "frames_ok": int(df_frames_ok),
            "frames_err": int(df_frames_err),
            "retraces_post_warmup": int(df_retraces),
            "handoffs": int(df_health["handoffs"]),
            "handoff_failures": int(df_health["handoff_failures"]),
            "chains": {"checked": df_report["checked"],
                       "complete": df_report["complete"],
                       "handed_off": df_report["handed_off"],
                       "requeued": df_report["requeued"],
                       "re_prefilled": df_report["re_prefilled"]},
            "kill": {
                "victim_pool": "decode",
                "streams": dk_n,
                "token_parity_with_interleaved": bool(dk_parity),
            },
            "survivor_leak_checks": {str(i): lk
                                     for i, lk in df_leaks.items()},
        },
        "p99_budget_ms": p99_budget,
        "model": args.model,
        "kv_dtype": engine.kv_snapshot()["kv_dtype"],
        "devices": n_chips,
        "platform": jax.devices()[0].platform,
        "gates": {
            "speedup_ge_2x": speedup >= 2.0,
            "zero_post_warmup_retraces": retraces_post == 0
            and misses_post == 0 and kill_retraces == 0,
            "intertoken_p99_under_budget": bool(
                intertoken_p99 is not None
                and intertoken_p99 <= p99_budget),
            "slot_occupancy_ge_0.8": bool(occupancy_mean is not None
                                          and occupancy_mean >= 0.8),
            "kill_chains_complete_no_dup_no_loss": bool(
                kill_parity and not report["incomplete"]),
            "paged_concurrency_ge_3x": bool(paged_peak >= 3 * slot_peak),
            "paged_token_parity": bool(pd_parity and pk_parity),
            "paged_full_hit_zero_prefill": hit_prefills == 0,
            "paged_zero_post_warmup_retraces": bool(
                pd_retraces == 0 and pd_misses == 0),
            "paged_zero_leaked_pages": bool(drained_clean and pk_clean),
            "spec_speedup_ge_1.8x": bool(sp_speedup >= 1.8),
            "spec_token_parity": bool(sp_parity and ck_parity),
            "spec_zero_post_warmup_retraces": bool(
                sp_retraces == 0 and sp_misses == 0),
            "spec_zero_leaked_pages": bool(sp_pages_clean),
            "spec_chains_complete": bool(
                not sp_report["incomplete"]
                and sp_report["speculated"] >= 1),
            "spec_controller_adapts_k": bool(
                3 in k_path and 0 in k_path and sp_reverts >= 1
                and sp_k_final == 0),
            "spec_decision_chains_complete": bool(
                not sp_decisions["incomplete"]
                and sp_decisions["by_knob"].get("draft_k", 0) >= 3),
            "disagg_decode_p99_isolated": bool(
                il_itok_p99 is not None and df_itok_p99 is not None
                and il_itok_p99 >= df_pad_ms
                and df_itok_p99 < df_pad_ms),
            "disagg_token_parity": bool(df_parity and dk_parity),
            "disagg_zero_post_warmup_retraces": bool(
                df_retraces == 0 and df_misses == 0
                and il_retraces == 0 and il_misses == 0),
            "disagg_wire_frames_clean": bool(
                df_frames_err == 0 and df_frames_ok >= df_n),
            "disagg_chains_complete_all_handed_off": bool(
                not df_report["incomplete"]
                and df_report["handed_off"] == df_n + dk_n),
            "disagg_kill_requeues_through_front_door": bool(
                df_report["requeued"] >= 1
                and df_report["re_prefilled"] >= 1),
            "disagg_zero_leaked_pages": bool(df_clean),
        },
        "failures": failures,
    }
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2)
        os.replace(tmp, out_path)
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("decode", "reprefill_baseline",
                                   "paged_storm", "speculation",
                                   "disaggregation")}))
    if failures:
        sys.exit("decode smoke FAILED:\n  - " + "\n  - ".join(failures)
                 + f"\n  see {out_path}")


def serve_load_smoke(argv) -> None:
    """``--serve-load``: closed-loop SLO gate for the multi-replica router.

    A Poisson arrival storm (``--serve_load_qps``, mixed lengths spanning
    3 buckets) is driven through a :class:`ReplicaRouter` over
    ``--serve_load_replicas`` engines while the smoke injects the failures
    the router exists to survive:

    - **mid-storm replica kill** (worker dies, beats stop — the SIGKILL
      shape at replica granularity): the router must eject it, requeue its
      queued + in-flight requests onto survivors, and — after the smoke
      relaunches it — reintegrate it only after a fresh bucket warmup;
    - **mid-storm rolling checkpoint swap**: one replica drained + swapped
      at a time, under load, with ZERO post-warmup retraces;
    - **an overload burst** (short deadlines, arrival >> service) that must
      walk ALL admission tiers: backpressure waits, shed-lowest-slack, and
      hard rejects, each recorded per tier.

    Then a **packed phase** (PR 9): the same seeded short-request storm
    (every request well under 64 tokens — the Chinese-emotion query shape)
    run CLOSED-LOOP twice over fresh pools, once padded
    (``serve_pack=off``) and once packed (``serve_pack=on``), with a
    mid-storm replica kill + relaunch on the packed run.  Gates: packed
    real-token throughput >= ``--serve_pack_ratio`` x the padded path,
    per-request logit parity between the runs (exact argmax where the
    padded top-2 margin is meaningful, max |diff| under 1e-3), token-level
    fill >= ``--serve_pack_fill``, ZERO post-warmup retraces on both pools
    (the packed path holds ONE compiled shape), and zero lost accepted
    requests through the kill.

    The storm runs TRACED (PR 10): every request mints a ``request_id``
    at admission and records hops through queue, pack placement,
    dispatch, eject-time requeue/re-pack and completion — and the smoke
    gates that every accepted request's hop chain is COMPLETE
    (reconstructable by ``trace_tpu.py request <id>``: one admit, one
    terminal, nothing after it), including at least one packed-phase request
    that crossed the mid-storm kill via re-pack.

    Gates (non-zero exit on any violation): zero LOST accepted requests (a
    request may succeed or deadline-fail, never vanish or surface a replica
    error), p99 latency at the target QPS under ``--serve_load_p99_ms``,
    zero post-warmup retraces across the pool, ejection-to-recovery under
    ``--serve_load_recovery_s``, a completed rolling swap with zero
    rollbacks, every admission tier engaged during the burst, complete
    hop chains incl. >=1 re-packed through the kill, and the packed-phase
    gates above.
    Snapshot: ``results/serve_load_smoke.json``.  Deterministic and
    CPU-safe like ``--serve`` (synthesized texts, seeded arrivals).
    """
    import random
    import tempfile
    import threading
    import time

    import jax

    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
    from pdnlp_tpu.serve import (
        InferenceEngine, LoadShedError, QueueFullError, ReplicaRouter,
    )
    from pdnlp_tpu.serve.batcher import DeadlineExceeded
    from pdnlp_tpu.train import checkpoint as ckpt_mod
    from pdnlp_tpu.utils.config import Args, parse_cli, pop_cli_flag

    argv, n_requests = pop_cli_flag(argv, "--serve_load_requests", 240, int)
    argv, qps = pop_cli_flag(argv, "--serve_load_qps", 120.0, float)
    argv, n_replicas = pop_cli_flag(argv, "--serve_load_replicas", 3, int)
    argv, p99_budget = pop_cli_flag(argv, "--serve_load_p99_ms", 1500.0,
                                    float)
    argv, recovery_bound = pop_cli_flag(argv, "--serve_load_recovery_s",
                                        20.0, float)
    argv, deadline_ms = pop_cli_flag(argv, "--serve_load_deadline_ms",
                                     8000.0, float)
    # 3600 requests: long enough that steady-state budget flushes dominate
    # the fill/throughput numbers over the timing-driven partials (ramp,
    # kill hop, tail) — the gates need headroom on a loaded CI host, not
    # a photo finish
    argv, pack_n = pop_cli_flag(argv, "--serve_pack_requests", 3600, int)
    argv, pack_ratio_floor = pop_cli_flag(argv, "--serve_pack_ratio", 1.5,
                                          float)
    argv, pack_fill_floor = pop_cli_flag(argv, "--serve_pack_fill", 0.85,
                                         float)
    argv, out_path = pop_cli_flag(
        argv, "--serve_load_out",
        os.path.join("results", "serve_load_smoke.json"))
    from pdnlp_tpu.obs.export import load_records
    from pdnlp_tpu.obs.request import chains, validate_chains

    # bert-tiny default (like --kernels): the gate measures ROUTER behavior
    # — ejection, requeue, tiers, swap — not model throughput; a bigger
    # model only slows the chaos loop without sharpening any assertion.
    # Tracing is ON: the hop-chain gate reconstructs every accepted
    # request's life from the flushed span files.
    # jaxlint: disable=L1 — the hop-chain gate reads this dir after the run
    trace_dir = tempfile.mkdtemp(prefix="pdnlp-serve-load-trace-")
    args = parse_cli(argv, base=Args(model="bert-tiny", trace=True,
                                     trace_dir=trace_dir))

    # deterministic mixed-length traffic across the 32/64/128 buckets
    chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐高兴悲伤讨厌愤怒"
    rng = random.Random(args.seed)
    lengths = [10, 24, 48, 60, 100, 120]
    texts = ["".join(rng.choice(chars)
                     for _ in range(lengths[i % len(lengths)]))
             for i in range(n_requests)]
    if os.path.exists(args.data_path) or os.path.exists(args.vocab_path):
        from pdnlp_tpu.data.tokenizer import get_or_build_vocab

        tok = WordPieceTokenizer(get_or_build_vocab(args))
    else:
        tok = WordPieceTokenizer(build_vocab(texts, size=256))

    buckets = (32, 64, 128)
    batch_size = 8
    max_queue = 64
    # one mesh slice per replica when the host has the devices; otherwise
    # independent plain-jit engines (the CPU-test shape)
    devices = list(jax.devices())
    per = len(devices) // n_replicas
    groups = [None] * n_replicas
    if per >= 1 and len(devices) >= n_replicas > 1:
        from pdnlp_tpu.parallel import make_mesh

        groups = [make_mesh(devices=devices[i * per:(i + 1) * per])
                  for i in range(n_replicas)]

    def factory(index: int) -> InferenceEngine:
        return InferenceEngine(args, tokenizer=tok, mesh=groups[index])

    engines = [factory(i) for i in range(n_replicas)]
    ckpt_path = ckpt_mod.latest(args.output_dir)
    if ckpt_path:
        try:
            for e in engines:
                e.load_checkpoint(ckpt_path)
        except Exception as exc:  # noqa: BLE001 — init weights are fine
            print(f"checkpoint {ckpt_path} not loadable ({exc}); "
                  "serving init weights", file=sys.stderr)
            ckpt_path = None
    # the main storm/burst pins the PADDED path: its tier gates (burst
    # sized at max_queue*3 REQUESTS) are calibrated in request units, and
    # on TPU `auto` would resolve packed and rescale admission to token
    # units out from under them — the packed phase below pins its own
    # modes explicitly
    router = ReplicaRouter(
        engines, engine_factory=factory, buckets=buckets,
        max_batch_size=batch_size, max_wait_ms=5.0, max_queue=max_queue,
        backpressure_wait_ms=10.0, default_deadline_ms=deadline_ms,
        serve_pack="off",
        stall_timeout=2.0, poll_interval=0.05, checkpoint_path=ckpt_path)
    router.start()
    if not router.wait_ready(600):
        sys.exit("serve-load smoke FAILED: replicas never finished warmup")

    # the rolling-swap artifact: the pool's own weights, re-published
    # through the manifest path (same shapes -> swap must not retrace)
    # jaxlint: disable=L1 — swap artifact must outlive the swap thread
    swap_dir = tempfile.mkdtemp(prefix="pdnlp-serve-load-")
    swap_path = os.path.join(swap_dir, "swap-cls.msgpack")
    ckpt_mod.save_params(swap_path,
                         {"params": jax.device_get(router.engine(0).params)})

    victim = n_replicas - 1
    kill_at, swap_at, relaunch_at = (n_requests // 3, n_requests // 2,
                                     (2 * n_requests) // 3)
    outcomes = {"ok": 0, "deadline": 0, "shed": 0, "rejected": 0,
                "lost": 0}
    swap_report: dict = {}
    swap_thread = None
    futs = []
    storm_t0 = time.monotonic()
    t_next = time.monotonic()
    for i in range(n_requests):
        if i == kill_at:
            # strand real work on the victim: a quick unpaced burst fills
            # every replica's queues, THEN the kill lands — the zero-lost
            # gate must cover requeued + retried requests, not an idle
            # replica's no-op death.  Guarded like every other submit: on
            # a slow host the backlog may already sit in the shed/reject
            # band, and that is an outcome to record, not a crash
            for j in range(2 * batch_size * n_replicas):
                try:
                    futs.append(router.submit(texts[(i + j) % len(texts)]))
                except LoadShedError:
                    outcomes["shed"] += 1
                except QueueFullError:
                    outcomes["rejected"] += 1
            router.kill_replica(victim, "crash")
        if i == relaunch_at:
            # the monitor needs one poll tick to classify the crash; the
            # relaunch API refuses to replace a live replica
            t_eject = time.monotonic() + 5.0
            while router.states[victim] != "ejected" \
                    and time.monotonic() < t_eject:
                time.sleep(0.01)
            router.relaunch(victim)
        if i == swap_at:
            # the rolling swap drains replicas one at a time — it must
            # run UNDER load, so it rides its own thread while arrivals
            # keep coming
            swap_thread = threading.Thread(
                target=lambda: swap_report.update(
                    router.swap_checkpoint(swap_path)))
            swap_thread.start()
        t_next += rng.expovariate(qps)  # Poisson arrivals at the target QPS
        time.sleep(max(0.0, t_next - time.monotonic()))
        try:
            futs.append(router.submit(texts[i]))
        except LoadShedError:
            outcomes["shed"] += 1
        except QueueFullError:
            outcomes["rejected"] += 1
    for f in futs:
        try:
            f.result(timeout=60)
            outcomes["ok"] += 1
        except DeadlineExceeded:
            outcomes["deadline"] += 1
        except LoadShedError:  # accepted, then shed while queued once the
            outcomes["shed"] += 1  # pool hit the shed band — by design
        except Exception:  # noqa: BLE001 — replica error/timeout = LOST
            outcomes["lost"] += 1
    if swap_thread is not None:
        swap_thread.join(timeout=60)
    storm_elapsed = time.monotonic() - storm_t0
    achieved_qps = len(futs) / storm_elapsed
    p99 = router.metrics.request_latency_ms.percentile(99)
    # the relaunched replica's warmup (fresh engine -> fresh compiles) may
    # outlast the storm tail; reintegration must COMPLETE before the gates
    # read recovery/reintegration counters
    if not router.wait_ready(300):
        sys.exit("serve-load smoke FAILED: relaunched replica never "
                 "finished its reintegration warmup")
    recovery = router.metrics.recovery_sec.snapshot()

    # ---- overload burst: every admission tier must engage + record ----
    burst_n = max_queue * 3
    burst_outcomes = {"ok": 0, "deadline": 0, "shed": 0, "rejected": 0,
                      "lost": 0}
    burst_lock = threading.Lock()
    burst_rids: list = []  # accepted burst requests join the chain gate

    def burster(k: int) -> None:
        fs = []
        for j in range(burst_n // 3):
            # every 3rd arrival carries a deadline under the shed tier's
            # slack floor: once the pool is in the shed band, those are
            # the lowest-slack requests and must be shed first
            dl = 8.0 if j % 3 == 0 else 150.0
            try:
                fs.append(router.submit(texts[(k + j) % len(texts)],
                                        deadline_ms=dl))
            except LoadShedError:
                with burst_lock:
                    burst_outcomes["shed"] += 1
            except QueueFullError:
                with burst_lock:
                    burst_outcomes["rejected"] += 1
        with burst_lock:
            burst_rids.extend(f.rid for f in fs)
        for f in fs:
            try:
                f.result(timeout=30)
                key = "ok"
            except DeadlineExceeded:
                key = "deadline"
            except LoadShedError:
                key = "shed"
            except Exception:  # noqa: BLE001
                key = "lost"
            with burst_lock:
                burst_outcomes[key] += 1

    bursters = [threading.Thread(target=burster, args=(k,))
                for k in range(3)]
    for t in bursters:
        t.start()
    for t in bursters:
        t.join(timeout=120)

    snap = router.snapshot()
    router.stop(drain=False)
    adm = snap["router"]["admission"]
    retraces_post = router.retraces_post_warmup

    # ---- hop-chain gate, storm half: flush the span file and validate
    # every ACCEPTED request's chain through the same offline path
    # `trace_tpu.py request <id>` uses (file round trip included)
    tracer = engines[0].tracer
    storm_trace = tracer.flush()
    storm_records = load_records(storm_trace)
    storm_rids = [f.rid for f in futs] + burst_rids
    storm_chains = validate_chains(storm_records, storm_rids)
    storm_chains["incomplete"] = dict(
        list(storm_chains["incomplete"].items())[:5])  # bounded report
    tracer.clear()  # the packed phases validate their own windows

    # ---- packed phase: short-request storm, packed vs padded pools ----
    # the throughput half of ROADMAP item 1: every request is well under
    # 64 tokens (the dominant production shape), so the padded path burns
    # most of each forward on [PAD] while the packed path bin-packs many
    # requests per 128-token row.  Closed-loop (window-bounded) submission
    # over the SAME seeded request sequence measures pool capacity; the
    # packed run also absorbs a mid-storm kill + relaunch.
    prng = random.Random(args.seed + 1)
    short_lengths = [4, 7, 10, 14, 18, 22]  # chars -> ~6..24 tokens
    ptexts = ["".join(prng.choice(chars)
                      for _ in range(short_lengths[i % len(short_lengths)]))
              for i in range(pack_n)]
    pids = [tok.encode_ids(t, max(buckets)) for t in ptexts]
    pack_tokens = sum(len(i) for i in pids)
    mean_tok = pack_tokens / max(1, len(pids))

    def run_pack_storm(mode: str, kill: bool) -> dict:
        tracer.clear()  # this phase's chain gate reads its own window
        engines2 = [factory(i) for i in range(n_replicas)]
        flush_tokens = engines2[0].pad_rows(batch_size) * max(buckets)
        if mode == "on":  # window ~= 2 packed flushes per replica, in
            per_rep = max(1, int(flush_tokens / mean_tok))  # request units
        else:
            per_rep = engines2[0].pad_rows(batch_size)
        window = 2 * n_replicas * per_rep
        # a 25ms age bound (vs the storm's 5ms): the phase is deadline-
        # free and throughput-gated, so partial aged flushes at the ramp,
        # the kill hop, and the tail should not eat the fill number
        r2 = ReplicaRouter(
            engines2, engine_factory=factory, buckets=buckets,
            max_batch_size=batch_size, max_wait_ms=25.0,
            max_queue=4 * window, serve_pack=mode, stall_timeout=2.0,
            poll_interval=0.05, checkpoint_path=ckpt_path)
        r2.start()
        if not r2.wait_ready(600):
            sys.exit(f"serve-load smoke FAILED: packed-phase pool "
                     f"(serve_pack={mode}) never finished warmup")
        victim2 = n_replicas - 1
        kill_at, relaunch_at = pack_n // 3, (2 * pack_n) // 3
        from collections import deque

        futs2: list = [None] * pack_n
        rids2: list = []
        inflight: deque = deque()
        lost = 0
        t0 = time.monotonic()
        for i, ids in enumerate(pids):
            if kill and i == kill_at:
                r2.kill_replica(victim2, "crash")
            if kill and i == relaunch_at:
                t_eject = time.monotonic() + 5.0
                while r2.states[victim2] != "ejected" \
                        and time.monotonic() < t_eject:
                    time.sleep(0.01)
                r2.relaunch(victim2)
            # deadline-free submits: the admission ladder never sheds
            # deadline-free work, so every request must complete — any
            # exception (queue-full would mean a mis-sized window) is LOST
            futs2[i] = r2.submit_ids(list(ids))
            rids2.append(futs2[i].rid)
            inflight.append(i)
            while len(inflight) >= window:
                j = inflight.popleft()
                try:
                    futs2[j] = futs2[j].result(timeout=120)
                except Exception:  # noqa: BLE001
                    futs2[j] = None
        while inflight:
            j = inflight.popleft()
            try:
                futs2[j] = futs2[j].result(timeout=120)
            except Exception:  # noqa: BLE001
                futs2[j] = None
        elapsed = time.monotonic() - t0
        lost = sum(1 for f in futs2 if f is None)
        if kill and not r2.wait_ready(300):
            sys.exit("serve-load smoke FAILED: packed-phase relaunch "
                     "never finished its reintegration warmup")
        snap2 = r2.snapshot()
        fills = [s["fill_ratio"] for s in snap2["replicas"].values()]
        fill_n = sum(f["count"] for f in fills)
        fill_mean = (sum((f["mean"] or 0.0) * f["count"] for f in fills)
                     / fill_n if fill_n else None)
        retr = r2.retraces_post_warmup
        r2.stop(drain=False)
        # hop-chain gate, phase half: every accepted request's chain must
        # be complete; the kill run must show >=1 requeue (re-pack when
        # packed) crossing the ejection with the SAME id
        phase_records = tracer.records()
        chain_report = validate_chains(phase_records, rids2)
        example = None
        if chain_report["requeued"]:
            # one indexed pass (chains), not a full-stream rescan per rid
            by_id = chains(phase_records)
            for rid in rids2:
                hops = [(r.get("attrs") or {})
                        for r in by_id.get(rid, [])]
                if any(h.get("hop") == "requeue" for h in hops):
                    example = {"request_id": rid,
                               "hops": [h.get("hop") for h in hops]}
                    break
        chain_report["incomplete"] = dict(
            list(chain_report["incomplete"].items())[:5])
        return {
            "serve_pack": mode,
            "request_tracing": {**chain_report, "example_requeued": example},
            "requests": pack_n,
            "real_tokens": pack_tokens,
            "elapsed_s": round(elapsed, 3),
            "tokens_per_s": round(pack_tokens / elapsed, 1),
            "requests_per_s": round(pack_n / elapsed, 1),
            "window": window,
            "lost": lost,
            "fill_mean": (round(fill_mean, 4)
                          if fill_mean is not None else None),
            "batches": sum(s["batches_total"]
                           for s in snap2["replicas"].values()),
            "retraces_post_warmup": retr,
            "kill": ({"victim": victim2,
                      "ejections": snap2["router"]["ejections_total"],
                      "requeued": snap2["router"]["requeued_total"],
                      "retries": snap2["router"]["retries_total"]}
                     if kill else None),
            "_logits": futs2,
        }

    padded_run = run_pack_storm("off", kill=False)
    packed_run = run_pack_storm("on", kill=True)
    # per-request parity between the two runs: exact argmax wherever the
    # padded top-2 margin is meaningful (offset segments reduce over
    # shifted key indices -> ulp-level drift, never semantic), tight
    # absolute bound everywhere
    import numpy as np

    parity = {"compared": 0, "argmax_mismatch": 0, "max_abs_diff": 0.0}
    for a, b in zip(padded_run.pop("_logits"), packed_run.pop("_logits")):
        if a is None or b is None:
            continue
        parity["compared"] += 1
        parity["max_abs_diff"] = max(parity["max_abs_diff"],
                                     float(np.abs(a - b).max()))
        top2 = np.sort(a)[-2:]
        if np.argmax(a) != np.argmax(b) and top2[1] - top2[0] > 1e-4:
            parity["argmax_mismatch"] += 1
    parity["max_abs_diff"] = round(parity["max_abs_diff"], 9)
    pack_ratio = (packed_run["tokens_per_s"]
                  / max(1e-9, padded_run["tokens_per_s"]))

    result = {
        "metric": "serve_load_smoke",
        "requests": n_requests,
        "target_qps": qps,
        "achieved_qps": round(achieved_qps, 1),
        "replicas": n_replicas,
        "device_groups": [g is not None for g in groups],
        "buckets": list(buckets),
        "batch_size": batch_size,
        "max_queue": max_queue,
        "deadline_ms": deadline_ms,
        "storm": outcomes,
        "latency_ms_p50":
            router.metrics.request_latency_ms.percentile(50),
        "latency_ms_p99": p99,
        "p99_budget_ms": p99_budget,
        "kill": {
            "victim": victim,
            "ejections": snap["router"]["ejections_total"],
            "requeued": snap["router"]["requeued_total"],
            "retries": snap["router"]["retries_total"],
            "reintegrations": snap["router"]["reintegrations_total"],
            "recovery_sec_max": recovery["max"],
            "recovery_bound_s": recovery_bound,
        },
        "swap": {
            "swapped": swap_report.get("swapped"),
            "rolled_back": swap_report.get("rolled_back"),
            "skipped": swap_report.get("skipped"),
        },
        "retraces_post_warmup": retraces_post,
        "burst": {"requests": 3 * (burst_n // 3), **burst_outcomes},
        "admission": adm,
        "request_tracing": {"storm": storm_chains},
        "packed_phase": {
            "padded": padded_run,
            "packed": packed_run,
            "tokens_throughput_ratio": round(pack_ratio, 2),
            "ratio_floor": pack_ratio_floor,
            "fill_floor": pack_fill_floor,
            "parity": parity,
        },
        "checkpoint": ckpt_path,
        "model": args.model,
        "serve_dtype": router.engine(0).dtype_label,
        "devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
        "metrics": snap,
    }

    failures = []
    if outcomes["lost"] or burst_outcomes["lost"]:
        failures.append(
            f"LOST accepted requests: storm {outcomes['lost']} / burst "
            f"{burst_outcomes['lost']} (every accepted request must "
            "complete or deadline-fail)")
    if outcomes["deadline"] + outcomes["shed"] + outcomes["rejected"] \
            > n_requests // 10:
        failures.append(
            f"storm shed too much at the target QPS: {outcomes} (the pool "
            "must absorb the configured load, not shed it)")
    if p99 is not None and p99 > p99_budget:
        failures.append(f"p99 latency {p99:.1f}ms over the "
                        f"{p99_budget:.0f}ms budget at {qps} QPS")
    if retraces_post != 0:
        failures.append(f"{retraces_post} post-warmup retraces (expected "
                        "0 across kill, relaunch and rolling swap)")
    if snap["router"]["ejections_total"] < 1 \
            or snap["router"]["reintegrations_total"] < 1:
        failures.append("the killed replica was not ejected+reintegrated "
                        f"(ejections {snap['router']['ejections_total']}, "
                        "reintegrations "
                        f"{snap['router']['reintegrations_total']})")
    if snap["router"]["requeued_total"] \
            + snap["router"]["retries_total"] < 1:
        failures.append("the kill stranded no requests — requeue/retry "
                        "was never exercised (requeued "
                        f"{snap['router']['requeued_total']}, retries "
                        f"{snap['router']['retries_total']})")
    if recovery["count"] < 1 or (recovery["max"] or 0) > recovery_bound:
        failures.append(f"ejection->recovery {recovery['max']}s outside "
                        f"the {recovery_bound}s bound")
    if len(swap_report.get("swapped") or []) < max(1, n_replicas - 1) \
            or swap_report.get("rolled_back"):
        failures.append(f"rolling swap incomplete: {swap_report}")
    for tier in ("backpressure_waits", "shed", "rejected"):
        if adm[tier] < 1:
            failures.append(f"admission tier {tier!r} never engaged "
                            f"during the burst ({adm})")
    # ---- packed-phase gates ----
    if pack_ratio < pack_ratio_floor:
        failures.append(
            f"packed tokens-throughput {packed_run['tokens_per_s']}/s is "
            f"only {pack_ratio:.2f}x the padded path "
            f"({padded_run['tokens_per_s']}/s) — floor "
            f"{pack_ratio_floor}x at the short-request mix")
    if parity["argmax_mismatch"] or parity["max_abs_diff"] > 1e-3:
        failures.append(f"packed-vs-padded per-request parity broken: "
                        f"{parity}")
    if parity["compared"] < pack_n:
        failures.append(f"parity compared only {parity['compared']}"
                        f"/{pack_n} requests (lost futures?)")
    if packed_run["fill_mean"] is None \
            or packed_run["fill_mean"] < pack_fill_floor:
        failures.append(f"packed fill {packed_run['fill_mean']} under the "
                        f"{pack_fill_floor} floor")
    if packed_run["retraces_post_warmup"] \
            or padded_run["retraces_post_warmup"]:
        failures.append(
            "packed-phase post-warmup retraces (packed "
            f"{packed_run['retraces_post_warmup']}, padded "
            f"{padded_run['retraces_post_warmup']}) — the packed path "
            "must hold ONE compiled shape")
    if packed_run["lost"] or padded_run["lost"]:
        failures.append(f"packed phase LOST requests through the kill "
                        f"(packed {packed_run['lost']}, padded "
                        f"{padded_run['lost']})")
    pk = packed_run["kill"]
    if pk["ejections"] < 1 or pk["requeued"] + pk["retries"] < 1:
        failures.append("the packed-phase kill stranded no work — "
                        f"eject/re-pack was never exercised ({pk})")
    # ---- hop-chain gates: every accepted request reconstructable ----
    for label, rep in (("storm", storm_chains),
                       ("padded", padded_run["request_tracing"]),
                       ("packed", packed_run["request_tracing"])):
        if rep["complete"] < rep["checked"]:
            failures.append(
                f"{label} phase: {rep['checked'] - rep['complete']} "
                "accepted request(s) without a complete hop chain "
                f"(first: {list(rep['incomplete'].items())[:2]})")
    if packed_run["request_tracing"]["repacked"] < 1:
        failures.append(
            "no packed-phase request crossed the mid-storm kill via "
            "re-pack with a joinable request_id (requeued="
            f"{packed_run['request_tracing']['requeued']})")

    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2)
        os.replace(tmp, out_path)
    print(json.dumps({k: v for k, v in result.items() if k != "metrics"}))
    # the smoke's temp dirs (span files, swap artifact) were consumed
    # above — a CI host must not accrete one per run
    import shutil

    shutil.rmtree(trace_dir, ignore_errors=True)
    shutil.rmtree(swap_dir, ignore_errors=True)
    if failures:
        sys.exit("serve-load smoke FAILED:\n  - " + "\n  - ".join(failures)
                 + f"\n  see {out_path}")


def replay_smoke(argv) -> None:
    """``--replay``: trace-driven load replay — the controller-vs-static
    proving ground (ROADMAP item 2's gate).

    Phase 0 — **record**: a seeded Poisson storm runs through a traced
    router; the flushed span file's ``admit`` hops (timestamp + tokens +
    deadline — ``serve.replay.arrivals_from_trace``) become the base
    arrival schedule.  The recording is reconstructed through the SAME
    file round trip ``trace_tpu.py`` uses, so any trace a production run
    flushed is replayable the same way.

    Phase 1 — **replay matrix**: the schedule is reshaped
    (``serve.replay.shape_arrivals``) into three traffic shapes —
    ``steady`` (1x), ``diurnal`` ramp (3x, trough -> peak -> trough), and
    ``flash`` crowd (5x with a mid-replay burst at 8x the base rate,
    plus the chaos replica kill + warmup-gated relaunch mid-storm) — and
    each shape is driven open-loop through three POOL CONFIGURATIONS over
    identical engines: two plausible static hand-tunings ("latency":
    1ms flush age + aggressive 10ms hedging; "throughput": 150ms flush
    age, no hedging) and the **controller** configuration
    (:class:`~pdnlp_tpu.serve.controller.ServeController` actuating flush
    age, hedge, admission and warm-standby replica count live).

    Phase 2 — **bad-actuation probe**: a short controller run where the
    smoke injects a harmful actuation (``max_wait_ms`` to its clamp
    ceiling) through the controller's own ``_actuate`` choke point, then
    gates that the evaluation window AUTO-REVERTS it and puts the knob in
    a backoff hold; a quiet tail + load burst then exercises the full
    scale-down -> warm-standby -> warmup-gated reactivation cycle.

    Gates (non-zero exit on any violation):

    - **frontier**: per shape, no static configuration dominates the
      controller on BOTH axes (p99 AND goodput, with noise margins), and
      the controller's geomean score (goodput_tokens_per_s / p99_ms
      across shapes) strictly beats every static's — adapting must win
      the p99 x throughput frontier, not just tie the best hand-tuning
      per shape;
    - **SLO** (the ``--serve-load`` discipline): ZERO lost accepted
      requests in every run, controller p99 under ``--replay_p99_ms``
      on every shape, ZERO post-warmup retraces everywhere — including
      through the kill/relaunch and the scale-down/reactivation cycles;
    - **decisions**: every controller actuation carries a complete
      cause -> action -> outcome chain (``obs.decision.validate_decisions``
      over the flushed file, plus a real ``trace_tpu.py decisions`` exit-0
      round trip), the probe's injected actuation is reverted within its
      evaluation window, and the probe exercised >= 1 scale-down AND
      >= 1 reactivation;
    - **chaos**: each flash run ejected + reintegrated the killed replica
      with >= 1 requeue/retry.

    Deterministic per host (seeded arrivals, seeded shapes; absolute
    throughput scales with the host's forward time — the comparisons are
    within-run).  Snapshot: ``results/replay_smoke.json``.
    """
    import math
    import tempfile
    import threading
    import time

    import jax

    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
    from pdnlp_tpu.obs.decision import validate_decisions
    from pdnlp_tpu.obs.export import load_records
    from pdnlp_tpu.serve import InferenceEngine, ReplicaRouter
    from pdnlp_tpu.serve.controller import (
        KnobSpec, ServeController, default_specs,
    )
    from pdnlp_tpu.serve.replay import (
        arrivals_from_trace, replay, shape_arrivals, synth_arrivals,
    )
    from pdnlp_tpu.utils.config import Args, parse_cli, pop_cli_flag

    argv, n_requests = pop_cli_flag(argv, "--replay_requests", 3600, int)
    argv, base_qps = pop_cli_flag(argv, "--replay_qps", None, float)
    argv, n_replicas = pop_cli_flag(argv, "--replay_replicas", 3, int)
    argv, deadline_ms = pop_cli_flag(argv, "--replay_deadline_ms", 250.0,
                                     float)
    argv, p99_budget = pop_cli_flag(argv, "--replay_p99_ms", 2000.0, float)
    argv, out_path = pop_cli_flag(
        argv, "--replay_out", os.path.join("results", "replay_smoke.json"))

    # jaxlint: disable=L1 — the replay gate reads this dir after the run
    trace_dir = tempfile.mkdtemp(prefix="pdnlp-replay-trace-")
    args = parse_cli(argv, base=Args(model="bert-tiny", trace=True,
                                     trace_dir=trace_dir))

    import random as _random

    chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐高兴悲伤讨厌愤怒"
    vocab_texts = ["".join(_random.Random(args.seed).choice(chars)
                           for _ in range(24)) for _ in range(64)]
    tok = WordPieceTokenizer(build_vocab(vocab_texts, size=256))

    buckets = (32,)
    batch_size = 8
    max_queue = 512  # token of head-room: overload policy is the knobs'

    def factory(index: int) -> InferenceEngine:
        return InferenceEngine(args, tokenizer=tok, mesh=None)

    # ONE engine pool reused across every run: each router start re-runs
    # the warmup on its worker (compile-cache hits after the first), so
    # eleven pools cost four compiles, and the per-run retrace baselines
    # stay exact
    engines = [factory(i) for i in range(n_replicas)]
    tracer = engines[0].tracer

    def build_router(cfg: dict) -> ReplicaRouter:
        return ReplicaRouter(
            engines, engine_factory=factory, buckets=buckets,
            max_batch_size=batch_size,
            max_wait_ms=cfg.get("max_wait_ms", 5.0),
            hedge_ms=cfg.get("hedge_ms"),
            max_queue=max_queue, serve_pack="off",
            stall_timeout=2.0, poll_interval=0.02)

    #: the replay controller tuned for second-scale runs: tight interval,
    #: short evaluation windows and cooldowns, a wide declared-safe flush
    #: age range (the probe's injected 250ms IS in range — in range and
    #: harmful is exactly what the evaluation loop exists to catch)
    def build_controller(router: ReplicaRouter, manage_flush: bool = True,
                         scale_patience: int = 8) -> ServeController:
        specs = default_specs()
        specs["max_wait_ms"] = KnobSpec(
            "max_wait_ms", 1.0, 250.0, cooldown_s=0.4, hysteresis=0.3,
            signal="p99_ms", noise_floor=8.0)
        specs["hedge_ms"] = KnobSpec(
            "hedge_ms", 5.0, 2000.0, cooldown_s=0.4, hysteresis=0.25,
            signal="p99_ms", noise_floor=8.0)
        specs["backpressure_at"] = KnobSpec(
            "backpressure_at", 8, 10 ** 9, cooldown_s=0.5, hysteresis=0.2,
            signal="slo_pressure", noise_floor=0.02, integer=True)
        specs["replicas"] = KnobSpec(
            "replicas", 1, n_replicas, cooldown_s=0.8, hysteresis=0.0,
            signal="p99_ms", noise_floor=8.0, integer=True)
        specs["hedge_ms"].lo = 25.0
        return ServeController(
            router, interval_s=0.12, min_replicas=1, specs=specs,
            eval_window_s=0.7, revert_margin=0.3, hold_base_s=3.0,
            hold_cap_s=30.0, hedge_factor=0.3, fill_fraction=0.12,
            wait_budget_ms=15.0, scale_patience=scale_patience,
            util_low=0.12,
            util_high=0.75, util_batch=0.5, ewma_alpha=0.5,
            manage_flush=manage_flush, tracer=tracer)

    configs = {
        "static_latency": {"max_wait_ms": 1.0, "hedge_ms": 5.0},
        "static_throughput": {"max_wait_ms": 150.0, "hedge_ms": None},
        "controller": {"max_wait_ms": 5.0, "hedge_ms": 50.0},
    }
    shapes = [("steady", 1.0, False), ("diurnal", 4.0, False),
              ("flash", 5.0, True)]

    # ---- phase 0: record a seeded storm, reconstruct it from the trace
    tracer.clear()
    rec_router = build_router({"max_wait_ms": 5.0}).start()
    if not rec_router.wait_ready(600):
        sys.exit("replay smoke FAILED: recording pool never warmed up")
    # calibrate the storm to the HOST's measured capacity: the shapes
    # must sit in the regime where batching and adaptation matter (steady
    # comfortable, diurnal peak near the small-batch cliff, flash over
    # it) on fast and slow CI hosts alike.  Explicit --replay_qps pins it.
    forward_ts = []
    probe_ids = [[tok.cls_id, 7, 9, tok.sep_id]] * batch_size
    for _ in range(15):
        t0 = time.perf_counter()
        # infer_ids returns HOST numpy (the engine materializes inside its
        # own forward span) — the delta below is real wall time, not an
        # async-dispatch enqueue measurement
        engines[0].infer_ids(probe_ids, buckets[0], rows=batch_size)
        forward_ts.append(time.perf_counter() - t0)  # jaxlint: disable=R4 — infer_ids blocked on host results above
    forward_ms = sorted(forward_ts)[len(forward_ts) // 2] * 1e3
    capacity_rps = n_replicas * batch_size / (forward_ms / 1e3)
    if base_qps is None:
        # 0.28 x full-batch capacity puts the storm INSIDE the regime the
        # comparison is about: batches execute as fixed padded shapes, so
        # a 1ms flush age burns whole padded batches on 1-3 real rows and
        # its EFFECTIVE capacity is a fraction of the batched pool's —
        # steady sits above that fraction, the diurnal peak well above it,
        # and the flash crowd above even the batched ceiling
        base_qps = round(min(1200.0, max(150.0, 0.28 * capacity_rps)), 1)
    rec_schedule = synth_arrivals(n_requests, base_qps,
                                  lengths=(6, 9, 12, 16, 20, 26),
                                  deadline_ms=deadline_ms, seed=args.seed)
    rec_report = replay(rec_router.submit_ids, rec_schedule)
    rec_router.stop(drain=False)
    trace_path = tracer.flush()
    base = arrivals_from_trace(load_records(trace_path))
    tracer.clear()
    if len(base) < 0.98 * n_requests:
        sys.exit(f"replay smoke FAILED: recording reconstructed only "
                 f"{len(base)}/{n_requests} arrivals from the trace")
    # determinism: the trace -> schedule reconstruction is pure
    base2 = arrivals_from_trace(load_records(trace_path))
    if [a.as_tuple() for a in base] != [a.as_tuple() for a in base2]:
        sys.exit("replay smoke FAILED: arrival reconstruction is not "
                 "deterministic over the same trace")

    # ---- phase 1: the shapes x configs matrix over identical engines
    def run_one(config_name: str, cfg: dict, shape: str, speed: float,
                kill: bool) -> dict:
        tracer.clear()
        # flash_factor 20: the crowd must OVERLOAD the pool long enough to
        # build deadline-scale backlog, or every configuration absorbs it
        # and the comparison degenerates to ties
        schedule = shape_arrivals(base, shape, speed=speed,
                                  flash_factor=20.0)
        router = build_router(cfg).start()
        if not router.wait_ready(600):
            sys.exit(f"replay smoke FAILED: {config_name}/{shape} pool "
                     "never warmed up")
        controller = None
        if config_name == "controller":
            controller = build_controller(router).start()
        victim = n_replicas - 1
        kill_at, relaunch_at = len(schedule) // 2, (3 * len(schedule)) // 4
        state = {"relaunched": False}

        def on_tick(i: int) -> None:
            if not kill:
                return
            if i == kill_at:
                router.kill_replica(victim, "crash")
            elif i >= relaunch_at and not state["relaunched"]:
                if router.states[victim] == "ejected":
                    router.relaunch(victim)
                    state["relaunched"] = True

        rep = replay(router.submit_ids, schedule, on_tick=on_tick)
        if kill and not state["relaunched"] and \
                router.states[victim] == "ejected":
            router.relaunch(victim)  # tail kill: still prove reintegration
        if kill and not router.wait_ready(300):
            sys.exit(f"replay smoke FAILED: {config_name}/{shape} "
                     "relaunch never finished reintegration warmup")
        if controller is not None:
            controller.stop()
        snap = router.snapshot()
        p99 = router.metrics.request_latency_ms.percentile(99)
        retraces = router.retraces_post_warmup
        router.stop(drain=False)
        out = {
            "config": config_name, "shape": shape, "speed": speed,
            **rep.as_dict(),
            "p99_ms": round(p99, 2) if p99 is not None else None,
            "p50_ms": round(
                router.metrics.request_latency_ms.percentile(50) or 0, 2),
            "retraces_post_warmup": retraces,
            "hedges": snap["router"]["hedges_total"],
            "knobs_final": snap["knobs"],
            "kill": ({"ejections": snap["router"]["ejections_total"],
                      "requeued": snap["router"]["requeued_total"],
                      "retries": snap["router"]["retries_total"],
                      "reintegrations":
                          snap["router"]["reintegrations_total"]}
                     if kill else None),
        }
        if controller is not None:
            decisions = validate_decisions(tracer.records())
            decisions["incomplete"] = dict(
                list(decisions["incomplete"].items())[:5])
            out["controller"] = {
                "actuations": controller.actuations_total,
                "reverts": controller.reverts_total,
                "blocked": controller.blocked_total,
                "errors": controller.errors_total,
                "scale_downs": snap["router"]["scale_downs_total"],
                "scale_ups": snap["router"]["scale_ups_total"],
                "decisions": decisions,
            }
        return out

    def run_score(run: dict):
        p99 = run.get("p99_ms")
        if not p99 or not run.get("goodput_tokens_per_s"):
            return None
        return run["goodput_tokens_per_s"] / p99

    # two INTERLEAVED passes per cell, keep each cell's better pass for
    # the frontier (one loaded-host hiccup must not sink a cell — the
    # same discipline as --telemetry's interleaved arms); the SLO gates
    # below run over EVERY pass, kept or not
    runs: dict = {}
    all_runs: list = []
    for pass_i in range(2):
        for shape, speed, kill in shapes:
            for config_name, cfg in configs.items():
                key = f"{config_name}/{shape}"
                run = run_one(config_name, cfg, shape, speed, kill)
                run["pass"] = pass_i
                all_runs.append(run)
                prev = runs.get(key)
                s_new, s_old = run_score(run), \
                    run_score(prev) if prev else None
                if prev is None or (s_new or 0) > (s_old or 0):
                    runs[key] = run
                print(f"[replay] pass{pass_i} {key}: "
                      f"goodput {run['goodput_tokens_per_s']} tok/s  "
                      f"p99 {run['p99_ms']}ms  "
                      f"deadline {run['deadline']}  "
                      f"hedges {run['hedges']}", file=sys.stderr)

    # ---- phase 2: bad-actuation probe + scale cycle on a short schedule
    tracer.clear()
    probe_router = build_router(configs["controller"]).start()
    if not probe_router.wait_ready(600):
        sys.exit("replay smoke FAILED: probe pool never warmed up")
    # the probe isolates the injected actuation: the flush-age LAW is off,
    # so the injection is max_wait_ms's only writer and the auto-revert
    # (not a concurrent law actuation) is what restores it; the short
    # scale patience makes the quiet-tail drain-to-standby prompt
    probe_ctl = build_controller(probe_router, manage_flush=False,
                                 scale_patience=2).start()
    probe_schedule = shape_arrivals(base[: max(600, n_requests // 4)],
                                    "steady", speed=1.0)
    inject_at = len(probe_schedule) // 3
    injected = {"done": False}

    def probe_tick(i: int) -> None:
        if i == inject_at and not injected["done"]:
            # a harmful-but-in-range actuation through the controller's
            # own choke point: clamped, decision-recorded — and WRONG
            injected["done"] = probe_ctl.inject("max_wait_ms", 250.0)

    probe_rep = replay(probe_router.submit_ids, probe_schedule,
                       on_tick=probe_tick)
    # quiet tail: the scaling law must drain a replica to warm standby...
    deadline_t = time.monotonic() + 10.0
    while probe_router.standby_count < 1 and time.monotonic() < deadline_t:
        time.sleep(0.05)
    scale_down_seen = probe_router.standby_count >= 1
    # ...and a load burst must bring it back through the warmup gate
    burst_futs = []
    deadline_t = time.monotonic() + 15.0
    while probe_router.standby_count > 0 and time.monotonic() < deadline_t:
        # outpace the reduced pool so queue pressure actually builds (the
        # scale-up signal); admission refusals are outcomes, not errors
        for _ in range(100):
            try:
                burst_futs.append(probe_router.submit_ids(
                    [tok.cls_id, 7, 8, 9, tok.sep_id],
                    deadline_ms=30_000))
            except Exception:  # noqa: BLE001
                pass
        time.sleep(0.02)
    scale_up_seen = probe_router.standby_count == 0 and scale_down_seen
    if not probe_router.wait_ready(120):
        sys.exit("replay smoke FAILED: probe reactivation never finished "
                 "its warmup gate")
    burst_ok = sum(1 for f in burst_futs
                   if _silent_result(f) is not None)
    probe_ctl.stop()
    probe_snap = probe_router.snapshot()
    probe_retraces = probe_router.retraces_post_warmup
    probe_router.stop(drain=False)
    probe_trace = tracer.flush()
    probe_decisions = validate_decisions(load_records(probe_trace))
    probe_decisions["incomplete"] = dict(
        list(probe_decisions["incomplete"].items())[:5])
    # the reconstructability contract, through the REAL CLI surface
    import trace_tpu

    decisions_cli_rc = trace_tpu.main(["decisions", probe_trace])

    # ---- the frontier: per-shape non-domination + geomean score win
    score = run_score
    frontier = {"per_shape": {}, "geomean": {}}
    failures = []
    for config_name in configs:
        vals = []
        for shape, _, _ in shapes:
            s = score(runs[f"{config_name}/{shape}"])
            frontier["per_shape"].setdefault(shape, {})[config_name] = \
                round(s, 3) if s is not None else None
            vals.append(max(s or 1e-9, 1e-9))
        frontier["geomean"][config_name] = round(
            math.exp(sum(math.log(v) for v in vals) / len(vals)), 3)

    ctrl_geo = frontier["geomean"]["controller"]
    for static in ("static_latency", "static_throughput"):
        if ctrl_geo <= frontier["geomean"][static]:
            failures.append(
                f"frontier: controller geomean score {ctrl_geo} does not "
                f"beat {static} ({frontier['geomean'][static]}) — "
                "adapting lost to a hand-tuned constant")
        for shape, _, _ in shapes:
            c = runs[f"controller/{shape}"]
            s = runs[f"{static}/{shape}"]
            if c["p99_ms"] and s["p99_ms"] \
                    and s["p99_ms"] < c["p99_ms"] / 1.15 \
                    and s["goodput_tokens_per_s"] \
                    > c["goodput_tokens_per_s"] * 1.10:
                failures.append(
                    f"frontier: {static} DOMINATES the controller on "
                    f"{shape} (p99 {s['p99_ms']} vs {c['p99_ms']}ms, "
                    f"goodput {s['goodput_tokens_per_s']} vs "
                    f"{c['goodput_tokens_per_s']} tok/s)")

    # ---- SLO gates: the --serve-load discipline, EVERY pass (kept or not)
    for run in all_runs:
        key = f"{run['config']}/{run['shape']} (pass {run['pass']})"
        if run["lost"]:
            failures.append(f"{key}: {run['lost']} LOST accepted "
                            "request(s)")
        if run["retraces_post_warmup"]:
            failures.append(f"{key}: {run['retraces_post_warmup']} "
                            "post-warmup retraces")
        if run["kill"] is not None:
            k = run["kill"]
            if k["ejections"] < 1 or k["reintegrations"] < 1:
                failures.append(f"{key}: kill not ejected+reintegrated "
                                f"({k})")
            if k["requeued"] + k["retries"] < 1:
                failures.append(f"{key}: the kill stranded no work ({k})")
        if run["config"] == "controller":
            if run["p99_ms"] is None or run["p99_ms"] > p99_budget:
                failures.append(f"{key}: p99 {run['p99_ms']}ms over the "
                                f"{p99_budget}ms budget")
            dec = run["controller"]["decisions"]
            if dec["incomplete"]:
                failures.append(f"{key}: incomplete decision chains "
                                f"{dec['incomplete']}")
            if run["controller"]["actuations"] < 1:
                failures.append(f"{key}: the controller never actuated — "
                                "the loop is not closed")

    # ---- probe gates: auto-revert + hold + the standby cycle
    if not injected["done"]:
        failures.append("probe: the bad actuation was never injected")
    if probe_decisions["reverted"] < 1:
        failures.append(
            "probe: the injected bad actuation was NOT auto-reverted "
            f"within its evaluation window ({probe_decisions})")
    if probe_decisions["incomplete"]:
        failures.append(f"probe: incomplete decision chains "
                        f"{probe_decisions['incomplete']}")
    if decisions_cli_rc != 0:
        failures.append("probe: `trace_tpu.py decisions` could not "
                        "reconstruct a valid chain (exit "
                        f"{decisions_cli_rc})")
    if not scale_down_seen:
        failures.append("probe: low load never drained a replica to warm "
                        "standby")
    if not scale_up_seen:
        failures.append("probe: the load burst never reactivated the "
                        "standby replica")
    if probe_retraces:
        failures.append(f"probe: {probe_retraces} post-warmup retraces "
                        "through the scale-down/reactivation cycle")
    if probe_rep.lost:
        failures.append(f"probe: {probe_rep.lost} LOST requests")

    result = {
        "metric": "replay_smoke",
        "requests": n_requests,
        "base_qps": base_qps,
        "calibration": {"forward_ms": round(forward_ms, 3),
                        "capacity_rps": round(capacity_rps, 1)},
        "deadline_ms": deadline_ms,
        "replicas": n_replicas,
        "buckets": list(buckets),
        "batch_size": batch_size,
        "recording": {"submitted": rec_report.submitted,
                      "reconstructed": len(base),
                      "deterministic": True},
        "shapes": [{"shape": s, "speed": v, "kill": k}
                   for s, v, k in shapes],
        "configs": {k: {kk: vv for kk, vv in v.items()}
                    for k, v in configs.items()},
        "runs": runs,
        "frontier": frontier,
        "probe": {
            **probe_rep.as_dict(),
            "injected": injected["done"],
            "scale_down_seen": scale_down_seen,
            "scale_up_seen": scale_up_seen,
            "burst_completed": burst_ok,
            "retraces_post_warmup": probe_retraces,
            "actuations": probe_ctl.actuations_total,
            "reverts": probe_ctl.reverts_total,
            "holds": probe_ctl.snapshot()["holds_s"],
            "scale_downs": probe_snap["router"]["scale_downs_total"],
            "scale_ups": probe_snap["router"]["scale_ups_total"],
            "decisions": probe_decisions,
            "decisions_cli_exit": decisions_cli_rc,
        },
        "p99_budget_ms": p99_budget,
        "model": args.model,
        "devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
    }

    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2)
        os.replace(tmp, out_path)
    print(json.dumps({k: v for k, v in result.items() if k != "runs"}))
    import shutil

    shutil.rmtree(trace_dir, ignore_errors=True)
    if failures:
        sys.exit("replay smoke FAILED:\n  - " + "\n  - ".join(failures)
                 + f"\n  see {out_path}")


def fleet_smoke(argv) -> None:
    """``--fleet``: the multi-model fleet gate (ROADMAP item 4) — three
    proofs over one reused engine set (2x primary bf16, 1x candidate
    loading a deliberately-PERTURBED checkpoint, 1x cheap int8 of the
    same weights):

    **(a) shadow impact** — the same seeded storm runs through
    control (no shadow) and treatment (``--fleet_shadow``, default 20%
    shadow onto the bad candidate) fleets, INTERLEAVED twice per arm
    (loaded-CI discipline, same as ``--telemetry``), at a rate
    auto-calibrated to the host's measured forward capacity (explicit
    ``--fleet_qps`` pins it).  Gates: per-request argmax outcomes are
    IDENTICAL across every pass (the candidate's answers measurably
    differ — parity mismatches prove the comparison is real — yet no
    caller ever sees one), best-arm p99 within the latency margin,
    every chain (incl. every shadow duplicate's, terminating shadow-side)
    complete through the file round trip, zero post-warmup retraces.

    **(b) canary rollout** — two storms under a
    :class:`~pdnlp_tpu.serve.controller.ServeController` rollout law:
    a GOOD candidate (same checkpoint) advances the canary fraction up
    the :class:`RolloutPlan` steps on live shadow-parity evidence; then
    the BAD candidate is pushed to 25% via the controller's own
    ``inject`` choke point mid-storm and the law AUTO-ROLLS-BACK to 0
    (parity regression), draining the candidate's queue to the primary.
    Gates: good rollout reaches >= the second step with zero rollbacks;
    bad rollout ends at fraction 0 with >= 1 recorded rollback, zero
    lost requests, and complete decision chains both ways.

    **(c) degrade tier** — a back-to-back overload burst against a
    tight primary ladder, control (no cheap model: the pre-fleet ladder
    sheds it) vs treatment (degrade band re-routes to the int8 cheap
    pool).  Gates: control sheds >= 1; treatment sheds/rejects 0 with
    >= 1 degraded request, every degraded chain carrying its ``degrade``
    hop before dispatch, and the cheap model's per-model metrics showing
    exactly the shifted traffic.

    Snapshot: ``results/fleet_smoke.json`` (non-zero exit on any gate).
    """
    import dataclasses
    import tempfile
    import time

    import jax
    import numpy as np

    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
    from pdnlp_tpu.obs.decision import validate_decisions
    from pdnlp_tpu.obs.export import load_records
    from pdnlp_tpu.obs.request import validate_chains
    from pdnlp_tpu.serve import (
        FleetRouter, InferenceEngine, LoadShedError, QueueFullError,
        ReplicaRouter, RolloutPlan, ServeController,
    )
    from pdnlp_tpu.serve.controller import KnobSpec, default_specs
    from pdnlp_tpu.serve.replay import ids_for, replay, synth_arrivals
    from pdnlp_tpu.train import checkpoint as ckpt_mod
    from pdnlp_tpu.utils.config import Args, parse_cli, pop_cli_flag

    argv, n_requests = pop_cli_flag(argv, "--fleet_requests", 600, int)
    argv, base_qps = pop_cli_flag(argv, "--fleet_qps", None, float)
    argv, shadow_fraction = pop_cli_flag(argv, "--fleet_shadow", 0.2,
                                         float)
    argv, deadline_ms = pop_cli_flag(argv, "--fleet_deadline_ms",
                                     30_000.0, float)
    argv, p99_factor = pop_cli_flag(argv, "--fleet_p99_factor", 1.5,
                                    float)
    argv, p99_margin_ms = pop_cli_flag(argv, "--fleet_p99_margin_ms",
                                       25.0, float)
    argv, out_path = pop_cli_flag(
        argv, "--fleet_out", os.path.join("results", "fleet_smoke.json"))

    # jaxlint: disable=L1 — fleet gate reads traces/ckpts after the run
    trace_dir = tempfile.mkdtemp(prefix="pdnlp-fleet-trace-")
    # jaxlint: disable=L1 — fleet gate reads traces/ckpts after the run
    ckpt_dir = tempfile.mkdtemp(prefix="pdnlp-fleet-ckpt-")
    args = parse_cli(argv, base=Args(model="bert-tiny", trace=True,
                                     trace_dir=trace_dir))

    import random as _random

    chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐高兴悲伤讨厌愤怒"
    vocab_texts = ["".join(_random.Random(args.seed).choice(chars)
                           for _ in range(24)) for _ in range(64)]
    tok = WordPieceTokenizer(build_vocab(vocab_texts, size=256))
    buckets = (32,)
    batch_size = 8

    # ONE engine set reused across every phase (compile once): the
    # per-group checkpoint_path makes each router's warmup load the right
    # artifact onto its engines
    eng_prim = [InferenceEngine(args, tokenizer=tok, mesh=None)
                for _ in range(2)]
    eng_cand = [InferenceEngine(args, tokenizer=tok, mesh=None)]
    eng_cheap = [InferenceEngine(
        dataclasses.replace(args, serve_dtype="int8"),
        tokenizer=tok, mesh=None)]
    tracer = eng_prim[0].tracer

    # the good checkpoint = the shared init weights; the BAD candidate
    # checkpoint is the same tree with the classifier head's class axis
    # ROLLED by one (every leaf whose last dim is num_labels) —
    # shape-valid, loads cleanly, and every answer is deterministically
    # the wrong class (logits permuted), which is exactly the regression
    # shadow parity exists to catch
    host = jax.device_get(eng_prim[0].params)
    good_ckpt = os.path.join(ckpt_dir, "good-cls.msgpack")
    ckpt_mod.save(good_ckpt, host)
    bad_ckpt = os.path.join(ckpt_dir, "bad-cls.msgpack")
    n_labels = args.num_labels
    ckpt_mod.save(bad_ckpt, jax.tree_util.tree_map(
        lambda a: (np.roll(np.asarray(a), 1, axis=-1)
                   if np.asarray(a).ndim >= 1
                   and np.asarray(a).shape[-1] == n_labels
                   else np.asarray(a)), host))

    def make_group(mid, engines, ckpt_path, **kw):
        kw.setdefault("max_queue", 512)
        return ReplicaRouter(
            engines, buckets=buckets, max_batch_size=batch_size,
            max_wait_ms=5.0, stall_timeout=10.0, poll_interval=0.02,
            serve_pack="off", checkpoint_path=ckpt_path, model_id=mid,
            tracer=tracer, **kw)

    def start_fleet(fleet):
        fleet.start()
        if not fleet.wait_ready(600):
            sys.exit("fleet smoke FAILED: a pool never finished warmup")
        return fleet

    failures: list = []

    # ---- calibration (deflake): the storm rate rides the HOST's measured
    # forward capacity, so the shadow-impact comparison sits in the same
    # sub-saturation regime on fast and slow CI hosts alike
    warm = make_group("prod", eng_prim, good_ckpt)
    start_fleet(FleetRouter({"prod": warm}, primary="prod",
                            tracer=tracer)).stop(drain=False)
    probe_ids = [[tok.cls_id, 7, 9, tok.sep_id]] * batch_size
    forward_ts = []
    for _ in range(15):
        t0 = time.perf_counter()
        # infer_ids returns HOST numpy — real wall time, not an enqueue
        eng_prim[0].infer_ids(probe_ids, buckets[0], rows=batch_size)
        forward_ts.append(time.perf_counter() - t0)  # jaxlint: disable=R4 — infer_ids blocked on host results above
    forward_ms = sorted(forward_ts)[len(forward_ts) // 2] * 1e3
    capacity_rps = len(eng_prim) * batch_size / (forward_ms / 1e3)
    if base_qps is None:
        base_qps = round(min(800.0, max(100.0, 0.25 * capacity_rps)), 1)
    schedule = synth_arrivals(n_requests, base_qps,
                              lengths=(6, 9, 12, 16, 20, 26),
                              deadline_ms=deadline_ms, seed=args.seed)

    # ---------------------------------------------- (a) shadow impact
    def run_storm(shadow_frac: float, label: str) -> dict:
        tracer.clear()
        prim = make_group("prod", eng_prim, good_ckpt)
        cand = make_group("cand", eng_cand, bad_ckpt)
        fleet = start_fleet(FleetRouter(
            {"prod": prim, "cand": cand}, primary="prod",
            candidate="cand", shadow_fraction=shadow_frac, tracer=tracer))
        futs: list = []

        def submit(ids, deadline_ms=None):
            f = fleet.submit_ids(ids, deadline_ms=deadline_ms)
            futs.append(f)
            return f

        rep = replay(submit, schedule)
        fleet.stop(drain=True)
        chains_rep = validate_chains(load_records(tracer.flush()))
        chains_rep["incomplete"] = dict(
            list(chains_rep["incomplete"].items())[:5])
        out = {
            "label": label, "shadow_fraction": shadow_frac,
            **rep.as_dict(),
            "p99_ms": round(prim.metrics.request_latency_ms
                            .percentile(99) or 0.0, 2),
            "argmaxes": [int(np.argmax(f._logits))
                         if f._error is None and f._logits is not None
                         else None for f in futs],
            "retraces_post_warmup": fleet.retraces_post_warmup,
            "chains": {k: v for k, v in chains_rep.items()
                       if k != "incomplete"},
            "chains_incomplete": chains_rep["incomplete"],
            "fleet": fleet.metrics.snapshot(),
            "shadow": fleet.shadow_report.snapshot(),
        }
        print(f"[fleet] {label}: p99 {out['p99_ms']}ms  ok {rep.ok}"
              f"/{rep.submitted}  shadows {out['fleet']['shadows_total']}"
              f"  parity {out['shadow']['checked']} checked "
              f"{out['shadow']['mismatches']} mismatched",
              file=sys.stderr)
        return out

    arms: dict = {"control": [], "shadow": []}
    for i in range(2):  # interleaved passes (loaded-CI discipline)
        arms["control"].append(run_storm(0.0, f"control/pass{i}"))
        arms["shadow"].append(run_storm(shadow_fraction,
                                        f"shadow/pass{i}"))

    baseline_argmax = arms["control"][0]["argmaxes"]
    for arm in ("control", "shadow"):
        for run in arms[arm]:
            if run["argmaxes"] != baseline_argmax:
                diff = sum(1 for a, b in zip(run["argmaxes"],
                                             baseline_argmax) if a != b)
                failures.append(
                    f"(a) {run['label']}: caller-visible outcomes differ "
                    f"from the no-shadow control ({diff} of "
                    f"{len(baseline_argmax)} argmaxes)")
            if run["lost"] or run["deadline"] or run["shed"] \
                    or run["rejected"]:
                failures.append(f"(a) {run['label']}: outcome split not "
                                "clean under the calibrated storm "
                                f"({run['lost']} lost, {run['deadline']} "
                                f"deadline, {run['shed']} shed, "
                                f"{run['rejected']} rejected)")
            if run["retraces_post_warmup"]:
                failures.append(f"(a) {run['label']}: "
                                f"{run['retraces_post_warmup']} "
                                "post-warmup retraces")
            if run["chains_incomplete"]:
                failures.append(f"(a) {run['label']}: incomplete chains "
                                f"{run['chains_incomplete']}")
    control_p99 = min(r["p99_ms"] for r in arms["control"])
    shadow_p99 = min(r["p99_ms"] for r in arms["shadow"])
    if shadow_p99 > control_p99 * p99_factor + p99_margin_ms:
        failures.append(
            f"(a) shadow p99 {shadow_p99}ms exceeds the no-shadow "
            f"control's {control_p99}ms beyond the margin "
            f"(x{p99_factor} + {p99_margin_ms}ms)")
    expect_shadows = int(shadow_fraction * n_requests)
    for run in arms["shadow"]:
        got = run["fleet"]["shadows_total"]
        if abs(got - expect_shadows) > 1:
            failures.append(f"(a) {run['label']}: {got} shadows vs the "
                            f"{expect_shadows} the fraction promises")
        if run["shadow"]["mismatches"] < 1:
            failures.append(f"(a) {run['label']}: the perturbed candidate "
                            "produced ZERO argmax mismatches — the parity "
                            "comparison cannot be real")
        if run["chains"]["shadowed"] < got:
            failures.append(f"(a) {run['label']}: only "
                            f"{run['chains']['shadowed']} shadow chains "
                            f"for {got} shadow submissions")

    # ------------------------------------- (b) canary rollout + rollback
    def rollout_controller(fleet, plan):
        specs = default_specs()
        specs["canary_fraction"] = KnobSpec(
            "canary_fraction", 0.0, 1.0, cooldown_s=0.25, hysteresis=0.0,
            signal="p99_ms", noise_floor=50.0)
        return ServeController(
            fleet, interval_s=0.05, specs=specs, rollout=plan,
            eval_window_s=0.4, revert_margin=1.0,
            manage_flush=False, manage_admission=False,
            manage_hedge=False, scale_patience=10 ** 6, tracer=tracer)

    def run_rollout(cand_ckpt: str, label: str, inject_frac, plan
                    ) -> dict:
        tracer.clear()
        prim = make_group("prod", eng_prim, good_ckpt)
        cand = make_group("cand", eng_cand, cand_ckpt)
        fleet = start_fleet(FleetRouter(
            {"prod": prim, "cand": cand}, primary="prod",
            candidate="cand",
            shadow_fraction=max(shadow_fraction, 0.25), tracer=tracer))
        ctl = rollout_controller(fleet, plan).start()
        futs: list = []
        inject_at = len(schedule) // 3
        injected = {"done": False}

        def on_tick(i: int) -> None:
            if inject_frac is not None and i == inject_at \
                    and not injected["done"]:
                # the optimistic-operator push, through the controller's
                # own choke point: clamped, decision-recorded — and WRONG
                injected["done"] = ctl.inject("canary_fraction",
                                              inject_frac)

        def submit(ids, deadline_ms=None):
            f = fleet.submit_ids(ids, deadline_ms=deadline_ms)
            futs.append(f)
            return f

        rep = replay(submit, schedule, on_tick=on_tick)
        # the law needs a few quiet ticks to finish judging (and the
        # rollback drain to land) after the storm's tail
        deadline_t = time.monotonic() + 5.0
        want_zero = inject_frac is not None
        while time.monotonic() < deadline_t:
            frac = fleet.canary_fraction
            if (want_zero and frac == 0.0) or \
                    (not want_zero and frac >= plan.steps[1]):
                break
            time.sleep(0.05)
        ctl.stop()
        fleet.stop(drain=True)
        lost = sum(1 for f in futs
                   if f._error is not None
                   and not isinstance(f._error, (LoadShedError,)))
        trace_path = tracer.flush()
        records = load_records(trace_path)
        chains_rep = validate_chains(records)
        chains_rep["incomplete"] = dict(
            list(chains_rep["incomplete"].items())[:5])
        decisions = validate_decisions(records)
        decisions["incomplete"] = dict(
            list(decisions["incomplete"].items())[:5])
        out = {
            "label": label, **rep.as_dict(), "lost_futures": lost,
            "injected": injected["done"],
            "final_fraction": fleet.canary_fraction,
            "canary_routed": fleet.metrics.canary_routed_total.value,
            "rollbacks": fleet.metrics.rollbacks_total.value,
            "rolled_back_requests":
                fleet.metrics.rolled_back_requests_total.value,
            "controller": {"actuations": ctl.actuations_total,
                           "rollbacks": ctl.rollbacks_total,
                           "reverts": ctl.reverts_total,
                           "errors": ctl.errors_total},
            "decisions": decisions,
            "chains": {k: v for k, v in chains_rep.items()
                       if k != "incomplete"},
            "chains_incomplete": chains_rep["incomplete"],
            "shadow": fleet.shadow_report.snapshot(),
            "retraces_post_warmup": fleet.retraces_post_warmup,
        }
        print(f"[fleet] {label}: fraction {out['final_fraction']}  "
              f"canary_routed {out['canary_routed']}  rollbacks "
              f"{out['rollbacks']}  actuations "
              f"{out['controller']['actuations']}", file=sys.stderr)
        return out

    good_plan = RolloutPlan(steps=(0.1, 0.25, 0.5), min_shadow_checked=10,
                            parity_tolerance=0.02, p99_factor=50.0,
                            patience=1)
    good_run = run_rollout(good_ckpt, "rollout/good", None, good_plan)
    bad_plan = RolloutPlan(steps=(0.25, 0.5, 1.0), min_shadow_checked=10,
                          parity_tolerance=0.02, p99_factor=50.0,
                          patience=2)
    bad_run = run_rollout(bad_ckpt, "rollout/bad", 0.25, bad_plan)

    if good_run["final_fraction"] < good_plan.steps[1]:
        failures.append(
            f"(b) good rollout stalled at fraction "
            f"{good_run['final_fraction']} (< step {good_plan.steps[1]}) "
            "— the law never advanced on clean parity evidence")
    if good_run["rollbacks"]:
        failures.append(f"(b) good rollout was rolled back "
                        f"{good_run['rollbacks']}x on clean evidence")
    if not bad_run["injected"]:
        failures.append("(b) the bad-canary fraction was never injected")
    if bad_run["final_fraction"] != 0.0 or bad_run["rollbacks"] < 1:
        failures.append(
            f"(b) the bad canary was NOT auto-rolled-back (final "
            f"fraction {bad_run['final_fraction']}, "
            f"{bad_run['rollbacks']} rollbacks)")
    if bad_run["canary_routed"] < 1:
        failures.append("(b) the injected fraction routed no caller "
                        "traffic — the rollback undid nothing real")
    for run in (good_run, bad_run):
        if run["lost"] or run["lost_futures"]:
            failures.append(f"(b) {run['label']}: {run['lost']} lost in "
                            f"replay, {run['lost_futures']} failed "
                            "futures — a rollout must never lose "
                            "accepted work")
        if run["decisions"]["incomplete"]:
            failures.append(f"(b) {run['label']}: incomplete decision "
                            f"chains {run['decisions']['incomplete']}")
        if run["chains_incomplete"]:
            failures.append(f"(b) {run['label']}: incomplete request "
                            f"chains {run['chains_incomplete']}")
        if run["retraces_post_warmup"]:
            failures.append(f"(b) {run['label']}: "
                            f"{run['retraces_post_warmup']} post-warmup "
                            "retraces")

    # --------------------------------------------------- (c) degrade tier
    def degrade_burst(with_cheap: bool, label: str) -> dict:
        tracer.clear()
        prim = make_group("prod", eng_prim, good_ckpt, max_queue=16,
                          backpressure_at=8,
                          degrade_at=10 if with_cheap else None,
                          shed_at=12, backpressure_wait_ms=1.0,
                          shed_slack_ms=2 * deadline_ms)
        groups = {"prod": prim}
        if with_cheap:
            groups["tiny"] = make_group("tiny", eng_cheap, good_ckpt)
        fleet = start_fleet(FleetRouter(
            groups, primary="prod",
            cheap="tiny" if with_cheap else None, tracer=tracer))
        futs: list = []
        shed = rejected = 0
        n_burst = 120
        for i in range(n_burst):  # back-to-back: the overload burst
            try:
                futs.append(fleet.submit_ids(
                    ids_for(schedule[i % len(schedule)], i),
                    deadline_ms=deadline_ms))
            except LoadShedError:
                shed += 1
            except QueueFullError:
                rejected += 1
        ok = lost = queued_shed = expired = 0
        for f in futs:
            try:
                f.result(timeout=deadline_ms / 1e3 + 10)
                ok += 1
            except LoadShedError:
                queued_shed += 1
            except Exception as e:  # noqa: BLE001
                if "Deadline" in type(e).__name__:
                    expired += 1
                else:
                    lost += 1
        fleet.stop(drain=True)
        chains_rep = validate_chains(load_records(tracer.flush()))
        chains_rep["incomplete"] = dict(
            list(chains_rep["incomplete"].items())[:5])
        snap = fleet.snapshot()
        out = {
            "label": label, "burst": n_burst, "ok": ok,
            "shed_on_arrival": shed, "shed_queued": queued_shed,
            "rejected": rejected, "deadline": expired, "lost": lost,
            "degraded": fleet.metrics.degraded_total.value,
            "degrade_fallthrough":
                fleet.metrics.degrade_fallthrough_total.value,
            "per_model_requests": {
                mid: snap["models"][mid]["router"]["requests_total"]
                for mid in snap["models"]},
            "chains": {k: v for k, v in chains_rep.items()
                       if k != "incomplete"},
            "chains_incomplete": chains_rep["incomplete"],
            "retraces_post_warmup": fleet.retraces_post_warmup,
        }
        print(f"[fleet] {label}: ok {ok}/{n_burst}  shed "
              f"{shed}+{queued_shed}  rejected {rejected}  degraded "
              f"{out['degraded']}", file=sys.stderr)
        return out

    control_burst = degrade_burst(False, "degrade/control")
    treat_burst = degrade_burst(True, "degrade/treatment")

    if control_burst["shed_on_arrival"] + control_burst["shed_queued"] \
            + control_burst["rejected"] < 1:
        failures.append("(c) the control burst never shed/rejected — the "
                        "overload is not an overload, nothing to absorb")
    if treat_burst["shed_on_arrival"] or treat_burst["shed_queued"] \
            or treat_burst["rejected"]:
        failures.append(
            f"(c) the degrade tier did NOT absorb the burst: "
            f"{treat_burst['shed_on_arrival']}+"
            f"{treat_burst['shed_queued']} shed, "
            f"{treat_burst['rejected']} rejected with a cheap model "
            "registered")
    if treat_burst["degraded"] < 1:
        failures.append("(c) no request was degraded — the band never "
                        "engaged")
    if treat_burst["lost"] or treat_burst["deadline"]:
        failures.append(f"(c) treatment lost {treat_burst['lost']} / "
                        f"expired {treat_burst['deadline']} — degraded "
                        "work must still complete")
    if treat_burst["chains"]["degraded"] != treat_burst["degraded"]:
        failures.append(
            f"(c) {treat_burst['degraded']} degrades counted but only "
            f"{treat_burst['chains']['degraded']} chains carry the "
            "degrade hop")
    if treat_burst["per_model_requests"].get("tiny", 0) \
            != treat_burst["degraded"]:
        failures.append(
            "(c) per-model metrics do not show the shift: cheap-model "
            f"requests {treat_burst['per_model_requests'].get('tiny')} "
            f"!= degraded {treat_burst['degraded']}")
    if treat_burst["chains_incomplete"]:
        failures.append(f"(c) incomplete chains "
                        f"{treat_burst['chains_incomplete']}")

    result = {
        "metric": "fleet_smoke",
        "requests": n_requests,
        "base_qps": base_qps,
        "calibration": {"forward_ms": round(forward_ms, 3),
                        "capacity_rps": round(capacity_rps, 1)},
        "deadline_ms": deadline_ms,
        "buckets": list(buckets),
        "batch_size": batch_size,
        "shadow_fraction": shadow_fraction,
        "shadow_impact": {
            "control_p99_ms": control_p99,
            "shadow_p99_ms": shadow_p99,
            "p99_gate": f"<= x{p99_factor} + {p99_margin_ms}ms",
            "outcome_parity": all(
                r["argmaxes"] == baseline_argmax
                for a in arms.values() for r in a),
            "passes": [{k: v for k, v in r.items() if k != "argmaxes"}
                       for a in arms.values() for r in a],
        },
        "rollout": {"good": good_run, "bad": bad_run},
        "degrade": {"control": control_burst, "treatment": treat_burst},
        "model": args.model,
        "devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
    }

    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2)
        os.replace(tmp, out_path)
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("shadow_impact", "rollout",
                                   "degrade")}))
    import shutil

    shutil.rmtree(trace_dir, ignore_errors=True)
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    if failures:
        sys.exit("fleet smoke FAILED:\n  - " + "\n  - ".join(failures)
                 + f"\n  see {out_path}")


def _silent_result(fut, timeout: float = 60.0):
    """Resolve a serve future to its logits or None (probe accounting —
    the probe's burst rides normal admission, so sheds are outcomes, not
    errors)."""
    try:
        return fut.result(timeout=timeout)
    except Exception:  # noqa: BLE001
        return None


def _smoke_model(args, vocab_size):
    """Mesh + sharded DP model + jitted step + put — the ONE model/mesh
    configuration every bench smoke measures against (``--pipeline``,
    ``--trace``, and ``--length`` all build on it, so they cannot drift in
    what they time).  Returns ``(mesh, cfg, tx, state0, sh, step, put)``."""
    from pdnlp_tpu.parallel import (
        make_global_batch, make_mesh, make_parallel_train_step,
        setup_sharded_model,
    )

    mesh = make_mesh(num_devices=args.num_devices, shape=args.mesh_shape)
    cfg, tx, state0, sh = setup_sharded_model(args, vocab_size, mesh, "dp")
    step = make_parallel_train_step(cfg, tx, args, mesh, sh)
    put = make_global_batch(mesh)
    return mesh, cfg, tx, state0, sh, step, put


def _smoke_train_setup(args):
    """Shared scaffold for the ``--pipeline`` and ``--trace`` smokes: the
    seeded corpus (real when present, synthetic otherwise), a
    fresh-DataLoader factory, and ONE jitted DP train step on the bench
    mesh (``_smoke_model``) — one copy, so the two smokes cannot drift in
    what they measure.  Returns ``(fresh_loader, mesh, state0, step, put)``."""
    import random

    from pdnlp_tpu.data import (
        Collator, DataLoader, WordPieceTokenizer, build_vocab,
    )
    from pdnlp_tpu.data.collate import EncodedDataset
    from pdnlp_tpu.data.sampler import DistributedShardSampler

    if os.path.exists(args.data_path):
        from pdnlp_tpu.data import load_data
        from pdnlp_tpu.data.tokenizer import get_or_build_vocab

        corpus = load_data(args.data_path)[:1024]
        tok = WordPieceTokenizer(get_or_build_vocab(args))
    else:
        chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐高兴悲伤讨厌愤怒"
        rng = random.Random(args.seed)
        corpus = [("".join(rng.choice(chars)
                           for _ in range(rng.randint(6, args.max_seq_len))),
                   rng.randrange(args.num_labels)) for _ in range(1010)]
        tok = WordPieceTokenizer(build_vocab((t for t, _ in corpus),
                                             size=256))

    def fresh_loader(encoded: bool = True):
        return DataLoader(
            corpus, Collator(tok, args.max_seq_len), args.train_batch_size,
            sampler=DistributedShardSampler(len(corpus), shuffle=True,
                                            seed=args.seed),
            prefetch=args.prefetch,
            encoded=EncodedDataset(corpus, tok, args.max_seq_len)
            if encoded else None)

    mesh, _cfg, _tx, state0, _sh, step, put = _smoke_model(
        args, tok.vocab_size)
    return fresh_loader, mesh, state0, step, put


def length_smoke(argv, modes_arg: str) -> None:
    """``--length {full,bucket,pack,all}``: length-aware training A/B.

    Short seeded training runs (bert-tiny, mesh DP, ``fuse_steps`` intact)
    per ``--length_mode``, all over ONE jitted step/multi-step pair, each
    driven through its own input pipeline (``auto`` — resident when
    eligible, exercising the per-bucket gathers).  The corpus is synthetic
    and CPU-safe with the REAL corpus's length shape (~18-token average,
    long tail) and a first-character-determined label, so every mode can
    actually learn it and the dev-accuracy parity gate compares converged
    numbers, not noise.  Reports per mode: samples/s and the speedup over
    ``full``, steps/epoch, compile counts (step + multi-step + resident
    gathers), the per-bucket batch histogram, token- and row-level padding
    waste, and dev accuracy on one SHARED full-width dev set (eval
    semantics never change with the training layout).  Exits non-zero on
    a retrace after warmup (any compile-cache growth during the timed
    epochs) or a dev-accuracy parity violation (``--length_tolerance``,
    default 0.08 absolute vs ``full``).  Writes ``results/
    length_smoke.json`` (override: ``--length_out``).
    """
    import random
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pdnlp_tpu.data import Collator, DataLoader, WordPieceTokenizer, build_vocab
    from pdnlp_tpu.data.collate import EncodedDataset
    from pdnlp_tpu.data.packing import PackedClassificationDataset
    from pdnlp_tpu.data.pipeline import build_pipeline
    from pdnlp_tpu.data.sampler import DistributedShardSampler
    from pdnlp_tpu.parallel import make_global_batch, make_parallel_eval_step
    from pdnlp_tpu.parallel.execution import make_parallel_multi_step
    from pdnlp_tpu.train.setup import build_length_train_loader
    from pdnlp_tpu.utils.config import Args, parse_cli, pop_cli_flag

    argv, out_path = pop_cli_flag(
        argv, "--length_out", os.path.join("results", "length_smoke.json"))
    argv, epochs = pop_cli_flag(argv, "--length_epochs", 6, int)
    argv, tolerance = pop_cli_flag(argv, "--length_tolerance", 0.08, float)
    # the smoke's bucket set adds a 16 floor under the stock 32/64/128:
    # this corpus (like the real one) averages ~18 tokens, so a 32-token
    # floor alone would pad the typical example ~45% — bucket choice is
    # part of the optimization, matched to the length profile
    args = parse_cli(argv, base=Args(
        model="bert-tiny", max_seq_len=128, train_batch_size=16,
        learning_rate=1e-3, dropout=0.0, attn_dropout=0.0, fuse_steps=4,
        length_buckets="16,32,64,128", log_every=10 ** 9))
    all_modes = ("full", "bucket", "pack")
    modes = all_modes if modes_arg == "all" else tuple(modes_arg.split(","))
    for m in modes:
        if m not in all_modes:
            sys.exit(f"--length {m!r}: pick from {'|'.join(all_modes)}|all")

    # synthetic corpus with the real corpus's length profile: one token per
    # CJK char, ~18-token average with a 30-126 tail; the label is a pure
    # function of the first character, so a converged dev accuracy is a
    # property of the MODE's training math, not of label noise
    chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐高兴悲伤讨厌愤怒"
    rng = random.Random(args.seed)

    def synth(n):
        out = []
        for _ in range(n):
            r = rng.random()
            length = (rng.randint(4, 24) if r < 0.78 else
                      rng.randint(25, 60) if r < 0.92 else
                      rng.randint(61, 126))
            text = "".join(rng.choice(chars) for _ in range(length))
            out.append((text, chars.index(text[0]) % args.num_labels))
        return out

    train_data, dev_data = synth(1024), synth(256)
    tok = WordPieceTokenizer(build_vocab((t for t, _ in train_data), size=256))
    col = Collator(tok, args.max_seq_len)
    enc = EncodedDataset(train_data, tok, args.max_seq_len)
    dev_enc = EncodedDataset(dev_data, tok, args.max_seq_len)
    dev_loader = DataLoader(
        dev_data, col, args.train_batch_size,
        sampler=DistributedShardSampler(len(dev_data), shuffle=False),
        encoded=dev_enc)

    mesh, cfg, tx, state0, sh, step, put = _smoke_model(args, tok.vocab_size)
    multi = make_parallel_multi_step(cfg, tx, args, mesh, sh)
    eval_step = make_parallel_eval_step(cfg, args, mesh, sh["params"])
    put_fused = make_global_batch(mesh, leading_stack=True)

    def cache_sizes(pipe):
        """(step, multi, gathers) compiled-variant counts — the bounded
        ``len(buckets) x len(step-variants)`` claim, measured."""
        gathers = sum(
            getattr(g, "_cache_size", lambda: 0)()
            for g in getattr(pipe, "_gathers", {}).values())
        return (step._cache_size(), multi._cache_size(), gathers)

    def run_epochs(pipe, state, n_epochs, first_epoch=0):
        """Dispatch ``n_epochs`` epochs; returns (state, examples, last).
        The caller fetches a VALUE from ``last`` before reading a clock —
        async dispatch would otherwise time enqueue, not compute."""
        examples, last = 0, None
        for e in range(first_epoch, first_epoch + n_epochs):
            pipe.set_epoch(e)
            for batch, n, fused, ex in pipe.macro_batches(args.fuse_steps):
                if fused:
                    state, m = multi(state, batch)
                    last = m["loss"][-1]
                else:
                    state, m = step(state, batch)
                    last = m["loss"]
                examples += ex
        return state, examples, last

    # compile the shared full-width eval program once up front: every mode
    # evaluates through the identical program, and the dev evals below all
    # run OUTSIDE the timed window
    ev = eval_step(state0["params"], put(next(iter(dev_loader))))
    float(jax.device_get(ev["correct"]))

    rows, acc_by_mode = [], {}
    for mode in modes:
        margs = args.replace(length_mode=mode)
        loader = build_length_train_loader(
            margs, train_data, col, enc,
            batch_size=args.train_batch_size)
        pipe = build_pipeline(margs, loader, put=put, put_fused=put_fused,
                              mesh=mesh)
        packed_stats = (loader.encoded.stats()
                        if isinstance(loader.encoded,
                                      PackedClassificationDataset) else None)
        # warmup: one full untimed epoch on a throwaway state copy visits
        # every (bucket x step-variant) shape this mode can produce.
        # step/multi jit caches are SHARED across the mode loop (that is
        # the point — one program pair), so per-mode compile counts are
        # deltas against the pre-warmup sizes, not absolute cache sizes
        pre = cache_sizes(pipe)
        wstate, _, wlast = run_epochs(
            pipe, jax.tree_util.tree_map(jnp.copy, state0), 1)
        float(jax.device_get(wlast))
        del wstate
        compiled = cache_sizes(pipe)
        pipe.stats.__init__()  # steady-state telemetry only
        pipe.stats.mode = pipe.mode

        state = jax.tree_util.tree_map(jnp.copy, state0)
        t0 = time.monotonic()
        state, examples, last = run_epochs(pipe, state, epochs,
                                           first_epoch=1)
        float(jax.device_get(last))  # completion barrier inside the timer
        elapsed = time.monotonic() - t0
        compiled_after = cache_sizes(pipe)
        retraces = sum(compiled_after) - sum(compiled)

        # dev accuracy, SHARED full-width eval path for every mode
        correct = weight = 0.0
        # untimed dev eval over a host loader: dispatch-all-then-gather is
        # already the async pattern, and the upload cost sits outside the
        # samples/s measurement window
        # jaxlint: disable=R7 — eval transport outside the timed window
        pending = [eval_step(state["params"], put(b)) for b in dev_loader]
        for m in jax.device_get(pending):
            correct += float(m["correct"])
            weight += float(m["weight"])
        acc = correct / max(weight, 1.0)
        acc_by_mode[mode] = acc
        del state

        snap = pipe.stats.snapshot()
        rows.append({
            "mode": mode,
            "pipeline": pipe.mode,
            "steps_per_epoch": len(loader),
            "epochs": epochs,
            "examples": examples,
            "samples_per_sec": round(examples / elapsed, 2),
            "steps_per_sec": round(snap["steps"] / elapsed, 2),
            "dev_accuracy": round(acc, 4),
            "compiled_variants": {
                "train_step": compiled[0] - pre[0],
                "multi_step": compiled[1] - pre[1],
                "resident_gathers": compiled[2] - pre[2]},
            "retraces_post_warmup": retraces,
            "padding_waste_tokens": snap["padding_waste_tokens"],
            "padding_waste_rows": snap["padding_waste_ratio"],
            "batches_by_bucket": {
                seq: b["steps"] for seq, b in
                snap.get("by_bucket", {}).items()},
            "by_bucket": snap.get("by_bucket"),
            "packing": packed_stats,
        })

    by_mode = {r["mode"]: r for r in rows}
    base_rate = by_mode.get("full", {}).get("samples_per_sec")
    for r in rows:
        r["speedup_vs_full"] = (round(r["samples_per_sec"] / base_rate, 3)
                                if base_rate and r["mode"] != "full"
                                else None)
    result = {
        "metric": "length_smoke",
        "model": args.model,
        "batch_size": args.train_batch_size,
        "seq_len": args.max_seq_len,
        "buckets": args.length_buckets,
        "fuse_steps": args.fuse_steps,
        "train_examples": len(train_data),
        "dev_examples": len(dev_data),
        "devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
        "dtype": args.dtype,
        "accuracy_tolerance": tolerance,
        "modes": rows,
    }
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2)
        os.replace(tmp, out_path)
    print(json.dumps({**result,
                      "modes": [{k: v for k, v in r.items()
                                 if k != "by_bucket"} for r in rows]}))
    bad_retrace = [r["mode"] for r in rows if r["retraces_post_warmup"]]
    if bad_retrace:
        sys.exit("length smoke FAILED: post-warmup retrace in "
                 f"{bad_retrace} — the compile count is not bounded by "
                 f"buckets x step-variants; see {out_path}")
    if "full" in acc_by_mode:
        drift = {m: round(a - acc_by_mode["full"], 4)
                 for m, a in acc_by_mode.items() if m != "full"}
        worst = [m for m, d in drift.items() if d < -tolerance]
        if worst:
            sys.exit("length smoke FAILED: dev-accuracy parity violated "
                     f"for {worst} (drift {drift}, tolerance {tolerance}) "
                     f"— see {out_path}")


def pipeline_smoke(argv, modes_arg: str) -> None:
    """``--pipeline {resident,prefetch,sync,all}``: input-pipeline A/B.

    Short seeded training runs (bert-tiny, mesh DP) through ONE shared
    jitted step, one run per pipeline mode, reporting steps/s and the
    transport counters (bytes uploaded per step, put-wait seconds,
    padding-waste ratio) — the numbers behind the device-resident claim:
    0 steady-state bytes/step at >= the sync pipeline's rate, with BITWISE
    identical per-step losses (enforced; a mismatch exits non-zero, as
    does any in-loop upload in resident mode).  ``resident`` is refused —
    loudly, with the reason recorded in the JSON — when the loader has no
    frozen ``EncodedDataset`` (a shuffling/augmenting collator re-encodes
    per epoch; there is nothing deterministic to hold in HBM).  Writes
    ``results/pipeline_smoke.json`` (override: ``--pipeline_out``); steps
    per mode: ``--pipeline_steps`` (default 30).  Deterministic and
    CPU-safe: a seeded synthetic corpus stands in when the real one is
    absent.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pdnlp_tpu.data.pipeline import build_pipeline
    from pdnlp_tpu.utils.config import Args, parse_cli, pop_cli_flag

    argv, out_path = pop_cli_flag(
        argv, "--pipeline_out", os.path.join("results", "pipeline_smoke.json"))
    # default covers one full epoch incl. the short final chunk, so the
    # padding-waste counter is exercised, not just defined
    argv, n_steps = pop_cli_flag(argv, "--pipeline_steps", 32, int)
    args = parse_cli(argv, base=Args(
        model="bert-tiny", max_seq_len=32, train_batch_size=32,
        learning_rate=1e-3, log_every=10 ** 9))
    all_modes = ("sync", "prefetch", "resident")
    modes = all_modes if modes_arg == "all" else tuple(modes_arg.split(","))
    for m in modes:
        if m not in all_modes:
            sys.exit(f"--pipeline {m!r}: pick from "
                     f"{'|'.join(all_modes)}|all")

    fresh_loader, mesh, state0, step, put = _smoke_train_setup(args)

    rows, losses = [], {}
    for mode in modes:
        loader = fresh_loader()
        pipe = build_pipeline(args.replace(pipeline=mode), loader, put=put,
                              mesh=mesh)
        # compile step + (resident) gather outside the timed window
        warm = pipe.warmup_batch(1)
        wstate, m = step(jax.tree_util.tree_map(jnp.copy, state0), warm)
        float(jax.device_get(m["loss"]))
        del wstate
        pipe.stats.__init__()  # drop warmup counts; keep steady-state only
        pipe.stats.mode = mode

        state = jax.tree_util.tree_map(jnp.copy, state0)
        seen, epoch = [], 0
        t0 = time.monotonic()
        while len(seen) < n_steps:
            pipe.set_epoch(epoch)
            for batch, n, _fused, _ex in pipe.macro_batches(1):
                state, m = step(state, batch)
                seen.append(m["loss"])
                if len(seen) == n_steps:
                    break
            epoch += 1
        losses[mode] = [float(x) for x in jax.device_get(seen)]
        elapsed = time.monotonic() - t0
        del state
        snap = pipe.stats.snapshot()
        rows.append({"mode": mode, "steps": n_steps,
                     "steps_per_sec": round(n_steps / elapsed, 2),
                     **{k: snap[k] for k in (
                         "bytes_per_step", "bytes_uploaded_in_loop",
                         "bytes_uploaded_total", "puts_in_loop",
                         "put_wait_sec", "padding_waste_ratio",
                         "prefetch_in_flight_max")}})

    # the refusal gate, demonstrated: no EncodedDataset -> no resident mode
    try:
        build_pipeline(args.replace(pipeline="resident"),
                       fresh_loader(encoded=False), put=put, mesh=mesh)
        refusal = None
    except ValueError as e:
        refusal = str(e)

    by_mode = {r["mode"]: r for r in rows}
    parity = None
    if "sync" in losses and "resident" in losses:
        parity = losses["sync"] == losses["resident"]
    result = {
        "metric": "pipeline_smoke",
        "model": args.model,
        "batch_size": args.train_batch_size,
        "seq_len": args.max_seq_len,
        "devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
        "dtype": args.dtype,
        "pipelines": rows,
        "loss_parity_bitwise": parity,
        "resident_vs_sync_speedup": round(
            by_mode["resident"]["steps_per_sec"]
            / by_mode["sync"]["steps_per_sec"], 3)
        if {"resident", "sync"} <= set(by_mode) else None,
        "resident_refusal_without_encoded": refusal,
    }
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2)
        os.replace(tmp, out_path)
    print(json.dumps(result))
    if "resident" in by_mode and \
            by_mode["resident"]["bytes_uploaded_in_loop"] != 0:
        sys.exit("pipeline smoke FAILED: resident mode uploaded "
                 f"{by_mode['resident']['bytes_uploaded_in_loop']} in-loop "
                 f"bytes (expected 0) — see {out_path}")
    if parity is False:
        sys.exit("pipeline smoke FAILED: resident losses diverge from sync "
                 f"— the gather is not bitwise faithful; see {out_path}")
    if refusal is None:
        sys.exit("pipeline smoke FAILED: resident mode accepted a loader "
                 "with no EncodedDataset (non-deterministic collation)")


def trace_smoke(argv) -> None:
    """``--trace``: obs tracing smoke — overhead gate + phase breakdown.

    Two short seeded training loops over ONE shared jitted step and
    warmed pipeline: untraced (a disabled ``obs.Tracer``, the exact no-op
    object production runs carry) vs traced (spans + per-step breakdown +
    regression detector).  Both variants run ``--trace_repeats`` times
    interleaved and keep their best rate — the honest comparison under CPU
    scheduler noise.  Reports steps/s for both, the overhead percentage,
    and the traced run's per-phase mean/p50/p95 breakdown embedded in the
    JSON; writes ``results/trace_smoke.json`` (override ``--trace_out``)
    plus the Chrome-trace export next to it, and EXITS NON-ZERO when the
    overhead exceeds ``--trace_tolerance`` (default 2%) or the export
    violates the Chrome-trace schema.  Deterministic and CPU-safe: the
    seeded synthetic corpus stands in when the real one is absent.
    """
    import time

    import jax
    import jax.numpy as jnp

    from pdnlp_tpu.data.pipeline import build_pipeline
    from pdnlp_tpu.obs import RegressionDetector, StepBreakdown, Tracer
    from pdnlp_tpu.obs.export import to_chrome_trace, write_chrome_trace
    from pdnlp_tpu.utils.config import Args, parse_cli, pop_cli_flag

    argv, out_path = pop_cli_flag(
        argv, "--trace_out", os.path.join("results", "trace_smoke.json"))
    argv, n_steps = pop_cli_flag(argv, "--trace_steps", 48, int)
    argv, repeats = pop_cli_flag(argv, "--trace_repeats", 3, int)
    argv, tolerance = pop_cli_flag(argv, "--trace_tolerance", 2.0, float)
    args = parse_cli(argv, base=Args(
        model="bert-tiny", max_seq_len=32, train_batch_size=32,
        learning_rate=1e-3, log_every=10 ** 9))

    fresh_loader, mesh, state0, step, put = _smoke_train_setup(args)

    # one pipeline per variant (the resident upload happens at build);
    # the traced pipeline's tracer is swapped per repeat below
    off = Tracer(enabled=False)
    pipes = {"untraced": build_pipeline(args, fresh_loader(), put=put,
                                        mesh=mesh, tracer=off),
             "traced": build_pipeline(args, fresh_loader(), put=put,
                                      mesh=mesh)}

    # compile the step + gather outside every timed window
    warm = pipes["untraced"].warmup_batch(1)
    wstate, m = step(jax.tree_util.tree_map(jnp.copy, state0), warm)
    float(jax.device_get(m["loss"]))
    del wstate, warm

    def timed_loop(pipe, tracer):
        """The traced-trainer loop shape: data_wait around the iterator,
        step_dispatch around the step, device_block on the loss.  With a
        disabled tracer every obs call is the production no-op, so the
        two variants differ ONLY by tracing overhead."""
        state = jax.tree_util.tree_map(jnp.copy, state0)
        seen, epoch, m = 0, 0, None
        t0 = time.monotonic()
        while seen < n_steps:
            pipe.set_epoch(epoch)
            for batch, n, _fused, _ex in tracer.wrap_iter(
                    "data_wait", pipe.macro_batches(1)):
                with tracer.span("step_dispatch", step=seen + 1, n=n):
                    state, m = step(state, batch)
                tracer.block(m["loss"], step=seen + 1, n=n)
                seen += 1
                if seen == n_steps:
                    break
            epoch += 1
        float(jax.device_get(m["loss"]))  # completion barrier, both runs
        dt = time.monotonic() - t0
        del state
        return n_steps / dt

    best = {"untraced": 0.0, "traced": 0.0}
    breakdown = detector = tracer = None
    for _ in range(max(1, repeats)):
        best["untraced"] = max(best["untraced"],
                               timed_loop(pipes["untraced"], off))
        tracer = Tracer(enabled=True)
        detector = RegressionDetector()
        breakdown = StepBreakdown(on_step=detector.observe)
        tracer.add_listener(breakdown.feed)
        pipes["traced"]._tracer = tracer
        best["traced"] = max(best["traced"],
                             timed_loop(pipes["traced"], tracer))
        breakdown.close()

    overhead_pct = (best["untraced"] / best["traced"] - 1.0) * 100
    records = tracer.records()
    chrome = to_chrome_trace(records)
    schema_ok = bool(chrome["traceEvents"]) and all(
        k in ev for ev in chrome["traceEvents"]
        for k in ("name", "ph", "ts", "pid", "tid"))
    trace_path = None
    if out_path:
        trace_path = out_path.rsplit(".", 1)[0] + ".trace.json"
        write_chrome_trace(records, trace_path)

    result = {
        "metric": "trace_smoke",
        "model": args.model,
        "batch_size": args.train_batch_size,
        "seq_len": args.max_seq_len,
        "steps": n_steps,
        "repeats": repeats,
        "pipeline": pipes["traced"].mode,
        "devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
        "dtype": args.dtype,
        "untraced_steps_per_sec": round(best["untraced"], 2),
        "traced_steps_per_sec": round(best["traced"], 2),
        "overhead_pct": round(overhead_pct, 2),
        "tolerance_pct": tolerance,
        "spans_recorded": len(records),
        "chrome_schema_ok": schema_ok,
        "chrome_export": trace_path,
        "regress_events": (detector.events if detector else []),
        "breakdown": breakdown.summary() if breakdown else None,
    }
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2)
        os.replace(tmp, out_path)
    print(json.dumps({k: v for k, v in result.items()
                      if k != "breakdown"}))
    if not schema_ok:
        sys.exit("trace smoke FAILED: Chrome-trace export is missing "
                 f"required event keys — see {trace_path}")
    if overhead_pct > tolerance:
        sys.exit(f"trace smoke FAILED: tracing costs {overhead_pct:.2f}% "
                 f"steps/s (tolerance {tolerance}%) — see {out_path}")


def telemetry_smoke(argv) -> None:
    """``--telemetry``: full-telemetry-plane overhead gate on the serve
    path.

    One closed-loop serve storm (DynamicBatcher over a bert-tiny engine,
    mixed-length synthesized requests) run twice, interleaved
    ``--telemetry_repeats`` times:

    - **OFF** — tracer disabled: no spans, no request hops, no memory
      sampling (the production default);
    - **ON** — the whole plane: span + per-request hop tracing, the
      per-batch HBM sampler, the live ``MetricsExporter`` (ephemeral-port
      ``/metrics`` + ``/healthz``) AND the flight-recorder JSONL at a
      2s cadence (5x the production 10s default).

    Throughput is estimated **per chunk, min over passes**: each arm's
    request stream is split into window-aligned chunks (drained at the
    boundary — batch formation stays deterministic, the bench asserts
    identical batch counts per arm) and each chunk keeps its FASTEST
    observation across the interleaved passes.  A shared-CI host's CPU
    steals are bursty; min-per-chunk filters them where a best-of over
    whole runs would need one entirely-clean 5-second window per arm —
    the same reason microbenchmarks report min, applied piecewise.

    Gates (non-zero exit): throughput delta <= ``--telemetry_tolerance``
    (default 1%), a NON-EMPTY ``/metrics`` scrape taken mid-storm (from a
    side thread — a dashboard polling must not need the storm to pause),
    at least one flight-recorder line on disk, and every ON-arm request's
    hop chain complete through the flushed span file (the
    ``trace_tpu.py request`` path).  Snapshot:
    ``results/telemetry_smoke.json``.  CPU-safe: the memory sampler
    no-ops where ``memory_stats`` is unsupported (recorded as
    ``memory.supported=false``).
    """
    import random
    import tempfile
    import threading
    import time
    import urllib.request
    from collections import deque

    import jax

    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
    from pdnlp_tpu.obs import MetricsExporter
    from pdnlp_tpu.obs.export import load_records
    from pdnlp_tpu.obs.request import validate_chains
    from pdnlp_tpu.obs.trace import Tracer
    from pdnlp_tpu.serve import DynamicBatcher, InferenceEngine
    from pdnlp_tpu.utils.config import Args, parse_cli, pop_cli_flag

    argv, n_requests = pop_cli_flag(argv, "--telemetry_requests", 1600,
                                    int)
    argv, repeats = pop_cli_flag(argv, "--telemetry_repeats", 8, int)
    argv, tolerance = pop_cli_flag(argv, "--telemetry_tolerance", 1.0,
                                   float)
    argv, out_path = pop_cli_flag(
        argv, "--telemetry_out",
        os.path.join("results", "telemetry_smoke.json"))
    args = parse_cli(argv, base=Args(model="bert-tiny"))

    chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐高兴悲伤讨厌愤怒"
    rng = random.Random(args.seed)
    lengths = [8, 14, 22, 30, 44, 58]
    texts = ["".join(rng.choice(chars)
                     for _ in range(lengths[i % len(lengths)]))
             for i in range(n_requests)]
    if os.path.exists(args.data_path) or os.path.exists(args.vocab_path):
        from pdnlp_tpu.data.tokenizer import get_or_build_vocab

        tok = WordPieceTokenizer(get_or_build_vocab(args))
    else:
        tok = WordPieceTokenizer(build_vocab(texts, size=256))

    # jaxlint: disable=L1 — flight recorder stays for post-run inspection
    td = tempfile.mkdtemp(prefix="pdnlp-telemetry-")
    # ONE tracer toggled per arm: the engine binds it at construction, and
    # flipping .enabled is exactly how production flips --trace
    tracer = Tracer(td, enabled=False, process_index=0)
    engine = InferenceEngine(args, tokenizer=tok, mesh=None, tracer=tracer)
    buckets = (32, 64)
    id_lists = [tok.encode_ids(t, max(buckets)) for t in texts]
    total_tokens = sum(len(i) for i in id_lists)
    flight_path = os.path.join(td, "flight.jsonl")
    chunk = 80  # window-aligned: every chunk drains to an empty batcher

    def run_arm(telemetry_on: bool) -> tuple:
        tracer.enabled = telemetry_on
        tracer.clear()
        exporter = None
        scrape: dict = {}
        scrape_thread = None
        batches0 = engine.metrics.batches_total.value
        if telemetry_on:
            exporter = MetricsExporter(
                {"serve": engine.metrics.snapshot,
                 "memory": engine.memory_snapshot},
                port=0, flight_path=flight_path,
                flight_interval_s=2.0).start()

        def scrape_now():
            try:
                base = f"http://127.0.0.1:{exporter.port}"
                with urllib.request.urlopen(base + "/metrics",
                                            timeout=10) as r:
                    scrape["metrics"] = r.read().decode()
                with urllib.request.urlopen(base + "/healthz",
                                            timeout=10) as r:
                    scrape["healthz"] = json.loads(r.read().decode())
            except Exception as e:  # noqa: BLE001 — recorded, gated below
                scrape["error"] = f"{type(e).__name__}: {e}"

        batcher = DynamicBatcher(engine, buckets=buckets, max_batch_size=8,
                                 max_wait_ms=2.0, max_queue=256,
                                 serve_pack="off").start()
        batcher.warmup()
        window = 2 * batcher.max_batch_size
        inflight: deque = deque()
        rids = []
        chunk_times = []
        t0 = time.monotonic()
        for i, ids in enumerate(id_lists):
            if telemetry_on and i == n_requests // 2:
                # mid-storm scrape from a side thread: the exporter must
                # serve a dashboard WHILE the storm runs, not around it
                scrape_thread = threading.Thread(target=scrape_now,
                                                 daemon=True)
                scrape_thread.start()
            fut = batcher.submit_ids(list(ids))
            rids.append(fut.rid)
            inflight.append(fut)
            while len(inflight) >= window:
                inflight.popleft().result(timeout=60)
            if (i + 1) % chunk == 0:
                while inflight:  # drain: chunk time owns its batches
                    inflight.popleft().result(timeout=60)
                t1 = time.monotonic()
                chunk_times.append(t1 - t0)
                t0 = t1
        while inflight:
            inflight.popleft().result(timeout=60)
        if n_requests % chunk:
            # a request count that is not a chunk multiple leaves a tail
            # whose tokens are counted — its time must be too
            chunk_times.append(time.monotonic() - t0)
        batcher.stop(drain=True)
        if scrape_thread is not None:
            scrape_thread.join(timeout=15)
        if exporter is not None:
            exporter.stop()
        batches = engine.metrics.batches_total.value - batches0
        return chunk_times, scrape, rids, batches

    best: dict = {"off": None, "on": None}
    # EVERY repeat's batch count (not just the last): the min-per-chunk
    # pool draws timings from all repeats, so any repeat that formed
    # different batches would poison the A/B
    batch_counts: dict = {"off": [], "on": []}
    per_repeat = []
    scrape: dict = {}
    rids: list = []
    for _ in range(max(1, repeats)):
        for mode in ("off", "on"):
            times, s, r_ids, batches = run_arm(mode == "on")
            batch_counts[mode].append(batches)
            if mode == "on":
                scrape, rids = s, r_ids
            best[mode] = times if best[mode] is None else \
                [min(a, b) for a, b in zip(best[mode], times)]
        per_repeat.append({
            m: round(total_tokens / sum(best[m]), 1) for m in best})
    off_tps = total_tokens / sum(best["off"])
    on_tps = total_tokens / sum(best["on"])
    overhead_pct = (off_tps / on_tps - 1.0) * 100

    # chain integrity of the LAST ON arm, through the file round trip
    trace_path = tracer.flush()
    chains = validate_chains(load_records(trace_path), rids)
    chains["incomplete"] = dict(list(chains["incomplete"].items())[:5])
    flight_lines = 0
    if os.path.exists(flight_path):
        with open(flight_path) as f:
            flight_lines = sum(1 for _ in f)
    memory = engine.memory_snapshot()

    result = {
        "metric": "telemetry_smoke",
        "model": args.model,
        "requests": n_requests,
        "real_tokens": total_tokens,
        "repeats": repeats,
        "buckets": list(buckets),
        "off_tokens_per_s": round(off_tps, 1),
        "on_tokens_per_s": round(on_tps, 1),
        "overhead_pct": round(overhead_pct, 2),
        "tolerance_pct": tolerance,
        "estimator": f"min-per-{chunk}-request-chunk over "
                     f"{repeats} interleaved passes",
        "batches_per_arm": batch_counts,
        "per_repeat_cumulative": per_repeat,
        "scrape": {
            "metrics_bytes": len(scrape.get("metrics", "")),
            "has_serve_counters": "pdnlp_serve_requests_total"
                                  in scrape.get("metrics", ""),
            "healthz": scrape.get("healthz"),
            "error": scrape.get("error"),
        },
        "flight_records": flight_lines,
        "request_tracing": chains,
        "memory": memory,
        "spans_recorded": len(tracer.records()),
        "devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
    }
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2)
        os.replace(tmp, out_path)
    print(json.dumps({k: v for k, v in result.items()
                      if k != "per_repeat_cumulative"}))

    failures = []
    if overhead_pct > tolerance:
        failures.append(
            f"telemetry plane costs {overhead_pct:.2f}% token throughput "
            f"(tolerance {tolerance}%): off {off_tps:.0f} vs on "
            f"{on_tps:.0f} tok/s")
    if batch_counts.get("off") != batch_counts.get("on"):
        failures.append(
            "batch formation diverged between arms "
            f"({batch_counts}) — the A/B is not comparing like work")
    if not result["scrape"]["has_serve_counters"]:
        failures.append(
            "mid-storm /metrics scrape missing serve counters "
            f"(bytes={result['scrape']['metrics_bytes']}, "
            f"error={result['scrape']['error']})")
    if flight_lines < 1:
        failures.append("flight recorder left no lines on disk")
    if chains["complete"] < chains["checked"]:
        failures.append(
            f"{chains['checked'] - chains['complete']} request(s) "
            f"without a complete hop chain ({chains['incomplete']})")
    if failures:
        sys.exit("telemetry smoke FAILED:\n  - "
                 + "\n  - ".join(failures) + f"\n  see {out_path}")


def kernel_smoke(argv) -> None:
    """``--kernels``: kernel-path parity + A/B smoke.

    Four gated blocks, written to ``results/kernel_smoke.json`` (override
    ``--kernels_out``), non-zero exit on any violation:

    1. **flash-attention parity** — pallas fwd/bwd vs XLA (dense mask AND
       segment-native packed mask), max |Δ| gated at fp32 tolerance;
    2. **no-HBM-bias proof** — the jaxpr of a packed ``bert.classify`` is
       walked recursively: under ``attn_impl=pallas`` NO equation may
       produce the [B, 1, S, S] ``segment_bias`` tensor (the XLA route
       must, as the sanity control) — materialization is checked
       structurally, not inferred from timings;
    3. **fused-CE parity** — kernel (loss, correct, objective) + grads vs
       the unfused logits path, and a full train step ``--fused_ce
       pallas`` vs ``xla`` at loss parity;
    4. **int8 serving** — a short seeded training run produces a real
       checkpoint; a bf16 and an int8 engine (the int8 one loading a
       ``quantize_ckpt``-style artifact) score the same dev set at
       dev-accuracy parity (``--kernels_tolerance``), zero post-warmup
       retraces each, with serve-forward throughput and the weight-bytes
       ratio recorded.  The >=1.5x int8 throughput gate applies on TPU,
       where the forward is weight-bound; on CPU the measured ratio is
       recorded (XLA CPU reads fp32-converted weights either way — there
       is no traffic to halve) and the gate is the parity set.

    Timings on a CPU host run the pallas kernels in INTERPRET mode (the
    ``pallas_interpreted`` flag in the JSON): numerics are identical to
    compiled Mosaic, speed is not — speedup columns are only meaningful
    from a TPU run, and the JSON says which kind produced it.
    """
    import random
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pdnlp_tpu.data import Collator, DataLoader, WordPieceTokenizer, build_vocab
    from pdnlp_tpu.data.collate import EncodedDataset
    from pdnlp_tpu.data.packing import segment_bias
    from pdnlp_tpu.data.sampler import DistributedShardSampler
    from pdnlp_tpu.models import bert, get_config
    from pdnlp_tpu.ops.attention import (
        dot_product_attention, mask_bias, resolve_impl, routed_impl,
    )
    from pdnlp_tpu.ops.fused_ce import fused_weighted_ce
    from pdnlp_tpu.serve import InferenceEngine
    from pdnlp_tpu.serve.offline import score_texts
    from pdnlp_tpu.serve.quant import quantize_params
    from pdnlp_tpu.train import checkpoint as ckpt_mod
    from pdnlp_tpu.train.steps import weighted_ce
    from pdnlp_tpu.utils.config import Args, parse_cli, pop_cli_flag

    argv, out_path = pop_cli_flag(
        argv, "--kernels_out", os.path.join("results", "kernel_smoke.json"))
    argv, epochs = pop_cli_flag(argv, "--kernels_epochs", 5, int)
    argv, tolerance = pop_cli_flag(argv, "--kernels_tolerance", 0.08, float)
    args = parse_cli(argv, base=Args(
        model="bert-tiny", max_seq_len=128, train_batch_size=16,
        learning_rate=1e-3, dropout=0.0, attn_dropout=0.0,
        log_every=10 ** 9))
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    failures = []

    def timeit_ms(fn, *a, reps=5):
        out = fn(*a)
        jax.block_until_ready(out)
        t0 = time.monotonic()
        for _ in range(reps):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.monotonic() - t0) / reps * 1e3

    # ---- 1. flash-attention parity (fwd + bwd), dense and segmented ----
    r = np.random.RandomState(args.seed)
    B, S, N, D = 2, args.max_seq_len, 4, 32
    q, k, v = (jnp.asarray(r.randn(B, S, N, D), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray((r.rand(B, S) > 0.2).astype(np.int32)).at[:, 0].set(1)
    bias = mask_bias(mask)
    seg = np.zeros((B, S), np.int32)
    for b in range(B):
        pos = 0
        for sid in range(1, 5):
            ln = r.randint(8, S // 3)
            seg[b, pos:pos + ln] = sid
            pos += ln
            if pos >= S:
                break
    segj = jnp.asarray(seg)
    seg_bias = jnp.asarray(segment_bias(seg))

    def attn_loss(impl, seg_route):
        def f(q, k, v):
            if seg_route:
                o = dot_product_attention(
                    q, k, v, impl=impl,
                    segment_ids=segj if impl == "pallas" else None,
                    bias=None if impl == "pallas" else seg_bias)
            else:
                o = dot_product_attention(q, k, v, bias, impl=impl)
            return (o.astype(jnp.float32) ** 2).sum()
        return f

    parity, attn_ms = {}, {}
    for label, seg_route in (("dense", False), ("packed", True)):
        outs, grads = {}, {}
        for impl in ("xla", "pallas"):
            fn = jax.jit(jax.value_and_grad(attn_loss(impl, seg_route),
                                            argnums=(0, 1, 2)))
            (val, g) = fn(q, k, v)
            outs[impl], grads[impl] = val, g
            attn_ms[f"attn_{label}_{impl}"] = round(
                timeit_ms(fn, q, k, v, reps=3 if impl == "pallas"
                          and not on_tpu else 5), 3)
        fwd_d = abs(float(outs["pallas"]) - float(outs["xla"])) \
            / max(abs(float(outs["xla"])), 1.0)
        bwd_d = max(float(jnp.abs(a - b).max())
                    for a, b in zip(grads["xla"], grads["pallas"]))
        parity[f"attn_{label}"] = {"fwd_rel": round(fwd_d, 9),
                                   "bwd_max_abs": round(bwd_d, 9)}
        if fwd_d > 1e-5 or bwd_d > 5e-4:
            failures.append(f"attention {label} parity: fwd_rel={fwd_d:g} "
                            f"bwd_max={bwd_d:g}")

    # ---- 2. structural no-HBM-bias proof on the packed classify --------
    cfg_t = get_config("bert-tiny", vocab_size=120).replace(max_position=S)
    params_t = bert.init_params(jax.random.key(0), cfg_t)
    M = 4
    cls = np.zeros((B, M), np.int64)
    for b in range(B):
        for mseg in range(1, M + 1):
            idx = np.flatnonzero(seg[b] == mseg)
            cls[b, mseg - 1] = idx[0] if idx.size else 0
    pbatch = {
        "input_ids": jnp.asarray(r.randint(0, 120, (B, S)), jnp.int32),
        "token_type_ids": jnp.zeros((B, S), jnp.int32),
        "attention_mask": jnp.asarray((seg > 0).astype(np.int32)),
        "segment_ids": segj,
        "cls_positions": jnp.asarray(cls, jnp.int32),
        "label": jnp.zeros((B, M), jnp.int32),
        "example_weight": jnp.ones((B, M), jnp.float32),
    }

    def shapes_in(jaxpr, acc):
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                aval = getattr(ov, "aval", None)
                if aval is not None and getattr(aval, "shape", None):
                    acc.add(tuple(aval.shape))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        shapes_in(inner, acc)
        return acc

    bias_shape = (B, 1, S, S)
    materialized = {}
    for impl in ("pallas", "xla"):
        jx = jax.make_jaxpr(
            lambda p, bt: bert.classify(p, cfg_t, bt, attn_impl=impl)
        )(params_t, pbatch)
        materialized[impl] = bias_shape in shapes_in(jx.jaxpr, set())
    if materialized["pallas"]:
        failures.append("packed pallas route materializes the "
                        f"{bias_shape} segment_bias in its jaxpr")
    if not materialized["xla"]:
        failures.append("sanity: the XLA fallback no longer materializes "
                        "segment_bias — the structural check lost its "
                        "control")

    # ---- 3. fused-CE parity + train-step A/B ---------------------------
    T, H, C = 96, 64, args.num_labels
    f32 = jnp.asarray(r.randn(T, H), jnp.float32)
    W = jnp.asarray(r.randn(H, C) * 0.1, jnp.float32)
    bW = jnp.asarray(r.randn(C) * 0.1, jnp.float32)
    lab = jnp.asarray(r.randint(0, C, T))
    wts = jnp.asarray((r.rand(T) > 0.2).astype(np.float32))

    def ce_obj(fused):
        def f(f32, W, bW):
            if fused:
                return fused_weighted_ce(f32, W, bW, lab, wts,
                                         smoothing=0.1)[2]
            return weighted_ce(f32 @ W + bW, lab, wts, smoothing=0.1)[2]
        return f

    ce_ms, ce_out = {}, {}
    for mode, fused in (("xla", False), ("pallas", True)):
        fn = jax.jit(jax.value_and_grad(ce_obj(fused), argnums=(0, 1, 2)))
        ce_out[mode] = fn(f32, W, bW)
        ce_ms[f"fused_ce_{mode}"] = round(timeit_ms(fn, f32, W, bW), 3)
    ce_val = abs(float(ce_out["pallas"][0]) - float(ce_out["xla"][0]))
    ce_grad = max(float(jnp.abs(a - b).max())
                  for a, b in zip(ce_out["xla"][1], ce_out["pallas"][1]))
    parity["fused_ce"] = {"value_abs": round(ce_val, 9),
                          "grad_max_abs": round(ce_grad, 9)}
    if ce_val > 1e-5 or ce_grad > 1e-4:
        failures.append(f"fused-CE parity: value={ce_val:g} "
                        f"grad_max={ce_grad:g}")

    # ---- 4. train a real checkpoint, then serve bf16 vs int8 -----------
    chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐高兴悲伤讨厌愤怒"
    rng = random.Random(args.seed)

    def synth(n):
        out = []
        for _ in range(n):
            ln = rng.randint(4, 24) if rng.random() < 0.8 \
                else rng.randint(25, 100)
            text = "".join(rng.choice(chars) for _ in range(ln))
            out.append((text, chars.index(text[0]) % args.num_labels))
        return out

    train_data, dev_data = synth(1024), synth(256)
    tok = WordPieceTokenizer(build_vocab((t for t, _ in train_data),
                                         size=256))
    mesh, cfg, tx, state0, sh, step, put = _smoke_model(args, tok.vocab_size)
    loader = DataLoader(
        train_data, Collator(tok, args.max_seq_len), args.train_batch_size,
        sampler=DistributedShardSampler(len(train_data), shuffle=True,
                                        seed=args.seed),
        encoded=EncodedDataset(train_data, tok, args.max_seq_len))
    state = state0
    for _ in range(epochs):
        # a one-shot seeded smoke train, outside every timed window; the
        # pipeline subsystem is not under test here
        for batch in loader:
            # jaxlint: disable=R7 — untimed checkpoint-producing loop
            state, m = step(state, put(batch))
    float(jax.device_get(m["loss"]))
    host_params = jax.device_get(state["params"])
    os.makedirs(args.output_dir, exist_ok=True)
    fpath = os.path.join(args.output_dir, "kernel-smoke-cls.msgpack")
    ckpt_mod.save_params(fpath, {"params": host_params})
    # the offline artifact (scripts/quantize_ckpt.py math, same module)
    from flax import serialization

    qpath = os.path.join(args.output_dir, "kernel-smoke-cls.int8.msgpack")
    qtmp = qpath + ".tmp"
    with open(qtmp, "wb") as fh:
        fh.write(serialization.to_bytes(quantize_params(host_params)))
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(qtmp, qpath)

    dev_texts = [t for t, _ in dev_data]
    dev_labels = np.asarray([y for _, y in dev_data])
    serve_rows, serve = {}, []
    fixed_ids = [[2] + list(r.randint(5, tok.vocab_size - 1,
                                      r.randint(3, 30))) + [3]
                 for _ in range(64)]
    for mode, path in (("bf16", fpath), ("int8", qpath)):
        eng = InferenceEngine(args.replace(serve_dtype=mode),
                              tokenizer=tok, mesh=mesh)
        eng.load_checkpoint(path)
        preds, _ = score_texts(eng, dev_texts, buckets=(32, 64, 128),
                               batch_size=16)
        acc = float((np.asarray(preds) == dev_labels).mean())
        eng.infer_ids(fixed_ids, args.max_seq_len)  # warm the fixed shape
        warm_retraces = eng.metrics.retraces.value
        fwd_ms = timeit_ms(lambda: eng.infer_ids(fixed_ids,
                                                 args.max_seq_len), reps=10)
        retraces = eng.metrics.retraces.value - warm_retraces
        serve_rows[mode] = {"dev_accuracy": round(acc, 4),
                            "forward_ms_batch64": round(fwd_ms, 3),
                            "rows_per_sec": round(64 / (fwd_ms / 1e3), 1),
                            "retraces_post_warmup": retraces,
                            # the timed forward runs at max_seq_len; the
                            # bucketed accuracy pass routes per width
                            "attn_impl": eng.routed_attn(args.max_seq_len),
                            "attn_impl_by_seq": {
                                str(s): i for s, i
                                in sorted(eng.attn_impl_by_seq.items())},
                            "dtype": eng.dtype_label,
                            "checkpoint": path}
        serve.append(serve_rows[mode])
        if retraces:
            failures.append(f"serve {mode}: {retraces} post-warmup "
                            "retraces (expected 0)")
    acc_drift = serve_rows["int8"]["dev_accuracy"] \
        - serve_rows["bf16"]["dev_accuracy"]
    if acc_drift < -tolerance:
        failures.append(f"int8 dev accuracy {serve_rows['int8']['dev_accuracy']}"
                        f" vs bf16 {serve_rows['bf16']['dev_accuracy']} "
                        f"(drift {acc_drift:+.4f}, tolerance {tolerance})")
    int8_speedup = round(serve_rows["bf16"]["forward_ms_batch64"]
                         / serve_rows["int8"]["forward_ms_batch64"], 3)
    if on_tpu and int8_speedup < 1.5:
        failures.append(f"int8 serve speedup {int8_speedup} < 1.5x on TPU")

    # weight HBM traffic per forward: the roofline quantity int8 halves
    def dense_bytes(tree, per_elem):
        total = 0
        for node in jax.tree_util.tree_leaves_with_path(tree):
            path, leaf = node
            if path and getattr(path[-1], "key", None) == "kernel" \
                    and getattr(leaf, "ndim", 0) >= 2:
                total += leaf.size * per_elem
        return total

    bytes_bf16 = dense_bytes(host_params, 2)
    qtree = quantize_params(host_params)
    bytes_int8 = dense_bytes(qtree, 1) + sum(
        leaf.size * 4 for path, leaf in
        jax.tree_util.tree_leaves_with_path(qtree)
        if path and getattr(path[-1], "key", None) == "qscale")

    result = {
        "metric": "kernel_smoke",
        "model": args.model,
        "seq_len": S,
        "devices": jax.device_count(),
        "platform": platform,
        "pallas_interpreted": not on_tpu,
        "routing": {
            # the policy table (resolve_impl), independent of this host's
            # backend: packed batches default to the segment-native kernel
            # on TPU; plus what THIS run actually routed
            "auto_packed_tpu": resolve_impl("auto", segmented=True,
                                            backend="tpu"),
            "auto_dense_tpu": resolve_impl("auto", segmented=False,
                                           backend="tpu"),
            "auto_packed_here": routed_impl("auto", S, segmented=True),
            "dropout_forces": routed_impl("pallas", S, dropout=True),
        },
        "segment_bias_materialized": materialized,
        "parity": parity,
        "timings_ms": {**attn_ms, **ce_ms},
        "serve": serve,
        "int8_vs_bf16": {
            "dev_accuracy_drift": round(acc_drift, 4),
            "accuracy_tolerance": tolerance,
            "forward_speedup": int8_speedup,
            "speedup_gate": "enforced >=1.5x on tpu; recorded on cpu "
                            "(weight traffic is the TPU-side bound)",
            "weight_bytes_bf16": bytes_bf16,
            "weight_bytes_int8": bytes_int8,
            "weight_bytes_ratio": round(bytes_bf16 / bytes_int8, 3),
        },
        "train": {"epochs": epochs, "examples": epochs * len(train_data),
                  "final_loss": round(float(jax.device_get(m["loss"])), 4)},
    }
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2)
        os.replace(tmp, out_path)
    print(json.dumps(result))
    if failures:
        sys.exit("kernel smoke FAILED:\n  - " + "\n  - ".join(failures)
                 + f"\nsee {out_path}")


def longcontext_smoke(argv) -> None:
    """``--longcontext``: the long-context gate (ROADMAP item 3).

    Six gated blocks, written to ``results/longcontext_smoke.json``
    (override ``--longcontext_out``), non-zero exit on any violation:

    1. **multi-tile kernel parity** — pallas fwd+bwd vs the XLA oracle at
       EVERY supported width (``--longcontext_widths``, default
       128/256/512), dense mask AND segment-native packed, plus the
       measured tile-map sparsity (the block-sparse skip's live fraction);
    2. **structural no-HBM-bias proof** — the jaxpr of a packed
       ``bert.classify`` at 512 and 1024 carries NO [B, 1, S, S] tensor
       under the pallas route (the XLA route must, as the control);
    3. **packed multi-width train throughput at 512** — ``--length_mode
       pack`` with 128/256/512 buckets vs the padded-full baseline over
       the SAME jitted DP step: gates fill >= 0.85 (the padding-waste
       headroom of the acceptance bar) and real-token throughput >=
       0.6x the slot-advantage (fill ratio of the two layouts), with
       zero post-warmup retraces;
    4. **ring+packed parity** — the sequence-parallel packed train step
       (ring attention, segment IDs sharded along seq) vs the
       single-device packed step, same batch, loss parity over 2 steps
       (recorded-skip on a single-device host);
    5. **mixed long/short storm** — chunked prefill (long widths 512)
       interleaved with a packed short-query storm on the online batcher:
       gates the short p99 against a short-only control run, exact
       long-request parity with whole-request scoring, zero lost;
    6. **zero post-warmup retraces** across the storm (the serve compile
       cache is closed by warmup, long widths included).

    Summary rows merge into ``results/longcontext.json`` (created when
    absent: the rows measured before PR 1 on v5e were removed with their
    record and not re-measured on this code) through
    ``scripts/bench_longcontext.merge_rows`` — error-free rows already in
    the file win over incoming ones.

    On a CPU host the pallas kernels run in INTERPRET mode (numerics
    identical, speed meaningless — the throughput gate compares packed
    vs padded under the SAME backend, so the ratio stays meaningful) and
    serve packing is forced on (``auto`` only packs on TPU).
    """
    import random
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pdnlp_tpu.data import Collator, DataLoader, WordPieceTokenizer, build_vocab
    from pdnlp_tpu.data.collate import EncodedDataset
    from pdnlp_tpu.data.packing import pack_id_lists, segment_bias, segment_cap
    from pdnlp_tpu.data.sampler import DistributedShardSampler
    from pdnlp_tpu.models import bert, get_config
    from pdnlp_tpu.ops import flash
    from pdnlp_tpu.ops.attention import (
        ROUTING_TABLE, dot_product_attention, mask_bias, routed_impl,
    )
    from pdnlp_tpu.serve import DynamicBatcher, InferenceEngine
    from pdnlp_tpu.train.setup import build_length_train_loader
    from pdnlp_tpu.utils.config import Args, parse_cli, pop_cli_flag

    argv, out_path = pop_cli_flag(
        argv, "--longcontext_out",
        os.path.join("results", "longcontext_smoke.json"))
    argv, widths_s = pop_cli_flag(argv, "--longcontext_widths", "128,256,512")
    argv, epochs = pop_cli_flag(argv, "--longcontext_epochs", 2, int)
    args = parse_cli(argv, base=Args(
        model="bert-tiny-long", max_seq_len=512, train_batch_size=8,
        learning_rate=1e-3, dropout=0.0, attn_dropout=0.0,
        length_buckets="128,256,512", log_every=10 ** 9))
    widths = tuple(int(w) for w in widths_s.split(",") if w.strip())
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    failures = []

    # ---- 1. multi-tile kernel parity at every width, dense + packed ----
    def packed_seg(B, S, seed):
        r = np.random.RandomState(seed)
        seg = np.zeros((B, S), np.int32)
        for b in range(B):
            pos, sid = 0, 0
            while pos < S - 24:
                ln = r.randint(8, 48)
                sid += 1
                seg[b, pos: pos + ln] = sid
                pos += ln
        return seg

    parity = {}
    Bk, N, D = 2, 2, 32
    for S in widths:
        if not flash.supported_seq(S):
            failures.append(f"width {S} does not tile the kernel blocks")
            continue
        r = np.random.RandomState(args.seed)
        q, k, v = (jnp.asarray(r.randn(Bk, S, N, D), jnp.float32)
                   for _ in range(3))
        seg = packed_seg(Bk, S, seed=S)
        segj = jnp.asarray(seg)
        seg_b = jnp.asarray(segment_bias(seg))
        mask = jnp.asarray((r.rand(Bk, S) > 0.4).astype(np.int32)
                           ).at[:, 0].set(1).at[-1, :].set(0)  # filler row
        bias = mask_bias(mask)
        cases = {
            "dense": (lambda q, k, v: dot_product_attention(
                q, k, v, bias, impl="xla"),
                lambda q, k, v: flash.flash_attention(q, k, v, bias=bias)),
            "packed": (lambda q, k, v: dot_product_attention(
                q, k, v, bias=seg_b, impl="xla"),
                lambda q, k, v: flash.flash_attention(
                    q, k, v, segment_ids=segj)),
        }
        row = {}
        for label, (ref_fn, ker_fn) in cases.items():
            def loss(f):
                return lambda q, k, v: (f(q, k, v).astype(jnp.float32)
                                        ** 2).sum()
            rv, rg = jax.jit(jax.value_and_grad(
                loss(ref_fn), argnums=(0, 1, 2)))(q, k, v)
            kv_, kg = jax.jit(jax.value_and_grad(
                loss(ker_fn), argnums=(0, 1, 2)))(q, k, v)
            fwd = abs(float(kv_) - float(rv)) / max(abs(float(rv)), 1.0)
            bwd = max(float(jnp.abs(a - b).max()) for a, b in zip(rg, kg))
            row[label] = {"fwd_rel": round(fwd, 9),
                          "bwd_max_abs": round(bwd, 9)}
            if fwd > 1e-5 or bwd > 5e-4:
                failures.append(f"width {S} {label} parity: fwd={fwd:g} "
                                f"bwd={bwd:g}")
        row["tile_map_live_fraction"] = round(float(np.asarray(
            flash.segment_block_map(segj)).mean()), 4)
        parity[str(S)] = row

    # ---- 2. structural no-HBM-bias proof at 512 and 1024 ---------------
    def shapes_in(jaxpr, acc):
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                aval = getattr(ov, "aval", None)
                if aval is not None and getattr(aval, "shape", None):
                    acc.add(tuple(aval.shape))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        shapes_in(inner, acc)
        return acc

    structural = {}
    cfg_t = get_config("bert-tiny-long", vocab_size=160)
    params_t = bert.init_params(jax.random.key(0), cfg_t)
    r = np.random.RandomState(0)
    for S in (512, 1024):
        cap = segment_cap(S, 8)
        lists = [list(r.randint(5, 150, r.randint(10, 100)))
                 for _ in range(12)]
        pbatch, _ = pack_id_lists(lists, S, rows=2, max_segments=cap)
        pbatch = {k2: jnp.asarray(v2) for k2, v2 in pbatch.items()}
        bias_shape = (2, 1, S, S)
        got = {}
        for impl in ("pallas", "xla"):
            jx = jax.make_jaxpr(
                lambda p, bt, impl=impl: bert.classify(p, cfg_t, bt,
                                                       attn_impl=impl)
            )(params_t, pbatch)
            got[impl] = bias_shape in shapes_in(jx.jaxpr, set())
        structural[str(S)] = got
        if got["pallas"]:
            failures.append(f"packed pallas route materializes the "
                            f"{bias_shape} bias at width {S}")
        if not got["xla"]:
            failures.append(f"sanity: XLA control lost its {bias_shape} "
                            f"materialization at width {S}")

    # ---- 3. packed multi-width train throughput at 512 -----------------
    chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐高兴悲伤讨厌愤怒"
    rng = random.Random(args.seed)

    def synth(n):
        out = []
        for _ in range(n):
            p = rng.random()
            ln = (rng.randint(6, 120) if p < 0.7 else
                  rng.randint(121, 350) if p < 0.92 else
                  rng.randint(351, 500))
            text = "".join(rng.choice(chars) for _ in range(ln))
            out.append((text, chars.index(text[0]) % args.num_labels))
        return out

    train_data = synth(512)
    tok = WordPieceTokenizer(build_vocab((t for t, _ in train_data),
                                         size=256))
    col = Collator(tok, args.max_seq_len)
    enc = EncodedDataset(train_data, tok, args.max_seq_len)
    mesh, cfg, tx, state0, sh, step, put = _smoke_model(args, tok.vocab_size)

    train_rows = {}
    for mode in ("full", "pack"):
        margs = args.replace(length_mode=mode)
        loader = build_length_train_loader(margs, train_data, col, enc,
                                           batch_size=args.train_batch_size)
        state = jax.tree_util.tree_map(jnp.copy, state0)
        pre = step._cache_size()
        for batch in loader:  # warmup epoch: visit every shape, untimed
            # jaxlint: disable=R7 — untimed warmup outside the measured loop
            state, m = step(state, put(batch))
        float(jax.device_get(m["loss"]))
        compiled = step._cache_size() - pre
        real = slots = steps = 0
        t0 = time.monotonic()
        for _ in range(epochs):
            for batch in loader:
                # the transport IS part of the measured tokens/s here and
                # both modes pay it identically
                # jaxlint: disable=R7 — transport is inside the metric
                state, m = step(state, put(batch))
                real += int(batch["attention_mask"].sum())
                slots += int(batch["attention_mask"].size)
                steps += 1
        float(jax.device_get(m["loss"]))
        elapsed = time.monotonic() - t0
        retraces = step._cache_size() - pre - compiled
        train_rows[mode] = {
            "steps": steps, "compiled_variants": compiled,
            "retraces_post_warmup": retraces,
            "fill_ratio": round(real / slots, 4),
            "tokens_real_per_sec": round(real / elapsed, 1),
            "tokens_slot_per_sec": round(slots / elapsed, 1),
            "attn_impl_packed_512": routed_impl(
                args.attention_impl, 512, segmented=(mode == "pack")),
        }
        if retraces:
            failures.append(f"train {mode}: {retraces} post-warmup "
                            "retraces")
    fill_packed = train_rows["pack"]["fill_ratio"]
    fill_full = train_rows["full"]["fill_ratio"]
    ratio = (train_rows["pack"]["tokens_real_per_sec"]
             / max(train_rows["full"]["tokens_real_per_sec"], 1e-9))
    headroom = fill_packed / max(fill_full, 1e-9)
    train_rows["pack"]["real_token_speedup_vs_full"] = round(ratio, 3)
    train_rows["pack"]["slot_advantage"] = round(headroom, 3)
    if fill_packed < 0.85:
        failures.append(f"packed fill {fill_packed} < 0.85")
    if ratio < 0.6 * headroom:
        failures.append(f"packed real-token throughput {ratio:.2f}x < "
                        f"0.6 x slot advantage {headroom:.2f}")

    # ---- 4. ring+packed vs single-device packed parity -----------------
    ring = {"devices": jax.device_count()}
    if jax.device_count() >= 2:
        from jax.sharding import PartitionSpec  # noqa: F401
        from pdnlp_tpu.parallel import make_mesh
        from pdnlp_tpu.parallel.sp import make_sp_batch, make_sp_train_step
        from pdnlp_tpu.train.steps import make_train_step

        shape = ({"data": 2, "seq": 2} if jax.device_count() >= 4
                 else {"seq": 2})
        sp_mesh = make_mesh(shape=shape)
        sargs = args.replace(dtype="float32")
        scfg = get_config(args.model, vocab_size=tok.vocab_size,
                          num_labels=args.num_labels, dropout=0.0,
                          attn_dropout=0.0)
        sparams = bert.init_params(jax.random.key(1), scfg)
        from pdnlp_tpu.train.optim import build_optimizer
        from pdnlp_tpu.train.steps import init_state
        stx = build_optimizer(sparams, sargs)
        sstate = init_state(jax.random.key(1), scfg, stx, params=sparams)
        rb = np.random.RandomState(7)
        lists = [list(rb.randint(5, tok.vocab_size - 1, rb.randint(12, 90)))
                 for _ in range(24)]
        pb, _ = pack_id_lists(lists, 256, rows=4, max_segments=16)
        M = pb["cls_positions"].shape[1]
        pb["label"] = rb.randint(0, args.num_labels, (4, M)).astype(np.int32)
        pb["example_weight"] = (pb["cls_positions"] > 0).astype(np.float32)
        pb["example_weight"][:, 0] = 1.0
        put_sp = make_sp_batch(sp_mesh)
        sp_step = make_sp_train_step(scfg, stx, sargs, sp_mesh)(put_sp(pb))
        single = jax.jit(make_train_step(scfg, stx, sargs),
                         donate_argnums=0)
        s1 = jax.tree_util.tree_map(jnp.copy, sstate)
        s2 = jax.tree_util.tree_map(jnp.copy, sstate)
        diffs = []
        for _ in range(2):
            s1, m1 = sp_step(s1, put_sp(pb))
            s2, m2 = single(s2, {k2: jnp.asarray(v2)
                                 for k2, v2 in pb.items()})
            diffs.append(abs(float(m1["loss"]) - float(m2["loss"])))
        ring.update({"mesh": shape, "loss_max_abs_diff": max(diffs)})
        if max(diffs) > 2e-5:
            failures.append(f"ring+packed loss diverges from single-device "
                            f"packed by {max(diffs):g}")
    else:
        ring["skipped"] = "single-device host — parity pinned by " \
                          "tests/test_longcontext.py on the CPU mesh"

    # ---- 5/6. mixed long/short storm + retrace gate --------------------
    sargs = args.replace(max_seq_len=512)
    eng = InferenceEngine(sargs, tokenizer=tok)
    bat = DynamicBatcher(eng, buckets=(128,), max_batch_size=8,
                         max_wait_ms=8.0, max_queue=256,
                         serve_pack="on" if not on_tpu else "auto",
                         pack_max_segments=16,
                         long_widths=(256, 512)).start()
    bat.warmup()
    rs = np.random.RandomState(11)

    def short_ids():
        return [2] + list(rs.randint(5, tok.vocab_size - 1,
                                     rs.randint(4, 40))) + [3]

    def long_ids():
        return [2] + list(rs.randint(5, tok.vocab_size - 1,
                                     rs.randint(300, 480))) + [3]

    def storm(n_short, every_long):
        futs, longs = [], []
        lat = []
        for i in range(n_short):
            if every_long and i % every_long == 0:
                lf = bat.submit_ids(long_ids())
                longs.append(lf)
            futs.append((time.monotonic(), bat.submit_ids(short_ids())))
            time.sleep(0.002)
        for t0s, f in futs:
            f.result(timeout=60)
            lat.append((time.monotonic() - t0s) * 1e3)
        lres = [(f.ids, f.result(timeout=60)) for f in longs]
        return np.asarray(lat), lres

    warm_retraces = eng.metrics.retraces.value
    control, _ = storm(200, 0)
    mixed, long_results = storm(200, 10)
    storm_retraces = eng.metrics.retraces.value - warm_retraces
    p99_control = float(np.percentile(control, 99))
    p99_mixed = float(np.percentile(mixed, 99))
    budget = max(3 * p99_control, p99_control + 250.0)
    serve_row = {
        "short_p99_ms_control": round(p99_control, 1),
        "short_p99_ms_mixed": round(p99_mixed, 1),
        "short_p99_budget_ms": round(budget, 1),
        "long_requests": len(long_results),
        "retraces_in_storm": storm_retraces,
    }
    if p99_mixed > budget:
        failures.append(f"mixed-storm short p99 {p99_mixed:.0f}ms blows "
                        f"the {budget:.0f}ms budget (control "
                        f"{p99_control:.0f}ms)")
    if storm_retraces:
        failures.append(f"{storm_retraces} post-warmup retraces in the "
                        "storm (long widths must be closed by warmup)")
    # chunked-prefill parity: every long result == whole-request scoring
    worst = 0.0
    for ids, got in long_results:
        w = 256 if len(ids) <= 256 else 512
        ref = eng.infer_ids([list(ids)], w)[0]
        worst = max(worst, float(np.abs(got - ref).max()))
    serve_row["long_parity_max_abs"] = worst
    if worst > 2e-5:
        failures.append(f"chunked-prefill parity {worst:g} > 2e-5")
    bat.stop()

    result = {
        "metric": "longcontext_smoke",
        "model": args.model,
        "platform": platform,
        "pallas_interpreted": not on_tpu,
        "devices": jax.device_count(),
        "widths": list(widths),
        "routing_table": {f"{k[0]}{'_packed' if k[1] else '_dense'}": v
                          for k, v in sorted(ROUTING_TABLE.items())},
        "kernel_parity": parity,
        "segment_bias_materialized": structural,
        "train_512": train_rows,
        "ring_packed": ring,
        "mixed_storm": serve_row,
    }
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=2)
        os.replace(tmp, out_path)
    # merge the summary rows into results/longcontext.json — rows already
    # there win
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "scripts"))
    import bench_longcontext as blc

    # row names carry the PLATFORM: merge_rows is history-wins, so an
    # un-keyed name written by a CPU smoke would forever block the
    # documented on-chip re-measurement from landing — per-platform names
    # let the v5e run coexist with (not fight) the CI numbers
    smoke_rows = {
        f"smoke_pack512_train_{platform}": {
            **{k2: train_rows["pack"][k2] for k2 in
               ("fill_ratio", "tokens_real_per_sec",
                "real_token_speedup_vs_full")},
            "config": {"seq": 512, "source": "bench.py --longcontext",
                       "platform": platform,
                       "pallas_interpreted": not on_tpu}},
        f"smoke_mixed_storm_{platform}": {
            **serve_row,
            "config": {"source": "bench.py --longcontext",
                       "platform": platform}},
    }
    _, merged = blc.merge_rows(smoke_rows)
    print(json.dumps(result))
    print(f"[longcontext] merged rows into results/longcontext.json: "
          f"{merged}", file=sys.stderr)
    if failures:
        sys.exit("longcontext smoke FAILED:\n  - " + "\n  - ".join(failures)
                 + f"\nsee {out_path}")


def resilience_smoke(argv) -> None:
    """``--resilience``: preemption-grade training smoke.

    Two gated blocks, written to ``results/resilience_smoke.json``
    (override ``--resilience_out``), non-zero exit on any violation:

    1. **save-pause A/B** — a seeded bert-tiny step loop saving full train
       state every ``--resilience_save_every`` steps, once through the
       synchronous ``checkpoint.save_state`` and once through the async
       writer (snapshot-in-loop + background publish).  Records the
       step-loop pause per save (mean/p95/max ms) for both, the async
       drain time, and writer stats.  Gates: every published file passes
       manifest verification, and the async writer ran with at most one
       save in flight (structural: one writer thread; the recorded stats
       must agree).
    2. **kill injection** — a width-1 elastic gang (CPU backend, 4 virtual
       devices) SIGKILLed mid-epoch; the supervisor must restart it from
       the async-published snapshot.  Gates: **zero lost optimizer steps**
       (the final train line reports step N/N — every remaining step ran
       after the restart), exactly one restart, and **bounded recovery**
       (total wall under ``--resilience_recovery_s``, default 600).  Runs
       single-process so the smoke is honest on images whose jax cannot
       form cross-process CPU gangs (the eviction-at-reduced-width path is
       chaos-tested in ``tests/test_chaos.py`` where the backend allows).
    """
    import re
    import subprocess
    import tempfile
    import time as _time

    import jax
    import jax.numpy as jnp

    from pdnlp_tpu.train import checkpoint as ckpt
    from pdnlp_tpu.train.async_ckpt import AsyncCheckpointer
    from pdnlp_tpu.utils.config import Args, parse_cli, pop_cli_flag

    argv, out_path = pop_cli_flag(
        argv, "--resilience_out",
        os.path.join("results", "resilience_smoke.json"))
    argv, n_steps = pop_cli_flag(argv, "--resilience_steps", 18, int)
    argv, save_every = pop_cli_flag(argv, "--resilience_save_every", 3, int)
    argv, recovery_bound = pop_cli_flag(argv, "--resilience_recovery_s",
                                        600.0, float)
    if n_steps < save_every:
        sys.exit(f"--resilience_steps ({n_steps}) must be >= "
                 f"--resilience_save_every ({save_every}): the smoke needs "
                 "at least one save to measure")
    args = parse_cli(argv, base=Args(
        strategy="dp", model="bert-tiny", data_limit=600, max_seq_len=32,
        train_batch_size=8, dtype="float32", dropout=0.0, attn_dropout=0.0,
        epochs=1, log_every=10 ** 9))

    fresh_loader, mesh, state0, step, put = _smoke_train_setup(args)
    batch = put(next(iter(fresh_loader())))
    # jaxlint: disable=L1 — holds the kill-injection gang's ckpts for triage
    tmp_dir = tempfile.mkdtemp(prefix="resilience_")

    def timed_saves(variant):
        state = jax.tree_util.tree_map(jnp.copy, state0)
        path = os.path.join(tmp_dir, f"{variant}.msgpack")
        writer = AsyncCheckpointer() if variant == "async" else None
        pauses = []
        state, m = step(state, batch)  # compile outside the timed loop
        float(jax.device_get(m["loss"]))
        for i in range(n_steps):
            state, m = step(state, batch)
            if (i + 1) % save_every == 0:
                t0 = _time.perf_counter()
                if writer is None:
                    # the sync baseline IS the hazard being measured
                    # jaxlint: disable=R9 — A/B baseline for the async saver
                    ckpt.save_state(path, state, meta={"step": i + 1})
                else:
                    writer.submit(path, ckpt.snapshot(state),
                                  meta={"step": i + 1})
                # the STEP-LOOP PAUSE is the metric: sync saves block
                # internally (consolidate fetches), async deliberately
                # measures snapshot+enqueue only — no barrier wanted
                # jaxlint: disable=R4 — the unblocked pause IS the metric
                pauses.append(_time.perf_counter() - t0)
        float(jax.device_get(m["loss"]))
        drain_s = 0.0
        stats = writer_error = None
        if writer is not None:
            t0 = _time.perf_counter()
            try:
                writer.wait()  # host-side thread join, not device dispatch
            except RuntimeError as e:
                # a failed publish must surface as a GATED violation in the
                # JSON result, not an unhandled traceback
                writer_error = str(e.__cause__ or e)
            # jaxlint: disable=R4 — times the writer drain, no device work
            drain_s = _time.perf_counter() - t0
            stats = writer.stats()
        del state
        ok, reason = ckpt.verify(path)
        p = sorted(pauses)
        row = {"variant": variant, "saves": len(pauses),
               "pause_mean_ms": round(sum(p) / len(p) * 1e3, 3),
               "pause_p95_ms": round(p[int(0.95 * (len(p) - 1))] * 1e3, 3),
               "pause_max_ms": round(p[-1] * 1e3, 3),
               "drain_s": round(drain_s, 3),
               "manifest_ok": ok, "manifest_reason": reason}
        if stats is not None:
            row["writer"] = stats
        if writer_error is not None:
            row["writer_error"] = writer_error
        return row

    sync_row = timed_saves("sync")
    async_row = timed_saves("async")

    # ---- kill injection: width-1 elastic gang, SIGKILL mid-epoch --------
    kill_dir = os.path.join(tmp_dir, "gang")
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONUNBUFFERED="1", PDNLP_SPAWN_PORT="12421",
               PDNLP_FAULT_STEP="5", PDNLP_FAULT_PROC="0",
               PDNLP_FAULT_KIND="sigkill")
    for k in ("COORDINATOR_ADDRESS", "PROCESS_ID"):
        env.pop(k, None)
    corpus = args.data_path
    if not os.path.exists(corpus):
        import random as _random

        corpus = os.path.join(tmp_dir, "corpus.json")
        rng = _random.Random(args.seed)
        chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐高兴悲伤讨厌愤怒"
        rows = [[" ".join(rng.choice(chars)
                          for _ in range(rng.randint(4, 30))),
                 rng.randrange(args.num_labels)] for _ in range(600)]
        with open(corpus, "w", encoding="utf-8") as f:
            json.dump(rows, f, ensure_ascii=False)
    t0 = _time.monotonic()
    timed_out = False
    try:
        proc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "multi-tpu-spawn-cls.py"),
             "--num_processes", "1", "--elastic", "true", "--resume_every",
             "2", "--stall_timeout", "60", "--log_every", "1",
             "--output_dir", kill_dir, "--data_path", corpus,
             "--model", "bert-tiny", "--data_limit", "256", "--max_seq_len",
             "32", "--train_batch_size", "4", "--dtype", "float32",
             "--dropout", "0.0", "--attn_dropout", "0.0", "--epochs", "1"],
            capture_output=True, text=True, timeout=recovery_bound, env=env)
        rc, out, err = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        # the recovery-bound violation must be a GATED result, not a crash
        timed_out = True
        rc = -1
        out = (e.stdout or b"").decode() if isinstance(e.stdout, bytes) \
            else (e.stdout or "")
        err = (e.stderr or b"").decode() if isinstance(e.stderr, bytes) \
            else (e.stderr or "")
    # jaxlint: disable=R4 — wall-clock of a subprocess, no device dispatch
    wall_s = _time.monotonic() - t0
    restarts = len(re.findall(r"restart \d+/", err))
    steps_line = re.findall(r"step：(\d+)/(\d+)", out)
    final_step, total_step = (int(steps_line[-1][0]), int(steps_line[-1][1])) \
        if steps_line else (0, -1)
    kill_row = {
        "completed": rc == 0,
        "timed_out": timed_out,
        "restarts": restarts,
        "final_step": final_step, "total_step": total_step,
        "lost_optimizer_steps": total_step - final_step,
        "recovery_wall_s": round(wall_s, 1),
        "recovery_bound_s": recovery_bound,
    }

    violations = []
    for row in (sync_row, async_row):
        if not row["manifest_ok"]:
            violations.append(f"{row['variant']}: published checkpoint "
                              f"fails manifest validation "
                              f"({row['manifest_reason']})")
    if async_row.get("writer_error"):
        violations.append(f"async writer publish failed: "
                          f"{async_row['writer_error']}")
    if not kill_row["completed"]:
        violations.append("killed gang did not complete: "
                          + ("recovery bound hit"
                             if timed_out else f"rc {rc}")
                          + f"; {err[-500:]}")
    if kill_row["lost_optimizer_steps"] != 0:
        violations.append(f"lost optimizer steps: {kill_row}")
    if kill_row["restarts"] != 1:
        violations.append(f"expected exactly 1 restart, saw "
                          f"{kill_row['restarts']}")
    if wall_s > recovery_bound:
        violations.append(f"recovery {wall_s:.0f}s over bound "
                          f"{recovery_bound:.0f}s")

    result = {
        "metric": "resilience_smoke",
        "model": args.model,
        "batch_size": args.train_batch_size,
        "seq_len": args.max_seq_len,
        "devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
        "steps": n_steps, "save_every": save_every,
        "save_pause": {"sync": sync_row, "async": async_row},
        "kill_injection": kill_row,
        "violations": violations,
        "ok": not violations,
    }
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    if violations:
        sys.exit("resilience smoke FAILED: " + "; ".join(violations))


def _lint_gate() -> None:
    """Refuse to burn accelerator time on a tree that fails the jaxlint
    gate (tracing + concurrency suites vs the committed baseline) — the
    same shape as the leaked-PDNLP_GELU_TANH refusal: a smoke number
    measured on a tree carrying NEW hazards is unreproducible evidence.
    Pure-ast, no jax import: the check costs ~2s against smokes that run
    for minutes."""
    from pdnlp_tpu.analysis import analyze_paths, baseline, default_paths

    repo = os.path.dirname(os.path.abspath(__file__))
    base_path = os.path.join(repo, baseline.DEFAULT_BASELINE)
    if not os.path.exists(base_path):
        return  # no ratchet recorded: nothing to enforce against
    findings = analyze_paths(default_paths(repo), root=repo)
    new, _fixed = baseline.compare(findings, baseline.load(base_path))
    if new:
        lines = "\n".join(f"  {f.path}:{f.line}: {f.rule_id} {f.message}"
                          for f in new[:20])
        more = "" if len(new) <= 20 else f"\n  ... and {len(new) - 20} more"
        sys.exit(
            "bench.py: jaxlint gate FAILED — this tree carries NEW "
            "tracing/concurrency violations vs results/"
            "jaxlint_baseline.json:\n" + lines + more + "\n"
            "Fix them (or suppress with a reasoned `# jaxlint: disable=`) "
            "and re-run scripts/lint_gate.sh before benching.")


def main() -> None:
    argv = sys.argv[1:]
    if not any(a in ("--help", "-h") for a in argv):
        _lint_gate()  # usage lookups stay free; every real run is gated
    if "--resilience" in argv:
        # resilience smoke intercept (async-save pause A/B + kill
        # injection, results/resilience_smoke.json) — like --kernels, not
        # an Args knob
        argv.remove("--resilience")
        return resilience_smoke(argv)
    if "--telemetry" in argv:
        # full-telemetry-plane overhead gate (exporter + flight recorder +
        # memory sampler + request hops vs all-off) — an intercept like
        # --trace, results/telemetry_smoke.json
        argv.remove("--telemetry")
        return telemetry_smoke(argv)
    if "--trace" in argv:
        # like --pipeline: a bench smoke intercept, not the Args.trace
        # bool (a traced HEADLINE run is `--trace true` on the ordinary
        # entrypoints; the bench's own flag is the overhead gate).  The
        # Args-style boolean value is tolerated — `--trace true` runs the
        # smoke, `--trace false` is a no-op — so the README's flag shape
        # works on every entrypoint including this one.
        i = argv.index("--trace")
        argv.pop(i)
        enabled = True
        if i < len(argv) and argv[i].lower() in ("true", "false", "1", "0"):
            enabled = argv.pop(i).lower() in ("true", "1")
        if enabled:
            return trace_smoke(argv)
    if "--pipeline" in argv:
        from pdnlp_tpu.utils.config import pop_cli_flag

        argv, modes_arg = pop_cli_flag(argv, "--pipeline", "all")
        return pipeline_smoke(argv, modes_arg)
    if "--length" in argv:
        # like --pipeline: a bench smoke intercept, not Args.length_mode (a
        # length-aware HEADLINE run is `--length_mode bucket|pack` on the
        # ordinary entrypoints; the bench's own flag is the A/B smoke)
        from pdnlp_tpu.utils.config import pop_cli_flag

        argv, modes_arg = pop_cli_flag(argv, "--length", "all")
        return length_smoke(argv, modes_arg)
    if "--kernels" in argv:
        # kernel-path smoke intercept (parity + A/B, results/
        # kernel_smoke.json) — like --pipeline/--length, not an Args knob
        argv.remove("--kernels")
        return kernel_smoke(argv)
    if "--longcontext" in argv:
        # long-context gate (multi-tile kernel parity, structural no-bias
        # proof, packed-512 throughput, ring+packed parity, mixed-storm
        # p99 — results/longcontext_smoke.json); an intercept like
        # --kernels.  The ring leg needs >1 device: give the CPU host its
        # virtual mesh BEFORE jax initializes (no-op for TPU backends,
        # the flag only shapes the host platform).
        if "jax" not in sys.modules:
            os.environ.setdefault(
                "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        argv.remove("--longcontext")
        return longcontext_smoke(argv)
    if "--fleet" in argv:
        # multi-model fleet gate: shadow-impact control/treatment, canary
        # rollout advance + bad-canary auto-rollback, degrade-tier burst
        # (results/fleet_smoke.json) — an intercept like --replay
        argv.remove("--fleet")
        return fleet_smoke(argv)
    if "--replay" in argv:
        # trace-driven load replay: controller-vs-static across replayed
        # traffic shapes (results/replay_smoke.json) — an intercept like
        # --serve-load
        argv.remove("--replay")
        return replay_smoke(argv)
    if "--decode" in argv:
        # generative-decoding gate (sharded KV cache, prefill/decode
        # split, continuous batching, mid-storm kill —
        # results/decode_smoke.json); an intercept like --serve-load
        argv.remove("--decode")
        return decode_smoke(argv)
    if "--serve-load" in argv or "--serve_load" in argv:
        # closed-loop router SLO gate (results/serve_load_smoke.json):
        # Poisson storm + mid-storm replica kill + rolling swap + overload
        # burst over N replica engines — like --serve, an intercept
        for flag in ("--serve-load", "--serve_load"):
            if flag in argv:
                argv.remove(flag)
        return serve_load_smoke(argv)
    if "--serve" in argv:
        # No pretrain-cache key to fold a leaked PDNLP_GELU_TANH into here:
        # serving would silently run tanh forwards over an erf-trained
        # checkpoint and record mismatched parity numbers.  Refuse.
        if os.environ.get("PDNLP_GELU_TANH", "0") == "1":
            sys.exit("bench.py --serve: PDNLP_GELU_TANH is set — the global "
                     "activation override would serve tanh forwards over a "
                     "checkpoint trained with the configured activation. "
                     "Unset it (the override belongs to "
                     "scripts/profile_step.py's A/B subprocesses only).")
        argv.remove("--serve")
        return serve_smoke(argv)

    import jax

    from pdnlp_tpu.train.run import build_parallel_trainer
    from pdnlp_tpu.utils.config import Args, parse_cli

    # Recipe (r5: batch-64 sweep in results/recipe_b64_sweep.json; the r4
    # b32 grid in results/ema_sweep.json): batch 64 amortizes the step's
    # fixed AdamW+EMA cost (+36% examples/s — ablation + XProf profile in
    # results/profile_r05.json); tanh GELU replaces the erf backward's VPU
    # transcendental chain (+7% step rate at b64, ~53% bf16 MFU) and its
    # end-to-end pretrain GAINS accuracy (3ep: 0.5887 vs erf's 0.5813);
    # ONE fine-tune epoch with the warmup->linear-decay schedule compressed
    # into it — the same 1-epoch protocol the reference's headline uses —
    # measured BEST in the tanh sweep: 0.5975 (6e-5) vs 0.5925/0.5938 at
    # the 5e-5/7e-5 half-steps, 0.5938 (4.5e-5), 0.5900-0.5950 (2ep),
    # 0.5887 (3ep); eval cadence 24 finds the same 0.5975 best (cadence
    # stays 48); trained head restored
    # (init_head), weight EMA at decay 0.99 (evaluated/checkpointed
    # weights are the Polyak average; 0.995 regresses to 0.5850), best-of
    # checkpointing with eval every 48 steps — 48, not the reference's 50,
    # so the cadence stays exact under fuse_steps=4 (trainer.py boundary
    # note).  fuse_steps=4 rides one dispatch per 4 optimizer steps
    # (multi_step docstring).  The pretrain cache is
    # keyed by activation (pretrained-tanh.msgpack vs pretrained.msgpack)
    # so --gelu erf reruns stay reproducible against the erf artifact the
    # per-strategy matrix protocol uses.
    args = parse_cli(base=Args(
        strategy="dp", dtype="bfloat16", fuse_steps=4, gelu="tanh",
        train_batch_size=64, learning_rate=6e-5,
        epochs=1, lr_schedule="warmup_linear", ema_decay=0.99,
        sft_epochs=5,        # measured best; --sft_epochs 0 = MLM-only warm start
        dev=True, eval_step=48,  # in-loop eval, keep best (reference ritual)
        log_every=10 ** 9,   # no per-step printing inside the timed loop
    ))

    # A leaked PDNLP_GELU_TANH (scripts/profile_step.py's A/B subprocess
    # override) force-enables tanh on EVERY forward regardless of --gelu,
    # while the pretrain cache below keys its artifact name on args.gelu —
    # a tanh trunk would silently land in the erf-named pretrained.msgpack
    # and corrupt the provenance the activation-keyed cache exists to
    # protect.  Fold the override into the key: the run IS tanh, so make
    # args.gelu (and with it the cache suffix, the recorded config, and
    # the warm-start artifact) say so.
    if os.environ.get("PDNLP_GELU_TANH", "0") == "1" and \
            (args.gelu or "erf") != "tanh":
        print("bench.py: PDNLP_GELU_TANH=1 leaked into this run — every "
              f"forward computes tanh GELU regardless of --gelu {args.gelu!r}"
              ". Folding it into the config: this run is keyed/cached as "
              "gelu=tanh (pretrained-tanh.msgpack).", file=sys.stderr)
        args = args.replace(gelu="tanh")

    with contextlib.redirect_stdout(sys.stderr):
        import numpy as np

        # cache keyed by activation: an erf-pretrained trunk silently warm-
        # starting a tanh fine-tune (or vice versa) measured fine (0.5813)
        # but would make the recipe's provenance depend on which run filled
        # the cache first
        sfx = "" if (args.gelu or "erf") == "erf" else f"-{args.gelu}"
        pretrain_ckpt = args.ckpt_path(f"pretrained{sfx}.msgpack")
        mlm_ckpt = args.ckpt_path(f"pretrained-mlm{sfx}.msgpack")
        explicit_init = bool(args.init_from)
        if not os.path.exists(pretrain_ckpt) and not args.init_from:
            # one-time in-repo pretraining (the "download weights" analog):
            # MLM over the packed corpus, then the supervised stage over the
            # ~30k labeled externals (sweep_sft.py measured 5 epochs best;
            # --sft_epochs 0 stops after the MLM phase)
            try:
                from pdnlp_tpu.train.pretrain import (
                    run_pretrain, run_supervised_stage,
                )

                # ema_decay is the FINE-TUNE recipe's knob: the pretrain
                # stages must not inherit it, or the regenerated artifact
                # would silently become sft-stage EMA weights and stop
                # reproducing the measured headline numbers
                if args.sft_epochs > 0:
                    if not os.path.exists(mlm_ckpt):
                        # a prior run's phase-1 artifact is reusable as-is:
                        # a supervised-stage failure must not cost the
                        # ~25-min MLM rerun on the next invocation
                        run_pretrain(args.replace(
                            strategy="pretrain", train_batch_size=64,
                            epochs=150, learning_rate=2e-4, mlm_prob=0.3,
                            dev=False, lr_schedule=None, ema_decay=0.0,
                            ckpt_name=f"pretrained-mlm{sfx}.msgpack"))
                    run_supervised_stage(args.replace(
                        strategy="sft", init_from=mlm_ckpt, init_head=False,
                        epochs=args.sft_epochs, learning_rate=args.sft_lr,
                        lr_schedule="warmup_linear", train_batch_size=32,
                        dev=False, ema_decay=0.0,
                        ckpt_name=f"pretrained{sfx}.msgpack"))
                else:
                    run_pretrain(args.replace(
                        strategy="pretrain", train_batch_size=64, epochs=150,
                        learning_rate=2e-4, mlm_prob=0.3, dev=False,
                        lr_schedule=None, ema_decay=0.0,
                        ckpt_name=f"pretrained{sfx}.msgpack"))
            except Exception as e:  # bench must still produce its JSON line
                print(f"pretrain stage failed ({type(e).__name__}: {e})",
                      file=sys.stderr)
        if not args.init_from:
            if os.path.exists(pretrain_ckpt):
                # MLM-only artifacts ('mlm' tree, no classifier) fail the
                # init_head load loudly; the retry ladder below drops to
                # trunk-only for them
                args = args.replace(init_from=pretrain_ckpt, init_head=True)
            elif os.path.exists(mlm_ckpt):
                # phase 2 failed but the MLM trunk survives: still a far
                # better warm start than from-scratch weights
                print(f"supervised stage unavailable; warm-starting from "
                      f"the MLM trunk {mlm_ckpt}", file=sys.stderr)
                args = args.replace(init_from=mlm_ckpt, init_head=False)
            else:
                print("no pretrain artifact; benching from-scratch weights",
                      file=sys.stderr)

        try:
            trainer, train_loader, dev_loader = build_parallel_trainer(args, mode="dp")
        except Exception as e:
            # an explicitly requested --init_from must fail loudly; only the
            # auto-selected cache falls back (e.g. a stale pretrained.msgpack
            # from a different --model must not kill the JSON line)
            if explicit_init or not args.init_from:
                raise
            retries = []
            if args.init_head:
                # an MLM-only cache has no trained classifier: still a
                # valid trunk warm-start
                retries.append((args.replace(init_head=False),
                                "retrying trunk-only"))
            retries.append((args.replace(init_from=None, init_head=False),
                            "benching from-scratch weights"))
            for cand, action in retries:
                print(f"init_from {args.init_from!r} failed "
                      f"({type(e).__name__}: {e}); {action}", file=sys.stderr)
                try:
                    args = cand
                    trainer, train_loader, dev_loader = \
                        build_parallel_trainer(args, mode="dp")
                    break
                except Exception as e2:
                    e = e2
            else:
                raise e
        # compile outside the timer (the reference times a warm CUDA context)
        host_batch = next(iter(train_loader))
        batch = trainer.put(host_batch)
        trainer.train_step.lower(trainer.state, batch).compile()
        # eval must lower against a DEV-loader batch: dev_batch_size differs
        # from the train batch, and a mismatched shape here would push the
        # real eval compile inside the timed loop on a cold XLA cache
        dev_batch = trainer.put(next(iter(dev_loader)))
        trainer.eval_step.lower(trainer.state["params"], dev_batch).compile()
        if trainer.multi_step is not None:
            stacked = {k: np.stack([v] * args.fuse_steps)
                       for k, v in host_batch.items()}
            trainer.multi_step.lower(
                trainer.state, trainer.put_fused(stacked)).compile()
        # hot-loop step time measured separately (30 re-fed steps): the
        # timed epoch below includes the in-loop dev evals (the reference's
        # protocol), so deriving steps/s from it would blur two metrics
        import time as _time

        import jax.numpy as jnp

        # probe on a copy: train_step donates its state argument, and the
        # real run below still needs trainer.state's buffers intact
        state = jax.tree_util.tree_map(jnp.copy, trainer.state)
        for _ in range(3):
            state, m = trainer.train_step(state, batch)
        float(jax.device_get(m["loss"]))
        t0 = _time.time()
        for _ in range(30):
            state, m = trainer.train_step(state, batch)
        float(jax.device_get(m["loss"]))
        sec_per_step = (_time.time() - t0) / 30
        del state, m

        total_minutes = trainer.train(train_loader, dev_loader)
        minutes = total_minutes / args.epochs
        # time-to-accuracy from the in-loop eval history: minutes until the
        # dev accuracy first reached the reference's 0.57, and until the
        # run's best — the numbers per-epoch framing hides
        to_target = next((e["minutes"] for e in trainer.eval_history
                          if e["accuracy"] >= 0.57), None)
        best_acc = max((e["accuracy"] for e in trainer.eval_history),
                       default=0.0)
        to_best = next((e["minutes"] for e in trainer.eval_history
                        if e["accuracy"] >= best_acc), None)
        # trainer adopted the best-of-epoch params at the end of train()
        loss, acc = trainer.dev(dev_loader)

        # MFU only means something against the matching peak: report it for
        # bf16 on a recognized TPU generation, null otherwise (fp32 runs at
        # a different MXU rate; CPU runs have no meaningful peak).
        mfu = None
        peak = bf16_peak(jax.devices()[0])
        if args.dtype == "bfloat16" and peak is not None:
            mfu = step_flops(trainer.cfg, args.train_batch_size,
                             args.max_seq_len) / sec_per_step / peak

    print(json.dumps({
        "metric": "total_train_minutes",
        "value": round(total_minutes, 4),
        "unit": "min",
        # TOTAL wall-clock vs the reference's total (its 1-epoch 0.6336):
        # the honest time-to-accuracy comparison, not per-epoch
        "vs_baseline": round(NORTH_STAR_MIN / total_minutes, 4),
        "baseline_min": NORTH_STAR_MIN,
        "single_gpu_baseline_min": SINGLE_GPU_MIN,
        "min_per_epoch": round(minutes, 4),
        "epochs": args.epochs,
        "minutes_to_0.57": round(to_target, 4) if to_target else None,
        "minutes_to_best": round(to_best, 4) if to_best else None,
        "dev_accuracy": round(acc, 4),
        "dev_loss": round(loss, 4),
        "steps_per_epoch": len(train_loader),
        "steps_per_sec": round(1.0 / sec_per_step, 2),
        "batch_size": args.train_batch_size,
        "mfu_pct": round(mfu * 100, 1) if mfu is not None else None,
        "devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
        "dtype": args.dtype,
        # the attention impl the hot loop actually routed to
        # (ops.attention.routed_impl — same decision the traced step and
        # the step_dispatch span attr resolve)
        "attn_impl": trainer._routed_attn(
            args.max_seq_len, args.length_mode == "pack"),
        "fuse_steps": args.fuse_steps,
        # input-pipeline mode + measured transport (utils.metrics
        # .TransportStats): resident mode must show 0 in-loop bytes/step
        "pipeline": trainer.pipeline.mode if trainer.pipeline else None,
        "transport": trainer.pipeline.stats.snapshot()
        if trainer.pipeline else None,
        "init_from": args.init_from,
        "note": ("fine-tuned from in-repo two-phase pretrain (MLM over the "
                 "40k-text corpus + supervised stage over the ~30k labeled "
                 "examples outside the protocol's [:10000] slice; no egress "
                 "— the reference's pretrained-checkpoint download is "
                 "rebuilt in-repo); reference dev acc target 0.57"
                 if args.init_from else
                 "from-scratch weights; reference dev acc 0.57 is from a "
                 "pretrained model"),
    }))


if __name__ == "__main__":
    main()
