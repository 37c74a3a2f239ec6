"""Paged KV cache tests: allocator/refcount/leak-check units, the prefix
index (full/partial hits, LRU eviction as the allocator's reclaimer),
sharing-vs-not TOKEN PARITY (cold, full-hit, partial-hit and copy-on-write
streams all continue identically to an engine that shares nothing), page-unit
capacity under ``--kv_hbm_mb``, the zero-retrace guarantee on the paged
decode path, pool-exhaustion queueing without deadlock, prefix-hit
telemetry on the hop chain, and kill-recovery where re-prefilled orphans
re-attach to shared prefix pages on the survivor — with the allocator
ledger reconciling to zero leaked pages after every drain."""
import time

import numpy as np
import pytest

from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
from pdnlp_tpu.obs.exporter import prometheus_lines
from pdnlp_tpu.obs.request import validate_chains
from pdnlp_tpu.serve import (
    DecodeBatcher, DecodeRouter, KVPagesExhausted,
    PagedDecodeEngine,
)
from pdnlp_tpu.serve.kvpage import (
    INDEX_OWNER, PageAllocator, PrefixIndex, pages_needed,
)
from pdnlp_tpu.utils.config import Args

TEXTS = ["天地人你我", "好坏大小上下来去" * 5, "爱恨喜怒哀乐" * 15]
BUCKETS = (16, 32)


@pytest.fixture(scope="module")
def tok():
    return WordPieceTokenizer(build_vocab(TEXTS, size=128))


def make_args(**kw):
    base = dict(model="bert-tiny", decode_slots=4, decode_max_len=48,
                max_new_tokens=8)
    base.update(kw)
    return Args(**base)


def prompts(n=6, seed=3, lo=4, hi=14, vocab=120):
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi, n)
    return [rng.integers(5, vocab, int(k)).tolist() for k in lens]


def paged_engine(tok, page_sz=16, **kw):
    return PagedDecodeEngine(make_args(**kw), tokenizer=tok, mesh=None,
                             buckets=BUCKETS, page_sz=page_sz)


@pytest.fixture(scope="module")
def pag(tok):
    """ONE warmed paged engine shared by the engine-level tests below —
    warmup compiles dominate this file's runtime, every test drains its
    streams, and the prompt seeds are disjoint so no test hits another's
    index entries by accident."""
    eng = paged_engine(tok, trace=True)
    eng.warmup_decode()
    return eng


@pytest.fixture(scope="module")
def cold_eng(tok):
    """The reference: the same engine with ``prefix_share=False`` — every
    stream prefills its whole prompt into pages of its own."""
    eng = PagedDecodeEngine(make_args(), tokenizer=tok, mesh=None,
                            buckets=BUCKETS, prefix_share=False)
    eng.warmup_decode()
    return eng


def drive_serial(eng, plist, max_new=6):
    """One stream at a time through a fresh batcher each — the
    order-independent reference drive."""
    outs = []
    for p in plist:
        b = DecodeBatcher(eng, replica=0)
        b.eos_id = -1
        b.start()
        s = b.submit_ids(p, max_new_tokens=max_new)
        outs.append(s.result(timeout=120))
        b.stop()
    return outs


# ------------------------------------------------------------- allocator

def test_pages_needed():
    assert pages_needed(0, 16) == 0
    assert pages_needed(1, 16) == 1
    assert pages_needed(16, 16) == 1
    assert pages_needed(17, 16) == 2


def test_allocator_alloc_share_release_roundtrip():
    a = PageAllocator(8, 16, page_bytes=1024)
    p1 = a.alloc(3, "r1")
    assert len(p1) == 3 and a.free_pages == 5
    a.share(p1[:2], "r2")          # refcount+1 on two of r1's pages
    assert a.used_pages == 3       # sharing allocates nothing
    assert a.release_owner("r1") == 1   # only the unshared page frees
    assert a.free_pages == 6
    assert a.release_owner("r2") == 2
    assert a.free_pages == 8
    lk = a.leak_check()
    assert lk["ok"] and lk["leaked_pages"] == 0


def test_allocator_exhaustion_is_loud_and_counted():
    a = PageAllocator(4, 16, page_bytes=1024)
    a.alloc(3, "r1")
    with pytest.raises(KVPagesExhausted) as e:
        a.alloc(2, "r2")
    assert "page" in str(e.value)
    assert a.alloc_failures == 1
    # a failed alloc holds nothing
    assert a.used_pages == 3 and "r2" not in a.owners()


def test_allocator_leak_check_flags_mismatch():
    a = PageAllocator(4, 16)
    a.alloc(2, "r1")
    lk = a.leak_check()
    assert lk["ok"] and lk["owners"] == 1
    # simulate a phantom hold (the ledger bug leak_check exists to
    # catch): an owner claims a page whose refcount never moved
    a._owned["ghost"] = {0: 1}
    assert not a.leak_check()["ok"]
    assert a.leak_check()["refcount_mismatches"] == 1


def test_allocator_reclaimer_is_called_on_shortfall():
    calls = []

    def reclaim(short):
        calls.append(short)
        return 0  # nothing reclaimable

    a = PageAllocator(2, 16)
    a.reclaimer = reclaim
    a.alloc(2, "r1")
    with pytest.raises(KVPagesExhausted):
        a.alloc(1, "r2")
    assert calls == [1]


# ---------------------------------------------------------- prefix index

def test_prefix_index_full_and_partial_hits():
    a = PageAllocator(16, 4)
    idx = PrefixIndex(a, 4)
    toks = list(range(10))                 # 2 full pages + 2 tokens
    pages = a.alloc(3, "r1")
    idx.register(toks, pages, first_token=77)
    full = idx.lookup(toks)
    assert full.kind == "full" and full.first_token == 77
    assert list(full.pages) == pages       # incl. the trailing partial
    part = idx.lookup(toks[:8] + [99, 98])  # diverges inside page 2
    assert part.kind == "partial"
    assert list(part.pages) == pages[:2]   # full pages only
    assert idx.lookup([5, 5, 5, 5]).kind == "miss"
    # the index holds its own refs: the registrant can vanish
    a.release_owner("r1")
    assert a.used_pages == 3 and a.owners() == [INDEX_OWNER]
    assert idx.evict(need_pages=16) == 3   # drop everything
    assert a.free_pages == 16


def test_prefix_index_peek_has_no_side_effects():
    a = PageAllocator(8, 4)
    idx = PrefixIndex(a, 4)
    idx.register(list(range(8)), a.alloc(2, "r"), first_token=1)
    before = idx.snapshot()
    assert idx.lookup(list(range(8)), count=False).kind == "full"
    assert idx.snapshot() == before        # no counters moved


def test_prefix_index_eviction_is_lru():
    a = PageAllocator(8, 4)
    idx = PrefixIndex(a, 4)
    idx.register([1] * 4, a.alloc(1, "x"), first_token=1)
    idx.register([2] * 4, a.alloc(1, "y"), first_token=2)
    # registrants drain: only the index pins the pages now, so eviction
    # can actually free them — and stops as soon as it has freed enough
    a.release_owner("x")
    a.release_owner("y")
    idx.lookup([1] * 4)                    # touch the older entry
    idx.evict(need_pages=1)
    assert idx.lookup([1] * 4).kind == "full"   # survivor = recently used
    assert idx.lookup([2] * 4).kind == "miss"
    assert a.evictions >= 1


# ------------------------------------------------- engine: parity + hits

def test_paged_cold_streams_match_an_engine_that_shares_nothing(
        tok, pag, cold_eng):
    """The parity pin: every stream that MISSES the index continues token
    for token as on an engine without an index, and registering its pages
    on the way changes nothing."""
    ps = prompts(6, seed=3, vocab=tok.vocab_size)
    assert drive_serial(pag, ps) == drive_serial(cold_eng, ps)
    assert pag.leak_check()["ok"] and cold_eng.leak_check()["ok"]
    assert len(cold_eng.prefix) == 0 and len(pag.prefix) > 0
    pag.prefix.clear()
    assert pag.allocator.free_pages == pag.n_pages


def test_full_prefix_hit_skips_prefill_and_matches(tok, pag):
    """A repeated prompt is a FULL hit: zero forwards (prefills_total is
    structural), the stored first token + shared pages reproduce the
    cold continuation exactly, and COW covers the trailing partial
    page."""
    p = prompts(1, seed=11, lo=18, hi=20, vocab=tok.vocab_size)[0]
    b = DecodeBatcher(pag, replica=0)
    b.eos_id = -1
    b.start()
    cold = b.submit_ids(p, max_new_tokens=6).result(timeout=120)
    before = b.metrics.prefills_total.value
    hit = b.submit_ids(p, max_new_tokens=6).result(timeout=120)
    assert b.metrics.prefills_total.value == before, \
        "full hit must not run a prefill forward"
    assert hit == cold
    assert pag.prefix.snapshot()["hits_full"] >= 1
    assert pag.allocator.cow_copies >= 1   # p % page_sz != 0 -> COW
    b.stop()
    assert pag.leak_check()["ok"]


def test_partial_prefix_hit_matches_cold_reference(tok, pag, cold_eng):
    """A prompt sharing >= 1 full page with an indexed prefix forwards
    only its suffix and still matches, token for token, the engine that
    shares nothing (its whole prompt prefilled cold)."""
    base = prompts(1, seed=5, lo=20, hi=22, vocab=tok.vocab_size)[0]
    va = base + [7, 8, 9]
    vb = base + [3, 4, 5]   # diverges after base's full page(s)
    ref = drive_serial(cold_eng, [vb])[0]

    b = DecodeBatcher(pag, replica=0)
    b.eos_id = -1
    b.start()
    b.submit_ids(va, max_new_tokens=6).result(timeout=120)
    got = b.submit_ids(vb, max_new_tokens=6).result(timeout=120)
    b.stop()
    assert got == ref
    assert pag.prefix.snapshot()["hits_partial"] >= 1
    assert pag.leak_check()["ok"]


def test_admit_and_prefill_hops_carry_prefix_hit(tok, pag):
    b = DecodeBatcher(pag, replica=0)
    b.eos_id = -1
    b.start()
    p = [5, 6, 7, 8, 9]
    b.submit_ids(p, max_new_tokens=3).result(timeout=120)
    s = b.submit_ids(p, max_new_tokens=3)
    s.result(timeout=120)
    b.stop()
    hops = [r["attrs"] for r in pag.tracer.records()
            if r.get("name") == "hop"
            and (r.get("attrs") or {}).get("request_id") == s.rid]
    admit = next(h for h in hops if h["hop"] == "admit")
    pre = next(h for h in hops if h["hop"] == "prefill")
    assert admit["prefix_hit"] == "full"
    assert pre["prefix_hit"] == "full"
    assert pre["cached_tokens"] == len(p)
    report = validate_chains(pag.tracer.records(), [s.rid])
    assert report["complete"] == 1


# ------------------------------------------------------ capacity / budget

def test_paged_layout_admits_more_streams_at_equal_hbm(tok, pag):
    """The capacity claim in miniature: a budget that would hold only two
    ``max_len`` stripes (the arithmetic ``slots x max_len x token_bytes``
    of a cache that gives every stream its worst case) seats strictly
    more short streams as pages (each reserves only what it needs)."""
    stripe_bytes = pag.token_bytes * pag.max_len
    budget = 2.2 * stripe_bytes / 2**20         # MB: 2 stripe-equivalents
    stripes = int(budget * 2**20) // stripe_bytes
    assert stripes == 2
    capped_pag = paged_engine(tok, kv_hbm_mb=budget, decode_slots=8)
    assert capped_pag.slots == 8                # slots are batch rows
    # short streams: prompt+max_new = 8 -> 1 page each
    per_stream = pages_needed(8, capped_pag.page_sz)
    assert capped_pag.n_pages // per_stream >= 3 * stripes


def test_pool_exhaustion_queues_without_deadlock(tok):
    """More concurrent streams than the page pool seats: the batcher
    parks the head-of-line stream on KVPagesExhausted and every stream
    still completes as pages drain."""
    # pool = one max-length stream's pages (the construction floor);
    # no warmup — only the keys the storm actually uses compile, and this
    # test asserts drain behavior, not retrace accounting
    probe = paged_engine(tok)
    floor_mb = (probe.page_bytes * probe.pages_per_stream) / 2**20
    tight = paged_engine(tok, kv_hbm_mb=1.05 * floor_mb)
    assert tight.n_pages == tight.pages_per_stream
    b = DecodeBatcher(tight, replica=0)
    b.eos_id = -1
    b.start()
    # 2-page streams (prompt+new <= 29) keep multi-page reservation in
    # play while compiling only the 32-bucket prefill + decode keys
    ps = prompts(6, seed=9, lo=18, hi=22, vocab=tok.vocab_size)
    streams = [b.submit_ids(p, max_new_tokens=8) for p in ps]
    outs = [s.result(timeout=180) for s in streams]
    b.stop()
    assert all(len(o) == 8 for o in outs)
    assert tight.leak_check()["ok"]
    tight.prefix.clear()
    assert tight.allocator.free_pages == tight.n_pages


def test_oversized_stream_refused_in_page_units(tok):
    from pdnlp_tpu.obs.memory import KVBudgetExceeded

    eng = paged_engine(tok, kv_hbm_mb=64)
    with pytest.raises(KVBudgetExceeded) as e:
        eng.check_stream_admissible(40, 40)    # 80 > max_len 48
    assert "pages" in str(e.value)


# ------------------------------------------------------------ zero retrace

def test_paged_decode_path_never_retraces_after_warmup(tok, pag,
                                                       monkeypatch):
    baseline = pag.metrics.cache_misses.value
    traced = pag.metrics.retraces.value
    widths = set()
    real = pag._jit_pdecode

    def spy(params, head, pools, tokens, table, *rest):
        widths.add(int(table.shape[1]))
        return real(params, head, pools, tokens, table, *rest)

    monkeypatch.setattr(pag, "_jit_pdecode", spy)
    b = DecodeBatcher(pag, replica=0)
    b.eos_id = -1
    b.start()
    ps = prompts(8, seed=21, vocab=tok.vocab_size)
    streams = [b.submit_ids(p, max_new_tokens=6) for p in ps]
    # re-submit the first two: full hits + COW flushes also must not trace
    streams += [b.submit_ids(p, max_new_tokens=6) for p in ps[:2]]
    for s in streams:
        s.result(timeout=180)
    # and lengths that reach EVERY rung of the decode extent: a stream
    # that decodes on from 28 positions past the second page boundary
    assert pag.decode_rungs == [1, 2, 3]
    long = prompts(1, seed=22, lo=28, hi=29, vocab=tok.vocab_size)[0]
    b.submit_ids(long, max_new_tokens=12).result(timeout=180)
    b.stop()
    assert widths == set(pag.decode_rungs)
    assert pag.metrics.cache_misses.value == baseline, \
        "paged decode path retraced after warmup"
    assert pag.metrics.retraces.value == traced


# --------------------------------------------------------- kill recovery

def test_paged_router_kill_reattaches_shared_pages(tok, pag):
    """Replica kill on a paged pool: orphans re-prefill on the survivor
    UNDER THE SAME REQUEST ID, re-attaching to the survivor's shared
    prefix pages where their prompts repeat; outputs match the
    no-failure reference exactly and the survivor's allocator reconciles
    to zero leaked pages after drain."""
    args = make_args(trace=True)
    shared = prompts(1, seed=2, lo=18, hi=20, vocab=tok.vocab_size)[0]
    tails = prompts(12, seed=4, lo=2, hi=6, vocab=tok.vocab_size)
    ps = [shared + t for t in tails] + prompts(6, seed=8,
                                               vocab=tok.vocab_size)

    # greedy reference from the shared warmed engine (paged==slot parity
    # is pinned above; prefix hits never change tokens, only forwards)
    refs = drive_serial(pag, ps, max_new=16)

    # pag rides again as the to-be-killed replica — kill semantics live
    # in the batcher, and the survivor (whose ledger the test audits)
    # stays a fresh engine
    engines = [pag,
               PagedDecodeEngine(args, tokenizer=tok, mesh=None,
                                 buckets=BUCKETS, page_sz=16)]
    tracer = engines[0].tracer
    for e in engines[1:]:
        e.tracer = tracer
    router = DecodeRouter(engines).start()
    for b in router.batchers:
        b.eos_id = -1
    router.warmup()
    streams = [router.submit_ids(p, max_new_tokens=16) for p in ps]
    deadline = time.monotonic() + 60
    while (router.batchers[0].metrics.tokens_out_total.value < 40
           and time.monotonic() < deadline):
        time.sleep(0.005)
    router.kill(0)
    outs = [s.result(timeout=300) for s in streams]
    router.stop()

    assert router.batchers[0].dead and not router.batchers[1].dead
    assert outs == refs, "paged kill recovery duplicated or lost tokens"
    report = validate_chains(tracer.records(), [s.rid for s in streams])
    assert report["incomplete"] == {}
    assert report["complete"] == len(streams)
    assert report["requeued"] >= 1
    # the survivor's ledger reconciles: only the index holds pages
    survivor = router.batchers[1].engine
    lk = survivor.leak_check()
    assert lk["ok"] and lk["stream_owners"] == []
    survivor.prefix.clear()
    assert survivor.allocator.free_pages == survivor.n_pages
    # prefix sharing did real work across the storm
    hits = survivor.prefix.snapshot()
    assert hits["hits_full"] + hits["hits_partial"] >= 1


# ------------------------------------------------------------- telemetry

def test_control_snapshot_aggregates_and_exports(tok, pag):
    router = DecodeRouter([pag]).start()
    router.batchers[0].eos_id = -1
    p = [5, 6, 7, 8, 9, 10]
    router.submit_ids(p, max_new_tokens=4).result(timeout=120)
    router.submit_ids(p, max_new_tokens=4).result(timeout=120)
    snap = router.control_snapshot()
    router.stop()
    agg = snap["pages"]
    assert agg["pages_total"] == pag.n_pages
    assert agg["hits_full"] >= 1
    assert 0.0 < agg["prefix_hit_rate"] <= 1.0
    rep = snap["replicas"]["0"]
    assert rep["layout"] == "paged"
    assert rep["prefix"]["entries"] >= 1
    assert rep["peak_live_streams"] >= 1
    lines = prometheus_lines("decode_control", snap)
    assert any("prefix_hit_rate" in ln for ln in lines)
    assert any("pages_live" in ln for ln in lines)
    assert any("cow_copies" in ln for ln in lines)


def test_decode_metrics_page_gauges(tok, pag):
    b = DecodeBatcher(pag, replica=0)
    b.eos_id = -1
    b.start()
    b.submit_ids([5, 6, 7, 8], max_new_tokens=4).result(timeout=120)
    b.stop()
    snap = b.metrics.snapshot()
    assert snap["peak_live_streams"] >= 1
    assert snap["kv_pages_free"] + snap["kv_pages_live"] == pag.n_pages
