"""Generative decoding tests: the bitwise incremental-vs-recompute
contract, slot reuse under continuous batching, int8 KV parity, the
zero-retrace guarantee, KV budgets, the streaming hop-chain contract, and
chain integrity through a mid-decode replica kill.

The bitwise gate compares incremental decode against a FULL RECOMPUTE
from a cold cache in the same page geometry — every cached value
recomputed from scratch, nothing reused — which is exactly the property
the KV cache + page machinery claims (page aliasing, stale-KV leaks,
donation bugs and wrong masks all break it).  Against the one-shot WIDE
causal forward the comparison is argmax-exact within 5e-6: XLA's CPU gemm
blocks the contraction differently per row extent (measured in
``models/decoder.py``'s docstring), so a ``[rows, 1]`` pass and a
``[rows, S]`` pass agree to accumulation order, not bits, on this
backend."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
from pdnlp_tpu.models import bert, decoder, get_config
from pdnlp_tpu.obs.memory import KVBudget, KVBudgetExceeded
from pdnlp_tpu.obs.request import chain_issues, validate_chains
from pdnlp_tpu.ops.attention import causal_bias, dot_product_attention
from pdnlp_tpu.serve import (
    DecodeBatcher, DecodeRouter, PagedDecodeEngine,
)
from pdnlp_tpu.serve.decode import (
    Chosen, DecodeStream, _Slot, chosen_ids, detokenize, greedy_ids,
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


@pytest.fixture(scope="module")
def eng4(tok):
    """ONE warmed default-geometry engine shared by the batcher-level
    tests below: stream counters live on each (fresh) DecodeBatcher, not
    the engine, so sharing the engine only shares its compiled jits —
    which is exactly what keeps this file inside the tier-1 budget."""
    eng = PagedDecodeEngine(make_args(trace=True), tokenizer=tok, mesh=None,
                            buckets=BUCKETS)
    eng.warmup_decode()
    return eng


PS = 16  # page size of the model-level tests' pools


def identity_cache(cfg, rows, width, ks, vs):
    """Float32 K and V pools holding a prefill's rows under an IDENTITY
    table: row i owns pages ``i * MP .. (i + 1) * MP - 1`` in order, so the
    paged step reads exactly the positions a dense ``[rows, width]`` cache
    would hold."""
    mp = width // PS
    table = np.arange(rows * mp, dtype=np.int32).reshape(rows, mp)
    shape = (cfg.num_layers, rows * mp, PS, cfg.hidden_size)
    p = np.arange(ks.shape[2])
    flat = table[:, p // PS] * PS + p % PS
    pk, pv = decoder.paged_insert(jnp.zeros(shape, jnp.float32),
                                  jnp.zeros(shape, jnp.float32), ks, vs,
                                  flat)
    return table, pk, pv


def run_streams(batcher, ps, max_new=8, eos=-1, timeout=120):
    batcher.eos_id = eos  # -1 = never stop early (deterministic lengths)
    streams = [batcher.submit_ids(p, max_new_tokens=max_new) for p in ps]
    return streams, [s.result(timeout=timeout) for s in streams]


# --------------------------------------------------------- model-level math

def test_causal_attention_composition():
    cb = np.asarray(causal_bias(8))
    assert cb.shape == (1, 1, 8, 8)
    assert (cb[0, 0][np.tril_indices(8)] == 0).all()
    assert (cb[0, 0][np.triu_indices(8, 1)] < -1e8).all()
    q = jnp.ones((2, 4, 2, 8))
    k = jnp.ones((2, 6, 2, 8))
    with pytest.raises(ValueError):  # causal needs a square mask
        dot_product_attention(q, k, k, causal=True)


def test_decode_step_bitwise_equals_full_recompute(tok):
    """THE decode-correctness pin: incremental KV decode (a live cache
    carried across steps) is bitwise equal, per step, to a full recompute
    from a COLD cache — fresh prefill + from-scratch replay of every
    generated token, nothing reused."""
    cfg = get_config("bert-tiny", vocab_size=tok.vocab_size, num_labels=6)
    params = bert.init_params(jax.random.key(0), cfg)
    head = decoder.init_lm_head(jax.random.key(1), cfg)
    B, W, bucket, steps = 3, 32, 16, 5
    ps = prompts(3, seed=7, hi=10, vocab=tok.vocab_size)
    pf = jax.jit(decoder.prefill, static_argnums=(2,))
    step = jax.jit(decoder.paged_decode_step, static_argnums=(2,))

    def run_chain():
        """prefill once, then decode `steps` tokens greedily, returning
        the per-step logits — the scratch replay recomputes the whole
        chain cold and must reproduce it bit for bit."""
        ids = np.zeros((B, bucket), np.int32)
        mask = np.zeros((B, bucket), np.int32)
        for i, p in enumerate(ps):
            ids[i, :len(p)] = p
            mask[i, :len(p)] = 1
        last = np.asarray([len(p) - 1 for p in ps], np.int32)
        lg, ks, vs = pf(params, head, cfg, ids, mask, last)
        table, ck, cv = identity_cache(cfg, B, W, ks, vs)
        out = [np.asarray(lg)]
        cur = np.argmax(out[0], -1).astype(np.int32)
        pos = last + 1
        for _ in range(steps):
            lg, ck, cv = step(params, head, cfg, cur[:, None], ck, cv,
                              table, pos)
            out.append(np.asarray(lg))
            cur = np.argmax(out[-1], -1).astype(np.int32)
            pos = pos + 1
        return out

    a = run_chain()
    b = run_chain()  # cold cache, every K/V recomputed
    for t, (x, y) in enumerate(zip(a, b)):
        assert np.array_equal(x, y), f"step {t} not bitwise"


def test_decode_matches_wide_forward_oracle(tok):
    """Incremental decode vs the INDEPENDENT one-shot wide causal
    forward: greedy argmax equal at every step, logits within 5e-6
    (the documented extent-blocking ULP bound; observed ~3e-7)."""
    cfg = get_config("bert-tiny", vocab_size=tok.vocab_size, num_labels=6)
    params = bert.init_params(jax.random.key(0), cfg)
    head = decoder.init_lm_head(jax.random.key(1), cfg)
    B, W, bucket = 3, 32, 16
    ps = prompts(3, seed=9, hi=10, vocab=tok.vocab_size)
    pf = jax.jit(decoder.prefill, static_argnums=(2,))
    step = jax.jit(decoder.paged_decode_step, static_argnums=(2,))

    ids = np.zeros((B, bucket), np.int32)
    mask = np.zeros((B, bucket), np.int32)
    for i, p in enumerate(ps):
        ids[i, :len(p)] = p
        mask[i, :len(p)] = 1
    last = np.asarray([len(p) - 1 for p in ps], np.int32)
    lg, ks, vs = pf(params, head, cfg, ids, mask, last)
    table, ck, cv = identity_cache(cfg, B, W, ks, vs)
    gen = [[] for _ in range(B)]
    cur = np.argmax(np.asarray(lg), -1).astype(np.int32)
    pos = last + 1
    for t in range(5):
        lg, ck, cv = step(params, head, cfg, cur[:, None], ck, cv, table,
                          pos)
        oid = np.zeros((B, W), np.int32)
        om = np.zeros((B, W), np.int32)
        for i, p in enumerate(ps):
            seq = p + gen[i] + [int(cur[i])]
            oid[i, :len(seq)] = seq
            om[i, :len(seq)] = 1
        olg, _, _ = pf(params, head, cfg, oid, om, pos)
        got, want = np.asarray(lg), np.asarray(olg)
        assert np.abs(got - want).max() < 5e-6, f"step {t}"
        assert (np.argmax(got, -1) == np.argmax(want, -1)).all(), f"step {t}"
        for i in range(B):
            gen[i].append(int(cur[i]))
        cur = np.argmax(got, -1).astype(np.int32)
        pos = pos + 1


def test_a_stream_over_reused_pages_is_bitwise_the_stream_over_fresh_ones(
        tok):
    """A stream decoded over REUSED pages (stale K/V of a previous occupant
    in every page of the pool, beyond and below its own positions) is
    bitwise identical to the same stream on a fresh engine — the
    visibility mask proves stale page contents contribute exact zeros."""
    args = make_args(decode_slots=1)   # one stream's pages = the whole pool
    p = prompts(1, seed=11, vocab=tok.vocab_size)[0]

    def occupy(engine, prompt, max_new):
        engine.attach_stream(0, DecodeStream(prompt, max_new), share=False)
        first = engine.prefill_ids([prompt], [0])
        return first, int(np.argmax(first[0]))

    def drive(engine, warm_garbage):
        t = np.zeros((engine.slots,), np.int32)
        po = np.zeros((engine.slots,), np.int32)
        if warm_garbage:  # a previous occupant fills EVERY page end to end
            g = list(range(5, 15))
            _, t[0] = occupy(engine, g, engine.max_len - len(g))
            po[0] = len(g)
            for _ in range(engine.max_len - len(g)):
                lg = engine.decode_batch(t, po, live=1)
                t[0] = int(np.argmax(lg[0]))
                po[0] += 1
            assert engine.allocator.used_pages == engine.n_pages
            engine.detach_slot(0)
            stale = np.abs(np.asarray(engine._pools[0])).sum(axis=(2, 3))
            assert (stale > 0).all()   # every page of every layer is dirty
        logits0, t[0] = occupy(engine, p, 6)
        out = [logits0[0]]
        po[0] = len(p)
        for _ in range(6):
            lg = engine.decode_batch(t, po, live=1)
            out.append(lg[0])
            t[0] = int(np.argmax(lg[0]))
            po[0] += 1
        engine.detach_slot(0)
        assert engine.leak_check()["ok"]
        return out

    a = drive(PagedDecodeEngine(args, tokenizer=tok, mesh=None,
                                buckets=BUCKETS, prefix_share=False),
              warm_garbage=True)
    b = drive(PagedDecodeEngine(args, tokenizer=tok, mesh=None,
                                buckets=BUCKETS, prefix_share=False),
              warm_garbage=False)
    for t, (x, y) in enumerate(zip(a, b)):
        assert np.array_equal(x, y), f"step {t}: stale page leaked"


# ------------------------------------------------------- continuous batching

def test_continuous_batching_slot_join_leave(tok, eng4):
    """More streams than slots: finished streams leave, waiting streams
    claim freed slots between steps, every stream completes, and the
    freed-slot reuse + occupancy metrics actually record it."""
    b = DecodeBatcher(eng4).start()
    ps = prompts(10, seed=5, vocab=tok.vocab_size)
    _, outs = run_streams(b, ps, max_new=6)
    assert all(len(o) == 6 for o in outs)
    snap = b.snapshot()
    assert snap["decode"]["tokens_out_total"] == 60
    assert snap["replica"]["slot_reuse_ms"]["count"] >= 4
    assert snap["replica"]["slot_occupancy"]["count"] >= 1
    assert snap["decode"]["streams_total"] == 10
    b.stop()


def test_batcher_tokens_deterministic_across_claim_orders(tok, eng4):
    """The same prompt generates the same tokens whatever else shares
    the decode batch and in whatever order slots were claimed."""
    ps = prompts(5, seed=13, vocab=tok.vocab_size)

    def run(order):
        b = DecodeBatcher(eng4).start()
        b.eos_id = -1
        streams = {i: b.submit_ids(ps[i], max_new_tokens=6) for i in order}
        res = {i: s.result(timeout=60) for i, s in streams.items()}
        b.stop()
        return res

    a, z = run([0, 1, 2, 3, 4]), run([4, 2, 0, 3, 1])
    assert all(a[i] == z[i] for i in range(5))


def test_streaming_surface_and_detokenize(tok, eng4):
    b = DecodeBatcher(eng4).start()
    b.eos_id = -1
    s = b.submit_ids([5, 6, 7], max_new_tokens=4)
    streamed = list(s.tokens(timeout=30))
    assert streamed == s.result(1)
    assert len(streamed) == 4
    text = detokenize(tok, streamed)
    assert isinstance(text, str) and text
    b.stop()


def test_zero_retraces_50_mixed_streams(tok):
    """The acceptance bar: across 50 mixed-length streams, neither the
    bucketed prefill nor the ONE fixed decode shape compiles after
    warmup (retrace counter AND compile-cache misses stay flat)."""
    eng = PagedDecodeEngine(make_args(decode_slots=8, decode_max_len=64,
                                      max_new_tokens=12),
                            tokenizer=tok, mesh=None, buckets=BUCKETS)
    b = DecodeBatcher(eng).start()
    b.warmup()
    retr0 = eng.metrics.retraces.value
    miss0 = eng.metrics.cache_misses.value
    ps = prompts(50, seed=17, lo=3, hi=30, vocab=tok.vocab_size)
    _, outs = run_streams(b, ps, max_new=8)
    assert all(len(o) == 8 for o in outs)
    assert eng.metrics.retraces.value - retr0 == 0
    assert eng.metrics.cache_misses.value - miss0 == 0
    b.stop()


# ------------------------------------------------------------------ int8 KV

def test_kv_int8_argmax_parity(tok, eng4):
    """int8 KV (calibrated per-channel scale tables) greedy-decodes the
    same token sequences as the fp32 cache."""
    ps = prompts(4, seed=1, vocab=tok.vocab_size)

    def gen(engine):
        b = DecodeBatcher(engine).start()
        b.warmup()
        _, outs = run_streams(b, ps, max_new=8)
        b.stop()
        return outs

    int8_eng = PagedDecodeEngine(make_args(kv_dtype="int8"), tokenizer=tok,
                                 mesh=None, buckets=BUCKETS)
    assert gen(eng4) == gen(int8_eng)


def test_kv_scales_offline_artifact_matches_self_calibration(tok, tmp_path):
    """`quantize_ckpt.py --kv_calib` emits byte-identical scale tables to
    engine self-calibration for the same params, and the engine auto-loads
    the manifest-verified sidecar on checkpoint swap."""
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    from quantize_ckpt import main as quantize_main

    from pdnlp_tpu.train import checkpoint as ckpt

    cfg = get_config("bert-tiny", vocab_size=tok.vocab_size, num_labels=6)
    params = bert.init_params(jax.random.key(42), cfg)
    path = str(tmp_path / "gen-cls.msgpack")
    ckpt.save(path, params)
    assert quantize_main([path, "--kv_calib", "bert-tiny",
                          "-o", str(tmp_path / "gen.int8.msgpack")]) == 0
    sidecar = str(tmp_path / "gen-cls.kvscales.msgpack")
    assert os.path.exists(sidecar)
    assert os.path.exists(sidecar + ".manifest.json")

    eng = PagedDecodeEngine(make_args(kv_dtype="int8"), tokenizer=tok,
                            mesh=None, buckets=BUCKETS)
    eng.load_checkpoint(path)          # auto-loads the sidecar
    loaded_k = np.asarray(eng._kv_scales[0])
    eng2 = PagedDecodeEngine(make_args(kv_dtype="int8"), tokenizer=tok,
                             mesh=None, buckets=BUCKETS)
    eng2.load_checkpoint(path)
    eng2._kv_scales = None             # force self-calibration instead
    eng2.calibrate_kv()
    np.testing.assert_array_equal(loaded_k, np.asarray(eng2._kv_scales[0]))


# ---------------------------------------------------------------- KV budget

def test_kv_budget_doors(tok, eng4):
    # one maximum-length stream's pages, in MB (3 pages of 16 at 48)
    stream_mb = eng4.pages_per_stream * eng4.page_bytes / 2**20
    assert eng4.n_pages == 4 * eng4.pages_per_stream      # unbudgeted
    # (a) construction refusal: not even one maximum-length stream fits
    with pytest.raises(KVBudgetExceeded):
        PagedDecodeEngine(make_args(kv_hbm_mb=stream_mb / 2), tokenizer=tok,
                          mesh=None, buckets=BUCKETS)
    # (b) loud page cap: the budget covers 2.2 of the 4 requested streams'
    # pages; the slots stay the batch width
    capped = PagedDecodeEngine(make_args(kv_hbm_mb=2.2 * stream_mb),
                               tokenizer=tok, mesh=None, buckets=BUCKETS)
    assert capped.n_pages == int(2.2 * eng4.pages_per_stream)
    assert capped.slots == 4
    assert capped.kv_snapshot()["budget_mb"] == pytest.approx(
        2.2 * stream_mb, abs=1e-3)
    # (c) admission refusal in budget units: a stream that cannot fit
    b = DecodeBatcher(capped).start()
    with pytest.raises(KVBudgetExceeded):
        b.submit_ids(list(range(5, 15)), max_new_tokens=10_000)
    # (d) live occupancy gauge moves while streams decode (and returns
    # to zero when the stream's pages free)
    b.warmup()
    b.eos_id = -1
    s = b.submit_ids(list(range(5, 12)), max_new_tokens=30)
    peak = 0
    deadline = time.monotonic() + 30
    while not s.done() and time.monotonic() < deadline:
        peak = max(peak, b.metrics.kv_bytes_live.value)
        time.sleep(0.001)
    s.result(timeout=60)
    assert peak > 0
    assert b.metrics.kv_bytes_live.value == 0
    b.stop()


def test_kv_budget_unbudgeted_plain_capacity_error(tok, eng4):
    b = DecodeBatcher(eng4).start()
    with pytest.raises(ValueError):
        b.submit_ids(list(range(5, 15)), max_new_tokens=10_000)
    b.stop()


def test_kv_budget_pure_policy():
    bgt = KVBudget(1.0)  # 1 MB
    assert bgt.cap_pages(8, 2**19) == 2          # two 0.5 MB pages fit
    assert bgt.cap_pages(1, 2**19) == 1          # never more than asked
    with pytest.raises(KVBudgetExceeded):
        bgt.cap_pages(8, 2**21)                  # a 2 MB page never fits
    with pytest.raises(KVBudgetExceeded):        # nor a stream of 3 pages
        bgt.cap_pages(8, 2**19, min_pages=3)
    with pytest.raises(KVBudgetExceeded):
        bgt.check_stream(tokens_total=2048, token_bytes=1024)
    bgt.set_live(4096)
    assert bgt.snapshot()["live_bytes"] == 4096
    assert KVBudget(0).cap_pages(8, 2**40) == 8  # unbudgeted: no checks


# ------------------------------------------------------------------ infill

def test_infill_scoring_matches_bidirectional_mlm(tok, eng4):
    """The MLM-infilling scorer is exactly the bidirectional trunk + LM
    head — pinned bitwise against the direct model-level computation at
    the same padded shapes."""
    eng = eng4
    ids = [5, 6, tok.unk_id, 8, 9]
    got = eng.infill_ids([ids])
    rows, bucket = eng.prefill_rows, 16
    pad_ids = np.zeros((rows, bucket), np.int32)
    pad_mask = np.zeros((rows, bucket), np.int32)
    pad_ids[0, :len(ids)] = ids
    pad_mask[0, :len(ids)] = 1
    want = decoder.infill_logits(eng.params, eng.head, eng.cfg,
                                 jnp.asarray(pad_ids),
                                 jnp.asarray(pad_mask))
    np.testing.assert_array_equal(got[0], np.asarray(want)[0])


# -------------------------------------------------------------- hop chains

def _hop(name, t, **attrs):
    return {"name": "hop", "t0": t, "t1": t, "attrs": attrs}


def test_streaming_chain_rules():
    ok = [_hop("hop", 0.0, request_id="r1", hop="admit"),
          _hop("hop", 1.0, request_id="r1", hop="prefill", slot=0),
          _hop("hop", 2.0, request_id="r1", hop="decode", slot=0, step=0),
          _hop("hop", 3.0, request_id="r1", hop="complete")]
    assert chain_issues(ok) == []
    # prefill-less decode is a violation
    bad = [ok[0], ok[2], ok[3]]
    assert any("no earlier 'prefill'" in i for i in chain_issues(bad))
    # a requeue + re-prefill continuation is legal
    requeued = ok[:3] + [
        _hop("hop", 4.0, request_id="r1", hop="requeue", streamed=True),
        _hop("hop", 5.0, request_id="r1", hop="prefill", slot=1),
        _hop("hop", 6.0, request_id="r1", hop="decode", slot=1, step=1),
        _hop("hop", 7.0, request_id="r1", hop="complete")]
    assert chain_issues(requeued) == []
    # zero-decode streams (EOS at prefill) are complete
    assert chain_issues([ok[0], ok[1], ok[3]]) == []


def test_decode_hops_carry_slot_step_tokens(tok, eng4):
    eng = eng4
    assert eng.tracer.enabled
    b = DecodeBatcher(eng).start()
    b.eos_id = -1
    s = b.submit_ids([5, 6, 7, 8], max_new_tokens=4)
    s.result(timeout=60)
    b.stop()
    hops = [r["attrs"] for r in eng.tracer.records()
            if r.get("name") == "hop"
            and (r.get("attrs") or {}).get("request_id") == s.rid]
    kinds = [h["hop"] for h in hops]
    assert kinds[0] == "admit" and kinds[-1] == "complete"
    assert "prefill" in kinds
    decodes = [h for h in hops if h["hop"] == "decode"]
    assert decodes and all(
        "slot" in d and "step" in d and "tokens_out" in d for d in decodes)
    # step = the index of the token each decode step produces; token 0
    # came from prefill, so decode steps run 1..max_new-1
    assert [d["step"] for d in decodes] == list(range(1, len(decodes) + 1))
    assert [d["tokens_out"] for d in decodes] == \
        list(range(2, len(decodes) + 2))
    report = validate_chains(eng.tracer.records(), [s.rid])
    assert report["complete"] == 1 and report["streamed"] == 1


# ------------------------------------------------------------ replica kill

def test_mid_decode_replica_kill_no_dup_no_loss(tok):
    """Chain integrity through a mid-decode replica kill: orphan streams
    re-prefill on the survivor and emit EXACTLY the reference token
    sequences — no duplicated, no lost tokens — with every chain complete
    (admit → prefill → decode* → requeue → prefill → ... → complete)."""
    args = make_args(decode_slots=4, decode_max_len=120,
                     max_new_tokens=64, trace=True)
    ps = prompts(30, seed=3, lo=3, hi=14, vocab=tok.vocab_size)

    # prefix_share off: this test is the kill contract alone (the sharing
    # variant of it is tests/test_kvpage.py's)
    ref_eng = PagedDecodeEngine(args, tokenizer=tok, mesh=None,
                                buckets=BUCKETS, prefix_share=False)
    rb = DecodeBatcher(ref_eng).start()
    rb.warmup()
    _, refs = run_streams(rb, ps, max_new=48)
    rb.stop()

    # the reference engine rides again as the to-be-killed replica: its
    # jits are already compiled and the kill contract is about batcher +
    # page state, which a stopped batcher leaves clean
    assert ref_eng.leak_check()["ok"]
    engines = [ref_eng,
               PagedDecodeEngine(args, tokenizer=tok, mesh=None,
                                 buckets=BUCKETS, prefix_share=False)]
    tracer = engines[0].tracer
    for e in engines[1:]:
        e.tracer = tracer
    router = DecodeRouter(engines).start()
    for b in router.batchers:
        b.eos_id = -1
    router.warmup()
    traced0 = sum(e.metrics.retraces.value for e in engines)
    streams = [router.submit_ids(p, max_new_tokens=48) for p in ps]
    deadline = time.monotonic() + 60
    while (router.batchers[0].metrics.tokens_out_total.value < 100
           and time.monotonic() < deadline):
        time.sleep(0.005)
    router.kill(0)
    outs = [s.result(timeout=180) for s in streams]
    router.stop()

    assert router.batchers[0].dead and not router.batchers[1].dead
    assert outs == refs, "kill recovery duplicated or lost tokens"
    # both replicas were warmed: the kill, the requeue and the survivor's
    # re-prefills (continuations longer than any prompt) compile nothing
    assert sum(e.metrics.retraces.value for e in engines) == traced0
    report = validate_chains(tracer.records(), [s.rid for s in streams])
    assert report["incomplete"] == {}
    assert report["complete"] == len(streams)
    assert report["requeued"] >= 1
    assert router.batchers[1].rmetrics.requeued_in.value >= 1


def test_router_all_replicas_dead_fails_loudly(tok, eng4):
    router = DecodeRouter([eng4]).start()
    router.warmup()
    router.kill(0)
    deadline = time.monotonic() + 10
    while not router.batchers[0].dead and time.monotonic() < deadline:
        time.sleep(0.01)
    with pytest.raises(RuntimeError):
        router.submit_ids([5, 6, 7])
    router.stop()


# ------------------------------------------- the choice made on the device
# What crosses from device to host after a launch is the chosen token of
# each row (``Chosen.ids``); the logits stay behind it.  Both model
# families through the one engine, as cases of each test.

KINDS = ("paged-bert", "paged-latent")


@pytest.fixture(scope="module", params=KINDS)
def choosing(request, tok):
    """ONE warmed engine a kind (8 slots of 64 positions), untraced."""
    kind = request.param
    model = "ax-k1-share-tiny" if kind == "paged-latent" else "bert-tiny"
    args = Args(model=model, decode_slots=8, decode_max_len=64,
                max_seq_len=64, max_new_tokens=8)
    eng = PagedDecodeEngine(args, tokenizer=tok, mesh=None, buckets=BUCKETS)
    eng.warmup_decode()
    return eng


def spied(eng, rows):
    """Record what every engine call handed its caller: ``(name, the slots
    a prefill wrote, the launch's logits as an array)``."""
    for name in ("prefill_ids", "prefill_chunk", "decode_batch"):
        real = getattr(type(eng), name)

        def spy(*a, _real=real, _name=name, **k):
            out = _real(eng, *a, **k)
            rows.append((_name, list(a[1]) if _name != "decode_batch"
                         else None, np.array(out)))
            return out

        setattr(eng, name, spy)


def unspied(eng):
    for name in ("prefill_ids", "prefill_chunk", "decode_batch"):
        eng.__dict__.pop(name, None)


def vocab_of(eng, tok):
    return min(eng.cfg.vocab_size, tok.vocab_size)


def test_greedy_ids_takes_the_first_index_on_an_exact_tie():
    x = np.zeros((5, 33), np.float32)
    x[0, [7, 20]] = 2.5              # two equal maxima
    x[1, :] = -1.0                   # every id ties
    x[2, [32, 3, 11]] = 9.0          # the last id among them
    x[3, 0] = x[3, 32] = 1e30
    x[4] = np.linspace(-1, 1, 33)    # no tie
    got = np.asarray(jax.jit(greedy_ids)(jnp.asarray(x)))
    assert got.dtype == np.int32
    assert got.tolist() == np.argmax(x, -1).tolist() == [7, 0, 3, 0, 32]


def plant_ties(eng, ids=(3, 9, 17)):
    """Make every logits row tie exactly at its maximum: the vocabulary
    rows of ``ids`` become copies of one row and every other row zero, so a
    row's logits are ``x`` at ``ids`` and exactly 0 elsewhere — whichever
    side wins, more than one id holds the maximum.  -> undo()."""
    params, head = eng.params, eng.head
    ids = list(ids)
    if "kernel" in head:             # the latent family's untied head
        k = np.asarray(head["kernel"].astype(jnp.float32))
        new = np.zeros_like(k)
        new[:, ids] = k[:, ids[:1]]
        eng.head = {"kernel": jnp.asarray(new).astype(head["kernel"].dtype)}
    else:                            # BERT: tied to the word embeddings
        w = np.asarray(params["embeddings"]["word"].astype(jnp.float32))
        new = np.zeros_like(w)
        new[ids] = w[ids[:1]]
        emb = dict(params["embeddings"])
        emb["word"] = jnp.asarray(new).astype(
            params["embeddings"]["word"].dtype)
        eng.params = {**params, "embeddings": emb}

    def undo():
        eng.params, eng.head = params, head

    return undo


@pytest.mark.parametrize("ties", [False, True], ids=["as-served", "ties"])
def test_a_launchs_ids_are_the_argmax_of_its_own_logits(choosing, tok, ties):
    """Prefill, chunk and every decode step: ``ids`` equals
    ``np.argmax`` of the SAME launch's float32 logits on every row, live or
    junk; with an exact tie planted at every row's maximum the first index
    wins, as on the host."""
    eng = choosing
    undo = plant_ties(eng) if ties else (lambda: None)
    try:
        V = vocab_of(eng, tok)
        ps = prompts(3, seed=31, lo=5, hi=15, vocab=V)
        streams = [DecodeStream(p, 8) for p in ps]
        for slot, st in enumerate(streams):
            eng.attach_stream(slot, st, share=False)
        launches = [eng.prefill_ids(ps, [0, 1, 2])]
        launches.append(eng.prefill_chunk([p[-3:] for p in ps], [0, 1, 2],
                                          [len(p) - 3 for p in ps]))
        t = np.zeros((eng.slots,), np.int32)
        po = np.zeros((eng.slots,), np.int32)
        t[:3] = launches[0].ids
        po[:3] = [len(p) for p in ps]
        for _ in range(5):
            out = eng.decode_batch(t, po, live=3)
            launches.append(out)
            t[:3] = out.ids[:3]
            po[:3] += 1
        for out in launches:
            assert isinstance(out, Chosen)
            logits = np.asarray(out)
            assert logits.dtype == np.float32 and out.ids.dtype == np.int32
            assert out.ids.shape == (len(out),) == logits.shape[:1]
            assert out.ids.tolist() == np.argmax(logits, -1).tolist()
            assert out.ids.tolist() == np.asarray(out.ids_device)[
                :len(out)].tolist() == chosen_ids(out)
            assert np.array_equal(out[0], logits[0])
            if ties:
                top = logits.max(-1, keepdims=True)
                assert ((logits == top).sum(-1) >= 2).all()
                assert set(out.ids.tolist()) <= {0, 3}
        assert chosen_ids(np.asarray(launches[-1])) == \
            launches[-1].ids.tolist()
    finally:
        for slot in range(3):
            eng.detach_slot(slot)
        undo()


def serve_block(eng, ps, news, wrap=None, eos=-1):
    """``ps`` (at most a stream a slot, so no slot is reused) through a
    fresh batcher; ``wrap`` stands between the engine's ``decode_batch``
    and the batcher.  -> (streams, what every engine call handed out)."""
    rows = []
    spied(eng, rows)
    if wrap is not None:
        inner = eng.decode_batch
        eng.decode_batch = lambda *a, **k: wrap(inner(*a, **k))
    try:
        b = DecodeBatcher(eng, replica=0)
        b.eos_id = eos
        b.start()
        streams = [b.submit_ids(p, max_new_tokens=n)
                   for p, n in zip(ps, news)]
        for s in streams:
            s.result(timeout=300)
        b.stop()
    finally:
        unspied(eng)
    return streams, rows


def test_what_a_batcher_emits_is_the_host_argmax_of_each_steps_logits(
        choosing, tok):
    """A replay that takes ``np.argmax`` of the logits of each launch a
    stream rode — the rule the batcher applied on the host before the
    choice moved into the program — gives exactly its ``emitted``."""
    eng = choosing
    ps = prompts(6, seed=41, lo=5, hi=15, vocab=vocab_of(eng, tok))
    news = [9, 3, 1, 12, 7, 5]
    streams, rows = serve_block(eng, ps, news)
    assert any(n == "decode_batch" for n, _, _ in rows)
    for s, n in zip(streams, news):
        replay = []
        for name, slots_written, logits in rows:
            if name == "decode_batch":
                if replay:           # live from its prefill on
                    replay.append(int(np.argmax(logits[s.slot])))
            elif s.slot in slots_written:
                assert not replay
                replay.append(int(np.argmax(
                    logits[slots_written.index(s.slot)])))
        assert len(s.emitted) == n
        assert s.emitted == replay[:n]
    leak = eng.leak_check()
    assert leak is None or leak["ok"], leak


@pytest.mark.parametrize("wrap,same", [
    (np.asarray, True),
    (lambda out: np.roll(out, 1, axis=-1), False),
], ids=["an-array-of-the-logits", "rolled-along-the-last-axis"])
def test_an_array_handed_to_the_batcher_is_argmaxed_on_the_host(
        choosing, tok, wrap, same):
    """A wrapper of ``decode_batch`` that hands the batcher an ARRAY has its
    rows argmaxed on the host: the same tokens for the logits themselves,
    other tokens for logits rolled one id along — the benchmark's planted
    fault (``benchmark/tests/test_correct.py::wrong_token``), at this size."""
    eng = choosing
    ps = prompts(5, seed=43, lo=5, hi=15, vocab=vocab_of(eng, tok))
    news = [8] * 5
    sound, _ = serve_block(eng, ps, news)
    wrapped, _ = serve_block(eng, ps, news, wrap=wrap)
    a = [s.emitted for s in sound]
    z = [s.emitted for s in wrapped]
    assert all(len(x) == 8 for x in a + z)
    if same:
        assert a == z
    else:
        # the first token comes from the prefill, which is not wrapped
        assert [x[0] for x in a] == [x[0] for x in z]
        assert all(x[1] != y[1] for x, y in zip(a, z))


class CountingLock:
    """``DecodeBatcher._lock`` with its acquisitions counted."""

    def __init__(self, lock):
        self.lock, self.taken = lock, 0

    def __enter__(self):
        self.taken += 1
        return self.lock.__enter__()

    def __exit__(self, *exc):
        return self.lock.__exit__(*exc)


def per_row_rule(tok_id, emitted, max_new, pos, eos, max_len):
    """``DecodeBatcher._advance`` as it stood before the block pass, one
    row at a time: -> (the token is emitted, the stream is finished)."""
    if tok_id == eos or max_new - emitted <= 0:
        return False, True           # EOS is a stop decision, not an emission
    return True, (emitted + 1 >= max_new or pos >= max_len)


def test_the_block_pass_finishes_rows_as_the_per_row_rule_did(choosing):
    """One block holding every case at once — EOS (not emitted), a budget
    already spent, ``max_new_tokens`` reached, ``pos == max_len``, past it,
    and rows that live on — against the old rule; the table is touched
    under ONE acquisition of the lock, a live row's ``_Slot`` is the same
    object moved in place, every token of the block carries one stamp."""
    eng = choosing
    EOS, M = 77, eng.max_len
    #        tok  emitted  max_new  pos
    cases = [(EOS, 2, 8, 10),        # EOS
             (11, 3, 3, 10),         # nothing left to emit
             (12, 4, 5, 10),         # reaches max_new_tokens
             (13, 1, 8, M),          # pos == max_len
             (14, 1, 8, M + 1),      # past it
             (15, 0, 8, 10),         # a first token, lives on
             (16, 5, 8, M - 1),      # lives on, one position left
             (17, 2, 8, 20)]         # lives on
    assert len(cases) == eng.slots
    b = DecodeBatcher(eng, replica=0)
    b.eos_id = EOS
    rows, seats = [], []
    for slot, (tk, emitted, max_new, pos) in enumerate(cases):
        st = DecodeStream([5, 6, 7], max_new, clock=b.tracer.now)
        for k in range(emitted):
            st._push(100 + k, 1.0)
        seat = _Slot(st, pos - 1, 99)
        b._slots[slot] = seat
        seats.append(seat)
        rows.append((slot, st, tk, pos))
    b._free.clear()
    before = b.metrics.tokens_out_total.value
    b._lock = lock = CountingLock(b._lock)
    b._advance_rows(rows, 2.5)
    assert lock.taken == 1
    pushed = 0
    for slot, (tk, emitted, max_new, pos) in enumerate(cases):
        emits, finishes = per_row_rule(tk, emitted, max_new, pos, EOS, M)
        st, seat = rows[slot][1], seats[slot]
        assert len(st.emitted) == emitted + emits, cases[slot]
        assert st.done() == finishes, cases[slot]
        if emits:
            pushed += 1
            assert st.emitted[-1] == tk and st.last_token_at == 2.5
        else:
            assert tk not in st.emitted
        if finishes:
            assert b._slots[slot] is None and slot in b._free
        else:
            assert b._slots[slot] is seat      # moved in place
            assert (seat.pos, seat.next_token) == (pos, tk)
    assert pushed == 6
    assert b.metrics.tokens_out_total.value == before + pushed
    assert sorted(b._free) == [0, 1, 2, 3, 4]
    # gaps: one a row that had a token before (five of the six pushed)
    assert b.metrics.intertoken_ms.count == 5
