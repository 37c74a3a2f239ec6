"""The decode step's ROW extent: ``DecodeBatcher`` seats the lowest free slot
and ``PagedDecodeEngine.decode_batch`` launches the smallest rung of
``row_rungs`` above the highest attached slot — one mechanism in two places.

Engines of 64 slots (the least that has the small rung) of ``bert-tiny-long``
(float and int8 pools) and ``ax-k1-share-tiny``, on the CPU.  Pinned here:
the served tokens are those of the same engine held at the top row rung; the
seat order; every (row rung, page rung) pair is traced in warmup and no churn,
kill storm included, traces another; what the ``decode.dispatch`` leaf
states; an array handed to the batcher decides tokens at the small rung."""
import heapq
import random
import time

import numpy as np
import pytest

from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
from pdnlp_tpu.obs.phases import decode_host_phases, format_decode_table
from pdnlp_tpu.obs.trace import Tracer
from pdnlp_tpu.serve import DecodeBatcher, DecodeRouter, PagedDecodeEngine
from pdnlp_tpu.serve.decode import DecodeStream
from pdnlp_tpu.utils.config import Args

TEXTS = ["天地人你我", "好坏大小上下来去" * 5, "爱恨喜怒哀乐" * 15]
BUCKETS = (16, 32)
SLOTS = 64
KINDS = {
    "bert": dict(model="bert-tiny-long"),
    "bert-int8-pool": dict(model="bert-tiny-long", kv_dtype="int8"),
    "latent": dict(model="ax-k1-share-tiny", dtype="float32"),
}


@pytest.fixture(scope="module")
def tok():
    return WordPieceTokenizer(build_vocab(TEXTS, size=128))


def make_engine(tok, kind, **kw):
    """A 64-slot engine with a tracer of its own, recording."""
    args = Args(decode_slots=SLOTS, decode_max_len=48, max_seq_len=48,
                max_new_tokens=8, **KINDS[kind])
    eng = PagedDecodeEngine(args, tokenizer=tok, mesh=None, buckets=BUCKETS,
                            tracer=Tracer(enabled=True), **kw)
    assert eng.row_rungs == (16, SLOTS)
    return eng


@pytest.fixture(scope="module")
def built(tok):
    """kind -> (ONE warmed 64-slot engine of it, programs traced by its
    warmup), made when a test first asks."""
    made = {}

    def get(kind):
        if kind not in made:
            eng = make_engine(tok, kind)
            before = eng.metrics.retraces.value
            eng.warmup_decode()
            made[kind] = eng, eng.metrics.retraces.value - before
        return made[kind]

    return get


@pytest.fixture(params=list(KINDS))
def rowed(request, built):
    return built(request.param)


def prompts(n, seed, tok, eng, lo=3, hi=20):
    rng = np.random.default_rng(seed)
    V = min(eng.cfg.vocab_size, tok.vocab_size)
    return [rng.integers(5, V, int(k)).tolist()
            for k in rng.integers(lo, hi, n)]


def launched(eng):
    """Spy on the engine's row rung: -> the list every decode launch
    appends ``(rows it answers for, highest attached slot + 1)`` to."""
    rows, real = [], eng._decode_rows

    def spy(*a, **k):
        top = np.flatnonzero(eng._table[:, 0] < eng.n_pages)
        out = real(*a, **k)
        rows.append((len(out), int(top[-1]) + 1 if len(top) else 0))
        return out

    eng._decode_rows = spy
    return rows


def churn(submit, eng, tok, seed=5):
    """Short and long streams in three waves that cross the row rung both
    ways: a few (16 rows), many at once on top of them (64), and, when all
    of those are done, a few again (16).  -> every stream's tokens, in
    submission order."""
    rng = random.Random(seed)
    ps = prompts(46, seed, tok, eng)

    def wave(chunk):
        return [submit(p, max_new_tokens=rng.choice([2, 5, 24]))
                for p in chunk]

    first = wave(ps[:6])
    deadline = time.monotonic() + 120
    while (not any(len(s.emitted) >= 2 or s.done() for s in first)
           and time.monotonic() < deadline):
        time.sleep(0.002)
    streams = first + wave(ps[6:40])
    outs = [s.result(timeout=300) for s in streams]
    streams = wave(ps[40:])
    return outs + [s.result(timeout=300) for s in streams]


def serve(eng, tok, fn):
    b = DecodeBatcher(eng).start()
    b.eos_id = -1
    try:
        return fn(b)
    finally:
        b.stop()
        eng.__dict__.pop("_decode_rows", None)
        eng.__dict__.pop("decode_batch", None)


# ------------------------------------------------ the same tokens are served

def test_a_churn_serves_the_tokens_of_the_engine_held_at_the_top_row_rung(
        rowed, tok):
    eng, _ = rowed
    rows = launched(eng)
    got = serve(eng, tok, lambda b: churn(b.submit_ids, eng, tok))
    assert {r for r, _ in rows} == {16, SLOTS}        # crossed, both ways
    assert [r for r, _ in rows][0] == 16 == [r for r, _ in rows][-1]
    # the smallest rung above the highest attached slot, every launch
    assert all(r == (16 if top <= 16 else SLOTS) for r, top in rows)
    assert eng.leak_check()["ok"]

    eng.reset_cache()                 # cold again: nothing indexed
    eng.row_rungs = (SLOTS,)
    try:
        held = launched(eng)
        want = serve(eng, tok, lambda b: churn(b.submit_ids, eng, tok))
    finally:
        eng.row_rungs = (16, SLOTS)
    assert {r for r, _ in held} == {SLOTS}
    assert all(len(o) > 0 for o in want)
    assert got == want
    assert eng.leak_check()["ok"]


# ------------------------------------------------------------ the seat order

def seat(b, streams):
    """Seat ``streams`` as the worker's round would -> their slots."""
    claims = []
    with b._lock:
        b._waiting.extend(streams)
        b._seat_locked(None, claims, [])
    return [slot for slot, _, _ in claims]


def free(b, slots):
    """Finish the streams seated in ``slots`` (EOS: nothing emitted)."""
    b._advance_rows([(s, b._slots[s].stream, b.eos_id, 1) for s in slots],
                    0.0)


def test_the_next_seat_is_the_lowest_free_slot_after_any_seats_and_frees(
        tok):
    eng = PagedDecodeEngine(
        Args(model="bert-tiny", decode_slots=12, decode_max_len=48,
             max_new_tokens=4), tokenizer=tok, mesh=None, buckets=BUCKETS)
    b = DecodeBatcher(eng)
    rng = random.Random(7)
    live = set()
    for _ in range(200):
        if live and (len(live) == eng.slots or rng.random() < 0.45):
            gone = rng.sample(sorted(live), rng.randint(1, len(live)))
            free(b, gone)
            live -= set(gone)
        else:
            n = rng.randint(1, eng.slots - len(live))
            want = sorted(set(range(eng.slots)) - live)[:n]
            got = seat(b, [DecodeStream([5, 6, 7], 4) for _ in range(n)])
            assert got == want
            live |= set(got)
        assert sorted(b._free) == sorted(set(range(eng.slots)) - live)
        assert not b._free or b._free[0] == min(b._free)   # a heap
    free(b, sorted(live))
    assert eng.leak_check()["ok"]


def test_a_put_back_after_exhausted_pages_keeps_the_lowest_slot_next(tok):
    """A pool of 7 pages, 3 a stream: the third seat finds no pages, its
    slot goes back, and it is the next one seated."""
    eng = PagedDecodeEngine(
        Args(model="bert-tiny", decode_slots=8, decode_max_len=48,
             max_new_tokens=40), tokenizer=tok, mesh=None, buckets=BUCKETS,
        prefix_share=False)
    eng.n_pages = 7
    eng.reset_cache()
    b = DecodeBatcher(eng)
    streams = [DecodeStream([5, 6, 7, 8], 40) for _ in range(4)]
    assert seat(b, streams[:2]) == [0, 1]
    free(b, [0])
    assert seat(b, streams[2:]) == [0]           # slot 2 was put back
    assert list(b._waiting) == streams[3:]
    assert heapq.nsmallest(2, b._free) == [2, 3]
    free(b, [1])
    assert seat(b, []) == [1]                    # the head of the queue
    free(b, [0, 1])
    assert eng.leak_check()["ok"]


# ------------------------------------------- every pair warmed, none after it

def test_warmup_traces_every_pair_of_rungs_and_a_churn_traces_nothing(
        rowed, tok):
    eng, traced = rowed
    decode = {k for k in eng._seen_shapes if k[0] == "decode"}
    assert decode == {("decode", r, g) for r in eng.row_rungs
                      for g in eng.decode_rungs}
    others = len(eng._seen_shapes) - len(decode)
    # prefill launches two programs (the forward, the insert)
    assert traced == len(decode) + others + len(eng.prefill_buckets)
    before = eng.metrics.retraces.value
    misses = eng.metrics.cache_misses.value
    rows = launched(eng)
    serve(eng, tok, lambda b: churn(b.submit_ids, eng, tok, seed=9))
    assert {r for r, _ in rows} == {16, SLOTS}
    assert eng.metrics.retraces.value == before
    assert eng.metrics.cache_misses.value == misses


def test_a_kill_storm_across_the_row_rung_traces_nothing(built, tok):
    """Two replicas behind a router, one killed while it decodes at the top
    rung: its streams re-prefill on the survivor, which crosses its own
    rung with them; no program is traced, no token is lost."""
    first, _ = built("bert")
    ps = prompts(43, 13, tok, first)
    news = [random.Random(i).choice([3, 12, 28]) for i in range(len(ps))]

    def all_of(submit):
        return [s.result(timeout=300) for s in
                [submit(p, max_new_tokens=n) for p, n in zip(ps, news)]]

    want = serve(first, tok, lambda b: all_of(b.submit_ids))
    second = make_engine(tok, "bert")
    second.tracer = first.tracer
    router = DecodeRouter([first, second]).start()
    for b in router.batchers:
        b.eos_id = -1
    router.warmup()
    traced0 = first.metrics.retraces.value + second.metrics.retraces.value
    rows = launched(second)
    try:
        streams = [router.submit_ids(p, max_new_tokens=n)
                   for p, n in zip(ps[:40], news)]
        deadline = time.monotonic() + 60
        while (router.batchers[0].metrics.tokens_out_total.value < 60
               and time.monotonic() < deadline):
            time.sleep(0.002)
        router.kill(0)
        got = [s.result(timeout=300) for s in streams]
        # the storm over, three more: the survivor is back at its small rung
        got += [s.result(timeout=300) for s in
                [router.submit_ids(p, max_new_tokens=n)
                 for p, n in zip(ps[40:], news[40:])]]
    finally:
        router.stop()
        second.__dict__.pop("_decode_rows", None)
    assert router.batchers[0].dead and not router.batchers[1].dead
    assert router.batchers[1].rmetrics.requeued_in.value >= 1
    assert got == want
    assert {r for r, _ in rows} == {16, SLOTS}
    assert first.metrics.retraces.value + second.metrics.retraces.value \
        == traced0
    # the dead replica's engine is left as it died: give the module's
    # engine its clean state back
    first.reset_cache()


def test_a_drafter_launches_the_row_rung_of_its_own_table(built, tok):
    """The speculative pair at 64 slots: the drafter's steps cover 16 rows
    while the verify window keeps all 64, and the tokens are the primary's
    own."""
    eng, _ = built("bert")
    ps = prompts(5, 29, tok, eng)

    def five(b):
        return [s.result(timeout=300) for s in
                [b.submit_ids(p, max_new_tokens=9) for p in ps]]

    want = serve(eng, tok, five)
    dr = make_engine(tok, "bert", prefix_share=False)
    b = DecodeBatcher(eng, drafter=dr, draft_k=3)
    b.warmup()
    traced0 = eng.metrics.retraces.value + dr.metrics.retraces.value
    rows = launched(dr)
    b.start()
    b.eos_id = -1
    try:
        got = five(b)
        snap = b.spec_snapshot()
    finally:
        b.stop()
    assert got == want
    assert snap["enabled"] and snap["accepted_tokens"] > 0
    assert rows and {r for r, _ in rows} == {16}
    assert eng.metrics.retraces.value + dr.metrics.retraces.value == traced0
    assert eng.leak_check()["ok"] and dr.leak_check()["ok"]


# ------------------------------------------------------ what the leaf states

def test_the_dispatch_leaf_states_the_rows_launched_and_what_they_read(
        rowed, tok):
    eng, _ = rowed
    tr = eng.tracer
    tr.clear()
    rows = launched(eng)
    serve(eng, tok, lambda b: churn(b.submit_ids, eng, tok, seed=21))
    recs = tr.records()
    tr.clear()
    leaves = [r["attrs"] for r in recs if r["name"] == "decode.dispatch"]
    assert len(leaves) == len(rows)
    extents = {g * eng.page_sz for g in eng.decode_rungs}
    for a, (r, top) in zip(leaves, rows):
        assert a["rows"] == r >= top
        if eng.family.name == "bert" and not eng.kv_int8:
            # the step walks the seated rows' own pages: the live positions,
            # each row's rounded up to a page
            assert a["attend"] == "kernel"
            assert a["kv_positions_read"] % eng.page_sz == 0
            assert a["kv_positions_read"] - a["kv_positions_live"] \
                <= r * (eng.page_sz - 1)
        else:
            assert a["attend"] == "gather"
            assert a["kv_positions_read"] % r == 0
            assert a["kv_positions_read"] // r in extents
        assert 0 < a["kv_positions_live"] <= a["kv_positions_read"]
        assert a["live"] <= r
    fetched = [r["attrs"]["bytes"] for r in recs
               if r["name"] == "decode.fetch"]
    if eng.expert_load is None:       # experts bring their counts as well
        assert fetched == [4 * r for r, _ in rows]
    worker = decode_host_phases(recs)["0"]
    shares = worker["decode_row_rungs"]
    assert set(shares) == {"16", str(SLOTS)}
    assert sum(shares.values()) == pytest.approx(1.0, abs=1e-3)
    assert shares["16"] == pytest.approx(
        sum(r == 16 for r, _ in rows) / len(rows), abs=1e-3)
    assert (f"decode steps by rows launched: {shares['16']:.1%} at 16, "
            f"{shares[str(SLOTS)]:.1%} at {SLOTS}"
            in format_decode_table({"0": worker}))


# --------------------------------- an array handed to the batcher still rules

@pytest.mark.parametrize("wrap,same", [
    (np.asarray, True),
    (lambda out: np.roll(out, 1, axis=-1), False),
], ids=["an-array-of-the-logits", "rolled-along-the-last-axis"])
def test_an_array_of_the_small_rung_decides_the_served_tokens(
        rowed, tok, wrap, same):
    eng, _ = rowed
    ps = prompts(5, 43, tok, eng)
    shapes = []

    def five(b):
        return [s.result(timeout=300) for s in
                [b.submit_ids(p, max_new_tokens=8) for p in ps]]

    sound = serve(eng, tok, five)
    inner = eng.decode_batch

    def wrapped(*a, **k):
        out = wrap(inner(*a, **k))
        shapes.append(out.shape)
        return out

    eng.decode_batch = wrapped
    got = serve(eng, tok, five)
    V = eng.cfg.vocab_size
    assert shapes and set(shapes) == {(16, V)}
    assert all(len(x) == 8 for x in sound + got)
    if same:
        assert got == sound
    else:
        # the first token comes from the prefill, which is not wrapped
        assert [x[0] for x in got] == [x[0] for x in sound]
        assert all(x[1] != y[1] for x, y in zip(got, sound))
