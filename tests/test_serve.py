"""Serve-layer tests: bucketing, batcher flush/backpressure/deadlines,
compile-cache stability (zero steady-state retraces), and offline-scoring
parity with the ``predict_tpu.py`` path on a saved checkpoint."""
import os

import jax
import numpy as np
import pytest

from pdnlp_tpu.data.collate import pad_ids_to_bucket
from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
from pdnlp_tpu.models import bert, get_config
from pdnlp_tpu.serve import (
    DeadlineExceeded, DynamicBatcher, InferenceEngine, QueueFullError,
    pick_bucket, score_texts,
)
from pdnlp_tpu.train import checkpoint as ckpt
from pdnlp_tpu.utils.config import Args
from pdnlp_tpu.utils.metrics import Histogram

BUCKETS = (32, 64, 128)
TEXTS = ["天地人你我", "好坏大小上下来去" * 5, "爱恨喜怒哀乐" * 15,
         "高兴悲伤", "讨厌愤怒来去" * 8]


@pytest.fixture(scope="module")
def tok():
    return WordPieceTokenizer(build_vocab(TEXTS, size=128))


@pytest.fixture(scope="module")
def engine(tok):
    return InferenceEngine(Args(model="bert-tiny"), tokenizer=tok, mesh=None)


# ------------------------------------------------------------------ bucketing
def test_pick_bucket_smallest_covering():
    assert pick_bucket(1, BUCKETS) == 32
    assert pick_bucket(32, BUCKETS) == 32
    assert pick_bucket(33, BUCKETS) == 64
    assert pick_bucket(128, BUCKETS) == 128
    # beyond the largest bucket: encode already truncated, so top out
    assert pick_bucket(500, BUCKETS) == 128


def test_pad_ids_to_bucket_shapes_and_filler():
    batch = pad_ids_to_bucket([[2, 5, 6, 3], [2, 3]], seq_len=32, rows=8)
    assert batch["input_ids"].shape == (8, 32)
    assert batch["attention_mask"][0].sum() == 4
    assert batch["attention_mask"][1].sum() == 2
    np.testing.assert_array_equal(batch["example_weight"],
                                  [1, 1, 0, 0, 0, 0, 0, 0])
    with pytest.raises(ValueError):  # a bucket must cover its rows
        pad_ids_to_bucket([[1] * 40], seq_len=32)


def test_histogram_percentiles():
    h = Histogram(window=100)
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100 and h.min == 1.0 and h.max == 100.0
    assert abs(h.percentile(50) - 50.5) < 1.0
    assert h.percentile(99) > 95
    assert h.snapshot()["p50"] is not None


@pytest.mark.parametrize("blocks", [(3, 4), (7, 9, 5), (2, 25, 1), (0, 6)],
                         ids=["under-the-window", "across-its-edge",
                              "a-block-wider-than-it", "an-empty-block"])
def test_histogram_block_equals_one_by_one(blocks):
    """``observe_many`` of a block = ``observe`` of each value in turn:
    totals, extremes, and WHICH values the ring still holds."""
    one, many = Histogram(window=10), Histogram(window=10)
    v = 0.0
    for n in blocks:
        block = [v + 1.5 * k for k in range(n)][::-1]
        v += 100.0
        for x in block:
            one.observe(x)
        many.observe_many(block)
    assert (many.count, many.min, many.max) == (one.count, one.min, one.max)
    assert many.total == pytest.approx(one.total)
    assert many._recent == one._recent and many._pos == one._pos
    assert many.percentiles((50, 99)) == one.percentiles((50, 99))


# ------------------------------------------------------------------- batcher
def test_batcher_flushes_on_size(engine):
    # wait bound effectively infinite: only the size trigger can flush
    with DynamicBatcher(engine, buckets=BUCKETS, max_batch_size=2,
                        max_wait_ms=60_000) as b:
        futs = [b.submit(TEXTS[0]), b.submit(TEXTS[3])]
        outs = [f.result(timeout=30) for f in futs]
    assert all(o.shape == (engine.cfg.num_labels,) for o in outs)


def test_batcher_flushes_on_timeout(engine):
    # size bound unreachable: only the max_wait_ms trigger can flush
    with DynamicBatcher(engine, buckets=BUCKETS, max_batch_size=64,
                        max_wait_ms=30) as b:
        out = b.submit(TEXTS[0]).result(timeout=30)
    assert out.shape == (engine.cfg.num_labels,)


def test_batcher_full_queue_rejects_not_blocks(engine):
    # nothing can flush (size 64, wait 60s) -> the queue fills and the
    # N+1th submit must raise immediately instead of blocking
    b = DynamicBatcher(engine, buckets=BUCKETS, max_batch_size=64,
                       max_wait_ms=60_000, max_queue=3).start()
    try:
        for _ in range(3):
            b.submit(TEXTS[0])
        with pytest.raises(QueueFullError):
            b.submit(TEXTS[0])
        assert b.metrics.rejected_total.value == 1
    finally:
        b.stop(drain=False)


def test_batcher_deadline_expires_instead_of_stalling(engine):
    with DynamicBatcher(engine, buckets=BUCKETS, max_batch_size=64,
                        max_wait_ms=60_000) as b:
        fut = b.submit(TEXTS[0], deadline_ms=1.0)
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=30)
        assert b.metrics.deadline_expired_total.value >= 1


def test_text_longer_than_largest_bucket_truncates_not_crashes(engine, tok):
    """A bucket list topping out below max_seq_len is a valid config: rows
    must truncate to the largest bucket instead of failing their batch
    (which would poison co-batched requests) — both online and offline."""
    long_text = TEXTS[2]  # 90 chars -> ~92 tokens > bucket 64
    assert len(tok.encode_ids(long_text, 128)) > 64
    with DynamicBatcher(engine, buckets=(32, 64), max_batch_size=2,
                        max_wait_ms=20) as b:
        out = b.submit(long_text).result(timeout=30)
    assert out.shape == (engine.cfg.num_labels,)
    # raw pre-encoded ids over the largest bucket truncate too
    with DynamicBatcher(engine, buckets=(32, 64), max_batch_size=2,
                        max_wait_ms=20) as b:
        out = b.submit_ids(list(range(2, 100))).result(timeout=30)
    assert out.shape == (engine.cfg.num_labels,)
    preds, _ = score_texts(engine, [long_text], buckets=(32, 64),
                           batch_size=2)
    assert preds.shape == (1,)


def test_submit_before_start_raises(engine):
    b = DynamicBatcher(engine, buckets=BUCKETS)
    with pytest.raises(RuntimeError):
        b.submit(TEXTS[0])


def test_batcher_restarts_after_stop(engine):
    b = DynamicBatcher(engine, buckets=BUCKETS, max_batch_size=2,
                       max_wait_ms=20)
    b.start()
    assert b.submit(TEXTS[0]).result(timeout=30) is not None
    b.stop()
    b.start()  # stop() must not leave the batcher permanently dead
    try:
        assert b.submit(TEXTS[0]).result(timeout=30) is not None
    finally:
        b.stop()


# -------------------------------------------------------------- compile cache
def test_retrace_counter_flat_across_same_bucket_requests(tok):
    eng = InferenceEngine(Args(model="bert-tiny"), tokenizer=tok, mesh=None)
    eng.warmup(BUCKETS, rows=4)
    warm = eng.metrics.retraces.value
    assert warm == len(BUCKETS)  # one trace per bucket shape
    assert eng.metrics.cache_misses.value == len(BUCKETS)
    ids = tok.encode_ragged(TEXTS, 128)
    for seq in BUCKETS:
        for _ in range(3):
            eng.infer_ids([ids[0][:seq]], seq, rows=4)
    assert eng.metrics.retraces.value == warm  # ZERO post-warmup retraces
    assert eng.metrics.cache_hits.value == 3 * len(BUCKETS)


def test_checkpoint_swap_keeps_compiled_cache(tok, tmp_path):
    eng = InferenceEngine(Args(model="bert-tiny"), tokenizer=tok, mesh=None)
    eng.warmup((32,), rows=4)
    params = bert.init_params(jax.random.key(7),
                              get_config("bert-tiny",
                                         vocab_size=tok.vocab_size,
                                         num_labels=6))
    path = str(tmp_path / "swap-cls.msgpack")
    ckpt.save_params(path, {"params": params})
    # template-free inspection helper sees the raw tree
    raw = ckpt.load_raw(path)
    assert raw["embeddings"]["word"].shape == \
        params["embeddings"]["word"].shape
    warm = eng.metrics.retraces.value
    eng.load_checkpoint(path)
    eng.infer_ids([[tok.cls_id, tok.sep_id]], 32, rows=4)
    assert eng.metrics.retraces.value == warm  # weight swap != new trace


def test_load_checkpoint_rejects_wrong_model(tok, tmp_path):
    eng = InferenceEngine(Args(model="bert-tiny"), tokenizer=tok, mesh=None)
    small = bert.init_params(jax.random.key(0),
                             get_config("bert-tiny", vocab_size=8,
                                        num_labels=6))
    path = str(tmp_path / "wrong-cls.msgpack")
    ckpt.save_params(path, {"params": small})
    with pytest.raises(ValueError):
        eng.load_checkpoint(path)


# ------------------------------------------------------------ offline parity
def test_offline_scoring_matches_predict_path(tok, tmp_path, corpus_path):
    """The offline bucketed path and the predict_tpu.py path (single text,
    padded to max_seq_len through the same engine) agree on a saved
    checkpoint — the parity the serve rebase of predict_tpu.py promises."""
    import predict_tpu

    args = Args(model="bert-tiny", output_dir=str(tmp_path),
                data_path=corpus_path,
                vocab_path=str(tmp_path / "vocab.txt"))
    cfg = get_config("bert-tiny", vocab_size=tok.vocab_size, num_labels=6)
    params = bert.init_params(jax.random.key(3), cfg)
    ckpt.save_params(str(tmp_path / "single-cls.msgpack"), {"params": params})
    # predict path: routed through the serve engine since the rebase
    import pdnlp_tpu.data.tokenizer as tokenizer_mod

    tokenizer_mod.save_vocab(tok.vocab_list, args.vocab_path)
    preds = predict_tpu.main(args, text=TEXTS[2], true_label=3)
    assert list(preds) == ["single-cls.msgpack"]

    # offline path: same checkpoint, bucketed batch scoring
    eng = InferenceEngine(args, tokenizer=tok, mesh=None)
    eng.load_checkpoint(str(tmp_path / "single-cls.msgpack"))
    offline_preds, logits = score_texts(eng, TEXTS, buckets=BUCKETS,
                                        batch_size=4)
    assert logits.shape == (len(TEXTS), 6)
    assert int(offline_preds[2]) == preds["single-cls.msgpack"]
    # determinism: a second pass is bitwise identical
    again, logits2 = score_texts(eng, TEXTS, buckets=BUCKETS, batch_size=4)
    np.testing.assert_array_equal(logits, logits2)


def test_engine_mesh_matches_plain_jit(tok):
    """Sharded serving returns the same logits as single-device jit."""
    from pdnlp_tpu.parallel import make_mesh

    args = Args(model="bert-tiny")
    plain = InferenceEngine(args, tokenizer=tok, mesh=None)
    mesh = make_mesh()
    sharded = InferenceEngine(args, tokenizer=tok, mesh=mesh)
    assert sharded.rows_multiple == mesh.shape["data"]
    ids = tok.encode_ragged(TEXTS[:3], 64)
    a = plain.infer_ids(ids, 64, rows=8)
    b = sharded.infer_ids(ids, 64, rows=8)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
