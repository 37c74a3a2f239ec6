"""Data pipeline tests: corpus, split determinism, tokenizer, collator,
sampler, loader."""
import numpy as np
import pytest

from pdnlp_tpu.data import (
    Collator,
    DataLoader,
    DistributedShardSampler,
    WordPieceTokenizer,
    build_vocab,
    load_data,
    split_data,
)
from pdnlp_tpu.data.tokenizer import SPECIALS, basic_tokenize, load_vocab, save_vocab


@pytest.fixture(scope="module")
def data(corpus_path):
    return load_data(corpus_path)


@pytest.fixture(scope="module")
def tok(data):
    vocab = build_vocab((t for t, _ in data), size=8000)
    return WordPieceTokenizer(vocab)


def test_load_data_strips_spaces(data):
    for text, label in data[:50]:
        assert " " not in text
        assert 0 <= label <= 5


def test_split_deterministic(data):
    tr1, dv1 = split_data(data, seed=123)
    tr2, dv2 = split_data(data, seed=123)
    assert tr1 == tr2 and dv1 == dv2
    # 92/8 ratio of the (limited) slice
    n = min(len(data), 10_000)
    assert len(tr1) == int(n * 0.92)
    assert len(tr1) + len(dv1) == n
    # different seed -> different order
    tr3, _ = split_data(data, seed=7)
    assert tr3 != tr1


def test_basic_tokenize_cjk_chars_isolated():
    assert basic_tokenize("我爱TPU!") == ["我", "爱", "tpu", "!"]
    assert basic_tokenize("hello,世界") == ["hello", ",", "世", "界"]


def test_vocab_roundtrip(tmp_path, tok):
    p = tmp_path / "vocab.txt"
    save_vocab(tok.vocab_list, str(p))
    assert load_vocab(str(p)) == tok.vocab_list
    assert tok.vocab_list[:5] == SPECIALS


def test_encode_shape_and_special_tokens(tok):
    ids, mask, types = tok.encode("我很高兴", max_len=16)
    assert len(ids) == len(mask) == len(types) == 16
    assert ids[0] == tok.cls_id
    n = sum(mask)
    assert ids[n - 1] == tok.sep_id
    assert all(i == tok.pad_id for i in ids[n:])


def test_encode_truncation(tok):
    long_text = "天" * 500
    ids, mask, _ = tok.encode(long_text, max_len=128)
    assert len(ids) == 128 and sum(mask) == 128
    assert ids[0] == tok.cls_id and ids[-1] == tok.sep_id


def test_oov_latin_decomposes(data):
    # A latin word unseen as a whole token must split into continuation
    # pieces whose characters are in the vocab — not collapse to [UNK].
    # The word's LETTERS have to be in the vocabulary for that: the real
    # corpus holds latin text, conftest's synthetic one holds none, so one
    # line of it is added to what the vocabulary is built from.
    tok = WordPieceTokenizer(build_vocab(
        [t for t, _ in data[:300]] + ["ok go look"], size=8000))
    word = "ok" * 8  # 'okokokok...' — certainly not a whole corpus token
    pieces = tok.tokenize(word)
    assert "[UNK]" not in pieces
    assert len(pieces) > 1
    assert all(p.lstrip("#") and (i == 0) == (not p.startswith("##"))
               for i, p in enumerate(pieces))
    assert tok.tokenize(word) == pieces  # deterministic


def test_vocab_coverage_on_corpus(data, tok):
    """The corpus-built vocab must cover the corpus itself: the OOV ([UNK])
    rate over a real slice must be tiny, else accuracy parity is hopeless."""
    total = unk = 0
    for text, _ in data[:500]:
        pieces = tok.tokenize(text)
        total += len(pieces)
        unk += sum(1 for p in pieces if p == "[UNK]")
    assert total > 0
    assert unk / total < 0.01, f"OOV rate {unk/total:.3%} too high"


def test_loader_propagates_collator_error(data, tok):
    class Boom(Collator):
        def __call__(self, examples, pad_to=0):
            raise RuntimeError("collate failed")

    loader = DataLoader(data[:64], Boom(tok, 16), batch_size=32, prefetch=2)
    with pytest.raises(RuntimeError, match="collate failed"):
        list(loader)


def test_loader_early_break_joins_worker(data, tok):
    import threading

    col = Collator(tok, max_seq_len=16)
    loader = DataLoader(data[:300], col, batch_size=16, prefetch=1)
    before = threading.active_count()
    for _ in range(3):
        it = iter(loader)
        next(it)
        it.close()  # early abandonment — generator finally must join worker
    assert threading.active_count() <= before


def test_loader_mid_epoch_break_tears_down_bounded(data, tok):
    """Regression: abandoning iteration mid-epoch must stop the worker in
    ONE bounded join — including the case where the worker is parked on
    the SENTINEL put (a full queue after the last batch), which the old
    unbounded ``q.put(_SENTINEL)`` + drain busy-spin could strand.

    Deflaked: no blind warm-up sleep.  ``_chunks`` resumes past its last
    yield only after the final batch's put has SUCCEEDED, so an event set
    there means the worker's next act is the sentinel put — the stranding
    state is reached by construction, not by hoping 0.3 s was enough under
    CPU contention.  (Old code fails either way: an unbounded sentinel put
    attempted after close() strands the thread and trips the count check.)"""
    import threading
    import time

    col = Collator(tok, max_seq_len=16)

    class ExhaustSignal(DataLoader):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.exhausted = threading.Event()

        def _chunks(self):
            yield from super()._chunks()
            self.exhausted.set()

    before = threading.active_count()
    # two batches, prefetch=1: after the consumer takes batch 0, the worker
    # queues batch 1 (full again) and parks on the sentinel put behind it
    loader = ExhaustSignal(data[:64], col, batch_size=32, prefetch=1)
    it = iter(loader)
    next(it)
    assert loader.exhausted.wait(timeout=30.0), "worker never exhausted"
    it.close()       # generator finally: stop + one bounded join
    deadline = time.monotonic() + 10.0
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.02)
    assert threading.active_count() <= before


def test_collator_batch_shapes(tok):
    col = Collator(tok, max_seq_len=32)
    batch = col([("我很高兴", 5), ("讨厌", 3)], pad_to=4)
    assert batch["input_ids"].shape == (4, 32)
    assert batch["input_ids"].dtype == np.int32
    assert batch["label"].tolist()[:2] == [5, 3]
    assert batch["example_weight"].tolist() == [1.0, 1.0, 0.0, 0.0]


def test_sampler_disjoint_cover():
    n = 103
    shards = [DistributedShardSampler(n, 4, i, seed=1) for i in range(4)]
    all_idx = np.concatenate([s.shard_indices() for s in shards])
    # padded to equal length per shard
    assert all(len(s) == 26 for s in shards)
    # every example covered
    assert set(all_idx.tolist()) == set(range(n))


def test_sampler_epoch_reshuffle():
    s = DistributedShardSampler(100, 2, 0, seed=1)
    a = s.shard_indices().copy()
    s.set_epoch(1)
    b = s.shard_indices().copy()
    assert not np.array_equal(a, b)
    s.set_epoch(0)
    assert np.array_equal(a, s.shard_indices())


def test_loader_static_shapes_and_counts(data, tok):
    col = Collator(tok, max_seq_len=16)
    loader = DataLoader(data[:70], col, batch_size=32, prefetch=2)
    batches = list(loader)
    assert len(batches) == len(loader) == 3
    for b in batches:
        assert b["input_ids"].shape == (32, 16)
    # total real examples preserved via weights
    assert sum(int(b["example_weight"].sum()) for b in batches) == 70


def test_loader_drop_last(data, tok):
    col = Collator(tok, max_seq_len=16)
    loader = DataLoader(data[:70], col, batch_size=32, drop_last=True, prefetch=0)
    assert len(list(loader)) == len(loader) == 2


def test_encoded_dataset_matches_collator(data, tok):
    """The cached-encoding fast path must be byte-identical to on-demand
    collation — EncodedDataset is an optimization, never a semantic."""
    from pdnlp_tpu.data import EncodedDataset

    subset = data[:100]
    col = Collator(tok, max_seq_len=32)
    enc = EncodedDataset(subset, tok, max_seq_len=32)
    idx = [5, 0, 99, 42]
    a = col([subset[i] for i in idx], pad_to=8)
    b = enc.take(idx, pad_to=8)
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_loader_encoded_equals_plain(data, tok):
    """A DataLoader with cached encodings yields the same batch stream."""
    from pdnlp_tpu.data import EncodedDataset

    subset = data[:70]
    col = Collator(tok, max_seq_len=32)
    sampler = lambda: DistributedShardSampler(len(subset), shuffle=True, seed=7)
    plain = DataLoader(subset, col, 16, sampler=sampler(), prefetch=0)
    cached = DataLoader(subset, col, 16, sampler=sampler(), prefetch=2,
                        encoded=EncodedDataset(subset, tok, max_seq_len=32))
    for epoch in range(2):
        plain.set_epoch(epoch)
        cached.set_epoch(epoch)
        for a, b in zip(plain, cached):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k], err_msg=k)
