"""MLM pretraining tests: packing geometry, segment isolation, device-side
masking statistics, a real (tiny) pretrain run, and the encoder warm-start
contract.  The reference has no pretraining to mirror (it downloads
``hfl/chinese-bert-wwm-ext``, ``/root/reference/single-gpu-cls.py:252``);
these tests define the in-repo replacement's behavior."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pdnlp_tpu.data.packing import pack_texts, segment_bias
from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
from pdnlp_tpu.train.pretrain import (
    PackedLoader, build_supervised_corpus, load_encoder, mask_tokens,
    run_pretrain, run_supervised_stage,
)
from pdnlp_tpu.utils.config import Args

TEXTS = ["今天天气真好", "我 很 高兴", "讨厌下雨", "伤心极了", "愤怒",
         "平常心", "喜欢喝茶", "开心一整天", "难过的一天", "无聊"]


@pytest.fixture(scope="module")
def tok():
    return WordPieceTokenizer(build_vocab(TEXTS * 3, min_freq=1))


# ---------------------------------------------------------------- packing

def test_pack_roundtrip_and_geometry(tok):
    packed = pack_texts(tok, TEXTS, max_seq_len=16)
    ids, segs = packed["input_ids"], packed["segment_ids"]
    assert ids.shape == segs.shape and ids.shape[1] == 16
    # every text appears exactly once: count [CLS] tokens
    assert (ids == tok.cls_id).sum() == len(TEXTS)
    # segments are 1-based consecutive within a row, 0 only on padding
    for row_ids, row_segs in zip(ids, segs):
        assert ((row_segs == 0) == (row_ids == tok.pad_id)).all()
        nz = row_segs[row_segs > 0]
        assert nz.min() == 1 and set(np.diff(nz)) <= {0, 1}
    # packing actually packs: strictly fewer rows than texts
    assert ids.shape[0] < len(TEXTS)


def test_pack_truncates_long_text(tok):
    long = "好" * 100
    packed = pack_texts(tok, [long], max_seq_len=16)
    row = packed["input_ids"][0]
    assert row[0] == tok.cls_id and tok.sep_id in row
    assert (packed["segment_ids"][0] > 0).sum() == 16  # exactly full


def test_segment_bias_blocks_cross_text_attention():
    seg = np.array([[1, 1, 2, 2, 0]])
    bias = segment_bias(seg)
    assert bias.shape == (1, 1, 5, 5)
    b = bias[0, 0]
    assert b[0, 1] == 0 and b[2, 3] == 0          # within-segment: visible
    assert b[0, 2] < -1e8 and b[1, 3] < -1e8       # cross-segment: masked
    assert b[0, 4] < -1e8 and b[4, 4] < -1e8       # padding: masked everywhere


def test_packed_encode_equals_separate_encode(tok):
    """A packed row must produce the same per-text hidden states as
    encoding each text alone (same positions, block-diagonal attention) —
    the correctness contract that lets packing claim 'free' throughput.

    Positions are absolute within the row, so the solo encodes are given
    the same position offsets via longer left-padding-free slices."""
    from pdnlp_tpu.models import bert, get_config

    cfg = get_config("bert-tiny", vocab_size=tok.vocab_size, num_labels=6)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)

    packed = pack_texts(tok, ["今天天气真好", "讨厌下雨"], max_seq_len=32)
    ids, segs = packed["input_ids"], packed["segment_ids"]
    assert ids.shape[0] == 1
    hidden = bert.encode(
        params, cfg, jnp.asarray(ids), jnp.zeros_like(ids),
        jnp.asarray((segs > 0).astype(np.int32)),
        attn_bias=jnp.asarray(segment_bias(segs)),
    )
    # solo encode of the SECOND text, placed at its packed offset
    start = int(np.argmax(segs[0] == 2))
    end = start + int((segs[0] == 2).sum())
    solo = np.zeros_like(ids)
    solo[0, start:end] = ids[0, start:end]
    mask = (solo > 0).astype(np.int32)
    seg_solo = np.where(solo > 0, 1, 0)
    h_solo = bert.encode(
        params, cfg, jnp.asarray(solo), jnp.zeros_like(solo),
        jnp.asarray(mask), attn_bias=jnp.asarray(segment_bias(seg_solo)),
    )
    np.testing.assert_allclose(
        np.asarray(hidden)[0, start:end], np.asarray(h_solo)[0, start:end],
        rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- masking

def test_mask_tokens_statistics(tok):
    rng = jax.random.PRNGKey(0)
    ids = jnp.full((64, 128), 100, jnp.int32)  # all real tokens
    mask_id = tok.vocab["[MASK]"]
    corrupted, labels, w = mask_tokens(rng, ids, mask_id, tok.vocab_size)
    sel = np.asarray(w) > 0
    frac = sel.mean()
    assert 0.12 < frac < 0.18                    # ~15% selected
    c = np.asarray(corrupted)[sel]
    assert 0.75 < (c == mask_id).mean() < 0.85   # ~80% -> [MASK]
    assert 0.05 < (c == 100).mean() < 0.15       # ~10% kept
    # labels echo the originals everywhere
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(ids))
    # unselected positions are untouched
    np.testing.assert_array_equal(np.asarray(corrupted)[~sel],
                                  np.asarray(ids)[~sel])


def test_mask_tokens_never_touches_specials(tok):
    rng = jax.random.PRNGKey(1)
    ids = jnp.asarray(np.tile(np.array([0, 1, 2, 3, 4], np.int32), (8, 20)))
    corrupted, _, w = mask_tokens(rng, ids, tok.vocab["[MASK]"], tok.vocab_size)
    assert float(jnp.sum(w)) == 0.0
    np.testing.assert_array_equal(np.asarray(corrupted), np.asarray(ids))


# ----------------------------------------------------------- end-to-end

def test_pretrain_then_finetune_warmstart(tmp_path, ndev, capsys,
                                          corpus_path):
    """Tiny real pretrain run: loss decreases, checkpoint written, encoder
    loads into a fine-tune model with classifier left fresh, and the
    fine-tune entry (setup_sharded_model with init_from) accepts it."""
    args = Args(strategy="pretrain", model="bert-tiny", max_seq_len=32,
                train_batch_size=8, epochs=3, learning_rate=1e-3,
                pretrain_limit=300, output_dir=str(tmp_path),
                data_path=corpus_path,
                vocab_path=str(tmp_path / "vocab.txt"),
                log_every=10 ** 9, dropout=0.0, attn_dropout=0.0)
    path = run_pretrain(args)

    # training must actually LEARN, not just produce a well-shaped file
    import re

    losses = [float(x) for x in re.findall(
        r"\[pretrain\] epoch \d+/\d+ loss ([0-9.]+)", capsys.readouterr().out)]
    assert len(losses) >= 2 and losses[-1] < losses[0], losses

    from pdnlp_tpu.parallel import make_mesh, setup_sharded_model
    from pdnlp_tpu.data.tokenizer import get_or_build_vocab

    vocab_size = len(get_or_build_vocab(args))
    ft_args = Args(model="bert-tiny", max_seq_len=32, init_from=path,
                   output_dir=str(tmp_path), data_path=corpus_path,
                   vocab_path=args.vocab_path, dropout=0.0, attn_dropout=0.0)
    mesh = make_mesh()
    cfg, tx, state, shardings = setup_sharded_model(ft_args, vocab_size, mesh, "dp")
    # warm-started encoder == pretrained encoder
    restored = load_encoder(path, state["params"])
    np.testing.assert_array_equal(
        np.asarray(state["params"]["layers"]["q"]["kernel"]),
        np.asarray(restored["layers"]["q"]["kernel"]))
    assert "mlm" not in state["params"]

    # ZeRO placement works too (leaves land sharded)
    cfg, tx, zstate, zsh = setup_sharded_model(ft_args, vocab_size, mesh, "zero")
    np.testing.assert_allclose(
        np.asarray(zstate["params"]["layers"]["q"]["kernel"]),
        np.asarray(state["params"]["layers"]["q"]["kernel"]), rtol=0, atol=0)


def test_supervised_corpus_is_disjoint_from_the_protocol_split(corpus_path):
    """The supervised stage trains only on labeled examples OUTSIDE the
    reference's [:10000] slice (the first two thirds of conftest's synthetic
    corpus where the real one is absent), with dev-duplicate texts dropped
    — no label of any dev text is ever seen."""
    from pdnlp_tpu.data.corpus import load_data, split_data

    data = load_data(corpus_path)
    real = len(data) > 35_000
    args = Args(data_path=corpus_path) if real else \
        Args(data_path=corpus_path, data_limit=2 * len(data) // 3)
    ext = build_supervised_corpus(args)
    train, dev = split_data(data, seed=args.seed, limit=args.data_limit,
                            ratio=args.ratio)
    dev_texts = {t for t, _ in dev}
    # the slice is actually used
    assert len(ext) > (25_000 if real else len(data) // 4)
    assert not any(t in dev_texts for t, _ in ext)  # zero dev leakage
    # exactly the post-slice examples minus dev-duplicate texts, in order
    expected = [(t, l) for t, l in data[args.data_limit:] if t not in dev_texts]
    assert ext == expected


def test_supervised_stage_trains_and_head_restores(tmp_path, ndev,
                                                   corpus_path):
    """Tiny real supervised stage: checkpoint carries pooler+classifier,
    --init_head restores them bit-exactly, and head=True on an MLM-only
    checkpoint fails loudly."""
    common = dict(model="bert-tiny", max_seq_len=32, data_limit=400,
                  output_dir=str(tmp_path), data_path=corpus_path,
                  vocab_path=str(tmp_path / "vocab.txt"),
                  log_every=10 ** 9,
                  dropout=0.0, attn_dropout=0.0)
    mlm_path = run_pretrain(Args(strategy="pretrain", train_batch_size=8,
                                 epochs=1, learning_rate=1e-3,
                                 pretrain_limit=200,
                                 ckpt_name="mlm.msgpack", **common))
    sft_path = run_supervised_stage(Args(
        strategy="sft", train_batch_size=8, epochs=1, pretrain_limit=200,
        init_from=mlm_path, lr_schedule="warmup_linear",
        ckpt_name="pretrained.msgpack", **common))

    from pdnlp_tpu.data.tokenizer import get_or_build_vocab
    from pdnlp_tpu.parallel import make_mesh, setup_sharded_model

    vocab_size = len(get_or_build_vocab(Args(**common)))
    mesh = make_mesh()
    ft = Args(init_from=sft_path, init_head=True, **common)
    cfg, tx, state, _ = setup_sharded_model(ft, vocab_size, mesh, "dp")

    import flax.serialization as ser

    with open(sft_path, "rb") as f:
        saved = ser.msgpack_restore(f.read())
    for tree in ("pooler", "classifier"):
        assert tree in saved
        np.testing.assert_array_equal(
            np.asarray(state["params"][tree]["kernel"]),
            np.asarray(saved[tree]["kernel"]))
    # trunk came through the stage too (sft continued from the MLM encoder)
    np.testing.assert_array_equal(
        np.asarray(state["params"]["embeddings"]["word"]),
        np.asarray(saved["embeddings"]["word"]))

    # default (trunk-only) load leaves the head fresh: classifier differs
    ft_fresh = Args(init_from=sft_path, **common)
    _, _, fresh_state, _ = setup_sharded_model(ft_fresh, vocab_size, mesh, "dp")
    assert not np.array_equal(
        np.asarray(fresh_state["params"]["classifier"]["kernel"]),
        np.asarray(saved["classifier"]["kernel"]))

    # MLM checkpoints carry no classifier: head=True must fail loudly
    with pytest.raises(ValueError, match="init_head"):
        load_encoder(mlm_path, state["params"], head=True)


def test_packed_loader_epochs_differ():
    packed = {"input_ids": np.arange(40)[:, None].repeat(4, 1).astype(np.int32),
              "segment_ids": np.ones((40, 4), np.int32)}
    loader = PackedLoader(packed, batch_size=8)
    assert len(loader) == 5
    loader.set_epoch(0)
    first = np.concatenate([b["input_ids"][:, 0] for b in loader])
    loader.set_epoch(1)
    second = np.concatenate([b["input_ids"][:, 0] for b in loader])
    assert not np.array_equal(first, second)
    assert set(first) == set(range(40))
