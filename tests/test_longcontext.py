"""Long-context path tests (PR 12): multi-tile flash kernels with
block-sparse tile skip, multi-width packing with backfill, ring+packed
sequence parallelism, chunked-prefill serving, and the per-width routing
table.  Pallas runs in interpret mode on the CPU mesh — identical
numerics, no Mosaic."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pdnlp_tpu.data import Collator, WordPieceTokenizer, build_vocab
from pdnlp_tpu.data.collate import EncodedDataset
from pdnlp_tpu.data.packing import (
    MultiWidthPackedDataset, PackedClassificationDataset, pack_id_lists,
    segment_bias, segment_cap,
)
from pdnlp_tpu.data.sampler import (
    LengthGroupedSampler, validate_length_buckets,
)
from pdnlp_tpu.models import bert, get_config
from pdnlp_tpu.ops import attention as attn_mod
from pdnlp_tpu.ops import flash
from pdnlp_tpu.ops.attention import dot_product_attention, mask_bias, routed_impl
from pdnlp_tpu.utils.config import Args

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_segments(B, S, seed=0, pad=30):
    """[B, S] packed segment IDs with many short segments + padding tail."""
    r = np.random.RandomState(seed)
    seg = np.zeros((B, S), np.int32)
    for b in range(B):
        pos, sid = 0, 0
        while pos < S - pad:
            ln = r.randint(6, 28)
            sid += 1
            seg[b, pos: pos + ln] = sid
            pos += ln
    return seg


def restart_positions(seg):
    pos = np.zeros_like(seg)
    for b in range(seg.shape[0]):
        for sid in np.unique(seg[b][seg[b] > 0]):
            idx = np.flatnonzero(seg[b] == sid)
            pos[b, idx] = np.arange(len(idx))
    return pos


# ------------------------------------------------- multi-tile flash kernel


def test_flash_multitile_packed_parity_512():
    """fwd+bwd parity vs the XLA segment_bias oracle at a 4-tile width —
    with the block-sparse map actually skipping off-diagonal tiles."""
    S, B, N, D = 512, 1, 2, 32
    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(B, S, N, D), jnp.float32)
               for _ in range(3))
    seg = small_segments(B, S)
    segj = jnp.asarray(seg)
    live = float(np.asarray(flash.segment_block_map(segj)).mean())
    assert live < 1.0  # the skip is engaged, not vacuous

    ref = dot_product_attention(q, k, v, bias=jnp.asarray(segment_bias(seg)),
                                impl="xla")
    out = flash.flash_attention(q, k, v, segment_ids=segj)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def loss(f):
        return lambda q, k, v: (f(q, k, v) ** 2).sum()

    gr = jax.grad(loss(lambda q, k, v: dot_product_attention(
        q, k, v, bias=jnp.asarray(segment_bias(seg)), impl="xla")),
        argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss(lambda q, k, v: flash.flash_attention(
        q, k, v, segment_ids=segj)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gf):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-5,
                                   err_msg=f"d{name} diverged at 512")


def test_flash_multitile_dense_parity_with_filler_row():
    """Dense-mask path at a 2-tile width: padding k-tiles skip, an
    ALL-masked filler row keeps every tile (softmax-of-raw semantics)."""
    S, B, N, D = 256, 2, 2, 32
    r = np.random.RandomState(1)
    q, k, v = (jnp.asarray(r.randn(B, S, N, D), jnp.float32)
               for _ in range(3))
    mask = np.zeros((B, S), np.int32)
    mask[0, :100] = 1          # row 0: one live k-tile, one dead
    # row 1: all masked (zero-weight filler row)
    bias = mask_bias(jnp.asarray(mask))
    act = flash.bias_block_map(bias.reshape(B, 1, S), S // flash.BLOCK_Q)
    act = np.asarray(act)
    assert act[0].tolist() == [[1, 0], [1, 0]]   # dead padding tile skips
    assert act[1].min() == 1                     # filler row keeps all
    ref = dot_product_attention(q, k, v, bias, impl="xla")
    out = flash.flash_attention(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def loss(f):
        return lambda q, k, v: (f(q, k, v) ** 2).sum()

    gr = jax.grad(loss(lambda q, k, v: dot_product_attention(
        q, k, v, bias, impl="xla")), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss(lambda q, k, v: flash.flash_attention(
        q, k, v, bias=bias)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-5)


def test_segment_block_map_structure():
    """Tile map: diagonal live, disjoint-segment off-diagonal dead,
    padding-bearing q-tiles fully live (their rows need every tile)."""
    S = 512
    seg = np.zeros((1, S), np.int32)
    seg[0, 0:128] = 1       # tile 0: segment 1 exactly
    seg[0, 128:256] = 2     # tile 1: segment 2
    seg[0, 256:384] = 3     # tile 2: segment 3
    seg[0, 384:400] = 4     # tile 3: segment 4 + padding tail
    am = np.asarray(flash.segment_block_map(jnp.asarray(seg)))[0]
    assert am[0].tolist() == [1, 0, 0, 0]
    assert am[1].tolist() == [0, 1, 0, 0]
    assert am[2].tolist() == [0, 0, 1, 0]
    assert am[3].tolist() == [1, 1, 1, 1]  # has padding rows


def test_packed_classify_pallas_matches_xla_512():
    """End-to-end multi-tile packed forward: per-segment logits identical
    whether the mask is in-kernel (pallas, tiles skipped) or materialized
    (XLA)."""
    S, B = 512, 2
    cfg = get_config("bert-tiny-long", vocab_size=160)
    params = bert.init_params(jax.random.key(0), cfg)
    r = np.random.RandomState(2)
    seg = small_segments(B, S, seed=2)
    M = segment_cap(S, 8)
    cls = np.zeros((B, M), np.int32)
    lab = np.zeros((B, M), np.int32)
    w = np.zeros((B, M), np.float32)
    for b in range(B):
        for sid in range(1, M + 1):
            idx = np.flatnonzero(seg[b] == sid)
            if idx.size:
                cls[b, sid - 1] = idx[0]
                w[b, sid - 1] = 1.0
    batch = {
        "input_ids": jnp.asarray(r.randint(0, 160, (B, S)), jnp.int32),
        "token_type_ids": jnp.zeros((B, S), jnp.int32),
        "attention_mask": jnp.asarray((seg > 0).astype(np.int32)),
        "segment_ids": jnp.asarray(seg),
        "position_ids": jnp.asarray(restart_positions(seg)),
        "cls_positions": jnp.asarray(cls),
        "label": jnp.asarray(lab),
        "example_weight": jnp.asarray(w),
    }
    a = bert.classify(params, cfg, batch, attn_impl="xla")
    b = bert.classify(params, cfg, batch, attn_impl="pallas")
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-4)


# -------------------------------------------- multi-width packing + sampler


@pytest.fixture(scope="module")
def longdoc_setup():
    import random

    chars = "天地人你我他好坏大小上下来去爱恨喜怒哀乐"
    rng = random.Random(0)

    def mklen():
        p = rng.random()
        return (rng.randint(6, 110) if p < 0.7 else
                rng.randint(111, 240) if p < 0.9 else
                rng.randint(241, 500))

    data = [("".join(rng.choice(chars) for _ in range(mklen())),
             rng.randrange(6)) for _ in range(240)]
    tok = WordPieceTokenizer(build_vocab((t for t, _ in data), size=128))
    enc = EncodedDataset(data, tok, 512)
    return data, tok, enc


def test_multiwidth_covers_every_example_once_with_caps(longdoc_setup):
    _, _, enc = longdoc_setup
    mw = MultiWidthPackedDataset(enc, (128, 256, 512), max_segments=12)
    seen = sorted(i for g in mw.groups.values()
                  for row in g.source_rows for i in row)
    assert seen == list(range(len(enc)))
    lengths = enc.lengths()
    for w, g in mw.groups.items():
        segcounts = (g.arrays["example_weight"] > 0).sum(1)
        assert segcounts.max() <= segment_cap(w, 12)
        for row in g.source_rows:  # every row fits its width
            assert int(lengths[row].sum()) <= w
    # the widest group exists (the corpus has >240-token docs) and its
    # rows backfill above the no-backfill ceiling
    assert 512 in mw.groups
    assert mw.stats()["fill_ratio"] > 0.85


def test_multiwidth_assignment_is_smallest_covering_or_backfill(
        longdoc_setup):
    """A long doc may never land in a row narrower than its length, and
    backfill never OPENS rows: every row above the smallest width was
    seeded by a member that actually needs it (length past the previous
    width) — short docs only top up already-open rows."""
    _, _, enc = longdoc_setup
    widths = (128, 256, 512)
    mw = MultiWidthPackedDataset(enc, widths, max_segments=12)
    lengths = enc.lengths()
    for w, g in mw.groups.items():
        prev = max((x for x in widths if x < w), default=0)
        for row in g.source_rows:
            assert all(int(lengths[i]) <= w for i in row)
            # the seeding member: at least one doc the narrower widths
            # could not hold (the invariant that keeps fill/compile
            # structure — a regression letting backfill open wide rows
            # of short docs would fail here)
            assert max(int(lengths[i]) for i in row) > prev


def test_multiwidth_sampler_width_homogeneous_and_sharded(longdoc_setup):
    _, _, enc = longdoc_setup
    mw = MultiWidthPackedDataset(enc, (128, 256, 512), max_segments=12)
    table = mw.row_width_table()
    shard_rows = []
    for shard in range(2):
        s = LengthGroupedSampler(table, batch_size=4,
                                 buckets=mw.widths, num_shards=2,
                                 shard_id=shard, shuffle=True, seed=5)
        rows = []
        for chunk, width in s.chunks():
            # width-homogeneous batches of packed rows
            assert all(table[i] == width for i in chunk)
            rows.extend(chunk)
        shard_rows.append(rows)
    # the two shards partition the row space (pad-wrapping may duplicate)
    union = set(shard_rows[0]) | set(shard_rows[1])
    assert union == set(range(mw.n))
    # both shards see the same number of steps
    s0 = LengthGroupedSampler(table, batch_size=4, buckets=mw.widths,
                              num_shards=2, shard_id=0, seed=5)
    s1 = LengthGroupedSampler(table, batch_size=4, buckets=mw.widths,
                              num_shards=2, shard_id=1, seed=5)
    assert s0.batches_per_epoch == s1.batches_per_epoch


def test_packed_vs_unpacked_logit_parity_1024(longdoc_setup):
    """Multi-tile packed rows at 1024 (wider than the 512-position table —
    positions restart per segment) reproduce each example's own unpacked
    logits exactly."""
    data, tok, enc = longdoc_setup
    cfg = get_config("bert-tiny-long", vocab_size=tok.vocab_size)
    params = bert.init_params(jax.random.key(3), cfg)
    sub = list(range(24))
    packed = PackedClassificationDataset(enc, max_segments=segment_cap(
        1024, 8), width=1024, subset=sub)
    pb = packed.take(list(range(min(2, packed.n))))
    logits = bert.classify(params, cfg,
                           {k: jnp.asarray(v) for k, v in pb.items()},
                           attn_impl="xla")
    lengths = enc.lengths()
    for rrow, members in enumerate(packed.source_rows[:2]):
        for s, orig in enumerate(members):
            L = int(lengths[orig])
            single = enc.take([orig], seq_len=128 if L <= 128 else 512)
            ref = bert.classify(params, cfg,
                                {k: jnp.asarray(v)
                                 for k, v in single.items()},
                                attn_impl="xla")
            np.testing.assert_allclose(
                np.asarray(logits[rrow, s]), np.asarray(ref[0]), atol=2e-4)


def test_validate_length_buckets_loud_and_specific():
    with pytest.raises(ValueError) as e:
        validate_length_buckets((128, 1024), max_position=512,
                                model="bert-base", mode="bucket")
    msg = str(e.value)
    assert "1024" in msg and "512 positions" in msg \
        and "bert-base-long" in msg  # the fix is named
    # pack mode: wide rows are fine, the bound is the encode width
    validate_length_buckets((128, 1024), max_position=512,
                            model="bert-base", mode="pack", max_seq_len=512)
    with pytest.raises(ValueError, match="longest segment"):
        validate_length_buckets((128,), max_position=512,
                                model="bert-base", mode="pack",
                                max_seq_len=1024)


def test_loader_refuses_bucket_past_position_table(longdoc_setup):
    from pdnlp_tpu.train.setup import build_length_train_loader

    data, tok, enc = longdoc_setup
    col = Collator(tok, 512)
    args = Args(model="bert-tiny-long", max_seq_len=1024,
                length_mode="bucket", length_buckets="128,1024")
    with pytest.raises(ValueError, match="position table"):
        build_length_train_loader(args, data, col, enc, batch_size=4)


# --------------------------------------------------------- routing table


def test_routing_table_consults_measured_crossover(capsys):
    # the shipped table: dense long widths measured slower -> auto = xla
    assert routed_impl("auto", 512, segmented=False, backend="tpu") == "xla"
    # segmented has no entry: the static packed-on-TPU rule stands
    assert routed_impl("auto", 512, segmented=True, backend="tpu") \
        == "pallas"
    # explicit pallas never consults the table
    assert routed_impl("pallas", 512, segmented=False) == "pallas"
    # a measured-slower entry overrides auto WITH the distinguishing reason
    attn_mod._FALLBACK_WARNED.clear()
    attn_mod.ROUTING_TABLE[(256, True)] = "xla"
    try:
        assert routed_impl("auto", 256, segmented=True,
                           backend="tpu") == "xla"
        assert "measured slower" in capsys.readouterr().err
    finally:
        del attn_mod.ROUTING_TABLE[(256, True)]
    # a measured WIN routes pallas past the conservative static rule
    # (how a chip re-measure flips a dense width) — TPU only
    attn_mod.ROUTING_TABLE[(384, False)] = "pallas"
    try:
        assert routed_impl("auto", 384, segmented=False,
                           backend="tpu") == "pallas"
        assert routed_impl("auto", 384, segmented=False,
                           backend="cpu") == "xla"
    finally:
        del attn_mod.ROUTING_TABLE[(384, False)]
    attn_mod._FALLBACK_WARNED.clear()
    assert routed_impl("pallas", 96) == "xla"
    assert "does not tile" in capsys.readouterr().err


# ------------------------------------------------------- ring + packed sp


def test_ring_attention_packed_matches_segment_route(ndev):
    from pdnlp_tpu.ops.ring import ring_attention
    from pdnlp_tpu.parallel import make_mesh
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if ndev < 2:
        pytest.skip("needs >1 device for a seq axis")
    mesh = make_mesh(shape={"seq": min(4, ndev)})
    n = mesh.shape["seq"]
    B, S, N, D = 2, 16 * n, 2, 16
    r = np.random.RandomState(4)
    q, k, v = (jnp.asarray(r.randn(B, S, N, D), jnp.float32)
               for _ in range(3))
    seg = small_segments(B, S, seed=4, pad=8)
    segj = jnp.asarray(seg)
    ref = dot_product_attention(q, k, v, impl="xla", segment_ids=segj)
    out = jax.jit(shard_map(
        lambda q, k, v, s: ring_attention(q, k, v, None, axis_name="seq",
                                          segment_ids=s),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq"),
                  P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False))(q, k, v, segj)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_sp_packed_train_step_matches_single_device(ndev):
    from pdnlp_tpu.parallel import make_mesh
    from pdnlp_tpu.parallel.sp import make_sp_batch, make_sp_train_step
    from pdnlp_tpu.train.setup import setup_model
    from pdnlp_tpu.train.steps import make_train_step

    if ndev < 4:
        pytest.skip("needs a (data, seq) mesh")
    args = Args(model="bert-tiny", max_seq_len=64, dropout=0.0,
                attn_dropout=0.0, dtype="float32")
    cfg, tx, state = setup_model(args, vocab_size=100)
    B, S = 2, 64
    r = np.random.RandomState(5)
    lists = [list(r.randint(5, 99, r.randint(8, 30))) for _ in range(10)]
    pb, _ = pack_id_lists(lists, S, rows=B, max_segments=8)
    M = pb["cls_positions"].shape[1]
    pb = dict(pb)
    pb["label"] = r.randint(0, 6, (B, M)).astype(np.int32)
    w = np.zeros((B, M), np.float32)
    w[(pb["segment_ids"].max(1)[:, None]
       > np.arange(M)[None, :]).nonzero()] = 1.0
    pb["example_weight"] = w
    mesh = make_mesh(shape={"data": 2, "seq": 2})
    put = make_sp_batch(mesh)
    sp_step = make_sp_train_step(cfg, tx, args, mesh)(put(pb))
    single = jax.jit(make_train_step(cfg, tx, args))
    s1 = jax.tree_util.tree_map(jnp.copy, state)
    s2 = jax.tree_util.tree_map(jnp.copy, state)
    for _ in range(2):
        s1, m1 = sp_step(s1, put(pb))
        s2, m2 = single(s2, {k2: jnp.asarray(v2) for k2, v2 in pb.items()})
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-6
        assert abs(float(m1["accuracy"]) - float(m2["accuracy"])) < 2e-6


# ------------------------------------------------------- chunked prefill


@pytest.fixture(scope="module")
def long_serve():
    from pdnlp_tpu.serve.batcher import DynamicBatcher
    from pdnlp_tpu.serve.engine import InferenceEngine

    args = Args(model="bert-tiny-long", max_seq_len=512, dropout=0.0,
                attn_dropout=0.0, num_labels=6)
    eng = InferenceEngine(args)
    bat = DynamicBatcher(eng, buckets=(128,), max_batch_size=4,
                         max_wait_ms=10.0, max_queue=64, serve_pack="on",
                         pack_max_segments=8,
                         long_widths=(256, 512)).start()
    bat.warmup()
    yield eng, bat
    bat.stop()


def test_chunked_prefill_parity_with_whole_request(long_serve):
    eng, bat = long_serve
    r = np.random.RandomState(6)
    long_ids = [2] + list(r.randint(5, 90, 400)) + [3]
    mid_ids = [2] + list(r.randint(5, 90, 180)) + [3]
    shorts = [[2] + list(r.randint(5, 90, r.randint(3, 40))) + [3]
              for _ in range(8)]
    warm = eng.metrics.retraces.value
    futs = [bat.submit_ids(long_ids), bat.submit_ids(mid_ids)] \
        + [bat.submit_ids(s) for s in shorts]
    res = [f.result(timeout=60) for f in futs]
    assert eng.metrics.retraces.value == warm  # closed by warmup
    np.testing.assert_allclose(res[0], eng.infer_ids([long_ids], 512)[0],
                               atol=2e-5)
    np.testing.assert_allclose(res[1], eng.infer_ids([mid_ids], 256)[0],
                               atol=2e-5)
    assert all(x.shape == (6,) for x in res[2:])


def test_chunked_prefill_routing_and_truncation(long_serve):
    eng, bat = long_serve
    assert bat.max_request_tokens == 512
    # over the top width: tail-truncated, still served
    huge = [2] + list(range(5, 5 + 700))
    got = bat.submit_ids(huge).result(timeout=60)
    ref = eng.infer_ids([huge[:512]], 512)[0]
    np.testing.assert_allclose(got, ref, atol=2e-5)


def test_long_width_validation_is_loud():
    from pdnlp_tpu.serve.batcher import DynamicBatcher
    from pdnlp_tpu.serve.engine import InferenceEngine

    args = Args(model="bert-tiny-long", max_seq_len=512, dropout=0.0,
                attn_dropout=0.0, num_labels=6)
    eng = InferenceEngine(args)
    with pytest.raises(ValueError, match="position table"):
        DynamicBatcher(eng, buckets=(128,), serve_pack="on",
                       long_widths=(1024,))
    with pytest.raises(ValueError, match="128"):
        DynamicBatcher(eng, buckets=(128,), serve_pack="on",
                       long_widths=(200,))
    with pytest.raises(ValueError, match="packed path"):
        DynamicBatcher(eng, buckets=(128,), serve_pack="off",
                       long_widths=(256,))
