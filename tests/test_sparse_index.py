"""The latent family's LEARNED SPARSE attention (``models/latent_moe``'s
indexer: ``index_project`` / ``index_scores`` / ``select_mask`` /
``select_picks``) and the preset that runs it (``glm52-share-tiny``: layers
``full, shared, shared, full, shared``, an indexer of 4 heads of 16 that
picks 16 positions, 8 experts of which 4 are held), against the plain
float32 reference (``tests/glm52_reference.py``, held to the benchmark's
copy by a test), on the CPU with seeded random weights.

The served comparisons are ``tests/test_latent_moe.py``'s, with its
tolerances (``LOGIT_TOL`` 5e-3, near-tie routing below ``SWAP_MARGIN``
excluded and limited).  The indexer alone:

- ``SCORE_TOL`` (2e-3): both sides compute ``I`` in float32 from the same
  bfloat16-rounded leaves; what is left is the CPU matmul's default
  precision over sums of 4 heads x 16 values of order 10 (measured: 2e-5).
  The PICKS are compared as sets, equal: at this size the 16th and 17th
  score of a row lie 0.1 or more apart.
"""
import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import glm52_reference as ref
import test_latent_moe as base
from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
from pdnlp_tpu.models import families, get_config
from pdnlp_tpu.models import latent_moe as lm
from pdnlp_tpu.serve import PagedDecodeEngine
from pdnlp_tpu.utils.config import Args

MODEL = "glm52-share-tiny"
SEED = base.SEED
SCORE_TOL = 2e-3
HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def tok():
    return WordPieceTokenizer(build_vocab(
        ["天地人你我", "好坏大小上下来去" * 5, "爱恨喜怒哀乐" * 15], size=128))


@pytest.fixture(scope="module")
def model():
    cfg = get_config(MODEL)
    sizes = base.sizes_of(cfg)
    params, head = base.program_weights(SEED, sizes, ref=ref)
    return cfg, sizes, params, head


def reference_layer(sizes, l):
    return ref._f32(ref.layer_weights(ref.seed_key(SEED), sizes, l))


# (a) one full layer's I and S_t ----------------------------------------------

@pytest.mark.parametrize("T", [48, 96])
def test_a_full_layers_scores_and_picks_match_the_reference(model, T):
    """Prompts well past ``index_topk`` 16: the program's ``I`` (its rotary
    columns de-interleaved) is the reference's, and the positions picked
    are the same, query by query."""
    cfg, sizes, params, _ = model
    a = jax.random.normal(jax.random.key(T), (1, T, cfg.hidden_size))
    cq = jax.random.normal(jax.random.key(T + 1), (1, T, cfg.q_lora_rank))
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    for at, l in enumerate(cfg.full_layers):
        ip = jax.tree_util.tree_map(lambda w: w[at], params["indexer"])
        qI, kI, w = lm.index_project(a, cq, ip, cfg, pos, jnp.float32)
        got = lm.index_scores(qI, w, kI)[0]
        want = ref.index_scores(*ref.index_project(
            a[0], cq[0], reference_layer(sizes, l)["indexer"], sizes, "f32"),
            "f32")
        vis = np.tril(np.ones((T, T), bool))
        np.testing.assert_allclose(np.asarray(got)[vis], np.asarray(want)[vis],
                                   atol=SCORE_TOL)
        mask = np.asarray(lm.index_mask(qI, w, kI, pos, cfg)[0])
        picks = np.asarray(ref.pick(want, 0, cfg.index_topk))
        np.testing.assert_array_equal(mask, picks)
        assert (mask.sum(-1) == np.minimum(np.arange(T) + 1, 16)).all()
        # ... and as the decode step takes them: positions, one query a row
        at_pos, ok = lm.select_picks(got, jnp.asarray(vis), cfg.index_topk)
        for t in (0, 15, 16, T - 1):
            assert sorted(np.asarray(at_pos[t])[np.asarray(ok[t])]) \
                == list(np.flatnonzero(picks[t]))


def test_while_every_position_is_picked_the_layer_is_the_dense_path(model):
    """``index_topk`` at or above the prompt: every visible position is
    picked, and the logits are those of the same weights without an
    indexer (today's dense path)."""
    cfg, _, params, head = model
    ids = jax.random.randint(jax.random.key(2), (1, 48), 5, cfg.vocab_size)
    args = (ids, jnp.ones_like(ids), jnp.asarray([47]))
    wide = cfg.replace(index_topk=48)
    got, load, _ = lm.prefill(params, head, wide, *args, dtype=jnp.float32)
    dense = cfg.replace(index_n_heads=0, indexer_types=())
    plain = {k: v for k, v in params.items() if k != "indexer"}
    want, _, _ = lm.prefill(plain, head, dense, *args, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert int(load[2]) == int(load[3]) == 48 * 49 // 2
    # and it is NOT the dense path once the selection binds
    sparse, _, _ = lm.prefill(params, head, cfg, *args, dtype=jnp.float32)
    assert np.abs(np.asarray(sparse) - np.asarray(want)).max() > 0.05


# (b) a shared layer -----------------------------------------------------------

def test_a_shared_layer_holds_no_indexer_and_uses_the_last_full_layers_picks(
        model):
    cfg, sizes, params, head = model
    shapes = lm.param_shapes(cfg)
    assert cfg.full_layers == (0, 3) and cfg.num_index_layers == 2
    assert {k: v[0] for k, v in shapes["indexer"].items()} == {
        k: 2 for k in ("iq", "ik", "ik_norm", "ik_bias", "iw")}
    for part in ("dense", "moe"):
        assert not [k for k in shapes[part] if k.startswith("i")], part
    key = ref.seed_key(SEED)
    for l, kind in enumerate(cfg.indexer_types):
        assert ("indexer" in ref.layer_weights(key, sizes, l)) \
            == (kind == "full")
    # the reference hands a full layer's picks on, and a shared layer
    # returns the picks it was handed
    h = jax.random.normal(jax.random.key(4), (40, cfg.hidden_size))
    w0, w1 = (ref.layer_weights(key, sizes, l) for l in (0, 1))
    h1, _, picks = ref.layer(h, w0, sizes, True, ref.held_of(sizes), "f32")
    h2, _, again = ref.layer(h1, w1, sizes, False, ref.held_of(sizes), "f32",
                             picks)
    np.testing.assert_array_equal(np.asarray(picks), np.asarray(again))
    # the program does the same: layer 1 under layer 0's picks is the
    # reference's, under the most recent 16 it is not
    recent = ref.pick(jnp.zeros((40, 40)), 0, cfg.index_topk, "recent")
    other, _, _ = ref.layer(h1, w1, sizes, False, ref.held_of(sizes), "f32",
                            recent)
    two = dataclasses.replace(cfg, num_layers=2,
                              indexer_types=("full", "shared"))
    p2 = {**params,
          "moe": jax.tree_util.tree_map(lambda w: w[:1], params["moe"]),
          "indexer": jax.tree_util.tree_map(lambda w: w[:1],
                                            params["indexer"])}

    def residual(p):
        # the two layers on ``h`` itself: the embedding's place is taken
        positions = jnp.arange(40, dtype=jnp.int32)[None]

        def attend(l, mask, q_nope, q_rope, latent, ap, index=None):
            if index is not None:
                qI, kI, w = index
                mask = lm.index_mask(qI, w, kI, positions, two)
            o = lm.attend_expanded(q_nope, q_rope, latent, ap, two, positions,
                                   jnp.float32, causal_cut=True, mask=mask)
            return o, mask

        x, _, _ = lm._run_layers(
            p, two, h[None], positions, jnp.ones((1, 40), bool), attend,
            jnp.zeros((1, 40, 40), bool), jnp.float32)
        return x[0]

    got = residual(p2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(h2), atol=2e-3)
    assert np.abs(np.asarray(other) - np.asarray(h2)).max() > 0.05


# (c), (d) served through the engine's pages ----------------------------------

@pytest.fixture(scope="module")
def served(tok):
    # "grows": a cold prompt of 9 tokens whose 12 new tokens carry it past
    # index_topk 16 WHILE decoding
    return base.serve_three(tok, ref=ref, model=MODEL, extra=(("grows", 9),))


@pytest.mark.parametrize("label, first_from, least", [
    ("cold", "prefill_ids", 9), ("prefix_hit", "prefill_chunk", 9),
    ("cow", None, 8), ("grows", "prefill_ids", 9)])
def test_served_logits_match_the_reference(served, label, first_from, least):
    """(c) a cold prompt prefilled, then decoded through the pages — latents
    and index keys through ONE table into two pools of different layer
    counts; (d) the chunk after a prefix hit (the hit's pages carry its
    index keys) and decoding after a full hit's copy on write; and a stream
    that passes ``index_topk`` while decoding."""
    prompt, emitted, slot, rows = served[label][:4]
    assert len(emitted) == 10
    if label == "grows":
        assert len(prompt) < 16 < len(prompt) + len(emitted)
    if first_from:
        assert rows[0][0] == first_from
    assert base.check_against_reference(
        served["sizes"], prompt, emitted, slot, rows, first_from,
        ref=ref) >= least


def test_prefix_sharing_stays_on_and_nothing_leaks(served):
    assert served["prefix"]["hits_partial"] >= 1
    assert served["prefix"]["hits_full"] >= 1
    assert served["cow"][4]["cow_copies"] >= 1
    assert served["cow"][1] == served["cold"][1]
    assert served["leak"]["ok"], served["leak"]


# (j) the counts of the selection ---------------------------------------------

def test_positions_visible_and_picked_against_a_hand_count(served):
    """Every fetch leaf says what its launch's real queries saw and picked
    (of the FIRST full layer); the engine's totals by program are their
    sums; the decode step reads ``index_topk`` latents a row, never the
    rung."""
    recs = served["records"]
    k = get_config(MODEL).index_topk

    def leaves(name):
        return [r["attrs"] for r in recs if r["name"] == name
                and "positions_visible" in r["attrs"]]

    # a cold prompt of n tokens: sum of t + 1 seen, min(16, t + 1) picked
    prompts = {len(served[label][0]) for label in ("cold", "grows")}
    real = [a for a in leaves("prefill.fetch") if a["positions_picked"]]
    assert {a["positions_visible"] for a in real} >= {
        n * (n + 1) // 2 for n in prompts}
    for a in real:
        n = int(round((2 * a["positions_visible"]) ** 0.5))
        assert a["positions_visible"] == n * (n + 1) // 2
        assert a["positions_picked"] == sum(min(k, t + 1) for t in range(n))
    # the chunk after the hit: 13 tokens at positions 32 .. 44
    chunk = [a for a in leaves("chunk.fetch") if a["positions_picked"]]
    assert [(a["positions_visible"], a["positions_picked"]) for a in chunk] \
        == [(sum(range(33, 46)), 13 * k)]
    # a decode step of ONE live stream at position p: p + 1 seen
    steps = [a for a in leaves("decode.fetch") if a["positions_visible"]]
    assert steps
    for a in steps:
        assert a["positions_picked"] == min(k, a["positions_visible"])
    totals = served["picks"]
    for leaf, name in (("prefill", "prefill.fetch"), ("chunk", "chunk.fetch"),
                       ("decode", "decode.fetch")):
        assert list(totals[leaf]) == [
            sum(a["positions_visible"] for a in leaves(name)),
            sum(a["positions_picked"] for a in leaves(name))]
    assert served["kv"]["positions_seen"]["decode"] == list(totals["decode"])
    launched = [r["attrs"] for r in recs if r["name"] == "decode.dispatch"]
    assert launched and all(
        a["kv_positions_read"] == a["rows"] * k for a in launched)


# (e) the shares add up --------------------------------------------------------

def test_two_processes_shares_add_up_to_the_whole_layer(model):
    """The 8 experts split over two processes (``expert_first`` 0 and 4),
    the shared expert counted once, give the reference's whole layer."""
    cfg, sizes = model[:2]
    E = cfg.n_routed_experts
    key = ref.seed_key(SEED)
    f = jax.random.normal(jax.random.key(3), (50, cfg.hidden_size))
    whole = ref.layer_weights(key, sizes, 1, held=(0, E))
    want, _ = ref.expert_layer(f, ref._f32(whole), sizes, (0, E), "f32")
    total = ref._gated(f, ref._f32(whole["shared"]), "f32")
    counts = []
    for first in (0, 4):
        w = ref.layer_weights(key, sizes, 1, held=(first, 4))
        share = cfg.replace(expert_first=first, experts_held=4)
        idx, gates, _ = lm.route(f, w["router"], share, jnp.float32,
                                 w["router_bias"])
        part, n = lm.held_experts(f, idx, gates, jnp.ones((50,), bool),
                                  base.stacked(w["experts"]), 0, share,
                                  jnp.float32)
        total = total + part
        counts.append(np.asarray(n))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-3)
    assert int(np.sum(counts)) == 50 * cfg.num_experts_per_tok


# (f) ties, and the rotary convention -----------------------------------------

def test_a_tie_goes_to_the_lower_position_on_both_sides():
    scores = jnp.asarray([[1.0, 3.0, 3.0, 0.5, 3.0, 3.0, -0.0, 0.0, 2.0, 3.0]])
    vis = jnp.ones((1, 10), bool)
    for k, want in ((3, [1, 2, 4]), (4, [1, 2, 4, 5]), (6, [1, 2, 4, 5, 8, 9]),
                    (8, [0, 1, 2, 3, 4, 5, 8, 9]),
                    (9, [0, 1, 2, 3, 4, 5, 6, 8, 9])):
        mask = np.asarray(lm.select_mask(scores, vis, k))[0]
        assert list(np.flatnonzero(mask)) == want, k
        pos, ok = lm.select_picks(scores, vis, k)
        assert sorted(np.asarray(pos[0])) == want and bool(ok.all()), k
        theirs = np.asarray(ref.pick(jnp.tile(scores, (10, 1)), 0, k))[9]
        assert list(np.flatnonzero(theirs)) == want, k
    # fewer visible than k: all of them, and no other
    vis = jnp.arange(10)[None] < 3
    assert list(np.flatnonzero(np.asarray(
        lm.select_mask(scores, vis, 5))[0])) == [0, 1, 2]
    pos, ok = lm.select_picks(scores, vis, 5)
    assert sorted(np.asarray(pos[0])[np.asarray(ok[0])]) == [0, 1, 2]
    # negative scores, a whole row of one value, k at the row's length
    neg = jnp.asarray([[-3.0, -1.0, -2.0, -1.0, -5.0]])
    assert list(np.flatnonzero(np.asarray(
        lm.select_mask(neg, jnp.ones((1, 5), bool), 2))[0])) == [1, 3]
    flat = jnp.zeros((1, 7))
    assert list(np.flatnonzero(np.asarray(
        lm.select_mask(flat, jnp.ones((1, 7), bool), 3))[0])) == [0, 1, 2]
    assert bool(lm.select_mask(flat, jnp.ones((1, 7), bool), 7).all())


def test_select_mask_is_the_sorts_pick_on_random_rows():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 300)).astype(np.float32)
    x[:, ::7] = np.round(x[:, ::7])           # many exact ties, some zeros
    vis = np.tril(np.ones((300, 300), bool))[236:]
    for k in (1, 17, 128, 299):
        got = np.asarray(lm.select_mask(jnp.asarray(x), jnp.asarray(vis), k))
        want = np.asarray(ref.pick(jnp.asarray(x), 236, k))
        np.testing.assert_array_equal(got, want)


def test_rope_factor_one_is_the_plain_table_and_scale():
    cfg = get_config("glm-5.2-ep16-share")
    d = cfg.qk_rope_head_dim
    want = (cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
            ).astype(np.float32)
    np.testing.assert_array_equal(lm.yarn_inv_freq(cfg), want)
    assert lm.softmax_scale(cfg) == 256 ** -0.5
    cos, sin = lm._rope_tables(cfg, jnp.asarray([[0, 5]]))
    np.testing.assert_allclose(np.asarray(cos[0, 1]), np.cos(5 * want),
                               rtol=1e-6)
    np.testing.assert_array_equal(ref.inv_freq(base.sizes_of(cfg)), want)
    assert ref.softmax_scale(base.sizes_of(cfg)) == 256 ** -0.5


def test_the_interleaved_convention_equals_the_programs_on_permuted_columns(
        model):
    """Rotating the pairs ``(x[2i], x[2i+1])`` and THEN de-interleaving is
    rotating the pairs ``(x[i], x[i + d/2])`` of the de-interleaved vector:
    so weights whose rotary columns are de-interleaved give every dot the
    reference's value (``program_layout``)."""
    cfg, sizes = model[:2]
    d, order = cfg.qk_rope_head_dim, ref._halves(cfg.qk_rope_head_dim)
    x = jax.random.normal(jax.random.key(1), (12, 3, d))
    theirs = ref._rope(x, sizes)[..., order]
    cos, sin = lm._rope_tables(cfg, jnp.arange(12)[None])
    mine = lm._rope(x[None][..., order], cos[:, :, None], sin[:, :, None])[0]
    np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs),
                               atol=1e-6)
    # and of the layout itself: the rotary columns alone move
    w = ref.layer_weights(ref.seed_key(SEED), sizes, 0)
    p = ref.program_layout(w, sizes)
    kr = cfg.kv_lora_rank
    np.testing.assert_array_equal(np.asarray(p["attn"]["kv_a"][:, :kr]),
                                  np.asarray(w["attn"]["kv_a"][:, :kr]))
    np.testing.assert_array_equal(
        np.asarray(p["attn"]["kv_a"][:, kr:]),
        np.asarray(w["attn"]["kv_a"][:, kr:][:, order]))
    np.testing.assert_array_equal(
        np.asarray(p["indexer"]["iq"].reshape(-1, cfg.index_n_heads,
                                              cfg.index_head_dim)[..., d:]),
        np.asarray(w["indexer"]["iq"].reshape(-1, cfg.index_n_heads,
                                              cfg.index_head_dim)[..., d:]))
    assert p["attn"]["q_b_nope"] is w["attn"]["q_b_nope"]


# (h) the older configurations' programs are the parent's ---------------------

def _primitives(jaxpr, out):
    for e in jaxpr.eqns:
        out.append(e.primitive.name)
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else (v,)):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    _primitives(j, out)
    return out


_ENGINES = {}


def _older_engine(tok, name):
    """One engine a preset for the two programs' cases."""
    if name not in _ENGINES:
        _ENGINES[name] = PagedDecodeEngine(
            Args(model=name, decode_slots=4, decode_max_len=64,
                 max_seq_len=64, dtype="float32"),
            tokenizer=tok, mesh=None, buckets=(16,), page_sz=16)
    return _ENGINES[name]


@pytest.mark.parametrize("program", ["_prefill_fn", "_pdecode_fn"])
@pytest.mark.parametrize("name", ["ax-k1-share-tiny", "xing4-stage-tiny"])
def test_the_older_presets_programs_and_pools_are_the_parents(tok, name,
                                                              program):
    """``tests/data/latent_programs_parent.json`` was written on the parent
    commit (PR 42): the programs' operands, outputs and their primitives in
    order, the pools' shapes and ``token_bytes``.  The indexer adds no
    operand, no output and no operation to a preset without one."""
    with open(os.path.join(HERE, "data", "latent_programs_parent.json")) as f:
        want = json.load(f)[name]
    eng = _older_engine(tok, name)
    assert eng.token_bytes == want["token_bytes"]
    assert [list(p.shape) for p in eng._pools] == want["pools"]
    assert eng.kv_snapshot()["index_bytes_a_token"] == 0
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    pools = tuple(jax.ShapeDtypeStruct(p.shape, p.dtype) for p in eng._pools)
    rows = eng.prefill_rows
    if program == "_prefill_fn":
        jaxpr = jax.make_jaxpr(eng._jit_prefill)(
            eng.params, eng.head, i32(rows, 16), i32(rows, 16), i32(rows))
    else:
        jaxpr = jax.make_jaxpr(eng._jit_pdecode)(
            eng.params, eng.head, pools, i32(4, 1), i32(4, 2), i32(4), ())
    seq = _primitives(jaxpr.jaxpr, [])
    assert len(jaxpr.in_avals) == want[program]["in"]
    assert [list(a.shape) for a in jaxpr.out_avals] == want[program]["out"]
    assert len(seq) == want[program]["equations"]
    assert hashlib.sha256("\n".join(seq).encode()).hexdigest() \
        == want[program]["sha256"]
    assert "dsa." not in str(jaxpr)


def test_the_indexers_programs_name_their_scopes(model):
    cfg, _, params, head = model
    ids = jnp.zeros((1, 32), jnp.int32)
    text = jax.jit(lambda p, h: lm.prefill(
        p, h, cfg, ids, jnp.ones_like(ids), jnp.asarray([31]),
        dtype=jnp.float32)).lower(params, head).as_text(debug_info=True)
    for scope in ("dsa.index", "dsa.select", "experts.loop"):
        assert scope in text, scope
    pools = (jnp.zeros((5, 8, 16, 128)), jnp.zeros((2, 8, 16, 128)))
    table = jnp.zeros((2, 4), jnp.int32)
    text = jax.jit(lambda p, h, pl: lm.paged_attend(
        p, h, cfg, jnp.zeros((2, 1), jnp.int32), pl, table,
        jnp.asarray([40, 3]), dtype=jnp.float32)).lower(
            params, head, pools).as_text(debug_info=True)
    for scope in ("dsa.index", "dsa.select", "dsa.gather"):
        assert scope in text, scope


# (i) pools of unequal layer counts -------------------------------------------

def test_token_bytes_and_the_page_budget_over_unequal_pools(tok):
    """By hand at the tiny size: latents 5 layers x 128 wide, index keys 2
    layers x 128 wide, float32: 896 values = 3 584 bytes a token, 57 344 a
    page of 16; a budget of 1 MB holds 18 pages."""
    cfg = get_config(MODEL)
    assert families.pool_shapes(cfg) == ((5, 128), (2, 128))
    assert families.token_bytes(cfg, jnp.float32) == (5 * 128 + 2 * 128) * 4
    assert families.token_bytes(cfg, jnp.bfloat16) == 1792
    eng = PagedDecodeEngine(Args(model=MODEL, decode_slots=4,
                                 decode_max_len=128, max_seq_len=128,
                                 dtype="float32", kv_hbm_mb=1.0),
                            tokenizer=tok, mesh=None, buckets=(16,),
                            page_sz=16)
    assert eng.token_bytes == 3584 and eng.page_bytes == 57344
    assert eng.n_pages == (1 << 20) // 57344 == 18
    assert [p.shape for p in eng._pools] == [(5, 18, 16, 128),
                                             (2, 18, 16, 128)]
    kv = eng.kv_snapshot()
    assert kv["index_bytes_a_token"] == 2 * 128 * 4
    assert kv["kv_pool_bytes"] == kv["cache_bytes"] == 18 * 57344
    assert eng.allocator.snapshot()["page_bytes"] == 57344
    # the older families read as they did: one count for every pool
    assert families.pool_shapes(get_config("bert-tiny")) == ((2, 128), (2, 128))
    solar = get_config("solar-open2-share-tiny")
    assert families.pool_shapes(solar) == (
        (solar.num_gqa_layers, solar.kv_width),) * 2
    # the full-size share: 7 x 640 + 2 x 128 values = 9 472 bytes a token
    big = get_config("glm-5.2-ep16-share")
    assert families.token_bytes(big, jnp.bfloat16) == 9472


# the presets -----------------------------------------------------------------

def test_the_preset_is_the_stated_share():
    cfg = get_config("glm-5.2-ep16-share")
    assert (cfg.num_layers, cfg.first_k_dense, cfg.experts_held,
            cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.n_group,
            cfg.vocab_size) == (7, 1, 16, 256, 8, 1, 19_360)
    assert cfg.indexer_types == ("full", "shared", "shared", "shared",
                                 "full", "shared", "shared")
    # ISSUE 43's arithmetic: 5.498 G parameters; an indexer 9.37 M
    assert abs(lm.param_count(cfg) / 1e9 - 5.498) < 0.002
    shapes = lm.param_shapes(cfg)["indexer"]
    assert abs(sum(int(np.prod(s[1:])) for s in shapes.values()) / 1e6
               - 9.37) < 0.01
    assert (cfg.latent_width, cfg.cache_width, cfg.index_cache_width) \
        == (576, 640, 128)
    l2 = get_config("glm-5.2-ep16-share-l2")
    assert l2 == cfg.replace(num_layers=2, indexer_types=("full", "shared"))
    # the published list: full for layers 0-2 and every fourth from 6
    from pdnlp_tpu.models.config import _glm52

    whole = _glm52()
    assert [l for l, k in enumerate(whole.indexer_types) if k == "full"] \
        == [0, 1, 2] + list(range(6, 78, 4))
    assert whole.indexer_types[2:9] == cfg.indexer_types
    with pytest.raises(ValueError, match="indexer_types"):
        cfg.replace(num_layers=6)
    with pytest.raises(ValueError, match="indexer_types"):
        cfg.replace(indexer_types=("shared",) + cfg.indexer_types[1:])


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(HERE, "glm52_reference.py")) as f:
        mine = f.read()
    with open(os.path.join(HERE, "..", "benchmark", "reference",
                           "glm52.py")) as f:
        theirs = f.read()
    assert mine == theirs
