"""Manifold-constrained hyper-connections (``models/hyper_connections``) and
the latent family's preset that runs them (``xing4-stage-tiny``: four
residual streams, a bias-corrected router, 8 experts all held), against the
plain float32 reference (``tests/xing4_reference.py``, held to the
benchmark's copy by a test), on the CPU with seeded random weights.

The served comparisons are ``tests/test_latent_moe.py``'s, with its
tolerances (``LOGIT_TOL`` 5e-3, near ties below ``SWAP_MARGIN`` excluded
and limited).  The mixing alone:

- ``MIX_TOL`` (2e-5): both sides compute one sub-layer's mixing in float32
  from the same leaves; what is left is the order of the sums over ``n*C``
  = 512 terms and of 40 normalisations (measured: 3e-6).
- after 20 Sinkhorn steps the COLUMNS of ``H_res`` sum to 1 to ``COLUMN_TOL``
  (1e-5: they were normalised last, ``hc_eps`` and rounding are left) and
  the ROWS to ``ROW_TOL`` (2e-2): what the iteration has converged to from
  seeded logits of the configuration's range (diagonal 2, noise 0.5 + the
  input's part of order one); the worst of 288 rows read 7.2e-3, nine in
  ten under 1e-4 — 20 steps do not finish every token's matrix.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_latent_moe as base
import xing4_reference as ref
from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
from pdnlp_tpu.models import get_config, hyper_connections as hc
from pdnlp_tpu.models import latent_moe as lm
from pdnlp_tpu.serve import PagedDecodeEngine
from pdnlp_tpu.utils.config import Args

MODEL = "xing4-stage-tiny"
SEED = base.SEED
MIX_TOL = 2e-5
COLUMN_TOL = 1e-5
ROW_TOL = 2e-2


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


@pytest.fixture(scope="module")
def streams(model):
    """Streams that differ, as they do after a layer: ``[n, B, T, C]``."""
    cfg = model[0]
    return jax.random.normal(jax.random.key(7),
                             (cfg.hc_mult, 2, 9, cfg.hidden_size))


def sub_leaves(model, part="dense", sub="attn", l=0):
    return jax.tree_util.tree_map(lambda w: w[l].astype(jnp.float32),
                                  model[2][part]["hc"][sub])


def reference_mixing(x, p, sizes, y=None):
    """The reference's per-token functions over the program's layout."""
    n, B, T, C = x.shape
    X = jnp.moveaxis(x, 0, 2).reshape(B * T, n, C)
    pre, post, res = ref.over_positions(
        lambda t: ref.mix_coefficients(t, p, sizes), X)
    u = ref.over_positions(ref.sublayer_read, X, pre)
    out = (pre, post, res, u.reshape(B, T, C))
    if y is not None:
        new = ref.over_positions(ref.sublayer_write, X, y.reshape(B * T, C),
                                 res, post)
        out += (jnp.moveaxis(new.reshape(B, T, n, C), 2, 0),)
    return out


# (a) one sub-layer's mixing against the reference ----------------------------

def test_one_sublayers_mixing_matches_the_reference(model, streams):
    cfg, sizes = model[:2]
    p = sub_leaves(model)
    y = jax.random.normal(jax.random.key(8), streams.shape[1:])
    pre, post, res = hc.coefficients(streams, p, cfg)
    u = hc.read(streams, pre)
    new = hc.write(streams, y, res, post)
    rpre, rpost, rres, ru, rnew = reference_mixing(streams, p, sizes, y)
    np.testing.assert_allclose(pre.T, rpre, atol=MIX_TOL)
    np.testing.assert_allclose(post.T, rpost, atol=MIX_TOL)
    np.testing.assert_allclose(jnp.moveaxis(res, -1, 0), rres, atol=MIX_TOL)
    np.testing.assert_allclose(u, ru, atol=10 * MIX_TOL)
    np.testing.assert_allclose(new, rnew, atol=10 * MIX_TOL)


def test_h_res_is_doubly_stochastic_and_visibly_not_the_identity(model,
                                                                 streams):
    cfg = model[0]
    for part, sub in (("dense", "attn"), ("dense", "ffn"), ("moe", "attn"),
                      ("moe", "ffn")):
        _, _, res = hc.coefficients(streams, sub_leaves(model, part, sub), cfg)
        res = np.asarray(res)                          # [row, column, token]
        np.testing.assert_allclose(res.sum(0), 1.0, atol=COLUMN_TOL)
        np.testing.assert_allclose(res.sum(1), 1.0, atol=ROW_TOL)
        assert (res > 0).all()
        diag = res[np.arange(4), np.arange(4)]
        # the seeded range: leaning on the diagonal, far from the identity
        assert 0.3 < diag.mean() < 0.9 and res.max() < 0.999
        off = res[~np.eye(4, dtype=bool)]
        assert off.mean() > 0.03


def test_a_clamp_that_binds_changes_the_result(model, streams):
    cfg = model[0]
    p = sub_leaves(model)
    _, _, free = hc.coefficients(streams, p, cfg)
    _, _, bound = hc.coefficients(streams, p,
                                  cfg.replace(hc_res_clamp=(-0.5, 0.5)))
    assert float(jnp.abs(free - bound).max()) > 0.05
    # the published clamp (-30, 30) does not bind at the seeded range
    _, _, wide = hc.coefficients(streams, p,
                                 cfg.replace(hc_res_clamp=(-1e9, 1e9)))
    np.testing.assert_array_equal(np.asarray(free), np.asarray(wide))
    # ... and where it binds, the reference clamps at the same place
    sizes = dict(model[1], mhc_h_res_clamp_min=-0.5, mhc_h_res_clamp_max=0.5)
    np.testing.assert_allclose(jnp.moveaxis(bound, -1, 0),
                               reference_mixing(streams, p, sizes)[2],
                               atol=MIX_TOL)


def test_sinkhorn_steps_are_rows_then_columns():
    m = jnp.exp(jax.random.normal(jax.random.key(1), (4, 4, 5)))
    one = hc.sinkhorn(m, iters=1, eps=1e-6)
    rows = m / (m.sum(1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(one, rows / (rows.sum(0, keepdims=True) + 1e-6),
                               rtol=1e-6)


# (b) one stream, a mixing that does nothing = x + F(x) -----------------------

def test_one_stream_with_a_null_mixing_is_the_plain_residual():
    """``hc_mult`` 1, ``phi`` 0, ``b_pre`` large, ``b_post`` 0: ``H_pre`` =
    sigmoid(large) = 1, ``H_post`` = 2 sigmoid(0) = 1, ``H_res`` = 1 — the
    mixed layer is the one-stream ``_layer``."""
    cfg = get_config("ax-k1-share-tiny")
    sizes = base.sizes_of(cfg)
    params, _ = base.program_weights(SEED, sizes)
    lp = jax.tree_util.tree_map(lambda w: w[0].astype(jnp.float32),
                                params["dense"])
    x = jax.random.normal(jax.random.key(2), (2, 12, cfg.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(12, dtype=jnp.int32), (2, 12))
    valid = jnp.ones((2, 12), bool)

    def attend(l, carry, q_nope, q_rope, latent, ap):
        return lm.attend_expanded(q_nope, q_rope, latent, ap, cfg, positions,
                                  jnp.float32, causal_cut=True), carry

    want, _, _ = lm._layer(x, lp, cfg, 0, positions, valid, attend, None,
                           jnp.float32)
    null = {"phi": jnp.zeros((cfg.hidden_size, 3)),
            "b": jnp.asarray([40.0, 0.0, 0.0]), "a": jnp.ones((3,))}
    got, _, _ = lm._layer(x[None], dict(lp, hc={"attn": null, "ffn": null}),
                          cfg, 0, positions, valid, attend, None, jnp.float32)
    assert got.shape == (1,) + want.shape
    np.testing.assert_allclose(got[0], want, atol=1e-5)


# (c), (d) served through the engine's pages ----------------------------------

@pytest.fixture(scope="module")
def served(tok):
    return base.serve_three(tok, ref=ref, model=MODEL)


@pytest.mark.parametrize("label, first_from, least", [
    ("cold", "prefill_ids", 9), ("prefix_hit", "prefill_chunk", 9),
    ("cow", None, 8)])
def test_served_logits_match_the_reference(served, label, first_from, least):
    """(c) a cold prompt prefilled, then decoded through the pages; (d) the
    chunk after a prefix hit — only latents are shared, so sharing stays
    allowed; and decoding after a full hit's copy on write."""
    prompt, emitted, slot, rows = served[label][:4]
    assert len(emitted) == 10
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


def test_the_streams_enter_no_pool_and_the_snapshot_says_their_bytes(served):
    cfg = get_config(MODEL)
    kv = served["kv"]
    # float32 here: 4 streams x 128 values x 4 bytes a token, never cached
    assert kv["stream_bytes_a_token"] == cfg.hc_mult * cfg.hidden_size * 4
    assert kv["kv_pool_bytes"] == kv["cache_bytes"]
    tb = kv["pages"]["page_bytes"] // base.PAGE
    assert tb == cfg.num_layers * cfg.cache_width * 4
    one = get_config("ax-k1-share-tiny")
    assert tb == one.num_layers * one.cache_width * 4


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


# (f) the selection bias -------------------------------------------------------

def test_the_bias_changes_the_choice_and_not_the_gates_of_the_chosen(model):
    cfg, sizes = model[:2]
    w = ref.layer_weights(ref.seed_key(SEED), sizes, 1)
    f = jax.random.normal(jax.random.key(5), (200, cfg.hidden_size))
    idx0, gates0, s = lm.route(f, w["router"], cfg, jnp.float32)
    idx1, gates1, s1 = lm.route(f, w["router"], cfg, jnp.float32,
                                w["router_bias"])
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s1))
    changed = np.asarray(jnp.sort(idx0, -1) != jnp.sort(idx1, -1)).any(-1)
    assert 0.1 < changed.mean() < 0.9, changed.mean()
    # chosen by score + bias ...
    sel = np.asarray(s + w["router_bias"].astype(jnp.float32))
    np.testing.assert_array_equal(
        np.sort(np.asarray(idx1), -1),
        np.sort(np.argsort(-sel, -1)[:, :cfg.num_experts_per_tok], -1))
    # ... gated by the score alone: the bias is in no gate
    picked = np.take_along_axis(np.asarray(s), np.asarray(idx1), -1)
    np.testing.assert_allclose(
        np.asarray(gates1), cfg.routed_scaling_factor * picked
        / picked.sum(-1, keepdims=True), rtol=1e-5)
    # and the reference routes the same way
    ridx, rgates = ref.route(f, w["router"].astype(jnp.float32),
                             w["router_bias"].astype(jnp.float32), sizes,
                             "f32")[:2]
    np.testing.assert_array_equal(np.asarray(idx1), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(gates1), np.asarray(rgates),
                               rtol=1e-5)


# (h) the one-stream configurations' programs are what they were ---------------

ONE_STREAM_LEAVES = {
    "dense": {"attn", "ffn"}, "moe": {"attn", "router", "experts", "shared"}}


@pytest.mark.parametrize("program", ["_prefill_fn", "_pdecode_fn"])
def test_the_one_stream_presets_programs_take_and_give_what_they_did(tok,
                                                                     program):
    """``ax-k1-share-tiny``: no mixing leaf, no bias leaf; ``_prefill_fn``
    and ``_pdecode_fn`` take the operands and give the outputs they took and
    gave (since PR 39 with the rows the experts' products computed, a scalar
    beside the held experts' counts), and nothing in them carries a mixing's
    name."""
    eng = PagedDecodeEngine(Args(model="ax-k1-share-tiny", decode_slots=4,
                                 decode_max_len=64, max_seq_len=64,
                                 dtype="float32"),
                            tokenizer=tok, mesh=None, buckets=(16,),
                            page_sz=16)
    cfg = eng.cfg
    assert cfg.hc_mult == 1 and not cfg.selection_bias
    shapes = lm.param_shapes(cfg)
    assert {k: set(v) for k, v in shapes.items()
            if isinstance(v, dict)} == ONE_STREAM_LEAVES
    weights = jax.tree_util.tree_leaves((eng.params, eng.head))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    pool = jax.ShapeDtypeStruct(eng._pools[0].shape, eng._pools[0].dtype)
    rows, V, Eh = eng.prefill_rows, cfg.vocab_size, cfg.experts_held
    if program == "_prefill_fn":
        args = (i32(rows, 16), i32(rows, 16), i32(rows))
        jaxpr = jax.make_jaxpr(eng._jit_prefill)(eng.params, eng.head, *args)
        outs = [((rows, V), jnp.float32), ((rows,), jnp.int32),
                ((Eh,), jnp.int32), ((), jnp.int32),
                ((cfg.num_layers, rows, 16, cfg.cache_width), jnp.float32)]
    else:
        args = ((pool,), i32(4, 1), i32(4, 2), i32(4), ())
        jaxpr = jax.make_jaxpr(eng._jit_pdecode)(eng.params, eng.head, *args)
        outs = [((4, V), jnp.float32), ((4,), jnp.int32), ((Eh,), jnp.int32),
                ((), jnp.int32), (pool.shape, pool.dtype)]
    want_in = [(w.shape, w.dtype) for w in weights] + [
        (a.shape, a.dtype) for a in jax.tree_util.tree_leaves(args)]
    assert [(a.shape, a.dtype) for a in jaxpr.in_avals] == want_in
    assert [(a.shape, a.dtype) for a in jaxpr.out_avals] == outs
    assert "mhc" not in str(jaxpr)


def test_the_mixed_presets_programs_name_their_scopes(model):
    cfg, _, params, head = model
    ids = jnp.zeros((1, 16), jnp.int32)
    text = jax.jit(lambda p, h: lm.prefill(
        p, h, cfg, ids, jnp.ones_like(ids), jnp.asarray([15]),
        dtype=jnp.float32)).lower(params, head).as_text(debug_info=True)
    for scope in ("mhc.coeff", "mhc.mix", "experts.loop"):
        assert scope in text, scope


# the presets -----------------------------------------------------------------

def test_the_preset_is_the_stated_stage():
    cfg = get_config("xing4-29b-ep1-stage")
    assert (cfg.num_layers, cfg.first_k_dense, cfg.experts_held,
            cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.n_group,
            cfg.vocab_size) == (6, 1, 64, 64, 4, 1, 131_072)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_res_clamp,
            cfg.selection_bias) == (4, 20, 1e-6, (-30.0, 30.0), True)
    # ISSUE 37's arithmetic: 4.793 G parameters, 6 912 bytes a token held
    # 7 680 wide, the mixing 0.69 M a layer
    assert abs(lm.param_count(cfg) / 1e9 - 4.793) < 0.001
    assert cfg.num_layers * cfg.cache_width * 2 == 7680
    mixing = 2 * sum(int(np.prod(s)) for s in
                     hc.param_shapes(cfg.hc_mult, cfg.hidden_size).values())
    assert abs(mixing / 1e6 - 0.69) < 0.005
    l2 = get_config("xing4-29b-ep1-stage-l2")
    assert l2 == cfg.replace(num_layers=2)
    assert abs(lm.softmax_scale(cfg) - 192 ** -0.5
               * (0.1 * np.log(64) + 1) ** 2) < 1e-9


def test_the_big_preset_is_the_configuration_files_numbers_one_by_one():
    """A default left standing in ``LatentMoEConfig`` would be A.X-K1's:
    every field of the preset against the file's published key."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmark", "configs",
                           "xing4-29b-ep1-stage.json")) as f:
        cfg = json.load(f)
    pre = get_config("xing4-29b-ep1-stage")
    rs = cfg["rope_scaling"]
    want = {
        "vocab_size": cfg["vocab_size"], "hidden_size": cfg["hidden_size"],
        "num_layers": cfg["num_hidden_layers"],
        "first_k_dense": cfg["first_k_dense_replace"],
        "num_heads": cfg["num_attention_heads"],
        "q_lora_rank": cfg["q_lora_rank"], "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "intermediate_size": cfg["intermediate_size"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "n_routed_experts": cfg["n_routed_experts"],
        "experts_held": cfg["n_routed_experts"],
        "expert_first": cfg["expert_first"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "n_shared_experts": cfg["n_shared_experts"],
        "n_group": cfg["n_group"], "topk_group": cfg["topk_group"],
        "routed_scaling_factor": cfg["routed_scaling_factor"],
        "rms_norm_eps": cfg["rms_norm_eps"], "rope_theta": cfg["rope_theta"],
        "rope_factor": rs["factor"],
        "rope_original_max": rs["original_max_position_embeddings"],
        "rope_beta_fast": rs["beta_fast"], "rope_beta_slow": rs["beta_slow"],
        "rope_mscale": rs["mscale"],
        "rope_mscale_all_dim": rs["mscale_all_dim"],
        "max_position": cfg["max_position_embeddings"],
        "hc_mult": cfg["hc_mult"],
        "hc_sinkhorn_iters": cfg["hc_sinkhorn_iters"],
        "hc_eps": cfg["hc_eps"],
        "hc_res_clamp": (cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]),
        "selection_bias": cfg["topk_method"] == "noaux_tc",
        # no learned sparse attention: no indexer, and its sizes unread
        "index_n_heads": 0, "index_head_dim": 128, "index_topk": 2048,
        "indexer_types": (),
        "weight_dtype": "bfloat16",
    }
    assert set(want) == {f.name for f in dataclasses.fields(pre)}
    for k, v in want.items():
        assert getattr(pre, k) == v, k


def test_the_two_copies_of_the_reference_are_one_text():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "xing4_reference.py")) as f:
        mine = f.read()
    with open(os.path.join(here, "..", "benchmark", "reference",
                           "xing4.py")) as f:
        theirs = f.read()
    assert mine == theirs
