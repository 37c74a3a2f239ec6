"""The latent-attention, sparse-expert decoder family (``models/latent_moe``)
served through ``PagedDecodeEngine`` / ``DecodeBatcher``, against the plain
float32 reference (``tests/axk1_reference.py``, held to the benchmark's copy
by a test), at the tiny preset on the CPU with seeded random weights.

Tolerances, each with its reason:

- ``LOGIT_TOL`` (5e-3): both sides hold the SAME bfloat16-rounded weights;
  the engine computes in float32 here, so what is left is the CPU matmul's
  default precision and the order of sums (measured: 5e-4).  A served logit
  this far from the reference's is a fault, not rounding.
- near-tie routing: a swap of the last expert taken for the first left out
  changes the layer by a whole expert.  At float32 compute no swap was ever
  seen at a margin above ``SWAP_MARGIN`` (1e-4, in score units), so
  positions whose reference margin is below it are EXCLUDED from the logit
  comparison, counted, and limited to ``SWAP_SHARE`` of the positions — the
  logit tolerance is never widened for them.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import axk1_reference as ref
from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
from pdnlp_tpu.models import decoder, families, get_config, latent_moe as lm
from pdnlp_tpu.serve import DecodeBatcher, PagedDecodeEngine
from pdnlp_tpu.serve.decode import PrefillWorker
from pdnlp_tpu.utils.config import Args

MODEL = "ax-k1-share-tiny"
SEED = 11
LOGIT_TOL = 5e-3
SWAP_MARGIN = 1e-4
SWAP_SHARE = 0.05
BUCKETS = (16, 32, 64)
PAGE = 16


@pytest.fixture(scope="module")
def tok():
    return WordPieceTokenizer(build_vocab(
        ["天地人你我", "好坏大小上下来去" * 5, "爱恨喜怒哀乐" * 15], size=128))


def sizes_of(cfg) -> dict:
    """The reference's ``sizes`` for a program config (the benchmark's
    configuration file holds the same keys)."""
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size,
        n_routed_experts=cfg.experts_held,
        router_width=cfg.n_routed_experts, expert_first=cfg.expert_first,
        num_experts_per_tok=cfg.num_experts_per_tok,
        n_shared_experts=cfg.n_shared_experts, n_group=cfg.n_group,
        topk_group=cfg.topk_group,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        num_hidden_layers=cfg.num_layers,
        first_k_dense_replace=cfg.first_k_dense, vocab_size=cfg.vocab_size,
        # the mixing's keys (read by ``xing4_reference`` alone)
        hc_mult=cfg.hc_mult, hc_sinkhorn_iters=cfg.hc_sinkhorn_iters,
        hc_eps=cfg.hc_eps, mhc_h_res_clamp_min=cfg.hc_res_clamp[0],
        mhc_h_res_clamp_max=cfg.hc_res_clamp[1],
        rope_scaling=dict(
            type="yarn", factor=cfg.rope_factor,
            original_max_position_embeddings=cfg.rope_original_max,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
            mscale=cfg.rope_mscale, mscale_all_dim=cfg.rope_mscale_all_dim))


def program_weights(seed, sizes, held=None, banned=(), ref=ref):
    """The reference's seeded weights laid into the program's trees."""
    key = ref.seed_key(seed)
    top = ref.top_weights(key, sizes, banned)
    K = sizes["first_k_dense_replace"]
    ws = [ref.layer_weights(key, sizes, l, held)
          for l in range(sizes["num_hidden_layers"])]

    def stack(xs):
        return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *xs)

    params = {"embed": top["embed"], "final_norm": top["final_norm"],
              "dense": stack(ws[:K]), "moe": stack(ws[K:])}
    return params, {"kernel": top["head"]}


def make_engine(tok, ref=ref, **kw):
    base = dict(model=MODEL, decode_slots=4, decode_max_len=128,
                max_seq_len=128, max_new_tokens=8, dtype="float32")
    base.update(kw)
    eng = PagedDecodeEngine(Args(**base), tokenizer=tok, mesh=None,
                            buckets=BUCKETS, page_sz=PAGE)
    sizes = sizes_of(eng.cfg)
    params, head = program_weights(SEED, sizes, ref=ref)
    like = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype),
                                  (eng.params, eng.head))
    assert like == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype),
                                          (params, head))
    eng.params, eng.head = params, head
    return eng, sizes


@pytest.fixture(scope="module")
def served(tok):
    return serve_three(tok)


def serve_three(tok, ref=ref, **kw):
    """One engine, three streams one after another, every logits row the
    engine handed its batcher recorded: a cold prompt, a prompt sharing its
    first two pages (the chunk path), and the cold prompt again (a full
    prefix hit whose trailing partial page is copied on write)."""
    eng, sizes = make_engine(tok, ref=ref, trace=True, **kw)
    eng.warmup_decode()
    rows = []
    for name in ("prefill_ids", "prefill_chunk", "decode_batch"):
        real = getattr(eng, name)

        def spy(*a, _real=real, _name=name, **k):
            out = _real(*a, **k)
            rows.append((_name, np.array(out)))
            return out

        setattr(eng, name, spy)
    rng = np.random.default_rng(5)
    V = eng.cfg.vocab_size
    cold = rng.integers(5, V, 41).tolist()
    shared = cold[:32] + rng.integers(5, V, 13).tolist()
    out = {}
    for label, prompt in (("cold", cold), ("prefix_hit", shared),
                          ("cow", cold)):
        del rows[:]
        b = DecodeBatcher(eng, replica=0)
        b.eos_id = -1
        b.start()
        s = b.submit_ids(prompt, max_new_tokens=10)
        emitted = s.result(timeout=300)
        b.stop()
        out[label] = (prompt, emitted, s.slot, list(rows))
    out["prefix"] = eng.prefix.snapshot()
    out["records"] = eng.tracer.records()
    out["kv"], out["load"] = eng.kv_snapshot(), eng.expert_load
    out["cow"] = out["cow"] + (eng.allocator.snapshot(),)
    out["sizes"] = sizes
    out["leak"] = eng.leak_check()
    return out


def check_against_reference(sizes, prompt, emitted, slot, rows, first_from,
                            ref=ref):
    """Every logits row the stream was served against the reference's full
    forward of prompt + emitted tokens; -> positions compared."""
    seq = prompt + emitted
    (logits, margin), = ref.forward(SEED, sizes, [seq])
    logits, margin = np.asarray(logits), np.asarray(margin)
    got = [(n, r) for n, r in rows]
    assert got[0][0] == first_from if first_from else got[0][0] == "decode_batch"
    at = len(prompt) - 1
    compared = swaps = 0
    for name, block in got:
        row = block[slot] if name == "decode_batch" else block[0]
        if name == "decode_batch" and first_from is None and at == len(prompt) - 1:
            at += 1       # a full hit's first token came from the index
        if margin[:at + 1].min() < SWAP_MARGIN:
            swaps += 1    # a near tie at or before this position
        else:
            np.testing.assert_allclose(row, logits[at], atol=LOGIT_TOL,
                                       rtol=0, err_msg=f"{name} at {at}")
            assert int(np.argmax(row)) == seq[at + 1]
            compared += 1
        at += 1
    assert swaps <= SWAP_SHARE * (compared + swaps), (swaps, compared)
    return compared


def test_cold_prefill_then_decode_matches_reference(served):
    prompt, emitted, slot, rows = served["cold"]
    assert len(emitted) == 10
    assert check_against_reference(served["sizes"], prompt, emitted, slot,
                                   rows, "prefill_ids") >= 9


def test_chunk_after_prefix_hit_matches_reference(served):
    prompt, emitted, slot, rows = served["prefix_hit"]
    assert rows[0][0] == "prefill_chunk"
    assert served["prefix"]["hits_partial"] >= 1
    assert check_against_reference(served["sizes"], prompt, emitted, slot,
                                   rows, "prefill_chunk") >= 9


def test_decode_after_copy_on_write_matches_reference(served):
    prompt, emitted, slot, rows, pages = served["cow"]
    assert served["prefix"]["hits_full"] >= 1 and pages["cow_copies"] >= 1
    assert all(n == "decode_batch" for n, _ in rows)
    assert emitted == served["cold"][1]
    assert check_against_reference(served["sizes"], prompt, emitted, slot,
                                   rows, None) >= 8


def test_no_page_leaks_after_the_three_streams(served):
    assert served["leak"]["ok"], served["leak"]


def test_the_leaves_carry_expert_load_and_cache_bytes(served):
    from pdnlp_tpu.obs.phases import decode_host_phases, format_decode_table

    recs = served["records"]
    fetch = [r["attrs"] for r in recs if r["name"] == "decode.fetch"]
    steps = [r["attrs"] for r in recs if r["name"] == "decode.dispatch"
             and r["attrs"].get("phase") == "decode"]
    assert fetch and steps
    for a in fetch:
        assert {"expert_assignments", "expert_tokens_max",
                "experts_idle"} <= set(a)
    # what a served launch hands the host: the chosen id of each of the 4
    # slots and the held experts' counts, never the [slots, vocab] logits
    held = served["sizes"]["n_routed_experts"]
    assert {a["bytes"] for a in fetch} == {4 * 4 + 4 * held}
    tb = served["kv"]["pages"]["page_bytes"] // PAGE
    assert all(a["cache_bytes_per_token"] == tb for a in steps)
    assert all(a["kv_positions_read"] >= a["kv_positions_live"] > 0
               for a in steps if a["live"])
    # the engine's running total is the sum of what its fetch leaves said
    pre = [r["attrs"] for r in recs
           if r["name"] in ("prefill.fetch", "chunk.fetch")]
    assert int(served["load"].sum()) == sum(
        a["expert_assignments"] for a in fetch + pre)
    assert served["kv"]["kv_pool_bytes"] == served["kv"]["cache_bytes"]
    assert served["kv"]["weights_bytes"] > 0
    table = decode_host_phases(recs)["0"]
    assert table["expert_load"]["assignments_per_step"] >= 0
    assert table["cache_bytes_per_token"] == tb
    text = format_decode_table({"0": table})
    assert "expert load per decode step" in text
    assert "KV read amplification" in text


@pytest.fixture(scope="module")
def model():
    cfg = get_config(MODEL)
    sizes = sizes_of(cfg)
    params, head = program_weights(SEED, sizes)
    return cfg, sizes, params, head


def test_absorbed_path_equals_expanded_path_on_one_cache(model):
    cfg, sizes, params, head = model
    rng = np.random.default_rng(2)
    ids = rng.integers(5, cfg.vocab_size, (3, 40)).astype(np.int32)
    pool = jnp.zeros((cfg.num_layers, 24, 8, cfg.cache_width), jnp.float32)
    _, _, lat = lm.prefill(params, head, cfg, ids[:, :32],
                           np.ones((3, 32), np.int32), jnp.full((3,), 31),
                           dtype=jnp.float32)
    table = np.arange(24, dtype=np.int32).reshape(3, 8)
    pool = decoder.insert_pool(pool, lat, jnp.asarray(table[:, :4]))
    outs = {}
    for absorb in (True, False):
        p, got = pool, []
        for t in range(32, 40):
            lg, counts, p = lm.paged_attend(
                params, head, cfg, ids[:, t:t + 1], p, jnp.asarray(table),
                jnp.full((3,), t), dtype=jnp.float32, absorb=absorb)
            got.append(np.asarray(lg))
        outs[absorb] = (np.stack(got), np.asarray(p))
    np.testing.assert_allclose(outs[True][0], outs[False][0], atol=2e-3)
    np.testing.assert_array_equal(outs[True][1][0], outs[False][1][0])
    np.testing.assert_allclose(outs[True][1], outs[False][1], atol=2e-3)


def stacked(experts):
    """One layer's experts as the stack of every layer's."""
    return jax.tree_util.tree_map(lambda x: x[None], experts)


def test_shares_add_up_to_the_uncut_layer(model):
    """All shares' parts of one expert layer, the shared expert counted
    once, against the reference's uncut layer."""
    cfg, sizes, _, _ = model
    E, Eh = cfg.n_routed_experts, cfg.experts_held
    key = ref.seed_key(SEED)
    f = jax.random.normal(jax.random.key(3), (50, cfg.hidden_size))
    whole = ref.layer_weights(key, sizes, 1, held=(0, E))
    want, _ = ref.expert_layer(f, ref._f32(whole), sizes, (0, E), "f32")
    total = ref._gated(f, ref._f32(whole["shared"]), "f32")
    counts = []
    for first in range(0, E, Eh):
        w = ref.layer_weights(key, sizes, 1, held=(first, Eh))
        share = cfg.replace(expert_first=first)
        idx, gates, _ = lm.route(f, w["router"], share, jnp.float32)
        part, n = lm.held_experts(f, idx, gates, jnp.ones((50,), bool),
                                  stacked(w["experts"]), 0, share,
                                  jnp.float32)
        total = total + part
        counts.append(np.asarray(n))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-3)
    assert int(np.sum(counts)) == 50 * cfg.num_experts_per_tok


def test_no_assignment_dropped_when_one_expert_gets_every_token(model):
    cfg, sizes, _, _ = model
    T = 2 * lm.EXPERT_BLOCK + 37          # three blocks of ONE expert
    w = ref.layer_weights(ref.seed_key(SEED), sizes, 1)
    f = jax.random.normal(jax.random.key(4), (T, cfg.hidden_size))
    k = cfg.num_experts_per_tok
    idx = jnp.tile(jnp.asarray([[2] + [cfg.experts_held + i
                                      for i in range(k - 1)]]), (T, 1))
    gates = jnp.full((T, k), 0.5)
    out, counts = lm.held_experts(f, idx, gates, jnp.ones((T,), bool),
                                  stacked(w["experts"]), 0, cfg,
                                  jnp.float32)
    assert counts.tolist() == [0, 0, T, 0]
    one = jax.tree_util.tree_map(lambda x: x[2].astype(jnp.float32),
                                 w["experts"])
    np.testing.assert_allclose(np.asarray(out),
                               0.5 * np.asarray(ref._gated(f, one, "f32")),
                               atol=2e-3)
    # padding and dead rows take no part and are not counted
    valid = jnp.arange(T) < 5
    out, counts = lm.held_experts(f, idx, gates, valid,
                                  stacked(w["experts"]), 0, cfg, jnp.float32)
    assert counts.tolist() == [0, 0, 5, 0]
    assert not np.asarray(out[5:]).any()


def test_engine_seam_is_bitwise_for_the_bert_family(tok):
    """``bert-tiny-long`` through the family table gives, bit for bit, what
    the decoder's own functions give when called as the engine used to."""
    args = Args(model="bert-tiny-long", decode_slots=4, decode_max_len=64,
                max_seq_len=64)
    eng = PagedDecodeEngine(args, tokenizer=tok, mesh=None, buckets=(16, 32),
                            page_sz=PAGE)
    assert eng.family.name == "bert" and len(eng._pools) == 2
    assert eng._cache_k is eng._pools[0] and eng._cache_v is eng._pools[1]
    cfg = eng.cfg
    rng = np.random.default_rng(9)
    prompt = rng.integers(5, cfg.vocab_size, 16).tolist()
    ids = np.zeros((eng.prefill_rows, 16), np.int32)
    ids[0] = prompt
    mask = np.zeros_like(ids)
    mask[0] = 1
    last = np.zeros((eng.prefill_rows,), np.int32)
    last[0] = 15
    logits, ks, vs = jax.jit(lambda p, h: decoder.prefill(
        p, h, cfg, ids, mask, last, dtype=eng.dtype))(eng.params, eng.head)
    class Stream:
        rid, prompt_ids, emitted, max_new_tokens = "r0", prompt, [], 8

    claim = eng.attach_stream(0, Stream())
    row = eng._table[0].copy()          # the pages the engine will use
    assert claim.kind == "cold" and row[1] < eng.n_pages
    pk, pv = (jnp.zeros_like(x) for x in eng._pools)
    flat = np.full((eng.prefill_rows, 1), eng.n_pages, np.int32)
    flat[0, 0] = row[0]
    pk, pv = jax.jit(decoder.paged_insert)(pk, pv, ks, vs, flat)
    rung = next(r for r in eng.decode_rungs if r >= 2)
    table = np.full((eng.slots, rung), eng.n_pages, np.int32)
    table[0] = row[:rung]
    tok0 = np.zeros((eng.slots, 1), np.int32)
    tok0[0, 0] = int(np.argmax(np.asarray(logits[0])))
    pos = np.zeros((eng.slots,), np.int32)
    pos[0] = 16
    want, pk, pv = jax.jit(lambda p, h, a, b: decoder.paged_decode_step(
        p, h, cfg, tok0, a, b, table, pos, dtype=eng.dtype))(
            eng.params, eng.head, pk, pv)
    first = eng.prefill_ids([prompt], [0])
    np.testing.assert_array_equal(first[0], np.asarray(logits[0]))
    got = eng.decode_batch(tok0[:, 0], pos, live=1)
    np.testing.assert_array_equal(got[0], np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(eng._cache_k), np.asarray(pk))
    np.testing.assert_array_equal(np.asarray(eng._cache_v), np.asarray(pv))


@pytest.mark.parametrize("model", [MODEL, "xing4-stage-tiny"])
@pytest.mark.parametrize("what", ["kv_int8", "weights_int8",
                                  "speculative_pair", "handoff"])
def test_refusals_are_loud_and_at_construction(tok, what, model):
    base = dict(model=model, decode_slots=4, decode_max_len=64,
                max_seq_len=64)
    if what == "kv_int8":
        with pytest.raises(ValueError, match="int8 cache"):
            PagedDecodeEngine(Args(kv_dtype="int8", **base), tokenizer=tok,
                              mesh=None, buckets=(16,))
    elif what == "weights_int8":
        with pytest.raises(ValueError, match="int8 weights"):
            PagedDecodeEngine(Args(serve_dtype="int8", **base),
                              tokenizer=tok, mesh=None, buckets=(16,))
    else:
        eng = PagedDecodeEngine(Args(**base), tokenizer=tok, mesh=None,
                                buckets=(16,), prefix_share=False)
        if what == "speculative_pair":
            with pytest.raises(ValueError, match="speculative pair"):
                DecodeBatcher(eng, drafter=eng)
        else:
            with pytest.raises(ValueError, match="disaggregated handoff"):
                PrefillWorker(eng, dispatch=lambda *a: None)
            with pytest.raises(ValueError, match="disaggregated handoff"):
                eng.warmup_handoff()


def test_token_bytes_come_from_the_familys_pools(tok):
    eng = PagedDecodeEngine(Args(model=MODEL, decode_slots=4,
                                 decode_max_len=64, max_seq_len=64,
                                 kv_dtype="bf16"),
                            tokenizer=tok, mesh=None, buckets=(16,))
    cfg = eng.cfg
    assert eng.token_bytes == cfg.num_layers * cfg.cache_width * 2
    assert eng.page_bytes == eng.token_bytes * eng.page_sz
    assert families.token_bytes(cfg, jnp.bfloat16) == eng.token_bytes
    assert eng.kv_snapshot()["cache_bytes"] == eng.n_pages * eng.page_bytes
    bert = get_config("bert-tiny")
    assert families.token_bytes(bert, jnp.float32) \
        == bert.num_layers * 2 * bert.hidden_size * 4
    assert eng._pools[0].shape == (cfg.num_layers, eng.n_pages, eng.page_sz,
                                   cfg.cache_width)


def test_the_preset_is_the_stated_share():
    cfg = get_config("ax-k1-ep16-share")
    assert (cfg.num_layers, cfg.first_k_dense, cfg.experts_held,
            cfg.n_routed_experts, cfg.num_experts_per_tok) == (6, 1, 12, 192, 8)
    # ISSUE 27's arithmetic: 4.166 G parameters, 6 912 bytes a token
    assert abs(lm.param_count(cfg) / 1e9 - 4.166) < 0.002
    assert cfg.num_layers * cfg.latent_width * 2 == 6912
    assert (cfg.latent_width, cfg.cache_width) == (576, 640)
    assert abs(lm.softmax_scale(cfg) - 192 ** -0.5
               * (0.1 * np.log(32) + 1) ** 2) < 1e-9


def test_the_two_copies_of_the_reference_are_one_text():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "axk1_reference.py")) as f:
        mine = f.read()
    with open(os.path.join(here, "..", "benchmark", "reference",
                           "axk1.py")) as f:
        theirs = f.read()
    assert mine == theirs
