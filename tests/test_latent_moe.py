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
        # the indexer's keys (read by ``glm52_reference`` alone)
        index_n_heads=cfg.index_n_heads, index_head_dim=cfg.index_head_dim,
        index_topk=cfg.index_topk, indexer_types=list(cfg.indexer_types),
        rope_parameters=dict(rope_theta=cfg.rope_theta, rope_type="default"),
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
    if hasattr(ref, "program_layout"):
        # a reference whose rotary pairs are interleaved: its weights as the
        # program takes them; its indexers are a stack of their own
        ws = [ref.program_layout(w, sizes) for w in ws]
    indexers = [w.pop("indexer") for w in ws if "indexer" in w]

    def stack(xs):
        return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *xs)

    params = {"embed": top["embed"], "final_norm": top["final_norm"],
              "dense": stack(ws[:K]), "moe": stack(ws[K:])}
    if indexers:
        params["indexer"] = stack(indexers)
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


def serve_three(tok, ref=ref, extra=(), **kw):
    """One engine, three streams one after another, every logits row the
    engine handed its batcher recorded: a cold prompt, a prompt sharing its
    first two pages (the chunk path), and the cold prompt again (a full
    prefix hit whose trailing partial page is copied on write).  ``extra``:
    (label, prompt length) of further cold streams served after them."""
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
    more = [(label, rng.integers(5, V, n).tolist()) for label, n in extra]
    for label, prompt in [("cold", cold), ("prefix_hit", shared),
                          ("cow", cold)] + more:
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
    out["picks"] = eng.positions_seen
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
    # slots, the held experts' counts and the rows computed for them, never
    # the [slots, vocab] logits
    held = served["sizes"]["n_routed_experts"]
    assert {a["bytes"] for a in fetch} == {4 * 4 + 4 * held + 4}
    # the experts' products run whole tiles: never fewer rows than
    # assignments, and a launch that assigned nothing computed nothing
    launches = fetch + [r["attrs"] for r in recs
                        if r["name"] == "prefill.fetch"]
    assert any(r["name"] == "prefill.fetch" for r in recs)
    for a in launches:
        assert a["expert_rows_computed"] >= a["expert_assignments"]
        assert (a["expert_rows_computed"] == 0) == (
            a["expert_assignments"] == 0)
        assert a["expert_rows_computed"] % 16 == 0
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
    assert 0 < table["expert_load"]["expert_fill"] <= 1
    assert table["expert_load"]["expert_fill"] == pytest.approx(
        sum(a["expert_assignments"] for a in fetch)
        / sum(a["expert_rows_computed"] for a in fetch), abs=1e-4)
    assert table["cache_bytes_per_token"] == tb
    text = format_decode_table({"0": table})
    assert "expert load per decode step" in text
    assert "of the rows their products computed" in text
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
    k = cfg.num_experts_per_tok
    T = 2 * lm.EXPERT_BLOCK + 37          # three tiles of ONE expert
    assert lm.expert_tile(T, k, cfg) == lm.EXPERT_BLOCK
    w = ref.layer_weights(ref.seed_key(SEED), sizes, 1)
    f = jax.random.normal(jax.random.key(4), (T, cfg.hidden_size))
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


def per_expert_sum(f, idx, gates, valid, experts, cfg):
    """The float32 reference's sum, an expert at a time over every row:
    ``sum over held e of [e chosen and the row valid] g_e Expert_e(f)``."""
    total = jnp.zeros(f.shape, jnp.float32)
    for e in range(cfg.experts_held):
        one = jax.tree_util.tree_map(lambda x: x[e].astype(jnp.float32),
                                     experts)
        g = jnp.sum(jnp.where((idx == cfg.expert_first + e)
                              & valid[:, None], gates, 0.0), axis=1)
        total = total + g[:, None] * ref._gated(f, one, "f32")
    return total


def rows_by_hand(counts, T, k, cfg):
    """The row tiles that hold any assignment, window by window, counted in
    a loop: a tile two runs share counts once for each."""
    tile, rows = lm.expert_tile(T, k, cfg), lm.expert_window(T, k, cfg)
    first, n = np.cumsum(counts) - counts, 0
    for lo in range(0, int(np.sum(counts)), rows):
        at = 0                                # the run's first row in here
        for a, c in zip(first, counts):
            part = max(min(a + c, lo + rows) - max(a, lo), 0)
            if part:
                n += (at + part - 1) // tile - at // tile + 1
            at += part
    return n * tile


def wide_router(cfg, experts=64):
    """The tiny share under a router wide enough that a decode-sized call
    runs tiles smaller than itself (a tile follows ``T * k / experts``)."""
    return cfg.replace(n_routed_experts=experts)


@pytest.mark.parametrize("T,tile,runs", [
    (16, 16, (0, 16, 1, 16)),       # idle, a tile = every row, one row
    (128, 64, (0, 64, 65, 128)),    # idle, a tile, a tile + 1, every row
])
def test_a_decode_sized_call_at_the_edges_of_a_tile(model, T, tile, runs):
    cfg, sizes, _, _ = model
    cfg = wide_router(cfg)
    k, Eh = cfg.num_experts_per_tok, cfg.experts_held
    assert (k, Eh) == (3, 4) and lm.expert_tile(T, k, cfg) == tile
    w = ref.layer_weights(ref.seed_key(SEED), sizes, 1)
    f = jax.random.normal(jax.random.key(6), (T, cfg.hidden_size))
    # expert 3 takes every row; experts 1 and 2 the first rows of their
    # runs' lengths; what is left of a token's k goes to absent experts
    idx = np.empty((T, k), np.int32)
    idx[:, 0] = 3
    idx[:, 1] = np.where(np.arange(T) < runs[1], 1, Eh + 1)
    idx[:, 2] = np.where(np.arange(T) < runs[2], 2, Eh + 2)
    gates = jax.random.uniform(jax.random.key(7), (T, k), minval=0.2)
    valid = jnp.ones((T,), bool)
    out, counts = lm.held_experts(f, jnp.asarray(idx), gates, valid,
                                  stacked(w["experts"]), 0, cfg,
                                  jnp.float32)
    assert counts.tolist() == list(runs)
    want = per_expert_sum(f, jnp.asarray(idx), gates, valid, w["experts"],
                          cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-3)
    assert int(lm.expert_rows(counts, T, k, cfg)) == rows_by_hand(
        runs, T, k, cfg) >= sum(runs)


@pytest.mark.parametrize("T", [16, 128, 300])
def test_absent_experts_and_rows_not_valid_add_exactly_zero(model, T):
    cfg, sizes, _, _ = model
    cfg = wide_router(cfg, 16)
    k, Eh = cfg.num_experts_per_tok, cfg.experts_held
    w = ref.layer_weights(ref.seed_key(SEED), sizes, 1)
    rng = np.random.default_rng(T)
    f = jax.random.normal(jax.random.key(8), (T, cfg.hidden_size))
    idx = np.stack([rng.permutation(16)[:k] for _ in range(T)]
                   ).astype(np.int32)
    idx[1] = [Eh, Eh + 1, Eh + 2]            # a row that chose none held
    gates = jax.random.uniform(jax.random.key(9), (T, k), minval=0.2)
    valid = np.ones((T,), bool)
    valid[[0, T // 2, T - 1]] = False        # padding, a dead slot
    out, counts = lm.held_experts(f, jnp.asarray(idx), gates,
                                  jnp.asarray(valid), stacked(w["experts"]),
                                  0, cfg, jnp.float32)
    mine = (idx < Eh) & valid[:, None]
    assert counts.tolist() == [int((mine & (idx == e)).sum())
                               for e in range(Eh)]
    silent = ~mine.any(axis=1)
    assert silent[[0, 1, T // 2, T - 1]].all()
    assert not np.asarray(out)[silent].any()
    want = per_expert_sum(f, jnp.asarray(idx), gates, jnp.asarray(valid),
                          w["experts"], cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-3)


def test_more_rows_than_a_window_take_further_passes_not_a_drop(model):
    """A share's buffers hold twice what it expects of the ``T * k``
    assignments (in whole tiles); every token choosing its one held expert
    is more."""
    cfg, sizes, _, _ = model
    cfg = wide_router(cfg, 16).replace(experts_held=1, expert_first=2)
    k, T = cfg.num_experts_per_tok, 700
    tile, rows = lm.expert_tile(T, k, cfg), lm.expert_window(T, k, cfg)
    assert tile < rows < T and rows % tile == 0
    w = ref.layer_weights(ref.seed_key(SEED), sizes, 1)
    one = jax.tree_util.tree_map(lambda x: x[2:3], w["experts"])
    f = jax.random.normal(jax.random.key(10), (T, cfg.hidden_size))
    idx = jnp.tile(jnp.asarray([[2, 0, 1]]), (T, 1))
    gates = jax.random.uniform(jax.random.key(11), (T, k), minval=0.2)
    out, counts = lm.held_experts(f, idx, gates, jnp.ones((T,), bool),
                                  stacked(one), 0, cfg, jnp.float32)
    assert counts.tolist() == [T]
    want = gates[:, :1] * ref._gated(
        f, jax.tree_util.tree_map(lambda x: x[0].astype(jnp.float32), one),
        "f32")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-3)
    # two windows: the run is cut at the first one's end
    assert int(lm.expert_rows(counts, T, k, cfg)) == rows_by_hand(
        [T], T, k, cfg) == rows + -(-(T - rows) // tile) * tile


@pytest.mark.parametrize("sizes", [
    (0, 0, 0, 0),            # nothing to do: no pair, nothing written
    (5, 0, 11, 16),          # an empty group, a tile shared by two groups
    (16, 16, 0, 0),          # groups that end on the tiles' edges
    (0, 0, 0, 45),           # one group over three tiles, the last partial
    (1, 1, 1, 1),            # four groups in one tile
])
def test_grouped_products_follow_their_plan(sizes):
    """``ops/grouped.py`` alone: every group's rows times its own matrix of
    a stack that holds other groups before it (``base``), rows past the last
    group's left unwritten."""
    from pdnlp_tpu.ops import grouped

    tile, n_tiles, K, N, base = 16, 3, 32, 256, 2
    rng = np.random.default_rng(sum(sizes))
    x = jnp.asarray(rng.normal(size=(tile * n_tiles, K)), jnp.float32)
    ws = [jnp.asarray(rng.normal(size=(base + 4, K, N)), jnp.bfloat16)
          for _ in range(2)]
    pairs, n_pairs = grouped.plan(jnp.asarray(sizes, jnp.int32), tile,
                                  n_tiles)
    end = np.cumsum(sizes)
    start = end - np.asarray(sizes)
    held = [(e - 1) // tile - s // tile + 1 if n else 0
            for s, e, n in zip(start, end, sizes)]
    assert int(n_pairs) == sum(held)
    assert grouped.tiles_held(jnp.asarray(sizes), tile).tolist() == held
    g, t = (np.asarray(a)[:int(n_pairs)] for a in pairs[:2])
    assert g.tolist() == [i for i, h in enumerate(held) for _ in range(h)]
    assert t.tolist() == [s // tile + j for s, h in zip(start, held)
                          for j in range(h)]
    got = grouped.grouped(x, ws, pairs, n_pairs, base, tile=tile,
                          combine=lambda a, b: a * b)
    for i, (s, e) in enumerate(zip(start, end)):
        want = (x[s:e] @ ws[0][base + i].astype(jnp.float32)) \
            * (x[s:e] @ ws[1][base + i].astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(got[s:e]), np.asarray(want),
                                   rtol=2e-5, atol=2e-4)


def _primitives(jaxpr, out=None, depth=0):
    """(depth of enclosing loops, primitive, operand shapes) of every
    equation, sub-jaxprs walked."""
    out = [] if out is None else out
    for e in jaxpr.eqns:
        out.append((depth, e.primitive.name,
                    [getattr(v.aval, "shape", None) for v in e.invars]))
        inner = depth + (e.primitive.name == "while")
        for v in e.params.values():
            for x in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(x, "jaxpr", x)
                if hasattr(sub, "eqns"):
                    _primitives(sub, out, inner)
    return out


def test_one_gather_in_one_pass_out_and_grouped_products_between(model):
    cfg, sizes, _, _ = model
    cfg = wide_router(cfg)
    T, k, H = 128, cfg.num_experts_per_tok, cfg.hidden_size
    F = cfg.moe_intermediate_size
    w = ref.layer_weights(ref.seed_key(SEED), sizes, 1)
    jaxpr = jax.make_jaxpr(lambda f, idx, gates, valid: lm.held_experts(
        f, idx, gates, valid, stacked(w["experts"]), 0, cfg, jnp.float32))(
        jnp.zeros((T, H)), jnp.zeros((T, k), jnp.int32), jnp.zeros((T, k)),
        jnp.ones((T,), bool))
    prims = _primitives(jaxpr.jaxpr)
    rows = lm.expert_window(T, k, cfg)
    # rows of activations are indexed in the window's body alone (one loop
    # deep): f laid out in sorted order by ONE gather, the results brought
    # back to their tokens a slot of the k at a time — whatever else is
    # indexed is a vector of ints
    wide = [(d, n, s[0]) for d, n, s in prims
            if (n == "gather" or n.startswith("scatter"))
            and len(s[0]) == 2 and s[0][1] in (H, F)]
    assert sorted(wide) == sorted([(1, "gather", (T, H))]
                                  + [(1, "gather", (rows, H))] * k)
    # between them two grouped products over the layout — gate and up in
    # one, then down — their plan (group sizes: data) a scalar operand, and
    # no loop over tiles or experts around them
    calls = [s for d, n, s in prims if n == "pallas_call" and d == 1]
    assert len(calls) == len([n for _, n, _ in prims
                              if n == "pallas_call"]) == 2
    assert [(rows, H) in s for s in calls] == [True, False]
    assert [(rows, F) in s for s in calls] == [False, True]
    assert not [n for d, n, _ in prims if d >= 2 and n == "while"]


#: (configuration, rows x experts a token, the tile PERF.md states): the
#: three expert-layer cells' decode steps (both row rungs) and prompts
TILES = [
    ("xing4-29b-ep1-stage", 128, 64), ("xing4-29b-ep1-stage", 16, 16),
    ("xing4-29b-ep1-stage", 1024, 128), ("xing4-29b-ep1-stage", 3072, 128),
    ("ax-k1-ep16-share", 128, 64), ("ax-k1-ep16-share", 16, 16),
    ("ax-k1-ep16-share", 1024, 128), ("ax-k1-ep16-share", 3072, 128),
    ("solar-open2-ep16-share", 64, 16),
    ("solar-open2-ep16-share", 3072, 128),
    ("solar-open2-ep16-share", 5632, 128),
]


@pytest.mark.parametrize("name,T,tile", TILES)
def test_the_tile_follows_the_load_the_shapes_state(name, T, tile):
    cfg = get_config(name)
    k = cfg.num_experts_per_tok
    assert lm.expert_tile(T, k, cfg) == tile
    # a share's window: twice its expected rows in whole tiles; all T * k
    # where every expert is held
    rows = lm.expert_window(T, k, cfg)
    assert rows % tile == 0
    if cfg.experts_held == cfg.n_routed_experts:
        assert rows == T * k
    else:
        twice = 2 * T * k * cfg.experts_held // cfg.n_routed_experts
        assert max(twice, 1) <= rows < twice + tile


def test_expert_fill_of_a_known_table():
    """``decode_host_phases``' ``expert_load``: assignments over the rows
    the experts' products computed, over the decode steps' fetch leaves."""
    from pdnlp_tpu.obs.phases import decode_host_phases

    def leaf(name, t, **attrs):
        return {"name": name, "t0": t, "dur": 0.001, "tid": 1,
                "attrs": dict(attrs, replica=0, round=int(t * 100))}

    recs = []
    for i, (n, rows) in enumerate([(2540, 7840), (2560, 7840), (0, 0)]):
        t = 0.05 * i
        recs += [leaf("decode.dispatch", t, phase="decode", rows=128),
                 leaf("decode.fetch", t + 0.02, bytes=772,
                      expert_assignments=n, expert_rows_computed=rows,
                      expert_tokens_max=25, experts_idle=75)]
    # a prompt's leaf is not a decode step's
    recs.append(leaf("prefill.fetch", 0.2, bytes=4, expert_assignments=60000,
                     expert_rows_computed=111616))
    load = decode_host_phases(recs)["0"]["expert_load"]
    assert load["assignments_per_step"] == 1700.0
    assert load["expert_fill"] == round(5100 / 15680, 4)
    # leaves of a program from before the counter: no fill, no error
    old = [dict(r, attrs={k: v for k, v in r["attrs"].items()
                          if k != "expert_rows_computed"}) for r in recs]
    assert "expert_fill" not in decode_host_phases(old)["0"]["expert_load"]


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


@pytest.mark.parametrize("model", [MODEL, "xing4-stage-tiny",
                                   "glm52-share-tiny"])
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
