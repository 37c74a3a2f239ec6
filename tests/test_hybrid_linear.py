"""The hybrid decoder family (``models/hybrid_linear``: gated delta-rule
linear attention with a per-slot recurrent state beside paged GQA layers,
sparse experts in every layer) served through ``PagedDecodeEngine`` /
``DecodeBatcher``, against the plain float32 reference
(``tests/solar_open2_reference.py``, held to the benchmark's copy by a
test), at the tiny preset on the CPU with seeded random weights.

Tolerances, each with its reason:

- ``LOGIT_TOL`` (5e-3): both sides hold the SAME bfloat16-rounded weights;
  the engine computes in float32 here, so what is left is the order of sums
  — the program's chunkwise form against the reference's one position after
  another, the CPU matmul's default precision (measured: 1.2e-4).  A served
  logit this far from the reference's is a fault, not rounding.
- ``STATE_TOL`` (2e-4): the chunkwise scan against the sequential recurrence
  on the SAME float32 inputs, states of magnitude about 3 (measured: 1e-5).
- near-tie routing, as ``tests/test_latent_moe.py``: positions at or after a
  reference margin below ``SWAP_MARGIN`` are EXCLUDED from the logit
  comparison, counted, and limited to ``SWAP_SHARE`` — the logit tolerance
  is never widened for them.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

import solar_open2_reference as ref
from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
from pdnlp_tpu.models import families, get_config, hybrid_linear as hl
from pdnlp_tpu.models import latent_moe as lm
from pdnlp_tpu.obs.memory import KVBudgetExceeded
from pdnlp_tpu.serve import DecodeBatcher, PagedDecodeEngine
from pdnlp_tpu.serve.decode import PrefillWorker
from pdnlp_tpu.utils.config import Args

MODEL = "solar-open2-share-tiny"
SEED = 11
LOGIT_TOL = 5e-3
STATE_TOL = 2e-4
SWAP_MARGIN = 1e-4
SWAP_SHARE = 0.1
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
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        moe_intermediate_size=cfg.moe_intermediate_size,
        n_routed_experts=cfg.experts_held,
        router_width=cfg.n_routed_experts, expert_first=cfg.expert_first,
        num_experts_per_tok=cfg.num_experts_per_tok,
        n_shared_experts=cfg.n_shared_experts,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rms_norm_eps=cfg.rms_norm_eps, num_hidden_layers=cfg.num_layers,
        vocab_size=cfg.vocab_size, kda_low_rank=cfg.low_rank,
        gqa_layers=[l for l in range(cfg.num_layers) if cfg.is_gqa(l)],
        linear_attn_config=dict(
            short_conv_kernel_size=cfg.conv_kernel,
            head_dim=cfg.linear_head_dim, num_heads=cfg.linear_num_heads,
            num_kv_heads=None))


def program_weights(seed, sizes, banned=()):
    """The reference's seeded weights as the program's trees: a layer's
    leaves under the same names, so nothing is re-laid."""
    key = ref.seed_key(seed)
    top = ref.top_weights(key, sizes, banned)
    params = {"embed": top["embed"], "final_norm": top["final_norm"],
              "layers": [ref.layer_weights(key, sizes, l)
                         for l in range(sizes["num_hidden_layers"])]}
    return params, {"kernel": top["head"]}


def shapes(tree):
    return jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), tree)


def make_engine(tok, **kw):
    base = dict(model=MODEL, decode_slots=4, decode_max_len=128,
                max_seq_len=128, max_new_tokens=8, dtype="float32")
    base.update(kw)
    eng = PagedDecodeEngine(Args(**base), tokenizer=tok, mesh=None,
                            buckets=BUCKETS, page_sz=PAGE)
    sizes = sizes_of(eng.cfg)
    weights = program_weights(SEED, sizes)
    assert shapes((eng.params, eng.head)) == shapes(weights)
    eng.params, eng.head = weights
    return eng, sizes


def spy_logits(eng, rows):
    """Record every logits block the engine hands its batcher."""
    for name in ("prefill_ids", "decode_batch"):
        real = getattr(eng, name)

        def spy(*a, _real=real, _name=name, **k):
            out = _real(*a, **k)
            rows.append((_name, np.array(out)))
            return out

        setattr(eng, name, spy)


def serve(eng, prompts, new=10):
    """The prompts through one batcher, all submitted at once ->
    (emitted ids, slot) a prompt."""
    b = DecodeBatcher(eng, replica=0)
    b.eos_id = -1
    b.start()
    streams = [b.submit_ids(p, max_new_tokens=new) for p in prompts]
    out = [(s.result(timeout=600), s.slot) for s in streams]
    b.stop()
    return out


@pytest.fixture(scope="module")
def served(tok):
    """One engine of 4 slots, two streams ONE AFTER ANOTHER in the same
    slot (the second is seated where the first's state lies), every logits
    row recorded."""
    eng, sizes = make_engine(tok, trace=True)
    eng.warmup_decode()
    warm = eng.metrics.retraces.value
    rows = []
    spy_logits(eng, rows)
    rng = np.random.default_rng(6)     # prompts without a near tie
    V = eng.cfg.vocab_size
    out = {"sizes": sizes, "keys": sorted(eng._seen_shapes, key=str)}
    for label, n in (("first", 41), ("reseated", 23)):
        del rows[:]
        prompt = rng.integers(5, V, n).tolist()
        (emitted, slot), = serve(eng, [prompt])
        out[label] = (prompt, emitted, slot, list(rows))
    out["retraced"] = eng.metrics.retraces.value - warm
    out["records"] = eng.tracer.records()
    out["kv"], out["leak"] = eng.kv_snapshot(), eng.leak_check()
    out["engine"] = eng
    return out


def check_against_reference(sizes, prompt, emitted, slot, rows):
    """Every logits row the stream was served against the reference's full
    forward of prompt + emitted tokens; -> positions compared."""
    seq = prompt + emitted
    (logits, margin), = ref.forward(SEED, sizes, [seq])
    logits, margin = np.asarray(logits), np.asarray(margin)
    assert rows[0][0] == "prefill_ids"
    at = len(prompt) - 1
    compared = swaps = 0
    for name, block in rows:
        row = block[slot] if name == "decode_batch" else block[0]
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


# ------------------------------------------------ (a) against the reference

def test_prefill_then_decode_matches_the_references_full_forward(served):
    prompt, emitted, slot, rows = served["first"]
    assert len(emitted) == 10 and slot == 0
    assert check_against_reference(served["sizes"], prompt, emitted, slot,
                                   rows) >= 9


# ------------------------------------- (d) a reseated slot, and the row rungs

def test_a_reseated_slot_serves_the_new_prompt_and_leaks_no_state(served):
    """The second stream sat in the slot the first one left: its prefill
    wrote the slot's state and convolution tail WHOLE, so what the first
    stream left there reaches nothing."""
    prompt, emitted, slot, rows = served["reseated"]
    assert slot == served["first"][2] == 0
    assert check_against_reference(served["sizes"], prompt, emitted, slot,
                                   rows) >= 9
    assert served["leak"]["ok"], served["leak"]


def test_no_program_was_traced_after_warmup_and_no_chunk_was_compiled(served):
    assert served["retraced"] == 0
    kinds = {k[-1] if isinstance(k[-1], str) else k[0] for k in served["keys"]}
    # the suffix chunk exists only after a prefix hit, which is refused
    assert "chunk" not in kinds and "prefill" in kinds and "decode" in kinds


def test_the_small_row_rung_and_the_full_block_choose_the_same_ids(tok):
    """An engine of 64 slots launches 16 rows while no slot above 15 is
    attached and all 64 otherwise: a stream's tokens are the same, and its
    state moves alike, under either."""
    rng = np.random.default_rng(7)
    outs = []
    for n_streams in (1, 17):
        eng, _ = make_engine(tok, decode_slots=64, decode_max_len=64,
                             max_seq_len=64)
        assert eng.row_rungs == (16, 64)
        V = eng.cfg.vocab_size
        if not outs:
            prompts = [rng.integers(5, V, 20).tolist() for _ in range(17)]
        got = serve(eng, prompts[:n_streams], new=6)
        rungs = {k[1] for k in eng._seen_shapes if k[0] == "decode"}
        outs.append((got[0][0], rungs, np.asarray(eng._states[0][0])))
    (alone, r1, s1), (crowded, r17, s17) = outs
    assert r1 == {16} and 64 in r17
    assert alone == crowded and len(alone) == 6
    np.testing.assert_allclose(s1, s17, atol=STATE_TOL)


# ----------------------------------- (b) the chunkwise scan, on padded rows

def delta_sequential(q, k, v, g, beta, S):
    """``hl.delta_step``, the recurrence as written, one position after
    another under a scan: what the chunkwise form is held to."""
    def step(S, x):
        o, S = hl.delta_step(*x, S)
        return S, o

    S, o = jax.lax.scan(step, S, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def _delta_inputs(key, B, T, N, d, decay):
    ks = jax.random.split(key, 6)
    q = hl._l2norm(jax.random.normal(ks[0], (B, T, N, d))) * d ** -0.5
    k = hl._l2norm(jax.random.normal(ks[1], (B, T, N, d)))
    v = jax.random.normal(ks[2], (B, T, N, d))
    lo, hi = decay
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, N, d), minval=np.log(lo),
                                    maxval=np.log(hi)))
    # beta over all of (0, 2): I - beta k k^T reaches eigenvalue -1
    beta = 2 * jax.nn.sigmoid(4 * jax.random.normal(ks[4], (B, T, N)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (B, N, d, d))


DECAYS = {"near_one": (1e-5, 1e-3), "near_zero": (1.0, 30.0),
          "both": (1e-4, 30.0)}
# T, heads, head width, chunks a group (None: the module's byte budget), and
# whether the triangular system is made as badly conditioned as it gets
SHAPES = {
    "T150": (150, 3, 16, None, False),
    "T64": (64, 3, 16, None, False),
    # 11 chunks, the last one padded, in 3 groups of 4: a ragged last group
    "T650_in_groups_of_4_chunks": (650, 3, 16, 4, False),
    # the published head width: the row-block factors are 128 wide
    "T150_d128": (150, 2, 128, None, False),
    "T150_d128_repeated_keys_beta_2": (150, 2, 128, None, True),
}


def _chunked_case(decay, shape, monkeypatch):
    """-> (the inputs of one case, its rows' real lengths)."""
    T, N, d, per, hard = SHAPES[shape]
    B = 2
    q, k, v, g, beta, S0 = _delta_inputs(jax.random.key(T), B, T, N, d,
                                         DECAYS[decay])
    if hard:
        # every key the same and beta near 2 (``kda_allow_neg_eigval``):
        # ``I + Diag(beta) tril(A, -1)`` at its worst conditioned, with a
        # decay of ``-g`` = 30 ON the row blocks' edges and inside them
        k = jnp.broadcast_to(k[:, :1], k.shape)
        beta = jnp.full_like(beta, 1.999)
        at = np.isin(np.arange(T) % hl.ROW, (0, 7, hl.ROW - 1))
        g = jnp.where(at[None, :, None, None], -30.0, g)
    else:
        assert float(beta.max()) > 1.9 and float(beta.min()) < 0.1
    if per:
        monkeypatch.setattr(hl, "GROUP_BYTES", per * (
            B * N * hl.CHUNK * hl.ROW * d * 4))
    nreal = np.array([T, T * 2 // 3])
    valid = jnp.arange(T)[None] < nreal[:, None]
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    assert float(jnp.abs(S0).max()) > 1.0       # not from an empty state
    return (q, k, v, g, beta, S0), nreal


@pytest.mark.parametrize("decay", list(DECAYS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_chunkwise_scan_equals_the_sequential_recurrence(decay, shape,
                                                         monkeypatch):
    """``-g`` from 1e-5 (alpha = 0.99999) to 30 (alpha = 1e-13: the factored
    form ``k / exp(G)`` would overflow inside a chunk), beta up to 2, a
    non-zero first state, rows padded past their real length with ``beta =
    0``, ``g = 0``: outputs and the state at the last REAL position agree —
    over one group of chunks and over several, at head widths 16 and 128."""
    (q, k, v, g, beta, S0), nreal = _chunked_case(decay, shape, monkeypatch)
    if SHAPES[shape][3]:
        loops = _loops(jax.make_jaxpr(hl.delta_chunked)(
            q, k, v, g, beta, S0).jaxpr)
        assert [(depth, e.params["length"]) for e, depth, _ in loops] == [
            (0, 3), (1, 4)]                          # 3 groups of 4 chunks
    o1, S1 = delta_sequential(q, k, v, g, beta, S0)
    o2, S2 = jax.jit(hl.delta_chunked)(q, k, v, g, beta, S0)
    assert np.isfinite(np.asarray(o2)).all()
    assert np.isfinite(np.asarray(S2)).all()
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=STATE_TOL)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S1), atol=STATE_TOL)
    # row 1's final state IS the state after its last real position
    n = int(nreal[1])
    _, S_cut = delta_sequential(q[1:, :n], k[1:, :n], v[1:, :n],
                                   g[1:, :n], beta[1:, :n], S0[1:])
    np.testing.assert_allclose(np.asarray(S2[1:]), np.asarray(S_cut),
                               atol=STATE_TOL)


# ------------------------ (b') the FORM of the chunkwise scan, as a jaxpr

def _inner(eqn):
    """The jaxprs an equation carries (a scan's body, a jitted helper's)."""
    found = (getattr(v, "jaxpr", v) for v in eqn.params.values())
    return [j for j in found if hasattr(j, "eqns")]


def _loops(jaxpr, depth=0):
    """Every ``scan`` of a jaxpr as (equation, depth, body), helpers looked
    through."""
    for e in jaxpr.eqns:
        for sub in _inner(e):
            if e.primitive.name == "scan":
                yield e, depth, sub
            yield from _loops(sub, depth + (e.primitive.name == "scan"))


def _equations(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in _inner(e):
            yield from _equations(sub)


def _evaluate(jaxpr, consts, args, seen):
    """The jaxpr run one equation at a time (a scan one step at a time),
    ``seen(primitive name, operand values)`` called before each."""
    env = dict(zip(jaxpr.constvars, consts))
    env.update(zip(jaxpr.invars, args))

    def read(v):
        return env[v] if isinstance(v, jex_core.Var) else v.val

    for e in jaxpr.eqns:
        vals = [read(v) for v in e.invars]
        seen(e.primitive.name, vals)
        if e.primitive.name == "scan":
            body, p = e.params["jaxpr"], e.params
            nc, nk = p["num_consts"], p["num_carry"]
            carry, ys = vals[nc:nc + nk], []
            steps = range(p["length"])
            for i in (reversed(steps) if p["reverse"] else steps):
                out = _evaluate(body.jaxpr, body.consts, vals[:nc] + carry + [
                    x[i] for x in vals[nc + nk:]], seen)
                carry, y = out[:nk], out[nk:]
                ys.append(y)
            ys = ys[::-1] if p["reverse"] else ys
            out = carry + [jnp.stack(y) for y in zip(*ys)]
        elif e.primitive.name == "jit":
            out = _evaluate(e.params["jaxpr"].jaxpr, e.params["jaxpr"].consts,
                            vals, seen)
        else:
            assert not _inner(e), e.primitive.name     # nothing is skipped
            out = e.primitive.bind(*vals, **e.params)
            out = out if e.primitive.multiple_results else [out]
        env.update(zip(e.outvars, out))
    return [read(v) for v in jaxpr.outvars]


@pytest.fixture(scope="module")
def chunked_jaxpr():
    """``delta_chunked`` at ``C = 64`` over 3 groups of 2 chunks, heads of
    the published width."""
    B, T, N, d = 1, 6 * hl.CHUNK, 2, 128
    args = _delta_inputs(jax.random.key(0), B, T, N, d, DECAYS["near_zero"])
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(hl, "GROUP_BYTES",
                      2 * B * N * hl.CHUNK * hl.ROW * d * 4)
        closed = jax.make_jaxpr(hl.delta_chunked)(*args)
    return closed, args, (B, N, hl.CHUNK, d)


def test_what_does_not_read_the_state_is_outside_the_innermost_loop(
        chunked_jaxpr):
    """The sequential loop carries the state through products and one
    multiply, nothing else: no solve, no running sum, no ``exp``, and
    nothing of ``C x C x d`` elements a head (the pairwise decays) — what
    keeps the next edit from sliding work back into the loop."""
    closed, _, (B, N, C, d) = chunked_jaxpr
    loops = list(_loops(closed.jaxpr))
    # 3 groups of chunks, a group's 2 chunks: nothing deeper
    assert [(depth, e.params["length"]) for e, depth, _ in loops] == [
        (0, 3), (1, 2)]
    body = loops[-1][2]
    names = [e.primitive.name for e in _equations(body)]
    assert not {"triangular_solve", "cumsum", "reduce_window_sum", "exp",
                "scan", "while", "custom_linear_solve"} & set(names), names
    assert names.count("dot_general") <= 4 and names.count("mul") == 1
    widest = max(int(np.prod(v.aval.shape)) for e in _equations(body)
                 for v in e.outvars)
    assert widest < B * N * C * C * d
    # ... and the solve is nowhere the compiler's: row blocks, by products
    everything = [e.primitive.name for e in _equations(closed.jaxpr)]
    assert "triangular_solve" not in everything
    assert "exp" in everything and "cumsum" in everything


def test_every_product_of_the_chunkwise_scan_is_float32_at_highest(
        chunked_jaxpr):
    """The configuration's ``precision``: "the delta rule's products at
    highest precision", float32 in, float32 out."""
    closed, _, _ = chunked_jaxpr
    dots = [e for e in _equations(closed.jaxpr)
            if e.primitive.name == "dot_general"]
    assert len(dots) >= 4
    for e in dots:
        assert e.params["precision"] == (hl.HIGHEST, hl.HIGHEST), e
        assert e.params["preferred_element_type"] == jnp.float32, e
        assert all(v.aval.dtype == jnp.float32 for v in e.invars), e


def test_no_exponent_of_the_chunkwise_scan_is_positive(chunked_jaxpr):
    """Every ``exp`` is of a difference ``G_later - G_earlier``: on decays
    down to ``alpha`` = 1e-13 a position no operand of any ``exp`` rises
    above 0 (the factored form ``k_i / exp(G_i)`` would reach e^1900)."""
    closed, args, _ = chunked_jaxpr
    tops = []

    def seen(name, vals):
        if name == "exp":
            tops.append(float(jnp.max(vals[0])))

    out = _evaluate(closed.jaxpr, closed.consts, list(args), seen)
    assert len(tops) >= 5 and max(tops) <= 0.0, tops
    # the walk above IS the function: the same outputs
    o, S = hl.delta_chunked(*args)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(o), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(S), atol=1e-6)


@pytest.mark.parametrize("nreal", [1, 2, 3, 9, 16])
def test_a_padded_prompts_state_and_tail_are_those_of_its_last_real_position(
        nreal):
    """The linear mixer over a row padded to its bucket hands over the state
    and the convolution's last 3 REAL inputs (zeros before position 0), and
    one more token from them equals the mixer over the longer row."""
    cfg = get_config(MODEL)
    sizes = sizes_of(cfg)
    mp = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32),
        ref.layer_weights(ref.seed_key(SEED), sizes, 1)["mixer"])
    T = 16
    a = jax.random.normal(jax.random.key(nreal), (1, T + 1, cfg.hidden_size))
    valid = (jnp.arange(T) < nreal)[None]
    _, S, tail = hl.linear_prompt(a[:, :T], mp, cfg, valid,
                                  jnp.asarray([nreal]), jnp.float32)
    qkv = lm._mm(a[:, :T], mp["qkv"], jnp.float32)[0]
    want = np.zeros((3, qkv.shape[-1]), np.float32)
    for i, p in enumerate(range(nreal - 3, nreal)):
        if p >= 0:
            want[i] = qkv[p]
    np.testing.assert_allclose(np.asarray(tail[0]), want, atol=1e-6)
    # the next token through the decode form, against the prompt form over
    # the real tokens and that token
    longer = jnp.concatenate([a[:, :nreal], a[:, T:]], axis=1)
    y_all, S_all, _ = hl.linear_prompt(
        longer, mp, cfg, jnp.ones((1, nreal + 1), bool),
        jnp.asarray([nreal + 1]), jnp.float32)
    y, S2, _ = hl.linear_token(a[:, T:], mp, cfg, S, tail,
                               jnp.ones((1,), bool), jnp.float32)
    np.testing.assert_allclose(np.asarray(y[0, 0]), np.asarray(y_all[0, -1]),
                               atol=STATE_TOL)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S_all),
                               atol=STATE_TOL)
    # a row that is not live keeps its state and its tail
    _, S3, tail3 = hl.linear_token(a[:, T:], mp, cfg, S, tail,
                                   jnp.zeros((1,), bool), jnp.float32)
    assert np.array_equal(np.asarray(S3), np.asarray(S))
    assert np.array_equal(np.asarray(tail3), np.asarray(tail))


def test_gqa_attention_serves_each_kv_head_to_its_query_heads():
    cfg = get_config(MODEL)
    sizes = sizes_of(cfg)
    mp = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32),
        ref.layer_weights(ref.seed_key(SEED), sizes, 0)["mixer"])
    a = jax.random.normal(jax.random.key(2), (1, 24, cfg.hidden_size))
    q, k, v = hl._gqa_project(a, mp, cfg, jnp.float32)
    pos = jnp.arange(24, dtype=jnp.int32)[None]
    got = hl._gqa_out(hl.gqa_attend(q, k, v, pos, cfg, jnp.float32), a, mp,
                      jnp.float32)
    want = ref.gqa(a[0], mp, sizes, "f32")
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-4)


# ------------------------------------------------------- (c) the shares add up

def test_shares_add_up_to_the_uncut_layer():
    """All shares' parts of one expert layer (two at this size, each told
    its own ``expert_first``), the shared expert counted once, against the
    reference's uncut layer."""
    cfg = get_config(MODEL)
    sizes = sizes_of(cfg)
    E, Eh = cfg.n_routed_experts, cfg.experts_held
    assert E // Eh == 2
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
        part, n = lm.held_experts(
            f, idx, gates, jnp.ones((50,), bool),
            {k: x[None] for k, x in w["experts"].items()}, 0, share,
            jnp.float32)
        total = total + part
        counts.append(np.asarray(n))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-3)
    assert int(np.sum(counts)) == 50 * cfg.num_experts_per_tok


# ------------------------------------------------------------ (e) refusals

@pytest.mark.parametrize("what", ["prefix_sharing", "kv_int8", "weights_int8",
                                  "speculative_pair", "handoff"])
def test_refusals_are_loud_and_at_construction(tok, what):
    base = dict(model=MODEL, decode_slots=4, decode_max_len=64,
                max_seq_len=64)

    def build(**kw):
        args = {k: kw.pop(k) for k in ("kv_dtype", "serve_dtype") if k in kw}
        return PagedDecodeEngine(Args(**base, **args), tokenizer=tok,
                                 mesh=None, buckets=(16,), **kw)

    if what == "prefix_sharing":
        with pytest.raises(ValueError, match="prefix sharing"):
            build(prefix_share=True)
        # asked for nothing, the engine shares nothing and looks nothing up
        eng = build()
        assert eng.prefix_share is False and eng.peek_prefix([5, 6]) is None
    elif what == "kv_int8":
        with pytest.raises(ValueError, match="int8 cache"):
            build(kv_dtype="int8")
    elif what == "weights_int8":
        with pytest.raises(ValueError, match="int8 weights"):
            build(serve_dtype="int8")
    elif what == "speculative_pair":
        eng = build()
        with pytest.raises(ValueError, match="speculative pair"):
            DecodeBatcher(eng, drafter=eng)
    else:
        eng = build()
        with pytest.raises(ValueError, match="disaggregated handoff"):
            PrefillWorker(eng, dispatch=lambda *a: None)
        with pytest.raises(ValueError, match="disaggregated handoff"):
            eng.warmup_handoff()


# -------------------------------------------------- (f) spans and counters

def test_the_leaves_carry_state_bytes_and_the_snapshot_the_state_pool(served):
    from pdnlp_tpu.obs.phases import decode_host_phases, format_decode_table

    eng, recs = served["engine"], served["records"]
    cfg = eng.cfg
    per_slot = cfg.num_linear_layers * (
        cfg.linear_num_heads * cfg.linear_head_dim ** 2 * 4
        + (cfg.conv_kernel - 1) * 3 * cfg.linear_width * 4)    # float32 here
    assert eng.state_bytes == per_slot
    steps = [r["attrs"] for r in recs if r["name"] == "decode.dispatch"
             and r["attrs"].get("phase") == "decode"]
    pre = [r["attrs"] for r in recs if r["name"] == "prefill.dispatch"
           and r["attrs"].get("phase") == "prefill"]
    assert steps and pre
    # a decode step reads and writes the launched rows' states; a prefill
    # writes its streams' final ones
    assert {a["state_bytes"] for a in steps} == {2 * 4 * per_slot}
    assert {a["state_bytes"] for a in pre} == {per_slot}
    # bytes a token count the layers that PAGE, not all eight
    tb = cfg.num_gqa_layers * 2 * cfg.kv_width * 4
    assert eng.token_bytes == tb == families.token_bytes(cfg, jnp.float32)
    assert all(a["cache_bytes_per_token"] == tb for a in steps)
    kv = served["kv"]
    assert kv["state_pool_bytes"] == 4 * per_slot
    assert kv["kv_pool_bytes"] == kv["cache_bytes"] == eng.n_pages * PAGE * tb
    assert eng._pools[0].shape == (cfg.num_gqa_layers, eng.n_pages, PAGE,
                                   cfg.kv_width)
    assert len(eng._states) == 2 * cfg.num_linear_layers
    fetch = [r["attrs"] for r in recs if r["name"] == "decode.fetch"]
    assert all("expert_assignments" in a for a in fetch)
    table = decode_host_phases(recs)["0"]
    assert table["state_bytes_per_step"] == 2 * 4 * per_slot
    text = format_decode_table({"0": table})
    assert "recurrent state a decode step" in text


def test_the_other_families_keep_no_state_and_their_snapshot_says_zero(tok):
    for model in ("bert-tiny-long", "ax-k1-share-tiny"):
        eng = PagedDecodeEngine(
            Args(model=model, decode_slots=4, decode_max_len=64,
                 max_seq_len=64), tokenizer=tok, mesh=None, buckets=(16,))
        assert eng._states == () and eng.state_bytes == 0
        assert eng.prefix_share is True          # the default still shares
        assert eng.kv_snapshot()["state_pool_bytes"] == 0


def test_a_budget_pays_for_the_slots_state_before_it_pays_for_a_page(tok):
    base = dict(model=MODEL, decode_slots=4, decode_max_len=64,
                max_seq_len=64, dtype="float32")
    free = PagedDecodeEngine(Args(**base), tokenizer=tok, mesh=None,
                             buckets=(16,), page_sz=PAGE)
    state = 4 * free.state_bytes
    need = free.pages_per_stream * free.page_bytes
    mb = (state + need + free.page_bytes) / 2 ** 20
    capped = PagedDecodeEngine(Args(kv_hbm_mb=mb, **base), tokenizer=tok,
                               mesh=None, buckets=(16,), page_sz=PAGE)
    assert capped.n_pages == free.pages_per_stream + 1 < free.n_pages
    with pytest.raises(KVBudgetExceeded, match="per-slot state"):
        PagedDecodeEngine(Args(kv_hbm_mb=(state + need / 2) / 2 ** 20,
                               **base), tokenizer=tok, mesh=None,
                          buckets=(16,), page_sz=PAGE)


# ------------------------------------------------------------- the presets

def test_the_preset_is_the_stated_share():
    cfg = get_config("solar-open2-ep16-share")
    assert (cfg.num_layers, cfg.period, cfg.num_gqa_layers,
            cfg.num_linear_layers, cfg.experts_held, cfg.n_routed_experts,
            cfg.num_experts_per_tok, cfg.vocab_size) \
        == (8, 4, 2, 6, 20, 320, 8, 24576)
    assert [l for l in range(8) if cfg.is_gqa(l)] == [0, 4]
    # ISSUE 35's arithmetic: 109.05 M and 137.73 M a mixer, 3.90 G in all
    gqa = sum(int(np.prod(s)) for s in hl.layer_shapes(cfg, 0)["mixer"].values())
    lin = sum(int(np.prod(s)) for s in hl.layer_shapes(cfg, 1)["mixer"].values())
    assert abs(gqa / 1e6 - 109.05) < 0.02 and abs(lin / 1e6 - 137.73) < 0.02
    assert abs(hl.param_count(cfg) / 1e9 - 3.90) < 0.005
    # 8 192 bytes a cached token; 25.2 MB of state + 0.9 MB of tails a slot
    assert families.token_bytes(cfg, jnp.bfloat16) == 8192
    per_slot = sum(int(np.prod(s[1:])) * jnp.dtype(dt or jnp.bfloat16).itemsize
                   for s, dt in hl.state_shapes(cfg, 64))
    assert per_slot == 6 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)
    with pytest.raises(ValueError, match="whole number of periods"):
        cfg.replace(num_layers=6)
    whole = get_config("solar-open2-ep16-share").replace(
        num_layers=48, experts_held=320, vocab_size=196_608)
    assert abs(hl.param_count(whole) / 1e9 - 250.3) < 0.2


def test_the_two_copies_of_the_reference_are_one_text():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "solar_open2_reference.py")) as f:
        mine = f.read()
    with open(os.path.join(here, "..", "benchmark", "reference",
                           "solar_open2.py")) as f:
        theirs = f.read()
    assert mine == theirs


def test_the_reference_imports_nothing_from_the_program():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "solar_open2_reference.py")) as f:
        text = f.read()
    assert "pdnlp_tpu" not in text.split('"""', 2)[2]
    assert "HIGHEST" in text and "lax.scan" in text
