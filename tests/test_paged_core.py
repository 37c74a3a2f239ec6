"""The ONE paged-attention core (``models.decoder.paged_attend_layers``)
and its three thin callers, against a float32 DENSE recompute — T = 1, T > 1
with ragged ``nreal``, the head at every position — plus what the core must
never do (dead rows, sentinel tables and filler write nothing), the int8
path, the decode step's kernel (``ops/paged.py``) against the gathered form it
replaces — and under NaN in every position it must not read —, greedy tokens equal to the dense recompute's over a stream that crosses
every rung of the decode extent and every page boundary, and the structural
guard: the donated pools are aliased to the outputs and no temporary is as
large as a pool (the pool is never rebuilt)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
from pdnlp_tpu.models import bert, decoder, get_config
from pdnlp_tpu.ops import paged
from pdnlp_tpu.ops.attention import mask_bias
from pdnlp_tpu.serve import DecodeBatcher, PagedDecodeEngine
from pdnlp_tpu.utils.config import Args

L_PAGES, PS, MP, ROWS = 24, 8, 6, 3       # pool pages, page size, table width
MAX_LEN = PS * MP


@pytest.fixture(scope="module")
def model():
    cfg = get_config("bert-tiny", num_labels=6, dropout=0.0,
                     attn_dropout=0.0)
    params = bert.init_params(jax.random.key(0), cfg)
    head = decoder.init_lm_head(jax.random.key(1), cfg)
    return cfg, params, head


def empty_pools(cfg, dtype=jnp.float32):
    shape = (cfg.num_layers, L_PAGES, PS, cfg.hidden_size)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def tables(seed=0, rows=ROWS):
    """Distinct physical pages per row, in a scrambled order."""
    perm = np.random.default_rng(seed).permutation(L_PAGES)
    return perm[:rows * MP].reshape(rows, MP).astype(np.int32)


def dense_logits(model, ids):
    """Float32 causal forward over whole sequences: [B, S, vocab]."""
    cfg, params, head = model
    ids = jnp.asarray(ids, jnp.int32)
    x, _ = bert.embed(params, cfg, ids, jnp.zeros_like(ids),
                      deterministic=True)
    hidden, _, _ = decoder.run_layers_kv(
        params["layers"], cfg, x,
        bias=mask_bias(jnp.ones_like(ids), jnp.float32), causal=True)
    return np.asarray(decoder.lm_logits(params, head, cfg, hidden))


def sequences(vocab, lens, seed=5):
    rng = np.random.default_rng(seed)
    ids = np.zeros((len(lens), max(lens)), np.int32)
    for i, n in enumerate(lens):
        ids[i, :n] = rng.integers(5, vocab, n)
    return ids


# ------------------------------------------------- against the dense model

def test_chunk_then_decode_steps_match_dense_recompute(model):
    """T > 1 with ragged ``nreal`` (the suffix chunk, head at the last real
    token), then T = 1 steps across page boundaries, each against the
    float32 dense forward of the same tokens."""
    cfg, params, head = model
    lens = [13, 5, 9]                       # ragged, none page-aligned
    total = 22                              # decode on past two boundaries
    ids = sequences(cfg.vocab_size, [total] * ROWS)
    want = dense_logits(model, ids)         # [B, total, V]
    pk, pv = empty_pools(cfg)
    table = tables()
    T = 16
    chunk = np.zeros((ROWS, T), np.int32)
    for i, n in enumerate(lens):
        chunk[i, :n] = ids[i, :n]
    logits, pk, pv = decoder.paged_chunk_step(
        params, head, cfg, chunk, pk, pv, table, np.zeros(ROWS, np.int32),
        np.asarray(lens, np.int32))
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(logits[i]), want[i, n - 1],
                                   atol=2e-4, rtol=2e-4)
    pos = np.asarray(lens, np.int32)
    step = jax.jit(lambda pk, pv, tok, pos: decoder.paged_decode_step(
        params, head, cfg, tok, pk, pv, table, pos))
    while pos.min() < total:
        live = pos < total
        tok = ids[np.arange(ROWS), np.minimum(pos, total - 1)][:, None]
        logits, pk, pv = step(pk, pv, tok, np.minimum(pos, total - 1))
        for i in np.flatnonzero(live):
            np.testing.assert_allclose(np.asarray(logits[i]),
                                       want[i, pos[i]], atol=2e-4,
                                       rtol=2e-4)
        pos = pos + live


def test_verify_reads_the_head_at_every_position(model):
    cfg, params, head = model
    base, K1 = 11, 5
    ids = sequences(cfg.vocab_size, [base + K1] * ROWS, seed=9)
    want = dense_logits(model, ids)
    pk, pv = empty_pools(cfg)
    table = tables(seed=1)
    _, pk, pv = decoder.paged_chunk_step(
        params, head, cfg, ids[:, :base], pk, pv, table,
        np.zeros(ROWS, np.int32), np.full(ROWS, base, np.int32))
    nreal = np.asarray([K1, 2, K1], np.int32)   # a short window rides too
    logits, _, _ = decoder.paged_verify_step(
        params, head, cfg, ids[:, base:], pk, pv, table,
        np.full(ROWS, base, np.int32), nreal)
    assert logits.shape == (ROWS, K1, cfg.vocab_size)
    for i, n in enumerate(nreal):
        np.testing.assert_allclose(np.asarray(logits[i, :n]),
                                   want[i, base:base + n], atol=2e-4,
                                   rtol=2e-4)


@pytest.mark.parametrize("fold_rows", [0, 10 ** 6])
def test_heads_folded_or_split_out_give_the_dense_logits(model, fold_rows,
                                                         monkeypatch):
    """The core picks its attention form by the window's shape (query rows
    against ``FOLD_ROWS``); forced either way, a chunk and a decode step
    both score what the dense forward scores."""
    monkeypatch.setattr(decoder, "FOLD_ROWS", fold_rows)
    cfg, params, head = model
    n = 13
    ids = sequences(cfg.vocab_size, [n + 1] * ROWS, seed=29)
    want = dense_logits(model, ids)
    pk, pv = empty_pools(cfg)
    table = tables(seed=3)
    first, pk, pv = decoder.paged_chunk_step(
        params, head, cfg, ids[:, :n], pk, pv, table,
        np.zeros(ROWS, np.int32), np.full(ROWS, n, np.int32))
    nxt, _, _ = decoder.paged_decode_step(
        params, head, cfg, ids[:, n:], pk, pv, table,
        np.full(ROWS, n, np.int32))
    np.testing.assert_allclose(np.asarray(first), want[:, n - 1], atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(nxt), want[:, n], atol=2e-4,
                               rtol=2e-4)


def test_narrow_table_gives_the_same_tokens_as_the_whole_one(model):
    """The extent is the table's width: a table cut to the pages that hold
    live positions scores the same as the full-width one."""
    cfg, params, head = model
    n = 10                                   # two pages of eight
    ids = sequences(cfg.vocab_size, [n + 1] * ROWS, seed=13)
    pk, pv = empty_pools(cfg)
    table = tables(seed=2)
    _, pk, pv = decoder.paged_chunk_step(
        params, head, cfg, ids[:, :n], pk, pv, table,
        np.zeros(ROWS, np.int32), np.full(ROWS, n, np.int32))
    pos = np.full(ROWS, n, np.int32)
    wide, _, _ = decoder.paged_decode_step(
        params, head, cfg, ids[:, n:], pk, pv, table, pos)
    narrow, _, _ = decoder.paged_decode_step(
        params, head, cfg, ids[:, n:], pk, pv, table[:, :2], pos)
    np.testing.assert_allclose(np.asarray(narrow), np.asarray(wide),
                               atol=1e-5, rtol=1e-5)


# --------------------------------------------- what must never be written

@pytest.mark.parametrize("program", ["decode", "chunk", "verify", "insert"])
def test_dead_rows_and_sentinel_tables_write_nothing(model, program):
    """Filler rows (``nreal == 0``), padded window slots, sentinel table
    rows and sentinel flat positions leave every byte of both pools as it
    was — in every layer (a sentinel must not alias into the next layer's
    rows of the flat view)."""
    cfg, params, head = model
    rng = np.random.default_rng(3)
    shape = (cfg.num_layers, L_PAGES, PS, cfg.hidden_size)
    pk = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    pv = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    dead = np.full((ROWS, MP), L_PAGES, np.int32)        # sentinel tables
    live = tables(seed=4)
    tok = sequences(cfg.vocab_size, [6] * ROWS, seed=4)
    zero = np.zeros(ROWS, np.int32)
    if program == "decode":
        _, k2, v2 = decoder.paged_decode_step(
            params, head, cfg, tok[:, :1], pk, pv, dead, zero + 3)
    elif program == "chunk":                 # live tables, but no real token
        _, k2, v2 = decoder.paged_chunk_step(
            params, head, cfg, tok, pk, pv, live, zero, zero)
    elif program == "verify":                # positions past the extent
        _, k2, v2 = decoder.paged_verify_step(
            params, head, cfg, tok, pk, pv, live, zero + MAX_LEN, zero + 6)
    else:
        ks = jnp.ones((cfg.num_layers, ROWS, PS, cfg.num_heads,
                       cfg.head_dim), jnp.float32)
        flat = np.full((ROWS, PS), L_PAGES * PS, np.int32)
        k2, v2 = decoder.paged_insert(pk, pv, ks, ks, flat)
        k2, v2 = decoder.paged_insert(k2, v2, ks, ks,      # and by page
                                      np.full((ROWS, 1), L_PAGES, np.int32))
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(pk))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(pv))


def test_insert_by_whole_pages_equals_insert_by_position(model):
    """``paged_insert`` reads its granularity off the index's width: whole
    pages land where the positions would, take the padded tail of the last
    page with them (no query can see it), and touch no other page."""
    cfg, params, head = model
    S, lens = 16, [16, 5, 11]                 # two pages of eight a row
    rng = np.random.default_rng(23)
    ks = jnp.asarray(rng.standard_normal(
        (cfg.num_layers, ROWS, S, cfg.num_heads, cfg.head_dim)), jnp.float32)
    table = tables(seed=8)
    by_pos = np.full((ROWS, S), L_PAGES * PS, np.int32)
    by_page = np.full((ROWS, S // PS), L_PAGES, np.int32)
    for i, n in enumerate(lens):
        p = np.arange(n)
        by_pos[i, :n] = table[i, p // PS] * PS + p % PS
        by_page[i, :-(-n // PS)] = table[i, :-(-n // PS)]
    a, _ = decoder.paged_insert(*empty_pools(cfg), ks, ks, by_pos)
    b, _ = decoder.paged_insert(*empty_pools(cfg), ks, ks, by_page)
    a, b = np.asarray(a), np.asarray(b)
    named = {int(p) for i, n in enumerate(lens)
             for p in table[i, :-(-n // PS)]}
    for page in range(L_PAGES):
        if page not in named:
            assert not b[:, page].any()
    for i, n in enumerate(lens):
        p = np.arange(n)
        np.testing.assert_array_equal(b[:, table[i, p // PS], p % PS],
                                      a[:, table[i, p // PS], p % PS])
    with pytest.raises(ValueError, match="neither"):
        decoder.paged_insert(*empty_pools(cfg), ks, ks, by_pos[:, :5])


def test_a_live_row_writes_only_its_own_position(model):
    cfg, params, head = model
    pk, pv = empty_pools(cfg)
    table = tables(seed=6)
    table[1:] = L_PAGES                      # rows 1, 2 are dead
    tok = sequences(cfg.vocab_size, [1] * ROWS, seed=6)
    pos = np.asarray([PS + 2, 0, 0], np.int32)
    _, k2, _ = decoder.paged_decode_step(params, head, cfg, tok, pk, pv,
                                         table, pos)
    written = np.argwhere(np.abs(np.asarray(k2)).sum(-1) > 0)
    want = [[l, table[0, 1], 2] for l in range(cfg.num_layers)]
    assert written.tolist() == want


# ----------------------------------------------------------------- int8

def test_int8_pool_round_trips_through_the_cache_like_the_dense_model(model):
    """The int8 pool holds what ``quantize_kv`` makes of the prefill's rows,
    position for position (exactly: the strong half), and the int8 step's
    logits follow the float32 dense forward of the same tokens to within
    the quantization's error (measured 4e-4 on these seeded weights, whose
    logits reach 1.0; a read that skips the dequantize is off by orders)."""
    cfg, params, head = model
    ks, vs = decoder.calibrate_kv_scales(params, cfg, seq_len=32)
    scales = (jnp.asarray(ks), jnp.asarray(vs))
    n, steps = 9, 4
    ids = sequences(cfg.vocab_size, [n + steps] * ROWS, seed=17)
    want = dense_logits(model, ids)
    mask = np.ones((ROWS, n), np.int32)
    _, pks, pvs = decoder.prefill(params, head, cfg, ids[:, :n], mask,
                                  np.full(ROWS, n - 1, np.int32))
    pk, pv = empty_pools(cfg, jnp.int8)
    table = tables(seed=7)
    p = np.arange(n)
    flat = table[:, p // PS] * PS + p % PS
    pk, pv = decoder.paged_insert(pk, pv, pks, pvs, flat, kv_scales=scales)
    assert pk.dtype == jnp.int8
    H = cfg.hidden_size
    qk = np.asarray(decoder.quantize_kv(pks, ks[:, None, None]))
    for i in range(ROWS):
        np.testing.assert_array_equal(
            np.asarray(pk)[:, table[i, p // PS], p % PS],
            qk[:, i].reshape(cfg.num_layers, n, H))
    worst = 0.0
    for t in range(steps):
        tok = ids[:, n + t][:, None]
        pos = np.full(ROWS, n + t, np.int32)
        got, pk, pv = decoder.paged_decode_step(
            params, head, cfg, tok, pk, pv, table, pos, kv_scales=scales)
        got = np.asarray(got)
        worst = max(worst, float(np.abs(got - want[:, n + t]).max()))
        # the step wrote its own token's quantized K where the table says
        assert np.abs(np.asarray(pk)[:, table[:, (n + t) // PS],
                                     (n + t) % PS]).sum() > 0
    assert worst < 2e-3, worst


# ------------------------------- the decode step's kernel (ops/paged.py)

K_PS, K_MP, K_N, K_D = 16, 20, 2, 8        # page size, table width, heads
K_P = 24 * K_MP + 4                        # a layer's pages (layer 1 of 2)


def kernel_case(rows, dtype, seed=0):
    """Random pools of two layers, queries, a shuffled table into the second
    layer, lengths at a page's edges, dead rows and sentinels."""
    rng = np.random.default_rng(seed)
    H, extent = K_N * K_D, K_PS * K_MP
    pools = [jnp.asarray(rng.standard_normal((2 * K_P, K_PS, H)), dtype)
             for _ in range(2)]
    q = jnp.asarray(rng.standard_normal((rows, 1, K_N, K_D)), jnp.float32)
    lengths = rng.integers(1, extent + 1, rows).astype(np.int32)
    # a page's edges, a block's edges (``paged.BLOCK``: the extent holds
    # one whole block and a part), dead rows
    lengths[:8] = [1, K_PS, K_PS + 1, extent, 0, paged.BLOCK + 1, 0,
                   paged.BLOCK]
    table = rng.permutation(K_P)[:rows * K_MP].reshape(rows, K_MP)
    # a sentinel tail after a live row's own pages, sentinel tables for the
    # dead rows (flat ids: the sentinel of layer 1 is the pool's end)
    table = np.where(np.arange(K_MP)[None] * K_PS < lengths[:, None],
                     table, K_P).astype(np.int32) + K_P
    return q, pools, table, lengths


def gathered(q, pools, table, lengths):
    """The form the kernel replaces: ``get`` + ``_attend_folded``."""
    B, extent = table.shape[0], table.shape[1] * K_PS
    k, v = (jnp.take(p, table, axis=0, mode="clip").reshape(B, extent, -1)
            for p in pools)
    bias = jnp.where(jnp.arange(extent)[None, None, None]
                     < lengths[:, None, None, None], 0.0, decoder.NEG_INF)
    return np.asarray(decoder._attend_folded(
        q, k.astype(q.dtype), v.astype(q.dtype), bias))


@pytest.mark.parametrize("rows,dtype", [
    (16, jnp.float32), (24, jnp.float32), (16, jnp.bfloat16),
    (24, jnp.bfloat16)])
def test_the_kernel_equals_the_gathered_form(rows, dtype):
    """Interpreted, on the same pools: lengths 1, a page, a page and one,
    a block, a block and one, the whole extent (two blocks); dead rows and
    sentinel tables give zeros; a sentinel tail in a live row's table is
    never followed."""
    assert paged.BLOCK < K_PS * K_MP < 2 * paged.BLOCK
    q, pools, table, lengths = kernel_case(rows, dtype)
    got = np.asarray(decoder._attend_paged(q, *pools, table, lengths))
    want = gathered(q, pools, table, lengths)
    live = lengths > 0
    assert not got[~live].any()
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=2e-5)


def test_the_kernel_reads_a_rows_live_pages_and_nothing_else():
    """Every page no row owns, and every row's last page past ``pos``, holds
    NaN: the output is still the gathered form's on the clean pools (which
    itself cannot pass this: it multiplies what it masks)."""
    q, pools, table, lengths = kernel_case(16, jnp.float32, seed=1)
    want = gathered(q, pools, table, lengths)
    owned = np.zeros((2 * K_P, K_PS), bool)
    for row, n in zip(table, lengths):
        p = np.arange(n)
        owned[row[p // K_PS], p % K_PS] = True
    poisoned = [jnp.where(owned[:, :, None], p, jnp.nan) for p in pools]
    assert np.isnan(np.asarray(poisoned[0])).mean() > 0.5
    got = np.asarray(decoder._attend_paged(q, *poisoned, table, lengths))
    live = lengths > 0
    assert not got[~live].any()
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=2e-5)
    assert np.isnan(gathered(q, poisoned, table, lengths)[live]).any()


@pytest.mark.parametrize("program,kernel", [
    ("decode", True), ("decode-int8", False), ("chunk", False),
    ("verify", False), ("decode-on-a-mesh", False)])
def test_only_the_float_decode_step_takes_the_kernel(model, program, kernel):
    """Told apart by shape and dtype, not by a flag: T = 1 over a float pool
    calls the kernel once a layer; the int8 pool, the chunk and the
    verify window keep the gathered form (none) — and so does the step of a
    caller that says its ``jit`` runs over several devices, where Mosaic
    refuses a kernel (``decoder.attend_form`` is the one place that
    decides, and what the engine's span reports)."""
    from pdnlp_tpu.parallel import make_mesh

    cfg, params, head = model
    table, zero = tables(), np.zeros(ROWS, np.int32)
    tok = sequences(cfg.vocab_size, [4] * ROWS)
    mesh = make_mesh(num_devices=2) if program.endswith("mesh") else None
    assert decoder.attend_form(
        1 if program.startswith("decode") else 4, program == "decode-int8",
        mesh) == ("kernel" if kernel else "gather")
    if program == "decode-int8":
        ks, vs = decoder.calibrate_kv_scales(params, cfg, seq_len=32)
        jaxpr = jax.make_jaxpr(lambda pk, pv: decoder.paged_decode_step(
            params, head, cfg, tok[:, :1], pk, pv, table, zero + 3,
            kv_scales=(jnp.asarray(ks), jnp.asarray(vs))))(
                *empty_pools(cfg, jnp.int8))
    else:
        step = {"decode": lambda pk, pv: decoder.paged_decode_step(
                    params, head, cfg, tok[:, :1], pk, pv, table, zero + 3),
                "decode-on-a-mesh": lambda pk, pv: decoder.paged_decode_step(
                    params, head, cfg, tok[:, :1], pk, pv, table, zero + 3,
                    mesh=mesh),
                "chunk": lambda pk, pv: decoder.paged_chunk_step(
                    params, head, cfg, tok, pk, pv, table, zero, zero + 4),
                "verify": lambda pk, pv: decoder.paged_verify_step(
                    params, head, cfg, tok, pk, pv, table, zero + 3,
                    zero + 4)}[program]
        jaxpr = jax.make_jaxpr(step)(*empty_pools(cfg))
    # jitted, so the layers share ONE traced kernel: it is lowered once
    assert str(jaxpr).count("pallas_call") == (1 if kernel else 0)


# ------------------------------------------- the engines, rung by rung

TEXTS = ["天地人你我", "好坏大小上下来去" * 5, "爱恨喜怒哀乐" * 15]


@pytest.fixture(scope="module")
def engines():
    tok = WordPieceTokenizer(build_vocab(TEXTS, size=128))
    args = Args(model="bert-tiny", decode_slots=4, decode_max_len=64,
                max_new_tokens=8)
    pag = PagedDecodeEngine(args, tokenizer=tok, mesh=None, buckets=(16,),
                            page_sz=8)
    pag.warmup_decode()
    return tok, pag


def drive(eng, prompt, max_new):
    b = DecodeBatcher(eng, replica=0)
    b.eos_id = -1
    b.start()
    out = b.submit_ids(prompt, max_new_tokens=max_new).result(timeout=300)
    b.stop()
    return out


def test_greedy_tokens_equal_the_dense_recomputes_across_every_rung(engines):
    """One stream from 9 positions to the limit: it crosses every page
    boundary and every rung of the decode extent; every token it serves is
    the argmax of the float32 dense forward over everything before it (the
    served sequence re-scored whole, one wide pass), and nothing is traced
    on the way."""
    tok, pag = engines
    assert pag.decode_rungs == [2, 4, 6, 8]
    before = pag.metrics.retraces.value
    seen0 = set(pag._seen_shapes)
    prompt = np.random.default_rng(11).integers(5, tok.vocab_size, 9).tolist()
    new = 64 - 9 - 1
    served = drive(pag, prompt, new)
    assert len(served) == new
    want = dense_logits((pag.cfg, pag.params, pag.head),
                        [prompt + served])[0]
    # position t's logits choose token t + 1
    chosen = np.argmax(want[len(prompt) - 1:-1], -1).tolist()
    assert served == chosen
    assert pag.metrics.retraces.value == before
    assert set(pag._seen_shapes) == seen0
    assert {k[2] for k in seen0 if k[0] == "decode"} == {2, 4, 6, 8}
    assert pag.leak_check()["ok"]


# ------------------------------------------------- the structural guard

def _compiled_memory(fn, donate, *shapes):
    m = jax.jit(fn, donate_argnums=donate).lower(*shapes).compile() \
        .memory_analysis()
    if m is None or not getattr(m, "alias_size_in_bytes", 0):
        pytest.skip("this backend's memory_analysis() reports no aliasing")
    return m


@pytest.mark.parametrize("program", ["decode", "chunk", "insert"])
def test_the_pool_is_aliased_and_never_rebuilt(model, program):
    """Lower and compile the paged programs with donated pools: the aliased
    bytes cover both pools, and the temporaries stay under ONE pool's bytes
    — a pool that is sliced, re-stacked or converted shows up as a
    pool-sized temporary.  (``tests/test_chip_compile.py`` asks the chip's
    compiler the same at the chip's layout.)"""
    cfg, params, head = model
    P, B = 1024, 4                           # a pool far above the logits
    S = jax.ShapeDtypeStruct
    pool = S((cfg.num_layers, P, PS, cfg.hidden_size), jnp.float32)
    pool_bytes = int(np.prod(pool.shape)) * 4
    i32 = jnp.int32
    if program == "decode":
        m = _compiled_memory(
            lambda pk, pv, tok, table, pos: decoder.paged_decode_step(
                params, head, cfg, tok, pk, pv, table, pos),
            (0, 1), pool, pool, S((B, 1), i32), S((B, MP), i32), S((B,), i32))
    elif program == "chunk":
        m = _compiled_memory(
            lambda pk, pv, tok, table, start, nreal: decoder.paged_chunk_step(
                params, head, cfg, tok, pk, pv, table, start, nreal),
            (0, 1), pool, pool, S((B, 16), i32), S((B, MP), i32),
            S((B,), i32), S((B,), i32))
    else:
        kv = S((cfg.num_layers, B, 16, cfg.num_heads, cfg.head_dim),
               jnp.float32)
        m = _compiled_memory(decoder.paged_insert, (0, 1), pool, pool, kv,
                             kv, S((B, 16 // PS), i32))
    assert m.alias_size_in_bytes >= 2 * pool_bytes
    assert m.temp_size_in_bytes < pool_bytes
