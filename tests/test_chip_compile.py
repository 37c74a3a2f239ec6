"""Ask the chip's compiler, without the chip.

The TPU compiler is installed next to JAX and compiles for a topology that
is described, not attached (``on-chip-measurement`` guide, section 2).  The
interpret-mode kernel tests cannot see what Mosaic refuses — a block shape
that breaks the (8, 128) tiling rule passed every one of them and was
refused at every width above 128 — so the kernels of the main path are
compiled here for ``v5e`` at bert-base's real widths, and the whole train
step once.  Nothing runs: this says a program compiles, never that it is
right or fast.

Code that asks ``jax.default_backend()`` sees the CPU here, so the test
steers ``_interpret`` itself (monkeypatch), not through an option.
"""
import base64
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from pdnlp_tpu.models import bert, get_config
from pdnlp_tpu.ops import flash, fused_ce
from pdnlp_tpu.train.optim import build_optimizer
from pdnlp_tpu.train.steps import init_state, make_train_step
from pdnlp_tpu.utils.config import Args
from pdnlp_tpu.utils.seeding import train_key

B, N, D, H, C = 8, 12, 64, 768, 6     # bert-base heads; the issue's B*N = 96


@pytest.fixture(scope="module")
def one_chip():
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    """Mosaic, not the interpreter (both modules bind ``_interpret``)."""
    monkeypatch.setattr(flash, "_interpret", lambda: False)
    monkeypatch.setattr(fused_ce, "_interpret", lambda: False)


def _attn_loss(mask: str):
    """Scalar of the attention output; ``mask`` names the mask argument."""
    return lambda q, k, v, m: flash.flash_attention(
        q, k, v, **{mask: m}).astype(jnp.float32).sum()


def _flash_case(kind, S):
    q = jax.ShapeDtypeStruct((B, S, N, D), jnp.bfloat16)
    bias = jax.ShapeDtypeStruct((B, 1, 1, S), jnp.float32)
    seg = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if kind == "biased-fwd":
        return _attn_loss("bias"), (q, q, q, bias)
    if kind == "segmented-fwd":
        return _attn_loss("segment_ids"), (q, q, q, seg)
    return (jax.grad(_attn_loss("segment_ids"), argnums=(0, 1, 2)),
            (q, q, q, seg))


def _fused_ce_case():
    def loss(f, w, b, y, ew):
        return fused_ce.fused_weighted_ce(f, w, b, y, ew)[2]

    return jax.value_and_grad(loss, argnums=(0, 1, 2)), (
        jax.ShapeDtypeStruct((64, H), jnp.bfloat16),
        jax.ShapeDtypeStruct((H, C), jnp.bfloat16),
        jax.ShapeDtypeStruct((C,), jnp.bfloat16),
        jax.ShapeDtypeStruct((64,), jnp.int32),
        jax.ShapeDtypeStruct((64,), jnp.float32))


def _train_step_case():
    """The whole jitted bert-base step (batch 64, seq 128, bf16, fused-CE
    kernel, all twelve layers) from ``eval_shape`` shapes.  The layer scan
    is kept rolled: the same layers compile in ~11 s instead of the ~50 s
    of the default full unroll, which ``chip_smoke.py`` compiles anyway."""
    args = Args(model="bert-base", dtype="bfloat16", train_batch_size=64,
                fused_ce="pallas", scan_unroll=1)
    cfg = get_config(args.model, num_labels=C, dropout=args.dropout,
                     attn_dropout=args.attn_dropout)
    params = jax.eval_shape(lambda: bert.init_params(jax.random.key(0), cfg))
    tx = build_optimizer(params, args)
    state = jax.eval_shape(lambda: init_state(
        jax.random.key(0), cfg, tx, rng=train_key(args.seed, args.rng_impl)))
    ids = jax.ShapeDtypeStruct((64, 128), jnp.int32)
    batch = {"input_ids": ids, "token_type_ids": ids, "attention_mask": ids,
             "label": jax.ShapeDtypeStruct((64,), jnp.int32),
             "example_weight": jax.ShapeDtypeStruct((64,), jnp.float32)}
    return make_train_step(cfg, tx, args), (state, batch)


# (builder, does the kernel carry grid dimension semantics): the flash grid
# hints must reach Mosaic — they were silently dropped when the params class
# was renamed; fused CE runs a 1-D sequential grid and sets none
CASES = [pytest.param(lambda k=k, S=S: _flash_case(k, S), True,
                      id=f"flash-{k}-{S}")
         for k in ("biased-fwd", "segmented-fwd", "segmented-bwd")
         for S in (128, 512)]
CASES.append(pytest.param(_fused_ce_case, False,
                          id="fused_ce-fwd+grad-64x768"))
CASES.append(pytest.param(_train_step_case, False, id="bert-base-train-step"))


def _mosaic_bodies(lowered_text: str):
    """The serialized Mosaic modules of every ``tpu_custom_call``."""
    return [base64.b64decode(m) for m in re.findall(
        r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', lowered_text)]


@pytest.mark.parametrize("case,grid_hints", CASES)
def test_compiles_for_v5e(case, grid_hints, one_chip):
    from jax.experimental.compilation_cache import compilation_cache as cc

    fn, shapes = case()
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    # a described-topology compile is written to the persistent cache but
    # cannot be read back without a chip: keep it off around these
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        fn = fn if hasattr(fn, "lower") else jax.jit(fn)
        lowered = fn.lower(*shapes)
        compiled = lowered.compile()   # raises what the chip's compiler would
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
    bodies = _mosaic_bodies(lowered.as_text())
    assert bodies
    hinted = [b for b in bodies
              if b"dimension_semantics" in b and b"parallel" in b]
    assert len(hinted) == (len(bodies) if grid_hints else 0)


# ---------------------------------------------------- the paged KV programs

def _paged_case(program):
    """A paged program at bert-base's widths (two layers: the layout is the
    pool's ``[page_sz, hidden]`` tail, not its depth), 128 rows (16 for the
    decode step's small row rung), pools of
    16 384 pages donated (0.75 GiB each: far above the step's gathered
    K/V and logits).  -> (fn, donate_argnums, shapes, pool bytes)."""
    from pdnlp_tpu.models import decoder

    cfg = get_config("bert-base", num_labels=C, dropout=0.0,
                     attn_dropout=0.0, num_layers=2)
    params = jax.eval_shape(lambda: bert.init_params(jax.random.key(0), cfg))
    head = jax.eval_shape(lambda: decoder.init_lm_head(jax.random.key(0),
                                                       cfg))
    P, ps, MP = 16384, 16, 32
    # the engine's two row rungs at 128 slots (PagedDecodeEngine.row_rungs)
    rows = 16 if program == "decode-16-rows" else 128
    S, i32, bf = jax.ShapeDtypeStruct, jnp.int32, jnp.bfloat16
    pool = S((cfg.num_layers, P, ps, H), bf)
    nbytes = cfg.num_layers * P * ps * H * 2
    if program.startswith("decode"):
        def fn(params, head, pk, pv, tok, table, pos):
            return decoder.paged_decode_step(params, head, cfg, tok, pk, pv,
                                             table, pos, dtype=bf)
        return fn, (2, 3), (params, head, pool, pool, S((rows, 1), i32),
                            S((rows, MP), i32), S((rows,), i32)), nbytes
    if program == "chunk":
        def fn(params, head, pk, pv, tok, table, start, nreal):
            return decoder.paged_chunk_step(params, head, cfg, tok, pk, pv,
                                            table, start, nreal, dtype=bf)
        return fn, (2, 3), (params, head, pool, pool, S((8, 256), i32),
                            S((8, MP), i32), S((8,), i32),
                            S((8,), i32)), nbytes
    kv = S((cfg.num_layers, 8, 256, N, D), bf)
    return decoder.paged_insert, (0, 1), (pool, pool, kv, kv,
                                          S((8, 256 // ps), i32)), nbytes


@pytest.mark.parametrize("program", ["decode", "decode-16-rows", "chunk",
                                     "insert"])
def test_paged_programs_leave_the_pool_where_it_lies(program, one_chip):
    """The chip's compiler at the chip's layout: the donated pools are
    aliased to the outputs, no temporary is as large as one pool, and the
    pool enters row-major (a ``[.., heads, 64]`` tail makes the PAGE axis
    the chip's minor one, and every program then converts the whole pool
    there and back — PERF.md, PR 26)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    fn, donate, shapes, pool_bytes = _paged_case(program)
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = jax.jit(fn, donate_argnums=donate).lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * pool_bytes
    assert m.temp_size_in_bytes < pool_bytes
    entry = compiled.as_text().split("ENTRY", 1)[1]
    pools = re.findall(r"bf16\[2,16384,16,768\]\{([0-9,]+):", entry)
    assert pools and set(pools) == {"3,2,1,0"}


# ------------------------------------ the BERT family's decode step kernel

@pytest.mark.parametrize("rows,pages", [(192, 8), (192, 32), (16, 8),
                                        (16, 32)])
def test_the_paged_decode_kernel_compiles_inside_the_decode_program(
        rows, pages, one_chip, monkeypatch):
    """``ops/paged.py`` alone, and the BERT family's whole ``_pdecode_fn`` at
    bert-base's widths and depth — the causal cells' row rungs and the page
    ladder's ends, the pools donated: Mosaic takes the kernel (the copies by
    page, the loop whose bound is data), the program calls it once a layer,
    and the pools enter it as they lie — aliased through, no ``copy`` of a
    pool-shaped operand (one would be 2.25 GiB of the cell's pool)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from pdnlp_tpu.models import families
    from pdnlp_tpu.ops import paged
    from pdnlp_tpu.serve.decode import greedy_ids

    monkeypatch.setattr(paged, "_interpret", lambda: False)
    cfg = get_config("bert-base", num_labels=C, dropout=0.0,
                     attn_dropout=0.0)
    family = families.of(cfg)
    key, bf, i32 = jax.random.key(0), jnp.bfloat16, jnp.int32
    L, P, ps = cfg.num_layers, 4096, 16

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, head = jax.tree_util.tree_map(
        lambda x: S(x.shape, x.dtype), jax.eval_shape(
            lambda: (family.init_params(key, cfg),
                     family.init_head(key, cfg))))
    pool = S((L, P, ps, H), bf)

    def _pdecode_fn(params, head, pools, tokens, table, pos):
        logits, aux, pools, _ = family.attend(
            params, head, cfg, tokens, pools, (), table, pos, None, "last",
            None, bf)
        return greedy_ids(logits), aux, pools

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        alone = paged.paged_decode.lower(
            S((rows, 16, H), bf), S((L * P, ps, H), bf),
            S((L * P, ps, H), bf), S((rows, pages), i32), S((rows,), i32),
            scale=D ** -0.5).compile()
        compiled = jax.jit(_pdecode_fn, donate_argnums=(2,)).lower(
            params, head, (pool, pool), S((rows, 1), i32),
            S((rows, pages), i32), S((rows,), i32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
    assert alone.as_text().count('custom_call_target="tpu_custom_call"') == 1
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == L
    assert text.count("paged_decode/pallas_call") == L
    m = compiled.memory_analysis()
    pool_bytes = L * P * ps * H * 2
    assert m.alias_size_in_bytes >= 2 * pool_bytes
    assert m.temp_size_in_bytes < pool_bytes // 4
    assert re.search(rf"bf16\[{L},{P},{ps},{H}\]", text)
    assert not re.search(
        rf"bf16\[({L},{P}|{L * P}),{ps},{H}\]\S* copy\(", text)


def test_a_decode_step_over_four_chips_keeps_the_gathered_form(monkeypatch):
    """An engine handed a mesh of several chips (``serve_tpu.py`` with fewer
    replicas than chips) runs its programs replicated over them, and Mosaic
    refuses a kernel in a ``jit`` over more than one device — at lowering,
    which interpret mode never reaches.  The BERT family's ``_pdecode_fn``
    with everything replicated over the described 2x2: told the mesh, as
    the engine tells it, it compiles and holds no kernel; not told, it is
    refused in Mosaic's own words."""
    import numpy as np
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from pdnlp_tpu.models import families
    from pdnlp_tpu.ops import paged

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    monkeypatch.setattr(paged, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices), ("data",))
    everywhere = NamedSharding(mesh, PartitionSpec())
    cfg = get_config("bert-base", num_labels=C, dropout=0.0,
                     attn_dropout=0.0, num_layers=2)
    family = families.of(cfg)
    key, bf, i32 = jax.random.key(0), jnp.bfloat16, jnp.int32

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=everywhere)

    params, head = jax.tree_util.tree_map(
        lambda x: S(x.shape, x.dtype), jax.eval_shape(
            lambda: (family.init_params(key, cfg),
                     family.init_head(key, cfg))))
    pool = S((cfg.num_layers, 1024, 16, H), bf)
    shapes = (params, head, (pool, pool), S((16, 1), i32), S((16, 8), i32),
              S((16,), i32))

    def _pdecode_fn(told):
        def fn(params, head, pools, tokens, table, pos):
            logits, _, pools, _ = family.attend(
                params, head, cfg, tokens, pools, (), table, pos, None,
                "last", None, bf, told)
            return logits, pools
        return jax.jit(fn, donate_argnums=(2,))

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = _pdecode_fn(mesh).lower(*shapes).compile()
        with pytest.raises(NotImplementedError, match="shard_map"):
            _pdecode_fn(None).lower(*shapes)
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
    assert "tpu_custom_call" not in compiled.as_text()


# ------------------------------------- the four-stream family's decode step

def test_the_four_stream_decode_step_keeps_its_temporaries(one_chip,
                                                           monkeypatch):
    """``xing4-29b-ep1-stage``'s ``_pdecode_fn`` at the cell's top rung (128
    rows, 256 pages a row, the pool donated): the experts' grouped products
    (``ops/grouped.py``) compile as Mosaic kernels — two, inside the scan
    over the layers: gate with up, then down —, read the experts' stack
    where it lies (no layer's slab is cut out: a
    copy of 1.4 GB a layer), and the program's temporaries stay what the
    parent's were (0.757 GiB in this compile, the gathered latents; PERF.md,
    PR 39): ``sat_hbm_peak_pct`` reads 96 % and the runtime's limit is
    near."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from pdnlp_tpu.models import families
    from pdnlp_tpu.ops import grouped
    from pdnlp_tpu.serve.decode import greedy_ids

    monkeypatch.setattr(grouped, "_interpret", lambda: False)
    cfg = get_config("xing4-29b-ep1-stage")
    family = families.of(cfg)
    key, bf, i32 = jax.random.key(0), jnp.bfloat16, jnp.int32
    rows, pages, ps = 128, 4096 // 16, 16

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, head = jax.tree_util.tree_map(
        lambda x: S(x.shape, x.dtype), jax.eval_shape(
            lambda: (family.init_params(key, cfg),
                     family.init_head(key, cfg))))
    pool = S((cfg.num_layers, rows * pages, ps, cfg.cache_width), bf)

    def _pdecode_fn(params, head, pools, tokens, table, pos):
        logits, aux, pools, _ = family.attend(
            params, head, cfg, tokens, pools, (), table, pos, None, "last",
            None, bf)
        return greedy_ids(logits), aux, pools

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = jax.jit(_pdecode_fn, donate_argnums=(2,)).lower(
            params, head, (pool,), S((rows, 1), i32), S((rows, pages), i32),
            S((rows,), i32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 0.78 * 2 ** 30, m.temp_size_in_bytes
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    # every held expert's matrices enter the kernels as the stack's own
    # buffer: no bf16[64, 3584, 1024] (one layer's slab) is ever written
    assert not re.search(r"bf16\[64,(3584,1024|1024,3584)\]", text)


# ------------------------------- the learned sparse attention's decode step

def test_the_sparse_decode_step_gathers_its_picks_and_never_the_extent(
        one_chip, monkeypatch):
    """``glm-5.2-ep16-share``'s ``_pdecode_fn`` at the cell's top rung (32
    rows, 512 pages a row, latents and index keys in two pools of 7 and 2
    layers, both donated): the index keys of a row's pages are read whole
    (``[32, 8192, 128]``), the latents are GATHERED at the 2 048 picked
    positions a row (``[32, 2048, 640]``) and a row's whole extent of them
    (``[32, 8192, 640]``: 336 MB a layer) is never built; the program's
    temporaries stay under 0.7 GiB beside 12.55 GiB of weights and pools
    (0.593 in this compile; PERF.md section 6, PR 43)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from pdnlp_tpu.models import families
    from pdnlp_tpu.ops import grouped
    from pdnlp_tpu.serve.decode import greedy_ids

    monkeypatch.setattr(grouped, "_interpret", lambda: False)
    cfg = get_config("glm-5.2-ep16-share")
    family = families.of(cfg)
    key, bf, i32 = jax.random.key(0), jnp.bfloat16, jnp.int32
    rows, pages, ps = 32, 8192 // 16, 16

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, head = jax.tree_util.tree_map(
        lambda x: S(x.shape, x.dtype), jax.eval_shape(
            lambda: (family.init_params(key, cfg),
                     family.init_head(key, cfg))))
    shapes = families.pool_shapes(cfg)
    assert shapes == ((7, 640), (2, 128))
    pools = tuple(S((n, rows * pages, ps, w), bf) for n, w in shapes)

    def _pdecode_fn(params, head, pools, tokens, table, pos):
        logits, aux, pools, _ = family.attend(
            params, head, cfg, tokens, pools, (), table, pos, None, "last",
            None, bf)
        return greedy_ids(logits), aux, pools

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = jax.jit(_pdecode_fn, donate_argnums=(2,)).lower(
            params, head, pools, S((rows, 1), i32), S((rows, pages), i32),
            S((rows,), i32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 0.7 * 2 ** 30, m.temp_size_in_bytes
    # both pools stay where they lie
    assert m.alias_size_in_bytes >= sum(
        n * rows * pages * ps * w * 2 for n, w in shapes)
    text = compiled.as_text()
    assert re.search(r"bf16\[32,2048,640\]", text)
    assert re.search(r"bf16\[32,8192,128\]", text)
    assert not re.search(r"bf16\[32,8192,640\]", text)
