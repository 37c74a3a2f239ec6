"""Kernel-path tests: segment-native flash attention (fwd/bwd vs the XLA
``segment_bias`` oracle), the fused projection+CE kernel (value+grad vs the
unfused loss), int8 weight quantization (round-trip bound + engine parity),
and the ``--attn_impl`` routing policy.  Every Pallas call runs in
interpret mode on the CPU mesh (``flash._interpret``) — the same numerics
as compiled Mosaic, minus the speed."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pdnlp_tpu.data.packing import segment_bias
from pdnlp_tpu.models import bert, get_config
from pdnlp_tpu.ops import attention as attn_mod
from pdnlp_tpu.ops import flash
from pdnlp_tpu.ops.attention import (
    dot_product_attention, mask_bias, resolve_impl, routed_impl,
)
from pdnlp_tpu.ops.fused_ce import fused_weighted_ce, resolve_fused_ce
from pdnlp_tpu.serve.quant import (
    dequantize_dense, is_quantized, quant_error_report, quantize_params,
)
from pdnlp_tpu.train.steps import weighted_ce
from pdnlp_tpu.utils.config import Args


def packed_segments(B, S, seed=0, pad_tail=True):
    """[B, S] segment IDs: 3-5 segments per row, padding (0) tail."""
    r = np.random.RandomState(seed)
    seg = np.zeros((B, S), np.int32)
    for b in range(B):
        pos = 0
        for sid in range(1, r.randint(3, 6)):
            length = r.randint(8, S // 3)
            seg[b, pos:pos + length] = sid
            pos += length
            if pos >= S:
                break
        if not pad_tail and pos < S:
            seg[b, pos:] = sid
    return seg


def qkv(B=2, S=128, N=4, D=32, seed=0):
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(r.randn(B, S, N, D), jnp.float32)
                 for _ in range(3))


# ------------------------------------------------ segment-native flash


def test_segment_mask_forward_equivalence():
    """In-kernel mask from IDs == the XLA path over the materialized
    [B, 1, S, S] ``segment_bias`` — same semantics, no HBM bias."""
    q, k, v = qkv()
    seg = packed_segments(2, 128)
    ref = dot_product_attention(
        q, k, v, bias=jnp.asarray(segment_bias(seg)), impl="xla")
    out = flash.flash_attention(q, k, v, segment_ids=jnp.asarray(seg))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("pad_tail", [True, False])
def test_segment_mask_backward_equivalence(pad_tail):
    """Gradcheck vs XLA, including fully-padded query rows — the case
    where a folded logsumexp would lose log(l) to fp32 rounding at -1e9
    (the kernel saves (m, l) separately for exactly this)."""
    q, k, v = qkv()
    seg = packed_segments(2, 128, pad_tail=pad_tail)
    bias = jnp.asarray(segment_bias(seg))
    segj = jnp.asarray(seg)

    def loss(f):
        return lambda q, k, v: (f(q, k, v) ** 2).sum()

    gr = jax.grad(loss(lambda q, k, v: dot_product_attention(
        q, k, v, bias=bias, impl="xla")), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss(lambda q, k, v: flash.flash_attention(
        q, k, v, segment_ids=segj)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gr, gf):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-5,
                                   err_msg=f"d{name} diverged")


def test_segment_ids_route_through_dot_product_attention():
    """``impl="pallas"`` + ``segment_ids`` runs the segment-native kernel;
    the XLA fallback builds ``segment_bias`` internally — both match."""
    q, k, v = qkv(seed=1)
    seg = jnp.asarray(packed_segments(2, 128, seed=1))
    out = dot_product_attention(q, k, v, impl="pallas", segment_ids=seg)
    ref = dot_product_attention(q, k, v, impl="xla", segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_bias_and_segment_ids_are_mutually_exclusive():
    q, k, v = qkv()
    seg = jnp.asarray(packed_segments(2, 128))
    bias = mask_bias(jnp.ones((2, 128)))
    with pytest.raises(ValueError, match="bias OR segment_ids"):
        flash.flash_attention(q, k, v, bias=bias, segment_ids=seg)
    # and on EVERY route — the XLA path would otherwise silently apply
    # only the bias and let co-packed examples cross-attend
    with pytest.raises(ValueError, match="bias OR segment_ids"):
        dot_product_attention(q, k, v, bias=bias, impl="xla",
                              segment_ids=seg)


def _jaxpr_shapes(jaxpr, acc):
    """Every intermediate's shape in ``jaxpr`` and the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            shape = getattr(getattr(ov, "aval", None), "shape", None)
            if shape:
                acc.add(tuple(shape))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    _jaxpr_shapes(getattr(inner, "jaxpr", inner), acc)
    return acc


@pytest.mark.parametrize("check", ["logits", "no-bias-in-the-jaxpr"])
def test_packed_classify_pallas_matches_xla(check):
    """End-to-end packed forward: per-segment logits identical whether the
    block-diagonal mask is in-kernel (pallas) or materialized (XLA) — and,
    structurally, the pallas route's jaxpr holds NO ``[B, 1, S, S]`` value
    (the segment bias never exists), while the XLA control's does."""
    cfg = get_config("bert-tiny", vocab_size=120).replace(max_position=128)
    params = bert.init_params(jax.random.key(0), cfg)
    r = np.random.RandomState(0)
    B, S, M = 2, 128, 4
    seg = packed_segments(B, S, seed=2)
    cls = np.zeros((B, M), np.int64)
    for b in range(B):
        for m in range(1, M + 1):
            idx = np.flatnonzero(seg[b] == m)
            cls[b, m - 1] = idx[0] if idx.size else 0
    batch = {
        "input_ids": jnp.asarray(r.randint(0, 120, (B, S)), jnp.int32),
        "token_type_ids": jnp.zeros((B, S), jnp.int32),
        "attention_mask": jnp.asarray((seg > 0).astype(np.int32)),
        "segment_ids": jnp.asarray(seg),
        "cls_positions": jnp.asarray(cls, jnp.int32),
        "label": jnp.zeros((B, M), jnp.int32),
        "example_weight": jnp.ones((B, M), jnp.float32),
    }
    if check == "no-bias-in-the-jaxpr":
        seen = {impl: _jaxpr_shapes(jax.make_jaxpr(
            lambda p, bt: bert.classify(p, cfg, bt, attn_impl=impl))(
                params, batch).jaxpr, set()) for impl in ("pallas", "xla")}
        assert (B, 1, S, S) not in seen["pallas"]
        assert (B, 1, S, S) in seen["xla"]   # the check has its control
        return
    a = bert.classify(params, cfg, batch, attn_impl="xla")
    b = bert.classify(params, cfg, batch, attn_impl="pallas")
    assert a.shape == (B, M, cfg.num_labels)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-4)


# ---------------------------------------------------- --attn_impl routing


def test_routing_dropout_forces_xla():
    assert routed_impl("pallas", 128, dropout=True) == "xla"
    assert routed_impl("pallas", 128, dropout=False) == "pallas"


def test_routing_unsupported_seq_falls_back_with_warning(capsys):
    attn_mod._FALLBACK_WARNED.clear()
    assert routed_impl("pallas", 96) == "xla"
    assert "seq_len=96" in capsys.readouterr().err
    # once per process per shape: the second route is silent
    assert routed_impl("pallas", 96) == "xla"
    assert capsys.readouterr().err == ""


def test_routing_auto_policy_by_backend():
    # the measured default: segment-native pallas for packed batches on
    # TPU; XLA for everything else (and everywhere on CPU)
    assert resolve_impl("auto", segmented=True, backend="tpu") == "pallas"
    assert resolve_impl("auto", segmented=False, backend="tpu") == "xla"
    assert resolve_impl("auto", segmented=True, backend="cpu") == "xla"
    assert resolve_impl("pallas", backend="cpu") == "pallas"
    with pytest.raises(ValueError, match="impl"):
        resolve_impl("cudnn")


def test_resolve_fused_ce():
    assert resolve_fused_ce(Args(fused_ce="pallas")) == "pallas"
    assert resolve_fused_ce(Args(fused_ce="xla")) == "xla"
    # auto = pallas only on a real TPU backend (tests run on CPU)
    expect = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert resolve_fused_ce(Args(fused_ce="auto")) == expect
    with pytest.raises(ValueError, match="fused_ce"):
        resolve_fused_ce(Args(fused_ce="fast"))


# ----------------------------------------------------------- fused CE


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_fused_ce_value_and_grad_parity(smoothing):
    """Kernel triple (loss, correct, objective) and d(feats)/dW/db match
    the unfused logits path — T deliberately off the 128 block, C=6
    exercising the lane padding, zero weights exercising filler rows."""
    r = np.random.RandomState(0)
    T, H, C = 37, 64, 6
    f = jnp.asarray(r.randn(T, H), jnp.float32)
    W = jnp.asarray(r.randn(H, C) * 0.1, jnp.float32)
    b = jnp.asarray(r.randn(C) * 0.1, jnp.float32)
    lab = jnp.asarray(r.randint(0, C, T))
    w = jnp.asarray((r.rand(T) > 0.3).astype(np.float32))

    ref = weighted_ce(f @ W + b, lab, w, smoothing=smoothing)
    out = fused_weighted_ce(f, W, b, lab, w, smoothing=smoothing)
    for name, a, o in zip(("loss", "correct", "objective"), ref, out):
        np.testing.assert_allclose(np.asarray(o), np.asarray(a), atol=1e-5,
                                   err_msg=f"{name} diverged")

    gr = jax.grad(lambda f, W, b: weighted_ce(
        f @ W + b, lab, w, smoothing=smoothing)[2],
        argnums=(0, 1, 2))(f, W, b)
    gf = jax.grad(lambda f, W, b: fused_weighted_ce(
        f, W, b, lab, w, smoothing=smoothing)[2],
        argnums=(0, 1, 2))(f, W, b)
    for name, a, o in zip(("dfeats", "dW", "db"), gr, gf):
        np.testing.assert_allclose(np.asarray(o), np.asarray(a), atol=1e-5,
                                   err_msg=f"{name} diverged")


def test_fused_ce_correct_matches_argmax_on_ties():
    """Tied max logits: argmax picks the FIRST index, so a label tied with
    a lower-indexed class counts INCORRECT — the kernel must agree (a
    ``logit_lab >= max`` indicator would not)."""
    H = C = 4
    W = jnp.eye(H, C, dtype=jnp.float32)
    b = jnp.zeros((C,), jnp.float32)
    # rows: logits == feats.  row0: tie 0/1, label 1 -> incorrect;
    # row1: tie 0/1, label 0 -> correct; row2: unique max at 2 -> correct
    f = jnp.asarray([[1., 1., 0., 0.],
                     [1., 1., 0., 0.],
                     [0., 0., 3., 0.]], jnp.float32)
    lab = jnp.asarray([1, 0, 2])
    w = jnp.ones((3,), jnp.float32)
    ref = weighted_ce(f @ W + b, lab, w)
    out = fused_weighted_ce(f, W, b, lab, w)
    assert float(ref[1]) == 2.0
    assert float(out[1]) == float(ref[1])


def test_fused_ce_train_step_parity():
    """One optimizer step with ``--fused_ce pallas`` vs ``xla``: identical
    loss metric and matching updated params — the kernel is a drop-in for
    the train step's whole loss tail."""
    from pdnlp_tpu.train.optim import build_optimizer
    from pdnlp_tpu.train.steps import build_train_step, init_state

    cfg = get_config("bert-tiny", vocab_size=120).replace(
        dropout=0.0, attn_dropout=0.0)
    r = np.random.RandomState(0)
    B, S = 8, 32
    batch = {
        "input_ids": jnp.asarray(r.randint(0, 120, (B, S)), jnp.int32),
        "token_type_ids": jnp.zeros((B, S), jnp.int32),
        "attention_mask": jnp.ones((B, S), jnp.int32),
        "label": jnp.asarray(r.randint(0, cfg.num_labels, B)),
        "example_weight": jnp.ones((B,), jnp.float32),
    }
    outs = {}
    for mode in ("xla", "pallas"):
        args = Args(model="bert-tiny", fused_ce=mode, label_smoothing=0.1)
        params = bert.init_params(jax.random.key(0), cfg)
        tx = build_optimizer(params, args)
        state = init_state(jax.random.key(0), cfg, tx,
                           rng=jax.random.key(1), params=params)
        step = jax.jit(build_train_step(cfg, tx, args), donate_argnums=0)
        state, m = step(state, batch)
        outs[mode] = (float(m["loss"]),
                      np.asarray(state["params"]["pooler"]["kernel"]))
    assert abs(outs["xla"][0] - outs["pallas"][0]) < 1e-5
    np.testing.assert_allclose(outs["pallas"][1], outs["xla"][1], atol=1e-6)


# --------------------------------------------------------------- int8


def test_int8_roundtrip_error_bound():
    """Symmetric per-output-channel int8: |W - dq(q(W))| <= scale/2 per
    channel (half a quantization step), embeddings/LN/gate untouched."""
    r = np.random.RandomState(0)
    params = {
        "layers": {"q": {"kernel": r.randn(3, 32, 32).astype(np.float32),
                         "bias": np.zeros((3, 32), np.float32)},
                   "gate": {"kernel": r.randn(3, 32, 4).astype(np.float32)},
                   "attn_ln": {"scale": np.ones((3, 32), np.float32),
                               "bias": np.zeros((3, 32), np.float32)}},
        "embeddings": {"word": r.randn(100, 32).astype(np.float32)},
    }
    qp = quantize_params(params)
    assert is_quantized(qp) and not is_quantized(params)
    qd = qp["layers"]["q"]
    assert qd["kernel"].dtype == np.int8
    assert qd["qscale"].shape == (3, 32)  # one scale per (layer, out-ch)
    # bias-less gate and non-dense trees pass through in full precision
    assert qp["layers"]["gate"]["kernel"].dtype == np.float32
    assert qp["embeddings"]["word"].dtype == np.float32
    err = np.abs(params["layers"]["q"]["kernel"] - dequantize_dense(qd))
    bound = qd["qscale"][:, None, :] * 0.5 + 1e-7
    assert (err <= bound).all()
    report = quant_error_report(params, qp)
    assert set(report) == {"layers/q"}
    _, rel = report["layers/q"]
    assert rel <= 0.5 / 127 + 1e-6  # symmetric int8: <= half step of amax


def test_int8_engine_matches_bf16_predictions(tmp_path):
    """The int8 engine serves the same argmax as the bf16 engine on random
    inputs from a trained-ish checkpoint; logits stay close."""
    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab

    texts = ["天地人你我", "好坏大小上下来去" * 4, "爱恨喜怒哀乐" * 10,
             "高兴悲伤", "讨厌愤怒来去" * 6]
    tok = WordPieceTokenizer(build_vocab(texts, size=128))
    from pdnlp_tpu.serve import InferenceEngine
    from pdnlp_tpu.train import checkpoint as ckpt

    # a non-init checkpoint: perturbed weights so logits are not symmetric
    base = Args(model="bert-tiny", seed=3)
    eng_bf16 = InferenceEngine(base.replace(serve_dtype="bf16"),
                               tokenizer=tok, mesh=None)
    path = os.path.join(tmp_path, "m.msgpack")
    perturbed = jax.tree_util.tree_map(
        lambda p: p + 0.01 * jax.random.normal(jax.random.key(1), p.shape),
        eng_bf16._template)
    ckpt.save(path, perturbed)
    eng_bf16.load_checkpoint(path)
    eng_int8 = InferenceEngine(base.replace(serve_dtype="int8"),
                               tokenizer=tok, mesh=None)
    eng_int8.load_checkpoint(path)
    assert eng_int8.dtype_label == "int8"

    r = np.random.RandomState(0)
    ids = [[2] + list(r.randint(5, 100, r.randint(3, 30))) + [3]
           for _ in range(32)]
    a = eng_bf16.infer_ids(ids, 32)
    b = eng_int8.infer_ids(ids, 32)
    agree = float((np.argmax(a, -1) == np.argmax(b, -1)).mean())
    assert agree >= 0.95
    assert float(np.abs(a - b).max()) < 0.15  # bf16 noise + int8 rounding


@pytest.mark.parametrize("dispatch", ["dense", "grouped"])
def test_int8_moe_experts_apply_qscale(dispatch):
    """Quantized MoE expert stacks ([E, in, out] kernels) must compose the
    per-output-channel scale in BOTH dispatch paths — the expert einsums
    bypass ``_dense``, so they apply it themselves (``_expert_scale``)."""
    cfg = get_config("bert-tiny-moe", vocab_size=64).replace(
        moe_dispatch=dispatch, moe_capacity_factor=4.0)
    r = np.random.RandomState(0)
    E, H, I = cfg.moe_experts, cfg.hidden_size, cfg.intermediate_size
    lp = {
        "gate": {"kernel": jnp.asarray(r.randn(H, E) * 0.1, jnp.float32)},
        "up": {"kernel": jnp.asarray(r.randn(E, H, I) * 0.1, jnp.float32),
               "bias": jnp.asarray(r.randn(E, I) * 0.1, jnp.float32)},
        "down": {"kernel": jnp.asarray(r.randn(E, I, H) * 0.1, jnp.float32),
                 "bias": jnp.asarray(r.randn(E, H) * 0.1, jnp.float32)},
    }
    qlp = jax.tree_util.tree_map(jnp.asarray, quantize_params(lp))
    assert qlp["up"]["kernel"].dtype == jnp.int8
    # the oracle: the float tree the quantized one approximates
    deq = {
        "gate": lp["gate"],
        "up": {"kernel": jnp.asarray(dequantize_dense(qlp["up"])),
               "bias": lp["up"]["bias"]},
        "down": {"kernel": jnp.asarray(dequantize_dense(qlp["down"])),
                 "bias": lp["down"]["bias"]},
    }
    x = jnp.asarray(r.randn(2, 16, H), jnp.float32)
    mask = jnp.ones((2, 16), jnp.int32)
    out_q, aux_q = bert.moe_mlp(x, qlp, cfg, mask=mask)
    out_f, aux_f = bert.moe_mlp(x, deq, cfg, mask=mask)
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_f),
                               atol=1e-5)
    np.testing.assert_allclose(float(aux_q), float(aux_f), atol=1e-6)


def test_serve_span_attn_impl_routes_per_bucket():
    """A pallas-requested engine stamps XLA on sub-128 buckets (the kernel
    blocks don't tile) and pallas at 128 — spans and the by-seq record
    must carry the per-width routing, not the max-width headline."""
    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
    from pdnlp_tpu.serve import InferenceEngine

    attn_mod._FALLBACK_WARNED.clear()
    tok = WordPieceTokenizer(build_vocab(["天地人你我"], size=64))
    eng = InferenceEngine(Args(model="bert-tiny", attention_impl="pallas"),
                          tokenizer=tok, mesh=None)
    assert eng.attn_impl == "pallas"  # headline: max_seq_len=128 tiles
    assert eng.routed_attn(32) == "xla"
    assert eng.routed_attn(128) == "pallas"
    assert eng.attn_impl_by_seq == {32: "xla", 128: "pallas"}


def test_quantized_artifact_into_float_engine_raises(tmp_path):
    from pdnlp_tpu.data.tokenizer import WordPieceTokenizer, build_vocab
    from pdnlp_tpu.serve import InferenceEngine
    from pdnlp_tpu.train import checkpoint as ckpt

    tok = WordPieceTokenizer(build_vocab(["天地人你我"], size=64))
    eng = InferenceEngine(Args(model="bert-tiny"), tokenizer=tok, mesh=None)
    qpath = os.path.join(tmp_path, "m.int8.msgpack")
    ckpt.save(qpath, quantize_params(eng._template))
    with pytest.raises(ValueError, match="int8 artifact"):
        eng.load_checkpoint(qpath)
    # and the int8 engine loads the artifact directly
    eng8 = InferenceEngine(Args(model="bert-tiny", serve_dtype="int8"),
                           tokenizer=tok, mesh=None)
    eng8.load_checkpoint(qpath)
    assert eng8.checkpoint_path == qpath
