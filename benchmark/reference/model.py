"""The plain reference: BERT (post-LayerNorm encoder, ``hidden_act`` gelu =
erf) in straightforward ``jax.numpy`` float32 at ``highest`` matmul precision.

No kernels, no cache, no batching tricks, nothing imported from the program.
Three things are computed from it:

- ``cls_loss``: the sequence classifier's weighted mean cross-entropy
  (HF ``BertForSequenceClassification``: tanh pooler over [CLS], linear head);
- ``train_steps``: that loss's gradients driven through AdamW with decoupled
  weight decay (biases and LayerNorm leaves exempt) under a linear warm-up /
  linear decay schedule, for the first few steps;
- ``causal_logits``: the same trunk under a causal mask with the MLM-shaped
  head tied to the word embeddings (HF ``BertLMHeadModel``, ``is_decoder``).

``prec`` lowers the precision of every matmul's operands ("bf16", "fp8":
forward and backward) and
is how the controls are computed: the reference put in the program's place,
one precision below what the configuration states.

Dropout (the published 0.1) is part of the training recipe.  A step can be
held to a reference only under the same masks, so ``dropout_masks`` states
the recipe's mask stream — which key, folded how, drops what — in
``jax.random`` terms of its own and ``train_steps`` applies those masks at
BERT's five sites (embeddings, attention probabilities, both residual
branches, the pooled vector).  The stream is a result of the recipe like the
weights' layout: a program that draws other masks trains another sample and
brings a configuration and a stream of its own.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e9


def _quant(x, prec, grad=False):
    """``x`` as the lower precision holds it.  fp8 is per-tensor scaled, as
    fp8 recipes are (e4m3 forward, e5m2 for gradients); bf16 is a cast."""
    if prec == "f32":
        return x
    if prec == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    dt, top = ((jnp.float8_e5m2, 57344.0) if grad
               else (jnp.float8_e4m3fn, 448.0))
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dt).astype(jnp.float32) * s


def _t(x):
    return jnp.swapaxes(x, -1, -2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm(a, b, prec):
    """``a @ b`` with both operands held in ``prec`` — and, in the backward
    pass, the incoming gradient too."""
    return jnp.matmul(_quant(a, prec), _quant(b, prec), precision=HIGHEST)


def _mm_fwd(a, b, prec):
    qa, qb = _quant(a, prec), _quant(b, prec)
    return jnp.matmul(qa, qb, precision=HIGHEST), (qa, qb)


def _unbroadcast(g, like):
    while g.ndim > like.ndim:
        g = g.sum(0)
    return g


def _mm_bwd(prec, res, g):
    qa, qb = res
    qg = _quant(g, prec, grad=True)
    da = jnp.matmul(qg, _t(qb), precision=HIGHEST)
    db = jnp.matmul(_t(qa), qg, precision=HIGHEST)
    return _unbroadcast(da, qa), _unbroadcast(db, qb)


_mm.defvjp(_mm_fwd, _mm_bwd)


def _ln(x, g, b, eps):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _drop(x, keep_mask, rate):
    """Inverted dropout under a given mask (``None``: no dropout)."""
    if keep_mask is None:
        return x
    return jnp.where(keep_mask, x / (1.0 - rate), 0.0)


def _layer(x, w, l, bias, heads, eps, prec, masks=None, rates=(0.0, 0.0)):
    B, S, H = x.shape
    D = H // heads
    m_probs, m_attn, m_mlp = masks if masks is not None else (None,) * 3

    def split(t):
        return t.reshape(B, S, heads, D).transpose(0, 2, 1, 3)

    q = split(_mm(x, w["q_w"][l], prec) + w["q_b"][l])
    k = split(_mm(x, w["k_w"][l], prec) + w["k_b"][l])
    v = split(_mm(x, w["v_w"][l], prec) + w["v_b"][l])
    s = _mm(q, k.transpose(0, 1, 3, 2), prec) / math.sqrt(D) + bias
    p = _drop(jax.nn.softmax(s, axis=-1), m_probs, rates[1])
    ctx = _mm(p, v, prec).transpose(0, 2, 1, 3).reshape(B, S, H)
    o = _drop(_mm(ctx, w["o_w"][l], prec) + w["o_b"][l], m_attn, rates[0])
    x = _ln(x + o, w["attn_ln_g"][l], w["attn_ln_b"][l], eps)
    h = _gelu(_mm(x, w["up_w"][l], prec) + w["up_b"][l])
    h = _drop(_mm(h, w["down_w"][l], prec) + w["down_b"][l], m_mlp, rates[0])
    return _ln(x + h, w["mlp_ln_g"][l], w["mlp_ln_b"][l], eps)


def trunk(w, ids, mask, *, heads, eps, causal, prec="f32", type_ids=None,
          masks=None, rates=(0.0, 0.0)):
    """[B, S] ids, {0,1} key mask -> hidden [B, S, H].  ``masks``: the keep
    masks of ``dropout_masks`` for these rows; ``rates``: (hidden, attention)."""
    B, S = ids.shape
    tt = jnp.zeros_like(ids) if type_ids is None else type_ids
    x = w["word"][ids] + w["pos"][jnp.arange(S)][None] + w["type"][tt]
    x = _ln(x, w["emb_ln_g"], w["emb_ln_b"], eps)
    if masks is not None:
        x = _drop(x, masks["emb"], rates[0])
    bias = ((1.0 - mask.astype(jnp.float32)) * NEG)[:, None, None, :]
    if causal:
        i = jnp.arange(S)
        bias = bias + jnp.where(i[:, None] >= i[None, :], 0.0, NEG)[None, None]
    layer = jax.checkpoint(_layer, static_argnums=(2, 4, 5, 6, 8))
    for l in range(w["q_w"].shape[0]):
        m = None if masks is None else (
            masks["probs"][l], masks["attn_out"][l], masks["mlp_out"][l])
        x = layer(x, w, l, bias, heads, eps, prec, m, rates)
    return x


def dropout_masks(seed: int, step: int, *, impl: str, rows: int, seq: int,
                  hidden: int, heads: int, layers: int, rates) -> dict:
    """The recipe's keep masks for optimizer step ``step`` (0-based) of a run
    seeded with ``seed``, for a batch of ``rows`` x ``seq``.

    The stream: root = key(seed, impl); one key a step by ``fold_in(root,
    step)``; that splits three ways (unused, encoder, pooled vector); the
    encoder's splits once more for the embeddings' mask; layer ``l`` folds
    ``3l`` (attention's residual branch), ``3l + 1`` (the MLP's) and
    ``3l + 2`` (attention probabilities, [rows, heads, seq, seq]) into what
    is left.  Each mask is ``bernoulli(key, 1 - rate)`` over its whole array.
    """
    keep, akeep = 1.0 - rates[0], 1.0 - rates[1]
    bern, fold = jax.random.bernoulli, jax.random.fold_in
    wide = (rows, seq, hidden)

    @jax.jit
    def make(step):
        k = fold(jax.random.key(seed, impl=impl), step)
        _, enc, pooled = jax.random.split(k, 3)
        enc, emb = jax.random.split(enc)
        per = range(layers)
        return {
            "emb": bern(emb, keep, wide),
            "pooled": bern(pooled, keep, (rows, hidden)),
            "attn_out": jnp.stack([bern(fold(enc, 3 * l), keep, wide)
                                   for l in per]),
            "mlp_out": jnp.stack([bern(fold(enc, 3 * l + 1), keep, wide)
                                  for l in per]),
            "probs": jnp.stack([bern(fold(enc, 3 * l + 2), akeep,
                                     (rows, heads, seq, seq)) for l in per]),
        }

    return make(jnp.int32(step))


def _rows_of(masks, lo, hi):
    """The masks of rows ``lo:hi`` of the batch."""
    return {k: (v[:, lo:hi] if k in ("attn_out", "mlp_out", "probs")
                else v[lo:hi]) for k, v in masks.items()}


def cls_loss(w, batch, *, heads, eps, prec="f32", masks=None,
             rates=(0.0, 0.0)):
    """Weighted mean CE of the classifier over ``batch`` (input_ids,
    token_type_ids, attention_mask, label, example_weight)."""
    h = trunk(w, batch["input_ids"], batch["attention_mask"], heads=heads,
              eps=eps, causal=False, prec=prec,
              type_ids=batch["token_type_ids"], masks=masks, rates=rates)
    pooled = jnp.tanh(_mm(h[:, 0], w["pooler_w"], prec) + w["pooler_b"])
    if masks is not None:
        pooled = _drop(pooled, masks["pooled"], rates[0])
    logits = _mm(pooled, w["cls_w"], prec) + w["cls_b"]
    logp = jax.nn.log_softmax(logits)
    ce = -jnp.take_along_axis(logp, batch["label"][:, None], axis=-1)[:, 0]
    wt = batch["example_weight"].astype(jnp.float32)
    return (ce * wt).sum() / jnp.maximum(wt.sum(), 1.0)


TRAINED = ("word", "pos", "type", "emb_ln_g", "emb_ln_b", "pooler_w",
           "pooler_b", "cls_w", "cls_b", "q_w", "q_b", "k_w", "k_b", "v_w",
           "v_b", "o_w", "o_b", "up_w", "up_b", "down_w", "down_b",
           "attn_ln_g", "attn_ln_b", "mlp_ln_g", "mlp_ln_b")


def _decayed(name: str) -> bool:
    return name.endswith("_w") or name in ("word", "pos", "type")


def lr_at(step: int, recipe: dict) -> float:
    """``warmup_linear``: 0 -> lr over the warm-up steps, then lr -> 0."""
    total, lr = recipe["total_steps"], recipe["learning_rate"]
    warm = max(1, int(total * recipe["warmup_ratio"]))
    if step < warm:
        return lr * step / warm
    return lr * max(0.0, 1.0 - (step - warm) / (total - warm))


def train_steps(w, batches, recipe, *, heads, eps, prec="f32", block=16,
                dropout=None):
    """Follow the optimizer through ``len(batches)`` steps.  Returns
    (losses, first moment per leaf, parameters after) — gradients are summed
    over blocks of ``block`` rows so the float32 activations stay small.
    ``dropout``: ``{"seed", "impl", "rates": (hidden, attention)}`` of the
    recipe's mask stream (``dropout_masks``), or ``None`` for none."""
    p = {n: w[n] for n in TRAINED}
    mu = {n: jnp.zeros_like(v) for n, v in p.items()}
    nu = {n: jnp.zeros_like(v) for n, v in p.items()}
    b1, b2 = recipe["adam_b1"], recipe["adam_b2"]

    rates = tuple(dropout["rates"]) if dropout else (0.0, 0.0)
    if not any(rates):
        dropout = None

    @jax.jit
    def block_grad(p, blk, wsum, masks):
        def f(p):
            # this block's share of the batch's weighted mean
            h_loss = cls_loss({**w, **p}, blk, heads=heads, eps=eps,
                              prec=prec, masks=masks, rates=rates)
            share = jnp.maximum(blk["example_weight"].astype(jnp.float32).sum(), 1.0)
            return h_loss * share / wsum
        return jax.value_and_grad(f)(p)

    @jax.jit
    def adam(p, mu, nu, g, lr, t):
        mu = {n: b1 * mu[n] + (1 - b1) * g[n] for n in p}
        nu = {n: b2 * nu[n] + (1 - b2) * g[n] ** 2 for n in p}
        new = {}
        for n in p:
            mh = mu[n] / (1 - b1 ** t)
            vh = nu[n] / (1 - b2 ** t)
            upd = mh / (jnp.sqrt(vh) + recipe["adam_eps"])
            if _decayed(n):
                upd = upd + recipe["weight_decay"] * p[n]
            new[n] = p[n] - lr * upd
        return new, mu, nu

    losses = []
    for t, batch in enumerate(batches):
        rows = batch["input_ids"].shape[0]
        wsum = jnp.maximum(
            jnp.asarray(batch["example_weight"], jnp.float32).sum(), 1.0)
        loss, grads = 0.0, None
        masks = dropout and dropout_masks(
            dropout["seed"], t, impl=dropout["impl"], rows=rows,
            seq=batch["input_ids"].shape[1], hidden=w["word"].shape[1],
            heads=heads, layers=w["q_w"].shape[0], rates=rates)
        for r in range(0, rows, block):
            blk = {k: jnp.asarray(v[r:r + block]) for k, v in batch.items()}
            l, g = block_grad(p, blk, wsum,
                              masks and _rows_of(masks, r, r + block))
            loss = loss + l
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        losses.append(float(loss))
        p, mu, nu = adam(p, mu, nu, grads, jnp.float32(lr_at(t, recipe)),
                         jnp.float32(t + 1))
    return losses, mu, p


def causal_logits(w, ids, mask, *, heads, eps, prec="f32"):
    """[B, S] -> [B, S, V] next-token logits of the causal LM."""
    h = trunk(w, ids, mask, heads=heads, eps=eps, causal=True, prec=prec)
    t = _gelu(_mm(h, w["head_w"], prec) + w["head_b"])
    t = _ln(t, w["head_ln_g"], w["head_ln_b"], eps)
    return _mm(t, w["word"].T, prec) + w["out_b"]


def leaf_norms(tree) -> dict:
    return {n: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for n, v in tree.items()}
