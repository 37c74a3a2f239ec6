"""A pre-norm decoder with latent attention (MLA) and sparse experts, served
over a ONE-pool latent page cache.

The second model family beside ``models/bert.py`` (the engine finds it
through ``models/families.py``).  The layer, from the published config of
A.X-K1 (``LatentMoEConfig``); ``benchmark/reference/axk1.py`` states the
same equations again in plain float32:

- ``h = E[ids]``; every layer ``a = rms(h)``; ``c_q = rms(a W_qa)``;
  ``[q_nope | q_rope] = c_q W_qb`` per head; ``[c_kv | k_rope] = a W_kva``,
  ``c_kv = rms(c_kv)``; yarn rotary on ``q_rope`` and on the ONE ``k_rope``
  all heads share; ``[k_nope | v] = c_kv W_kvb`` per head; scores ``(q_nope .
  k_nope + q_rope . k_rope) * s`` with the family's yarn scale; causal
  softmax in float32; ``h += concat(P v) W_o``.
- feed-forward on ``f = rms(h)``: gated silu, dense in the leading
  layer(s); after them a router over ALL ``n_routed_experts`` (sigmoid
  scores in float32, group-limited choice, ``num_experts_per_tok`` a token,
  normalised and scaled) plus a shared expert.

With ``cfg.hc_mult`` > 1 (Xing4.0-29B-A4B) the residual ``h`` is that many
STREAMS ``[n, B, T, H]``: the embedding is copied into them, every sub-layer
(attention, feed-forward) reads, writes and carries them through
``models/hyper_connections.py``'s mixing, the final norm reads their sum.
The streams are never cached: nothing below the layer knows of them.  With
``cfg.selection_bias`` the router chooses by ``score + bias`` and gates by
the score.  ``benchmark/reference/xing4.py`` states both in plain float32.

With ``cfg.index_n_heads`` (GLM-5.2) the attention is a LEARNED SPARSE one
(:func:`index_project`, :func:`index_scores`, :func:`select_mask`,
:func:`select_picks`).  A layer whose ``cfg.indexer_types`` entry is
``"full"`` holds a lightning indexer: index queries ``qI = c_q W_iq`` (heads
of ``index_head_dim``), ONE index key ``kI = layer_norm(a W_ik)`` a token,
head weights ``w = a W_iw``, the first ``qk_rope_head_dim`` values of every
``qI`` head and of ``kI`` rotated; ``I[t, s] = sum_h w_t[h] relu(qI_t[h] .
kI_s)`` in float32 over the visible ``s <= t``; the ``index_topk`` largest
are PICKED (a tie to the lower position; every visible position while there
are no more than that).  A ``"shared"`` layer holds no indexer and uses the
picks of the last ``"full"`` layer before it: they travel beside the
residual.  The softmax runs over the picked positions alone.  The ``"full"``
layers' index keys are a SECOND pool, through the same page table
(``benchmark/reference/glm52.py`` states all of it in plain float32).

**What is cached** is one vector a token a layer, ``[c_kv | k_rope]``
(``cfg.latent_width`` values, padded to ``cfg.cache_width``: whole lane
tiles), in pages ``[L, P, page_sz, width]`` that the
programs below never rebuild (``models/decoder.py``'s paged-cache contract:
writes are one scatter a layer on a flat view, reads are whole pages through
the table over the extent the table handed in covers).  Two attention paths
read it:

- **expanded** (a prompt, or the divergent suffix after a prefix hit): K
  and V are expanded from the latents and attended as usual, queries in
  blocks so that no ``[heads, T, T]`` float32 block is ever whole;
- **absorbed** (the decode step, one query a row): ``W_kvb``'s key part is
  folded into the query and its value part into the output, so attention
  runs over the latent pages as they lie — heads are rows of one dot
  against ``[positions, width]``, the form ``decoder._attend_folded`` found
  for the twin pools.

Under an indexer the picks take two forms.  A window of SEVERAL queries (a
prompt, the chunk after a prefix hit) keeps them as a MASK ``[B, T, S]`` over
the expanded path's dense scores.  The decode step keeps them as POSITIONS
``[B, index_topk]`` with a flag of which are real: it reads the ``"full"``
layers' index keys of the row's pages, and in every layer gathers
``index_topk`` latents a row by position through the table — never the
row's whole extent — for the absorbed path.

**The share.**  The expert layer is TOLD which experts it holds
(``cfg.expert_first``, ``cfg.experts_held``).  The router keeps its width,
its groups and its experts per token; the layer computes ``sum over held e
of g_e Expert_e(f)`` for the tokens routed to them, plus the shared expert.
No token is dropped and no capacity is sized: assignments are sorted by
expert, laid out contiguous and run as row tiles, as many as there are (a
loop whose trip count is data), so an expert's weights are read only if a
token chose it.  What
the absent experts would add is left out — on one chip the layer runs
without its exchange.  Every program also returns its expert LOAD, summed
over the layers: the count of assignments to each held expert and the rows
the experts' products computed for them (whole tiles).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pdnlp_tpu.models import hyper_connections
from pdnlp_tpu.models.config import LatentMoEConfig
from pdnlp_tpu.models.decoder import _layer_rows
from pdnlp_tpu.ops import grouped
from pdnlp_tpu.ops.attention import NEG_INF

Params = Dict[str, Any]
F32 = jnp.float32

#: query rows of one attention block on the expanded path
Q_BLOCK = 512
#: the most rows of one tile of the experts' grouped products
EXPERT_BLOCK = 128
#: the most tokens one pass of an expert layer takes (:func:`moe_in_parts`)
MOE_TOKENS = 4096
#: query rows of one block of index scores ([rows, index heads, keys] float32)
INDEX_BLOCK = 128
#: float32 bytes of one attention block's scores under a mask of picks: the
#: block's rows follow it (:func:`_mask_block`)
MASK_SCORE_BYTES = 2 ** 28
#: key extents a prompt's masked attention is cut into (:func:`_attend_masked`)
MASK_GROUPS = 4


# ------------------------------------------------------------------- weights

def _weight_dtype(cfg: LatentMoEConfig):
    return jnp.dtype(cfg.weight_dtype)


def param_shapes(cfg: LatentMoEConfig) -> Dict[str, Any]:
    """The parameter tree as shapes.  Matrices are ``y = x @ w``; the key and
    value halves of ``W_kvb`` (and the two halves of ``W_qb``) are separate
    leaves, which is a layout of the published matrix's columns."""
    H, N = cfg.hidden_size, cfg.num_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F, I = cfg.moe_intermediate_size, cfg.intermediate_size
    E, Eh = cfg.n_routed_experts, cfg.experts_held

    def attn(lead):
        return {"in_norm": lead + (H,), "q_a": lead + (H, qr),
                "q_norm": lead + (qr,), "q_b_nope": lead + (qr, N * dn),
                "q_b_rope": lead + (qr, N * dr),
                "kv_a": lead + (H, kr + dr), "kv_norm": lead + (kr,),
                "kv_b_k": lead + (kr, N * dn), "kv_b_v": lead + (kr, N * dv),
                "o": lead + (N * dv, H), "post_norm": lead + (H,)}

    def ffn(lead, width):
        return {"gate": lead + (H, width), "up": lead + (H, width),
                "down": lead + (width, H)}

    K, M = cfg.first_k_dense, cfg.num_moe_layers
    Fs = F * cfg.n_shared_experts
    shapes = {
        "embed": (cfg.vocab_size, H),
        "dense": {"attn": attn((K,)), "ffn": ffn((K,), I)},
        "moe": {"attn": attn((M,)), "router": (M, H, E),
                "experts": ffn((M, Eh), F), "shared": ffn((M,), Fs)},
        "final_norm": (H,),
    }
    if cfg.selection_bias:
        shapes["moe"]["router_bias"] = (M, E)
    if cfg.index_n_heads:
        # one set a "full" layer, dense or expert: stacked by their order
        Nf, Hi, di = cfg.num_index_layers, cfg.index_n_heads, cfg.index_head_dim
        shapes["indexer"] = {
            "iq": (Nf, qr, Hi * di), "ik": (Nf, H, di), "ik_norm": (Nf, di),
            "ik_bias": (Nf, di), "iw": (Nf, H, Hi)}
    if cfg.hc_mult > 1:
        # one mixing a sub-layer: before attention, before the feed-forward
        for part, lead in (("dense", (K,)), ("moe", (M,))):
            shapes[part]["hc"] = {
                sub: hyper_connections.param_shapes(cfg.hc_mult, H, lead)
                for sub in ("attn", "ffn")}
    return shapes


def _is_norm(path) -> bool:
    return "norm" in str(getattr(path[-1], "key", ""))


def init_params(key: jax.Array, cfg: LatentMoEConfig) -> Params:
    """Seeded weights in the family's STORED dtype: matrices normal /
    sqrt(fan-in), norm gains 1 + 0.1 normal, the selection bias and the
    index key's norm bias 0.1 normal, the mixing leaves
    ``hyper_connections.init_leaf``'s.  One leaf at a
    time, so that nothing float32 the size of the model is ever alive."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    wd = _weight_dtype(cfg)
    out = []
    for i, (path, shape) in enumerate(leaves):
        k, name = jax.random.fold_in(key, i), path[-1].key
        if any(p.key == "hc" for p in path):
            out.append(hyper_connections.init_leaf(
                k, name, shape, cfg.hc_mult).astype(wd))
            continue
        x = jax.random.normal(k, shape, F32)
        if _is_norm(path):
            x = 1.0 + 0.1 * x
        elif name in ("router_bias", "ik_bias"):
            x = 0.1 * x
        elif len(shape) >= 2 and path[0].key != "embed":
            x = x * (shape[-2] ** -0.5)
        out.append(x.astype(wd))
    return jax.tree_util.tree_unflatten(treedef, out)


def init_head(key: jax.Array, cfg: LatentMoEConfig) -> Params:
    """The untied output head ``[hidden, vocab]``."""
    w = jax.random.normal(key, (cfg.hidden_size, cfg.vocab_size), F32)
    return {"kernel": (w * cfg.hidden_size ** -0.5).astype(_weight_dtype(cfg))}


def param_count(cfg: LatentMoEConfig) -> int:
    leaves = jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    return int(sum(int(np.prod(s)) for s in leaves)
               + cfg.hidden_size * cfg.vocab_size)


# --------------------------------------------------------------- small parts

def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """RMSNorm, statistics in float32, result in ``x``'s dtype."""
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(F32)).astype(x.dtype)


def _einsum(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """``einsum`` of two operands of one dtype, accumulated in and returned
    as float32.  The CPU backend has no bfloat16 dot with a float32 result;
    there the operands are promoted first, which gives the same sums (a
    product of two bfloat16 values is exact in float32)."""
    if a.dtype != F32 and jax.default_backend() == "cpu":
        a, b = a.astype(F32), b.astype(F32)
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def _mm(x: jax.Array, w: jax.Array, dtype) -> jax.Array:
    """``x @ w`` with operands in the compute dtype, accumulated in float32."""
    return _einsum("...k,kn->...n", x.astype(dtype), w.astype(dtype))


def _gated(x: jax.Array, p: Params, dtype) -> jax.Array:
    """``W_d (silu(W_g x) * W_u x)``, float32 out."""
    g = _mm(x, p["gate"], dtype)
    u = _mm(x, p["up"], dtype)
    return _mm((jax.nn.silu(g) * u).astype(dtype), p["down"], dtype)


def yarn_inv_freq(cfg: LatentMoEConfig) -> np.ndarray:
    """The rotary frequencies under yarn scaling ``[rope_dim / 2]``: each
    frequency is the published one, the published one over ``factor``, or a
    ramp between them by how many turns it makes over the original context."""
    d, base = cfg.qk_rope_head_dim, cfg.rope_theta
    i = np.arange(0, d, 2, dtype=np.float64) / d
    extra, inter = 1.0 / base ** i, 1.0 / (cfg.rope_factor * base ** i)
    if cfg.rope_factor <= 1:
        return extra.astype(np.float32)         # the plain table

    def correction(turns):
        return d * math.log(cfg.rope_original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(cfg: LatentMoEConfig) -> float:
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _rope_tables(cfg: LatentMoEConfig, positions: jax.Array):
    """cos, sin ``[..., rope_dim / 2]`` float32 at ``positions``."""
    ang = positions.astype(F32)[..., None] * jnp.asarray(yarn_inv_freq(cfg))
    m = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def _rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the pairs ``(x[i], x[i + d/2])`` by the i-th angle."""
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


# ----------------------------------------------------------------- attention

def _project(a: jax.Array, ap: Params, cfg: LatentMoEConfig,
             positions: jax.Array, dtype):
    """Normed input ``a [B, T, H]`` -> (q_nope ``[B, T, N, dn]``, q_rope
    ``[B, T, N, dr]``, latent ``[B, T, kr + dr]`` = ``[c_kv | k_rope]``, the
    normed query latent ``c_q [B, T, qr]`` an indexer reads)."""
    B, T = a.shape[:2]
    N, kr = cfg.num_heads, cfg.kv_lora_rank
    cos, sin = _rope_tables(cfg, positions)                    # [B, T, dr/2]
    cq = _rms(_mm(a, ap["q_a"], dtype).astype(dtype), ap["q_norm"],
              cfg.rms_norm_eps)
    q_nope = _mm(cq, ap["q_b_nope"], dtype).astype(dtype).reshape(
        B, T, N, cfg.qk_nope_head_dim)
    q_rope = _mm(cq, ap["q_b_rope"], dtype).astype(dtype).reshape(
        B, T, N, cfg.qk_rope_head_dim)
    q_rope = _rope(q_rope, cos[:, :, None], sin[:, :, None])
    kv = _mm(a, ap["kv_a"], dtype).astype(dtype)
    c_kv = _rms(kv[..., :kr], ap["kv_norm"], cfg.rms_norm_eps)
    k_rope = _rope(kv[..., kr:], cos, sin)
    return q_nope, q_rope, jnp.concatenate([c_kv, k_rope], axis=-1), cq


def _softmax_rows(scores: jax.Array, qpos: jax.Array, kpos: jax.Array,
                  dtype, mask: Optional[jax.Array] = None) -> jax.Array:
    """``scores [B, N, T, S]`` float32 -> probabilities in ``dtype``; key j
    is visible to query t iff ``kpos[j] <= qpos[b, t]`` — or, under an
    indexer's picks, iff ``mask[b, t, j]``."""
    vis = (kpos[None, None, None, :] <= qpos[:, None, :, None]
           if mask is None else mask[:, None])
    return jax.nn.softmax(jnp.where(vis, scores, NEG_INF), axis=-1).astype(dtype)


@jax.named_scope("mla.attend")
def attend_expanded(q_nope, q_rope, latent, ap: Params,
                    cfg: LatentMoEConfig, qpos: jax.Array, dtype,
                    causal_cut: bool = False,
                    mask: Optional[jax.Array] = None) -> jax.Array:
    """K and V expanded from ``latent [B, S, width]`` (key j at position j),
    queries at ``qpos [B, T]`` attended in blocks of :data:`Q_BLOCK`.
    ``causal_cut``: query t IS position t (a prompt from position 0), so a
    block needs only the keys up to its own end.  ``mask [B, T, S]``: an
    indexer's picks — key j takes part in query t's softmax iff ``mask[b, t,
    j]`` (:func:`_attend_masked`).  -> ``[B, T, N * dv]``."""
    B, T, N = q_nope.shape[:3]
    S, kr = latent.shape[1], cfg.kv_lora_rank
    c_kv, k_rope = latent[..., :kr], latent[..., kr:cfg.latent_width]
    k_nope = _mm(c_kv, ap["kv_b_k"], dtype).astype(dtype).reshape(
        B, S, N, cfg.qk_nope_head_dim)
    v = _mm(c_kv, ap["kv_b_v"], dtype).astype(dtype).reshape(
        B, S, N, cfg.v_head_dim)
    scale = softmax_scale(cfg)

    def block(qn, qr, qp, s1, m=None):
        scores = (_einsum("btnd,bsnd->bnts", qn, k_nope[:, :s1])
                  + _einsum("btnr,bsr->bnts", qr, k_rope[:, :s1]))
        probs = _softmax_rows(scores * scale, qp,
                              jnp.arange(s1, dtype=jnp.int32), dtype, m)
        return _einsum("bnts,bsnd->btnd", probs, v[:, :s1]).astype(dtype)

    if mask is not None:
        o = _attend_masked(block, q_nope, q_rope, qpos, mask, causal_cut)
    elif T <= Q_BLOCK:
        o = block(q_nope, q_rope, qpos, min(S, T) if causal_cut else S)
    elif causal_cut or T % Q_BLOCK:
        # a prompt from position 0: each block has its own key extent
        o = jnp.concatenate(
            [block(q_nope[:, t0:t0 + Q_BLOCK], q_rope[:, t0:t0 + Q_BLOCK],
                   qpos[:, t0:t0 + Q_BLOCK],
                   min(S, t0 + Q_BLOCK) if causal_cut else S)
             for t0 in range(0, T, Q_BLOCK)], axis=1)
    else:
        # blocks of one shape, ONE AFTER ANOTHER: unrolled, the compiler
        # keeps every block's float32 scores alive at once (six blocks of
        # [64, 512, 4096]: 3.1 GiB of temporaries, read from a
        # described-v5e compile of the 3072 chunk)
        def cut(x):
            return jnp.moveaxis(
                x.reshape((B, T // Q_BLOCK, Q_BLOCK) + x.shape[2:]), 1, 0)

        o = jax.lax.map(lambda a: block(a[0], a[1], a[2], S),
                        (cut(q_nope), cut(q_rope), cut(qpos)))
        o = jnp.moveaxis(o, 0, 1).reshape(B, T, N, cfg.v_head_dim)
    return o.reshape(B, T, N * cfg.v_head_dim)


def _mask_block(N: int, S: int) -> int:
    """Query rows of one attention block under a mask of picks: the largest
    power of two up to :data:`Q_BLOCK` whose ``[N, rows, S]`` float32 scores
    stay within :data:`MASK_SCORE_BYTES` (64 heads over 7 680 keys: 128)."""
    rows = Q_BLOCK
    while rows > 16 and N * rows * S * 4 > MASK_SCORE_BYTES:
        rows //= 2
    return rows


def _attend_masked(block, q_nope, q_rope, qpos, mask, causal_cut: bool):
    """:func:`attend_expanded`'s blocks under ``mask [B, T, S]``, ONE AFTER
    ANOTHER (``lax.map``: never two blocks' scores alive).  ``causal_cut``:
    the blocks go in up to :data:`MASK_GROUPS` runs, each with the key extent
    its last block needs — five eighths of the whole square's products at
    four runs, where a block of its own extent each (a half) would unroll
    sixty blocks a layer.  -> ``[B, T, N, dv]``."""
    B, T, N = q_nope.shape[:3]
    S = mask.shape[-1]
    rows = _mask_block(N, S)
    if T <= rows or T % rows:
        s1 = min(S, T) if causal_cut else S
        return block(q_nope, q_rope, qpos, s1, mask[..., :s1])
    n = T // rows
    per = -(-n // (min(MASK_GROUPS, n) if causal_cut else 1))
    outs = []
    for g0 in range(0, n, per):
        g1 = min(n, g0 + per)
        s1 = min(S, g1 * rows) if causal_cut else S

        def cut(x, t0=g0 * rows, t1=g1 * rows, g=g1 - g0):
            return jnp.moveaxis(
                x[:, t0:t1].reshape((B, g, rows) + x.shape[2:]), 1, 0)

        o = jax.lax.map(lambda a, s1=s1: block(a[0], a[1], a[2], s1, a[3]),
                        (cut(q_nope), cut(q_rope), cut(qpos),
                         cut(mask[..., :s1])))
        outs.append(jnp.moveaxis(o, 0, 1).reshape((B, -1) + o.shape[3:]))
    return jnp.concatenate(outs, axis=1)


@jax.named_scope("mla.attend")
def attend_absorbed(q_nope, q_rope, latent, ap: Params,
                    cfg: LatentMoEConfig, qpos: jax.Array, dtype,
                    mask: Optional[jax.Array] = None) -> jax.Array:
    """The same attention with ``W_kvb`` absorbed: ``q' = q_nope W_kvb[k]``
    (per head, ``kr`` wide), scores ``q' . c_kv + q_rope . k_rope`` against
    the latents as they lie (heads are rows of ONE dot), ``o = (P c_kv)
    W_kvb[v]``.  For few query rows: the decode step.  Both dots take the
    latents WHOLE (``P @ latent``, its ``c_kv`` columns cut from the small
    result): cutting the columns out of the gathered pages first is a copy
    of them, and cutting them out of the pool a copy of the pool (read from
    a described-v5e compile: 3.2 GB).  ``mask [B, T, S]``: ``latent`` holds a
    row's PICKED positions (any order) and ``mask`` says which are real."""
    B, T, N = q_nope.shape[:3]
    S, kr = latent.shape[1], cfg.kv_lora_rank
    wk = ap["kv_b_k"].astype(dtype).reshape(kr, N, cfg.qk_nope_head_dim)
    wv = ap["kv_b_v"].astype(dtype).reshape(kr, N, cfg.v_head_dim)
    q_abs = _einsum("btnd,cnd->btnc", q_nope, wk).astype(dtype)
    # zeros under the cache's padding columns: the latents are read whole
    pad = jnp.zeros((B, T, N, latent.shape[-1] - cfg.latent_width), dtype)
    qf = jnp.concatenate([q_abs, q_rope, pad], axis=-1).reshape(B, T * N, -1)
    scores = _einsum("bqc,bsc->bqs", qf, latent) * softmax_scale(cfg)
    probs = _softmax_rows(
        jnp.swapaxes(scores.reshape(B, T, N, S), 1, 2), qpos,
        jnp.arange(S, dtype=jnp.int32), dtype, mask)           # [B, N, T, S]
    o_lat = _einsum("bnts,bsc->btnc", probs, latent)[..., :kr].astype(dtype)
    o = _einsum("btnc,cnd->btnd", o_lat, wv).astype(dtype)
    return o.reshape(B, T, N * cfg.v_head_dim)


# ------------------------------------------------------------------- indexer

def _layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float
                ) -> jax.Array:
    """LayerNorm with weight and bias, statistics and result in float32."""
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return (xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) \
        + b.astype(F32)


def index_project(a: jax.Array, cq: jax.Array, ip: Params,
                  cfg: LatentMoEConfig, positions: jax.Array, dtype):
    """A ``"full"`` layer's indexer on the layer's normed input ``a [B, T,
    H]`` and normed query latent ``cq [B, T, qr]`` -> (index queries ``[B,
    T, Hi, di]`` and the ONE index key a token ``[B, T, di]``, both in
    ``dtype`` with their first ``qk_rope_head_dim`` values rotated at
    ``positions``; head weights ``[B, T, Hi]`` float32)."""
    B, T = a.shape[:2]
    Hi, di, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    cos, sin = _rope_tables(cfg, positions)

    def rotated(x, cos, sin):
        return jnp.concatenate([_rope(x[..., :dr], cos, sin), x[..., dr:]],
                               axis=-1)

    with jax.named_scope("dsa.index"):
        q = _mm(cq, ip["iq"], dtype).astype(dtype).reshape(B, T, Hi, di)
        k = _layer_norm(_mm(a, ip["ik"], dtype), ip["ik_norm"],
                        ip["ik_bias"], cfg.rms_norm_eps).astype(dtype)
        return (rotated(q, cos[:, :, None], sin[:, :, None]),
                rotated(k, cos, sin), _mm(a, ip["iw"], dtype))


def index_scores(qI: jax.Array, w: jax.Array, kI: jax.Array) -> jax.Array:
    """``I[b, t, s] = sum_h w[b, t, h] relu(qI[b, t, h] . kI[b, s])`` float32
    (``kI [B, S, >= di]``: a pool's rows may be held wider than a key)."""
    B, T, Hi, di = qI.shape
    dots = _einsum("bqd,bsd->bqs", qI.reshape(B, T * Hi, di), kI[..., :di])
    return jnp.sum(jax.nn.relu(dots).reshape(B, T, Hi, -1) * w[..., None],
                   axis=2)


def _seen(scores: jax.Array, visible: jax.Array) -> jax.Array:
    """Float32 scores as a pick compares them: what is not visible below
    everything, and -0 made +0 (equal as scores, apart as bits and to the
    sort beneath ``lax.top_k``)."""
    return jnp.where(visible, jnp.where(scores == 0, 0.0, scores),
                     -jnp.inf).astype(F32)


def select_mask(scores: jax.Array, visible: jax.Array, k: int) -> jax.Array:
    """The ``k`` largest visible ``scores [..., S]`` of every row as a MASK
    (all the visible where there are no more than ``k``); a tie goes to the
    lower position.  Exact, and without a sort: the ``k``-th largest value
    is found a bit at a time over the floats' order-preserving integer keys
    (32 counts over the row), then the lowest tied positions fill what is
    left.  On the chip a prompt's ``[T, T]`` pick costs 45 passes over its
    scores where ``lax.top_k`` of thousands sorts them (PERF.md section 6,
    PR 43)."""
    if k >= scores.shape[-1]:
        return visible
    u = jax.lax.bitcast_convert_type(_seen(scores, visible), jnp.uint32)
    key = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))

    def bit(i, thr):
        cand = thr | jnp.left_shift(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        n = jnp.sum(key >= cand, axis=-1, keepdims=True, dtype=jnp.int32)
        return jnp.where(n >= k, cand, thr)

    thr = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32))
    above, tied = key > thr, key == thr
    need = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    first = jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= need
    return (above | (tied & first)) & visible


def select_picks(scores: jax.Array, visible: jax.Array, k: int):
    """The same pick as POSITIONS, for one query a row: ``scores [B, S]`` ->
    (positions ``[B, min(k, S)]`` int32, which of them are real ``[B, min(k,
    S)]``).  ``lax.top_k`` puts the lower index first among equals."""
    vals, pos = jax.lax.top_k(_seen(scores, visible),
                              min(k, scores.shape[-1]))
    return pos.astype(jnp.int32), vals > -jnp.inf


def index_mask(qI, w, kI, qpos: jax.Array, cfg: LatentMoEConfig) -> jax.Array:
    """The picks of a window of queries at ``qpos [B, T]`` over the keys
    ``kI [B, S, >= di]`` (key j at position j, visible iff ``j <= qpos``) as
    a mask ``[B, T, S]``; queries in blocks of :data:`INDEX_BLOCK`, one after
    another, so that ``[rows, heads, S]`` float32 is the most alive."""
    B, T = qpos.shape
    kpos = jnp.arange(kI.shape[1], dtype=jnp.int32)

    def block(q, wt, qp):
        with jax.named_scope("dsa.index"):
            scores = index_scores(q, wt, kI)
        with jax.named_scope("dsa.select"):
            return select_mask(scores, kpos[None, None, :] <= qp[..., None],
                               cfg.index_topk)

    if T <= INDEX_BLOCK or T % INDEX_BLOCK:
        return block(qI, w, qpos)

    def cut(x):
        return jnp.moveaxis(x.reshape(
            (B, T // INDEX_BLOCK, INDEX_BLOCK) + x.shape[2:]), 1, 0)

    m = jax.lax.map(lambda a: block(*a), (cut(qI), cut(w), cut(qpos)))
    return jnp.moveaxis(m, 0, 1).reshape(B, T, -1)


def no_picks():
    """The zero of what a program counts of its selection: positions visible
    to its real queries and positions picked for them, both int32 scalars
    (of the FIRST ``"full"`` layer: every one picks as many)."""
    return jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)


def _count_picks(picked: jax.Array, positions: jax.Array, real: jax.Array):
    """``picked [B, T, ...]`` flags, queries at ``positions [B, T]`` of which
    ``real`` count -> (visible, picked) as :func:`no_picks`."""
    n = jnp.sum(picked.reshape(real.shape + (-1,)), axis=-1, dtype=jnp.int32)
    return (jnp.sum(jnp.where(real, positions + 1, 0), dtype=jnp.int32),
            jnp.sum(jnp.where(real, n, 0), dtype=jnp.int32))


# -------------------------------------------------------------- expert layer

def route(f: jax.Array, router: jax.Array, cfg, dtype,
          bias: Optional[jax.Array] = None):
    """``f [T, H]`` -> (expert ids ``[T, k]``, gates ``[T, k]`` float32,
    scores ``[T, E]`` float32): sigmoid scores over ALL experts; a group's
    score is the sum of its two largest; the ``topk_group`` best groups
    stay; the ``k`` largest scores inside them are taken, normalised to sum
    1 and scaled.  With a selection ``bias [E]`` (``noaux_tc``) groups and
    experts are CHOSEN by ``score + bias`` and the chosen are GATED by their
    scores alone."""
    T, E, G = f.shape[0], cfg.n_routed_experts, cfg.n_group
    s = jax.nn.sigmoid(_mm(f, router, dtype))
    sel = s if bias is None else s + bias.astype(F32)
    grp = jax.lax.top_k(sel.reshape(T, G, E // G), 2)[0].sum(-1)     # [T, G]
    _, keep = jax.lax.top_k(grp, cfg.topk_group)
    in_kept = jnp.zeros((T, G), bool).at[
        jnp.arange(T)[:, None], keep].set(True)
    # a biased score can be negative: a dropped group's lie below any
    masked = jnp.where(jnp.repeat(in_kept, E // G, axis=1), sel,
                       0.0 if bias is None else -jnp.inf)
    top, idx = jax.lax.top_k(masked, cfg.num_experts_per_tok)
    if bias is not None:
        top = jnp.take_along_axis(s, idx, axis=-1)
    gates = top / (top.sum(-1, keepdims=True) + 1e-20) \
        * cfg.routed_scaling_factor
    return idx, gates, s


def expert_tile(T: int, k: int, cfg: LatentMoEConfig) -> int:
    """Rows of one tile of the experts' grouped products, from the load the
    shapes state: eight times one expert's expected run ``T * k /
    n_routed_experts`` as a power of two between 16 — a bfloat16 sublane
    tile — and :data:`EXPERT_BLOCK`: 16-64 rows in a decode step,
    :data:`EXPERT_BLOCK` in a prompt (measured on the chip at the
    four-stream cell's sizes, PERF.md PR 39: a decode step's products 9.07 /
    8.22 / 7.82 ms at 16 / 32 / 64 rows, a prompt's 19.3 / 20.0 ms at 128 /
    256 — a tile is a step of the kernel's grid, and what a step computes
    hides behind the weights it waits for)."""
    tile = 16
    while tile < min(8 * T * k / cfg.n_routed_experts, EXPERT_BLOCK):
        tile *= 2
    return tile


def expert_window(T: int, k: int, cfg: LatentMoEConfig) -> int:
    """Sorted assignments laid out at a time, in whole tiles: twice what
    this process's share of the experts expects of the ``T * k`` (all of
    them where it holds every expert).  More than that — every token
    choosing held experts — takes further passes, never a drop."""
    tile = expert_tile(T, k, cfg)
    rows = min(T * k, 2 * T * k * cfg.experts_held // cfg.n_routed_experts)
    return max(-(-rows // tile), 1) * tile


def _window_runs(first, counts, lo, rows: int):
    """The held experts' runs ``[first, first + counts)`` of the sorted
    order, each cut to the window ``[lo, lo + rows)`` -> its rows there."""
    return (jnp.clip(first + counts - lo, 0, rows)
            - jnp.clip(first - lo, 0, rows))


def expert_rows(counts: jax.Array, T: int, k: int, cfg: LatentMoEConfig
                ) -> jax.Array:
    """Rows the grouped products of :func:`held_experts` compute for
    ``counts [experts_held]`` assignments: the row tiles that hold any of
    them, window by window (a tile that two runs share is computed once for
    each)."""
    tile, rows = expert_tile(T, k, cfg), expert_window(T, k, cfg)
    first = jnp.cumsum(counts) - counts
    lo = jnp.arange(0, T * k, rows, dtype=jnp.int32)[:, None]
    return tile * jnp.sum(grouped.tiles_held(
        _window_runs(first[None, :], counts[None, :], lo, rows), tile))


@functools.partial(jax.jit, static_argnames=("cfg", "dtype"))
def held_experts(f: jax.Array, idx: jax.Array, gates: jax.Array,
                 valid: jax.Array, experts: Params, m, cfg: LatentMoEConfig,
                 dtype):
    """``sum over held e of g_e Expert_e(f)`` -> (``[T, H]`` float32, counts
    ``[experts_held]`` int32).  Dropless: the ``T * k`` assignments are
    sorted by expert and their rows of ``f`` LAID OUT in that order by one
    gather (a window of :func:`expert_window` rows at a time: one pass
    unless more tokens chose held experts than twice the share expects);
    the experts' gated feed-forward runs over the layout as two grouped
    products (``ops/grouped.py``: group sizes are data, row tiles of
    :func:`expert_tile`, an expert's matrices read once and only if a token
    chose it); a gather by the inverse permutation brings the results back
    to token order, a slot of the ``k`` at a time, where a token's gated
    parts are summed in float32.  Assignments to absent experts and of rows
    that are not ``valid`` (padding, dead slots) sort past the end, are
    never computed and add exactly 0.
    ``experts``: EVERY expert layer's ``gate`` / ``up`` ``[M, Eh, H, F]`` and
    ``down`` ``[M, Eh, F, H]``, of which this is layer ``m``: the products
    read an expert's matrices where they lie, the stack seen as ``M * Eh``
    groups (a layer's slab cut out first — as a scan over the layers would —
    is a copy of all twelve, read from a described-v5e compile: three
    temporaries of 352 MB a layer).  Jitted, so that a trunk that calls it a
    layer at a time lowers it once a program."""
    T, H = f.shape
    k, Eh = idx.shape[1], cfg.experts_held
    tile, rows = expert_tile(T, k, cfg), expert_window(T, k, cfg)
    local = idx - cfg.expert_first
    held = (local >= 0) & (local < Eh) & valid[:, None]
    flat_e = jnp.where(held, local, Eh).reshape(-1)
    counts = jnp.sum(flat_e[:, None] == jnp.arange(Eh)[None, :], axis=0,
                     dtype=jnp.int32)                                # [Eh]
    first = jnp.cumsum(counts) - counts            # an expert's first row
    order = jnp.argsort(flat_e, stable=True)
    # assignment (t, j) lies at row at[t, j] of the sorted order
    at = jnp.argsort(order).astype(jnp.int32).reshape(T, k)
    # slack behind the T * k rows: a window's slice is never clamped back
    tok_sorted = jnp.concatenate(
        [(order // k).astype(jnp.int32), jnp.zeros((rows,), jnp.int32)])
    stack = {n: w.reshape((-1,) + w.shape[2:]) for n, w in experts.items()}

    def window(p, out):
        lo = p * rows
        pairs, n_pairs = grouped.plan(
            _window_runs(first, counts, lo, rows), tile, rows // tile)
        with jax.named_scope("experts.lay"):
            x = jnp.take(f, jax.lax.dynamic_slice(tok_sorted, (lo,), (rows,)),
                         axis=0).astype(dtype)
        with jax.named_scope("experts.loop"):
            a = grouped.grouped(
                x, (stack["gate"], stack["up"]), pairs, n_pairs, m * Eh,
                tile=tile, combine=lambda g, u: jax.nn.silu(g) * u,
                out_dtype=dtype)
            y = grouped.grouped(a, (stack["down"],), pairs, n_pairs, m * Eh,
                                tile=tile)
        with jax.named_scope("experts.unsort"):
            # rows past the last run's were never written: selected, not
            # multiplied, away
            here = held & (at >= lo) & (at < lo + rows)
            back = jnp.clip(at - lo, 0, rows - 1)
            for j in range(k):
                out = out + jnp.where(
                    here[:, j, None],
                    jnp.take(y, back[:, j], axis=0) * gates[:, j, None], 0.0)
            return out

    out = jax.lax.fori_loop(0, -(-jnp.sum(counts) // rows), window,
                            jnp.zeros((T, H), F32))
    return out, counts


def moe_ffn(f: jax.Array, lp: Params, experts: Params, m,
            cfg: LatentMoEConfig, valid: jax.Array, dtype):
    """Expert layer ``m`` on ``f [T, H]``: this process's experts' part plus
    the shared expert -> (``[T, H]`` float32, the layer's load: counts
    ``[experts_held]`` and the rows computed for them, :func:`no_load`)."""
    idx, gates, _ = route(f, lp["router"], cfg, dtype, lp.get("router_bias"))
    routed, counts = held_experts(f, idx, gates, valid, experts, m, cfg,
                                  dtype)
    rows = expert_rows(counts, f.shape[0], idx.shape[1], cfg)
    return routed + _gated(f, lp["shared"], dtype), (counts, rows)


def moe_in_parts(f: jax.Array, lp: Params, experts: Params, m,
                 cfg: LatentMoEConfig, valid: jax.Array, dtype):
    """:func:`moe_ffn` over ``f [T, H]`` in equal parts of at most
    :data:`MOE_TOKENS` tokens, one after another, their loads summed: a
    prompt of 8 192 tokens in one pass keeps 2.07 GiB of temporaries alive
    (the ``k`` gathers of the un-sort, float32 ``[T, H]`` each; read from a
    described-v5e compile, PERF.md section 6, PR 43), which do not fit
    beside 12.6 GiB of weights and pools.  A held expert's matrices are
    then read once a part.  No prompt of the other configurations is longer
    than one part."""
    n = -(-f.shape[0] // MOE_TOKENS)
    if n == 1 or f.shape[0] % n:
        return moe_ffn(f, lp, experts, m, cfg, valid, dtype)
    ys, (counts, rows) = jax.lax.map(
        lambda a: moe_ffn(a[0], lp, experts, m, cfg, a[1], dtype),
        (f.reshape(n, -1, f.shape[1]), valid.reshape(n, -1)))
    return ys.reshape(f.shape), (counts.sum(0), rows.sum())


def no_load(cfg: LatentMoEConfig):
    """The zero of what a program counts of its expert layers: assignments
    to each held expert ``[experts_held]`` and the rows the experts'
    products computed (a scalar), both int32, summed over the layers."""
    return (jnp.zeros((cfg.experts_held,), jnp.int32),
            jnp.zeros((), jnp.int32))


def add_load(a, b):
    """The sum of two loads (:func:`no_load`'s pairs)."""
    return a[0] + b[0], a[1] + b[1]


# -------------------------------------------------------------------- layers

def _read(x, hp: Optional[Params], cfg: LatentMoEConfig):
    """What a sub-layer reads of the residual, and how its output goes back
    (:func:`_write`): ``x [B, T, H]`` itself and a plain add — or, of the
    streams ``x [n, B, T, H]`` with the sub-layer's mixing leaves ``hp``,
    ``H_pre @ X`` and the pair (``H_res``, ``H_post``)."""
    if hp is None:
        return x, None
    pre, post, res = hyper_connections.coefficients(x, hp, cfg)
    return hyper_connections.read(x, pre), (res, post)


def _write(x, y, mix, dtype):
    """The residual after a sub-layer's output ``y [B, T, H]`` float32."""
    if mix is None:
        return x + y.astype(dtype)
    return hyper_connections.write(x, y, *mix)


def _layer(x, lp: Params, cfg: LatentMoEConfig, l, positions, valid, attend,
           carry, dtype, experts: Optional[Params] = None,
           indexer: Optional[Params] = None):
    """Layer ``l`` on the residual ``x [B, T, H]`` (``[n, B, T, H]`` streams
    where ``lp`` holds mixing leaves).  ``attend(l, carry, q_nope, q_rope,
    latent, ap) -> ([B, T, N * dv], carry')`` puts the latent where it has
    to go (the pool, in place; or the collected prompt latents) and attends.
    ``experts``: every expert layer's experts (``None``: a dense layer).
    ``indexer``: a ``"full"`` layer's indexer leaves — ``attend`` is then
    also given :func:`index_project`'s three, scores and picks anew; without
    them it attends under the picks its carry holds.
    -> (x', carry', the layer's load or None)."""
    ap, hc = lp["attn"], lp.get("hc", {})
    u, mix = _read(x, hc.get("attn"), cfg)
    B, T, H = u.shape
    a = _rms(u, ap["in_norm"], cfg.rms_norm_eps)
    q_nope, q_rope, latent, cq = _project(a, ap, cfg, positions, dtype)
    index = () if indexer is None else (
        index_project(a, cq, indexer, cfg, positions, dtype),)
    o, carry = attend(l, carry, q_nope, q_rope, latent, ap, *index)
    x = _write(x, _mm(o, ap["o"], dtype), mix, dtype)
    u, mix = _read(x, hc.get("ffn"), cfg)
    f = _rms(u, ap["post_norm"], cfg.rms_norm_eps)
    if experts is None:
        y, load = _gated(f, lp["ffn"], dtype), None
    else:
        y, load = moe_in_parts(f.reshape(B * T, H), lp, experts,
                               l - cfg.first_k_dense, cfg,
                               valid.reshape(B * T), dtype)
        y = y.reshape(B, T, H)
    return _write(x, y, mix, dtype), carry, load


def _run_layers(params: Params, cfg: LatentMoEConfig, x, positions, valid,
                attend, carry, dtype):
    """The leading dense layer(s), then the expert layers under ONE scan
    whose carry holds ``attend``'s state (the pool is carried whole, never
    sliced by layer).  With an indexer the expert layers go in RUNS: a
    ``"full"`` layer stands alone (its leaves exist for it only, its place
    in the index pool is a number), the ``"shared"`` layers after it are one
    scan that carries its picks."""
    K = cfg.first_k_dense
    full = cfg.full_layers

    def indexer(l):
        return None if l not in full else jax.tree_util.tree_map(
            lambda w: w[full.index(l)], params["indexer"])

    for l in range(K):
        lp = jax.tree_util.tree_map(lambda w: w[l], params["dense"])
        x, carry, _ = _layer(x, lp, cfg, l, positions, valid, attend, carry,
                             dtype, indexer=indexer(l))

    def step(c, scanned, indexer=None):
        x, carry, load = c
        lp, l = scanned
        x, carry, n = _layer(x, lp, cfg, l, positions, valid, attend, carry,
                             dtype, experts, indexer)
        return (x, carry, add_load(load, n)), None

    # the experts stay OUT of the scanned inputs: a block indexes (layer,
    # expert) into the whole stack (held_experts)
    moe = dict(params["moe"])
    experts = moe.pop("experts")
    if not cfg.index_n_heads:
        li = jnp.arange(K, cfg.num_layers, dtype=jnp.int32)
        (x, carry, load), _ = jax.lax.scan(step, (x, carry, no_load(cfg)),
                                           (moe, li))
        return x, carry, load

    def of(l):
        # a layer's leaves where they lie in the stack (a run's layers cut
        # out to be scanned would be a copy of them)
        return jax.tree_util.tree_map(lambda w: w[l - K], moe)

    c, l = (x, carry, no_load(cfg)), K
    while l < cfg.num_layers:
        if l in full:
            (c, _), l = step(c, (of(l), l), indexer(l)), l + 1
            continue
        end = min([f for f in full if f > l], default=cfg.num_layers)
        c, _ = jax.lax.scan(lambda c, m: step(c, (of(m), m)), c,
                            jnp.arange(l, end, dtype=jnp.int32))
        l = end
    return c


def _logits(params: Params, head: Params, cfg: LatentMoEConfig, x, dtype):
    """Final norm and the untied head, float32."""
    return _mm(_rms(x, params["final_norm"], cfg.rms_norm_eps),
               head["kernel"], dtype)


def _embed(params: Params, ids: jax.Array, dtype) -> jax.Array:
    return jnp.take(params["embed"], ids, axis=0).astype(dtype)


def _streams(x: jax.Array, cfg: LatentMoEConfig) -> jax.Array:
    """The embedding as the layers' residual: itself, or copied into the
    ``hc_mult`` streams ``[n, B, T, H]``."""
    if cfg.hc_mult == 1:
        return x
    return jnp.broadcast_to(x[None], (cfg.hc_mult,) + x.shape)


def _read_out(x: jax.Array, at: jax.Array, cfg: LatentMoEConfig, dtype
              ) -> jax.Array:
    """Position ``at[b]`` of every row of the residual -> ``[B, 1, H]``: what
    the final norm reads (of streams, their SUM)."""
    at = at.astype(jnp.int32)[:, None, None]
    if cfg.hc_mult == 1:
        return jnp.take_along_axis(x, at, axis=1)
    x = jnp.take_along_axis(x, at[None], axis=2)
    return jnp.sum(x.astype(F32), axis=0).astype(dtype)


# ----------------------------------------------------------------- programs

def prefill(params: Params, head: Params, cfg: LatentMoEConfig,
            input_ids: jax.Array,       # [B, S] int32 (left-aligned)
            attention_mask: jax.Array,  # [B, S] {0,1}
            last_pos: jax.Array,        # [B] index of the last real token
            *, dtype=jnp.bfloat16):
    """A cold prompt: the expanded path from position 0 -> (next-token
    logits ``[B, vocab]`` float32, the load (:func:`no_load`), the latents
    ``[L, B, S, cache_width]`` for
    :func:`~pdnlp_tpu.models.decoder.insert_pool`).  With an indexer every
    query attends under its picks (a mask over the dense scores), the load
    is followed by :func:`no_picks`'s two counts, and the rows are a PAIR:
    the latents and the ``"full"`` layers' index keys ``[Lf, B, S,
    index_cache_width]``."""
    B, S = input_ids.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = attention_mask.astype(bool)
    x = _streams(_embed(params, input_ids, dtype), cfg)
    collected = jnp.zeros((cfg.num_layers, B, S, cfg.cache_width), dtype)
    pad = ((0, 0), (0, 0), (0, cfg.cache_width - cfg.latent_width))

    def attend(l, collected, q_nope, q_rope, latent, ap):
        o = attend_expanded(q_nope, q_rope, latent, ap, cfg, positions,
                            dtype, causal_cut=True)
        return o, jax.lax.dynamic_update_index_in_dim(
            collected, jnp.pad(latent, pad), l, axis=0)

    def attend_picked(l, carry, q_nope, q_rope, latent, ap, index=None):
        collected, keys, mask, counts = carry
        if index is not None:
            qI, kI, w = index
            at = cfg.full_layers.index(l)
            mask = index_mask(qI, w, kI, positions, cfg)
            keys = keys.at[at].set(jnp.pad(kI, (
                (0, 0), (0, 0), (0, keys.shape[-1] - kI.shape[-1]))))
            if at == 0:
                counts = _count_picks(mask, positions, valid)
        o = attend_expanded(q_nope, q_rope, latent, ap, cfg, positions,
                            dtype, causal_cut=True, mask=mask)
        collected = jax.lax.dynamic_update_index_in_dim(
            collected, jnp.pad(latent, pad), l, axis=0)
        return o, (collected, keys, mask, counts)

    if not cfg.index_n_heads:
        x, collected, load = _run_layers(params, cfg, x, positions, valid,
                                         attend, collected, dtype)
        news = collected
    else:
        keys = jnp.zeros((cfg.num_index_layers, B, S, cfg.index_cache_width),
                         dtype)
        x, (collected, keys, _, counts), load = _run_layers(
            params, cfg, x, positions, valid, attend_picked,
            (collected, keys, jnp.zeros((B, S, S), bool), no_picks()), dtype)
        load, news = load + counts, (collected, keys)
    h_last = _read_out(x, last_pos, cfg, dtype)
    return _logits(params, head, cfg, h_last, dtype)[:, 0], load, news


def paged_attend(params: Params, head: Params, cfg: LatentMoEConfig,
                 tokens: jax.Array,      # [B, T] int32
                 pool,                   # [L, P, page_sz, width]
                 page_table: jax.Array,  # [B, <= MP] int32 (sentinel P)
                 start: jax.Array,       # [B] abs position of tokens[:, 0]
                 nreal: Optional[jax.Array] = None,   # [B] real lengths
                 *, dtype=jnp.bfloat16, absorb: Optional[bool] = None):
    """The body of the decode step (T = 1, absorbed) and of the suffix chunk
    after a prefix hit (T = the bucket, expanded): ``tokens[b, t]`` sits at
    ``start[b] + t``, writes its latent through the table in place and
    attends to positions ``<= start[b] + t`` of the pages the table names.
    The contract is ``decoder.paged_attend_layers``'s, for one pool: padded
    window slots, dead rows (sentinel tables) and positions past the table
    write nothing and take no part in the expert layer.  -> (last real
    token's logits ``[B, vocab]``, the load, the pool).  ``absorb``: ``None``
    = by the window (T == 1); the tests ask for either path.

    With an indexer ``pool`` is the PAIR (latents, index keys ``[Lf, P,
    page_sz, index_cache_width]``) over ONE table, returned as a pair, and
    the load is followed by :func:`no_picks`'s counts.  A ``"full"`` layer
    writes its index key in place, reads the index keys of the table's
    pages and picks.  One query a row (the decode step): the picks are
    POSITIONS, and every layer gathers those ``index_topk`` latents a row by
    position through the table — never the row's extent.  A window of
    several: the picks are a mask over the extent's dense scores."""
    if cfg.index_n_heads:
        pool, ipool = pool
    L, P, ps, W = pool.shape
    B, T = tokens.shape
    MP = page_table.shape[1]
    extent = MP * ps
    start = start.astype(jnp.int32)
    positions = start[:, None] + jnp.arange(T, dtype=jnp.int32)
    real = positions < extent
    if nreal is not None:
        real &= jnp.arange(T, dtype=jnp.int32)[None, :] < nreal[:, None]
    phys = jnp.take_along_axis(
        page_table, jnp.clip(positions // ps, 0, MP - 1), axis=1)
    real &= phys < P
    wflat = jnp.where(real, phys * ps + positions % ps, P * ps)
    wrows = _layer_rows(wflat, L, P * ps).reshape(L, B * T)
    rpages = _layer_rows(page_table, L, P)                     # [L, B, MP]
    absorb = (T == 1) if absorb is None else absorb

    def put(pool, rows, l, new):
        """``new [B, T, <= width]`` into layer ``l``'s rows ``rows[l]`` of a
        pool's flat view -> the view."""
        width = pool.shape[-1]
        new = jnp.pad(new, ((0, 0), (0, 0), (0, width - new.shape[-1])))
        return pool.reshape(-1, width).at[rows[l]].set(
            new.reshape(B * T, width).astype(pool.dtype), mode="drop")

    def pages_of(flat, pages, l):
        """The whole extent of every row in layer ``l``, page by page
        through the table."""
        return jnp.take(flat.reshape(-1, ps, flat.shape[-1]), pages[l],
                        axis=0, mode="clip").reshape(B, extent, -1).astype(
                            dtype)

    def attend(l, pool, q_nope, q_rope, latent, ap):
        flat = put(pool, wrows, l, latent)
        got = pages_of(flat, rpages, l)
        if not absorb:
            o = attend_expanded(q_nope, q_rope, got, ap, cfg, positions,
                                dtype)
        else:
            o = attend_absorbed(q_nope, q_rope, got, ap, cfg, positions,
                                dtype)
        return o, flat.reshape(pool.shape)

    def attend_picked(l, carry, q_nope, q_rope, latent, ap, index=None):
        pool, ipool, picks, counts = carry
        flat = put(pool, wrows, l, latent)
        if index is not None:
            qI, kI, w = index
            at = cfg.full_layers.index(l)
            with jax.named_scope("dsa.index"):
                iflat = put(ipool, iwrows, at, kI)
                keys = pages_of(iflat, ipages, at)
            ipool = iflat.reshape(ipool.shape)
            if T > 1:
                picks = index_mask(qI, w, keys, positions, cfg)
            else:
                with jax.named_scope("dsa.index"):
                    scores = index_scores(qI, w, keys)[:, 0]
                with jax.named_scope("dsa.select"):
                    picks = select_picks(scores, kpos[None, :] <= positions,
                                         cfg.index_topk)
            if at == 0:
                counts = _count_picks(picks if T > 1 else picks[1][:, None],
                                      positions, real)
        if T > 1:
            got, mask = pages_of(flat, rpages, l), picks
        else:
            at_pos, mask = picks[0], picks[1][:, None]
            with jax.named_scope("dsa.gather"):
                page = jnp.take_along_axis(page_table, at_pos // ps, axis=1)
                rows = l * (P * ps) + jnp.where(
                    page < P, page * ps + at_pos % ps, 0)
                got = jnp.take(flat, rows, axis=0, mode="clip").astype(dtype)
        o = (attend_absorbed if absorb else attend_expanded)(
            q_nope, q_rope, got, ap, cfg, positions, dtype, mask=mask)
        return o, (flat.reshape(pool.shape), ipool, picks, counts)

    x = _streams(_embed(params, tokens, dtype), cfg)
    if not cfg.index_n_heads:
        x, pool, load = _run_layers(params, cfg, x, positions, real, attend,
                                    pool, dtype)
    else:
        Lf = ipool.shape[0]
        iwrows = _layer_rows(wflat, Lf, P * ps).reshape(Lf, B * T)
        ipages = _layer_rows(page_table, Lf, P)
        kpos = jnp.arange(extent, dtype=jnp.int32)
        k = min(cfg.index_topk, extent)
        none = (jnp.zeros((B, T, extent), bool) if T > 1 else
                (jnp.zeros((B, k), jnp.int32), jnp.zeros((B, k), bool)))
        x, (pool, ipool, _, counts), load = _run_layers(
            params, cfg, x, positions, real, attend_picked,
            (pool, ipool, none, no_picks()), dtype)
        load, pool = load + counts, (pool, ipool)
    last = (jnp.zeros((B,), jnp.int32) if nreal is None
            else jnp.clip(nreal.astype(jnp.int32) - 1, 0, T - 1))
    x = _read_out(x, last, cfg, dtype)
    return _logits(params, head, cfg, x, dtype)[:, 0], load, pool
