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
    sqrt(fan-in), norm gains 1 + 0.1 normal, the selection bias 0.1 normal,
    the mixing leaves ``hyper_connections.init_leaf``'s.  One leaf at a
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
        elif name == "router_bias":
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
    ``[B, T, N, dr]``, latent ``[B, T, kr + dr]`` = ``[c_kv | k_rope]``)."""
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
    return q_nope, q_rope, jnp.concatenate([c_kv, k_rope], axis=-1)


def _softmax_rows(scores: jax.Array, qpos: jax.Array, kpos: jax.Array,
                  dtype) -> jax.Array:
    """``scores [B, N, T, S]`` float32 -> probabilities in ``dtype``; key j
    is visible to query t iff ``kpos[j] <= qpos[b, t]``."""
    vis = kpos[None, None, None, :] <= qpos[:, None, :, None]
    return jax.nn.softmax(jnp.where(vis, scores, NEG_INF), axis=-1).astype(dtype)


def attend_expanded(q_nope, q_rope, latent, ap: Params,
                    cfg: LatentMoEConfig, qpos: jax.Array, dtype,
                    causal_cut: bool = False) -> jax.Array:
    """K and V expanded from ``latent [B, S, width]`` (key j at position j),
    queries at ``qpos [B, T]`` attended in blocks of :data:`Q_BLOCK`.
    ``causal_cut``: query t IS position t (a prompt from position 0), so a
    block needs only the keys up to its own end.  -> ``[B, T, N * dv]``."""
    B, T, N = q_nope.shape[:3]
    S, kr = latent.shape[1], cfg.kv_lora_rank
    c_kv, k_rope = latent[..., :kr], latent[..., kr:cfg.latent_width]
    k_nope = _mm(c_kv, ap["kv_b_k"], dtype).astype(dtype).reshape(
        B, S, N, cfg.qk_nope_head_dim)
    v = _mm(c_kv, ap["kv_b_v"], dtype).astype(dtype).reshape(
        B, S, N, cfg.v_head_dim)
    scale = softmax_scale(cfg)

    def block(qn, qr, qp, s1):
        scores = (_einsum("btnd,bsnd->bnts", qn, k_nope[:, :s1])
                  + _einsum("btnr,bsr->bnts", qr, k_rope[:, :s1]))
        probs = _softmax_rows(scores * scale, qp,
                              jnp.arange(s1, dtype=jnp.int32), dtype)
        return _einsum("bnts,bsnd->btnd", probs, v[:, :s1]).astype(dtype)

    if T <= Q_BLOCK:
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


def attend_absorbed(q_nope, q_rope, latent, ap: Params,
                    cfg: LatentMoEConfig, qpos: jax.Array, dtype
                    ) -> jax.Array:
    """The same attention with ``W_kvb`` absorbed: ``q' = q_nope W_kvb[k]``
    (per head, ``kr`` wide), scores ``q' . c_kv + q_rope . k_rope`` against
    the latents as they lie (heads are rows of ONE dot), ``o = (P c_kv)
    W_kvb[v]``.  For few query rows: the decode step.  Both dots take the
    latents WHOLE (``P @ latent``, its ``c_kv`` columns cut from the small
    result): cutting the columns out of the gathered pages first is a copy
    of them, and cutting them out of the pool a copy of the pool (read from
    a described-v5e compile: 3.2 GB)."""
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
        jnp.arange(S, dtype=jnp.int32), dtype)                 # [B, N, T, S]
    o_lat = _einsum("bnts,bsc->btnc", probs, latent)[..., :kr].astype(dtype)
    o = _einsum("btnc,cnd->btnd", o_lat, wv).astype(dtype)
    return o.reshape(B, T, N * cfg.v_head_dim)


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
           carry, dtype, experts: Optional[Params] = None):
    """Layer ``l`` on the residual ``x [B, T, H]`` (``[n, B, T, H]`` streams
    where ``lp`` holds mixing leaves).  ``attend(l, carry, q_nope, q_rope,
    latent, ap) -> ([B, T, N * dv], carry')`` puts the latent where it has
    to go (the pool, in place; or the collected prompt latents) and attends.
    ``experts``: every expert layer's experts (``None``: a dense layer).
    -> (x', carry', the layer's load or None)."""
    ap, hc = lp["attn"], lp.get("hc", {})
    u, mix = _read(x, hc.get("attn"), cfg)
    B, T, H = u.shape
    a = _rms(u, ap["in_norm"], cfg.rms_norm_eps)
    q_nope, q_rope, latent = _project(a, ap, cfg, positions, dtype)
    o, carry = attend(l, carry, q_nope, q_rope, latent, ap)
    x = _write(x, _mm(o, ap["o"], dtype), mix, dtype)
    u, mix = _read(x, hc.get("ffn"), cfg)
    f = _rms(u, ap["post_norm"], cfg.rms_norm_eps)
    if experts is None:
        y, load = _gated(f, lp["ffn"], dtype), None
    else:
        y, load = moe_ffn(f.reshape(B * T, H), lp, experts,
                          l - cfg.first_k_dense, cfg, valid.reshape(B * T),
                          dtype)
        y = y.reshape(B, T, H)
    return _write(x, y, mix, dtype), carry, load


def _run_layers(params: Params, cfg: LatentMoEConfig, x, positions, valid,
                attend, carry, dtype):
    """The leading dense layer(s), then the expert layers under ONE scan
    whose carry holds ``attend``'s state (the pool is carried whole, never
    sliced by layer)."""
    K = cfg.first_k_dense
    for l in range(K):
        lp = jax.tree_util.tree_map(lambda w: w[l], params["dense"])
        x, carry, _ = _layer(x, lp, cfg, l, positions, valid, attend, carry,
                             dtype)

    def step(c, scanned):
        x, carry, load = c
        lp, l = scanned
        x, carry, n = _layer(x, lp, cfg, l, positions, valid, attend, carry,
                             dtype, experts)
        return (x, carry, add_load(load, n)), None

    # the experts stay OUT of the scanned inputs: a block indexes (layer,
    # expert) into the whole stack (held_experts)
    moe = dict(params["moe"])
    experts = moe.pop("experts")
    li = jnp.arange(K, cfg.num_layers, dtype=jnp.int32)
    (x, carry, load), _ = jax.lax.scan(step, (x, carry, no_load(cfg)),
                                       (moe, li))
    return x, carry, load


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
    :func:`~pdnlp_tpu.models.decoder.insert_pool`)."""
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

    x, collected, load = _run_layers(params, cfg, x, positions, valid,
                                     attend, collected, dtype)
    h_last = _read_out(x, last_pos, cfg, dtype)
    return _logits(params, head, cfg, h_last, dtype)[:, 0], load, collected


def paged_attend(params: Params, head: Params, cfg: LatentMoEConfig,
                 tokens: jax.Array,      # [B, T] int32
                 pool: jax.Array,        # [L, P, page_sz, width]
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
    = by the window (T == 1); the tests ask for either path."""
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
    wrows = _layer_rows(jnp.where(real, phys * ps + positions % ps, P * ps),
                        L, P * ps).reshape(L, B * T)
    rpages = _layer_rows(page_table, L, P)                     # [L, B, MP]
    absorb = (T == 1) if absorb is None else absorb

    def attend(l, pool, q_nope, q_rope, latent, ap):
        new = jnp.pad(latent, ((0, 0), (0, 0), (0, W - cfg.latent_width)))
        flat = pool.reshape(L * P * ps, W).at[wrows[l]].set(
            new.reshape(B * T, W).astype(pool.dtype), mode="drop")
        got = jnp.take(flat.reshape(L * P, ps, W), rpages[l], axis=0,
                       mode="clip").reshape(B, extent, W).astype(dtype)
        if not absorb:
            o = attend_expanded(q_nope, q_rope, got, ap, cfg, positions,
                                dtype)
        else:
            o = attend_absorbed(q_nope, q_rope, got, ap, cfg, positions,
                                dtype)
        return o, flat.reshape(pool.shape)

    x = _streams(_embed(params, tokens, dtype), cfg)
    x, pool, load = _run_layers(params, cfg, x, positions, real, attend,
                                pool, dtype)
    last = (jnp.zeros((B,), jnp.int32) if nreal is None
            else jnp.clip(nreal.astype(jnp.int32) - 1, 0, T - 1))
    x = _read_out(x, last, cfg, dtype)
    return _logits(params, head, cfg, x, dtype)[:, 0], load, pool
