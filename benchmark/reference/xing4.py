"""The plain reference of Xing4.0-29B-A4B
(https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json,
``model_type`` ``xing4_0``): latent attention (MLA), sparse experts behind a
bias-corrected router, and a FOUR-STREAM residual mixed by
manifold-constrained hyper-connections (mHC).  The full forward pass in
straightforward ``jax.numpy`` float32 at ``highest`` matmul precision.  No
cache, no blocks of experts, no scan, nothing imported from the program;
the weights are made here from ``--seed``, a layer at a time, and the
program is GIVEN the same values.

The residual is ``X [n, C]`` a token (``n = hc_mult`` = 4 streams of ``C =
hidden_size``).  ``E[id]`` is copied into the ``n`` streams.  ONE sub-layer
``F`` — attention with its ``in_norm``, or the feed-forward / expert layer
with its ``post_norm`` — with its own ``phi``, ``b``, ``a``
(:func:`mix_coefficients`, :func:`sublayer_read`, :func:`sublayer_write`,
each written for ONE token and mapped over the positions):

    x~      = rms_norm(vec(X))                       # [n*C]; no weight
    h_pre   = a_pre  * (x~ @ phi_pre)  + b_pre       # [n]      phi_pre  [n*C, n]
    h_post  = a_post * (x~ @ phi_post) + b_post      # [n]      phi_post [n*C, n]
    h_res   = a_res  * mat(x~ @ phi_res) + b_res     # [n, n]   phi_res  [n*C, n*n]
    H_pre   = sigmoid(h_pre)
    H_post  = 2 * sigmoid(h_post)
    M       = exp(clip(h_res, clamp_min, clamp_max))
    repeat hc_sinkhorn_iters times:  M = M / (M.sum(-1, keepdims) + hc_eps)   # rows
                                     M = M / (M.sum(-2, keepdims) + hc_eps)   # columns
    H_res   = M
    u       = H_pre @ X                              # [C]: what F reads
    y       = F(u)                                   # F's own RMSNorm inside
    X'      = H_res @ X + outer(H_post, y)           # [n, C]

A layer is two of these; the final norm and the head read the SUM of the
streams.  The sub-layers themselves (``sizes`` holds the config file's
numbers and its ``rope_scaling`` group):

- attention on ``a = rms(u)``: ``c_q = rms(a W_qa)``; ``[q_nope | q_rope] =
  c_q W_qb`` per head; ``[c_kv | k_rope] = a W_kva``, ``c_kv = rms(c_kv)``;
  yarn rotary on ``q_rope`` and on the one ``k_rope`` all heads share;
  ``[k_nope | v] = c_kv W_kvb`` per head, EXPANDED for every position;
  scores ``(q_nope . k_nope + q_rope . k_rope) * s``, ``s = (d_nope +
  d_rope)^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; causal
  softmax; ``y = concat(P v) W_o``.
- feed-forward on ``f = rms(u)``.  A leading dense layer: ``y = W_d
  (silu(W_g f) * W_u f)``.  An expert layer (``topk_method: "noaux_tc"``):
  ``s = sigmoid(f W_r)``; the ``num_experts_per_tok`` experts with the
  largest ``s + e_score_correction_bias`` are CHOSEN (``n_group`` 1: the
  group step keeps the one group); they are GATED by ``s`` alone, ``g =
  routed_scaling_factor * s_sel / sum(s_sel)``; ``y = sum_e g_e Expert_e(f)
  + Shared(f)``, each a gated silu feed-forward, the held experts as a dense
  loop.
- final ``rms`` of the streams' sum, then ``logits = h W_head`` (untied).

Departures from the published config, each the configuration's:

- **the multi-token-prediction module is NOT built**
  (``num_nextn_predict_layers`` 1 -> 0): the config gives its count and
  nothing on how it reads a four-stream residual; the next-token logits do
  not depend on it, and it is discarded when serving without speculation.
- **depth**: ``num_hidden_layers`` 40 -> what ``sizes`` says;
  ``first_k_dense_replace`` 2 -> 1 (leading dense layers count once).
- ``held = (first, count)`` names the experts this process holds: ALL of
  them in the configuration (``ep_size`` 1); a test splits them over two
  processes and adds the shares up.

Assumed, where the config gives a key and no equation (the configuration
file lists the same under ``assumed``): the norm of the flattened streams
carries no weight and uses ``rms_norm_eps``; ``hc_eps`` is the Sinkhorn
denominators'; the clamp acts on ``h_res`` before ``exp``; rows are
normalised before columns; ``a_*`` are scalars; the streams start as copies
and end as a sum.  ``phi_pre | phi_post | phi_res`` are the column blocks of
ONE leaf ``phi [n*C, n + n + n*n]``, ``b_pre | b_post | vec(b_res)`` of ``b``,
``a_pre, a_post, a_res`` of ``a``: a layout of the three.  Rotary pairs are
``(x[i], x[i + d/2])``; ``W_qb`` and ``W_kvb`` are made as their column
blocks.

Weights are random: normal / sqrt(fan-in) for matrices (``phi`` among
them: ``x~ @ phi`` is then of order one), normal for the embedding, 1 + 0.1
normal for norm gains and for ``a``; ``b`` normal with ``b_res`` = 2 I + 0.5
normal (``H_res`` leans on the diagonal, about 0.6, and is visibly not the
identity); the selection bias 0.1 normal (it changes the choice at about a
position in three).  Every value is rounded to bfloat16 (as the
configuration stores them) and promoted to float32 here.

``prec`` lowers the precision of every matmul's operands ("bf16"; "fp8",
per-tensor scaled e4m3); ``mix`` = "bf16" rounds every intermediate of the
MIXING (norm statistic, ``phi`` products, sigmoids, every Sinkhorn step,
``H_pre @ X``, ``H_res @ X + outer``) to bfloat16.  They are how the
controls are computed.

Near-tie routing: under bfloat16 the last expert taken and the first left
out can swap against this reference, and the layer's output then differs by
a whole expert.  :func:`forward` therefore also returns, per position, the
smallest MARGIN over the expert layers: the smallest distance, in ``s +
bias``, between an expert taken and one left out of which at least one is
held.  The comparison that decides ``correct`` allows a swap only below a
named margin, counts the swaps and limits their share
(``kinds/closed_loop_latent_moe.judge``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e9
Q_BLOCK = 512      # query rows of one attention block


# ------------------------------------------------------------------- weights

def seed_key(seed: int):
    return jax.random.key(int(seed) % (2 ** 32))


def held_of(sizes: dict):
    """(first, count) of the experts the configuration holds."""
    return int(sizes.get("expert_first", 0)), int(sizes["n_routed_experts"])


def router_width(sizes: dict) -> int:
    return int(sizes.get("router_width", sizes["n_routed_experts"]))


def attn_shapes(sizes: dict) -> dict:
    H, N = sizes["hidden_size"], sizes["num_attention_heads"]
    qr, kr = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    return {"in_norm": (H,), "q_a": (H, qr), "q_norm": (qr,),
            "q_b_nope": (qr, N * dn), "q_b_rope": (qr, N * dr),
            "kv_a": (H, kr + dr), "kv_norm": (kr,), "kv_b_k": (kr, N * dn),
            "kv_b_v": (kr, N * dv), "o": (N * dv, H), "post_norm": (H,)}


def ffn_shapes(sizes: dict, width: int) -> dict:
    H = sizes["hidden_size"]
    return {"gate": (H, width), "up": (H, width), "down": (width, H)}


def _leaf(key, shape, name, store=jnp.bfloat16):
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm"):
        x = 1.0 + 0.1 * x
    elif name != "embed":
        x = x * shape[-2] ** -0.5
    return x.astype(store)


def _leaves(key, shapes: dict) -> dict:
    names = sorted(shapes)
    return {n: _leaf(k, shapes[n], n)
            for n, k in zip(names, jax.random.split(key, len(names)))}


def is_dense(sizes: dict, l: int) -> bool:
    return l < int(sizes.get("first_k_dense_replace", 1))


def mixing_weights(key, sizes: dict) -> dict:
    """ONE sub-layer's mixing leaves, bfloat16: ``phi [n*C, n + n + n*n]``
    normal / sqrt(n*C), ``b [n + n + n*n]`` normal with its ``res`` block
    2 I + 0.5 normal, ``a [3]`` 1 + 0.1 normal."""
    n, C = int(sizes["hc_mult"]), sizes["hidden_size"]
    kp, kb, ka = jax.random.split(key, 3)
    b = jax.random.normal(kb, (n * (n + 2),), jnp.float32)
    b = jnp.concatenate([b[:2 * n], 2.0 * jnp.eye(n).reshape(-1)
                         + 0.5 * b[2 * n:]])
    return {"phi": _leaf(kp, (n * C, n * (n + 2)), "phi"),
            "b": b.astype(jnp.bfloat16),
            "a": (1.0 + 0.1 * jax.random.normal(ka, (3,), jnp.float32)
                  ).astype(jnp.bfloat16)}


def layer_weights(key, sizes: dict, l: int, held=None) -> dict:
    """Layer ``l``'s weights, bfloat16 (traceable).  ``attn``, ``hc`` (the
    two sub-layers' mixing leaves) and, by the layer's kind, ``ffn`` or
    ``router`` / ``router_bias`` / ``experts`` / ``shared``.  An expert's
    values depend on the seed, the layer and the expert's OWN number, so
    every share of a layer holds the same expert ``e``; ``held`` = (first,
    count), default the configuration's."""
    k = jax.random.fold_in(key, 1000 + l)
    ka, kf, kr, ks, ke, kh, kb = jax.random.split(k, 7)
    out = {"attn": _leaves(ka, attn_shapes(sizes)),
           "hc": {sub: mixing_weights(kk, sizes) for sub, kk in
                  zip(("attn", "ffn"), jax.random.split(kh))}}
    if is_dense(sizes, l):
        out["ffn"] = _leaves(kf, ffn_shapes(sizes, sizes["intermediate_size"]))
        return out
    F = sizes["moe_intermediate_size"]
    first, count = held if held is not None else held_of(sizes)
    out["router"] = _leaf(kr, (sizes["hidden_size"], router_width(sizes)),
                          "router")
    out["router_bias"] = (0.1 * jax.random.normal(
        kb, (router_width(sizes),), jnp.float32)).astype(jnp.bfloat16)
    out["shared"] = _leaves(
        ks, ffn_shapes(sizes, F * int(sizes.get("n_shared_experts", 1))))
    # one expert after another: an expert's float32 draw is the most alive
    out["experts"] = jax.lax.map(
        lambda e: _leaves(jax.random.fold_in(ke, e), ffn_shapes(sizes, F)),
        first + jnp.arange(count))
    return out


def top_weights(key, sizes: dict, banned: tuple = ()) -> dict:
    """Embedding, final norm and the untied head, bfloat16 (traceable).
    ``banned``: ids the served model must never emit (the batcher's EOS) —
    their column of the head is zero, so their logit is 0 where the best of
    a vocabulary of unit-variance logits is far above it."""
    ke, kn, kh = jax.random.split(jax.random.fold_in(key, 1), 3)
    V, H = sizes["vocab_size"], sizes["hidden_size"]
    head = _leaf(kh, (H, V), "head")
    if banned:
        head = head.at[:, jnp.asarray([int(b) for b in banned])].set(0)
    return {"embed": _leaf(ke, (V, H), "embed"),
            "final_norm": _leaf(kn, (H,), "final_norm"), "head": head}


# ---------------------------------------------------------------- arithmetic

def _quant(x, prec):
    """``x`` as the lower precision holds it (fp8: per-tensor scaled e4m3)."""
    if prec == "f32":
        return x
    if prec == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, prec):
    return jnp.matmul(_quant(a, prec), _quant(b, prec), precision=HIGHEST)


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _gated(x, p, prec):
    return _mm(jax.nn.silu(_mm(x, p["gate"], prec)) * _mm(x, p["up"], prec),
               p["down"], prec)


def yarn_inv_freq(sizes: dict) -> np.ndarray:
    """Rotary frequencies ``[d_rope / 2]`` under yarn: the published one
    where a dimension turns more than ``beta_fast`` times over the original
    context, the published one over ``factor`` where it turns fewer than
    ``beta_slow`` times, a linear ramp between."""
    d, base = sizes["qk_rope_head_dim"], float(sizes["rope_theta"])
    rs = sizes["rope_scaling"]
    i = np.arange(0, d, 2, dtype=np.float64) / d
    extra, inter = 1.0 / base ** i, 1.0 / (rs["factor"] * base ** i)

    def correction(turns):
        return d * math.log(rs["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(sizes: dict) -> float:
    rs = sizes["rope_scaling"]
    m = _mscale(rs["factor"], rs["mscale_all_dim"])
    return (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, sizes):
    """``x [S, ..., d_rope]`` at positions 0..S-1."""
    rs = sizes["rope_scaling"]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(sizes))
    m = _mscale(rs["factor"], rs["mscale"]) / _mscale(rs["factor"],
                                                     rs["mscale_all_dim"])
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos, sin = (jnp.cos(ang) * m).reshape(shape), (jnp.sin(ang) * m).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, w, sizes, prec):
    """What the sub-layer read ``h [S, H]`` -> the attention branch's output
    ``[S, H]`` (its ``in_norm`` inside)."""
    S, N = h.shape[0], sizes["num_attention_heads"]
    kr, dn, dv = (sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
                  sizes["v_head_dim"])
    eps = sizes["rms_norm_eps"]
    a = _rms(h, w["in_norm"], eps)
    cq = _rms(_mm(a, w["q_a"], prec), w["q_norm"], eps)
    q_nope = _mm(cq, w["q_b_nope"], prec).reshape(S, N, dn)
    q_rope = _rope(_mm(cq, w["q_b_rope"], prec).reshape(S, N, -1), sizes)
    kv = _mm(a, w["kv_a"], prec)
    c_kv = _rms(kv[:, :kr], w["kv_norm"], eps)
    k_rope = _rope(kv[:, kr:], sizes)                               # [S, dr]
    k_nope = _mm(c_kv, w["kv_b_k"], prec).reshape(S, N, dn)
    v = _mm(c_kv, w["kv_b_v"], prec).reshape(S, N, dv)
    q = jnp.concatenate([q_nope, q_rope], -1).transpose(1, 0, 2)  # [N, S, d]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (S, N, k_rope.shape[-1]))],
        -1).transpose(1, 2, 0)                                     # [N, d, S]
    v = v.transpose(1, 0, 2)                                       # [N, S, dv]
    pos = jnp.arange(S)
    out = []
    for t0 in range(0, S, Q_BLOCK):        # query blocks: scores fit
        t1 = min(S, t0 + Q_BLOCK)
        s = _mm(q[:, t0:t1], k, prec) * softmax_scale(sizes)
        s = jnp.where(pos[None, None, :] <= pos[None, t0:t1, None], s, NEG)
        out.append(_mm(jax.nn.softmax(s, -1), v, prec))           # [N, t, dv]
    o = jnp.concatenate(out, 1).transpose(1, 0, 2).reshape(S, N * dv)
    return _mm(o, w["o"], prec)


def route(f, router, bias, sizes, prec):
    """``f [S, H]`` -> (ids ``[S, k]``, gates ``[S, k]``, what the choice was
    made by ``s + bias [S, E]``, group scores ``[S, G]``, kept-group mask
    ``[S, G]``).  ``noaux_tc``: CHOSEN by ``s + bias``, GATED by ``s``."""
    S, E, G = f.shape[0], router.shape[1], sizes["n_group"]
    s = jax.nn.sigmoid(_mm(f, router, prec))
    sel = s + bias
    grp = jax.lax.top_k(sel.reshape(S, G, E // G), 2)[0].sum(-1)
    keep = jax.lax.top_k(grp, sizes["topk_group"])[1]
    kept = jnp.zeros((S, G), bool).at[jnp.arange(S)[:, None], keep].set(True)
    masked = jnp.where(jnp.repeat(kept, E // G, axis=1), sel, -jnp.inf)
    idx = jax.lax.top_k(masked, sizes["num_experts_per_tok"])[1]
    top = jnp.take_along_axis(s, idx, axis=-1)
    gates = top / (top.sum(-1, keepdims=True) + 1e-20) \
        * sizes["routed_scaling_factor"]
    return idx, gates, sel, grp, kept


def routing_margin(idx, s, grp, kept, first, count):
    """Per position, how far the choice is from another one ``[S]``, in
    what it was made by (``s`` = score + bias): the worst kept group's
    score less the best dropped group's (none is dropped at ``n_group`` 1:
    1e9); and, inside the kept groups, the smallest distance between a
    score taken and a score left out of which at least one belongs to a
    held expert."""
    S, E = s.shape
    G = grp.shape[1]
    big = jnp.float32(1e9)
    g_margin = jnp.where(jnp.all(kept, -1), big,
                         jnp.min(jnp.where(kept, grp, big), -1)
                         - jnp.max(jnp.where(kept, -big, grp), -1))
    taken = jnp.zeros((S, E), bool).at[jnp.arange(S)[:, None], idx].set(True)
    cand = jnp.repeat(kept, E // G, axis=1)
    left = cand & ~taken
    e = jnp.arange(E)
    held = (e >= first) & (e < first + count)
    lo_taken = jnp.min(jnp.where(taken, s, big), -1)
    lo_taken_held = jnp.min(jnp.where(taken & held, s, big), -1)
    hi_left = jnp.max(jnp.where(left, s, -big), -1)
    hi_left_held = jnp.max(jnp.where(left & held, s, -big), -1)
    e_margin = jnp.minimum(lo_taken_held - hi_left, lo_taken - hi_left_held)
    return jnp.minimum(g_margin, e_margin)


def expert_layer(f, w, sizes, held, prec):
    """``f [S, H]`` -> (the expert layer's output ``[S, H]``, margin ``[S]``):
    every held expert's feed-forward over every position, weighted by its
    gate (0 where the router did not take it), plus the shared expert."""
    first, count = held
    idx, gates, s, grp, kept = route(f, w["router"], w["router_bias"], sizes,
                                     prec)
    out = _gated(f, w["shared"], prec)
    for j in range(count):
        g = jnp.sum(jnp.where(idx == first + j, gates, 0.0), -1)    # [S]
        p = jax.tree_util.tree_map(lambda x: x[j], w["experts"])
        out = out + g[:, None] * _gated(f, p, prec)
    return out, routing_margin(idx, s, grp, kept, first, count)


# -------------------------------------------------------------------- mixing

def _dot(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def mix_coefficients(X, p, sizes, mix="f32"):
    """ONE token's streams ``X [n, C]`` and one sub-layer's leaves ``p`` ->
    (``H_pre [n]``, ``H_post [n]``, ``H_res [n, n]``)."""
    n = X.shape[0]
    r = lambda v: _quant(v, mix)            # the control's rounding
    phi_pre, phi_post, phi_res = (p["phi"][:, :n], p["phi"][:, n:2 * n],
                                  p["phi"][:, 2 * n:])
    b_pre, b_post, b_res = (p["b"][:n], p["b"][n:2 * n],
                            p["b"][2 * n:].reshape(n, n))
    a_pre, a_post, a_res = p["a"]
    x = X.reshape(-1)
    xt = r(x * jax.lax.rsqrt(r(jnp.mean(x * x)) + sizes["rms_norm_eps"]))
    h_pre = r(a_pre * r(_dot(xt, phi_pre)) + b_pre)
    h_post = r(a_post * r(_dot(xt, phi_post)) + b_post)
    h_res = r(a_res * r(_dot(xt, phi_res)).reshape(n, n) + b_res)
    H_pre = r(jax.nn.sigmoid(h_pre))
    H_post = r(2.0 * jax.nn.sigmoid(h_post))
    M = r(jnp.exp(jnp.clip(h_res, sizes["mhc_h_res_clamp_min"],
                           sizes["mhc_h_res_clamp_max"])))
    for _ in range(int(sizes["hc_sinkhorn_iters"])):
        M = r(M / (r(M.sum(-1, keepdims=True)) + sizes["hc_eps"]))   # rows
        M = r(M / (r(M.sum(-2, keepdims=True)) + sizes["hc_eps"]))   # columns
    return H_pre, H_post, M


def sublayer_read(X, H_pre, mix="f32"):
    """ONE token: ``u = H_pre @ X`` ``[C]``, what the sub-layer reads."""
    return _quant(_dot(H_pre, X), mix)


def sublayer_write(X, y, H_res, H_post, mix="f32"):
    """ONE token: ``X' = H_res @ X + outer(H_post, y)`` ``[n, C]``."""
    return _quant(_dot(H_res, X) + jnp.outer(H_post, y), mix)


def over_positions(fn, *xs):
    """A per-token function over every position of ONE sequence."""
    return jax.vmap(fn)(*xs)


def layer(X, w, sizes, dense: bool, held, prec, mix="f32"):
    """One layer on ONE sequence's streams ``X [S, n, C]`` -> (X', margin
    ``[S]``): two sub-layers, each read, computed and written back."""
    w = _f32(w)

    def sub(X, p, F):
        H_pre, H_post, H_res = over_positions(
            lambda x: mix_coefficients(x, p, sizes, mix), X)
        u = over_positions(lambda x, h: sublayer_read(x, h, mix), X, H_pre)
        y, extra = F(u)
        return over_positions(
            lambda x, yy, hr, hp: sublayer_write(x, yy, hr, hp, mix),
            X, y, H_res, H_post), extra

    X, _ = sub(X, w["hc"]["attn"],
               lambda u: (attention(u, w["attn"], sizes, prec), None))

    def feed_forward(u):
        f = _rms(u, w["attn"]["post_norm"], sizes["rms_norm_eps"])
        if dense:
            return _gated(f, w["ffn"], prec), jnp.full((u.shape[0],), 1e9)
        return expert_layer(f, w, sizes, held, prec)

    return sub(X, w["hc"]["ffn"], feed_forward)


def forward(seed: int, sizes: dict, seqs, *, held=None, banned=(),
            prec: str = "f32", mix: str = "f32", at=None):
    """The full forward pass of every sequence in ``seqs`` (lists of ids,
    any lengths) -> per sequence (logits ``[n, vocab]`` float32 at the
    positions ``at[i]`` — default all — and the routing margin ``[S]``).

    A layer's weights are made once and every sequence goes through it
    before the next layer's are, so one layer's float32 weights are the
    most this holds; sequences of one length share a compiled program."""
    key = seed_key(seed)
    held = held if held is not None else held_of(sizes)
    top = jax.jit(lambda k: _f32(top_weights(k, sizes, banned)))(key)
    n = int(sizes["hc_mult"])
    # the embedding copied into the n streams [S, n, C]
    hs = [jnp.repeat(top["embed"][jnp.asarray(np.asarray(s, np.int32))]
                     [:, None], n, axis=1) for s in seqs]
    margins = [jnp.full((len(s),), 1e9, jnp.float32) for s in seqs]
    fns = {}
    for l in range(int(sizes["num_hidden_layers"])):
        dense = is_dense(sizes, l)
        w = jax.jit(lambda k, l=l: layer_weights(k, sizes, l, held))(key)
        if dense not in fns:
            fns[dense] = jax.jit(
                lambda h, w, dense=dense: layer(h, w, sizes, dense, held, prec,
                                                mix))
        for i, h in enumerate(hs):
            hs[i], m = fns[dense](h, w)
            margins[i] = jnp.minimum(margins[i], m)
        del w
    # the head's weights are ARGUMENTS (closed over, they would be compiled
    # in as constants, once for every count of rows) and every sequence is
    # read at the same count of positions
    head = jax.jit(lambda rows, norm, w: _mm(
        _rms(rows, norm, sizes["rms_norm_eps"]), w, prec))
    most = max(len(a) for a in at) if at is not None else None
    out = []
    for i, h in enumerate(hs):
        h = h.sum(1)                           # the streams' sum [S, C]
        if at is None:
            rows, n = h, h.shape[0]
        else:
            n = len(at[i])
            rows = h[jnp.asarray(np.asarray(
                list(at[i]) + [0] * (most - n), np.int32))]
        out.append((head(rows, top["final_norm"], top["head"])[:n],
                    margins[i]))
    return out
