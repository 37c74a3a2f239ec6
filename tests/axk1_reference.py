"""The plain reference of the latent-attention, sparse-expert decoder
(A.X-K1, https://huggingface.co/skt/A.X-K1/blob/main/config.json): the full
forward pass in straightforward ``jax.numpy`` float32 at ``highest`` matmul
precision.  No cache, no kernel, no batching tricks, nothing imported from
the program; the weights are made here from ``--seed``, a layer at a time,
and the program is GIVEN the same values.

The layer (``sizes`` holds the config file's numbers and its
``rope_scaling`` group):

- ``h = E[ids]``; ``a = rms(h)``; ``c_q = rms(a W_qa)``; ``[q_nope | q_rope]
  = c_q W_qb`` per head; ``[c_kv | k_rope] = a W_kva``, ``c_kv = rms(c_kv)``;
  yarn rotary on ``q_rope`` and on the one ``k_rope`` all heads share;
  ``[k_nope | v] = c_kv W_kvb`` per head; scores ``(q_nope . k_nope + q_rope
  . k_rope) * s``, ``s = (d_nope + d_rope)^-1/2 * m^2``, ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``; causal softmax; ``h += concat(P v) W_o``.
- ``f = rms(h)``.  A leading dense layer: ``h += W_d (silu(W_g f) * W_u f)``.
  An expert layer: ``s = sigmoid(f W_r)`` over the router's whole width; the
  experts stand in ``n_group`` groups; a group's score is the sum of its two
  largest ``s``; the ``topk_group`` best groups stay; the
  ``num_experts_per_tok`` largest ``s`` inside them are taken; ``g =
  routed_scaling_factor * s_sel / sum(s_sel)``; ``h += sum_e g_e Expert_e(f)
  + Shared(f)``, each a gated silu feed-forward.
- final ``rms``, then ``logits = h W_head`` (untied).

Departures from the published description, each the configuration's:

- **the share**: ``held = (first, count)`` names the experts this chip
  holds.  The router keeps its width, its groups and its experts a token;
  only ``sum over held e`` is added (plus the shared expert); what the
  absent experts would add is left out, here as in the program.  ``None``
  holds them all: the uncut layer.
- **the vocabulary slice**: ``vocab_size`` rows of the embedding and of the
  head; ids, logits and argmax are over the slice.
- ``topk_method: "none"`` is read as "no selection bias is added to the
  scores", with the group-limited choice ``n_group`` / ``topk_group`` state.
- rotary pairs are ``(x[i], x[i + d/2])`` (the layout the published code
  rotates in after it de-interleaves a head's columns); ``W_qb`` and
  ``W_kvb`` are made as their column blocks (``q_b_nope`` / ``q_b_rope``,
  ``kv_b_k`` / ``kv_b_v``), a layout of the published matrices' columns.
- weights are random: normal / sqrt(fan-in) for matrices, normal for the
  embedding, 1 + 0.1 normal for norm gains, every value rounded to bfloat16
  (as the configuration stores them) and promoted to float32 here.

``prec`` lowers the precision of every matmul's operands ("bf16"; "fp8",
per-tensor scaled e4m3) and is how the control is computed.

Near-tie routing: under bfloat16 the last score taken and the first left out
can swap against this reference, and the layer's output then differs by a
whole expert.  :func:`forward` therefore also returns, per position, the
smallest MARGIN over the expert layers: the distance between the worst group
kept and the best group dropped, or between a score taken and one left out
of which at least one is a held expert's, whichever is smaller.  The
comparison that decides ``correct`` allows a swap only below a named margin,
counts the swaps and limits their share (``kinds/closed_loop_latent_moe``).
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


def layer_weights(key, sizes: dict, l: int, held=None) -> dict:
    """Layer ``l``'s weights, bfloat16 (traceable).  ``attn`` and, by the
    layer's kind, ``ffn`` or ``router`` / ``experts`` / ``shared``.  An
    expert's values depend on the seed, the layer and the expert's OWN
    number, so every share of a layer holds the same expert ``e``; ``held``
    = (first, count), default the configuration's."""
    k = jax.random.fold_in(key, 1000 + l)
    ka, kf, kr, ks, ke = jax.random.split(k, 5)
    out = {"attn": _leaves(ka, attn_shapes(sizes))}
    if is_dense(sizes, l):
        out["ffn"] = _leaves(kf, ffn_shapes(sizes, sizes["intermediate_size"]))
        return out
    F = sizes["moe_intermediate_size"]
    first, count = held if held is not None else held_of(sizes)
    out["router"] = _leaf(kr, (sizes["hidden_size"], router_width(sizes)),
                          "router")
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
    """``h [S, H]`` -> the attention branch's output ``[S, H]``."""
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


def route(f, router, sizes, prec):
    """``f [S, H]`` -> (ids ``[S, k]``, gates ``[S, k]``, scores ``[S, E]``,
    group scores ``[S, G]``, kept-group mask ``[S, G]``)."""
    S, E, G = f.shape[0], router.shape[1], sizes["n_group"]
    s = jax.nn.sigmoid(_mm(f, router, prec))
    grp = jax.lax.top_k(s.reshape(S, G, E // G), 2)[0].sum(-1)
    keep = jax.lax.top_k(grp, sizes["topk_group"])[1]
    kept = jnp.zeros((S, G), bool).at[jnp.arange(S)[:, None], keep].set(True)
    masked = jnp.where(jnp.repeat(kept, E // G, axis=1), s, 0.0)
    top, idx = jax.lax.top_k(masked, sizes["num_experts_per_tok"])
    gates = top / (top.sum(-1, keepdims=True) + 1e-20) \
        * sizes["routed_scaling_factor"]
    return idx, gates, s, grp, kept


def routing_margin(idx, s, grp, kept, first, count):
    """Per position, how far the choice is from another one ``[S]``: the
    worst kept group's score less the best dropped group's; and, inside the
    kept groups, the smallest distance between a score taken and a score
    left out of which at least one belongs to a held expert."""
    S, E = s.shape
    G = grp.shape[1]
    big = jnp.float32(1e9)
    g_margin = (jnp.min(jnp.where(kept, grp, big), -1)
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
    idx, gates, s, grp, kept = route(f, w["router"], sizes, prec)
    out = _gated(f, w["shared"], prec)
    for j in range(count):
        g = jnp.sum(jnp.where(idx == first + j, gates, 0.0), -1)    # [S]
        p = jax.tree_util.tree_map(lambda x: x[j], w["experts"])
        out = out + g[:, None] * _gated(f, p, prec)
    return out, routing_margin(idx, s, grp, kept, first, count)


def layer(h, w, sizes, dense: bool, held, prec):
    """One layer on ONE sequence ``h [S, H]`` -> (h', margin ``[S]``)."""
    w = _f32(w)
    h = h + attention(h, w["attn"], sizes, prec)
    f = _rms(h, w["attn"]["post_norm"], sizes["rms_norm_eps"])
    if dense:
        return h + _gated(f, w["ffn"], prec), jnp.full((h.shape[0],), 1e9)
    y, margin = expert_layer(f, w, sizes, held, prec)
    return h + y, margin


def forward(seed: int, sizes: dict, seqs, *, held=None, banned=(),
            prec: str = "f32", at=None):
    """The full forward pass of every sequence in ``seqs`` (lists of ids,
    any lengths) -> per sequence (logits ``[n, vocab]`` float32 at the
    positions ``at[i]`` — default all — and the routing margin ``[S]``).

    A layer's weights are made once and every sequence goes through it
    before the next layer's are, so one layer's float32 weights are the
    most this holds; sequences of one length share a compiled program."""
    key = seed_key(seed)
    held = held if held is not None else held_of(sizes)
    top = jax.jit(lambda k: _f32(top_weights(k, sizes, banned)))(key)
    hs = [top["embed"][jnp.asarray(np.asarray(s, np.int32))] for s in seqs]
    margins = [jnp.full((len(s),), 1e9, jnp.float32) for s in seqs]
    fns = {}
    for l in range(int(sizes["num_hidden_layers"])):
        dense = is_dense(sizes, l)
        w = jax.jit(lambda k, l=l: layer_weights(k, sizes, l, held))(key)
        if dense not in fns:
            fns[dense] = jax.jit(
                lambda h, w, dense=dense: layer(h, w, sizes, dense, held, prec))
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
        if at is None:
            rows, n = h, h.shape[0]
        else:
            n = len(at[i])
            rows = h[jnp.asarray(np.asarray(
                list(at[i]) + [0] * (most - n), np.int32))]
        out.append((head(rows, top["final_norm"], top["head"])[:n],
                    margins[i]))
    return out
