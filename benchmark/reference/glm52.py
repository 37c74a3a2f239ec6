"""The plain reference of GLM-5.2
(https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json, ``model_type``
``glm_moe_dsa``): latent attention (MLA) under a LEARNED SPARSE selection — a
lightning indexer in the ``full`` layers whose picks the ``shared`` layers
after it reuse — and sparse experts behind a bias-corrected router.  The full
forward pass in straightforward ``jax.numpy`` float32 at ``highest`` matmul
precision.  No cache, no pages, no scan over layers, nothing imported from
the program; the weights are made here from ``--seed``, a layer at a time,
and the program is GIVEN the same values (:func:`program_layout`).

One layer on ``h [S, H]`` (``sizes`` holds the config file's numbers, its
``rope_parameters`` group and ``indexer_types``, one entry a layer HELD):

- ``a = rms(h)``; ``c_q = rms(a W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` per
  head; ``[c_kv | k_rope] = a W_kva``, ``c_kv = rms(c_kv)``; plain rotary
  positions (``theta ** (-2i/d)``, no scaling) on ``q_rope`` and on the one
  ``k_rope`` all heads share, pairs INTERLEAVED ``(x[2i], x[2i+1])``
  (``rope_interleave``); ``[k_nope | v] = c_kv W_kvb`` per head, EXPANDED for
  every position; scores ``(q_nope . k_nope + q_rope . k_rope) * (d_nope +
  d_rope) ** -0.5``.
- the selection.  In a ``full`` layer, for the token at position ``t``:

      qI_t   = (c_q_t @ W_iq).reshape(Hi, di)
      kI_t   = layer_norm(a_t @ W_ik)             # weight and bias
               the first d_rope values of every qI_t[h] and of kI_t rotated
               at position t (the same table, pairs interleaved)
      w_t    = a_t @ W_iw                         # [Hi]
      I[t,s] = sum_h w_t[h] * relu(qI_t[h] . kI_s)        for s <= t
      S_t    = the min(index_topk, t + 1) positions s <= t of largest
               I[t, s]; a tie goes to the lower position

  as a dense ``[T, T]`` array and a full (stable) sort.  A ``shared`` layer
  holds no indexer and uses the ``S_t`` of the last ``full`` layer before it.
- the softmax runs over ``S_t`` alone (a mask over the dense scores); ``y =
  concat(P v) W_o``; ``h += y``.
- feed-forward on ``f = rms(h)``.  A leading dense layer: ``W_d (silu(W_g f)
  * W_u f)``.  An expert layer (``topk_method: "noaux_tc"``): ``s =
  sigmoid(f W_r)``; the ``num_experts_per_tok`` experts with the largest ``s
  + e_score_correction_bias`` are CHOSEN (``n_group`` 1 keeps the one group);
  they are GATED by ``s`` alone, ``g = routed_scaling_factor * s_sel /
  sum(s_sel)``; ``y = sum_e g_e Expert_e(f) + Shared(f)``, the held experts
  as a loop.
- final ``rms``, then ``logits = h W_head`` (untied).

Queries go in blocks of ``Q_BLOCK`` (index scores, the sort and the
attention alike; the whole blocks one after another under ``lax.map``), so
that a sequence of 8 192 fits.

Departures from the published config, each the configuration's:

- **the multi-token-prediction module is NOT built**
  (``num_nextn_predict_layers`` 1 -> 0): the next-token logits do not depend
  on it (``index_share_for_mtp_iteration`` is therefore not consumed).
- **depth**: ``num_hidden_layers`` 78 -> what ``sizes`` says, published
  layers 2-8; ``first_k_dense_replace`` 3 -> 1 (leading dense layers count
  once); ``indexer_types`` the published list's entries for the layers held.
- **the published indexer's Hadamard rotation and FP8 quantisation of ``qI``
  and ``kI`` are left out**: the rotation is orthogonal and cancels in ``qI .
  kI``; the quantisation is a storage format the config does not state.
- ``held = (first, count)`` names the experts this process holds: 16 of 256
  in the configuration; a test splits a layer's over two processes and adds
  the shares up.

Assumed, where the config gives a key and no equation (the configuration
file lists the same under ``assumed``): the index key's norm is a LayerNorm
with weight and bias at ``rms_norm_eps``; the indexer reads the layer's
normed input and the normed query latent; ``shared`` layers hold no indexer
weights; ``indexer_types`` is authoritative (``index_topk_freq`` and
``index_skip_topk_offset`` restate it); ties go to the lower position.

Weights are random: normal / sqrt(fan-in) for matrices, normal for the
embedding, 1 + 0.1 normal for norm gains, 0.1 normal for the index key's
norm bias and the selection bias — and ``W_qb`` TWICE that, so that a
query's scores spread by about 2 and a few dozen of thousands of positions
carry its softmax: WHICH positions were picked then shows in the logits
(``W_iw`` as it is gives ``w_t`` both signs, and ``I`` no lean to the recent
positions).  Every value is rounded to bfloat16 (as the configuration stores
them) and promoted to float32 here.

``prec`` lowers the precision of every matmul's operands ("bf16"; "fp8",
per-tensor scaled e4m3); ``select`` = "recent" replaces every ``S_t`` by the
most recent ``index_topk`` positions — the mechanism left out.  They are how
the controls are computed.

Near-tie routing: as ``reference/xing4.py`` — :func:`forward` also returns,
per position, the smallest MARGIN over the expert layers between an expert
taken and one left out of which at least one is held.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e9
Q_BLOCK = 256      # query rows of one block of index scores and of attention
Q_SPREAD = 2.0     # the seeded W_qb's scale over normal / sqrt(fan-in)


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


def indexer_shapes(sizes: dict) -> dict:
    H, qr = sizes["hidden_size"], sizes["q_lora_rank"]
    Hi, di = sizes["index_n_heads"], sizes["index_head_dim"]
    return {"iq": (qr, Hi * di), "ik": (H, di), "ik_norm": (di,),
            "ik_bias": (di,), "iw": (H, Hi)}


def ffn_shapes(sizes: dict, width: int) -> dict:
    H = sizes["hidden_size"]
    return {"gate": (H, width), "up": (H, width), "down": (width, H)}


def _leaf(key, shape, name, store=jnp.bfloat16):
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm"):
        x = 1.0 + 0.1 * x
    elif name.endswith("bias"):
        x = 0.1 * x
    elif name != "embed":
        x = x * shape[-2] ** -0.5
        if name.startswith("q_b"):
            x = x * Q_SPREAD
    return x.astype(store)


def _leaves(key, shapes: dict) -> dict:
    names = sorted(shapes)
    return {n: _leaf(k, shapes[n], n)
            for n, k in zip(names, jax.random.split(key, len(names)))}


def is_dense(sizes: dict, l: int) -> bool:
    return l < int(sizes.get("first_k_dense_replace", 1))


def is_full(sizes: dict, l: int) -> bool:
    """Layer ``l`` (of those held) scores and picks; else it reuses."""
    return sizes["indexer_types"][l] == "full"


def layer_weights(key, sizes: dict, l: int, held=None) -> dict:
    """Layer ``l``'s weights, bfloat16 (traceable).  ``attn``; in a ``full``
    layer ``indexer``; and, by the layer's kind, ``ffn`` or ``router`` /
    ``router_bias`` / ``experts`` / ``shared``.  An expert's values depend
    on the seed, the layer and the expert's OWN number, so every share of a
    layer holds the same expert ``e``; ``held`` = (first, count), default
    the configuration's."""
    k = jax.random.fold_in(key, 1000 + l)
    ka, kf, kr, ks, ke, ki, kb = jax.random.split(k, 7)
    out = {"attn": _leaves(ka, attn_shapes(sizes))}
    if is_full(sizes, l):
        out["indexer"] = _leaves(ki, indexer_shapes(sizes))
    if is_dense(sizes, l):
        out["ffn"] = _leaves(kf, ffn_shapes(sizes, sizes["intermediate_size"]))
        return out
    F = sizes["moe_intermediate_size"]
    first, count = held if held is not None else held_of(sizes)
    out["router"] = _leaf(kr, (sizes["hidden_size"], router_width(sizes)),
                          "router")
    out["router_bias"] = _leaf(kb, (router_width(sizes),), "router_bias")
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


def _halves(d: int) -> np.ndarray:
    """Column ``j`` of the half-split convention reads interleaved column
    ``_halves(d)[j]``: the evens, then the odds."""
    return np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])


def program_layout(w: dict, sizes: dict) -> dict:
    """One layer's weights as a program that rotates the pairs ``(x[i], x[i
    + d/2])`` takes them: the rotary columns of ``q_b_rope`` (every head's),
    of ``kv_a`` (its last ``d_rope``) and of the indexer's ``iq`` (every
    head's first ``d_rope``), ``ik``, ``ik_norm``, ``ik_bias`` (their first
    ``d_rope``) DE-INTERLEAVED.  Both sides of every rotated dot take the
    same permutation, a rotation of a pair is the same rotation wherever the
    pair lies, and a LayerNorm's statistics do not see the order: every
    score, and so every result, is the same."""
    N, dr = sizes["num_attention_heads"], sizes["qk_rope_head_dim"]
    kr = sizes["kv_lora_rank"]
    order = _halves(dr)
    attn = dict(w["attn"])
    attn["q_b_rope"] = attn["q_b_rope"][
        :, (np.arange(N)[:, None] * dr + order[None, :]).reshape(-1)]
    attn["kv_a"] = attn["kv_a"][
        :, np.concatenate([np.arange(kr), kr + order])]
    out = {**w, "attn": attn}
    if "indexer" in w:
        Hi, di = sizes["index_n_heads"], sizes["index_head_dim"]
        one = np.concatenate([order, np.arange(dr, di)])
        ix = dict(w["indexer"])
        ix["iq"] = ix["iq"][
            :, (np.arange(Hi)[:, None] * di + one[None, :]).reshape(-1)]
        for name in ("ik", "ik_norm", "ik_bias"):
            ix[name] = ix[name][..., one]
        out["indexer"] = ix
    return out


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


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gated(x, p, prec):
    return _mm(jax.nn.silu(_mm(x, p["gate"], prec)) * _mm(x, p["up"], prec),
               p["down"], prec)


def inv_freq(sizes: dict) -> np.ndarray:
    """The plain rotary frequencies ``theta ** (-2i/d)`` ``[d_rope / 2]``
    (``rope_type`` default: no scaling)."""
    d = sizes["qk_rope_head_dim"]
    base = float(sizes["rope_parameters"]["rope_theta"])
    return (1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
            ).astype(np.float32)


def softmax_scale(sizes: dict) -> float:
    return (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]) ** -0.5


def _rope(x, sizes):
    """``x [S, ..., d_rope]`` at positions 0..S-1, pairs INTERLEAVED: the
    pair ``(x[2i], x[2i+1])`` turns by the i-th angle."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq(sizes))
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _rope_head(x, sizes):
    """The first ``d_rope`` values of the last axis rotated, the rest not."""
    dr = sizes["qk_rope_head_dim"]
    return jnp.concatenate([_rope(x[..., :dr], sizes), x[..., dr:]], -1)


def index_project(a, cq, w, sizes, prec):
    """A ``full`` layer's indexer on the layer's normed input ``a [S, H]``
    and normed query latent ``cq [S, qr]`` -> (``qI [S, Hi, di]``, ``kI [S,
    di]``, ``w [S, Hi]``)."""
    S = a.shape[0]
    Hi, di = sizes["index_n_heads"], sizes["index_head_dim"]
    qI = _rope_head(_mm(cq, w["iq"], prec).reshape(S, Hi, di), sizes)
    kI = _rope_head(_layer_norm(_mm(a, w["ik"], prec), w["ik_norm"],
                                w["ik_bias"], sizes["rms_norm_eps"]), sizes)
    return qI, kI, _mm(a, w["iw"], prec)


def index_scores(qI, kI, wt, prec):
    """``I [T, S]`` (every pair, visible or not) of the queries ``qI [T, Hi,
    di]`` with their head weights ``wt [T, Hi]`` over the keys ``kI [S,
    di]``."""
    dots = jax.nn.relu(_mm(qI.transpose(1, 0, 2), kI.T, prec))    # [Hi, T, S]
    return jnp.einsum("hts,th->ts", dots, wt, precision=HIGHEST)


def pick(scores, t0, k: int, select: str = "index"):
    """``S_t`` for the queries at positions ``t0 ..`` as a mask ``[T, S]``:
    the ``min(k, t + 1)`` visible positions of largest ``scores[t]`` by a
    full stable sort (a tie to the lower position); ``select`` = "recent":
    the most recent ``k`` (the control)."""
    T, S = scores.shape
    pos, t = jnp.arange(S)[None, :], t0 + jnp.arange(T)[:, None]
    visible = pos <= t
    if select == "recent":
        return visible & (pos > t - k)
    order = jnp.argsort(-jnp.where(visible, scores, -jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)          # a position's place in it
    return visible & (rank < k)


def attention(h, w, sizes, prec, picks=None, indexer=None, select="index"):
    """``h [S, H]`` -> (the attention branch's output ``[S, H]`` (its
    ``in_norm`` inside), the picks ``[S, S]`` it attended under): its own if
    it holds an ``indexer``, else the ``picks`` handed in."""
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
    if indexer is not None:
        qI, kI, wt = index_project(a, cq, indexer, sizes, prec)

    def block(t0, n):
        """Queries ``t0 .. t0 + n``: (their output ``[N, n, dv]``, the picks
        ``[n, S]`` they attended under)."""
        def cut(x, axis=0):
            return jax.lax.dynamic_slice_in_dim(x, t0, n, axis)

        if indexer is not None:
            m = pick(index_scores(cut(qI), kI, cut(wt), prec), t0,
                     sizes["index_topk"], select)
        else:
            m = cut(picks)
        s = _mm(cut(q, 1), k, prec) * softmax_scale(sizes)
        s = jnp.where(m[None], s, NEG)
        return _mm(jax.nn.softmax(s, -1), v, prec), m

    # query blocks, so that the scores fit: the whole ones one after
    # another under ``lax.map`` (one compiled block), then what is left
    whole, out, masks = S // Q_BLOCK, [], []
    if whole:
        o, m = jax.lax.map(lambda t0: block(t0, Q_BLOCK),
                           jnp.arange(whole) * Q_BLOCK)
        out.append(o.transpose(1, 0, 2, 3).reshape(N, whole * Q_BLOCK, dv))
        masks.append(m.reshape(whole * Q_BLOCK, S))
    if S % Q_BLOCK:
        o, m = block(whole * Q_BLOCK, S % Q_BLOCK)
        out.append(o)
        masks.append(m)
    o = jnp.concatenate(out, 1).transpose(1, 0, 2).reshape(S, N * dv)
    return _mm(o, w["o"], prec), jnp.concatenate(masks, 0)


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


def layer(h, w, sizes, dense: bool, held, prec, picks=None, select="index"):
    """One layer on ONE sequence ``h [S, H]`` -> (h', margin ``[S]``, the
    picks ``[S, S]`` it attended under, for the layers after it)."""
    w = _f32(w)
    y, picks = attention(h, w["attn"], sizes, prec, picks, w.get("indexer"),
                         select)
    h = h + y
    f = _rms(h, w["attn"]["post_norm"], sizes["rms_norm_eps"])
    if dense:
        return (h + _gated(f, w["ffn"], prec),
                jnp.full((h.shape[0],), 1e9), picks)
    y, margin = expert_layer(f, w, sizes, held, prec)
    return h + y, margin, picks


def forward(seed: int, sizes: dict, seqs, *, held=None, banned=(),
            prec: str = "f32", select: str = "index", at=None):
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
    picks = [None] * len(seqs)
    fns = {}
    for l in range(int(sizes["num_hidden_layers"])):
        kind = (is_dense(sizes, l), is_full(sizes, l))
        w = jax.jit(lambda k, l=l: layer_weights(k, sizes, l, held))(key)
        if kind not in fns:
            fns[kind] = jax.jit(
                lambda h, w, p, dense=kind[0]: layer(h, w, sizes, dense, held,
                                                     prec, p, select))
        for i, h in enumerate(hs):
            hs[i], m, picks[i] = fns[kind](h, w, picks[i])
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
