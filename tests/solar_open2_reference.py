"""The plain reference of the hybrid decoder — gated delta-rule linear
attention beside softmax GQA without positions, sparse experts in every
layer (Solar-Open2-250B,
https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json): the
full forward pass in straightforward ``jax.numpy`` float32 at ``highest``
matmul precision.  No cache, no chunks, no kernel, no batching tricks,
nothing imported from the program; the weights are made here from
``--seed``, a layer at a time, and the program is GIVEN the same values.

The layer (``sizes`` holds the config file's numbers, its
``linear_attn_config`` group and its ``gqa_layers`` list).  Pre-norm:
``h += Mixer(rms(h))``; ``h += MoE(rms(h))``; final ``rms``; ``logits = h
W_head`` (untied).

- **GQA layer** (``l in gqa_layers``): ``q = a W_q`` (64 heads of 128),
  ``k = a W_k``, ``v = a W_v`` (8 heads of 128, each serving 8 query heads);
  NO rotary and no other position term (``use_rope`` false); causal softmax
  of ``q k^T / sqrt(128)``; ``o = (attn * sigmoid(a W_g)) W_o``
  (``use_gqa_gate``).
- **linear layer** (every other), a head (``d_k = d_v = 128``, 64 heads):
  ``[q~ | k~ | v~] = a W_qkv``; ``y_t = sum_j w_j x_{t-3+j}`` a channel (a
  depthwise causal convolution of width 4), then silu; ``q = l2norm(q~) /
  sqrt(d_k)``, ``k = l2norm(k~)``, ``v = v~``; ``g_t = -exp(A_log) *
  softplus(a W_a1 W_a2 + dt_bias)`` a channel, ``alpha_t = exp(g_t)``
  (``kda_use_full_proj`` false: the projection is low-rank); ``beta_t = 2 *
  sigmoid(a W_beta)`` a head (``kda_allow_neg_eigval``); the SEQUENTIAL
  recurrence, one position after another from ``S_0 = 0``:
  ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``; out ``= (rms_head(o_t) * sigmoid(a W_g1 W_g2)) W_o``.
- **experts, every layer** (``first_k_dense_replace`` 0): ``s = sigmoid(f
  W_r)`` over the router's whole width; the ``num_experts_per_tok`` largest
  are taken; ``g = routed_scaling_factor * s_sel / sum(s_sel)``
  (``norm_topk_prob``); ``h += sum_e g_e Expert_e(f) + Shared(f)``, each a
  gated silu feed-forward of ``moe_intermediate_size``.

Departures from the published description, each the configuration's
(``assumed`` in its file lists what the catalog row does not give):

- **the share**: ``held = (first, count)`` names the experts this chip
  holds; the router keeps its width and its experts a token; only ``sum over
  held e`` is added (plus the shared expert), here as in the program.
  ``None`` = the configuration's; ``(0, router_width)`` is the uncut layer.
- **the vocabulary slice**: ``vocab_size`` rows of the embedding and of the
  head; ids, logits and argmax are over the slice.
- ``W_q | W_k | W_v`` of a linear layer and its three convolutions are made
  as ONE matrix each (``qkv``, ``conv``): a layout of their columns.
- weights are random: normal / sqrt(fan-in) for matrices (the convolution:
  / sqrt(4)), normal for the embedding, 1 + 0.1 normal for norm gains,
  ``A_log`` the log of uniform (1, 16), ``dt_bias`` the inverse softplus of
  log-uniform (0.001, 0.1); every value rounded to bfloat16 (as the
  configuration stores them) and promoted to float32 here.

``prec`` lowers the precision of every matmul's operands ("bf16"; "fp8",
per-tensor scaled e4m3) and ``state`` that of the recurrent state between
positions ("bf16"): how the controls are computed.

Near-tie routing, as ``reference/axk1.py``: :func:`forward` also returns,
per position, the smallest MARGIN over the layers between a score taken and
one left out of which at least one is a held expert's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e9
Q_BLOCK = 512      # query rows of one attention block
L2_EPS = 1e-6      # under the square root of l2norm


# ------------------------------------------------------------------- weights

def seed_key(seed: int):
    return jax.random.key(int(seed) % (2 ** 32))


def held_of(sizes: dict):
    """(first, count) of the experts the configuration holds."""
    return int(sizes.get("expert_first", 0)), int(sizes["n_routed_experts"])


def router_width(sizes: dict) -> int:
    return int(sizes.get("router_width", sizes["n_routed_experts"]))


def is_gqa(sizes: dict, l: int) -> bool:
    return l in sizes["gqa_layers"]


def linear_dims(sizes: dict):
    """(heads, head size, convolution width, low rank) of a linear layer."""
    la = sizes["linear_attn_config"]
    return (int(la["num_heads"]), int(la["head_dim"]),
            int(la["short_conv_kernel_size"]), int(sizes["kda_low_rank"]))


def mixer_shapes(sizes: dict, l: int) -> dict:
    H = sizes["hidden_size"]
    if is_gqa(sizes, l):
        N = sizes["num_attention_heads"] * sizes["head_dim"]
        kv = sizes["num_key_value_heads"] * sizes["head_dim"]
        return {"in_norm": (H,), "q": (H, N), "k": (H, kv), "v": (H, kv),
                "g": (H, N), "o": (N, H), "post_norm": (H,)}
    n, d, K, r = linear_dims(sizes)
    W = n * d
    return {"in_norm": (H,), "qkv": (H, 3 * W), "conv": (K, 3 * W),
            "a_down": (H, r), "a_up": (r, W), "a_log": (n,),
            "dt_bias": (W,), "beta": (H, n), "g_down": (H, r),
            "g_up": (r, W), "o_norm": (d,), "o": (W, H), "post_norm": (H,)}


def ffn_shapes(sizes: dict, width: int) -> dict:
    H = sizes["hidden_size"]
    return {"gate": (H, width), "up": (H, width), "down": (width, H)}


def _leaf(key, shape, name, store=jnp.bfloat16):
    if name == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
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


def layer_weights(key, sizes: dict, l: int, held=None) -> dict:
    """Layer ``l``'s weights, bfloat16 (traceable): ``mixer`` by the layer's
    kind, ``router``, ``shared``, ``experts``.  An expert's values depend on
    the seed, the layer and the expert's OWN number, so every share of a
    layer holds the same expert ``e``; ``held`` = (first, count), default
    the configuration's."""
    k = jax.random.fold_in(key, 1000 + l)
    km, kr, ks, ke = jax.random.split(k, 4)
    F = sizes["moe_intermediate_size"]
    first, count = held if held is not None else held_of(sizes)
    return {
        "mixer": _leaves(km, mixer_shapes(sizes, l)),
        "router": _leaf(kr, (sizes["hidden_size"], router_width(sizes)),
                        "router"),
        "shared": _leaves(
            ks, ffn_shapes(sizes, F * int(sizes.get("n_shared_experts", 1)))),
        # one expert after another: an expert's float32 draw is the most alive
        "experts": jax.lax.map(
            lambda e: _leaves(jax.random.fold_in(ke, e), ffn_shapes(sizes, F)),
            first + jnp.arange(count)),
    }


def top_weights(key, sizes: dict, banned: tuple = ()) -> dict:
    """Embedding, final norm and the untied head, bfloat16 (traceable).
    ``banned``: ids the served model must never emit (the batcher's EOS) —
    their column of the head is zero."""
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


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


# -------------------------------------------------------------------- mixers

def gqa(a, w, sizes, prec):
    """``a [S, H]`` (normed) -> the GQA mixer's output ``[S, H]``."""
    S, N, d = a.shape[0], sizes["num_attention_heads"], sizes["head_dim"]
    Nkv = sizes["num_key_value_heads"]
    q = _mm(a, w["q"], prec).reshape(S, N, d).transpose(1, 0, 2)   # [N, S, d]
    k = _mm(a, w["k"], prec).reshape(S, Nkv, d).transpose(1, 2, 0)
    v = _mm(a, w["v"], prec).reshape(S, Nkv, d).transpose(1, 0, 2)
    # query head n is served by KV head n // (N / Nkv)
    k, v = jnp.repeat(k, N // Nkv, axis=0), jnp.repeat(v, N // Nkv, axis=0)
    pos = jnp.arange(S)
    out = []
    for t0 in range(0, S, Q_BLOCK):        # query blocks: scores fit
        t1 = min(S, t0 + Q_BLOCK)
        s = _mm(q[:, t0:t1], k, prec) * d ** -0.5
        s = jnp.where(pos[None, None, :] <= pos[None, t0:t1, None], s, NEG)
        out.append(_mm(jax.nn.softmax(s, -1), v, prec))           # [N, t, d]
    o = jnp.concatenate(out, 1).transpose(1, 0, 2).reshape(S, N * d)
    return _mm(o * jax.nn.sigmoid(_mm(a, w["g"], prec)), w["o"], prec)


def linear_inputs(a, w, sizes, prec):
    """-> ``q k v g [S, n, d]``, ``beta [S, n]``: what the recurrence reads."""
    S = a.shape[0]
    n, d, K, _ = linear_dims(sizes)
    x = jnp.pad(_mm(a, w["qkv"], prec), ((K - 1, 0), (0, 0)))
    y = sum(x[j:j + S] * w["conv"][j] for j in range(K))
    q, k, v = (z.reshape(S, n, d) for z in jnp.split(jax.nn.silu(y), 3, -1))
    z = _mm(_mm(a, w["a_down"], prec), w["a_up"], prec) + w["dt_bias"]
    g = -jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(z).reshape(S, n, d)
    beta = 2.0 * jax.nn.sigmoid(_mm(a, w["beta"], prec))
    return _l2norm(q) * d ** -0.5, _l2norm(k), v, g, beta


def recurrence(q, k, v, g, beta, state="f32"):
    """The delta rule with channel-wise decay, one position after another
    from an empty state -> ``o [S, n, d_v]``."""
    n, d = q.shape[1], q.shape[2]

    def hold(S):
        return S.astype(jnp.bfloat16).astype(jnp.float32) \
            if state == "bf16" else S

    def step(S, x):
        q, k, v, g, b = x
        Sd = jnp.exp(g)[:, :, None] * S                       # Diag(alpha) S
        kS = jnp.einsum("nk,nkv->nv", k, Sd, precision=HIGHEST)
        S = hold(Sd + k[:, :, None] * (b[:, None] * (v - kS))[:, None, :])
        return S, jnp.einsum("nk,nkv->nv", q, S, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((n, d, v.shape[-1]), jnp.float32),
                        (q, k, v, g, beta))
    return o


def linear(a, w, sizes, prec, state="f32"):
    """``a [S, H]`` (normed) -> the linear mixer's output ``[S, H]``."""
    S = a.shape[0]
    o = recurrence(*linear_inputs(a, w, sizes, prec), state=state)
    o = _rms(o, w["o_norm"], sizes["rms_norm_eps"]).reshape(S, -1)
    gate = jax.nn.sigmoid(_mm(_mm(a, w["g_down"], prec), w["g_up"], prec))
    return _mm(o * gate, w["o"], prec)


# ------------------------------------------------------------------- experts

def route(f, router, sizes, prec):
    """``f [S, H]`` -> (ids ``[S, k]``, gates ``[S, k]``, scores ``[S, E]``)."""
    s = jax.nn.sigmoid(_mm(f, router, prec))
    top, idx = jax.lax.top_k(s, sizes["num_experts_per_tok"])
    gates = top / (top.sum(-1, keepdims=True) + 1e-20) \
        * sizes["routed_scaling_factor"]
    return idx, gates, s


def routing_margin(idx, s, first, count):
    """Per position ``[S]``: the smallest distance between a score taken
    and a score left out of which at least one belongs to a held expert."""
    S, E = s.shape
    big = jnp.float32(1e9)
    taken = jnp.zeros((S, E), bool).at[jnp.arange(S)[:, None], idx].set(True)
    e = jnp.arange(E)
    held = (e >= first) & (e < first + count)
    lo_taken = jnp.min(jnp.where(taken, s, big), -1)
    lo_taken_held = jnp.min(jnp.where(taken & held, s, big), -1)
    hi_left = jnp.max(jnp.where(~taken, s, -big), -1)
    hi_left_held = jnp.max(jnp.where(~taken & held, s, -big), -1)
    return jnp.minimum(lo_taken_held - hi_left, lo_taken - hi_left_held)


def expert_layer(f, w, sizes, held, prec, shared: bool = True):
    """``f [S, H]`` -> (the expert layer's output ``[S, H]``, margin
    ``[S]``): every held expert's feed-forward over every position, weighted
    by its gate (0 where the router did not take it), plus the shared expert
    (``shared=False``: a share that leaves it to another)."""
    first, count = held
    idx, gates, s = route(f, w["router"], sizes, prec)
    out = _gated(f, w["shared"], prec) if shared else jnp.zeros_like(f)
    for j in range(count):
        g = jnp.sum(jnp.where(idx == first + j, gates, 0.0), -1)    # [S]
        p = jax.tree_util.tree_map(lambda x: x[j], w["experts"])
        out = out + g[:, None] * _gated(f, p, prec)
    return out, routing_margin(idx, s, first, count)


def layer(h, w, sizes, l: int, held, prec, state="f32"):
    """Layer ``l`` on ONE sequence ``h [S, H]`` -> (h', margin ``[S]``)."""
    w = _f32(w)
    eps = sizes["rms_norm_eps"]
    a = _rms(h, w["mixer"]["in_norm"], eps)
    if is_gqa(sizes, l):
        h = h + gqa(a, w["mixer"], sizes, prec)
    else:
        h = h + linear(a, w["mixer"], sizes, prec, state)
    y, margin = expert_layer(_rms(h, w["mixer"]["post_norm"], eps), w, sizes,
                             held, prec)
    return h + y, margin


def forward(seed: int, sizes: dict, seqs, *, held=None, banned=(),
            prec: str = "f32", state: str = "f32", at=None):
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
        kind = is_gqa(sizes, l)
        w = jax.jit(lambda k, l=l: layer_weights(k, sizes, l, held))(key)
        if kind not in fns:
            fns[kind] = jax.jit(
                lambda h, w, l=l: layer(h, w, sizes, l, held, prec, state))
        for i, h in enumerate(hs):
            hs[i], m = fns[kind](h, w)
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
