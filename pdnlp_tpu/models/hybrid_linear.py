"""A pre-norm decoder whose layers are of TWO kinds in a fixed period —
softmax GQA without positions beside gated delta-rule linear attention —
with sparse experts in every layer, served over paged K/V pools for the
GQA layers and a PER-SLOT recurrent state for the linear ones.

The third model family (the engine finds it through ``models/families.py``).
The layer, from the published config of Solar-Open2-250B
(``HybridLinearConfig``); ``benchmark/reference/solar_open2.py`` states the
same equations again in plain float32, the linear layers by their
SEQUENTIAL recurrence:

- ``x += Mixer(rms(x))``; ``x += MoE(rms(x))``; final ``rms``; untied head.
- **GQA layer** (layers 0, 4, 8, ...): ``q = a W_q`` (``num_heads`` heads),
  ``k = a W_k``, ``v = a W_v`` (``num_kv_heads`` heads, each serving
  ``num_heads / num_kv_heads`` query heads); NO position term of any kind;
  causal softmax of ``q k^T / sqrt(d)`` in float32; ``o = (attn *
  sigmoid(a W_g)) W_o``.
- **linear layer** (the ``gqa_interval`` layers after each): ``[q~ | k~ |
  v~] = a W_qkv``; a depthwise causal convolution of width ``conv_kernel``
  over time on each channel, then silu; ``q = l2norm(q~) / sqrt(d_k)``, ``k =
  l2norm(k~)``, ``v = v~``; log decay ``g = -exp(A_log) * softplus(a W_a1
  W_a2 + dt_bias)`` a channel (``alpha = exp(g)`` in (0, 1)); step ``beta =
  2 * sigmoid(a W_beta)`` a head; the state ``S`` (``d_k x d_v`` a head,
  float32) moves by ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} +
  beta_t k_t v_t^T`` and ``o_t = S_t^T q_t``; out ``= (rms_head(o_t) *
  sigmoid(a W_g1 W_g2)) W_o``.
- **experts**: ``models/latent_moe.py``'s layer as it stands (``route`` at
  ``n_group`` 1 is a plain top-k; ``held_experts`` told which it holds).

**What is kept between launches.**  A GQA layer caches K and V, ``kv_width``
values a position each, in twin pools ``[n_gqa, P, page_sz, kv_width]`` that
the programs never rebuild (``models/decoder.py``'s paged-cache contract).
A linear layer keeps, for every SLOT, its state ``[slots, heads, d_k, d_v]``
float32 and the last ``conv_kernel - 1`` inputs of its convolution
``[slots, conv_kernel - 1, 3 x linear_width]``: one array a layer of each
(``state_shapes``), donated like the pools.  A prompt's FINAL state — at
the last REAL position of its padded row: padded positions are given ``beta
= 0`` and ``g = 0``, which leaves the state as it is — is what the prefill
hands over; a decode step reads and writes the rows of its row rung.

**Two forms of one recurrence.**  A decode step applies it as written.  A
prompt is cut into chunks of :data:`CHUNK`.  Inside a chunk, with ``G_r``
the running sum of ``g`` and ``M = I + Diag(beta) tril(A, -1)``, ``A_ri =
sum_d k_rd k_id exp(G_rd - G_id)``, the rows ``U`` that the state gains
solve ``M U = Diag(beta) (V - (exp(G) K) S_0)``, which is linear in the
chunk's first state ``S_0``: ``U = u - w S_0`` with ``u = M^-1 (beta V)``
and ``w = M^-1 (beta exp(G) K)``; then ``o = (exp(G) Q) S_0 + Aqk U``
(``Aqk_ri = sum_d q_rd k_id exp(G_rd - G_id)``, ``i <= r``) and ``S_C =
Diag(exp(G_C)) S_0 + (exp(G_C - G) K)^T U``.  **What does not read the
state is not in the sequential loop**: ``G``, ``A``, ``Aqk``, ``u``, ``w``
and the decayed ``q`` and ``k`` are computed for a GROUP of chunks at once
(:data:`GROUP_BYTES`: a long prompt's are never all alive), and the loop
that is left carries ``S`` through three products a chunk — ``[w; exp(G) Q]
S_0`` stacked, ``Aqk U``, ``(exp(G_C - G) K)^T U`` — and one multiply.
**The pairwise sums are matrix products where a reference point allows
it**: a chunk's positions are cut into row blocks of :data:`ROW`; for a
key ``i`` of an EARLIER block than row ``r``, with ``ref`` the first
position of ``r``'s block, ``exp(G_r - G_i) = exp(G_r - G_ref) exp(G_ref -
G_i)``, both factors at most 1, so ``A_ri = (k_r exp(G_r - G_ref)) . (k_i
exp(G_ref - G_i))``; only a key of the row's OWN block is taken element by
element (``[.., ROW, ROW, d]``).  ``M`` is inverted the same way: the own
blocks by doubling (``_unit_lower_inverse``), the blocks before them by
products, a row block after another.  Every exponent is a
difference ``G_later - G_earlier <= 0``, so no decay, however strong,
overflows (the factored form ``k_i / exp(G_i)`` does, and a test evaluates
every ``exp``'s operand).  Everything that touches the state is float32 at
``highest`` matmul precision.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pdnlp_tpu.models.config import HybridLinearConfig
from pdnlp_tpu.models.decoder import _layer_rows
from pdnlp_tpu.models.latent_moe import (F32, _einsum, _embed, _logits, _mm,
                                         _rms, _weight_dtype, add_load,
                                         moe_ffn, no_load)
from pdnlp_tpu.ops.attention import NEG_INF

Params = Dict[str, Any]
HIGHEST = jax.lax.Precision.HIGHEST

#: positions of one chunk of the chunkwise delta rule
CHUNK = 64
#: positions of one row block of a chunk: pairwise decays inside a block are
#: taken element by element, across blocks through the later block's first
#: position
ROW = 16
#: what one GROUP of chunks may hold of its widest intermediate (the own-block
#: products): the terms that do not read the state are computed a group at a
#: time, so a long prompt's are never all alive at once
GROUP_BYTES = 256 * 2 ** 20
#: query rows of one softmax attention block of a prompt
Q_BLOCK = 512
#: under the square root of ``l2norm``
L2_EPS = 1e-6


# ------------------------------------------------------------------- weights

def _ffn(H: int, width: int, lead: tuple = ()) -> Dict[str, tuple]:
    return {"gate": lead + (H, width), "up": lead + (H, width),
            "down": lead + (width, H)}


def layer_shapes(cfg: HybridLinearConfig, l: int) -> Dict[str, Any]:
    """Layer ``l``'s parameters as shapes.  Matrices are ``y = x @ w``; a
    linear layer's ``W_q | W_k | W_v`` and its three convolutions are ONE
    leaf each (``qkv``, ``conv``): a layout of the published columns."""
    H, F = cfg.hidden_size, cfg.moe_intermediate_size
    if cfg.is_gqa(l):
        N, kv = cfg.num_heads * cfg.head_dim, cfg.kv_width
        mixer = {"in_norm": (H,), "q": (H, N), "k": (H, kv), "v": (H, kv),
                 "g": (H, N), "o": (N, H), "post_norm": (H,)}
    else:
        W, r = cfg.linear_width, cfg.low_rank
        mixer = {"in_norm": (H,), "qkv": (H, 3 * W),
                 "conv": (cfg.conv_kernel, 3 * W),
                 "a_down": (H, r), "a_up": (r, W),
                 "a_log": (cfg.linear_num_heads,), "dt_bias": (W,),
                 "beta": (H, cfg.linear_num_heads),
                 "g_down": (H, r), "g_up": (r, W),
                 "o_norm": (cfg.linear_head_dim,), "o": (W, H),
                 "post_norm": (H,)}
    return {"mixer": mixer, "router": (H, cfg.n_routed_experts),
            "shared": _ffn(H, F * cfg.n_shared_experts),
            "experts": _ffn(H, F, (cfg.experts_held,))}


def param_shapes(cfg: HybridLinearConfig) -> Dict[str, Any]:
    """The parameter tree as shapes: every layer's leaves are its OWN (no
    stack over layers — a program reads a layer's matrices where they lie,
    and a stack sliced by layer is a copy of it)."""
    return {"embed": (cfg.vocab_size, cfg.hidden_size),
            "layers": [layer_shapes(cfg, l) for l in range(cfg.num_layers)],
            "final_norm": (cfg.hidden_size,)}


def _init_leaf(key, name: str, shape: tuple) -> jax.Array:
    """Seeded values, float32: matrices normal / sqrt(fan-in), norm gains 1
    + 0.1 normal, the convolution normal / sqrt(width); ``a_log`` the log
    of uniform (1, 16) and ``dt_bias`` the inverse softplus of log-uniform
    (0.001, 0.1), so that decays lie from near 0 to near 1."""
    decay = name in ("a_log", "dt_bias")
    draw = jax.random.uniform if decay else jax.random.normal
    x = draw(key, shape, F32)
    if name == "a_log":
        return jnp.log(1.0 + 15.0 * x)
    if name == "dt_bias":
        dt = jnp.exp(np.log(1e-3) + x * (np.log(1e-1) - np.log(1e-3)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name.endswith("norm"):
        return 1.0 + 0.1 * x
    if name == "embed":
        return x
    return x * shape[-2] ** -0.5


def init_params(key: jax.Array, cfg: HybridLinearConfig) -> Params:
    """Seeded weights in the family's STORED dtype, one leaf at a time (so
    that nothing float32 the size of the model is ever alive)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    wd = _weight_dtype(cfg)
    out = [_init_leaf(jax.random.fold_in(key, i), str(path[-1].key),
                      shape).astype(wd)
           for i, (path, shape) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def init_head(key: jax.Array, cfg: HybridLinearConfig) -> Params:
    """The untied output head ``[hidden, vocab]``."""
    w = jax.random.normal(key, (cfg.hidden_size, cfg.vocab_size), F32)
    return {"kernel": (w * cfg.hidden_size ** -0.5).astype(_weight_dtype(cfg))}


def param_count(cfg: HybridLinearConfig) -> int:
    leaves = jax.tree_util.tree_leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    return int(sum(int(np.prod(s)) for s in leaves)
               + cfg.hidden_size * cfg.vocab_size)


def state_shapes(cfg: HybridLinearConfig, slots: int) -> Tuple:
    """What a SLOT keeps besides its pages, as (shape, dtype): every linear
    layer's recurrent state (float32), then every linear layer's
    convolution tail (the compute dtype's values: ``None`` = the engine's)."""
    N, d = cfg.linear_num_heads, cfg.linear_head_dim
    n = cfg.num_linear_layers
    return (((slots, N, d, d), jnp.float32),) * n \
        + (((slots, cfg.conv_kernel - 1, 3 * cfg.linear_width), None),) * n


# ---------------------------------------------------------------- delta rule

def _heinsum(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """A float32 product that touches the state: ``highest`` precision."""
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def delta_step(q, k, v, g, beta, S):
    """The recurrence as written, one position: ``q k g [B, N, dk]``, ``v
    [B, N, dv]``, ``beta [B, N]``, ``S [B, N, dk, dv]``, all float32 ->
    (``o [B, N, dv]``, ``S'``)."""
    Sd = jnp.exp(g)[..., None] * S
    u = beta[..., None] * (v - jnp.sum(k[..., None] * Sd, axis=-2))
    S = Sd + k[..., None] * u[..., None, :]
    return jnp.sum(q[..., None] * S, axis=-2), S


def _unit_lower_inverse(L: jax.Array) -> jax.Array:
    """``(I + L)^-1`` for strictly lower-triangular ``L [..., R, R]``, ``R``
    a power of two, by doubling: with the diagonal blocks of one size
    inverted (``a`` above ``d``), the block under them is ``-d L_21 a`` —
    every matrix and every pair of blocks at once, ``log2 R`` steps.  Exact
    sums of float32 products; the power series ``sum (-L)^n`` cancels
    catastrophically at ``beta`` near 2."""
    lead, R = L.shape[:-2], L.shape[-1]

    def mm(x, y):
        return jnp.sum(x[..., :, :, None] * y[..., None, :, :], axis=-2)

    inv = jnp.ones(lead + (R, 1, 1), L.dtype)         # the 1 x 1 blocks'
    h = 1
    while h < R:
        n = R // (2 * h)                               # pairs of blocks
        pair = np.eye(n, dtype=bool)[:, None, :, None]
        below = jnp.sum(jnp.where(pair, L.reshape(lead + (n, 2 * h, n, 2 * h)),
                                  0.0), axis=-2)[..., h:, :h]   # [.., n, h, h]
        ad = inv.reshape(lead + (n, 2, h, h))
        a, d = ad[..., 0, :, :], ad[..., 1, :, :]
        inv = jnp.block([[a, jnp.zeros_like(a)], [-mm(mm(d, below), a), d]])
        h *= 2
    return inv[..., 0, :, :]


def _chunk_terms(q, k, v, g, b, R: int):
    """All of a chunk's recurrence that does NOT read the state (module
    note), for every chunk handed in at once: ``q k g [..., C, dk]``, ``v
    [..., C, dv]``, ``b [..., C]`` -> ``w`` and ``exp(G) q`` stacked ``[...,
    2C, dk]``, ``u [..., C, dv]``, ``Aqk [..., C, C]``, ``exp(G_C - G) k
    [..., C, dk]``, ``exp(G_C) [..., dk]``.  Every exponent is a difference
    ``G_later - G_earlier``."""
    lead, (C, dk) = q.shape[:-2], q.shape[-2:]
    nb = C // R

    def rows(x):                     # [..., C, d] -> [..., nb, R, d]
        return x.reshape(lead + (nb, R) + x.shape[-1:])

    G = jnp.cumsum(g, axis=-2)
    Gb, qb, kb = rows(G), rows(q), rows(k)
    # a key of an EARLIER row block: through the block's first position
    ref = Gb[..., :1, :]                                      # [.., nb, 1, dk]
    earlier = np.arange(C) < R * np.arange(nb)[:, None]             # [nb, C]
    kk = k[..., None, :, :] * jnp.exp(jnp.where(
        earlier[..., None], ref - G[..., None, :, :], -jnp.inf))
    fall = jnp.exp(Gb - ref)
    A = _heinsum("...brd,...bid->...bri", kb * fall, kk)      # [.., nb, R, C]
    Aqk = _heinsum("...brd,...bid->...bri", qb * fall, kk)
    # a key of the row's OWN block, i <= r: element by element
    own = np.tril(np.ones((R, R), bool))
    kd = kb[..., None, :, :] * jnp.exp(jnp.where(
        own[..., None], Gb[..., :, None, :] - Gb[..., None, :, :], -jnp.inf))
    A_own = jnp.sum(kb[..., :, None, :] * kd, axis=-1)        # [.., nb, R, R]
    Aqk_own = jnp.sum(qb[..., :, None, :] * kd, axis=-1)
    at_own = np.eye(nb, dtype=bool)[:, None, :, None]         # [nb, 1, nb, 1]
    Aqk = jnp.where(at_own, Aqk_own[..., None, :],
                    Aqk.reshape(lead + (nb, R, nb, R))).reshape(lead + (C, C))
    # (I + Diag(b) tril(A, -1)) [u | w] = Diag(b) [v | exp(G) k], a row
    # block after another: the own block inverted, the earlier ones' rows
    # of the solution taken off first
    eG = jnp.exp(G)
    bb = rows(b[..., None])
    inv = _unit_lower_inverse(bb * jnp.where(np.tril(own, -1), A_own, 0.0))
    rhs = rows(b[..., None] * jnp.concatenate([v, k * eG], axis=-1))
    A = bb * A
    sol = []
    for j in range(nb):
        x = rhs[..., j, :, :]
        if j:
            x = x - _heinsum("...ri,...iv->...rv", A[..., j, :, :j * R],
                             jnp.concatenate(sol, axis=-2))
        sol.append(_heinsum("...ri,...iv->...rv", inv[..., j, :, :], x))
    uw = jnp.concatenate(sol, axis=-2)                        # [.., C, dv+dk]
    dv = v.shape[-1]
    GC = G[..., -1:, :]
    return (jnp.concatenate([uw[..., dv:], q * eG], axis=-2), uw[..., :dv],
            Aqk, k * jnp.exp(GC - G), jnp.exp(GC)[..., 0, :])


@functools.partial(jax.jit, static_argnames=("C", "R", "per"))
def _scan_groups(q, k, v, g, beta, S, C: int, R: int, per: int):
    """:func:`delta_chunked` at chunks of ``C`` positions in row blocks of
    ``R``, ``per`` chunks a group, over whole groups.  Jitted, so that the
    layers of a trunk — one shape, some hundred operations each — are traced
    and lowered ONCE a program: a program's set-up goes with what is
    lowered."""
    B, T, N, _ = q.shape                       # T: whole groups
    nG = T // (per * C)

    def cut(x):    # [B, T, N, ...] -> [nG, B, per, C, N, ...]: no relayout
        return jnp.moveaxis(x.reshape((B, nG, per, C) + x.shape[2:]), 1, 0)

    def step(S, x):
        wq, u, Aqk, kend, eGC = x
        wqS = _heinsum("bnck,bnkv->bncv", wq, S)
        U = u - wqS[..., :C, :]
        o = wqS[..., C:, :] + _heinsum("bnri,bniv->bnrv", Aqk, U)
        S = eGC[..., None] * S + _heinsum("bnck,bncv->bnkv", kend, U)
        return S, o

    def group(S, x):
        # heads before positions HERE, a group at a time, where the
        # relayout rides on the first elementwise pass over each operand
        x = (jnp.moveaxis(jnp.moveaxis(y, 1, 0), 3, 2) for y in x)
        S, o = jax.lax.scan(step, S, _chunk_terms(*x, R))
        return S, jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)   # [B, per, C, N, dv]

    S, o = jax.lax.scan(group, S, tuple(cut(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).reshape(B, T, N, -1), S


def delta_chunked(q, k, v, g, beta, S, chunk: int = CHUNK):
    """The same recurrence over ``T`` positions in chunks (module note):
    what does not read the state computed a GROUP of chunks at a time, the
    state carried over a group's chunks, and over the groups, under
    ``lax.scan``.  ``q k g [B, T, N, dk]``, ``v [B, T, N, dv]``, ``beta [B,
    T, N]``, ``S [B, N, dk, dv]``, all float32; a position with ``beta = 0``
    and ``g = 0`` leaves the state as it is."""
    B, T, N, dk = q.shape
    C = min(chunk, -(-T // ROW) * ROW)
    R = math.gcd(ROW, C)
    nC = -(-T // C)
    # chunks a group: the widest thing a chunk's terms hold is the own-block
    # products ``[B, N, C, R, dk]``.  A count that DIVIDES the chunks where
    # one over half the budget does (padding a long prompt to whole groups
    # is a copy of every operand), else groups of one size, as few as the
    # budget allows, the last one padded
    most = min(nC, max(1, GROUP_BYTES // (B * N * C * R * dk * 4)))
    per = max(p for p in range(1, most + 1) if nC % p == 0)
    if 2 * per < most:
        per = -(-nC // -(-nC // most))
    pad = -T % (per * C)
    if pad:      # positions that leave the state alone; their rows are cut
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (
            x.ndim - 2)) for x in (q, k, v, g, beta))
    o, S = _scan_groups(q, k, v, g, beta, S, C, R, per)
    return o[:, :T], S


# ------------------------------------------------------------ a linear layer

def _l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _linear_inputs(a, mp: Params, cfg: HybridLinearConfig, conv_in, valid,
                   dtype):
    """Normed input ``a [B, T, H]`` and the convolution's window ``conv_in
    [B, K - 1 + T, 3W]`` (the ``K - 1`` inputs before the first position,
    then the ``T`` projected ones) -> ``q k v g [B, T, N, d]`` and ``beta
    [B, T, N]`` float32, with ``beta = 0`` and ``g = 0`` where not
    ``valid``."""
    B, T = a.shape[:2]
    N, d, K = cfg.linear_num_heads, cfg.linear_head_dim, cfg.conv_kernel
    w = mp["conv"].astype(F32)
    y = sum(conv_in[:, j:j + T].astype(F32) * w[j] for j in range(K))
    q, k, v = (x.reshape(B, T, N, d)
               for x in jnp.split(jax.nn.silu(y), 3, axis=-1))
    q, k = _l2norm(q) * d ** -0.5, _l2norm(k)
    z = _mm(_mm(a, mp["a_down"], dtype).astype(dtype), mp["a_up"], dtype)
    g = -jnp.exp(mp["a_log"].astype(F32))[:, None] * jax.nn.softplus(
        z + mp["dt_bias"].astype(F32)).reshape(B, T, N, d)
    beta = 2.0 * jax.nn.sigmoid(_mm(a, mp["beta"], dtype))
    keep = valid[..., None]
    return (q, k, v, jnp.where(keep[..., None], g, 0.0),
            jnp.where(keep, beta, 0.0))


def _linear_out(o, a, mp: Params, cfg: HybridLinearConfig, dtype):
    """``(rms_head(o) * sigmoid(a W_g1 W_g2)) W_o``: ``o [B, T, N, dv]``
    float32 -> ``[B, T, H]`` float32."""
    B, T = o.shape[:2]
    gate = jax.nn.sigmoid(_mm(_mm(a, mp["g_down"], dtype).astype(dtype),
                              mp["g_up"], dtype))
    o = _rms(o, mp["o_norm"], cfg.rms_norm_eps).reshape(B, T, -1) * gate
    return _mm(o.astype(dtype), mp["o"], dtype)


def linear_prompt(a, mp: Params, cfg: HybridLinearConfig, valid, nreal,
                  dtype):
    """A linear layer's mixer over whole prompts from an EMPTY state ->
    (``[B, T, H]`` float32, the final state ``[B, N, dk, dv]`` float32, the
    convolution tail ``[B, K - 1, 3W]``: the last ``K - 1`` inputs before
    position ``nreal``, zeros before position 0)."""
    B = a.shape[0]
    N, d, K = cfg.linear_num_heads, cfg.linear_head_dim, cfg.conv_kernel
    qkv = _mm(a, mp["qkv"], dtype).astype(dtype)
    conv_in = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
    q, k, v, g, beta = _linear_inputs(a, mp, cfg, conv_in, valid, dtype)
    o, S = delta_chunked(q, k, v, g, beta, jnp.zeros((B, N, d, d), F32))
    # conv_in[p] is the input of position p - (K - 1)
    at = nreal.astype(jnp.int32)[:, None] + jnp.arange(K - 1, dtype=jnp.int32)
    tail = jnp.take_along_axis(conv_in, at[:, :, None], axis=1)
    return _linear_out(o, a, mp, cfg, dtype), S, tail


def linear_token(a, mp: Params, cfg: HybridLinearConfig, S, tail, live,
                 dtype):
    """The same mixer for ONE new position a row (``a [B, 1, H]``) from the
    row's state ``S`` and convolution ``tail`` -> (``[B, 1, H]``, ``S'``,
    ``tail'``); a row that is not ``live`` keeps both as they are."""
    qkv = _mm(a, mp["qkv"], dtype).astype(tail.dtype)
    conv_in = jnp.concatenate([tail, qkv], axis=1)              # [B, K, 3W]
    q, k, v, g, beta = _linear_inputs(a, mp, cfg, conv_in, live[:, None],
                                      dtype)
    o, S2 = delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], S)
    S2 = jnp.where(live[:, None, None, None], S2, S)
    tail2 = jnp.where(live[:, None, None], conv_in[:, 1:], tail)
    return _linear_out(o[:, None], a, mp, cfg, dtype), S2, tail2


# --------------------------------------------------------------- a GQA layer

def _gqa_project(a, mp: Params, cfg: HybridLinearConfig, dtype):
    """-> ``q [B, T, Nkv, rep, d]``, ``k v [B, T, kv_width]`` in ``dtype``."""
    B, T = a.shape[:2]
    q = _mm(a, mp["q"], dtype).astype(dtype).reshape(
        B, T, cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads,
        cfg.head_dim)
    return (q, _mm(a, mp["k"], dtype).astype(dtype),
            _mm(a, mp["v"], dtype).astype(dtype))


def gqa_attend(q, k, v, qpos, cfg: HybridLinearConfig, dtype) -> jax.Array:
    """Causal softmax without positions: queries ``q [B, T, Nkv, rep, d]``
    at ``qpos [B, T]`` over keys and values ``[B, S, kv_width]`` (key j AT
    position j), each KV head serving its ``rep`` query heads -> ``[B, T,
    heads * d]`` in ``dtype``."""
    B, T = q.shape[:2]
    S, d = k.shape[1], cfg.head_dim
    k = k.reshape(B, S, cfg.num_kv_heads, d)
    v = v.reshape(B, S, cfg.num_kv_heads, d)
    kpos = jnp.arange(S, dtype=jnp.int32)

    def block(qb, qp):
        s = _einsum("btgrd,bsgd->bgrts", qb, k) * d ** -0.5
        vis = kpos[None, None, None, None, :] <= qp[:, None, None, :, None]
        p = jax.nn.softmax(jnp.where(vis, s, NEG_INF), axis=-1).astype(dtype)
        return _einsum("bgrts,bsgd->btgrd", p, v).astype(dtype)

    if T <= Q_BLOCK or T % Q_BLOCK:
        o = block(q, qpos)
    else:
        # blocks of one shape, ONE AFTER ANOTHER (latent_moe.attend_expanded:
        # unrolled, every block's float32 scores are alive at once)
        def cut(x):
            return jnp.moveaxis(
                x.reshape((B, T // Q_BLOCK, Q_BLOCK) + x.shape[2:]), 1, 0)

        o = jax.lax.map(lambda x: block(x[0], x[1]), (cut(q), cut(qpos)))
        o = jnp.moveaxis(o, 0, 1)
    return o.reshape(B, T, cfg.num_heads * d)


def gqa_attend_folded(q, k, v, qpos, cfg: HybridLinearConfig,
                       dtype) -> jax.Array:
    """:func:`gqa_attend` for ONE query a row (the decode step) over the
    gathered pages AS THEY LIE, ``k v [B, S, kv_width]`` with the KV heads
    folded in the minor axis: query head ``(g, r)`` becomes a row that holds
    ``q[g, r]`` in KV head ``g``'s columns and zeros elsewhere, so both
    products are ONE dot a row against ``[S, kv_width]`` and the head's own
    block is cut from the small result.  Splitting ``[.., 1024]`` into ``[..,
    8, 128]`` is a relayout of every gathered page (read from the first
    traced chip run: four copies of 805 MB, 9.8 of the step's 42.5 ms);
    ``num_kv_heads`` times the multiply-adds on a unit that has nothing
    else to do.  -> ``[B, 1, heads * d]`` in ``dtype``."""
    B = q.shape[0]
    S, W = k.shape[1:]
    G, d = cfg.num_kv_heads, cfg.head_dim
    R = cfg.num_heads // G
    own = jnp.eye(G, dtype=dtype)[None, :, None, :, None]  # [1, G, 1, G', 1]
    qf = (q[:, 0, :, :, None, :] * own).reshape(B, G * R, W)
    s = _einsum("bqc,bsc->bqs", qf, k) * d ** -0.5
    vis = jnp.arange(S, dtype=jnp.int32)[None, None, :] <= qpos[:, :, None]
    p = jax.nn.softmax(jnp.where(vis, s, NEG_INF), axis=-1).astype(dtype)
    of = _einsum("bqs,bsc->bqc", p, v).reshape(B, G, R, G, d)
    o = jnp.sum(of * own.astype(F32), axis=3)                 # [B, G, R, d]
    return o.astype(dtype).reshape(B, 1, G * R * d)


def _gqa_out(o, a, mp: Params, dtype):
    """``(attn * sigmoid(a W_g)) W_o`` -> float32."""
    gate = jax.nn.sigmoid(_mm(a, mp["g"], dtype))
    return _mm((o.astype(F32) * gate).astype(dtype), mp["o"], dtype)


# -------------------------------------------------------------------- layers

def _moe(x, lp: Params, cfg: HybridLinearConfig, valid, dtype):
    """``x += MoE(rms(x))`` -> (x', the layer's load: ``moe_ffn``'s)."""
    B, T, H = x.shape
    f = _rms(x, lp["mixer"]["post_norm"], cfg.rms_norm_eps)
    # the layer's own experts as a stack of one: held_experts indexes
    # (layer, expert) into the leaves where they lie
    experts = {n: w[None] for n, w in lp["experts"].items()}
    y, load = moe_ffn(f.reshape(B * T, H), lp, experts, 0, cfg,
                      valid.reshape(B * T), dtype)
    return x + y.reshape(B, T, H).astype(dtype), load


# ----------------------------------------------------------------- programs

def prefill(params: Params, head: Params, cfg: HybridLinearConfig,
            input_ids: jax.Array,       # [B, S] int32 (left-aligned)
            attention_mask: jax.Array,  # [B, S] {0,1}
            last_pos: jax.Array,        # [B] index of the last real token
            *, dtype=jnp.bfloat16):
    """A cold prompt from position 0 -> (next-token logits ``[B, vocab]``
    float32, the load (``latent_moe.no_load``), the GQA layers' keys and values
    ``2 x [n_gqa, B, S, kv_width]`` for ``decoder.insert_pool``, the linear
    layers' final states then their convolution tails, a layer each)."""
    B, S = input_ids.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = attention_mask.astype(bool)
    nreal = last_pos.astype(jnp.int32) + 1
    x = _embed(params, input_ids, dtype)
    load = no_load(cfg)
    ks, vs, states, tails = [], [], [], []
    for l, lp in enumerate(params["layers"]):
        mp = lp["mixer"]
        a = _rms(x, mp["in_norm"], cfg.rms_norm_eps)
        if cfg.is_gqa(l):
            q, k, v = _gqa_project(a, mp, cfg, dtype)
            y = _gqa_out(gqa_attend(q, k, v, positions, cfg, dtype), a, mp,
                         dtype)
            ks.append(k)
            vs.append(v)
        else:
            y, S_l, tail = linear_prompt(a, mp, cfg, valid, nreal, dtype)
            states.append(S_l)
            tails.append(tail)
        x, n = _moe(x + y.astype(dtype), lp, cfg, valid, dtype)
        load = add_load(load, n)
    h_last = jnp.take_along_axis(
        x, last_pos.astype(jnp.int32)[:, None, None], axis=1)
    return (_logits(params, head, cfg, h_last, dtype)[:, 0], load,
            (jnp.stack(ks), jnp.stack(vs)), tuple(states) + tuple(tails))


def paged_decode(params: Params, head: Params, cfg: HybridLinearConfig,
                 tokens: jax.Array,      # [B, 1] int32
                 pools: Tuple,           # K and V: [n_gqa, P, page_sz, W]
                 states: Tuple,          # state_shapes, rows >= B
                 page_table: jax.Array,  # [B, <= MP] int32 (sentinel P)
                 start: jax.Array,       # [B] abs position of the token
                 *, dtype=jnp.bfloat16):
    """The decode step: row b's token sits at ``start[b]``; a GQA layer
    writes its key and value through the table in place and attends to
    positions ``<= start[b]`` of the pages the table names; a linear layer
    moves rows ``[0, B)`` of its state and convolution tail by one position.
    A dead row (sentinel table) writes nothing, keeps its state and takes no
    part in the expert layer.  -> (logits ``[B, vocab]``, the load, the pools,
    the states)."""
    pk, pv = pools
    Lp, P, ps, W = pk.shape
    B = tokens.shape[0]
    MP = page_table.shape[1]
    extent = MP * ps
    n_lin = cfg.num_linear_layers
    start = start.astype(jnp.int32)
    positions = start[:, None]
    phys = jnp.take_along_axis(
        page_table, jnp.clip(positions // ps, 0, MP - 1), axis=1)
    real = (positions < extent) & (phys < P)                       # [B, 1]
    live = real[:, 0]
    wrows = _layer_rows(jnp.where(real, phys * ps + positions % ps, P * ps),
                        Lp, P * ps).reshape(Lp, B)
    rpages = _layer_rows(page_table, Lp, P)                    # [Lp, B, MP]

    def cached(pool, new, j):
        flat = pool.reshape(Lp * P * ps, W).at[wrows[j]].set(
            new.reshape(B, W).astype(pool.dtype), mode="drop")
        got = jnp.take(flat.reshape(Lp * P, ps, W), rpages[j], axis=0,
                       mode="clip").reshape(B, extent, W).astype(dtype)
        return flat.reshape(pool.shape), got

    x = _embed(params, tokens, dtype)
    load = no_load(cfg)
    states = list(states)
    for l, lp in enumerate(params["layers"]):
        mp = lp["mixer"]
        a = _rms(x, mp["in_norm"], cfg.rms_norm_eps)
        j = l // cfg.period
        if cfg.is_gqa(l):
            q, k, v = _gqa_project(a, mp, cfg, dtype)
            pk, ck = cached(pk, k, j)
            pv, cv = cached(pv, v, j)
            y = _gqa_out(gqa_attend_folded(q, ck, cv, positions, cfg, dtype),
                         a, mp, dtype)
        else:
            i = l - j - 1                       # which linear layer this is
            S, tail = states[i], states[n_lin + i]
            y, S2, tail2 = linear_token(a, mp, cfg, S[:B], tail[:B], live,
                                        dtype)
            states[i] = S.at[:B].set(S2)
            states[n_lin + i] = tail.at[:B].set(tail2)
        x, n = _moe(x + y.astype(dtype), lp, cfg, real, dtype)
        load = add_load(load, n)
    return (_logits(params, head, cfg, x, dtype)[:, 0], load, (pk, pv),
            tuple(states))

