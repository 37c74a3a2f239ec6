"""Functional BERT encoder + sequence-classification head (pure JAX).

Capability twin of the reference's HF ``BertForSequenceClassification``
(``/root/reference/single-gpu-cls.py:252-255``: BERT-base, ``num_labels=6``,
forward ``(input_ids, token_type_ids, attention_mask)`` -> logits), but the
implementation is TPU-native rather than a port:

- **params are a plain pytree** (nested dicts of ``jnp`` arrays) — no module
  system.  This makes per-leaf ``NamedSharding`` (ZeRO/tensor sharding),
  donation, and checkpointing trivial.
- **one ``lax.scan`` over stacked layers**: every transformer layer's weights
  carry a leading ``[L, ...]`` axis and the 12 layers run as a single traced
  step — compile time stays flat in depth and XLA pipelines HBM prefetch of
  layer ``i+1`` against compute of layer ``i``.
- **mixed precision by policy**: master params live in fp32; ``dtype``
  selects the compute precision (bf16 = the AMP analog,
  ``/root/reference/multi-gpu-distributed-mp-amp-cls.py:160-175``).  Softmax
  and LayerNorm reduce in fp32; logits return in fp32.
- **remat**: ``remat=True`` wraps the scanned layer body in
  ``jax.checkpoint`` (the activation-checkpointing analog of
  ``/root/reference/multi-gpu-deepspeed-cls.py:240-244``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from pdnlp_tpu.models.config import BertConfig
from pdnlp_tpu.ops.attention import dot_product_attention, mask_bias

Params = Dict[str, Any]


def _fuse_qkv() -> bool:
    """Whether attention computes q/k/v as ONE fused [H, 3H] matmul.

    Trace-time switch (``PDNLP_FUSE_QKV``), default OFF: the fused form is
    the textbook win on GPU, but on v5e it measured 3% SLOWER than three
    separate projections (33.1 -> 32.0 probe steps/s — XLA materializes the
    weight concat each step instead of folding it; a record older than the
    ledger, removed, not re-measured)
    and the split form keeps tp's per-tensor output sharding natural.  The
    path stays for A/B profiling on other TPU generations."""
    import os

    return os.environ.get("PDNLP_FUSE_QKV", "0") == "1"


def _gelu(x, form: str = "erf"):
    """GELU — ``form`` comes from ``cfg.gelu`` at every call site.

    ``"erf"`` is the exact form — the reference BERT's activation
    (``transformers`` ``hidden_act="gelu"``).  ``"tanh"`` trades the erf
    backward (a VPU transcendental chain the step profile priced at
    ~3.3 ms; record removed, not re-measured) for a
    cheaper polynomial; max |Δ| vs erf is ~4e-4, and the shipped recipe
    measured +7% step rate AND +0.7pt fine-tune accuracy when pretrained
    with it end to end (0.5887 vs erf's 0.5813; a record older than the
    ledger, not re-measured).
    ``PDNLP_GELU_TANH=1`` force-enables tanh regardless of config — the
    A/B profiling override."""
    import os

    if form not in ("erf", "tanh"):
        # loud: a typo'd --gelu would otherwise silently run erf while
        # a pretrain cache is keyed on the raw string
        raise ValueError(f"gelu must be 'erf' or 'tanh', got {form!r}")
    approx = form == "tanh" or os.environ.get("PDNLP_GELU_TANH", "0") == "1"
    return jax.nn.gelu(x, approximate=approx)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _dense_init(key, fan_in: int, fan_out: int, std: float, stacked: int = 0):
    shape = (fan_in, fan_out) if not stacked else (stacked, fan_in, fan_out)
    k = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std
    b = jnp.zeros(shape[:-2] + (fan_out,), jnp.float32)
    return {"kernel": k, "bias": b}


def _ln_init(width: int, stacked: int = 0):
    shape = (stacked, width) if stacked else (width,)
    return {"scale": jnp.ones(shape, jnp.float32), "bias": jnp.zeros(shape, jnp.float32)}


def init_params(key: jax.Array, cfg: BertConfig) -> Params:
    """Build the parameter pytree (fp32 masters), truncated-normal 0.02."""
    H, L, I, std = cfg.hidden_size, cfg.num_layers, cfg.intermediate_size, cfg.initializer_range
    keys = jax.random.split(key, 12)

    def emb(k, rows):
        return jax.random.truncated_normal(k, -2.0, 2.0, (rows, H), jnp.float32) * std

    layers = {
        # all per-layer weights stacked on a leading [L] axis for lax.scan
        "q": _dense_init(keys[3], H, H, std, L),
        "k": _dense_init(keys[4], H, H, std, L),
        "v": _dense_init(keys[5], H, H, std, L),
        "o": _dense_init(keys[6], H, H, std, L),
        "attn_ln": _ln_init(H, L),
        "mlp_ln": _ln_init(H, L),
    }
    if not cfg.moe_experts:
        layers["up"] = _dense_init(keys[7], H, I, std, L)
        layers["down"] = _dense_init(keys[8], I, H, std, L)
    else:
        # MLP becomes E gated experts: weights gain an expert dim after the
        # layer dim ([L, E, in, out]) so the "ep" sharding mode can split
        # dim 1 over an "expert" mesh axis
        E = cfg.moe_experts

        def expert_dense(k, fan_in, fan_out):
            kk = jax.random.truncated_normal(
                k, -2.0, 2.0, (L, E, fan_in, fan_out), jnp.float32) * std
            return {"kernel": kk,
                    "bias": jnp.zeros((L, E, fan_out), jnp.float32)}

        layers["up"] = expert_dense(keys[7], H, I)
        layers["down"] = expert_dense(keys[8], I, H)
        layers["gate"] = {"kernel": jax.random.truncated_normal(
            keys[11], -2.0, 2.0, (L, H, E), jnp.float32) * std}
    return {
        "embeddings": {
            "word": emb(keys[0], cfg.vocab_size),
            "position": emb(keys[1], cfg.max_position),
            "token_type": emb(keys[2], cfg.type_vocab_size),
            "ln": _ln_init(H),
        },
        "layers": layers,
        "pooler": _dense_init(keys[9], H, H, std),
        "classifier": _dense_init(keys[10], H, cfg.num_labels, std),
    }


def param_count(params: Params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _layer_norm(x, scale, bias, eps):
    # reduce in fp32 whatever the compute dtype
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _dense(x, p, dtype):
    if "qscale" in p:
        # int8 weight-only serving (serve.quant): the per-OUTPUT-channel
        # scale commutes through the contraction, so it multiplies the
        # [.., out] RESULT — the int8 kernel is the only weight HBM reads,
        # and no dequantized copy materializes
        y = x @ p["kernel"].astype(dtype)
        return y * p["qscale"].astype(dtype) + p["bias"].astype(dtype)
    return x @ p["kernel"].astype(dtype) + p["bias"].astype(dtype)


def _expert_scale(p, y, dtype):
    """int8 serving (``serve.quant``): per-output-channel scale applied to
    an expert einsum OUTPUT ``[E, ..., out]`` — the same commute as
    ``_dense``, which never sees the MoE expert layouts.  Identity for
    float params."""
    if "qscale" not in p:
        return y
    s = p["qscale"].astype(dtype)                      # [E, out]
    return y * s.reshape(s.shape[0], *([1] * (y.ndim - 2)), s.shape[-1])


def _dropout(x, rate, key):
    if rate <= 0.0:  # trace-time constant: rate-0 configs skip mask codegen
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


def encode(
    params: Params,
    cfg: BertConfig,
    input_ids: jax.Array,        # [B, S] int32
    token_type_ids: jax.Array,   # [B, S] int32
    attention_mask: jax.Array,   # [B, S] {0,1}
    *,
    dtype=jnp.float32,
    deterministic: bool = True,
    rng: Optional[jax.Array] = None,
    remat: bool = False,
    attn_impl: str = "auto",
    seq_axis: Optional[str] = None,
    attn_bias: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    position_ids: Optional[jax.Array] = None,
    unroll=True,
    with_aux: bool = False,
) -> jax.Array:
    """Run the encoder stack; returns hidden states [B, S, H] in ``dtype``
    (or ``(hidden, moe_aux)`` under ``with_aux`` — see ``run_layers``).

    ``unroll``: ``lax.scan`` unroll factor over the stacked layers.  Full
    unroll (``True``) measured 14% faster per fused train step on v5e than
    the rolled scan (27.7 vs 32.3 ms at batch 32/seq 128) — XLA regains
    per-layer layout/fusion freedom; ``1`` keeps compile time flat in
    depth.

    ``seq_axis``: name of a mesh axis the *sequence* dimension is sharded
    over (must be inside ``shard_map``).  Position embeddings use global
    positions (shard offset) and attention runs as ring attention over the
    axis (``ops.ring``) — the long-context sequence-parallel path.

    ``attn_bias``: optional additive bias broadcastable to [B, N, S, S]
    that *replaces* the mask-derived bias (an explicit pre-built mask;
    always the XLA-style additive contract).

    ``segment_ids``: [B, S] packed-row segment IDs (0 = padding) — the
    preferred packed-path mask input: the block-diagonal mask is ROUTED,
    not materialized here.  A pallas-routed attention computes it inside
    the kernel (``ops.flash``); the XLA fallback builds
    ``data.packing.segment_bias`` inside ``ops.attention``; under
    ``seq_axis`` the sharded IDs ride the ring and each hop masks its
    own shard-local block (``ops.ring``).  On every route this module
    never holds the [B, 1, S, S] bias.

    ``position_ids``: optional explicit [B, S] position-embedding indices
    (packed rows restart positions per segment); default is the row
    position ``arange(S)`` every unpacked batch uses.
    """
    B, S = input_ids.shape
    shard_offset = 0
    if seq_axis is not None:
        shard_offset = jax.lax.axis_index(seq_axis) * S
        if (position_ids is None
                and S * jax.lax.axis_size(seq_axis) > cfg.max_position):
            raise ValueError("global sequence exceeds max_position")
    elif position_ids is None and S > cfg.max_position:
        # explicit position_ids (packed rows restart per segment) carry
        # their own bound — the longest SEGMENT, validated at setup
        # (data.sampler.validate_length_buckets); rows may be wider than
        # the table, that is the packed long-context payoff
        raise ValueError(
            f"sequence length {S} exceeds max_position {cfg.max_position}; "
            "JAX gather would silently clamp position embeddings")
    x, rng = embed(params, cfg, input_ids, token_type_ids, dtype=dtype,
                   deterministic=deterministic, rng=rng,
                   shard_offset=shard_offset, position_ids=position_ids)

    ring_bias = bias = None
    if attn_bias is not None:
        if seq_axis is not None:
            raise ValueError("attn_bias overrides are not supported on the "
                             "sequence-parallel (ring attention) path")
        if segment_ids is not None:
            raise ValueError("pass attn_bias OR segment_ids, not both — "
                             "the packed mask rides the IDs (padding is "
                             "segment 0), an explicit bias replaces it")
        bias = attn_bias.astype(dtype)
    elif segment_ids is not None:
        # bias stays None on EVERY route — the mask rides the IDs: in-kernel
        # on pallas, segment_bias inside ops.attention on XLA, per-hop
        # shard-local blocks on the ring (ops.ring receives the sharded IDs)
        pass
    elif seq_axis is None:
        bias = mask_bias(attention_mask, dtype)
    else:
        # same additive-mask semantics, squeezed to the [B, S_local] rows the
        # ring rotates alongside KV
        ring_bias = mask_bias(attention_mask, jnp.float32)[:, 0, 0, :]
    return run_layers(
        params["layers"], cfg, x, li=jnp.arange(cfg.num_layers), bias=bias,
        ring_bias=ring_bias, dtype=dtype, deterministic=deterministic,
        rng=rng, remat=remat, attn_impl=attn_impl, seq_axis=seq_axis,
        segment_ids=segment_ids, unroll=unroll, with_aux=with_aux,
        token_mask=attention_mask,
    )


def embed(params: Params, cfg: BertConfig, input_ids: jax.Array,
          token_type_ids: jax.Array, *, dtype=jnp.float32,
          deterministic: bool = True, rng: Optional[jax.Array] = None,
          shard_offset=0, position_ids: Optional[jax.Array] = None):
    """Embedding sum + LayerNorm + dropout; returns ``(x, rng)`` with the
    embedding dropout's split consumed, so layer streams continue from the
    returned key exactly as they did when this lived inline in ``encode``.
    Public so the pipeline-parallel path can run it on its first stage.
    ``position_ids`` overrides the row-position ``arange`` (packed rows
    restart positions per segment)."""
    S = input_ids.shape[1]
    emb = params["embeddings"]
    pos = (emb["position"][position_ids] if position_ids is not None
           else emb["position"][jnp.arange(S) + shard_offset])
    x = (
        emb["word"][input_ids]
        + pos
        + emb["token_type"][token_type_ids]
    ).astype(dtype)
    x = _layer_norm(x, emb["ln"]["scale"], emb["ln"]["bias"], cfg.layer_norm_eps)
    if not deterministic:
        rng, k = jax.random.split(rng)
        x = _dropout(x, cfg.dropout, k)
    return x, rng


def run_layers(layers: Params, cfg: BertConfig, x: jax.Array, *,
               li: jax.Array, bias: Optional[jax.Array] = None,
               ring_bias: Optional[jax.Array] = None, dtype=jnp.float32,
               deterministic: bool = True, rng: Optional[jax.Array] = None,
               remat: bool = False, attn_impl: str = "auto",
               seq_axis: Optional[str] = None,
               segment_ids: Optional[jax.Array] = None, unroll=True,
               with_aux: bool = False, token_mask: Optional[jax.Array] = None):
    """Scan a stacked slice of encoder layers over ``x`` ([B, S, H]).

    ``layers`` holds leading-dim-stacked weights (any contiguous slice of
    the stack) and ``li`` the matching *global* layer indices — dropout
    streams key on the global index, so a pipeline stage running layers
    [k..2k) reproduces exactly the streams the full stack would.  Public so
    the pipeline-parallel path can run per-stage slices.

    A ``gate`` tree marks MoE layers (``cfg.moe_experts``): the MLP becomes
    top-k gated experts and the scan additionally accumulates the
    load-balancing auxiliary loss — pass ``with_aux=True`` to receive
    ``(x, aux)`` (training needs it; eval may drop it)."""
    B, S = x.shape[0], x.shape[1]
    N, D = cfg.num_heads, cfg.head_dim
    moe = "gate" in layers
    if moe and seq_axis is not None:
        raise ValueError("MoE layers are not supported on the "
                         "sequence-parallel (ring attention) path")

    def attn_block(x, lp, idx, rng):
        def heads(t):
            return t.reshape(B, S, N, D)

        if _fuse_qkv() and "qscale" not in lp["q"]:
            # (int8 params skip the fused form: concatenating quantized
            # kernels would drop their per-channel scales)
            # one [H, 3H] projection: x is read from HBM once instead of
            # three times and XLA tiles a single larger MXU matmul.  Params
            # stay stored as separate q/k/v trees (checkpoint + tp-sharding
            # compatibility); the concat below is trace-time weight reshaping
            # that XLA folds into the matmul's operand layout.
            w = jnp.concatenate([lp["q"]["kernel"], lp["k"]["kernel"],
                                 lp["v"]["kernel"]], -1).astype(dtype)
            bqkv = jnp.concatenate([lp["q"]["bias"], lp["k"]["bias"],
                                    lp["v"]["bias"]], -1).astype(dtype)
            q, k, v = (heads(t) for t in jnp.split(x @ w + bqkv, 3, -1))
        else:
            q = heads(_dense(x, lp["q"], dtype))
            k = heads(_dense(x, lp["k"], dtype))
            v = heads(_dense(x, lp["v"], dtype))
        if seq_axis is not None:
            from pdnlp_tpu.ops.ring import ring_attention

            attn = ring_attention(
                q, k, v, ring_bias, axis_name=seq_axis,
                dropout_rate=0.0 if deterministic else cfg.attn_dropout,
                dropout_rng=None if deterministic else jax.random.fold_in(rng, 3 * idx + 2),
                segment_ids=segment_ids,
            )
        else:
            attn = dot_product_attention(
                q, k, v, bias, impl=attn_impl,
                dropout_rate=0.0 if deterministic else cfg.attn_dropout,
                dropout_rng=None if deterministic else jax.random.fold_in(rng, 3 * idx + 2),
                segment_ids=segment_ids,
            )
        attn = _dense(attn.reshape(B, S, N * D), lp["o"], dtype)
        if not deterministic:
            attn = _dropout(attn, cfg.dropout, jax.random.fold_in(rng, 3 * idx))
        return _layer_norm(x + attn, lp["attn_ln"]["scale"], lp["attn_ln"]["bias"],
                           cfg.layer_norm_eps)

    def mlp_out(x, lp, idx, rng, h):
        if not deterministic:
            h = _dropout(h, cfg.dropout, jax.random.fold_in(rng, 3 * idx + 1))
        return _layer_norm(x + h, lp["mlp_ln"]["scale"], lp["mlp_ln"]["bias"],
                           cfg.layer_norm_eps)

    def layer(carry, scanned):
        x, rng = carry
        lp, idx = scanned
        x = attn_block(x, lp, idx, rng)
        h = _gelu(_dense(x, lp["up"], dtype), cfg.gelu)
        h = _dense(h, lp["down"], dtype)
        x = mlp_out(x, lp, idx, rng, h)
        return (x, rng), None

    def layer_moe(carry, scanned):
        x, rng, aux = carry
        lp, idx = scanned
        x = attn_block(x, lp, idx, rng)
        h, a = moe_mlp(x, lp, cfg, dtype=dtype, mask=token_mask)
        x = mlp_out(x, lp, idx, rng, h)
        return (x, rng, aux + a), None

    body = layer_moe if moe else layer
    if remat:
        body = jax.checkpoint(body)

    if rng is None:
        rng = jax.random.key(0)  # unused when deterministic
    if moe:
        (x, _, aux), _ = jax.lax.scan(
            body, (x, rng, jnp.zeros((), jnp.float32)), (layers, li),
            unroll=unroll)
    else:
        (x, _), _ = jax.lax.scan(body, (x, rng), (layers, li), unroll=unroll)
        aux = jnp.zeros((), jnp.float32)
    return (x, aux) if with_aux else x


def moe_mlp(x: jax.Array, lp: Params, cfg: BertConfig, *, dtype=jnp.float32,
            mask: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Top-k gated mixture-of-experts MLP, one layer.

    Routing (shared by both dispatches): fp32 softmax gate, top-k experts
    per token, renormalized combine weights, Switch-style load-balancing
    aux loss E * sum_e(token_frac_e * prob_frac_e) (caller accumulates;
    1.0 = perfectly balanced).  ``mask`` ([B, S] {0,1}) keeps padding out
    of the balancing statistics — and, under grouped dispatch, out of the
    capacity slots — without it, padding (identical embeddings routed
    identically) dilutes the pressure on real tokens by the padding
    fraction.

    ``cfg.moe_dispatch`` picks the compute:

    - ``"grouped"`` (default): capacity-based dispatch — gather each
      expert's tokens into a static ``[E, capacity, H]`` buffer, run the
      expert FFNs as batched matmuls, scatter-combine.  FFN cost scales
      with ``k * capacity_factor``, not ``E`` (the property that makes
      expert counts beyond a handful affordable); tokens over a full
      expert's capacity skip that expert (the residual connection carries
      them — standard Switch/GShard semantics).
    - ``"dense"``: every expert computes every token and the gate-weighted
      combine contracts the expert dim (the GSPMD formulation; exact — no
      capacity drops — and the parity oracle for the grouped path, but
      O(E) FLOPs: measured 11.7 vs 35.5 dense-model steps/s at E=4 on
      v5e, r4 matrix).

    Under the "ep" sharding mode the expert dim of the weights (and of the
    grouped path's ``[E, capacity, H]`` buffers) is split over an "expert"
    mesh axis; XLA inserts the combine all-reduce from the shardings.

    Returns ``(output [B,S,H], aux)``.
    """
    E = lp["gate"]["kernel"].shape[-1]
    gate_logits = (x @ lp["gate"]["kernel"].astype(dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(gate_logits)                      # [B,S,E] fp32
    k = min(cfg.moe_top_k, E)
    top_p, top_idx = jax.lax.top_k(probs, k)                 # [B,S,k]
    # renormalized top-k combine weights
    renorm = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    if cfg.moe_dispatch not in ("grouped", "dense"):
        raise ValueError(
            f"moe_dispatch={cfg.moe_dispatch!r} — use 'grouped' or 'dense'; "
            "a silent fallback would quietly benchmark the O(E) path")
    if cfg.moe_dispatch == "grouped":
        out = _moe_grouped(x, lp, top_idx, renorm, cfg, dtype=dtype,
                           mask=mask)
    else:
        onehot = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # [B,S,k,E]
        combine = jnp.einsum("bske,bsk->bse", onehot, renorm)   # [B,S,E]
        up_k, up_b = lp["up"]["kernel"], lp["up"]["bias"]    # [E,H,I],[E,I]
        down_k, down_b = lp["down"]["kernel"], lp["down"]["bias"]
        h = _expert_scale(lp["up"],
                          jnp.einsum("bsh,ehi->ebsi", x, up_k.astype(dtype)),
                          dtype) + up_b.astype(dtype)[:, None, None, :]
        h = _gelu(h, cfg.gelu)
        y = _expert_scale(lp["down"],
                          jnp.einsum("ebsi,eih->ebsh", h, down_k.astype(dtype)),
                          dtype) + down_b.astype(dtype)[:, None, None, :]
        out = jnp.einsum("ebsh,bse->bsh", y, combine.astype(dtype))

    # Switch load-balancing statistics (masked means: see docstring)
    top1 = jax.nn.one_hot(top_idx[..., 0], E, dtype=jnp.float32)
    if mask is not None:
        m = mask.astype(jnp.float32).reshape(-1)[:, None]     # [BS, 1]
        denom = jnp.maximum(m.sum(), 1.0)
        token_frac = (top1.reshape(-1, E) * m).sum(0) / denom
        prob_frac = (probs.reshape(-1, E) * m).sum(0) / denom
    else:
        token_frac = top1.reshape(-1, E).mean(0)
        prob_frac = probs.reshape(-1, E).mean(0)
    aux = E * jnp.sum(token_frac * prob_frac)
    return out, aux


def _moe_grouped(x: jax.Array, lp: Params, top_idx: jax.Array,
                 renorm: jax.Array, cfg: BertConfig, *, dtype,
                 mask: Optional[jax.Array]) -> jax.Array:
    """Capacity-based expert dispatch: static shapes end to end.

    Slot assignment is the GShard position-in-expert cumsum: assignments
    are ranked token-major (earlier tokens win capacity), each keeps its
    slot iff ``position < capacity``.  Dropped assignments simply don't
    contribute (the caller's residual carries the token).  Padding tokens
    (``mask`` 0) never occupy slots — on this corpus ~80% of positions are
    padding, which would otherwise eat most of the capacity real tokens
    need.  With ``capacity >= tokens`` nothing can drop and the result
    equals dense dispatch up to summation order (pinned in
    ``tests/test_moe.py``)."""
    import math

    B, S, H = x.shape
    T = B * S
    E = lp["up"]["kernel"].shape[0]
    k = top_idx.shape[-1]
    C = int(math.ceil(cfg.moe_capacity_factor * k * T / E))
    C = min(C, T)  # one slot per token per expert is the most ever needed

    x2 = x.reshape(T, H)
    flat_e = top_idx.reshape(-1)                      # [T*k], token-major
    w_flat = renorm.reshape(-1)                       # [T*k] fp32
    keep = jnp.ones((T * k,), bool)
    if mask is not None:
        keep = jnp.repeat(mask.reshape(-1).astype(bool), k)
    # position-in-expert: how many kept assignments to my expert precede me
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32) * keep[:, None]
    pos = jnp.cumsum(onehot, axis=0) - onehot         # [T*k, E]
    mypos = jnp.take_along_axis(pos, flat_e[:, None], 1)[:, 0]
    keep = keep & (mypos < C)
    # slot tables: [E, C] -> source token (sentinel T = zero row) + weight
    e_idx = jnp.where(keep, flat_e, E)                # E = out of bounds
    tok = jnp.arange(T * k, dtype=jnp.int32) // k
    slot_tok = jnp.full((E, C), T, jnp.int32).at[e_idx, mypos].set(
        tok, mode="drop")
    slot_w = jnp.zeros((E, C), jnp.float32).at[e_idx, mypos].set(
        w_flat, mode="drop")

    xe = jnp.concatenate([x2, jnp.zeros((1, H), x2.dtype)])[slot_tok]
    h = _expert_scale(
        lp["up"],
        jnp.einsum("ech,ehi->eci", xe, lp["up"]["kernel"].astype(dtype)),
        dtype) + lp["up"]["bias"].astype(dtype)[:, None, :]
    h = _gelu(h, cfg.gelu)
    y = _expert_scale(
        lp["down"],
        jnp.einsum("eci,eih->ech", h, lp["down"]["kernel"].astype(dtype)),
        dtype) + lp["down"]["bias"].astype(dtype)[:, None, :]
    y = y * slot_w[..., None].astype(dtype)           # sentinel slots -> 0
    out = jnp.zeros((T + 1, H), dtype).at[slot_tok.reshape(-1)].add(
        y.reshape(E * C, H), mode="drop")[:T]
    return out.reshape(B, S, H)


def init_mlm_head(key: jax.Array, cfg: BertConfig) -> Params:
    """Masked-LM head params (kept as a SEPARATE tree so classification
    checkpoints and the fine-tune model never carry it): dense transform +
    LayerNorm, then a decoder TIED to the word-embedding matrix plus a
    per-token output bias — the standard BERT MLM head, which the reference
    never needs because it downloads already-pretrained weights
    (``/root/reference/single-gpu-cls.py:252``)."""
    H = cfg.hidden_size
    return {
        "transform": _dense_init(key, H, H, cfg.initializer_range),
        "ln": _ln_init(H),
        "bias": jnp.zeros((cfg.vocab_size,), jnp.float32),
    }


def mlm_logits(params: Params, head: Params, cfg: BertConfig,
               hidden: jax.Array, *, dtype=jnp.float32) -> jax.Array:
    """[B, S, H] encoder output -> [B, S, vocab] logits (fp32).

    The decoder weight is ``params['embeddings']['word']`` transposed (weight
    tying): on a corpus this small the embedding table gets gradient signal
    from every masked position, not just from input lookups."""
    h = _gelu(_dense(hidden, head["transform"], dtype), cfg.gelu)
    h = _layer_norm(h, head["ln"]["scale"], head["ln"]["bias"], cfg.layer_norm_eps)
    word = params["embeddings"]["word"].astype(dtype)
    logits = jnp.einsum("bsh,vh->bsv", h, word) + head["bias"].astype(dtype)
    return logits.astype(jnp.float32)


def classify(
    params: Params,
    cfg: BertConfig,
    batch: Dict[str, jax.Array],
    *,
    dtype=jnp.float32,
    deterministic: bool = True,
    rng: Optional[jax.Array] = None,
    remat: bool = False,
    attn_impl: str = "auto",
    seq_axis: Optional[str] = None,
    unroll=True,
    return_aux: bool = False,
    return_pooled: bool = False,
) -> jax.Array:
    """Logits [B, num_labels] (fp32) — the ``model(**batch) -> logits`` twin
    of the reference's classification forward (``single-gpu-cls.py:119-124``:
    pooled [CLS] -> dropout -> linear).  ``return_aux`` additionally returns
    the MoE load-balancing loss (0 for dense models).

    Under ``seq_axis`` (sequence-parallel), the [CLS] position lives on
    shard 0; a masked ``psum`` broadcasts it so every shard computes the
    same logits.  Attention-probability dropout runs per ring block
    (``ops.ring``) — same distribution as the dense path, shard-layout-
    dependent draws.

    A PACKED batch (``--length_mode pack``: ``segment_ids`` +
    ``cls_positions`` channels, ``data.packing.PackedClassificationDataset``)
    carries several examples per row: attention applies the block-diagonal
    segment mask so examples never cross-attend (in-kernel from
    ``segment_ids`` on the pallas route; ``data.packing.segment_bias``
    built inside ``ops.attention`` on the XLA fallback — this function
    never materializes it), each segment's [CLS] hidden state is gathered
    at its ``cls_positions`` offset, and the head returns per-SEGMENT
    logits ``[B, M, num_labels]`` (labels/weights in the batch are
    ``[B, M]`` to match) — per-example semantics, packed compute.  The
    batch-key check is trace-static (dict structure, not values): packed
    and unpacked batches are separate compiled programs.

    ``return_pooled``: return the pooled PRE-classifier features
    ([B, H] / packed [B, M, H], tanh + dropout applied) instead of logits
    — the input contract of the fused projection+CE kernel
    (``ops.fused_ce``), which consumes the classifier weights itself."""
    packed = "cls_positions" in batch
    if not deterministic:
        rng, enc_rng, drop_rng = jax.random.split(rng, 3)
    else:
        enc_rng = drop_rng = None
    hidden, aux = encode(
        params, cfg,
        batch["input_ids"], batch["token_type_ids"], batch["attention_mask"],
        dtype=dtype, deterministic=deterministic, rng=enc_rng, remat=remat,
        attn_impl=attn_impl, seq_axis=seq_axis,
        segment_ids=batch["segment_ids"] if packed else None,
        position_ids=batch.get("position_ids") if packed else None,
        unroll=unroll, with_aux=True,
    )
    head = pooled_features if return_pooled else pooled_logits
    if packed:
        # per-segment pooled-output gather: [B, S, H] at [B, M] offsets
        pos = batch["cls_positions"].astype(jnp.int32)
        if seq_axis is not None:
            # cls offsets are GLOBAL; hidden is this shard's [B, S_local]
            # slice.  Each shard gathers the offsets landing in its slice
            # (clipped gather, masked) and a psum assembles the full
            # [B, M, H] on every shard — the packed analog of the
            # shard-0 [CLS] broadcast below, same head-grads-counted-once
            # contract (the sp loss is gated to seq-shard 0).
            S_local = hidden.shape[1]
            off = jax.lax.axis_index(seq_axis) * S_local
            local = pos - off
            inb = (local >= 0) & (local < S_local)
            safe = jnp.clip(local, 0, S_local - 1)
            hM = jnp.take_along_axis(hidden, safe[..., None], axis=1)
            hM = jax.lax.psum(
                hM * inb[..., None].astype(hidden.dtype), seq_axis)
        else:
            hM = jnp.take_along_axis(hidden, pos[..., None], axis=1)
        B, M, H = hM.shape
        out = head(params, cfg, hM.reshape(B * M, H), dtype=dtype,
                   drop_rng=None if deterministic else drop_rng)
        out = out.reshape(B, M, -1)
        return (out, aux) if return_aux else out
    h0 = hidden[:, 0, :]
    if seq_axis is not None:
        on_shard0 = (jax.lax.axis_index(seq_axis) == 0).astype(h0.dtype)
        h0 = jax.lax.psum(h0 * on_shard0, seq_axis)
    out = head(params, cfg, h0, dtype=dtype,
               drop_rng=None if deterministic else drop_rng)
    return (out, aux) if return_aux else out


def pooled_features(params: Params, cfg: BertConfig, h0: jax.Array, *,
                    dtype=jnp.float32, drop_rng=None) -> jax.Array:
    """[CLS] hidden rows [B, H] -> pooled pre-classifier features [B, H]
    (tanh pooler + optional dropout) — the classifier's input, split out so
    the fused projection+CE kernel (``ops.fused_ce``) can consume the final
    matmul itself."""
    pooled = jnp.tanh(_dense(h0, params["pooler"], dtype))
    if drop_rng is not None:
        pooled = _dropout(pooled, cfg.dropout, drop_rng)
    return pooled


def pooled_logits(params: Params, cfg: BertConfig, h0: jax.Array, *,
                  dtype=jnp.float32, drop_rng=None) -> jax.Array:
    """[CLS] hidden rows [B, H] -> logits [B, num_labels] (fp32): tanh
    pooler, optional dropout (``drop_rng`` given), classifier.  Shared by
    ``classify`` and the pipeline-parallel path so the head cannot drift
    between them."""
    pooled = pooled_features(params, cfg, h0, dtype=dtype, drop_rng=drop_rng)
    logits = _dense(pooled, params["classifier"], dtype)
    return logits.astype(jnp.float32)
